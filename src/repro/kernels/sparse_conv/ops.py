"""Public wrapper: builds block structure from weights and dispatches,
with the dense-vs-sparse policy hook the thesis' §6.2 comparison needs."""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sparse_conv.kernel import (build_block_index,
                                              sparse_conv2d_pallas)
from repro.kernels.sparse_conv.ref import sparse_conv_ref


@dataclasses.dataclass(frozen=True)
class BlockSparsity:
    """Host-side compacted sparsity structure of a weight tensor."""
    idx: np.ndarray        # [n_oc_blocks, max_nnz]
    counts: np.ndarray     # [n_oc_blocks]
    block: Dict[str, int]
    n_ic_blocks: int

    @property
    def density(self) -> float:
        return float(self.counts.sum()) / (len(self.counts)
                                           * self.n_ic_blocks)

    @property
    def imbalance(self) -> float:
        """max/mean nonzero count across oc blocks — the thesis' dense-
        region straggler measure (§3.6)."""
        mean = max(float(self.counts.mean()), 1e-9)
        return float(self.counts.max(initial=0)) / mean


def analyze_weights(wgt: np.ndarray, block: Dict[str, int],
                    threshold: float = 0.0) -> BlockSparsity:
    oc, ic = wgt.shape[0], wgt.shape[1]
    boc, bic = block["oc"], block["ic"]
    w = np.abs(np.asarray(wgt)).reshape(oc // boc, boc, ic // bic, bic, -1)
    mask = (w.max(axis=(1, 3, 4)) > threshold)
    idx, counts = build_block_index(mask)
    return BlockSparsity(idx=idx, counts=counts, block=dict(block),
                         n_ic_blocks=ic // bic)


def sparse_conv2d(img: jnp.ndarray, wgt: jnp.ndarray, *,
                  block: Dict[str, int],
                  sparsity: Optional[BlockSparsity] = None,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Block-sparse direct conv; recomputes structure if not supplied."""
    if sparsity is None:
        sparsity = analyze_weights(np.asarray(wgt), block)
    return sparse_conv2d_pallas(
        img, wgt, jnp.asarray(sparsity.idx), jnp.asarray(sparsity.counts),
        block=block, interpret=interpret)


def sparse_conv2d_scheduled(img: jnp.ndarray, wgt: jnp.ndarray, *,
                            schedule,
                            sparsity: Optional[BlockSparsity] = None,
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """Schedule-as-static-arg entry point: run ``sparse_conv2d`` with a
    committed :class:`~repro.core.schedule.SparseConvSchedule` skip-block
    shape (frozen, hashable)."""
    return sparse_conv2d(img, wgt, block=schedule.block_dict(),
                         sparsity=sparsity, interpret=interpret)


def sparse_conv2d_dispatched(img: jnp.ndarray, wgt: jnp.ndarray, *,
                             density: Optional[float] = None,
                             service=None,
                             interpret: Optional[bool] = None) -> jnp.ndarray:
    """`sparse_conv2d` through the adaptive dispatch runtime: the (oc,
    ic) skip-block shape for this (layer, density) comes from the
    registry-backed top-K and each call's measured time feeds the online
    selector (see :mod:`repro.runtime.dispatch`).  The dispatch key uses
    the weight tensor's element-level density quantised to a 1/16 grid —
    an upper bound on block density at any granularity — so one key
    covers all block candidates.  Computing that density pulls the whole
    weight tensor to the host; serving loops that call this repeatedly
    with the same weights should pass ``density`` (density is a property
    of the weights, not the call)."""
    from repro.core.registry import quantize_density
    from repro.runtime.dispatch import get_dispatch_service
    n, ic, h2, w2 = img.shape
    oc, _, kh, kw = wgt.shape
    h, w = h2 - kh + 1, w2 - kw + 1
    if density is None:
        density = float((np.abs(np.asarray(wgt)) > 0.0).mean())
    svc = service if service is not None else get_dispatch_service()
    problem = {"oc": oc, "ic": ic, "h": h, "w": w, "kh": kh, "kw": kw,
               "density_16": quantize_density(density)}
    with svc.measure("sparse_conv", problem,
                     elem_bytes=img.dtype.itemsize) as sched:
        out = sparse_conv2d(img, wgt, block=sched.block_dict(),
                            interpret=interpret)
        jax.block_until_ready(out)
    return out


__all__ = ["sparse_conv2d", "sparse_conv2d_scheduled",
           "sparse_conv2d_dispatched", "sparse_conv_ref",
           "analyze_weights", "BlockSparsity"]
