"""Block-sparse direct convolution with scalar-prefetch index skipping.

Hardware adaptation of the thesis' sparsity-sensitive algorithm (§3.6,
Fig 6.2).  Loki skips scalar multiply-adds when a weight or activation is
zero; a TPU cannot branch per element, but it *can* skip whole blocks: the
weight-block nonzero structure is compacted on the host into, per output-
channel block, the list of input-channel blocks with any nonzero weight.
The Pallas grid iterates only over that compacted list via
``PrefetchScalarGridSpec`` — the BlockSpec index maps read the next ic-block
id from a prefetched scalar array, so zero blocks cost neither MXU cycles
nor HBM->VMEM DMA.  Runtime therefore scales with *block* density, which is
the thesis' Fig 6.2 behaviour with the crossover moved to block granularity
(see DESIGN.md §2; per-element skipping does not transfer to systolic
hardware).

The thesis' observation that dense regions assigned to one core become
stragglers (§3.6) maps to nnz-count imbalance across oc blocks; the ops
wrapper reports the imbalance factor so the adaptive layer (core/sparsity)
can fall back to the dense kernel — the same dense-vs-sparse decision the
thesis makes.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def build_block_index(mask: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Compact a [n_oc, n_ic] block-nonzero mask into (idx, counts):
    idx[o, j] = j-th nonzero ic block for oc block o (padded with 0),
    counts[o] = number of valid entries."""
    n_oc, n_ic = mask.shape
    counts = mask.sum(axis=1).astype(np.int32)
    width = max(int(counts.max(initial=0)), 1)
    idx = np.zeros((n_oc, width), np.int32)
    for o in range(n_oc):
        nz = np.nonzero(mask[o])[0]
        idx[o, :len(nz)] = nz
    return idx, counts


def _sparse_kernel(idx_ref, cnt_ref, img_ref, wgt_ref, out_ref, acc_ref, *,
                   kh: int, kw: int, n_steps: int):
    oc_i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < cnt_ref[oc_i])
    def _compute():
        boc, bic = wgt_ref.shape[0], wgt_ref.shape[1]
        h, w = out_ref.shape[2], out_ref.shape[3]  # out block: [1,BOC,H,W]
        acc = acc_ref[...]
        for ky in range(kh):
            for kx in range(kw):
                patch = img_ref[0, :, ky:ky + h, kx:kx + w]
                patch2 = patch.reshape(bic, h * w)
                tap = wgt_ref[:, :, ky, kx]
                acc += jax.lax.dot_general(
                    tap, patch2, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32).reshape(boc, h, w)
        acc_ref[...] = acc

    @pl.when(j == n_steps - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def sparse_conv2d_pallas(img: jnp.ndarray, wgt: jnp.ndarray,
                         idx: jnp.ndarray, counts: jnp.ndarray, *,
                         block: Dict[str, int],
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """img: [N, IC, H+KH-1, W+KW-1]; wgt: [OC, IC, KH, KW];
    idx/counts from :func:`build_block_index` over (oc, ic) blocks.
    Output blocks keep full spatial extent (thesis-scale images are small);
    the sparse grid is (N, n_oc_blocks, max_nnz)."""
    n, ic, h2, w2 = img.shape
    oc, _, kh, kw = wgt.shape
    h, w = h2 - kh + 1, w2 - kw + 1
    boc, bic = block["oc"], block["ic"]
    assert oc % boc == 0 and ic % bic == 0
    n_steps = idx.shape[1]

    # With PrefetchScalarGridSpec the index maps receive the grid indices
    # first, then the prefetched scalar refs.
    def img_index(b, oc_i, j, idx_ref, cnt_ref):
        return (b, idx_ref[oc_i, j], 0, 0)

    def wgt_index(b, oc_i, j, idx_ref, cnt_ref):
        return (oc_i, idx_ref[oc_i, j], 0, 0)

    def out_index(b, oc_i, j, idx_ref, cnt_ref):
        return (b, oc_i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, oc // boc, n_steps),
        in_specs=[
            pl.BlockSpec((1, bic, h2, w2), img_index),
            pl.BlockSpec((boc, bic, kh, kw), wgt_index),
        ],
        out_specs=pl.BlockSpec((1, boc, h, w), out_index),
        scratch_shapes=[pltpu.VMEM((boc, h, w), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_sparse_kernel, kh=kh, kw=kw, n_steps=n_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, oc, h, w), img.dtype),
        interpret=resolve_interpret(interpret),
    )(idx, counts, img, wgt)
