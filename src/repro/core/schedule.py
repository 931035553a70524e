"""Schedule: the unit of the thesis' design space, as a first-class object.

A :class:`Schedule` is one point in the optimisation space the thesis
explores — a loop/grid order plus block (tile) shapes plus the VMEM-split
("tiles-for-L2", §6.3) choice.  The tuner produces ranked schedules from the
cost model; the adaptive runtime (core/adaptive.py) micro-profiles the top
few and commits; the kernels consume a schedule as launch parameters.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ConvSchedule:
    """Conv2d launch point: grid order + block shapes (Table 4.1 axes)."""

    grid_order: Tuple[str, ...]           # permutation of (oc, ic, y, x)
    block: Tuple[Tuple[str, int], ...]    # hashable block dict

    def block_dict(self) -> Dict[str, int]:
        """Block shapes as a plain dict (the kernels' kwarg form)."""
        return dict(self.block)

    @staticmethod
    def make(grid_order, block: Dict[str, int]) -> "ConvSchedule":
        """Build from a plain block dict (canonicalised for hashing)."""
        return ConvSchedule(tuple(grid_order),
                            tuple(sorted(block.items())))

    def to_dict(self) -> Dict:
        """Registry-serialisable form (see registry.schedule_to_dict)."""
        from repro.core import registry
        return registry.schedule_to_dict(self)

    def run(self, img: jnp.ndarray, wgt: jnp.ndarray, *,
            interpret: Optional[bool] = None) -> jnp.ndarray:
        """Execute the conv2d kernel with this schedule's parameters."""
        from repro.kernels.conv2d import conv2d
        return conv2d(img, wgt, block=self.block_dict(),
                      grid_order=self.grid_order, interpret=interpret)


@dataclasses.dataclass(frozen=True)
class MatmulSchedule:
    """Matmul launch point: grid order, blocks, and the VMEM split."""

    grid_order: Tuple[str, ...]           # permutation of (m, n, k)
    block: Tuple[Tuple[str, int], ...]
    resident_rhs: bool = False            # the "tiles-for-L2" switch

    def block_dict(self) -> Dict[str, int]:
        """Block shapes as a plain dict (the kernels' kwarg form)."""
        return dict(self.block)

    @staticmethod
    def make(grid_order, block: Dict[str, int],
             resident_rhs: bool = False) -> "MatmulSchedule":
        """Build from a plain block dict (canonicalised for hashing)."""
        return MatmulSchedule(tuple(grid_order),
                              tuple(sorted(block.items())), resident_rhs)

    def to_dict(self) -> Dict:
        """Registry-serialisable form (see registry.schedule_to_dict)."""
        from repro.core import registry
        return registry.schedule_to_dict(self)

    def run(self, a: jnp.ndarray, b: jnp.ndarray, *,
            interpret: Optional[bool] = None) -> jnp.ndarray:
        """Execute the matmul kernel with this schedule's parameters."""
        from repro.kernels.matmul import matmul
        return matmul(a, b, block=self.block_dict(),
                      grid_order=self.grid_order,
                      resident_rhs=self.resident_rhs, interpret=interpret)


@dataclasses.dataclass(frozen=True)
class FlashAttentionSchedule:
    """Prefill/training attention launch point: q/kv block sizes."""
    block_q: int
    block_kv: int

    def to_dict(self) -> Dict:
        """Registry-serialisable form (see registry.schedule_to_dict)."""
        from repro.core import registry
        return registry.schedule_to_dict(self)

    def run(self, q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
            causal: bool = True, window: Optional[int] = None,
            interpret: Optional[bool] = None) -> jnp.ndarray:
        """Execute flash attention with this schedule's block sizes."""
        from repro.kernels.flash_attention import flash_attention
        return flash_attention(q, k, v, block_q=self.block_q,
                               block_kv=self.block_kv, causal=causal,
                               window=window, interpret=interpret)


@dataclasses.dataclass(frozen=True)
class DecodeAttentionSchedule:
    """Serving decode-step launch point: the KV streaming block."""
    block_kv: int

    def to_dict(self) -> Dict:
        """Registry-serialisable form (see registry.schedule_to_dict)."""
        from repro.core import registry
        return registry.schedule_to_dict(self)

    def run(self, q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
            pos, *, interpret: Optional[bool] = None) -> jnp.ndarray:
        """Execute one decode attention step with this schedule."""
        from repro.kernels.decode_attention import decode_attention
        return decode_attention(q, k, v, pos, block_kv=self.block_kv,
                                interpret=interpret)


@dataclasses.dataclass(frozen=True)
class SSMScanSchedule:
    """Fused selective-scan launch point: the channel block."""
    block_d: int

    def to_dict(self) -> Dict:
        """Registry-serialisable form (see registry.schedule_to_dict)."""
        from repro.core import registry
        return registry.schedule_to_dict(self)

    def run(self, x, dt, b, c, a, d, *,
            interpret: Optional[bool] = None) -> jnp.ndarray:
        """Execute the fused selective scan with this schedule."""
        from repro.kernels.ssm_scan import ssm_scan
        return ssm_scan(x, dt, b, c, a, d, block_d=self.block_d,
                        interpret=interpret)


@dataclasses.dataclass(frozen=True)
class ScheduleBundle:
    """The committed schedules a compiled model step runs with.

    One frozen (hashable) object holding the per-kernel-family schedule
    for every kernel a model step can hit, so the whole bundle threads
    through ``jax.jit`` as ONE static argument: a different bundle is a
    different compiled executable, an identical bundle is a cache hit.
    ``None`` fields fall back to the kernel's default launch parameters.

    Resolution lives in :meth:`repro.runtime.dispatch.DispatchService.
    schedule_bundle` (committed winner > registry measurement > offline
    rank-0); models only consume the bundle.
    """
    flash_attention: Optional["FlashAttentionSchedule"] = None
    decode_attention: Optional["DecodeAttentionSchedule"] = None
    ssm_scan: Optional["SSMScanSchedule"] = None
    matmul: Optional["MatmulSchedule"] = None
    conv2d: Optional["ConvSchedule"] = None
    sparse_conv: Optional["SparseConvSchedule"] = None

    def get(self, kind: str):
        """Schedule for a dispatch-kind name (None when unset)."""
        return getattr(self, kind, None)

    def replace(self, **kw) -> "ScheduleBundle":
        """A copy with the given per-family slots swapped out."""
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> Dict:
        """Per-family serialisable dict (None for unset slots)."""
        from repro.core import registry
        return {f.name: (registry.schedule_to_dict(getattr(self, f.name))
                         if getattr(self, f.name) is not None else None)
                for f in dataclasses.fields(self)}


@dataclasses.dataclass(frozen=True)
class SparseConvSchedule:
    """Block-sparse conv launch point: (oc, ic) skip-block shape."""
    block: Tuple[Tuple[str, int], ...]    # hashable {"oc","ic"} dict

    def block_dict(self) -> Dict[str, int]:
        """Block shapes as a plain dict (the kernels' kwarg form)."""
        return dict(self.block)

    @staticmethod
    def make(block: Dict[str, int]) -> "SparseConvSchedule":
        """Build from a plain block dict (canonicalised for hashing)."""
        return SparseConvSchedule(tuple(sorted(block.items())))

    def to_dict(self) -> Dict:
        """Registry-serialisable form (see registry.schedule_to_dict)."""
        from repro.core import registry
        return registry.schedule_to_dict(self)

    def run(self, img: jnp.ndarray, wgt: jnp.ndarray, *,
            sparsity=None, interpret: Optional[bool] = None) -> jnp.ndarray:
        """Execute the block-sparse conv kernel with this schedule."""
        from repro.kernels.sparse_conv import sparse_conv2d
        return sparse_conv2d(img, wgt, block=self.block_dict(),
                             sparsity=sparsity, interpret=interpret)
