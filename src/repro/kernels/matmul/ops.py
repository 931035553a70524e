"""Jit'd public wrapper for the tiled matmul kernel.

`matmul_tuned` consults the persistent tuning registry for the best
(grid order x blocks x resident-RHS) schedule instead of static defaults.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.matmul.kernel import matmul_pallas, GRID_AXES
from repro.kernels.matmul.ref import matmul_ref


def _divisor_le(n: int, cap: int) -> int:
    best = 1
    for d in range(1, n + 1):
        if n % d == 0 and d <= cap:
            best = d
    return best


def default_block(m: int, n: int, k: int) -> Dict[str, int]:
    return {"m": _divisor_le(m, 256), "n": _divisor_le(n, 256),
            "k": _divisor_le(k, 512)}


@functools.partial(jax.jit, static_argnames=("block_tuple", "grid_order",
                                             "resident_rhs", "interpret"))
def _matmul_jit(a, b, block_tuple, grid_order, resident_rhs, interpret):
    block = dict(zip(GRID_AXES, block_tuple))
    return matmul_pallas(a, b, block=block, grid_order=grid_order,
                         resident_rhs=resident_rhs, interpret=interpret)


def matmul(a: jnp.ndarray, b: jnp.ndarray, *,
           block: Optional[Dict[str, int]] = None,
           grid_order: Sequence[str] = ("m", "n", "k"),
           resident_rhs: bool = False,
           interpret: Optional[bool] = None) -> jnp.ndarray:
    m, k = a.shape
    _, n = b.shape
    if block is None:
        block = default_block(m, n, k)
    block_tuple = tuple(block[ax] for ax in GRID_AXES)
    return _matmul_jit(a, b, block_tuple, tuple(grid_order), resident_rhs,
                       interpret)


@functools.lru_cache(maxsize=512)
def _tuned_schedule(mnk: Tuple[int, int, int], elem_bytes: int,
                    registry_path: str):
    from repro.core import tuner
    m, n, k = mnk
    ranked = tuner.cached_tune_matmul(m, n, k, elem_bytes=elem_bytes,
                                      top_k=1)
    return ranked[0][0]


def matmul_tuned(a: jnp.ndarray, b: jnp.ndarray, *,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """`matmul` with the schedule picked by the tuning registry; tunes at
    most once per (m, n, k, dtype) per machine — ever."""
    from repro.core.registry import TuningRegistry
    m, k = a.shape
    _, n = b.shape
    sched = _tuned_schedule((m, n, k), a.dtype.itemsize,
                            TuningRegistry.default_path())
    return matmul(a, b, block=sched.block_dict(),
                  grid_order=sched.grid_order,
                  resident_rhs=sched.resident_rhs, interpret=interpret)


def matmul_scheduled(a: jnp.ndarray, b: jnp.ndarray, *, schedule,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Schedule-as-static-arg entry point: run ``matmul`` with a
    committed :class:`~repro.core.schedule.MatmulSchedule` (frozen,
    hashable — the underlying jit keys on its block/grid/residency)."""
    return matmul(a, b, block=schedule.block_dict(),
                  grid_order=schedule.grid_order,
                  resident_rhs=schedule.resident_rhs, interpret=interpret)


def matmul_dispatched(a: jnp.ndarray, b: jnp.ndarray, *,
                      service=None, interpret: Optional[bool] = None) -> jnp.ndarray:
    """`matmul` through the adaptive dispatch runtime (see
    :mod:`repro.runtime.dispatch`): propose a registry-backed candidate,
    time the call, feed the selector, commit + write back once steady."""
    from repro.runtime.dispatch import get_dispatch_service
    m, k = a.shape
    _, n = b.shape
    svc = service if service is not None else get_dispatch_service()
    with svc.measure("matmul", {"m": m, "n": n, "k": k},
                     elem_bytes=a.dtype.itemsize) as sched:
        out = matmul(a, b, block=sched.block_dict(),
                     grid_order=sched.grid_order,
                     resident_rhs=sched.resident_rhs, interpret=interpret)
        jax.block_until_ready(out)
    return out


__all__ = ["matmul", "matmul_tuned", "matmul_scheduled",
           "matmul_dispatched", "matmul_ref", "default_block"]
