"""Jit'd public wrapper for flash attention."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import mha_ref


@functools.partial(jax.jit, static_argnames=("block_q", "block_kv", "causal",
                                             "window", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    block_q: int = 128, block_kv: int = 128,
                    causal: bool = True, window: Optional[int] = None,
                    starts=None, interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention; q [B,HQ,S,D], k/v [B,HKV,S,D] -> [B,HQ,S,D].

    ``starts`` ([B] int32, optional) masks keys below each row's first
    real token (left-padded batches)."""
    return flash_attention_pallas(q, k, v, block_q=block_q,
                                  block_kv=block_kv, causal=causal,
                                  window=window, starts=starts,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("schedule", "causal",
                                             "window", "interpret"))
def flash_attention_scheduled(q: jnp.ndarray, k: jnp.ndarray,
                              v: jnp.ndarray, *, schedule,
                              causal: bool = True,
                              window: Optional[int] = None,
                              starts=None,
                              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Schedule-as-static-arg entry point: a committed
    :class:`~repro.core.schedule.FlashAttentionSchedule` (frozen,
    hashable) keys the compiled executable.  Blocks are clamped to the
    sequence so one schedule serves nearby shapes."""
    s = q.shape[2]
    return flash_attention_pallas(q, k, v,
                                  block_q=min(schedule.block_q, s),
                                  block_kv=min(schedule.block_kv, s),
                                  causal=causal, window=window,
                                  starts=starts, interpret=interpret)


def flash_attention_dispatched(q: jnp.ndarray, k: jnp.ndarray,
                               v: jnp.ndarray, *, causal: bool = True,
                               window: Optional[int] = None,
                               service=None,
                               interpret: Optional[bool] = None) -> jnp.ndarray:
    """`flash_attention` through the adaptive dispatch runtime: the
    (block_q, block_kv) schedule comes from the registry-backed top-K
    for this (B, HQ, HKV, S, D) shape and the measured call time feeds
    the online selector (see :mod:`repro.runtime.dispatch`)."""
    from repro.runtime.dispatch import get_dispatch_service
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    svc = service if service is not None else get_dispatch_service()
    problem = {"b": b, "hq": hq, "hkv": hkv, "s": s, "d": d,
               "causal": causal}
    with svc.measure("flash_attention", problem,
                     elem_bytes=q.dtype.itemsize) as sched:
        out = flash_attention(q, k, v,
                              block_q=min(sched.block_q, s),
                              block_kv=min(sched.block_kv, s),
                              causal=causal, window=window,
                              interpret=interpret)
        jax.block_until_ready(out)
    return out


__all__ = ["flash_attention", "flash_attention_scheduled",
           "flash_attention_dispatched", "mha_ref"]
