"""Jit'd wrapper for the decode-attention kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.decode_attention.ref import decode_attention_ref


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention(q, k, v, pos, *, starts=None, block_kv: int = 256,
                     interpret: Optional[bool] = None):
    """Single-query flash decode; ``pos`` scalar or [B], ``starts``
    optional [B] first-valid cache index per row (left padding)."""
    return decode_attention_pallas(q, k, v, pos, starts=starts,
                                   block_kv=block_kv,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("schedule", "interpret"))
def decode_attention_scheduled(q, k, v, pos, *, schedule, starts=None,
                               interpret: Optional[bool] = None):
    """Schedule-as-static-arg entry point: the compiled decode step
    threads a committed :class:`~repro.core.schedule.
    DecodeAttentionSchedule` (frozen, hashable) straight into the
    launch, so the executable is keyed by the schedule itself."""
    return decode_attention_pallas(q, k, v, pos, starts=starts,
                                   block_kv=schedule.block_kv,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, tables, pos, *,
                           interpret: Optional[bool] = None):
    """Block-table-aware flash decode over a paged KV pool.

    q [B,HQ,1,D]; pools [NB,HKV,bs,D]; tables [B,MB] int32; pos [B]
    int32.  Streaming granularity is the pool block size (paging fixes
    the KV block; there is no block_kv knob on this path)."""
    return paged_decode_attention_pallas(q, k_pool, v_pool, tables, pos,
                                         interpret=interpret)


def decode_attention_dispatched(q, k, v, pos, *, service=None,
                                interpret: Optional[bool] = None):
    """`decode_attention` through the adaptive dispatch runtime: the KV
    streaming block for this (B, HQ, HKV, S, D) cache shape comes from
    the registry-backed top-K and each call's measured time feeds the
    online selector (see :mod:`repro.runtime.dispatch`)."""
    from repro.runtime.dispatch import get_dispatch_service
    b, hq, _, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    svc = service if service is not None else get_dispatch_service()
    problem = {"b": b, "hq": hq, "hkv": hkv, "s": s, "d": d}
    with svc.measure("decode_attention", problem,
                     elem_bytes=q.dtype.itemsize) as sched:
        out = decode_attention(q, k, v, pos, block_kv=sched.block_kv,
                               interpret=interpret)
        jax.block_until_ready(out)
    return out


__all__ = ["decode_attention", "decode_attention_scheduled",
           "decode_attention_dispatched", "decode_attention_ref",
           "paged_decode_attention"]
