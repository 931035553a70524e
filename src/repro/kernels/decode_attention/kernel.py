"""Single-query flash decode over a KV cache (Pallas TPU).

Decode attention is the memory roofline of serving: each new token must
stream the whole valid cache prefix.  This kernel reads each K/V block
exactly once (online softmax in VMEM scratch) and — via scalar prefetch of
the current position — *skips whole KV blocks beyond ``pos``*: with a
32k-slot cache at position 1k, 31/32 of the DMAs never issue.  That is
the thesis' sparsity-guard idea (§3.6) applied to the temporal dimension,
and the same scalar-prefetch machinery as kernels/sparse_conv.

GQA is handled by the KV index map folding query heads onto their group
(no repeated KV in HBM), matching kernels/flash_attention.  The paged
kernel fetches every KV head of a pool block in one step and groups the
query heads under them in VMEM instead.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

NEG_INF = -1e30
# Name of the paged kernel's custom call in compiled programs and device
# traces (what a trace reduction matches on).
PAGED_DECODE_KERNEL_NAME = "paged_decode_attention"


def _decode_kernel(pos_ref, starts_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bkv: int, n_kv: int,
                   hq: int):
    bh = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[bh // hq]
    start = starts_ref[bh // hq]
    k_start = ki * bkv

    # Skip blocks wholly beyond pos or wholly inside the pad prefix.
    @pl.when(jnp.logical_and(k_start <= pos, k_start + bkv > start))
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [1, D]
        k = k_ref[0].astype(jnp.float32)            # [BKV, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        s = jnp.where(jnp.logical_and(kpos <= pos, kpos >= start),
                      s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _flush():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(q: jnp.ndarray, k: jnp.ndarray,
                            v: jnp.ndarray, pos: jnp.ndarray, *,
                            starts: jnp.ndarray = None,
                            block_kv: int = 256,
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """q [B,HQ,1,D]; k/v [B,HKV,S,D]; pos scalar or [B] int32.

    ``starts`` ([B] int32, optional) marks each row's first valid cache
    index (left-padded prefill wrote pads below it): valid keys satisfy
    ``starts[b] <= kpos <= pos[b]``.  Both vectors ride the scalar
    prefetch channel, so block skipping stays per-row."""
    b, hq, _, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    group = hq // hkv
    bkv = min(block_kv, s)
    while s % bkv:
        bkv //= 2
    n_kv = s // bkv

    scale = 1.0 / (d ** 0.5)
    qf = (q * jnp.asarray(scale, q.dtype)).reshape(b * hq, 1, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    if starts is None:
        starts_arr = jnp.zeros((b,), jnp.int32)
    else:
        starts_arr = jnp.asarray(starts, jnp.int32).reshape(b)

    def kv_index(bh, ki, pos_ref, starts_ref):
        batch = bh // hq
        head = bh % hq
        return (batch * hkv + head // group, ki, 0)

    def q_index(bh, ki, pos_ref, starts_ref):
        return (bh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * hq, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, d), q_index),
            pl.BlockSpec((1, bkv, d), kv_index),
            pl.BlockSpec((1, bkv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bkv=bkv, n_kv=n_kv, hq=hq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(pos_arr, starts_arr, qf, kf, vf)
    return out.reshape(b, hq, 1, d)


# ---------------------------------------------------------------------------
# Block-table-aware paged decode (in-flight continuous batching)
# ---------------------------------------------------------------------------

def _paged_decode_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, bs: int, mb: int):
    """One pool block of one row per grid step, for every head at once.
    The index map already fetched pool block ``tables[b, ki]`` whole
    (all KV heads, [HKV, bs, D]); this body only applies the per-row
    validity window ``kpos <= pos[b]`` over logical positions.

    Scores and context are lane-wise products reduced on the VPU: a
    decode query is one vector per head, so a matmul per head would
    fill one MXU row.  Query heads are grouped under their KV head
    ([HKV, G, 1, D]), so GQA reuses each K/V block across its group."""
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    k_start = ki * bs

    @pl.when(k_start <= pos)        # skip logical blocks beyond the row
    def _compute():
        hkv, _, d = k_ref.shape[1:]
        q = q_ref[0].astype(jnp.float32)                        # [HKV,G,1,D]
        k = k_ref[0].astype(jnp.float32).reshape(hkv, 1, bs, d)
        v = v_ref[0].astype(jnp.float32).reshape(hkv, 1, bs, d)
        s = jnp.sum(q * k, axis=3, keepdims=True)               # [HKV,G,bs,1]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]                 # [HKV,G,1,1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.sum(p * v, axis=2,
                                                      keepdims=True)
        m_ref[...] = m_new

    @pl.when(ki == mb - 1)
    def _flush():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention_pallas(q: jnp.ndarray, k_pool: jnp.ndarray,
                                  v_pool: jnp.ndarray,
                                  tables: jnp.ndarray,
                                  pos: jnp.ndarray, *,
                                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """q [B,HQ,1,D]; pools [NB,HKV,bs,D]; tables [B,MB] int32; pos [B].

    The thesis' scalar-prefetch sparsity guard applied to paging: the
    flattened block table rides the prefetch channel and the KV index
    map dereferences it, so each grid step of the ``(B, MB)`` grid DMAs
    exactly the pool block the row's table names — every KV head of it,
    one contiguous [HKV, bs, D] slab — with no gather materialisation.
    The map clamps ``ki`` to the row's last live block, so the steps
    past ``pos[b]`` repeat its index and issue no DMA."""
    b, hq, _, d = q.shape
    nb, hkv, bs, _ = k_pool.shape
    mb = tables.shape[1]
    group = hq // hkv

    scale = 1.0 / (d ** 0.5)
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(b, hkv, group, 1, d)
    tables_flat = jnp.asarray(tables, jnp.int32).reshape(b * mb)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    def q_index(bi, ki, tables_ref, pos_ref):
        return (bi, 0, 0, 0, 0)

    def kv_index(bi, ki, tables_ref, pos_ref):
        last = pos_ref[bi] // bs
        return (tables_ref[bi * mb + jnp.minimum(ki, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, hkv, group, 1, d), q_index),
            pl.BlockSpec((1, hkv, bs, d), kv_index),
            pl.BlockSpec((1, hkv, bs, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, hkv, group, 1, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((hkv, group, 1, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, bs=bs, mb=mb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, 1, d), q.dtype),
        interpret=resolve_interpret(interpret),
        name=PAGED_DECODE_KERNEL_NAME,
    )(tables_flat, pos_arr, qg, k_pool, v_pool)
    return out.reshape(b, hq, 1, d)
