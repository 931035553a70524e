"""Pallas TPU flash attention (online softmax), causal + sliding window,
GQA via index-map head folding.

Grid: (B*HQ, n_q_blocks, n_kv_blocks) with the KV axis innermost — the
reduction-innermost choice the thesis' partial-sums analysis prescribes: the
(m, l, acc) running statistics live in VMEM scratch across the KV sweep and
the output block is written exactly once.  GQA never materialises repeated
KV heads: the KV BlockSpec index map folds the query-head index onto its KV
group (zero extra HBM traffic for grouped queries).

Sliding-window ("local") attention — used by the recurrentgemma hybrid — is
the same kernel with a tighter mask; fully-masked KV blocks are skipped
with `pl.when` (no MXU work), the TPU analogue of the thesis' zero-skipping
sparsity guard (§3.6).
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bkv: int, causal: bool, window: Optional[int],
                  n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bkv

    # Block-level reachability: skip KV blocks that are fully masked.
    reachable = True
    if causal:
        reachable = k_start <= q_start + bq - 1
    if window is not None:
        # youngest query in block attends to keys > q_pos - window
        reachable = jnp.logical_and(
            reachable, k_start + bkv - 1 > q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [BQ, D]
        k = k_ref[0].astype(jnp.float32)            # [BKV, D]
        v = v_ref[0].astype(jnp.float32)            # [BKV, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                          # [BQ, 1]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # [BQ, BKV]
        alpha = jnp.exp(m_prev - m_new)              # [BQ, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows -> zeros
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _flash_kernel_starts(starts_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, bq: int, bkv: int,
                         causal: bool, window: Optional[int], n_kv: int,
                         hq: int):
    """Starts-masked variant: ``starts_ref`` ([B] int32, scalar-prefetched)
    holds each row's first real token index; keys below it are masked so
    left-padded rows attend exactly like unpadded ones.  Blocks wholly
    inside a row's pad prefix are skipped like any fully-masked block."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    start_b = starts_ref[bh // hq]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bkv

    reachable = k_start + bkv > start_b  # block has keys past the pads
    if causal:
        reachable = jnp.logical_and(reachable,
                                    k_start <= q_start + bq - 1)
    if window is not None:
        reachable = jnp.logical_and(
            reachable, k_start + bkv - 1 > q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = kpos >= start_b
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows -> zeros
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, block_q: int = 128, block_kv: int = 128,
                           causal: bool = True,
                           window: Optional[int] = None,
                           starts: Optional[jnp.ndarray] = None,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: [B, HQ, S, D]; k, v: [B, HKV, S, D] -> [B, HQ, S, D].

    ``starts`` ([B] int32, optional): per-row first real token index for
    left-padded batches — keys before it are masked for every query."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    bq = min(block_q, s)
    bkv = min(block_kv, s)
    assert s % bq == 0 and s % bkv == 0, (s, bq, bkv)

    scale = 1.0 / (d ** 0.5)
    qf = (q * jnp.asarray(scale, q.dtype)).reshape(b * hq, s, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)

    if starts is None:
        def q_index(bh, qi, ki):
            return (bh, qi, 0)

        def kv_index(bh, qi, ki):
            batch = bh // hq
            head = bh % hq
            return (batch * hkv + head // group, ki, 0)

        out = pl.pallas_call(
            functools.partial(_flash_kernel, bq=bq, bkv=bkv, causal=causal,
                              window=window, n_kv=s // bkv),
            grid=(b * hq, s // bq, s // bkv),
            in_specs=[
                pl.BlockSpec((1, bq, d), q_index),
                pl.BlockSpec((1, bkv, d), kv_index),
                pl.BlockSpec((1, bkv, d), kv_index),
            ],
            out_specs=pl.BlockSpec((1, bq, d), q_index),
            out_shape=jax.ShapeDtypeStruct((b * hq, s, d), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
            interpret=resolve_interpret(interpret),
        )(qf, kf, vf)
        return out.reshape(b, hq, s, d)

    starts_arr = jnp.asarray(starts, jnp.int32).reshape(b)

    def q_index_p(bh, qi, ki, starts_ref):
        return (bh, qi, 0)

    def kv_index_p(bh, qi, ki, starts_ref):
        batch = bh // hq
        head = bh % hq
        return (batch * hkv + head // group, ki, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hq, s // bq, s // bkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_index_p),
            pl.BlockSpec((1, bkv, d), kv_index_p),
            pl.BlockSpec((1, bkv, d), kv_index_p),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_index_p),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_flash_kernel_starts, bq=bq, bkv=bkv,
                          causal=causal, window=window, n_kv=s // bkv,
                          hq=hq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, s, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(starts_arr, qf, kf, vf)
    return out.reshape(b, hq, s, d)
