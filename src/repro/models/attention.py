"""Attention: training/prefill (flash-kernel or XLA reference backends),
decode against a KV cache, sliding-window (local) and cross variants.

Backend switch: ``backend="pallas"`` routes through the flash-attention
Pallas kernel (the perf-critical path on TPU); ``backend="xla"`` is the
pure-jnp formulation used for CPU smoke tests and for dry-run lowering
(clean HLO for the roofline analysis).  Both are validated against each
other in tests.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import Params, dense, rmsnorm, rope


def _mask_bias(s_q: int, s_kv: int, *, causal: bool,
               window: Optional[int], q_offset: int = 0) -> jnp.ndarray:
    qpos = q_offset + jnp.arange(s_q)[:, None]
    kpos = jnp.arange(s_kv)[None, :]
    ok = jnp.ones((s_q, s_kv), bool)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, window: Optional[int] = None,
              backend: str = "xla",
              schedule=None,
              starts: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q [B,HQ,S,D]; k/v [B,HKV,S,D] -> [B,HQ,S,D] (GQA aware).

    Backends: "pallas" (flash kernel, TPU), "xla" (naive reference — S^2
    intermediates), "chunked" (pure-jnp online-softmax over KV blocks —
    the thesis' loop-tiling future work (§7.2) applied to attention; no
    S^2 HBM tensor, bf16 probs).  With ``schedule`` (a committed
    :class:`~repro.core.schedule.FlashAttentionSchedule`), the pallas
    backend launches with the tuned blocks instead of defaults.

    ``starts`` ([B] int32, optional) is the first *real* token index of
    each left-padded row: keys at positions < starts[b] are masked for
    every query, so padded rows attend exactly as their unpadded
    equivalents.  Queries inside the pad prefix end up fully masked;
    their outputs are garbage by construction and must be discarded by
    the caller (they never feed a real row's residual stream because
    their keys are masked too)."""
    if backend == "pallas":
        if schedule is not None:
            from repro.kernels.flash_attention import \
                flash_attention_scheduled
            return flash_attention_scheduled(q, k, v, schedule=schedule,
                                             causal=causal, window=window,
                                             starts=starts)
        from repro.kernels.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               starts=starts)
    if backend == "chunked":
        if starts is not None:
            raise NotImplementedError(
                "attention_chunked does not support per-row starts")
        return attention_chunked(q, k, v, causal=causal, window=window)
    if backend == "stub":
        # Calibration stub for the kernel-substitution roofline
        # accounting (launch/roofline.flash_attention_cost): shape- and
        # dtype-correct, near-zero flops/bytes.  NOT a model — only used
        # by dry-run calibration compiles.
        b, hq, s, d = q.shape
        group = hq // k.shape[1]
        return (jnp.repeat(v, group, axis=1) + q * jnp.float32(0.0)
                .astype(q.dtype))
    b, hq, s, d = q.shape
    hkv, s_kv = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, s, d).astype(jnp.float32)
    scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg * scale,
                        k.astype(jnp.float32))
    if causal or window is not None:
        scores = scores + _mask_bias(s, s_kv, causal=causal, window=window)
    if starts is not None:
        key_ok = (jnp.arange(s_kv)[None, :]
                  >= starts[:, None])                 # [B, S_kv]
        scores = jnp.where(key_ok[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, s, d).astype(q.dtype)


def attention_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True, window: Optional[int] = None,
                      chunk_q: int = 512, chunk_kv: int = 1024
                      ) -> jnp.ndarray:
    """Flash-style attention in pure jnp: lax.scan over KV chunks carrying
    (m, l, acc) running statistics, q processed in chunks.  Keeps peak
    intermediates at O(S * chunk) instead of O(S^2); the probability
    block is cast to bf16 for the PV matmul (halves score traffic).

    This is the beyond-paper §Perf optimisation for the memory-bound
    attention cells — and exactly the *loop tiling* the thesis names as
    the natural extension of its loop-order study (§7.2)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    cq = min(chunk_q, s)
    ckv = min(chunk_kv, s)
    while s % cq:
        cq //= 2
    while s % ckv:
        ckv //= 2
    n_q, n_kv = s // cq, s // ckv
    scale = 1.0 / (d ** 0.5)

    qg = q.reshape(b, hkv, group, s, d)
    kg = k
    vg = v

    def q_block(qi_chunk, q_start):
        # qi_chunk: [B,HKV,G,CQ,D] float32-scaled
        qc = qi_chunk.astype(jnp.float32) * scale

        def kv_step(carry, ki):
            m, l, acc = carry
            k_start = ki * ckv
            kc = jax.lax.dynamic_slice_in_dim(kg, k_start, ckv, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(vg, k_start, ckv, axis=2)
            sblk = jnp.einsum("bhgqd,bhkd->bhgqk", qc,
                              kc.astype(jnp.float32))
            qpos = q_start + jnp.arange(cq)[:, None]
            kpos = k_start + jnp.arange(ckv)[None, :]
            ok = jnp.ones((cq, ckv), bool)
            if causal:
                ok &= kpos <= qpos
            if window is not None:
                ok &= kpos > qpos - window
            sblk = jnp.where(ok[None, None, None], sblk, -1e30)
            m_cur = sblk.max(axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(sblk - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + p.sum(axis=-1, keepdims=True)
            # bf16 probs for the PV matmul (halves the block traffic)
            pv = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(jnp.bfloat16),
                            vc, preferred_element_type=jnp.float32)
            acc_new = acc * alpha + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, group, cq, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, group, cq, 1), jnp.float32)
        a0 = jnp.zeros((b, hkv, group, cq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(n_kv))
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l).astype(q.dtype)

    if n_q == 1:
        out = q_block(qg, 0)
    else:
        qs = qg.reshape(b, hkv, group, n_q, cq, d).transpose(
            3, 0, 1, 2, 4, 5)                       # [NQ,B,HKV,G,CQ,D]
        out = jax.lax.map(
            lambda t: q_block(t[0], t[1] * cq),
            (qs, jnp.arange(n_q)))
        out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, hkv, group, s, d)
    return out.reshape(b, hq, s, d)


def cross_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray
                    ) -> jnp.ndarray:
    """Unmasked attention over a fixed memory (whisper decoder->encoder)."""
    return attention(q, k, v, causal=False, window=None, backend="xla")


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, pos: jnp.ndarray, *,
                     window: Optional[int] = None,
                     backend: str = "xla",
                     schedule=None,
                     starts: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """One-token attention against a cache.

    q [B,HQ,1,D]; caches [B,HKV,S,D]; ``pos`` — current position (cache
    entries at indices > pos are invalid), a scalar int32 shared by the
    batch or a per-row [B] int32 vector (in-flight batching: each row
    decodes at its own depth).  For local attention the cache is a
    rolling buffer of size ``window`` and all (valid) entries are in
    range by construction.

    ``starts`` ([B] int32, optional) masks cache entries below each
    row's first real token, completing the left-pad mask in decode:
    valid keys are ``starts[b] <= kpos <= pos[b]``.

    ``backend="pallas"`` routes through the single-query flash-decode
    kernel — the serving memory roofline — streaming the cache in
    ``schedule.block_kv`` blocks (a committed
    :class:`~repro.core.schedule.DecodeAttentionSchedule`) and skipping
    blocks wholly beyond ``pos``.  The kernel's validity mask
    (``kpos <= pos``) coincides with the rolling-buffer rule for both
    ``pos < S`` (partial) and ``pos >= S`` (wrapped: every slot valid),
    so one code path serves global and windowed caches.
    """
    if backend == "pallas":
        if schedule is not None:
            from repro.kernels.decode_attention import \
                decode_attention_scheduled
            return decode_attention_scheduled(q, k_cache, v_cache, pos,
                                              schedule=schedule,
                                              starts=starts)
        from repro.kernels.decode_attention import \
            decode_attention as decode_attention_kernel
        return decode_attention_kernel(q, k_cache, v_cache, pos,
                                       starts=starts)
    b, hq, _, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("bhgd,bhkd->bhgk", qg * scale,
                        k_cache.astype(jnp.float32))
    kpos = jnp.arange(s)[None, None, None, :]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    pos_b = pos_b[:, None, None, None]
    if window is None:
        valid = kpos <= pos_b
    else:
        # rolling buffer: slots written so far
        valid = kpos <= jnp.minimum(pos_b, s - 1)
    if starts is not None:
        valid &= kpos >= starts[:, None, None, None]
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", probs,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, hq, 1, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged KV cache primitives (serving: block tables instead of row tensors)
# ---------------------------------------------------------------------------

def paged_update_kv(pool_k: jnp.ndarray, pool_v: jnp.ndarray,
                    k: jnp.ndarray, v: jnp.ndarray,
                    tables: jnp.ndarray, pos: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write one decode step's k/v into a block-paged pool.

    pool_k/pool_v [NB, HKV, bs, D] — the shared block pool (``NB``
    fixed-size blocks of ``bs`` slots each); k/v [B, HKV, 1, D]; tables
    [B, MB] int32 — each row's logical-block -> pool-block mapping;
    pos [B] int32 — each row's logical write position.  Row ``b``'s
    token lands in pool block ``tables[b, pos[b] // bs]`` at offset
    ``pos[b] % bs``.  Idle rows must point at a reserved garbage block
    (the allocator never hands out block 0) so their writes cannot
    corrupt live sequences.
    """
    bs = pool_k.shape[2]
    rows = jnp.arange(tables.shape[0])
    blk = tables[rows, pos // bs]                     # [B]
    off = pos % bs                                    # [B]
    pk = pool_k.at[blk, :, off].set(k[:, :, 0, :].astype(pool_k.dtype))
    pv = pool_v.at[blk, :, off].set(v[:, :, 0, :].astype(pool_v.dtype))
    return pk, pv


def paged_decode_attention(q: jnp.ndarray, pool_k: jnp.ndarray,
                           pool_v: jnp.ndarray, tables: jnp.ndarray,
                           pos: jnp.ndarray, *, backend: str = "xla",
                           schedule=None) -> jnp.ndarray:
    """One-token attention against a block-paged KV pool.

    q [B,HQ,1,D]; pools [NB,HKV,bs,D]; tables [B,MB] int32; pos [B]
    int32 per-row positions.  Row ``b`` attends to logical keys
    ``0..pos[b]``, gathered through its block table — rows written
    contiguously from logical 0 need no ``starts`` mask (the in-flight
    engine stores only real tokens).  Unassigned table slots may point
    anywhere (conventionally the reserved block 0): their logical
    positions exceed ``pos`` so the validity mask discards them.

    ``backend="pallas"`` runs the block-table-aware gather kernel on a
    ``(B, MB)`` grid: each step streams one pool block of one row with
    every KV head in it, and blocks wholly beyond the row's ``pos`` are
    neither fetched nor computed (scalar prefetch); the XLA path
    materialises the gather (reference semantics).  ``schedule`` is accepted for
    signature parity but paging fixes the streaming granularity at the
    block size."""
    if backend == "pallas":
        from repro.kernels.decode_attention import paged_decode_attention \
            as paged_decode_attention_kernel
        return paged_decode_attention_kernel(q, pool_k, pool_v, tables,
                                             pos)
    b, hq, _, d = q.shape
    nb, hkv, bs, _ = pool_k.shape
    mb = tables.shape[1]
    group = hq // hkv
    # Gather each row's blocks: [B, MB, HKV, bs, D] -> [B, HKV, MB*bs, D]
    kg = pool_k[tables].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, mb * bs, d)
    vg = pool_v[tables].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, mb * bs, d)
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("bhgd,bhkd->bhgk", qg * scale,
                        kg.astype(jnp.float32))
    kpos = jnp.arange(mb * bs)[None, None, None, :]
    valid = kpos <= pos[:, None, None, None]
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", probs, vg.astype(jnp.float32))
    return out.reshape(b, hq, 1, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention sub-layer (projection + rope + qk-norm + attend)
# ---------------------------------------------------------------------------

def attn_params(b, prefix: str, n_layers: int, d: int, n_heads: int,
                n_kv: int, hd: int, qk_norm: bool,
                cross: bool = False) -> None:
    b.normal(f"{prefix}/wq", [n_layers, d, n_heads * hd],
             ("layers", "embed", "heads"), fan_in=d)
    b.normal(f"{prefix}/wk", [n_layers, d, n_kv * hd],
             ("layers", "embed", "kv_heads"), fan_in=d)
    b.normal(f"{prefix}/wv", [n_layers, d, n_kv * hd],
             ("layers", "embed", "kv_heads"), fan_in=d)
    b.normal(f"{prefix}/wo", [n_layers, n_heads * hd, d],
             ("layers", "heads", "embed"), fan_in=n_heads * hd)
    if qk_norm:
        b.zeros(f"{prefix}/q_norm", [n_layers, hd], ("layers", None))
        b.zeros(f"{prefix}/k_norm", [n_layers, hd], ("layers", None))


def qkv_project(x: jnp.ndarray, p: Params, *, n_heads: int, n_kv: int,
                hd: int, positions: jnp.ndarray, rope_theta: float,
                qk_norm: bool, use_rope: bool = True,
                norm_eps: float = 1e-6
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x [B,S,D] -> q [B,HQ,S,hd], k/v [B,HKV,S,hd]."""
    b_, s, _ = x.shape
    q = dense(x, p["wq"]).reshape(b_, s, n_heads, hd).transpose(0, 2, 1, 3)
    k = dense(x, p["wk"]).reshape(b_, s, n_kv, hd).transpose(0, 2, 1, 3)
    v = dense(x, p["wv"]).reshape(b_, s, n_kv, hd).transpose(0, 2, 1, 3)
    if qk_norm:
        q = rmsnorm(q, p["q_norm"], norm_eps)
        k = rmsnorm(k, p["k_norm"], norm_eps)
    if use_rope:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    return q, k, v


def attn_out(ctx: jnp.ndarray, p: Params) -> jnp.ndarray:
    """ctx [B,H,S,hd] -> [B,S,D]."""
    b_, h, s, hd = ctx.shape
    return dense(ctx.transpose(0, 2, 1, 3).reshape(b_, s, h * hd), p["wo"])


def update_kv_cache(cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                    k: jnp.ndarray, v: jnp.ndarray, pos: jnp.ndarray,
                    window: Optional[int] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write one step's k/v [B,HKV,1,hd] at position ``pos`` (mod window
    for rolling local-attention buffers)."""
    s = cache_k.shape[2]
    slot = pos % s if window is not None else pos
    ck = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                      (0, 0, slot, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                      (0, 0, slot, 0))
    return ck, cv
