"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

One parameter tree, three entry points:

  ``forward``     — teacher-forced logits (train / prefill path), scan over
                    stacked layer params with per-layer remat.
  ``prefill``     — forward + assembled decode caches.
  ``decode_step`` — one token against the caches (serve_step).

Layer temporal-mixing is chosen per family: attention (dense/moe/vlm),
mamba (ssm), or the recurrentgemma pattern (hybrid: scan over
(rglru, rglru, local-attn) groups plus an explicit tail).  The sharding
layer never appears here — models annotate *logical* axes only (via
ParamBuilder) and accept an optional ``shard_fn`` to constrain activations.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (ParamBuilder, Params, dense, dtype_of,
                                 mlp, mlp_params, rmsnorm, softmax_xent)

Identity = lambda x, where="boundary": x  # noqa: E731


def _remat(body, mode):
    """Remat policy switch: False/"none" (save everything), True/"full"
    (recompute everything — default), "dots" (save matmul outputs, skip
    recompute of the expensive dots — a §Perf knob)."""
    if mode in (False, "none"):
        return body
    if mode == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if mode == "moe":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "moe_in", "moe_out"))
    return jax.checkpoint(body)


def _scan(body, init, xs):
    """lax.scan honouring the dry-run unroll knob (see scan_config)."""
    from repro.models import scan_config
    return jax.lax.scan(body, init, xs, unroll=scan_config.UNROLL)



# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

def _attn_layer_params(b: ParamBuilder, prefix: str, cfg: ModelConfig,
                       n_layers: int) -> None:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    b.zeros(f"{prefix}/ln1", [n_layers, d], ("layers", "embed"))
    attn.attn_params(b, f"{prefix}/attn", n_layers, d, cfg.n_heads,
                     cfg.n_kv_heads, hd, cfg.qk_norm)
    b.zeros(f"{prefix}/ln2", [n_layers, d], ("layers", "embed"))
    if cfg.n_experts:
        moe_mod.moe_params(b, f"{prefix}/moe", n_layers, d, cfg.n_experts,
                           cfg.moe_d_ff, cfg.n_shared_experts,
                           cfg.moe_d_ff)
    else:
        mlp_params(b, f"{prefix}/mlp", n_layers, d, cfg.d_ff, cfg.mlp_type)


def _mamba_layer_params(b: ParamBuilder, prefix: str, cfg: ModelConfig,
                        n_layers: int) -> None:
    b.zeros(f"{prefix}/ln1", [n_layers, cfg.d_model], ("layers", "embed"))
    ssm_mod.mamba_params(b, f"{prefix}/mamba", n_layers, cfg.d_model,
                         cfg.d_inner, cfg.ssm_state, cfg.ssm_conv,
                         cfg.resolved_dt_rank)


def _rglru_layer_params(b: ParamBuilder, prefix: str, cfg: ModelConfig,
                        n_layers: int) -> None:
    d = cfg.d_model
    b.zeros(f"{prefix}/ln1", [n_layers, d], ("layers", "embed"))
    ssm_mod.rglru_params(b, f"{prefix}/rglru", n_layers, d,
                         cfg.resolved_lru_width, cfg.ssm_conv)
    b.zeros(f"{prefix}/ln2", [n_layers, d], ("layers", "embed"))
    mlp_params(b, f"{prefix}/mlp", n_layers, d, cfg.d_ff, cfg.mlp_type)


def _hybrid_counts(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...]]:
    pat = cfg.block_pattern
    return cfg.n_layers // len(pat), pat[:cfg.n_layers % len(pat)]


def init_params(cfg: ModelConfig, rng: jax.Array
                ) -> Tuple[Params, Params]:
    """Returns (params, logical_axes) with matching tree structure."""
    b = ParamBuilder(rng, dtype_of(cfg.dtype))
    d = cfg.d_model
    b.normal("embed", [cfg.vocab_size, d], ("vocab", "embed"),
             fan_in=d, scale=float(d) ** 0.5)

    if cfg.family == "ssm":
        _mamba_layer_params(b, "layers", cfg, cfg.n_layers)
    elif cfg.family == "hybrid":
        n_groups, tail = _hybrid_counts(cfg)
        for i, kind in enumerate(cfg.block_pattern):
            if kind == "rglru":
                _rglru_layer_params(b, f"groups/b{i}", cfg, n_groups)
            else:
                _attn_layer_params(b, f"groups/b{i}", cfg, n_groups)
        for i, kind in enumerate(tail):
            if kind == "rglru":
                _rglru_layer_params(b, f"tail/b{i}", cfg, 1)
            else:
                _attn_layer_params(b, f"tail/b{i}", cfg, 1)
    else:  # dense / moe / vlm
        _attn_layer_params(b, "layers", cfg, cfg.n_layers)

    b.zeros("final_norm", [d], ("embed",))
    if not cfg.tie_embeddings:
        b.normal("lm_head", [d, cfg.vocab_size], ("embed", "vocab"),
                 fan_in=d)
    return b.params, b.axes


# ---------------------------------------------------------------------------
# Layer bodies (train / prefill path)
# ---------------------------------------------------------------------------

def _attn_body(x: jnp.ndarray, lp: Params, cfg: ModelConfig,
               positions: jnp.ndarray, *, causal: bool,
               window: Optional[int], backend: str,
               shard_fn: Callable, schedule=None, starts=None
               ) -> Tuple[jnp.ndarray, Dict]:
    """One transformer layer; returns (x, {kv for cache assembly, aux})."""
    hd = cfg.resolved_head_dim
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = attn.qkv_project(
        h, lp["attn"], n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, hd=hd,
        positions=positions, rope_theta=cfg.rope_theta,
        qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)
    ctx = attn.attention(q, k, v, causal=causal, window=window,
                         backend=backend, schedule=schedule,
                         starts=starts)
    x = x + attn.attn_out(ctx, lp["attn"])
    x = shard_fn(x)

    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.n_experts:
        y, aux = moe_mod.moe_ffn(h, lp["moe"], n_experts=cfg.n_experts,
                                 top_k=cfg.experts_per_token,
                                 capacity_factor=cfg.capacity_factor,
                                 shard_fn=shard_fn)
    else:
        y = mlp(h, lp["mlp"], cfg.mlp_type)
    x = shard_fn(x + y)
    return x, {"k": k, "v": v, "aux": aux}


def _mamba_body(x: jnp.ndarray, lp: Params, cfg: ModelConfig,
                shard_fn: Callable, backend: str = "xla",
                schedule=None, seq_valid=None) -> jnp.ndarray:
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    y, _ = ssm_mod.mamba_block(h, lp["mamba"], state=cfg.ssm_state,
                               conv=cfg.ssm_conv,
                               dt_rank=cfg.resolved_dt_rank,
                               backend=backend, schedule=schedule,
                               seq_valid=seq_valid)
    return shard_fn(x + y)


def _rglru_body(x: jnp.ndarray, lp: Params, cfg: ModelConfig,
                shard_fn: Callable) -> jnp.ndarray:
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    y, _ = ssm_mod.rglru_block(h, lp["rglru"])
    x = shard_fn(x + y)
    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    return shard_fn(x + mlp(h, lp["mlp"], cfg.mlp_type))


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def embed_inputs(params: Params, cfg: ModelConfig,
                 batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    tok = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.family == "vlm":
        img = batch["image_embeds"].astype(tok.dtype)
        tok = jnp.concatenate([img, tok], axis=1)
    return tok


def forward(params: Params, cfg: ModelConfig,
            batch: Dict[str, jnp.ndarray], *,
            backend: str = "xla",
            shard_fn: Callable = Identity,
            remat: bool = True,
            collect_kv: bool = False,
            schedules=None,
            seq_starts: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Teacher-forced logits [B, S, V] (+ aux dict: moe aux loss, kv).

    ``schedules`` (a :class:`~repro.core.schedule.ScheduleBundle`)
    carries the committed kernel schedules the pallas backend launches
    with; None fields (or ``schedules=None``) use kernel defaults.

    ``seq_starts`` ([B] int32, optional) marks the first real token of
    each left-padded row: rope positions become per-row
    ``arange(S) - starts`` and pad positions are masked out of attention
    (or out of the SSM recurrence), so a left-padded row's logits at its
    real positions equal the unpadded row's up to float reduction
    order.  Supported
    for the dense/moe/ssm families (vlm interleaves image tokens and
    hybrid's rolling-window caches assume dense prefixes — both raise).
    """
    x = embed_inputs(params, cfg, batch)
    bsz, seq, _ = x.shape
    seq_valid = None
    if seq_starts is not None:
        if cfg.family not in ("dense", "moe", "ssm"):
            raise ValueError(
                f"seq_starts is not supported for family {cfg.family!r}")
        positions = (jnp.arange(seq)[None, :]
                     - seq_starts[:, None])            # [B, S]
        seq_valid = jnp.arange(seq)[None, :] >= seq_starts[:, None]
    else:
        positions = jnp.arange(seq)
    x = shard_fn(x)

    fa_sched = (schedules.flash_attention if schedules is not None
                else None)
    ssm_sched = schedules.ssm_scan if schedules is not None else None
    extras: Dict[str, Any] = {}

    if cfg.family == "ssm":
        def body(carry, lp):
            return _mamba_body(carry, lp, cfg, shard_fn, backend,
                               ssm_sched, seq_valid), None
        body = _remat(body, remat)
        x, _ = _scan(body, x, params["layers"])
    elif cfg.family == "hybrid":
        window = cfg.local_window if seq > cfg.local_window else None

        def group_body(carry, gp):
            kvs = {}
            for i, kind in enumerate(cfg.block_pattern):
                lp = gp[f"b{i}"]
                if kind == "rglru":
                    carry = _rglru_body(carry, lp, cfg, shard_fn)
                else:
                    carry, kv = _attn_body(
                        carry, lp, cfg, positions, causal=True,
                        window=window, backend=backend, shard_fn=shard_fn,
                        schedule=fa_sched)
                    kvs[f"b{i}"] = {"k": kv["k"], "v": kv["v"]}
            return carry, (kvs if collect_kv else None)
        gb = _remat(group_body, remat)
        x, group_kv = _scan(gb, x, params["groups"])
        extras["group_kv"] = group_kv
        _, tail = _hybrid_counts(cfg)
        tail_kv = {}
        for i, kind in enumerate(tail):
            lp = jax.tree.map(lambda a: a[0], params["tail"][f"b{i}"])
            if kind == "rglru":
                x = _rglru_body(x, lp, cfg, shard_fn)
            else:
                x, kv = _attn_body(x, lp, cfg, positions, causal=True,
                                   window=window, backend=backend,
                                   shard_fn=shard_fn, schedule=fa_sched)
                if collect_kv:
                    tail_kv[f"b{i}"] = {"k": kv["k"], "v": kv["v"]}
        extras["tail_kv"] = tail_kv
    else:
        def body(carry, lp):
            carry, kv = _attn_body(carry, lp, cfg, positions, causal=True,
                                   window=None, backend=backend,
                                   shard_fn=shard_fn, schedule=fa_sched,
                                   starts=seq_starts)
            ys = {"aux": kv["aux"]}
            if collect_kv:
                ys["k"] = kv["k"]
                ys["v"] = kv["v"]
            return carry, ys
        body = _remat(body, remat)
        x, ys = _scan(body, x, params["layers"])
        extras["aux"] = jnp.mean(ys["aux"])
        if collect_kv:
            extras["kv"] = {"k": ys["k"], "v": ys["v"]}

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jax.lax.dot_general(
        x, head, (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return logits, extras


def loss_fn(params: Params, cfg: ModelConfig,
            batch: Dict[str, jnp.ndarray], *,
            backend: str = "xla", shard_fn: Callable = Identity,
            remat="full", schedules=None
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits, extras = forward(params, cfg, batch, backend=backend,
                             shard_fn=shard_fn, remat=remat,
                             schedules=schedules)
    loss, denom = softmax_xent(logits, batch["labels"])
    metrics = {"xent": loss, "tokens": denom}
    if "aux" in extras:
        loss = loss + 0.01 * extras["aux"]
        metrics["moe_aux"] = extras["aux"]
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, bsz: int, max_len: int,
               dtype=None) -> Dict[str, Any]:
    """Empty caches sized for ``max_len`` context."""
    dt = dtype or dtype_of(cfg.dtype)
    hd = cfg.resolved_head_dim
    if cfg.family == "ssm":
        return {"layers": {
            "ssm": jnp.zeros((cfg.n_layers, bsz, cfg.d_inner,
                              cfg.ssm_state), dt),
            "conv": jnp.zeros((cfg.n_layers, bsz, cfg.ssm_conv - 1,
                               cfg.d_inner), dt)}}
    if cfg.family == "hybrid":
        n_groups, tail = _hybrid_counts(cfg)
        win = min(cfg.local_window, max_len)
        groups: Dict[str, Any] = {}
        for i, kind in enumerate(cfg.block_pattern):
            if kind == "rglru":
                groups[f"b{i}"] = {
                    "h": jnp.zeros((n_groups, bsz, cfg.resolved_lru_width),
                                   dt),
                    "conv": jnp.zeros((n_groups, bsz, cfg.ssm_conv - 1,
                                       cfg.resolved_lru_width), dt)}
            else:
                groups[f"b{i}"] = {
                    "k": jnp.zeros((n_groups, bsz, cfg.n_kv_heads, win,
                                    hd), dt),
                    "v": jnp.zeros((n_groups, bsz, cfg.n_kv_heads, win,
                                    hd), dt)}
        tail_c: Dict[str, Any] = {}
        for i, kind in enumerate(tail):
            if kind == "rglru":
                tail_c[f"b{i}"] = {
                    "h": jnp.zeros((bsz, cfg.resolved_lru_width), dt),
                    "conv": jnp.zeros((bsz, cfg.ssm_conv - 1,
                                       cfg.resolved_lru_width), dt)}
            else:
                tail_c[f"b{i}"] = {
                    "k": jnp.zeros((bsz, cfg.n_kv_heads, win, hd), dt),
                    "v": jnp.zeros((bsz, cfg.n_kv_heads, win, hd), dt)}
        return {"groups": groups, "tail": tail_c}
    return {"layers": {
        "k": jnp.zeros((cfg.n_layers, bsz, cfg.n_kv_heads, max_len, hd),
                       dt),
        "v": jnp.zeros((cfg.n_layers, bsz, cfg.n_kv_heads, max_len, hd),
                       dt)}}


def init_paged_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                     dtype=None) -> Dict[str, Any]:
    """Empty block-paged KV pools: ``n_blocks`` shared fixed-size blocks
    of ``block_size`` token slots per layer, addressed through per-row
    block tables instead of per-row cache tensors (attention families
    only; recurrent caches are O(1) per row and need no paging)."""
    if cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"paged KV caches need an attention family, got "
            f"{cfg.family!r}")
    dt = dtype or dtype_of(cfg.dtype)
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, n_blocks, cfg.n_kv_heads, block_size, hd)
    return {"layers": {"k": jnp.zeros(shape, dt),
                       "v": jnp.zeros(shape, dt)}}


# ---------------------------------------------------------------------------
# Decode step (serve_step)
# ---------------------------------------------------------------------------

def _attn_decode(x, lp, cache, cfg, pos, window, backend="xla",
                 schedule=None, starts=None):
    hd = cfg.resolved_head_dim
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    if starts is not None:
        positions = (pos - starts)[:, None]            # [B, 1]
    else:
        positions = jnp.full((1,), pos)
    q, k, v = attn.qkv_project(
        h, lp["attn"], n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, hd=hd,
        positions=positions, rope_theta=cfg.rope_theta,
        qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)
    ck, cv = attn.update_kv_cache(cache["k"], cache["v"], k, v, pos,
                                  window=window)
    ctx = attn.decode_attention(q, ck, cv, pos, window=window,
                                backend=backend, schedule=schedule,
                                starts=starts)
    x = x + attn.attn_out(ctx, lp["attn"])
    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        y, _ = moe_mod.moe_ffn(h, lp["moe"], n_experts=cfg.n_experts,
                               top_k=cfg.experts_per_token,
                               capacity_factor=cfg.capacity_factor)
    else:
        y = mlp(h, lp["mlp"], cfg.mlp_type)
    return x + y, {"k": ck, "v": cv}


def _attn_decode_paged(x, lp, cache, cfg, pos, tables, backend="xla",
                      schedule=None):
    """Paged twin of :func:`_attn_decode`: ``cache`` holds pool tensors
    [NB,HKV,bs,hd], ``pos`` is a per-row [B] vector of logical
    positions, and the write/attend addressing goes through ``tables``
    [B,MB].  Rows store only real tokens from logical position 0, so no
    ``starts`` mask is needed on this path."""
    hd = cfg.resolved_head_dim
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = attn.qkv_project(
        h, lp["attn"], n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, hd=hd,
        positions=pos[:, None], rope_theta=cfg.rope_theta,
        qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)
    pk, pv = attn.paged_update_kv(cache["k"], cache["v"], k, v, tables,
                                  pos)
    ctx = attn.paged_decode_attention(q, pk, pv, tables, pos,
                                      backend=backend, schedule=schedule)
    x = x + attn.attn_out(ctx, lp["attn"])
    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        y, _ = moe_mod.moe_ffn(h, lp["moe"], n_experts=cfg.n_experts,
                               top_k=cfg.experts_per_token,
                               capacity_factor=cfg.capacity_factor)
    else:
        y = mlp(h, lp["mlp"], cfg.mlp_type)
    return x + y, {"k": pk, "v": pv}


def decode_step(params: Params, cfg: ModelConfig, cache: Dict[str, Any],
                tokens: jnp.ndarray, pos: jnp.ndarray, *,
                shard_fn: Callable = Identity,
                backend: str = "xla", schedules=None,
                seq_starts: Optional[jnp.ndarray] = None,
                block_tables: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step.  tokens [B, 1] int32; pos scalar int32 (shared
    write position) or, with ``block_tables``, a per-row [B] vector.
    Returns (logits [B, 1, V], new cache).

    ``backend="pallas"`` runs the per-token cache attention (or the
    fused SSM update) through the Pallas serving kernels, launched with
    the committed schedules in ``schedules`` (a
    :class:`~repro.core.schedule.ScheduleBundle`) — the compiled step
    *is* the tuner's output.

    ``seq_starts`` ([B] int32, optional) continues the left-pad masks
    of a :func:`prefill` that was given the same vector: cache entries
    below each row's start stay masked and rope counts from the row's
    first real token (dense/moe only; recurrent caches carry no pads).

    ``block_tables`` ([B,MB] int32, optional) switches the attention
    families to the block-paged cache layout: ``cache`` must be an
    :func:`init_paged_cache` tree, ``pos`` a per-row vector, and each
    row reads/writes pool blocks through its table row (the in-flight
    continuous-batching path)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard_fn(x)

    da_sched = (schedules.decode_attention if schedules is not None
                else None)
    ssm_sched = schedules.ssm_scan if schedules is not None else None

    if block_tables is not None and cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"block_tables needs an attention family, got "
            f"{cfg.family!r}")
    if seq_starts is not None and cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"seq_starts in decode_step needs an attention family, got "
            f"{cfg.family!r} (recurrent caches carry no pad entries)")

    if cfg.family == "ssm":
        def body(carry, inp):
            lp, lc = inp
            h = rmsnorm(carry, lp["ln1"], cfg.norm_eps)
            y, nc = ssm_mod.mamba_block(
                h, lp["mamba"], state=cfg.ssm_state, conv=cfg.ssm_conv,
                dt_rank=cfg.resolved_dt_rank, cache=lc,
                backend=backend, schedule=ssm_sched)
            return carry + y, nc
        x, new_layers = _scan(body, x,
                                     (params["layers"], cache["layers"]))
        new_cache: Dict[str, Any] = {"layers": new_layers}
    elif cfg.family == "hybrid":
        win = cache_window(cfg, cache)

        def gbody(carry, inp):
            gp, gc = inp
            ncs = {}
            for i, kind in enumerate(cfg.block_pattern):
                lp, lc = gp[f"b{i}"], gc[f"b{i}"]
                if kind == "rglru":
                    h = rmsnorm(carry, lp["ln1"], cfg.norm_eps)
                    y, nc = ssm_mod.rglru_block(h, lp["rglru"], cache=lc)
                    carry = carry + y
                    h = rmsnorm(carry, lp["ln2"], cfg.norm_eps)
                    carry = carry + mlp(h, lp["mlp"], cfg.mlp_type)
                else:
                    carry, nc = _attn_decode(carry, lp, lc, cfg, pos, win,
                                             backend, da_sched)
                ncs[f"b{i}"] = nc
            return carry, ncs
        x, new_groups = _scan(gbody, x,
                                     (params["groups"], cache["groups"]))
        _, tail = _hybrid_counts(cfg)
        new_tail = {}
        for i, kind in enumerate(tail):
            lp = jax.tree.map(lambda a: a[0], params["tail"][f"b{i}"])
            lc = cache["tail"][f"b{i}"]
            if kind == "rglru":
                h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
                y, nc = ssm_mod.rglru_block(h, lp["rglru"], cache=lc)
                x = x + y
                h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
                x = x + mlp(h, lp["mlp"], cfg.mlp_type)
            else:
                x, nc = _attn_decode(x, lp, lc, cfg, pos, win,
                                     backend, da_sched)
            new_tail[f"b{i}"] = nc
        new_cache = {"groups": new_groups, "tail": new_tail}
    else:
        if block_tables is not None:
            def body(carry, inp):
                lp, lc = inp
                carry, nc = _attn_decode_paged(carry, lp, lc, cfg, pos,
                                               block_tables, backend,
                                               da_sched)
                return carry, nc
        else:
            def body(carry, inp):
                lp, lc = inp
                carry, nc = _attn_decode(carry, lp, lc, cfg, pos, None,
                                         backend, da_sched,
                                         starts=seq_starts)
                return carry, nc
        x, new_layers = _scan(body, x,
                                     (params["layers"], cache["layers"]))
        new_cache = {"layers": new_layers}

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jax.lax.dot_general(x, head, (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return logits, new_cache


def cache_window(cfg: ModelConfig, cache: Dict[str, Any]) -> Optional[int]:
    """Rolling-window size used by hybrid attention caches."""
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            return cache["groups"][f"b{i}"]["k"].shape[3]
    return None


# ---------------------------------------------------------------------------
# Prefill: forward + cache assembly
# ---------------------------------------------------------------------------

def _window_cache(k: jnp.ndarray, seq: int, win: int) -> jnp.ndarray:
    """Last ``win`` entries of a [..., S, hd] K/V tensor, rotated so entry
    for absolute position p sits at rolling slot p % win."""
    if seq <= win:
        return k
    tail = k[..., seq - win:, :]
    return jnp.roll(tail, shift=(seq - win) % win, axis=-2)


def prefill(params: Params, cfg: ModelConfig,
            batch: Dict[str, jnp.ndarray], *,
            backend: str = "xla", shard_fn: Callable = Identity,
            schedules=None, seq_starts: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Run the full prompt; return (logits [B,S,V], decode caches filled
    up to S).  Attention families collect per-layer K/V; recurrent
    families capture final scan states; hybrid collects both (windowed
    K/V in rolling-slot order).  ``schedules`` carries the committed
    kernel schedules for the pallas backend (see :func:`forward`);
    ``seq_starts`` enables the left-pad masks (see :func:`forward`)."""
    seq = batch["tokens"].shape[1]
    fa_sched = (schedules.flash_attention if schedules is not None
                else None)
    ssm_sched = schedules.ssm_scan if schedules is not None else None
    if cfg.family == "vlm":
        seq += cfg.num_image_tokens
    if seq_starts is not None and cfg.family not in ("dense", "moe",
                                                     "ssm"):
        raise ValueError(
            f"seq_starts is not supported for family {cfg.family!r}")
    if cfg.family == "ssm":
        x = embed_inputs(params, cfg, batch)
        x = shard_fn(x)
        seq_valid = None
        if seq_starts is not None:
            seq_valid = (jnp.arange(seq)[None, :]
                         >= seq_starts[:, None])

        def body(carry, lp):
            h = rmsnorm(carry, lp["ln1"], cfg.norm_eps)
            y, st = ssm_mod.mamba_block(h, lp["mamba"],
                                        state=cfg.ssm_state,
                                        conv=cfg.ssm_conv,
                                        dt_rank=cfg.resolved_dt_rank,
                                        backend=backend,
                                        schedule=ssm_sched,
                                        seq_valid=seq_valid)
            return shard_fn(carry + y), st
        x, states = _scan(body, x, params["layers"])
        logits = _head(params, cfg, x)
        return logits, {"layers": states}

    if cfg.family == "hybrid":
        x = embed_inputs(params, cfg, batch)
        x = shard_fn(x)
        positions = jnp.arange(seq)
        win = min(cfg.local_window, seq)
        mask_win = cfg.local_window if seq > cfg.local_window else None

        def gbody(carry, gp):
            states = {}
            for i, kind in enumerate(cfg.block_pattern):
                lp = gp[f"b{i}"]
                if kind == "rglru":
                    h = rmsnorm(carry, lp["ln1"], cfg.norm_eps)
                    y, st = ssm_mod.rglru_block(h, lp["rglru"])
                    carry = carry + y
                    h = rmsnorm(carry, lp["ln2"], cfg.norm_eps)
                    carry = shard_fn(carry + mlp(h, lp["mlp"],
                                                 cfg.mlp_type))
                    states[f"b{i}"] = st
                else:
                    carry, kv = _attn_body(
                        carry, lp, cfg, positions, causal=True,
                        window=mask_win, backend=backend,
                        shard_fn=shard_fn, schedule=fa_sched)
                    states[f"b{i}"] = {
                        "k": _window_cache(kv["k"], seq, win),
                        "v": _window_cache(kv["v"], seq, win)}
            return carry, states
        x, group_states = _scan(gbody, x, params["groups"])
        _, tail = _hybrid_counts(cfg)
        tail_states = {}
        for i, kind in enumerate(tail):
            lp = jax.tree.map(lambda a: a[0], params["tail"][f"b{i}"])
            if kind == "rglru":
                h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
                y, st = ssm_mod.rglru_block(h, lp["rglru"])
                x = x + y
                h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
                x = x + mlp(h, lp["mlp"], cfg.mlp_type)
                tail_states[f"b{i}"] = st
            else:
                x, kv = _attn_body(x, lp, cfg, positions, causal=True,
                                   window=mask_win, backend=backend,
                                   shard_fn=shard_fn, schedule=fa_sched)
                tail_states[f"b{i}"] = {
                    "k": _window_cache(kv["k"], seq, win),
                    "v": _window_cache(kv["v"], seq, win)}
        logits = _head(params, cfg, x)
        return logits, {"groups": group_states, "tail": tail_states}

    logits, extras = forward(params, cfg, batch, backend=backend,
                             shard_fn=shard_fn, collect_kv=True,
                             remat=False, schedules=schedules,
                             seq_starts=seq_starts)
    kv = extras["kv"]
    # kv["k"]: [L, B, HKV, S, hd]
    return logits, {"layers": {"k": kv["k"], "v": kv["v"]}}


def _head(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return jax.lax.dot_general(x, head, (((2,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
