"""Shared layers and the parameter builder.

Parameters are plain pytrees (nested dicts of jnp arrays).  The
:class:`ParamBuilder` records, for every leaf it creates, a tuple of
*logical axis names* in a parallel tree — the sharding layer
(runtime/sharding.py) maps logical names to mesh axes with divisibility
fallbacks, MaxText-style, so models never hard-code mesh details.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
Axes = Tuple[Optional[str], ...]


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _scaled_normal(rng: jax.Array, shape: Tuple[int, ...], std: float,
                   dtype) -> jnp.ndarray:
    """f32 normal * std cast to ``dtype``, fused into one program so that
    only the ``dtype`` result is allocated: a published-width weight
    (e.g. [32, 3072, 8192]) never has f32 temporaries beside it."""
    return (jax.random.normal(rng, shape, jnp.float32) * std).astype(dtype)


class ParamBuilder:
    """Creates params + a parallel logical-axes tree."""

    def __init__(self, rng: jax.Array, dtype: jnp.dtype):
        self._rng = rng
        self.dtype = dtype
        self.params: Params = {}
        self.axes: Params = {}

    def _next_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _put(self, tree: Params, path: str, value) -> None:
        keys = path.split("/")
        node = tree
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = value

    def normal(self, path: str, shape: Sequence[int], axes: Axes,
               fan_in: Optional[int] = None, scale: float = 1.0) -> None:
        assert len(shape) == len(axes), (path, shape, axes)
        std = scale / math.sqrt(fan_in if fan_in else shape[-2]
                                if len(shape) >= 2 else shape[-1])
        v = _scaled_normal(self._next_rng(), tuple(shape), std, self.dtype)
        self._put(self.params, path, v)
        self._put(self.axes, path, tuple(axes))

    def zeros(self, path: str, shape: Sequence[int], axes: Axes) -> None:
        assert len(shape) == len(axes)
        self._put(self.params, path, jnp.zeros(tuple(shape), self.dtype))
        self._put(self.axes, path, tuple(axes))

    def ones(self, path: str, shape: Sequence[int], axes: Axes) -> None:
        assert len(shape) == len(axes)
        self._put(self.params, path, jnp.ones(tuple(shape), self.dtype))
        self._put(self.axes, path, tuple(axes))

    def const(self, path: str, value: jnp.ndarray, axes: Axes) -> None:
        assert value.ndim == len(axes)
        self._put(self.params, path, value.astype(self.dtype))
        self._put(self.axes, path, tuple(axes))


def dtype_of(name: str) -> jnp.dtype:
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


# ---------------------------------------------------------------------------
# Primitive layers (all take explicit params; f32 internal math)
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6
            ) -> jnp.ndarray:
    """RMSNorm with f32 *statistics* but params-dtype *application*.

    The reduction (x^2 mean) runs in f32 for accuracy; the full-size
    tensors stay in the compute dtype — the f32-residual-stream traffic
    was the dominant memory-roofline term in the §Perf analysis (each
    full-size f32 elementwise pass over [B,S,D] costs 2x its bf16
    counterpart, and there were hundreds per step)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(var + eps).astype(x.dtype)
    w1 = (1.0 + w.astype(jnp.float32)).astype(x.dtype)
    return x * scale * w1


def dense(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x [..., D] @ w [D, F] in the params dtype, f32 accumulation."""
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


def mlp(x: jnp.ndarray, p: Params, kind: str) -> jnp.ndarray:
    if kind == "swiglu":
        gate = jax.nn.silu(dense(x, p["w1"]).astype(jnp.float32))
        up = dense(x, p["w3"]).astype(jnp.float32)
        return dense((gate * up).astype(x.dtype), p["w2"])
    if kind == "relu2":  # nemotron squared-ReLU
        h = jax.nn.relu(dense(x, p["w1"]).astype(jnp.float32)) ** 2
        return dense(h.astype(x.dtype), p["w2"])
    if kind == "gelu":
        h = jax.nn.gelu(dense(x, p["w1"]).astype(jnp.float32))
        return dense(h.astype(x.dtype), p["w2"])
    raise ValueError(kind)


def mlp_params(b: ParamBuilder, prefix: str, n_layers: int, d: int, f: int,
               kind: str) -> None:
    shp, ax = ([n_layers, d, f], ("layers", "embed", "ffn"))
    b.normal(f"{prefix}/w1", shp, ax, fan_in=d)
    if kind == "swiglu":
        b.normal(f"{prefix}/w3", shp, ax, fan_in=d)
    b.normal(f"{prefix}/w2", [n_layers, f, d], ("layers", "ffn", "embed"),
             fan_in=f)


def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float = 10000.0) -> jnp.ndarray:
    """Rotary embedding.  x: [B, H, S, hd]; positions: [B, S] or [S]."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freq[None, :]
        ang = ang[None, None]                      # [1,1,S,half]
    else:
        ang = positions[:, None, :, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 z_loss: float = 1e-4) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean cross-entropy over labels >= 0 (masked), plus z-loss for
    logit drift control at scale.  logits [..., V]; labels [...] int.

    The label log-prob is extracted with a one-hot contraction rather
    than take_along_axis: under SPMD with a vocab-sharded logits tensor
    the contraction partitions cleanly (partial sums + psum over the
    vocab axis), whereas a gather on the sharded axis forces an
    all-gather of the full fp32 logits — a §Perf iteration measured in
    EXPERIMENTS.md."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    onehot = jax.nn.one_hot(labels.clip(0), logits.shape[-1],
                            dtype=jnp.float32)
    ll = jnp.einsum("...v,...v->...", lf, onehot)
    nll = lse - ll
    mask = (labels >= 0).astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    zl = z_loss * ((lse ** 2) * mask).sum() / denom
    return loss + zl, denom
