"""Plain reference and seeded weights for ``phi3-mini-3.8b.json``.

The reference is the Phi-3 decoder as its ``config.json`` and the paper
(arXiv:2404.14219) describe it, in straightforward ``jax.numpy``:
token embedding, then per layer RMSNorm, multi-head attention with
rotary embeddings (rotate-half form, ``rope_theta``) under a causal
mask, a residual add, RMSNorm, a SwiGLU MLP and a residual add; a final
RMSNorm and an untied output head.  ``sliding_window`` (2047) never
binds at the lengths a cell serves (at most 768 positions).

It imports nothing of the program under test.  It does know the
program's parameter layout, because the benchmark makes the weights and
hands the same arrays to both: ``make_params`` builds them in that
layout, on the device, from the seed.  The program stores each RMSNorm
gain as an offset from one (``gain = 1 + w``); the reference applies it
so.

``mode="f32"`` computes in float32 with every matrix product at
``Precision.HIGHEST``.  ``mode="int8"`` is the control: every projection
quantized to int8 (weights per output channel, activations per token,
symmetric), accumulated in int32 and rescaled.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(spec: Dict[str, Any]):
    d = int(spec["hidden_size"])
    h = int(spec["num_attention_heads"])
    return (int(spec["num_hidden_layers"]), d, h,
            int(spec["num_key_value_heads"]), d // h,
            int(spec["intermediate_size"]), int(spec["vocab_size"]))


def program_fields(spec: Dict[str, Any]) -> Dict[str, Any]:
    """The program's model-configuration fields for this file."""
    n_layers, d, h, kv, hd, f, v = _dims(spec)
    return dict(family="dense", n_layers=n_layers, d_model=d, n_heads=h,
                n_kv_heads=kv, head_dim=hd, d_ff=f, vocab_size=v,
                mlp_type="swiglu", rope_theta=float(spec["rope_theta"]),
                norm_eps=float(spec["rms_norm_eps"]), dtype="bfloat16",
                tie_embeddings=bool(spec["tie_word_embeddings"]),
                max_seq_len=int(spec["max_position_embeddings"]))


def matmul_params(spec: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix product per token (every
    weight but the embedding table, which is a lookup)."""
    n_layers, d, h, kv, hd, f, v = _dims(spec)
    return n_layers * (d * hd * (h + 2 * kv) + h * hd * d + 3 * d * f) + d * v


@functools.partial(jax.jit, static_argnums=0)
def _init(dims, key):
    n_layers, d, h, kv, hd, f, v = dims
    keys = iter(jax.random.split(key, 16))
    bf16 = jnp.bfloat16

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(bf16)

    def stacked(shape, std):
        # One layer at a time, so no float32 copy of a whole stack exists.
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape, jnp.float32)
                       * std).astype(bf16),
            jax.random.split(next(keys), n_layers))

    return {
        "embed": normal((v, d), 1.0),
        "layers": {
            "ln1": stacked((d,), 0.1),
            "attn": {"wq": stacked((d, h * hd), d ** -0.5),
                     "wk": stacked((d, kv * hd), d ** -0.5),
                     "wv": stacked((d, kv * hd), d ** -0.5),
                     "wo": stacked((h * hd, d), (h * hd) ** -0.5)},
            "ln2": stacked((d,), 0.1),
            "mlp": {"w1": stacked((d, f), d ** -0.5),
                    "w3": stacked((d, f), d ** -0.5),
                    "w2": stacked((f, d), f ** -0.5)},
        },
        "final_norm": normal((d,), 0.1),
        "lm_head": normal((d, v), d ** -0.5),
    }


def make_params(spec: Dict[str, Any], seed: int):
    """bf16 weights in the program's layout, made on the device in one
    jitted call from ``seed``."""
    key = int(np.random.default_rng(seed).integers(2 ** 32))
    return _init(_dims(spec), jax.random.key(key))


def _mm(x, w, mode):
    """x [T, K] @ w [K, N] in the reference's precision."""
    w = w.astype(jnp.float32)
    if mode == "f32":
        return jnp.dot(x, w, precision=HIGHEST)
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-30) / 127.0
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=1), 1e-30) / 127.0
    qw = jnp.clip(jnp.rint(w / sw), -127, 127).astype(jnp.int8)
    qx = jnp.clip(jnp.rint(x / sx[:, None]), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot(qx, qw, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx[:, None] * sw[None, :]


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """x [T, H, D]: rotate-half rotary embedding at positions 0..T-1."""
    t, _, dim = x.shape
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    half = dim // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta", "mode"))
def _forward(params, tokens, *, dims, eps, theta, mode):
    n_layers, d, h, kv, hd, f, v = dims
    t = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, lp):
        a = _rms(x, lp["ln1"], eps)
        q = _rope(_mm(a, lp["attn"]["wq"], mode).reshape(t, h, hd), theta)
        k = _rope(_mm(a, lp["attn"]["wk"], mode).reshape(t, kv, hd), theta)
        val = _mm(a, lp["attn"]["wv"], mode).reshape(t, kv, hd)
        k = jnp.repeat(k, h // kv, axis=1)
        val = jnp.repeat(val, h // kv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / hd ** 0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        ctx = jnp.einsum("hqk,khd->qhd", p, val, precision=HIGHEST)
        x = x + _mm(ctx.reshape(t, h * hd), lp["attn"]["wo"], mode)
        m = _rms(x, lp["ln2"], eps)
        g = jax.nn.silu(_mm(m, lp["mlp"]["w1"], mode))
        u = _mm(m, lp["mlp"]["w3"], mode)
        return x + _mm(g * u, lp["mlp"]["w2"], mode), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _mm(_rms(x, params["final_norm"], eps), params["lm_head"], mode)


def reference_logits(params, spec: Dict[str, Any], tokens, *,
                     mode: str = "f32"):
    """Logits [T, vocab] (float32, on the device) at every position of
    ``tokens`` ([T] int32), each from the tokens up to it."""
    return _forward(params, jnp.asarray(tokens, jnp.int32),
                    dims=_dims(spec), eps=float(spec["rms_norm_eps"]),
                    theta=float(spec["rope_theta"]), mode=mode)
