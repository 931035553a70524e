"""Plain reference and seeded weights for ``falcon-mamba-7b.json``.

The reference is the Mamba-1 language model that falcon-mamba-7b's
``config.json`` and paper (arXiv:2410.05355) describe, in
straightforward ``jax.numpy``: token embedding, then per layer RMSNorm
and the selective-state-space mixer (input projection split into x and
the gate z, a depthwise causal convolution of width ``conv_kernel`` with
bias, SiLU, the x projection to dt, B and C, dt through its projection,
bias and softplus, the recurrence h_t = exp(dt_t A) h_{t-1} +
dt_t B_t x_t taken one step at a time, y_t = C_t h_t + D x_t, gated by
SiLU(z), the output projection) and a residual add; a final RMSNorm and
an untied output head.  The published model also normalises dt, B and C
with RMS norms inside the mixer; the program under test has no such
norms, the configuration file states that departure, and this reference
follows the configuration as it is run.

It imports nothing of the program.  It knows the program's parameter
layout, because the benchmark makes the weights and hands the same
arrays to both; RMSNorm gains are stored as offsets from one.

``mode="f32"`` computes in float32 with every matrix product at
``Precision.HIGHEST``; ``mode="int8"`` is the control, with every
projection quantized to int8 (weights per output channel, activations
per token, symmetric) and the recurrence kept in float32.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(spec: Dict[str, Any]):
    return (int(spec["num_hidden_layers"]), int(spec["hidden_size"]),
            int(spec["intermediate_size"]), int(spec["state_size"]),
            int(spec["conv_kernel"]), int(spec["time_step_rank"]),
            int(spec["vocab_size"]))


def program_fields(spec: Dict[str, Any]) -> Dict[str, Any]:
    """The program's model-configuration fields for this file."""
    n_layers, d, di, n, k, r, v = _dims(spec)
    if di % d:
        raise ValueError("intermediate_size must be a multiple of "
                         "hidden_size for the program's ssm_expand")
    return dict(family="ssm", n_layers=n_layers, d_model=d, n_heads=1,
                n_kv_heads=1, d_ff=0, vocab_size=v, ssm_state=n,
                ssm_conv=k, ssm_expand=di // d, dt_rank=r,
                norm_eps=float(spec["layer_norm_epsilon"]),
                dtype="bfloat16",
                tie_embeddings=bool(spec["tie_word_embeddings"]))


def matmul_params(spec: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix product per token."""
    n_layers, d, di, n, k, r, v = _dims(spec)
    return n_layers * (2 * d * di + di * (r + 2 * n) + r * di + di * d) + d * v


@functools.partial(jax.jit, static_argnums=0)
def _init(dims, key):
    n_layers, d, di, n, k, r, v = dims
    keys = iter(jax.random.split(key, 16))
    bf16 = jnp.bfloat16

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(bf16)

    def stacked(fn):
        return jax.lax.map(fn, jax.random.split(next(keys), n_layers))

    def gauss(shape, std):
        return stacked(lambda kk: (jax.random.normal(kk, shape, jnp.float32)
                                   * std).astype(bf16))

    def dt_bias(kk):
        # Mamba's initialisation: softplus(bias) log-uniform in
        # [1e-3, 1e-1], stored as its inverse softplus.
        u = jax.random.uniform(kk, (di,), jnp.float32)
        dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(bf16)

    a_log = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
    return {
        "embed": normal((v, d), 1.0),
        "layers": {
            "ln1": gauss((d,), 0.1),
            "mamba": {
                "in_proj": gauss((d, 2 * di), d ** -0.5),
                "conv_w": gauss((di, k), k ** -0.5),
                "conv_b": gauss((di,), 0.1),
                "x_proj": gauss((di, r + 2 * n), di ** -0.5),
                "dt_proj": gauss((r, di), r ** -0.5),
                "dt_bias": stacked(dt_bias),
                "A_log": jnp.broadcast_to(a_log, (n_layers, di, n)
                                          ).astype(bf16),
                "D": jnp.ones((n_layers, di), bf16),
                "out_proj": gauss((di, d), di ** -0.5),
            },
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


@functools.partial(jax.jit, static_argnames=("dims", "eps", "mode"))
def _forward(params, tokens, *, dims, eps, mode):
    n_layers, d, di, n, k, r, v = dims
    t = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, lp):
        m = lp["mamba"]
        xz = _mm(_rms(x, lp["ln1"], eps), m["in_proj"], mode)
        xin, z = xz[:, :di], xz[:, di:]
        padded = jnp.concatenate([jnp.zeros((k - 1, di)), xin], axis=0)
        w = m["conv_w"].astype(jnp.float32)                  # [di, k]
        conv = sum(padded[i:i + t] * w[:, i] for i in range(k))
        xc = jax.nn.silu(conv + m["conv_b"].astype(jnp.float32))
        dbc = _mm(xc, m["x_proj"], mode)
        dt = jax.nn.softplus(_mm(dbc[:, :r], m["dt_proj"], mode)
                             + m["dt_bias"].astype(jnp.float32))
        bm, cm = dbc[:, r:r + n], dbc[:, r + n:]
        a = -jnp.exp(m["A_log"].astype(jnp.float32))         # [di, n]

        def step(hs, inp):
            dt_t, b_t, c_t, x_t = inp
            hs = jnp.exp(dt_t[:, None] * a) * hs \
                + (dt_t * x_t)[:, None] * b_t[None, :]
            return hs, jnp.dot(hs, c_t, precision=HIGHEST)

        _, y = jax.lax.scan(step, jnp.zeros((di, n)), (dt, bm, cm, xc))
        y = (y + m["D"].astype(jnp.float32) * xc) * jax.nn.silu(z)
        return x + _mm(y, m["out_proj"], mode), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _mm(_rms(x, params["final_norm"], eps), params["lm_head"], mode)


def reference_logits(params, spec: Dict[str, Any], tokens, *,
                     mode: str = "f32"):
    """Logits [T, vocab] (float32, on the device) at every position of
    ``tokens`` ([T] int32), each from the tokens up to it."""
    return _forward(params, jnp.asarray(tokens, jnp.int32),
                    dims=_dims(spec), eps=float(spec["layer_norm_epsilon"]),
                    mode=mode)
