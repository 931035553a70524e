"""Fused selective-scan Pallas kernel (mamba-1) — the TPU adaptation of
the mamba CUDA kernel's insight, and the fix for the measured
falcon-mamba memory wall (EXPERIMENTS.md §Perf).

The naive JAX path materialises dA/dBx tensors of shape [Bt, S, Di, N] in
HBM (N=16 state copies of every activation, in f32): the §Roofline
baseline shows falcon-mamba train 40× memory-bound because of it.  This
kernel keeps the recurrent state h in VMEM scratch and streams
x/dt/B/C blocks once: HBM traffic drops from ~(4·N·bytes_f32) per element
to ~(4·bytes_bf16) — a ~50× reduction on the scan's memory term
(quantified in EXPERIMENTS.md).

Grid: (batch, Di/BD, S/BS) — batch and channel blocks are parallel
(independent scans); the sequence axis is sequential ("arbitrary") and
carries the state across its blocks, so the VMEM working set is bounded
by the sequence block, not the prompt length.  Inside a block the
recurrence runs over chunks of ``CHUNK`` timesteps: each chunk is one
sublane-aligned load of x/dt/B/C and one aligned store of y (16 rows
satisfy both bf16's 16-row and f32's 8-row tiling), and the steps of a
chunk are unrolled over values.  This is the one loop the thesis'
interchange machinery must keep innermost, the same conclusion as for
(ky, kx).

Layout: the state lives transposed, h^T [N, BD], so the channel block
fills the 128 lanes and the N=16 states fill sublanes (no lane padding).
B and C chunks arrive as [T, N] rows and are turned into [N, T] columns
by an identity NT matmul (exact at HIGHEST precision), the same
contraction form as attention's q·kᵀ.

The scan carries an explicit initial state and emits the final state, so
the same kernel covers training (h0 = 0, state discarded), prefill
(h0 = 0, state becomes the decode cache) and the decode step itself
(S = 1, h0 = cache) — which is what lets a committed ``SSMScanSchedule``
reach the compiled serve step.  Every length, decode's S = 1 included, is
padded to whole chunks with dt = x = 0, which leaves the state unchanged:
one unrolled chunk body then serves every length, so a prompt that fits
one chunk rounds the same whether or not it was left-padded.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

CHUNK = 16                 # timesteps per aligned load/store
STREAM_BYTES = 2 << 20     # x/dt/y bytes per sequence block (x2 buffers)


def _columns(eye: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """[T, N] -> [N, T] as ``eye @ rows.T`` on the MXU."""
    return jax.lax.dot_general(eye, rows.astype(jnp.float32),
                               (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _ssm_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                y_ref, hout_ref, h_ref, *, chunk: int, n_chunks: int):
    """One (batch, Di-block, S-block): chunked scan, state in VMEM."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)                  # [N, BD]
    dvec = d_ref[...].astype(jnp.float32)               # [1, BD]
    n = a.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
           ).astype(jnp.float32)

    def scan_chunk(ci, h):
        rows = pl.ds(pl.multiple_of(ci * chunk, chunk), chunk)
        xs = x_ref[0, rows, :].astype(jnp.float32)       # [T, BD]
        dts = dt_ref[0, rows, :].astype(jnp.float32)     # [T, BD]
        bcol = _columns(eye, b_ref[0, rows, :])          # [N, T]
        ccol = _columns(eye, c_ref[0, rows, :])          # [N, T]
        ys = []
        for i in range(chunk):
            xt = xs[i:i + 1, :]                          # [1, BD]
            dtt = dts[i:i + 1, :]
            h = jnp.exp(dtt * a) * h + (dtt * xt) * bcol[:, i:i + 1]
            ys.append(jnp.sum(h * ccol[:, i:i + 1], axis=0, keepdims=True)
                      + dvec * xt)
        y_ref[0, rows, :] = jnp.concatenate(ys, axis=0).astype(y_ref.dtype)
        return h

    # A loop even for one chunk: its body is one program for every
    # length, never fused with the code around the kernel.
    h_ref[...] = jax.lax.fori_loop(0, n_chunks, scan_chunk, h_ref[...])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        hout_ref[0] = h_ref[...]


def _seq_block(seq_p: int, chunk: int, row_bytes: int) -> int:
    """Largest whole-chunk divisor of ``seq_p`` whose x/dt/y rows fit
    :data:`STREAM_BYTES`."""
    limit = max(chunk, STREAM_BYTES // row_bytes)
    return max(bs for bs in range(chunk, min(seq_p, limit) + 1, chunk)
               if seq_p % bs == 0)


def ssm_scan_pallas(x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray,
                    c: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray, *,
                    h0: jnp.ndarray = None,
                    block_d: int = 128,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x, dt: [Bt, S, Di]; b, c: [Bt, S, N]; a: [Di, N]; d: [Di];
    h0 (optional): [Bt, Di, N] initial state (zeros when omitted).
    Returns (y [Bt, S, Di], final state [Bt, Di, N] in f32).

    ``block_d`` is clamped to the nearest divisor of Di (same policy as
    decode_attention's ``block_kv``); tuner candidates are exact
    divisors, so the clamp only fires for hand-rolled schedules.  On TPU
    the block must be a multiple of 128 lanes or all of Di."""
    bt, seq, di = x.shape
    n = b.shape[-1]
    bd = min(block_d, di)
    while di % bd:
        bd //= 2
    seq_p = -(-seq // CHUNK) * CHUNK
    if seq_p != seq:
        pad = ((0, 0), (0, seq_p - seq), (0, 0))
        x, dt, b, c = (jnp.pad(t, pad) for t in (x, dt, b, c))
    bs = _seq_block(seq_p, CHUNK,
                    bd * (2 * x.dtype.itemsize + dt.dtype.itemsize))
    h0t = (jnp.zeros((bt, n, di), jnp.float32) if h0 is None
           else jnp.swapaxes(h0.astype(jnp.float32), 1, 2))

    xd_spec = pl.BlockSpec((1, bs, bd), lambda i, j, s: (i, s, j))
    bc_spec = pl.BlockSpec((1, bs, n), lambda i, j, s: (i, s, 0))
    a_spec = pl.BlockSpec((n, bd), lambda i, j, s: (0, j))
    d_spec = pl.BlockSpec((1, bd), lambda i, j, s: (0, j))
    h_spec = pl.BlockSpec((1, n, bd), lambda i, j, s: (i, 0, j))

    interpret = resolve_interpret(interpret)
    args = (x, dt, b, c, a.T, d.reshape(1, di), h0t)
    if interpret:
        # The interpreter inlines the kernel as XLA ops; a barrier keeps
        # XLA from fusing them with the caller's, as the custom call is
        # on the chip, so results do not depend on the calling program.
        args = jax.lax.optimization_barrier(args)
    y, h_out = pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=CHUNK, n_chunks=bs // CHUNK),
        grid=(bt, di // bd, seq_p // bs),
        in_specs=[xd_spec, xd_spec, bc_spec, bc_spec, a_spec, d_spec,
                  h_spec],
        out_specs=[xd_spec, h_spec],
        out_shape=[jax.ShapeDtypeStruct((bt, seq_p, di), x.dtype),
                   jax.ShapeDtypeStruct((bt, n, di), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    if interpret:
        y, h_out = jax.lax.optimization_barrier((y, h_out))
    return y[:, :seq], jnp.swapaxes(h_out, 1, 2)
