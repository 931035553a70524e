"""Pallas TPU tiled matmul with permutable grid order and a resident-RHS
("tiles-for-L2") mode.

The thesis' loop-interchange space projects onto matmul as the 3! orderings
of the (m, n, k) block loops; its tiles-for-L2 trade (§6.3 — give up compute
tiles to hold a bigger unified cache) projects onto the VMEM budget split:
``resident_rhs=True`` pins the whole RHS (the weights of an LM layer) in
VMEM so it is DMA'd exactly once, at the price of smaller streaming blocks
for the LHS/output.  The tuner decides per layer shape which side of the
trade wins — the same decision Fig 6.3 makes per layer.

Accumulation is float32 in VMEM scratch when k is innermost (partial sums,
thesis §3.3), read-modify-write through the output block otherwise (legal
in interpret mode; charged by the cost model on hardware).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

GRID_AXES: Tuple[str, ...] = ("m", "n", "k")


def _mm_scratch_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_pos, n_k):
    k_idx = pl.program_id(k_pos)

    @pl.when(k_idx == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_rmw_kernel(a_ref, b_ref, o_ref, *, k_pos, n_k):
    k_idx = pl.program_id(k_pos)
    contrib = jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = contrib.astype(o_ref.dtype)

    @pl.when(k_idx != 0)
    def _accum():
        o_ref[...] = (o_ref[...].astype(jnp.float32)
                      + contrib).astype(o_ref.dtype)


def _mm_resident_kernel(a_ref, b_ref, o_ref, *, bk: int, n_k: int):
    """RHS fully resident in VMEM: grid is (m, n) only and the k loop runs
    in-kernel over slices of the resident B panel (one DMA for all of B)."""
    bn = b_ref.shape[1]
    acc = jnp.zeros((a_ref.shape[0], bn), jnp.float32)

    def body(i, acc):
        a_blk = a_ref[:, pl.dslice(i * bk, bk)]
        b_blk = b_ref[pl.dslice(i * bk, bk), :]
        return acc + jax.lax.dot_general(
            a_blk, b_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, n_k, body, acc)
    o_ref[...] = acc.astype(o_ref.dtype)


def matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, *,
                  block: Dict[str, int],
                  grid_order: Sequence[str] = ("m", "n", "k"),
                  resident_rhs: bool = False,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """C[m,n] = A[m,k] @ B[k,n] with explicit BlockSpec VMEM tiling."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = block["m"], block["n"], block["k"]
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (block, a.shape,
                                                         b.shape)

    if resident_rhs:
        grid = (m // bm, n // bn)
        return pl.pallas_call(
            functools.partial(_mm_resident_kernel, bk=bk, n_k=k // bk),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                pl.BlockSpec((k, bn), lambda i, j: (0, j)),  # full-K panel
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
            interpret=resolve_interpret(interpret),
        )(a, b)

    assert sorted(grid_order) == sorted(GRID_AXES), grid_order
    trips = {"m": m // bm, "n": n // bn, "k": k // bk}
    pos = {ax: i for i, ax in enumerate(grid_order)}
    grid = tuple(trips[ax] for ax in grid_order)

    def axis(gidx, ax):
        return gidx[pos[ax]]

    a_spec = pl.BlockSpec((bm, bk), lambda *g: (axis(g, "m"), axis(g, "k")))
    b_spec = pl.BlockSpec((bk, bn), lambda *g: (axis(g, "k"), axis(g, "n")))
    o_spec = pl.BlockSpec((bm, bn), lambda *g: (axis(g, "m"), axis(g, "n")))
    out_shape = jax.ShapeDtypeStruct((m, n), a.dtype)

    k_innermost = grid_order[-1] == "k"
    if k_innermost:
        return pl.pallas_call(
            functools.partial(_mm_scratch_kernel, k_pos=pos["k"],
                              n_k=trips["k"]),
            grid=grid, in_specs=[a_spec, b_spec], out_specs=o_spec,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=resolve_interpret(interpret),
        )(a, b)
    return pl.pallas_call(
        functools.partial(_mm_rmw_kernel, k_pos=pos["k"], n_k=trips["k"]),
        grid=grid, in_specs=[a_spec, b_spec], out_specs=o_spec,
        out_shape=out_shape, interpret=resolve_interpret(interpret),
    )(a, b)
