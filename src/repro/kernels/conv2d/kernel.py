"""Pallas TPU direct-convolution kernel with a permutable grid order.

This is the hardware adaptation of the thesis' loop-interchange study
(DESIGN.md §2): the Pallas *grid* is the loop nest — a TPU core executes
grid steps sequentially, so permuting the grid axes changes block residency
and HBM↔VMEM traffic exactly as loop interchange changes cache behaviour on
Loki.  The four block axes (oc, ic, y, x) are permutable; the kernel loops
(ky, kx) run *in-kernel*, unrolled — the thesis' own conclusion (kernel
loops make bad outer loops: trip counts of 1–11 and no parallelism).

Partial sums (thesis §3.3): the float32 accumulator lives in a VMEM scratch
block that is zeroed when the reduction axis (ic) starts and flushed to the
output block when it finishes.  With ``ic`` innermost this is the classic
revisiting-accumulation pattern; non-innermost reduction orders are accepted
(part of the search space, exact in interpret mode) but cost extra
flush/refill traffic on hardware — which the cost model charges them for.

The MXU mapping: each (ky, kx) tap is a [BOC, BIC] x [BIC, BY*BX] matmul
(`jax.lax.dot_general` contracting IC), so systolic utilisation follows the
(oc, ic) block alignment to 128.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

GRID_AXES: Tuple[str, ...] = ("oc", "ic", "y", "x")


def _block_contribution(img_ref, wgt_ref, *, kh, kw, by, bx, y_pos, x_pos):
    """float32 contribution of one (oc, ic, y, x) block: sum over the
    in-kernel (ky, kx) taps of a [BOC,BIC] x [BIC,BY*BX] MXU matmul."""
    y0 = pl.program_id(y_pos) * by
    x0 = pl.program_id(x_pos) * bx
    boc, bic = wgt_ref.shape[0], wgt_ref.shape[1]
    acc = jnp.zeros((boc, by, bx), jnp.float32)
    for ky in range(kh):
        for kx in range(kw):
            patch = img_ref[:, pl.dslice(y0 + ky, by),
                            pl.dslice(x0 + kx, bx)]           # [BIC,BY,BX]
            patch2 = patch.reshape(bic, by * bx)
            tap = wgt_ref[:, :, ky, kx]                        # [BOC,BIC]
            acc += jax.lax.dot_general(
                tap, patch2, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).reshape(boc, by, bx)
    return acc


def _conv_kernel_scratch(img_ref, wgt_ref, out_ref, acc_ref, *,
                         kh: int, kw: int, by: int, bx: int,
                         ic_pos: int, y_pos: int, x_pos: int, n_ic: int):
    """Fast path (reduction axis innermost): VMEM scratch partial sums
    (thesis §3.3) — zero at ic==0, accumulate, flush once at ic==n-1."""
    ic_idx = pl.program_id(ic_pos)

    @pl.when(ic_idx == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _block_contribution(img_ref, wgt_ref, kh=kh, kw=kw,
                                        by=by, bx=bx, y_pos=y_pos,
                                        x_pos=x_pos)

    @pl.when(ic_idx == n_ic - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _conv_kernel_rmw(img_ref, wgt_ref, out_ref, *,
                     kh: int, kw: int, by: int, bx: int,
                     ic_pos: int, y_pos: int, x_pos: int, n_ic: int):
    """General path (any grid order): accumulate through the output block.
    Exact in interpret mode; on hardware each revisit is an HBM round-trip
    — the flush/refetch penalty the thesis' partial-sums analysis (and our
    cost model) charges reduction-outer loop orders."""
    ic_idx = pl.program_id(ic_pos)
    contrib = _block_contribution(img_ref, wgt_ref, kh=kh, kw=kw, by=by,
                                  bx=bx, y_pos=y_pos, x_pos=x_pos)

    @pl.when(ic_idx == 0)
    def _init():
        out_ref[...] = contrib.astype(out_ref.dtype)

    @pl.when(ic_idx != 0)
    def _accum():
        out_ref[...] = (out_ref[...].astype(jnp.float32)
                        + contrib).astype(out_ref.dtype)


def conv2d_pallas(img: jnp.ndarray, wgt: jnp.ndarray, *,
                  block: Dict[str, int],
                  grid_order: Sequence[str] = ("oc", "y", "x", "ic"),
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Direct conv via pallas_call.

    img: [N, IC, H+KH-1, W+KW-1]; wgt: [OC, IC, KH, KW].
    ``block``: {"oc","ic","y","x"} block sizes (must divide the dims).
    ``grid_order``: permutation of GRID_AXES, outermost -> innermost (the
    last grid dimension iterates fastest, matching TPU semantics).
    The batch dim N is an implicit outermost grid axis.
    """
    n, ic, h2, w2 = img.shape
    oc, ic2, kh, kw = wgt.shape
    assert ic == ic2
    h, w = h2 - kh + 1, w2 - kw + 1
    boc, bic = block["oc"], block["ic"]
    by, bx = block["y"], block["x"]
    assert oc % boc == 0 and ic % bic == 0 and h % by == 0 and w % bx == 0, (
        f"blocks {block} must divide dims oc={oc} ic={ic} h={h} w={w}")
    assert sorted(grid_order) == sorted(GRID_AXES), grid_order

    trips = {"oc": oc // boc, "ic": ic // bic, "y": h // by, "x": w // bx}
    # Grid position of each named axis; batch occupies position 0.
    pos = {a: 1 + i for i, a in enumerate(grid_order)}
    grid = (n,) + tuple(trips[a] for a in grid_order)

    def axis(gidx, a):
        return gidx[pos[a] - 1]

    def img_index(b, *gidx):
        return (b, axis(gidx, "ic"), 0, 0)

    def wgt_index(b, *gidx):
        return (axis(gidx, "oc"), axis(gidx, "ic"), 0, 0)

    def out_index(b, *gidx):
        return (b, axis(gidx, "oc"), axis(gidx, "y"), axis(gidx, "x"))

    common = dict(kh=kh, kw=kw, by=by, bx=bx, ic_pos=pos["ic"],
                  y_pos=pos["y"], x_pos=pos["x"], n_ic=trips["ic"])
    # Scratch partial sums are only well-defined when no output-indexing
    # axis iterates inside the reduction axis (canonically: ic innermost).
    out_axes_after_ic = [a for a in grid_order[pos["ic"]:]
                         if a in ("oc", "y", "x")]
    use_scratch = not out_axes_after_ic

    in_specs = [
        # Full-spatial img block stays VMEM-resident; the kernel slices
        # the (y,x) window dynamically (halo reuse for free).
        pl.BlockSpec((None, bic, h2, w2), img_index),
        pl.BlockSpec((boc, bic, kh, kw), wgt_index),
    ]
    out_spec = pl.BlockSpec((None, boc, by, bx), out_index)
    out_shape = jax.ShapeDtypeStruct((n, oc, h, w), img.dtype)

    if use_scratch:
        return pl.pallas_call(
            functools.partial(_conv_kernel_scratch, **common),
            grid=grid, in_specs=in_specs, out_specs=out_spec,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((boc, by, bx), jnp.float32)],
            interpret=resolve_interpret(interpret),
        )(img, wgt)
    return pl.pallas_call(
        functools.partial(_conv_kernel_rmw, **common),
        grid=grid, in_specs=in_specs, out_specs=out_spec,
        out_shape=out_shape, interpret=resolve_interpret(interpret),
    )(img, wgt)
