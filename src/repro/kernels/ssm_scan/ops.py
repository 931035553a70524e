"""Jit'd wrapper + HBM-traffic accounting for the fused selective scan."""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels.ssm_scan.kernel import ssm_scan_pallas
from repro.kernels.ssm_scan.ref import ssm_scan_ref


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ssm_scan(x, dt, b, c, a, d, *, block_d: int = 128,
             interpret: Optional[bool] = None):
    y, _ = ssm_scan_pallas(x, dt, b, c, a, d, block_d=block_d,
                           interpret=interpret)
    return y


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ssm_scan_with_state(x, dt, b, c, a, d, h0=None, *,
                        block_d: int = 128, interpret: Optional[bool] = None):
    """Fused scan carrying an explicit state: ``h0`` [Bt, Di, N] (zeros
    when None) in, final state out — the decode-cache form the model
    step uses.  Returns (y [Bt, S, Di], h_final [Bt, Di, N] f32)."""
    return ssm_scan_pallas(x, dt, b, c, a, d, h0=h0, block_d=block_d,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("schedule", "interpret"))
def ssm_scan_scheduled(x, dt, b, c, a, d, h0=None, *, schedule,
                       interpret: Optional[bool] = None):
    """Schedule-as-static-arg entry point: the compiled model step
    threads a committed :class:`~repro.core.schedule.SSMScanSchedule`
    (frozen, hashable) straight into the launch — a different committed
    schedule is a different executable, same schedule is a jit cache
    hit."""
    return ssm_scan_pallas(x, dt, b, c, a, d, h0=h0,
                           block_d=schedule.block_d, interpret=interpret)


def traffic_model(bt: int, seq: int, di: int, n: int,
                  elem_bytes: int = 2) -> Dict[str, float]:
    """HBM bytes of the fused kernel vs the naive materialising path —
    the quantified win recorded in EXPERIMENTS.md."""
    fused = elem_bytes * bt * seq * (3 * di + 2 * n)     # x,dt,y + b,c
    fused += 4 * di * n + 4 * di                          # A, D (f32)
    # naive: dA and dBx written+read in f32, h written+read by the
    # associative scan (~2 passes), plus the same I/O as fused
    naive = fused + 4 * bt * seq * di * n * (2 + 2 + 2)
    return {"fused_bytes": float(fused), "naive_bytes": float(naive),
            "reduction": naive / fused}


def ssm_scan_dispatched(x, dt, b, c, a, d, *, service=None,
                        interpret: Optional[bool] = None):
    """`ssm_scan` through the adaptive dispatch runtime: the channel
    block for this (Bt, S, Di, N) shape comes from the registry-backed
    top-K and each call's measured time feeds the online selector (see
    :mod:`repro.runtime.dispatch`)."""
    from repro.runtime.dispatch import get_dispatch_service
    bt, seq, di = x.shape
    n = b.shape[-1]
    svc = service if service is not None else get_dispatch_service()
    problem = {"bt": bt, "seq": seq, "di": di, "n": n}
    with svc.measure("ssm_scan", problem,
                     elem_bytes=x.dtype.itemsize) as sched:
        out = ssm_scan(x, dt, b, c, a, d, block_d=sched.block_d,
                       interpret=interpret)
        jax.block_until_ready(out)
    return out


__all__ = ["ssm_scan", "ssm_scan_with_state", "ssm_scan_scheduled",
           "ssm_scan_dispatched", "ssm_scan_ref", "traffic_model"]
