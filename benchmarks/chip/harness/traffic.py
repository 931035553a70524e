"""The one general traffic generator: an open loop of requests, read from
a mix's parameter file (``traffic/<name>.json``).

A mix file holds::

    {"kind": "open_loop",
     "knee_rps": 1.5,                       # measured once, by a sweep
     "phases": [{"seconds": 6.5, "rate_x_knee": 0.3},
                {"seconds": 1.5, "rate_x_knee": 2.5}],
     "prompt_len": {"median": 64, "sigma": 0.9, "min": 16, "max": 256},
     "output_len": {"median": 32, "sigma": 0.8, "min": 8, "max": 128}}

Arrivals are an inhomogeneous Poisson process whose rate cycles through
``phases`` from a seeded phase offset; one phase is a steady Poisson
stream.  Lengths are lognormal, rounded and clipped.

Every key the generator does not read is refused (``check_mix``), so a
mix that asks for a feature the generator lacks fails at once instead of
running without it; a later mix adds the feature here with its key.

Every seed gets the same work: the number of requests, the multiset of
prompt and output lengths and the multiset of unit-rate inter-arrival
gaps are the stratified quantiles of their distributions, fixed by the
mix and the window.  The seed permutes their order, draws the phase
offset and draws every token id.  So two seeds differ in arrival order,
burst placement and content, never in the amount of work offered.

A mix above the engine's capacity serves only the first part of its
queue inside the window, and there the order decides how much work is
served: the same order serves the same work, another order does not.
Such a mix sets ``"schedule_seed"``: the arrival schedule (the phase
offset, the gaps and the lengths, in their order) is then drawn from it,
the same for every run, and the run's seed draws only the token ids.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request of the open loop: due ``due_s`` after the window opens."""

    due_s: float
    prompt: np.ndarray        # [prompt_len] int32 token ids
    max_new_tokens: int


MIX_KEYS = {"kind", "knee_rps", "phases", "prompt_len", "output_len", "why",
            "schedule_seed"}
OPTIONAL_KEYS = {"why", "schedule_seed"}
PHASE_KEYS = {"seconds", "rate_x_knee"}
LENGTH_KEYS = {"median", "sigma", "min", "max"}


def check_mix(mix: Dict[str, Any]) -> None:
    """Raise on a mix the generator cannot honour: another ``kind``, or
    any key it does not read."""
    if mix.get("kind") != "open_loop":
        raise ValueError(f"unknown traffic kind {mix.get('kind')!r}")
    parts = [("mix", mix, MIX_KEYS)]
    parts += [(f"phases[{i}]", p, PHASE_KEYS)
              for i, p in enumerate(mix["phases"])]
    parts += [(k, mix[k], LENGTH_KEYS) for k in ("prompt_len", "output_len")]
    for where, part, known in parts:
        unknown = sorted(set(part) - known)
        if unknown:
            raise ValueError(f"traffic {where}: the generator does not read "
                             f"{unknown}")
        missing = sorted(known - set(part) - OPTIONAL_KEYS)
        if missing:
            raise ValueError(f"traffic {where}: missing {missing}")


def _lognormal_quantiles(n: int, dist: Dict[str, Any]) -> np.ndarray:
    """``n`` stratified quantiles of a clipped lognormal, as ints."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def _rate_fn(mix: Dict[str, Any], offset: float):
    """Rate (requests/s) at window time t, cycling through the phases."""
    knee = float(mix["knee_rps"])
    phases = [(float(p["seconds"]), float(p["rate_x_knee"]) * knee)
              for p in mix["phases"]]
    period = sum(s for s, _ in phases)

    def rate(t: np.ndarray) -> np.ndarray:
        u = np.mod(t + offset, period)
        out = np.zeros_like(u)
        lo = 0.0
        for s, r in phases:
            out = np.where((u >= lo) & (u < lo + s), r, out)
            lo += s
        return out
    return rate, period


def offered_requests(mix: Dict[str, Any], seconds: float) -> float:
    """Expected arrivals in a window (independent of the phase offset
    when the window is a whole number of phase periods)."""
    rate, period = _rate_fn(mix, 0.0)
    grid = np.linspace(0.0, seconds, int(seconds * 1000) + 1)
    return float(np.trapezoid(rate(grid), grid))


def generate(mix: Dict[str, Any], *, vocab_size: int, seconds: float,
             seed: int, rate_scale: float = 1.0) -> List[Arrival]:
    """The window's arrivals in due order (see the module docstring).

    ``rate_scale`` multiplies every phase's rate; the knee sweep uses it,
    a cell's runs never do."""
    check_mix(mix)
    rng = np.random.default_rng(seed)
    sched = (np.random.default_rng(int(mix["schedule_seed"]))
             if "schedule_seed" in mix else rng)
    rate, period = _rate_fn(mix, 0.0)
    offset = float(sched.uniform(0.0, period)) if len(mix["phases"]) > 1 else 0.0
    rate, _ = _rate_fn(mix, offset)
    dt = 1e-3
    grid = np.arange(0.0, seconds + dt, dt)
    lam = np.concatenate([[0.0], np.cumsum(rate(grid[:-1]) * rate_scale * dt)])
    total = float(lam[-1])
    n = int(math.floor(total))
    if n < 1:
        raise ValueError(f"the mix offers {total:.2f} requests in "
                         f"{seconds} s; a window needs at least one")
    # Unit-rate gaps: stratified exponential quantiles in seeded order,
    # scaled so that the last arrival lands one mean gap before the end.
    unit = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    unit = sched.permutation(unit)
    u = np.cumsum(unit) * (total / (unit.sum() + unit.mean()))
    due = np.interp(u, lam, grid)
    prompt_lens = sched.permutation(_lognormal_quantiles(n, mix["prompt_len"]))
    output_lens = sched.permutation(_lognormal_quantiles(n, mix["output_len"]))
    out = []
    for t, p, o in zip(due, prompt_lens, output_lens):
        ids = rng.integers(1, vocab_size, int(p), dtype=np.int64)
        out.append(Arrival(float(t), ids.astype(np.int32), int(o)))
    return out
