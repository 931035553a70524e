"""Design-space search (thesis Ch. 4–5) over loop orders and schedules.

Offline part of the thesis' methodology: sweep the permutation space with
the fast cost model, derive *static candidates* (single permutations that
are near-optimal across a layer design space — Fig 4.7/4.8), *top-K
combinations* (pairs selected per layer by quick profiling — Fig 5.3),
*random-sampling bounds* (Fig 5.4), and locality-aware *neighbour-swap
search* on the permutohedron (the thesis' proposed future work, §7.2,
enabled by the Hamiltonian index).

The TPU half tunes actual kernel schedules: grid order × block shapes ×
resident-weights, ranked by the TPU cost model; the adaptive runtime
(core/adaptive.py) then micro-profiles the top few.

Everything here consumes the *batch* cost-model entry points
(``simulate_batch`` / ``conv_schedule_cost_batch`` /
``matmul_schedule_cost_batch``): one call scores an entire candidate
space as arrays, bit-identical to the scalar model.  The process pool
survives only behind :func:`exact_sweep`, the trace-driven validator.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import itertools
import math
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import cost_model as cm
from repro.core import ecm as ecm_model
from repro.core import permutations as perms
from repro.core import registry as reg
from repro.core.loopnest import ConvLayer
from repro.core.schedule import (ConvSchedule, DecodeAttentionSchedule,
                                 FlashAttentionSchedule, MatmulSchedule,
                                 SparseConvSchedule, SSMScanSchedule)

Perm = Tuple[int, ...]
ALL_PERMS: Tuple[Perm, ...] = tuple(itertools.permutations(range(6)))


# ---------------------------------------------------------------------------
# Sweeps and signatures (thesis Ch. 4)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SweepResult:
    """720-permutation sweep of one layer: the thesis' 'signature'."""
    layer: ConvLayer
    cycles: np.ndarray      # [720], indexed by lex order (ALL_PERMS)
    l1_misses: np.ndarray
    l2_misses: np.ndarray

    def signature(self, metric: str = "cycles",
                  indexing: str = "hamiltonian") -> np.ndarray:
        """Metric reordered by an indexing function (Fig 4.2)."""
        vals = {"cycles": self.cycles, "l1": self.l1_misses,
                "l2": self.l2_misses}[metric]
        order = np.empty(len(ALL_PERMS), dtype=np.int64)
        for i, p in enumerate(ALL_PERMS):
            if indexing == "hamiltonian":
                order[perms.hamiltonian_index(p)] = i
            elif indexing == "lex":
                order[perms.lex_index(p)] = i
            elif indexing == "revlex":
                order[perms.revlex_index(p)] = i
            else:
                raise ValueError(indexing)
        return vals[order]


def sweep_layer(layer: ConvLayer,
                machine: cm.MachineModel = cm.MachineModel(),
                threads: int = 1) -> SweepResult:
    """All-720 signature through the vectorized batch engine: one
    :func:`repro.core.cost_model.simulate_batch` call scores the whole
    permutation space (bit-identical to per-perm ``simulate`` calls)."""
    batch = cm.simulate_batch(layer, ALL_PERMS, machine, threads)
    l1 = machine.levels[0].name
    l2 = machine.levels[1].name
    return SweepResult(layer=layer, cycles=batch.cycles,
                       l1_misses=batch.misses[l1],
                       l2_misses=batch.misses[l2])


def batch_perm_scorer(layer: ConvLayer,
                      machine: cm.MachineModel = cm.MachineModel(),
                      threads: int = 1,
                      ) -> Callable[[Sequence[Perm]], np.ndarray]:
    """A many-perms-at-once cycles scorer for the permutohedron searches:
    ``scorer(perms) -> float64 [len(perms)]``."""
    def score_batch(candidates: Sequence[Perm]) -> np.ndarray:
        """Cycles for each candidate via one simulate_batch call."""
        return cm.simulate_batch(layer, list(candidates), machine,
                                 threads).cycles
    return score_batch


def speedup_matrix(sweeps: Sequence[SweepResult],
                   metric: str = "cycles") -> np.ndarray:
    """S[l, p] = best(layer l) / metric(layer l, perm p)  (in (0, 1];
    1 = per-layer optimal).  The thesis' normalised 'speedup' measure."""
    rows = []
    for s in sweeps:
        v = s.cycles if metric == "cycles" else (
            s.l2_misses if metric == "l2" else s.l1_misses)
        v = np.maximum(v, 1e-12)
        rows.append(v.min() / v)
    return np.stack(rows)


# ---------------------------------------------------------------------------
# Static candidates (thesis §4.3, Fig 4.7/4.8)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Candidate:
    """A static permutation candidate with its design-space speedups."""

    perm: Perm
    avg_speedup: float
    worst_speedup: float
    criterion: str


def static_candidates(sweeps: Sequence[SweepResult]) -> Dict[str, Candidate]:
    """The thesis' three candidates: top average (cycles), top worst-case
    (cycles), top average (L2 misses)."""
    s_cyc = speedup_matrix(sweeps, "cycles")
    s_l2 = speedup_matrix(sweeps, "l2")
    out: Dict[str, Candidate] = {}

    avg = s_cyc.mean(axis=0)
    p = int(avg.argmax())
    out["top_average"] = Candidate(ALL_PERMS[p], float(avg[p]),
                                   float(s_cyc[:, p].min()), "cycles/avg")
    worst = s_cyc.min(axis=0)
    p = int(worst.argmax())
    out["top_worst_case"] = Candidate(ALL_PERMS[p], float(avg[p]),
                                      float(worst[p]), "cycles/worst")
    avg2 = s_l2.mean(axis=0)
    p = int(avg2.argmax())
    out["top_l2"] = Candidate(ALL_PERMS[p], float(s_cyc[:, p].mean()),
                              float(s_cyc[:, p].min()), "l2/avg")
    return out


# ---------------------------------------------------------------------------
# Top-K combinations (thesis §5.3.1, Fig 5.3)
# ---------------------------------------------------------------------------

def top_pairs(sweeps: Sequence[SweepResult], metric: str = "cycles",
              n_best: int = 5) -> List[Tuple[Perm, Perm, float, float]]:
    """Best pairs of permutations when, per layer, the better of the two is
    used (the micro-profiling pick).  Exact over all 720*719/2 pairs,
    vectorised.  Returns (perm_a, perm_b, avg_speedup, worst_speedup)."""
    S = speedup_matrix(sweeps, metric)            # [L, P]
    P = S.shape[1]
    best: List[Tuple[float, float, int, int]] = []
    for i in range(P):
        pair = np.maximum(S[:, i:i + 1], S)       # [L, P]
        avg = pair.mean(axis=0)
        avg[:i + 1] = -1.0                        # dedupe (j > i only)
        j = int(avg.argmax())
        worst = float(pair[:, j].min())
        best.append((float(avg[j]), worst, i, j))
    best.sort(reverse=True)
    return [(ALL_PERMS[i], ALL_PERMS[j], a, w) for a, w, i, j in
            best[:n_best]]


# ---------------------------------------------------------------------------
# Random sampling (thesis §5.3.2, Fig 5.4)
# ---------------------------------------------------------------------------

def sample_size_for_confidence(sweeps: Sequence[SweepResult],
                               good_threshold: float = 0.9,
                               confidence: float = 0.683,
                               metric: str = "cycles") -> int:
    """Smallest random-sample size k such that, for the *worst* layer of
    the design space, a sample of k permutations contains a >=threshold
    one with the given probability (thesis: 10 for 1 sigma, 26 for 2)."""
    S = speedup_matrix(sweeps, metric)
    n = S.shape[1]
    g_min = int((S >= good_threshold).sum(axis=1).min())
    if g_min == 0:
        return n
    for k in range(1, n + 1):
        # P(no good in k draws without replacement)
        p_none = math.prod((n - g_min - t) / (n - t) for t in range(k)
                           if n - g_min - t > 0) if k <= n - g_min else 0.0
        if 1.0 - p_none >= confidence:
            return k
    return n


def good_permutation_counts(sweeps: Sequence[SweepResult],
                            good_threshold: float = 0.9,
                            metric: str = "cycles") -> np.ndarray:
    """Per-layer count of >=threshold permutations (Fig 5.4 input)."""
    S = speedup_matrix(sweeps, metric)
    return (S >= good_threshold).sum(axis=1)


# ---------------------------------------------------------------------------
# Locality-aware search on the permutohedron (thesis §7.2 future work)
# ---------------------------------------------------------------------------

def _score_perms(score: Optional[Callable[[Perm], float]],
                 score_batch: Optional[Callable[[Sequence[Perm]],
                                                np.ndarray]],
                 candidates: Sequence[Perm]) -> List[float]:
    """Score candidates via the batch scorer when given, else per-perm."""
    if not candidates:
        return []
    if score_batch is not None:
        return [float(v) for v in score_batch(list(candidates))]
    return [score(p) for p in candidates]


def neighbor_swap_search(score: Optional[Callable[[Perm], float]],
                         start: Perm,
                         max_steps: int = 100,
                         score_batch: Optional[
                             Callable[[Sequence[Perm]], np.ndarray]] = None,
                         ) -> Tuple[Perm, float, int]:
    """Greedy descent over adjacent-transposition neighbours.  ``score`` is
    minimised (e.g. predicted cycles).  Returns (perm, score, evals).

    With ``score_batch`` (e.g. :func:`batch_perm_scorer`) each descent
    step scores its whole neighbourhood in one vectorized call; ``score``
    may then be None."""
    cur = tuple(start)
    cur_score = _score_perms(score, score_batch, [cur])[0]
    evals = 1
    for _ in range(max_steps):
        nbrs = perms.permutohedron_neighbors(cur)
        vals = _score_perms(score, score_batch, nbrs)
        evals += len(nbrs)
        best_i = min(range(len(nbrs)), key=vals.__getitem__)
        if vals[best_i] >= cur_score:
            return cur, cur_score, evals
        cur, cur_score = nbrs[best_i], vals[best_i]
    return cur, cur_score, evals


def bfs_search(score: Optional[Callable[[Perm], float]], start: Perm,
               budget: int = 60,
               score_batch: Optional[
                   Callable[[Sequence[Perm]], np.ndarray]] = None,
               ) -> Tuple[Perm, float, int]:
    """Best-first search on the permutohedron with an evaluation budget
    (the thesis' suggested BFS variant).  ``score_batch`` scores each
    expansion's unseen neighbours in one call."""
    import heapq
    start = tuple(start)
    seen = {start}
    s0 = _score_perms(score, score_batch, [start])[0]
    heap = [(s0, start)]
    best = (s0, start)
    evals = 1
    while heap and evals < budget:
        s, p = heapq.heappop(heap)
        fresh = [q for q in perms.permutohedron_neighbors(p)
                 if q not in seen][:max(budget - evals, 0)]
        seen.update(fresh)
        for q, sq in zip(fresh, _score_perms(score, score_batch, fresh)):
            evals += 1
            if sq < best[0]:
                best = (sq, q)
            heapq.heappush(heap, (sq, q))
    return best[1], best[0], evals


# ---------------------------------------------------------------------------
# TPU schedule tuning (hardware-adapted search)
# ---------------------------------------------------------------------------

def _divisors(n: int, cap: int = 1 << 30) -> List[int]:
    """All divisors of ``n`` up to ``cap``."""
    return [d for d in range(1, min(n, cap) + 1) if n % d == 0]


def _block_candidates(dim: int, targets: Sequence[int]) -> List[int]:
    """Divisors of ``dim`` closest to each MXU-friendly target."""
    divs = _divisors(dim)
    out = sorted({max(d for d in divs if d <= t) for t in targets if t >= 1})
    return out


def tune_conv(layer: ConvLayer, spec: cm.TPUSpec = cm.TPUSpec(),
              elem_bytes: int = 2, top_k: int = 5,
              ) -> List[Tuple[ConvSchedule, cm.KernelCost]]:
    """Rank (grid order x block shape) conv schedules by the TPU model.

    The whole enumeration is scored as one
    :func:`repro.core.cost_model.conv_schedule_cost_batch` array
    computation; a stable argsort over the same enumeration order keeps
    the ranking identical to the old per-candidate loop."""
    oc_c = _block_candidates(layer.oc, (32, 128, 256))
    ic_c = _block_candidates(layer.ic, (32, 128, 256))
    y_c = _block_candidates(layer.h, (4, 8, layer.h))
    x_c = _block_candidates(layer.w, (8, 16, layer.w))
    orders = list(itertools.permutations(("oc", "ic", "y", "x")))
    blocks = [{"oc": boc, "ic": bic, "y": by, "x": bx}
              for boc, bic, by, bx
              in itertools.product(oc_c, ic_c, y_c, x_c)]
    batch = cm.conv_schedule_cost_batch(layer, orders, blocks, spec,
                                        elem_bytes)
    flat = batch.time_s.reshape(-1)
    top = np.argsort(flat, kind="stable")[:top_k]
    n_b = len(blocks)
    return [(ConvSchedule.make(orders[i // n_b], blocks[i % n_b]),
             batch.cost((i // n_b, i % n_b))) for i in map(int, top)]


def tune_matmul(m: int, n: int, k: int,
                spec: cm.TPUSpec = cm.TPUSpec(), elem_bytes: int = 2,
                top_k: int = 5,
                ) -> List[Tuple[MatmulSchedule, cm.KernelCost]]:
    """Rank matmul schedules: 6 loop orders x blocks x resident-RHS (the
    kernel-level tiles-for-L2 trade, thesis §6.3), scored by one
    :func:`repro.core.cost_model.matmul_schedule_cost_batch` call."""
    m_c = _block_candidates(m, (128, 256, 512))
    n_c = _block_candidates(n, (128, 256, 512))
    k_c = _block_candidates(k, (128, 512, k))
    orders = list(itertools.permutations(("m", "n", "k")))
    blocks = list(itertools.product(m_c, n_c, k_c))
    batch = cm.matmul_schedule_cost_batch(m, n, k, blocks, orders, spec,
                                          elem_bytes)
    flat = batch.time_s.reshape(-1)       # [(order, block, resident)]
    top = np.argsort(flat, kind="stable")[:top_k]
    n_b = len(blocks)
    out: List[Tuple[MatmulSchedule, cm.KernelCost]] = []
    for i in map(int, top):
        o, rem = divmod(i, n_b * 2)
        b, resident = divmod(rem, 2)
        bm, bn, bk = blocks[b]
        sched = MatmulSchedule.make(orders[o], {"m": bm, "n": bn, "k": bk},
                                    bool(resident))
        out.append((sched, batch.cost((o, b, resident))))
    return out


# ---------------------------------------------------------------------------
# Serving-kernel schedule tuning (the remaining four families)
# ---------------------------------------------------------------------------

def tune_flash_attention(b: int, hq: int, hkv: int, s: int, d: int,
                         causal: bool = True,
                         spec: cm.TPUSpec = cm.TPUSpec(),
                         elem_bytes: int = 2, top_k: int = 5,
                         ) -> List[Tuple[FlashAttentionSchedule,
                                         cm.KernelCost]]:
    """Rank (block_q, block_kv) flash-attention schedules via one
    :func:`repro.core.cost_model.flash_attention_schedule_cost_batch`."""
    q_c = _block_candidates(s, (64, 128, 256, 512))
    kv_c = _block_candidates(s, (128, 256, 512, 1024))
    blocks = list(itertools.product(q_c, kv_c))
    batch = cm.flash_attention_schedule_cost_batch(
        b, hq, hkv, s, d, blocks, causal, spec, elem_bytes)
    top = np.argsort(batch.time_s, kind="stable")[:top_k]
    return [(FlashAttentionSchedule(*blocks[i]), batch.cost(i))
            for i in map(int, top)]


def tune_decode_attention(b: int, hq: int, hkv: int, s: int, d: int,
                          spec: cm.TPUSpec = cm.TPUSpec(),
                          elem_bytes: int = 2, top_k: int = 5,
                          pos: Optional[int] = None,
                          ) -> List[Tuple[DecodeAttentionSchedule,
                                          cm.KernelCost]]:
    """Rank KV streaming blocks for the single-token decode kernel."""
    kv_c = _block_candidates(s, (64, 128, 256, 512, 1024, 2048))
    batch = cm.decode_attention_schedule_cost_batch(
        b, hq, hkv, s, d, kv_c, pos, spec, elem_bytes)
    top = np.argsort(batch.time_s, kind="stable")[:top_k]
    return [(DecodeAttentionSchedule(kv_c[i]), batch.cost(i))
            for i in map(int, top)]


def tune_ssm_scan(bt: int, seq: int, di: int, n: int,
                  spec: cm.TPUSpec = cm.TPUSpec(),
                  elem_bytes: int = 2, top_k: int = 5,
                  ) -> List[Tuple[SSMScanSchedule, cm.KernelCost]]:
    """Rank channel blocks for the fused selective scan.  The channel
    block is the kernel's lane dimension, so on TPU it is a multiple of
    128 or all of ``di``."""
    d_c = _block_candidates(di, (128, 256, 512, di))
    batch = cm.ssm_scan_schedule_cost_batch(bt, seq, di, n, d_c, spec,
                                            elem_bytes)
    top = np.argsort(batch.time_s, kind="stable")[:top_k]
    return [(SSMScanSchedule(d_c[i]), batch.cost(i))
            for i in map(int, top)]


def tune_sparse_conv(layer: ConvLayer, density: float = 1.0,
                     spec: cm.TPUSpec = cm.TPUSpec(),
                     elem_bytes: int = 2, top_k: int = 5,
                     ) -> List[Tuple[SparseConvSchedule, cm.KernelCost]]:
    """Rank (oc, ic) skip blocks for the block-sparse conv kernel at a
    given block density."""
    oc_c = _block_candidates(layer.oc, (16, 32, 128, 256))
    ic_c = _block_candidates(layer.ic, (16, 32, 128, 256))
    blocks = [{"oc": boc, "ic": bic}
              for boc, bic in itertools.product(oc_c, ic_c)]
    batch = cm.sparse_conv_schedule_cost_batch(layer, blocks, density,
                                               1, spec, elem_bytes)
    top = np.argsort(batch.time_s, kind="stable")[:top_k]
    return [(SparseConvSchedule.make(blocks[i]), batch.cost(i))
            for i in map(int, top)]


# ---------------------------------------------------------------------------
# Cached tuning — the registry front door
# ---------------------------------------------------------------------------
#
# ``cached_tune_*`` check the persistent registry before sweeping and
# record the ranked result after, so a process (or fleet of processes)
# never pays for the same (problem, machine, cost-model) twice.  A warm
# hit performs ZERO cost-model evaluations — it deserialises the stored
# schedules and costs directly (asserted by tests/test_registry.py against
# cm.EVAL_COUNTS).

def _tune_counter(name: str):
    """A counter on the process-default metrics registry (telemetry for
    the offline tuner: warm hits, sweeps, wall time, model evals)."""
    from repro.obs.metrics import get_metrics_registry
    return get_metrics_registry().counter(
        name, help="offline-tuner sweep accounting")


def _ranked_to_value(ranked) -> Dict:
    """Registry value for a ranked (schedule, cost) list, stamped with
    the cost-model tier that produced it (roofline-style analytic)."""
    return {"schedules": [reg.schedule_to_dict(s) for s, _ in ranked],
            "costs": [reg.cost_to_dict(c) for _, c in ranked],
            "tier": "roofline"}


def _has_ranked(value: Dict, top_k: int) -> bool:
    """A record satisfies a top_k request only if it carries that many
    ranked (schedule, cost) pairs — or the *whole* enumeration
    (``complete``: small schedule spaces can have fewer candidates than
    any top_k asks for, and re-sweeping them would never help).  Records
    created purely by adaptive write-back hold a winner but no cost list
    — those must re-tune."""
    n = min(len(value.get("schedules", ())), len(value.get("costs", ())))
    if value.get("complete") and n > 0:
        return True
    return n >= top_k


def _value_to_ranked(value: Dict, top_k: Optional[int] = None):
    """Rebuild the ranked (schedule, cost) list from a registry value."""
    pairs = zip(value["schedules"][:top_k], value["costs"][:top_k])
    return [(reg.schedule_from_dict(s), reg.cost_from_dict(c))
            for s, c in pairs]


def _cached_ranked(key: reg.RegistryKey, tune: Callable[[int], List],
                   top_k: int,
                   registry: Optional[reg.TuningRegistry],
                   refresh: bool) -> List:
    """The memoisation pattern shared by every ``cached_tune_*``: return
    the stored ranking on a warm hit (zero cost-model evals), otherwise
    run ``tune(top_k)`` and persist it — preserving any run-time
    measurement already attached to the key."""
    registry = registry if registry is not None else \
        reg.TuningRegistry.default()
    prev = registry.get(key)
    rec = None if refresh else prev
    if rec is not None and _has_ranked(rec.value, top_k):
        _tune_counter("tune.warm_hits_total").inc()
        return _value_to_ranked(rec.value, top_k)
    want = max(top_k, 5)
    evals0 = cm.total_evals()
    t0 = time.perf_counter()
    ranked = tune(want)
    _tune_counter("tune.sweeps_total").inc()
    _tune_counter("tune.sweep_wall_s_total").inc(
        time.perf_counter() - t0)
    _tune_counter("tune.cost_model_evals_total").inc(
        cm.total_evals() - evals0)
    value = _ranked_to_value(ranked)
    if len(ranked) < want:
        value["complete"] = True      # the whole enumeration fits
    registry.put(reg.TuningRecord(key=key, value=value,
                                  measured=prev.measured if prev else None,
                                  source="offline"))
    return ranked[:top_k]


def cached_tune_conv(layer: ConvLayer, spec: cm.TPUSpec = cm.TPUSpec(),
                     elem_bytes: int = 2, top_k: int = 5,
                     registry: Optional[reg.TuningRegistry] = None,
                     refresh: bool = False,
                     ) -> List[Tuple[ConvSchedule, cm.KernelCost]]:
    """:func:`tune_conv` with persistent memoisation."""
    return _cached_ranked(
        reg.conv_schedule_key(layer, spec, elem_bytes),
        lambda k: tune_conv(layer, spec, elem_bytes, top_k=k),
        top_k, registry, refresh)


def cached_tune_matmul(m: int, n: int, k: int,
                       spec: cm.TPUSpec = cm.TPUSpec(),
                       elem_bytes: int = 2, top_k: int = 5,
                       registry: Optional[reg.TuningRegistry] = None,
                       refresh: bool = False,
                       ) -> List[Tuple[MatmulSchedule, cm.KernelCost]]:
    """:func:`tune_matmul` with persistent memoisation."""
    return _cached_ranked(
        reg.matmul_schedule_key(m, n, k, spec, elem_bytes),
        lambda kk: tune_matmul(m, n, k, spec, elem_bytes, top_k=kk),
        top_k, registry, refresh)


def cached_tune_flash_attention(
        b: int, hq: int, hkv: int, s: int, d: int, causal: bool = True,
        spec: cm.TPUSpec = cm.TPUSpec(), elem_bytes: int = 2,
        top_k: int = 5, registry: Optional[reg.TuningRegistry] = None,
        refresh: bool = False,
        ) -> List[Tuple[FlashAttentionSchedule, cm.KernelCost]]:
    """:func:`tune_flash_attention` with persistent memoisation."""
    return _cached_ranked(
        reg.flash_attention_schedule_key(b, hq, hkv, s, d, spec, causal,
                                         elem_bytes),
        lambda k: tune_flash_attention(b, hq, hkv, s, d, causal, spec,
                                       elem_bytes, top_k=k),
        top_k, registry, refresh)


def cached_tune_decode_attention(
        b: int, hq: int, hkv: int, s: int, d: int,
        spec: cm.TPUSpec = cm.TPUSpec(), elem_bytes: int = 2,
        top_k: int = 5, registry: Optional[reg.TuningRegistry] = None,
        refresh: bool = False,
        ) -> List[Tuple[DecodeAttentionSchedule, cm.KernelCost]]:
    """:func:`tune_decode_attention` with persistent memoisation."""
    return _cached_ranked(
        reg.decode_attention_schedule_key(b, hq, hkv, s, d, spec,
                                          elem_bytes),
        lambda k: tune_decode_attention(b, hq, hkv, s, d, spec,
                                        elem_bytes, top_k=k),
        top_k, registry, refresh)


def cached_tune_ssm_scan(
        bt: int, seq: int, di: int, n: int,
        spec: cm.TPUSpec = cm.TPUSpec(), elem_bytes: int = 2,
        top_k: int = 5, registry: Optional[reg.TuningRegistry] = None,
        refresh: bool = False,
        ) -> List[Tuple[SSMScanSchedule, cm.KernelCost]]:
    """:func:`tune_ssm_scan` with persistent memoisation."""
    return _cached_ranked(
        reg.ssm_scan_schedule_key(bt, seq, di, n, spec, elem_bytes),
        lambda k: tune_ssm_scan(bt, seq, di, n, spec, elem_bytes,
                                top_k=k),
        top_k, registry, refresh)


def cached_tune_sparse_conv(
        layer: ConvLayer, density: float = 1.0,
        spec: cm.TPUSpec = cm.TPUSpec(), elem_bytes: int = 2,
        top_k: int = 5, registry: Optional[reg.TuningRegistry] = None,
        refresh: bool = False,
        ) -> List[Tuple[SparseConvSchedule, cm.KernelCost]]:
    """:func:`tune_sparse_conv` with persistent memoisation (density
    quantised to the registry's 1/16 grid so the key space stays
    finite)."""
    density_q = reg.quantize_density(density) / 16.0
    return _cached_ranked(
        reg.sparse_conv_schedule_key(layer, density, spec, elem_bytes),
        lambda k: tune_sparse_conv(layer, density_q, spec, elem_bytes,
                                   top_k=k),
        top_k, registry, refresh)


def cached_sweep_layer(layer: ConvLayer,
                       machine: cm.MachineModel = cm.MachineModel(),
                       threads: int = 1,
                       registry: Optional[reg.TuningRegistry] = None,
                       refresh: bool = False) -> SweepResult:
    """:func:`sweep_layer` (the 720-permutation signature) memoised."""
    registry = registry if registry is not None else \
        reg.TuningRegistry.default()
    key = reg.conv_sweep_key(layer, machine, threads)
    rec = None if refresh else registry.get(key)
    if rec is not None:
        _tune_counter("tune.warm_hits_total").inc()
        v = rec.value
        return SweepResult(layer=layer,
                           cycles=np.asarray(v["cycles"]),
                           l1_misses=np.asarray(v["l1_misses"]),
                           l2_misses=np.asarray(v["l2_misses"]))
    sweep = sweep_layer(layer, machine, threads)
    registry.put(reg.TuningRecord(
        key=key,
        value={"cycles": sweep.cycles.tolist(),
               "l1_misses": sweep.l1_misses.tolist(),
               "l2_misses": sweep.l2_misses.tolist()},
        source="offline"))
    return sweep


# ---------------------------------------------------------------------------
# Multi-layer sweeps + the exact-validator pool
# ---------------------------------------------------------------------------
#
# Since the batch engine, a full 720-permutation sweep is a sub-millisecond
# array computation, so multi-layer warms run in-process: no pickling, no
# worker startup, and determinism for free (the old guarantee — parallel
# warm byte-identical to serial — now holds trivially).  The
# forkserver/spawn process pool survives only as ``exact_sweep``'s engine:
# the trace-driven validator (core/tracesim) really does cost seconds per
# permutation and still wants the fan-out.

def _cpu_only_worker() -> None:
    """Pool initializer: a worker that touches JAX gets the CPU backend,
    never the parent's accelerator (tracesim itself is pure numpy)."""
    import jax
    jax.config.update("jax_platforms", "cpu")


def _map_parallel(fn, jobs: Sequence, workers: Optional[int]) -> List:
    """Map ``fn`` over ``jobs`` preserving order.  ``workers`` None/0/1 =>
    serial; otherwise a process pool (tracesim is pure Python, so threads
    gain nothing under the GIL), degrading gracefully to threads then
    serial where the platform forbids subprocesses.

    Uses a forkserver/spawn start method, never plain fork: the parent
    has usually initialised JAX by the time a validation runs, and forking
    a multithreaded JAX process can deadlock.  Workers are pinned to the
    CPU backend before they run anything: a chip belongs to one process,
    and the parent may hold it."""
    if not workers or workers <= 1 or len(jobs) <= 1:
        return [fn(j) for j in jobs]
    import multiprocessing as mp
    methods = mp.get_all_start_methods()
    method = "forkserver" if "forkserver" in methods else "spawn"
    try:
        ctx = mp.get_context(method)
        with concurrent.futures.ProcessPoolExecutor(
                workers, mp_context=ctx,
                initializer=_cpu_only_worker) as ex:
            return list(ex.map(fn, jobs))
    except (OSError, PermissionError, concurrent.futures.process
            .BrokenProcessPool):
        try:
            with concurrent.futures.ThreadPoolExecutor(workers) as ex:
                return list(ex.map(fn, jobs))
        except OSError:
            return [fn(j) for j in jobs]


def _exact_sweep_worker(args) -> float:
    """Pool target: one trace-driven simulation, returns cycles."""
    layer, perm, machine, max_iters = args
    from repro.core import tracesim
    return float(tracesim.simulate_trace(layer, perm, machine,
                                         max_iters=max_iters).cycles)


def exact_sweep(layer: ConvLayer,
                sample: Sequence[Perm],
                machine: cm.MachineModel = cm.MachineModel(),
                workers: Optional[int] = None,
                max_iters: Optional[int] = None) -> np.ndarray:
    """Exact trace-driven cycles for a permutation sample — the validator
    for the analytic batch engine, and the one remaining consumer of the
    worker pool (a trace costs seconds; the analytic batch costs
    microseconds).  ``max_iters`` truncates each trace like the thesis'
    instruction caps (§4.3.2), keeping consultations on big layers
    bounded."""
    jobs = [(layer, tuple(p), machine, max_iters) for p in sample]
    return np.asarray(_map_parallel(_exact_sweep_worker, jobs, workers))


@dataclasses.dataclass
class ECMSweepResult:
    """Outcome of the three-tier sweep over ``L`` layers x ``P`` perms.

    ``tiers[l]`` records which tier decided layer ``l``'s winner:
    ``"ecm"`` when roofline and ECM agreed within tolerance on the
    short-list, ``"exact"`` when tracesim arbitrated.  ``consulted[l]``
    holds the permutation indices actually sent to tracesim (empty when
    the exact tier never fired).
    """

    layers: Tuple[ConvLayer, ...]
    perms: Tuple[Perm, ...]
    roofline_cycles: np.ndarray            # float64 [L, P]
    ecm_cycles: np.ndarray                 # float64 [L, P] (corrected)
    best: List[Tuple[Perm, float]]         # per-layer winner + cycles
    tiers: List[str]                       # per-layer "ecm" | "exact"
    consulted: List[Tuple[int, ...]]       # per-layer tracesim'd indices

    @property
    def consultation_rate(self) -> float:
        """Fraction of the L x P space that reached the exact tier."""
        total = len(self.layers) * len(self.perms)
        return sum(len(c) for c in self.consulted) / max(total, 1)


def ecm_sweep(layers: Sequence[ConvLayer],
              machine: cm.MachineModel = cm.MachineModel(),
              threads: int = 1,
              perms_subset: Optional[Sequence[Perm]] = None,
              top_k: int = 8,
              tolerance: float = 0.25,
              correction: Optional[ecm_model.ECMCorrection] = None,
              max_exact_iters: Optional[int] = None,
              workers: Optional[int] = None,
              consult: bool = True,
              registry: Optional[reg.TuningRegistry] = None,
              ) -> ECMSweepResult:
    """The three-tier sweep (docs/TUNING.md): roofline + ECM everywhere,
    tracesim only where they disagree.

    Tier 1 scores each layer's permutation space with the batch roofline
    engine; tier 2 scores all layers at once with the ECM
    layer-condition model (plus the machine's learned ``correction`` if
    given).  Per layer, the union of both tiers' top-``top_k``
    short-lists is compared: if the models' relative disagreement on any
    short-listed candidate exceeds ``tolerance``, the exact trace
    simulator arbitrates *those candidates only* (``max_exact_iters``
    bounds each trace); otherwise the ECM argmin wins without a single
    trace.  With a ``registry``, each layer's winner is persisted under
    ``ecm_sweep_key`` with its deciding tier stamped in the value.
    """
    layers = tuple(layers)
    perm_tuple: Tuple[Perm, ...] = (ALL_PERMS if perms_subset is None
                                    else tuple(tuple(p) for p
                                               in perms_subset))
    roof = np.stack([cm.simulate_batch(l, perm_tuple, machine,
                                       threads).cycles for l in layers])
    ecm_res = ecm_model.ecm_predict(layers, perm_tuple, machine, threads)
    ecm_cyc = ecm_model.apply_correction(ecm_res, correction)

    best: List[Tuple[Perm, float]] = []
    tiers: List[str] = []
    consulted: List[Tuple[int, ...]] = []
    for li, layer in enumerate(layers):
        short_r = np.argsort(roof[li], kind="stable")[:top_k]
        short_e = np.argsort(ecm_cyc[li], kind="stable")[:top_k]
        cand = np.union1d(short_r, short_e)
        rel = np.abs(ecm_cyc[li, cand] - roof[li, cand]) \
            / np.maximum(roof[li, cand], 1e-12)
        if consult and float(rel.max()) > tolerance:
            exact = exact_sweep(layer, [perm_tuple[i] for i in cand],
                                machine, workers, max_exact_iters)
            win = int(cand[int(np.argmin(exact))])
            best.append((perm_tuple[win], float(exact.min())))
            tiers.append("exact")
            consulted.append(tuple(int(i) for i in cand))
        else:
            win = int(np.argmin(ecm_cyc[li]))
            best.append((perm_tuple[win], float(ecm_cyc[li, win])))
            tiers.append("ecm")
            consulted.append(())
        if registry is not None:
            registry.put(reg.TuningRecord(
                key=reg.ecm_sweep_key(layer, machine, threads),
                value={"perm": list(best[-1][0]),
                       "cycles": best[-1][1],
                       "tier": tiers[-1],
                       "consulted": len(consulted[-1])},
                source="offline"))
    return ECMSweepResult(layers=layers, perms=perm_tuple,
                          roofline_cycles=roof, ecm_cycles=ecm_cyc,
                          best=best, tiers=tiers, consulted=consulted)


def parallel_sweep(layers: Sequence[ConvLayer],
                   machine: cm.MachineModel = cm.MachineModel(),
                   threads: int = 1,
                   workers: Optional[int] = None) -> List[SweepResult]:
    """Sweep many layers; result order == input order, values
    bit-identical to per-layer :func:`sweep_layer` calls.

    ``workers`` is accepted for API compatibility but ignored: the batch
    engine made in-process sweeping faster than any pool could ship the
    work."""
    del workers
    return [sweep_layer(l, machine, threads) for l in layers]


def warm_registry(layers: Sequence[ConvLayer],
                  registry: reg.TuningRegistry,
                  machine: cm.MachineModel = cm.MachineModel(),
                  spec: cm.TPUSpec = cm.TPUSpec(),
                  threads: int = 1, elem_bytes: int = 2, top_k: int = 5,
                  kinds: Sequence[str] = ("conv_sweep", "conv_schedule"),
                  workers: Optional[int] = None,
                  refresh: bool = False) -> Dict[str, int]:
    """Tune every layer (sweeps and/or TPU schedules) into ``registry``.

    Only missing keys are computed (unless ``refresh``); each layer is one
    batch-engine array computation, run in-process (``workers`` is
    accepted for API compatibility but ignored).  The merge stays
    deterministic: records land in input order and the file is compacted
    (sorted by key), so warm output is byte-identical run to run.
    """
    del workers  # batch engine: in-process beats any pool (see above)
    evals0 = cm.total_evals()
    t0 = time.perf_counter()
    done = {"conv_sweep": 0, "conv_schedule": 0, "skipped": 0}
    if "conv_sweep" in kinds:
        keys = [reg.conv_sweep_key(l, machine, threads) for l in layers]
        todo = [(l, k) for l, k in zip(layers, keys)
                if refresh or k not in registry]
        done["skipped"] += len(layers) - len(todo)
        for layer, k in todo:
            s = sweep_layer(layer, machine, threads)
            registry.put(reg.TuningRecord(
                key=k,
                value={"cycles": s.cycles.tolist(),
                       "l1_misses": s.l1_misses.tolist(),
                       "l2_misses": s.l2_misses.tolist()},
                source="offline"))
            done["conv_sweep"] += 1
    if "conv_schedule" in kinds:
        keys = [reg.conv_schedule_key(l, spec, elem_bytes) for l in layers]
        todo = [(l, k) for l, k in zip(layers, keys)
                if refresh or k not in registry]
        done["skipped"] += len(layers) - len(todo)
        for layer, k in todo:
            value = _ranked_to_value(tune_conv(layer, spec, elem_bytes,
                                               top_k=top_k))
            registry.put(reg.TuningRecord(key=k, value=value,
                                          source="offline"))
            done["conv_schedule"] += 1
    registry.compact()
    _tune_counter("tune.warm_hits_total").inc(done["skipped"])
    _tune_counter("tune.sweeps_total").inc(
        done["conv_sweep"] + done["conv_schedule"])
    _tune_counter("tune.sweep_wall_s_total").inc(
        time.perf_counter() - t0)
    _tune_counter("tune.cost_model_evals_total").inc(
        cm.total_evals() - evals0)
    return done
