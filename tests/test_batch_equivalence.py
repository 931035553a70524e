"""Batch sweep engine ≡ scalar cost model (the PR-2 tentpole contract).

``simulate_batch`` / ``conv_schedule_cost_batch`` /
``matmul_schedule_cost_batch`` must reproduce the scalar model bit for
bit: same argmin, cycles within 1e-9 relative (they are in fact exactly
equal — the arithmetic is sequenced identically), across random layers,
all 720 permutations, and all three §5.1 cache hierarchies.  This is what
lets COST_MODEL_VERSION stay at "1" so warm registries survive the
engine swap.
"""
import itertools

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import cost_model as cm
from repro.core import tuner
from repro.core.cost_model import CacheLevel, MachineModel
from repro.core.loopnest import ConvLayer

ALL_PERMS = list(itertools.permutations(range(6)))
SMALL = MachineModel(levels=(CacheLevel("L1", 2048, 32, 3),
                             CacheLevel("L2", 8192, 32, 10,
                                        associativity=8)))
REL_TOL = 1e-9

layer_st = st.builds(
    ConvLayer,
    oc=st.integers(2, 24), ic=st.integers(2, 24),
    h=st.integers(3, 16), w=st.integers(3, 16),
    kh=st.sampled_from([1, 3]), kw=st.sampled_from([1, 3]))


def _assert_matches_scalar(layer, machine, threads=1, partial_sums=True):
    batch = cm.simulate_batch(layer, ALL_PERMS, machine, threads,
                              partial_sums)
    scalar = [cm.simulate(layer, p, machine, threads, partial_sums)
              for p in ALL_PERMS]
    s_cycles = np.array([r.cycles for r in scalar])
    np.testing.assert_allclose(batch.cycles, s_cycles, rtol=REL_TOL)
    assert int(np.argmin(batch.cycles)) == int(np.argmin(s_cycles))
    for lv in ("L1", "L2"):
        s_m = np.array([r.misses[lv] for r in scalar])
        np.testing.assert_allclose(batch.misses[lv], s_m, rtol=REL_TOL)
    s_acc = np.array([r.accesses for r in scalar])
    np.testing.assert_allclose(batch.accesses, s_acc, rtol=REL_TOL)


@given(layer_st)
@settings(max_examples=8, deadline=None)
def test_simulate_batch_matches_scalar_small_machine(layer):
    _assert_matches_scalar(layer, SMALL)


@given(layer_st, st.sampled_from(sorted(cm.HIERARCHIES)))
@settings(max_examples=10, deadline=None)
def test_simulate_batch_matches_scalar_section_5_1_hierarchies(
        layer, hierarchy):
    _assert_matches_scalar(layer, cm.HIERARCHIES[hierarchy])


@given(layer_st, st.sampled_from([2, 8, 64]))
@settings(max_examples=6, deadline=None)
def test_simulate_batch_matches_scalar_threaded(layer, threads):
    _assert_matches_scalar(layer, SMALL, threads=threads)


@given(layer_st)
@settings(max_examples=6, deadline=None)
def test_simulate_batch_matches_scalar_no_partial_sums(layer):
    _assert_matches_scalar(layer, SMALL, partial_sums=False)


def test_simulate_batch_is_bit_identical_not_just_close():
    # Stronger than the 1e-9 contract: identical float64 bit patterns.
    layer = ConvLayer(256, 32, 28, 28, 3, 3)
    for machine in (SMALL, MachineModel(), *cm.HIERARCHIES.values()):
        batch = cm.simulate_batch(layer, ALL_PERMS, machine)
        scalar = np.array([cm.simulate(layer, p, machine).cycles
                           for p in ALL_PERMS])
        np.testing.assert_array_equal(batch.cycles, scalar)


def test_squeezenet_argmin_and_cycles_match_scalar():
    # The acceptance criterion, asserted in tests (not just the bench):
    # identical per-layer argmin permutations and cycles within 1e-9
    # relative over the SqueezeNet/TinyDarknet layer set.
    from repro.configs.squeezenet_layers import TABLE_4_1
    for layer in TABLE_4_1.values():
        sweep = tuner.sweep_layer(layer)
        scalar = np.array([cm.simulate(layer, p).cycles
                           for p in ALL_PERMS])
        np.testing.assert_allclose(sweep.cycles, scalar, rtol=REL_TOL)
        assert int(np.argmin(sweep.cycles)) == int(np.argmin(scalar))


def test_batch_result_scalar_view_roundtrip():
    layer = ConvLayer(16, 8, 12, 12, 3, 3)
    batch = cm.simulate_batch(layer, ALL_PERMS, SMALL)
    for i in (0, 100, 719):
        ref = cm.simulate(layer, ALL_PERMS[i], SMALL)
        assert batch.result(i) == ref
    best_perm, best_res = batch.best()
    assert best_perm == ALL_PERMS[int(np.argmin(batch.cycles))]
    assert best_res.cycles == float(batch.cycles.min())


def test_simulate_batch_counts_evals():
    cm.reset_eval_counts()
    cm.simulate_batch(ConvLayer(4, 4, 6, 6, 3, 3), ALL_PERMS, SMALL)
    assert cm.EVAL_COUNTS["simulate_batch"] == 720
    assert cm.total_evals() == 720
    cm.reset_eval_counts()


# ---------------------------------------------------------------- TPU

conv_layer_st = st.builds(
    ConvLayer,
    oc=st.sampled_from([8, 48, 64, 200]),
    ic=st.sampled_from([3, 16, 96]),
    h=st.sampled_from([7, 14, 28]), w=st.sampled_from([7, 14, 28]),
    kh=st.sampled_from([1, 3]), kw=st.sampled_from([1, 3]))


@given(conv_layer_st)
@settings(max_examples=6, deadline=None)
def test_conv_schedule_batch_matches_scalar(layer):
    orders = list(itertools.permutations(("oc", "ic", "y", "x")))
    blocks = [{"oc": boc, "ic": bic, "y": by, "x": bx}
              for boc, bic, by, bx in itertools.product(
                  tuner._block_candidates(layer.oc, (32, 128)),
                  tuner._block_candidates(layer.ic, (32, 128)),
                  tuner._block_candidates(layer.h, (4, layer.h)),
                  tuner._block_candidates(layer.w, (8, layer.w)))]
    batch = cm.conv_schedule_cost_batch(layer, orders, blocks)
    for o, order in enumerate(orders):
        for b in range(0, len(blocks), max(1, len(blocks) // 7)):
            assert batch.cost((o, b)) == cm.conv_schedule_cost(
                layer, order, blocks[b])
    scalar_t = np.array([[cm.conv_schedule_cost(layer, o, b).time_s
                          for b in blocks] for o in orders])
    np.testing.assert_array_equal(batch.time_s, scalar_t)
    assert (int(np.argmin(batch.time_s.reshape(-1)))
            == int(np.argmin(scalar_t.reshape(-1))))


@given(st.sampled_from([64, 256, 4096]), st.sampled_from([128, 384]),
       st.sampled_from([96, 256]))
@settings(max_examples=6, deadline=None)
def test_matmul_schedule_batch_matches_scalar(m, n, k):
    orders = list(itertools.permutations(("m", "n", "k")))
    blocks = list(itertools.product(
        tuner._block_candidates(m, (128, 512)),
        tuner._block_candidates(n, (128, 512)),
        tuner._block_candidates(k, (128, k))))
    batch = cm.matmul_schedule_cost_batch(m, n, k, blocks, orders)
    scalar_t = np.array(
        [[[cm.matmul_schedule_cost(m, n, k, bm, bn, bk, order,
                                   resident_rhs=r).time_s
           for r in (False, True)] for (bm, bn, bk) in blocks]
         for order in orders])
    np.testing.assert_array_equal(batch.time_s, scalar_t)
    o, rem = divmod(int(np.argmin(batch.time_s.reshape(-1))),
                    len(blocks) * 2)
    b, r = divmod(rem, 2)
    assert batch.cost((o, b, r)) == cm.matmul_schedule_cost(
        m, n, k, *blocks[b], orders[o], resident_rhs=bool(r))


def test_tune_conv_ranking_matches_scalar_reference():
    # tune_conv consumes the batch scorer; its ranking must equal the
    # old per-candidate loop + stable sort.
    layer = ConvLayer(64, 32, 16, 16, 3, 3)
    ranked = tuner.tune_conv(layer, top_k=5)
    reference = []
    for order in itertools.permutations(("oc", "ic", "y", "x")):
        for boc, bic, by, bx in itertools.product(
                tuner._block_candidates(layer.oc, (32, 128, 256)),
                tuner._block_candidates(layer.ic, (32, 128, 256)),
                tuner._block_candidates(layer.h, (4, 8, layer.h)),
                tuner._block_candidates(layer.w, (8, 16, layer.w))):
            block = {"oc": boc, "ic": bic, "y": by, "x": bx}
            cost = cm.conv_schedule_cost(layer, order, block)
            reference.append((cost.time_s,
                              len(reference)))  # stable tiebreak
    reference.sort()
    from repro.core.schedule import ConvSchedule
    assert len(ranked) == 5
    for (sched, cost), (t, _) in zip(ranked, reference[:5]):
        assert isinstance(sched, ConvSchedule)
        assert cost.time_s == t


def test_tune_matmul_ranking_matches_scalar_reference():
    ranked = tuner.tune_matmul(512, 256, 128, top_k=5)
    reference = []
    for order in itertools.permutations(("m", "n", "k")):
        for bm, bn, bk in itertools.product(
                tuner._block_candidates(512, (128, 256, 512)),
                tuner._block_candidates(256, (128, 256, 512)),
                tuner._block_candidates(128, (128, 512, 128))):
            for resident in (False, True):
                c = cm.matmul_schedule_cost(512, 256, 128, bm, bn, bk,
                                            order, resident_rhs=resident)
                reference.append((c.time_s, len(reference)))
    reference.sort()
    for (sched, cost), (t, _) in zip(ranked, reference[:5]):
        assert cost.time_s == t


def test_permutohedron_searches_batch_equals_scalar():
    layer = ConvLayer(16, 8, 12, 12, 3, 3)
    score = lambda p: cm.simulate(layer, p, SMALL).cycles  # noqa: E731
    score_batch = tuner.batch_perm_scorer(layer, SMALL)
    start = (5, 4, 3, 2, 1, 0)
    p_s, v_s, e_s = tuner.neighbor_swap_search(score, start)
    p_b, v_b, e_b = tuner.neighbor_swap_search(None, start,
                                               score_batch=score_batch)
    assert (p_s, e_s) == (p_b, e_b)
    assert abs(v_s - v_b) <= REL_TOL * abs(v_s)
    q_s = tuner.bfs_search(score, start, budget=60)
    q_b = tuner.bfs_search(None, start, budget=60,
                           score_batch=score_batch)
    assert q_s[0] == q_b[0]
    assert abs(q_s[1] - q_b[1]) <= REL_TOL * abs(q_s[1])
