"""CPU tests of the per-layer metrics that read the engine's host-phase
spans (``decode_host_ms``, ``admit_host_ms``, ``queue_held_share``), on
hand-built traces whose answers are counted by hand.

    PYTHONPATH=src python -m pytest -q tests/bench_chip/test_span_metrics.py
"""
from __future__ import annotations

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from harness import spans as spanmod  # noqa: E402
from harness import spec  # noqa: E402
from harness.trace import Trace  # noqa: E402

DEV = "/device:TPU:0"
US = 1_000                                   # ns


def _read(name, tr):
    return spec.metric_reader(name, BENCH).read({"trace": tr})


def _trace(ops, spans, window=(0, 1000 * US)):
    return Trace({DEV: [("%op.1 = f32[] fusion()", s, e) for s, e in ops]},
                 {DEV: []}, [], window, spans)


# Two decode steps and one admission in a 1 ms window.  The device runs
# [0, 100), [300, 500), [510, 700) and [900, 1000) us.  Idle gaps:
# [100, 300) 200 us, [500, 510) 10 us (under the 20 us threshold),
# [700, 900) 200 us.
OPS = [(0, 100 * US), (300 * US, 500 * US), (510 * US, 700 * US),
       (900 * US, 1000 * US)]
SPANS = [
    # step 1: upload and launch idle 50 us, fetch 40 us of the gap.
    ("serve.decode_step", 50 * US, 310 * US),
    ("serve.decode_step.upload", 50 * US, 120 * US),
    ("serve.decode_step.launch", 120 * US, 150 * US),
    ("serve.decode_step.fetch", 260 * US, 300 * US),
    # step 2: the fetch lies over the 10 us bubble, which does not count;
    # check covers 30 us of the second gap.
    ("serve.decode_step", 480 * US, 730 * US),
    ("serve.decode_step.upload", 480 * US, 490 * US),
    ("serve.decode_step.launch", 490 * US, 495 * US),
    ("serve.decode_step.fetch", 495 * US, 520 * US),
    ("serve.decode_step.check", 700 * US, 730 * US),
    # one admission: prepare 20 us idle, prefill busy, place 50 us idle;
    # first_token and place overlap, and the union counts once.
    ("serve.admit", 730 * US, 910 * US),
    ("serve.admit.prepare", 730 * US, 750 * US),
    ("serve.prefill", 750 * US, 760 * US),
    ("serve.admit.first_token", 760 * US, 800 * US),
    ("serve.admit.place", 790 * US, 850 * US),
    # the queue's head held twice, overlapping, and once past the window
    ("serve.queue.held", 100 * US, 400 * US),
    ("serve.queue.held", 350 * US, 450 * US),
    ("serve.queue.held", 950 * US, 1200 * US),
]


def test_decode_host_ms_by_hand():
    # idle inside decode phases: step 1 (120-100) + 30 + 40 = 90 us,
    # step 2 only the check's 30 us: 120 us over 2 launches.
    assert _read("decode_host_ms", _trace(OPS, SPANS)) == \
        pytest.approx(120e-3 / 2)


def test_admit_host_ms_by_hand():
    # [730, 850) inside the [700, 900) gap: 120 us over 1 admission.
    assert _read("admit_host_ms", _trace(OPS, SPANS)) == \
        pytest.approx(120e-3)


def test_queue_held_share_by_hand():
    # union [100, 450) + [950, 1000) clipped = 400 us of 1000.
    assert _read("queue_held_share", _trace(OPS, SPANS)) == \
        pytest.approx(40.0)


def test_only_spans_starting_in_the_window_count_as_steps():
    late = SPANS + [("serve.decode_step.launch", 1100 * US, 1150 * US),
                    ("serve.admit.prepare", -50 * US, -10 * US)]
    tr = _trace(OPS, late)
    assert _read("decode_host_ms", tr) == pytest.approx(120e-3 / 2)
    assert _read("admit_host_ms", tr) == pytest.approx(120e-3)


def test_no_held_span_reads_zero_with_phases():
    no_hold = [s for s in SPANS if s[0] != "serve.queue.held"]
    assert _read("queue_held_share", _trace(OPS, no_hold)) == 0.0


@pytest.mark.parametrize("name", ["decode_host_ms", "admit_host_ms",
                                  "queue_held_share"])
def test_nothing_without_the_phase_spans(name):
    # A program whose engine records only whole steps and admissions.
    old = [s for s in SPANS if s[0] in ("serve.decode_step", "serve.admit",
                                        "serve.prefill")]
    assert _read(name, _trace(OPS, old)) is None
    assert _read(name, _trace(OPS, [])) is None


@pytest.mark.parametrize("name", ["decode_host_ms", "admit_host_ms"])
def test_nothing_without_a_device(name):
    tr = Trace({}, {}, [], (0, 1000 * US), SPANS)
    assert _read(name, tr) is None


def test_idle_gaps_threshold_matches_the_breakdown():
    tr = _trace(OPS, SPANS)
    assert spanmod.idle_gaps(tr, DEV) == [(100 * US, 300 * US),
                                          (700 * US, 900 * US)]
    labelled = dict(tr.idle_gaps())
    assert labelled["device:between-ops"] == pytest.approx(10e-6)
