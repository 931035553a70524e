"""The engine's host-phase spans: each decode step and each admission
split into the phases that hold the chip, the queue's holds, and every
span's copy in a device profile.

Decode step: ``serve.decode_step.{upload,launch,fetch,check}``.
Admission: ``serve.admit.prepare``, ``serve.prefill``,
``serve.admit.first_token``, ``serve.admit.place``.  A queue head that
waits with a row free: ``serve.queue.held`` (its own track), with a
``reason``.  Both engine families run at smoke size under a fake clock;
spans change no token and, with telemetry off, are never reached.
"""
import collections
import glob
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import registry as reg
from repro.obs import (MetricsRegistry, NULL_TELEMETRY, NullTracer,
                       SpanTracer, Telemetry)
from repro.obs.trace import QUEUE_TID
from repro.runtime.dispatch import DispatchService
from repro.serving import ServeSession

REPO = Path(__file__).resolve().parent.parent
DECODE_PHASES = ["serve.decode_step.upload", "serve.decode_step.launch",
                 "serve.decode_step.fetch", "serve.decode_step.check"]
ADMIT_PHASES = ["serve.admit.prepare", "serve.prefill",
                "serve.admit.first_token", "serve.admit.place"]
ARCHS = ["phi3-mini-3.8b-smoke", "falcon-mamba-7b-smoke"]


class FakeClock:
    """Deterministic monotonic clock: each reading advances 1 ms."""

    def __init__(self, start=100.0, tick=1e-3):
        self.t = start
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


@pytest.fixture(scope="module", params=ARCHS)
def smoke(request):
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config(request.param)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    return cfg, model, params


def _session(model, params, telemetry, **kw):
    kw.setdefault("batch_sizes", (2,))
    kw.setdefault("bucket_lengths", (8, 16))
    return ServeSession(model, params,
                        dispatch=DispatchService(reg.TuningRegistry(None)),
                        backend="reference", straggler_threshold=1e9,
                        telemetry=telemetry, **kw)


def _serve(cfg, model, params, telemetry, **kw):
    sess = _session(model, params, telemetry, **kw)
    rng = np.random.default_rng(0)
    for i in range(3):
        sess.submit(rng.integers(1, cfg.vocab_size, 5 + i),
                    max_new_tokens=4, request_id=f"req-{i}")
    return sess, sess.drain()


def _spans(tel, tid=0):
    """(name, start, end, args) of the tracer's complete spans on one
    track, in start order (a parent before its first child)."""
    out = [(e["name"], e["ts"], e["ts"] + e["dur"], e["args"])
           for e in tel.tracer.events if e["ph"] == "X" and e["tid"] == tid]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _children(spans, parent):
    """The spans that lie inside ``parent``, in order."""
    _, lo, hi, _ = parent
    return [s for s in spans if s is not parent and lo <= s[1]
            and s[2] <= hi]


@pytest.mark.parametrize("nan_check", [True, False])
def test_decode_step_phases_once_each_in_order(smoke, nan_check):
    cfg, model, params = smoke
    tel = Telemetry(metrics=MetricsRegistry(), clock=FakeClock())
    _serve(cfg, model, params, tel, nan_check=nan_check)
    spans = _spans(tel)
    steps = [s for s in spans if s[0] == "serve.decode_step"]
    assert steps
    want = DECODE_PHASES if nan_check else DECODE_PHASES[:3]
    for step in steps:
        inner = [s for s in _children(spans, step)
                 if s[0].startswith("serve.decode_step.")]
        assert [s[0] for s in inner] == want
        # disjoint and in order
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
    n_phase = collections.Counter(s[0] for s in spans)
    assert all(n_phase[p] == len(steps) for p in want)
    assert n_phase["serve.decode_step.check"] == (len(steps) if nan_check
                                                  else 0)


def test_admission_phases_inside_admit(smoke):
    cfg, model, params = smoke
    tel = Telemetry(metrics=MetricsRegistry(), clock=FakeClock())
    _serve(cfg, model, params, tel)
    spans = _spans(tel)
    admits = [s for s in spans if s[0] == "serve.admit"]
    assert [s[3]["request_id"] for s in admits] == ["req-0", "req-1", "req-2"]
    for adm in admits:
        inner = [s for s in _children(spans, adm) if s[0] in ADMIT_PHASES]
        assert [s[0] for s in inner] == ADMIT_PHASES
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
        assert all(s[3]["request_id"] == adm[3]["request_id"] for s in inner)


def test_spans_change_no_token(smoke):
    cfg, model, params = smoke
    tel = Telemetry(metrics=MetricsRegistry(), clock=FakeClock())
    _, on = _serve(cfg, model, params, tel)
    _, off = _serve(cfg, model, params, None)
    assert ([np.asarray(r.tokens).tolist() for r in on]
            == [np.asarray(r.tokens).tolist() for r in off])
    assert [r.state for r in on] == [r.state for r in off]


def test_telemetry_off_never_reaches_the_tracer(smoke, monkeypatch):
    cfg, model, params = smoke

    def boom(*a, **k):
        raise AssertionError("telemetry-off path touched the tracer")

    for name in ("span", "begin", "end", "instant", "async_begin",
                 "async_end"):
        monkeypatch.setattr(NullTracer, name, boom)
    sess, results = _serve(cfg, model, params, None)
    assert sess.telemetry is NULL_TELEMETRY
    assert all(r.state == "COMPLETED" for r in results)


def test_recurrent_decode_executable_is_jit_step():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("falcon-mamba-7b-smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    sess, _ = _serve(cfg, model, params, None)
    decode = [fn for key, fn in sess.exec_cache.items()
              if key.role == "decode"]
    assert decode
    for fn in decode:
        assert fn.as_text().startswith("HloModule jit_step")


# ------------------------------------------------------------ queue holds

def _dense():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("phi3-mini-3.8b-smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    return cfg, model, params


def _check_trace_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_trace", REPO / "tools" / "check_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_pool_hold_one_span_covering_the_wait(tmp_path):
    cfg, model, params = _dense()
    tel = Telemetry(metrics=MetricsRegistry(), clock=FakeClock())
    # 16-token blocks, 4 usable: each request needs 3, so the second
    # waits with a row free until the first retires.
    sess = _session(model, params, tel, kv_blocks=5, bucket_lengths=(8,))
    rng = np.random.default_rng(1)
    for i in range(2):
        sess.submit(rng.integers(1, cfg.vocab_size, 8), max_new_tokens=33,
                    request_id=f"req-{i}")
    results = sess.drain()
    assert all(r.state == "COMPLETED" for r in results)
    held = _spans(tel, QUEUE_TID)
    assert [(s[0], s[3]) for s in held] == [
        ("serve.queue.held", {"reason": "pool", "request_id": "req-1"})]
    spans = _spans(tel)
    admit = {s[3]["request_id"]: s for s in spans if s[0] == "serve.admit"}
    steps = [s for s in spans if s[0] == "serve.decode_step"]
    _, lo, hi, _ = held[0]
    # from before the first decode step to req-1's admission, which
    # follows every step of req-0
    assert admit["req-0"][2] <= lo < steps[0][1]
    assert hi <= admit["req-1"][1]
    assert sum(1 for s in steps if s[2] <= hi) == 32
    # A hold crosses engine steps on its own track: the trace still
    # nests as the CI validator requires.
    tel.tracer.write(str(tmp_path / "trace.json"))
    assert _check_trace_module().check_trace(str(tmp_path / "trace.json")) \
        == []


def test_table_hold_until_the_activation_ends():
    cfg, model, params = _dense()
    tel = Telemetry(metrics=MetricsRegistry(), clock=FakeClock())
    # A fixed pool large enough for both: only the table holds "long".
    sess = _session(model, params, tel, kv_blocks=16, bucket_lengths=(8,))
    rng = np.random.default_rng(2)
    sess.submit(rng.integers(1, cfg.vocab_size, 8), max_new_tokens=6,
                request_id="short")
    late = {}

    def on_step(info):
        if not late:
            # Needs a wider table than the running activation holds.
            late["rid"] = sess.submit(rng.integers(1, cfg.vocab_size, 8),
                                      max_new_tokens=60, request_id="long")

    results = sess.drain(on_step=on_step)
    assert sorted(r.request_id for r in results) == ["long", "short"]
    held = _spans(tel, QUEUE_TID)
    assert [(s[0], s[3]["reason"], s[3]["request_id"]) for s in held] == [
        ("serve.queue.held", "table", "long")]
    acts = [s for s in _spans(tel) if s[0] == "serve.activation"]
    assert len(acts) == 2
    assert acts[0][1] <= held[0][1] and held[0][2] <= acts[0][2]


# ------------------------------------------------------ device profile

def test_every_engine_span_has_its_copy_in_the_profile(smoke, tmp_path):
    cfg, model, params = smoke
    tel = Telemetry(metrics=MetricsRegistry())
    with jax.profiler.trace(str(tmp_path)):
        _serve(cfg, model, params, tel)
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert files
    prof = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    copies = collections.Counter(
        e.name for plane in prof.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("serve."))
    spans = collections.Counter(e["name"] for e in tel.tracer.events
                                if e["ph"] == "X"
                                and e["name"].startswith("serve."))
    assert spans["serve.decode_step.launch"] > 0
    assert copies == spans


def test_tracer_imports_and_records_without_jax():
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from repro.obs.trace import SpanTracer\n"
        "t = SpanTracer()\n"
        "with t.span('a'):\n"
        "    o = t.begin('b', x=1)\n"
        "    t.end(o, y=2)\n"
        "ev = [e for e in t.events if e['ph'] == 'X']\n"
        "assert [e['name'] for e in ev] == ['b', 'a'], ev\n"
        "assert ev[0]['args'] == {'x': 1, 'y': 2}\n"
        "assert 'jax' not in [m for m in sys.modules if sys.modules[m]]\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env={"PYTHONPATH": str(REPO / "src"),
                               "PATH": "/usr/bin:/bin"},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_begin_end_spans_nest_and_cross_loops():
    tr = SpanTracer(clock=FakeClock())
    held = tr.begin("held", tid=QUEUE_TID, reason="pool")
    for step in range(2):
        with tr.span("step", step=step):
            pass
    tr.end(held, more=1)
    ev = {(e["name"], e["args"].get("step")): e for e in tr.events
          if e["ph"] == "X"}
    h = ev[("held", None)]
    assert h["tid"] == QUEUE_TID and h["args"] == {"reason": "pool",
                                                   "more": 1}
    for step in range(2):
        s = ev[("step", step)]
        assert h["ts"] <= s["ts"] and s["ts"] + s["dur"] <= h["ts"] + h["dur"]
