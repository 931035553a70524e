"""ISSUE 8: unified telemetry — metrics registry, span tracer,
lifecycle log, unified events, and the artifact validator.

The two acceptance-critical tests live here: (1) two identical
ServeSession runs under an injected fake clock serialize to
byte-identical trace JSON, and (2) the telemetry-off fast path never
touches the tracer (every NullTracer method is patched to raise and a
full drain still succeeds).  The rest unit-tests the exporters, the
derived lifecycle latencies, and tools/check_trace.py against valid
and deliberately-broken inputs.
"""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.core import registry as reg
from repro.obs import (
    Counter,
    Event,
    Gauge,
    Histogram,
    LifecycleLog,
    MetricsRegistry,
    NULL_TELEMETRY,
    NullTracer,
    SpanTracer,
    Telemetry,
    format_event_summary,
    prom_name,
    summarize_events,
)
from repro.runtime.dispatch import DispatchService
from repro.serving import ServeSession

REPO = Path(__file__).resolve().parent.parent


class FakeClock:
    """Deterministic monotonic clock: each reading advances 1 ms."""

    def __init__(self, start=100.0, tick=1e-3):
        self.t = start
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


def _check_trace_module():
    spec = importlib.util.spec_from_file_location(
        "check_trace", REPO / "tools" / "check_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------- metrics


def test_prom_name_sanitises():
    assert prom_name("serve.ttft_seconds") == "serve_ttft_seconds"
    assert prom_name("bench.serve.cache_hit_rate") == (
        "bench_serve_cache_hit_rate")


def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    c = r.counter("a.total", help="h")
    c.inc()
    c.inc(2.5)
    assert c.value == pytest.approx(3.5)
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("b.live")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == pytest.approx(3)
    h = r.histogram("c.seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 3 and h.sum == pytest.approx(5.55)
    # cumulative counts per upper bound, +Inf last
    assert h.cumulative() == [(0.1, 1), (1.0, 2), (float("inf"), 3)]


def test_registry_kind_mismatch_and_reuse():
    r = MetricsRegistry()
    c = r.counter("x")
    assert r.counter("x") is c  # same instrument on re-request
    with pytest.raises(TypeError):
        r.gauge("x")
    with pytest.raises(TypeError):
        r.histogram("x")


def test_set_gauges_skips_non_numeric():
    r = MetricsRegistry()
    r.set_gauges({"hits": 3, "rate": 0.5, "on": True, "name": "lru"},
                 prefix="cache.")
    names = r.names()
    assert "cache.hits" in names and "cache.rate" in names
    assert "cache.on" not in names and "cache.name" not in names


def test_prometheus_exposition_grammar(tmp_path):
    r = MetricsRegistry()
    r.counter("serve.exec_cache_hits_total", help="hits").inc(7)
    r.gauge("serve.kv_fragmentation").set(0.25)
    r.histogram("serve.ttft_seconds", buckets=(0.01, 0.1)).observe(0.05)
    text = r.to_prometheus()
    assert "# TYPE serve_exec_cache_hits_total counter" in text
    assert 'serve_ttft_seconds_bucket{le="+Inf"} 1' in text
    assert "serve_ttft_seconds_count 1" in text
    out = tmp_path / "m.prom"
    r.write_prometheus(out)
    ct = _check_trace_module()
    assert ct.check_metrics(str(out), [
        "serve_exec_cache_hits_total", "serve_kv_fragmentation",
        "serve_ttft_seconds"]) == []
    # snapshot mirrors the same instruments as plain dicts
    snap = r.snapshot()
    assert snap["serve.exec_cache_hits_total"]["value"] == 7


# ------------------------------------------------------------ events


def test_event_attribute_passthrough_and_summary():
    ev = Event(kind="nan_poisoned", step=3, request_id="r1",
               ts=0.5, data={"row": 2})
    assert ev.row == 2 and ev.kind == "nan_poisoned"
    assert ev.as_dict()["row"] == 2
    with pytest.raises(AttributeError):
        ev.missing_field
    events = [ev, Event(kind="nan_poisoned", step=4, request_id="r2",
                        ts=0.6, data={"row": 0})]
    assert summarize_events(events) == {"nan_poisoned": 2}
    line = format_event_summary(events, degraded=["b4"])
    assert "nan_poisoned=2" in line and "b4" in line
    assert format_event_summary([]) == "faults: none"


# --------------------------------------------------------- lifecycle


def test_lifecycle_derived_latencies():
    log = LifecycleLog()
    log.submitted("r1", 10.0)
    log.submitted("r1", 99.0)  # idempotent: first submit wins
    log.admitted("r1", 10.5)
    log.token("r1", 11.0)
    log.token("r1", 12.0)
    log.decode_step("r1")
    log.terminal("r1", 12.5, "COMPLETED")
    (rec,) = log.records.values()
    assert rec.submitted_ts == 10.0
    assert rec.queue_s == pytest.approx(0.5)
    assert rec.ttft_s == pytest.approx(1.0)
    assert rec.per_token_s == pytest.approx(1.0)
    assert log.ttft_values() == [pytest.approx(1.0)]
    (d,) = log.as_dicts()
    assert d["state"] == "COMPLETED" and d["ttft_s"] == pytest.approx(1.0)
    # unknown ids are ignored, never KeyError
    log.token("ghost", 1.0)
    log.terminal("ghost", 2.0, "FAILED")


# ------------------------------------------------------------- tracer


def test_span_tracer_deterministic_exports():
    def run():
        tr = SpanTracer(clock=FakeClock())
        with tr.span("outer", step=0):
            with tr.span("inner"):
                tr.instant("tick", n=1)
        tr.end(tr.begin("manual", what="x"))
        tr.async_begin("request", "r1", request_id="r1")
        tr.async_end("request", "r1", state="COMPLETED")
        return tr

    a, b = run(), run()
    assert a.to_json() == b.to_json()
    doc = a.to_chrome()
    phases = sorted({e["ph"] for e in doc["traceEvents"]})
    assert phases == ["M", "X", "b", "e", "i"]
    # inner nests strictly inside outer
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    o, i = spans["outer"], spans["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"]


def test_null_tracer_is_inert():
    tr = NullTracer()
    assert tr.enabled is False
    with tr.span("x"):
        tr.instant("y")
    tr.async_begin("request", "r")
    tr.async_end("request", "r")
    assert tr.to_chrome()["traceEvents"] == []


# ----------------------------------------------------- check_trace.py


def test_check_trace_valid_and_broken(tmp_path):
    ct = _check_trace_module()
    good = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "repro"}},
        {"ph": "X", "name": "outer", "ts": 0.0, "dur": 10.0,
         "pid": 1, "tid": 0},
        {"ph": "X", "name": "inner", "ts": 2.0, "dur": 3.0,
         "pid": 1, "tid": 0},
        {"ph": "b", "name": "request", "cat": "request", "id": "r1",
         "ts": 0.0, "pid": 1, "tid": 1},
        {"ph": "e", "name": "request", "cat": "request", "id": "r1",
         "ts": 9.0, "pid": 1, "tid": 1},
    ]}
    p = tmp_path / "good.json"
    p.write_text(json.dumps(good))
    assert ct.check_trace(str(p)) == []

    # partial overlap: [2, 12] pokes out of outer [0, 10]
    bad = json.loads(json.dumps(good))
    bad["traceEvents"][2]["dur"] = 10.0
    p_bad = tmp_path / "overlap.json"
    p_bad.write_text(json.dumps(bad))
    assert any("partially overlaps" in s
               for s in ct.check_trace(str(p_bad)))

    # unclosed async begin
    dangling = {"traceEvents": [good["traceEvents"][3]]}
    p_d = tmp_path / "dangling.json"
    p_d.write_text(json.dumps(dangling))
    assert any("begin without end" in s for s in ct.check_trace(str(p_d)))

    # not JSON at all
    p_junk = tmp_path / "junk.json"
    p_junk.write_text("not json")
    assert ct.check_trace(str(p_junk))


def test_check_metrics_broken(tmp_path):
    ct = _check_trace_module()
    p = tmp_path / "bad.prom"
    p.write_text("# TYPE x bogus\nname value_is_not_numeric\n")
    problems = ct.check_metrics(str(p), ["absent_family"])
    assert any("malformed TYPE" in s for s in problems)
    assert any("non-numeric" in s for s in problems)
    assert any("absent_family" in s for s in problems)


# ------------------------------------- end-to-end: ServeSession runs


def _smoke_model(arch="phi3-mini-3.8b-smoke"):
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(arch)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    return cfg, model, params


def _run_session(cfg, model, params, telemetry):
    """A small deterministic 3-request stream (fixed request ids and a
    huge straggler threshold, so the only nondeterminism left would be
    a telemetry bug)."""
    session = ServeSession(
        model, params,
        dispatch=DispatchService(reg.TuningRegistry(None)),
        backend="reference", batch_sizes=(1, 2),
        bucket_lengths=(8, 16), straggler_threshold=1e9,
        telemetry=telemetry)
    rng = np.random.default_rng(0)
    for i in range(3):
        session.submit(rng.integers(0, cfg.vocab_size, 5 + i),
                       max_new_tokens=3, request_id=f"req-{i}")
    results = session.drain()
    assert len(results) == 3
    return session, results


def test_trace_byte_identical_under_fake_clock():
    cfg, model, params = _smoke_model()

    def run():
        tel = Telemetry(metrics=MetricsRegistry(), clock=FakeClock())
        _run_session(cfg, model, params, tel)
        return tel

    a, b = run(), run()
    ja, jb = a.tracer.to_json(), b.tracer.to_json()
    assert ja == jb
    assert ja.encode("utf-8") == jb.encode("utf-8")
    # and it is a trace the validator + Perfetto accept: engine spans
    # nested, request tracks paired
    names = {e["name"] for e in a.tracer.to_chrome()["traceEvents"]}
    assert {"serve.step", "serve.prefill", "serve.decode_step",
            "serve.activation", "request"} <= names
    # lifecycle derived TTFT present for every request, on the fake
    # clock's timeline
    ttfts = a.lifecycle.ttft_values()
    assert len(ttfts) == 3 and all(t > 0 for t in ttfts)
    recs = a.lifecycle.as_dicts()
    assert [r["request_id"] for r in recs] == ["req-0", "req-1", "req-2"]
    assert all(r["state"] == "COMPLETED" for r in recs)
    # metrics flowed through the injected (non-default) registry
    assert a.metrics.counter(
        "serve.requests_submitted_total").value == 3


def test_telemetry_off_never_touches_tracer(monkeypatch):
    cfg, model, params = _smoke_model()

    def boom(*a, **k):
        raise AssertionError("telemetry-off path touched the tracer")

    for name in ("span", "begin", "end", "instant", "async_begin",
                 "async_end"):
        monkeypatch.setattr(NullTracer, name, boom)
    assert NULL_TELEMETRY.enabled is False
    session, results = _run_session(cfg, model, params, None)
    assert session.telemetry is NULL_TELEMETRY
    assert all(r.state == "COMPLETED" for r in results)
    # and no lifecycle/metric state accrued anywhere
    assert NULL_TELEMETRY.lifecycle.records == {}


def test_telemetry_on_off_results_identical():
    cfg, model, params = _smoke_model()
    tel = Telemetry(metrics=MetricsRegistry(), clock=FakeClock())
    _, r_on = _run_session(cfg, model, params, tel)
    _, r_off = _run_session(cfg, model, params, None)
    assert ([np.asarray(r.tokens).tolist() for r in r_on]
            == [np.asarray(r.tokens).tolist() for r in r_off])
    assert [r.state for r in r_on] == [r.state for r in r_off]


# --------------------- ISSUE 10 S3: TTFT semantics for preempted paths


def test_lifecycle_ttft_absent_without_first_token():
    log = LifecycleLog()
    log.submitted("r1", 10.0)
    log.terminal("r1", 10.2, "REJECTED", reason="kv pool too small")
    log.submitted("r2", 11.0)
    log.admitted("r2", 11.1)
    log.terminal("r2", 11.4, "CANCELLED")
    for rec in log.records.values():
        assert rec.first_token_ts is None
        assert rec.ttft_s is None          # absent, never 0 or negative
        assert rec.as_dict()["ttft_s"] is None
    assert log.ttft_values() == []         # percentiles skip them too


def test_preempted_requests_have_null_ttft(tmp_path):
    """End-to-end: REJECTED / CANCELLED / TIMED_OUT-before-first-token
    requests carry no TTFT in the exported lifecycle (S3), and the
    export passes tools/check_trace.py --lifecycle."""
    cfg, model, params = _smoke_model()
    tel = Telemetry(metrics=MetricsRegistry(), clock=FakeClock())
    # kv_blocks=2 => pool holds 1 usable block of 4 tokens: a request
    # needing 3 blocks can NEVER fit and is rejected at admission.
    session = ServeSession(
        model, params,
        dispatch=DispatchService(reg.TuningRegistry(None)),
        backend="reference", batch_sizes=(1, 2),
        bucket_lengths=(8, 16), straggler_threshold=1e9,
        kv_block_size=4, kv_blocks=2, telemetry=tel)
    prompt = np.array([3, 5, 7], dtype=np.int64)
    session.submit(prompt, max_new_tokens=1, request_id="r-ok")
    session.submit(prompt, max_new_tokens=9, request_id="r-reject")
    session.submit(prompt, max_new_tokens=1, request_id="r-timeout",
                   deadline_s=0.0)
    session.submit(prompt, max_new_tokens=1, request_id="r-cancel")
    assert session.cancel("r-cancel") is True
    results = {r.request_id: r for r in session.drain()}
    assert results["r-ok"].state == "COMPLETED"
    assert results["r-reject"].state == "REJECTED"
    assert results["r-timeout"].state == "TIMED_OUT"
    assert results["r-cancel"].state == "CANCELLED"

    recs = {d["request_id"]: d for d in tel.lifecycle.as_dicts()}
    assert recs["r-ok"]["ttft_s"] > 0
    for rid in ("r-reject", "r-timeout", "r-cancel"):
        assert recs[rid]["first_token_ts"] is None
        assert recs[rid]["ttft_s"] is None, rid
        assert recs[rid]["finished_ts"] >= recs[rid]["submitted_ts"]

    path = tmp_path / "lifecycle.json"
    path.write_text(json.dumps(tel.lifecycle.as_dicts()))
    assert _check_trace_module().check_lifecycle(str(path)) == []


def test_check_lifecycle_good_and_bad(tmp_path):
    ct = _check_trace_module()
    good = [
        {"request_id": "a", "submitted_ts": 1.0, "admitted_ts": 1.5,
         "first_token_ts": 2.0, "last_token_ts": 3.0,
         "finished_ts": 3.0, "ttft_s": 1.0, "state": "COMPLETED"},
        {"request_id": "b", "submitted_ts": 1.0, "admitted_ts": None,
         "first_token_ts": None, "last_token_ts": None,
         "finished_ts": 1.2, "ttft_s": None, "state": "REJECTED"},
    ]
    p = tmp_path / "good.json"
    p.write_text(json.dumps(good))
    assert ct.check_lifecycle(str(p)) == []

    # a preempted request reporting a zero TTFT is the S3 failure mode
    bad = json.loads(p.read_text())
    bad[1]["ttft_s"] = 0.0
    p_ttft = tmp_path / "ttft.json"
    p_ttft.write_text(json.dumps(bad))
    assert any("must be null" in s
               for s in ct.check_lifecycle(str(p_ttft)))

    # ...as is a first token with a non-positive TTFT
    bad = json.loads(p.read_text())
    bad[0]["ttft_s"] = 0.0
    p_zero = tmp_path / "zero.json"
    p_zero.write_text(json.dumps(bad))
    assert any("must be > 0" in s for s in ct.check_lifecycle(str(p_zero)))

    # timestamps running backwards
    bad = json.loads(p.read_text())
    bad[0]["finished_ts"] = 0.5
    p_mono = tmp_path / "mono.json"
    p_mono.write_text(json.dumps(bad))
    assert any("precedes" in s for s in ct.check_lifecycle(str(p_mono)))

    p_junk = tmp_path / "junk.json"
    p_junk.write_text("{}")
    assert ct.check_lifecycle(str(p_junk))


def test_check_metrics_pair_good_and_bad(tmp_path):
    ct = _check_trace_module()
    old = tmp_path / "old.prom"
    new = tmp_path / "new.prom"
    old.write_text(
        "# TYPE c_total counter\nc_total 3\n"
        "# TYPE g gauge\ng 9\n"
        "# TYPE h histogram\n"
        'h_bucket{le="1"} 2\nh_bucket{le="+Inf"} 4\n'
        "h_sum 1.5\nh_count 4\n")
    new.write_text(
        "# TYPE c_total counter\nc_total 5\n"
        "# TYPE g gauge\ng 2\n"          # gauges may move freely
        "# TYPE h histogram\n"
        'h_bucket{le="1"} 2\nh_bucket{le="+Inf"} 6\n'
        "h_sum 2.5\nh_count 6\n"
        "# TYPE late_total counter\nlate_total 1\n")  # new series: fine
    assert ct.check_metrics_pair(str(old), str(new)) == []

    shrunk = tmp_path / "shrunk.prom"
    shrunk.write_text(
        "# TYPE c_total counter\nc_total 2\n"
        "# TYPE g gauge\ng 9\n"
        "# TYPE h histogram\n"
        'h_bucket{le="1"} 2\nh_bucket{le="+Inf"} 3\n'
        "h_sum 1.5\nh_count 3\n")
    problems = ct.check_metrics_pair(str(old), str(shrunk))
    assert any(s.startswith("c_total:") for s in problems)
    assert any(s.startswith('h_bucket{le="+Inf"}') for s in problems)
    assert any(s.startswith("h_count") for s in problems)
    assert not any(s.startswith("g") for s in problems)
