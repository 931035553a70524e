"""ISSUE 7: fault-tolerant serving under deterministic fault injection.

Every recovery path in the engine is exercised with an injected fault
and the survivors' tokens are required to be bit-identical to an
uninjected run of the surviving set: NaN poison-row retirement,
transient/persistent AOT compile failures (retry then per-bucket
degradation), allocator exhaustion backpressure, double-free
containment, straggler detection with the admission-shrinking hook,
deadlines (queued and mid-decode), cancellation, load shedding, and the
crash-safe tuning-registry JSONL log.
"""

import json

import numpy as np
import pytest

import jax

from repro.core import registry as reg
from repro.serving import (FaultInjector, FaultSpec, RequestState,
                           ServeSession, parse_fault)


@pytest.fixture(scope="module")
def smoke():
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("phi3-mini-3.8b-smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    return cfg, model, params


def _prompts(cfg, lengths, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def _run(model, params, prompts, budgets, backend="reference",
         faults=None, **kw):
    s = ServeSession(model, params, backend=backend, kv_block_size=4,
                     faults=faults, **kw)
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        s.submit(p, b, request_id=f"r{i}")
    res = {r.request_id: r for r in s.drain()}
    return s, res


def _tokens(res):
    return {k: r.tokens.tolist() for k, r in res.items()}


# ------------------------------------------------------------- spec parsing


def test_parse_fault_specs():
    assert parse_fault("nan@3") == FaultSpec("nan", 3)
    assert parse_fault("compile@0x3") == FaultSpec("compile", 0, times=3)
    assert parse_fault("nan@2.1") == FaultSpec("nan", 2, row=1)
    assert parse_fault("slow@5x2.1") == FaultSpec("slow", 5, times=2,
                                                  row=1)
    with pytest.raises(ValueError, match="cannot parse"):
        parse_fault("nan3")
    with pytest.raises(ValueError, match="unknown fault kind"):
        parse_fault("frobnicate@3")
    with pytest.raises(ValueError, match="invalid fault spec"):
        FaultSpec("nan", 1, times=0)


def test_injector_window_and_fired_log():
    fi = FaultInjector([FaultSpec("alloc", 2, times=2)])
    assert not fi.alloc_blocked(1)
    assert fi.alloc_blocked(2) and fi.alloc_blocked(3)
    assert not fi.alloc_blocked(4)
    assert [f.step for f in fi.fired] == [2, 3]


# ------------------------------------------------------- poison-row faults


def test_nan_poison_row_isolated_survivors_bit_identical(smoke):
    cfg, model, params = smoke
    prompts = _prompts(cfg, [5, 7, 3])
    budgets = [6, 6, 6]
    _, clean = _run(model, params, prompts, budgets)
    fi = FaultInjector([parse_fault("nan@2.1")])
    s, res = _run(model, params, prompts, budgets, faults=fi)
    assert res["r1"].state == RequestState.FAILED
    assert "non-finite" in res["r1"].reason
    for rid in ("r0", "r2"):  # survivors unaffected by the poison row
        assert res[rid].state == RequestState.COMPLETED
        assert res[rid].tokens.tolist() == clean[rid].tokens.tolist()
    assert s.stats.poisoned_rows == 1 and s.stats.failed == 1
    assert any(e.kind == "poison_row" for e in s.stats.events)
    assert fi.fired  # the injector really fired
    # the session stays serviceable after the poison event
    s.submit(prompts[0], 3, request_id="after")
    after = {r.request_id: r for r in s.drain()}
    assert after["after"].state == RequestState.COMPLETED


def test_double_free_contained_as_allocator_event(smoke):
    cfg, model, params = smoke
    prompts = _prompts(cfg, [5, 7, 3])
    budgets = [4, 6, 5]
    _, clean = _run(model, params, prompts, budgets)
    fi = FaultInjector([parse_fault("doublefree@0x99")])
    s, res = _run(model, params, prompts, budgets, faults=fi)
    assert _tokens(res) == _tokens(clean)  # no drain abort, no damage
    assert all(r.state == RequestState.COMPLETED for r in res.values())
    assert any(e.kind == "allocator" for e in s.stats.events)


def test_compaction_under_partially_failed_batch(smoke):
    cfg, model, params = smoke
    prompts = _prompts(cfg, [5, 5, 5, 5, 5, 5])
    budgets = [2, 12, 2, 12, 2, 12]

    def run(faults):
        s = ServeSession(model, params, backend="reference",
                         kv_block_size=2, batch_sizes=(4,),
                         faults=faults)
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            s.submit(p, b, request_id=f"c{i}")
        return s, {r.request_id: r for r in s.drain()}

    _, clean = run(None)
    s, res = run(FaultInjector([parse_fault("nan@4.1")]))
    failed = [k for k, r in res.items()
              if r.state == RequestState.FAILED]
    assert len(failed) == 1
    for k, r in res.items():
        if k in failed:
            continue
        assert r.state == RequestState.COMPLETED
        assert r.tokens.tolist() == clean[k].tokens.tolist(), \
            f"survivor {k} corrupted by compaction after poison row"
    assert s.stats.compactions >= 1


# --------------------------------------------- compile faults / degradation


def test_transient_compile_failure_recovers(smoke):
    cfg, model, params = smoke
    prompts = _prompts(cfg, [5, 7, 3])
    budgets = [4, 6, 5]
    _, clean = _run(model, params, prompts, budgets)
    fi = FaultInjector([parse_fault("compile@0")])
    s, res = _run(model, params, prompts, budgets, faults=fi)
    assert _tokens(res) == _tokens(clean)
    assert s.stats.compile_retries >= 1
    assert s.stats.fallbacks == 0 and not s.stats.degraded


def test_persistent_compile_failure_degrades_pallas_bucket(smoke):
    cfg, model, params = smoke
    prompts = _prompts(cfg, [5, 7, 3])
    budgets = [4, 6, 5]
    _, clean = _run(model, params, prompts, budgets, backend="pallas")
    fi = FaultInjector([parse_fault("compile@0x99")])
    s, res = _run(model, params, prompts, budgets, backend="pallas",
                  faults=fi)
    # tokens survive degradation bit-identically (reference == pallas)
    assert _tokens(res) == _tokens(clean)
    assert s.stats.degraded and s.stats.degraded_buckets >= 1
    assert s.stats.fallbacks >= 1
    assert any(e.kind == "degraded" for e in s.stats.events)
    assert s.stats.to_dict()["degraded"] is True


def test_fallback_none_keeps_pallas_without_degrading(smoke):
    cfg, model, params = smoke
    prompts = _prompts(cfg, [5, 7, 3])
    budgets = [4, 6, 5]
    _, clean = _run(model, params, prompts, budgets, backend="pallas")
    fi = FaultInjector([parse_fault("compile@0x99")])
    s, res = _run(model, params, prompts, budgets, backend="pallas",
                  faults=fi, fallback_backend="none")
    assert _tokens(res) == _tokens(clean)  # un-lowered jit still serves
    assert s.stats.fallbacks >= 1
    assert not s.stats.degraded and s.stats.degraded_buckets == 0


def test_fallback_backend_validated(smoke):
    cfg, model, params = smoke
    with pytest.raises(ValueError, match="fallback_backend"):
        ServeSession(model, params, fallback_backend="tpu")


# ------------------------------------------------- allocator exhaustion


def test_injected_alloc_exhaustion_is_backpressure(smoke):
    cfg, model, params = smoke
    prompts = _prompts(cfg, [5, 7, 3])
    budgets = [4, 6, 5]
    _, clean = _run(model, params, prompts, budgets)
    fi = FaultInjector([parse_fault("alloc@0x2")])
    s, res = _run(model, params, prompts, budgets, faults=fi)
    assert _tokens(res) == _tokens(clean)  # delayed, never dropped
    assert all(r.state == RequestState.COMPLETED for r in res.values())
    assert any(e.kind == "alloc_exhausted" for e in s.stats.events)


# --------------------------------------------------------- stragglers


def test_straggler_detected_and_hook_can_hold_admission(smoke):
    cfg, model, params = smoke
    prompts = _prompts(cfg, [5, 7, 3])
    budgets = [10, 10, 10]
    hooks = []

    def on_straggler(ev):
        hooks.append(ev)
        return 2  # ask the engine to skip two admission boundaries

    fi = FaultInjector([parse_fault("slow@7")])
    s = ServeSession(model, params, backend="reference", kv_block_size=4,
                     faults=fi, on_straggler=on_straggler)
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        s.submit(p, b, request_id=f"r{i}")
    res = {r.request_id: r for r in s.drain()}
    assert s.stats.stragglers == 1 and len(hooks) == 1
    assert hooks[0].ratio > 3.0  # the 10s spike vs a ms-scale EWMA
    assert any(e.kind == "straggler" for e in s.stats.events)
    # the stream still completes; the hold only delays admission
    assert all(r.state == RequestState.COMPLETED for r in res.values())
    assert s._admission_hold == 0


# ------------------------------------------- deadlines / shedding / cancel


def test_deadline_blown_mid_decode_keeps_partial_tokens(smoke):
    cfg, model, params = smoke
    pA, pB = _prompts(cfg, [6, 5])
    s = ServeSession(model, params, backend="reference", kv_block_size=4)
    fake = [0.0]
    s._clock = lambda: fake[0]
    s.submit(pA, 10, request_id="dl", deadline_s=0.5)
    s.submit(pB, 4, request_id="ok")
    steps = [0]

    def on_step(info):
        steps[0] += 1
        if steps[0] == 3:
            fake[0] = 1.0  # blow dl's deadline mid-decode

    res = {r.request_id: r for r in s.drain(on_step=on_step)}
    assert res["dl"].state == RequestState.TIMED_OUT
    assert "deadline" in res["dl"].reason
    assert 0 < len(res["dl"].tokens) < 10  # partial delivery
    assert res["ok"].state == RequestState.COMPLETED
    assert s.stats.timed_out == 1


def test_deadline_blown_in_queue(smoke):
    cfg, model, params = smoke
    s = ServeSession(model, params, backend="reference",
                     request_deadline_s=0.0)
    s.submit(_prompts(cfg, [5])[0], 4, request_id="q")
    res = s.drain()
    assert res[0].state == RequestState.TIMED_OUT
    assert len(res[0].tokens) == 0
    assert s.stats.timed_out == 1 and s.stats.requests == 1


def test_max_queue_s_sheds_and_counts(smoke):
    cfg, model, params = smoke
    s = ServeSession(model, params, backend="reference", max_queue_s=0.0)
    s.submit(_prompts(cfg, [5])[0], 4, request_id="shed-me")
    res = s.drain()
    assert res[0].state == RequestState.TIMED_OUT
    assert s.stats.shed == 1 and s.stats.timed_out == 1


def test_cancel_queued_and_running(smoke):
    cfg, model, params = smoke
    pA, pB = _prompts(cfg, [6, 5])
    s = ServeSession(model, params, backend="reference", kv_block_size=4)
    s.submit(pA, 8, request_id="a")
    s.submit(pB, 8, request_id="b")

    def on_step(info):
        if info["step"] == 2:
            assert s.cancel("b")

    res = {r.request_id: r for r in s.drain(on_step=on_step)}
    assert res["b"].state == RequestState.CANCELLED
    assert res["a"].state == RequestState.COMPLETED
    assert s.stats.cancelled == 1

    s.submit(pA, 4, request_id="queued")
    assert s.cancel("queued") and not s.cancel("nonexistent")
    res2 = s.drain()
    assert [r.state for r in res2] == [RequestState.CANCELLED]


def test_stats_to_dict_json_serializable(smoke):
    cfg, model, params = smoke
    fi = FaultInjector([parse_fault("nan@1.0")])
    s, _ = _run(model, params, _prompts(cfg, [5, 3]), [4, 4], faults=fi)
    d = s.stats.to_dict()
    json.dumps(d)  # events and counters must all be JSON-ready
    for k in ("rejected", "timed_out", "cancelled", "failed", "shed",
              "fallbacks", "poisoned_rows", "stragglers", "degraded",
              "degraded_buckets", "events"):
        assert k in d


# ----------------------------------------------- crash-safe registry log


def test_registry_counts_malformed_lines_in_stats(tmp_path):
    path = str(tmp_path / "tuning.jsonl")
    r = reg.TuningRegistry(path)
    key = reg.matmul_schedule_key(8, 8, 8, None)
    r.record_measurement(key, {"type": "matmul"}, 1e-4)
    with open(path, "a", encoding="utf-8") as f:
        f.write("not json at all\n")
        f.write('{"torn": ')  # crash mid-append: no trailing newline
    r2 = reg.TuningRegistry(path)
    assert len(r2) == 1
    assert r2.malformed_lines == 2
    assert r2.stats()["malformed_lines"] == 2


def test_registry_append_after_torn_tail_is_not_corrupted(tmp_path):
    path = str(tmp_path / "tuning.jsonl")
    r = reg.TuningRegistry(path)
    r.record_measurement(reg.matmul_schedule_key(8, 8, 8, None),
                         {"type": "matmul"}, 1e-4)
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"half": "a record without a newline')
    # the next append must start a fresh line, not extend the torn tail
    r.record_measurement(reg.matmul_schedule_key(16, 16, 16, None),
                         {"type": "matmul"}, 2e-4)
    r2 = reg.TuningRegistry(path)
    assert len(r2) == 2  # both real records survive
    assert r2.malformed_lines == 1  # exactly the torn line is lost


# ----------------------------------------------------------- launcher CLI


def test_launch_serve_fault_flags(tmp_path, capsys, monkeypatch):
    from repro.launch import serve as serve_cli

    reqs = tmp_path / "requests.jsonl"
    reqs.write_text('{"prompt_len": 4, "new_tokens": 4}\n'
                    '{"prompt_len": 5, "new_tokens": 4}\n')
    monkeypatch.setattr(
        "sys.argv",
        ["serve", "--arch", "phi3-mini-3.8b-smoke", "--session",
         "--requests-file", str(reqs), "--batch-sizes", "1,2",
         "--fallback-backend", "reference",
         "--inject-fault", "nan@1.0"],
    )
    serve_cli.main()
    out = capsys.readouterr().out
    assert "session: 2 requests" in out
    assert "FAILED" in out  # the poisoned request's terminal state
    assert "faults:" in out  # fault summary line


def test_launch_serve_exits_nonzero_on_unrequested_degradation(
        tmp_path, monkeypatch):
    """Every AOT compile fails and nobody injected a fault: the session
    still completes the request (degraded, on the jit fallback), but the
    launcher must not report that run as a success."""
    import jax

    from repro.launch import serve as serve_cli

    def refuse(self, *a, **k):
        raise RuntimeError("forced AOT failure")

    monkeypatch.setattr(jax.stages.Lowered, "compile", refuse)
    reqs = tmp_path / "requests.jsonl"
    reqs.write_text('{"prompt_len": 4, "new_tokens": 2}\n')
    monkeypatch.setattr(
        "sys.argv",
        ["serve", "--arch", "phi3-mini-3.8b-smoke", "--session",
         "--backend", "pallas", "--requests-file", str(reqs),
         "--batch-sizes", "1"])
    with pytest.raises(SystemExit) as exc:
        serve_cli.main()
    assert exc.value.code not in (0, None)
    assert "degraded bucket" in str(exc.value.code)
