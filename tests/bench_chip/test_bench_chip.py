"""CPU tests of the on-chip benchmark harness (``benchmarks/chip``).

They run the harness at tiny sizes, with the program's Pallas kernels
interpreted, and check what a CPU can: the traffic generator, the tail
arithmetic, the kernels' FLOP and byte counts, the peaks table, the
trace reduction on a small trace recorded on a TPU v5e, discovery of new
files by name, that ``run.py`` refuses the CPU, and that the check that
decides ``correct`` passes a sound run and fails a broken one and the
int8 control.

    PYTHONPATH=src python -m pytest -q tests/bench_chip
"""
from __future__ import annotations

import json
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from harness import check, spec, stats, traffic  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402

TINY_DENSE = {
    "name": "tiny-dense", "source": "test", "task": "serve",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "reduced": [],
    "engine": {"backend": "pallas", "dispatch": True, "rows": 2,
               "kv_block_size": 16, "kv_blocks": 9, "prompt_buckets": [16, 32],
               "cache_capacity": 64},
    "limits": {"max_logit_gap": 0.05, "min_tokens": 20, "max_requests": 4},
}
TINY_MAMBA = {
    "name": "tiny-mamba", "source": "test", "task": "serve",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "state_size": 8, "conv_kernel": 4, "time_step_rank": 4,
    "vocab_size": 256, "layer_norm_epsilon": 1e-5,
    "tie_word_embeddings": False, "reduced": [],
    "engine": {"backend": "pallas", "dispatch": True, "rows": 2,
               "kv_block_size": 16, "prompt_buckets": [16, 32],
               "cache_capacity": 64},
    "limits": {"max_logit_gap": 0.05, "min_tokens": 20, "max_requests": 4},
}
TINY_MIX = {
    "kind": "open_loop", "knee_rps": 2.0,
    "phases": [{"seconds": 1.0, "rate_x_knee": 1.0}],
    "prompt_len": {"median": 12, "sigma": 0.6, "min": 4, "max": 32},
    "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 16},
}


def _tiny_root(tmp_path: pathlib.Path, sizes, reference: str) -> pathlib.Path:
    """A checkout holding one tiny cell, the real harness files beside."""
    base = tmp_path / "benchmarks" / "chip"
    for sub in ("configs", "traffic"):
        (base / sub).mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", base / "metrics")
    (base / "configs" / f"{sizes['name']}.json").write_text(json.dumps(sizes))
    shutil.copy(BENCH / "configs" / reference,
                base / "configs" / f"{sizes['name']}.py")
    (base / "traffic" / "tiny-mix.json").write_text(json.dumps(TINY_MIX))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "tiny-cell", "config": sizes["name"],
                           "traffic": "tiny-mix", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"]:
        m.pop("workloads", None)
    for m in bench["per_layer"]:
        m["workloads"] = ["tiny-cell"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def _run_tiny(tmp_path, capsys, sizes, reference, seed=5, trace=0):
    from harness import cli
    root = _tiny_root(tmp_path, sizes, reference)
    rc = cli.run(["--workload", "tiny-cell", "--seed", str(seed),
                  "--seconds", "3", "--trace", str(trace)],
                 t_start=time.perf_counter(), allow_cpu=True,
                 bench_root=root, out_root=tmp_path / "out")
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ------------------------------------------------------------ traffic

def test_traffic_same_seed_same_requests_other_seed_same_work():
    mix = json.loads((BENCH / "traffic" / "mamba-chat-burst.json").read_text())
    big = 2 ** 31 + 977
    a = traffic.generate(mix, vocab_size=1000, seconds=48, seed=big)
    b = traffic.generate(mix, vocab_size=1000, seconds=48, seed=big)
    c = traffic.generate(mix, vocab_size=1000, seconds=48, seed=big + 1)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # Another seed: other order, burst phase and ids, the same work.
    assert [x.due_s for x in a] != [x.due_s for x in c]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert len(a) == len(c)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in c)
    assert sorted(x.max_new_tokens for x in a) \
        == sorted(x.max_new_tokens for x in c)
    assert all(0 <= x.due_s < 48 for x in a)
    assert all(16 <= len(x.prompt) <= 256 for x in a)


def test_traffic_rate_follows_the_phases():
    mix = dict(TINY_MIX, knee_rps=10.0,
               phases=[{"seconds": 1.0, "rate_x_knee": 0.2},
                       {"seconds": 1.0, "rate_x_knee": 1.8}])
    seed = 3
    due = np.array([x.due_s for x in traffic.generate(
        mix, vocab_size=50, seconds=40, seed=seed)])
    assert abs(len(due) - traffic.offered_requests(mix, 40)) < 2
    # The phase offset is the seed's first draw; nine tenths of the
    # arrivals fall in the fast phase (1.8 against 0.2 x the knee).
    offset = np.random.default_rng(seed).uniform(0.0, 2.0)
    fast = np.mod(due + offset, 2.0) >= 1.0
    assert 0.85 <= fast.mean() <= 0.95


def test_schedule_seed_fixes_the_schedule_not_the_content():
    """``schedule_seed``: every run gets the same arrival times and
    lengths in the same order; the run's seed draws only the token ids."""
    mix = json.loads((BENCH / "traffic" / "phi3-chat-overload.json")
                     .read_text())
    big = 2 ** 31 + 55
    a, b = (traffic.generate(mix, vocab_size=1000, seconds=48, seed=s)
            for s in (big, big + 1))
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new_tokens) for x in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    free = dict(mix)
    del free["schedule_seed"]
    c = traffic.generate(free, vocab_size=1000, seconds=48, seed=big)
    assert [x.max_new_tokens for x in c] != [x.max_new_tokens for x in a]
    assert sorted(x.max_new_tokens for x in c) == \
        sorted(x.max_new_tokens for x in a)


@pytest.mark.parametrize("where,bad", [
    ("mix", {"shared_prefix_tokens": 64}),
    ("prompt_len", {"prompt_len": dict(TINY_MIX["prompt_len"],
                                       dist="uniform")}),
    ("phases[0]", {"phases": [{"seconds": 1.0, "rate_x_knee": 1.0,
                               "burst": 4}]}),
    ("output_len", {"output_len": {"median": 6, "min": 2, "max": 16}}),
    ("kind", {"kind": "closed_loop"}),
])
def test_traffic_refuses_keys_it_does_not_read(where, bad):
    with pytest.raises(ValueError, match=where.split("[")[0]):
        traffic.generate(dict(TINY_MIX, **bad), vocab_size=50, seconds=4,
                         seed=1)


def test_committed_mixes_hold_only_what_the_generator_reads():
    for path in sorted((BENCH / "traffic").glob("*.json")):
        traffic.check_mix(json.loads(path.read_text()))


# ------------------------------------------------------------ tails

def test_percentile_and_censored_tails():
    assert stats.percentile([1, 2, 3, 4, 5], 90) == pytest.approx(4.6)
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 50) is None
    # Due at 0, 1, 2; first tokens at 0.5, never, 2.25; window ends at 3.
    ttft = stats.censored([0, 1, 2], [0.5, None, 2.25], 3.0)
    assert ttft == [0.5, 2.0, 0.25]
    # A token after the end of the window is not there yet.
    assert stats.censored([0], [3.5], 3.0) == [3.0]
    assert stats.gaps([0.0, 0.1, 0.4, 3.2], 3.0) == pytest.approx([0.1, 0.3])
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


# ------------------------------------------------------ FLOPs and bytes

def test_paged_decode_cost_against_hand_count():
    mod = spec.metric_reader("paged_decode_roofline")
    # Two rows reading 10 and 30 positions, 32 heads of 96, bf16:
    # QK and PV are 2 x 2 x 96 FLOPs per head per position.
    flops, nbytes = mod.call_cost([10, 30], heads=32, kv_heads=32,
                                  head_dim=96)
    assert flops == 4 * 32 * 96 * 40
    # K and V of every live position, and q in and the output out.
    assert nbytes == 2 * 32 * 96 * 40 * 2 + 2 * (2 * 32 * 96 * 2)


def test_ssm_scan_cost_against_hand_count():
    mod = spec.metric_reader("ssm_scan_roofline")
    di, n = 8192, 16
    # Decode: 3 live rows, one token each, state read and written.
    flops, nbytes = mod.call_cost([1, 1, 1], di=di, state=n, carried=True)
    assert flops == 3 * 7 * di * n
    per_token = di * 2 + di * 4 + 2 * n * 4 + di * 2
    per_row = 2 * di * n * 4
    per_call = di * n * 4 + di * 2
    assert nbytes == 3 * per_token + 3 * per_row + per_call
    # Prefill: one row of 100 real tokens, the final state written once.
    flops, nbytes = mod.call_cost([100], di=di, state=n, carried=False)
    assert flops == 100 * 7 * di * n
    assert nbytes == 100 * per_token + di * n * 4 + per_call


# ------------------------------------------------------------ peaks

def test_peaks_known_and_unknown_device_kind():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


# ------------------------------------------------------ trace reduction

def test_trace_reduction_on_recorded_chip_trace(tmp_path):
    """A 4 s window of phi3-chat-steady traced on a TPU v5e (run.py
    --trace 1), reduced again here; ``expected.json`` holds what the
    harness read from it on the chip."""
    import gzip
    from harness import kernels
    from harness import trace as tracemod
    rec = HERE / "data"
    expect = json.loads((rec / "expected.json").read_text())
    with gzip.open(rec / "phi3-chat-steady.xplane.pb.gz", "rb") as f:
        (tmp_path / "t.xplane.pb").write_bytes(f.read())
    tr = tracemod.load(tmp_path, [], None)
    assert tr.window_s() == pytest.approx(expect["window_s"], rel=1e-12)
    assert tr.busy_s() == pytest.approx(expect["busy_s"], rel=1e-12)
    assert 0 < tr.busy_s() <= tr.window_s()
    dec = tr.module_events(kernels.is_decode_module)
    pre = tr.module_events(kernels.is_prefill_module)
    assert len(dec) == expect["decode_runs"] > 0
    assert len(pre) == expect["prefill_runs"]
    paged = tr.op_events(kernels.is_paged_decode_kernel)
    assert len(paged) == expect["paged_decode_calls"]
    # One paged attention call per layer in every decode step run.
    assert len(paged) == expect["layers"] * len(dec)
    # Every kernel call lies inside a decode step's run.
    runs = sorted((s, e) for _, _, s, e in dec)
    assert all(any(s <= a and b <= e for s, e in runs)
               for _, _, a, b in paged)
    assert tr.breakdown() == expect["breakdown"]


def test_union_of_intervals():
    from harness.trace import union, total
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert total(union([(0, 10), (2, 4)])) == 10


# ------------------------------------------------------ discovery

def test_new_config_traffic_and_metric_found_by_name(tmp_path):
    root = _tiny_root(tmp_path, TINY_DENSE, "phi3-mini-3.8b.py")
    base = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    # A later PR adds files only.
    (base / "configs" / "tiny-two.json").write_text(
        json.dumps(dict(TINY_DENSE, name="tiny-two")))
    shutil.copy(BENCH / "configs" / "phi3-mini-3.8b.py",
                base / "configs" / "tiny-two.py")
    (base / "traffic" / "tiny-burst.json").write_text(json.dumps(TINY_MIX))
    (base / "metrics" / "answer.tiny.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-two-cell", "config": "tiny-two",
                               "traffic": "tiny-burst", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answer.tiny", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "Device (TPU v5e)",
                               "moves": "itl_mean_ms",
                               "workloads": ["tiny-two-cell"]})
    assert all(p.read_bytes() == b for p, b in before.items())
    sizes, ref = spec.load_config("tiny-two", base)
    assert sizes["hidden_size"] == 64 and hasattr(ref, "reference_logits")
    assert spec.load_traffic("tiny-burst", base)["knee_rps"] == 2.0
    names = [m["name"] for m in spec.per_layer_for(bench, "tiny-two-cell")]
    assert names == ["answer.tiny"]
    assert spec.metric_reader("answer.tiny", base).read({}) == 42.0
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("not-there", base)


# ------------------------------------------------------ run.py

def test_run_exits_nonzero_on_the_cpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(ROOT)}
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "phi3-chat-steady", "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


# --------------------------------------- the comparison behind `correct`

@pytest.mark.parametrize("sizes,reference", [
    (TINY_DENSE, "phi3-mini-3.8b.py"), (TINY_MAMBA, "falcon-mamba-7b.py")],
    ids=["dense", "mamba"])
def test_sound_tiny_run_is_correct(tmp_path, capsys, sizes, reference):
    out = _run_tiny(tmp_path, capsys, sizes, reference)
    assert out["correct"] is True, out
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"itl_mean_ms", "output_tok_s", "setup_s"}
    assert out["checks"]["max_logit_gap"]["value"] <= \
        sizes["limits"]["max_logit_gap"]
    assert out["attempted"] >= 4 and out["failed"] == 0


def _break_decode(monkeypatch, fault):
    """Break the program's decode step underneath the engine."""
    from repro.models import transformer
    real = transformer.decode_step

    def broken(params, cfg, cache, tokens, pos, **kw):
        logits, new_cache = real(params, cfg, cache, tokens, pos, **kw)
        if fault == "state_unchanged":
            return logits, cache
        # A token altered where it is produced.
        return logits.at[..., 3].add(1e4), new_cache
    monkeypatch.setattr(transformer, "decode_step", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
@pytest.mark.parametrize("sizes,reference", [
    (TINY_DENSE, "phi3-mini-3.8b.py"), (TINY_MAMBA, "falcon-mamba-7b.py")],
    ids=["dense", "mamba"])
def test_broken_decode_is_not_correct(tmp_path, capsys, monkeypatch,
                                      sizes, reference, fault):
    _break_decode(monkeypatch, fault)
    out = _run_tiny(tmp_path, capsys, sizes, reference)
    assert out["correct"] is False, out
    assert out["checks"]["max_logit_gap"]["value"] > \
        sizes["limits"]["max_logit_gap"]


@pytest.mark.parametrize("gaps,failed,correct", [
    ({"max_logit_gap": 0.01, "tokens_checked": 300}, 0, True),
    ({"max_logit_gap": 0.06, "tokens_checked": 300}, 0, False),
    ({"max_logit_gap": 0.0, "tokens_checked": 0}, 0, False),
    ({"max_logit_gap": 0.01, "tokens_checked": 300}, 1, False),
])
def test_judge_holds_every_check(gaps, failed, correct):
    checks, ok = check.judge(gaps, {"max_logit_gap": 0.05, "min_tokens": 1},
                             failed)
    assert ok is correct
    assert checks["max_logit_gap"] == {"value": gaps["max_logit_gap"],
                                       "limit": 0.05}
    assert set(checks) == {"max_logit_gap", "served_tokens_checked",
                           "failed_requests"}
    with pytest.raises(ValueError):
        check.judge(gaps, {"min_tokens": 1}, failed)


def test_control_is_judged_by_the_same_comparison(tmp_path):
    """``control=True`` (``control.py``) holds the int8 control's
    readings to the configuration's limits through ``judge``, beside the
    program's own."""
    import jax
    from harness import cli
    root = _tiny_root(tmp_path, TINY_DENSE, "phi3-mini-3.8b.py")
    bench = spec.load_benchmark(root)
    res = cli.run_cell(bench, spec.workload(bench, "tiny-cell"), root,
                       jax.devices()[:1], seed=5, seconds=3, trace=False,
                       out_root=tmp_path / "out",
                       t_start=time.perf_counter(), control=True)
    ctl = res["control"]
    readings = ctl["readings"]
    assert res["checks"]["max_logit_gap"]["value"] == \
        readings["max_logit_gap"]
    expect, ok = check.judge(check.control_gaps(readings),
                             TINY_DENSE["limits"], res["failed"])
    assert ctl["checks"] == expect and ctl["correct"] is ok
    assert ctl["checks"]["max_logit_gap"]["value"] == \
        readings["control_max_logit_gap"]
    assert res["correct"] is True


WIDE = {"phi3-mini-3.8b.py": dict(num_attention_heads=4, num_key_value_heads=4,
                                  rms_norm_eps=1e-5, rope_theta=1e4),
        "falcon-mamba-7b.py": dict(state_size=16, conv_kernel=4,
                                   time_step_rank=32,
                                   layer_norm_epsilon=1e-5)}


@pytest.mark.parametrize("reference", sorted(WIDE), ids=["mamba", "dense"])
def test_int8_control_reads_above_a_sound_run(reference):
    """The control: the reference computed in int8 in the program's place.
    At a width a CPU test holds (d 512, vocab 32064, 2 layers) a sound
    run, here the float32 reference's own greedy tokens, reads 0, and the
    int8 control reads a gap well above float32 rounding at the same
    positions; on the chip, at the cells' sizes, its readings set the
    upper end of each limit (PERF.md)."""
    import jax
    sizes = dict(WIDE[reference], hidden_size=512, intermediate_size=1024,
                 num_hidden_layers=2, vocab_size=32064)
    ref = spec._module(BENCH / "configs" / reference, "config")
    params = ref.make_params(sizes, 11)
    rng = np.random.default_rng(0)
    sample = []
    for n in (9, 17, 30):
        seq = np.zeros((64,), np.int32)
        seq[:n] = rng.integers(1, sizes["vocab_size"], n)
        for i in range(n, n + 24):
            lg = ref.reference_logits(params, sizes, seq)
            seq[i] = int(jax.numpy.argmax(lg[i - 1]))
        sample.append((seq[:n].copy(), seq[n:n + 24].copy()))
    res = check.served_gaps(ref, sizes, params, sample, 64, control=True)
    assert res["tokens_checked"] == 72
    assert res["max_logit_gap"] <= 1e-5
    assert res["control_max_logit_gap"] > 1e-3
