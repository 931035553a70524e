"""Smoke run of the serving engine on a TPU, through its normal entry points.

    python chip_smoke.py                # one chip: phases 1 and 2
    python chip_smoke.py --four-chips   # four chips: sharded training only

Phase 1 builds phi3-mini-3.8b at its published widths (random bf16
weights from ``--seed``) and serves a few greedy requests through
``ServeSession(backend="pallas", dispatch=...)``, the path that
``python -m repro.launch.serve --session --dispatch --backend pallas``
takes, then the same requests through a ``backend="reference"`` session.
It fails unless every request completes, nothing fell back or degraded,
the compiled prefill and decode executables hold Mosaic kernels
(``tpu_custom_call``), and the pallas prefill logits agree with the
reference backend's.

Phase 2 runs each main-path kernel against its ``ref.py`` oracle on the
chip: flash attention with left-pad ``starts``, decode attention and
paged decode at phi3-mini widths, and the selective scan at
falcon-mamba-7b widths (sequence 512 and a one-step continuation from a
carried state; the default channel block and the largest tuner block).

``--four-chips`` runs only the sharded path: a few Trainer steps of
phi3-mini at published widths (depth cut to fit one chip) on a 2x2
``(data, model)`` mesh, against the same steps on one chip.

Everything goes to stdout; the last line is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  With no TPU,
or when any check fails, the script exits nonzero and prints no such
line.  Tokens per second printed here come from a smoke run, not from a
benchmark.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import pathlib
import sys
import time
import traceback
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ENGINE_ARCH = "phi3-mini-3.8b"
# Pallas and reference prefill differ only in how attention rounds: the
# flash kernel scales q in bf16 and accumulates an online softmax, XLA
# takes a one-pass softmax.  bf16 keeps 8 significant bits (one rounding
# is <= 2^-9 relative); 32 layers that each round differently add about
# sqrt(32) such errors to the residual stream.  5e-2 of the logits' RMS
# leaves ~2.5x headroom over that estimate; a wrong mask or a wrong
# block is an O(1) error.
LOGIT_RTOL = 5e-2
# A kernel against its oracle: inputs are bf16 and the kernel writes its
# output in bf16 (<= 2^-9 relative rounding); the f32 accumulations run
# in a different order.  2e-2 of the output's scale bounds both.
KERNEL_RTOL = 2e-2
# Sharded against one-chip training: the same bf16 weights and batch;
# only where partial sums are reduced differs.  A loss near ln(vocab)
# ~ 10.4 moves by ~1e-3 from that; 2e-2 absolute (0.2%) is the bound.
LOSS_ATOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ phase 1

@dataclasses.dataclass(frozen=True)
class Traffic:
    """The requests phase 1 serves.  Prompt lengths fall in two buckets
    (128 and 512) and the engine has ``rows`` rows, which bounds the
    number of full-model compiles to two prefills and one decode step
    per backend."""

    prompt_lens: Sequence[int] = (100, 128, 77, 300, 480, 512, 120, 400)
    new_tokens: Sequence[int] = (16, 32, 64, 16, 32, 64, 48, 24)
    rows: int = 4


SMOKE_TRAFFIC = Traffic(prompt_lens=(5, 8, 12, 16), new_tokens=(4, 3, 4, 2),
                        rows=2)


def _compile_seconds(telemetry) -> Dict[str, float]:
    return {e["args"]["what"]: e["dur"] / 1e6
            for e in telemetry.tracer.events
            if e.get("name") == "serve.aot_compile"}


def _rel_rms(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.sqrt(np.mean((got - ref) ** 2))
                 / max(np.sqrt(np.mean(ref ** 2)), 1e-30))


def phase_engine(cfg, traffic: Traffic = Traffic(), *, seed: int = 0,
                 out_dir: pathlib.Path, mosaic: bool = True) -> List[str]:
    """Serve ``traffic`` on both backends; returns the failed checks.
    ``mosaic`` requires ``tpu_custom_call`` in the pallas executables
    (False only where the kernels are interpreted, on the CPU)."""
    from repro.core.registry import TuningRegistry
    from repro.models import build_model
    from repro.models.model_zoo import bucket_length, left_pad_prompts
    from repro.obs import Telemetry
    from repro.runtime.dispatch import DispatchService
    from repro.serving import RequestState, ServeSession

    failures: List[str] = []
    model = build_model(cfg)
    t0 = time.perf_counter()
    params, _ = model.init(jax.random.key(seed))
    jax.block_until_ready(params)
    n_params = sum(int(p.size) for p in jax.tree.leaves(params))
    log(f"phase 1: {cfg.name} ({cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}): "
        f"{n_params / 1e9:.3f}B random params in "
        f"{time.perf_counter() - t0:.1f}s")

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in traffic.prompt_lens]
    registry = out_dir / "registry.jsonl"
    registry.unlink(missing_ok=True)
    runs = {}
    for backend in ("pallas", "reference"):
        telemetry = Telemetry()
        session = ServeSession(
            model, params, backend=backend,
            dispatch=(DispatchService(TuningRegistry(str(registry)))
                      if backend == "pallas" else None),
            batch_sizes=(traffic.rows,), telemetry=telemetry)
        ids = [session.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, traffic.new_tokens)]
        t0 = time.perf_counter()
        results = {r.request_id: r for r in session.drain()}
        wall = time.perf_counter() - t0
        runs[backend] = (session, [results[i] for i in ids])
        st = session.stats
        states = [results[i].state for i in ids]
        log(f"  {backend}: {states.count(RequestState.COMPLETED)}/"
            f"{len(ids)} completed in {wall:.1f}s (compiles included); "
            f"fallbacks={st.fallbacks} compile_retries="
            f"{st.compile_retries} degraded={st.degraded} "
            f"re-AOTs={st.recompiles}")
        for what, sec in sorted(_compile_seconds(telemetry).items()):
            log(f"    compile {what}: {sec:.1f}s")
        log(f"    smoke-run figure, not a benchmark result: "
            f"{st.tokens_generated} tokens over {st.decode_s:.2f}s of "
            f"decode steps = {st.tokens_generated / max(st.decode_s, 1e-9):.1f}"
            f" tok/s")
        if any(s != RequestState.COMPLETED for s in states):
            failures.append(f"{backend}: requests not COMPLETED: "
                            + ", ".join(f"{results[i].state}: "
                                        f"{results[i].reason}"
                                        for i in ids
                                        if results[i].state
                                        != RequestState.COMPLETED))
        if st.fallbacks or st.compile_retries or st.degraded:
            failures.append(f"{backend}: fallbacks={st.fallbacks} "
                            f"compile_retries={st.compile_retries} "
                            f"degraded={st.degraded}")

    # The compiled code: every executable was AOT-compiled, and on the
    # chip the pallas ones launch Mosaic kernels.
    pallas_session, pallas_results = runs["pallas"]
    for key, exe in pallas_session.exec_cache.items():
        name = f"{key.role}[b{key.batch},{key.length}]"
        if not hasattr(exe, "as_text"):
            failures.append(f"pallas {name} is not an AOT executable")
            continue
        kernels = exe.as_text().count("tpu_custom_call")
        log(f"  pallas {name}: {kernels} tpu_custom_call site(s)")
        if mosaic and not kernels:
            failures.append(f"pallas {name} has no tpu_custom_call: the "
                            f"kernels were not compiled by Mosaic")

    # Prefill logits, pallas against reference, on one prompt per bucket.
    def prefill_exe(session, p_len):
        return next(exe for key, exe in session.exec_cache.items()
                    if key.role == "prefill" and key.length == p_len)

    seen = set()
    for prompt in prompts:
        p_len = bucket_length(len(prompt))
        if p_len in seen:
            continue
        seen.add(p_len)
        toks = {"tokens": jnp.asarray(left_pad_prompts([prompt], p_len))}
        start = p_len - len(prompt)
        starts = jnp.asarray([start], jnp.int32)
        got, ref = (np.asarray(prefill_exe(runs[b][0], p_len)(
            params, toks, starts)[0][0, start:], np.float32)
            for b in ("pallas", "reference"))
        finite = bool(np.isfinite(got).all() and np.isfinite(ref).all())
        rel = _rel_rms(got, ref)
        same_top = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
        log(f"  prefill logits p{p_len} ({len(prompt)} real tokens): "
            f"relative RMS difference {rel:.3e} (limit {LOGIT_RTOL:g}), "
            f"max |diff| {np.abs(got - ref).max():.3e}, argmax agreement "
            f"{same_top:.3f}, finite={finite}")
        if not finite or not rel <= LOGIT_RTOL:
            failures.append(f"prefill logits p{p_len}: finite={finite}, "
                            f"relative RMS difference {rel:.3e} > "
                            f"{LOGIT_RTOL:g}")

    same = total = first = 0
    for rp, rr in zip(pallas_results, runs["reference"][1]):
        n = min(len(rp.tokens), len(rr.tokens))
        same += int(np.sum(rp.tokens[:n] == rr.tokens[:n]))
        total += n
        first += int(n > 0 and rp.tokens[0] == rr.tokens[0])
    log(f"  greedy tokens equal between backends: {same}/{total} "
        f"(first token {first}/{len(pallas_results)}); random weights "
        f"leave near-ties, so this is reported, not checked")
    return failures


# ------------------------------------------------------------ phase 2

@dataclasses.dataclass(frozen=True)
class KernelWidths:
    """Kernel shapes for phase 2: attention at phi3-mini-3.8b widths,
    the selective scan at falcon-mamba-7b widths."""

    heads: int = 32
    head_dim: int = 96
    prefill_len: int = 512
    decode_rows: int = 8
    cache_len: int = 2048
    pool_blocks: int = 512
    block_size: int = 16
    d_inner: int = 8192
    state: int = 16
    scan_len: int = 512
    scan_blocks: Sequence[int] = (128, 8192)   # default, largest tuned


SMOKE_WIDTHS = KernelWidths(heads=2, head_dim=16, prefill_len=32,
                            decode_rows=2, cache_len=64, pool_blocks=16,
                            block_size=16, d_inner=256, state=8,
                            scan_len=32, scan_blocks=(128, 256))


def _check(name: str, got, ref, failures: List[str]) -> None:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1.0))
    ok = bool(np.isfinite(got).all()) and err <= KERNEL_RTOL
    log(f"  {name}: max |diff| / scale {err:.3e} (limit {KERNEL_RTOL:g})"
        f"{'' if ok else '  FAIL'}")
    if not ok:
        failures.append(f"{name}: error {err:.3e} > {KERNEL_RTOL:g}")


def phase_kernels(w: KernelWidths = KernelWidths(), *, seed: int = 0
                  ) -> List[str]:
    """Each main-path kernel against its oracle; returns failed checks."""
    from repro.kernels.decode_attention import (decode_attention_pallas,
                                                decode_attention_ref,
                                                paged_decode_attention_pallas)
    from repro.kernels.flash_attention import flash_attention_pallas, mha_ref
    from repro.kernels.ssm_scan import ssm_scan_pallas, ssm_scan_ref

    failures: List[str] = []
    keys = iter(jax.random.split(jax.random.key(seed), 16))

    def normal(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def oracle(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    h, d = w.heads, w.head_dim
    log(f"phase 2: kernels against their oracles (attention {h} heads of "
        f"{d}; scan d_inner {w.d_inner}, state {w.state})")

    s = w.prefill_len
    q, k, v = (normal((2, h, s, d)) for _ in range(3))
    starts = [0, s // 5]
    out = jax.jit(flash_attention_pallas)(q, k, v,
                                          starts=jnp.asarray(starts))
    for b, s0 in enumerate(starts):
        ref = oracle(mha_ref, q[b:b + 1, :, s0:], k[b:b + 1, :, s0:],
                     v[b:b + 1, :, s0:])
        _check(f"flash_attention S={s} starts={s0}",
               out[b:b + 1, :, s0:], ref, failures)

    r, s = w.decode_rows, w.cache_len
    q = normal((r, h, 1, d))
    k, v = normal((r, h, s, d)), normal((r, h, s, d))
    pos = np.random.default_rng(seed).integers(s // 2, s, r)
    for block in (256, 2048):
        out = jax.jit(decode_attention_pallas, static_argnames="block_kv")(
            q, k, v, jnp.asarray(pos, jnp.int32), block_kv=block)
        ref = jnp.concatenate([oracle(decode_attention_ref, q[i:i + 1],
                                      k[i:i + 1], v[i:i + 1], int(pos[i]))
                               for i in range(r)])
        _check(f"decode_attention S={s} block_kv={min(block, s)}", out, ref,
               failures)

    # Each row owns distinct blocks of the pool (block 0 is the sink).
    bs = w.block_size
    mb = min(w.cache_len // bs, (w.pool_blocks - 1) // r)
    pos = np.minimum(pos, mb * bs - 1)
    pool_k = normal((w.pool_blocks, h, bs, d))
    pool_v = normal((w.pool_blocks, h, bs, d))
    perm = np.random.default_rng(seed + 1).permutation(w.pool_blocks - 1) + 1
    tables = perm[:r * mb].reshape(r, mb).astype(np.int32)
    out = jax.jit(paged_decode_attention_pallas)(
        q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(pos, jnp.int32))

    def gather(pool, row):
        return pool[row].transpose(1, 0, 2, 3).reshape(1, h, mb * bs, d)

    ref = jnp.concatenate([oracle(decode_attention_ref, q[i:i + 1],
                                  gather(pool_k, tables[i]),
                                  gather(pool_v, tables[i]), int(pos[i]))
                           for i in range(r)])
    _check(f"paged_decode_attention pool {w.pool_blocks}x{bs}", out, ref,
           failures)

    # Dtypes as models/ssm.py passes them.
    L, di, n = w.scan_len, w.d_inner, w.state
    x = normal((1, L, di))
    dt = jnp.abs(normal((1, L, di), jnp.float32)) * 0.1
    bm, cm = normal((1, L, n), jnp.float32), normal((1, L, n), jnp.float32)
    a = -jnp.exp(normal((di, n), jnp.float32))
    dd = normal((di,))
    ref = oracle(ssm_scan_ref, x, dt, bm, cm, a, dd)
    scan = jax.jit(ssm_scan_pallas, static_argnames="block_d")
    for block in w.scan_blocks:
        y, _ = scan(x, dt, bm, cm, a, dd, block_d=block)
        _check(f"ssm_scan S={L} block_d={block}", y, ref, failures)
        _, h_prev = scan(x[:, :-1], dt[:, :-1], bm[:, :-1], cm[:, :-1], a,
                         dd, block_d=block)
        y1, _ = scan(x[:, -1:], dt[:, -1:], bm[:, -1:], cm[:, -1:], a, dd,
                     h0=h_prev, block_d=block)
        _check(f"ssm_scan S=1 from a carried state block_d={block}", y1,
               ref[:, -1:], failures)
    return failures


# ------------------------------------------------------ four chips

def phase_four_chips(cfg, *, steps: int = 4, batch: int = 8,
                     seq: int = 512, seed: int = 0) -> List[str]:
    """Trainer steps on a 2x2 (data, model) mesh against the same steps
    meshless on one chip; returns the failed checks."""
    from repro.data import DataConfig
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.runtime.train_loop import TrainConfig, Trainer

    if len(jax.devices()) < 4:
        return [f"--four-chips needs 4 devices, found {len(jax.devices())}"]
    n = cfg.param_count()
    log(f"four chips: {cfg.name} cut to {cfg.n_layers} layers at published "
        f"widths; {n / 1e9:.2f}B params: {2 * n / 1e9:.1f} GB bf16 weights "
        f"+ {2 * n / 1e9:.1f} GB grads + {8 * n / 1e9:.1f} GB AdamW m/v "
        f"(f32) on the one-chip run; batch {batch} x seq {seq}")
    model = build_model(cfg)
    losses = {}
    for name, mesh in (("one chip", None),
                       ("2x2 mesh", make_mesh((2, 2), ("data", "model")))):
        t0 = time.perf_counter()
        out = Trainer(model, TrainConfig(steps=steps, seed=seed,
                                         warmup_steps=1),
                      DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                 global_batch=batch, seed=seed),
                      mesh=mesh).run()
        losses[name] = [h["loss"] for h in out["history"]]
        del out
        log(f"  {name}: losses {['%.6f' % x for x in losses[name]]} "
            f"({time.perf_counter() - t0:.1f}s, compile included)")
    diff = float(np.max(np.abs(np.subtract(losses["one chip"],
                                           losses["2x2 mesh"]))))
    finite = bool(np.isfinite(losses["2x2 mesh"]).all())
    log(f"  max per-step |loss difference| {diff:.3e} (limit {LOSS_ATOL:g})")
    if not finite or not diff <= LOSS_ATOL:
        return [f"sharded losses differ from one chip by {diff:.3e} "
                f"(finite={finite})"]
    return []


# ------------------------------------------------------------ main

def _run_phase(name: str, fn, failures: List[str]) -> None:
    t0 = time.perf_counter()
    try:
        failures.extend(f"{name}: {f}" for f in fn())
    except Exception:
        traceback.print_exc(file=sys.stdout)
        failures.append(f"{name} raised (traceback above)")
    log(f"{name} done in {time.perf_counter() - t0:.1f}s")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training comparison on a "
                         "2x2 mesh of four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir", default=str(ROOT / "artifacts"
                                             / "chip_smoke"),
                    help="where the run keeps its tuning registry")
    args = ap.parse_args(argv)
    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this script only runs on a chip", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    # JAX reports each persistent-cache lookup as a monitoring event
    # (compiles too short for JAX to cache count as neither).
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update([event]))
    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    log(f"device: {dev.device_kind} x{len(devices)}; jax {jax.__version__};"
        f" compile cache {cache_dir}")

    failures: List[str] = []
    if args.four_chips:
        cfg = dataclasses.replace(get_config(ENGINE_ARCH), n_layers=4)
        _run_phase("four chips", lambda: phase_four_chips(
            cfg, seed=args.seed), failures)
    else:
        _run_phase("phase 1", lambda: phase_engine(
            get_config(ENGINE_ARCH), seed=args.seed, out_dir=out_dir),
            failures)
        _run_phase("phase 2", lambda: phase_kernels(seed=args.seed),
                   failures)

    hits = cache_events["/jax/compilation_cache/cache_hits"]
    misses = cache_events["/jax/compilation_cache/cache_misses"]
    log(f"compile cache: {hits} hits, {misses} misses"
        f" ({'hit' if hits else 'no hits'})")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"peak device memory: {stats['peak_bytes_in_use'] / 2**30:.2f} "
            f"GiB of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    if failures:
        for f in failures:
            log(f"FAIL {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
