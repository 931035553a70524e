"""ISSUE 6: block-paged KV cache invariants and the in-flight engine.

Covers the host-side allocator (alloc/free round-trips, the reserved
sink block, fragmentation + table compaction with its pool gather map),
the paged attention primitives (paged decode bit-identical to the
monolithic-cache decode, on both backends), and the serving engine's
contracts: out-of-blocks admission backpressure, compaction during a
live stream, and a request admitted mid-decode producing tokens
identical to running it alone.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.serving.paged_kv import (RESERVED_BLOCK, BlockAllocator,
                                    blocks_needed)
from repro.serving.session import ServeSession


def _smoke(arch="phi3-mini-3.8b-smoke"):
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(arch)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    return cfg, model, params


def _prompts(cfg, lengths, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def _solo_generate(model, params, prompt, n, backend):
    mb = "pallas" if backend == "pallas" else "xla"
    batch = {"tokens": jnp.asarray(np.asarray(prompt, np.int32)[None])}
    logits, cache = model.prefill(params, batch, backend=mb)
    full = model.init_cache(1, len(prompt) + n)

    def fit(dst, src):
        if dst.shape == src.shape:
            return src.astype(dst.dtype)
        sl = tuple(slice(0, s) for s in src.shape)
        return dst.at[sl].set(src.astype(dst.dtype))

    cache = jax.tree.map(fit, full, cache)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    out = [int(tok[0])]
    for i in range(n - 1):
        lg, cache = model.decode_step(params, cache, tok[:, None],
                                      jnp.int32(len(prompt) + i),
                                      backend=mb)
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
        out.append(int(tok[0]))
    return out


# ------------------------------------------------------- allocator


def test_blocks_needed_rounds_up_with_floor():
    assert blocks_needed(1, 4) == 1
    assert blocks_needed(4, 4) == 1
    assert blocks_needed(5, 4) == 2
    assert blocks_needed(0, 4) == 1  # even an empty row owns a block


def test_allocator_round_trip_and_reserved_sink():
    a = BlockAllocator(n_blocks=9, block_size=4)
    assert a.num_free == 8 and a.num_live == 0
    r1, r2 = a.alloc(3), a.alloc(5)
    # deterministic lowest-first order; block 0 never handed out
    assert r1 == [1, 2, 3] and r2 == [4, 5, 6, 7, 8]
    assert RESERVED_BLOCK not in r1 + r2
    assert a.alloc(1) is None  # exhausted -> backpressure signal
    a.free(r2)
    a.free(r1)
    assert a.num_free == 8 and a.num_live == 0
    assert a.alloc(2) == [1, 2]  # freed ids recycle lowest-first
    with pytest.raises(ValueError):
        a.free([RESERVED_BLOCK])
    with pytest.raises(ValueError):
        a.free([5])  # not live: double free
    with pytest.raises(ValueError):
        BlockAllocator(n_blocks=1, block_size=4)  # only the sink


def test_allocator_can_fit_tracks_free_blocks():
    a = BlockAllocator(n_blocks=5, block_size=4)
    assert a.can_fit(16)           # 4 blocks free
    assert not a.can_fit(17)       # would need 5
    a.alloc(3)
    assert a.can_fit(4) and not a.can_fit(5)


def test_compaction_repacks_tables_and_returns_gather_map():
    a = BlockAllocator(n_blocks=9, block_size=4)
    r1, r2, r3 = a.alloc(3), a.alloc(2), a.alloc(2)
    a.free(r2)  # live = {1,2,3,6,7} -> holes at 4,5
    frag = a.fragmentation()
    assert frag == pytest.approx(1.0 - 5 / 7)
    tables = np.zeros((2, 4), np.int32)
    tables[0, :3], tables[1, :2] = r1, r3
    blocks = [list(r1), list(r3)]
    perm, moved = a.compact_tables(tables, blocks)
    assert moved == 2
    # blocks 6,7 moved down to 4,5; tables/ownership rewritten in place
    assert blocks == [[1, 2, 3], [4, 5]]
    assert tables[1, :2].tolist() == [4, 5]
    assert tables[0, 3] == 0 and tables[1, 2] == 0  # sink untouched
    # gather semantics: new_pool[i] = old_pool[perm[i]]
    assert perm[4] == 6 and perm[5] == 7
    assert perm[RESERVED_BLOCK] == RESERVED_BLOCK
    assert a.fragmentation() == 0.0
    assert a._free == [6, 7, 8]  # contiguous tail
    # a no-op compaction reports zero moves
    perm2, moved2 = a.compact_tables(tables, blocks)
    assert moved2 == 0 and np.array_equal(perm2, np.arange(9))


# --------------------------------------- paged primitives vs monolithic


# (hq, hkv, d, lens, mb, junk_tail): bs = 4, one row per entry of lens.
# With junk_tail, table slots past a row's last live block point at the
# reserved block, filled with large finite values: a read of them that
# the validity mask lets through moves the output far off.
PAGED_CASES = {
    "gqa2": (4, 2, 8, (5, 9), 3, False),
    "group1": (4, 4, 8, (5, 9), 3, False),
    "group4": (8, 2, 8, (5, 9), 3, False),
    "d96": (4, 2, 96, (5, 9), 3, False),
    "pos0": (4, 2, 8, (0, 9), 3, True),
    "wide_table": (4, 2, 8, (5, 9), 6, True),
}


@pytest.mark.parametrize("case", list(PAGED_CASES))
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_paged_decode_matches_monolithic_cache(backend, case):
    """One decode step through block tables == the same step through a
    contiguous cache, for rows at different depths."""
    from repro.models import attention as attn

    hq, hkv, d, lens, mb, junk_tail = PAGED_CASES[case]
    rng = np.random.RandomState(0)
    b, bs = len(lens), 4
    n_blocks = 1 + b * mb
    s = mb * bs
    lens = np.array(lens, np.int32)  # per-row logical depth
    k = rng.randn(b, hkv, s, d).astype(np.float32)
    v = rng.randn(b, hkv, s, d).astype(np.float32)
    q = rng.randn(b, hq, 1, d).astype(np.float32)
    # contiguous reference: mask by per-row pos
    ref = attn.decode_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), jnp.asarray(lens),
                                backend=backend)
    # paged: scatter the same K/V into out-of-order pool blocks
    tables = np.full((b, mb), RESERVED_BLOCK, np.int32)
    order = 1 + np.random.RandomState(1).permutation(b * mb)
    pool_k = np.full((n_blocks, hkv, bs, d), 1e4 if junk_tail else 0.0,
                     np.float32)
    pool_v = pool_k.copy()
    for row in range(b):
        live = lens[row] // bs + 1 if junk_tail else mb
        for j in range(live):
            blk = order[row * mb + j]
            tables[row, j] = blk
            pool_k[blk] = k[row, :, j * bs:(j + 1) * bs]
            pool_v[blk] = v[row, :, j * bs:(j + 1) * bs]
    out = attn.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray(tables), jnp.asarray(lens), backend=backend)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# ----------------------------------------------- engine-level contracts


def test_engine_tokens_identical_to_solo_across_depths():
    cfg, model, params = _smoke()
    prompts = _prompts(cfg, [5, 7, 3, 6])
    budgets = [6, 3, 8, 1]
    session = ServeSession(model, params, backend="reference",
                           kv_block_size=4)
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        session.submit(p, b, request_id=f"r{i}")
    res = {r.request_id: r.tokens for r in session.drain()}
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        assert res[f"r{i}"].tolist() == _solo_generate(
            model, params, p, b, "reference")
    # the stream ran through the step-loop engine, one activation
    assert session.stats.batches == 1
    assert session.stats.inflight_admissions == 4
    assert session.stats.steps > 0


def test_mid_decode_admission_bit_identical_to_running_alone():
    cfg, model, params = _smoke()
    pA, pB = _prompts(cfg, [6, 5])
    session = ServeSession(model, params, backend="reference",
                           kv_block_size=4)
    session.submit(pA, 10, request_id="A")
    submitted = {}

    def on_step(info):
        # B arrives while A is mid-decode; the engine must admit it at
        # the next step boundary, not after A finishes
        if info["step"] == 3 and "B" not in submitted:
            submitted["B"] = info["step"]
            session.submit(pB, 4, request_id="B")

    res = {r.request_id: r for r in session.drain(on_step=on_step)}
    assert res["A"].tokens.tolist() == _solo_generate(
        model, params, pA, 10, "reference")
    assert res["B"].tokens.tolist() == _solo_generate(
        model, params, pB, 4, "reference")
    # B really was admitted in flight (same activation, 2 admissions)
    assert session.stats.batches == 1
    assert session.stats.inflight_admissions == 2


def test_out_of_blocks_admission_backpressure():
    cfg, model, params = _smoke()
    # each request needs ceil((5 + 4 - 1)/4) = 2 blocks; a 5-block pool
    # (4 usable) serves at most 2 requests concurrently
    session = ServeSession(model, params, backend="reference",
                           kv_block_size=4, kv_blocks=5,
                           batch_sizes=(4,))
    for i, p in enumerate(_prompts(cfg, [5, 5, 5, 5])):
        session.submit(p, 4, request_id=f"q{i}")
    concurrency = []
    res = session.drain(
        on_step=lambda info: concurrency.append(len(info["active"])))
    assert len(res) == 4
    assert max(concurrency) == 2  # block budget capped admission
    # FIFO order held under backpressure: q0/q1 retire before q2/q3
    order = [r.request_id for r in res]
    assert order.index("q0") < order.index("q2")
    assert order.index("q1") < order.index("q3")


def test_unservable_request_rejected_per_request():
    """A never-fits request is REJECTED with a reason instead of raising
    RuntimeError out of drain() (the pre-ISSUE-7 behaviour), and the
    engine keeps serving requests that do fit."""
    cfg, model, params = _smoke()
    session = ServeSession(model, params, backend="reference",
                           kv_block_size=4, kv_blocks=2)
    big, small = _prompts(cfg, [6, 3])
    session.submit(big, 8, request_id="big")
    session.submit(small, 2, request_id="small")
    res = {r.request_id: r for r in session.drain()}  # must not raise
    assert res["big"].state == "REJECTED"
    assert "kv_blocks" in res["big"].reason
    assert len(res["big"].tokens) == 0
    assert res["small"].state == "COMPLETED"
    assert res["small"].tokens.tolist() == _solo_generate(
        model, params, small, 2, "reference")
    assert session.stats.rejected == 1
    assert session.stats.requests == 2


def test_compaction_mid_stream_preserves_tokens():
    cfg, model, params = _smoke()
    # retire a long-lived neighbour early to punch holes in the pool:
    # small blocks + mixed budgets force free()s below live blocks, so
    # fragmentation crosses 1/2 and the engine compacts while rows are
    # still decoding — tokens must be unaffected by the pool permute
    prompts = _prompts(cfg, [5, 5, 5, 5, 5, 5])
    budgets = [2, 12, 2, 12, 2, 12]
    session = ServeSession(model, params, backend="reference",
                           kv_block_size=2, batch_sizes=(4,))
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        session.submit(p, b, request_id=f"c{i}")
    res = {r.request_id: r.tokens for r in session.drain()}
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        assert res[f"c{i}"].tolist() == _solo_generate(
            model, params, p, b, "reference"), f"row c{i} corrupted"
    assert session.stats.compactions >= 1


def test_engine_pallas_matches_reference_backend():
    cfg, model, params = _smoke()
    prompts = _prompts(cfg, [5, 7, 3])
    budgets = [4, 6, 5]

    def run(backend):
        s = ServeSession(model, params, backend=backend,
                         kv_block_size=4)
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            s.submit(p, b, request_id=f"r{i}")
        return {r.request_id: r.tokens.tolist() for r in s.drain()}

    assert run("pallas") == run("reference")
