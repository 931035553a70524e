"""ISSUE 6 satellite: the pad-token attention leak, fixed and fenced.

Before this PR, left-padded rows let pad tokens participate in
attention (and in the SSM recurrence), so a padded prompt's logits —
and occasionally its greedy tokens — differed from the same prompt run
unpadded.  ``seq_starts`` threads a per-row first-real-token index
through prefill and decode; these tests pin the resulting guarantee:

* padded and unpadded prefill logits agree to float reduction order
  (``rtol = atol = 1e-5``) with the same argmax: XLA may block a
  length-8 matmul or reduction differently from a length-3 one, so the
  float summation order (not the masked values) differs; a pad leak is
  an O(1) error.  SSM-pallas is bit-identical (its tiled recurrence is
  length-invariant);
* full greedy generation through ``ServeSession.run_batch`` with
  ``seq_starts`` is token-for-token identical to unpadded solo runs,
  on both backends.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models.model_zoo import (build_model, left_pad_prompts,
                                    prompt_starts)
from repro.serving.session import ServeSession


def _smoke(arch):
    from repro.configs import get_config

    cfg = get_config(arch)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    return cfg, model, params


def _prompts(cfg, lengths, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def _solo_generate(model, params, prompt, n, backend):
    """Unpadded single-prompt greedy reference."""
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


def _padded_prefill_logits(model, params, prompts, target, backend):
    """Last-position logits per row of a masked left-padded prefill."""
    mb = "pallas" if backend == "pallas" else "xla"
    toks = left_pad_prompts(prompts, target)
    starts = jnp.asarray(prompt_starts(prompts, target))
    logits, _ = model.prefill(params, {"tokens": jnp.asarray(toks)},
                              backend=mb, seq_starts=starts)
    return np.asarray(logits[:, -1])


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_dense_padded_prefill_logits_bit_identical(backend):
    cfg, model, params = _smoke("phi3-mini-3.8b-smoke")
    prompts = _prompts(cfg, [3, 5, 8])
    padded = _padded_prefill_logits(model, params, prompts, 8, backend)
    mb = "pallas" if backend == "pallas" else "xla"
    for i, p in enumerate(prompts):
        solo, _ = model.prefill(
            params, {"tokens": jnp.asarray(p[None])}, backend=mb)
        solo = np.asarray(solo[0, -1])
        # Reduction order only (see module docstring): measured padded
        # vs solo differences are ~1e-6 absolute on this model.
        np.testing.assert_allclose(
            padded[i], solo, rtol=1e-5, atol=1e-5,
            err_msg=f"row {i} (len {len(p)}) leaked pad tokens")
        assert int(np.argmax(padded[i])) == int(np.argmax(solo))


def test_ssm_padded_prefill_logits_equivalent():
    cfg, model, params = _smoke("falcon-mamba-7b-smoke")
    prompts = _prompts(cfg, [3, 5, 8])
    # pallas scan: tiled recurrence is length-invariant -> bit-exact
    padded = _padded_prefill_logits(model, params, prompts, 8, "pallas")
    for i, p in enumerate(prompts):
        solo, _ = model.prefill(
            params, {"tokens": jnp.asarray(p[None])}, backend="pallas")
        np.testing.assert_array_equal(padded[i], np.asarray(solo[0, -1]))
    # XLA scan: associative_scan's reduction tree depends on S, so the
    # summation ORDER differs between padded (S=8) and unpadded (S=3)
    # runs — tight allclose plus exact argmax, not bit equality
    padded = _padded_prefill_logits(model, params, prompts, 8,
                                    "reference")
    for i, p in enumerate(prompts):
        solo, _ = model.prefill(
            params, {"tokens": jnp.asarray(p[None])}, backend="xla")
        solo = np.asarray(solo[0, -1])
        np.testing.assert_allclose(padded[i], solo, rtol=1e-5,
                                   atol=1e-5)
        assert int(np.argmax(padded[i])) == int(np.argmax(solo))


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b-smoke",
                                  "falcon-mamba-7b-smoke"])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_padded_generation_token_identical_to_solo(arch, backend):
    cfg, model, params = _smoke(arch)
    prompts = _prompts(cfg, [3, 6, 8])
    n = 5
    session = ServeSession(model, params, backend=backend)
    starts = prompt_starts(prompts, 8)
    batch = {"tokens": jnp.asarray(left_pad_prompts(prompts, 8))}
    out, _ = session.run_batch(batch, max_new_tokens=n,
                               seq_starts=starts)
    for i, p in enumerate(prompts):
        solo = _solo_generate(model, params, p, n, backend)
        assert out[i].tolist() == solo, (
            f"{arch}/{backend} row {i}: padded batch diverged from "
            f"unpadded solo run")


def test_seq_starts_rejected_for_unsupported_families():
    # hybrid mixes attention and rglru blocks and is not plumbed for
    # per-row masks; the family check must fire before any compute
    cfg, model, params = _smoke("recurrentgemma-9b-smoke")
    with pytest.raises(ValueError):
        model.prefill(params, {"tokens": jnp.zeros((1, 8), jnp.int32)},
                      seq_starts=jnp.zeros((1,), jnp.int32))
    cache = model.init_cache(1, 16)
    with pytest.raises(ValueError):
        model.decode_step(params, cache, jnp.zeros((1, 1), jnp.int32),
                          jnp.int32(8),
                          seq_starts=jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError):
        model.init_paged_cache(4, 4)
