"""The main-path Pallas kernels compile for a TPU v5e (Mosaic, not the
interpreter) at the widths the serving engine runs them.

No chip is needed: the TPU compiler builds for a *described* ``v5e:2x2``
topology.  That catches what interpret mode cannot — tiles that are not
sublane/lane aligned, blocks over the scoped-VMEM limit — and proves the
executable holds a ``tpu_custom_call``.  Widths are phi3-mini-3.8b's
(32 heads of 96) for attention and falcon-mamba-7b's (d_inner 8192,
N 16) for the selective scan, at the kernels' default blocks and at the
largest candidate each tuner ranks (``core/tuner.py``).

The topology is described inside a fixture and never at import time:
only one process may load the TPU library, and the suite runs under
several pytest-xdist workers.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.ssm_scan.kernel import ssm_scan_pallas

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
HEADS, HEAD_DIM = 32, 96            # phi3-mini-3.8b
D_INNER, STATE = 8192, 16           # falcon-mamba-7b


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("seq,block_q,block_kv", [
    (512, 128, 128),      # kernel default blocks
    (2048, 512, 1024),    # largest tuner candidate
])
def test_flash_attention_with_starts(one_chip, seq, block_q, block_kv):
    qkv = ((1, HEADS, seq, HEAD_DIM), BF16)
    text = _compile_text(
        lambda q, k, v, st: flash_attention_pallas(
            q, k, v, starts=st, block_q=block_q, block_kv=block_kv,
            interpret=False),
        one_chip, qkv, qkv, qkv, ((1,), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("seq,block_kv", [
    (1024, 256),          # kernel default block
    (4096, 2048),         # largest tuner candidate
])
def test_decode_attention(one_chip, seq, block_kv):
    cache = ((8, HEADS, seq, HEAD_DIM), BF16)
    text = _compile_text(
        lambda q, k, v, p: decode_attention_pallas(
            q, k, v, p, block_kv=block_kv, interpret=False),
        one_chip, ((8, HEADS, 1, HEAD_DIM), BF16), cache, cache,
        ((8,), I32))
    assert "tpu_custom_call" in text


def test_paged_decode_attention(one_chip):
    pool = ((512, HEADS, 16, HEAD_DIM), BF16)
    text = _compile_text(
        lambda q, k, v, t, p: paged_decode_attention_pallas(
            q, k, v, t, p, interpret=False),
        one_chip, ((8, HEADS, 1, HEAD_DIM), BF16), pool, pool,
        ((8, 64), I32), ((8,), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("seq,block_d", [
    (1, 128),             # decode step, kernel default block
    (512, 128),           # prefill, kernel default block
    (512, D_INNER),       # prefill, largest tuner candidate
])
def test_ssm_scan(one_chip, seq, block_d):
    """Dtypes as ``models/ssm.py`` passes them: x bf16, dt/B/C/A f32,
    D bf16 (a weight), state f32."""
    text = _compile_text(
        lambda x, dt, b, c, a, d, h0: ssm_scan_pallas(
            x, dt, b, c, a, d, h0=h0, block_d=block_d, interpret=False),
        one_chip,
        ((1, seq, D_INNER), BF16), ((1, seq, D_INNER), F32),
        ((1, seq, STATE), F32), ((1, seq, STATE), F32),
        ((D_INNER, STATE), F32), ((D_INNER,), BF16),
        ((1, D_INNER, STATE), F32))
    assert "tpu_custom_call" in text
