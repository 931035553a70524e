"""Multi-device tests (subprocesses with XLA host-platform placeholder
devices — the main pytest process must keep seeing 1 device)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_meshed_train_step_executes():
    print(run_py("""
import jax, jax.numpy as jnp, functools
from repro.configs import get_config
from repro.models import build_model
from repro.optim import adamw
from repro.optim.schedule import constant
from repro.runtime import sharding as shd
from repro.runtime.train_loop import make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
rules = shd.ShardingRules()
cfg = get_config("phi3-mini-3.8b-smoke")
m = build_model(cfg)
params, axes = m.init(jax.random.key(0))
opt = adamw.init(params)
ns = lambda s: NamedSharding(mesh, s)
is_ax = lambda x: isinstance(x, tuple) and all(isinstance(e,(str,type(None))) for e in x)
p_sh = jax.tree.map(lambda ax, l: ns(shd.resolve_spec(ax, l.shape, mesh, rules)),
                    axes, params, is_leaf=is_ax)
m_sh = jax.tree.map(lambda ax, l: ns(shd.resolve_spec(ax, l.shape, mesh, rules)),
                    axes, opt.m, is_leaf=is_ax)
opt_sh = adamw.AdamWState(step=ns(P()), m=m_sh, v=m_sh)
step = jax.jit(make_train_step(m, adamw.AdamWConfig(lr=1e-3),
                               functools.partial(constant, peak_lr=1e-3),
                               shard_fn=shd.make_activation_shard_fn(mesh, rules)),
               in_shardings=(p_sh, opt_sh, None), donate_argnums=(0, 1))
batch = {"tokens": jnp.zeros((4, 16), jnp.int32),
         "labels": jnp.zeros((4, 16), jnp.int32)}
p2, o2, metrics = step(params, opt, batch)
loss = float(metrics["loss"])
assert loss == loss, "nan"
# at least one param leaf really sharded over model
sharded = any(getattr(l.sharding, "spec", None) is not None and
              "model" in str(l.sharding.spec) for l in jax.tree.leaves(p2))
print("OK loss=%.3f sharded=%s ndev=%d" % (loss, sharded, len(jax.devices())))
assert sharded
""", devices=4))


def test_grad_compress_allreduce_shard_map():
    print(run_py("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.optim import grad_compress

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
g = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16) / 100.0
err = jnp.zeros((8, 16), jnp.float32)

def f(gs, es):
    mean, new_err = grad_compress.allreduce_compressed(gs, es, "data")
    return mean, new_err

fm = jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                   out_specs=(P("data"), P("data")))
mean, new_err = fm(g, err)
expected = g.mean(axis=0)
got = np.asarray(mean)[0]
rel = np.abs(got - np.asarray(expected)).max() / (np.abs(expected).max() + 1e-9)
print("OK rel=%.4f" % rel)
assert rel < 0.02, rel
""", devices=8))


def test_dryrun_cell_small_mesh():
    print(run_py("""
import jax
from repro.configs import get_config, SHAPES
from repro.launch.mesh import make_mesh
from repro.launch import hlo_analysis
from repro.runtime import sharding as shd

# import dryrun AFTER jax init: its XLA_FLAGS line is then a no-op
import repro.launch.dryrun as dr

mesh = make_mesh((2, 4), ("data", "model"))
rules = shd.ShardingRules()
for arch, shape in [("qwen3-32b-smoke", "train_4k"),
                    ("falcon-mamba-7b-smoke", "decode_32k"),
                    ("qwen2-moe-a2.7b-smoke", "prefill_32k")]:
    import dataclasses
    cfg = get_config(arch)
    shp = dataclasses.replace(SHAPES[shape], seq_len=32, global_batch=8)
    out = dr.lower_compile(cfg, shp, mesh, rules)
    assert out["compile_s"] > 0
    print("OK", arch, shape, "coll_bytes=%.3g" % out["collective_bytes_per_chip"])
""", devices=8))


def test_elastic_resume_different_mesh():
    """Checkpoint saved from a (2,2) mesh restores onto (4,1)."""
    print(run_py("""
import tempfile, os, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import save, restore

from repro.launch.mesh import make_mesh
mesh_a = make_mesh((2, 2), ("data", "model"))
mesh_b = make_mesh((4, 1), ("data", "model"))
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
xa = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))
d = tempfile.mkdtemp()
save(os.path.join(d, "ck"), {"x": xa})
tree, _ = restore(os.path.join(d, "ck"), like={"x": x})
xb = jax.device_put(tree["x"], NamedSharding(mesh_b, P("data", "model")))
np.testing.assert_array_equal(np.asarray(xb), np.asarray(x))
print("OK elastic restore")
""", devices=8))
