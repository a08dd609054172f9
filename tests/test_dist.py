"""Distribution tests (8 host devices via subprocess): sharded train step
numerics == single-device, pipeline parallelism == sequential reference,
dry-run smoke on a small mesh."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=900)
    assert "OK" in r.stdout, f"stdout={r.stdout[-2000:]}\nstderr={r.stderr[-3000:]}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.configs.base import reduced
from repro.dist import sharding as shd
from repro.dist.constrain import activation_sharding
from repro.lm import model as model_mod
from repro.train import step as step_mod

cfg = reduced(get_config("yi_9b"), remat=False, n_layers=2)
params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
opt = step_mod.init_opt(params)
oc = step_mod.OptConfig(compute_dtype="float32", lr=1e-3)
fn = step_mod.make_train_step(cfg, oc)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}

# single device reference
p1, o1, m1 = jax.jit(fn)(params, opt, batch)

# 2x4 mesh
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
specs = shd.param_specs(params)
specs = shd.enforce_divisibility(
    jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
    specs, mesh)
shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                     is_leaf=lambda x: isinstance(x, P))
params_s = jax.device_put(params, shard)
opt_s = {"m": jax.device_put(opt["m"], shard),
         "v": jax.device_put(opt["v"], shard),
         "step": opt["step"]}
batch_s = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
with jax.set_mesh(mesh), activation_sharding(("data", "model")):
    p2, o2, m2 = jax.jit(fn)(params_s, opt_s, batch_s)
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=2e-5)
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5)
print("OK")
""")


def test_pipeline_parallel_matches_sequential():
    _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.dist.pipeline import pipeline_apply
mesh = jax.make_mesh((4,), ("pipe",),
                     axis_types=(jax.sharding.AxisType.Auto,))
S, M, mb, d = 4, 6, 3, 16
w = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
stage = lambda p, h: jnp.tanh(h @ p["w"])
out = pipeline_apply(stage, {"w": w}, x, mesh)
ref = x
for s in range(S):
    ref = jnp.tanh(ref @ w[s])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)
print("OK")
""")


def test_dryrun_smoke_reduced_config():
    """dryrun.py machinery on the production 512-device mesh with a reduced
    config (fast compile) — exercises the full lower/compile/analyze path."""
    _run("""
import sys; sys.path.insert(0, "src")
from repro.launch.dryrun import run
import tempfile, os, json
out = os.path.join(tempfile.mkdtemp(), "dr.json")
failures = run(["olmo_1b"], ["train_4k"], ["single"], out, reduced_for_test=True)
r = json.load(open(out))
cell = r["olmo_1b|train_4k|single"]
assert failures == 0 and cell["status"] == "ok"
assert cell["per_device"]["flops"] > 0
assert cell["per_device"]["collective_bytes"]["total"] > 0
print("OK")
""")
