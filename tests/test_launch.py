"""Launch-layer tests: checkpoint/restore (incl. elastic + corruption),
train driver resume, data pipeline determinism, compression numerics."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import DataConfig, ZipfPipeline
from repro.launch import ckpt as ckpt_mod
from repro.train.compress import (dequantize_int8, ef_compress_grads,
                                  quantize_int8)


def _tree():
    return {"a": jnp.arange(12.0).reshape(3, 4), "b": {"c": jnp.ones(5)}}


def test_checkpoint_roundtrip(tmp_path):
    params, opt = _tree(), {"m": _tree(), "step": jnp.int32(7)}
    ckpt_mod.save_checkpoint(str(tmp_path), 10, params, opt, 10,
                             jax.random.PRNGKey(1))
    out = ckpt_mod.restore_latest(str(tmp_path), params, opt)
    assert out["step"] == 10 and out["data_cursor"] == 10
    np.testing.assert_array_equal(out["params"]["a"], params["a"])
    np.testing.assert_array_equal(out["opt"]["step"], 7)


def test_checkpoint_keeps_last_k_and_skips_corrupt(tmp_path):
    params, opt = _tree(), {"step": jnp.int32(0)}
    for s in [1, 2, 3, 4]:
        ckpt_mod.save_checkpoint(str(tmp_path), s, params, opt, s,
                                 jax.random.PRNGKey(0), keep=3)
    names = ckpt_mod.list_checkpoints(str(tmp_path))
    assert names == ["ckpt_00000002", "ckpt_00000003", "ckpt_00000004"]
    # corrupt the newest: restore must fall back to the previous
    with open(os.path.join(str(tmp_path), "ckpt_00000004", "params.npz"),
              "wb") as f:
        f.write(b"garbage")
    out = ckpt_mod.restore_latest(str(tmp_path), params, opt)
    assert out["step"] == 3


def test_checkpoint_elastic_restore_other_mesh(tmp_path):
    """Save from default placement, restore onto an explicit 1-device
    sharding (the elastic path: mesh shape is a restore-time choice)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    params, opt = _tree(), {"step": jnp.int32(0)}
    ckpt_mod.save_checkpoint(str(tmp_path), 5, params, opt, 5,
                             jax.random.PRNGKey(0))
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), params)
    sho = jax.tree.map(lambda _: NamedSharding(mesh, P()), opt)
    out = ckpt_mod.restore_latest(str(tmp_path), params, opt,
                                  shardings={"params": sh, "opt": sho})
    np.testing.assert_array_equal(out["params"]["a"], params["a"])


def test_data_pipeline_deterministic_and_shardable():
    dc = DataConfig(vocab_size=1000, seq_len=32, batch_size=4)
    p1, p2 = ZipfPipeline(dc), ZipfPipeline(dc)
    b1, b2 = p1.batch(17), p2.batch(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # different shards differ
    b3 = p1.batch(17, shard=1, num_shards=2)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are shifted tokens
    full = p1.batch(3)
    np.testing.assert_array_equal(full["tokens"][:, 1:], full["labels"][:, :-1])


def test_quantize_roundtrip_error_bounded():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64,)).astype(np.float32))
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-6


def test_error_feedback_accumulates_residual():
    g = {"w": jnp.asarray([0.004, -0.002, 1.0], jnp.float32)}
    r = {"w": jnp.zeros(3)}
    g1, r1 = ef_compress_grads(g, r)
    # residual + quantized == original
    np.testing.assert_allclose(np.asarray(g1["w"] + r1["w"]),
                               np.asarray(g["w"]), rtol=1e-6)


def test_compressed_psum_on_host_mesh():
    """Numerics of the cross-pod compressed mean on an 8-device host mesh
    (subprocess so the 8-device XLA flag doesn't leak into this process)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.train.compress import compressed_psum
mesh = jax.make_mesh((8,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 128)).astype(np.float32))
f = jax.shard_map(lambda a: compressed_psum(a[0], "pod")[None],
                  mesh=mesh, in_specs=P("pod"), out_specs=P("pod"))
out = np.asarray(f(x))
exact = x.mean(axis=0)
for row in out:
    np.testing.assert_allclose(row, exact, atol=2 * float(np.abs(x).max()) / 127)
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "OK" in r.stdout, r.stderr[-2000:]


def test_train_driver_resume(tmp_path):
    """Kill-and-resume: driver continues from the checkpoint step."""
    from repro.launch.train import main
    args = ["--arch", "olmo_1b", "--preset", "tiny", "--steps", "6",
            "--batch", "2", "--seq", "32", "--ckpt-dir", str(tmp_path),
            "--ckpt-every", "3"]
    main(args)
    assert ckpt_mod.list_checkpoints(str(tmp_path))
    out = ckpt_mod.restore_latest(
        str(tmp_path),
        *_driver_templates(tmp_path))
    assert out["step"] == 6


def _driver_templates(tmp_path):
    # rebuild matching templates exactly as the driver does
    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.launch.train import PRESETS
    from repro.lm import model as model_mod
    from repro.train import step as step_mod
    import dataclasses
    from repro.core.vocab import reorder_vocab
    from repro.data.pipeline import DataConfig, ZipfPipeline
    cfg = reduced(get_config("olmo_1b"), **PRESETS["tiny"], remat=False)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, batch_size=2)
    pipe = ZipfPipeline(dc)
    vr = reorder_vocab(pipe.frequencies(), row_multiple=128)
    cfg = dataclasses.replace(cfg, hot_vocab_rows=max(128, min(cfg.hot_vocab_rows, vr.hot_rows)))
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    return params, step_mod.init_opt(params)
