import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: the 512
placeholder host devices let ``jax.make_mesh`` build the production meshes
((16,16) single-pod, (2,16,16) multi-pod); every cell's step function is
lowered with ShapeDtypeStruct inputs (no allocation) and compiled; we record
``memory_analysis()`` (fits/doesn't), ``cost_analysis()`` (FLOPs/bytes for
§Roofline) and the collective schedule parsed from the optimized HLO.

Results append incrementally to a JSON file so interrupted runs resume.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi_9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out experiments/dryrun.json
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, get_config
from ..configs.base import ArchConfig, SHAPES, ShapeCell
from ..dist import sharding as shd
from ..dist.constrain import activation_sharding
from ..lm import model as model_mod
from ..roofline import analysis as roofline
from ..train import step as train_step_mod
from .mesh import make_production_mesh


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; weak-type-correct, shardable)
# ---------------------------------------------------------------------------


def input_specs(cfg: ArchConfig, cell: ShapeCell, mesh) -> Dict[str, Any]:
    """Model inputs for one cell as ShapeDtypeStructs with shardings."""
    bspec = shd.batch_spec(mesh)
    b, s = cell.global_batch, cell.seq_len

    def sds(shape, dtype, spec):
        spec = shd.enforce_divisibility(
            jax.ShapeDtypeStruct(shape, dtype), spec, mesh)
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    batch: Dict[str, Any] = {}
    if cell.kind in ("train",):
        s_text = s - cfg.prefix_len if cfg.prefix_len else s
        batch["tokens"] = sds((b, s_text), jnp.int32, P(*bspec, None))
        batch["labels"] = sds((b, s_text), jnp.int32, P(*bspec, None))
        if cfg.prefix_len:
            batch["prefix"] = sds((b, cfg.prefix_len, cfg.d_model), jnp.bfloat16,
                                  P(*bspec, None, None))
        if cfg.n_enc_layers:
            batch["frames"] = sds((b, s, cfg.d_model), jnp.bfloat16,
                                  P(*bspec, None, None))
    elif cell.kind == "prefill":
        s_text = s - cfg.prefix_len if cfg.prefix_len else s
        batch["tokens"] = sds((b, s_text), jnp.int32, P(*bspec, None))
        if cfg.prefix_len:
            batch["prefix"] = sds((b, cfg.prefix_len, cfg.d_model), jnp.bfloat16,
                                  P(*bspec, None, None))
        if cfg.n_enc_layers:
            batch["frames"] = sds((b, s, cfg.d_model), jnp.bfloat16,
                                  P(*bspec, None, None))
    else:  # decode: one new token against a seq_len cache
        batch["token"] = sds((b, 1), jnp.int32, P(*bspec, None))
    return batch


def _with_shardings(tree_shapes, tree_specs, mesh):
    tree_specs = shd.enforce_divisibility(tree_shapes, tree_specs, mesh)
    return jax.tree.map(
        lambda sd, spec: jax.ShapeDtypeStruct(
            sd.shape, sd.dtype, sharding=NamedSharding(mesh, spec)),
        tree_shapes, tree_specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------


def lower_cell(cfg: ArchConfig, cell: ShapeCell, mesh,
               oc_overrides: Dict[str, Any] | None = None,
               fsdp_over_pods: bool = False) -> Dict[str, Any]:
    t0 = time.time()
    batch = input_specs(cfg, cell, mesh)

    if cell.kind == "train":
        p_shapes = jax.eval_shape(
            lambda: model_mod.init_params(cfg, jax.random.PRNGKey(0),
                                          dtype=jnp.float32))
        p_specs = shd.param_specs(p_shapes, fsdp_over_pods=fsdp_over_pods)
        params_sds = _with_shardings(p_shapes, p_specs, mesh)
        oc = train_step_mod.OptConfig(**(oc_overrides or {}))
        mdtype = jnp.bfloat16 if oc.moment_dtype == "bfloat16" else jnp.float32
        o_shapes = jax.eval_shape(
            lambda pp: train_step_mod.init_opt(pp, mdtype), p_shapes)
        o_specs = {"m": p_specs, "v": p_specs, "step": P()}
        opt_sds = _with_shardings(o_shapes, o_specs, mesh)
        fn = train_step_mod.make_train_step(cfg, oc)
        jitted = jax.jit(fn, donate_argnums=(0, 1))
        with jax.set_mesh(mesh), activation_sharding(
                tuple(mesh.axis_names), dict(mesh.shape)):
            lowered = jitted.lower(params_sds, opt_sds, batch)
    elif cell.kind == "prefill":
        p_shapes = jax.eval_shape(
            lambda: model_mod.init_params(cfg, jax.random.PRNGKey(0),
                                          dtype=jnp.bfloat16))
        p_specs = shd.param_specs(p_shapes)
        params_sds = _with_shardings(p_shapes, p_specs, mesh)

        def prefill_fn(params, batch):
            logits, _ = model_mod.forward(
                params, cfg, batch["tokens"],
                prefix=batch.get("prefix"), frames=batch.get("frames"),
                last_only=True)
            return logits[:, -1]

        jitted = jax.jit(prefill_fn)
        with jax.set_mesh(mesh), activation_sharding(
                tuple(mesh.axis_names), dict(mesh.shape)):
            lowered = jitted.lower(params_sds, batch)
    else:  # decode
        p_shapes = jax.eval_shape(
            lambda: model_mod.init_params(cfg, jax.random.PRNGKey(0),
                                          dtype=jnp.bfloat16))
        p_specs = shd.param_specs(p_shapes)
        params_sds = _with_shardings(p_shapes, p_specs, mesh)
        c_shapes = jax.eval_shape(
            lambda: model_mod.init_cache(cfg, cell.global_batch,
                                         max_len=cell.seq_len,
                                         dtype=jnp.bfloat16))
        c_specs = shd.cache_specs(c_shapes, mesh)
        cache_sds = _with_shardings(c_shapes, c_specs, mesh)

        def decode_fn(params, cache, batch):
            logits, cache = model_mod.decode_step(params, cfg, cache,
                                                  batch["token"])
            return logits, cache

        jitted = jax.jit(decode_fn, donate_argnums=(1,))
        with jax.set_mesh(mesh), activation_sharding(
                tuple(mesh.axis_names), dict(mesh.shape)):
            lowered = jitted.lower(params_sds, cache_sds, batch)

    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax returns [per-device dict]
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = roofline.parse_collective_bytes(hlo)
    parsed = roofline.parse_hlo_costs(hlo)  # trip-count-aware (see §Roofline)
    n_devices = int(np.prod(list(mesh.shape.values())))

    flops = float(parsed["flops"])
    bytes_acc = float(parsed["bytes"])
    terms = roofline.roofline_terms(flops, bytes_acc, coll["total"])
    out = {
        "arch": cfg.arch_id,
        "shape": cell.name,
        "kind": cell.kind,
        "mesh": "x".join(str(v) for v in mesh.shape.values()),
        "n_devices": n_devices,
        "seconds_to_compile": round(time.time() - t0, 1),
        "per_device": {
            "flops": flops,
            "bytes_accessed": bytes_acc,
            "xla_cost_analysis_flops": float(cost.get("flops", 0.0)),
            "xla_cost_analysis_bytes": float(cost.get("bytes accessed", 0.0)),
            "collective_bytes": coll,
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": (
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
            ),
        },
        "roofline": terms,
    }
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run(arch_ids, shape_names, meshes, out_path: str,
        reduced_for_test: bool = False,
        oc_overrides: Dict[str, Any] | None = None,
        variant: str = "", fsdp_over_pods: bool = False,
        cfg_overrides: Dict[str, Any] | None = None) -> int:
    try:
        with open(out_path) as f:
            results = json.load(f)
    except Exception:
        results = {}
    failures = 0
    for mesh_kind in meshes:
        if mesh_kind.startswith("pods"):
            import jax as _jax
            n_pods = int(mesh_kind[4:])
            mesh = _jax.make_mesh(
                (n_pods, 16, 16), ("pod", "data", "model"),
                axis_types=(_jax.sharding.AxisType.Auto,) * 3)
        else:
            mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        for arch in arch_ids:
            cfg = get_config(arch)
            if cfg_overrides:
                cfg = dataclasses.replace(cfg, **cfg_overrides)
            if reduced_for_test:
                from ..configs.base import reduced
                cfg = reduced(cfg)
            for sname in shape_names:
                cell = SHAPES[sname]
                key = f"{arch}|{sname}|{mesh_kind}"
                if variant:
                    key += f"|{variant}"
                if key in results and results[key].get("status") == "ok":
                    continue
                if sname == "long_500k" and not cfg.sub_quadratic:
                    results[key] = {
                        "status": "skipped",
                        "reason": "pure full-attention arch — sub-quadratic "
                                  "required for 500k (DESIGN.md §4)",
                    }
                    _save(out_path, results)
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    r = lower_cell(cfg, cell, mesh, oc_overrides=oc_overrides,
                                   fsdp_over_pods=fsdp_over_pods)
                    r["status"] = "ok"
                    results[key] = r
                    print(f"[dryrun] {key}: OK "
                          f"(compile {r['seconds_to_compile']}s, "
                          f"peak {r['per_device']['peak_bytes']/2**30:.2f} GiB, "
                          f"dominant {r['roofline']['dominant']})", flush=True)
                except Exception as e:
                    failures += 1
                    results[key] = {"status": "error", "error": str(e)[:2000],
                                    "traceback": traceback.format_exc()[-4000:]}
                    print(f"[dryrun] {key}: FAIL {e}", flush=True)
                _save(out_path, results)
    return failures


def _save(path: str, results) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1)
    os.replace(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--out", default="experiments/dryrun.json")
    ap.add_argument("--reduced", action="store_true",
                    help="use reduced configs (CI smoke)")
    ap.add_argument("--variant", default="")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--loss-chunk", type=int, default=0)
    ap.add_argument("--moment-dtype", default="float32")
    ap.add_argument("--fsdp-pods", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    args = ap.parse_args()
    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    oc_over = {}
    if args.grad_accum > 1:
        oc_over["grad_accum"] = args.grad_accum
    if args.loss_chunk:
        oc_over["loss_chunk"] = args.loss_chunk
    if args.moment_dtype != "float32":
        oc_over["moment_dtype"] = args.moment_dtype
    failures = run(archs, shapes, meshes, args.out,
                   reduced_for_test=args.reduced,
                   oc_overrides=oc_over or None, variant=args.variant,
                   fsdp_over_pods=args.fsdp_pods,
                   cfg_overrides={"seq_parallel": True} if args.seq_parallel else None)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
