import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell we build ShapeDtypeStruct stand-ins (weak-type-correct,
sharded, zero allocation), ``jit(...).lower(...).compile()`` against the
production mesh, and record:

* ``memory_analysis()``  — per-device bytes (proves it fits),
* ``cost_analysis()``    — FLOPs / bytes for the roofline,
* collective operand bytes parsed from the optimized HLO,
* the derived roofline terms.

Results are cached as JSON per cell under ``results/dryrun/`` so reruns
skip completed cells (``--force`` recomputes).

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, input_specs, skip_reason
from repro.dist import sharding as shd
from repro.launch import estimates
from repro.launch import hlo_analysis as hlo
from repro.launch import hlo_count as hc
from repro.launch.mesh import make_production_mesh
from repro.models import decoder
from repro.models.common import param_shapes
from repro.train import optimizer as opt
from repro.train.train_step import TrainConfig, make_train_step

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


# ---------------------------------------------------------------------------
# Struct builders (no allocation)
# ---------------------------------------------------------------------------

def _struct_tree(shapes_tree, dtype, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(tuple(s), dtype, sharding=sh),
        shapes_tree, shardings,
        is_leaf=lambda s: isinstance(s, tuple),
    )


def param_structs(cfg, mesh, dtype):
    shapes = param_shapes(cfg, model_size=int(mesh.shape["model"]))
    shards = shd.param_shardings(cfg, mesh)
    return _struct_tree(shapes, dtype, shards)


def opt_structs(params_struct):
    zeros = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=s.sharding),
        params_struct,
    )
    m = zeros
    v = jax.tree.map(lambda s: s, zeros)
    return opt.OptState(m=m, v=v, count=jax.ShapeDtypeStruct((), jnp.int32))


def batch_structs(cfg, mesh, shape_name):
    specs = input_specs(cfg, shape_name)
    pspecs = shd.batch_pspecs(cfg, mesh, specs)
    return {
        k: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=jax.sharding.NamedSharding(mesh, pspecs[k])
        )
        for k, v in specs.items()
    }


def cache_structs(cfg, mesh, batch: int, max_len: int, dtype=jnp.bfloat16):
    tree = jax.eval_shape(lambda: decoder.init_cache(cfg, batch, max_len, dtype))
    pspecs = shd.cache_pspecs(cfg, mesh, tree, batch)
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=jax.sharding.NamedSharding(mesh, p)),
        tree, pspecs,
    )


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------

def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    remat: str = "dots",
    extra_tag: str = "",
    ctx_overrides: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    cfg = get_config(arch)
    spec = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": extra_tag}

    reason = skip_reason(arch, cfg, shape_name)
    if reason:
        cell["status"] = "SKIP"
        cell["skip_reason"] = reason
        return cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    ax = shd.MeshAxes.for_mesh(mesh)
    n_chips = int(np.prod([int(mesh.shape[a]) for a in mesh.axis_names]))

    ctx_kw: Dict[str, Any] = dict(
        mesh=mesh, batch_axes=ax.batch, use_kernel="ref",
        remat=(remat if spec.kind == "train" else "none"),
    )
    ctx_kw.update(ctx_overrides or {})
    param_bf16 = ctx_kw.pop("_param_bf16", False)
    ctx = decoder.RunCtx(**ctx_kw)

    t0 = time.time()
    if spec.kind == "train":
        pdt = jnp.bfloat16 if param_bf16 else jnp.float32
        pstr = param_structs(cfg, mesh, pdt)
        ostr = opt_structs(pstr)
        bstr = batch_structs(cfg, mesh, shape_name)
        step = make_train_step(cfg, ctx, TrainConfig())
        jitted = jax.jit(step, donate_argnums=(0, 1))
        lowered = jitted.lower(pstr, ostr, bstr)
        tokens = spec.global_batch * spec.seq_len
    elif spec.kind == "prefill":
        pstr = param_structs(cfg, mesh, jnp.bfloat16)
        bstr = batch_structs(cfg, mesh, shape_name)

        def prefill_fn(params, batch):
            return decoder.prefill(cfg, ctx, params, batch)

        lowered = jax.jit(prefill_fn).lower(pstr, bstr)
        tokens = spec.global_batch * spec.seq_len
    else:  # decode
        pstr = param_structs(cfg, mesh, jnp.bfloat16)
        bstr = batch_structs(cfg, mesh, shape_name)
        cstr = cache_structs(cfg, mesh, spec.global_batch, spec.seq_len)

        def serve_step(params, caches, tokens, pos):
            return decoder.decode_step(cfg, ctx, params, caches, tokens, pos)

        lowered = jax.jit(serve_step, donate_argnums=(1,)).lower(
            pstr, cstr, bstr["tokens"], bstr["pos"])
        tokens = spec.global_batch  # one new token per sequence

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                k: int(getattr(ma, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes")
                if hasattr(ma, k)
            }
    except Exception as e:  # CPU backend may not implement it
        mem = {"error": str(e)}

    text = compiled.as_text()
    # trip-count-aware costs (XLA's cost_analysis counts while bodies ONCE —
    # verified experimentally; hlo_count multiplies through the call graph)
    counted = hc.analyze(text)
    coll = counted.collectives
    flops = float(counted.flops + counted.elemwise_flops)

    # memory term: analytic TPU traffic model (CPU 'bytes accessed' counts
    # unfused elementwise traffic and misses scan trip counts)
    est = estimates.estimate(cfg, spec, n_chips, tp=int(mesh.shape["model"]),
                             param_bytes=(2 if param_bf16 else 4))
    bytes_analytic = est.traffic_bytes
    bytes_xla_once = float(cost.get("bytes accessed", 0.0))

    # collective term uses the CPU-widening-corrected (TPU-dtype) bytes
    terms = hlo.roofline_terms(flops, bytes_analytic, float(coll.tpu_bf16_bytes))
    # 6·N·D counts fwd+bwd (train); inference steps are fwd-only -> 2·N·D
    mf = hlo.model_flops(cfg.param_count(), tokens, cfg.active_param_count())
    if spec.kind != "train":
        mf /= 3.0

    cell.update({
        "status": "OK",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "tokens": tokens,
        "flops_per_device": flops,
        "dot_flops_per_device": float(counted.flops),
        "flops_xla_body_once": float(cost.get("flops", 0.0)),
        "bytes_per_device": bytes_analytic,
        "bytes_xla_body_once": bytes_xla_once,
        "n_while": counted.n_while,
        "trip_counts": counted.trip_counts,
        "collectives": coll.as_dict(),
        "memory_analysis": mem,
        "memory_estimate": est.as_dict(),
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_device": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips / flops) if flops else None,
        "hlo_bytes": len(text),
    })
    return cell


def cell_path(arch: str, shape: str, mesh_name: str, tag: str = "") -> Path:
    t = f"__{tag}" if tag else ""
    return RESULTS_DIR / f"{arch}__{shape}__{mesh_name}{t}.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--tag", default="")
    ap.add_argument("--param-bf16", action="store_true",
                    help="bf16 weights + fp32 m/v (halves ZeRO gather wire)")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                out = cell_path(arch, shape, mesh_name, args.tag)
                if out.exists() and not args.force:
                    print(f"[cached] {arch} {shape} {mesh_name}")
                    continue
                print(f"[run]    {arch} {shape} {mesh_name} ...", flush=True)
                try:
                    cell = run_cell(
                        arch, shape, mp, remat=args.remat, extra_tag=args.tag,
                        ctx_overrides=(
                            {"_param_bf16": True} if args.param_bf16 else None),
                    )
                except Exception:
                    cell = {
                        "arch": arch, "shape": shape, "mesh": mesh_name,
                        "status": "FAIL", "error": traceback.format_exc(),
                    }
                out.write_text(json.dumps(cell, indent=2))
                status = cell["status"]
                extra = ""
                if status == "OK":
                    r = cell["roofline"]
                    extra = (f" compile={cell['compile_s']}s dom={r['dominant']}"
                             f" tc={r['t_compute_s']:.4f} tm={r['t_memory_s']:.4f}"
                             f" tx={r['t_collective_s']:.4f}")
                elif status == "SKIP":
                    extra = f" ({cell['skip_reason']})"
                print(f"[{status}] {arch} {shape} {mesh_name}{extra}", flush=True)


if __name__ == "__main__":
    main()
