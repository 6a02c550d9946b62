"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh, prove it partitions, and extract the roofline terms (§Dry-run,
§Roofline in EXPERIMENTS.md).

MUST be run as its own process (the XLA_FLAGS below lock in 512 host
placeholder devices before any other jax import — do NOT import this module
from tests or benchmarks).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single --out runs/dryrun
  (--mesh single|multi|both; emits one JSON per combo)
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCHS, SHAPES
from ..models import transformer as T
from ..optim import adamw_init, cosine_schedule
from ..sharding import make_specs, batch_axes
from ..train.steps import build_train_step, build_prefill_step, \
    build_decode_step
from .hlo_analysis import analyze_hlo, roofline, top_hotspots
from .mesh import make_production_mesh
from .specs import input_specs, input_shardings, shape_config


def _param_structs(cfg):
    """ShapeDtypeStructs of params (+ opt state) — no allocation."""
    from ..models.common import Dtype
    defs = T.param_defs(cfg)
    params = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0)))
    return params


def dryrun_one(arch: str, shape_name: str, multi_pod: bool,
               verbose: bool = True, extra_overrides: dict | None = None,
               hotspots: bool = False):
    """Lower + compile one (arch, shape, mesh) combo; return result record."""
    cfg = shape_config(ARCHS[arch], shape_name)
    if extra_overrides:
        cfg = cfg.with_overrides(**extra_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    kind, inputs = input_specs(cfg, shape_name)
    in_sh = input_shardings(cfg, shape_name, mesh)

    params = _param_structs(cfg)
    axes = T.param_axes(cfg)
    pspecs = make_specs(mesh, params, axes,
                        fsdp_min_elems=cfg.fsdp_min_elems)
    from jax.sharding import NamedSharding
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(
                           x, jax.sharding.PartitionSpec))

    t0 = time.time()
    with mesh:
        if kind == "train":
            opt = jax.eval_shape(lambda p: adamw_init(
                p, dtype=jnp.dtype(cfg.optstate_dtype)), params)
            osh = jax.tree.map(
                lambda l: NamedSharding(mesh, jax.sharding.PartitionSpec())
                if l.ndim == 0 else None, opt)
            # optimizer moments inherit param specs
            osh = type(opt)(m=psh, v=psh,
                            count=NamedSharding(
                                mesh, jax.sharding.PartitionSpec()))
            step = build_train_step(cfg, cosine_schedule(3e-4, 100, 10000),
                                    grad_specs=pspecs)
            jitted = jax.jit(step, in_shardings=(psh, osh, in_sh),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params, opt, inputs)
        elif kind == "prefill":
            S = SHAPES[shape_name]["seq_len"]
            step = build_prefill_step(cfg, cache_len=S)
            jitted = jax.jit(step, in_shardings=(psh, in_sh))
            lowered = jitted.lower(params, inputs)
        else:  # decode
            token, caches, index = inputs
            tok_sh, cache_sh, idx_sh = in_sh
            step = build_decode_step(cfg)
            jitted = jax.jit(step,
                             in_shardings=(psh, tok_sh, cache_sh, idx_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(params, token, caches, index)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    hlo_totals = analyze_hlo(hlo_text)
    if hotspots:
        print("--- top computations by (traffic + collectives) ---")
        for name, mult, fl, tr, cb, hint in top_hotspots(hlo_text, 18):
            print(f"  x{mult:<8.0f} flops={fl:.2e} traffic={tr:.2e} "
                  f"coll={cb:.2e}  {name[:40]:40s} {hint[:70]}")

    n_tokens = (SHAPES[shape_name]["global_batch"]
                * (SHAPES[shape_name]["seq_len"] if kind in ("train",
                                                             "prefill")
                   else 1))
    n_active = T.count_params(cfg, active_only=True)
    mult = 6.0 if kind == "train" else 2.0
    model_flops = mult * n_active * n_tokens
    rl = roofline(cost, hlo_totals, n_chips, model_flops=model_flops)
    coll = {k: hlo_totals.get(k, 0.0) for k in
            ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")}
    coll["count"] = hlo_totals.get("coll_count", 0)

    rec = {
        "arch": arch, "shape": shape_name, "kind": kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "param_count": T.count_params(cfg),
        "param_count_active": n_active,
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
        },
        "roofline": rl,
        "collectives": coll,
    }
    if verbose:
        print(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="directory for JSON records")
    ap.add_argument("--quiet", action="store_true")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ArchConfig overrides (perf iteration)")
    ap.add_argument("--hotspots", action="store_true",
                    help="dump per-computation breakdown (perf debugging)")
    args = ap.parse_args()

    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    overrides = json.loads(args.override) if args.override else None

    failures = []
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                tag = f"{arch}|{shape}|{'multi' if multi else 'single'}"
                try:
                    rec = dryrun_one(arch, shape, multi,
                                     verbose=not args.quiet,
                                     extra_overrides=overrides,
                                     hotspots=args.hotspots)
                    status = "OK"
                except Exception as e:  # noqa: BLE001 — report and continue
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if multi else "16x16",
                           "error": repr(e)}
                    failures.append(tag)
                    status = "FAIL"
                print(f"[{status}] {tag}", flush=True)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    fname = tag.replace("|", "__") + ".json"
                    with open(os.path.join(args.out, fname), "w") as f:
                        json.dump(rec, f, indent=2)
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("all dry-runs passed")


if __name__ == "__main__":
    main()
