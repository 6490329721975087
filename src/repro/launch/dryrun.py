import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
CPU devices host the production meshes; every cell's step function is
``jax.jit(...).lower(**ShapeDtypeStructs).compile()``-ed, and the compiled
artifact's memory_analysis / cost_analysis / collective schedule are recorded
as a JSON artifact per cell (consumed by benchmarks/bench_roofline.py and
EXPERIMENTS.md).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all            # full sweep, cached
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.common import named, shape_dtypes, shardings
from repro.configs import ARCH_IDS, SHAPES, all_cells, get_config, skipped_cells
from repro.launch.mesh import chips, make_production_mesh
from repro.models.model import build, cache_specs, input_specs
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_train_step, state_specs
from repro.analysis import roofline as RL

ART_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _spec_shardings(mesh, spec_tree):
    return shardings(spec_tree, mesh)


def _pspec_shardings(mesh, pspec_tree, sds_tree):
    return jax.tree.map(lambda ps, _: named(mesh, ps), pspec_tree, sds_tree)


def lower_cell(arch: str, shape_name: str, mesh, donate: bool = True, opt: bool = False):
    """Build and lower one cell. Returns (lowered, meta)."""
    import dataclasses

    import jax.numpy as _jnp

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind in ("prefill", "decode"):
        # serving deployments store weights in bf16
        cfg = dataclasses.replace(cfg, params_dtype=_jnp.bfloat16)
    if opt:
        # beyond-paper §Perf configuration (EXPERIMENTS.md §Perf):
        # flash train attention + mixed-precision norms.  ("save_dots"
        # selective remat was tried and refuted — compute term improved but
        # the saved-activation traffic raised the dominant memory term.)
        over = {"fast_norm": True}
        if cfg.n_heads:
            over["attn_impl"] = "flash"
        cfg = dataclasses.replace(cfg, **over)
    model = build(cfg, tp=mesh.shape["model"])
    inputs, in_pspecs = input_specs(cfg, shape)
    in_shard = _pspec_shardings(mesh, in_pspecs, inputs)

    if shape.kind == "train":
        oc = OptConfig(moments_dtype=cfg.moments_dtype)
        sspecs = state_specs(model, oc)
        step = make_train_step(model, oc, accum_steps=shape.accum_steps, mesh=mesh)
        state_sds = shape_dtypes(sspecs)
        state_shard = _spec_shardings(mesh, sspecs)
        fn = jax.jit(
            step,
            in_shardings=(state_shard, in_shard),
            out_shardings=(state_shard, None),
            donate_argnums=(0,) if donate else (),
        )
        lowered = fn.lower(state_sds, inputs)
    elif shape.kind == "prefill":
        pspecs_params = _spec_shardings(mesh, model.specs)
        csds, cps = cache_specs(cfg, shape, tp=mesh.shape["model"])
        cache_shard = _pspec_shardings(mesh, cps, csds)
        fn = jax.jit(
            lambda p, b: model.prefill(p, b, mesh=mesh),
            in_shardings=(pspecs_params, in_shard),
            out_shardings=(cache_shard, None),
        )
        lowered = fn.lower(shape_dtypes(model.specs), inputs)
    else:  # decode
        pspecs_params = _spec_shardings(mesh, model.specs)
        csds, cps = cache_specs(cfg, shape, tp=mesh.shape["model"])
        cache_shard = _pspec_shardings(mesh, cps, csds)
        fn = jax.jit(
            lambda p, c, b, pos: model.decode_step(p, c, b, pos, mesh=mesh),
            in_shardings=(pspecs_params, cache_shard, in_shard, None),
            out_shardings=(None, cache_shard),
            donate_argnums=(1,) if donate else (),
        )
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = fn.lower(shape_dtypes(model.specs), csds, inputs, pos_sds)
    return lowered


def run_cell(arch: str, shape_name: str, mesh_kind: str, verbose: bool = True, opt: bool = False):
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = lower_cell(arch, shape_name, mesh, opt=opt)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        print(compiled.memory_analysis())
        print({k: v for k, v in compiled.cost_analysis().items()
               if k in ("flops", "bytes accessed")})
        rl = RL.from_compiled(compiled)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mf = RL.model_flops(cfg, shape, shape.kind)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "chips": chips(mesh),
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_state_bytes_per_chip": mem.argument_size_in_bytes + mem.temp_size_in_bytes,
        },
        "roofline": rl.to_dict(),
        "model_flops_global": mf,
        "model_flops_per_chip": mf / chips(mesh),
        "useful_flop_ratio": (mf / chips(mesh)) / max(rl.flops, 1.0),
    }
    if verbose:
        print(
            f"[{arch} × {shape_name} × {mesh_kind}] compile {t_compile:.1f}s  "
            f"args/chip {mem.argument_size_in_bytes/2**30:.2f} GiB  "
            f"temp/chip {mem.temp_size_in_bytes/2**30:.2f} GiB  "
            f"bottleneck {rl.bottleneck}  t={rl.t_bound*1e3:.2f} ms  "
            f"useful-flop-ratio {rec['useful_flop_ratio']:.2f}"
        )
    return rec


def artifact_path(arch, shape_name, mesh_kind, tag="baseline"):
    return ART_DIR / tag / f"{arch}__{shape_name}__{mesh_kind}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true", help="sweep all cells × meshes")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--opt", action="store_true", help="§Perf beyond-paper config")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    todo = []
    if args.all:
        for a, s in all_cells():
            for mk in ("single", "multi"):
                todo.append((a, s, mk))
    else:
        assert args.arch and args.shape
        todo.append((args.arch, args.shape, args.mesh))

    failures = []
    for a, s, mk in todo:
        path = artifact_path(a, s, mk, args.tag)
        if path.exists() and not args.force:
            print(f"[skip cached] {a} × {s} × {mk}")
            continue
        path.parent.mkdir(parents=True, exist_ok=True)
        try:
            rec = run_cell(a, s, mk, opt=args.opt)
        except Exception as e:  # record the failure; the sweep continues
            traceback.print_exc()
            rec = {"arch": a, "shape": s, "mesh": mk, "ok": False, "error": repr(e)[:2000]}
            failures.append((a, s, mk))
        path.write_text(json.dumps(rec, indent=1))
    for a, s, reason in skipped_cells():
        path = artifact_path(a, s, "skip", args.tag)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps({"arch": a, "shape": s, "ok": True, "skipped": reason}))
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
