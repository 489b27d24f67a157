import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers, SPMD-
partitions, and compiles on the production meshes — and harvest the compiled
artifacts (memory_analysis / cost_analysis / HLO collectives) that feed
EXPERIMENTS.md §Dry-run and §Roofline.

MUST be the process entrypoint (the XLA_FLAGS line above has to run before
any jax import, which is why it precedes this docstring).  Do not import this
module from test/bench processes that need a 1-device platform.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi    # 2x16x16 only
  ... --layers 2           # L-override (roofline extrapolation compiles)
  ... --out experiments/dryrun.json
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell
from repro.models import lm
from repro.models.config import SHAPES, ArchConfig
from repro.models.lm import layers_per_group, num_groups

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in (post-SPMD) HLO.

    Matches lines like ``%x = bf16[2,512]{...} all-gather(...)`` and sums the
    byte size of the result shape per collective kind.  Tuple shapes
    ``(f32[..], f32[..])`` are summed element-wise.
    """
    sizes = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8, "c64": 8}
    out = {k: 0 for k in _COLLECTIVES}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES)
                      + r")(?:-start|-done)?\(", line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        total = 0
        for dt, dims in shape_re.findall(shape_str):
            if dt not in sizes:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * sizes[dt]
        out[kind] += total
    return out


def lower_cell(arch: str, shape_name: str, mesh, *, layers=None,
               opts: lm.TrainOptions | None = None, compile_only=True,
               overrides: dict | None = None):
    """Returns (record dict, compiled) for one cell.  ``overrides``:
    ArchConfig field replacements (hillclimb knobs, e.g. attn_tp=False)."""
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    if layers is not None:
        # L-extrapolation override; enc-dec archs scale both stacks together
        # (they have equal depth, so cost(L) stays affine in L).
        cfg = dataclasses.replace(
            cfg, n_layers=layers,
            encoder_layers=layers if cfg.encoder_layers else 0)
    t0 = time.time()
    with shd.use_mesh(mesh):
        prog = build_cell(cfg, shape, mesh, opts=opts)
        jfn = jax.jit(prog.fn, in_shardings=prog.in_shardings,
                      donate_argnums=prog.donate)
        lowered = jfn.lower(*prog.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())

    record = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "layers": cfg.n_layers,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops": cost.get("flops", 0.0) if cost else None,
        "bytes_accessed": cost.get("bytes accessed", 0.0) if cost else None,
        "collective_bytes": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                            None),
        },
    }
    return record, compiled


def lower_mf_cell(shape_name: str, mesh, *, users=None, items=None):
    """Dry-run the paper's own model (distributed HEAT MF, core/mf_distributed)
    at Amazon Product Reviews scale on the production mesh."""
    from repro.configs.heat_mf import AMAZON
    from repro.core.mf_distributed import MF_SHAPES, build_mf_cell

    cfg = AMAZON
    if users or items:
        cfg = dataclasses.replace(cfg, num_users=users or cfg.num_users,
                                  num_items=items or cfg.num_items)
    shape = MF_SHAPES[shape_name]
    t0 = time.time()
    with shd.use_mesh(mesh):
        fn, args_abs, shardings, donate = build_mf_cell(cfg, mesh,
                                                        shape.global_batch)
        jfn = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jfn.lower(*args_abs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
    record = {
        "arch": "heat-mf-amazon", "shape": shape_name,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops": cost.get("flops", 0.0) if cost else None,
        "bytes_accessed": cost.get("bytes accessed", 0.0) if cost else None,
        "collective_bytes": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
    }
    return record, compiled


def run(args) -> int:
    """Lower + memory-audit the selected arches over the production meshes;
    returns a process exit code."""
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    archs = [args.arch] if args.arch else ARCH_NAMES
    shapes = [args.shape] if args.shape else list(SHAPES)
    results, failures = [], []

    # The paper's own model (distributed HEAT MF) as an extra dry-run family.
    if args.arch in (None, "heat-mf"):
        from repro.core.mf_distributed import MF_SHAPES
        mf_shapes = ([args.shape] if args.shape in MF_SHAPES
                     else list(MF_SHAPES) if args.arch == "heat-mf" or not args.shape
                     else [])
        for shape_name in mf_shapes:
            for mesh_name, mesh in meshes:
                tag = f"heat-mf-amazon x {shape_name} x {mesh_name}"
                try:
                    rec, compiled = lower_mf_cell(shape_name, mesh)
                    rec["status"] = "ok"
                    rec["mesh_name"] = mesh_name
                    results.append(rec)
                    print(f"[dryrun] OK    {tag}  compile={rec['compile_s']}s "
                          f"flops={rec['flops']:.3e} "
                          f"coll={sum(rec['collective_bytes'].values()):.3e}B")
                    del compiled
                except Exception as e:  # noqa: BLE001
                    failures.append(tag)
                    results.append({"arch": "heat-mf-amazon",
                                    "shape": shape_name, "mesh_name": mesh_name,
                                    "status": "fail",
                                    "error": f"{type(e).__name__}: {e}"})
                    print(f"[dryrun] FAIL  {tag}: {type(e).__name__}: {e}")
        if args.arch == "heat-mf":
            archs = []

    for arch in archs:
        cfg = get_config(arch)
        for shape_name in shapes:
            reason = cfg.skip_reason(shape_name)
            if reason:
                results.append({"arch": arch, "shape": shape_name,
                                "status": "skip", "reason": reason})
                print(f"[dryrun] SKIP  {arch} x {shape_name}: {reason}")
                continue
            for mesh_name, mesh in meshes:
                tag = f"{arch} x {shape_name} x {mesh_name}"
                try:
                    rec, compiled = lower_cell(arch, shape_name, mesh,
                                               layers=args.layers)
                    rec["status"] = "ok"
                    rec["mesh_name"] = mesh_name
                    results.append(rec)
                    print(f"[dryrun] OK    {tag}  "
                          f"compile={rec['compile_s']}s "
                          f"flops={rec['flops']:.3e} "
                          f"coll={sum(rec['collective_bytes'].values()):.3e}B")
                    if args.verbose:
                        print(compiled.memory_analysis())
                    del compiled
                except Exception as e:  # noqa: BLE001 — report, keep going
                    failures.append(tag)
                    results.append({"arch": arch, "shape": shape_name,
                                    "mesh_name": mesh_name, "status": "fail",
                                    "error": f"{type(e).__name__}: {e}"})
                    print(f"[dryrun] FAIL  {tag}: {type(e).__name__}: {e}")
                    if args.verbose:
                        traceback.print_exc()

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"[dryrun] wrote {args.out} ({len(results)} records)")
    print(f"[dryrun] {len(failures)} failures" + (f": {failures}" if failures else ""))
    return 1 if failures else 0


def main():
    """CLI entry: parse args and run the dry-run audit."""
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    p.add_argument("--layers", type=int, default=None)
    p.add_argument("--out", default=None)
    p.add_argument("--verbose", action="store_true")
    sys.exit(run(p.parse_args()))


if __name__ == "__main__":
    main()
