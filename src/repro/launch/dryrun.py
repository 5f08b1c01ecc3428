import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST precede every other import (jax locks the
# device count on first init) — hence no `from __future__` in this module.

DOC = """Multi-pod dry-run: lower + compile every (arch x input-shape) cell
on the production meshes and record memory / cost / collective statistics.

    PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-7b \
        --shape train_4k --mesh both --out results/dryrun

This is the proof that the distribution config is coherent without real
hardware: a sharding mismatch, compile-time OOM, or unsupported collective
fails the cell.  512 host devices exist ONLY in this process (the env var
above must precede any jax import — jax locks the device count on first
init); smoke tests and benchmarks see 1 device.
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.configs.base import Axes, axes_for_mesh, opt_sharding_like
from repro.configs.registry import ARCH_NAMES, get_arch
from repro.launch.mesh import make_production_mesh
from repro.optim.adamw import adamw_init

COLLECTIVE_RE = re.compile(
    r"=\s*([a-z0-9_\[\],\{\} ()]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.IGNORECASE)
SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64)"
                      r"\[([0-9,]*)\]")

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "s64": 8, "f64": 8}


def shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-device bytes moved by collective kind, parsed from post-SPMD
    HLO (result shapes are per-device)."""
    stats: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(2).lower()
        # result shape(s) appear on the lhs of the '=' in HLO
        lhs = line.split("=", 1)[0] + "=" + line.split("=", 1)[1].split("(")[0]
        b = shape_bytes(lhs)
        s = stats.setdefault(kind, {"count": 0, "bytes": 0})
        s["count"] += 1
        s["bytes"] += b
    return stats


def _named(mesh, spec_tree, abstract_tree):
    """Prefix spec tree (or None -> fully replicated) to NamedSharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if spec_tree is None:
        return NamedSharding(mesh, P())
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    ax = axes_for_mesh(mesh)
    arch = get_arch(arch_name, axes=ax)
    cell = arch.cell(shape_name)

    if hasattr(arch, "abstract_params_for"):
        params_abs = arch.abstract_params_for(shape_name)
    else:
        params_abs = arch.abstract_params()
    param_spec = arch.param_sharding(ax)
    p_shard = _named(mesh, param_spec, params_abs)

    inputs_abs = cell.input_specs()
    in_shard = _named(mesh, cell.input_sharding(ax), inputs_abs)

    args = [params_abs]
    shards = [p_shard]
    if cell.needs_opt:
        opt_abs = jax.eval_shape(adamw_init, params_abs)
        opt_shard = _named(
            mesh,
            opt_sharding_like(param_spec) if param_spec is not None else None,
            opt_abs)
        args.append(opt_abs)
        shards.append(opt_shard)
    args.append(inputs_abs)
    shards.append(in_shard)

    t0 = time.time()
    result = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(np.prod(mesh.devices.shape)),
        "kind": cell.kind,
    }
    # set_mesh (not `with mesh:`): also installs the ABSTRACT mesh context
    # so in-model shard_map regions (MoE dispatch) see the mesh axes.
    with jax.sharding.set_mesh(mesh):
        jitted = jax.jit(cell.step, in_shardings=tuple(shards),
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*args)
        result["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t1, 1)

        try:
            mem = compiled.memory_analysis()
            result["memory"] = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "peak_bytes": int(mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - getattr(mem, "alias_size_in_bytes", 0)),
            }
        except Exception as exc:  # CPU backend may not implement it
            result["memory"] = {"error": str(exc)}
        try:
            cost = compiled.cost_analysis()
            cost = cost[0] if isinstance(cost, (list, tuple)) else cost
            result["cost"] = {
                "flops": float(cost.get("flops", -1)),
                "bytes_accessed": float(cost.get("bytes accessed", -1)),
                "transcendentals": float(cost.get("transcendentals", 0)),
            }
        except Exception as exc:
            result["cost"] = {"error": str(exc)}
        try:
            hlo = compiled.as_text()
            result["collectives"] = collective_stats(hlo)
            result["hlo_bytes"] = len(hlo)
        except Exception as exc:
            result["collectives"] = {"error": str(exc)}
    result["total_s"] = round(time.time() - t0, 1)
    result["ok"] = True
    if verbose:
        print(json.dumps(result, indent=None), flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help=f"one of {ARCH_NAMES} or 'all'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    archs = ARCH_NAMES if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_fail = 0
    for arch_name in archs:
        arch = get_arch(arch_name)
        shapes = sorted(arch.cells) if args.shape == "all" else [args.shape]
        for shape_name in shapes:
            for multi in meshes:
                tag = f"{arch_name}__{shape_name}__{'multi' if multi else 'single'}"
                path = out / f"{tag}.json"
                if args.skip_existing and path.exists():
                    prev = json.loads(path.read_text())
                    if prev.get("ok"):
                        n_ok += 1
                        continue
                print(f"=== {tag}", flush=True)
                try:
                    res = run_cell(arch_name, shape_name, multi)
                    n_ok += 1
                except Exception as exc:
                    res = {"arch": arch_name, "shape": shape_name,
                           "mesh": "multi" if multi else "single",
                           "ok": False, "error": str(exc),
                           "traceback": traceback.format_exc()[-4000:]}
                    n_fail += 1
                    print(f"FAIL {tag}: {exc}", flush=True)
                path.write_text(json.dumps(res, indent=2))
    print(f"dryrun complete: {n_ok} ok, {n_fail} failed", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
