import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("DRYRUN_DEVICES", "512")).strip()
"""Multi-pod dry-run entrypoint.

Lowers and compiles every (architecture x input-shape) pair against the
production mesh — (16,16) single-pod and (2,16,16) multi-pod — and
records memory_analysis / cost_analysis / collective statistics for the
roofline (EXPERIMENTS.md §Dry-run, §Roofline).

The two os.environ lines above MUST stay the first statements: jax locks
the device count on first init. Smoke tests and benchmarks never import
this module (they see 1 device).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
      --shape train_4k [--multi-pod] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro.configs import load_all, ARCH_IDS
from repro.launch import mesh as mesh_lib
from repro.launch import specs as specs_lib

COLLECTIVE_RE = re.compile(
    r"(\w[\w.-]*)\s*=\s*((?:\(|)[a-z0-9_\[\],{}\s/]*(?:\)|))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\(",
)
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str):
    """Sum result-shape bytes per collective kind, with per-computation
    counts so the roofline can scale while-body occurrences by trip count."""
    stats = {}
    comp = "<entry>"
    while_bodies = set(re.findall(r"body=%?([\w.-]+)", hlo_text))
    for line in hlo_text.splitlines():
        m = re.match(r"\s*%?([\w.-]+)\s*\(", line)
        if line.startswith(("%", "ENTRY")) and "{" in line:
            nm = re.match(r"(?:ENTRY\s+)?%?([\w.-]+)", line)
            if nm:
                comp = nm.group(1)
        cm = COLLECTIVE_RE.search(line)
        if cm:
            kind = cm.group(3)
            by = _shape_bytes(line.split("=", 1)[1].split(kind)[0])
            rec = stats.setdefault(kind, {"count": 0, "bytes": 0,
                                          "in_loop_bytes": 0})
            rec["count"] += 1
            rec["bytes"] += by
            if comp in while_bodies:
                rec["in_loop_bytes"] += by
    return stats


def run_one(arch: str, shape: str, multi_pod: bool = False,
            mesh=None, verbose: bool = True, cfg_override=None):
    if mesh is None:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        job = specs_lib.build_job(arch, shape, mesh,
                                  cfg_override=cfg_override)
        with jax.set_mesh(mesh):
            jitted = jax.jit(job.fn, in_shardings=job.in_shardings)
            lowered = jitted.lower(*job.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        coll = collective_stats(hlo)
        res = {
            "arch": arch, "shape": shape, "status": "ok",
            "mesh": list(mesh.devices.shape),
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                # peak_memory_in_bytes only exists on TPU backends; the
                # arg+out+temp sum is the CPU approximation
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None)
                or (getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "output_size_in_bytes", 0)
                    + getattr(mem, "temp_size_in_bytes", 0)) or None,
            },
            "cost": {k: cost.get(k) for k in
                     ("flops", "bytes accessed", "transcendentals")
                     if isinstance(cost, dict) and k in cost},
            "collectives": coll,
            "clients": job.clients,
        }
        if verbose:
            print(f"[ok] {arch} x {shape} mesh={res['mesh']} "
                  f"lower={t_lower:.0f}s compile={t_compile:.0f}s "
                  f"flops={res['cost'].get('flops')}")
        return res
    except Exception as e:  # noqa: BLE001 — report, don't crash the matrix
        if verbose:
            traceback.print_exc()
        return {"arch": arch, "shape": shape, "status": "error",
                "error": f"{type(e).__name__}: {e}",
                "elapsed_s": round(time.time() - t0, 1)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    load_all()
    mesh = mesh_lib.make_production_mesh(multi_pod=args.multi_pod)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"on {len(jax.devices())} host devices")

    results = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in specs_lib.SHAPES:
                results.append(run_one(arch, shape, mesh=mesh))
    else:
        results.append(run_one(args.arch, args.shape, mesh=mesh))

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    bad = [r for r in results if r["status"] == "error"]
    print(f"{len(results)} jobs: "
          f"{sum(r['status'] == 'ok' for r in results)} ok, "
          f"{len(bad)} error")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
