"""Roofline analysis (deliverable g).

For every (arch x shape) on the single-pod mesh, derive:
  compute term    = FLOPs / (chips x 197 TFLOP/s bf16)
  memory term     = HBM bytes / (chips x 819 GB/s)
  collective term = collective bytes / (chips x 50 GB/s/link)

Sources and corrections:
* analytic FLOPs / bytes from benchmarks/analytic.py (primary — XLA's
  cost_analysis counts while-loop bodies once, undercounting scanned
  stacks; see the probe study in EXPERIMENTS.md §Roofline-method);
* collective bytes from the compiled HLO of the dry-run, with while-body
  occurrences scaled by the layer-scan trip count via a 1-group vs
  2-group probe pair (per-group collective bytes = probe difference).

Usage: PYTHONPATH=src python -m benchmarks.roofline \
    --dryrun results/dryrun_single_pod.json --out results/roofline.json
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

CHIPS = 256
PEAK = 197e12
HBM = 819e9
ICI = 50e9


def roofline_terms(arch: str, shape: str, dry: dict, analytic) -> dict:
    coll = dry.get("collectives") or {}
    base = sum(v["bytes"] - v["in_loop_bytes"] for v in coll.values())
    in_loop = sum(v["in_loop_bytes"] for v in coll.values())
    # trip-count scaling for loop collectives
    from repro.configs.base import get_config
    from repro.models.decoder_lm import layer_program
    _, G = layer_program(get_config(arch))
    tau = 2  # local steps of the train step
    coll_bytes = base + in_loop * G * tau
    t_comp = analytic.flops_global / (CHIPS * PEAK)
    t_mem = analytic.bytes_per_device / HBM
    t_coll = coll_bytes / ICI  # HLO shapes are already per-device shards
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    mf_ratio = (analytic.model_flops / analytic.flops_global
                if analytic.flops_global else 0.0)
    return {
        "arch": arch, "shape": shape, **terms,
        "dominant": dom.replace("_s", ""),
        "model_flops": analytic.model_flops,
        "hlo_flops_per_device": (dry.get("cost") or {}).get("flops"),
        "analytic_flops_global": analytic.flops_global,
        "useful_fraction": mf_ratio,
        "collective_bytes": coll_bytes,
        "peak_gib": (dry.get("memory") or {}).get("peak_bytes", 0) / 2**30,
        "status": dry.get("status"),
    }


def build_table(dryrun_path: str):
    from repro.configs import load_all, ARCH_IDS
    from repro.configs.base import get_config
    from repro.launch.specs import SHAPES
    from benchmarks import analytic as ana

    load_all()
    dry = {(r["arch"], r["shape"]): r
           for r in json.load(open(dryrun_path))}
    rows = []
    for arch in ARCH_IDS:
        for shape in SHAPES:
            d = dry.get((arch, shape), {"status": "missing"})
            if d.get("status") != "ok":
                rows.append({"arch": arch, "shape": shape,
                             "status": d.get("status")})
                continue
            step = ana.analytic_step(get_config(arch), shape)
            rows.append(roofline_terms(arch, shape, d, step))
    return rows


def format_table(rows) -> str:
    out = ["arch,shape,compute_s,memory_s,collective_s,dominant,"
           "useful_frac,peak_gib"]
    for r in rows:
        if r.get("status") != "ok" and "compute_s" not in r:
            out.append(f"{r['arch']},{r['shape']},,,,SKIP,,")
            continue
        out.append(
            f"{r['arch']},{r['shape']},{r['compute_s']:.3e},"
            f"{r['memory_s']:.3e},{r['collective_s']:.3e},{r['dominant']},"
            f"{r['useful_fraction']:.2f},{r['peak_gib']:.2f}")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="results/dryrun_single_pod.json")
    ap.add_argument("--out", default="results/roofline.json")
    args = ap.parse_args(argv)
    rows = build_table(args.dryrun)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(format_table(rows))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
