#!/usr/bin/env python3
"""Readings the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --out calib.jsonl

    python3 bench/calibrate.py --limits calib.jsonl --workload <cell>

For each seed: the cell's data and weights, ONE ``run_grid`` call of the
timed configuration driven through the first three updates (the same
call and feed a benchmark run warms up with), and the float32 reference
of those three rounds. It prints, per seed, the numbers the check
compares for the program against the reference and, for the control
seeds, for the control (the reference one precision step below the
configuration's, put in the program's place) and for the faults the
check must catch, planted in the reference put in the program's place
(``unchanged``: the round returns its state; ``halfbatch``: each
minibatch's loss over half of it; ``altered``: one leaf's update
applied twice).
``--program-precisions`` also runs the program at other matmul
precisions and ``--reference-precisions`` the reference at other operand
roundings, as witnesses. One JSON object per line; ``--out``
appends them to a file.

``--limits`` reads such a file back and writes the cell's
``bench/limits/<cell>.json`` by ``set_limits``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

# the control's precision: one step below the configuration's
CONTROL = {"highest": "high"}
FAULTS = {"unchanged": 3.0, "halfbatch": 10.0, "altered": 10.0}
RUNS = 3.0     # the control must read this many times the program's worst
FLOOR = 1e-6   # the least lower reading a limit is set from


def set_limits(records) -> dict:
    """Each compared number's limit from its two readings: the lower, the
    largest the program reads over the seeds; the upper, the smallest the
    control or a planted fault reads, among those that read at least
    ``RUNS`` (the control) or ``FAULTS[fault]`` times the lower. The
    limit lies two thirds of the way from the lower to the upper, on a
    log scale. A lower reading under ``FLOOR`` counts as ``FLOOR``: the
    compared numbers are relative gaps of float32 quantities, and under a
    millionth they are the rounding of those quantities themselves. A
    number with no upper reading gets no limit and is not compared."""
    import harness
    out = {}
    kinds = dict(FAULTS, control=RUNS)
    for name in harness.COMPARED:
        lower = max(r["program"][name] for r in records)
        base = max(lower, FLOOR)
        cands = []
        for kind, factor in kinds.items():
            vals = [r[kind][name] for r in records if kind in r]
            if vals and min(vals) >= factor * base:
                cands.append((min(vals), kind))
        entry = {"lower": lower, "program_seeds": len(records),
                 "readings": {k: [r[k][name] for r in records if k in r]
                              for k in ("program", *kinds)}}
        if cands:
            upper, kind = min(cands)
            entry.update(upper=upper, upper_from=kind,
                         limit=base ** (1 / 3) * upper ** (2 / 3))
        else:
            entry.update(upper=None, limit=None)
        out[name] = entry
    return out


def write_limits(path: str, cell: str, precision: str) -> dict:
    """Writes ``bench/limits/<cell>.json`` from the readings in ``path``
    and logs, for the control and each fault, whether it fails a
    compared number on every seed it was read on."""
    import harness
    with open(path) as f:
        records = [json.loads(line) for line in f if line.strip()]
    records = [r for r in records
               if r["cell"] == cell and r["precision"] == precision]
    limits = dict(set_limits(records), source=path)
    with open(os.path.join(BENCH, "limits", cell + ".json"), "w") as f:
        json.dump(limits, f, indent=1)
        f.write("\n")
    for kind in ("control", *FAULTS):
        fails = all(
            not harness.is_correct(harness.checks_from(r[kind], limits))
            for r in records if kind in r)
        harness.log(f"{cell}: {kind} not correct on every seed: {fails}")
    harness.log(f"{cell}: limits " + json.dumps(
        {n: limits[n]["limit"] for n in harness.COMPARED}))
    return limits


def program_readings(cell, seed: int, precision: str, data, host0,
                     trainable):
    """The weights after the first three updates of one ``run_grid`` call
    of the cell's program at ``precision``."""
    import jax
    from repro.sim import grid as simgrid

    import harness
    params = jax.tree.map(jax.numpy.asarray,
                          harness.common.nest(dict(host0)))
    clock = harness.Clock(math.inf, 10 ** 9, trainable, None)
    with harness.program_precision(precision):
        simgrid.run_grid(
            lambda _seed: params, cell.model.program_loss(), data,
            harness.round_config(cell.mix), harness.CHECK_STEPS,
            grid=harness.grid_config(cell.mix, False),
            freeze_spec=tuple(cell.freeze), seed=seed,
            data_kind=cell.model.TASK.kind, eval_every=1, eval_fn=clock)
    return clock.captured


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--limits", default=None,
                    help="write the cell's limits from this readings file")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--program-precisions", default="")
    ap.add_argument("--reference-precisions", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import harness
    cell = harness.load_cell(args.workload)
    precision = cell.cfg["matmul_precision"]
    if args.limits:
        write_limits(args.limits, args.workload, precision)
        return 0

    import jax
    import numpy as np
    from repro.launch.cache import enable_compile_cache

    import reference as ref_lib

    if jax.devices()[0].platform != "tpu":
        harness.log("no TPU")
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    extra_p = [p for p in args.program_precisions.split(",") if p]
    extra_r = [p for p in args.reference_precisions.split(",") if p]
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.time()
        data = cell.model.TASK.make(cell.cfg, seed)
        params = harness.make_params(cell, seed)
        trainable = harness.trainable_paths(cell, params)
        host0 = {p: np.asarray(v)
                 for p, v in harness.common.flatten(params).items()}
        del params
        runs = {"program": program_readings(cell, seed, precision, data,
                                            host0, trainable)}
        for p in extra_p:
            runs["program_" + p] = program_readings(cell, seed, p, data,
                                                    host0, trainable)
        ys, noise = ref_lib.Reference(cell.model, cell.cfg, cell.mix, data,
                                      host0, trainable, seed).run(
            harness.CHECK_STEPS)
        others = {"reference_" + p: {"precision": p} for p in extra_r}
        if seed in controls:
            others["control"] = {"precision": CONTROL[precision]}
            others.update({f: {"fault": f} for f in FAULTS})
        for name, kw in others.items():
            runs[name], _ = ref_lib.Reference(
                cell.model, cell.cfg, cell.mix, data, host0, trainable,
                seed, **kw).run(harness.CHECK_STEPS)
        y0 = {p: host0[p] for p in trainable}
        frozen = {p: v for p, v in host0.items() if p not in y0}
        rec = {"cell": cell.name, "seed": seed, "precision": precision,
               "client_lr": cell.mix["client_lr"]}
        test = cell.model.TASK.test(data)
        for name, got in runs.items():
            rec[name] = ref_lib.compare(cell.model, cell.cfg, y0, frozen,
                                        got, ys, noise, test)
        rec["seconds"] = time.time() - t
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
