#!/usr/bin/env python3
"""Benchmark entry: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the cell
asks for; without them it exits nonzero and prints no result. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``: each number the
correctness check compared, with its limit. The same numbers close
standard error.
"""
from __future__ import annotations

import time

START_WALL = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.cache import enable_compile_cache

    import harness

    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        harness.log(f"no TPU: jax found {devs[0].platform} devices")
        return 2
    if len(devs) < cell.chips:
        harness.log(f"{args.workload} needs {cell.chips} chips, jax found "
                    f"{len(devs)}")
        return 2
    peaks = harness.load_peaks(devs[0].device_kind)
    harness.log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    res = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      START_WALL, peaks)
    harness.log(f"correct {res['correct']}")
    for name, c in res["checks"].items():
        harness.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    keys = ("correct", "attempted", "failed", "metrics", "device",
            "breakdown", "compilations_in_window", "checks")
    print(json.dumps({k: res[k] for k in keys if k in res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
