#!/usr/bin/env python3
"""One traced run of a cell, with its window's device time put on the
round program's scopes and its host time on the grid's spans.

    python3 bench/scoped_trace.py --workload <cell> --seed <n> \
        --seconds <s> [--telemetry 0|1] [--fixture <path>]

Runs ``harness.run`` as ``bench/run.py --trace 1`` does and reads the
same trace a second time with ``scopes.read_scoped``: besides the
cell's per-layer metrics it prints the ones read from ``scope_s`` and
``span_s`` (``bench/metrics/{host_prep,host_post,client_step,server}_ms_per_update.py``),
the device time by scope, the host self time by span and the idle
time by span. ``--telemetry
0`` runs the grid without its virtual-time tracer (the spans stay on);
``--fixture`` writes one round of the window in the format of
``bench/testdata/trace_emnist.json``, each operation with its
``scope``. Needs a TPU, as ``bench/run.py`` does; the last line of
standard output is one JSON object.
"""
from __future__ import annotations

import time

START_WALL = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from typing import Dict  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

import trace_reduce  # noqa: E402

SCOPED_METRICS = ("host_prep_ms_per_update", "host_post_ms_per_update",
                  "client_step_ms_per_update", "server_ms_per_update")
ROUND_SPANS = ("grid/round", "grid/flush")


def sum_by_name(pairs) -> Dict[str, float]:
    """Values summed by name."""
    out: Dict[str, float] = {}
    for name, t in pairs:
        out[name] = out.get(name, 0.0) + t
    return out


def idle_split(device, host, lo, hi) -> Dict[str, float]:
    """The window's idle time (no program running on the first chip) by
    the innermost ``grid/`` span open at each instant: unlike
    ``Reduced.gaps``, which gives a whole gap to the span at its
    midpoint, this splits a gap among the spans it crosses."""
    import trace_reduce
    chip = sorted(device)[0]
    busy = trace_reduce.union(trace_reduce.clip(
        [(e.start, e.end) for e in device[chip]["modules"]], lo, hi))
    gaps = trace_reduce.gaps_of(busy, lo, hi)
    spans = [e for e in host if e.name.startswith("grid/")
             and e.end > lo and e.start < hi]
    points = sorted({lo, hi} | {t for g in gaps for t in g}
                    | {t for e in spans for t in (e.start, e.end)
                       if lo < t < hi})
    out: Dict[str, float] = {}
    gi = 0
    for a, b in zip(points, points[1:]):
        mid = 0.5 * (a + b)
        while gi < len(gaps) and gaps[gi][1] <= mid:
            gi += 1
        if gi == len(gaps) or gaps[gi][0] > mid:
            continue
        name = trace_reduce.attribute((a, b), spans)
        out[name] = out.get(name, 0.0) + b - a
    return out


def fixture(device, host, lo, hi, kernels) -> dict:
    """The stretch of one round (the middle ``grid/round`` or
    ``grid/flush`` span of the window) in the fixture format; operation
    names shortened to the instruction's name and stats to the kernel
    names they hold."""
    rounds = sorted((e for e in host if e.name in ROUND_SPANS
                     and e.start >= lo and e.end <= hi),
                    key=lambda e: e.start)
    mid = rounds[len(rounds) // 2]
    a, b = mid.start, mid.end

    def keep(e):
        return e.end > a and e.start < b

    def rec(e, **extra):
        meta = " ".join(k for k in kernels if k in e.meta)
        return dict(name=e.short, start=round(e.start - a, 9),
                    dur=round(e.dur, 9), meta=meta, **extra)

    return {"window": [0.0, round(b - a, 9)],
            "device": {chip: {"modules": [rec(e) for e in lines["modules"]
                                          if keep(e)],
                              "ops": [rec(e, scope=e.scope)
                                      for e in lines["ops"] if keep(e)]}
                       for chip, lines in device.items()},
            "host": [rec(e) for e in host if keep(e)
                     and e.name.startswith(("grid/", "bench/"))
                     and e.name != "bench/window"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--telemetry", type=int, choices=(0, 1), default=1)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.cache import enable_compile_cache

    import harness
    import scopes

    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        harness.log(f"no TPU: jax found {devs[0].platform} devices")
        return 2
    peaks = harness.load_peaks(devs[0].device_kind)
    harness.log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    if not args.telemetry:
        grid_config = harness.grid_config
        harness.grid_config = lambda mix, trace: dataclasses.replace(
            grid_config(mix, trace), telemetry=None)

    @dataclasses.dataclass
    class ScopedContext(harness.MetricContext):
        scope_s: dict = dataclasses.field(default_factory=dict)
        span_s: dict = dataclasses.field(default_factory=dict)

    scoped = {}
    traced_metrics = harness.traced_metrics

    def traced_and_scoped(cell, trace_dir, updates, peaks):
        metrics, extra = traced_metrics(cell, trace_dir, updates, peaks)
        try:
            scope(cell, trace_dir, updates, peaks, metrics)
        except Exception:   # keep the run's own result, report the fault
            scoped["error"] = traceback.format_exc()
        return metrics, extra

    def scope(cell, trace_dir, updates, peaks, metrics):
        path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        t0 = time.perf_counter()
        device, host = scopes.read_scoped(path)
        red = trace_reduce.reduce(device, host,
                                  kernels=harness.FUSED_KERNELS)
        sred = scopes.reduce_scoped(device, host)
        K, N = harness.tail_shape(cell)
        ctx = ScopedContext(
            reduced=red, updates=updates,
            flops_per_update=harness.flops_per_update(cell),
            tail_bytes_per_update=harness.counters.agg_tail_bytes(K, N),
            tail_flops_per_update=harness.counters.agg_tail_flops(K, N),
            peak=peaks, scope_s=sred.scope_s, span_s=sred.span_s)
        for name in SCOPED_METRICS:
            reader = harness.load_module(
                os.path.join(BENCH, "metrics", name + ".py"),
                "metric_" + name)
            v = reader.read(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": "ms"}
        ops_total = sum(red.op_s.values())
        idle = red.window_s - red.busy_s
        gaps = sum_by_name(red.gaps)
        children = sum(t for n, t in gaps.items()
                       if n.startswith("grid/") and n not in ROUND_SPANS)
        lo, hi = trace_reduce.find_window(host, "bench/window")
        scoped.update(
            read_s=time.perf_counter() - t0,
            updates=updates, window_s=red.window_s, busy_s=red.busy_s,
            op_self_s=ops_total, scope_s=sred.scope_s, span_s=sred.span_s,
            idle_s=idle, idle_by_span=gaps,
            idle_split=idle_split(device, host, lo, hi),
            idle_in_named_children=children / idle if idle > 0 else None,
            scoped_share_of_ops=(1 - sred.scope_s.get(scopes.UNSCOPED, 0.0)
                                 / ops_total) if ops_total else None,
            updates_per_s=updates / red.window_s,
            idle_ms_per_update=1e3 * idle / updates if updates else None,
            device_ms_per_update=(1e3 * red.busy_s / updates
                                  if updates else None))
        scoped["unscoped_top"] = sorted(sum_by_name(
            (e.short, e.dur) for lines in device.values()
            for e in lines["ops"]
            if not e.scope and lo <= e.start <= hi).items(),
            key=lambda x: -x[1])[:12]
        if args.fixture:
            with open(args.fixture, "w") as f:
                json.dump(fixture(device, host, lo, hi,
                                  harness.FUSED_KERNELS), f)

    harness.traced_metrics = traced_and_scoped
    res = harness.run(cell, args.seed, args.seconds, True, START_WALL, peaks)
    harness.log(f"correct {res['correct']}")
    out = {k: res[k] for k in ("correct", "metrics", "device", "breakdown",
                               "compilations_in_window", "checks")
           if k in res}
    out["telemetry"] = bool(args.telemetry)
    out["scoped"] = scoped
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
