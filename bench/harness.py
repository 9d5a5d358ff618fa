"""One run of one benchmark cell: set-up, the timed window, the check.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<name>.json`` with its module beside it)
and a traffic mix (``bench/mixes/<traffic>.json``); its limits are in
``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``. Nothing here names a cell, and nothing
here knows what an example holds: the configuration's module makes the
data and states the loss (``bench/configs/tasks.py`` lists what it
defines).

A run makes the data and the weights from the seed on the device, then
makes ONE ``repro.sim.grid.run_grid`` call, the entry every user of the
program goes through. Its ``eval_fn`` hook, called once after every
applied server update, is the run's clock: the first updates warm up
(tracing and compiling the round program inside the call, and handing
the weights after the first three to the check), then the window opens
and stays open for ``seconds``; the first update that completes after
that closes it, and the hook ends the call by raising.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax
import numpy as np

import counters
import reference as ref_lib
import trace_reduce
from configs import common

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CHECK_STEPS = 3
FUSED_KERNELS = ("agg_tail_stats", "agg_tail_pack", "agg_tail_apply")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    model: object
    mix: dict
    limits: Optional[dict]
    per_layer: List[dict]
    end_to_end: List[str]

    @property
    def freeze(self) -> List[str]:
        return list(self.cfg["freeze"]) if self.mix["freeze"] == "fedpt" \
            else []


def load_config(path: str):
    """A configuration file and the module of its plain reference."""
    cfg = load_json(path)
    model = load_module(os.path.join(os.path.dirname(path), cfg["module"]),
                        "config_" + cfg["name"].replace("-", "_"))
    return cfg, model


def load_cell(name: str, benchmark: Optional[dict] = None) -> Cell:
    bm = benchmark or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    wl = {w["name"]: w for w in bm["workloads"]}[name]
    centry = {c["name"]: c for c in bm["configs"]}[wl["config"]]
    cfg, model = load_config(os.path.join(ROOT, centry["file"]))
    mix = load_json(os.path.join(BENCH, "mixes", wl["traffic"] + ".json"))
    lpath = os.path.join(BENCH, "limits", name + ".json")
    limits = load_json(lpath) if os.path.exists(lpath) else None
    per_layer = [m for m in bm["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m["name"] for m in bm["end_to_end"]
                  if name in m.get("workloads", [name])]
    return Cell(name, wl["chips"], cfg, model, mix, limits, per_layer,
                end_to_end)


# ---------------------------------------------------------------------------
# the weights from the seed (the data is the configuration module's)


def make_params(cell: Cell, seed: int):
    key = jax.random.fold_in(jax.random.key(seed), 2)
    return jax.jit(cell.model.init_params, static_argnums=0)(
        _Hashable(cell.cfg), key)


class _Hashable(dict):
    """A config dict usable as a static jit argument."""
    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


# ---------------------------------------------------------------------------
# the program under test


def round_config(mix: dict):
    from repro.core import fedpt
    return fedpt.RoundConfig(
        clients_per_round=mix["cohort"], local_steps=mix["local_steps"],
        local_batch=mix["local_batch"], client_opt=mix["client_opt"],
        client_lr=mix["client_lr"], server_opt=mix["server_opt"],
        server_lr=mix["server_lr"], server_momentum=mix["server_momentum"],
        dp_clip_norm=mix["dp_clip_norm"],
        dp_noise_multiplier=mix["dp_noise_multiplier"],
        uplink_bits=mix["uplink_bits"])


def grid_config(mix: dict, trace: bool):
    from repro.obs import trace as trace_lib
    from repro.sim import grid as simgrid
    kw = {k: mix[k] for k in ("concurrency", "goal_count", "lanes",
                              "staleness") if k in mix}
    return simgrid.GridConfig(
        mode=mix["mode"], fleet=mix["fleet"],
        telemetry=(trace_lib.TelemetryConfig(profile=True) if trace
                   else None), **kw)


def program_precision(p: str):
    """The program's matmul precision: the configuration states it;
    ``default`` leaves JAX's own (one bf16 pass for float32 on a TPU)."""
    return (contextlib.nullcontext() if p == "default"
            else jax.default_matmul_precision(p))


class WindowClosed(Exception):
    pass


class Clock:
    """The ``eval_fn`` hook: counts updates, keeps host copies of the
    trainable weights after the first ``CHECK_STEPS``, opens the window
    after ``warmup`` updates and closes it on the first update completed
    ``seconds`` later."""

    def __init__(self, seconds: float, warmup: int, trainable: List[str],
                 trace_dir: Optional[str]):
        self.seconds, self.warmup = seconds, warmup
        self.trainable = trainable
        self.trace_dir = trace_dir
        self.n = 0
        self.captured: List[Dict[str, np.ndarray]] = []
        self.done: List[float] = []
        self.t0 = self.t_end = self.t0_wall = None
        self.compiles = 0
        self.compiles_at_open = None
        self._span = None

    def on_event(self, name, *_a, **_k):
        if name == COMPILE_EVENT:
            self.compiles += 1

    def __call__(self, params):
        now = time.perf_counter()
        self.n += 1
        if self.n <= CHECK_STEPS:
            flat = common.flatten(params)
            self.captured.append({p: np.asarray(flat[p])
                                  for p in self.trainable})
        if self.n == self.warmup:
            jax.block_until_ready(params)
            if self.trace_dir is not None:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self._span = jax.profiler.TraceAnnotation("bench/window")
                self._span.__enter__()
            self.compiles_at_open = self.compiles
            self.t0_wall = time.time()
            self.t0 = time.perf_counter()
        elif self.n > self.warmup:
            self.done.append(now)
            if now - self.t0 >= self.seconds:
                jax.block_until_ready(params)
                self.t_end = time.perf_counter()
                if self._span is not None:
                    self._span.__exit__(None, None, None)
                raise WindowClosed
        return {}


def trainable_paths(cell: Cell, params) -> List[str]:
    return sorted(p for p in common.flatten(params)
                  if not counters.is_frozen(p, cell.freeze))


# ---------------------------------------------------------------------------
# one run


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        start_wall: float, peaks: Optional[dict] = None) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax.monitoring as monitoring
    from repro.sim import grid as simgrid

    mix, cfg, model = cell.mix, cell.cfg, cell.model
    data = model.TASK.make(cfg, seed)
    params = make_params(cell, seed)
    flat0 = common.flatten(params)
    trainable = trainable_paths(cell, params)
    host0 = {p: np.asarray(v) for p, v in flat0.items()}
    tmp = tempfile.TemporaryDirectory() if trace else None
    clock = Clock(seconds, mix["warmup_updates"], trainable,
                  tmp.name if tmp else None)
    monitoring.register_event_duration_secs_listener(clock.on_event)
    try:
        with program_precision(cfg["matmul_precision"]):
            simgrid.run_grid(
                lambda _seed: params, model.program_loss(), data,
                round_config(mix), 10 ** 9, grid=grid_config(mix, trace),
                freeze_spec=tuple(cell.freeze), seed=seed,
                data_kind=model.TASK.kind, eval_every=1, eval_fn=clock)
        raise RuntimeError("run_grid returned before the window closed")
    except WindowClosed:
        pass
    finally:
        monitoring.unregister_event_duration_listener(clock.on_event)
    del params, flat0
    gc.collect()
    mem = peak_bytes()
    window = clock.t_end - clock.t0
    updates = len(clock.done)
    intervals = np.diff(np.asarray([clock.t0] + clock.done)) * 1e3
    compiles = clock.compiles - clock.compiles_at_open
    log(f"[{cell.name}] seed {seed}: set-up {clock.t0_wall - start_wall:.3f}"
        f" s, window {window:.3f} s, {updates} updates, {compiles} "
        f"compilations in the window")

    result = {"attempted": updates, "failed": 0}
    dev = dict(device_info(), memory_peak_bytes=mem)
    if trace:
        jax.profiler.stop_trace()
        metrics, extra = traced_metrics(cell, tmp.name, updates, peaks)
        tmp.cleanup()
        dev.update(extra["device"])
        result["breakdown"] = extra["breakdown"]
    else:
        metrics = {
            "updates_per_s": {"value": updates / window,
                              "unit": "updates/s"},
            "update_ms_p95": {"value": p95(intervals), "unit": "ms"},
            "peak_hbm_gib": {"value": mem / 2 ** 30, "unit": "GiB"},
            "setup_s": {"value": clock.t0_wall - start_wall, "unit": "s"},
        }
        metrics = {k: v for k, v in metrics.items() if k in cell.end_to_end}
    checks = check(cell, data, host0, trainable, clock.captured, seed)
    correct = is_correct(checks)
    result.update(correct=correct, metrics=metrics, device=dev,
                  compilations_in_window=compiles, checks=checks)
    return result


def p95(values) -> float:
    values = list(values)
    if len(values) < 2:
        return float(values[0])
    return float(statistics.quantiles(values, n=100,
                                      method="inclusive")[94])


COMPARED = ("loss_gap", "grad_gap", "change_gap")


def check(cell: Cell, data, host0: dict, trainable: List[str],
          captured, seed: int) -> Dict[str, dict]:
    """The reference's first ``CHECK_STEPS`` rounds against the weights
    the window's own call produced; each number with its limit."""
    readings = readings_against(cell, data, host0, trainable, captured,
                                seed)
    log(f"[{cell.name}] readings {json.dumps(readings)}")
    return checks_from(readings, cell.limits)


def checks_from(readings: dict, limits: Optional[dict]) -> Dict[str, dict]:
    """The compared numbers with their limits. A number whose limits file
    entry has ``"limit": null`` had no upper reading and is not compared;
    a cell without a limits file compares against NaN, which fails."""
    if limits is None:
        return {n: {"value": readings[n], "limit": float("nan")}
                for n in COMPARED}
    return {n: {"value": readings[n], "limit": limits[n]["limit"]}
            for n in COMPARED if limits[n]["limit"] is not None}


def is_correct(checks: Dict[str, dict]) -> bool:
    """At least one number compared, each finite and within its limit."""
    return bool(checks) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())


def readings_against(cell: Cell, data, host0: dict,
                     trainable: List[str], captured, seed: int) -> dict:
    ref = ref_lib.Reference(cell.model, cell.cfg, cell.mix, data, host0,
                            trainable, seed)
    ys, noise = ref.run(CHECK_STEPS)
    y0 = {p: host0[p] for p in trainable}
    frozen = {p: v for p, v in host0.items() if p not in y0}
    return ref_lib.compare(cell.model, cell.cfg, y0, frozen, captured, ys,
                           noise, cell.model.TASK.test(data))


# ---------------------------------------------------------------------------
# traced run


def flops_per_update(cell: Cell) -> int:
    mix = cell.mix
    return (counters.step_flops(cell.model.layers(cell.cfg), cell.freeze)
            * mix["cohort"] * mix["local_steps"] * mix["local_batch"])


def tail_shape(cell: Cell):
    """(K, N) of the aggregation buffer: cohort x padded trainable size."""
    leaves = [(p, shape) for p, shape, _k, _f in cell.model.specs(cell.cfg)
              if not counters.is_frozen(p, cell.freeze)]
    return cell.mix["cohort"], counters.padded_size(
        int(np.prod(s)) for _p, s in leaves)


def traced_metrics(cell: Cell, trace_dir: str, updates: int, peaks: dict):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    device, host = trace_reduce.read_xplane(paths[0])
    red = trace_reduce.reduce(device, host, kernels=FUSED_KERNELS)
    K, N = tail_shape(cell)
    ctx = MetricContext(reduced=red, updates=updates,
                        flops_per_update=flops_per_update(cell),
                        tail_bytes_per_update=counters.agg_tail_bytes(K, N),
                        tail_flops_per_update=counters.agg_tail_flops(K, N),
                        peak=peaks)
    metrics = {}
    for m in cell.per_layer:
        reader = load_module(os.path.join(BENCH, "metrics",
                                          m["name"] + ".py"),
                             "metric_" + m["name"])
        v = reader.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"[{cell.name}] trace: {red.n_device_events} device events, busy "
        f"{red.busy_s} s of {red.window_s} s, kernels {red.kernel_s}")
    extra = {"device": {"busy_s": red.busy_s, "window_s": red.window_s},
             "breakdown": {"device_ops": red.top_ops(),
                           "idle_gaps": red.top_gaps()}}
    return metrics, extra


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader (``bench/metrics/<name>.py``,
    ``read(ctx) -> float | None``) gets."""
    reduced: trace_reduce.Reduced
    updates: int
    flops_per_update: int
    tail_bytes_per_update: int
    tail_flops_per_update: int
    peak: dict


def load_peaks(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return table[kind]
