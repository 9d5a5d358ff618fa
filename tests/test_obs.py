"""Grid telemetry (src/repro/obs/): the metrics registry, the structured
event tracer and its exporters, and the acceptance guarantees — telemetry
off is bit-identical (sync) / lane-exact (async), and telemetry on emits
a schema-valid stream whose virtual timestamps cross-check against
GridResult's own totals."""
import dataclasses
import glob
import json
import math
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.partition as part
from repro.core import comm, fedpt
from repro.core import flat as flat_lib
from repro.data import synthetic as syn
from repro.nn import basic
from repro.obs import export as export_lib
from repro.obs import metrics as metrics_lib
from repro.obs import profiling as prof_lib
from repro.obs import schema as schema_lib
from repro.obs import trace as trace_lib
from repro.sim import devices as dev_lib
from repro.sim import dynamics as dyn_lib
from repro.sim import grid as simgrid


def init_fn(seed):
    return {"dense": basic.init_dense(seed, "dense", 64, 4, jnp.float32,
                                      bias=True)}


def loss_fn(params, b):
    x = b["images"].reshape(b["images"].shape[0], -1)
    logits = basic.dense(x, params["dense"])
    lp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(lp, b["labels"][:, None], 1)), {}


def make_ds(n_clients=10, seed=0):
    return syn.make_federated_images(n_clients, 30, (8, 8, 1), 4, seed=seed,
                                     test_examples=64)


RC = fedpt.RoundConfig(4, 2, 8, "sgd", 0.1, "sgd", 1.0)

TIER_PLAN = {"full": (), "mid": (r"/bias$",), "lite": (r"/kernel$",)}


def _fleet(mults, **kw):
    mb = 1024.0 * 1024.0
    return dev_lib.Fleet(name="test", profiles=[
        dev_lib.DeviceProfile(downlink_bps=mb, uplink_bps=mb,
                              compute_multiplier=m, **kw) for m in mults])


def _assert_same_run(a, b):
    assert [h["loss"] for h in a.history] == [h["loss"] for h in b.history]
    for ha, hb in zip(a.history, b.history):
        assert ha["virtual_seconds"] == hb["virtual_seconds"]
    for (pa, la), (pb, lb) in zip(basic.flatten_params(a.y),
                                  basic.flatten_params(b.y)):
        assert pa == pb and bool(jnp.all(la == lb)), pa
    assert a.scheduler_stats == b.scheduler_stats
    assert a.comm.measured_down_bytes == b.comm.measured_down_bytes
    assert a.comm.measured_up_bytes == b.comm.measured_up_bytes


# ---------------------------------------------------------------------------
# Metrics registry


def test_metrics_counter_gauge_histogram():
    reg = metrics_lib.MetricsRegistry()
    c = reg.counter("uploads")
    c.inc()
    c.inc(3, label=0)
    c.inc(2, label=1)
    assert c.value == 6
    assert c.get(0) == 3 and c.get(1) == 2 and c.get(9, -1) == -1
    assert reg.counter("uploads") is c       # create-on-demand, cached
    g = reg.gauge("compute")
    assert g.value is None
    g.set(2.5)
    g.set(0.5, label=1)
    assert g.value == 0.5 and g.get(1) == 0.5 and g.get(0) is None
    h = reg.histogram("rtt")
    assert h.summary() == {"count": 0, "sum": 0.0, "mean": 0.0,
                           "min": 0.0, "max": 0.0}
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    assert h.summary() == {"count": 3, "sum": 6.0, "mean": 2.0,
                           "min": 1.0, "max": 3.0}


def test_metrics_snapshot_json_roundtrip():
    reg = metrics_lib.MetricsRegistry()
    reg.counter("tier_up_bytes").inc(100, label=2)
    reg.gauge("sigma").set(0.4)
    reg.histogram("round_seconds").observe(1.5)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["v"] == metrics_lib.SNAPSHOT_VERSION
    # labels stringify so the snapshot survives json round-trips
    assert snap["counters"]["tier_up_bytes"]["labels"] == {"2": 100}
    assert snap["gauges"]["sigma"]["value"] == 0.4
    assert snap["histograms"]["round_seconds"]["count"] == 1


# ---------------------------------------------------------------------------
# Schema validation


def test_schema_accepts_valid_records():
    good = [
        {"v": 1, "kind": "dispatch", "t": 0.0, "dur": 2.5, "cid": 3,
         "tier": None, "down_bytes": 100, "up_bytes": 50, "outcome": "ok"},
        {"v": 1, "kind": "upload", "t": 2.5, "cid": 3, "up_bytes": 50,
         "rtt": 2.5, "staleness": 0},
        {"v": 1, "kind": "retry", "t": 1.0, "backoff": 30.0},
        {"v": 1, "kind": "flush", "t": 9.0, "version": 2,
         "buffer_fill": 3.0, "staleness_mean": 0.5, "staleness_max": 2.0},
        {"v": 1, "kind": "round", "t": 0.0, "dur": 4.0, "round": 0,
         "participants": 4.0, "cohort": 5, "loss": 1.38},
        {"v": 1, "kind": "dp_flush", "t": 9.0, "flush": 0, "n_real": 3,
         "multiplicity": 1, "sigma": 0.066, "epsilon": 1.2,
         "delta": 1e-5, "padded": False},
        {"v": 1, "kind": "tier_upload", "t": 30.0, "tier_name": "lite",
         "down_bytes": 1000, "up_bytes": 400, "transfers": 5, "uploads": 4},
    ]
    assert schema_lib.validate_records(good) == []


def test_schema_rejects_malformed_records():
    assert schema_lib.validate_record([1, 2]) != []          # not an object
    assert any("unknown kind" in e for e in schema_lib.validate_record(
        {"v": 1, "kind": "teleport", "t": 0.0}))
    assert any("missing required" in e for e in schema_lib.validate_record(
        {"v": 1, "kind": "upload", "t": 0.0, "cid": 1}))     # no up_bytes
    assert any("wrong type" in e for e in schema_lib.validate_record(
        {"v": 1, "kind": "dispatch", "t": 0.0, "cid": True}))  # bool != int
    assert any("unexpected field" in e for e in schema_lib.validate_record(
        {"v": 1, "kind": "retry", "t": 0.0, "speed": 9}))
    assert any("v=" in e for e in schema_lib.validate_record(
        {"v": 99, "kind": "retry", "t": 0.0}))
    assert any("t=" in e for e in schema_lib.validate_record(
        {"v": 1, "kind": "retry", "t": -1.0}))
    assert any("dur=" in e for e in schema_lib.validate_record(
        {"v": 1, "kind": "round", "t": 0.0, "dur": math.inf, "round": 0}))


def test_resolve_telemetry_variants():
    assert trace_lib.resolve_telemetry(None) is None
    cfg = trace_lib.TelemetryConfig(jsonl_path="x.jsonl")
    assert trace_lib.resolve_telemetry(cfg) is cfg
    for spec in (True, "on", "memory"):
        got = trace_lib.resolve_telemetry(spec)
        assert isinstance(got, trace_lib.TelemetryConfig)
        assert got.jsonl_path is None and not got.profile
    got = trace_lib.resolve_telemetry({"perfetto_path": "t.json"})
    assert got.perfetto_path == "t.json"
    with pytest.raises(ValueError, match="telemetry"):
        trace_lib.resolve_telemetry(42)


def test_null_tracer_is_noop():
    nt = trace_lib.NULL_TRACER
    assert nt.enabled is False and nt.events == ()
    assert nt.span("dispatch", 0.0, 1.0, cid=1) is None
    assert nt.instant("flush", 0.0) is None
    assert nt.events == ()


# ---------------------------------------------------------------------------
# Exporters


def test_perfetto_track_layout():
    recs = [
        trace_lib.TraceRecord("dispatch", 1.0, 2.0, {"cid": 7,
                                                     "tier": None}),
        trace_lib.TraceRecord("upload", 3.0, None, {"cid": 7,
                                                    "up_bytes": 10}),
        trace_lib.TraceRecord("flush", 3.0, None, {"version": 0,
                                                   "buffer_fill": 1.0}),
        trace_lib.TraceRecord("dp_flush", 3.0, None,
                              {"flush": 0, "n_real": 1, "multiplicity": 1}),
    ]
    doc = export_lib.perfetto_trace(recs)
    ev = {e["name"]: e for e in doc["traceEvents"] if e.get("ph") != "M"}
    # client events on the clients process, one thread per cid
    assert ev["dispatch"]["pid"] == 1 and ev["dispatch"]["tid"] == 7
    assert ev["dispatch"]["ph"] == "X"
    assert ev["dispatch"]["ts"] == 1.0e6 and ev["dispatch"]["dur"] == 2.0e6
    # None payload values are dropped from args, never serialized
    assert "tier" not in ev["dispatch"]["args"]
    assert ev["upload"]["ph"] == "i" and ev["upload"]["s"] == "t"
    # server events on pid 0: flushes with the rounds, dp on "privacy"
    assert ev["flush"]["pid"] == 0 and ev["flush"]["tid"] == 0
    assert ev["dp_flush"]["tid"] == 1
    meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    names = {(m["name"], m.get("pid"), m.get("tid")): m["args"]["name"]
             for m in meta}
    assert names[("process_name", 0, None)] == "server"
    assert names[("process_name", 1, None)] == "clients"
    assert names[("thread_name", 1, 7)] == "client 7"


def _host_spans(trace_dir):
    """(name, start_ns, end_ns, stats) of every span on the host plane of
    the one trace ``jax.profiler`` wrote under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    pd = jax.profiler.ProfileData.from_file(paths[0])
    out = []
    with warnings.catch_warnings():
        # the profiler's stats type warns on iteration (no __module__)
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def _check_nested(spans, parent, children, steps, after=()):
    """Every ``parent`` span carries its ``step_num``, one per step in
    order, and holds each of ``children`` at least once; every child span
    lies inside a parent. ``after`` spans follow their parent step's
    span and precede the next one."""
    rounds = sorted((s for s in spans if s[0] == parent),
                    key=lambda s: s[1])
    assert [s[3].get("step_num") for s in rounds] == list(steps)
    for name in children:
        found = [s for s in spans if s[0] == name]
        assert found, name
        for s in found:
            assert any(p[1] <= s[1] and s[2] <= p[2] for p in rounds), name
        for p in rounds:
            assert any(p[1] <= s[1] and s[2] <= p[2] for s in found), \
                (name, p[3])
    for name in after:
        found = sorted((s for s in spans if s[0] == name),
                       key=lambda s: s[1])
        assert found, name
        for s in found:
            assert not any(p[1] <= s[1] < p[2] for p in rounds), name


def test_profiling_annotation_wrappers(tmp_path):
    """``annotate``/``annotate_map`` wrap calls, ``span`` and
    ``round_span`` are plain profiler annotations: all land on the host
    plane of a trace, with their args and step number."""
    calls = []

    def fn(x):
        calls.append(x)
        return x + 1

    wrapped = prof_lib.annotate(fn, "test/fn")
    m = prof_lib.annotate_map({"a": fn}, "test/map")
    assert wrapped(2) == 3 and m["a"](5) == 6 and calls == [2, 5]
    with jax.profiler.trace(str(tmp_path)):
        with prof_lib.round_span("test/round", 7):
            assert wrapped(1) == 2 and m["a"](2) == 3
            with prof_lib.span("test/span", clients=3) as sp:
                sp.set_metadata(bytes=12)
    spans = {s[0]: s for s in _host_spans(str(tmp_path))}
    for name in ("test/round", "test/fn", "test/map[a]", "test/span"):
        assert name in spans, name
    assert spans["test/round"][3]["step_num"] == 7
    assert spans["test/span"][3]["clients"] == 3
    assert spans["test/span"][3]["bytes"] == 12
    _check_nested(list(spans.values()), "test/round",
                  ("test/fn", "test/map[a]", "test/span"), [7])


SYNC_SPANS = ("grid/plan", "grid/cohort_batch", "grid/round_fn",
              "grid/wait", "grid/bookkeeping", "grid/eval_fn",
              "grid/checkpoint")
ASYNC_SPANS = ("grid/restack", "grid/lane_step[None]", "grid/server_apply",
               "grid/wait", "grid/bookkeeping", "grid/eval_fn")


# ---------------------------------------------------------------------------
# Acceptance: telemetry off is exactly free


def test_sync_telemetry_off_bit_identical():
    """GridConfig.telemetry=None and telemetry='memory' must produce
    bit-for-bit the same sync run — tracing consumes no PRNG draws."""
    ds = make_ds()
    gc = simgrid.GridConfig(fleet="pareto-mobile", over_selection=1.3,
                            straggler_deadline=120.0)
    off = simgrid.run_grid(init_fn, loss_fn, ds, RC, 4, grid=gc, seed=3)
    on = simgrid.run_grid(
        init_fn, loss_fn, ds, RC, 4, seed=3,
        grid=dataclasses.replace(gc, telemetry="memory"))
    _assert_same_run(off, on)
    assert off.telemetry is None and on.telemetry is not None


def test_async_telemetry_off_lane_exact():
    ds = make_ds(n_clients=16)
    gc = simgrid.GridConfig(mode="async", fleet="pareto-mobile",
                            concurrency=6, goal_count=3,
                            staleness="polynomial")
    off = simgrid.run_grid(init_fn, loss_fn, ds, RC, 8, grid=gc, seed=2)
    on = simgrid.run_grid(
        init_fn, loss_fn, ds, RC, 8, seed=2,
        grid=dataclasses.replace(gc, telemetry="memory"))
    _assert_same_run(off, on)
    for ha, hb in zip(off.history, on.history):
        assert ha["staleness_mean"] == hb["staleness_mean"]


def _eval(params):
    return {"norm": float(jnp.sum(params["dense"]["kernel"] ** 2))}


def test_sync_profile_spans_run(tmp_path):
    """The sync round loop's wall-clock spans are always on: under a
    profiler session each round is one grid/round (step_num = round)
    holding every named child span, and the run is bit-identical to
    one with no profiler session."""
    ds = make_ds()
    gc = simgrid.GridConfig(fleet="pareto-mobile", over_selection=1.3,
                            straggler_deadline=120.0, checkpoint_every=1,
                            checkpoint_dir=str(tmp_path / "ckpt"))
    kw = dict(grid=gc, seed=3, eval_every=1, eval_fn=_eval)
    ref = simgrid.run_grid(init_fn, loss_fn, ds, RC, 3, **kw)
    with jax.profiler.trace(str(tmp_path / "trace")):
        prof = simgrid.run_grid(init_fn, loss_fn, ds, RC, 3, **kw)
    _assert_same_run(ref, prof)
    assert ref.history == prof.history
    spans = _host_spans(str(tmp_path / "trace"))
    _check_nested(spans, "grid/round", SYNC_SPANS, [0, 1, 2])
    batch = [s for s in spans if s[0] == "grid/cohort_batch"][0][3]
    assert batch["clients"] == RC.clients_per_round
    # images (C, tau, batch, 8, 8, 1) float32 + labels (C, tau, batch) int32
    assert batch["bytes"] == RC.clients_per_round * RC.local_steps \
        * RC.local_batch * (64 * 4 + 4)


def test_async_profile_annotations_run(tmp_path):
    """The async flush's wall-clock spans are always on: under a
    profiler session each flush is one grid/flush (step_num = updates
    applied before it) holding every named child span, each checkpoint
    follows its flush, and the run is bit-identical to one with no
    profiler session."""
    ds = make_ds()
    gc = simgrid.GridConfig(mode="async", concurrency=4, goal_count=2,
                            checkpoint_every=1,
                            checkpoint_dir=str(tmp_path / "ckpt"))
    kw = dict(grid=gc, seed=1, eval_every=1, eval_fn=_eval)
    ref = simgrid.run_grid(init_fn, loss_fn, ds, RC, 3, **kw)
    with jax.profiler.trace(str(tmp_path / "trace")):
        prof = simgrid.run_grid(init_fn, loss_fn, ds, RC, 3, **kw)
    _assert_same_run(ref, prof)
    assert ref.history == prof.history
    spans = _host_spans(str(tmp_path / "trace"))
    _check_nested(spans, "grid/flush", ASYNC_SPANS, [0, 1, 2],
                  after=("grid/checkpoint",))


def test_round_programs_carry_scopes():
    """The round programs name their three stages with jax.named_scope:
    the sync round, and the async lane step and buffered apply."""
    rc = dataclasses.replace(RC, dp_clip_norm=1.0, dp_noise_multiplier=0.1,
                             uplink_bits=8)
    y = init_fn(0)
    ds = make_ds()
    batch, w = syn.cohort_batch(ds, np.arange(rc.clients_per_round),
                                rc.local_steps, rc.local_batch,
                                np.random.default_rng(0))
    round_fn, sopt = fedpt.make_round_fn(loss_fn, rc)
    key = jax.random.key(0)
    text = jax.jit(round_fn).lower(y, sopt.init(y), {}, batch,
                                   jnp.asarray(w), key).as_text(
                                       debug_info=True)
    for scope in (fedpt.SCOPE_CLIENT_STEP, fedpt.SCOPE_AGG_TAIL,
                  fedpt.SCOPE_SERVER_APPLY):
        assert scope in text, scope
    lane = {k: v[:2] for k, v in batch.items()}
    text = jax.jit(fedpt.make_lane_step(loss_fn, rc, 2)).lower(
        y, {}, lane).as_text(debug_info=True)
    assert fedpt.SCOPE_CLIENT_STEP in text
    deltas = jnp.zeros((2, flat_lib.FlatLayout.of(y).size))
    apply_fn = fedpt.make_buffered_apply(sopt)
    text = jax.jit(apply_fn).lower(y, sopt.init(y), deltas,
                                   jnp.ones(2)).as_text(debug_info=True)
    for scope in (fedpt.SCOPE_AGG_TAIL, fedpt.SCOPE_SERVER_APPLY):
        assert scope in text, scope


# ---------------------------------------------------------------------------
# Satellite: one normalized stats schema across scheduling modes


def test_stats_schema_normalized_across_modes():
    """Both modes emit every STAT_KEYS key, with explicit zeros for
    counters that cannot fire in that mode — no more async-only retries
    or sync-only offline."""
    ds = make_ds()
    sync = simgrid.run_grid(init_fn, loss_fn, ds, RC, 2, seed=0)
    asyn = simgrid.run_grid(
        init_fn, loss_fn, ds, RC, 2, seed=0,
        grid=simgrid.GridConfig(mode="async", concurrency=4, goal_count=2))
    assert tuple(sync.scheduler_stats) == simgrid.STAT_KEYS
    assert tuple(asyn.scheduler_stats) == simgrid.STAT_KEYS
    # uniform always-on fleet, no dynamics: nothing can retry/drop
    assert sync.scheduler_stats["retries"] == 0
    for k in ("offline", "deadline_drops", "excess"):
        assert asyn.scheduler_stats[k] == 0
    assert asyn.scheduler_stats["uploads"] > 0


def test_scheduler_stats_is_registry_view():
    ds = make_ds()
    res = simgrid.run_grid(
        init_fn, loss_fn, ds, RC, 3, seed=1,
        grid=simgrid.GridConfig(mode="async", concurrency=4, goal_count=2))
    snap = res.metrics.snapshot()
    for k, v in res.scheduler_stats.items():
        assert snap["counters"][k]["value"] == v, k
    assert snap["gauges"]["payload_up_bytes"]["value"] \
        == res.comm.measured_up_bytes // max(res.scheduler_stats["uploads"], 1)


@pytest.mark.dynamics
def test_sync_dark_window_repoll_counts_as_retry():
    """The sync dark-window backoff advance is the retry analogue of the
    async parked dispatch — it must land in the same normalized key."""
    ds = make_ds(n_clients=4)
    cfg = dyn_lib.DynamicsConfig(
        availability=dyn_lib.StepTrace([0.0, 100.0], [0.0, 1.0]),
        redispatch_backoff=30.0)
    gc = simgrid.GridConfig(fleet=_fleet([1.0] * 4), dynamics=cfg,
                            telemetry="memory")
    res = simgrid.run_grid(init_fn, loss_fn, ds, RC, 6, grid=gc, seed=0)
    # ceil(100/30) = 4 dark re-polls before the window opens
    assert res.scheduler_stats["retries"] == 4
    retries = res.telemetry.of_kind("retry")
    assert len(retries) == 4
    assert all(r.payload["backoff"] == 30.0 for r in retries)


# ---------------------------------------------------------------------------
# Acceptance: traced runs export valid streams whose timestamps
# cross-check against GridResult's own totals


def test_sync_traced_events_cross_check():
    ds = make_ds()
    gc = simgrid.GridConfig(fleet="pareto-mobile", over_selection=1.3,
                            straggler_deadline=120.0, telemetry="memory")
    res = simgrid.run_grid(init_fn, loss_fn, ds, RC, 4, grid=gc, seed=3)
    tr = res.telemetry
    st = res.scheduler_stats
    assert len(tr.of_kind("dispatch")) == st["dispatches"]
    uploads = tr.of_kind("upload")
    # the stats "uploads" counter includes late arrivals (the server
    # still pays their uplink); upload *instants* are only emitted for
    # deltas that made the deadline
    assert len(uploads) == st["uploads"] - st["deadline_drops"]
    assert sum(u.payload["participant"] for u in uploads) \
        == int(sum(h["participants"] for h in res.history))
    rounds = tr.of_kind("round")
    assert len(rounds) == len(res.history)
    for span, rec in zip(rounds, res.history):
        # the round span ends exactly at the history's virtual timestamp
        assert span.t + span.dur == pytest.approx(rec["virtual_seconds"])
        assert span.payload["loss"] == rec["loss"]
    # dropouts are dispatch spans with a null duration and no upload
    drops = [d for d in tr.of_kind("dispatch")
             if d.payload["outcome"] == "dropout"]
    assert len(drops) == st["dropouts"]
    assert all(d.dur is None for d in drops)
    assert schema_lib.validate_records(
        [r.to_json() for r in tr.events]) == []


def test_async_traced_run_exports_and_cross_checks(tmp_path):
    """The ISSUE's acceptance run: traced async DP grid -> schema-valid
    JSONL + loadable Perfetto containing dispatch/upload/flush/dp_flush,
    with virtual timestamps matching GridResult.stats totals."""
    jsonl = str(tmp_path / "trace.jsonl")
    pft = str(tmp_path / "trace.json")
    ds = make_ds()
    rc = fedpt.RoundConfig(4, 2, 8, "sgd", 0.1, "sgd", 1.0,
                           dp_clip_norm=0.5, dp_noise_multiplier=0.4)
    gc = simgrid.GridConfig(
        mode="async", concurrency=5, goal_count=3,
        telemetry=trace_lib.TelemetryConfig(jsonl_path=jsonl,
                                            perfetto_path=pft))
    res = simgrid.run_grid(init_fn, loss_fn, ds, rc, 6, grid=gc, seed=4)
    tr = res.telemetry
    st = res.scheduler_stats
    assert len(tr.of_kind("dispatch")) == st["dispatches"]
    assert len(tr.of_kind("upload")) == st["uploads"]
    flushes = tr.of_kind("flush")
    assert len(flushes) == len(res.history)
    for f, rec in zip(flushes, res.history):
        assert f.t == rec["virtual_seconds"]
        assert f.payload["staleness_mean"] == rec["staleness_mean"]
    # the dp_flush stream is the accountant's composition, step by step:
    # monotone epsilon, final value = the reported budget
    dps = tr.of_kind("dp_flush")
    assert len(dps) == res.dp["flushes"] == 6
    eps = [d.payload["epsilon"] for d in dps]
    assert eps == sorted(eps)
    assert eps[-1] == pytest.approx(res.dp["epsilon"])
    assert all(d.payload["sigma"] == res.dp["sigma"] for d in dps)
    for d, f in zip(dps, flushes):
        assert d.t == f.t                 # accounted at flush time
    # every completed dispatch carries its realized round trip as a span
    spans = [d for d in tr.of_kind("dispatch")
             if d.payload["outcome"] == "ok"]
    assert spans and all(d.dur is not None for d in spans)
    assert sum(u.payload["up_bytes"] for u in tr.of_kind("upload")) \
        == res.comm.measured_up_bytes
    # exports were written by flush_outputs and validate cleanly
    n, errs = schema_lib.validate_jsonl(jsonl)
    assert errs == [] and n == len(tr.events)
    pn, perrs = schema_lib.validate_perfetto(
        pft, require=["dispatch", "upload", "flush", "dp_flush"])
    assert perrs == [] and pn == n
    # the Perfetto timeline uses microseconds of virtual time
    with open(pft) as f:
        doc = json.load(f)
    fl = [e for e in doc["traceEvents"] if e.get("name") == "flush"]
    assert sorted(e["ts"] for e in fl) \
        == [pytest.approx(h["virtual_seconds"] * 1e6) for h in res.history]
    assert schema_lib.main([jsonl, "--perfetto", pft,
                            "--require", "dispatch", "flush"]) == 0


def test_async_tiered_traced_tier_billing(tmp_path):
    """tier_upload events from the comm ledger: one instant per tier's
    end-of-run billing batch, bytes summing to the ledger totals, and
    tier_stats' rtt_mean fed by the registry's labeled accumulators."""
    ds = make_ds(n_clients=12)
    gc = simgrid.GridConfig(mode="async", fleet="pareto-mobile",
                            concurrency=6, goal_count=3, plan=TIER_PLAN,
                            telemetry="memory")
    res = simgrid.run_grid(init_fn, loss_fn, ds, RC, 8, grid=gc, seed=5)
    tus = res.telemetry.of_kind("tier_upload")
    assert tus and {t.payload["tier_name"] for t in tus} \
        <= set(TIER_PLAN)
    assert sum(t.payload["up_bytes"] for t in tus) \
        == res.comm.measured_up_bytes
    assert sum(t.payload["down_bytes"] for t in tus) \
        == res.comm.measured_down_bytes
    assert all(t.t == res.virtual_seconds for t in tus)
    # dispatch spans carry the tier the payload was sliced for
    tiers_seen = {d.payload["tier"] for d in
                  res.telemetry.of_kind("dispatch")}
    assert tiers_seen <= {0, 1, 2}
    # rtt_mean comes from tier_rtt_sum / tier_rtt_n in the registry
    for name, rec in res.tier_stats.items():
        if rec["uploads"]:
            assert rec["rtt_mean"] > 0.0, name
    assert schema_lib.validate_records(
        [r.to_json() for r in res.telemetry.events]) == []


# ---------------------------------------------------------------------------
# Satellite: CommReport edge cases (tier_table / transfer_seconds /
# per_client_round_mb)


def test_comm_tier_table_empty_and_zero_uploads():
    rep = comm.CommReport(full_bytes=1000, trainable_bytes=100)
    assert rep.tier_table() == {}            # nothing metered yet
    # a tier that dispatched but never uploaded (all dropouts): the
    # per-upload figure must be an explicit 0.0, not a ZeroDivisionError
    rep.add_tier_measured("lite", down_bytes=400, up_bytes=0, transfers=4,
                          uploads=0)
    tab = rep.tier_table()
    assert tab["lite"]["up_bytes_per_upload"] == 0.0
    assert tab["lite"]["down_mb"] == pytest.approx(400 / 2 ** 20)
    assert tab["lite"]["up_mb"] == 0.0
    # ... and the zero-byte batch still counts its transfers globally
    assert rep.transfers == 4 and rep.measured_up_bytes == 0
    assert rep.measured_total_bytes == 400


def test_comm_transfer_seconds_full_vs_fedpt():
    mb = 2 ** 20
    rep = comm.CommReport(full_bytes=4 * mb, trainable_bytes=1 * mb,
                          rounds=2)
    # fedpt=True: (trainable + seed) down, trainable up, per round
    want_fedpt = ((1 * mb + comm.SEED_BYTES) * 2 / mb / comm.DOWNLINK_MBPS
                  + 1 * 2 / comm.UPLINK_MBPS)
    assert rep.transfer_seconds() == pytest.approx(want_fedpt)
    # fedpt=False bills the full model both ways
    want_full = 4 * 2 / comm.DOWNLINK_MBPS + 4 * 2 / comm.UPLINK_MBPS
    assert rep.transfer_seconds(fedpt=False) == pytest.approx(want_full)
    assert rep.transfer_seconds(fedpt=False) > rep.transfer_seconds()
    # analytic columns are independent of wire metering
    before = rep.transfer_seconds()
    rep.add_measured(0, 0, transfers=1)      # zero measured bytes
    assert rep.transfer_seconds() == before


def test_comm_per_client_round_mb_quantized():
    mb = 2 ** 20
    rep = comm.CommReport(full_bytes=4 * mb, trainable_bytes=1 * mb,
                          rounds=3, uplink_bits=8,
                          quantized_trainable_bytes=mb // 4)
    out = rep.per_client_round_mb()
    assert out["full_down_mb"] == out["full_up_mb"] == 4.0
    assert out["fedpt_down_mb"] == pytest.approx(
        (mb + comm.SEED_BYTES) / mb)
    # quantized uplink: per-round upload is the int8 payload
    assert out["fedpt_up_mb"] == pytest.approx(0.25)
    assert rep.upload_fedpt == (mb // 4) * 3
    # zero quantized bytes falls back to fp32 (the fedpt=False-ish path)
    rep0 = comm.CommReport(full_bytes=4 * mb, trainable_bytes=1 * mb,
                           uplink_bits=8, quantized_trainable_bytes=0)
    assert rep0.per_client_round_mb()["fedpt_up_mb"] == 1.0


def test_comm_add_tier_measured_emits_traced_instant():
    rep = comm.CommReport(full_bytes=1000, trainable_bytes=100,
                          tracer=trace_lib.Tracer())
    rep.add_tier_measured("mid", down_bytes=300, up_bytes=120, transfers=3,
                          uploads=2, now=7.5)
    (rec,) = rep.tracer.of_kind("tier_upload")
    assert rec.t == 7.5
    assert rec.payload == {"tier_name": "mid", "down_bytes": 300,
                           "up_bytes": 120, "transfers": 3, "uploads": 2}
    assert schema_lib.validate_record(rec.to_json()) == []
    # the tracer is plumbing, never ledger state: equality ignores it
    assert rep == dataclasses.replace(rep, tracer=trace_lib.NULL_TRACER)
