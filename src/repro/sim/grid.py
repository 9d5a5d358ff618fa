"""The simulation-grid driver.

``run_grid`` trains a FedPT model over a heterogeneous client fleet under
either scheduling regime and reports *measured* wire bytes plus simulated
cross-device wall-clock. ``fl.runtime.run_federated`` delegates here with
``GridConfig()`` defaults (uniform fleet, synchronous, no deadline) and is
reproduced **bit-for-bit**: the grid consumes the data-sampling RNG stream
(``seed + 77``) and the per-round DP keys (``seed*100_003 + r``) in
exactly the same order, and routes all device/availability randomness
through a separate stream (and all *dynamics* randomness — link jitter,
trace phases — through an independent child of that stream).

``GridConfig.dynamics`` (sim/dynamics.py) makes links stochastic and
availability trace-driven at virtual time; ``GridConfig.selection``
(sim/selection.py) makes cohort choice a policy — bandwidth-aware
sampling with importance weights, FedPLT-style tier rotation, or online
re-tiering from observed round trips. The trivial corner (static links,
always-on, uniform selection) routes through the exact pre-dynamics
code paths.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.partition as part
from repro.checkpoint import grid_state as gstate_lib
from repro.core import comm, dp as dp_lib, fedpt
from repro.core import flat as flat_lib
from repro.core import plan as plan_lib
from repro.core import sanitize as sanitize_lib
from repro.data import synthetic as syn
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shard_lib
from repro.obs import metrics as metrics_lib
from repro.obs import profiling as prof_lib
from repro.obs import trace as trace_lib
from repro.sim import devices as dev_lib
from repro.sim import dynamics as dyn_lib
from repro.sim import faults as faults_lib
from repro.sim import scheduler as sched_lib
from repro.sim import selection as sel_lib
from repro.sim import topology as topo_lib
from repro.sim import wire


@dataclasses.dataclass
class GridConfig:
    mode: str = "sync"                      # "sync" | "async"
    fleet: Union[str, dev_lib.Fleet] = "uniform"
    # virtual seconds one local step takes on the reference device; each
    # client scales it by its profile's compute_multiplier
    base_step_time: float = 0.01
    # --- sync knobs ---
    over_selection: float = 1.0             # dispatch ceil(f*C), keep first C
    straggler_deadline: float = math.inf    # virtual seconds per round
    # --- async (FedBuff) knobs ---
    concurrency: int = 10                   # clients kept in flight
    goal_count: int = 5                     # buffer size K per server update
    staleness: Any = "polynomial"           # name or callable (core.fedpt)
    staleness_kw: Dict[str, float] = dataclasses.field(default_factory=dict)
    # fixed-width client lanes: in-flight client steps are deferred and
    # executed as one vmapped (lane, ...) batch per flush instead of one
    # jit dispatch per client. None = auto (lane width == goal_count);
    # 0 = the sequential per-client reference engine. Virtual-clock
    # history is identical either way (execution timing never feeds the
    # event clock); only device dispatch granularity changes.
    lanes: Optional[int] = None
    # virtual-seconds budget for the whole async run: the first event
    # past it ends the run, flushing the partial buffer as one final
    # short update (padded to goal_count with zero weights)
    async_deadline: float = math.inf
    # --- mesh execution ---
    # None = single-device dispatch. A launch/mesh.py preset name
    # ("single", "debug", "debug-pod", "production", ...) or a mesh
    # object shards the grid's device work end-to-end: lane-batched
    # client steps run data-parallel with the lane axis on the mesh's
    # ("pod", "data") axes and the flat delta's size axis on "model",
    # and the buffered apply reduces the sharded (K, size) buffer in
    # place (no gather). The virtual clock, staleness bookkeeping and
    # wire metering are mesh-independent; histories match the
    # single-device run to fp32 round-off.
    mesh: Any = None
    # --- trainability tiers (core/plan.py) ---
    # None = every client trains the full freeze_spec trainable tree
    # (the pre-plan system, bit for bit — as is a one-tier plan). A
    # TrainPlan / {name: extra_freeze_spec} dict / (name, spec) sequence
    # assigns each client a tier: weak devices train (and upload) less.
    plan: Any = None
    # "capability" (quantile-split devices.capability_score, most
    # capable -> tier 0), an explicit per-client tier-index array, or a
    # callable DeviceProfile -> tier index
    tier_assignment: Any = "capability"
    # --- device dynamics (sim/dynamics.py) ---
    # None = the fleet preset's default (static for every pre-dynamics
    # preset; "pareto-mobile-diurnal" implies the "diurnal" preset). A
    # preset name ("static", "jitter", "diurnal") or a DynamicsConfig
    # turns on stochastic links (per-transfer log-normal jitter + RTT
    # floor) and trace-driven availability queried at virtual time.
    # Trivial dynamics resolve to the exact pre-dynamics code paths.
    dynamics: Any = None
    # --- cohort selection (sim/selection.py) ---
    # "uniform" (exact pre-selection behavior), "bandwidth-aware",
    # "tier-rotation", "adaptive-capability", or a SelectionPolicy
    # instance
    selection: Any = "uniform"
    # --- two-level aggregation topology (sim/topology.py) ---
    # None = the flat single-hop grid (no hierarchical machinery at
    # all). An int region count, a TopologyConfig or an explicit
    # per-client region array partitions the fleet into edge regions:
    # each edge pre-reduces its members' flat deltas into one (size,)
    # buffer per flush, the wire bills the client->edge and
    # edge->server hops separately (CommReport.hop_traffic), and
    # correlated region shocks (DynamicsConfig.shocks) can down a
    # whole edge at once. A one-region topology runs the full edge
    # machinery and stays bit-identical to the flat grid
    # (test-enforced), so hierarchy can be A/B'd against flat.
    topology: Any = None
    # --- telemetry (repro/obs) ---
    # None = the NULL tracer: no event records, no extra PRNG draws,
    # bit-identical histories (test-enforced). A TelemetryConfig (or
    # True/"on", or a dict of its fields) records typed span/event
    # traces in virtual time — dispatches, uploads, retries, flushes,
    # rounds, dp_flush accounting, tier wire billing — inspectable on
    # GridResult.telemetry and exportable as schema-versioned JSONL or
    # a Chrome/Perfetto timeline. The metrics registry backing
    # GridResult.scheduler_stats/tier_stats is always on either way.
    telemetry: Any = None
    # --- fault injection (sim/faults.py) ---
    # None = no failure model: zero extra PRNG draws, bit-identical
    # histories (test-enforced). A preset name ("chaos"), a FaultConfig
    # or a dict of its fields injects client crash-mid-compute, upload
    # truncation, payload corruption (NaN / bit-flip), duplicate
    # deliveries and a server kill at virtual time T — all drawn from an
    # independent spawned fault stream. Sync mode supports crashes and
    # the kill only (payload faults need a per-client wire payload).
    faults: Any = None
    # --- delta quarantine (core/sanitize.py) ---
    # None/False = off (clean-data aggregation is bit-identical either
    # way). True / a SanitizeConfig / a dict screens the delta buffer
    # before aggregation: non-finite rows and norm outliers are zeroed
    # with zero weight (under DP the fixed denominator is untouched);
    # every quarantined row emits a traced "quarantine" event.
    sanitize: Any = None
    # --- fused aggregation tail (kernels/ops.agg_tail) ---
    # None = the shape- and pipeline-aware default: quantized delta
    # buffers (uplink_bits > 0) with at least
    # kernels.ops.AGG_FUSE_THRESHOLD elements (K x size) take the fused
    # stats/pack/apply sweep, everything else the staged per-op tail
    # (bit-identical to the historical sequence). An int overrides it
    # and routes purely by size: 0 forces fused everywhere, a huge
    # value forces staged everywhere — both round engines (sync rounds
    # and async buffered flushes) thread it through.
    agg_tail_threshold: Optional[int] = None
    # --- mid-run checkpoint / resume (checkpoint/grid_state.py) ---
    # checkpoint_every > 0 snapshots the full execution state into
    # checkpoint_dir every N server updates (async: at flush
    # boundaries; sync: at round boundaries). resume_from restores a
    # snapshot and continues — the resumed run reproduces the
    # uninterrupted run's history exactly (bitwise on CPU).
    checkpoint_every: int = 0
    checkpoint_dir: Optional[str] = None
    resume_from: Optional[str] = None
    # --- rng plumbing ---
    fleet_seed: int = 0                     # profile sampling
    device_seed: int = 13                   # availability/dropout/latency
    # (dynamics draws — jitter, trace phases — come from an independent
    # child stream spawned off [seed, device_seed], so enabling dynamics
    # never moves the availability/dropout stream above; fault draws
    # come from a SECOND spawned child, created only when faults are on)


@dataclasses.dataclass
class GridResult:
    y: Any
    frozen: Any
    history: List[Dict[str, float]]
    comm: comm.CommReport
    seconds_per_round: float                # real wall-clock
    virtual_seconds: float                  # simulated cross-device time
    fleet: dev_lib.Fleet
    mode: str
    scheduler_stats: Dict[str, int]
    # per-flush DP accounting (async mode with dp_noise_multiplier > 0):
    # flushes, padded_flushes, sigma, noise_multiplier, epsilon, delta
    dp: Optional[Dict[str, float]] = None
    # trainability-tier breakdown (GridConfig.plan set): tier name ->
    # {clients, down_bytes, up_bytes, transfers, uploads, ...}; the
    # same per-tier traffic also lives in comm.tier_traffic
    tier_stats: Optional[Dict[str, Dict[str, float]]] = None
    # the CompiledPlan the run used (None without a plan)
    plan: Any = None
    # the bound SelectionPolicy the run used (inspect e.g. .refits or
    # .current_tiers() after an adaptive run)
    policy: Any = None
    # the BoundDynamics the run used (None = static links, always-on)
    dynamics: Any = None
    # the bound Topology the run used (None = flat single-hop grid);
    # per-hop wire traffic lives in comm.hop_traffic
    topology: Any = None
    # the run's MetricsRegistry (always present): scheduler_stats and
    # tier_stats above are dict views over it — metrics.snapshot() is
    # the superset
    metrics: Any = None
    # the Tracer when GridConfig.telemetry was set (else None):`.events`
    # holds the virtual-time records, `.export_jsonl`/`.export_perfetto`
    # write them out
    telemetry: Any = None
    # fault-injection summary (GridConfig.faults set): the run's fired
    # fault counters — crashes, truncated, corrupted, duplicates — plus
    # quarantined rows (None when no failure model was active)
    faults: Optional[Dict[str, int]] = None

    @property
    def stats(self) -> Dict[str, int]:
        """Alias for ``scheduler_stats`` (the normalized per-run
        scheduler counters; same key set in both modes)."""
        return self.scheduler_stats


def num_clients(ds) -> int:
    if hasattr(ds, "num_clients"):
        return ds.num_clients
    return len(ds.client_tokens)


def _uplink_bytes(tree, bits: int) -> int:
    """Measured (serialized) uplink size when the wire format supports
    the payload (fp32 / int8); analytic int-k estimate otherwise, so
    sub-byte quantization configs keep running."""
    if bits in (0, 8):
        return wire.uplink_bytes(tree, bits=bits)
    from repro.core import compress
    return compress.quantized_uplink_bytes(tree, bits)


def run_grid(init_fn: Callable[[int], Any], loss_fn: Callable, dataset,
             rc: fedpt.RoundConfig, rounds: int,
             grid: Optional[GridConfig] = None, freeze_spec=(),
             seed: int = 0, data_kind: str = "images", eval_every: int = 0,
             eval_fn: Optional[Callable[[Any], Dict[str, float]]] = None,
             server_opt=None, log: bool = False) -> GridResult:
    """Train for `rounds` server updates on the simulated fleet. In sync
    mode a "round" is one cohort; in async mode it is one buffered server
    update (goal_count client deltas)."""
    grid = grid or GridConfig()
    N = num_clients(dataset)
    if rc.clients_per_round > N:
        raise ValueError(f"clients_per_round={rc.clients_per_round} exceeds "
                         f"the dataset's {N} clients")
    fleet = dev_lib.make_fleet(N, grid.fleet, seed=grid.fleet_seed)
    y, frozen = part.partition(init_fn(seed), freeze_spec)

    # telemetry: the metrics registry is ALWAYS live (it backs
    # scheduler_stats/tier_stats); the tracer is the NULL no-op unless
    # GridConfig.telemetry asks for event records
    registry = metrics_lib.MetricsRegistry()
    tel_cfg = trace_lib.resolve_telemetry(grid.telemetry)
    tracer = (trace_lib.Tracer(tel_cfg, registry) if tel_cfg is not None
              else trace_lib.NULL_TRACER)

    report = comm.report_for(y, frozen, uplink_bits=rc.uplink_bits)
    report.tracer = tracer                       # tier_upload billing
    # two-level aggregation topology: None keeps the flat single-hop
    # grid untouched; otherwise every add_measured call mirrors into
    # the client_edge hop ledger and the grid bills the edge_server
    # hop (pre-reduced flat buffers) separately per flush
    topo = topo_lib.resolve_topology(grid.topology, N)
    if topo is not None:
        report.bill_hops = True
    down_bytes = wire.downlink_bytes(y)          # y + 8-byte seed, measured
    up_bytes = _uplink_bytes(y, rc.uplink_bits)  # shape-determined
    compute_seconds = rc.local_steps * grid.base_step_time
    registry.gauge("payload_down_bytes").set(int(down_bytes))
    registry.gauge("payload_up_bytes").set(int(up_bytes))
    registry.gauge("compute_seconds").set(float(compute_seconds))

    # trainability plan: capability->tier per client, tier-sliced uplink
    # payloads (downlink stays the full y + seed for every tier — other
    # tiers keep training the blocks a tier froze, so their current
    # values cannot be regenerated from the seed). The virtual clock
    # also charges per-tier compute: a tier's local step scales with its
    # trainable fraction (a lite tier's backward pass is cheaper); the
    # full tier's fraction is exactly 1.0, so one-tier plans keep the
    # pre-plan clock bit for bit.
    if grid.plan is not None:
        cplan = plan_lib.compile_plan(grid.plan, y)
        tier_of_client = dev_lib.assign_tiers(fleet, len(cplan.tiers),
                                              grid.tier_assignment)
        tier_up = np.asarray(
            [p["up"] for p in
             wire.tier_payloads(y, cplan, rc.uplink_bits).values()],
            np.int64)
        total_params = sum(cplan.layout.sizes)
        tier_compute = np.asarray(
            [compute_seconds * (t.param_count / total_params
                                if total_params else 1.0)
             for t in cplan.tiers], np.float64)
        for t in cplan.tiers:
            # per-tier virtual compute charge, the registry's copy (the
            # tier_stats view and the benchmarks read it from here)
            registry.gauge("tier_compute").set(float(tier_compute[t.index]),
                                               label=t.index)
    else:
        cplan = None
        tier_of_client = None
        tier_up = None
        tier_compute = None

    data_rng = np.random.default_rng(seed + 77)  # == run_federated's stream
    dev_rng = np.random.default_rng([seed, grid.device_seed])
    # the dynamics stream: an independent child of [seed, device_seed].
    # Spawning advances no draws of dev_rng, so the scheduler's
    # fixed-count availability/dropout streams are byte-identical with
    # dynamics on or off (tests pin this).
    dyn_rng = dev_rng.spawn(1)[0]
    dyn_cfg = dyn_lib.resolve_dynamics(grid.dynamics, fleet)
    dyn = dyn_cfg.bind(fleet, dyn_rng) if dyn_cfg is not None else None

    # the fault stream: a SECOND independent child, spawned ONLY when a
    # failure model is active — spawning advances no dev_rng draws and
    # the fault stream's own draws never touch the other streams, so
    # faults=None runs are bit-identical (test-enforced)
    faults_cfg = faults_lib.resolve_faults(grid.faults)
    if faults_cfg is not None and grid.mode == "sync" \
            and faults_cfg.payload_prob > 0:
        raise ValueError(
            "sync mode supports only crash_compute and server_kill_at "
            "faults — payload faults (truncate/corrupt/duplicate) need "
            "the async per-client wire path")
    bfaults = (faults_cfg.bind(dev_rng.spawn(1)[0])
               if faults_cfg is not None else None)
    # the shock stream: a THIRD independent child, spawned ONLY when
    # correlated region shocks are configured — same hygiene as the
    # fault stream, so shock-free runs see identical streams everywhere
    shocks_cfg = dyn_cfg.shocks if dyn_cfg is not None else None
    if shocks_cfg is not None and topo is None:
        raise ValueError(
            "DynamicsConfig.shocks needs a topology (GridConfig."
            "topology): shocks down whole edge regions, and the flat "
            "grid has none")
    bshocks = (shocks_cfg.bind(topo.num_regions, dev_rng.spawn(1)[0],
                               tracer=tracer)
               if shocks_cfg is not None else None)
    san = sanitize_lib.resolve_sanitize(grid.sanitize)
    if grid.checkpoint_every > 0 and not grid.checkpoint_dir:
        raise ValueError("checkpoint_every > 0 needs a checkpoint_dir")

    # cohort-selection policy: estimates feed bandwidth-aware inclusion
    # probabilities and seed the adaptive policy's observed-RTT EMA
    policy = sel_lib.resolve_policy(grid.selection)
    est_up = (tier_up[tier_of_client] if cplan is not None
              else np.full(N, up_bytes, np.int64))
    est_comp = (tier_compute[tier_of_client] if cplan is not None
                else np.full(N, compute_seconds, np.float64))
    # one array op over the FleetState struct-of-arrays — the former
    # per-profile listcomp was O(N) Python objects per run and dominated
    # startup at 10^5+ clients (benchmarks/fleet_bench.py)
    rtt_estimate = np.asarray(
        fleet.state.round_trip_seconds(down_bytes, est_up, est_comp),
        np.float64)
    policy.bind(fleet=fleet, num_clients=N, cplan=cplan,
                tiers=tier_of_client, rtt_estimate=rtt_estimate)

    common = dict(fleet=fleet, report=report, down_bytes=down_bytes,
                  up_bytes=up_bytes, compute_seconds=compute_seconds,
                  data_rng=data_rng, dev_rng=dev_rng, seed=seed,
                  data_kind=data_kind, eval_every=eval_every,
                  eval_fn=eval_fn, log=log, cplan=cplan,
                  tier_of_client=tier_of_client, tier_up=tier_up,
                  tier_compute=tier_compute, dyn=dyn, dyn_rng=dyn_rng,
                  policy=policy, registry=registry, tracer=tracer,
                  bfaults=bfaults, san=san,
                  topo=topo, bshocks=bshocks)
    runner = {"sync": _run_sync, "async": _run_async}.get(grid.mode)
    if runner is None:
        raise ValueError(f"unknown grid mode {grid.mode!r} "
                         "(expected 'sync' or 'async')")
    mesh = mesh_lib.resolve_mesh(grid.mesh)
    # the mesh is the ambient one while the run traces: the model's
    # maybe_constrain hints see it, and the kernel dispatchers
    # (kernels/ops.use_kernels) keep Mosaic calls off a multi-device
    # program, which GSPMD cannot partition
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        return runner(y, frozen, loss_fn, dataset, rc, rounds, grid,
                      server_opt, mesh=mesh, **common)


# ---------------------------------------------------------------------------
# Synchronous cohorts


# the normalized scheduler-stats schema: BOTH modes emit every key,
# with explicit zeros where a counter cannot fire (sync never retries
# in-flight dispatches; async has no over-selection excess and no
# availability-draw offline stage; sync supports only the crash fault)
# — regression-tested
STAT_KEYS = ("dispatches", "uploads", "offline", "dropouts",
             "deadline_drops", "excess", "retries",
             "crashes", "truncated", "corrupted", "duplicates",
             "quarantined")


def _stats_view(registry: metrics_lib.MetricsRegistry) -> Dict[str, int]:
    """GridResult.scheduler_stats as a dict view over the metrics
    registry — the registry is the one source of truth, this is its
    stable-schema rendering."""
    return {k: int(registry.counter(k).value) for k in STAT_KEYS}


def _tier_stats(report, cplan, tier_of_client,
                registry: metrics_lib.MetricsRegistry):
    """GridResult.tier_stats: the comm ledger's per-tier traffic plus
    the fleet census (how many clients each tier owns — the run's final
    tier map, which rotation/adaptive policies move over time), the
    tier's compute charge per local run, and the mean observed
    round-trip of its uploads. Timing/compute columns are read from the
    metrics registry (labels = tier indices), the wire columns from the
    comm ledger."""
    if cplan is None:
        return None
    rtt_sum = registry.counter("tier_rtt_sum")
    rtt_n = registry.counter("tier_rtt_n")
    compute = registry.gauge("tier_compute")
    out = {}
    for t in cplan.tiers:
        rec = dict(report.tier_traffic.get(
            t.name, {"down_bytes": 0, "up_bytes": 0, "transfers": 0,
                     "uploads": 0}))
        rec["clients"] = int(np.sum(tier_of_client == t.index))
        # measured wire cost per upload (int8-aware), matching
        # CommReport.tier_table(); the analytic fp32 slice size keeps
        # its own key
        rec["up_bytes_per_upload"] = (rec["up_bytes"] / rec["uploads"]
                                      if rec["uploads"] else 0.0)
        rec["trainable_bytes"] = t.trainable_bytes
        # per-tier virtual compute charge (reference device, one
        # dispatch): base compute scaled by the trainable fraction
        rec["compute_seconds"] = float(compute.get(t.index, 0.0))
        n = rtt_n.get(t.index, 0)
        rec["rtt_mean"] = (rtt_sum.get(t.index, 0.0) / n) if n else 0.0
        out[t.name] = rec
    return out


def _sync_quarantine(rmetrics, sel, tiers_now, cplan, mc, tracer, vt0,
                     rseq, r) -> None:
    """Quarantined cohort rows -> traced events + counter (the masks are
    tiny (C,) vectors, read after the round's loss has synced)."""
    nonf = np.asarray(rmetrics["quarantine_nonfinite"])
    outl = np.asarray(rmetrics["quarantine_outlier"])
    norms = np.asarray(rmetrics["quarantine_norms"])
    for i in np.nonzero(nonf | outl)[0]:
        mc("quarantined").inc()
        tracer.instant(
            "quarantine", vt0, parent=rseq,
            cause="nonfinite" if nonf[i] else "norm-outlier",
            cid=int(sel[i]),
            tier=(int(tiers_now[sel[i]]) if cplan is not None else None),
            norm=float(norms[i]), round=r)


def _bill_tiers(report, cplan, cohort_tiers, plan, down_bytes, tier_up, vt,
                rseq) -> None:
    """Bill a sync round per tier: dispatches pay the (tier-invariant)
    downlink, uploads pay the tier-sliced uplink."""
    uploaded = np.isfinite(plan.arrival)
    for t in cplan.tiers:
        sel_t = cohort_tiers == t.index
        nd = int(np.sum(plan.dispatched & sel_t))
        nu = int(np.sum(uploaded & sel_t))
        if nd or nu:
            report.add_tier_measured(
                t.name, down_bytes * nd, int(tier_up[t.index]) * nu,
                transfers=nd, uploads=nu, now=vt, parent=rseq)


def _bill_edges(report, topo, cohort_regions, plan, mc, tracer, down_bytes,
                edge_bytes, vt, rseq, r) -> None:
    """Hierarchical hop billing of a sync round: every region with a
    dispatch downloads one model payload server->edge (the edge fans it
    out on the client hop); every region with a completed upload
    pre-reduces its members' deltas and flushes one flat buffer
    upstream."""
    disp_counts = np.bincount(cohort_regions[plan.dispatched],
                              minlength=topo.num_regions)
    up_counts = np.bincount(cohort_regions[plan.completed],
                            minlength=topo.num_regions)
    for k in np.nonzero(disp_counts)[0]:
        mc("region_dispatches").inc(int(disp_counts[k]), label=int(k))
    active = np.nonzero(up_counts)[0]
    for k in active:
        mc("region_uploads").inc(int(up_counts[k]), label=int(k))
        mc("edge_flushes").inc(label=int(k))
        mc("edge_up_bytes").inc(edge_bytes, label=int(k))
        tracer.instant("edge_flush", vt, parent=rseq, region=int(k),
                       fill=int(up_counts[k]), up_bytes=edge_bytes, round=r)
    n_down = int(np.sum(disp_counts > 0))
    report.add_hop("edge_server", down_bytes=down_bytes * n_down,
                   up_bytes=edge_bytes * len(active), transfers=n_down,
                   uploads=len(active))


def _run_sync(y, frozen, loss_fn, dataset, rc, rounds, grid, server_opt, *,
              fleet, report, down_bytes, up_bytes, compute_seconds,
              data_rng, dev_rng, seed, data_kind, eval_every, eval_fn, log,
              cplan, tier_of_client, tier_up, tier_compute, dyn, dyn_rng,
              policy, registry, tracer, bfaults, san, topo,
              bshocks, mesh):
    constrain_flat = shard_lib.flat_constrainer(mesh) if mesh else None
    constrain_batch = shard_lib.cohort_constrainer(mesh) if mesh else None
    # a trivial (one-tier, nothing-extra-frozen) plan routes through the
    # exact pre-plan engine: same trace, same history, bit for bit
    tiered = cplan is not None and not cplan.trivial
    round_fn, sopt = fedpt.make_round_fn(loss_fn, rc, server_opt=server_opt,
                                         constrain_flat_fn=constrain_flat,
                                         constrain_batch_fn=constrain_batch,
                                         plan=cplan, sanitize=san,
                                         fused_threshold=grid.agg_tail_threshold)
    round_fn = jax.jit(round_fn, donate_argnums=(0, 1))
    sstate = sopt.init(y)
    N = num_clients(dataset)
    C = rc.clients_per_round
    m = min(N, max(C, int(math.ceil(C * grid.over_selection))))
    # one pre-reduced fp32 flat buffer per active edge per round
    # (shape-determined, so measured once)
    edge_bytes = wire.edge_flush_bytes(y) if topo is not None else 0

    # every live RNG stream a snapshot must capture (the fault stream
    # only exists when a failure model is active)
    rngs = {"data": data_rng, "dev": dev_rng, "dyn": dyn_rng}
    if bfaults is not None:
        rngs["fault"] = bfaults.rng

    history: List[Dict[str, float]] = []
    mc = registry.counter
    vt = 0.0
    start_round = 0
    last_ckpt: Optional[str] = None
    if grid.resume_from:
        meta, arrays = gstate_lib.load_state(grid.resume_from)
        y, sstate, start_round, vt, history = gstate_lib.decode_sync(
            meta, arrays, sstate_template=sstate, rngs=rngs,
            policy=policy, registry=registry, report=report,
            shocks=bshocks, topo=topo)
        last_ckpt = grid.resume_from
    t0 = None
    for r in range(start_round, rounds):
        # wall-clock spans (obs/profiling.py): the round's host work in
        # named children of grid/round
        with prof_lib.round_span("grid/round", r):
            if bfaults is not None and vt > bfaults.kill_at:
                raise faults_lib.ServerKilled(at=vt, applied=r,
                                              checkpoint=last_ckpt)
            with prof_lib.span("grid/plan"):
                # the policy's tier map can move between rounds
                # (tier-rotation, adaptive-capability); static policies
                # return the bound map
                tiers_now = (policy.current_tiers() if cplan is not None
                             else None)
                cids = policy.select_cohort(data_rng, m)
                # tier-sliced uplink payloads + per-tier compute feed the
                # virtual clock: a lite client's smaller delta clears the
                # 0.25 MB/s uplink sooner AND its backward pass is cheaper
                cohort_up = (tier_up[tiers_now[cids]] if cplan is not None
                             else up_bytes)
                cohort_comp = (tier_compute[tiers_now[cids]]
                               if cplan is not None else compute_seconds)
                cohort_regions = (topo.region_of[cids] if topo is not None
                                  else None)
                plan = sched_lib.plan_sync_round(
                    fleet, cids, down_bytes, cohort_up, cohort_comp, C,
                    dev_rng, deadline=grid.straggler_deadline,
                    dynamics=dyn, dyn_rng=dyn_rng, now=vt, tracer=tracer,
                    tiers=tiers_now[cids] if cplan is not None else None,
                    faults=bfaults, shocks=bshocks, regions=cohort_regions)
                # the C slots the compiled round engine sees: participants
                # in arrival order, padded (weight 0) with the remaining
                # cohort in dispatch order when drops leave the round short
                kept_cids = plan.participant_cids()
                pad = plan.cids[~plan.participant][:C - len(kept_cids)]
                sel = np.concatenate([kept_cids, pad]).astype(np.int64)
                kept = np.arange(C) < len(kept_cids)

            with prof_lib.span("grid/cohort_batch", clients=len(sel)) as sp:
                batch, w = syn.cohort_batch(dataset, sel, rc.local_steps,
                                            rc.local_batch, data_rng,
                                            kind=data_kind)
                w = np.where(kept, w, 0.0).astype(np.float32)
                if not policy.trivial and not (rc.uniform_weights
                                               or rc.dp_clip_norm > 0):
                    # importance-unbiased selection weights multiply into
                    # the aggregation weights; under DP the engine forces
                    # uniform weighting with a fixed denominator (sigma
                    # calibration), so the correction is dropped there by
                    # design
                    iw = policy.cohort_weights(sel)
                    if iw is not None:
                        w = (w * iw).astype(np.float32)
                sp.set_metadata(bytes=sum(int(v.nbytes)
                                          for v in batch.values()))

            with prof_lib.span("grid/round_fn"):
                args = (y, sstate, frozen, batch, jnp.asarray(w))
                if tiered:
                    args += (jnp.asarray(tiers_now[sel], jnp.int32),)
                y, sstate, rmetrics = round_fn(
                    *args, jax.random.key(seed * 100_003 + r))

            with prof_lib.span("grid/wait"):
                if t0 is None:
                    jax.block_until_ready(y)
                    t0 = time.time()  # exclude compile from the timing
                # the round's one blocking host sync
                loss = float(rmetrics["loss"])

            with prof_lib.span("grid/bookkeeping"):
                vt0, vt = vt, vt + plan.round_seconds
                # the round span goes out as soon as its wall time is
                # known — before the quarantine/billing/edge instants it
                # causally precedes, so they can parent on it. Its own
                # parent is the upload that closed the round
                # (plan.bound_seq), which links round -> bounding upload
                # -> dispatch for analyze.py's critical-path walk.
                rseq = tracer.span("round", vt0, plan.round_seconds,
                                   parent=plan.bound_seq, round=r,
                                   participants=float(len(kept_cids)),
                                   cohort=int(m), loss=loss)
                if san is not None:
                    _sync_quarantine(rmetrics, sel, tiers_now, cplan, mc,
                                     tracer, vt0, rseq, r)
                registry.histogram("round_seconds").observe(
                    plan.round_seconds)
                n_dispatched = int(np.sum(plan.dispatched))
                n_uploads = n_dispatched - plan.dropouts
                # observed round trips flow back to the policy (adaptive
                # re-tiering) and into the per-tier timing stats
                for i in np.nonzero(plan.completed)[0]:
                    rtt = float(plan.arrival[i])
                    policy.observe(int(plan.cids[i]), rtt)
                    registry.histogram("upload_rtt").observe(rtt)
                    if cplan is not None:
                        t_idx = int(tiers_now[plan.cids[i]])
                        mc("tier_rtt_sum").inc(rtt, label=t_idx)
                        mc("tier_rtt_n").inc(label=t_idx)
                if cplan is not None:
                    _bill_tiers(report, cplan, tiers_now[plan.cids], plan,
                                down_bytes, tier_up, vt, rseq)
                else:
                    report.add_measured(down_bytes * n_dispatched,
                                        up_bytes * n_uploads,
                                        transfers=n_dispatched)
                if topo is not None:
                    _bill_edges(report, topo, cohort_regions, plan, mc,
                                tracer, down_bytes, edge_bytes, vt, rseq, r)
                mc("dispatches").inc(n_dispatched)
                mc("uploads").inc(n_uploads)
                mc("offline").inc(plan.offline)
                mc("dropouts").inc(plan.dropouts)
                mc("deadline_drops").inc(plan.deadline_drops)
                mc("excess").inc(plan.excess)
                mc("retries").inc(plan.retries)
                mc("crashes").inc(plan.crashes)
                policy.end_round(r)

            rec = {"round": r, "loss": loss}
            if eval_fn and eval_every and (r + 1) % eval_every == 0:
                with prof_lib.span("grid/eval_fn"):
                    rec.update(eval_fn(part.merge(y, frozen)))
            rec["virtual_seconds"] = vt
            rec["participants"] = float(len(kept_cids))
            history.append(rec)
            if grid.checkpoint_every > 0 \
                    and (r + 1) % grid.checkpoint_every == 0:
                with prof_lib.span("grid/checkpoint"):
                    meta, arrays = gstate_lib.encode_sync(
                        y=y, sstate=sstate, round_idx=r, now=vt,
                        history=history, rngs=rngs, policy=policy,
                        registry=registry, report=report, shocks=bshocks,
                        topo=topo)
                    last_ckpt = gstate_lib.save_state(
                        gstate_lib.checkpoint_path(grid.checkpoint_dir,
                                                   r + 1, "sync"),
                        meta, arrays)
                mc("checkpoints").inc()
                tracer.instant("checkpoint", vt, parent=rseq,
                               path=last_ckpt, round=r, mode="sync")
        if log and (r % max(1, rounds // 10) == 0):
            print(f"  round {r}: " + " ".join(
                f"{k}={v:.4f}" for k, v in rec.items() if k != "round"))
    jax.block_until_ready(y)
    spr = (time.time() - t0) / max(rounds - start_round - 1, 1) \
        if t0 else float("nan")
    final_tiers = (policy.current_tiers() if cplan is not None
                   else tier_of_client)
    if tracer.enabled:
        tracer.flush_outputs()
    return GridResult(y=y, frozen=frozen, history=history, comm=report,
                      seconds_per_round=spr, virtual_seconds=vt,
                      fleet=fleet, mode="sync",
                      scheduler_stats=_stats_view(registry),
                      tier_stats=_tier_stats(report, cplan, final_tiers,
                                             registry),
                      plan=cplan, policy=policy, dynamics=dyn,
                      topology=topo, metrics=registry,
                      telemetry=tracer if tracer.enabled else None,
                      faults=_faults_view(registry, bfaults))


def _faults_view(registry: metrics_lib.MetricsRegistry,
                 bfaults) -> Optional[Dict[str, int]]:
    """GridResult.faults: the fired-fault counters, when a failure model
    was active (quarantined rows ride along — they are the sanitize
    screen's answer to the corruption faults)."""
    if bfaults is None:
        return None
    return {k: int(registry.counter(k).value)
            for k in ("crashes", "truncated", "corrupted", "duplicates",
                      "quarantined")}


# ---------------------------------------------------------------------------
# Buffered async (FedBuff)


class _LaneCell:
    """Handle for a client step deferred into a lane batch: filled with
    this client's own (delta row, loss) when the lane executes — rows
    are sliced out at fill time so a straggler entry keeps one (size,)
    row alive, not the whole (lane, size) batch."""
    __slots__ = ("delta", "loss")

    def __init__(self):
        self.delta = None

    def resolve(self):
        return self.delta, self.loss


def _flush_quarantine(m, entries, registry, tracer, now, parent,
                      applied) -> None:
    """Quarantined buffer rows of a flush -> traced events + counter
    (the masks are tiny (K,) vectors, synced with the flush's losses)."""
    nonf = np.asarray(m["quarantine_nonfinite"])
    outl = np.asarray(m["quarantine_outlier"])
    norms = np.asarray(m["quarantine_norms"])
    for i in np.nonzero((nonf | outl)[:len(entries)])[0]:
        registry.counter("quarantined").inc()
        w = entries[i].work
        tracer.instant(
            "quarantine", now, parent=parent,
            cause="nonfinite" if nonf[i] else "norm-outlier",
            cid=int(w["cid"]),
            tier=None if w.get("tier") is None else int(w["tier"]),
            norm=float(norms[i]), flush=applied)


def _edge_reduce_flush(topo, flat_deltas, wts, entries, registry, tracer,
                       down_bytes, edge_bytes, now, parent,
                       applied) -> None:
    """Edge pre-reduce of a flush: its rows grouped by uploader region —
    each edge's (size,) buffer is what it transmits upstream (billed on
    the edge_server hop at end of run). The authoritative server reduce
    consumed the same rows fused, so the model path is
    topology-invariant."""
    regs = topo.region_of[[int(e.work["cid"]) for e in entries]]
    ebuf = topo_lib.edge_reduce(
        np.asarray(flat_deltas)[:len(entries)],
        np.asarray(wts[:len(entries)], np.float32),
        regs, topo.num_regions)
    counts = np.bincount(regs, minlength=topo.num_regions)
    for k in np.nonzero(counts)[0]:
        registry.counter("edge_flushes").inc(label=int(k))
        registry.counter("edge_up_bytes").inc(edge_bytes, label=int(k))
        registry.counter("edge_down_bytes").inc(down_bytes, label=int(k))
        tracer.instant("edge_flush", now, parent=parent, region=int(k),
                       fill=int(counts[k]), up_bytes=edge_bytes,
                       norm=float(np.linalg.norm(ebuf[k])), flush=applied)


def _run_async(y, frozen, loss_fn, dataset, rc, rounds, grid, server_opt, *,
               fleet, report, down_bytes, up_bytes, compute_seconds,
               data_rng, dev_rng, seed, data_kind, eval_every, eval_fn, log,
               cplan, tier_of_client, tier_up, tier_compute, dyn, dyn_rng,
               policy, registry, tracer, bfaults, san, topo,
               bshocks, mesh):
    if server_opt is None:
        server_opt = fedpt.resolve_server_opt(rc)
    # trivial plans keep the pre-plan engine (lane-exact acceptance);
    # per-tier metering still runs off the scheduler's tier counters
    tiered = cplan is not None and not cplan.trivial
    # per-flush DP: the flush (goal_count buffered deltas, fixed
    # denominator) is the unit of composition — see core/dp.py
    flush_dp = accountant = None
    if rc.dp_noise_multiplier > 0:
        if rc.dp_clip_norm <= 0:
            raise ValueError("async DP noise needs dp_clip_norm > 0 "
                             "(per-client clipping bounds the flush "
                             "sensitivity)")
        flush_dp = dp_lib.FlushDPConfig(
            clip_norm=rc.dp_clip_norm,
            noise_multiplier=rc.dp_noise_multiplier,
            goal_count=grid.goal_count)
        accountant = dp_lib.FlushAccountant(flush_dp, tracer=tracer)
    constrain_flat = shard_lib.flat_constrainer(mesh) if mesh else None
    lane = grid.goal_count if grid.lanes is None else int(grid.lanes)
    # one engine per tier: lanes are tier-homogeneous (pending clients
    # group by tier below), so each tier's lane step traces exactly once
    # at its own (lane, tier_size) width
    tier_keys = [t.index for t in cplan.tiers] if tiered else [None]
    if lane > 0:
        lane_steps = {
            k: jax.jit(fedpt.make_lane_step(
                loss_fn, rc, lane, constrain_flat_fn=constrain_flat,
                tier=None if k is None else cplan.tiers[k],
                plan=None if k is None else cplan))
            for k in tier_keys}
        lane_steps = prof_lib.annotate_map(lane_steps, "grid/lane_step")
    else:
        client_steps = {
            k: jax.jit(fedpt.make_client_step(
                loss_fn, rc,
                tier=None if k is None else cplan.tiers[k],
                plan=None if k is None else cplan))
            for k in tier_keys}
        client_steps = prof_lib.annotate_map(client_steps,
                                             "grid/client_step")
    apply_fn = prof_lib.annotate(
        jax.jit(fedpt.make_buffered_apply(
            server_opt, flush_dp=flush_dp, constrain_flat_fn=constrain_flat,
            plan=cplan, sanitize=san,
            fused_threshold=grid.agg_tail_threshold), donate_argnums=(0, 1)),
        "grid/server_apply")
    staleness_fn = fedpt.get_staleness_fn(grid.staleness, **grid.staleness_kw)
    if flush_dp is not None:
        # the per-flush sensitivity bound (clip_norm / goal_count)
        # assumes aggregation weights in [0, 1]; a custom staleness fn
        # exceeding 1 would silently invalidate the reported epsilon
        inner_staleness = staleness_fn

        def staleness_fn(s):
            w = inner_staleness(s)
            if not 0.0 <= w <= 1.0:
                raise ValueError(
                    f"staleness weight {w} for staleness {s} is outside "
                    "[0, 1]: per-flush DP calibrates sigma for weights "
                    "<= 1 (use a non-amplifying staleness_fn with DP)")
            return w
    N = num_clients(dataset)
    batch_fn = (syn.client_batch_images if data_kind == "images"
                else syn.client_batch_tokens)
    # one pre-reduced fp32 flat buffer per active edge per flush
    # (shape-determined, so measured once)
    edge_bytes = wire.edge_flush_bytes(y) if topo is not None else 0

    # mutable server state shared with the scheduler callbacks; events are
    # processed in virtual-time order, so "the model right now" is exactly
    # what a client dispatched at the current event time downloads
    state = {"y": y, "sstate": server_opt.init(y), "applied": 0}
    # lane mode: client steps dispatched since the last flush, grouped
    # by trainability tier (each group is one lane batch at its tier's
    # width). They all trained on the model of the CURRENT server
    # version (y only changes at flushes), so deferring them until the
    # next flush and running them as (lane, ...) batches is exactly the
    # sequential semantics — their completion times never depend on when
    # the compute runs.
    pending: Dict[Any, List] = {k: [] for k in tier_keys}

    def run_pending():
        for key, queue in pending.items():
            while queue:
                chunk = queue[:lane]
                del queue[:len(chunk)]
                n = len(chunk)
                # pad short lanes with a repeat of the last real batch:
                # one fixed (lane, ...) shape -> lane_step never re-traces
                with prof_lib.span("grid/restack", clients=n):
                    stacked = {k: np.stack([b[k] for b, _ in chunk]
                                           + [chunk[-1][0][k]] * (lane - n))
                               for k in chunk[0][0]}
                deltas, losses = lane_steps[key](state["y"], frozen, stacked)
                for i, (_, cell) in enumerate(chunk):
                    cell.delta, cell.loss = deltas[i], losses[i]

    def sample_cid(rng):
        return policy.sample_cid(rng)

    def tier_of(cid):
        # the policy's map, queried at dispatch time (rotation/adaptive
        # policies move it between server updates)
        return (int(policy.current_tiers()[cid]) if cplan is not None
                else None)

    def run_client(cid, version):
        b, w = batch_fn(dataset, cid, rc.local_steps, rc.local_batch,
                        data_rng)
        if rc.uniform_weights or rc.dp_clip_norm > 0:
            w = 1.0  # DP / uniform weighting, as in the sync engine
        elif not policy.trivial:
            # importance-unbiased selection weight (dropped under DP —
            # the fixed-denominator uniform weighting calibrates sigma)
            w = w * policy.client_weight(cid)
        # payload size is shape-determined: reuse the once-measured
        # (tier-sliced, when a plan is active) value instead of
        # serializing every delta just to count its bytes
        t = tier_of(cid)
        up = int(tier_up[t]) if cplan is not None else up_bytes
        key = t if tiered else None
        if lane > 0:
            cell = _LaneCell()
            pending[key].append((b, cell))
            return {"cell": cell, "weight": w, "up_bytes": up,
                    "cid": cid, "tier": t}
        delta, metrics = client_steps[key](state["y"], frozen, b)
        # loss stays a device scalar: converted once per flush, not per
        # client (a float() here would force a host round-trip per client)
        return {"delta": delta, "loss": metrics["client_loss"],
                "weight": w, "up_bytes": up, "cid": cid, "tier": t}

    def entry_arrays(e):
        cell = e.work.get("cell")
        if cell is not None:
            return cell.resolve()
        return e.work["delta"], e.work["loss"]

    def apply_update(entries, now, version):
        # wall-clock spans (obs/profiling.py): one grid/flush per server
        # update, its host work in named children
        with prof_lib.round_span("grid/flush", state["applied"]):
            return flush(entries, now)

    def flush(entries, now):
        if lane > 0:
            run_pending()
        with prof_lib.span("grid/restack", clients=len(entries)):
            rows, losses = [], []
            for e in entries:
                d, l = entry_arrays(e)
                f = e.work.get("fault")
                if f is not None and f["kind"] in ("nan", "bitflip"):
                    # materialize the wire corruption from the per-event
                    # seed (duplicate rows share the work dict and damage
                    # identically; the client's reported loss predates
                    # the wire, so it stays intact)
                    d = jnp.asarray(faults_lib.corrupt_row(
                        np.asarray(d), f["kind"], f["seed"], bfaults.cfg))
                rows.append(d)
                losses.append(l)
            wts = [e.weight for e in entries]
            # pad a short (drained) flush to the fixed goal_count shape
            # with zero-weight rows, so apply_fn never re-traces — and
            # under DP the fixed-denominator mean and per-flush sigma
            # never change
            flat_deltas = flat_lib.pad_rows(jnp.stack(rows),
                                            grid.goal_count)
            wts = wts + [0.0] * (grid.goal_count - len(entries))
            args = (state["y"], state["sstate"], flat_deltas,
                    jnp.asarray(wts, jnp.float32))
            if tiered:
                # per-row tier ids drive the apply's block masks; padding
                # rows carry tier 0 + weight 0 and fall out of both means
                tids = ([e.work["tier"] for e in entries]
                        + [0] * (grid.goal_count - len(entries)))
                args += (jnp.asarray(tids, jnp.int32),)
        if flush_dp is not None:
            # one PRNG key per flush, from the same stream family as the
            # sync engine's per-round keys
            args += (jax.random.key(seed * 100_003 + state["applied"]),)
            # dispatch samples clients WITH replacement, so one client
            # may own several rows of this flush; the accountant scales
            # that flush's sensitivity by the observed multiplicity
            counts = Counter(e.work["cid"] for e in entries)
            # sched is assigned before run() ever calls this closure;
            # last_flush_seq is the flush instant the scheduler emitted
            # just before invoking us, i.e. this very flush
            accountant.record_flush(len(entries),
                                    multiplicity=max(counts.values()),
                                    now=now,
                                    parent=sched.last_flush_seq)
        y_new, ss, m = apply_fn(*args)
        state["y"], state["sstate"] = y_new, ss
        with prof_lib.span("grid/wait"):
            # ONE host sync per flush for the buffered losses
            out = {"loss": float(jnp.mean(jnp.stack(losses))),
                   "delta_norm": float(m["delta_norm"])}
        applied = state["applied"]
        with prof_lib.span("grid/bookkeeping"):
            if san is not None:
                _flush_quarantine(m, entries, registry, tracer, now,
                                  sched.last_flush_seq, applied)
            if topo is not None and entries:
                _edge_reduce_flush(topo, flat_deltas, wts, entries,
                                   registry, tracer, down_bytes,
                                   edge_bytes, now, sched.last_flush_seq,
                                   applied)
        state["applied"] = applied + 1
        if eval_fn and eval_every and state["applied"] % eval_every == 0:
            with prof_lib.span("grid/eval_fn"):
                out.update(eval_fn(part.merge(y_new, frozen)))
        # a flush is the async "round": rotation/adaptive policies step
        # their tier maps here
        policy.end_round(applied)
        return out

    # every live RNG stream a snapshot must capture (the fault stream
    # only exists when a failure model is active)
    rngs = {"data": data_rng, "dev": dev_rng, "dyn": dyn_rng}
    if bfaults is not None:
        rngs["fault"] = bfaults.rng
    last_ckpt = {"path": None}

    def checkpoint_hook(s, now):
        # called by the scheduler after every full-buffer flush — the
        # one boundary where run_pending() has resolved every lane cell
        if state["applied"] % grid.checkpoint_every != 0:
            return
        with prof_lib.span("grid/checkpoint"):
            meta, arrays = gstate_lib.encode_async(
                state=state, sched=s, rngs=rngs, accountant=accountant,
                policy=policy, registry=registry, shocks=bshocks, topo=topo)
            path = gstate_lib.save_state(
                gstate_lib.checkpoint_path(grid.checkpoint_dir,
                                           state["applied"], "async"),
                meta, arrays)
        last_ckpt["path"] = path
        registry.counter("checkpoints").inc()
        tracer.instant("checkpoint", now, parent=s.last_flush_seq,
                       path=path,
                       applied=state["applied"], mode="async",
                       buffer_fill=float(len(s.buffer)),
                       events_in_flight=len(s.q))

    sched = sched_lib.BufferedAsyncScheduler(
        fleet=fleet, concurrency=min(grid.concurrency, N),
        goal_count=grid.goal_count, staleness_fn=staleness_fn,
        sample_cid=sample_cid, run_client=run_client,
        apply_update=apply_update, down_bytes=down_bytes,
        compute_seconds=compute_seconds, rng=dev_rng,
        tier_of=tier_of if cplan is not None else None,
        compute_of=((lambda cid: float(tier_compute[tier_of(cid)]))
                    if cplan is not None else None),
        region_of=((lambda cid: int(topo.region_of[cid]))
                   if topo is not None else None),
        shocks=bshocks,
        dynamics=dyn, dyn_rng=dyn_rng, observe=policy.observe,
        tracer=tracer, metrics=registry, faults=bfaults,
        checkpoint_hook=(checkpoint_hook if grid.checkpoint_every > 0
                         else None))
    if grid.resume_from:
        gstate_lib.decode_async(
            *gstate_lib.load_state(grid.resume_from), state=state,
            sched=sched, sstate_template=state["sstate"], rngs=rngs,
            accountant=accountant, policy=policy, registry=registry,
            shocks=bshocks, topo=topo,
            make_cell=_LaneCell if lane > 0 else None)
        last_ckpt["path"] = grid.resume_from
    t_wall = time.time()
    try:
        history = sched.run(rounds, deadline=grid.async_deadline)
    except faults_lib.ServerKilled as e:
        # annotate the kill with the latest snapshot so callers can
        # resume (None when checkpointing was off)
        e.checkpoint = last_ckpt["path"]
        raise
    spr = (time.time() - t_wall) / max(rounds, 1)
    if log:
        for rec in history[:: max(1, rounds // 10)]:
            print(f"  update {rec['round']}: " + " ".join(
                f"{k}={v:.4f}" for k, v in rec.items() if k != "round"))

    vt = history[-1]["virtual_seconds"] if history else 0.0
    if cplan is not None:
        for t in cplan.tiers:
            nd = sched.tier_dispatches.get(t.index, 0)
            if nd or sched.tier_uploads.get(t.index, 0):
                report.add_tier_measured(
                    t.name, down_bytes * nd,
                    sched.tier_up_bytes.get(t.index, 0), transfers=nd,
                    uploads=sched.tier_uploads.get(t.index, 0), now=vt,
                    parent=sched.last_flush_seq)
    else:
        report.add_measured(down_bytes * sched.dispatches,
                            sched.up_bytes_total,
                            transfers=sched.dispatches)
    if topo is not None:
        # edge_server hop, billed from the registry's per-region edge
        # counters — the registry is snapshotted/restored with the run,
        # so a resumed run bills this hop exactly
        n_flush = int(registry.counter("edge_flushes").value)
        report.add_hop(
            "edge_server",
            down_bytes=int(registry.counter("edge_down_bytes").value),
            up_bytes=int(registry.counter("edge_up_bytes").value),
            transfers=n_flush, uploads=n_flush)
    final_tiers = (policy.current_tiers() if cplan is not None
                   else tier_of_client)
    if tracer.enabled:
        tracer.flush_outputs()
    return GridResult(y=state["y"], frozen=frozen, history=history,
                      comm=report, seconds_per_round=spr,
                      virtual_seconds=vt, fleet=fleet, mode="async",
                      scheduler_stats=_stats_view(registry),
                      dp=accountant.summary() if accountant else None,
                      tier_stats=_tier_stats(report, cplan, final_tiers,
                                             registry),
                      plan=cplan, policy=policy, dynamics=dyn,
                      topology=topo, metrics=registry,
                      telemetry=tracer if tracer.enabled else None,
                      faults=_faults_view(registry, bfaults))
