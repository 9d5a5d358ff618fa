"""Simulation grid: bit-for-bit equivalence with the plain federated
loop, byte-exact wire metering, straggler/dropout handling, and buffered
async aggregation with staleness weighting."""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.partition as part
from repro.core import comm, fedpt
from repro.data import synthetic as syn
from repro.fl import runtime
from repro.nn import basic
from repro.sim import devices as dev_lib
from repro.sim import grid as simgrid
from repro.sim import scheduler as sched_lib
from repro.sim import wire


# ---------------------------------------------------------------------------
# A tiny linear model so each test compiles in well under a second.


def init_fn(seed):
    return {"dense": basic.init_dense(seed, "dense", 64, 4, jnp.float32,
                                      bias=True)}


def loss_fn(params, b):
    x = b["images"].reshape(b["images"].shape[0], -1)
    logits = basic.dense(x, params["dense"])
    lp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(lp, b["labels"][:, None], 1)), {}


def make_ds(n_clients=12, seed=0):
    return syn.make_federated_images(n_clients, 30, (8, 8, 1), 4, seed=seed,
                                     test_examples=64)


RC = fedpt.RoundConfig(4, 2, 8, "sgd", 0.1, "sgd", 1.0)


# ---------------------------------------------------------------------------
# Acceptance: homogeneous sync grid == the plain loop, bit for bit


def test_sync_grid_reproduces_plain_loop_bit_for_bit():
    ds = make_ds()
    seed, rounds = 3, 5
    # reference: the pre-grid run_federated loop, inlined
    y, frozen = part.partition(init_fn(seed), ())
    round_fn, sopt = fedpt.make_round_fn(loss_fn, RC)
    round_fn = jax.jit(round_fn, donate_argnums=(0, 1))
    ss = sopt.init(y)
    rng = np.random.default_rng(seed + 77)
    ref_losses = []
    for r in range(rounds):
        cids = syn.sample_cohort(rng, ds.num_clients, RC.clients_per_round)
        batch, w = syn.cohort_batch(ds, cids, RC.local_steps, RC.local_batch,
                                    rng)
        y, ss, m = round_fn(y, ss, frozen, batch, jnp.asarray(w),
                            jax.random.key(seed * 100_003 + r))
        ref_losses.append(float(m["loss"]))

    res = runtime.run_federated(init_fn, loss_fn, ds, RC, rounds, seed=seed)
    assert [h["loss"] for h in res.history] == ref_losses
    for (p1, l1), (p2, l2) in zip(basic.flatten_params(y),
                                  basic.flatten_params(res.y)):
        assert p1 == p2
        assert bool(jnp.all(l1 == l2)), p1


# ---------------------------------------------------------------------------
# Acceptance: measured wire bytes == analytic ledger (fp32 exactly)


def test_wire_bytes_match_analytic():
    y, frozen = part.partition(init_fn(0), (r"bias",))
    wire.assert_matches_analytic(y, frozen, uplink_bits=0)
    wire.assert_matches_analytic(y, frozen, uplink_bits=8)
    rep = comm.report_for(y, frozen)
    assert wire.downlink_bytes(y) == rep.download_fedpt \
        == basic.tree_bytes(y) + comm.SEED_BYTES
    assert wire.uplink_bytes(y) == rep.upload_fedpt == basic.tree_bytes(y)


def test_wire_roundtrip():
    y, _ = part.partition(init_fn(1), ())
    spec = wire.TreeSpec.of(y)
    buf = wire.encode_downlink(y, seed=42)
    y2, seed = wire.decode_downlink(buf, spec)
    assert seed == 42
    for a, b in zip(jax.tree_util.tree_leaves(y),
                    jax.tree_util.tree_leaves(y2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # fp32 uplink is lossless
    delta = jax.tree_util.tree_map(lambda l: l * 0.1, y)
    d2 = wire.decode_uplink(wire.encode_uplink(delta), spec)
    for a, b in zip(jax.tree_util.tree_leaves(delta),
                    jax.tree_util.tree_leaves(d2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # int8 uplink is lossy but within half a quantization step per leaf
    buf8 = wire.encode_uplink(delta, bits=8)
    from repro.core import compress
    assert len(buf8) == compress.quantized_uplink_bytes(delta, 8)
    d8 = wire.decode_uplink(buf8, spec, bits=8)
    for a, b in zip(jax.tree_util.tree_leaves(delta),
                    jax.tree_util.tree_leaves(d8)):
        step = float(jnp.max(jnp.abs(a))) / 127.0
        assert float(jnp.max(jnp.abs(a - b))) <= step / 2 + 1e-7


def test_grid_meters_every_transfer():
    ds = make_ds()
    res = simgrid.run_grid(init_fn, loss_fn, ds, RC, 4, seed=0)
    rep = comm.report_for(res.y, res.frozen)
    n = res.comm.transfers
    assert n == 4 * RC.clients_per_round
    assert res.comm.measured_down_bytes == rep.download_fedpt * n
    assert res.comm.measured_up_bytes == rep.upload_fedpt * n


# ---------------------------------------------------------------------------
# Scheduler: straggler deadlines, over-selection, dropout


def _fleet(mults, **kw):
    mb = 1024.0 * 1024.0
    return dev_lib.Fleet(name="test", profiles=[
        dev_lib.DeviceProfile(downlink_bps=mb, uplink_bps=mb,
                              compute_multiplier=m, **kw) for m in mults])


def test_sync_plan_straggler_deadline_drop():
    # compute_seconds=1.0, no wire bytes: finish times == multipliers
    fleet = _fleet([1.0, 2.0, 3.0, 50.0])
    plan = sched_lib.plan_sync_round(fleet, [0, 1, 2, 3], 0, 0, 1.0,
                                     clients_needed=4,
                                     rng=np.random.default_rng(0),
                                     deadline=10.0)
    assert plan.deadline_drops == 1
    assert list(plan.participant) == [True, True, True, False]
    assert plan.round_seconds == 10.0  # server waited the deadline out
    np.testing.assert_array_equal(plan.participant_cids(), [0, 1, 2])


def test_sync_plan_over_selection_takes_first_arrivals():
    fleet = _fleet([5.0, 1.0, 3.0, 2.0])
    plan = sched_lib.plan_sync_round(fleet, [0, 1, 2, 3], 0, 0, 1.0,
                                     clients_needed=2,
                                     rng=np.random.default_rng(0))
    # fastest two finish at t=1 (cid 1) and t=2 (cid 3)
    np.testing.assert_array_equal(plan.participant_cids(), [1, 3])
    assert plan.round_seconds == 2.0
    # over-selected losers arrived on time but past the quota: counted as
    # excess, NOT as deadline drops (there is no deadline here)
    assert plan.excess == 2 and plan.deadline_drops == 0


def test_sync_plan_dropout_and_offline():
    fleet = _fleet([1.0, 1.0, 1.0], dropout=1.0)     # everyone drops
    plan = sched_lib.plan_sync_round(fleet, [0, 1, 2], 0, 0, 1.0, 3,
                                     np.random.default_rng(0), deadline=5.0)
    assert plan.dropouts == 3 and not plan.participant.any()
    assert plan.round_seconds == 5.0
    off = _fleet([1.0, 1.0], availability=0.0)       # everyone offline
    plan = sched_lib.plan_sync_round(off, [0, 1], 0, 0, 1.0, 2,
                                     np.random.default_rng(0), deadline=5.0)
    assert plan.offline == 2 and plan.dropouts == 0


def test_sync_grid_drops_straggler_weight():
    """A client that can never finish by the deadline must not influence
    the aggregate: its round-engine weight is zeroed."""
    ds = make_ds(n_clients=4)
    fleet = _fleet([1.0, 1.0, 1.0, 500.0])
    rc = fedpt.RoundConfig(4, 2, 8, "sgd", 0.1, "sgd", 1.0)
    gc = simgrid.GridConfig(mode="sync", fleet=fleet, straggler_deadline=10.0,
                            base_step_time=1.0)
    res = simgrid.run_grid(init_fn, loss_fn, ds, rc, 3, grid=gc, seed=0)
    assert res.scheduler_stats["deadline_drops"] == 3  # slow client, 3 rounds
    assert all(h["participants"] == 3.0 for h in res.history)
    assert res.virtual_seconds == pytest.approx(30.0)


# ---------------------------------------------------------------------------
# Buffered async scheduler (unit, no JAX)


def test_async_goal_count_and_staleness_weighting():
    # cid 0 finishes in 1s, cid 1 in 5.5s; no wire time
    fleet = _fleet([1.0, 5.5])
    samples = iter([0, 1] + [0] * 50)
    applied = []

    def run_client(cid, version):
        return {"delta": cid, "weight": 1.0, "loss": 0.5, "up_bytes": 0}

    def apply_update(entries, now, version):
        applied.append((now, version, [(e.staleness, e.weight) for e in entries]))
        return {}

    sched = sched_lib.BufferedAsyncScheduler(
        fleet=fleet, concurrency=2, goal_count=2,
        staleness_fn=fedpt.get_staleness_fn("polynomial", power=0.5),
        sample_cid=lambda rng: next(samples), run_client=run_client,
        apply_update=apply_update, down_bytes=0, compute_seconds=1.0,
        rng=np.random.default_rng(0))
    records = sched.run(3)

    assert len(records) == 3
    assert all(len(entries) == 2 for _, _, entries in applied)  # goal count K
    # updates 1 and 2 are pure fast-client buffers (staleness 0)
    assert applied[0][2] == [(0, 1.0), (0, 1.0)]
    assert applied[1][2] == [(0, 1.0), (0, 1.0)]
    # the slow client dispatched at t=0 lands at t=5.5, after 2 server
    # updates: staleness 2, weight (1+2)^-0.5
    stale = dict(applied[2][2])
    assert 2 in stale
    assert stale[2] == pytest.approx((1.0 + 2.0) ** -0.5)
    assert records[2]["staleness_max"] == 2.0
    assert records[-1]["virtual_seconds"] >= records[0]["virtual_seconds"]


def test_staleness_fns():
    poly = fedpt.get_staleness_fn("polynomial", power=0.5)
    assert poly(0) == 1.0 and poly(3) == pytest.approx(0.5)
    const = fedpt.get_staleness_fn("constant")
    assert const(100) == 1.0
    hinge = fedpt.get_staleness_fn("hinge", delay=2.0, slope=1.0)
    assert hinge(2) == 1.0 and hinge(4) == pytest.approx(1.0 / 3.0)
    assert fedpt.get_staleness_fn(lambda s: 7.0)(1) == 7.0
    with pytest.raises(ValueError):
        fedpt.get_staleness_fn("nope")


# ---------------------------------------------------------------------------
# Async grid end-to-end (heterogeneous fleet + quantized uplink)


def test_async_grid_end_to_end():
    ds = make_ds(n_clients=20, seed=0)
    rc = fedpt.RoundConfig(4, 2, 8, "sgd", 0.1, "sgd", 1.0, uplink_bits=8)
    gc = simgrid.GridConfig(mode="async", fleet="pareto-mobile",
                            concurrency=6, goal_count=3,
                            staleness="polynomial")
    res = simgrid.run_grid(init_fn, loss_fn, ds, rc, 12, grid=gc, seed=1)
    assert len(res.history) == 12
    assert res.history[-1]["loss"] < res.history[0]["loss"]
    assert res.virtual_seconds > 0
    assert any(h["staleness_max"] > 0 for h in res.history)
    # every upload was metered at the measured int8 payload size
    per_up = wire.uplink_bytes(res.y, bits=8)
    assert res.comm.measured_up_bytes == per_up * res.scheduler_stats["uploads"]
    assert res.comm.measured_down_bytes == (wire.downlink_bytes(res.y)
                                            * res.scheduler_stats["dispatches"])
    assert res.comm.upload_fedpt == per_up  # analytic agrees with the wire


def test_async_grid_dp_per_flush():
    """Async DP composes per flush: noise is drawn once per buffered
    server update with the fixed goal_count denominator, the run is
    replay-deterministic, and the accountant reports the composition."""
    ds = make_ds(n_clients=10)
    rc = fedpt.RoundConfig(4, 2, 8, "sgd", 0.1, "sgd", 1.0,
                           dp_clip_norm=0.5, dp_noise_multiplier=0.4)
    gc = simgrid.GridConfig(mode="async", concurrency=5, goal_count=3)
    a = simgrid.run_grid(init_fn, loss_fn, ds, rc, 6, grid=gc, seed=4)
    b = simgrid.run_grid(init_fn, loss_fn, ds, rc, 6, grid=gc, seed=4)
    # deterministic: per-flush keys come from the seed stream
    assert [h["loss"] for h in a.history] == [h["loss"] for h in b.history]
    for (pa, la), (pb, lb) in zip(basic.flatten_params(a.y),
                                  basic.flatten_params(b.y)):
        assert bool(jnp.all(la == lb)), pa
    assert a.dp == b.dp
    assert a.dp["flushes"] == 6 and a.dp["padded_flushes"] == 0
    assert a.dp["sigma"] == pytest.approx(0.4 * 0.5 / 3)
    assert a.dp["max_multiplicity"] >= 1   # with-replacement dispatch
    assert 0 < a.dp["epsilon"] < math.inf
    # the noise path actually fires: same config with z=0 diverges
    rc0 = fedpt.RoundConfig(4, 2, 8, "sgd", 0.1, "sgd", 1.0,
                            dp_clip_norm=0.5)
    c = simgrid.run_grid(init_fn, loss_fn, ds, rc0, 6, grid=gc, seed=4)
    assert c.dp is None
    assert any(h["loss"] != hc["loss"] or h["delta_norm"] != hc["delta_norm"]
               for h, hc in zip(a.history, c.history))
    # ... but the virtual clock / staleness bookkeeping is unaffected
    for h, hc in zip(a.history, c.history):
        assert h["virtual_seconds"] == hc["virtual_seconds"]
        assert h["staleness_mean"] == hc["staleness_mean"]


def test_async_grid_dp_noise_requires_clip():
    ds = make_ds(n_clients=6)
    rc = fedpt.RoundConfig(4, 2, 8, dp_noise_multiplier=0.5)
    with pytest.raises(ValueError, match="dp_clip_norm"):
        simgrid.run_grid(init_fn, loss_fn, ds, rc, 1,
                         grid=simgrid.GridConfig(mode="async"))


def test_async_grid_dp_drained_flush_keeps_noise_scale():
    """The deadline-drained final buffer is padded to goal_count with
    zero weights: same fixed denominator, same sigma, and the accountant
    records it as one (padded) flush."""
    ds = make_ds(n_clients=10)
    rc = fedpt.RoundConfig(4, 2, 8, "sgd", 0.1, "sgd", 1.0,
                           dp_clip_norm=0.5, dp_noise_multiplier=0.4)
    gc = simgrid.GridConfig(mode="async", concurrency=4, goal_count=3)
    full = simgrid.run_grid(init_fn, loss_fn, ds, rc, 6, grid=gc, seed=2)
    cut = (full.history[1]["virtual_seconds"]
           + full.history[2]["virtual_seconds"]) / 2.0
    gcd = dataclasses.replace(gc, async_deadline=cut)
    res = simgrid.run_grid(init_fn, loss_fn, ds, rc, 6, grid=gcd, seed=2)
    assert res.history[-1]["buffer_fill"] < gc.goal_count
    assert res.dp["flushes"] == len(res.history)
    assert res.dp["padded_flushes"] == 1
    assert res.dp["sigma"] == full.dp["sigma"]
    # the un-cut prefix replays the unconstrained run exactly (identical
    # per-flush keys and fixed denominator)
    for a, b in zip(full.history[:2], res.history[:2]):
        assert a["loss"] == b["loss"]
        assert a["delta_norm"] == b["delta_norm"]


# ---------------------------------------------------------------------------
# Trainability tiers (core/plan.py) in the grid

TIER_PLAN = {"full": (), "mid": (r"/bias$",), "lite": (r"/kernel$",)}


def _assert_same_run(a, b):
    assert [h["loss"] for h in a.history] == [h["loss"] for h in b.history]
    for ha, hb in zip(a.history, b.history):
        assert ha["virtual_seconds"] == hb["virtual_seconds"]
    for (pa, la), (pb, lb) in zip(basic.flatten_params(a.y),
                                  basic.flatten_params(b.y)):
        assert pa == pb and bool(jnp.all(la == lb)), pa
    assert a.comm.measured_down_bytes == b.comm.measured_down_bytes
    assert a.comm.measured_up_bytes == b.comm.measured_up_bytes
    assert a.scheduler_stats == b.scheduler_stats


def test_sync_grid_one_tier_plan_bit_for_bit():
    """Acceptance: a one-tier plan covering all clients IS the pre-plan
    single-spec system — same history, params, clock and wire bytes."""
    from repro.core import plan as plan_lib
    ds = make_ds()
    ref = simgrid.run_grid(init_fn, loss_fn, ds, RC, 4, seed=3)
    gc = simgrid.GridConfig(plan=plan_lib.TrainPlan.single())
    got = simgrid.run_grid(init_fn, loss_fn, ds, RC, 4, grid=gc, seed=3)
    _assert_same_run(ref, got)
    # ... and the whole ledger lands on the single tier
    assert set(got.tier_stats) == {"full"}
    assert got.tier_stats["full"]["up_bytes"] == ref.comm.measured_up_bytes
    assert got.tier_stats["full"]["clients"] == ds.num_clients


def test_async_grid_one_tier_plan_lane_exact():
    """Acceptance: the async lane engine under a one-tier plan replays
    the pre-plan run exactly (virtual clock, staleness, params)."""
    from repro.core import plan as plan_lib
    ds = make_ds(n_clients=16)
    gc = simgrid.GridConfig(mode="async", fleet="pareto-mobile",
                            concurrency=6, goal_count=3)
    ref = simgrid.run_grid(init_fn, loss_fn, ds, RC, 8, grid=gc, seed=2)
    got = simgrid.run_grid(
        init_fn, loss_fn, ds, RC, 8, seed=2,
        grid=dataclasses.replace(gc, plan=plan_lib.TrainPlan.single()))
    _assert_same_run(ref, got)
    for ha, hb in zip(ref.history, got.history):
        assert ha["staleness_mean"] == hb["staleness_mean"]


def test_async_grid_mixed_tiers_bills_fewer_uplink():
    """Acceptance: a mixed-tier fleet bills strictly fewer uplink bytes
    than the all-`full` run, with per-tier byte counts reported."""
    ds = make_ds(n_clients=12)
    gc = simgrid.GridConfig(mode="async", fleet="pareto-mobile",
                            concurrency=6, goal_count=3)
    full = simgrid.run_grid(init_fn, loss_fn, ds, RC, 10, grid=gc, seed=5)
    mixed = simgrid.run_grid(
        init_fn, loss_fn, ds, RC, 10, seed=5,
        grid=dataclasses.replace(gc, plan=TIER_PLAN))
    assert mixed.history[-1]["loss"] < mixed.history[0]["loss"]
    st = mixed.tier_stats
    assert set(st) == {"full", "mid", "lite"}
    assert sum(r["clients"] for r in st.values()) == ds.num_clients
    # per-tier bytes are reported and sum to the ledger totals
    assert sum(r["up_bytes"] for r in st.values()) \
        == mixed.comm.measured_up_bytes
    assert sum(r["down_bytes"] for r in st.values()) \
        == mixed.comm.measured_down_bytes
    # every mid/lite upload is strictly smaller than a full upload, so
    # with any non-full participation the mixed fleet pays less uplink
    # per upload on average
    per_up_mixed = mixed.comm.measured_up_bytes / max(
        mixed.scheduler_stats["uploads"], 1)
    per_up_full = full.comm.measured_up_bytes / max(
        full.scheduler_stats["uploads"], 1)
    assert sum(r["uploads"] for r in st.values() if r["uploads"]) > 0
    assert any(r["uploads"] > 0 for k, r in st.items() if k != "full")
    assert per_up_mixed < per_up_full
    # tier uplink is billed at the measured sliced payload, and
    # tier_stats' per-upload figure matches the measured ledger
    y_mid, _ = mixed.plan.split(mixed.y, mixed.plan.tiers[1])
    assert st["mid"]["up_bytes"] == wire.uplink_bytes(y_mid) \
        * st["mid"]["uploads"]
    for name, rec in st.items():
        want = rec["up_bytes"] / rec["uploads"] if rec["uploads"] else 0.0
        assert rec["up_bytes_per_upload"] == want, name
        assert rec["up_bytes_per_upload"] \
            == mixed.comm.tier_table()[name]["up_bytes_per_upload"]


def test_sync_grid_mixed_tiers():
    """Mixed tiers in the synchronous cohort engine: per-row tier masks
    keep frozen-for-this-tier leaves still when no capable client is
    sampled, and the wire bills tier-sliced uploads."""
    ds = make_ds(n_clients=9)
    # explicit census: clients 0-2 full, 3-5 mid (bias frozen), 6-8 lite
    assign = [0, 0, 0, 1, 1, 1, 2, 2, 2]
    gc = simgrid.GridConfig(plan=TIER_PLAN, tier_assignment=assign)
    res = simgrid.run_grid(init_fn, loss_fn, ds, RC, 4, grid=gc, seed=1)
    assert res.history[-1]["loss"] < res.history[0]["loss"]
    st = res.tier_stats
    assert [st[k]["clients"] for k in ("full", "mid", "lite")] == [3, 3, 3]
    assert sum(r["up_bytes"] for r in st.values()) \
        == res.comm.measured_up_bytes
    assert res.comm.measured_up_bytes > 0
    # lite uploads cost the bias bytes only
    if st["lite"]["uploads"]:
        y_lite, _ = res.plan.split(res.y, res.plan.tiers[2])
        assert st["lite"]["up_bytes"] == wire.uplink_bytes(y_lite) \
            * st["lite"]["uploads"]


def test_sync_grid_lite_only_cohort_freezes_masked_leaves():
    """A cohort made entirely of kernel-frozen clients must leave every
    kernel untouched — exact freezing, not just down-weighting."""
    ds = make_ds(n_clients=6)
    gc = simgrid.GridConfig(plan={"full": (), "lite": (r"/kernel$",)},
                            tier_assignment=[1] * 6)
    res = simgrid.run_grid(init_fn, loss_fn, ds, RC, 3, grid=gc, seed=0)
    y0, _ = part.partition(init_fn(0), ())
    assert bool(jnp.all(res.y["dense"]["kernel"] == y0["dense"]["kernel"]))
    assert not bool(jnp.all(res.y["dense"]["bias"] == y0["dense"]["bias"]))


def test_async_grid_mixed_tiers_dp():
    """Tiers compose with per-flush DP: the masked, clipped row keeps
    sensitivity clip/goal_count, so sigma and the accountant are
    tier-independent."""
    ds = make_ds(n_clients=10)
    rc = fedpt.RoundConfig(4, 2, 8, "sgd", 0.1, "sgd", 1.0,
                           dp_clip_norm=0.5, dp_noise_multiplier=0.4)
    gc = simgrid.GridConfig(mode="async", concurrency=5, goal_count=3,
                            plan=TIER_PLAN,
                            tier_assignment=[0, 0, 0, 1, 1, 1, 2, 2, 2, 2])
    a = simgrid.run_grid(init_fn, loss_fn, ds, rc, 6, grid=gc, seed=4)
    b = simgrid.run_grid(init_fn, loss_fn, ds, rc, 6, grid=gc, seed=4)
    assert [h["loss"] for h in a.history] == [h["loss"] for h in b.history]
    assert a.dp == b.dp
    assert a.dp["sigma"] == pytest.approx(0.4 * 0.5 / 3)
    assert a.dp["flushes"] == 6


# ---------------------------------------------------------------------------
# FlushAccountant satellites: repeated clients, multiplicity, and the
# staleness-weight rejection path, end to end through the grid


def test_async_grid_dp_repeated_clients_raise_multiplicity():
    """With-replacement dispatch over a 2-client dataset guarantees one
    client owns several rows of a 3-deep flush: the accountant must see
    multiplicity > 1 and charge more epsilon than a distinct-client
    composition of the same length."""
    ds = make_ds(n_clients=2)
    rc = fedpt.RoundConfig(2, 2, 8, "sgd", 0.1, "sgd", 1.0,
                           dp_clip_norm=0.5, dp_noise_multiplier=1.0)
    gc = simgrid.GridConfig(mode="async", concurrency=4, goal_count=3)
    res = simgrid.run_grid(init_fn, loss_fn, ds, rc, 5, grid=gc, seed=7)
    assert res.dp["max_multiplicity"] >= 2
    from repro.core import dp as dp_lib
    distinct = dp_lib.FlushAccountant(dp_lib.FlushDPConfig(
        clip_norm=0.5, noise_multiplier=1.0, goal_count=3))
    for _ in range(res.dp["flushes"]):
        distinct.record_flush(3, multiplicity=1)
    assert res.dp["epsilon"] > distinct.epsilon(res.dp["delta"])


def test_async_grid_dp_rejects_amplifying_staleness_weight():
    """Per-flush DP calibrates sigma for weights <= 1; a staleness fn
    that amplifies must be rejected, not silently under-noised."""
    ds = make_ds(n_clients=8)
    rc = fedpt.RoundConfig(4, 2, 8, "sgd", 0.1, "sgd", 1.0,
                           dp_clip_norm=0.5, dp_noise_multiplier=0.4)
    gc = simgrid.GridConfig(mode="async", concurrency=4, goal_count=3,
                            staleness=lambda s: 1.0 + s)
    with pytest.raises(ValueError, match=r"\[0, 1\]"):
        simgrid.run_grid(init_fn, loss_fn, ds, rc, 3, grid=gc, seed=1)
    # the same amplifying weighting is fine WITHOUT DP
    rc0 = fedpt.RoundConfig(4, 2, 8, "sgd", 0.1, "sgd", 1.0)
    res = simgrid.run_grid(init_fn, loss_fn, ds, rc0, 3, grid=gc, seed=1)
    assert len(res.history) == 3


def test_grid_rejects_oversized_cohort():
    ds = make_ds(n_clients=3)
    with pytest.raises(ValueError, match="clients_per_round"):
        simgrid.run_grid(init_fn, loss_fn, ds, RC, 1)


def test_fleet_presets():
    uni = dev_lib.make_fleet(8, "uniform")
    mb = 1024.0 * 1024.0
    for p in uni.profiles:
        assert p.downlink_bps == comm.DOWNLINK_MBPS * mb
        assert p.uplink_bps == comm.UPLINK_MBPS * mb
        assert p.availability == 1.0 and p.dropout == 0.0
    par = dev_lib.make_fleet(64, "pareto-mobile", seed=1)
    dls = {p.downlink_bps for p in par.profiles}
    assert len(dls) > 32                     # heterogeneous
    assert max(dls) <= comm.DOWNLINK_MBPS * mb
    silo = dev_lib.make_fleet(4, "cross-silo")
    assert all(p.availability == 1.0 for p in silo.profiles)
    assert silo.profiles[0].downlink_bps > 100 * mb
    with pytest.raises(ValueError):
        dev_lib.make_fleet(4, "galaxy-brain")
    # round-trip time composes download + compute + upload
    p = uni.profiles[0]
    t = p.round_trip_seconds(mb, mb, 2.0)
    assert t == pytest.approx(1 / comm.DOWNLINK_MBPS + 2.0
                              + 1 / comm.UPLINK_MBPS)


def test_summarize_delegates_to_comm_report():
    params = init_fn(0)
    spec = (r"bias",)
    s = part.summarize(params, spec)
    y, z = part.partition(params, spec)
    assert s["comm_reduction"] == comm.report_for(y, z).reduction
    assert s["trainable_bytes"] == basic.tree_bytes(y)


# ---------------------------------------------------------------------------
# One-round lookahead: round r+1's host work runs while round r runs, and
# changes nothing a run reports


def _lookahead_cases():
    from repro.sim import dynamics as dyn_lib
    from repro.sim import selection as sel_lib
    base = dict(fleet="pareto-mobile", over_selection=1.5,
                straggler_deadline=120.0, telemetry="memory")
    return {
        "plain": dict(base),
        # the policy re-tiers from the RTTs round r observes, which round
        # r+1's plan reads
        "tiered": dict(base, plan=TIER_PLAN,
                       selection=sel_lib.AdaptiveCapabilityPolicy(
                           refit_every=2)),
        # region shocks draw inside the plan; crash faults add the kill
        # check to every round
        "topology": dict(base, topology=3, faults={"crash_compute": 0.1},
                         dynamics=dyn_lib.DynamicsConfig(
                             shocks=dyn_lib.RegionShocks(
                                 every=0.005, duration=0.05,
                                 residual=0.0))),
    }


def _records(res):
    return [(e.kind, e.seq, e.parent, e.t, e.dur, list(e.payload.items()))
            for e in res.telemetry.events]


@pytest.mark.parametrize("case", ["plain", "tiered", "topology"])
def test_sync_lookahead_bit_identical(case, monkeypatch):
    """The lookahead run equals the same run with the lookahead forced
    off: weights, history, virtual clock, registry and wire ledger, and
    the tracer's records in kind, order, seq, parent and payload."""
    ds = make_ds()
    rounds = 5

    def run():
        return simgrid.run_grid(
            init_fn, loss_fn, ds, RC, rounds, seed=3,
            grid=simgrid.GridConfig(**_lookahead_cases()[case]))

    ahead = run()
    monkeypatch.setattr(simgrid, "_prefetch_next", lambda *a: False)
    plain = run()
    _assert_same_run(ahead, plain)
    assert ahead.history == plain.history
    assert ahead.virtual_seconds == plain.virtual_seconds
    assert ahead.tier_stats == plain.tier_stats
    assert ahead.comm.tier_traffic == plain.comm.tier_traffic
    assert ahead.comm.hop_traffic == plain.comm.hop_traffic
    snap_a, snap_p = ahead.metrics.snapshot(), plain.metrics.snapshot()
    assert snap_a["counters"].pop("prefetched_rounds")["value"] \
        == rounds - 1
    assert "prefetched_rounds" not in snap_p["counters"]
    assert snap_a == snap_p
    assert _records(ahead) == _records(plain)
    assert all(e.payload["loss"] == h["loss"] for e, h in zip(
        ahead.telemetry.of_kind("round"), ahead.history))


@pytest.mark.parametrize("extra", [dict(sanitize=True),
                                   dict(checkpoint_every=1)],
                         ids=["sanitize", "checkpoint"])
def test_sync_lookahead_waits_when_round_must_be_read(extra, tmp_path):
    """The quarantine screen and a checkpoint after every round read
    round r's result before round r+1 may be drawn: the loop never
    looks ahead there."""
    if "checkpoint_every" in extra:
        extra = dict(extra, checkpoint_dir=str(tmp_path / "ckpt"))
    res = simgrid.run_grid(init_fn, loss_fn, make_ds(), RC, 4, seed=3,
                           grid=simgrid.GridConfig(**extra))
    assert res.metrics.counter("prefetched_rounds").value == 0
    assert len(res.history) == 4


def test_sync_lookahead_kill_raises_after_round_record(tmp_path,
                                                      monkeypatch):
    """A kill due before round r+1 (seen by round r's lookahead) still
    raises at round r+1's start, after round r, with the same position
    and checkpoint as with the lookahead forced off."""
    from repro.sim import faults as faults_lib
    ds = make_ds()
    gc = simgrid.GridConfig(fleet="pareto-mobile")
    straight = simgrid.run_grid(init_fn, loss_fn, ds, RC, 6, grid=gc,
                                seed=3)
    T = 0.5 * (straight.history[2]["virtual_seconds"]
               + straight.history[3]["virtual_seconds"])

    def kill(sub):
        # checkpoints after rounds 2 and 5: round 3 looks ahead
        killed = dataclasses.replace(
            gc, faults={"server_kill_at": T}, checkpoint_every=3,
            checkpoint_dir=str(tmp_path / sub))
        with pytest.raises(faults_lib.ServerKilled) as ei:
            simgrid.run_grid(init_fn, loss_fn, ds, RC, 6, grid=killed,
                             seed=3)
        return ei.value

    ahead = kill("ahead")
    monkeypatch.setattr(simgrid, "_prefetch_next", lambda *a: False)
    plain = kill("plain")
    assert ahead.applied == plain.applied == 4
    assert ahead.at == plain.at == straight.history[3]["virtual_seconds"]
    assert os.path.basename(ahead.checkpoint) \
        == os.path.basename(plain.checkpoint)


def test_sync_lookahead_checkpoint_resume_bitwise(tmp_path):
    """A snapshot taken between looked-ahead rounds holds the RNG and
    policy state before the next round's draws: resuming from a
    mid-run checkpoint reproduces the straight run."""
    from repro.checkpoint import grid_state as gstate
    ds = make_ds()
    gc = simgrid.GridConfig(fleet="pareto-mobile", over_selection=1.5,
                            checkpoint_every=2,
                            checkpoint_dir=str(tmp_path / "ckpt"))
    straight = simgrid.run_grid(init_fn, loss_fn, ds, RC, 6, grid=gc,
                                seed=3)
    assert straight.metrics.counter("prefetched_rounds").value == 3
    resumed = simgrid.run_grid(
        init_fn, loss_fn, ds, RC, 6, seed=3,
        grid=dataclasses.replace(
            gc, checkpoint_dir=str(tmp_path / "again"),
            resume_from=gstate.checkpoint_path(str(tmp_path / "ckpt"), 2,
                                               "sync")))
    _assert_same_run(straight, resumed)
    assert straight.history == resumed.history
