"""FedPT round engine — Algorithm 1 of the paper, as a single jitted
mesh program.

One federated round:
  1. server "sends" (y_t, z): under datacenter simulation the trainable
     tree y is broadcast along the client (data) mesh axis and the frozen
     tree is regenerated from the seed (never communicated);
  2. every sampled client runs tau local ClientOpt steps with gradients
     flowing only into y (the frozen side is a constant input -> XLA
     allocates no grad buffers or optimizer state for it);
  3. client deltas are clipped (optionally, for DP) and weighted-mean
     aggregated — on the mesh this is the cross-client psum whose payload
     FedPT shrinks by |frozen|/|full|;
  4. ServerOpt treats -delta as a pseudo-gradient.

The engine is model-agnostic: it takes any ``loss_fn(params, batch)``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

import repro.core.partition as part
from repro.core import flat as flat_lib
from repro.core import sanitize as sanitize_lib
from repro.kernels import ops as kernel_ops
from repro.optim import optimizers as opt_lib

# ``jax.named_scope`` names of the round programs' three stages: HLO
# metadata only (each instruction's ``op_name``), so a device trace can
# put every operation's time on a stage; the computation is unchanged
SCOPE_CLIENT_STEP = "fedpt/client_step"
SCOPE_AGG_TAIL = "fedpt/agg_tail"
SCOPE_SERVER_APPLY = "fedpt/server_apply"


@dataclasses.dataclass(frozen=True)
class RoundConfig:
    clients_per_round: int
    local_steps: int            # tau
    local_batch: int
    client_opt: str = "sgd"
    client_lr: float = 0.05
    server_opt: str = "sgd"
    server_lr: float = 1.0
    server_momentum: float = 0.9
    # DP (DP-FedAvg clip/noise; DP-FTRL lives in core/dp.py ServerOpt)
    dp_clip_norm: float = 0.0   # 0 = off
    dp_noise_multiplier: float = 0.0
    uniform_weights: bool = False  # DP requires fixed (uniform) weighting
    # lossy uplink compression of client deltas (0 = off); complementary
    # to FedPT per the paper's §2/§5
    uplink_bits: int = 0


def make_client_update(loss_fn: Callable, client_opt: opt_lib.Optimizer,
                       local_steps: int):
    """Returns f(y, frozen, client_batch[, grad_mask]) -> (delta, metrics).

    client_batch: pytree with leading axis tau (one microbatch per local
    step). Gradients are taken wrt y only. The scalars of the loss's aux
    dict (a model's counters, e.g. the MoE routing counts) come back
    averaged over the local steps in ``metrics["client_aux"]``. ``grad_mask`` (optional 0/1
    tree over y) zeroes the gradient of frozen-for-this-tier leaves each
    local step — exact freezing under SGD-family ClientOpts — and the
    final delta is masked again (belt & braces) so a tiered client's
    upload is structurally zero outside its tier.
    """

    def client_update(y0, frozen, client_batch, grad_mask=None):
        opt_state = client_opt.init(y0)

        def local_step(carry, mb):
            y, st = carry
            def loss_of_y(yy):
                full = part.merge(yy, jax.tree_util.tree_map(
                    jax.lax.stop_gradient, frozen))
                out = loss_fn(full, mb)
                return (out[0], out[1]) if isinstance(out, tuple) else (out, {})
            (loss, aux), grads = jax.value_and_grad(loss_of_y,
                                                    has_aux=True)(y)
            if grad_mask is not None:
                grads = jax.tree_util.tree_map(
                    lambda g, m: g * m.astype(g.dtype), grads, grad_mask)
            y, st = client_opt.update(y, grads, st)
            aux = {k: v for k, v in aux.items() if jnp.ndim(v) == 0}
            return (y, st), (loss, aux)

        (y_fin, _), (losses, auxs) = jax.lax.scan(
            local_step, (y0, opt_state), client_batch)
        delta = opt_lib.tree_sub(y_fin, y0)
        if grad_mask is not None:
            delta = jax.tree_util.tree_map(
                lambda d, m: d * m.astype(d.dtype), delta, grad_mask)
        metrics = {"client_loss": jnp.mean(losses)}
        if auxs:
            metrics["client_aux"] = {k: jnp.mean(v) for k, v in auxs.items()}
        return delta, metrics

    return client_update


def clip_delta(delta, clip_norm: float):
    """Per-client L2 clipping: delta * min(1, C/||delta||).

    Runs over the flat buffer — the fused dp_clip.py kernel on TPU, the
    reshaped kernels/ref.py fallback on CPU — instead of a per-leaf
    tree sweep. Accepts and returns a tree (or a flat fp32 vector, in
    which case no unflatten round-trip is paid)."""
    if isinstance(delta, jnp.ndarray) and delta.ndim == 1:
        layout = None
        vec = delta
    else:
        layout = flat_lib.FlatLayout.of(delta)
        vec = layout.flatten(delta)
    clipped, nrm = flat_lib.clip(vec, clip_norm, layout)
    if layout is None:
        return clipped, nrm
    # match the old tree-path dtype behaviour: leaves keep their dtype
    return layout.unflatten(clipped), nrm


def resolve_server_opt(rc: RoundConfig) -> opt_lib.Optimizer:
    """The ServerOpt a RoundConfig names (shared by the sync round engine
    and the async grid, so the two can't drift)."""
    if rc.server_opt == "sgdm":
        return opt_lib.sgdm(rc.server_lr, rc.server_momentum)
    return opt_lib.get_optimizer(rc.server_opt, rc.server_lr)


def make_round_fn(loss_fn: Callable, rc: RoundConfig,
                  server_opt: Optional[opt_lib.Optimizer] = None,
                  donate: bool = True, constrain_fn: Optional[Callable] = None,
                  constrain_flat_fn: Optional[Callable] = None,
                  constrain_batch_fn: Optional[Callable] = None,
                  plan=None, sanitize=None, fused_threshold=None):
    """Builds round_step(y, server_state, frozen, batch, weights, rng) —
    or, under a non-trivial trainability ``plan``,
    round_step(y, server_state, frozen, batch, weights, tiers, rng).

    batch: pytree, leaves (clients, tau, local_batch, ...).
    weights: (clients,) float — e.g. #examples per client (paper's p_i).
    tiers: (clients,) int32 tier index per cohort slot (plan mode only).
    rng: PRNG key for DP noise (ignored when DP is off).
    constrain_fn(tree, clients: bool): optional sharding-constraint hook
    used on the mesh — pins the per-client trainable copies to the data
    axis so GSPMD never replicates C copies of y per device.
    constrain_flat_fn(arr, clients: bool): same, for the flat delta
    buffer ((C, size) when clients=True, (size,) when False).
    constrain_batch_fn(tree): same, for the cohort input batch — pins
    each leaf's leading (client) axis to the data mesh axes (see
    ``launch/sharding.cohort_constrainer``), so SYNC-mode inputs land
    data-parallel instead of replicated.
    plan: a ``core.plan.CompiledPlan``. Trivial plans (one tier, nothing
    extra frozen) take the exact single-spec path below — bit for bit.
    Non-trivial plans mask each client's gradients with its tier's leaf
    mask every local step (exact freezing under SGD-family ClientOpts),
    so frozen-for-this-tier blocks contribute zero delta; aggregation
    divides per block by the tier-mask-weighted participant sum, so
    those blocks also carry zero *weight*. Under DP the denominator
    stays the fixed ``clients_per_round`` — clipping the masked row
    bounds per-client sensitivity unchanged, so clip norms and sigma
    are tier-independent.

    The aggregation tail (quantize / clip / weighted mean / DP noise)
    runs over ``core.flat.FlatLayout`` buffers: client deltas are
    flattened *inside* the vmapped client step, so each per-client pass
    is one op over (C, size) instead of a tree_map per leaf. With DP
    and quantization off the result is bit-for-bit the old tree path
    (same dot_general over the client axis).

    ``sanitize`` (a ``core.sanitize.SanitizeConfig``) screens the (C,
    size) delta buffer FIRST — before quantization and clipping, since a
    NaN norm would poison the clip weights too: quarantined rows
    (non-finite / norm-outlier) are zeroed with zero weight, the
    quarantine masks land in the returned metrics, and under DP the
    fixed denominator is untouched (sigma stays calibrated). With clean
    data the screened aggregate is bit-identical to ``sanitize=None``.
    """
    client_opt = opt_lib.get_optimizer(rc.client_opt, rc.client_lr)
    if server_opt is None:
        server_opt = resolve_server_opt(rc)
    client_update = make_client_update(loss_fn, client_opt, rc.local_steps)
    tiered = plan is not None and not plan.trivial

    def _round_step(y, server_state, frozen, batch, weights, tiers, rng):
        layout = flat_lib.FlatLayout.of(y)   # static: shapes only
        if constrain_batch_fn is not None:
            batch = constrain_batch_fn(batch)
        if tiered:
            # (n_tiers,) per leaf, indexed by each client's runtime tier
            stacked_masks = jax.tree_util.tree_map(
                lambda *ms: jnp.stack(ms), *plan.leaf_masks())

        def flat_client(y0, cb, tier):
            if tiered:
                mask = jax.tree_util.tree_map(lambda s: s[tier],
                                              stacked_masks)
                delta, metrics = client_update(y0, frozen, cb, mask)
            else:
                delta, metrics = client_update(y0, frozen, cb)
            return layout.flatten(delta), metrics

        # --- local training on every sampled client (vmapped over the
        # client axis; under pjit that axis is sharded over `data`) -----
        with jax.named_scope(SCOPE_CLIENT_STEP):
            if constrain_fn is not None:
                C = weights.shape[0]
                yb = jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a[None], (C,) + a.shape), y)
                yb = constrain_fn(yb, clients=True)
                if tiered:
                    deltas, metrics = jax.vmap(flat_client)(yb, batch,
                                                            tiers)
                else:
                    deltas, metrics = jax.vmap(
                        lambda yc, cb: flat_client(yc, cb, None))(yb, batch)
            elif tiered:
                deltas, metrics = jax.vmap(
                    lambda cb, t: flat_client(y, cb, t))(batch, tiers)
            else:
                deltas, metrics = jax.vmap(
                    lambda cb: flat_client(y, cb, None))(batch)
            if constrain_flat_fn is not None:
                deltas = constrain_flat_fn(deltas, clients=True)

        # --- the whole server tail — quarantine screen, lossy uplink
        # quantize, clip fold, weighted/fixed-denominator mean, output
        # constraint, DP Gaussian noise — as ONE dispatched op
        # (kernels/ops.agg_tail): staged per-op sequence for small
        # buffers (bit-identical to the historical tail), the fused
        # stats/pack/apply sweep above the dispatch threshold. Under DP
        # the denominator is the fixed clients_per_round (sigma is
        # calibrated to sensitivity C/n, so dropped zero-weight
        # participants shrink the numerator, never the denominator) ----
        noised = rc.dp_clip_norm > 0 and rc.dp_noise_multiplier > 0
        sigma = (rc.dp_noise_multiplier * rc.dp_clip_norm
                 / rc.clients_per_round) if noised else 0.0
        with jax.named_scope(SCOPE_AGG_TAIL):
            flat_delta, ainfo = kernel_ops.agg_tail(
                deltas, weights,
                block_leaf=layout.block_leaf(),
                n_leaves=len(layout.sizes),
                align=layout.align,
                bits=rc.uplink_bits or 0,
                clip_norm=rc.dp_clip_norm if rc.dp_clip_norm > 0 else 0.0,
                # uniform among *participants*: zero weights mark clients
                # the grid scheduler dropped and must stay excluded even
                # under DP's fixed weighting
                uniform=bool(rc.uniform_weights or rc.dp_clip_norm > 0),
                wsum_fixed=(float(rc.clients_per_round)
                            if rc.dp_clip_norm > 0 else None),
                sigma=sigma, rng=rng if noised else None,
                # per-block mask-weighted mean for tiers (blocks a tier
                # froze carry zero weight for its clients); under DP/clip
                # the mean keeps the fixed denominator instead
                bmask=(jnp.asarray(plan.block_masks())[tiers]
                       if tiered and rc.dp_clip_norm <= 0 else None),
                block_denom=tiered and rc.dp_clip_norm <= 0,
                screen=sanitize,
                constrain_fn=(None if constrain_flat_fn is None else
                              lambda v: constrain_flat_fn(v, clients=False)),
                threshold=fused_threshold)

        # --- ServerOpt on the pseudo-gradient ---------------------------
        with jax.named_scope(SCOPE_SERVER_APPLY):
            delta = layout.unflatten(flat_delta, dtype=jnp.float32)
            neg = jax.tree_util.tree_map(lambda d: -d, delta)
            y_new, server_state = server_opt.update(y, neg, server_state)
            out_metrics = {"loss": jnp.mean(metrics["client_loss"]),
                           "delta_norm": opt_lib.tree_global_norm(delta)
                           if noised else jnp.sqrt(
                               flat_lib.sumsq(flat_delta, layout.align))}
        if "client_aux" in metrics:
            # the loss's own counters, each the cohort's mean
            out_metrics["client_aux"] = {
                k: jnp.mean(v) for k, v in metrics["client_aux"].items()}
        if "update_norms" in ainfo:
            out_metrics["update_norm"] = jnp.mean(ainfo["update_norms"])
        if sanitize is not None:
            out_metrics["quarantine_nonfinite"] = ainfo["nonfinite"]
            out_metrics["quarantine_outlier"] = ainfo["outlier"]
            out_metrics["quarantine_norms"] = ainfo["norms"]
        return y_new, server_state, out_metrics

    if tiered:
        round_step = _round_step     # (y, sstate, frozen, batch, w, tiers, rng)
    else:
        def round_step(y, server_state, frozen, batch, weights, rng):
            return _round_step(y, server_state, frozen, batch, weights,
                               None, rng)

    return round_step, server_opt


# ---------------------------------------------------------------------------
# Asynchronous (buffered) aggregation hooks — used by repro/sim/scheduler.py.
#
# FedBuff-style servers weight each buffered client delta by a function of
# its *staleness* s = (server version now) - (server version the client
# downloaded). The weighting is pluggable; the named defaults follow
# Nguyen et al. 2022 (polynomial, a=0.5) and Xie et al. 2019 (hinge).


def staleness_constant():
    """No down-weighting (plain buffered FedAvg)."""
    return lambda s: 1.0


def staleness_polynomial(power: float = 0.5):
    """w(s) = (1+s)^-a; a=0.5 is FedBuff's 1/sqrt(1+s)."""
    return lambda s: (1.0 + float(s)) ** (-power)


def staleness_hinge(delay: float = 4.0, slope: float = 0.5):
    """w(s) = 1 while s <= delay, then 1/(slope*(s-delay)+1)."""
    def fn(s):
        s = float(s)
        return 1.0 if s <= delay else 1.0 / (slope * (s - delay) + 1.0)
    return fn


STALENESS_FNS = {
    "constant": staleness_constant,
    "polynomial": staleness_polynomial,
    "hinge": staleness_hinge,
}


def get_staleness_fn(name="polynomial", **kw) -> Callable[[float], float]:
    """Resolve a staleness weighting: a callable passes through, a name
    looks up STALENESS_FNS (kw forwarded to the factory)."""
    if callable(name):
        return name
    try:
        return STALENESS_FNS[name](**kw)
    except KeyError:
        raise ValueError(f"unknown staleness_fn {name!r}; "
                         f"options: {sorted(STALENESS_FNS)}") from None


def make_client_step(loss_fn: Callable, rc: RoundConfig,
                     client_opt: Optional[opt_lib.Optimizer] = None,
                     tier=None, plan=None, scatter: bool = True):
    """Single-client step for the async grid: (y, frozen, client_batch) ->
    (flat_delta, metrics). The delta is born flat — flattened inside the
    jitted step onto the ``FlatLayout`` of ``y`` — and the same uplink
    quantization and DP clipping as the synchronous round engine are
    applied over the flat buffer, in the same order.

    ``tier`` (a ``core.plan.TierSlice``, with its ``plan`` the owning
    ``CompiledPlan``) builds the step for ONE trainability tier: ``y``
    is split structurally — the tier's extra-frozen leaves join the
    frozen side, so XLA allocates no grad buffers or optimizer state
    for them — and the delta is the tier's *contiguous* ``(tier_size,)``
    flat slice. Quantization scales and the DP clip norm computed on
    the slice equal those of the zero-scattered full row (absent blocks
    are exactly zero), so per-client DP sensitivity is unchanged by
    tiering. With ``scatter=True`` the step returns the slice scattered
    to global ``(size,)`` width; ``scatter=False`` returns the raw
    contiguous slice (the wire payload)."""
    if client_opt is None:
        client_opt = opt_lib.get_optimizer(rc.client_opt, rc.client_lr)
    client_update = make_client_update(loss_fn, client_opt, rc.local_steps)
    if tier is not None and plan is None:
        raise ValueError("a tiered client step needs the owning "
                         "CompiledPlan (plan=...)")

    @jax.named_scope(SCOPE_CLIENT_STEP)
    def client_step(y, frozen, client_batch):
        if tier is None:
            layout = flat_lib.FlatLayout.of(y)
            delta, metrics = client_update(y, frozen, client_batch)
            flat_delta = layout.flatten(delta)
        else:
            y_t, extra = plan.split(y, tier)
            layout = flat_lib.FlatLayout.of(y_t)
            delta, metrics = client_update(y_t, part.merge(frozen, extra),
                                           client_batch)
            flat_delta = layout.flatten(delta)
        if rc.uplink_bits:
            flat_delta = flat_lib.fake_quantize(flat_delta, layout,
                                                rc.uplink_bits)
        if rc.dp_clip_norm > 0:
            flat_delta, nrm = flat_lib.clip(flat_delta, rc.dp_clip_norm,
                                            layout)
            metrics = dict(metrics, update_norm=nrm)
        if tier is not None and scatter:
            flat_delta = plan.scatter(flat_delta, tier)
        return flat_delta, metrics

    return client_step


def make_lane_step(loss_fn: Callable, rc: RoundConfig, lane: int,
                   client_opt: Optional[opt_lib.Optimizer] = None,
                   constrain_flat_fn: Optional[Callable] = None,
                   tier=None, plan=None):
    """Batched client step for the async grid's fixed-width lanes:
    (y, frozen, lane_batch) -> (flat_deltas (lane, size), losses (lane,)).

    One vmapped dispatch replaces `lane` sequential jit calls; under a
    launch/sharding.py mesh, pass ``constrain_flat_fn`` to pin the lane
    axis to the data mesh axes so clients execute data-parallel.

    With a ``tier``/``plan`` pair the lane is tier-homogeneous (the grid
    groups pending clients by tier, so each tier traces exactly once):
    the vmapped steps run at the tier's ``(lane, tier_size)`` width —
    grad buffers and the clip/quantize tail all shrink with the tier —
    and ONE static-index scatter widens the batch to the global
    ``(lane, size)`` buffer before the sharding constraint, so
    frozen-for-this-tier blocks enter the aggregation as exact zeros.
    """
    step = make_client_step(loss_fn, rc, client_opt, tier=tier, plan=plan,
                            scatter=False)

    def lane_step(y, frozen, lane_batch):
        flat_deltas, metrics = jax.vmap(
            lambda cb: step(y, frozen, cb))(lane_batch)
        # ``step`` carries the scope itself; this covers the widening
        with jax.named_scope(SCOPE_CLIENT_STEP):
            if tier is not None:
                flat_deltas = plan.scatter(flat_deltas, tier)
            if constrain_flat_fn is not None:
                flat_deltas = constrain_flat_fn(flat_deltas, clients=True)
        return flat_deltas, metrics["client_loss"]

    return lane_step


def make_buffered_apply(server_opt: opt_lib.Optimizer,
                        flush_dp=None,
                        constrain_flat_fn: Optional[Callable] = None,
                        plan=None, sanitize=None, fused_threshold=None):
    """Server-side flush of an async buffer: apply(y, server_state,
    flat_deltas, weights[, rng]) with ``flat_deltas`` the (K, size) stack
    of flat client deltas and weights (K,) already including the
    staleness factor (w_i = staleness_fn(s_i) * p_i). Weighted-mean as
    one dot, then ServerOpt on the pseudo-gradient, mirroring the sync
    engine.

    ``plan`` (a non-trivial ``core.plan.CompiledPlan``) switches to the
    tiered signature apply(y, server_state, flat_deltas, weights,
    tier_ids[, rng]): ``tier_ids`` (K,) int32 names each row's tier, and
    the per-row tier block masks make frozen-for-this-tier blocks
    contribute zero delta (rows are re-masked, belt & braces — tiered
    client steps already scatter exact zeros there) and zero *weight*:
    without DP the mean divides per block by the mask-weighted
    participant sum (blocks nobody trained keep delta 0); with
    ``flush_dp`` the denominator stays the FIXED ``goal_count`` — the
    masked, clipped row still has sensitivity ``clip_norm/goal_count``,
    so sigma is tier-independent. Padding rows carry weight 0 and tier 0;
    both denominators ignore them.

    K is a fixed shape: short buffers (e.g. a drained final flush) are
    padded with zero-weight rows by the caller, which fall out of the
    weighted mean — so partial flushes never re-trace.

    ``flush_dp`` (a :class:`repro.core.dp.FlushDPConfig`) turns on
    per-flush DP: the mean uses the FIXED ``goal_count`` denominator —
    sigma is calibrated once per flush and zero-weight padding rows of a
    drained buffer change neither the denominator nor the noise scale —
    and ``rng`` (one key per flush) drives ONE Gaussian draw over the
    flat buffer. Client deltas must arrive clipped (``make_client_step``
    does this when ``rc.dp_clip_norm > 0``) with staleness weights
    <= 1, so per-flush sensitivity is ``clip_norm / goal_count``.

    ``constrain_flat_fn`` (see ``launch/sharding.flat_constrainer``)
    pins the buffer's K axis to the data mesh axes and its size axis to
    "model": the weighted mean then reduces the sharded buffer in place
    (a cross-data-axis collective) — the K rows are never gathered onto
    one device.

    ``sanitize`` (a ``core.sanitize.SanitizeConfig``) screens the (K,
    size) buffer FIRST: quarantined rows (non-finite / norm-outlier) are
    zeroed with zero weight — under ``flush_dp`` the FIXED goal_count
    denominator is untouched, so a quarantined row degrades to exactly a
    padding row and sigma / the epsilon ledger stay valid. The
    quarantine masks ride back on the metrics dict for the grid to turn
    into traced events. Clean buffers aggregate bit-identically to
    ``sanitize=None``.
    """

    tiered = plan is not None and not plan.trivial

    def _apply(y, server_state, flat_deltas, weights, tier_ids, rng):
        layout = flat_lib.FlatLayout.of(y)
        if constrain_flat_fn is not None:
            flat_deltas = constrain_flat_fn(flat_deltas, clients=True)
        noised = flush_dp is not None and flush_dp.noise_multiplier > 0
        if noised and rng is None:
            raise ValueError("flush DP noise needs a per-flush rng key")
        # screen -> tier row re-mask -> mean (fixed goal_count
        # denominator under flush DP, per-block mask-weighted otherwise
        # for tiers) -> constraint -> per-flush Gaussian, as ONE
        # dispatched op — staged per-op sequence below the threshold,
        # fused stats/apply sweep above it
        with jax.named_scope(SCOPE_AGG_TAIL):
            flat_delta, ainfo = kernel_ops.agg_tail(
                flat_deltas, weights,
                block_leaf=layout.block_leaf(),
                n_leaves=len(layout.sizes),
                align=layout.align,
                wsum_fixed=(float(flush_dp.goal_count)
                            if flush_dp is not None else None),
                sigma=flush_dp.sigma if noised else 0.0,
                rng=rng if noised else None,
                bmask=(jnp.asarray(plan.block_masks())[tier_ids]
                       if tiered else None),
                remask_rows=tiered,
                block_denom=tiered and flush_dp is None,
                screen=sanitize,
                constrain_fn=(None if constrain_flat_fn is None else
                              lambda v: constrain_flat_fn(v, clients=False)),
                threshold=fused_threshold)
        qinfo = ainfo if sanitize is not None else None
        with jax.named_scope(SCOPE_SERVER_APPLY):
            delta = layout.unflatten(flat_delta, dtype=jnp.float32)
            neg = jax.tree_util.tree_map(lambda d: -d, delta)
            y_new, server_state = server_opt.update(y, neg, server_state)
            # with noise on pad slots, the flat vector's norm overstates
            # the model update — report the unflattened norm instead
            # (sync engine does the same)
            norm = (opt_lib.tree_global_norm(delta) if noised
                    else jnp.sqrt(flat_lib.sumsq(flat_delta, layout.align)))
        out = {"delta_norm": norm}
        if qinfo is not None:
            out["quarantine_nonfinite"] = qinfo["nonfinite"]
            out["quarantine_outlier"] = qinfo["outlier"]
            out["quarantine_norms"] = qinfo["norms"]
        return y_new, server_state, out

    if tiered:
        def apply_fn(y, server_state, flat_deltas, weights, tier_ids,
                     rng=None):
            return _apply(y, server_state, flat_deltas, weights,
                          jnp.asarray(tier_ids, jnp.int32), rng)
    else:
        def apply_fn(y, server_state, flat_deltas, weights, rng=None):
            return _apply(y, server_state, flat_deltas, weights, None, rng)

    return apply_fn


def make_eval_fn(loss_fn: Callable):
    """Centralized eval of the merged model."""

    def eval_step(y, frozen, batch):
        out = loss_fn(part.merge(y, frozen), batch)
        return out[0] if isinstance(out, tuple) else out

    return jax.jit(eval_step)
