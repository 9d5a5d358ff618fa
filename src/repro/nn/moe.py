"""Mixture-of-Experts: top-k router + capacity-bounded sort-based dispatch.

TPU-native design: tokens are routed with a sort (XLA sort lowers well on
TPU), scattered into a dense (experts, capacity, d_model) buffer, expert
FFNs run as one batched einsum whose expert dimension is sharded over the
`model` mesh axis (expert parallelism — GSPMD inserts the all-to-all when
resharding token-sharded activations to expert-sharded buffers), and
combined back with the router weights. Overflowing tokens beyond capacity
are dropped (standard Switch/GShard semantics).

Shared experts (DeepSeek-V2) run densely on every token.

With ``cfg.moe_capacity_factor == 0`` the layer drops nothing
(``moe_ffn_share``): it holds experts ``[expert_offset, expert_offset +
experts_held)`` of ``num_experts`` (all by default), routes every token
over all of them, and returns its held experts' part of the result plus
the shared experts: the slots routed to held experts go through
``kernels/ops.moe_held_ffn`` (one grouped matmul per SwiGLU projection,
the slots sorted by expert), and the slots routed elsewhere contribute
nothing here (on a chip of an expert-parallel deployment, the chips
holding those experts add them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.nn import basic
from repro.configs.base import ModelConfig

# ``jax.named_scope`` names of the no-drop layer's stages (HLO metadata
# only; a device trace puts each operation's time on the innermost one)
SCOPE_ROUTE = "fedpt/moe_route"
SCOPE_HELD = "fedpt/moe_held_ffn"
SCOPE_SHARED = "fedpt/moe_shared"


def _maybe_constrain(x, spec):
    return basic.maybe_constrain(x, spec)


def init_moe(seed, path, cfg: ModelConfig, dtype):
    """The router scores all ``num_experts``; the expert stacks hold the
    ``held_experts`` this layer computes (all of them by default)."""
    d, e = cfg.d_model, cfg.num_experts
    h = cfg.held_experts
    ff = cfg.expert_d_ff
    p = {
        "router": basic.init_dense(seed, f"{path}/router", d, e, dtype),
        # stacked expert weights: (E, d, ff) / (E, ff, d)
        "wi_gate": basic.normal_init(seed, f"{path}/wi_gate", (h, d, ff), dtype, fan_in=d),
        "wi_up": basic.normal_init(seed, f"{path}/wi_up", (h, d, ff), dtype, fan_in=d),
        "wo": basic.normal_init(seed, f"{path}/wo", (h, ff, d), dtype, fan_in=ff),
    }
    if cfg.num_shared_experts > 0:
        sff = cfg.expert_d_ff * cfg.num_shared_experts
        p["shared"] = basic.init_mlp(seed, f"{path}/shared", d, sff, dtype,
                                     gated=True)
    return p


def router_topk(x, p, cfg: ModelConfig):
    """Returns (weights (T,k), experts (T,k) int32, aux_loss scalar)."""
    logits = basic.dense(x, p["router"], jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    w = _topk_weights(w, cfg)
    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    e = cfg.num_experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0)
    aux = e * jnp.sum(me * ce)
    return w.astype(x.dtype), idx.astype(jnp.int32), aux


def _topk_weights(w, cfg: ModelConfig):
    """Renormalised to sum 1 (``norm_topk_prob``), else scaled by
    ``routed_scaling_factor``."""
    if cfg.norm_topk_prob:
        return w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        return w * cfg.routed_scaling_factor
    return w


def _sort_dispatch(x, w, idx, e: int, cap: int, cd):
    """Sort-based dispatch of (T, d) tokens into an (E, cap, d) buffer.
    Returns (buf, combine_meta) where combine_meta = (st, sw, keep, slot)."""
    T, d = x.shape
    k = idx.shape[1]
    flat_e = idx.reshape(-1)                        # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    flat_w = w.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    # position of each routed token within its expert's buffer
    starts = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype))
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos < cap
    slot = jnp.where(keep, se.astype(jnp.int32) * cap + pos, e * cap)
    buf = jnp.zeros((e * cap + 1, d), cd)
    buf = buf.at[slot].set(x[st].astype(cd), mode="drop")
    return buf[: e * cap].reshape(e, cap, d), (st, sw, keep, slot)


def _combine_local(y_flat, meta, T: int, e: int, cap: int, cd):
    """Inverse of _sort_dispatch: weighted scatter back into (T, d)."""
    st, sw, keep, slot = meta
    d = y_flat.shape[-1]
    gathered = jnp.where(keep[:, None],
                         y_flat[jnp.clip(slot, 0, e * cap - 1)], 0.0)
    gathered = gathered * sw[:, None].astype(cd)
    return jnp.zeros((T, d), cd).at[st].add(gathered)


def moe_ffn(x, p, cfg: ModelConfig):
    """x: (T, d) flat tokens -> (T, d), plus aux loss.

    Sort-based dispatch with capacity = ceil(T*k/E * capacity_factor).
    With cfg.moe_dispatch_groups > 1 the sort/scatter runs group-LOCALLY
    (groups sharded over the data axis) so no global argsort / scatter
    collectives are emitted — only the expert-parallel all-to-all.
    """
    T, d = x.shape
    assert cfg.moe_capacity_factor > 0 and cfg.held_experts == \
        cfg.num_experts, "capacity dispatch holds every expert"
    g = cfg.moe_dispatch_groups
    if g and g > 1 and T % g == 0 and T // g >= cfg.num_experts_per_tok:
        return _moe_ffn_grouped(x, p, cfg, g)
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    cap = int(max(1, round(T * k / e * cfg.moe_capacity_factor)))
    cd = cfg.cdtype

    w, idx, aux = router_topk(x, p, cfg)  # (T,k)

    # scatter tokens into (E*C+1, d); last row is the drop bucket.
    # Expert-dim sharding axis mirrors launch/sharding.py: "data" for huge
    # banks (2-D expert sharding), "model" when divisible, else intra-
    # expert TP (shard the FFN dim only).
    if cfg.num_experts >= 64:
        expert_axis, ff_axis = "data", "model"
    else:
        expert_axis, ff_axis = "model", None
    buf, meta = _sort_dispatch(x, w, idx, e, cap, cd)
    buf = _maybe_constrain(buf, (expert_axis, None, None))

    # expert FFN: batched over the (sharded) expert dim — this reshard is
    # the expert-parallel all-to-all under GSPMD
    g = jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"].astype(cd))
    u = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"].astype(cd))
    h = jax.nn.silu(g) * u
    h = _maybe_constrain(h, (expert_axis, None, ff_axis))
    y = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(cd))
    y = _maybe_constrain(y, (expert_axis, None, None))

    # combine: gather back to (T*k, d), weight, segment-sum into tokens
    out = _combine_local(y.reshape(e * cap, d), meta, T, e, cap, cd)

    if cfg.num_shared_experts > 0:
        out = out + basic.mlp(x, p["shared"], "silu", cd)
    return out, aux


def _moe_ffn_grouped(x, p, cfg: ModelConfig, g: int):
    """Group-local dispatch (perf variant, DESIGN.md §Perf/H1).

    Tokens reshape to (g, T/g, d) with the group dim pinned to the data
    axis; routing, sort, scatter and combine are all group-local (no
    cross-group collectives). Only the batched expert einsum crosses the
    mesh — the canonical expert-parallel all-to-all.
    """
    T, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    cd = cfg.cdtype
    Tl = T // g
    cap = int(max(1, round(Tl * k / e * cfg.moe_capacity_factor)))

    xg = _maybe_constrain(x.reshape(g, Tl, d), ("data", None, None))

    def local(xl):
        w, idx, aux = router_topk(xl, p, cfg)
        buf, meta = _sort_dispatch(xl, w, idx, e, cap, cd)
        return buf, meta, aux

    bufs, metas, auxs = jax.vmap(local)(xg)          # (g, E, cap, d)
    # Iteration 2 (EXPERIMENTS.md §Perf/H1): keep group dim on "data" AND
    # expert dim on "model" through the expert einsums — the per-shard
    # expert weights (O(100MB)) gather across their secondary axis instead
    # of the O(10GB) token buffers.
    e_ax = "model"
    bufs = _maybe_constrain(bufs, ("data", e_ax, None, None))

    gg = jnp.einsum("gecd,edf->gecf", bufs, p["wi_gate"].astype(cd))
    uu = jnp.einsum("gecd,edf->gecf", bufs, p["wi_up"].astype(cd))
    h = jax.nn.silu(gg) * uu
    h = _maybe_constrain(h, ("data", e_ax, None, None))
    y = jnp.einsum("gecf,efd->gecd", h, p["wo"].astype(cd))
    y = _maybe_constrain(y, ("data", None, None, None))

    out = jax.vmap(
        lambda yl, st, sw, keep, slot: _combine_local(
            yl.reshape(e * cap, d), (st, sw, keep, slot), Tl, e, cap, cd)
    )(y, *metas)
    out = out.reshape(T, d)
    if cfg.num_shared_experts > 0:
        out = out + basic.mlp(x, p["shared"], "silu", cd)
    return out, jnp.mean(auxs)


def moe_ffn_share(x, p, cfg: ModelConfig, seqs: int = 1):
    """The no-drop layer over this chip's held experts (module doc).

    x: (T, d), the tokens of ``seqs`` sequences of equal length. Returns
    (out (T, d), aux, counters): ``aux`` the sequence-wise balance loss
    over all ``num_experts`` (DeepSeek-V2's ``seq_aux``: per sequence,
    sum_e f_e P_e with f_e the share of its top-k slots on expert e times
    E / k and P_e its mean router probability; mean over sequences);
    ``counters`` the slots routed to held experts (``moe_routed_held``)
    and the largest held expert's slot count over the held experts' mean
    (``moe_load_max_over_mean``; 0 when none is routed here)."""
    T = x.shape[0]
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    held, off = cfg.held_experts, cfg.expert_offset
    cd = cfg.cdtype
    with jax.named_scope(SCOPE_ROUTE):
        logits = basic.dense(x, p["router"], jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, k)
        w = _topk_weights(w, cfg)
        per_seq = (seqs, T // max(seqs, 1), e)
        counts = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32),
                         axis=1).reshape(per_seq)
        f = jnp.mean(counts, axis=1) * (e / k)
        aux = jnp.mean(jnp.sum(
            f * jnp.mean(probs.reshape(per_seq), axis=1), -1))
        local = idx - off
        sizes = jnp.sum(jax.nn.one_hot(local, held, dtype=jnp.int32),
                        axis=(0, 1))
    with jax.named_scope(SCOPE_HELD):
        out = kernel_ops.moe_held_ffn(
            x.astype(cd), local, w, p["wi_gate"].astype(cd),
            p["wi_up"].astype(cd), p["wo"].astype(cd)).astype(cd)
    if cfg.num_shared_experts > 0:
        with jax.named_scope(SCOPE_SHARED):
            out = out + basic.mlp(x, p["shared"], "silu", cd)
    routed = jnp.sum(sizes)
    counters = {
        "moe_routed_held": routed.astype(jnp.float32),
        "moe_load_max_over_mean": jnp.where(
            routed > 0, jnp.max(sizes) * held / jnp.maximum(routed, 1),
            0.0).astype(jnp.float32)}
    return out, aux, counters


def moe_ffn_dense_fallback(x, p, cfg: ModelConfig):
    """Reference: run every expert on every token and mask (oracle for tests)."""
    T, d = x.shape
    cd = jnp.float32
    w, idx, aux = router_topk(x, p, cfg)
    g = jnp.einsum("td,edf->tef", x.astype(cd), p["wi_gate"].astype(cd))
    u = jnp.einsum("td,edf->tef", x.astype(cd), p["wi_up"].astype(cd))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("tef,efd->ted", h, p["wo"].astype(cd))
    mask = jnp.zeros((T, cfg.num_experts), cd)
    mask = mask.at[jnp.arange(T)[:, None], idx].add(w.astype(cd))
    out = jnp.einsum("ted,te->td", y, mask)
    if cfg.num_shared_experts > 0:
        out = out + basic.mlp(x.astype(cd), p["shared"], "silu", cd)
    return out.astype(x.dtype), aux
