"""Composable decoder-only LM covering the dense / MoE / hybrid / SSM /
VLM families of the assigned architectures.

Layer stacks are built as a *periodic program*: the layer sequence is
grouped into `num_layers / period` identical groups, each containing
`period` slots of fixed kind (attention / Mamba / mLSTM / sLSTM, with a
dense or MoE FFN). The stack is executed with `lax.scan` over groups —
this keeps the HLO (and CPU compile time for 512-device dry-runs) bounded
for 60-layer models, and the roofline accounting multiplies scan-body
costs by the trip count.

Leading layers unlike the period (``cfg.first_k_dense``: DeepSeek's
dense layer 0) run unscanned before the stack, each an attention block
with a dense FFN.

Parameter layout (nested dicts; ``G`` = scanned groups):

* ``embed/embedding`` (V, d); ``final_norm``; ``unembed/kernel`` (d, V)
  unless tied;
* ``lead/layer{i}`` for i < first_k_dense: one layer's ``ln1``,
  ``attn``, ``ln2``, ``ffn``, unstacked;
* ``layers/slot{j}``: slot j of every group, each leaf stacked on a
  leading (G,) axis: ``ln1``, ``attn`` (or ``mamba`` / ``mlstm`` /
  ``slstm``), ``ln2`` and ``ffn`` or ``moe`` (``router``, the held
  experts' ``wi_gate``/``wi_up`` (E_held, d, ff) and ``wo`` (E_held, ff,
  d), ``shared``).

With ``cfg.remat`` every layer (a lead layer, a scanned group) keeps only
its input for the backward pass and recomputes the rest.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ATTN, MAMBA, MLSTM, SLSTM
from repro.nn import attention as attn_lib
from repro.nn import basic, moe as moe_lib, ssm as ssm_lib


# ---------------------------------------------------------------------------
# Layer program


class Slot(NamedTuple):
    kind: str       # attn | mamba | mlstm | slstm
    use_moe: bool
    cross_attn: bool = False


def layer_program(cfg: ModelConfig) -> Tuple[Tuple[Slot, ...], int]:
    """Returns (slots-per-group, n_groups) of the scanned stack, which
    starts after the ``first_k_dense`` leading layers."""
    kinds = cfg.block_kinds()
    lead = cfg.first_k_dense
    period = 1
    if cfg.family == "hybrid" and cfg.attn_period:
        period = cfg.attn_period
    if cfg.family == "ssm" and cfg.slstm_every:
        period = cfg.slstm_every
    if cfg.num_experts > 0 and cfg.moe_period > 1:
        period = math.lcm(period, cfg.moe_period)
    n = cfg.num_layers - lead
    assert n % period == 0, (cfg.name, n, period)
    slots = tuple(
        Slot(kind=kinds[lead + i], use_moe=cfg.layer_uses_moe(lead + i))
        for i in range(period))
    return slots, n // period


def lead_slots(cfg: ModelConfig) -> Tuple[Slot, ...]:
    """The leading dense layers, in order."""
    kinds = cfg.block_kinds()
    return tuple(Slot(kind=kinds[i], use_moe=False)
                 for i in range(cfg.first_k_dense))


# ---------------------------------------------------------------------------
# Init


def _init_slot(key, cfg: ModelConfig, slot: Slot, si: int, decoder_cross: bool):
    dt = cfg.pdtype
    path = f"layers/slot{si}"
    p: Dict[str, Any] = {"ln1": basic.init_norm(key, f"{path}/ln1", cfg.d_model,
                                                dt, cfg.norm_type)}
    if slot.kind == ATTN:
        if cfg.use_mla:
            p["attn"] = attn_lib.init_mla(key, f"{path}/attn", cfg, dt)
        else:
            p["attn"] = attn_lib.init_attention(key, f"{path}/attn", cfg, dt)
    elif slot.kind == MAMBA:
        p["mamba"] = ssm_lib.init_mamba(key, f"{path}/mamba", cfg, dt)
    elif slot.kind == MLSTM:
        p["mlstm"] = ssm_lib.init_mlstm(key, f"{path}/mlstm", cfg, dt)
    elif slot.kind == SLSTM:
        p["slstm"] = ssm_lib.init_slstm(key, f"{path}/slstm", cfg, dt)
    if decoder_cross and slot.kind == ATTN:
        p["ln_cross"] = basic.init_norm(key, f"{path}/ln_cross", cfg.d_model,
                                        dt, cfg.norm_type)
        p["cross_attn"] = attn_lib.init_attention(key, f"{path}/cross_attn",
                                                  cfg, dt)
    if slot.kind in (ATTN, MAMBA):  # blocks with a separate FFN
        p["ln2"] = basic.init_norm(key, f"{path}/ln2", cfg.d_model, dt,
                                   cfg.norm_type)
        if slot.use_moe:
            p["moe"] = moe_lib.init_moe(key, f"{path}/moe", cfg, dt)
        else:
            p["ffn"] = basic.init_mlp(key, f"{path}/ffn", cfg.d_model, cfg.d_ff,
                                      dt, gated=cfg.gated_mlp)
    return p


def _init_stack(seed, cfg: ModelConfig, decoder_cross: bool = False):
    slots, n_groups = layer_program(cfg)
    root = basic.path_key(seed, f"{cfg.name}/stack" + ("/dec" if decoder_cross else ""))
    keys = jax.vmap(lambda g: jax.random.fold_in(root, g))(jnp.arange(n_groups))
    stacked = {}
    for si, slot in enumerate(slots):
        stacked[f"slot{si}"] = jax.vmap(
            lambda k, si=si, slot=slot: _init_slot(k, cfg, slot, si,
                                                   decoder_cross))(keys)
    return stacked


def init_model(cfg: ModelConfig, seed: int) -> Dict[str, Any]:
    dt = cfg.pdtype
    p: Dict[str, Any] = {
        "embed": basic.init_embedding(seed, "embed", cfg.vocab_size,
                                      cfg.d_model, dt),
        "final_norm": basic.init_norm(seed, "final_norm", cfg.d_model, dt,
                                      cfg.norm_type),
        "layers": _init_stack(seed, cfg),
    }
    if cfg.first_k_dense:
        p["lead"] = {
            f"layer{i}": _init_slot(
                basic.path_key(seed, f"{cfg.name}/lead{i}"), cfg, slot, i,
                False)
            for i, slot in enumerate(lead_slots(cfg))}
    if not cfg.tie_embeddings:
        p["unembed"] = {"kernel": basic.normal_init(
            seed, "unembed/kernel", (cfg.d_model, cfg.vocab_size), dt,
            fan_in=cfg.d_model)}
    if cfg.family == "vlm":
        # projector from the (stubbed) vision tower dim to d_model
        p["mm_proj"] = basic.init_dense(seed, "mm_proj", 1152, cfg.d_model, dt,
                                        bias=True)
    if cfg.is_encoder_decoder:
        p["enc_layers"] = _init_stack(seed, cfg.with_(
            num_layers=cfg.encoder_layers or cfg.num_layers,
            sliding_window=0), decoder_cross=False)
        p["enc_norm"] = basic.init_norm(seed, "enc_norm", cfg.d_model, dt,
                                        cfg.norm_type)
        # decoder stack gets cross-attention
        p["layers"] = _init_stack(seed, cfg, decoder_cross=True)
    return p


# ---------------------------------------------------------------------------
# Forward (training)


def sinusoid_pos(positions, d_model, dtype):
    """Classic sinusoidal position embedding: positions (..., S) -> (..., S, d)."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ``jax.named_scope`` names (HLO metadata only) of the stages of the
# MLA/MoE decoder that a device trace puts time on; the no-drop MoE
# layer's own are in nn/moe.py
SCOPE_MLA = "fedpt/mla"
SCOPE_LEAD = "fedpt/lead_dense"
SCOPE_HEAD = "fedpt/head"


def _stats0(cfg: ModelConfig) -> Dict[str, Any]:
    """The per-forward statistics the layers accumulate: the router aux
    loss (summed over MoE layers) and, for the no-drop MoE layer, the
    slots routed to held experts (summed) and the held experts' largest
    load over their mean (the largest over layers)."""
    stats = {"moe_aux_loss": jnp.zeros((), jnp.float32)}
    if no_drop_moe(cfg):
        stats["moe_routed_held"] = jnp.zeros((), jnp.float32)
        stats["moe_load_max_over_mean"] = jnp.zeros((), jnp.float32)
    return stats


def no_drop_moe(cfg: ModelConfig) -> bool:
    return cfg.num_experts > 0 and cfg.moe_capacity_factor == 0


def _moe(h2, mp, cfg: ModelConfig, stats):
    """The MoE FFN of (B, S, d) tokens, with ``stats`` updated."""
    B, S, D = h2.shape
    flat = h2.reshape(B * S, D)
    if no_drop_moe(cfg):
        y, aux_l, counts = moe_lib.moe_ffn_share(flat, mp, cfg, seqs=B)
        stats = dict(
            stats, moe_routed_held=stats["moe_routed_held"]
            + counts["moe_routed_held"],
            moe_load_max_over_mean=jnp.maximum(
                stats["moe_load_max_over_mean"],
                counts["moe_load_max_over_mean"]))
    else:
        y, aux_l = moe_lib.moe_ffn(flat, mp, cfg)
    stats = dict(stats, moe_aux_loss=stats["moe_aux_loss"] + aux_l)
    return y.reshape(B, S, D), stats


def _apply_slot(x, sp, cfg: ModelConfig, slot: Slot, positions, stats,
                encoder_out=None, prefix_len=0, causal=True):
    """One residual block. Returns (x, stats)."""
    cd = cfg.cdtype
    h = basic.apply_norm(x, sp["ln1"], cfg.norm_type)
    if slot.kind == ATTN:
        if cfg.use_mla:
            with jax.named_scope(SCOPE_MLA):
                q, k, v = attn_lib.mla_qkv(h, sp["attn"], cfg, positions)
                o = attn_lib.flash_attention(
                    q, k, v, cfg.with_(sliding_window=0), causal=causal,
                    prefix_len=prefix_len,
                    scale=attn_lib.mla_softmax_scale(cfg))
                o = o.reshape(o.shape[:2] + (o.shape[2] * o.shape[3],))
                o = basic.dense(o, sp["attn"]["wo"], cd)
        else:
            q, k, v = attn_lib.qkv_project(h, sp["attn"], cfg)
            if cfg.use_rope:
                cos, sin = attn_lib.rope_freqs(cfg.resolved_head_dim,
                                               cfg.rope_theta, positions,
                                               cfg.rope_scaling)
                q = attn_lib.apply_rope(q, cos, sin)
                k = attn_lib.apply_rope(k, cos, sin)
            o = attn_lib.flash_attention(q, k, v, cfg, causal=causal,
                                         prefix_len=prefix_len)
            o = o.reshape(o.shape[0], o.shape[1], -1)
            o = basic.dense(o, sp["attn"]["wo"], cd)
        x = x + o
        if "cross_attn" in sp and encoder_out is not None:
            hc = basic.apply_norm(x, sp["ln_cross"], cfg.norm_type)
            qc, _, _ = attn_lib.qkv_project(hc, sp["cross_attn"], cfg)
            _, kc, vc = attn_lib.qkv_project(encoder_out, sp["cross_attn"], cfg)
            oc = attn_lib.flash_attention(
                qc, kc, vc, cfg.with_(sliding_window=0), causal=False)
            oc = oc.reshape(oc.shape[0], oc.shape[1], -1)
            x = x + basic.dense(oc, sp["cross_attn"]["wo"], cd)
    elif slot.kind == MAMBA:
        o, _ = ssm_lib.mamba_forward(h, sp["mamba"], cfg)
        x = x + o
    elif slot.kind == MLSTM:
        o, _ = ssm_lib.mlstm_forward(h, sp["mlstm"], cfg)
        return x + o, stats
    elif slot.kind == SLSTM:
        o, _ = ssm_lib.slstm_forward(h, sp["slstm"], cfg)
        return x + o, stats

    h2 = basic.apply_norm(x, sp["ln2"], cfg.norm_type)
    if slot.use_moe:
        y, stats = _moe(h2, sp["moe"], cfg, stats)
    else:
        y = basic.mlp(h2, sp["ffn"], cfg.act, cd)
    return x + y, stats


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def forward(params, cfg: ModelConfig, tokens, prefix_embeds=None,
            encoder_embeds=None):
    """tokens: (B, S) int32. prefix_embeds: (B, P, 1152) VLM stub input.
    encoder_embeds: (B, E, d_model) audio stub input (enc-dec only).

    Returns (logits, metrics).
    """
    cd = cfg.cdtype
    x = basic.embed(tokens, params["embed"], cd)
    prefix_len = 0
    if cfg.family == "vlm" and prefix_embeds is not None:
        pe = basic.dense(prefix_embeds.astype(cd), params["mm_proj"], cd)
        x = jnp.concatenate([pe, x], axis=1)
        prefix_len = pe.shape[1]
    positions = jnp.arange(x.shape[1])[None, :]
    if not cfg.use_rope:
        x = x + sinusoid_pos(positions, cfg.d_model, cd)

    encoder_out = None
    if cfg.is_encoder_decoder and encoder_embeds is not None:
        enc_pos = jnp.arange(encoder_embeds.shape[1])[None, :]
        enc_x = encoder_embeds.astype(cd)
        if not cfg.use_rope:
            enc_x = enc_x + sinusoid_pos(enc_pos, cfg.d_model, cd)
        encoder_out = _run_stack(params["enc_layers"],
                                 cfg.with_(num_layers=cfg.encoder_layers or
                                           cfg.num_layers, sliding_window=0),
                                 enc_x, enc_pos, noncausal=True)[0]
        encoder_out = basic.apply_norm(encoder_out, params["enc_norm"],
                                       cfg.norm_type)

    stats = _stats0(cfg)
    for i, slot in enumerate(lead_slots(cfg)):
        def lead_layer(x, stats, lp, slot=slot):
            with jax.named_scope(SCOPE_LEAD):
                return _apply_slot(x, lp, cfg, slot, positions, stats,
                                   prefix_len=prefix_len)
        x, stats = _maybe_remat(lead_layer, cfg)(
            x, stats, params["lead"][f"layer{i}"])

    x, stats = _run_stack(params["layers"], cfg, x, positions, stats=stats,
                          encoder_out=encoder_out, prefix_len=prefix_len)

    with jax.named_scope(SCOPE_HEAD):
        x = basic.apply_norm(x, params["final_norm"], cfg.norm_type)
        if cfg.tie_embeddings:
            logits = basic.unembed(x, params["embed"], cd)
        else:
            logits = x @ params["unembed"]["kernel"].astype(cd)
    return logits, stats


def _run_stack(stack_params, cfg: ModelConfig, x, positions, noncausal=False,
               stats=None, encoder_out=None, prefix_len=0):
    slots, n_groups = layer_program(cfg)

    def group_body(carry, group_params):
        x, stats = carry
        for si, slot in enumerate(slots):
            x, stats = _apply_slot(x, group_params[f"slot{si}"], cfg, slot,
                                   positions, stats, encoder_out=encoder_out,
                                   prefix_len=prefix_len,
                                   causal=not noncausal)
        return (x, stats), ()

    (x, stats), _ = jax.lax.scan(
        _maybe_remat(group_body, cfg),
        (x, _stats0(cfg) if stats is None else stats), stack_params)
    return x, stats


# ---------------------------------------------------------------------------
# Loss


def lm_loss(logits, labels, mask=None):
    """Cross-entropy; labels: (B, S) int32, mask 1.0 where counted."""
    v = logits.shape[-1]
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(lp, labels[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    if mask is None:
        mask = jnp.ones_like(ll)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def train_loss(params, cfg: ModelConfig, batch):
    kw = {}
    if cfg.family == "vlm":
        kw["prefix_embeds"] = batch["prefix_embeds"]
    if cfg.is_encoder_decoder:
        kw["encoder_embeds"] = batch["encoder_embeds"]
    logits, metrics = forward(params, cfg, batch["tokens"], **kw)
    # VLM: logits cover prefix+text; align to text labels only
    if cfg.family == "vlm" and "prefix_embeds" in kw:
        P = kw["prefix_embeds"].shape[1]
        logits = logits[:, P:, :]
    mask = batch.get("mask", None)
    if mask is not None:
        mask = mask[:, 1:].astype(jnp.float32)
    loss = lm_loss(logits[:, :-1, :], batch["labels"][:, 1:], mask)
    if cfg.router_aux_loss and cfg.num_experts:
        loss = loss + cfg.router_aux_loss * metrics["moe_aux_loss"]
    return loss, metrics
