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

KV caches: full-length buffers for global attention, ring buffers of
`sliding_window` size for SWA architectures (Mistral-style rolling
cache) — the latter is what makes `long_500k` decode feasible.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

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
# Forward (training / prefill)


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
    """One residual block. Returns (x, stats, cache_entry)."""
    cd = cfg.cdtype
    h = basic.apply_norm(x, sp["ln1"], cfg.norm_type)
    cache = ()
    if slot.kind == ATTN:
        if cfg.use_mla:
            with jax.named_scope(SCOPE_MLA):
                q, k, v, (ckv, kpe) = attn_lib.mla_qkv(h, sp["attn"], cfg,
                                                       positions)
                o = attn_lib.flash_attention(
                    q, k, v, cfg.with_(sliding_window=0), causal=causal,
                    prefix_len=prefix_len,
                    scale=attn_lib.mla_softmax_scale(cfg))
                o = o.reshape(o.shape[:2] + (o.shape[2] * o.shape[3],))
                o = basic.dense(o, sp["attn"]["wo"], cd)
            cache = (ckv, kpe)
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
            cache = (k, v)
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
        o, st = ssm_lib.mamba_forward(h, sp["mamba"], cfg)
        x = x + o
        cache = st
    elif slot.kind == MLSTM:
        o, st = ssm_lib.mlstm_forward(h, sp["mlstm"], cfg)
        return x + o, stats, st
    elif slot.kind == SLSTM:
        o, st = ssm_lib.slstm_forward(h, sp["slstm"], cfg)
        return x + o, stats, st

    h2 = basic.apply_norm(x, sp["ln2"], cfg.norm_type)
    if slot.use_moe:
        y, stats = _moe(h2, sp["moe"], cfg, stats)
    else:
        y = basic.mlp(h2, sp["ffn"], cfg.act, cd)
    return x + y, stats, cache


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def forward(params, cfg: ModelConfig, tokens, prefix_embeds=None,
            encoder_embeds=None, return_caches: bool = False):
    """tokens: (B, S) int32. prefix_embeds: (B, P, 1152) VLM stub input.
    encoder_embeds: (B, E, d_model) audio stub input (enc-dec only).

    Returns (logits, metrics[, caches]).
    """
    cd = cfg.cdtype
    slots, n_groups = layer_program(cfg)
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
    lead_caches = {}
    for i, slot in enumerate(lead_slots(cfg)):
        def lead_layer(x, stats, lp, slot=slot):
            with jax.named_scope(SCOPE_LEAD):
                return _apply_slot(x, lp, cfg, slot, positions, stats,
                                   prefix_len=prefix_len)
        x, stats, lead_caches[f"layer{i}"] = _maybe_remat(lead_layer, cfg)(
            x, stats, params["lead"][f"layer{i}"])

    x, stats, caches = _run_stack(params["layers"], cfg, x, positions,
                                  stats=stats, encoder_out=encoder_out,
                                  prefix_len=prefix_len,
                                  collect_caches=return_caches)

    with jax.named_scope(SCOPE_HEAD):
        x = basic.apply_norm(x, params["final_norm"], cfg.norm_type)
        if cfg.tie_embeddings:
            logits = basic.unembed(x, params["embed"], cd)
        else:
            logits = x @ params["unembed"]["kernel"].astype(cd)
    if return_caches:
        if lead_caches:
            caches = {"lead": lead_caches, "stack": caches}
        return logits, stats, caches
    return logits, stats


def _run_stack(stack_params, cfg: ModelConfig, x, positions, noncausal=False,
               stats=None, encoder_out=None, prefix_len=0,
               collect_caches=False):
    slots, n_groups = layer_program(cfg)

    def group_body(carry, group_params):
        x, stats = carry
        caches = []
        for si, slot in enumerate(slots):
            x, stats, c = _apply_slot(x, group_params[f"slot{si}"], cfg,
                                      slot, positions, stats,
                                      encoder_out=encoder_out,
                                      prefix_len=prefix_len,
                                      causal=not noncausal)
            caches.append(c)
        out = tuple(caches) if collect_caches else ()
        return (x, stats), out

    (x, stats), caches = jax.lax.scan(
        _maybe_remat(group_body, cfg),
        (x, _stats0(cfg) if stats is None else stats), stack_params)
    return x, stats, caches


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


# ---------------------------------------------------------------------------
# Decode (serving): single-token step against per-layer caches.
#
# Attention layers use a full-length cache, or a Mistral-style ring buffer
# of `sliding_window` entries for SWA architectures (RoPE is applied at
# absolute positions on write, so relative geometry survives the ring).
# SSM layers carry constant-size recurrent states.


def cache_capacity(cfg: ModelConfig, max_len: int) -> int:
    if cfg.sliding_window and cfg.sliding_window < max_len:
        return cfg.sliding_window
    return max_len


def _cache_entry(cfg: ModelConfig, slot: Slot, G: int, batch: int, S: int,
                 cd):
    """One slot's zero cache, stacked over G layers."""
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    if slot.kind == ATTN and cfg.use_mla:
        return {"ckv": jnp.zeros((G, batch, S, cfg.kv_lora_rank), cd),
                "kpe": jnp.zeros((G, batch, S, cfg.qk_rope_head_dim), cd)}
    if slot.kind == ATTN:
        return {"k": jnp.zeros((G, batch, S, kvh, hd), cd),
                "v": jnp.zeros((G, batch, S, kvh, hd), cd)}
    if slot.kind == MAMBA:
        di, _ = ssm_lib.mamba_dims(cfg)
        return {"h": jnp.zeros((G, batch, di, cfg.mamba_d_state),
                               jnp.float32),
                "conv": jnp.zeros((G, batch, cfg.mamba_d_conv - 1, di), cd)}
    d_in_x, nh_x, dh_x = ssm_lib.xlstm_dims(cfg)
    if slot.kind == MLSTM:
        return {"C": jnp.zeros((G, batch, nh_x, dh_x, dh_x), jnp.float32),
                "n": jnp.zeros((G, batch, nh_x, dh_x), jnp.float32),
                "conv": jnp.zeros((G, batch, 3, d_in_x), cd)}
    dh_s = cfg.d_model // cfg.num_heads
    z = jnp.zeros((G, batch, cfg.num_heads, dh_s), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": z - 30.0,
            "conv": jnp.zeros((G, batch, 3, cfg.d_model), cd)}


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    """Zero caches for decoding up to max_len tokens. Returns a pytree with
    a per-slot entry stacked over groups, a per-leading-layer entry (with
    a leading axis of 1, so every entry has the same layout) and a scalar
    cache_len."""
    cd = dtype or cfg.cdtype
    slots, G = layer_program(cfg)
    S = cache_capacity(cfg, max_len)
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    entries = [_cache_entry(cfg, slot, G, batch, S, cd) for slot in slots]
    cache = {"slots": {f"slot{i}": e for i, e in enumerate(entries)},
             "cache_len": jnp.zeros((), jnp.int32)}
    if cfg.first_k_dense:
        cache["lead"] = {f"layer{i}": _cache_entry(cfg, slot, 1, batch, S,
                                                   cd)
                         for i, slot in enumerate(lead_slots(cfg))}
    if cfg.is_encoder_decoder:
        E = cfg.encoder_seq_len
        cache["cross"] = {
            f"slot{i}": {"k": jnp.zeros((G, batch, E, kvh, hd), cd),
                         "v": jnp.zeros((G, batch, E, kvh, hd), cd)}
            for i, slot in enumerate(slots) if slot.kind == ATTN}
    return cache


def build_cross_cache(params, cfg: ModelConfig, encoder_embeds):
    """Precompute encoder K/V for every decoder cross-attention slot."""
    cd = cfg.cdtype
    slots, G = layer_program(cfg)
    enc_pos = jnp.arange(encoder_embeds.shape[1])[None, :]
    enc_x = encoder_embeds.astype(cd)
    if not cfg.use_rope:
        enc_x = enc_x + sinusoid_pos(enc_pos, cfg.d_model, cd)
    enc_cfg = cfg.with_(num_layers=cfg.encoder_layers or cfg.num_layers,
                        sliding_window=0)
    enc = _run_stack(params["enc_layers"], enc_cfg, enc_x, enc_pos,
                     noncausal=True)[0]
    enc = basic.apply_norm(enc, params["enc_norm"], cfg.norm_type)

    def per_group(gp):
        out = {}
        for i, slot in enumerate(slots):
            if slot.kind != ATTN:
                continue
            sp = gp[f"slot{i}"]
            _, kc, vc = attn_lib.qkv_project(enc, sp["cross_attn"], cfg)
            out[f"slot{i}"] = {"k": kc, "v": vc}
        return out

    return jax.vmap(per_group, in_axes=0, out_axes=0)(params["layers"])


def _decode_slot(x, sp, cfg: ModelConfig, slot: Slot, cache, cross,
                 cache_len, pos):
    """x: (B,1,d). Returns (x, new_cache)."""
    cd = cfg.cdtype
    h = basic.apply_norm(x, sp["ln1"], cfg.norm_type)
    if slot.kind == ATTN:
        S = cache["k"].shape[1] if "k" in cache else cache["ckv"].shape[1]
        widx = jnp.mod(cache_len, S)                      # ring write index
        cl_eff = jnp.minimum(cache_len + 1, S)
        if cfg.use_mla:
            ckv, kpe = attn_lib.mla_compress(h, sp["attn"], cfg, pos[None, :])
            new_ckv = jax.lax.dynamic_update_slice(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, widx, 0))
            new_kpe = jax.lax.dynamic_update_slice(
                cache["kpe"], kpe.astype(cache["kpe"].dtype), (0, widx, 0))
            o = attn_lib.mla_decode(h, sp["attn"], cfg, new_ckv, new_kpe,
                                    cl_eff)
            cache = {"ckv": new_ckv, "kpe": new_kpe}
        else:
            q, k, v = attn_lib.qkv_project(h, sp["attn"], cfg)
            if cfg.use_rope:
                cos, sin = attn_lib.rope_freqs(cfg.resolved_head_dim,
                                               cfg.rope_theta, pos[None, :],
                                               cfg.rope_scaling)
                q = attn_lib.apply_rope(q, cos, sin)
                k = attn_lib.apply_rope(k, cos, sin)
            new_k = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, widx, 0, 0))
            new_v = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, widx, 0, 0))
            o = attn_lib.decode_attention(q, new_k, new_v, cl_eff,
                                          cfg.with_(sliding_window=0))
            o = basic.dense(o.reshape(o.shape[0], 1, -1), sp["attn"]["wo"], cd)
            cache = {"k": new_k, "v": new_v}
        x = x + o
        if cross is not None and "cross_attn" in sp:
            hc = basic.apply_norm(x, sp["ln_cross"], cfg.norm_type)
            qc, _, _ = attn_lib.qkv_project(hc, sp["cross_attn"], cfg)
            oc = attn_lib.decode_attention(
                qc, cross["k"], cross["v"], cross["k"].shape[1],
                cfg.with_(sliding_window=0))
            x = x + basic.dense(oc.reshape(oc.shape[0], 1, -1),
                                sp["cross_attn"]["wo"], cd)
    elif slot.kind == MAMBA:
        o, (hh, conv) = ssm_lib.mamba_step(h[:, 0, :], sp["mamba"], cfg,
                                           (cache["h"], cache["conv"]))
        x = x + o[:, None, :]
        cache = {"h": hh, "conv": conv}
    elif slot.kind == MLSTM:
        o, (C, n, conv) = ssm_lib.mlstm_step(
            h[:, 0, :], sp["mlstm"], cfg, (cache["C"], cache["n"], cache["conv"]))
        return x + o[:, None, :], {"C": C, "n": n, "conv": conv}
    elif slot.kind == SLSTM:
        cell = (cache["c"], cache["n"], cache["h"], cache["m"])
        o, (cell, conv) = ssm_lib.slstm_step(h[:, 0, :], sp["slstm"], cfg,
                                             (cell, cache["conv"]))
        return x + o[:, None, :], {"c": cell[0], "n": cell[1], "h": cell[2],
                                   "m": cell[3], "conv": conv}

    h2 = basic.apply_norm(x, sp["ln2"], cfg.norm_type)
    if slot.use_moe:
        y, _ = _moe(h2, sp["moe"], cfg, _stats0(cfg))
    else:
        y = basic.mlp(h2, sp["ffn"], cfg.act, cd)
    return x + y, cache


def decode_step(params, cfg: ModelConfig, cache, tokens):
    """tokens: (B, 1) int32 -> (logits (B, 1, V), new cache)."""
    cd = cfg.cdtype
    slots, G = layer_program(cfg)
    cache_len = cache["cache_len"]
    pos = cache_len[None]  # absolute position of this token
    x = basic.embed(tokens, params["embed"], cd)
    if not cfg.use_rope:
        x = x + sinusoid_pos(pos[None, :], cfg.d_model, cd)

    new_lead = {}
    for i, slot in enumerate(lead_slots(cfg)):
        lc = jax.tree_util.tree_map(lambda a: a[0],
                                    cache["lead"][f"layer{i}"])
        x, lc = _decode_slot(x, params["lead"][f"layer{i}"], cfg, slot, lc,
                             None, cache_len, pos)
        new_lead[f"layer{i}"] = jax.tree_util.tree_map(lambda a: a[None],
                                                       lc)

    def group_body(x, xs):
        gp, gc, gcross = xs
        new_caches = {}
        for si, slot in enumerate(slots):
            key = f"slot{si}"
            cr = gcross.get(key) if gcross else None
            x, nc = _decode_slot(x, gp[key], cfg, slot, gc[key], cr,
                                 cache_len, pos)
            new_caches[key] = nc
        return x, new_caches

    cross = cache.get("cross")
    (x, new_slots) = jax.lax.scan(
        group_body, x, (params["layers"], cache["slots"], cross))

    x = basic.apply_norm(x, params["final_norm"], cfg.norm_type)
    if cfg.tie_embeddings:
        logits = basic.unembed(x, params["embed"], cd)
    else:
        logits = x @ params["unembed"]["kernel"].astype(cd)
    new_cache = dict(cache)
    new_cache["slots"] = new_slots
    if new_lead:
        new_cache["lead"] = new_lead
    new_cache["cache_len"] = cache_len + 1
    return logits, new_cache
