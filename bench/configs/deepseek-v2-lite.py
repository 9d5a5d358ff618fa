"""DeepSeek-V2-Lite at its published widths, cut to one chip's share of
an expert-parallel deployment, with its plain reference.

Layer 0: MLA attention and a dense SwiGLU (10,944); layers 1-4 of the 26
MoE layers: MLA attention and an MoE whose router scores all 64 experts
(softmax, top-6, weights not renormalised, times
``routed_scaling_factor``) of which this chip holds experts
``expert_offset``.. ``expert_offset + n_routed_experts - 1`` (width
1,408), plus 2 shared experts (2,816). MLA: 16 heads, no q compression,
q/k of 128 + 64 (rope) dims, v of 128, a 512-dim latent with an RMSNorm;
RoPE with YaRN (factor 40 over 4,096 positions), softmax scale
192^-1/2 * yarn_mscale(40, 0.707)^2. Pre-RMSNorm residual blocks (eps
1e-6), a final RMSNorm and an untied head over the vocabulary slice.
The loss is next-token cross-entropy over positions 1..S-1 plus
``router_aux_loss`` times the sequence-wise balance loss of each MoE
layer (DeepSeek's ``seq_aux``). The sizes come from
``deepseek-v2-lite.json``.

Departures from DeepSeek's published modeling code, in the program and
here alike:

* RoPE pairs dimension i with i + 32 of the 64 rope dims (split halves),
  where DeepSeek pairs 2i with 2i + 1: a fixed permutation of the rope
  columns of ``wq`` and ``wkv_a``, immaterial for random weights;
* ``kv_b_proj`` is stored as two matrices, ``wk_b`` and ``wv_b``: the
  same columns, regrouped;
* RMSNorm weights store the scale minus 1 (zeros at init);
* the aux loss is added to the loss (DeepSeek adds only its gradient).

The absent experts' part of each MoE layer (56 of 64 on the other seven
chips) is left out here as in the program.
"""
from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

import counters
from configs import common, tasks

TASK = tasks.TOKENS_TASK

EMBED_FAN_IN = 2500    # LeCun normal at this fan-in has std 0.02
FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "deepseek-v2-lite.json")
MOE_COUNTERS = ("moe_routed_held", "moe_load_max_over_mean")


def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qn, qr, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return d, h, qn, qr, vd, cfg["kv_lora_rank"]


def _lead(cfg) -> int:
    return cfg["first_k_dense_replace"]


def _moe_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - _lead(cfg)


def _attn_specs(path, cfg, stack=()):
    d, h, qn, qr, vd, r = _dims(cfg)

    def w(name, d_in, d_out):
        return (f"{path}/{name}/kernel", stack + (d_in, d_out), "normal",
                d_in)
    return [w("wq", d, h * (qn + qr)), w("wkv_a", d, r + qr),
            (f"{path}/kv_norm/scale", stack + (r,), "zeros", 0),
            w("wk_b", r, h * qn), w("wv_b", r, h * vd), w("wo", h * vd, d)]


def _swiglu_specs(path, d, f, stack=()):
    return [(f"{path}/wi_gate/kernel", stack + (d, f), "normal", d),
            (f"{path}/wi_up/kernel", stack + (d, f), "normal", d),
            (f"{path}/wo/kernel", stack + (f, d), "normal", f)]


def specs(cfg):
    """The program's layout (``models/decoder_lm.py``): the leading dense
    layers unstacked under ``lead/``, the MoE layers stacked on a leading
    axis under ``layers/slot0/``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    e, ff = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    out = [("embed/embedding", (v, d), "normal", EMBED_FAN_IN),
           ("final_norm/scale", (d,), "zeros", 0),
           ("unembed/kernel", (d, v), "normal", d)]
    for i in range(_lead(cfg)):
        path = f"lead/layer{i}"
        out += [(f"{path}/ln1/scale", (d,), "zeros", 0),
                (f"{path}/ln2/scale", (d,), "zeros", 0)]
        out += _attn_specs(f"{path}/attn", cfg)
        out += _swiglu_specs(f"{path}/ffn", d, cfg["intermediate_size"])
    n = (_moe_layers(cfg),)
    path = "layers/slot0"
    out += [(f"{path}/ln1/scale", n + (d,), "zeros", 0),
            (f"{path}/ln2/scale", n + (d,), "zeros", 0)]
    out += _attn_specs(f"{path}/attn", cfg, n)
    out += [(f"{path}/moe/router/kernel", n + (d, cfg["router_experts"]),
             "normal", d),
            (f"{path}/moe/wi_gate", n + (e, d, ff), "normal", d),
            (f"{path}/moe/wi_up", n + (e, d, ff), "normal", d),
            (f"{path}/moe/wo", n + (e, ff, d), "normal", ff)]
    out += _swiglu_specs(f"{path}/moe/shared", d,
                         ff * cfg["n_shared_experts"], n)
    return out


def init_params(cfg, key):
    return common.nest(common.init_leaves(key, specs(cfg)))


def _attn_layers(path, cfg, s):
    """The MLA matmuls of one layer, per sequence of ``s`` tokens. The
    two attention products have no weight: their ``dweight`` term counts
    the gradient of their second operand."""
    d, h, qn, qr, vd, r = _dims(cfg)
    L = counters.Layer
    return [L(f"{path}/attn/wq", s * d * h * (qn + qr)),
            L(f"{path}/attn/wkv_a", s * d * (r + qr)),
            L(f"{path}/attn/wk_b", s * r * h * qn),
            L(f"{path}/attn/wv_b", s * r * h * vd),
            L(f"{path}/attn/scores", s * s * h * (qn + qr)),
            L(f"{path}/attn/mix", s * s * h * vd),
            L(f"{path}/attn/wo", s * h * vd * d)]


def routed_rows(cfg, tokens: int) -> float:
    """The slots of ``tokens`` routed to the held experts when routing is
    uniform over all of them: tokens x top-k x held / all."""
    return (tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_experts"])


def layers(cfg):
    """Per sequence, in forward order. The held experts count at the
    uniform-routing expectation (``routed_rows``)."""
    d, s = cfg["hidden_size"], cfg["seq_len"]
    ff, f = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    sf = ff * cfg["n_shared_experts"]
    rows = routed_rows(cfg, s)
    L = counters.Layer
    out = [L("embed", 0)]
    for i in range(_lead(cfg)):
        path = f"lead/layer{i}"
        out += _attn_layers(path, cfg, s)
        out += [L(f"{path}/ffn/wi_gate", s * d * f),
                L(f"{path}/ffn/wi_up", s * d * f),
                L(f"{path}/ffn/wo", s * f * d)]
    for i in range(_moe_layers(cfg)):
        path = f"layers/slot0/{i}"
        out += _attn_layers(path, cfg, s)
        out += [L(f"{path}/moe/router", s * d * cfg["router_experts"]),
                L(f"{path}/moe/wi_gate", round(rows * d * ff)),
                L(f"{path}/moe/wi_up", round(rows * d * ff)),
                L(f"{path}/moe/wo", round(rows * ff * d)),
                L(f"{path}/moe/shared/wi_gate", s * d * sf),
                L(f"{path}/moe/shared/wi_up", s * d * sf),
                L(f"{path}/moe/shared/wo", s * sf * d)]
    return out + [L("unembed", s * d * cfg["vocab_size"])]


# ---------------------------------------------------------------------------
# the held experts' grouped matmul (``moe_gmm``), counted per update


GMM_CALLS_PER_LAYER = 6   # forward gate, up, down; input grad of each


def _gmm_rows(cfg, mix) -> float:
    """Rows of one ``moe_gmm`` call: the round engine vmaps the cohort's
    clients, and the batched call takes every client's routed slots."""
    return routed_rows(cfg, mix["cohort"] * mix["local_batch"]
                       * cfg["seq_len"])


def gmm_calls_per_update(cfg, mix) -> int:
    return GMM_CALLS_PER_LAYER * _moe_layers(cfg) * mix["local_steps"]


def gmm_bytes_per_update(cfg, mix) -> float:
    """HBM bytes the ``moe_gmm`` calls of one update need at least: each
    call reads the held experts' (E, d, ff) float32 weights once, and its
    routed rows in (width d or ff) and out (the other)."""
    d, ff, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                cfg["n_routed_experts"])
    per_call = 4 * e * d * ff + 4 * _gmm_rows(cfg, mix) * (d + ff)
    return per_call * gmm_calls_per_update(cfg, mix)


def gmm_flops_per_update(cfg, mix) -> float:
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 2 * _gmm_rows(cfg, mix) * d * ff * gmm_calls_per_update(cfg, mix)


# ---------------------------------------------------------------------------
# the program


def program_config(params):
    """The registered ``deepseek-v2-lite`` with the cut these weights
    carry: depth, vocabulary slice and held experts (and, at the CPU test
    size, the model and FFN widths and the latent rank) read from their
    shapes, the held experts' offset from ``deepseek-v2-lite.json``;
    float32 compute, each layer recomputed in the backward pass (the
    cohort's activations would not fit the chip's memory otherwise)."""
    from repro.configs.base import get_config
    with open(FILE) as f:
        offset = json.load(f)["expert_offset"]
    lead, moe = params["lead"]["layer0"], params["layers"]["slot0"]["moe"]
    vocab, d = params["embed"]["embedding"].shape
    return get_config("deepseek-v2-lite").with_(
        num_layers=len(params["lead"]) + moe["router"]["kernel"].shape[0],
        vocab_size=vocab, d_model=d,
        d_ff=lead["ffn"]["wo"]["kernel"].shape[0],
        moe_d_ff=moe["wo"].shape[-2], experts_held=moe["wo"].shape[-3],
        expert_offset=offset,
        kv_lora_rank=lead["attn"]["wk_b"]["kernel"].shape[0],
        param_dtype="float32", compute_dtype="float32", remat=True)


def program_loss():
    from repro.models import decoder_lm

    def loss(params, b):
        cfg = program_config(params)
        logits, stats = decoder_lm.forward(params, cfg, b["tokens"])
        value = (decoder_lm.lm_loss(logits[:, :-1], b["tokens"][:, 1:])
                 + cfg.router_aux_loss * stats["moe_aux_loss"])
        return value, {k: stats[k] for k in MOE_COUNTERS}
    return loss


# ---------------------------------------------------------------------------
# the plain reference


def yarn(cfg):
    """(inverse frequencies (qk_rope_head_dim / 2,), softmax scale): YaRN
    (arXiv:2309.00071) as DeepSeek-V2 applies it, transcribed from its
    equations in float64."""
    rs, dim, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    s, big = rs["factor"], rs["original_max_position_embeddings"]

    def mscale(m):
        return 0.1 * m * math.log(s) + 1.0 if s > 1 else 1.0

    def corr(rot):
        return dim * math.log(big / (rot * 2 * math.pi)) / (2 * math.log(
            theta))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    extrap = theta ** (-2 * i / dim)
    m = 1 - np.clip((i - low) / (high - low), 0, 1)
    inv = extrap / s * (1 - m) + extrap * m
    assert mscale(rs["mscale"]) == mscale(rs["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5 * mscale(
        rs["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), scale


def _rope(x, inv, dtype):
    """Split-half rotation of x (b, s, heads, dim) at positions 0..s-1."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    c = jnp.cos(ang)[None, :, None, :].astype(dtype)
    s = jnp.sin(ang)[None, :, None, :].astype(dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rmsnorm(x, scale, eps, dtype):
    x = x.astype(dtype)
    return (x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
            * (1 + scale.astype(dtype)))


def _mm(x, w, dtype):
    return common.einsum("...i,io->...o", x, w, dtype)


def _swiglu(x, wg, wu, wo, dtype):
    return _mm(jax.nn.silu(_mm(x, wg, dtype)) * _mm(x, wu, dtype), wo, dtype)


def _mla(x, w, cfg, dtype):
    """Full (non-absorbed) MLA of x (b, s, d); ``w(name)`` a weight."""
    d, h, qn, qr, vd, r = _dims(cfg)
    b, s, _ = x.shape
    inv, scale = yarn(cfg)
    q = _mm(x, w("attn/wq/kernel"), dtype).reshape(b, s, h, qn + qr)
    kv = _mm(x, w("attn/wkv_a/kernel"), dtype)
    ckv = _rmsnorm(kv[..., :r], w("attn/kv_norm/scale"),
                   cfg["rms_norm_eps"], dtype)
    k_nope = _mm(ckv, w("attn/wk_b/kernel"), dtype).reshape(b, s, h, qn)
    v = _mm(ckv, w("attn/wv_b/kernel"), dtype).reshape(b, s, h, vd)
    q = jnp.concatenate([q[..., :qn], _rope(q[..., qn:], inv, dtype)], -1)
    k_pe = _rope(kv[..., None, r:], inv, dtype)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, h, qr))], -1)
    scores = common.einsum("bqhd,bkhd->bhqk", q, k, dtype) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = common.einsum("bhqk,bkhd->bqhd", att, v, dtype)
    return _mm(o.reshape(b, s, h * vd), w("attn/wo/kernel"), dtype)


def _moe(x, w, cfg, dtype):
    """The held experts' part of the MoE of x (b, s, d) plus the shared
    experts, the layer's sequence-wise balance loss, and how many of its
    top-k slots land on the held experts."""
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    off = cfg["expert_offset"]
    probs = jax.nn.softmax(_mm(x, w("moe/router/kernel"), dtype), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    else:
        top = top * cfg["routed_scaling_factor"]
    f = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=dtype), -2), 1) * (
        E / k)
    aux = jnp.mean(jnp.sum(f * jnp.mean(probs, 1), -1))
    y = _swiglu(x, w("moe/shared/wi_gate/kernel"),
                w("moe/shared/wi_up/kernel"), w("moe/shared/wo/kernel"),
                dtype)
    held = cfg["n_routed_experts"]
    for e in range(held):
        c = jnp.sum(jnp.where(idx == off + e, top, 0.0), -1)
        y = y + c[..., None] * _swiglu(x, w("moe/wi_gate")[e],
                                       w("moe/wi_up")[e], w("moe/wo")[e],
                                       dtype)
    return y, aux, jnp.sum((idx >= off) & (idx < off + held))


def reference_loss(p, batch, cfg, dtype):
    """p: flat dict path -> array; batch["tokens"] (B, S). Plain jnp in
    ``dtype``."""
    logits, aux, _ = reference_forward(p, batch["tokens"], cfg, dtype)
    return (tasks.next_token_loss(logits, batch["tokens"])
            + cfg["router_aux_loss"] * aux)


def reference_forward(p, tokens, cfg, dtype):
    """The logits (B, S, vocab) of tokens (B, S), the MoE layers' summed
    balance loss and their routed slots on the held experts, summed."""
    eps = cfg["rms_norm_eps"]
    x = p["embed/embedding"].astype(dtype)[tokens]
    aux = routed = 0
    layer_w = [lambda n, i=i: p[f"lead/layer{i}/{n}"]
               for i in range(_lead(cfg))]
    layer_w += [lambda n, i=i: p[f"layers/slot0/{n}"][i]
                for i in range(_moe_layers(cfg))]
    for i, w in enumerate(layer_w):
        x = x + _mla(_rmsnorm(x, w("ln1/scale"), eps, dtype), w, cfg, dtype)
        h = _rmsnorm(x, w("ln2/scale"), eps, dtype)
        if i < _lead(cfg):
            x = x + _swiglu(h, w("ffn/wi_gate/kernel"),
                            w("ffn/wi_up/kernel"), w("ffn/wo/kernel"),
                            dtype)
        else:
            y, a, n = _moe(h, w, cfg, dtype)
            x, aux, routed = x + y, aux + a, routed + n
    x = _rmsnorm(x, p["final_norm/scale"], eps, dtype)
    return _mm(x, p["unembed/kernel"], dtype), aux, routed


def small(cfg):
    """The CPU size: the program reads the heads and head sizes from the
    registry, so those stay; the model, FFN and expert widths, the
    latent rank, the vocabulary, the sequence, the depth and the
    population are cut. The router keeps its 64 experts and top-6, and
    8 experts are held."""
    return dict(cfg, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, kv_lora_rank=32, vocab_size=256,
                seq_len=12, num_hidden_layers=3, clients=16,
                examples_per_client=40, test_examples=16)
