"""Stack Overflow next-word Transformer of the paper's Table 3, with its
plain reference.

Token embedding (10,004 ids) plus a learned position embedding (20
positions), three pre-LayerNorm blocks of 8-head causal self-attention
(head size 12, width 96) and a ReLU FFN (2048), a final LayerNorm, and
logits from the token embedding (tied); 2,261,472 parameters. Freezing
the first FFN dense of all three blocks (Table 11) leaves 1,665,504.
The loss is next-token cross-entropy over positions 1..S-1. The sizes
come from ``so-transformer.json``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import counters
from configs import common, tasks

TASK = tasks.TOKENS_TASK

EMBED_FAN_IN = 2500    # LeCun normal at this fan-in has std 0.02
PROJECTIONS = ("wq", "wk", "wv")


def _ln_specs(path: str, d: int):
    return [(f"{path}/scale", (d,), "zeros", 0),
            (f"{path}/bias", (d,), "zeros", 0)]


def specs(cfg):
    d, f = cfg["d_model"], cfg["d_ff"]
    hd = cfg["num_heads"] * cfg["head_dim"]
    out = [("embed/embedding", (cfg["vocab_size"], d), "normal",
            EMBED_FAN_IN),
           ("pos", (cfg["seq_len"], d), "normal", EMBED_FAN_IN)]
    for i in range(cfg["num_layers"]):
        path = f"layer{i}"
        out += _ln_specs(f"{path}/ln1", d)
        for w in PROJECTIONS:
            out += common.dense_specs(f"{path}/{w}", d, hd)
        out += common.dense_specs(f"{path}/wo", hd, d)
        out += _ln_specs(f"{path}/ln2", d)
        out += common.dense_specs(f"{path}/ffn1", d, f)
        out += common.dense_specs(f"{path}/ffn2", f, d)
    return out + _ln_specs("final_ln", d)


def init_params(cfg, key):
    return common.nest(common.init_leaves(key, specs(cfg)))


def layers(cfg):
    """Per sequence. The embedding lookup comes first with no MACs, so
    every layer above it counts its input gradient. The two attention
    products have no weight: their ``dweight`` term counts the gradient
    of their second operand."""
    d, f, s = cfg["d_model"], cfg["d_ff"], cfg["seq_len"]
    hd = cfg["num_heads"] * cfg["head_dim"]
    out = [counters.Layer("embed", 0)]
    for i in range(cfg["num_layers"]):
        path = f"layer{i}"
        out += [counters.Layer(f"{path}/{w}", s * d * hd)
                for w in PROJECTIONS]
        out += [counters.Layer(f"{path}/scores", s * s * hd),
                counters.Layer(f"{path}/mix", s * s * hd),
                counters.Layer(f"{path}/wo", s * hd * d),
                counters.Layer(f"{path}/ffn1", s * d * f),
                counters.Layer(f"{path}/ffn2", s * f * d)]
    return out + [counters.Layer("embed", s * d * cfg["vocab_size"])]


def _layernorm(x, scale, bias, eps, dtype):
    """The program's LayerNorm: its weight stores the scale minus 1."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + eps) * (1 + scale.astype(dtype))
            + bias.astype(dtype))


def reference_logits(p, tokens, cfg, dtype):
    """p: flat dict path -> array; tokens (B, S) -> logits (B, S, vocab).
    Plain jnp in ``dtype``."""
    h, hd, eps = cfg["num_heads"], cfg["head_dim"], cfg["layernorm_eps"]
    b, s = tokens.shape
    x = (p["embed/embedding"].astype(dtype)[tokens]
         + p["pos"][:s].astype(dtype))
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(cfg["num_layers"]):
        path = f"layer{i}"

        def proj(name, v):
            return common.dense(v, p[f"{path}/{name}/kernel"],
                                p[f"{path}/{name}/bias"], dtype)

        def ln(name, v):
            return _layernorm(v, p[f"{path}/{name}/scale"],
                              p[f"{path}/{name}/bias"], eps, dtype)

        hx = ln("ln1", x)
        q, k, v = (proj(w, hx).reshape(b, s, h, hd) for w in PROJECTIONS)
        scores = common.einsum("bqhd,bkhd->bhqk", q, k, dtype) / jnp.sqrt(
            jnp.asarray(hd, dtype))
        att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = common.einsum("bhqk,bkhd->bqhd", att, v, dtype)
        x = x + proj("wo", o.reshape(b, s, h * hd))
        x = x + proj("ffn2", jax.nn.relu(proj("ffn1", ln("ln2", x))))
    x = _layernorm(x, p["final_ln/scale"], p["final_ln/bias"], eps, dtype)
    return common.einsum("bsd,vd->bsv", x, p["embed/embedding"], dtype)


def program_forward():
    from repro.models import paper_models
    return paper_models.so_transformer_forward


def program_loss():
    from repro.models import decoder_lm
    forward = program_forward()

    def loss(params, b):
        logits = forward(params, b["tokens"])
        return decoder_lm.lm_loss(logits[:, :-1], b["tokens"][:, 1:]), {}
    return loss


def reference_loss(p, batch, cfg, dtype):
    return tasks.next_token_loss(
        reference_logits(p, batch["tokens"], cfg, dtype), batch["tokens"])


def small(cfg):
    """The program's forward fixes the attention widths; the CPU size cuts
    the vocabulary, the FFN, the sequence, the depth and the population.
    Few ReLUs keep float32 rounding from flipping one of them, which at
    the published FFN of 2048 moves the program's widest gap 100-fold
    from seed to seed."""
    return dict(cfg, vocab_size=512, d_ff=128, seq_len=12, num_layers=2,
                clients=16, examples_per_client=40, test_examples=64)
