"""End-to-end system behaviour: federated FedPT training actually learns,
and the training forward is causal for every family and architecture.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.partition as part
from repro.core import fedpt
from repro.data import synthetic as syn
from repro.configs import load_all, ARCH_IDS
from repro.configs.base import ModelConfig, get_config
from repro.launch.train import reduced_config
from repro.models import decoder_lm as dlm
from repro.models import paper_models as pm

load_all()


def test_fedpt_learns_synthetic_emnist():
    ds = syn.make_federated_images(16, 40, (28, 28, 1), 62, seed=0,
                                   test_examples=200)

    def loss_fn(params, b):
        logits = pm.emnist_cnn_forward(params, b["images"])
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, b["labels"][:, None], 1)), {}

    y, z = part.partition(pm.init_emnist_cnn(0), pm.EMNIST_FREEZE)
    rc = fedpt.RoundConfig(6, 2, 16, "sgd", 0.05, "sgd", 0.5)
    round_fn, sopt = fedpt.make_round_fn(loss_fn, rc)
    round_fn = jax.jit(round_fn)
    ss = sopt.init(y)
    rng = np.random.default_rng(0)
    losses = []
    for r in range(8):
        cids = syn.sample_cohort(rng, 16, 6)
        batch, w = syn.cohort_batch(ds, cids, 2, 16, rng)
        y, ss, m = round_fn(y, ss, z, batch, jnp.asarray(w), jax.random.key(r))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.95, losses
    acc = float(jnp.mean(jnp.argmax(pm.emnist_cnn_forward(
        part.merge(y, z), ds.test_images), -1) == ds.test_labels))
    assert acc > 0.10  # >6x chance after 8 rounds


FAMILY_CFGS = {
    "t-dense": dict(family="dense"),
    "t-swa": dict(family="dense", sliding_window=4),
    "t-moe": dict(family="moe", num_experts=4, num_experts_per_tok=2,
                  moe_capacity_factor=8.0),
    "t-mla": dict(family="dense", use_mla=True, kv_lora_rank=32,
                  q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    "t-hybrid": dict(family="hybrid", num_layers=4, attn_period=4,
                     use_rope=False),
    "t-ssm": dict(family="ssm", num_layers=4, d_ff=0, slstm_every=4,
                  use_rope=False, tie_embeddings=True),
}


def _causal_case(case):
    """(cfg, batch, seq) of one case: a family config at the small sizes
    (batch 2, 8 tokens), or a reduced architecture (batch 2, 16 tokens)
    whose capacity-limited MoE runs at a factor that drops no slot."""
    if case in FAMILY_CFGS:
        kw = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  d_ff=128, vocab_size=64, compute_dtype="float32")
        kw.update(FAMILY_CFGS[case])
        return ModelConfig(name=case, **kw), 2, 8
    cfg = reduced_config(get_config(case))
    if cfg.num_experts and cfg.moe_capacity_factor > 0:
        cfg = cfg.with_(moe_capacity_factor=8.0)
    return cfg, 2, 16


@pytest.mark.parametrize("case", list(FAMILY_CFGS) + list(ARCH_IDS))
def test_forward_is_causal(case):
    """Perturbing every token from position t on leaves the logits before
    t as they were, and moves the logits at t."""
    cfg, B, T = _causal_case(case)
    p = dlm.init_model(cfg, 0)
    keys = jax.random.split(jax.random.key(1), 3)
    toks = jax.random.randint(keys[0], (B, T), 0, cfg.vocab_size)
    kw, P = {}, 0
    if cfg.family == "vlm":
        P = cfg.num_prefix_tokens
        kw["prefix_embeds"] = jax.random.normal(keys[1], (B, P, 1152))
    if cfg.is_encoder_decoder:
        kw["encoder_embeds"] = jax.random.normal(
            keys[2], (B, cfg.encoder_seq_len, cfg.d_model))
    fwd = jax.jit(lambda tk: dlm.forward(p, cfg, tk, **kw)[0])
    full = np.asarray(fwd(toks))
    assert full.shape == (B, P + T, cfg.vocab_size)
    for t in (1, T // 2, T - 1):
        moved = toks.at[:, t:].set((toks[:, t:] + 1) % cfg.vocab_size)
        got = np.asarray(fwd(moved))
        np.testing.assert_allclose(got[:, :P + t], full[:, :P + t],
                                   atol=1e-5, rtol=1e-5)
        assert np.abs(got[:, P + t] - full[:, P + t]).max() > 1e-4
