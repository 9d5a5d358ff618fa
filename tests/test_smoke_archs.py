"""Per-architecture smoke tests (assignment requirement): a REDUCED
variant of each assigned architecture's family (<=2 effective layer
groups, d_model <= 512, <= 4 experts) runs one forward and one federated
train step on CPU; output shapes and finiteness are asserted. The FULL
configs are exercised by the dry-run only.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import load_all, ARCH_IDS
from repro.configs.base import get_config
from repro.core import fedpt
import repro.core.partition as part
from repro.launch.train import reduced_config
from repro.models import decoder_lm as dlm

load_all()
ARCHS = list(ARCH_IDS)


def make_batch(cfg, clients=2, tau=1, b=2, seq=16):
    key = jax.random.key(0)
    batch = {
        "tokens": jax.random.randint(key, (clients, tau, b, seq), 0,
                                     cfg.vocab_size),
    }
    batch["labels"] = batch["tokens"]
    if cfg.family == "vlm":
        batch["prefix_embeds"] = jnp.zeros(
            (clients, tau, b, cfg.num_prefix_tokens, 1152), jnp.float32)
    if cfg.is_encoder_decoder:
        batch["encoder_embeds"] = jnp.zeros(
            (clients, tau, b, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = reduced_config(get_config(arch))
    assert cfg.d_model <= 512 and cfg.num_experts <= 4
    params = dlm.init_model(cfg, 0)
    b, s = 2, 16
    toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
    kw = {}
    exp_s = s
    if cfg.family == "vlm":
        kw["prefix_embeds"] = jnp.zeros((b, cfg.num_prefix_tokens, 1152))
        exp_s = s + cfg.num_prefix_tokens
    if cfg.is_encoder_decoder:
        kw["encoder_embeds"] = jnp.zeros((b, cfg.encoder_seq_len, cfg.d_model))
    logits, metrics = dlm.forward(params, cfg, toks, **kw)
    assert logits.shape == (b, exp_s, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_one_federated_train_step(arch):
    cfg = reduced_config(get_config(arch))
    init_fn = lambda s: dlm.init_model(cfg, s)
    y, frozen = part.partition(init_fn(0), cfg.freeze_spec)
    assert part.count_params(frozen) > 0, "freeze spec must bind"

    def loss_fn(params, mb):
        return dlm.train_loss(params, cfg, mb)

    rc = fedpt.RoundConfig(2, 1, 2, "sgd", 0.05, "sgd", 1.0)
    round_fn, sopt = fedpt.make_round_fn(loss_fn, rc)
    sstate = sopt.init(y)
    batch = make_batch(cfg)
    w = jnp.ones((2,), jnp.float32)
    y2, sstate, m = jax.jit(round_fn)(y, sstate, frozen, batch, w,
                                      jax.random.key(0))
    assert np.isfinite(float(m["loss"]))
    # trainable moved, frozen untouched by construction
    moved = jax.tree_util.tree_reduce(
        lambda a, l: a + float(jnp.sum(jnp.abs(l))),
        jax.tree_util.tree_map(lambda a, b: a - b, y2, y), 0.0)
    assert moved > 0.0

