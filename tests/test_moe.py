"""MoE dispatch: sort-based capacity dispatch vs the dense oracle,
capacity-drop semantics, and router invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.nn import basic, moe as moe_lib

CFG = ModelConfig(name="m", family="moe", num_layers=1, d_model=32,
                  num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=8,
                  num_experts=4, num_experts_per_tok=2,
                  moe_capacity_factor=8.0,  # high capacity: no drops
                  compute_dtype="float32")


def test_moe_matches_dense_oracle_when_no_drops():
    p = moe_lib.init_moe(0, "moe", CFG, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (64, 32))
    got, aux1 = moe_lib.moe_ffn(x, p, CFG)
    want, aux2 = moe_lib.moe_ffn_dense_fallback(x, p, CFG)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux1), float(aux2), rtol=1e-5)


def test_moe_with_shared_experts():
    cfg = CFG.with_(num_shared_experts=1, moe_d_ff=32)
    p = moe_lib.init_moe(0, "moe", cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(2), (32, 32))
    got, _ = moe_lib.moe_ffn(x, p, cfg)
    want, _ = moe_lib.moe_ffn_dense_fallback(x, p, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_capacity_drop_reduces_output_not_crashes():
    cfg = CFG.with_(moe_capacity_factor=0.25)  # force heavy dropping
    p = moe_lib.init_moe(0, "moe", cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(3), (64, 32))
    got, _ = moe_lib.moe_ffn(x, p, cfg)
    assert got.shape == x.shape
    assert bool(jnp.isfinite(got).all())
    # dropped tokens -> some outputs exactly zero (no expert contribution)
    norms = jnp.linalg.norm(np.asarray(got), axis=-1)
    assert float(jnp.min(norms)) == 0.0


def test_router_weights_normalized_topk():
    p = moe_lib.init_moe(0, "moe", CFG, jnp.float32)
    x = jax.random.normal(jax.random.key(4), (16, 32))
    w, idx, aux = moe_lib.router_topk(x, p, CFG)
    assert w.shape == (16, 2) and idx.shape == (16, 2)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 1.0, rtol=1e-5)
    assert int(idx.min()) >= 0 and int(idx.max()) < 4
    # top-k indices are distinct per token
    assert bool((idx[:, 0] != idx[:, 1]).all())
    assert float(aux) > 0


# the no-drop expert-share layer (moe_ffn_share): 16 experts, top-3,
# weights not renormalised, 2 shared experts
SHARE = CFG.with_(num_experts=16, num_experts_per_tok=3, num_shared_experts=2,
                  moe_d_ff=16, moe_capacity_factor=0.0, norm_topk_prob=False,
                  routed_scaling_factor=1.5)


def _held(p, cfg, offset, held):
    """The layer's parameters as a chip holding ``held`` experts from
    ``offset`` has them, and its config."""
    part = {k: (v[offset:offset + held] if k in ("wi_gate", "wi_up", "wo")
                else v) for k, v in p.items()}
    return part, cfg.with_(expert_offset=offset, experts_held=held)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips holding 2 of 16 experts each: their outputs, with the
    shared experts (which every chip computes) counted once, add up to
    the dense oracle of the whole layer; every routed slot lands on
    exactly one share."""
    p = moe_lib.init_moe(0, "moe", SHARE, jnp.float32)
    x = jax.random.normal(jax.random.key(5), (48, 32))
    want, _ = moe_lib.moe_ffn_dense_fallback(x, p, SHARE)
    shared = basic.mlp(x, p["shared"], "silu", jnp.float32)
    total, routed, auxs = -7 * shared, 0.0, []
    for s in range(8):
        out, aux, counts = moe_lib.moe_ffn_share(x, *_held(p, SHARE, 2 * s, 2),
                                                 seqs=4)
        total, routed = total + out, routed + counts["moe_routed_held"]
        auxs.append(float(aux))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(routed) == 48 * 3
    np.testing.assert_allclose(auxs, auxs[0], rtol=1e-6)
    # the uncut layer is the share that holds every expert
    whole, _, counts = moe_lib.moe_ffn_share(x, p, SHARE, seqs=4)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(counts["moe_routed_held"]) == 48 * 3


def test_no_slot_dropped_when_every_token_routes_to_one_held_expert():
    """Every token's top slot goes to expert 0 and its others to experts
    this chip does not hold: expert 0 takes all 64 slots (a capacity
    layer would keep ceil(64 * 3 / 16 * 1.25) = 15 of them), and the
    share equals the dense oracle with the absent experts zeroed."""
    cfg = SHARE
    p = moe_lib.init_moe(0, "moe", cfg, jnp.float32)
    router = np.zeros((32, 16), np.float32)
    router[0, 0], router[0, 9], router[0, 12] = 10.0, 6.0, 5.0
    p = dict(p, router={"kernel": jnp.asarray(router)})
    x = jax.random.normal(jax.random.key(6), (64, 32))
    x = x.at[:, 0].set(1.0)
    share, share_cfg = _held(p, cfg, 0, 2)
    out, _, counts = moe_lib.moe_ffn_share(x, share, share_cfg, seqs=2)
    assert float(counts["moe_routed_held"]) == 64
    assert float(counts["moe_load_max_over_mean"]) == 2.0
    absent = (jnp.arange(16) < 2)[:, None, None]
    zeroed = {k: (v * absent if k in ("wi_gate", "wi_up", "wo") else v)
              for k, v in p.items()}
    want, _ = moe_lib.moe_ffn_dense_fallback(x, zeroed, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # expert 0's part is really there: without it only the shared remain
    shared = basic.mlp(x, p["shared"], "silu", jnp.float32)
    assert float(jnp.min(jnp.linalg.norm(out - shared, axis=-1))) > 1e-3
