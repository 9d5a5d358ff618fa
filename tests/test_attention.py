"""Attention-layer unit properties: RoPE algebra, flash-vs-dense oracle,
GQA head grouping, YaRN frequencies.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import ModelConfig
from repro.nn import attention as att

CFG = ModelConfig(name="a", family="dense", num_layers=1, d_model=32,
                  num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=8,
                  compute_dtype="float32")


def test_rope_preserves_norm_and_relative_positions():
    x = jax.random.normal(jax.random.key(0), (1, 6, 2, 16))
    pos = jnp.arange(6)[None, :]
    cos, sin = att.rope_freqs(16, 1e4, pos)
    xr = att.apply_rope(x, cos, sin)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(xr), axis=-1),
                               rtol=1e-5)
    # relative property: <q_m, k_n> depends only on m-n
    q = jax.random.normal(jax.random.key(1), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.key(2), (1, 1, 1, 16))

    def dot_at(m, n):
        cm, sm = att.rope_freqs(16, 1e4, jnp.asarray([[m]]))
        cn, sn = att.rope_freqs(16, 1e4, jnp.asarray([[n]]))
        qm = att.apply_rope(q, cm, sm)
        kn = att.apply_rope(k, cn, sn)
        return float(jnp.sum(qm * kn))

    assert abs(dot_at(5, 3) - dot_at(9, 7)) < 1e-4
    assert abs(dot_at(5, 3) - dot_at(5, 2)) > 1e-6  # but changes with gap


@given(st.integers(1, 2), st.sampled_from([17, 64, 130]),
       st.sampled_from([0, 8]))
@settings(max_examples=8, deadline=None)
def test_flash_matches_dense_softmax(b, s, window):
    cfg = CFG.with_(sliding_window=window)
    h, hd = 2, 16
    ks = jax.random.split(jax.random.key(s * 7 + b), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd))
    v = jax.random.normal(ks[2], (b, s, h, hd))
    out = att.flash_attention(q, k, v, cfg, chunk=32)
    # dense reference
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = qpos >= kpos
    if window:
        mask = mask & (qpos - kpos < window)
    sc = jnp.where(mask[None, None], sc, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_gqa_grouping_matches_explicit_repeat():
    """h=4 queries on kvh=2: heads (0,1)->kv0, (2,3)->kv1."""
    b, s, hd = 1, 5, 16
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (b, s, 4, hd))
    k = jax.random.normal(ks[1], (b, s, 2, hd))
    v = jax.random.normal(ks[2], (b, s, 2, hd))
    out = att.flash_attention(q, k, v, CFG)
    krep = jnp.repeat(k, 2, axis=2)
    vrep = jnp.repeat(v, 2, axis=2)
    want = att.flash_attention(q, krep, vrep, CFG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


def test_yarn_frequencies_and_softmax_scale_match_the_equations():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4,096 positions, beta_fast
    32, beta_slow 1, mscale = mscale_all_dim = 0.707) on its 64 rope dims,
    against a direct numpy transcription of arXiv:2309.00071 as
    DeepSeek's modeling code applies it."""
    from repro.configs.base import get_config
    cfg = get_config("deepseek-v2-lite")
    rs = cfg.rope_scaling
    d, theta, s = 64, 1e4, 40.0
    assert (cfg.qk_rope_head_dim, cfg.rope_theta, rs.factor) == (d, theta, s)
    low = np.floor(d * np.log(4096 / (32 * 2 * np.pi)) / (2 * np.log(theta)))
    high = np.ceil(d * np.log(4096 / (1 * 2 * np.pi)) / (2 * np.log(theta)))
    assert (low, high) == (10, 23)
    i = np.arange(d // 2)
    extrap = theta ** (-2 * i / d)
    m = 1 - np.clip((i - low) / (high - low), 0, 1)
    inv = extrap / s * (1 - m) + extrap * m
    np.testing.assert_allclose(att.yarn_inv_freq(d, theta, rs), inv,
                               rtol=1e-6)
    # the cell's positions; a float32 angle under 1024 is within 1.2e-4
    pos = jnp.arange(0, 1024, 7)[None, :]
    cos, sin = att.rope_freqs(d, theta, pos, rs)
    ang = np.asarray(pos, np.float64)[..., None] * inv
    # cos and sin scaled by yarn_mscale(40, 0.707) / itself, that is 1
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-4)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=2e-4)
    mscale = 0.1 * 0.707 * np.log(s) + 1
    assert abs(mscale ** 2 - 1.5896) < 1e-4
    np.testing.assert_allclose(float(att.mla_softmax_scale(cfg)),
                               192 ** -0.5 * mscale ** 2, rtol=1e-6)
    # without scaling, the plain RoPE frequencies and 192^-1/2
    plain = cfg.with_(rope_scaling=None)
    np.testing.assert_allclose(float(att.mla_softmax_scale(plain)),
                               192 ** -0.5, rtol=1e-6)
    cos0, _ = att.rope_freqs(d, theta, pos)
    np.testing.assert_allclose(np.asarray(cos0),
                               np.cos(np.asarray(pos)[..., None] * extrap),
                               atol=2e-4)
