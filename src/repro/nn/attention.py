"""Attention: RoPE, GQA multi-head attention with chunked online-softmax
(flash-style, bounded memory at 32k+ sequence lengths), sliding windows,
and MLA (DeepSeek-V2 multi-head latent attention).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops
from repro.nn import basic
from repro.configs.base import ModelConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE


def rope_freqs(head_dim: int, theta: float, positions, scaling=None):
    """positions: (..., seq) int32 -> cos/sin (..., seq, head_dim//2).
    ``scaling``: a ``configs.base.RopeScaling`` for YaRN (see
    :func:`yarn_inv_freq`), else plain RoPE."""
    half = head_dim // 2
    if scaling is None:
        inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
        mscale = 1.0
    else:
        inv = jnp.asarray(yarn_inv_freq(head_dim, theta, scaling))
        mscale = (yarn_mscale(scaling.factor, scaling.mscale)
                  / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    if mscale == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * mscale, jnp.sin(ang) * mscale


# YaRN (Peng et al., arXiv:2309.00071, §3.2-3.4) as DeepSeek-V2 applies it
# to its rope dims (d = qk_rope_head_dim, theta, scale factor s, original
# context L). For frequency index i < d/2:
#
#   extrap_i = theta^(-2i/d),   interp_i = extrap_i / s
#   low  = max(floor(d ln(L / (beta_fast 2 pi)) / (2 ln theta)), 0)
#   high = min(ceil (d ln(L / (beta_slow 2 pi)) / (2 ln theta)), d - 1)
#   m_i  = 1 - clip((i - low) / (high - low), 0, 1)
#   inv_freq_i = interp_i (1 - m_i) + extrap_i m_i
#
# (a dimension that turns more than beta_fast times over L keeps its
# frequency, one that turns less than beta_slow times is interpolated by
# s, a linear ramp between). cos and sin are scaled by
# yarn_mscale(s, mscale) / yarn_mscale(s, mscale_all_dim), and the
# softmax scale (qk_head_dim^-1/2) by yarn_mscale(s, mscale_all_dim)^2,
# where yarn_mscale(s, m) = 0.1 m ln s + 1 for s > 1, else 1 (the
# paper's sqrt(1/t) = 0.1 ln s + 1 at m = 1).


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling) -> np.ndarray:
    """The (dim // 2,) float32 YaRN inverse frequencies (equations above)."""
    def correction(rotations):
        return (dim * math.log(scaling.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction(scaling.beta_fast)), 0)
    high = min(math.ceil(correction(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float32)
    extrap = 1.0 / theta ** (2 * i / dim)
    interp = extrap / scaling.factor
    m = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return (interp * (1 - m) + extrap * m).astype(np.float32)


def softmax_scale(head_dim: int) -> float:
    """head_dim^-1/2, rounded to float32 as the scores are."""
    return float(np.float32(1.0) / np.sqrt(np.float32(head_dim)))


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """MLA's softmax scale: (qk_nope + qk_rope)^-1/2, times
    yarn_mscale(s, mscale_all_dim)^2 under YaRN."""
    scale = np.float32(softmax_scale(cfg.qk_nope_head_dim
                                     + cfg.qk_rope_head_dim))
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale = scale * np.float32(
            yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2)
    return float(scale)


def apply_rope(x, cos, sin):
    """x: (..., seq, heads, head_dim); cos/sin: (..., seq, head_dim//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x.dtype)  # broadcast over heads
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# GQA projections


def init_attention(seed, path, cfg: ModelConfig, dtype):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    b = cfg.qkv_bias
    return {
        "wq": basic.init_dense(seed, f"{path}/wq", d, h * hd, dtype, bias=b),
        "wk": basic.init_dense(seed, f"{path}/wk", d, kv * hd, dtype, bias=b),
        "wv": basic.init_dense(seed, f"{path}/wv", d, kv * hd, dtype, bias=b),
        "wo": basic.init_dense(seed, f"{path}/wo", h * hd, d, dtype, bias=False),
    }


def qkv_project(x, p, cfg: ModelConfig):
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    cd = cfg.cdtype
    q = basic.dense(x, p["wq"], cd).reshape(b, s, h, hd)
    k = basic.dense(x, p["wk"], cd).reshape(b, s, kv, hd)
    v = basic.dense(x, p["wv"], cd).reshape(b, s, kv, hd)
    return q, k, v


# ---------------------------------------------------------------------------
# Chunked (flash-style) causal attention.
#
# Memory stays O(seq * chunk) instead of O(seq^2): we scan over KV chunks
# carrying the online-softmax running (max, sum, acc). Masked positions
# contribute exp(-inf)=0, but XLA still computes every chunk, the ones the
# causal mask or a window hides entirely too. On one TPU, plain causal
# self-attention takes the flash kernels of kernels/flash_attention.py
# instead (forward and backward, masked blocks skipped, scores kept in
# VMEM): see ``takes_kernel``.


def _attend_chunk(q, k, v, qpos, kpos, window: int, softcap: float, scale,
                  causal: bool, prefix_len: int):
    """q:(b,h,sq,d) k,v:(b,h,sc,d) -> logits-masked scores (b,h,sq,sc)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    if causal:
        mask = qpos[:, None] >= kpos[None, :]
        if prefix_len > 0:  # bidirectional prefix (PaliGemma-style)
            mask = mask | (kpos[None, :] < prefix_len)
        if window > 0:
            mask = mask & (qpos[:, None] - kpos[None, :] < window)
    else:
        mask = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    return jnp.where(mask[None, None], s, NEG_INF)


def takes_kernel(sq: int, skv: int, hd: int, dv: int, cfg: ModelConfig,
                 causal: bool, prefix_len: int) -> bool:
    """Whether :func:`flash_attention` runs the flash kernels
    (``kernel_ops.causal_attention``) rather than the chunked scan: on
    one TPU, for causal self-attention with no window, softcap or
    bidirectional prefix, over a sequence and head dims the kernels
    tile."""
    return (kernel_ops.use_kernels() and causal and cfg.sliding_window == 0
            and not cfg.attn_logit_softcap and prefix_len == 0 and sq == skv
            and kernel_ops.flash_tiles(sq, hd, dv))


def flash_attention(q, k, v, cfg: ModelConfig, chunk: int = 512,
                    causal: bool = True, prefix_len: int = 0,
                    scale: Optional[float] = None):
    """Causal (optionally sliding-window) attention.

    q: (b, sq, h, hd);  k, v: (b, skv, kv_heads, hd_k); v may have a
    different per-head dim than q/k (MLA).
    scale: the softmax scale (default hd^-1/2).
    Returns (b, sq, h, dv).
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    kvh = k.shape[2]
    dv = v.shape[3]
    rep = h // kvh
    if scale is None:
        scale = softmax_scale(hd)
    window = cfg.sliding_window

    qh = q.transpose(0, 2, 1, 3)  # b,h,sq,hd
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    if rep > 1:
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    if takes_kernel(sq, skv, hd, dv, cfg, causal, prefix_len):
        out = kernel_ops.causal_attention(qh, kh, vh, scale)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)

    nchunks = max(1, (skv + chunk - 1) // chunk)
    pad = nchunks * chunk - skv
    if pad:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kh = kh.reshape(b, h, nchunks, chunk, hd).transpose(2, 0, 1, 3, 4)
    vh = vh.reshape(b, h, nchunks, chunk, dv).transpose(2, 0, 1, 3, 4)

    qpos = jnp.arange(sq)

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, ci = xs
        kpos = ci * chunk + jnp.arange(chunk)
        valid = kpos < skv
        s = _attend_chunk(qh, kc, vc, qpos, kpos, window, cfg.attn_logit_softcap,
                          scale, causal, prefix_len)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kh, vh, jnp.arange(nchunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention (arXiv:2405.04434).
#
# KV is compressed to a kv_lora_rank latent c_kv plus a shared rope key
# k_pe, and up-projected to per-head keys and values.


def init_mla(seed, path, cfg: ModelConfig, dtype):
    d = cfg.d_model
    h = cfg.num_heads
    qn, qr, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    qlr = cfg.q_lora_rank
    p = {
        "wkv_a": basic.init_dense(seed, f"{path}/wkv_a", d, r + qr, dtype),
        "kv_norm": basic.init_norm(seed, f"{path}/kv_norm", r, dtype, "rmsnorm"),
        "wk_b": basic.init_dense(seed, f"{path}/wk_b", r, h * qn, dtype),
        "wv_b": basic.init_dense(seed, f"{path}/wv_b", r, h * vd, dtype),
        "wo": basic.init_dense(seed, f"{path}/wo", h * vd, d, dtype),
    }
    if qlr > 0:
        p["wq_a"] = basic.init_dense(seed, f"{path}/wq_a", d, qlr, dtype)
        p["q_norm"] = basic.init_norm(seed, f"{path}/q_norm", qlr, dtype, "rmsnorm")
        p["wq_b"] = basic.init_dense(seed, f"{path}/wq_b", qlr, h * (qn + qr), dtype)
    else:
        p["wq"] = basic.init_dense(seed, f"{path}/wq", d, h * (qn + qr), dtype)
    return p


def mla_qkv(x, p, cfg: ModelConfig, positions):
    """Full (non-absorbed) MLA.

    Returns q, k, v shaped (b, s, h, dim) with rope applied; k/v have
    per-head dims qn+qr and v_head_dim.
    """
    b, s, _ = x.shape
    h = cfg.num_heads
    qn, qr, vd, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    cd = cfg.cdtype

    if "wq_a" in p:
        qc = basic.dense(x, p["wq_a"], cd)
        qc = basic.rmsnorm(qc, p["q_norm"]["scale"])
        q = basic.dense(qc, p["wq_b"], cd).reshape(b, s, h, qn + qr)
    else:
        q = basic.dense(x, p["wq"], cd).reshape(b, s, h, qn + qr)

    kv = basic.dense(x, p["wkv_a"], cd)
    c_kv, k_pe = kv[..., :r], kv[..., r:]
    c_kv = basic.rmsnorm(c_kv, p["kv_norm"]["scale"])
    k_nope = basic.dense(c_kv, p["wk_b"], cd).reshape(b, s, h, qn)
    v = basic.dense(c_kv, p["wv_b"], cd).reshape(b, s, h, vd)

    cos, sin = rope_freqs(qr, cfg.rope_theta, positions, cfg.rope_scaling)
    q_nope, q_pe = q[..., :qn], q[..., qn:]
    q_pe = apply_rope(q_pe, cos, sin)
    k_pe_r = apply_rope(k_pe[..., None, :], cos, sin)  # single shared rope head
    k_pe_b = jnp.broadcast_to(k_pe_r, (b, s, h, qr))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe_b], axis=-1)
    return q, k, v
