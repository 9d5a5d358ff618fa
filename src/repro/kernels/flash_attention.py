"""Causal flash attention and its backward (Pallas TPU kernels).

``flash_fwd`` computes softmax attention block by block with the online
softmax: the running max, sum and output accumulator of a block of
queries stay in VMEM across the key blocks, so no score or probability
ever reaches HBM. It also returns each query's log-sum-exp, from which
``flash_bwd`` recomputes the probabilities block by block: one kernel
sums dq over the key blocks of a query block (``flash_dq``), the other
dk and dv over the query blocks of a key block (``flash_dkv``), with
``delta = rowsum(dO * O)`` standing in for the softmax's Jacobian.

Blocks that the causal mask (and, in the forward, an optional sliding
window) hides entirely are skipped: the kernel body does not run, and
the index maps clamp the skipped steps to a live block, so a skipped
block fetches nothing new. q and k share a head dim, v may have its
own (MLA: 192 and 128). Contractions run at the ambient
``jax.default_matmul_precision`` (``highest`` gives Mosaic's float32
contraction). The kernels are named ``flash_fwd``, ``flash_dq`` and
``flash_dkv`` in the HLO and in the device trace.

Grid: (batch*heads, outer blocks, inner blocks), the inner axis the one
summed over.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME_FWD, NAME_DQ, NAME_DKV = "flash_fwd", "flash_dq", "flash_dkv"
NEG_INF = -1e30
LANES = 128         # the log-sum-exp and delta are stored lane-replicated


def _live(q_start, bq: int, k_start, bk: int, window: int):
    """Whether any key of the block at k_start is visible to a query of
    the block at q_start: k <= q and, with a window, q - k < window."""
    live = k_start <= q_start + bq - 1
    if window > 0:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)
    return live


def _kv_map(bq: int, bk: int, nk: int, window: int):
    """Index map of a key block for grid step (b, qi, ki): ki clamped to
    the key blocks that query block qi sees."""
    def index(b, qi, ki):
        q_start = qi * bq
        first = jnp.maximum((q_start - window + 1) // bk, 0) if window else 0
        last = jnp.minimum((q_start + bq - 1) // bk, nk - 1)
        return b, jnp.clip(ki, first, last), 0
    return index


def _scores(q, k, scale: float, q_start, k_start, *, seq: int,
            window: int):
    """Scaled scores of a (bq, d) query and (bk, d) key block, and the
    mask of the visible ones."""
    bq, bk = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.logical_and(kpos < seq, qpos >= kpos)
    if window > 0:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    return s, mask


def _probs(q, k, lse, scale, q_start, k_start, **kw):
    """The softmax probabilities of one block, recomputed from lse."""
    s, mask = _scores(q, k, scale, q_start, k_start, **kw)
    return jnp.where(mask, jnp.exp(s - lse[:, :1]), 0.0)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale: float, bq: int, bk: int, **kw):
    qi, ki = pl.program_id(1), pl.program_id(2)
    q_start, k_start = qi * bq, ki * bk

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_live(q_start, bq, k_start, bk, kw["window"]))
    def _body():
        v = v_ref[0]
        s, mask = _scores(q_ref[0], k_ref[0], scale, q_start, k_start, **kw)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_ref[...] + jnp.log(l), (bq, LANES))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale: float, bq: int, bk: int, **kw):
    qi, ki = pl.program_id(1), pl.program_id(2)
    q_start, k_start = qi * bq, ki * bk

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_live(q_start, bq, k_start, bk, kw["window"]))
    def _body():
        k, v, do = k_ref[0], v_ref[0], do_ref[0]
        p = _probs(q_ref[0], k, lse_ref[0], scale, q_start, k_start, **kw)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        acc_ref[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale: float, bq: int, bk: int,
                **kw):
    ki, qi = pl.program_id(1), pl.program_id(2)
    q_start, k_start = qi * bq, ki * bk
    t0 = (((0,), (0,)), ((), ()))       # contract the query axis

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_live(q_start, bq, k_start, bk, kw["window"]))
    def _body():
        q, v, do = q_ref[0], v_ref[0], do_ref[0]
        p = _probs(q, k_ref[0], lse_ref[0], scale, q_start, k_start, **kw)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, t0, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, t0, preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _padded(x, sp: int):
    """(B, H, S, D) -> (B*H, sp, D), zero rows appended."""
    B, H, S, D = x.shape
    if sp != S:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, sp - S), (0, 0)))
    return x.reshape(B * H, sp, D)


def _geometry(S: int, bq: int, bk: int):
    bq, bk = min(bq, S), min(bk, S)
    blk = max(bq, bk)
    return bq, bk, -(-S // blk) * blk


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def flash_fwd(q, k, v, *, scale=None, window: int = 0, bq: int = 128,
              bk: int = 128, interpret: bool = False):
    """q, k: (B, H, S, D); v: (B, H, S, Dv) -> (out (B, H, S, Dv),
    lse (B*H, Sp, LANES) float32), Sp being S padded to a block multiple.

    ``scale`` is the softmax scale (default D^-1/2); ``window`` > 0 lets
    a query see only the ``window`` latest keys up to itself."""
    B, H, S, D = q.shape
    Dv = v.shape[-1]
    if scale is None:
        scale = D ** -0.5
    bq, bk, Sp = _geometry(S, bq, bk)
    nk = Sp // bk
    kw = dict(scale=float(scale), bq=bq, bk=bk, seq=S, window=window)
    kv_map = _kv_map(bq, bk, nk, window)

    def q_map(b, qi, ki):
        return b, qi, 0

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, **kw),
        name=NAME_FWD,
        grid=(B * H, Sp // bq, nk),
        in_specs=[pl.BlockSpec((1, bq, D), q_map),
                  pl.BlockSpec((1, bk, D), kv_map),
                  pl.BlockSpec((1, bk, Dv), kv_map)],
        out_specs=[pl.BlockSpec((1, bq, Dv), q_map),
                   pl.BlockSpec((1, bq, LANES), q_map)],
        out_shape=[jax.ShapeDtypeStruct((B * H, Sp, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B * H, Sp, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),   # running max
                        pltpu.VMEM((bq, 1), jnp.float32),   # running sum
                        pltpu.VMEM((bq, Dv), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(_padded(q, Sp), _padded(k, Sp), _padded(v, Sp))
    return o.reshape(B, H, Sp, Dv)[:, :, :S], lse


def flash_bwd(q, k, v, o, lse, do, *, scale=None, bq: int = 128,
              bk: int = 128, interpret: bool = False):
    """The gradients (dq, dk, dv) of :func:`flash_fwd`'s output (with no
    window), given its ``o`` and ``lse`` and the output gradient ``do``
    (B, H, S, Dv)."""
    B, H, S, D = q.shape
    Dv = v.shape[-1]
    if scale is None:
        scale = D ** -0.5
    bq, bk, Sp = _geometry(S, bq, bk)
    nq, nk = Sp // bq, Sp // bk
    kw = dict(scale=float(scale), bq=bq, bk=bk, seq=S, window=0)
    qf, kf, vf, dof = (_padded(x, Sp) for x in (q, k, v, do))
    delta = jnp.sum(_padded(o, Sp).astype(jnp.float32)
                    * dof.astype(jnp.float32), axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (B * H, Sp, LANES))
    kv_map = _kv_map(bq, bk, nk, 0)

    def q_map(b, qi, ki):
        return b, qi, 0

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        name=NAME_DQ,
        grid=(B * H, nq, nk),
        in_specs=[pl.BlockSpec((1, bq, D), q_map),
                  pl.BlockSpec((1, bk, D), kv_map),
                  pl.BlockSpec((1, bk, Dv), kv_map),
                  pl.BlockSpec((1, bq, Dv), q_map),
                  pl.BlockSpec((1, bq, LANES), q_map),
                  pl.BlockSpec((1, bq, LANES), q_map)],
        out_specs=pl.BlockSpec((1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    def qo_map(b, ki, qi):
        # the query blocks from the one holding key ki * bk on see it
        return b, jnp.clip(qi, (ki * bk) // bq, nq - 1), 0

    def k_map(b, ki, qi):
        return b, ki, 0

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        name=NAME_DKV,
        grid=(B * H, nk, nq),
        in_specs=[pl.BlockSpec((1, bq, D), qo_map),
                  pl.BlockSpec((1, bk, D), k_map),
                  pl.BlockSpec((1, bk, Dv), k_map),
                  pl.BlockSpec((1, bq, Dv), qo_map),
                  pl.BlockSpec((1, bq, LANES), qo_map),
                  pl.BlockSpec((1, bq, LANES), qo_map)],
        out_specs=[pl.BlockSpec((1, bk, D), k_map),
                   pl.BlockSpec((1, bk, Dv), k_map)],
        out_shape=[jax.ShapeDtypeStruct((B * H, Sp, D), k.dtype),
                   jax.ShapeDtypeStruct((B * H, Sp, Dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, Dv), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    def back(x, d):
        return x.reshape(B, H, Sp, d)[:, :, :S]

    return back(dq, D), back(dk, D), back(dv, Dv)
