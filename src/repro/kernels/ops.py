"""Jitted public wrappers around the Pallas kernels.

Each wrapper decides when it is traced (:func:`use_kernels`): on a TPU
the kernels lower to Mosaic; elsewhere the dispatchers take the pure-jnp
refs, and the kernels that have no ref path run under the Pallas
interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import agg_tail as _agg
from repro.kernels import dp_clip as _dp
from repro.kernels import moe_gmm as _gmm
from repro.kernels import quantize as _q
from repro.kernels import ref as _ref
from repro.kernels import seed_reconstruct as _sr
from repro.kernels import swa_attention as _swa


def use_kernels() -> bool:
    """True when the trace in progress lowers for ONE TPU device.

    Mosaic kernels are not auto-partitioned, so under an ambient mesh of
    several devices (``jax.set_mesh``, which the grid enters for
    ``GridConfig.mesh``) the dispatchers take the jnp refs, which GSPMD
    partitions like any other op."""
    return (jax.default_backend() == "tpu"
            and jax.sharding.get_abstract_mesh().size <= 1)


# agg_tail dispatcher: the fused stats/pack/apply path engages by
# default only when BOTH hold —
#   * quantization is on (bits > 0): that is where the staged tail
#     pays >= 4 sweeps (maxabs, Q->DQ write, norm, mean) and the fused
#     int8 pack/apply collapses them. The unquantized pipelines are
#     already minimal-sweep (mean: one GEMV; clip: norm + GEMV), so
#     the fused stage orchestration is pure overhead there (measured
#     0.1-0.9x on concrete CPU buffers);
#   * the buffer has at least this many elements (K * size): below it
#     the orchestration's fixed cost loses to one well-fused XLA
#     program even with quantization on. 4M elements puts the bench's
#     300k-param smoke shapes on the staged side and every
#     >= 1M x 8-client quantized cell on the fused side.
# An EXPLICIT threshold routes purely by size (0 forces fused, a huge
# value forces staged) — that is the test/bench override knob.
AGG_FUSE_THRESHOLD = 4 << 20


@functools.partial(jax.jit, static_argnames=("window", "causal", "bq", "bk"))
def swa_attention(q, k, v, window: int = 0, causal: bool = True,
                  bq: int = 128, bk: int = 128):
    """(B, H, S, D) sliding-window flash attention (see swa_attention.py)."""
    return _swa.swa_attention(q, k, v, window=window, causal=causal,
                              bq=bq, bk=bk, interpret=not use_kernels())


@functools.partial(jax.jit, static_argnames=("clip_norm",))
def clip_accumulate(acc, x, clip_norm: float):
    """Fused DP clip-and-accumulate over flat f32 vectors."""
    return _dp.clip_accumulate(acc, x, clip_norm,
                               interpret=not use_kernels())


@functools.partial(jax.jit, static_argnames=("clip_norm",))
def flat_clip(x, clip_norm: float):
    """Per-vector L2 clip over a flat f32 delta: (clipped, pre-clip
    norm). Fused two-pass kernel on TPU, reshaped pure-jnp elsewhere."""
    if use_kernels():
        return _dp.clip_flat(x, clip_norm)
    return _ref.flat_clip_ref(x, clip_norm)


@functools.partial(jax.jit, static_argnames=("n_leaves", "bits", "block"))
def fake_quantize_flat(x, block_leaf, n_leaves: int = 0, bits: int = 8,
                       block: int = _q.BLOCK):
    """Fused per-leaf int8 fake-quantize of block-aligned flat deltas,
    (N,) or (K, N) (see quantize.py). Kernel on TPU, segment-reduction
    ref elsewhere."""
    if use_kernels():
        return _q.fake_quantize_flat(x, block_leaf, n_leaves, bits=bits,
                                     block=block)
    return _ref.fake_quantize_flat_ref(x, block_leaf, bits=bits, block=block,
                                       n_leaves=n_leaves)


@functools.partial(jax.jit, static_argnames=("leaf_id", "shape", "stddev",
                                             "dtype"))
def seed_reconstruct(seed, leaf_id: int, shape, stddev: float,
                     dtype=jnp.float32):
    """Deterministic on-chip Gaussian tensor from (seed, leaf_id)."""
    return _sr.seed_reconstruct(seed, leaf_id, shape, stddev, dtype=dtype,
                                interpret=not use_kernels())


# ---------------------------------------------------------------------------
# Fused server aggregation tail (kernels/agg_tail.py) behind a
# shape-aware dispatcher.


def _fake_quantize(mat, block_leaf, n_leaves, bits, align):
    # same dispatch as core.flat.fake_quantize, without needing a layout
    if use_kernels() and bits == 8:
        return _q.fake_quantize_flat(mat, block_leaf, n_leaves, block=align)
    return _ref.fake_quantize_flat_ref(mat, block_leaf, bits=bits,
                                       block=align, n_leaves=n_leaves)


def _staged_tail(mat, weights, block_leaf, bmask, rng, *, n_leaves,
                 align, bits, clip_norm, uniform, wsum_fixed, sigma,
                 block_denom, remask_rows, screen, constrain_fn=None):
    """The historical op-by-op tail — what the round engines ran before
    the fused path existed, verbatim. Small shapes dispatch here (and it
    is the bit-exactness oracle the fused contract is tested against)."""
    from repro.core import flat as flat_lib       # lazy: layering
    from repro.core import sanitize as sanitize_lib

    # no "route" key here: this function runs under jit, and jit outputs
    # must be arrays — agg_tail stamps the route after the call
    info = {}
    if screen is not None:
        mat, weights, sinfo = sanitize_lib.screen_rows(
            mat, weights, screen, align)
        info.update(sinfo)
    w = (weights > 0).astype(weights.dtype) if uniform else weights
    if wsum_fixed is not None:
        wsum = jnp.asarray(float(wsum_fixed), jnp.float32)
    else:
        wsum = jnp.maximum(jnp.sum(w), 1e-12)
    if remask_rows:
        K = mat.shape[0]
        mat = (mat.reshape(K, -1, align) * bmask[:, :, None]).reshape(K, -1)
    if bits > 0:
        mat = _fake_quantize(mat, block_leaf, n_leaves, bits, align)
    if clip_norm > 0:
        norms = jnp.sqrt(_ref.row_sumsq_ref(mat, chunk=align))
        w = w * jnp.minimum(1.0, clip_norm / jnp.maximum(norms, 1e-12))
        info["update_norms"] = norms
    if block_denom:
        out = flat_lib.block_masked_mean(mat, w, bmask, align)
    else:
        out = flat_lib.weighted_mean(mat, w, wsum)
    if constrain_fn is not None:
        out = constrain_fn(out)
    if sigma > 0:
        out = flat_lib.add_noise(out, sigma, rng)
    return out, info


_staged_tail_jit = jax.jit(
    _staged_tail,
    static_argnames=("n_leaves", "align", "bits", "clip_norm", "uniform",
                     "wsum_fixed", "sigma", "block_denom", "remask_rows",
                     "screen"))


def agg_route(K: int, size: int, bits: int, threshold=None) -> str:
    """The route :func:`agg_tail` takes for a (K, size) buffer:
    ``"fused"`` or ``"staged"`` (see the dispatch rule there)."""
    if threshold is None:
        fuse = bits > 0 and K * size >= AGG_FUSE_THRESHOLD
    else:
        fuse = K * size >= threshold
    return "fused" if fuse else "staged"


def agg_tail(mat, weights, *, block_leaf, n_leaves: int, align: int = 1024,
             bits: int = 0, clip_norm: float = 0.0, uniform: bool = False,
             wsum_fixed=None, sigma: float = 0.0, rng=None, bmask=None,
             remask_rows: bool = False, block_denom: bool = False,
             screen=None, constrain_fn=None, threshold=None):
    """One-sweep server aggregation tail with shape-aware dispatch.

    Computes the full post-training server pipeline over the (K, size)
    flat delta buffer — quarantine screen, per-leaf int-``bits``
    fake-quantize, per-row L2 clip folded into the weights, weighted /
    fixed-denominator mean (per-block denominator for trainability
    tiers), output sharding constraint, DP Gaussian noise — and returns
    ``(update, info)`` with the quarantine masks / norms the round
    engines report as metrics plus the dispatch ``route`` taken.

    Dispatch (shape- AND pipeline-aware): by default the fused
    stats/pack/apply path of ``kernels/agg_tail.py`` engages only for
    quantized pipelines (``bits > 0`` — where the staged tail pays its
    >= 4 sweeps) on buffers of at least :data:`AGG_FUSE_THRESHOLD`
    elements; everything else runs the staged op sequence,
    bit-identical to the historical tail. The fused path is Pallas
    kernels on TPU, python-orchestrated stage jits on concrete CPU
    buffers, the inlined ref composition under an outer trace. An
    explicit ``threshold`` routes purely by size: ``0`` forces fused,
    ``threshold > K*size`` forces staged.
    """
    kw = dict(n_leaves=n_leaves, align=align, bits=bits,
              clip_norm=clip_norm, uniform=uniform, wsum_fixed=wsum_fixed,
              sigma=sigma, block_denom=block_denom,
              remask_rows=remask_rows, screen=screen)
    K, size = mat.shape
    traced = isinstance(mat, jax.core.Tracer)
    if agg_route(K, size, bits, threshold) == "staged":
        if traced or constrain_fn is not None:
            out, info = _staged_tail(mat, weights, block_leaf, bmask, rng,
                                     constrain_fn=constrain_fn, **kw)
        else:
            out, info = _staged_tail_jit(mat, weights,
                                         jnp.asarray(block_leaf, jnp.int32),
                                         bmask, rng, **kw)
        info["route"] = "staged"
        return out, info
    if use_kernels():
        engine = "tpu"
    elif traced:
        engine = "ref"
    else:
        engine = "jit"
    return _agg.compose(mat, weights, block_leaf=block_leaf, rng=rng,
                        bmask=bmask, constrain_fn=constrain_fn,
                        engine=engine, **kw)


# ---------------------------------------------------------------------------
# Grouped matmul of an MoE layer's held experts (kernels/moe_gmm.py).
#
# Differentiable in the rows: the input gradient is the same kernel
# against the transposed experts; the experts' own gradient is plain jnp
# (``ref.moe_tgmm_ref``), which XLA drops where the experts are frozen,
# so no weight-gradient kernel runs for FedPT's frozen experts. Under
# ``jax.vmap`` (the round engine vmaps each client's step) the batch's
# problems merge into one call over shared experts: rows are permuted to
# expert-major order across the batch, so each expert's weights are read
# once per call rather than once per client.


def _gmm_call(transpose_rhs: bool):
    @jax.custom_batching.custom_vmap
    def gmm(lhs, rhs, group_sizes):
        if use_kernels():
            return _gmm.gmm(lhs, rhs, group_sizes,
                            transpose_rhs=transpose_rhs)
        return _ref.moe_gmm_ref(lhs, rhs, group_sizes, transpose_rhs)

    @gmm.def_vmap
    def _batched(axis_size, in_batched, lhs, rhs, group_sizes):
        lb, rb, gb = in_batched
        if not lb:
            lhs = jnp.broadcast_to(lhs, (axis_size,) + lhs.shape)
        if not gb:
            group_sizes = jnp.broadcast_to(
                group_sizes, (axis_size,) + group_sizes.shape)
        if rb:       # experts of their own per problem: one call each
            return jax.lax.map(lambda a: gmm(*a),
                               (lhs, rhs, group_sizes)), True
        B, m, k = lhs.shape
        dest = _merged_rows(group_sizes.astype(jnp.int32), m)
        src = jnp.zeros((B * m,), jnp.int32).at[dest].set(
            jnp.arange(B * m, dtype=jnp.int32))
        out = gmm(lhs.reshape(B * m, k)[src], rhs,
                  jnp.sum(group_sizes, axis=0))
        return out[dest].reshape(B, m, -1), True

    return gmm


def _merged_rows(group_sizes, m: int):
    """Where each row of B problems (rows sorted by group, sizes
    ``group_sizes`` (B, G)) goes in one problem of B*m rows sorted by
    group: group g's rows of problem 0, then of problem 1, ...; the rows
    past each problem's total go after every group, in problem order."""
    B, G = group_sizes.shape
    ends = jnp.cumsum(group_sizes, axis=1)
    tot = ends[:, -1]
    per_group = jnp.sum(group_sizes, axis=0)
    base = jnp.cumsum(per_group) - per_group
    before = jnp.cumsum(group_sizes, axis=0) - group_sizes
    j = jnp.arange(m, dtype=jnp.int32)
    gid = jnp.minimum(jax.vmap(lambda e: jnp.searchsorted(
        e, j, side="right"))(ends), G - 1)
    start = jnp.take_along_axis(ends - group_sizes, gid, axis=1)
    routed = (base[gid] + jnp.take_along_axis(before, gid, axis=1)
              + j[None] - start)
    spare = m - tot
    unrouted = (jnp.sum(tot) + (jnp.cumsum(spare) - spare)[:, None]
                + j[None] - tot[:, None])
    return jnp.where(j[None] < tot[:, None], routed,
                     unrouted).reshape(-1).astype(jnp.int32)


_gmm_fwd_call = _gmm_call(False)
_gmm_t_call = _gmm_call(True)


@jax.custom_vjp
def moe_gmm(lhs, rhs, group_sizes):
    """Grouped matmul: lhs (m, k) rows sorted by expert, rhs (g, k, n)
    held experts, group_sizes (g,) rows per expert -> (m, n) float32, rows
    past ``sum(group_sizes)`` zero. Pallas kernel on TPU (named
    ``moe_gmm``), ``ref.moe_gmm_ref`` elsewhere."""
    return _gmm_fwd_call(lhs, rhs, group_sizes)


def _moe_gmm_fwd(lhs, rhs, group_sizes):
    return _gmm_fwd_call(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _moe_gmm_bwd(res, grad):
    lhs, rhs, group_sizes = res
    dlhs = _gmm_t_call(grad, rhs, group_sizes).astype(lhs.dtype)
    drhs = _ref.moe_tgmm_ref(lhs, grad, group_sizes,
                             rhs.shape[0]).astype(rhs.dtype)
    return dlhs, drhs, None


moe_gmm.defvjp(_moe_gmm_fwd, _moe_gmm_bwd)
