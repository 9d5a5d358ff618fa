"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references: each kernel's test sweeps shapes /
dtypes and asserts allclose (or, for the PRNG kernel, distributional and
determinism properties) against these functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def swa_attention_ref(q, k, v, window: int, causal: bool = True):
    """Dense sliding-window attention oracle.

    q, k, v: (B, H, S, D). window: number of past positions visible
    (window <= 0 means full causal attention). Returns (B, H, S, D) f32.
    """
    B, H, S, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask = qpos >= kpos
    if window > 0:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def dp_clip_accumulate_ref(acc, x, clip_norm: float):
    """Oracle for the fused clip-and-accumulate: acc + x * min(1, C/||x||).

    acc, x: (N,) float32. Returns (new_acc (N,), norm scalar).
    """
    nrm = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(nrm, 1e-12))
    return acc + x.astype(jnp.float32) * scale, nrm


# ---------------------------------------------------------------------------
# Flat-buffer aggregation fallbacks (core/flat.py dispatches here off-TPU).
#
# Every reduction is expressed over a (rows, chunk) block view instead of
# a (C, N) row sweep: XLA:CPU lowers the former to a vectorized loop and
# the latter to a scalar one (~20x slower at N=10^7), and the block view
# is also exactly the layout the TPU kernels tile.


def _chunked(x, chunk: int):
    """(..., N) -> (..., N//chunk, chunk); N must divide (FlatLayout
    aligns it) — falls back to one chunk otherwise."""
    n = x.shape[-1]
    if chunk <= 1 or n == 0 or n % chunk:
        return x.reshape(x.shape[:-1] + (1, n))
    return x.reshape(x.shape[:-1] + (n // chunk, chunk))


# rows are processed in a few large independent slices: at >=10^7
# elements per operand XLA:CPU schedules the slices measurably better
# than one monolithic cascade (and it bounds intermediate live range)
_ROW_CHUNKS = 4
_CHUNK_MIN = 1 << 20


def _rowwise(x3, one_chunk, nchunks: int = _ROW_CHUNKS):
    """Apply `one_chunk` ((rows, width) -> (rows,)) over the trailing
    axis of (..., width), slicing the flattened row dim into a few
    large independent chunks. The chunk count never changes the result
    (each row reduces independently); it only bounds the live range of
    the halving cascade's intermediates."""
    width = x3.shape[-1]
    rows = x3.reshape(-1, width)
    n = rows.shape[0]
    if n * width <= _CHUNK_MIN or n < nchunks:
        return one_chunk(rows).reshape(x3.shape[:-1])
    step = -(-n // nchunks)
    parts = [one_chunk(rows[i:i + step]) for i in range(0, n, step)]
    return jnp.concatenate(parts).reshape(x3.shape[:-1])


def _sumsq_chunk(rows):
    """sum(x^2) over each row, by log-halving: pairwise elementwise adds
    stream at memory bandwidth, where XLA:CPU's reduce op runs a ~5x
    slower scalar loop at these shapes. The first halving fuses the
    squaring (and any int8->f32 cast)."""
    h = rows.shape[-1] // 2
    if rows.shape[-1] % 2 or h == 0:
        rows = rows.astype(jnp.float32)
        return jnp.sum(rows * rows, axis=-1)
    a = rows[..., :h].astype(jnp.float32)
    b = rows[..., h:].astype(jnp.float32)
    y = a * a + b * b
    while y.shape[-1] > 1 and y.shape[-1] % 2 == 0:
        h = y.shape[-1] // 2
        y = y[..., :h] + y[..., h:]
    return jnp.sum(y, axis=-1)


_ABS_MASK_I32 = np.int32(0x7FFFFFFF)


def _maxabs_chunk(rows):
    """max|x| over each row, same log-halving trick.

    f32 rows run on the bitcast int32 view: clearing the sign bit of an
    IEEE f32 gives a pattern that orders exactly like |x| for finite
    values, and every NaN payload orders above +Inf, so integer max IS
    max|x| with NaN propagation intact (possibly a different NaN
    payload, never a lost NaN). XLA:CPU's integer max streams ~1.4x
    faster than the float cascade (no NaN-ordering blend per element).
    """
    h = rows.shape[-1] // 2
    if rows.shape[-1] % 2 or h == 0:
        return jnp.max(jnp.abs(rows), axis=-1)
    if rows.dtype == jnp.float32:
        z = jax.lax.bitcast_convert_type(rows, jnp.int32) & _ABS_MASK_I32
        while z.shape[-1] > 1 and z.shape[-1] % 2 == 0:
            h = z.shape[-1] // 2
            z = jnp.maximum(z[..., :h], z[..., h:])
        return jax.lax.bitcast_convert_type(jnp.max(z, axis=-1), jnp.float32)
    y = jnp.maximum(jnp.abs(rows[..., :h]), jnp.abs(rows[..., h:]))
    while y.shape[-1] > 1 and y.shape[-1] % 2 == 0:
        h = y.shape[-1] // 2
        y = jnp.maximum(y[..., :h], y[..., h:])
    return jnp.max(y, axis=-1)


def _last_axis_sumsq(x3):
    return _rowwise(x3, _sumsq_chunk)


def _last_axis_maxabs(x3):
    return _rowwise(x3, _maxabs_chunk)


def flat_sumsq_ref(x, chunk: int = 1024):
    """Sum of squares of a 1-D flat vector via a two-stage reduction."""
    return jnp.sum(_last_axis_sumsq(_chunked(x.astype(jnp.float32), chunk)))


def row_sumsq_ref(mat, chunk: int = 1024):
    """(C, N) -> (C,) per-row sum of squares, one fused pass."""
    part = _last_axis_sumsq(_chunked(mat.astype(jnp.float32), chunk))
    return jnp.matmul(part, jnp.ones((part.shape[-1],), jnp.float32))


def flat_clip_ref(x, clip_norm: float, chunk: int = 1024):
    """Oracle for the flat per-vector clip: x * min(1, C/||x||).
    Returns (clipped, pre-clip norm)."""
    nrm = jnp.sqrt(flat_sumsq_ref(x, chunk))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(nrm, 1e-12))
    return x.astype(jnp.float32) * scale, nrm


def fake_quantize_flat_ref(mat, block_leaf, bits: int = 8,
                           block: int = 1024, n_leaves: int = 0):
    """Per-leaf symmetric int-k fake-quantize over a block-aligned flat
    buffer. ``mat``: (..., N) with N = len(block_leaf) * block; each
    block belongs to one leaf (block_leaf: (K,) int). Matches
    `compress.quantize_leaf` + `dequantize_leaf` exactly: scale is the
    leaf max-abs / qmax (zero padding never raises a max).

    ``n_leaves`` must be passed when ``block_leaf`` is a traced value
    (e.g. through a jitted wrapper); with a concrete map it is derived.
    """
    qmax = 2.0 ** (bits - 1) - 1
    if not n_leaves:
        n_leaves = int(np.max(np.asarray(block_leaf))) + 1 \
            if len(block_leaf) else 0
    block_leaf = jnp.asarray(block_leaf, jnp.int32)
    xc = _chunked(mat.astype(jnp.float32), block)      # (..., K, block)
    bmax = _last_axis_maxabs(xc)                       # (..., K)
    lmax = jax.ops.segment_max(jnp.moveaxis(bmax, -1, 0), block_leaf,
                               num_segments=n_leaves)  # (L, ...)
    scales = jnp.maximum(jnp.moveaxis(lmax, 0, -1), 1e-12) / qmax
    sblock = jnp.take(scales, block_leaf, axis=-1)     # (..., K)
    q = jnp.clip(jnp.round(xc / sblock[..., None]), -qmax, qmax)
    return (q * sblock[..., None]).reshape(mat.shape)


# ---------------------------------------------------------------------------
# Fused aggregation-tail stages (kernels/agg_tail.py's oracles).
#
# The server tail (screen / quantize / clip / mean / noise) fuses into at
# most three reads of the (K, size) buffer plus one (size,) write:
#
#   stats: one f32 read  -> per-(row, block) max-abs (+ sum-of-squares,
#          when the quarantine screen needs raw row norms);
#   pack:  one f32 read  -> int8 codes, plus the quantized row sumsq the
#          clip stage folds into the aggregation weights;
#   apply: one int8 (or f32) read -> weighted mean over rows, pre-drawn
#          DP noise added in the same pass, one (size,) write.
#
# Each stage is deliberately a SEPARATE function: composing them into one
# XLA:CPU program costs +300-650ms at 10M params x 16 clients (the fusion
# pass re-materializes producers across stage boundaries), so
# kernels/agg_tail.py jits the stages individually and orchestrates them
# from Python when handed concrete buffers.
#
# `apply` has two formulations with different contracts:
#   * agg_apply_exact_ref — a column-chunked GEMV, bitwise identical to
#     weighted_mean / block_masked_mean on the same operand (chunking a
#     GEMV along columns never reorders the K-axis accumulation);
#   * agg_apply_ref — a row-at-a-time accumulation starting from the
#     noise vector, ~2x faster from int8 codes but only fp-round-off
#     close to the GEMV. The dispatcher uses it exactly where the
#     staged-vs-fused contract is already fp-level (quantize + clip/DP).

# the stats/pack sweeps prefer finer row slices than the default
# _ROW_CHUNKS=4: at (K * num_blocks, 1024) granularity, ~L2-sized slices
# keep the halving cascade's intermediates cache-resident (~25% faster
# at 10M x 16 than 4 slices)
_STATS_ROW_CHUNKS = 128


def agg_block_stats_ref(mat, block: int = 1024, with_sumsq: bool = False,
                        row_chunks: int = _STATS_ROW_CHUNKS):
    """(K, N) -> per-(row, block) max-abs, optionally with per-(row,
    block) sum-of-squares, in one read of the buffer.

    ``bmax`` feeds the per-leaf quantization scales (segment-max over the
    block->leaf map) and the row-finiteness flag (a row's max-abs is NaN
    iff the row has a NaN, +Inf iff its largest magnitude is Inf).
    ``bsumsq @ ones`` equals ``row_sumsq_ref`` bitwise — per-block sums
    are row-local, so the fused screen's raw norms match
    ``core.sanitize.screen_rows``'s separate sweep exactly on finite
    rows."""
    x3 = _chunked(mat.astype(jnp.float32), block)
    bmax = _rowwise(x3, _maxabs_chunk, nchunks=row_chunks)
    if not with_sumsq:
        return bmax, None
    bsumsq = _rowwise(x3, _sumsq_chunk, nchunks=row_chunks)
    return bmax, bsumsq


def agg_scales_ref(bmax, block_leaf, bits: int, n_leaves: int):
    """Per-(row, block) quantization scales from the stats pass.

    Exactly `fake_quantize_flat_ref`'s scale rule (leaf max-abs / qmax
    with the 1e-12 floor), so packed codes dequantize bit-for-bit to the
    staged fake-quantize output."""
    qmax = 2.0 ** (bits - 1) - 1
    block_leaf = jnp.asarray(block_leaf, jnp.int32)
    lmax = jax.ops.segment_max(jnp.moveaxis(bmax, -1, 0), block_leaf,
                               num_segments=n_leaves)
    scales = jnp.maximum(jnp.moveaxis(lmax, 0, -1), 1e-12) / qmax
    return jnp.take(scales, block_leaf, axis=-1)          # (K, NB)


def agg_pack_ref(mat, sblock, bits: int, block: int = 1024):
    """Quantize to int8 codes: (K, N), (K, NB) -> (K, NB, block) int8.

    The int8-out store is the point: the apply stage then reads 4x fewer
    bytes, and ``codes * sblock[..., None]`` reconstructs the staged
    fake-quantize output bit-for-bit (same divide, same round, same
    clip)."""
    qmax = 2.0 ** (bits - 1) - 1
    x3 = _chunked(mat.astype(jnp.float32), block)
    return jnp.clip(jnp.round(x3 / sblock[..., None]),
                    -qmax, qmax).astype(jnp.int8)


def agg_quant_sumsq_ref(q, sblock):
    """Quantized per-row sum of squares from int8 codes: sum_b s_b^2 *
    sum(q_b^2). Equal in value to row_sumsq of the dequantized buffer
    (fp-round-off: the per-block scale factors out of the block sum), at
    int8 read cost instead of another f32 sweep."""
    return quant_sumsq_fold(_last_axis_sumsq(q), sblock)


def quant_sumsq_fold(qsq, sblock):
    """(K, NB) per-block sums of squared codes -> (K,) quantized row
    sumsq. The block sums are exact integers in f32, so the Pallas pack
    and this oracle agree bitwise through this shared fold."""
    return jnp.einsum("kb,kb->k", qsq, sblock.astype(jnp.float32) ** 2)


def agg_apply_ref(q, coeff, noise=None, block: int = 1024):
    """Weighted accumulation over rows, one read + one write.

    q: (K, NB, block) int8 codes or f32 blocks; coeff: (K, NB) per-(row,
    block) coefficients with everything folded in (dequantize scale x
    clip scale x weight / denominator); noise: optional pre-drawn (N,)
    vector the accumulator STARTS from, so DP noise costs no extra
    sweep. Row-at-a-time keeps one f32 accumulator hot instead of
    materializing a (K, NB, block) dequantized copy."""
    K, NB = coeff.shape
    if noise is not None:
        acc = noise.reshape(NB, block).astype(jnp.float32)
    else:
        acc = jnp.zeros((NB, block), jnp.float32)
    for k in range(K):
        acc = acc + q[k].astype(jnp.float32) * coeff[k][:, None]
    return acc.reshape(-1)


def agg_apply_exact_ref(x3, weights, sblock=None, wsum=None, block_den=None,
                        noise=None, cols: int = 1024):
    """Column-chunked weighted-mean GEMV, bitwise identical to the staged
    mean on the same operand.

    x3: (K, NB, block) f32 blocks or int8 codes (with ``sblock`` (K, NB)
    to dequantize each column chunk in registers — the reconstruction is
    bitwise the staged fake-quantize output). Each output element is the
    same K-length dot ``jnp.matmul(weights, mat)`` computes — chunking
    along columns never touches the K accumulation order — and the
    ``/wsum`` (or per-block ``/block_den``, repeated to elements) and
    ``+noise`` tails are elementwise, so chunk-then-divide equals
    divide-then-chunk bit for bit. This is the quantize-only route's
    bitwise staged-vs-fused contract (test-enforced)."""
    K, NB, block = x3.shape
    outs = []
    for i in range(0, NB, cols):
        part = x3[:, i:i + cols].astype(jnp.float32)
        if sblock is not None:
            part = part * sblock[:, i:i + cols, None]
        t = jnp.matmul(weights.astype(jnp.float32), part.reshape(K, -1))
        if block_den is not None:
            t = t / jnp.repeat(block_den[i:i + cols], block)
        elif wsum is not None:
            t = t / wsum
        outs.append(t)
    out = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
    if noise is not None:
        out = out + noise
    return out


def seed_reconstruct_ref(seed: int, shape, stddev: float):
    """Distributional reference for the TPU-PRNG Gaussian generator.

    NOT bit-identical to the Pallas kernel (different PRNG); used for
    moment / independence checks. Determinism of the kernel itself is
    asserted kernel-vs-kernel.
    """
    return stddev * jax.random.normal(jax.random.key(seed), shape,
                                      jnp.float32)


# ---------------------------------------------------------------------------
# Grouped matmul of the held experts (kernels/moe_gmm.py)


def _row_groups(group_sizes, m: int):
    """The group of each of ``m`` rows sorted by group; rows past the
    groups' total get ``len(group_sizes)``."""
    return jnp.searchsorted(jnp.cumsum(group_sizes), jnp.arange(m),
                            side="right")


def moe_gmm_ref(lhs, rhs, group_sizes, transpose_rhs: bool = False):
    """Oracle for ``moe_gmm.gmm``: each row times its group's matrix (one
    masked matmul per group, whose other rows add exact zeros); rows past
    the groups' total are zero. float32 out."""
    gid = _row_groups(group_sizes, lhs.shape[0])[:, None]
    lhs = lhs.astype(jnp.float32)
    out = None
    for g in range(rhs.shape[0]):
        w = rhs[g].astype(jnp.float32)
        part = jnp.where(gid == g, lhs, 0.0) @ (w.T if transpose_rhs else w)
        out = part if out is None else out + part
    return out


def moe_tgmm_ref(lhs, grad, group_sizes, groups: int):
    """The weight gradient of ``moe_gmm_ref``: (groups, k, n) with
    ``[g] = lhs[rows of g].T @ grad[rows of g]``; rows in no group may
    hold anything, even NaN."""
    gid = _row_groups(group_sizes, lhs.shape[0])[:, None]
    return jnp.stack([jnp.where(gid == g, lhs, 0.0).T
                      @ jnp.where(gid == g, grad, 0.0)
                      for g in range(groups)])
