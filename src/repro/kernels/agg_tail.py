"""Fused one-sweep server aggregation tail: stats -> pack -> apply.

The server tail (quarantine screen / int8 fake-quantize / L2 clip fold /
weighted mean / DP Gaussian noise) used to be five separate sweeps over
the (K, size) client-delta buffer. This module runs it as at most three
reads plus one (size,) write:

1. **stats** — per-(row, block) max-abs and sum-of-squares in one read.
   The max-abs feeds the per-leaf quantization scales AND the row
   finiteness flag (a row's max-abs is NaN iff the row holds a NaN, +Inf
   iff its largest magnitude is Inf); the sum-of-squares reduces to the
   raw row norms the quarantine screen needs — bitwise identical to
   ``core.sanitize.screen_rows``'s separate norm sweep, which the fused
   route therefore deletes.
2. **pack** — one read producing int8 codes (4x fewer bytes for the
   apply read) plus the quantized row sum-of-squares the clip stage
   folds into the aggregation weights.
3. **apply** — one read of the codes accumulating the weighted mean,
   with the pre-drawn (size,) DP noise vector as the accumulator's
   starting value, and one write of the update.

On TPU each stage is a Pallas kernel (grid over (8 rows, 128 blocks)
tiles, same layout contract as kernels/quantize.py: leaves own whole
blocks, so a block never straddles leaves). On CPU each stage is a
separately jitted wrapper of the `kernels/ref.py` oracle, orchestrated
from Python:
composing the stages into ONE XLA:CPU program costs +300-650ms at 10M
params x 16 clients (the fusion pass re-materializes producers across
stage boundaries), so the concrete-buffer path deliberately keeps the
stage boundaries at jit boundaries. Inside an outer trace (the round
engines under ``sim/grid.py``'s jit) the same composition is inlined
with the ref oracles.

Staged-vs-fused contract (test-enforced, see tests/test_kernels.py):

* plain / uniform / tier-masked means and quantize-only: **bitwise
  identical** to the staged ops on CPU — the apply runs as a
  column-chunked GEMV (chunking a GEMV along columns never reorders the
  K-axis accumulation) and the quantization scales come off an integer
  max, which no cross-program contraction can shift;
* clip fold and/or DP noise without quantization: within a couple of
  ulps on the concrete stage-jit path (XLA:CPU contracts the fold's
  multiply-adds differently across program boundaries); under an outer
  trace both paths inline into ONE program and stay bitwise — which is
  what the jitted round engines run;
* quantize + clip and/or noise: within fp round-off — the clip weights
  come from the quantized sum-of-squares fold (one int8 read instead of
  an f32 norm sweep) and the apply folds scale x clip x weight /
  denominator into one per-(row, block) coefficient.

Non-finite rows are excluded *inside* the sweep: their aggregation
weight is zeroed by the screen, and an int8 code of a NaN element is
finite garbage, so `0 * garbage` contributes exact zero — quarantine
without a dedicated zeroing sweep. (With the screen disabled entirely,
the fused quantized route assumes finite data; the unquantized routes
propagate NaN exactly like the staged ops.) The DP fixed denominator is
untouched: a quarantined row contributes the same zero as a padding
row, so sigma calibration and the epsilon ledger stay valid.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

BLOCK = 1024  # one f32 (8, 128) TPU tile; must equal the layout's align

# ---------------------------------------------------------------------------
# Pallas TPU kernels over the (K, NB, block) view of the buffer. One grid
# step holds up to 8 rows x 128 align-blocks, so every per-(row, block)
# result is an (8, 128) lane-dense tile of a (K, NB) array: the TPU
# lowering wants the last two block dims divisible by (8, 128) or equal
# to the whole array's, which a (K, 1) per-block column is not.
_ROWS = 8
_BLOCKS = 128


def tile(K: int, nb: int):
    """(rows, blocks) of one grid step for a (K, nb, block) buffer: whole
    dims when smaller than a tile. Ragged edge tiles read padding that
    only ever lands in masked-out output positions."""
    return min(K, _ROWS), min(nb, _BLOCKS)


def _stats_kernel(x_ref, bmax_ref, bsumsq_ref):
    x = x_ref[...].astype(jnp.float32)           # (rows, blocks, block)
    bmax_ref[...] = jnp.max(jnp.abs(x), axis=-1)
    bsumsq_ref[...] = jnp.sum(x * x, axis=-1)


def _pack_kernel(x_ref, s_ref, q_ref, qsq_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(x / s_ref[...][:, :, None]), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    # per-block sum of squared codes: integers below 2^24, exact in f32
    qsq_ref[...] = jnp.sum(q * q, axis=-1)


def _apply_kernel(q_ref, c_ref, noise_ref, o_ref, *, rows: int):
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = noise_ref[...]

    rb = q_ref.shape[0]
    c = c_ref[...]                                       # (rows, blocks)
    acc = o_ref[...]
    for k in range(rb):          # row order = agg_apply_ref's order
        part = q_ref[k].astype(jnp.float32) * c[k][:, None]
        if rows % rb:            # ragged last row tile: padding rows
            part = jnp.where(r * rb + k < rows, part, 0.0)
        acc = acc + part
    o_ref[...] = acc


def block_stats(mat, block: int = BLOCK, interpret: bool = False):
    """(K, N) -> per-(row, block) (max-abs, sumsq), one HBM read."""
    K, N = mat.shape
    nb = N // block
    rb, gb = tile(K, nb)
    spec = pl.BlockSpec((rb, gb), lambda r, j: (r, j))
    return pl.pallas_call(
        _stats_kernel,
        name="agg_tail_stats",
        grid=(pl.cdiv(K, rb), pl.cdiv(nb, gb)),
        in_specs=[pl.BlockSpec((rb, gb, block), lambda r, j: (r, j, 0))],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((K, nb), jnp.float32),
                   jax.ShapeDtypeStruct((K, nb), jnp.float32)],
        interpret=interpret,
    )(mat.reshape(K, nb, block))


def pack(mat, sblock, bits: int = 8, block: int = BLOCK,
         interpret: bool = False):
    """(K, N), (K, NB) scales -> ((K, NB, block) int8 codes, (K,)
    quantized row sumsq), one read + one int8 write."""
    qmax = 2.0 ** (bits - 1) - 1
    K, N = mat.shape
    nb = N // block
    rb, gb = tile(K, nb)
    spec = pl.BlockSpec((rb, gb), lambda r, j: (r, j))
    spec3 = pl.BlockSpec((rb, gb, block), lambda r, j: (r, j, 0))
    q, qsq = pl.pallas_call(
        functools.partial(_pack_kernel, qmax=qmax),
        name="agg_tail_pack",
        grid=(pl.cdiv(K, rb), pl.cdiv(nb, gb)),
        in_specs=[spec3, spec],
        out_specs=[spec3, spec],
        out_shape=[jax.ShapeDtypeStruct((K, nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((K, nb), jnp.float32)],
        interpret=interpret,
    )(mat.reshape(K, nb, block), sblock)
    return q, ref.quant_sumsq_fold(qsq, sblock)


def apply_coeff(q, coeff, noise, block: int = BLOCK,
                interpret: bool = False):
    """(K, NB, block) codes x (K, NB) coefficients -> (N,), starting the
    accumulator from ``noise`` — one codes read, one update write. The
    row tiles are the grid's inner (accumulating) axis."""
    K, nb, _ = q.shape
    rb, gb = tile(K, nb)
    out = pl.pallas_call(
        functools.partial(_apply_kernel, rows=K),
        name="agg_tail_apply",
        grid=(pl.cdiv(nb, gb), pl.cdiv(K, rb)),
        in_specs=[pl.BlockSpec((rb, gb, block), lambda j, r: (r, j, 0)),
                  pl.BlockSpec((rb, gb), lambda j, r: (r, j)),
                  pl.BlockSpec((gb, block), lambda j, r: (j, 0))],
        out_specs=pl.BlockSpec((gb, block), lambda j, r: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, coeff, noise.reshape(nb, block))
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# CPU stage jits. One jit per stage — the stage boundaries ARE the
# performance model on XLA:CPU (see module docstring); the tiny (K,)-level
# glue between them runs eagerly at negligible cost.

_stats_j = jax.jit(ref.agg_block_stats_ref,
                   static_argnames=("block", "with_sumsq", "row_chunks"))
_rss_j = jax.jit(ref.row_sumsq_ref, static_argnames=("chunk",))
_scales_j = jax.jit(ref.agg_scales_ref, static_argnames=("bits", "n_leaves"))
_pack_j = jax.jit(ref.agg_pack_ref, static_argnames=("bits", "block"))
_qss_j = jax.jit(ref.agg_quant_sumsq_ref)
_apply_j = jax.jit(ref.agg_apply_ref, static_argnames=("block",))
_apply_exact_j = jax.jit(ref.agg_apply_exact_ref, static_argnames=("cols",))
_noise_j = jax.jit(
    lambda rng, sigma, size: sigma * jax.random.normal(
        rng, (size,), jnp.float32),
    static_argnames=("size",))


class _Stages:
    """Stage implementations for one engine: 'ref' (inline, traceable),
    'jit' (concrete CPU, python-orchestrated stage jits), 'tpu'
    (Pallas kernels; scales/exact-apply stay jnp)."""

    def __init__(self, engine: str, interpret: bool = False):
        self.engine = engine
        self.interpret = interpret

    def stats(self, mat, block, with_sumsq):
        if self.engine == "tpu":
            bmax, bss = block_stats(mat, block=block,
                                    interpret=self.interpret)
            return bmax, (bss if with_sumsq else None)
        if self.engine == "jit":
            return _stats_j(mat, block=block, with_sumsq=with_sumsq)
        return ref.agg_block_stats_ref(mat, block=block,
                                       with_sumsq=with_sumsq)

    def row_sumsq(self, mat, block):
        if self.engine == "jit":
            return _rss_j(mat, chunk=block)
        return ref.row_sumsq_ref(mat, chunk=block)

    def scales(self, bmax, block_leaf, bits, n_leaves):
        if self.engine == "jit":
            return _scales_j(bmax, jnp.asarray(block_leaf, jnp.int32),
                             bits=bits, n_leaves=n_leaves)
        return ref.agg_scales_ref(bmax, block_leaf, bits, n_leaves)

    def pack(self, mat, sblock, bits, block, need_qss):
        if self.engine == "tpu":
            return pack(mat, sblock, bits=bits, block=block,
                        interpret=self.interpret)
        if self.engine == "jit":
            q = _pack_j(mat, sblock, bits=bits, block=block)
            return q, (_qss_j(q, sblock) if need_qss else None)
        q = ref.agg_pack_ref(mat, sblock, bits=bits, block=block)
        return q, (ref.agg_quant_sumsq_ref(q, sblock) if need_qss else None)

    def apply_coeff(self, q, coeff, noise, block):
        if self.engine == "tpu":
            nb = coeff.shape[1]
            nvec = (noise if noise is not None
                    else jnp.zeros((nb * block,), jnp.float32))
            return apply_coeff(q, coeff, nvec, block=block,
                               interpret=self.interpret)
        if self.engine == "jit":
            return _apply_j(q, coeff, noise, block=block)
        return ref.agg_apply_ref(q, coeff, noise=noise, block=block)

    def apply_exact(self, x3, w, sblock, wsum, block_den, noise):
        if self.engine == "jit":
            return _apply_exact_j(x3, w, sblock=sblock, wsum=wsum,
                                  block_den=block_den, noise=noise)
        return ref.agg_apply_exact_ref(x3, w, sblock=sblock, wsum=wsum,
                                       block_den=block_den, noise=noise)


def compose(mat, weights, *, block_leaf, n_leaves: int, align: int = BLOCK,
            bits: int = 0, clip_norm: float = 0.0, uniform: bool = False,
            wsum_fixed: Optional[float] = None, sigma: float = 0.0,
            rng=None, bmask=None, remask_rows: bool = False,
            block_denom: bool = False, screen=None, constrain_fn=None,
            engine: str = "ref", interpret: bool = False):
    """The fused tail, generic over both round engines.

    Stage order matches the staged ops exactly: screen -> uniform weight
    transform -> denominator -> row re-mask (async tiers) -> quantize ->
    clip fold -> mean (per-block denominator for sync tiers) -> output
    constraint -> noise. Returns ``(update, info)``; ``info`` carries the
    quarantine masks/norms (screen on), per-row post-quantize norms
    (clip on) and the route taken.
    """
    from repro.core import flat as flat_lib          # lazy: layering
    from repro.core import sanitize as sanitize_lib

    K, size = mat.shape
    nb = size // align
    stages = _Stages(engine, interpret=interpret)
    info = {}

    # ---- stats read: everything screen/quantize need, one sweep --------
    need_max = bits > 0 or screen is not None
    need_raw = screen is not None or (clip_norm > 0 and bits == 0)
    bmax = raw_norms = None
    if need_max:
        bmax, bsumsq = stages.stats(mat, align, with_sumsq=need_raw)
        if need_raw:
            raw_norms = jnp.sqrt(
                jnp.matmul(bsumsq, jnp.ones((nb,), jnp.float32)))
    elif need_raw:
        raw_norms = jnp.sqrt(stages.row_sumsq(mat, align))

    # ---- quarantine screen from the stats (no extra sweep) -------------
    q_mask = None
    if screen is not None:
        row_finite = jnp.all(jnp.isfinite(bmax), axis=-1)
        weights, q_mask, sinfo = sanitize_lib.screen_from_stats(
            raw_norms, row_finite, weights, screen)
        info.update(sinfo)

    # ---- aggregation weights and denominator ---------------------------
    w = (weights > 0).astype(weights.dtype) if uniform else weights
    if wsum_fixed is not None:
        wsum = jnp.asarray(float(wsum_fixed), jnp.float32)
    else:
        wsum = jnp.maximum(jnp.sum(w), 1e-12)

    # ---- quantize: scales from stats, then the pack read ---------------
    sblock = q8 = None
    if bits > 0:
        sblock = stages.scales(bmax, block_leaf, bits, n_leaves)
        if q_mask is not None:
            # a quarantined NaN/Inf row has NaN/Inf scales; its weight is
            # zero, but 0 * NaN would still poison the coefficient fold —
            # neutralize the scales (the row's codes are garbage either
            # way and contribute exact zero through the zero weight)
            sblock = jnp.where(q_mask[:, None], 1.0, sblock)
        q8, qss = stages.pack(mat, sblock, bits, align,
                              need_qss=clip_norm > 0)

    # ---- clip fold: per-row scale into the weights ---------------------
    if clip_norm > 0:
        norms = jnp.sqrt(qss) if bits > 0 else raw_norms
        if q_mask is not None:
            # staged zeroes quarantined rows before the norm pass; mask
            # here so a NaN/outlier norm can't poison the fold (the row's
            # weight is already zero either way)
            norms = jnp.where(q_mask, 0.0, norms)
        w = w * jnp.minimum(1.0, clip_norm / jnp.maximum(norms, 1e-12))
        info["update_norms"] = norms

    noise = None
    if sigma > 0:
        if engine == "jit":
            noise = _noise_j(rng, sigma, size)
        else:
            noise = flat_lib.draw_noise(rng, size, sigma)

    # ---- apply: route on what was folded -------------------------------
    # quantize+clip/noise -> per-(row, block) coefficient accumulation
    # (fp-round-off contract); everything else -> column-chunked GEMV,
    # bitwise identical to weighted_mean / block_masked_mean.
    if bits > 0 and (clip_norm > 0 or sigma > 0):
        coeff = (w / wsum)[:, None] * sblock
        fold_noise = noise if constrain_fn is None else None
        out = stages.apply_coeff(q8, coeff, fold_noise, align)
        if constrain_fn is not None:
            out = constrain_fn(out)
            if noise is not None:
                out = out + noise
        info["route"] = f"fused/{engine}/coeff"
    else:
        if bits > 0:
            x3 = q8        # dequantized in-register by the exact apply
        else:
            x = mat
            if q_mask is not None:
                # bits==0 reads raw f32: a quarantined NaN row must be
                # zeroed (NaN * 0 = NaN in the GEMV); finite outlier
                # rows would be fine on weight alone, but matching the
                # staged zeroing keeps the contract exact
                x = jnp.where(q_mask[:, None], 0.0, x)
            if remask_rows:
                x = (x.reshape(K, nb, align)
                     * bmask[:, :, None]).reshape(K, size)
            x3 = x.reshape(K, nb, align)
        block_den = None
        mean_wsum = wsum
        if block_denom:
            block_den = jnp.maximum(
                jnp.matmul(w.astype(jnp.float32), bmask), 1e-12)
            mean_wsum = None
        out = stages.apply_exact(x3, w, sblock, mean_wsum, block_den, None)
        if constrain_fn is not None:
            out = constrain_fn(out)
        if noise is not None:
            out = out + noise
        info["route"] = f"fused/{engine}/exact"
    return out, info
