"""Fused DP clip-and-accumulate — Pallas TPU kernel.

The DP-FedAvg / DP-FTRL hot-spot: for every client update Δ_i (flattened
trainable vector, up to ~10^8 elements), compute ‖Δ_i‖₂, scale by
min(1, C/‖Δ_i‖), and accumulate into the aggregation buffer. Done naively
this is 3 HBM sweeps (square-reduce, scale, add); the kernel pair fuses
it into 2: a block-tiled sum-of-squares reduction, then a single
read-modify-write pass `acc += x * scale` with the scalar prefetched to
SMEM. The norm reduction accumulates across the block grid into one
resident (8, 128) VMEM tile (TPU grid iterations are sequential, so the
accumulation is race-free). Vectors are viewed as (rows, 128) tiles, so
both kernels also lower when vmapped over clients.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 8 * 128 * 32  # 32768 f32 elements = 128 KiB per tile
_LANES = 128
_ROWS = BLOCK // _LANES


def _sumsq_kernel(x_ref, o_ref):
    # one resident (8, 128) partial-sum tile across the sequential grid;
    # summed to a scalar outside. No scalar store, so the kernel batches
    # (vmap adds a grid axis) like any tiled kernel.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.sum((x * x).reshape(-1, 8, _LANES), axis=0)


def _scale_add_kernel(scale_ref, x_ref, acc_ref, o_ref):
    # scale is a scalar-prefetch operand (SMEM)
    o_ref[...] = acc_ref[...] + x_ref[...].astype(jnp.float32) * scale_ref[0]


def _tiles(x, block):
    """(N,) -> zero-padded (rows, 128) view, rows a multiple of the
    block's row count: lane-dense tiles that stay tiled under vmap."""
    n = x.shape[0]
    npad = (n + block - 1) // block * block - n
    if npad:
        x = jnp.pad(x, (0, npad))
    return x.reshape(-1, _LANES)


def _row_spec(block):
    return pl.BlockSpec((block // _LANES, _LANES), lambda i, *_: (i, 0))


def sumsq(x, block: int = BLOCK, interpret: bool = False):
    """Sum of squares of a 1-D vector via a grid-accumulated reduction."""
    xt = _tiles(x, block)
    part = pl.pallas_call(
        _sumsq_kernel,
        name="dp_clip_sumsq",
        grid=(xt.shape[0] * _LANES // block,),
        in_specs=[_row_spec(block)],
        out_specs=pl.BlockSpec((8, _LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, _LANES), jnp.float32),
        interpret=interpret,
    )(xt)
    return jnp.sum(part)


def _scale_kernel(scale_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(jnp.float32) * scale_ref[0]


def clip_flat(x, clip_norm: float, block: int = BLOCK,
              interpret: bool = False):
    """x * min(1, clip_norm/||x||) over a flat f32 vector — the round
    engine's per-client clip (no accumulate target). Returns
    (clipped (N,), pre-clip norm). Two fused HBM passes.
    """
    n = x.shape[0]
    nrm = jnp.sqrt(sumsq(x, block=block, interpret=interpret))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(nrm, 1e-12))
    xt = _tiles(x, block)
    out = pl.pallas_call(
        _scale_kernel,
        name="dp_clip_scale",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(xt.shape[0] * _LANES // block,),
            in_specs=[_row_spec(block)],
            out_specs=_row_spec(block),
        ),
        out_shape=jax.ShapeDtypeStruct(xt.shape, jnp.float32),
        interpret=interpret,
    )(scale.reshape(1), xt)
    return out.reshape(-1)[:n], nrm


def clip_accumulate(acc, x, clip_norm: float, block: int = BLOCK,
                    interpret: bool = False):
    """acc += x * min(1, clip_norm/||x||). acc, x: (N,) f32.

    Returns (new_acc, norm). Two fused HBM passes instead of three.
    """
    n = x.shape[0]
    nrm = jnp.sqrt(sumsq(x, block=block, interpret=interpret))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(nrm, 1e-12))
    xt = _tiles(x, block)
    out = pl.pallas_call(
        _scale_add_kernel,
        name="dp_clip_scale_add",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(xt.shape[0] * _LANES // block,),
            in_specs=[_row_spec(block), _row_spec(block)],
            out_specs=_row_spec(block),
        ),
        out_shape=jax.ShapeDtypeStruct(xt.shape, jnp.float32),
        interpret=interpret,
    )(scale.reshape(1), xt, _tiles(acc.astype(jnp.float32), block))
    return out.reshape(-1)[:n], nrm
