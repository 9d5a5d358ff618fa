"""Grouped matmul of an MoE layer's held experts (Pallas TPU kernel).

``gmm(lhs, rhs, group_sizes)`` computes, for each group ``g`` (one held
expert), ``lhs[rows of g] @ rhs[g]``, where the rows of ``lhs`` are
sorted by group and group ``g`` holds ``group_sizes[g]`` consecutive rows
starting at ``sum(group_sizes[:g])``. Rows at or past
``sum(group_sizes)`` (the slots routed to experts held elsewhere) are
not computed: the kernel never writes them, so they hold whatever the
output buffer held, and a caller selects them away. ``transpose_rhs`` multiplies by
``rhs[g].T`` instead: the input gradient through a frozen expert, so the
backward pass needs no weight-gradient kernel.

The grid walks only the row tiles that hold some group's rows (the
group metadata of ``jax.experimental.pallas.ops.tpu.megablox``, whose
``gmm`` this kernel follows): a tile that two groups share is visited
once per group and each visit stores only its group's rows. The matmul
runs at the ambient ``jax.default_matmul_precision`` (``highest`` gives
Mosaic's float32 contraction). The kernel is named ``moe_gmm`` in the
HLO and in the device trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

NAME = "moe_gmm"
TM = 512            # rows per tile
WIDE = 1536         # a k or n dim up to this is one block
TK = TN = 512       # else blocks of this (must divide the dim)
VMEM_LIMIT = 64 * 1024 * 1024


def _block(dim: int, pref: int) -> int:
    if dim <= WIDE or dim % pref:
        return dim
    return pref


def _kernel(meta, lhs_ref, rhs_ref, out_ref, acc_ref, *, tm: int, tn: int,
            tiles_k: int, transpose_rhs: bool):
    offsets, group_ids, m_tile_ids = meta
    tile = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1,)), ((), ())) if transpose_rhs else \
        (((1,), (0,)), ((), ()))
    acc_ref[...] += lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                    preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        g = group_ids[tile]
        row = m_tile_ids[tile] * tm + lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...]).astype(out_ref.dtype)


def gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
        interpret: bool = False):
    """lhs (m, k), rhs (g, k, n) (``(g, n, k)`` with ``transpose_rhs``),
    group_sizes (g,) int32 -> (m, n) float32, the rows past
    ``sum(group_sizes)`` unwritten (see the module doc)."""
    m, k = lhs.shape
    groups = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = min(TM, -(-m // 8) * 8)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    mp = m + pad
    tk, tn = _block(k, TK), _block(n, TN)
    tiles_k, tiles_n = k // tk, n // tn
    group_sizes = group_sizes.astype(jnp.int32)
    meta, active = make_group_metadata(
        group_sizes=group_sizes, m=mp, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=groups,
        visit_empty_groups=False)

    def lhs_map(n_i, t, k_i, meta):
        return meta[2][t], k_i

    def rhs_map(n_i, t, k_i, meta):
        return (meta[1][t], n_i, k_i) if transpose_rhs else \
            (meta[1][t], k_i, n_i)

    def out_map(n_i, t, k_i, meta):
        return meta[2][t], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs),
        name=NAME,
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(tiles_n, active, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(meta, lhs, rhs)
    return out[:m] if pad else out
