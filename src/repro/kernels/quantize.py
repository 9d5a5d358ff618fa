"""Fused per-leaf int8 fake-quantize — Pallas TPU kernel pair.

The lossy-uplink hot-spot: quantize flat trainable deltas (one
contiguous fp32 vector per client, block-aligned per leaf by
``core.flat.FlatLayout``) with a symmetric per-(client, leaf) scale,
then dequantize in place — the in-graph Q->DQ the round engine applies
when ``RoundConfig.uplink_bits > 0``. Done per leaf with tree ops this
is 2 sweeps *per leaf* plus a dispatch per leaf; the kernel pair fuses
it into 2 total HBM sweeps over the whole buffer:

1. the aggregation tail's block-stats kernel (``agg_tail.block_stats``):
   per-(row, block) max-abs as lane-dense tiles, then a tiny segment-max
   over the static block->leaf map gives each block its leaf's scale;
2. a single read-modify-write pass ``round/clip/rescale`` with each
   block's scale broadcast from a lane-dense (rows, blocks) tile.

Because each leaf is padded to a whole number of blocks, a block never
straddles leaves and the zero padding can never raise a leaf's max.
Scales match ``core.compress.quantize_leaf`` exactly (max-abs/qmax with
the same 1e-12 floor), so kernel and tree path agree bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import agg_tail
from repro.kernels import ref

BLOCK = 1024  # one f32 (8, 128) tile; must equal the layout's `align`


def _qdq_kernel(x_ref, s_ref, o_ref, *, qmax: float):
    s = s_ref[...][:, :, None]
    q = jnp.clip(jnp.round(x_ref[...].astype(jnp.float32) / s), -qmax, qmax)
    o_ref[...] = q * s


def fake_quantize_flat(x, block_leaf, n_leaves: int, bits: int = 8,
                       block: int = BLOCK, interpret: bool = False):
    """Fused Q->DQ of flat client deltas with per-leaf symmetric scales.

    ``x`` is (N,) or (K, N) with N == len(block_leaf) * block; every row
    gets its own per-leaf scales. Semantics match
    `compress.fake_quantize_tree` on the unflattened tree. Two HBM sweeps
    total, independent of the leaf count.
    """
    qmax = 2.0 ** (bits - 1) - 1
    mat = x.reshape(-1, x.shape[-1])
    K, N = mat.shape
    nb = N // block
    bmax, _ = agg_tail.block_stats(mat, block=block, interpret=interpret)
    sblock = ref.agg_scales_ref(bmax, block_leaf, bits, n_leaves)
    rb, gb = agg_tail.tile(K, nb)
    spec3 = pl.BlockSpec((rb, gb, block), lambda r, j: (r, j, 0))
    out = pl.pallas_call(
        functools.partial(_qdq_kernel, qmax=qmax),
        name="quantize_qdq",
        grid=(pl.cdiv(K, rb), pl.cdiv(nb, gb)),
        in_specs=[spec3, pl.BlockSpec((rb, gb), lambda r, j: (r, j))],
        out_specs=spec3,
        out_shape=jax.ShapeDtypeStruct((K, nb, block), jnp.float32),
        interpret=interpret,
    )(mat.reshape(K, nb, block), sblock)
    return out.reshape(x.shape)
