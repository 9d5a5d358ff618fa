"""On-chip frozen-parameter regeneration — Pallas TPU kernel.

The FedPT reconstruction step (Algorithm 1 line 5) regenerates the frozen
Gaussians from the scalar seed. On a TPU pod this kernel removes the HBM
broadcast / checkpoint read entirely: each device fills its *local shard*
of the frozen tensor directly in VMEM and a Box-Muller transform turns
uniform bits into Gaussians.

Bit source: a **counter-based hash PRNG** (squirrel3-style avalanche over
the global element index mixed with (seed, leaf_id)). Counter-based
generation is the right primitive here — the value of element (i, j) is a
pure function of (seed, leaf, i, j), so the tensor is *identical no
matter how it is sharded, blocked, or which backend generates it*
(server CPU vs client TPU — exactly FedPT's requirement that server and
clients "share the same random number generator"). The TPU hardware PRNG
(pltpu.prng_seed / prng_random_bits) would be faster but is stateful and
backend-specific, and its interpret-mode emulation is a zero stub in
current JAX; we keep the counter-based path as the only path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TWO_PI = 6.283185307179586

# squirrel3 avalanche constants (python ints; cast at trace time inside
# the kernel so they are not captured as closure constants)
_C1 = 0xB5297A4D
_C2 = 0x68E31DA4
_C3 = 0x1B56C4E9


def _squirrel3(n, seed):
    """Vectorized integer hash; n, seed: uint32 arrays -> uint32 bits."""
    n = n * jnp.uint32(_C1)
    n = n + seed
    n = n ^ jnp.right_shift(n, jnp.uint32(8))
    n = n + jnp.uint32(_C2)
    n = n ^ jnp.left_shift(n, jnp.uint32(8))
    n = n * jnp.uint32(_C3)
    n = n ^ jnp.right_shift(n, jnp.uint32(8))
    return n


def _uniform(bits):
    """uint32 -> (0, 1): top 24 bits as mantissa, offset by half an ulp.
    The shifted value fits in int32, and the TPU lowering converts only
    signed integers to float, so it goes through an int32 bitcast."""
    top = jax.lax.bitcast_convert_type(jnp.right_shift(bits, jnp.uint32(8)),
                                       jnp.int32)
    return (top.astype(jnp.float32) + 0.5) * (1.0 / 16777216.0)


def _seed_kernel(seed_ref, o_ref, *, stddev: float, rows: int, cols: int,
                 block_rows: int):
    i = pl.program_id(0)
    br, cp = o_ref.shape
    # global element index (row-major over the LOGICAL cols, so padding
    # columns do not perturb the stream of real elements)
    r = i * block_rows + jax.lax.broadcasted_iota(jnp.int32, (br, cp), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (br, cp), 1)
    idx = (r * cols + c).astype(jnp.uint32)
    seed = seed_ref[0].astype(jnp.uint32) * jnp.uint32(0x9E3779B9) + \
        seed_ref[1].astype(jnp.uint32)
    b1 = _squirrel3(idx * jnp.uint32(2), seed)
    b2 = _squirrel3(idx * jnp.uint32(2) + jnp.uint32(1), seed)
    u1 = _uniform(b1)
    u2 = _uniform(b2)
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(TWO_PI * u2)
    valid = jnp.logical_and(r < rows, c < cols)
    z = jnp.where(valid, z, 0.0)
    o_ref[...] = (stddev * z).astype(o_ref.dtype)


def seed_reconstruct(seed, leaf_id: int, shape, stddev: float,
                     dtype=jnp.float32, block_rows: int = 256,
                     interpret: bool = False):
    """Generate the deterministic Gaussian tensor of `shape` on-chip.

    `shape` is flattened to (rows, cols) on the last dim; cols padded to
    the 128-lane boundary inside the kernel and sliced off after.
    """
    if len(shape) == 1:
        rows, cols = 1, int(shape[0])
    else:
        rows = 1
        for d in shape[:-1]:
            rows *= int(d)
        cols = int(shape[-1])
    cpad = (cols + 127) // 128 * 128
    br = min(block_rows, max(rows, 8))
    nblocks = (rows + br - 1) // br
    rpad = nblocks * br

    seeds = jnp.asarray([jnp.asarray(seed, jnp.int32),
                         jnp.asarray(leaf_id * 40503, jnp.int32)], jnp.int32)
    out = pl.pallas_call(
        functools.partial(_seed_kernel, stddev=float(stddev), rows=rows,
                          cols=cols, block_rows=br),
        name="seed_reconstruct",
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((br, cpad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rpad, cpad), dtype),
        interpret=interpret,
    )(seeds)
    return out[:rows, :cols].reshape(shape)
