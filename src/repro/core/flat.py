"""Flat-buffer aggregation layout — the server hot path's data plane.

Every per-round server op (clip, fake-quantize, weighted mean, DP noise)
used to sweep the trainable tree leaf-by-leaf: N_leaves tiny XLA ops per
client per pass, each with its own dispatch and its own badly-shaped
reduction. :class:`FlatLayout` maps the trainable tree ``y`` onto ONE
contiguous fp32 vector with a static layout (offsets/shapes computed
once per freeze_spec at trace time), so the whole aggregation tail runs
as a handful of single-pass ops over ``(clients, size)``:

* client deltas are *born flat* — ``flatten`` runs inside the jitted
  client step, so the delta is written straight into the flat buffer
  instead of into per-leaf arrays and re-concatenated later;
* per-client L2 norms, per-leaf int8 quantization scales and the
  weighted mean are dot/segment ops over the flat buffer (Pallas
  kernels on TPU via ``repro.kernels.ops``; reshaped pure-JAX fallbacks
  from ``repro.kernels.ref`` on CPU — XLA:CPU's row-reductions over
  ``(C, 10^7)`` run ~20x slower than the same reduction expressed over
  ``(C*K, align)`` blocks, which is why every reduction here goes
  through the block view);
* leaves are padded to ``align``-element boundaries so each leaf owns
  whole blocks — block-local reductions never straddle leaves, and the
  TPU kernels get a static block->leaf map to prefetch.

Padding is zero-filled and inert: zeros contribute nothing to norms or
max-abs scales, survive quantization as zeros, and are sliced away by
``unflatten`` — DP noise may land on pad slots (``add_noise``) because
unflatten drops them before the server update.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops

# default block size: one f32 (8, 128) TPU tile, and a CPU reduction
# chunk small enough to vectorize.
ALIGN = 1024


def _ceil_to(n: int, align: int) -> int:
    return (n + align - 1) // align * align


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Static mapping tree <-> one contiguous fp32 vector.

    Built once per (freeze_spec, model) from abstract shapes — safe to
    construct from tracers inside ``jit``. All fields are Python/numpy
    statics, so closing over a layout never adds jit arguments.
    """
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    sizes: Tuple[int, ...]          # true leaf sizes
    padded: Tuple[int, ...]         # leaf sizes rounded up to `align`
    offsets: Tuple[int, ...]        # leaf start offsets in the flat vector
    size: int                       # total flat length (multiple of align)
    align: int

    @classmethod
    def of(cls, tree, align: int = ALIGN) -> "FlatLayout":
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        shapes = tuple(tuple(l.shape) for l in leaves)
        dtypes = tuple(jnp.result_type(l) for l in leaves)
        sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
        padded = tuple(_ceil_to(max(n, 1), align) for n in sizes)
        offsets = tuple(int(o) for o in np.cumsum((0,) + padded[:-1]))
        return cls(treedef=treedef, shapes=shapes, dtypes=dtypes,
                   sizes=sizes, padded=padded, offsets=offsets,
                   size=int(sum(padded)) if leaves else 0, align=align)

    # -- static block metadata (numpy; fed to kernels as prefetch args) --

    @property
    def num_blocks(self) -> int:
        return self.size // self.align

    def block_leaf(self) -> np.ndarray:
        """(num_blocks,) int32: which leaf each align-block belongs to."""
        return np.repeat(np.arange(len(self.sizes), dtype=np.int32),
                         [p // self.align for p in self.padded])

    # -- tree <-> vector ------------------------------------------------

    def flatten(self, tree) -> jnp.ndarray:
        """Tree -> (size,) fp32. vmap-safe (use it inside the client step
        so deltas are written flat from birth)."""
        leaves = jax.tree_util.tree_leaves(tree)
        if not leaves:
            return jnp.zeros((0,), jnp.float32)
        parts = []
        for leaf, n, pad in zip(leaves, self.sizes, self.padded):
            v = jnp.ravel(leaf).astype(jnp.float32)
            if pad != n:
                v = jnp.pad(v, (0, pad - n))
            parts.append(v)
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    def unflatten(self, vec: jnp.ndarray, dtype: Optional[Any] = None):
        """(size,) vector -> tree. ``dtype=None`` restores each leaf's
        original dtype; pass e.g. ``jnp.float32`` to keep aggregation
        precision (the round engine's delta trees are fp32 regardless of
        the parameter dtype, matching the old tensordot path)."""
        leaves = []
        for shape, dt, n, off in zip(self.shapes, self.dtypes, self.sizes,
                                     self.offsets):
            piece = jax.lax.slice_in_dim(vec, off, off + n)
            leaves.append(piece.reshape(shape).astype(dtype or dt))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def zeros(self) -> jnp.ndarray:
        return jnp.zeros((self.size,), jnp.float32)

    # -- block sub-layouts (core/plan.py trainability tiers) -------------

    def leaf_blocks(self, leaf_on) -> np.ndarray:
        """(k,) int32 global block ids owned by the leaves ``leaf_on``
        selects (bool per leaf, layout order). Because every leaf owns
        whole ``align`` blocks, any per-leaf subset of the tree is a
        per-block subset of the flat vector — the static index map that
        makes a tier's payload a contiguous slice of its own."""
        if len(leaf_on) != len(self.sizes):
            raise ValueError(f"leaf_on has {len(leaf_on)} entries for "
                             f"{len(self.sizes)} leaves")
        per_leaf = self.block_leaf()
        keep = np.asarray(leaf_on, bool)[per_leaf]
        return np.nonzero(keep)[0].astype(np.int32)

    def block_mask(self, leaf_on) -> np.ndarray:
        """(num_blocks,) float32 0/1 mask over align-blocks for the
        leaves ``leaf_on`` selects."""
        mask = np.zeros((self.num_blocks,), np.float32)
        mask[self.leaf_blocks(leaf_on)] = 1.0
        return mask


def gather_blocks(vec: jnp.ndarray, block_ids: np.ndarray,
                  align: int = ALIGN) -> jnp.ndarray:
    """(size,) or (k, size) -> the selected blocks as ONE contiguous
    vector/matrix ((n*align,) or (k, n*align)). Static index map: the
    gather is a single XLA take over the block view."""
    ids = jnp.asarray(block_ids, jnp.int32)
    if vec.ndim == 1:
        return vec.reshape(-1, align)[ids].reshape(-1)
    k = vec.shape[0]
    return vec.reshape(k, -1, align)[:, ids].reshape(k, -1)


def scatter_blocks(sub: jnp.ndarray, block_ids: np.ndarray,
                   num_blocks: int, align: int = ALIGN) -> jnp.ndarray:
    """Inverse of :func:`gather_blocks`: place a contiguous block slice
    back into a zero-filled full-width vector ((size,) or (k, size)).
    Unselected blocks are exactly zero, so a scattered tier delta
    contributes nothing outside its tier's trainable blocks."""
    ids = jnp.asarray(block_ids, jnp.int32)
    if sub.ndim == 1:
        out = jnp.zeros((num_blocks, align), jnp.float32)
        return out.at[ids].set(sub.reshape(-1, align)).reshape(-1)
    k = sub.shape[0]
    out = jnp.zeros((k, num_blocks, align), jnp.float32)
    return out.at[:, ids].set(sub.reshape(k, -1, align)).reshape(k, -1)


def expand_block_mask(mask: jnp.ndarray, align: int = ALIGN) -> jnp.ndarray:
    """(num_blocks,) 0/1 -> (size,) elementwise mask (static repeat)."""
    return jnp.repeat(jnp.asarray(mask, jnp.float32), align)


# ---------------------------------------------------------------------------
# Flat ops used by the round engine. Each dispatches: fused Pallas kernel
# on TPU, reshaped pure-JAX fallback (kernels/ref.py) elsewhere.


def sumsq(vec: jnp.ndarray, align: int = ALIGN) -> jnp.ndarray:
    """Sum of squares of a flat vector (scalar, fp32)."""
    from repro.kernels import ref
    if (kernel_ops.use_kernels() and vec.ndim == 1
            and vec.shape[0] % align == 0):
        from repro.kernels import dp_clip
        return dp_clip.sumsq(vec)
    return ref.flat_sumsq_ref(vec, chunk=align)


def row_sumsq(mat: jnp.ndarray, align: int = ALIGN) -> jnp.ndarray:
    """(C, size) -> (C,) per-row sum of squares, single pass."""
    from repro.kernels import ref
    return ref.row_sumsq_ref(mat, chunk=align)


def row_norms(mat: jnp.ndarray, align: int = ALIGN) -> jnp.ndarray:
    return jnp.sqrt(row_sumsq(mat, align))


def clip(vec: jnp.ndarray, clip_norm: float,
         layout: Optional[FlatLayout] = None):
    """Per-vector L2 clip: vec * min(1, C/||vec||). Returns (clipped,
    pre-clip norm). Fused two-pass kernel on TPU (kernels/dp_clip.py)."""
    align = layout.align if layout is not None else ALIGN
    if (kernel_ops.use_kernels() and vec.shape[0]
            and vec.shape[0] % align == 0):
        return kernel_ops.flat_clip(vec, clip_norm)
    from repro.kernels import ref
    return ref.flat_clip_ref(vec, clip_norm, chunk=align)


def fake_quantize(mat: jnp.ndarray, layout: FlatLayout, bits: int = 8):
    """Per-leaf symmetric int-k fake-quantization of flat client deltas.

    ``mat`` is (C, size) or (size,). Scales are per (client, leaf) —
    exactly `compress.quantize_leaf`'s max-abs/qmax — computed from the
    block view, so the result matches the tree path bit-for-bit.
    """
    if layout.size == 0:
        return mat
    squeeze = mat.ndim == 1
    if squeeze:
        mat = mat[None]
    block_leaf = layout.block_leaf()
    if kernel_ops.use_kernels() and bits == 8:
        out = kernel_ops.fake_quantize_flat(mat, block_leaf,
                                            len(layout.sizes),
                                            block=layout.align)
    else:
        from repro.kernels import ref
        out = ref.fake_quantize_flat_ref(mat, block_leaf, bits=bits,
                                         block=layout.align)
    return out[0] if squeeze else out


def weighted_mean(mat: jnp.ndarray, weights: jnp.ndarray,
                  wsum: jnp.ndarray) -> jnp.ndarray:
    """(C, size), (C,) -> (size,): sum_c w_c * mat_c / wsum as ONE dot.

    Bit-for-bit identical to the old per-leaf ``tensordot`` sweep (same
    dot_general reduction over the client axis, same fp32 division), so
    sync-mode histories are unchanged when DP/quantization are off.
    """
    return jnp.matmul(weights.astype(jnp.float32),
                      mat.astype(jnp.float32)) / wsum


def block_masked_mean(mat: jnp.ndarray, weights: jnp.ndarray,
                      block_masks: jnp.ndarray,
                      align: int = ALIGN) -> jnp.ndarray:
    """(C, size), (C,), (C, num_blocks) -> (size,): the trainability-tier
    aggregation rule, shared by the sync round engine and the async
    buffered apply so the two cannot drift numerically.

    Per block j: sum_c w_c mat_c[j] / max(sum_c w_c m_c[j], eps) — a
    client contributes zero weight on blocks its tier froze (its rows
    are already zero there), and blocks nobody trained keep delta 0.
    Reduces to :func:`weighted_mean` when every mask is all-ones."""
    w = weights.astype(jnp.float32)
    num = jnp.matmul(w, mat.astype(jnp.float32))
    den = jnp.repeat(jnp.maximum(jnp.matmul(w, block_masks), 1e-12), align)
    return num / den


def pad_rows(mat: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Pad a (k, size) stack to (rows, size) with zero rows (k <= rows).

    The async grid's drained final flush uses this to keep the buffered
    apply at its fixed ``goal_count`` shape: padding rows carry zero
    weight, so they fall out of the weighted mean — and under per-flush
    DP the fixed-denominator mean and noise sigma are unchanged by them.
    """
    if mat.shape[0] > rows:
        raise ValueError(f"cannot pad {mat.shape[0]} rows down to {rows}")
    if mat.shape[0] == rows:
        return mat
    pad = jnp.zeros((rows - mat.shape[0],) + mat.shape[1:], mat.dtype)
    return jnp.concatenate([mat, pad])


def draw_noise(rng, size: int, sigma: float) -> jnp.ndarray:
    """Pre-draw the (size,) Gaussian :func:`add_noise` would add:
    ``add_noise(v, sigma, rng) == v + draw_noise(rng, v.size, sigma)``
    bit-for-bit (same single PRNG call, same scaling) — the invariance
    contract the fused aggregation tail relies on to start its
    accumulator from the noise vector instead of sweeping again."""
    return sigma * jax.random.normal(rng, (size,), jnp.float32)


def add_noise(vec: jnp.ndarray, sigma: float, rng) -> jnp.ndarray:
    """Add N(0, sigma^2) to the flat vector: ONE PRNG call instead of
    one per leaf. Pad slots receive noise too — ``unflatten`` discards
    them, so the model update is untouched; only flat-vector norms see
    the extra energy (callers that report a post-noise update norm
    compute it from the unflattened tree)."""
    return vec + sigma * jax.random.normal(rng, vec.shape, jnp.float32)
