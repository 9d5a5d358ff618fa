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
from repro.kernels import flash_attention as _fa
from repro.kernels import moe_gmm as _gmm
from repro.kernels import quantize as _q
from repro.kernels import ref as _ref
from repro.kernels import seed_reconstruct as _sr


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


# ---------------------------------------------------------------------------
# Causal self-attention through the flash kernels of
# kernels/flash_attention.py, forward and backward.


def flash_block(seq: int) -> int:
    """The kernels' query and key block for a sequence of ``seq``: 256
    where it divides, else 128; 0 where neither does (no kernel)."""
    for block in (256, 128):
        if seq % block == 0:
            return block
    return 0


def flash_tiles(seq: int, d: int, dv: int) -> bool:
    """Whether the flash kernels tile a sequence of ``seq`` with q/k head
    dim ``d`` and v head dim ``dv``."""
    return bool(flash_block(seq)) and d % 8 == 0 and dv % 8 == 0 \
        and max(d, dv) <= 256


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def causal_attention(q, k, v, scale: float):
    """Causal softmax attention of (B, H, S, D) q and k and (B, H, S, Dv)
    v at softmax scale ``scale`` -> (B, H, S, Dv); S a multiple of 128.
    The Pallas kernels on TPU, interpreted elsewhere."""
    return _causal_fwd(q, k, v, scale)[0]


def _causal_fwd(q, k, v, scale):
    block = flash_block(q.shape[2])
    o, lse = _fa.flash_fwd(q, k, v, scale=scale, bq=block, bk=block,
                           interpret=not use_kernels())
    return o, (q, k, v, o, lse)


def _causal_bwd(scale, res, do):
    q = res[0]
    block = flash_block(q.shape[2])
    return _fa.flash_bwd(*res, do, scale=scale, bq=block, bk=block,
                         interpret=not use_kernels())


causal_attention.defvjp(_causal_fwd, _causal_bwd)


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
# The held experts of an MoE layer, through the grouped matmul of
# kernels/moe_gmm.py.
#
# A problem's (T, k) slots go to expert-major rows (a stable sort of each
# slot's held expert, the slots routed elsewhere last); one gather brings
# the tokens in, the SwiGLU's three grouped matmuls run back to back on
# those rows, and one gather takes them back to slot order. The kernel
# leaves the rows past the held slots unwritten, so every read of them is
# a select on the small held mask. The input gradient runs the kernel
# against the transposed experts; the experts' own gradient is plain jnp
# (``ref.moe_tgmm_ref``), which XLA drops where the experts are frozen, so
# no weight-gradient kernel runs for FedPT's frozen experts. Under
# ``jax.vmap`` (the round engine vmaps each client's step) the clients'
# tokens over shared experts merge into one problem: one sort, one gather
# in and one out per pass, and each expert's weights read once per call.


def _grouped(lhs, rhs, sizes, transpose_rhs: bool = False):
    if use_kernels():
        return _gmm.gmm(lhs, rhs, sizes, transpose_rhs=transpose_rhs)
    return _ref.moe_gmm_ref(lhs, rhs, sizes, transpose_rhs)


def _swiglu(gate, up, dtype):
    return (jax.nn.silu(gate) * up).astype(dtype)


def _slot_rows(rows, pos, held):
    """Rows in slot order, (P, T, k, n): the slots routed elsewhere are
    zero whatever their row holds."""
    P, T, k = held.shape
    return jnp.where(held[..., None], rows[pos].reshape(P, T, k, -1), 0.0)


def _merging(fn):
    """``fn(wi_gate, wi_up, wo, *stacks)`` takes stacks of P problems
    over the shared experts and returns stacks of P. Under ``jax.vmap``
    the batch's stacks merge into one over shared experts; experts of
    their own per problem run one stack at a time."""
    fn = jax.custom_batching.custom_vmap(fn)

    @fn.def_vmap
    def _batched(axis_size, in_batched, *args):
        def batched(a, b):
            return a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)

        experts, stacks = args[:3], [batched(a, b) for a, b in
                                     zip(args[3:], in_batched[3:])]
        if any(in_batched[:3]):
            out = jax.lax.map(lambda a: fn(*a), (*map(
                batched, experts, in_batched[:3]), *stacks))
        else:
            out = fn(*experts,
                     *[a.reshape((-1,) + a.shape[2:]) for a in stacks])
            out = jax.tree.map(
                lambda o: o.reshape((axis_size, -1) + o.shape[1:]), out)
        return out, jax.tree.map(lambda _: True, out)

    return fn


@_merging
def _held_fwd(wi_gate, wi_up, wo, x, idx, w):
    P, T, d = x.shape
    k, groups = idx.shape[-1], wi_gate.shape[0]
    key = jnp.where((idx >= 0) & (idx < groups), idx, groups)
    held = key < groups
    sizes = jnp.sum(jax.nn.one_hot(key, groups, dtype=jnp.int32),
                    axis=(1, 2))
    total = jnp.sum(sizes, axis=0)
    # expert g's rows of problem 0, of problem 1, ..., then the slots
    # routed elsewhere
    order = jnp.argsort(key.reshape(-1), stable=True)
    pos = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.size, dtype=order.dtype), unique_indices=True)
    xs = x.reshape(P * T, d)[order // k]
    gate = _grouped(xs, wi_gate, total)
    up = _grouped(xs, wi_up, total)
    yk = _slot_rows(_grouped(_swiglu(gate, up, x.dtype), wo, total), pos,
                    held)
    out = jnp.einsum("ptkd,ptk->ptd", yk, w.astype(yk.dtype))
    res = [a.reshape((P, -1) + a.shape[1:]) for a in (order, pos, xs, gate,
                                                      up)]
    return out, (held, sizes, *res, yk)


@_merging
def _held_bwd(wi_gate, wi_up, wo, held, sizes, order, pos, xs, gate, up,
              yk, w, g):
    P, T, k = held.shape
    groups = wi_gate.shape[0]
    order, pos = order.reshape(-1), pos.reshape(-1)
    xs, gate, up = (a.reshape((-1,) + a.shape[2:]) for a in (xs, gate, up))
    total = jnp.sum(sizes, axis=0)
    g = g.astype(yk.dtype)
    dw = jnp.einsum("ptkd,ptd->ptk", yk, g)
    dys = (g.reshape(P * T, -1)[order // k]
           * w.reshape(-1)[order, None].astype(g.dtype))
    h, swiglu_vjp = jax.vjp(lambda a, b: _swiglu(a, b, xs.dtype), gate, up)
    dgate, dup = swiglu_vjp(_grouped(dys, wo, total, True).astype(h.dtype))
    dxs = (_grouped(dgate, wi_gate, total, True).astype(xs.dtype)
           + _grouped(dup, wi_up, total, True).astype(xs.dtype))
    dx = jnp.sum(_slot_rows(dxs, pos, held), axis=2)
    # expert g's rows of problem p are group g * P + p of the merged rows
    per = sizes.T.reshape(-1)

    def dexperts(lhs, grad):
        out = _ref.moe_tgmm_ref(lhs, grad, per, groups * P)
        return out.reshape((groups, P) + out.shape[1:]).swapaxes(0, 1)

    return (dexperts(xs, dgate), dexperts(xs, dup), dexperts(h, dys), dx,
            dw.astype(w.dtype))


@jax.custom_vjp
def moe_held_ffn(x, idx, w, wi_gate, wi_up, wo):
    """The held experts' weighted SwiGLU of one problem's (T, d) tokens:
    ``out[t] = sum_j w[t, j] * ffn_{idx[t, j]}(x[t])`` over the slots j
    whose ``idx`` names a held expert (``0 <= idx < wi_gate.shape[0]``);
    the other slots are routed elsewhere and add nothing. Experts
    ``wi_gate``, ``wi_up`` (G, d, f) and ``wo`` (G, f, d); float32 out.
    The Pallas kernel ``moe_gmm`` on TPU, ``ref.moe_gmm_ref``
    elsewhere."""
    return _held_fwd(wi_gate, wi_up, wo, x[None], idx[None], w[None])[0][0]


def _held_ffn_fwd(x, idx, w, wi_gate, wi_up, wo):
    out, res = _held_fwd(wi_gate, wi_up, wo, x[None], idx[None], w[None])
    return out[0], (wi_gate, wi_up, wo, res, w[None])


def _held_ffn_bwd(res, g):
    wi_gate, wi_up, wo, res, w = res
    grads = _held_bwd(wi_gate, wi_up, wo, *res, w, g[None])
    dwg, dwu, dwo, dx, dw = (a[0] for a in grads)
    return dx, None, dw, dwg, dwu, dwo


moe_held_ffn.defvjp(_held_ffn_fwd, _held_ffn_bwd)
