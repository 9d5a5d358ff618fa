"""Pallas kernel validation (interpret mode on CPU): shape/dtype sweeps
asserting allclose against the pure-jnp oracles in kernels/ref.py, plus
hypothesis property sweeps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sanitize as sanitize_lib
from repro.kernels import agg_tail
from repro.kernels import ref
from repro.kernels.dp_clip import clip_accumulate, sumsq
from repro.kernels.seed_reconstruct import seed_reconstruct
from repro.kernels.swa_attention import swa_attention


# ---------------------------------------------------------------------------
# sliding-window flash attention


@pytest.mark.interpret
@pytest.mark.parametrize("B,H,S,D,window,dtype", [
    (1, 1, 128, 128, 0, jnp.float32),
    (2, 2, 256, 128, 64, jnp.float32),
    (1, 2, 384, 128, 128, jnp.float32),
    (1, 1, 256, 256, 96, jnp.float32),
    (1, 1, 200, 128, 64, jnp.float32),   # non-multiple seq (padding path)
    (1, 1, 256, 128, 0, jnp.bfloat16),
])
def test_swa_attention_matches_oracle(B, H, S, D, window, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32).astype(dtype)
    out = swa_attention(q, k, v, window=window, interpret=True)
    want = ref.swa_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                                 v.astype(jnp.float32), window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=tol, rtol=tol)


@pytest.mark.interpret
@given(st.integers(1, 3), st.integers(1, 2),
       st.sampled_from([128, 192, 256]), st.sampled_from([0, 32, 100]))
@settings(max_examples=6, deadline=None)
def test_swa_attention_property_sweep(B, H, S, window):
    D = 128
    ks = jax.random.split(jax.random.key(B * 100 + H * 10 + S + window), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    out = swa_attention(q, k, v, window=window, bq=64, bk=64, interpret=True)
    want = ref.swa_attention_ref(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5,
                               rtol=3e-5)


@pytest.mark.interpret
def test_swa_window_actually_windows():
    """Row S-1 must ignore keys older than the window."""
    B, H, S, D, W = 1, 1, 256, 128, 64
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    out1 = swa_attention(q, k, v, window=W, interpret=True)
    # perturb keys/values far outside the window of the last query
    k2 = k.at[:, :, :S - W - 8].set(0.0)
    v2 = v.at[:, :, :S - W - 8].set(0.0)
    out2 = swa_attention(q, k2, v2, window=W, interpret=True)
    np.testing.assert_allclose(np.asarray(out1[:, :, -1]),
                               np.asarray(out2[:, :, -1]), atol=1e-6)


# ---------------------------------------------------------------------------
# DP clip-accumulate


@pytest.mark.interpret
@pytest.mark.parametrize("n,clip", [(1000, 0.5), (32768, 3.0),
                                    (100_001, 1.0), (5, 10.0)])
def test_dp_clip_matches_oracle(n, clip):
    x = jax.random.normal(jax.random.key(n), (n,)) * 2.0
    acc = jnp.linspace(0, 1, n)
    got, nrm = clip_accumulate(acc, x, clip, block=4096, interpret=True)
    want, wn = ref.dp_clip_accumulate_ref(acc, x, clip)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_allclose(float(nrm), float(wn), rtol=1e-6)


@pytest.mark.interpret
@given(st.integers(1, 50_000), st.floats(0.1, 20.0))
@settings(max_examples=8, deadline=None)
def test_sumsq_property(n, scale):
    x = jax.random.normal(jax.random.key(n), (n,)) * scale
    got = sumsq(x, block=2048, interpret=True)
    np.testing.assert_allclose(float(got), float(jnp.sum(x * x)), rtol=2e-5)


# ---------------------------------------------------------------------------
# fused aggregation tail (agg_tail.py): per-stage Pallas kernels vs the
# ref.py oracles, then the whole fused composition vs the staged
# reference on every row pathology the server screen handles


_AT_BL = np.asarray([0, 0, 1, 1, 2, 2, 2, 3], np.int32)   # 4 leaves
_AT_NB = len(_AT_BL)
_AT_BLOCK = 256
_AT_SIZE = _AT_NB * _AT_BLOCK


def _at_mat(seed=0, k=5, nan_row=None, outlier_row=None):
    m = np.random.default_rng(seed).normal(0, 0.5, (k, _AT_SIZE))
    m = m.astype(np.float32)
    if nan_row is not None:
        m[nan_row, 33] = np.nan
    if outlier_row is not None:
        m[outlier_row] *= 1e6
    return jnp.asarray(m)


@pytest.mark.interpret
@pytest.mark.parametrize("nan_row", [None, 2])
def test_agg_stats_kernel_matches_ref(nan_row):
    mat = _at_mat(seed=1, nan_row=nan_row)
    bmax, bsumsq = agg_tail.block_stats(mat, block=_AT_BLOCK,
                                        interpret=True)
    rmax, rsumsq = ref.agg_block_stats_ref(mat, block=_AT_BLOCK,
                                           with_sumsq=True)
    np.testing.assert_array_equal(np.asarray(bmax), np.asarray(rmax))
    np.testing.assert_allclose(np.asarray(bsumsq), np.asarray(rsumsq),
                               rtol=1e-6)
    if nan_row is not None:
        assert np.isnan(np.asarray(bmax)[nan_row, 0])


@pytest.mark.interpret
def test_agg_pack_kernel_matches_ref():
    mat = _at_mat(seed=2)
    bmax, _ = ref.agg_block_stats_ref(mat, block=_AT_BLOCK)
    sblock = ref.agg_scales_ref(bmax, _AT_BL, 8, 4)
    q, qss = agg_tail.pack(mat, sblock, bits=8, block=_AT_BLOCK,
                           interpret=True)
    want_q = ref.agg_pack_ref(mat, sblock, 8, block=_AT_BLOCK)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(want_q))
    want_qss = ref.agg_quant_sumsq_ref(want_q, sblock)
    np.testing.assert_allclose(np.asarray(qss), np.asarray(want_qss),
                               rtol=1e-5)


@pytest.mark.interpret
def test_agg_apply_kernel_matches_ref():
    mat = _at_mat(seed=3)
    k = mat.shape[0]
    bmax, _ = ref.agg_block_stats_ref(mat, block=_AT_BLOCK)
    sblock = ref.agg_scales_ref(bmax, _AT_BL, 8, 4)
    q = ref.agg_pack_ref(mat, sblock, 8, block=_AT_BLOCK)
    w = jnp.linspace(0.2, 1.4, k)
    coeff = (w / jnp.sum(w))[:, None] * sblock
    noise = jnp.asarray(np.random.default_rng(9).normal(
        0, 0.01, (_AT_SIZE,)), jnp.float32)
    got = agg_tail.apply_coeff(q, coeff, noise, block=_AT_BLOCK,
                               interpret=True)
    want = ref.agg_apply_ref(q, coeff, noise=noise, block=_AT_BLOCK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-7)


_AT_SCREEN = sanitize_lib.SanitizeConfig(nonfinite=True, norm_mult=10.0)


@pytest.mark.interpret
@pytest.mark.parametrize("scenario", [
    "clean", "nan_rows", "outlier_rows", "tier_sliced", "zero_weight_pad"])
def test_agg_tail_fused_kernels_match_staged_composition(scenario):
    """The full fused tail with the Pallas 'tpu' engine (interpret mode)
    vs the inline ref composition — which tests/test_agg_tail.py pins to
    the staged op sequence — on every row pathology: clean rows, NaN
    rows, outlier-norm rows, tier-sliced widths, zero-weight padding."""
    k = 5
    kw = dict(block_leaf=_AT_BL, n_leaves=4, align=_AT_BLOCK, bits=8,
              clip_norm=0.5, uniform=True, wsum_fixed=float(k),
              sigma=0.01, screen=_AT_SCREEN)
    mat = _at_mat(seed=4, k=k)
    w = jnp.linspace(0.5, 1.5, k)
    if scenario == "nan_rows":
        mat = _at_mat(seed=4, k=k, nan_row=1)
    elif scenario == "outlier_rows":
        mat = _at_mat(seed=4, k=k, outlier_row=3)
    elif scenario == "tier_sliced":
        # rows as tier lanes emit them: zero outside the tier's
        # contiguous block sub-layout — partial-width rows through the
        # stats/pack/apply kernels
        masks = np.ones((k, _AT_NB), np.float32)
        masks[::2] = (_AT_BL == 0) | (_AT_BL == 2)
        mat = mat * jnp.repeat(jnp.asarray(masks), _AT_BLOCK, axis=1)
    elif scenario == "zero_weight_pad":
        w = w.at[0].set(0.0).at[4].set(0.0)
    rng = jax.random.key(11)
    tpu_out, tpu_info = agg_tail.compose(mat, w, rng=rng, engine="tpu",
                                         interpret=True, **kw)
    ref_out, ref_info = agg_tail.compose(mat, w, rng=rng, engine="ref",
                                         **kw)
    assert tpu_info["route"] == "fused/tpu/coeff"
    np.testing.assert_allclose(np.asarray(tpu_out), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-6)
    for key in ("nonfinite", "outlier"):
        if key in tpu_info:
            np.testing.assert_array_equal(np.asarray(tpu_info[key]),
                                          np.asarray(ref_info[key]))
    if scenario == "nan_rows":
        assert bool(np.asarray(tpu_info["nonfinite"])[1])
    if scenario == "outlier_rows":
        assert bool(np.asarray(tpu_info["outlier"])[3])


# ---------------------------------------------------------------------------
# seed_reconstruct


@pytest.mark.interpret
def test_seed_reconstruct_deterministic_and_invariant():
    a = seed_reconstruct(42, 7, (300, 200), 0.05, interpret=True)
    b = seed_reconstruct(42, 7, (300, 200), 0.05, interpret=True)
    c = seed_reconstruct(43, 7, (300, 200), 0.05, interpret=True)
    d = seed_reconstruct(42, 8, (300, 200), 0.05, interpret=True)
    e = seed_reconstruct(42, 7, (300, 200), 0.05, block_rows=64,
                         interpret=True)
    assert bool((a == b).all())
    assert bool((a != c).any()) and bool((a != d).any())
    assert bool((a == e).all()), "blocking must not change the stream"


@pytest.mark.interpret
@pytest.mark.parametrize("shape,std", [((1024, 256), 0.02), ((17, 130), 1.0),
                                       ((4096,), 0.5)])
def test_seed_reconstruct_moments(shape, std):
    x = np.asarray(seed_reconstruct(1, 2, shape, std, interpret=True)).ravel()
    n = x.size
    assert abs(x.mean()) < 5 * std / np.sqrt(n)
    assert abs(x.std() - std) < 0.05 * std + 1e-3
    # distribution sanity vs the jnp reference (moment match, not bitwise)
    r = np.asarray(ref.seed_reconstruct_ref(1, shape, std)).ravel()
    assert abs(np.abs(x).mean() - np.abs(r).mean()) < 0.1 * std


# ---------------------------------------------------------------------------
# grouped matmul of the held experts (kernels/moe_gmm.py)


def _gmm_case(m, k, n, sizes, transpose, seed=0):
    rng = np.random.default_rng(seed)
    g = len(sizes)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(g, n, k) if transpose else (g, k, n)),
                      jnp.float32)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


@pytest.mark.interpret
@pytest.mark.parametrize("m,k,n,sizes,transpose", [
    # an empty group, 14 rows past the groups' total, one row tile
    (40, 16, 24, [8, 0, 13, 5], False),
    (40, 16, 24, [8, 0, 13, 5], True),
    # three row tiles, groups across tile edges, k in four blocks
    (1100, 2048, 128, [300, 0, 500, 250], False),
    (1100, 128, 2048, [300, 0, 500, 250], True),
    # every row in one group; no row routed here at all
    (64, 32, 16, [0, 64, 0], False),
    (64, 32, 16, [0, 0, 0], False),
])
def test_moe_gmm_kernel_matches_ref(m, k, n, sizes, transpose):
    from repro.kernels import moe_gmm
    lhs, rhs, gs = _gmm_case(m, k, n, sizes, transpose)
    with jax.default_matmul_precision("highest"):
        got = moe_gmm.gmm(lhs, rhs, gs, transpose_rhs=transpose,
                          interpret=True)
        want = ref.moe_gmm_ref(lhs, rhs, gs, transpose)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    # the rows past the groups' total are left unwritten
    routed = sum(sizes)
    np.testing.assert_allclose(np.asarray(got)[:routed],
                               np.asarray(want)[:routed],
                               rtol=1e-5, atol=1e-4)


def _held_oracle(x, idx, w, wg, wu, wo):
    """``ops.moe_held_ffn`` in plain jnp: every held expert on every
    token, each slot taking its own expert's row."""
    y = jnp.stack([(jax.nn.silu(x @ wg[g]) * (x @ wu[g])) @ wo[g]
                   for g in range(wg.shape[0])], axis=1)        # (T, G, d)
    sel = jax.nn.one_hot(idx, wg.shape[0], dtype=x.dtype)     # (T, k, G)
    return jnp.einsum("tgd,tkg,tk->td", y, sel, w)


def _held_case(T=24, d=8, f=6, G=3, k=2, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    # slots on experts 0..G-1 and, as -1 or G+1, on experts held elsewhere
    idx = jnp.asarray(rng.integers(-1, G + 2, size=(T, k)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    experts = [jnp.asarray(rng.normal(size=s) / np.sqrt(s[1]), jnp.float32)
               for s in ((G, d, f), (G, d, f), (G, f, d))]
    return x, idx, w, experts


def test_moe_gmm_gradients_and_batching(gmm_path):
    """The grouped matmuls of ``ops.moe_held_ffn``: their gradients in
    the tokens, the weights and the experts are the plain oracle's;
    under ``vmap`` the problems merge into one call over the shared
    experts (or map one by one over experts of their own, or merge
    again under a second ``vmap``), and every problem gets what it gets
    alone, gradients included."""
    from repro.kernels import ops
    x, idx, w, experts = _held_case()
    tol = dict(rtol=1e-5, atol=1e-5)
    with jax.default_matmul_precision("highest"):
        def f(fn):
            return lambda x, w, *e: jnp.sum(jnp.sin(fn(x, idx, w, *e)))
        got = jax.grad(f(ops.moe_held_ffn), range(5))(x, w, *experts)
        want = jax.grad(f(_held_oracle), range(5))(x, w, *experts)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)
        xb = jnp.stack([x, 2 * x, x[::-1]])
        ib = jnp.stack([idx, jnp.zeros_like(idx), jnp.full_like(idx, -1)])
        wb = jnp.stack([w, w, 3 * w])
        loop = [_held_oracle(xb[i], ib[i], wb[i], *experts)
                for i in range(3)]
        merged = jax.vmap(ops.moe_held_ffn, (0, 0, 0, None, None, None))(
            xb, ib, wb, *experts)
        np.testing.assert_allclose(np.asarray(merged), np.stack(loop), **tol)
        nested = jax.vmap(jax.vmap(ops.moe_held_ffn,
                                   (0, 0, 0, None, None, None)),
                          (0, 0, 0, None, None, None))(
            xb[None], ib[None], wb[None], *experts)
        np.testing.assert_allclose(np.asarray(nested[0]), np.stack(loop),
                                   **tol)
        own = [jnp.stack([e, -e, 3 * e]) for e in experts]
        got = jax.vmap(ops.moe_held_ffn)(xb, ib, wb, *own)
        loop = [_held_oracle(xb[i], ib[i], wb[i], *(e[i] for e in own))
                for i in range(3)]
        np.testing.assert_allclose(np.asarray(got), np.stack(loop), **tol)
        # the batched gradients, as the round engine takes them: each
        # problem's own gradient in the shared experts too
        def g(fn):
            return jax.vmap(jax.grad(lambda x, i, w, *e: jnp.sum(
                fn(x, i, w, *e) ** 2), (0, 2, 3, 4, 5)),
                (0, 0, 0, None, None, None))
        got = g(ops.moe_held_ffn)(xb, ib, wb, *experts)
        want = g(_held_oracle)(xb, ib, wb, *experts)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)