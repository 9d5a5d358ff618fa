"""MoE dispatch: sort-based capacity dispatch vs the dense oracle,
capacity-drop semantics, router invariants, and the no-drop layer's
held experts, one client and a cohort merged under ``vmap``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels import ref
from repro.nn import basic, moe as moe_lib

CFG = ModelConfig(name="m", family="moe", num_layers=1, d_model=32,
                  num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=8,
                  num_experts=4, num_experts_per_tok=2,
                  moe_capacity_factor=8.0,  # high capacity: no drops
                  compute_dtype="float32")


def test_moe_matches_dense_oracle_when_no_drops():
    p = moe_lib.init_moe(0, "moe", CFG, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (64, 32))
    got, aux1 = moe_lib.moe_ffn(x, p, CFG)
    want, aux2 = moe_lib.moe_ffn_dense_fallback(x, p, CFG)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux1), float(aux2), rtol=1e-5)


def test_moe_with_shared_experts():
    cfg = CFG.with_(num_shared_experts=1, moe_d_ff=32)
    p = moe_lib.init_moe(0, "moe", cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(2), (32, 32))
    got, _ = moe_lib.moe_ffn(x, p, cfg)
    want, _ = moe_lib.moe_ffn_dense_fallback(x, p, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_capacity_drop_reduces_output_not_crashes():
    cfg = CFG.with_(moe_capacity_factor=0.25)  # force heavy dropping
    p = moe_lib.init_moe(0, "moe", cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(3), (64, 32))
    got, _ = moe_lib.moe_ffn(x, p, cfg)
    assert got.shape == x.shape
    assert bool(jnp.isfinite(got).all())
    # dropped tokens -> some outputs exactly zero (no expert contribution)
    norms = jnp.linalg.norm(np.asarray(got), axis=-1)
    assert float(jnp.min(norms)) == 0.0


def test_router_weights_normalized_topk():
    p = moe_lib.init_moe(0, "moe", CFG, jnp.float32)
    x = jax.random.normal(jax.random.key(4), (16, 32))
    w, idx, aux = moe_lib.router_topk(x, p, CFG)
    assert w.shape == (16, 2) and idx.shape == (16, 2)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 1.0, rtol=1e-5)
    assert int(idx.min()) >= 0 and int(idx.max()) < 4
    # top-k indices are distinct per token
    assert bool((idx[:, 0] != idx[:, 1]).all())
    assert float(aux) > 0


# the no-drop expert-share layer (moe_ffn_share): 16 experts, top-3,
# weights not renormalised, 2 shared experts
SHARE = CFG.with_(num_experts=16, num_experts_per_tok=3, num_shared_experts=2,
                  moe_d_ff=16, moe_capacity_factor=0.0, norm_topk_prob=False,
                  routed_scaling_factor=1.5)


def _held(p, cfg, offset, held):
    """The layer's parameters as a chip holding ``held`` experts from
    ``offset`` has them, and its config."""
    part = {k: (v[offset:offset + held] if k in ("wi_gate", "wi_up", "wo")
                else v) for k, v in p.items()}
    return part, cfg.with_(expert_offset=offset, experts_held=held)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips holding 2 of 16 experts each: their outputs, with the
    shared experts (which every chip computes) counted once, add up to
    the dense oracle of the whole layer; every routed slot lands on
    exactly one share."""
    p = moe_lib.init_moe(0, "moe", SHARE, jnp.float32)
    x = jax.random.normal(jax.random.key(5), (48, 32))
    want, _ = moe_lib.moe_ffn_dense_fallback(x, p, SHARE)
    shared = basic.mlp(x, p["shared"], "silu", jnp.float32)
    total, routed, auxs = -7 * shared, 0.0, []
    for s in range(8):
        out, aux, counts = moe_lib.moe_ffn_share(x, *_held(p, SHARE, 2 * s, 2),
                                                 seqs=4)
        total, routed = total + out, routed + counts["moe_routed_held"]
        auxs.append(float(aux))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(routed) == 48 * 3
    np.testing.assert_allclose(auxs, auxs[0], rtol=1e-6)
    # the uncut layer is the share that holds every expert
    whole, _, counts = moe_lib.moe_ffn_share(x, p, SHARE, seqs=4)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(counts["moe_routed_held"]) == 48 * 3


def test_no_slot_dropped_when_every_token_routes_to_one_held_expert():
    """Every token's top slot goes to expert 0 and its others to experts
    this chip does not hold: expert 0 takes all 64 slots (a capacity
    layer would keep ceil(64 * 3 / 16 * 1.25) = 15 of them), and the
    share equals the dense oracle with the absent experts zeroed."""
    cfg = SHARE
    p = moe_lib.init_moe(0, "moe", cfg, jnp.float32)
    router = np.zeros((32, 16), np.float32)
    router[0, 0], router[0, 9], router[0, 12] = 10.0, 6.0, 5.0
    p = dict(p, router={"kernel": jnp.asarray(router)})
    x = jax.random.normal(jax.random.key(6), (64, 32))
    x = x.at[:, 0].set(1.0)
    share, share_cfg = _held(p, cfg, 0, 2)
    out, _, counts = moe_lib.moe_ffn_share(x, share, share_cfg, seqs=2)
    assert float(counts["moe_routed_held"]) == 64
    assert float(counts["moe_load_max_over_mean"]) == 2.0
    absent = (jnp.arange(16) < 2)[:, None, None]
    zeroed = {k: (v * absent if k in ("wi_gate", "wi_up", "wo") else v)
              for k, v in p.items()}
    want, _ = moe_lib.moe_ffn_dense_fallback(x, zeroed, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # expert 0's part is really there: without it only the shared remain
    shared = basic.mlp(x, p["shared"], "silu", jnp.float32)
    assert float(jnp.min(jnp.linalg.norm(out - shared, axis=-1))) > 1e-3


# the merged dispatch of the held experts (kernels/ops.moe_held_ffn)
# under the round engine's vmap over clients: 4 clients of 32 tokens,
# experts 4-7 held; client 0 routes no slot here, client 1 all three of
# every token's (the no-drop worst case), clients 2 and 3 as they fall
HELD_CFG = SHARE.with_(expert_offset=4, experts_held=4)


def _cohort():
    p = moe_lib.init_moe(0, "moe", SHARE, jnp.float32)
    router = 0.1 * np.array(p["router"]["kernel"])
    router[0, 4:7] = [3.0, 2.5, 2.0]
    router[1, 10:13] = [3.0, 2.5, 2.0]
    p = dict(p, router={"kernel": jnp.asarray(router)})
    x = np.array(jax.random.normal(jax.random.key(7), (4, 32, 32)))
    x[0, :, :2] = [0.0, 5.0]
    x[1, :, :2] = [5.0, 0.0]
    return jnp.asarray(x), p


def _share_and_oracle(x, p):
    """Per client: the share through ``moe_ffn_share`` on experts 4-7,
    and the dense oracle of the whole layer with every other expert
    zeroed (the shared experts in both)."""
    share, _ = _held(p, HELD_CFG, 4, 4)
    mine = ((jnp.arange(16) >= 4) & (jnp.arange(16) < 8))[:, None, None]
    zeroed = {k: (v * mine if k in ("wi_gate", "wi_up", "wo") else v)
              for k, v in p.items()}
    return (moe_lib.moe_ffn_share(x, share, HELD_CFG)[0],
            moe_lib.moe_ffn_dense_fallback(x, zeroed, SHARE)[0])


def _old_formula(x, p):
    """The held share as the layer computed it before the merged
    dispatch: the slots sorted by held expert, one grouped matmul per
    projection, back to slot order by the inverse permutation, each
    token's slots weighted."""
    T, d = x.shape
    k = SHARE.num_experts_per_tok
    w, idx, _ = moe_lib.router_topk(x, p, SHARE)
    local = idx.reshape(-1) - 4
    key = jnp.where((local >= 0) & (local < 4), local, 4)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, 4, dtype=jnp.int32), axis=0)
    xs = x[order // k]
    gate = ref.moe_gmm_ref(xs, p["wi_gate"][4:8], sizes)
    up = ref.moe_gmm_ref(xs, p["wi_up"][4:8], sizes)
    ys = ref.moe_gmm_ref(jax.nn.silu(gate) * up, p["wo"][4:8], sizes)
    slots = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    y = jnp.einsum("tkd,tk->td", ys[slots].reshape(T, k, d), w)
    return y + basic.mlp(x, p["shared"], "silu", jnp.float32)


def _rel_close(got, want, rtol=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=rtol * np.max(np.abs(want)))


def test_merged_dispatch_matches_each_client_alone(gmm_path):
    """Under ``vmap`` over 4 clients of unequal loads the held share
    equals, client by client, the old formula and the dense oracle; its
    gradients in the tokens, the router and the held experts equal the
    dense oracle's."""
    x, p = _cohort()
    with jax.default_matmul_precision("highest"):
        loads = [int(jnp.sum(jnp.isin(moe_lib.router_topk(xi, p, SHARE)[1],
                                      jnp.arange(4, 8)))) for xi in x]
        assert loads[0] == 0 and loads[1] == 32 * 3, loads
        got, want = jax.vmap(_share_and_oracle, (0, None))(x, p)
        for i in range(4):
            _rel_close(got[i], want[i])
            _rel_close(got[i], _old_formula(x[i], p))

        def grads(which):
            def loss(x, router, wg, wu, wo):
                q = dict(p, router=router, wi_gate=p["wi_gate"].at[4:8].set(wg),
                         wi_up=p["wi_up"].at[4:8].set(wu),
                         wo=p["wo"].at[4:8].set(wo))
                return jnp.sum(jnp.sin(_share_and_oracle(x, q)[which]))
            return jax.vmap(jax.grad(loss, range(5)),
                            (0, None, None, None, None))(
                x, p["router"], p["wi_gate"][4:8], p["wi_up"][4:8],
                p["wo"][4:8])

        for a, b in zip(jax.tree.leaves(grads(0)), jax.tree.leaves(grads(1))):
            _rel_close(a, b)


def test_merged_dispatch_moves_each_row_once(monkeypatch):
    """``vmap(grad(moe_ffn_share))`` lowered for a TPU (kernels on, no
    chip needed) moves the held slots' rows at most four times: one
    gather in and one out in each pass, none by a float scatter. The
    router's top-k gradient scatters into (B, T, experts) and the
    kernel's group metadata into a few floats; neither is a row of the
    slots."""
    from jax._src.lib.mlir import ir
    from repro.kernels import ops
    monkeypatch.setattr(ops, "use_kernels", lambda: True)
    B, T, d, f = 4, 128, 256, 128
    cfg = HELD_CFG.with_(d_model=d, moe_d_ff=f)
    k = cfg.num_experts_per_tok
    p = jax.eval_shape(lambda: moe_lib.init_moe(0, "moe", cfg, jnp.float32))

    def loss(x, router, p):
        out, aux, _ = moe_lib.moe_ffn_share(x, dict(p, router=router), cfg)
        return jnp.sum(out ** 2) + aux

    x = jax.ShapeDtypeStruct((B, T, d), jnp.float32)
    fn = jax.vmap(jax.grad(loss, (0, 1)), (0, None, None))
    with jax.default_matmul_precision("highest"):
        module = jax.jit(fn).trace(x, p["router"], p).lower(
            lowering_platforms=("tpu",)).compiler_ir()
    funcs = {op.attributes["sym_name"].value: op
             for op in module.body.operations}

    def census(func):
        """(op name, float?, elements) of every op ``func`` runs, the
        functions it calls followed."""
        for block in (b for r in func.regions for b in r.blocks):
            for o in block.operations:
                o = o.operation
                if o.name == "func.call":
                    callee = str(o.attributes["callee"]).lstrip("@")
                    yield from census(funcs[callee])
                    continue
                t = o.results[0].type if o.results else None
                if isinstance(t, ir.RankedTensorType):
                    yield (o.name, isinstance(t.element_type, ir.FloatType),
                           int(np.prod(t.shape)))
                yield from census(o)

    ops_run = list(census(funcs["main"]))
    rows = B * T * k * min(d, f)
    gathers = [n for op, fl, n in ops_run
               if op == "stablehlo.gather" and fl and n >= rows]
    scatters = [n for op, fl, n in ops_run
                if op == "stablehlo.scatter" and fl and n >= B * T * f]
    kernels = [n for op, fl, n in ops_run if op == "stablehlo.custom_call"
               and fl and n >= B * T * k * f]
    assert len(gathers) <= 4, gathers
    assert not scatters, scatters
    assert len(kernels) == 6, kernels      # 3 forward, 3 input gradients
