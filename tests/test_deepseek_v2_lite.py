"""DeepSeek-V2-Lite (``configs/deepseek_v2_lite.py``) against the plain
reference the benchmark keeps beside its configuration
(``bench/configs/deepseek-v2-lite.py``): the program's leading dense
layer, YaRN MLA and no-drop expert-share MoE give the reference's logits
and gradients on seeded weights at the module's CPU size, and the
benchmark's one-chip cut is the registered model's.
"""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import load_all
from repro.configs.base import get_config
import repro.core.partition as part
from repro.models import decoder_lm as dlm

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")
CONFIG = os.path.join(BENCH, "configs", "deepseek-v2-lite.json")


@pytest.fixture(scope="module")
def bench_model():
    """The configuration's module (it imports the benchmark's own
    helpers, so ``bench/`` goes on the path) and its configuration."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    spec = importlib.util.spec_from_file_location(
        "dsv2lite_bench", os.path.join(BENCH, "configs",
                                       "deepseek-v2-lite.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(CONFIG) as f:
        return mod, json.load(f)


def test_program_matches_the_plain_reference(bench_model):
    """Logits, loss and every leaf's gradient, float32 on the CPU, at the
    module's CPU size (a leading dense layer and two MoE layers holding 8
    of 64 experts)."""
    mod, cfg = bench_model
    cfg = mod.small(cfg)
    params = mod.init_params(cfg, jax.random.key(3))
    flat = mod.common.flatten(params)
    tokens = jnp.asarray(mod.TASK.test(mod.TASK.make(cfg, 4))["tokens"][:4])
    pcfg = mod.program_config(params)
    assert pcfg.first_k_dense == 1 and pcfg.held_experts == 8
    with jax.default_matmul_precision("highest"):
        got, stats = dlm.forward(params, pcfg, tokens)
        want, aux, _ = mod.reference_forward(flat, tokens, cfg,
                                             jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(float(stats["moe_aux_loss"]), float(aux),
                                   rtol=1e-5)
        batch = {"tokens": tokens}
        lp, gp = jax.value_and_grad(
            lambda p: mod.program_loss()(p, batch)[0])(params)
        lr, gr = jax.value_and_grad(
            lambda p: mod.reference_loss(p, batch, cfg, jnp.float32))(flat)
    np.testing.assert_allclose(float(lp), float(lr), rtol=2e-5)
    gp = mod.common.flatten(gp)
    assert sorted(gp) == sorted(gr)
    for k, g in gr.items():
        scale = float(np.abs(g).max())
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(g),
                                   rtol=2e-5, atol=2e-5 * scale, err_msg=k)


def test_routing_counters_count_every_held_slot(bench_model):
    """The loss's counters: each MoE layer's top-6 slots that land on the
    held experts, summed over the layers, as the reference's routing
    counts them (nothing dropped)."""
    mod, cfg = bench_model
    cfg = mod.small(cfg)
    params = mod.init_params(cfg, jax.random.key(5))
    tokens = jnp.asarray(mod.TASK.test(mod.TASK.make(cfg, 6))["tokens"][:4])
    with jax.default_matmul_precision("highest"):
        _, counts = mod.program_loss()(params, {"tokens": tokens})
        want = mod.reference_forward(mod.common.flatten(params), tokens,
                                     cfg, jnp.float32)[2]
    assert float(counts["moe_routed_held"]) == float(want) > 0
    assert float(counts["moe_load_max_over_mean"]) >= 1.0


def test_the_benchmark_cut_is_the_registered_model(bench_model):
    """The configuration file's widths are the registry's; its weights at
    full size have the stated parameter counts, and the program config
    read from them differs from the registered one only by the cut."""
    mod, cfg = bench_model
    reg = get_config("deepseek-v2-lite")
    same = {"hidden_size": reg.d_model, "intermediate_size": reg.d_ff,
            "moe_intermediate_size": reg.moe_d_ff,
            "num_attention_heads": reg.num_heads,
            "kv_lora_rank": reg.kv_lora_rank,
            "qk_nope_head_dim": reg.qk_nope_head_dim,
            "qk_rope_head_dim": reg.qk_rope_head_dim,
            "v_head_dim": reg.v_head_dim,
            "num_experts_per_tok": reg.num_experts_per_tok,
            "n_shared_experts": reg.num_shared_experts,
            "router_experts": reg.num_experts,
            "first_k_dense_replace": reg.first_k_dense,
            "norm_topk_prob": reg.norm_topk_prob,
            "routed_scaling_factor": reg.routed_scaling_factor,
            "rope_theta": reg.rope_theta,
            "router_aux_loss": reg.router_aux_loss}
    assert {k: cfg[k] for k in same} == same
    rs = cfg["rope_scaling"]
    assert (rs["factor"], rs["original_max_position_embeddings"],
            rs["mscale"], rs["mscale_all_dim"]) == (
        reg.rope_scaling.factor,
        reg.rope_scaling.original_max_position_embeddings,
        reg.rope_scaling.mscale, reg.rope_scaling.mscale_all_dim)
    assert cfg["published"]["num_hidden_layers"] == reg.num_layers
    assert cfg["published"]["vocab_size"] == reg.vocab_size
    shapes = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    y, frozen = part.partition(shapes, tuple(cfg["freeze"]))
    n_y, n_frozen = part.count_params(y), part.count_params(frozen)
    assert (n_y + n_frozen, n_y) == (cfg["params"], cfg["trainable"])
    got = mod.program_config(shapes)
    want = reg.with_(num_layers=cfg["num_hidden_layers"],
                     vocab_size=cfg["vocab_size"],
                     experts_held=cfg["n_routed_experts"],
                     expert_offset=cfg["expert_offset"],
                     param_dtype="float32", compute_dtype="float32",
                     remat=True)
    assert got == want
    # the registered freeze is the benchmark's
    assert tuple(cfg["freeze"]) == reg.freeze_spec


def test_leading_dense_layers_in_the_registered_deepseeks():
    """Both registered DeepSeek-V2 models keep layer 0 dense and route
    without renormalising; the 236B scales the weights by 16."""
    load_all()
    for name, scale in (("deepseek-v2-lite", 1.0), ("deepseek-v2-236b", 16.0)):
        cfg = get_config(name)
        assert cfg.first_k_dense == 1 and not cfg.layer_uses_moe(0)
        assert all(cfg.layer_uses_moe(i) for i in range(1, cfg.num_layers))
        assert not cfg.norm_topk_prob
        assert cfg.routed_scaling_factor == scale
        slots, groups = dlm.layer_program(cfg)
        assert len(dlm.lead_slots(cfg)) == 1
        assert groups * len(slots) == cfg.num_layers - 1
