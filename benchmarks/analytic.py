"""Analytic FLOP / byte models per (architecture x shape).

XLA's cost_analysis counts while-loop bodies ONCE (scan-over-layers and
scan-over-time are both loops), so the compiled numbers systematically
undercount deep stacks and SSM time scans. The roofline therefore uses
these closed-form models as the primary compute/memory terms and reports
the measured HLO numbers alongside (benchmarks/roofline.py corrects them
by probe extrapolation).

Conventions: FLOPs are global (whole step, all devices); bytes are
per-device per step, bf16 weights/caches unless stated.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from repro.configs.base import ModelConfig
from repro.models import decoder_lm as dlm
from repro.nn import basic
import jax
import jax.numpy as jnp


def param_counts(cfg: ModelConfig) -> Dict[str, int]:
    """Exact parameter counts from the real init (eval_shape, no alloc)."""
    full = jax.eval_shape(lambda: dlm.init_model(cfg, 0))
    import repro.core.partition as part
    y, z = part.partition(full, cfg.freeze_spec)
    n_all = basic.tree_size(full)
    n_y = basic.tree_size(y)
    return {"total": n_all, "trainable": n_y, "frozen": n_all - n_y}


def active_params(cfg: ModelConfig, counts) -> float:
    """Parameters touched per token (MoE: top-k + shared of each bank)."""
    if cfg.num_experts <= 0:
        return counts["total"]
    full = jax.eval_shape(lambda: dlm.init_model(cfg, 0))
    flat = dict(basic.flatten_params(full))
    expert_leaves = {k: v for k, v in flat.items()
                     if "/moe/wi_" in k or k.endswith("/moe/wo")}
    n_experts_params = sum(int(jnp.prod(jnp.asarray(v.shape)))
                           for v in expert_leaves.values())
    frac = cfg.num_experts_per_tok / cfg.num_experts
    return counts["total"] - n_experts_params * (1.0 - frac)


def attention_flops(cfg: ModelConfig, seq: int, batch: int) -> float:
    """Score+PV flops for all attention layers (excl. projections, which
    live in 2*N*D)."""
    slots, G = dlm.layer_program(cfg)
    n_attn = sum(s.kind == "attn" for s in slots) * G
    hd = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
          if cfg.use_mla else cfg.resolved_head_dim)
    vd = cfg.v_head_dim if cfg.use_mla else cfg.resolved_head_dim
    h = cfg.num_heads
    if cfg.sliding_window and cfg.sliding_window < seq:
        pairs = seq * cfg.sliding_window - cfg.sliding_window ** 2 / 2
    else:
        pairs = seq * seq / 2
    return 2.0 * batch * h * (hd + vd) * pairs * n_attn


def ssm_flops(cfg: ModelConfig, seq: int, batch: int) -> float:
    """Recurrent-state update flops for Mamba / mLSTM / sLSTM layers."""
    slots, G = dlm.layer_program(cfg)
    total = 0.0
    import repro.nn.ssm as ssm_lib
    n_mamba = sum(s.kind == "mamba" for s in slots) * G
    if n_mamba:
        di, _ = ssm_lib.mamba_dims(cfg)
        total += 6.0 * batch * seq * di * cfg.mamba_d_state * n_mamba
    n_mlstm = sum(s.kind == "mlstm" for s in slots) * G
    if n_mlstm:
        d_in, nh, dh = ssm_lib.xlstm_dims(cfg)
        # chunkwise: intra-chunk quadratic + state outer products
        chunk = 128
        total += (2.0 * batch * seq * nh * (chunk * dh * 2 + dh * dh * 2)
                  * n_mlstm)
    n_slstm = sum(s.kind == "slstm" for s in slots) * G
    if n_slstm:
        nh = cfg.num_heads
        dh = cfg.d_model // nh
        total += 2.0 * batch * seq * nh * dh * 4 * dh * n_slstm
    return total


@dataclasses.dataclass
class StepModel:
    flops_global: float        # total useful flops for the step
    model_flops: float         # 6*N(_active)*D convention
    bytes_per_device: float    # HBM traffic estimate per device
    coll_hint: str = ""


def analytic_step(cfg: ModelConfig, shape: str, mesh_devices: int = 256,
                  model_axis: int = 16) -> StepModel:
    from repro.launch.specs import SHAPES
    info = SHAPES[shape]
    seq, gb = info["seq"], info["global_batch"]
    counts = param_counts(cfg)
    n_act = active_params(cfg, counts)
    pb = counts["total"] * 2.0  # bf16 weight bytes (global)

    tokens = gb * seq
    mf = 6.0 * n_act * tokens
    fl = mf + 3.0 * attention_flops(cfg, seq, gb) + 3.0 * ssm_flops(cfg, seq, gb)
    # fwd+bwd reads weights ~3x; trainable also written; activations ~
    # 2 bytes x tokens x d x layers x ~12 tensors, sharded over devices
    act = 12.0 * 2.0 * tokens * cfg.d_model * cfg.num_layers / mesh_devices
    by = 3.0 * pb / model_axis + act
    return StepModel(fl, mf, by)
