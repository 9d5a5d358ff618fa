"""Model configuration system.

Every architecture (the paper's own three models and the ten assigned
architectures) is described by a single ``ModelConfig``. The FedPT freeze
specification is a first-class field: a tuple of regexes over parameter
paths (``layers/attn/wq`` style) that selects the *frozen* subset.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Optional

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Block kinds used by the hybrid / ssm stacks.
ATTN = "attn"
MAMBA = "mamba"
MLSTM = "mlstm"
SLSTM = "slstm"


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN context extension of RoPE (arXiv:2309.00071), as DeepSeek-V2's
    ``rope_scaling`` states it: ``factor`` over the
    ``original_max_position_embeddings`` it was trained at, the
    ``beta_fast``/``beta_slow`` rotation counts that bound the ramp
    between extrapolated and interpolated frequencies, and the ``mscale``
    and ``mscale_all_dim`` of the attention temperature
    (``nn/attention.py`` writes the equations)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Configuration of a transformer-family model.

    The same dataclass covers dense, MoE, hybrid (attention+Mamba), SSM
    (xLSTM), VLM and audio (encoder-decoder) architectures; the family
    field selects the stack wiring.
    """

    name: str
    family: str  # dense | moe | hybrid | ssm | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- MoE ----------------------------------------------------------------
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0          # expert hidden dim (0 -> d_ff)
    router_aux_loss: float = 0.0
    # > 0: each expert takes at most ceil(T*k/E * factor) tokens and the
    # rest are dropped; 0: no capacity, no token dropped (the held experts
    # run as grouped matmuls over exactly the slots routed to them)
    moe_capacity_factor: float = 1.25
    norm_topk_prob: bool = True        # renormalise the top-k weights
    routed_scaling_factor: float = 1.0  # scales them when not renormalised
    # the experts this chip holds, experts [expert_offset, expert_offset +
    # experts_held) of num_experts (0: all of them). The router still
    # scores all num_experts; the layer returns its held experts' part
    # (expert parallelism without the exchange; needs no-drop dispatch)
    expert_offset: int = 0
    experts_held: int = 0
    # perf knobs (hillclimb variants; 0/auto = paper-faithful baseline)
    moe_dispatch_groups: int = 0   # >1: group-local sort dispatch
    expert_shard: str = "auto"     # auto | model | 2d | 2d_swapped

    # --- MLA (DeepSeek-V2) ----------------------------------------------------
    use_mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    # --- attention details ----------------------------------------------------
    qkv_bias: bool = False
    sliding_window: int = 0    # 0 = full attention
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None
    use_rope: bool = True
    attn_logit_softcap: float = 0.0

    # --- hybrid (Jamba) -------------------------------------------------------
    attn_period: int = 0       # one attention layer per `attn_period` layers
    moe_period: int = 1        # MoE FFN every `moe_period` layers (else dense)

    # --- Mamba ---------------------------------------------------------------
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2

    # --- xLSTM ---------------------------------------------------------------
    slstm_every: int = 0       # an sLSTM block every k blocks (0 = none)
    xlstm_proj_factor: float = 2.0

    # --- encoder-decoder / multimodal ------------------------------------------
    encoder_layers: int = 0
    is_encoder_decoder: bool = False
    num_prefix_tokens: int = 0   # VLM patch / audio frame embeddings (stub frontend)
    encoder_seq_len: int = 0     # fixed encoder length (audio)

    # --- depth -----------------------------------------------------------------
    # leading layers with a dense FFN, run unscanned before the periodic
    # stack (DeepSeek's first_k_dense_replace); they count in num_layers
    first_k_dense: int = 0
    # recompute each layer's activations in the backward pass instead of
    # keeping them (jax.checkpoint around every layer)
    remat: bool = False

    # --- misc ------------------------------------------------------------------
    norm_type: str = "rmsnorm"  # rmsnorm | layernorm
    act: str = "silu"            # silu | gelu | relu
    gated_mlp: bool = True
    tie_embeddings: bool = False
    max_seq_len: int = 32768
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    # --- FedPT ------------------------------------------------------------------
    # regexes over parameter paths selecting the FROZEN subset.
    freeze_spec: tuple = ()
    # citation for the architecture numbers
    source: str = ""

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.num_heads

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff if self.moe_d_ff else self.d_ff

    @property
    def held_experts(self) -> int:
        return self.experts_held if self.experts_held else self.num_experts

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def block_kinds(self):
        """Sequence of block kinds (length num_layers)."""
        kinds = []
        for i in range(self.num_layers):
            if self.family == "hybrid" and self.attn_period:
                # Jamba: one attention layer per period, at the middle slot
                # of each period-group (arXiv:2403.19887 uses offset 4 of 8).
                kinds.append(ATTN if (i % self.attn_period) == self.attn_period // 2 else MAMBA)
            elif self.family == "ssm":
                if self.slstm_every and (i % self.slstm_every) == self.slstm_every - 1:
                    kinds.append(SLSTM)
                else:
                    kinds.append(MLSTM)
            else:
                kinds.append(ATTN)
        return kinds

    def layer_uses_moe(self, i: int) -> bool:
        if self.num_experts <= 0 or i < self.first_k_dense:
            return False
        return (i % self.moe_period) == (self.moe_period - 1)

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Registry

_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        # lazily import config modules
        from repro import configs as _c  # noqa: F401
        _c.load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown architecture {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs():
    from repro import configs as _c
    _c.load_all()
    return dict(_REGISTRY)


def match_freeze(path: str, freeze_spec) -> bool:
    """True if a parameter path is frozen under the spec."""
    return any(re.search(pat, path) for pat in freeze_spec)
