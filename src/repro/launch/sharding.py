"""Sharding rules: parameter-path regex -> PartitionSpec.

Conventions (Megatron-style TP on the "model" axis; clients/batch on
("pod", "data")):

* column-parallel: qkv / FFN-in / up projections shard their *output*
  dim on "model"; row-parallel: wo / FFN-out shard their *input* dim.
* MoE expert stacks shard the expert dim on "model" when divisible (and,
  for very large expert counts — DeepSeek's 160 — additionally the FFN
  dim, giving 2-D expert sharding so the 236B frozen bank fits HBM).
* embeddings/unembeddings shard the vocab dim (parallel-vocab with the
  log-softmax psum under GSPMD).
* norms, biases, gates, routers, small SSM tensors replicate.
* FROZEN leaves follow the same rules — they are inputs, never updated,
  and FedPT's aggregation collective excludes them entirely.

Every rule is divisibility-guarded: a dim that does not divide the axis
falls back to replication on that axis (e.g. whisper's 51866 vocab).
"""
from __future__ import annotations

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch import mesh as mesh_lib
from repro.nn import basic


# (regex over path, spec template) — first match wins. Spec templates use
# NEGATIVE dim indices (relative to the trailing dims), so the same rule
# covers both a bare leaf and its scan-stacked (leading group dim) form.
_RULES = [
    # attention: column-parallel in, row-parallel out
    (r"/attn/w[qkv]/kernel$", {-1: "model"}),
    (r"/attn/w[qkv]/bias$", {-1: "model"}),
    (r"/attn/wo/kernel$", {-2: "model"}),
    (r"/cross_attn/w[qkv]/kernel$", {-1: "model"}),
    (r"/cross_attn/wo/kernel$", {-2: "model"}),
    # MLA
    (r"/attn/wq_b/kernel$", {-1: "model"}),
    (r"/attn/wk_b/kernel$", {-1: "model"}),
    (r"/attn/wv_b/kernel$", {-1: "model"}),
    # dense FFN
    (r"/ffn/wi(_gate|_up)?/kernel$", {-1: "model"}),
    (r"/ffn/wo/kernel$", {-2: "model"}),
    # MoE experts: stacked (E, d, ff) / (E, ff, d); expert dim on model
    (r"/moe/wi_(gate|up)$", {-3: "model"}),
    (r"/moe/wo$", {-3: "model"}),
    (r"/moe/shared/wi(_gate|_up)?/kernel$", {-1: "model"}),
    (r"/moe/shared/wo/kernel$", {-2: "model"}),
    # Mamba: in column-parallel, out row-parallel; channel tensors sharded
    (r"/mamba/in_proj/kernel$", {-1: "model"}),
    (r"/mamba/out_proj/kernel$", {-2: "model"}),
    (r"/mamba/x_proj/kernel$", {-2: "model"}),
    (r"/mamba/dt_proj/kernel$", {-1: "model"}),
    (r"/mamba/conv_w$", {-1: "model"}),
    (r"/mamba/conv_b$", {-1: "model"}),
    (r"/mamba/A_log$", {-2: "model"}),
    (r"/mamba/D$", {-1: "model"}),
    # xLSTM
    (r"/mlstm/up_proj/kernel$", {-1: "model"}),
    (r"/mlstm/down_proj/kernel$", {-2: "model"}),
    # embeddings: parallel-vocab
    (r"embed/embedding$", {-2: "model"}),
    (r"unembed/kernel$", {-1: "model"}),
]

# 2-D expert sharding for very large expert banks (DeepSeek-V2): expert
# dim on "data", FFN dim on "model" — 236B of frozen experts / 256 chips.
_RULES_2D_EXPERTS = [
    (r"/moe/wi_(gate|up)$", {-3: "data", -1: "model"}),
    (r"/moe/wo$", {-3: "data", -2: "model"}),
]

# When the expert count does not divide the model axis (Mixtral's 8 on a
# 16-wide axis), shard the expert FFN dim instead (intra-expert TP) —
# otherwise 45B of experts replicate per device.
_RULES_FFN_EXPERTS = [
    (r"/moe/wi_(gate|up)$", {-1: "model"}),
    (r"/moe/wo$", {-2: "model"}),
]

# 2-D expert sharding with the axes swapped (expert dim on "model", FFN
# dim on "data") — used by the grouped-dispatch perf variant, where the
# "data" axis is needed for the token groups.
_RULES_2D_EXPERTS_SWAPPED = [
    (r"/moe/wi_(gate|up)$", {-3: "model", -1: "data"}),
    (r"/moe/wo$", {-3: "model", -2: "data"}),
]


def _spec_for(path: str, shape, mesh, rules) -> P:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for pat, dims in rules:
        if re.search(pat, path):
            spec = [None] * len(shape)
            for d, ax in dims.items():
                di = d + len(shape) if d < 0 else d
                if 0 <= di < len(shape) and shape[di] % sizes.get(ax, 1) == 0 \
                        and shape[di] >= sizes.get(ax, 1):
                    spec[di] = ax
            return P(*spec)
    return P()


def param_shardings(params_struct, cfg: ModelConfig, mesh):
    """Tree of NamedShardings matching the (possibly stacked) param tree."""
    rules = list(_RULES)
    msize = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    mode = cfg.expert_shard
    if mode == "auto":
        mode = ("2d" if cfg.num_experts >= 64 else
                ("ffn" if cfg.num_experts and cfg.num_experts % msize else
                 "model"))
    if mode == "2d":
        rules = _RULES_2D_EXPERTS + rules
    elif mode == "2d_swapped":
        rules = _RULES_2D_EXPERTS_SWAPPED + rules
    elif mode == "ffn":
        rules = _RULES_FFN_EXPERTS + rules
    flat = dict(basic.flatten_params(params_struct))
    out = {}
    for path, leaf in flat.items():
        spec = _spec_for(path, leaf.shape, mesh, rules)
        out[path] = NamedSharding(mesh, spec)
    return basic.unflatten_params(out)


def replicated(tree, mesh):
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), tree)


def flat_constrainer(mesh):
    """``constrain_flat_fn(arr, clients: bool)`` for this mesh — the one
    sharding rule of the flat aggregation plane, shared by the dry-run
    specs (``launch/specs.py``) and the simulation grid
    (``sim/grid.py``) so the two cannot drift.

    The ``(C, size)`` client-delta buffer pins its client/lane axis to
    the data axes (``("pod", "data")`` when both exist) and its size
    axis to ``"model"`` (GSPMD pads uneven splits), so a tensor-parallel
    mesh never materializes C full-size fp32 vectors per data shard; the
    aggregated ``(size,)`` vector stays model-sharded until ``unflatten``
    reshards each leaf to its parameter layout. The weighted mean's
    client-axis reduction then lowers to the cross-data-axis collective
    directly on the sharded buffer — no gather of the K rows first."""
    dax = mesh_lib.data_axes(mesh)
    model = "model" if "model" in mesh.axis_names else None
    client_axes = dax if len(dax) > 1 else (dax[0] if dax else None)

    def constrain_flat(arr, clients: bool):
        spec = P(client_axes, model) if clients else P(model)
        return jax.lax.with_sharding_constraint(
            arr, NamedSharding(mesh, spec))

    return constrain_flat


def cohort_sharding(mesh):
    """``sharding(x)``: the ``NamedSharding`` of one SYNC-mode cohort
    input leaf — its leading (client/lane) axis on the data axes
    (``("pod", "data")`` when both exist), trailing dims replicated.
    Divisibility-guarded per leaf: a cohort that does not divide the
    data axes replicates, exactly like :func:`batch_sharding`. The grid
    uploads each round's batch with it, and :func:`cohort_constrainer`
    pins the same layout inside the round program, so the upload is
    never resharded."""
    dax = mesh_lib.data_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 1
    for a in dax:
        total *= sizes[a]
    axes = dax if len(dax) > 1 else (dax[0] if dax else None)

    def sharding(x):
        if axes is not None and x.ndim >= 1 and x.shape[0] % total == 0:
            return NamedSharding(mesh, P(axes, *([None] * (x.ndim - 1))))
        return NamedSharding(mesh, P())

    return sharding


def cohort_constrainer(mesh):
    """``constrain_batch_fn(tree)`` for SYNC-mode cohort inputs — the
    input-plane twin of :func:`flat_constrainer`'s rule: every batch
    leaf takes :func:`cohort_sharding`'s layout, so the cohort's
    microbatches land data-parallel inside the jitted round instead of
    replicated per device.

    Also applied to tier-grouped lane batches: the rule only names the
    leading axis, so tier-sliced shapes share it unchanged."""
    sharding = cohort_sharding(mesh)

    def constrain_batch(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, sharding(x)),
            tree)

    return constrain_batch


def batch_sharding(tree_struct, mesh, batch_axes=("pod", "data"),
                   batch_dim: int = 0):
    """Shard the leading (client/batch) dim over the data axes."""
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)

    def one(leaf):
        spec = [None] * len(leaf.shape)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        total = 1
        for a in axes:
            total *= sizes[a]
        if leaf.shape[batch_dim] % total == 0:
            spec[batch_dim] = axes if len(axes) > 1 else axes[0]
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, tree_struct)

