"""Input specifications and the round-step builder for every
(architecture x input-shape) pair — the substrate of the multi-pod
dry-run.

All inputs are ShapeDtypeStructs (no allocation); params come from
jax.eval_shape over the real init. The FROZEN tree is bf16 (read-only
weights), the TRAINABLE tree stays f32 (master copy) — the standard
mixed-precision split.

Shapes:
  train_4k     seq 4,096   global_batch 256   -> fedpt_round_step
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

import repro.core.partition as part
from repro.configs.base import ModelConfig, get_config
from repro.core import fedpt
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shard_lib
from repro.models import decoder_lm as dlm

F32 = jnp.float32
BF16 = jnp.bfloat16
I32 = jnp.int32

SHAPES = {
    "train_4k": dict(seq=4096, global_batch=256),
}
VISION_TOWER_DIM = 1152


# ---------------------------------------------------------------------------
# Parameter structs


def param_structs(cfg: ModelConfig, seed: int = 0):
    """eval_shape the init and split into (y_struct f32, frozen_struct bf16)."""
    full = jax.eval_shape(lambda: dlm.init_model(cfg, seed))
    y, z = part.partition(full, cfg.freeze_spec)
    z = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, BF16), z)
    return y, z


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype)


# ---------------------------------------------------------------------------
# Input specs


def train_specs(cfg: ModelConfig, mesh, seq: int, global_batch: int,
                tau: int = 2):
    """(batch_struct, weights_struct, clients) for one federated round."""
    dax = mesh_lib.data_axes(mesh)
    clients = 1
    for a in dax:
        clients *= mesh_lib.axis_size(mesh, a)
    b = global_batch // (clients * tau)
    assert b >= 1, (cfg.name, global_batch, clients, tau)
    tok_seq = seq - cfg.num_prefix_tokens if cfg.family == "vlm" else seq
    batch = {
        "tokens": _sds((clients, tau, b, tok_seq), I32),
        "labels": _sds((clients, tau, b, tok_seq), I32),
    }
    if cfg.family == "vlm":
        batch["prefix_embeds"] = _sds(
            (clients, tau, b, cfg.num_prefix_tokens, VISION_TOWER_DIM), BF16)
    if cfg.is_encoder_decoder:
        batch["encoder_embeds"] = _sds(
            (clients, tau, b, cfg.encoder_seq_len, cfg.d_model), BF16)
    weights = _sds((clients,), F32)
    return batch, weights, clients


# ---------------------------------------------------------------------------
# Step builder


def make_train_step(cfg: ModelConfig, mesh, y_struct):
    """FedPT round step for this architecture (client sgd, server sgdm)."""
    rc = fedpt.RoundConfig(clients_per_round=0, local_steps=2, local_batch=0,
                           client_opt="sgd", client_lr=0.02,
                           server_opt="sgdm", server_lr=0.5)

    shard_y = shard_lib.param_shardings(y_struct, cfg, mesh)
    dax = mesh_lib.data_axes(mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P

    def constrain(tree, clients: bool):
        def one(x, ns):
            spec = ns.spec
            if clients:
                spec = P(dax if len(dax) > 1 else dax[0], *spec)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
        return jax.tree_util.tree_map(one, tree, shard_y)

    # the flat delta buffer's and the cohort input batch's sharding
    # rules live in launch/sharding.py (shared with the simulation
    # grid's mesh execution path, so the two cannot drift)
    constrain_flat = shard_lib.flat_constrainer(mesh)
    constrain_batch = shard_lib.cohort_constrainer(mesh)

    def loss_fn(params, mb):
        return dlm.train_loss(params, cfg, mb)

    round_step, server_opt = fedpt.make_round_fn(
        loss_fn, rc, constrain_fn=constrain,
        constrain_flat_fn=constrain_flat,
        constrain_batch_fn=constrain_batch)

    def train_step(y, sstate, frozen, batch, weights, seed):
        rng = jax.random.key(seed[0])
        return round_step(y, sstate, frozen, batch, weights, rng)

    return train_step, server_opt


# ---------------------------------------------------------------------------
# Assembled lowering spec per (arch, shape, mesh)


@dataclasses.dataclass
class LoweringJob:
    arch: str
    shape: str
    fn: Callable
    args: tuple                 # ShapeDtypeStructs
    in_shardings: tuple
    cfg: ModelConfig
    clients: int = 0


def build_job(arch: str, shape: str, mesh, cfg_override=None) -> LoweringJob:
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    info = SHAPES[shape]
    y_struct, z_struct = param_structs(cfg)
    shard_y = shard_lib.param_shardings(y_struct, cfg, mesh)
    shard_z = shard_lib.param_shardings(z_struct, cfg, mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())

    batch, weights, clients = train_specs(cfg, mesh, info["seq"],
                                          info["global_batch"])
    train_step, server_opt = make_train_step(cfg, mesh, y_struct)
    sstate_struct = jax.eval_shape(server_opt.init, y_struct)
    # sgdm state mirrors y's structure -> same shardings
    shard_sstate = shard_lib.param_shardings(sstate_struct, cfg, mesh)
    shard_batch = shard_lib.batch_sharding(batch, mesh)
    seed = _sds((1,), I32)
    args = (y_struct, sstate_struct, z_struct, batch, weights, seed)
    inshard = (shard_y, shard_sstate, shard_z, shard_batch,
               shard_lib.batch_sharding(weights, mesh), rep)
    return LoweringJob(arch, shape, train_step, args, inshard, cfg, clients)
