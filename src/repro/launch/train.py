"""Training launcher.

Two modes:

* paper tasks (CPU-runnable end-to-end): federated training of the
  paper's own models on synthetic federated data —
    PYTHONPATH=src python -m repro.launch.train --task emnist \
        --rounds 100 [--fully-trainable]
* assigned architectures (reduced variants for CPU; the full configs are
  exercised by the dry-run):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        --reduced --rounds 10
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.partition as part
from repro.configs import load_all
from repro.configs.base import get_config
from repro.core import fedpt
from repro.data import synthetic as syn
from repro.fl import runtime
from repro.launch.cache import enable_compile_cache
from repro.models import decoder_lm as dlm
from repro.models import paper_models as pm


def reduced_config(cfg, max_layers: int = 2, d_model: int = 256,
                   vocab: int = 512):
    """Smoke-scale variant of an assigned architecture (same family/wiring)."""
    slots, _ = __import__("repro.models.decoder_lm", fromlist=["layer_program"]
                          ).layer_program(cfg)
    period = len(slots)
    layers = max(period, (max_layers + period - 1) // period * period)
    d = min(cfg.d_model, d_model)
    heads = min(cfg.num_heads, max(1, d // 64))
    kvh = max(1, min(cfg.num_kv_heads, heads))
    while heads % kvh:
        kvh -= 1
    return cfg.with_(
        num_layers=layers, d_model=d, num_heads=heads, num_kv_heads=kvh,
        head_dim=d // heads if cfg.head_dim else 0,
        d_ff=min(cfg.d_ff, 4 * d) if cfg.d_ff else 0,
        moe_d_ff=min(cfg.expert_d_ff, 2 * d) if cfg.num_experts else 0,
        num_experts=min(cfg.num_experts, 4),
        num_experts_per_tok=min(cfg.num_experts_per_tok, 2),
        vocab_size=min(cfg.vocab_size, vocab),
        kv_lora_rank=min(cfg.kv_lora_rank, 64),
        q_lora_rank=min(cfg.q_lora_rank, 96),
        qk_nope_head_dim=32 if cfg.use_mla else cfg.qk_nope_head_dim,
        qk_rope_head_dim=16 if cfg.use_mla else cfg.qk_rope_head_dim,
        v_head_dim=32 if cfg.use_mla else cfg.v_head_dim,
        encoder_layers=min(cfg.encoder_layers, 2),
        encoder_seq_len=min(cfg.encoder_seq_len, 16) or 0,
        num_prefix_tokens=min(cfg.num_prefix_tokens, 8),
        compute_dtype="float32",
    )


def run_paper_task(task: str, rounds: int, fully_trainable: bool = False,
                   seed: int = 0, log: bool = True):
    if task == "emnist":
        ds = syn.make_federated_images(60, 60, (28, 28, 1), 62, seed=seed)
        init_fn = lambda s: pm.init_emnist_cnn(s)
        fwd = pm.emnist_cnn_forward
        spec = () if fully_trainable else pm.EMNIST_FREEZE
        rc = fedpt.RoundConfig(20, 2, 16, "sgd", 0.05, "sgd", 0.5)
        kind = "images"
        ev = runtime.accuracy_eval(fwd, ds.test_images, ds.test_labels)
    elif task == "cifar":
        ds = syn.make_federated_images(50, 100, (24, 24, 3), 10, seed=seed)
        init_fn = lambda s: pm.init_resnet18(s)
        fwd = pm.resnet18_forward
        spec = () if fully_trainable else pm.resnet18_freeze_spec((3,))
        rc = fedpt.RoundConfig(10, 2, 32, "sgdm", 10**-0.5, "sgdm", 0.1)
        kind = "images"
        ev = runtime.accuracy_eval(fwd, ds.test_images, ds.test_labels)
    elif task == "stackoverflow":
        ds = syn.make_federated_tokens(64, 64, vocab=2004, seed=seed)
        init_fn = lambda s: pm.init_so_transformer(s, vocab=2004)
        fwd = pm.so_transformer_forward
        spec = () if fully_trainable else pm.so_freeze_spec((0, 1, 2))
        rc = fedpt.RoundConfig(32, 2, 16, "adam", 0.1, "sgd", 0.03)
        kind = "tokens"
        ev = runtime.nwp_accuracy_eval(fwd, ds.test_tokens)
    else:
        raise ValueError(task)

    if kind == "images":
        def loss_fn(params, b):
            logits = fwd(params, b["images"])
            lp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(
                lp, b["labels"][:, None], 1)), {}
    else:
        def loss_fn(params, b):
            logits = fwd(params, b["tokens"])
            return dlm.lm_loss(logits[:, :-1], b["tokens"][:, 1:]), {}

    res = runtime.run_federated(init_fn, loss_fn, ds, rc, rounds,
                                freeze_spec=spec, seed=seed, data_kind=kind,
                                eval_every=max(1, rounds // 4), eval_fn=ev,
                                log=log)
    return res


def run_reduced_arch(arch: str, rounds: int, seed: int = 0, log: bool = True):
    load_all()
    cfg = reduced_config(get_config(arch))
    ds = syn.make_federated_tokens(16, 32, seq_len=32, vocab=cfg.vocab_size,
                                   seed=seed)
    init_fn = lambda s: dlm.init_model(cfg, s)

    def loss_fn(params, b):
        batch = {"tokens": b["tokens"], "labels": b["tokens"]}
        if cfg.family == "vlm":
            batch["prefix_embeds"] = jnp.zeros(
                (b["tokens"].shape[0], cfg.num_prefix_tokens, 1152))
        if cfg.is_encoder_decoder:
            batch["encoder_embeds"] = jnp.zeros(
                (b["tokens"].shape[0], cfg.encoder_seq_len, cfg.d_model))
        return dlm.train_loss(params, cfg, batch)

    rc = fedpt.RoundConfig(4, 2, 4, "sgd", 0.1, "sgdm", 0.5)
    return runtime.run_federated(init_fn, loss_fn, ds, rc, rounds,
                                 freeze_spec=cfg.freeze_spec, seed=seed,
                                 data_kind="tokens", log=log), cfg


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", choices=["emnist", "cifar", "stackoverflow"])
    ap.add_argument("--arch", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--fully-trainable", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.task:
        res = run_paper_task(args.task, args.rounds, args.fully_trainable,
                             args.seed)
    else:
        res, cfg = run_reduced_arch(args.arch, args.rounds, args.seed)
        print(f"arch={cfg.name} trainable share: "
              f"{100 * res.comm.trainable_bytes / res.comm.full_bytes:.2f}%")
    print(f"final loss={res.history[-1]['loss']:.4f} "
          f"comm reduction={res.comm.reduction:.1f}x "
          f"sec/round={res.seconds_per_round:.2f}")


if __name__ == "__main__":
    main()
