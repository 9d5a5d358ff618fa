"""EMNIST CNN of the paper's Table 6, with its plain reference.

conv(5x5, 32) -> ReLU -> maxpool 2 -> conv(5x5, 64) -> GroupNorm(2) ->
ReLU -> maxpool 2 -> dense(3136 -> 512) -> ReLU -> dense(512 -> 62);
1,690,174 parameters. The sizes come from ``emnist-cnn.json``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import counters
from configs import common, tasks

TASK = tasks.IMAGES_TASK


def _widths(cfg):
    h, w, c = cfg["image_shape"]
    c1, c2 = cfg["conv_channels"]
    flat = (h // 4) * (w // 4) * c2
    return h, c, c1, c2, flat, cfg["dense_width"], cfg["num_classes"]


def specs(cfg):
    h, c, c1, c2, flat, d, ncls = _widths(cfg)
    k = cfg["kernel_size"]
    return (common.conv_specs("conv1", k, c, c1, True)
            + common.conv_specs("conv2", k, c1, c2, True)
            + common.gn_specs("gn", c2)
            + common.dense_specs("dense1", flat, d)
            + common.dense_specs("dense2", d, ncls))


def init_params(cfg, key):
    return common.nest(common.init_leaves(key, specs(cfg)))


def layers(cfg):
    h, c, c1, c2, flat, d, ncls = _widths(cfg)
    k = cfg["kernel_size"]
    return [counters.Layer("conv1", counters.conv_macs(h, k, c, c1)),
            counters.Layer("conv2", counters.conv_macs(h // 2, k, c1, c2)),
            counters.Layer("dense1", flat * d),
            counters.Layer("dense2", d * ncls)]


def reference_logits(p, x, cfg, dtype):
    """p: flat dict path -> array. Plain jnp in ``dtype``."""
    eps = cfg["groupnorm_eps"]
    x = jax.nn.relu(common.conv(x, p["conv1/kernel"], 1, dtype)
                    + p["conv1/bias"].astype(dtype))
    x = common.maxpool2(x)
    x = common.conv(x, p["conv2/kernel"], 1, dtype) + p["conv2/bias"].astype(
        dtype)
    x = jax.nn.relu(common.groupnorm(x, p["gn/scale"], p["gn/bias"],
                                     cfg["groupnorm_groups"], eps, dtype))
    x = common.maxpool2(x).reshape(x.shape[0], -1)
    x = jax.nn.relu(common.dense(x, p["dense1/kernel"], p["dense1/bias"],
                                 dtype))
    return common.dense(x, p["dense2/kernel"], p["dense2/bias"], dtype)


def program_forward():
    from repro.models import paper_models
    return paper_models.emnist_cnn_forward


def program_loss():
    return tasks.classifier_loss(program_forward())


def reference_loss(p, batch, cfg, dtype):
    return tasks.cross_entropy(
        reference_logits(p, batch["images"], cfg, dtype), batch["labels"])


def small(cfg):
    return dict(cfg, clients=16, examples_per_client=40, test_examples=64,
                conv_channels=[4, 8], dense_width=32)
