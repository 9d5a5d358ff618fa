"""ResNet-18 with GroupNorm for CIFAR-10 (the paper's Table 2 model),
with its plain reference.

3x3 stem (64) -> GroupNorm -> ReLU, four stages of two basic blocks
(64, 128, 256, 512 channels; the first block of stages 1-3 strides 2
with a 1x1 projection shortcut), global average pool, dense to 10
classes; GroupNorm with 32 groups after every conv; 11,172,170
parameters. The sizes come from ``resnet18-gn-cifar10.json``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import counters
from configs import common, tasks

TASK = tasks.IMAGES_TASK


def _blocks(cfg):
    """(path, c_in, c_out, stride, has_proj, out_hw) per basic block."""
    h = cfg["image_shape"][0]
    c_in = cfg["stem_channels"]
    out = []
    for si, (c, stride) in enumerate(cfg["stages"]):
        for bi in range(cfg["blocks_per_stage"]):
            st = stride if bi == 0 else 1
            h = h // st
            cin = c_in if bi == 0 else c
            out.append((f"stage{si}_block{bi}", cin, c, st,
                        bi == 0 and cin != c, h))
        c_in = c
    return out


def specs(cfg):
    c0 = cfg["stem_channels"]
    out = (common.conv_specs("stem", 3, cfg["image_shape"][2], c0, False)
           + common.gn_specs("stem_gn", c0))
    for path, cin, c, _st, proj, _h in _blocks(cfg):
        out += common.conv_specs(f"{path}/conv1", 3, cin, c, False)
        out += common.gn_specs(f"{path}/gn1", c)
        out += common.conv_specs(f"{path}/conv2", 3, c, c, False)
        out += common.gn_specs(f"{path}/gn2", c)
        if proj:
            out += common.conv_specs(f"{path}/proj", 1, cin, c, False)
    out += common.dense_specs("fc", cfg["stages"][-1][0], cfg["num_classes"])
    return out


def init_params(cfg, key):
    return common.nest(common.init_leaves(key, specs(cfg)))


def layers(cfg):
    h0, _, cin0 = cfg["image_shape"]
    c0 = cfg["stem_channels"]
    out = [counters.Layer("stem", counters.conv_macs(h0, 3, cin0, c0))]
    for path, cin, c, _st, proj, h in _blocks(cfg):
        out.append(counters.Layer(f"{path}/conv1",
                                  counters.conv_macs(h, 3, cin, c)))
        out.append(counters.Layer(f"{path}/conv2",
                                  counters.conv_macs(h, 3, c, c)))
        if proj:
            out.append(counters.Layer(f"{path}/proj",
                                      counters.conv_macs(h, 1, cin, c)))
    out.append(counters.Layer("fc", cfg["stages"][-1][0]
                              * cfg["num_classes"]))
    return out


def reference_logits(p, x, cfg, dtype):
    """p: flat dict path -> array. Plain jnp in ``dtype``."""
    g, eps = cfg["groupnorm_groups"], cfg["groupnorm_eps"]

    def gn(h, path):
        return common.groupnorm(h, p[f"{path}/scale"], p[f"{path}/bias"], g,
                                eps, dtype)

    x = jax.nn.relu(gn(common.conv(x, p["stem/kernel"], 1, dtype),
                       "stem_gn"))
    for path, _cin, _c, st, proj, _h in _blocks(cfg):
        h = common.conv(x, p[f"{path}/conv1/kernel"], st, dtype)
        h = jax.nn.relu(gn(h, f"{path}/gn1"))
        h = gn(common.conv(h, p[f"{path}/conv2/kernel"], 1, dtype),
               f"{path}/gn2")
        if proj:
            sc = common.conv(x, p[f"{path}/proj/kernel"], st, dtype)
        elif st != 1:
            sc = x[:, ::st, ::st, :]
        else:
            sc = x
        x = jax.nn.relu(h + sc.astype(dtype))
    x = jnp.mean(x, axis=(1, 2))
    return common.dense(x, p["fc/kernel"], p["fc/bias"], dtype)


def program_forward():
    from repro.models import paper_models
    return paper_models.resnet18_forward


def program_loss():
    return tasks.classifier_loss(program_forward())


def reference_loss(p, batch, cfg, dtype):
    return tasks.cross_entropy(
        reference_logits(p, batch["images"], cfg, dtype), batch["labels"])


def small(cfg):
    return dict(cfg, clients=16, examples_per_client=40, test_examples=64,
                stem_channels=8, stages=[[8, 1], [16, 2], [64, 2], [64, 2]])
