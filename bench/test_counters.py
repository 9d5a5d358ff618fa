"""The FLOP and byte counts the benchmark divides by measured time."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import counters
import harness
from repro.kernels import agg_tail


def test_step_flops_small_hand_count():
    # three layers of 10, 20 and 30 MACs; the middle one frozen
    layers = [counters.Layer("a", 10), counters.Layer("b", 20),
              counters.Layer("c", 30)]
    # forward 60; input-grads of b and c (above the lowest trainable, a)
    # 50; weight-grads of a and c 40 -> 2 x 150
    assert counters.step_flops(layers, ["^b/"]) == 300
    # lowest trainable is c: input-grads of nothing above it
    assert counters.step_flops(layers, ["^a/", "^b/"]) == 2 * (60 + 0 + 30)
    assert counters.step_flops(layers, []) == 2 * (60 + 50 + 60)


def config(name: str):
    return harness.load_config(os.path.join(harness.BENCH, "configs",
                                            name + ".json"))


@pytest.mark.parametrize("name,fedpt,macs,flops", [
    # stem 995,328; stage 0: 4 x 21,233,664; stages 1-3: conv1 10,616,832,
    # three 21,233,664 convs, proj 1,179,648; fc 5,120
    ("resnet18-gn-cifar10", True, 312_427_520, 1_723_938_816),
    # conv1 627,200; conv2 10,035,200; dense1 1,605,632; dense2 31,744
    ("emnist-cnn", True, 12_299_776, 69_332_992),
    ("emnist-cnn", False, 12_299_776, 72_544_256),
])
def test_model_flops_at_published_widths(name, fedpt, macs, flops):
    cfg, model = config(name)
    layers = model.layers(cfg)
    assert sum(l.macs for l in layers) == macs
    assert counters.step_flops(layers,
                               cfg["freeze"] if fedpt else []) == flops


@pytest.mark.parametrize("name,fedpt,params,trainable", [
    ("resnet18-gn-cifar10", True, 11_172_170, 2_914_634),
    ("emnist-cnn", True, 1_690_174, 84_030),
    ("emnist-cnn", False, 1_690_174, 1_690_174),
])
def test_config_trees_match_the_program(name, fedpt, params, trainable):
    """The benchmark's own weights have the program's tree, leaf for
    leaf, and the published parameter counts."""
    from repro.models import paper_models as pm
    cfg, model = config(name)
    freeze = cfg["freeze"] if fedpt else []
    init = {"resnet18-gn-cifar10": pm.init_resnet18,
            "emnist-cnn": pm.init_emnist_cnn}[cfg["name"]]
    want = jax.eval_shape(lambda: init(0))
    got = jax.eval_shape(lambda: model.init_params(cfg, jax.random.key(0)))
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))
    leaves = harness.common.flatten(got)
    assert sum(int(np.prod(v.shape)) for v in leaves.values()) == params
    assert sum(int(np.prod(v.shape)) for p, v in leaves.items()
               if not counters.is_frozen(p, freeze)) == trainable
    assert cfg["params"] == params


def _pallas_io_bytes(fn, *args) -> int:
    """Bytes of every operand and result of the pallas_calls in fn."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    total = 0
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            for v in list(eqn.invars) + list(eqn.outvars):
                total += int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
    return total


def test_agg_tail_bytes_match_the_kernels():
    """The byte model is the sum of what the three kernels read and
    write, taken from their operands and results."""
    K, nb, block = 16, 256, counters.ALIGN
    N = nb * block
    mat = jnp.zeros((K, N), jnp.float32)
    sblock = jnp.ones((K, nb), jnp.float32)
    q = jnp.zeros((K, nb, block), jnp.int8)
    noise = jnp.zeros((N,), jnp.float32)
    got = (_pallas_io_bytes(lambda m: agg_tail.block_stats(
        m, interpret=True), mat)
        + _pallas_io_bytes(lambda m, s: agg_tail.pack(m, s, interpret=True),
                           mat, sblock)
        + _pallas_io_bytes(lambda a, c, n: agg_tail.apply_coeff(
            a, c, n, interpret=True), q, sblock, noise))
    assert got == counters.agg_tail_bytes(K, N)
    # by hand: 10 bytes an element, 20 per (row, block), 8 per column
    assert counters.agg_tail_bytes(K, N) == 10 * K * N + 20 * K * nb + 8 * N


def test_padded_size():
    assert counters.padded_size([1, 1024, 1025]) == 1024 + 1024 + 2048
