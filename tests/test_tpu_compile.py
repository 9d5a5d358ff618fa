"""Compile the main-path Pallas kernels for a TPU v5e without the chip.

The TPU compiler is installed with libtpu, and it compiles for a chip
that is only described (``get_topology_desc``): it refuses tiling, SMEM
and memory violations that interpret mode never sees. Each case lowers
one kernel at the real width of the CIFAR ResNet-18 trainable slice
(2,914,634 parameters in 2,874 align-blocks) with K=16 client rows (the
grouped matmul at DeepSeek-V2-Lite's expert widths, float32 at
``highest``), and checks that the compiled program calls the kernel by
its name.

The kernel functions are called directly (not through ``kernels/ops``),
because the dispatchers choose the ref path whenever the trace is not for
a TPU, which this CPU process never is.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core.partition as part
from repro.core import flat as flat_lib
from repro.kernels import agg_tail, dp_clip, moe_gmm, quantize
from repro.models import paper_models as pm

K = 16


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2. Without a working libtpu this errors: these
    cases are the only CPU-side check that the kernels lower for Mosaic,
    so they must not turn into skips."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache, so keep it out of the cache entirely."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def layout():
    y, _ = part.partition(jax.eval_shape(lambda: pm.init_resnet18(0)),
                          pm.resnet18_freeze_spec((3,)))
    return flat_lib.FlatLayout.of(y)


def _case(name, layout):
    """(kernel fn, argument shapes, kernel name in the HLO)."""
    N, nb = layout.size, layout.num_blocks
    bl, nl = layout.block_leaf(), len(layout.sizes)
    f32 = jnp.float32
    if name == "agg_tail_stats":
        return agg_tail.block_stats, [((K, N), f32)], name
    if name == "agg_tail_pack":
        return agg_tail.pack, [((K, N), f32), ((K, nb), f32)], name
    if name == "agg_tail_apply":
        return (agg_tail.apply_coeff,
                [((K, nb, layout.align), jnp.int8), ((K, nb), f32),
                 ((N,), f32)], name)
    if name == "fake_quantize_flat":
        return (lambda x: quantize.fake_quantize_flat(x, bl, nl),
                [((K, N), f32)], "quantize_qdq")
    if name == "clip_flat":
        return (lambda x: dp_clip.clip_flat(x, 1.0), [((N,), f32)],
                "dp_clip_scale")
    if name == "clip_flat_vmapped":
        # the async lane step vmaps the per-client clip over its lane
        return (jax.vmap(lambda x: dp_clip.clip_flat(x, 1.0)),
                [((K, N), f32)], "dp_clip_scale")
    # DeepSeek-V2-Lite's held experts at their published widths (8
    # experts, d 2048, width 1408) over a cohort of 8 x 1024 tokens' top-6
    # slots: the forward projections and the input gradient
    rows, d, ff, g = 8 * 1024 * 6, 2048, 1408, 8
    gmm_args = [((rows, d), f32), ((g, d, ff), f32), ((g,), jnp.int32)]
    if name == "moe_gmm":
        return moe_gmm.gmm, gmm_args, name
    assert name == "moe_gmm_transposed"
    return (lambda a, b, c: moe_gmm.gmm(a, b, c, transpose_rhs=True),
            [((rows, ff), f32), ((g, d, ff), f32), ((g,), jnp.int32)],
            "moe_gmm")


@pytest.mark.parametrize("name", [
    "agg_tail_stats", "agg_tail_pack", "agg_tail_apply",
    "fake_quantize_flat", "clip_flat", "clip_flat_vmapped", "moe_gmm",
    "moe_gmm_transposed"])
def test_kernel_compiles_for_v5e(name, layout, one_chip,
                                 no_persistent_cache):
    fn, shapes, kernel = _case(name, layout)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert kernel in text          # the pallas_call's name scope
    assert compiled.memory_analysis() is not None
