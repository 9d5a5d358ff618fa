"""Functional NN primitives: deterministic path-keyed initialization,
norms, dense layers, embeddings, gated MLPs.

Parameters live in nested dicts ("param trees"). Every leaf is
initialized from a key derived *deterministically from the root seed and
the parameter path* — this is what lets FedPT regenerate frozen leaves
from a single scalar seed on every client (core/reconstruct.py).
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Path-keyed deterministic PRNG


def path_key(root_seed, path: str):
    """Derive a PRNG key for a parameter path from an integer root seed.

    Stable across processes (crc32 of the path), so a client holding only
    the scalar seed can regenerate any frozen leaf.
    """
    k = jax.random.key(root_seed) if isinstance(root_seed, int) else root_seed
    return jax.random.fold_in(k, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def normal_init(root_seed, path: str, shape, dtype, fan_in: int | None = None,
                stddev: float | None = None):
    """Gaussian init (the paper freezes 'parameters ... generated from
    Gaussian initializers'); default is LeCun-normal by fan-in."""
    if stddev is None:
        if fan_in is None:
            fan_in = shape[-2] if len(shape) >= 2 else max(shape[-1], 1)
        stddev = 1.0 / np.sqrt(max(fan_in, 1))
    k = path_key(root_seed, path)
    return (jax.random.normal(k, shape, jnp.float32) * stddev).astype(dtype)


def zeros_init(_root_seed, _path, shape, dtype, **_kw):
    return jnp.zeros(shape, dtype)


def ones_init(_root_seed, _path, shape, dtype, **_kw):
    return jnp.ones(shape, dtype)


# Initializer registry used by reconstruct: every leaf records how it was
# made so the frozen side can be regenerated without shipping bytes.
INITIALIZERS = {
    "normal": normal_init,
    "zeros": zeros_init,
    "ones": ones_init,
}


# ---------------------------------------------------------------------------
# Param tree utilities


def flatten_params(tree: Params, prefix: str = "") -> Iterable[Tuple[str, Any]]:
    for k in sorted(tree):
        v = tree[k]
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from flatten_params(v, path)
        else:
            yield path, v


def unflatten_params(flat: Dict[str, Any]) -> Params:
    out: Params = {}
    for path, v in flat.items():
        parts = path.split("/")
        d = out
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return out


def tree_size(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# Norms


def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32)) + bias.astype(jnp.float32)).astype(dt)


def groupnorm(x, scale, bias, num_groups: int, eps: float = 1e-5):
    """GroupNorm over channel-last input (N, H, W, C) or (N, C)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    c = x.shape[-1]
    g = num_groups
    xg = x.reshape(x.shape[:-1] + (g, c // g))
    axes = tuple(range(1, xg.ndim - 2)) + (xg.ndim - 1,)
    mu = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    x = xg.reshape(x.shape)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def init_norm(seed, path, d, dtype, norm_type: str):
    if norm_type == "rmsnorm":
        return {"scale": zeros_init(seed, f"{path}/scale", (d,), dtype)}
    return {"scale": zeros_init(seed, f"{path}/scale", (d,), dtype),
            "bias": zeros_init(seed, f"{path}/bias", (d,), dtype)}


def apply_norm(x, p, norm_type: str):
    if norm_type == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


# ---------------------------------------------------------------------------
# Dense / embedding


def init_dense(seed, path, d_in, d_out, dtype, bias: bool = False):
    p = {"kernel": normal_init(seed, f"{path}/kernel", (d_in, d_out), dtype,
                               fan_in=d_in)}
    if bias:
        p["bias"] = zeros_init(seed, f"{path}/bias", (d_out,), dtype)
    return p


def dense(x, p, compute_dtype=None):
    k = p["kernel"]
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        k = k.astype(compute_dtype)
    y = x @ k
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y


def init_embedding(seed, path, vocab, d, dtype):
    return {"embedding": normal_init(seed, f"{path}/embedding", (vocab, d),
                                     dtype, stddev=0.02)}


def embed(ids, p, compute_dtype):
    return jnp.take(p["embedding"], ids, axis=0).astype(compute_dtype)


def unembed(x, p, compute_dtype):
    return x.astype(compute_dtype) @ p["embedding"].astype(compute_dtype).T


# ---------------------------------------------------------------------------
# Activations & MLP


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def init_mlp(seed, path, d_model, d_ff, dtype, gated: bool = True,
             bias: bool = False):
    if gated:
        return {
            "wi_gate": init_dense(seed, f"{path}/wi_gate", d_model, d_ff, dtype, bias),
            "wi_up": init_dense(seed, f"{path}/wi_up", d_model, d_ff, dtype, bias),
            "wo": init_dense(seed, f"{path}/wo", d_ff, d_model, dtype, bias),
        }
    return {
        "wi": init_dense(seed, f"{path}/wi", d_model, d_ff, dtype, bias),
        "wo": init_dense(seed, f"{path}/wo", d_ff, d_model, dtype, bias),
    }


def mlp(x, p, act: str, compute_dtype):
    f = activation(act)
    if "wi_gate" in p:
        g = dense(x, p["wi_gate"], compute_dtype)
        u = dense(x, p["wi_up"], compute_dtype)
        return dense(f(g) * u, p["wo"], compute_dtype)
    h = f(dense(x, p["wi"], compute_dtype))
    return dense(h, p["wo"], compute_dtype)


def maybe_constrain(x, spec):
    """GSPMD sharding hint against the ambient mesh.

    Filters the spec per-dimension: an axis that is absent from the
    ambient mesh, or that does not divide the dimension, degrades to None
    for THAT dim only (instead of dropping the whole constraint). No-ops
    when no ambient mesh is set (single-device runs); any other failure
    raises, so a broken mesh never silently runs unsharded.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    filt = []
    for d, ax in enumerate(spec):
        if ax is None:
            filt.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        # keep the subset of axes that exist on the ambient mesh
        present = tuple(a for a in axes if a in sizes)
        total = 1
        for a in present:
            total *= sizes[a]
        if present and d < x.ndim and x.shape[d] % total == 0 \
                and x.shape[d] >= total:
            filt.append(present if len(present) > 1 else present[0])
        else:
            filt.append(None)
    if all(f is None for f in filt):
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*filt))
