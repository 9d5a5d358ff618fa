"""Plain-jnp building blocks shared by the configurations' references.

Nothing here imports the program. Matmul and convolution precision
is the ambient ``jax.default_matmul_precision``, which the reference
sets. Parameters are flat dicts keyed by the
program's leaf paths (``"stage0_block0/conv1/kernel"``); ``nest`` turns
such a dict into the nested tree the program's forward takes.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# bfloat16 terms each matmul and convolution operand is rounded to, in
# the forward and the backward pass: None keeps float32 operands; 1 is
# one bf16 pass (a TPU's ``default`` for float32); 2 keeps a bf16 pair
# (hi + lo), the operands of ``high``'s three passes. Set by
# ``operand_terms`` around tracing; products accumulate in float32.
_TERMS: List = [None]


@contextlib.contextmanager
def operand_terms(terms):
    prev, _TERMS[0] = _TERMS[0], terms
    try:
        yield
    finally:
        _TERMS[0] = prev


def _split(x, terms: int):
    x = x.astype(jnp.float32)
    out = jnp.zeros_like(x)
    for _ in range(terms):
        part = (x - out).astype(jnp.bfloat16).astype(jnp.float32)
        out = out + part
    return out


def _rounded(op):
    """``op(a, b)`` with both operands and the output's cotangent rounded
    to ``_TERMS`` bf16 terms, so the backward products see rounded
    operands too."""
    terms = _TERMS[0]
    if terms is None:
        return op

    @jax.custom_vjp
    def operand(x):
        return _split(x, terms)

    operand.defvjp(lambda x: (_split(x, terms), None), lambda _, g: (g,))

    @jax.custom_vjp
    def cotangent(y):
        return y

    cotangent.defvjp(lambda y: (y, None),
                     lambda _, g: (_split(g, terms),))
    return lambda a, b: cotangent(op(operand(a), operand(b)))


def conv(x, w, stride: int, dtype):
    return _rounded(lambda a, b: lax.conv_general_dilated(
        a, b, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")))(x.astype(dtype),
                                                     w.astype(dtype))


def dense(x, w, b, dtype):
    return _rounded(jnp.dot)(x.astype(dtype), w.astype(dtype)) + b.astype(
        dtype)


def einsum(spec: str, a, b, dtype):
    return _rounded(functools.partial(jnp.einsum, spec))(a.astype(dtype),
                                                         b.astype(dtype))


def groupnorm(x, scale, bias, groups: int, eps: float, dtype):
    """GroupNorm over NHWC with ``groups`` lowered until it divides C."""
    c = x.shape[-1]
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.astype(dtype).reshape(x.shape[:-1] + (g, c // g))
    axes = tuple(range(1, xg.ndim - 2)) + (xg.ndim - 1,)
    mu = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=axes, keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + eps)
    return xg.reshape(x.shape) * scale.astype(dtype) + bias.astype(dtype)


def maxpool2(x):
    b, h, w, c = x.shape
    return jnp.max(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def nest(flat: Dict[str, jnp.ndarray]) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for k in parents:
            node = node.setdefault(k, {})
        node[last] = leaf
    return out


def flatten(tree, prefix: str = "") -> Dict[str, jnp.ndarray]:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, p + "/"))
        else:
            out[p] = v
    return out


def init_leaves(key, specs: Sequence[Tuple[str, Tuple[int, ...], str, int]]
                ) -> Dict[str, jnp.ndarray]:
    """specs: (path, shape, kind, fan_in) with kind 'normal' (LeCun
    normal, std 1/sqrt(fan_in)), 'zeros' or 'ones'."""
    out = {}
    for i, (path, shape, kind, fan_in) in enumerate(specs):
        if kind == "normal":
            out[path] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                           jnp.float32)
                         / jnp.sqrt(jnp.float32(fan_in)))
        elif kind == "zeros":
            out[path] = jnp.zeros(shape, jnp.float32)
        else:
            out[path] = jnp.ones(shape, jnp.float32)
    return out


def conv_specs(path: str, k: int, c_in: int, c_out: int,
               bias: bool) -> List[Tuple]:
    out = [(f"{path}/kernel", (k, k, c_in, c_out), "normal", k * k * c_in)]
    if bias:
        out.append((f"{path}/bias", (c_out,), "zeros", 0))
    return out


def gn_specs(path: str, c: int) -> List[Tuple]:
    return [(f"{path}/scale", (c,), "ones", 0),
            (f"{path}/bias", (c,), "zeros", 0)]


def dense_specs(path: str, d_in: int, d_out: int) -> List[Tuple]:
    return [(f"{path}/kernel", (d_in, d_out), "normal", d_in),
            (f"{path}/bias", (d_out,), "zeros", 0)]
