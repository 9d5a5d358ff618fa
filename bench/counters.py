"""Operation and byte counts the benchmark divides by measured time.

* Model FLOPs of one client step, from the layer shapes of a
  configuration (``bench/configs/<name>.py`` lists its conv and dense
  layers): 2 x MACs for the forward pass of every layer, 2 x MACs for the
  input-gradient of every layer above the lowest trainable one, and
  2 x MACs for the weight-gradient of every trainable layer. Norms,
  elementwise work and recomputation are not counted, so a FedPT freeze
  lowers the count honestly and ``mfu`` cannot pass the chip's peak.
* HBM bytes and vector operations of the fused aggregation tail
  (``kernels/agg_tail.py``: ``agg_tail_stats``, ``agg_tail_pack``,
  ``agg_tail_apply``) at a (K, N) buffer, as the three kernels read and
  write it.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Iterable, Sequence

ALIGN = 1024          # the flat layout's block: one f32 (8, 128) tile


@dataclasses.dataclass(frozen=True)
class Layer:
    """One conv or dense layer: ``macs`` per example, ``param`` the path
    prefix of its weight leaf in the parameter tree."""
    param: str
    macs: int


def conv_macs(out_hw: int, k: int, c_in: int, c_out: int) -> int:
    return out_hw * out_hw * k * k * c_in * c_out


def is_frozen(path: str, freeze: Iterable[str]) -> bool:
    return any(re.search(p, path) for p in freeze)


def step_flops(layers: Sequence[Layer], freeze: Sequence[str]) -> int:
    """FLOPs of one example's forward and backward pass. ``layers`` are in
    forward order; a layer is trainable when its weight leaf
    (``<param>/kernel``) matches none of the ``freeze`` regexes."""
    trainable = [not is_frozen(f"{l.param}/kernel", freeze) for l in layers]
    if not any(trainable):
        return 2 * sum(l.macs for l in layers)
    lowest = trainable.index(True)
    fwd = sum(l.macs for l in layers)
    dinput = sum(l.macs for l in layers[lowest + 1:])
    dweight = sum(l.macs for l, t in zip(layers, trainable) if t)
    return 2 * (fwd + dinput + dweight)


def padded_size(leaf_sizes: Iterable[int], align: int = ALIGN) -> int:
    """Length of the flat delta buffer: every leaf padded to ``align``."""
    return sum((max(n, 1) + align - 1) // align * align for n in leaf_sizes)


def agg_tail_bytes(K: int, N: int, align: int = ALIGN) -> int:
    """HBM bytes the three fused-tail kernels move for a (K, N) f32
    buffer (N a multiple of ``align``, NB = N / align blocks):

    * stats: read the f32 buffer; write (K, NB) max-abs and sum of squares;
    * pack: read the f32 buffer and the (K, NB) scales; write int8 codes
      and the (K, NB) code sums of squares;
    * apply: read the int8 codes, the (K, NB) coefficients and the (N,)
      starting accumulator (the DP noise, zeros without it); write the
      (N,) update.

    ``benchmarks/agg_bench.agg_bytes_moved`` leaves out the four (K, NB)
    side arrays and, without noise, the accumulator read."""
    nb = N // align
    stats = 4 * K * N + 2 * 4 * K * nb
    pack = 4 * K * N + 4 * K * nb + K * N + 4 * K * nb
    apply = K * N + 4 * K * nb + 4 * N + 4 * N
    return stats + pack + apply


def agg_tail_flops(K: int, N: int) -> int:
    """Vector operations of the three kernels per element of the buffer:
    stats |x|, max, x*x, add (4); pack divide, round, clip (2), q*q, add
    (6); apply convert, multiply, add (3)."""
    return 13 * K * N
