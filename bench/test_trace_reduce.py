"""The trace reduction against interval arithmetic done by hand.

``testdata/trace_emnist.json`` is a short stretch of a recorded TPU v5e
trace of the ``emnist.sync.c10-fp32`` cell's window: the first 400
events of the device's ``XLA Ops`` line (24.5 ms, nested loop bodies
included) and the host spans in it. It was recorded before the reduction
read the ``XLA Modules`` line, so it holds no program executions: busy
time and gaps are checked on the hand-made trace, operation self times
on the recording."""
import json
import os

import numpy as np
import pytest

import trace_reduce as tr
from trace_reduce import Event

HERE = os.path.dirname(os.path.abspath(__file__))


def test_hand_made_trace():
    # window [1, 10]; programs [0, 2], [3, 5], [4, 6] (overlapping), [9, 12]
    host = [Event("bench/window", 1.0, 9.0), Event("grid/round_fn", 2.5, 1.0),
            Event("other", 6.0, 3.0)]
    modules = [Event("a", 0.0, 2.0), Event("b", 3.0, 2.0),
               Event("c", 4.0, 2.0), Event("d", 9.0, 3.0)]
    # a loop [3, 5] whose body is [3.5, 4.5]; a kernel [4.6, 4.9]
    ops = [Event("%while.1 = (...)", 3.0, 2.0), Event("%fusion.2", 3.5, 1.0),
           Event("%custom-call.3", 4.6, 0.3, "agg_tail_pack")]
    red = tr.reduce({"chip": {"modules": modules, "ops": ops}}, host,
                    kernels=("agg_tail_pack", "agg_tail_apply"))
    assert red.window_s == 9.0
    # busy: [1, 2] + [3, 6] + [9, 10] = 5
    assert red.busy_s == pytest.approx(5.0)
    # gaps [2, 3] (midpoint 2.5 in grid/round_fn), [6, 9] (no bench/ or
    # grid/ span: "other" is not attributed)
    assert sorted(red.gaps) == sorted([("grid/round_fn", 1.0),
                                       (tr.UNATTRIBUTED, 3.0)])
    # loop self time 2 - 1 - 0.3
    assert red.op_s["%while.1"] == pytest.approx(0.7)
    assert red.op_s["%fusion.2"] == pytest.approx(1.0)
    assert red.kernel_s == pytest.approx({"agg_tail_pack": 0.3,
                                          "agg_tail_apply": 0.0})
    assert red.n_device_events == 4


def _load(name):
    with open(os.path.join(HERE, "testdata", name)) as f:
        rec = json.load(f)
    lo, hi = rec["window"]
    host = [Event(**e) for e in rec["host"] if e["name"] != "bench/window"]
    host.append(Event("bench/window", lo, hi - lo))
    device = {chip: {k: [Event(**e) for e in evs] for k, evs in lines.items()}
              for chip, lines in rec["device"].items()}
    return lo, hi, device, host


def test_recorded_trace_op_self_times():
    lo, hi, device, host = _load("trace_emnist.json")
    red = tr.reduce(device, host, kernels=("agg_tail_stats",))
    assert red.window_s == pytest.approx(hi - lo)
    assert red.busy_s == 0.0 and red.n_device_events == 0
    assert red.kernel_s == {"agg_tail_stats": 0.0}   # the staged tail
    # by brute force on a 10-ns timeline: the self times add up to the
    # union of the op intervals, nested loop bodies counted once
    grid = np.arange(lo, hi, 1e-8)
    cover = np.zeros(grid.shape, bool)
    for c in device.values():
        for e in c["ops"]:
            cover |= (grid >= e.start) & (grid < e.end)
    assert sum(red.op_s.values()) == pytest.approx(
        cover.mean() * (hi - lo), rel=2e-3)


def test_self_times_nested():
    outer = Event("loop", 0.0, 10.0)
    inner = [Event("x", 1.0, 2.0), Event("y", 4.0, 3.0)]
    inner2 = Event("z", 4.5, 1.0)
    got = {e.name: t for e, t in tr.self_times([outer, *inner, inner2])}
    assert got == pytest.approx({"loop": 5.0, "x": 2.0, "y": 2.0, "z": 1.0})


def test_union_and_gaps():
    u = tr.union([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert u == [(0, 4), (5, 6)]
    assert tr.gaps_of(u, -1, 7) == [(-1, 0), (4, 5), (6, 7)]
