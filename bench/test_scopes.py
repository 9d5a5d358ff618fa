"""The scoped reduction (``scopes.py``) and the metrics that read it.

``testdata/trace_c256_scoped.json`` is one round of a recorded TPU v5e
trace of the ``emnist.sync.c256-ft-int8dp`` cell's window
(``scoped_trace.py --fixture``): the device's program executions and
operations, each operation with the scope ``scopes.read_scoped`` gave
it, and the ``grid/`` host spans; operation names are shortened to the
instruction's name and their stats to the kernel names they hold."""
import dataclasses
import glob
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

import scopes
import trace_reduce as tr
from trace_reduce import Event

HERE = os.path.dirname(os.path.abspath(__file__))
KERNELS = ("agg_tail_stats", "agg_tail_pack", "agg_tail_apply")


def _scoped(ev, scope):
    return scopes.ScopedEvent(ev.name, ev.start, ev.dur, ev.meta, scope)


def _load(name):
    with open(os.path.join(HERE, "testdata", name)) as f:
        rec = json.load(f)
    lo, hi = rec["window"]
    host = [Event(**e) for e in rec["host"] if e["name"] != "bench/window"]
    host.append(Event("bench/window", lo, hi - lo))
    device = {chip: {k: [Event(**{f: v for f, v in e.items()
                                  if f != "scope"}) for e in evs]
                     for k, evs in lines.items()}
              for chip, lines in rec["device"].items()}
    scoped = {chip: dict(lines, ops=[
        _scoped(Event(**{f: v for f, v in e.items() if f != "scope"}),
                e.get("scope", ""))
        for e in rec["device"][chip]["ops"]])
        for chip, lines in device.items()}
    return lo, hi, device, scoped, host


def test_scope_of():
    assert scopes.scope_of("jit(f)/fedpt/client_step/vmap(jvp())/tanh") \
        == "fedpt/client_step"
    assert scopes.scope_of(
        "jit(lane_step)/vmap(fedpt/client_step)/dot_general") \
        == "fedpt/client_step"
    # the innermost scope wins
    assert scopes.scope_of("jit(f)/fedpt/agg_tail/x/fedpt/server_apply/y") \
        == "fedpt/server_apply"
    assert scopes.scope_of("jit(f)/add") == ""
    assert scopes.scope_of("") == ""


def test_hand_made_scoped_trace():
    # window [1, 9]; a round [1.5, 9.5] with plan [1.5, 2], round_fn
    # [2, 2.5], wait [2.5, 6] and eval_fn [8, 9.5], both cut at 9
    host = [Event("bench/window", 1.0, 8.0), Event("grid/round", 1.5, 8.0),
            Event("grid/plan", 1.5, 0.5), Event("grid/round_fn", 2.0, 0.5),
            Event("grid/wait", 2.5, 3.5), Event("grid/eval_fn", 8.0, 1.5),
            Event("other", 6.0, 1.0)]
    modules = [Event("jit_round_step(5)", 2.5, 3.5)]
    # a loop [2.5, 5] in client_step whose body [3, 4] is unscoped, a
    # kernel [5, 5.5] in agg_tail, [5.5, 6] in server_apply, and an op
    # [0, 2] half outside the window
    ops = [Event("%while.1", 2.5, 2.5), Event("%fusion.2", 3.0, 1.0),
           Event("%custom-call.3", 5.0, 0.5, "agg_tail_pack"),
           Event("%fusion.4", 5.5, 0.5), Event("%copy.5", 0.0, 2.0)]
    scope = ["fedpt/client_step", "", "fedpt/agg_tail",
             "fedpt/server_apply", "fedpt/client_step"]
    plain = {"chip": {"modules": modules, "ops": ops}}
    scoped = {"chip": {"modules": modules,
                       "ops": [_scoped(e, s) for e, s in zip(ops, scope)]}}
    red = tr.reduce(plain, host, kernels=KERNELS)
    # the scopes change no reading of the existing reduction
    assert tr.reduce(scoped, host, kernels=KERNELS) == red
    got = scopes.reduce_scoped(scoped, host)
    assert got.scope_s == pytest.approx({
        "fedpt/client_step": 1.5 + 1.0, scopes.UNSCOPED: 1.0,
        "fedpt/agg_tail": 0.5, "fedpt/server_apply": 0.5})
    assert sum(got.scope_s.values()) == pytest.approx(sum(red.op_s.values()))
    # the round keeps what its children leave: [6, 8]; eval_fn is cut at 9
    assert got.span_s == pytest.approx({
        "grid/round": 2.0, "grid/plan": 0.5, "grid/round_fn": 0.5,
        "grid/wait": 3.5, "grid/eval_fn": 1.0})
    # plain events carry no scope: all of it is unscoped
    assert scopes.reduce_scoped(plain, host).scope_s == pytest.approx(
        {scopes.UNSCOPED: sum(red.op_s.values())})


def test_recorded_trace_unchanged():
    """On the recorded cohort-10 stretch, reading the operations as
    scoped events changes no field of the existing reduction."""
    _lo, _hi, device, _s, host = _load("trace_emnist.json")
    scoped = {c: dict(lines, ops=[_scoped(e, "") for e in lines["ops"]])
              for c, lines in device.items()}
    red = tr.reduce(device, host, kernels=KERNELS)
    assert tr.reduce(scoped, host, kernels=KERNELS) == red
    got = scopes.reduce_scoped(scoped, host)
    assert got.scope_s == pytest.approx(
        {scopes.UNSCOPED: sum(red.op_s.values())})


def test_recorded_scoped_trace_coverage():
    """One round of the cohort-256 cell on the chip: the three scopes
    hold at least 95% of the operations' self time, and the scopes move
    no reading of the existing reduction."""
    _lo, _hi, device, scoped, host = _load("trace_c256_scoped.json")
    red = tr.reduce(device, host, kernels=KERNELS)
    assert tr.reduce(scoped, host, kernels=KERNELS) == red
    got = scopes.reduce_scoped(scoped, host)
    total = sum(got.scope_s.values())
    assert total == pytest.approx(sum(red.op_s.values()))
    for s in ("fedpt/client_step", "fedpt/agg_tail", "fedpt/server_apply"):
        assert got.scope_s.get(s, 0.0) > 0, s
    assert 1 - got.scope_s.get(scopes.UNSCOPED, 0.0) / total >= 0.95
    # the fused tail's kernels sit in the tail's scope
    for lines in scoped.values():
        for e in lines["ops"]:
            if tr.kernel_of(e, KERNELS):
                assert e.scope == "fedpt/agg_tail", e
    assert set(got.span_s) >= {"grid/round", "grid/plan",
                               "grid/cohort_batch", "grid/round_fn",
                               "grid/wait", "grid/bookkeeping"}


def _f(w, x):
    with jax.named_scope("fedpt/client_step"):
        g = jax.vmap(lambda xx: jax.grad(
            lambda w: jnp.sum(jnp.tanh(xx @ w) ** 2))(w))(x)
    with jax.named_scope("fedpt/agg_tail"):
        m = jnp.mean(g, 0)
    with jax.named_scope("fedpt/server_apply"):
        return w - 0.1 * m


def test_hlo_scopes_from_a_trace(tmp_path):
    """The wire reader finds each program's instructions and their scopes
    on a real trace's metadata plane, and ``add_scopes`` names the
    operations of a program execution from them."""
    f = jax.jit(_f)
    w, x = jnp.ones((16, 16)), jnp.ones((4, 8, 16))
    f(w, x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(w, x).block_until_ready()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    programs = scopes.hlo_scopes(path)
    (pid, (name, ops)), = [(p, v) for p, v in programs.items()
                           if v[0].startswith("jit__f")]
    by_scope = {}
    for instr, scope in ops.items():
        by_scope.setdefault(scope, []).append(instr)
    assert {"fedpt/client_step", "fedpt/agg_tail",
            "fedpt/server_apply"} <= set(by_scope)
    instr = by_scope["fedpt/agg_tail"][0]
    # the module line's own id names the program
    assert name.endswith(f"({pid})")
    device = {"chip": {
        "modules": [Event(name, 0.0, 1.0)],
        "ops": [Event(f"%{instr} = f32[16,16] op(...)", 0.1, 0.1),
                Event("%not_an_instruction", 0.3, 0.1),
                Event(f"%{instr}", 2.0, 0.1)]}}   # outside any program
    got = scopes.add_scopes(device, programs)
    assert [e.scope for e in got["chip"]["ops"]] == ["fedpt/agg_tail", "",
                                                     ""]


def _pb(*pairs) -> bytes:
    """Encode (field number, value) pairs: an int as a varint, bytes or
    str length-delimited, a list of ints packed."""
    def varint(v):
        out = bytearray()
        while True:
            b, v = v & 0x7F, v >> 7
            out.append(b | (0x80 if v else 0))
            if not v:
                return bytes(out)
    out = b""
    for num, v in pairs:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            if isinstance(v, str):
                v = v.encode()
            elif isinstance(v, list):
                v = b"".join(varint(i) for i in v)
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _instr(iid, name, op_name="", operands=(), calls=()):
    pairs = [(1, name), (35, iid)]
    if op_name:
        pairs.append((7, _pb((1, "op"), (2, op_name))))
    if operands:
        pairs.append((36, list(operands)))
    if calls:
        pairs.append((38, list(calls)))
    return _pb(*pairs)


def test_instruction_scopes_inherit():
    """Instructions the compiler made without metadata take the scope of
    the instructions they call (a fusion), else of their first scoped
    operand (a layout copy); a copy of a parameter stays unscoped."""
    fused = _pb((1, "fused_computation"), (5, 1),
                (2, _instr(1, "add.1", "jit(f)/fedpt/agg_tail/add")),
                (2, _instr(2, "mul.1", "jit(f)/fedpt/agg_tail/mul")),
                (2, _instr(8, "neg.1", "jit(f)/fedpt/server_apply/neg")))
    entry = _pb((1, "main"), (5, 2),
                (2, _instr(3, "fusion.1", calls=[1])),
                (2, _instr(4, "dot.1", "jit(f)/vmap(fedpt/client_step)/dot")),
                (2, _instr(5, "copy.1", operands=[6, 4])),
                (2, _instr(6, "param.1")),
                (2, _instr(7, "copy.2", operands=[6])))
    proto = _pb((1, _pb((1, "jit_f"), (3, fused), (3, entry))))
    got = scopes.instruction_scopes(memoryview(proto))
    assert got == {"add.1": "fedpt/agg_tail", "mul.1": "fedpt/agg_tail",
                   "neg.1": "fedpt/server_apply",
                   "fusion.1": "fedpt/agg_tail",
                   "dot.1": "fedpt/client_step",
                   "copy.1": "fedpt/client_step", "param.1": "",
                   "copy.2": ""}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Ctx:
    updates: int
    scope_s: dict = None
    span_s: dict = None


SPANS = {"grid/round": 0.1, "grid/plan": 0.2, "grid/cohort_batch": 0.3,
         "grid/round_fn": 0.05, "grid/wait": 5.0, "grid/bookkeeping": 0.4,
         "grid/eval_fn": 0.01, "grid/checkpoint": 0.02,
         "grid/lane_step[None]": 0.5}
SCOPES = {"fedpt/client_step": 4.0, "fedpt/agg_tail": 0.6,
          "fedpt/server_apply": 0.2, "unscoped": 0.1}


def test_host_prep_ms_per_update():
    r = _reader("host_prep_ms_per_update")
    assert r.read(Ctx(10, span_s=SPANS)) == pytest.approx(
        1e3 * (0.2 + 0.3 + 0.05 + 0.5) / 10)
    assert r.read(Ctx(10, span_s={"grid/wait": 1.0})) is None
    assert r.read(Ctx(10)) is None and r.read(Ctx(0, span_s=SPANS)) is None


def test_host_post_ms_per_update():
    r = _reader("host_post_ms_per_update")
    assert r.read(Ctx(10, span_s=SPANS)) == pytest.approx(
        1e3 * (0.4 + 0.01 + 0.02) / 10)
    assert r.read(Ctx(10, span_s={"grid/plan": 1.0})) is None
    assert r.read(Ctx(10)) is None and r.read(Ctx(0, span_s=SPANS)) is None


def test_client_step_ms_per_update():
    r = _reader("client_step_ms_per_update")
    assert r.read(Ctx(10, scope_s=SCOPES)) == pytest.approx(400.0)
    assert r.read(Ctx(10, scope_s={"unscoped": 1.0})) is None
    assert r.read(Ctx(10)) is None and r.read(Ctx(0, scope_s=SCOPES)) is None


def test_server_ms_per_update():
    r = _reader("server_ms_per_update")
    assert r.read(Ctx(10, scope_s=SCOPES)) == pytest.approx(80.0)
    assert r.read(Ctx(10, scope_s={"fedpt/agg_tail": 0.5})) == \
        pytest.approx(50.0)
    assert r.read(Ctx(10, scope_s={"unscoped": 1.0})) is None
    assert r.read(Ctx(10)) is None and r.read(Ctx(0, scope_s=SCOPES)) is None


def test_idle_split_and_fixture():
    """``scoped_trace.idle_split`` splits each idle gap among the spans
    it crosses; ``scoped_trace.fixture`` keeps the middle round."""
    import scoped_trace
    host = [Event("bench/window", 0.0, 10.0),
            Event("grid/round", 0.0, 5.0), Event("grid/plan", 0.0, 1.0),
            Event("grid/wait", 1.0, 3.0), Event("grid/bookkeeping", 4.0, 1.0),
            Event("grid/round", 5.0, 5.0), Event("grid/plan", 5.0, 2.0)]
    # programs [2, 4] and [6, 9]: idle [0, 2], [4, 6], [9, 10]
    modules = [Event("m(1)", 2.0, 2.0), Event("m(1)", 6.0, 3.0)]
    ops = [scopes.ScopedEvent("%f.1", 2.0, 2.0, "", "fedpt/client_step"),
           scopes.ScopedEvent("%f.1", 6.0, 3.0, "", "fedpt/client_step")]
    device = {"chip": {"modules": modules, "ops": ops}}
    assert scoped_trace.idle_split(device, host, 0.0, 10.0) == \
        pytest.approx({"grid/plan": 1.0 + 1.0, "grid/wait": 1.0,
                       "grid/bookkeeping": 1.0, "grid/round": 1.0})
    assert scoped_trace.sum_by_name([("a", 1.0), ("b", 2.0), ("a", 0.5)]) \
        == {"a": 1.5, "b": 2.0}
    fx = scoped_trace.fixture(device, host, 0.0, 10.0, KERNELS)
    assert fx["window"] == [0.0, 5.0]
    assert [e["name"] for e in fx["host"]] == ["grid/round", "grid/plan"]
    assert fx["device"]["chip"]["ops"] == [
        {"name": "%f.1", "start": 1.0, "dur": 3.0, "meta": "",
         "scope": "fedpt/client_step"}]
