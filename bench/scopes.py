"""Put a traced window's device time on the round program's named scopes
and its host time on the grid's spans.

The round programs name their stages with ``jax.named_scope``
(``fedpt/client_step``, ``fedpt/agg_tail``, ``fedpt/server_apply``),
which reaches each HLO instruction's ``metadata.op_name``; the grid's
host loop opens ``grid/...`` spans (``repro/obs/profiling.py``). A
``jax.profiler`` trace holds both on one clock:

* each device operation event names its HLO instruction, and the
  ``/host:metadata`` plane holds every program's ``Hlo Proto``, whose
  instructions carry their ``op_name``. ``jax.profiler.ProfileData``
  does not expose that plane's event metadata, so ``hlo_scopes`` reads
  it from the ``.xplane.pb`` file with a small protobuf wire reader (no
  ``tensorflow`` import); instructions the compiler added without
  metadata inherit a scope (``instruction_scopes``). The operation
  events themselves carry no op name on a TPU v5e;
* host spans are ``trace_reduce.Event``s already.

``reduce_scoped`` gives, clipped to the window like ``trace_reduce.reduce``
(whose fields it leaves alone):

* ``scope_s``: operation self time by innermost ``fedpt/`` scope, summed
  over chips, with what no scope names under ``"unscoped"``; its values
  add up to ``sum(reduce(...).op_s.values())``;
* ``span_s``: self time of each ``grid/`` host span (its time inside
  the window not covered by a span nested in it).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import trace_reduce
from trace_reduce import Event

UNSCOPED = "unscoped"
SCOPE_RE = re.compile(r"fedpt/[A-Za-z0-9_]+")
PROGRAM_ID_RE = re.compile(r"\((\d+)\)\s*$")


def scope_of(op_name: str) -> str:
    """The innermost ``fedpt/...`` scope of a framework op name
    (``jit(f)/vmap(fedpt/client_step)/dot`` -> ``fedpt/client_step``),
    or ``""``."""
    found = SCOPE_RE.findall(op_name)
    return found[-1] if found else ""


@dataclasses.dataclass(frozen=True)
class ScopedEvent(Event):
    scope: str = ""


@dataclasses.dataclass
class ScopedReduced:
    scope_s: Dict[str, float]
    span_s: Dict[str, float]


# ---------------------------------------------------------------------------
# protobuf wire format, just enough to walk an XSpace and an HloProto


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited field, raw bytes for a
    fixed-width one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            val, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield num, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map<int64,
# XEventMetadata>), .stat_metadata = 5 (map<int64, XStatMetadata>);
# XEventMetadata.id = 1, .name = 2, .stats = 5; XStatMetadata.id = 1,
# .name = 2; XStat.metadata_id = 1, .bytes_value = 6.
# HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto.instructions = 2, .id = 5; HloInstructionProto.name
# = 1, .metadata = 7, .id = 35, .operand_ids = 36,
# .called_computation_ids = 38; OpMetadata.op_name = 2.


def _map_entries(v) -> Tuple[int, memoryview]:
    key, val = 0, memoryview(b"")
    for num, x in fields(v):
        if num == 1:
            key = x
        elif num == 2:
            val = x
    return key, val


def _ids(x) -> List[int]:
    """A repeated int64 field's values, packed or not."""
    if isinstance(x, int):
        return [x]
    out, i = [], 0
    while i < len(x):
        v, i = _varint(x, i)
        out.append(v)
    return out


@dataclasses.dataclass
class _Instr:
    name: str = ""
    scope: str = ""
    operands: List[int] = dataclasses.field(default_factory=list)
    calls: List[int] = dataclasses.field(default_factory=list)


def instruction_scopes(hlo_proto) -> Dict[str, str]:
    """{instruction name: scope} of one serialized ``HloProto``. An
    instruction's scope is the innermost ``fedpt/`` scope of its own
    ``op_name``; one the compiler made without metadata (a layout copy,
    a fusion whose root carries none) takes the scope most of the
    instructions it calls carry, else that of its first scoped
    operand."""
    instrs: Dict[int, _Instr] = {}
    comps: Dict[int, List[int]] = {}
    for num, module in fields(hlo_proto):
        if num != 1:
            continue
        for cnum, comp in fields(module):
            if cnum != 3:
                continue
            cid, members = None, []
            for inum, x in fields(comp):
                if inum == 5:
                    cid = x
                if inum != 2:
                    continue
                ins, iid = _Instr(), None
                for fnum, y in fields(x):
                    if fnum == 1:
                        ins.name = _text(y)
                    elif fnum == 7:
                        for mnum, z in fields(y):
                            if mnum == 2:
                                ins.scope = scope_of(_text(z))
                    elif fnum == 35:
                        iid = y
                    elif fnum == 36:
                        ins.operands += _ids(y)
                    elif fnum == 38:
                        ins.calls += _ids(y)
                instrs[iid] = ins
                members.append(iid)
            comps[cid] = members
    resolved: Dict[int, str] = {}

    def called_scopes(iid, seen):
        for c in instrs[iid].calls:
            for m in comps.get(c, ()):
                if m not in seen:
                    seen.add(m)
                    if instrs[m].scope:
                        yield instrs[m].scope
                    yield from called_scopes(m, seen)

    def resolve(iid, depth=0) -> str:
        if iid in resolved:
            return resolved[iid]
        ins = instrs[iid]
        scope = ins.scope
        if not scope:
            counts: Dict[str, int] = {}
            for sc in called_scopes(iid, {iid}):
                counts[sc] = counts.get(sc, 0) + 1
            if counts:
                scope = max(sorted(counts), key=counts.get)
        if not scope and depth < 64:
            for op in ins.operands:
                if op in instrs:
                    scope = resolve(op, depth + 1)
                    if scope:
                        break
        resolved[iid] = scope
        return scope

    return {ins.name: resolve(iid) for iid, ins in instrs.items()}


def hlo_scopes(path: str) -> Dict[int, Tuple[str, Dict[str, str]]]:
    """program id -> (module name, {instruction name: scope}) from the
    ``Hlo Proto``s on the trace's ``/host:metadata`` plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[int, Tuple[str, Dict[str, str]]] = {}
    for num, plane in fields(space):
        if num != 1:
            continue
        name = ""
        metas, stat_names = [], {}
        for pnum, x in fields(plane):
            if pnum == 2:
                name = _text(x)
            elif pnum == 4:
                metas.append(x)
            elif pnum == 5:
                sid, smeta = _map_entries(x)
                for snum, y in fields(smeta):
                    if snum == 2:
                        stat_names[sid] = _text(y)
        if name != "/host:metadata":
            continue
        for entry in metas:
            pid, meta = _map_entries(entry)
            mname, proto = "", None
            for mnum, x in fields(meta):
                if mnum == 2:
                    mname = _text(x)
                elif mnum == 5:
                    sid, blob = None, None
                    for snum, y in fields(x):
                        if snum == 1:
                            sid = y
                        elif snum == 6:
                            blob = y
                    if blob is not None and stat_names.get(sid) == \
                            "Hlo Proto":
                        proto = blob
            if proto is not None:
                out[pid] = (mname, instruction_scopes(proto))
    return out


# ---------------------------------------------------------------------------
# scopes for the device operations


def _program_of(modules: Sequence[Event], starts: List[float],
                ev: Event) -> Optional[int]:
    """The program id of the module execution enclosing ``ev``, from the
    module event's name (``jit_round_step(<id>)``)."""
    i = bisect.bisect_right(starts, ev.start) - 1
    if i < 0 or modules[i].end < ev.start:
        return None
    m = PROGRAM_ID_RE.search(modules[i].name)
    return int(m.group(1)) if m else None


def add_scopes(device: Dict[str, Dict[str, List[Event]]],
               programs: Dict[int, Tuple[str, Dict[str, str]]]):
    """``device`` with each operation a ``ScopedEvent`` carrying the scope
    its program's HLO gives its instruction (``""`` when none)."""
    out = {}
    for chip, lines in device.items():
        modules = sorted(lines["modules"], key=lambda e: e.start)
        starts = [e.start for e in modules]
        ops = []
        for ev in lines["ops"]:
            instrs = programs.get(_program_of(modules, starts, ev),
                                  ("", {}))[1]
            ops.append(ScopedEvent(ev.name, ev.start, ev.dur, ev.meta,
                                   instrs.get(ev.short.lstrip("%"), "")))
        out[chip] = dict(lines, ops=ops)
    return out


def read_scoped(path: str):
    """(device lines with scoped operations, host events) from an
    ``.xplane.pb``."""
    device, host = trace_reduce.read_xplane(path)
    return add_scopes(device, hlo_scopes(path)), host


# ---------------------------------------------------------------------------
# the reduction


def reduce_scoped(device: Dict[str, Dict[str, List[Event]]],
                  host: List[Event], window: str = "bench/window",
                  span_prefix: str = "grid/") -> ScopedReduced:
    """Device self time by scope and host self time by span, in the
    window (see the module docstring)."""
    lo, hi = trace_reduce.find_window(host, window)
    scope_s: Dict[str, float] = {}
    for _chip, lines in sorted(device.items()):
        ops = [e for e in lines["ops"] if e.end > lo and e.start < hi]
        # the same clipping as trace_reduce.reduce's op_s
        for ev, own in trace_reduce.self_times(ops):
            frac = (min(ev.end, hi) - max(ev.start, lo)) / ev.dur \
                if ev.dur > 0 else 0.0
            key = getattr(ev, "scope", "") or UNSCOPED
            scope_s[key] = scope_s.get(key, 0.0) + own * frac
    spans = [Event(e.name, max(e.start, lo), min(e.end, hi) - max(e.start, lo))
             for e in host if e.name.startswith(span_prefix)
             and e.end > lo and e.start < hi]
    span_s: Dict[str, float] = {}
    for ev, own in trace_reduce.self_times(spans):
        span_s[ev.name] = span_s.get(ev.name, 0.0) + own
    return ScopedReduced(scope_s=scope_s, span_s=span_s)
