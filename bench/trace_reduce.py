"""Reduce a profiler trace of a run's window to the device metrics.

A trace is, on one clock (seconds), for each chip the executions of
whole device programs (the ``XLA Modules`` line of a TPU plane) and of
the operations inside them (``XLA Ops``, where a loop's event encloses
those of its body), and the host's spans (``jax.profiler.TraceAnnotation``
scopes: the benchmark's ``bench/...`` and the grid's ``grid/...``).
``read_xplane`` takes them from the ``.xplane.pb`` file ``jax.profiler``
writes; ``reduce`` computes

* busy time: the union of the program intervals inside the window;
* idle gaps: the window minus that union, each attributed to the
  innermost host span that covers its midpoint, or to
  ``host loop (unattributed)``;
* self time by operation (an enclosing loop's time minus its body's),
  and time by kernel for the names asked for.

Everything is clipped to the window, which is the host span named
``window`` (the benchmark opens ``bench/window`` when its timed window
starts and closes it when it ends).
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

UNATTRIBUTED = "host loop (unattributed)"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # seconds
    dur: float            # seconds
    meta: str = ""        # the event's stat values, joined (kernel names)

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def short(self) -> str:
        """An HLO op event's name is its whole instruction; keep the
        instruction's name (``%fusion.12``)."""
        return self.name.split(" = ", 1)[0]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over chips
    op_s: Dict[str, float]            # summed over chips, by op name
    kernel_s: Dict[str, float]        # summed over chips, by kernel
    gaps: List[Tuple[str, float]]     # every idle gap, longest first
    n_device_events: int

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in self.gaps[:n]]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps_of(busy: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> List[Tuple[float, float]]:
    """The parts of [lo, hi] not covered by the disjoint sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """The innermost (shortest) host span covering the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best: Optional[Event] = None
    for sp in spans:
        if not sp.start <= mid <= sp.end:
            continue
        if best is None or sp.dur < best.dur:
            best = sp
    return best.name if best is not None else UNATTRIBUTED


def kernel_of(ev: Event, kernels: Sequence[str]) -> Optional[str]:
    for k in kernels:
        if k in ev.name or k in ev.meta:
            return k
    return None


def find_window(host: Sequence[Event], name: str) -> Tuple[float, float]:
    spans = [e for e in host if e.name == name]
    if len(spans) != 1:
        raise ValueError(f"expected one host span {name!r}, found "
                         f"{len(spans)}")
    return spans[0].start, spans[0].end


def self_times(ops: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each op with the time in which it is the innermost running op:
    at every instant the time goes to the latest-started op still
    running (the shorter on a tie), so a loop's event keeps what its
    body's events leave, and partly overlapping ops share their overlap
    instead of counting it twice. The self times add up to the union of
    the op intervals."""
    evs = sorted(ops, key=lambda e: (e.start, e.dur))
    own = [0.0] * len(evs)
    points = sorted({t for e in evs for t in (e.start, e.end)})
    active: List[Tuple[float, float, int]] = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(evs) and evs[i].start <= a:
            heapq.heappush(active, (-evs[i].start, evs[i].dur, i))
            i += 1
        while active and evs[active[0][2]].end <= a:
            heapq.heappop(active)
        if active:
            own[active[0][2]] += b - a
    return list(zip(evs, own))


def reduce(device: Dict[str, Dict[str, List[Event]]], host: List[Event],
           window: str = "bench/window",
           kernels: Sequence[str] = (),
           span_prefixes: Sequence[str] = ("bench/", "grid/")) -> Reduced:
    """``device[chip]`` holds ``"modules"`` (program executions) and
    ``"ops"`` (operations) lists."""
    lo, hi = find_window(host, window)
    spans = [e for e in host if e.name.startswith(tuple(span_prefixes))
             and e.name != window]
    op_s: Dict[str, float] = {}
    kernel_s: Dict[str, float] = {k: 0.0 for k in kernels}
    busy_total = 0.0
    gaps: List[Tuple[str, float]] = []
    n = 0
    for _chip, lines in sorted(device.items()):
        inside = clip([(e.start, e.end) for e in lines["modules"]], lo, hi)
        n += len(inside)
        busy = union(inside)
        busy_total += sum(e - s for s, e in busy)
        gaps += [(attribute(g, spans), g[1] - g[0])
                 for g in gaps_of(busy, lo, hi)]
        ops = [e for e in lines["ops"] if e.end > lo and e.start < hi]
        for ev, own in self_times(ops):
            frac = (min(ev.end, hi) - max(ev.start, lo)) / ev.dur \
                if ev.dur > 0 else 0.0
            op_s[ev.short] = op_s.get(ev.short, 0.0) + own * frac
            k = kernel_of(ev, kernels)
            if k is not None:
                kernel_s[k] += own * frac
    chips = max(len(device), 1)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=hi - lo, busy_s=busy_total / chips, op_s=op_s,
                   kernel_s=kernel_s, gaps=gaps, n_device_events=n)


# ---------------------------------------------------------------------------
# reading jax.profiler's xplane.pb


def _stat_text(ev) -> str:
    parts = []
    for _k, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def read_xplane(path: str):
    """(device lines by chip plane, host events) from an .xplane.pb.

    Device planes are those named ``/device:TPU:<n>``; for each, the
    events of its ``XLA Modules`` line (``"modules"``) and of its
    ``XLA Ops`` line (``"ops"``, a Pallas kernel being one custom call).
    Host events are every event on the ``/host:CPU`` plane. Times are in
    seconds on the trace's clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    wanted = {"XLA Modules": "modules", "XLA Ops": "ops"}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines: Dict[str, List[Event]] = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name in wanted:
                    lines[wanted[line.name]] = [
                        Event(ev.name, ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9, _stat_text(ev))
                        for ev in line.events]
            device[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    host.append(Event(ev.name, ev.start_ns * 1e-9,
                                      ev.duration_ns * 1e-9))
    return device, host
