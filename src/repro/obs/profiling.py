"""Optional ``jax.profiler`` annotation hooks for traced grid runs.

Virtual-time spans (obs/trace.py) say *when the simulated fleet* was
busy; a wall-time profile says where the *host* actually spent its
compute. With ``TelemetryConfig(profile=True)`` the grid wraps its two
jitted hot paths — the vmapped client lane step and the buffered-apply
server tail — in named ``jax.profiler.TraceAnnotation`` scopes, so a
profile captured around the run (``jax.profiler.trace(...)`` or
``start_trace``/``stop_trace``) shows ``grid/lane_step`` /
``grid/server_apply`` blocks that line up with the virtual-time flush
spans one-to-one.

Everything degrades to a plain call when profiling is off — the wrapper
adds one function frame, never a device sync.
"""
from __future__ import annotations

import functools
from typing import Callable

from jax.profiler import TraceAnnotation as _TraceAnnotation


def annotate(fn: Callable, name: str,
             enabled: bool = True) -> Callable:
    """Wrap ``fn`` so each call runs inside a ``TraceAnnotation(name)``.
    With ``enabled=False`` (telemetry off, or profile not requested)
    returns ``fn`` unchanged — zero added frames on the default path."""
    if not enabled:
        return fn

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with _TraceAnnotation(name):
            return fn(*args, **kwargs)

    return wrapped


def annotate_map(fns: dict, name: str, enabled: bool = True) -> dict:
    """``annotate`` over a dict of callables (the grid's per-tier lane
    step / client step tables), tagging each with its key."""
    if not enabled:
        return fns
    return {k: annotate(fn, f"{name}[{k}]") for k, fn in fns.items()}
