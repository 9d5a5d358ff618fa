"""Wall-clock spans for the grid's host loop, always on.

Virtual-time spans (obs/trace.py) say *when the simulated fleet* was
busy; these say where the *host* spends wall-clock time. They are
``jax.profiler`` annotations: with no profiler session active each one
costs a single check, and under ``jax.profiler.trace(...)`` (or
``start_trace``/``stop_trace``) they land on the ``/host:CPU`` plane, on
the same clock as the device's operations. They draw from no PRNG
stream and add no device sync, so a run's history is bit-identical with
and without a profiler session.

The grid's names (``sim/grid.py``):

* sync, one ``grid/round`` per round (``step_num`` = round index) with
  the children ``grid/plan`` (tier map, cohort selection,
  ``plan_sync_round``), ``grid/cohort_batch`` (the host batch and its
  weights, put on the device; args ``clients``, ``bytes``),
  ``grid/round_fn`` (the dispatch), ``grid/wait`` (the round's one
  blocking host sync), ``grid/bookkeeping`` (quarantine, billing,
  counters, the policy), ``grid/prefetch`` (arg ``round`` = r+1: round
  r+1's ``grid/plan`` and ``grid/cohort_batch``, made while round r
  runs), ``grid/eval_fn`` and ``grid/checkpoint``. Only the first round
  of a call, and a round after one that could not look ahead, opens
  its own ``grid/plan`` and ``grid/cohort_batch``;
* async, one ``grid/flush`` per server update (``step_num`` = updates
  applied before it) with ``grid/restack`` (lane batches and the
  ``(K, size)`` buffer), ``grid/lane_step[k]``, ``grid/server_apply``,
  ``grid/wait``, ``grid/bookkeeping`` and ``grid/eval_fn``;
  ``grid/client_step[k]`` (no lanes) runs at dispatch, between flushes,
  and ``grid/checkpoint`` right after its flush.

The device side carries ``jax.named_scope`` paths instead
(``core/fedpt.py``: ``fedpt/client_step``, ``fedpt/agg_tail``,
``fedpt/server_apply``), which reach each HLO instruction's
``metadata.op_name``.
"""
from __future__ import annotations

import functools
from typing import Callable

from jax.profiler import StepTraceAnnotation as _StepTraceAnnotation
from jax.profiler import TraceAnnotation as _TraceAnnotation


def span(name: str, **args) -> _TraceAnnotation:
    """A wall-clock span: ``with span("grid/plan"): ...``. ``args`` ride
    on the trace event; more can be added inside the block with the
    span's ``set_metadata(**args)``."""
    return _TraceAnnotation(name, **args)


def round_span(name: str, step: int) -> _StepTraceAnnotation:
    """The span of one round or flush: every span opened inside it
    belongs to step ``step``."""
    return _StepTraceAnnotation(name, step_num=int(step))


def annotate(fn: Callable, name: str) -> Callable:
    """Wrap ``fn`` so each call runs inside ``span(name)``."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with _TraceAnnotation(name):
            return fn(*args, **kwargs)

    return wrapped


def annotate_map(fns: dict, name: str) -> dict:
    """``annotate`` over a dict of callables (the grid's per-tier lane
    step / client step tables), tagging each with its key."""
    return {k: annotate(fn, f"{name}[{k}]") for k, fn in fns.items()}
