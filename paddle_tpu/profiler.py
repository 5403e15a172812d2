"""Profiler front-end over jax.profiler (XPlane/Xprof traces).

TPU-native replacement for the reference's profiler stack:
  * python context manager `profiler` — reference fluid/profiler.py:225
  * RecordEvent host spans — reference platform/profiler.h:81
  * CUPTI device tracer -> here the XLA runtime's own trace collection
    (/root/reference/paddle/fluid/platform/device_tracer.cc:272); the output
    is an XPlane protobuf directory loadable in TensorBoard/Xprof instead of
    the reference's chrome://tracing JSON (its timeline.py).

The stage counters below are thin shims over the unified telemetry
registry (observability/): record_stage/bump/stage_counters keep their PR 2
API exactly (every legacy call site lands unchanged), but the accumulators
now live in the one registry snapshot() reads back, and timed stages gain
streaming-percentile histograms when FLAGS_obs_enable is on.
"""
from __future__ import annotations

import contextlib
import os
import threading

import jax

from . import flags
from . import observability as _obs

__all__ = ["profiler", "start_profiler", "stop_profiler", "RecordEvent",
           "record_event", "record_stage", "stage_timer", "stage_counters",
           "bump"]


def _resolve_dir(path: str | None) -> str:
    return path or flags.get_flag("profiler_dir")


# trace lifecycle state: start/stop must pair, and a failed start (e.g.
# os.makedirs on a read-only path) must not leave a half-open trace that
# makes every later start_profiler fail with a raw jax error
_trace_lock = threading.Lock()
_trace_active = False


def _begin_trace(path: str) -> None:
    global _trace_active
    with _trace_lock:
        if _trace_active:
            raise RuntimeError(
                "a profiler trace is already active; call stop_profiler() "
                "(or leave the profiler() context) before starting another")
        # makedirs BEFORE start_trace: if the directory cannot be created
        # nothing has started and the profiler stays cleanly stoppable/
        # restartable (no half-open trace)
        os.makedirs(path, exist_ok=True)
        jax.profiler.start_trace(path)
        _trace_active = True


def _end_trace() -> None:
    global _trace_active
    with _trace_lock:
        if not _trace_active:
            raise RuntimeError(
                "no active profiler trace — call start_profiler() (or use "
                "the profiler() context manager) before stop_profiler()")
        try:
            jax.profiler.stop_trace()
        finally:
            _trace_active = False


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str | None = None,
             profile_path: str | None = None):
    """`with profiler.profiler(...):` traces everything inside to an XPlane
    directory. `state`/`sorted_key` are accepted for reference API parity
    (fluid/profiler.py:225); on TPU the trace always covers host + device and
    sorting happens in the viewer."""
    _begin_trace(_resolve_dir(profile_path))
    try:
        yield
    finally:
        _end_trace()


def start_profiler(state: str = "All", profile_path: str | None = None):
    """Imperative start (reference fluid/profiler.py start_profiler)."""
    _begin_trace(_resolve_dir(profile_path))


def stop_profiler(sorted_key: str | None = None, profile_path: str | None = None):
    """Stop the active trace. Both args are reference-API-parity no-ops: the
    trace lands in the directory given to start_profiler, and sorting happens
    in the viewer. Raises RuntimeError (naming start_profiler) when no trace
    is active instead of surfacing the raw jax error."""
    _end_trace()


class RecordEvent(contextlib.ContextDecorator):
    """Named host span visible in the trace (reference platform/profiler.h:81
    RAII RecordEvent). Usable as a context manager or decorator."""

    def __init__(self, name: str):
        self._name = name
        self._anns: list = []  # stack: one instance may nest/recurse

    def __enter__(self):
        ann = jax.profiler.TraceAnnotation(self._name)
        ann.__enter__()
        self._anns.append(ann)
        return self

    def __exit__(self, *a):
        return self._anns.pop().__exit__(*a)


record_event = RecordEvent


# -- pipeline stage counters --------------------------------------------------
# Cheap always-on accumulators for the async feed/dispatch pipeline (host
# ingest / device transfer / dispatch / window drain). Unlike the XPlane
# trace these need no viewer: the benchmark's `stage_seconds` reader
# (`host_dispatch_ms`, `host_prepare_ms`) and ad-hoc debugging read them
# directly to see which stage the end-to-end path is losing time to.
# Since ISSUE 13 the storage is the observability registry — same API, same
# cost, but the counters ride the unified snapshot/export path too.


def record_stage(stage: str, seconds: float, events: int = 1):
    """Accumulate `seconds` of wall time against a named pipeline stage."""
    _obs.stage_record(stage, seconds, events)


def bump(stage: str, events: int = 1):
    """Count an event with no wall time against a named counter — the
    robustness paths (corrupt-record skips, non-finite send drops, guard
    skips) use these so post-mortems can see how much was dropped."""
    _obs.stage_record(stage, 0.0, events)


def stage_timer(stage: str):
    """`with stage_timer(stage):` accumulates the block's wall time against
    the stage and, with FLAGS_obs_enable on, is a span like `obs.span`: a
    TraceAnnotation on the profiler's clock, nested under the span that
    encloses it."""
    return _obs.registry().stage_timer(stage)


def stage_counters(reset: bool = False) -> dict:
    """Snapshot {stage: {"events": n, "seconds": s}}; reset=True zeroes the
    accumulators after reading (epoch-scoped measurements)."""
    return _obs.stage_counters(reset)
