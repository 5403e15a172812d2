"""Typed, thread-safe metrics registry — the one telemetry spine.

Five previously-incompatible instrumentation vocabularies (profiler stage
counters, the serving engine's stats dict, watchdog dumps, guardrail
events, tuner provenance) all land here, so one `snapshot()` answers what
used to take five bespoke readers. Design points:

  * one lock, plain dicts: the hot path (a counter bump) costs one lock
    acquisition and two dict operations — the same as the PR 2 stage
    counters it replaces, so always-on instrumentation stays ~free;
  * histograms are streaming log-bucketed (8 buckets/decade, 1e-9..1e9):
    p50/p95/p99 in O(buckets) with bounded memory, no reservoir, no sort;
  * labeled series: a (name, labels) pair is one series — the tuner's
    per-(op, tier) provenance and the embedding engine's per-table
    counters stop being ad-hoc nested dicts;
  * declared schema: names are registered up front (schema.DECLARED);
    free-form names still record but surface in `snapshot()["undeclared"]`;
  * spans nest: a per-thread stack gives every span its parent, its self
    time and the `step` of its root, and both ways to time host work
    (`span`, `profiler.stage_timer`) are the one `Span` class, a
    `jax.profiler.TraceAnnotation` each (within a per-thread budget, by
    whole trees), so they sit on the device trace's clock; closing one
    takes the lock once, and builds a record only when a sink is attached;
  * `snapshot(reset=True)` is atomic — read-and-zero under the lock, so
    concurrent writers can never be double-counted or lost across the
    reset boundary (the 8-thread test pins this);
  * FLAGS_obs_enable gates the *extra* machinery (histograms, events,
    spans, exporter sinks). Counters/gauges/stages stay on either way so
    `profiler.stage_counters()` semantics never depend on the flag — off
    reduces the layer to exactly the legacy accumulator cost (the bench
    telemetry A/B measures the difference; gate ceiling 2%).
"""
from __future__ import annotations

import bisect
import functools
import gc
import math
import threading
import time
from collections import deque

import numpy as np

from .. import flags
from . import schema as _schema
from .compile_events import CompileWatch

__all__ = ["MetricsRegistry", "registry", "enabled", "counter_inc",
           "gauge_set", "histogram_observe", "event", "span", "spanned",
           "snapshot",
           "stage_record", "stage_counters", "reset", "attach_sink",
           "detach_sink", "gc_pause_seconds", "note_import"]


def enabled() -> bool:
    """FLAGS_obs_enable (histograms/events/spans/sinks). Counters, gauges
    and stage accumulators are always on."""
    try:
        return bool(flags.get_flag("obs_enable"))
    except KeyError:  # flags module mid-import
        return True


# log-spaced histogram bounds: 8 per decade over 1e-9 .. 1e9 (145 bounds,
# 146 buckets). Bucket ratio 10^(1/8) ~= 1.33, so a reported percentile is
# within ~15% of the true one — plenty for latency SLOs.
_BOUNDS = tuple(10.0 ** (k / 8.0) for k in range(-72, 73))


class _Histogram:
    __slots__ = ("count", "total", "vmin", "vmax", "buckets")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf
        self.buckets = [0] * (len(_BOUNDS) + 1)

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v
        self.buckets[bisect.bisect_right(_BOUNDS, v)] += 1

    def quantile(self, q: float) -> float | None:
        if not self.count:
            return None
        target = q * self.count
        cum = 0
        for i, n in enumerate(self.buckets):
            cum += n
            if n and cum >= target:
                lo = _BOUNDS[i - 1] if i > 0 else self.vmin
                hi = _BOUNDS[i] if i < len(_BOUNDS) else self.vmax
                mid = math.sqrt(lo * hi) if lo > 0 and hi > 0 else hi
                return min(max(mid, self.vmin), self.vmax)
        return self.vmax

    def summary(self) -> dict:
        if not self.count:
            return {"count": 0, "sum": 0.0, "min": None, "max": None,
                    "p50": None, "p95": None, "p99": None}
        return {"count": self.count, "sum": self.total,
                "min": self.vmin, "max": self.vmax,
                "p50": self.quantile(0.50), "p95": self.quantile(0.95),
                "p99": self.quantile(0.99)}


def _lkey(labels: dict | None) -> tuple:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items())) \
        if labels else ()


def _fmt(key: tuple) -> str:
    """Series display key: `name` or `name{k="v",...}` (Prometheus style)."""
    name, labels = key
    if not labels:
        return name
    body = ",".join(f'{k}="{v}"' for k, v in labels)
    return f"{name}{{{body}}}"


def base_name(series_key: str) -> str:
    """Strip the label body from a formatted series key."""
    return series_key.split("{", 1)[0]


# What one thread may put on the profiler's clock: a trace reduction that
# holds every device idle gap against every host span (benchmark/
# trace_reduce._attribute) costs gaps x spans, and both grow with the loop's
# rate. At 140 serving steps a second a 3 s slice held 257k gaps and 5,200
# spans of the program's, and its reduction took 608 s (PERF.md section 6,
# PR 24). A loop under the budget is annotated whole; a faster one is sampled
# by whole span trees, and the registry and the stream still see every span.
ANNOTATED_SPANS_PER_S = 100.0
ANNOTATED_SPANS_BURST = 200.0


class _ThreadState(threading.local):
    """The open spans of one thread, innermost last, the dict the innermost
    collecting span books self seconds into, and the thread's budget of
    profiler annotations: `annotate` is the decision its outermost open span
    took for its whole tree."""

    def __init__(self):
        self.stack: list = []
        self.collect: dict | None = None
        self.annotate = True
        self.budget = ANNOTATED_SPANS_BURST
        self.budget_at = time.perf_counter()


_tls = _ThreadState()
_TraceAnnotation = None  # jax.profiler.TraceAnnotation, resolved once


def _annotation_cls():
    global _TraceAnnotation
    if _TraceAnnotation is None:
        import jax  # deferred: tools that only read streams never pay it

        _TraceAnnotation = jax.profiler.TraceAnnotation
    return _TraceAnnotation


class Span:
    """One timed host interval: a `jax.profiler.TraceAnnotation` (so it sits
    on the device trace's clock; an outermost span decides it for its whole
    tree, within the thread's budget of `ANNOTATED_SPANS_PER_S`), a sample
    in the registry (`<name>.seconds` for a span, the `[events, seconds]`
    stage for a stage timer) and, when a sink is attached, one JSONL record
    carrying `parent` (the enclosing span's name) and `step` (the `step`
    attribute of the outermost span that has one), so the spans of one
    iteration share an identifier.

    After exit `dur_s` is its duration and `self_s` that minus what its
    direct children covered. A span opened with `collect=<dict>` has the self
    seconds of itself and everything under it added to that dict by name:
    the entries sum to its duration."""

    __slots__ = ("_reg", "name", "labels", "attrs", "_stage", "_collect",
                 "_outer_collect", "_ann", "_t0", "_child_s", "parent",
                 "step", "dur_s", "self_s")

    def __init__(self, reg, name, labels, attrs, stage=False, collect=None):
        self._reg = reg
        self.name = name
        self.labels = labels
        self.attrs = attrs
        self._stage = stage
        self._collect = collect

    def note(self, **attrs) -> None:
        """Attributes learned after entry: they reach the sink record, not
        the annotation (which took its attributes when it was opened)."""
        self.attrs.update(attrs)

    def __enter__(self):
        st = _tls
        stack = st.stack
        attrs = self.attrs
        if stack:
            outer = stack[-1]
            self.parent = outer.name
            self.step = attrs["step"] if "step" in attrs else outer.step
        else:
            self.parent = None
            self.step = attrs.get("step")
            now = time.perf_counter()
            st.budget = min(ANNOTATED_SPANS_BURST, st.budget
                            + (now - st.budget_at) * ANNOTATED_SPANS_PER_S)
            st.budget_at = now
            st.annotate = st.budget >= 1.0  # a tree may overdraw: it is whole
        if self._collect is not None:
            self._outer_collect = st.collect
            st.collect = self._collect
        stack.append(self)
        self._child_s = 0.0
        if st.annotate:
            st.budget -= 1.0
            self._ann = ann = _annotation_cls()(self.name, **attrs)
            ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        st = _tls
        stack = st.stack
        name = self.name
        stack.pop()  # `with` blocks of one thread close innermost first
        self.dur_s = dt
        self.self_s = own = dt - self._child_s
        if stack:
            stack[-1]._child_s += dt
        collect = st.collect
        if collect is not None:
            collect[name] = collect.get(name, 0.0) + own
            if self._collect is not None:
                st.collect = self._outer_collect
        reg = self._reg
        with reg._lock:
            if self._stage:
                reg._stage_locked(name, dt, 1, True)
            else:
                reg._observe_locked(name + ".seconds", self.labels, dt)
        sinks = reg._sinks
        if sinks:
            rec = {"ts": time.time(), "type": "span", "name": name,
                   "dur_s": round(dt, 9)}
            if self.labels:
                rec["labels"] = dict(self.labels)
            if self.attrs:
                rec["attrs"] = dict(self.attrs)
            if self.parent is not None:
                rec["parent"] = self.parent
            if self.step is not None:
                rec["step"] = self.step
            _emit(sinks, rec)
        return False


class _NullSpan:
    """What `span` hands out while FLAGS_obs_enable is off."""

    __slots__ = ()
    dur_s = self_s = 0.0

    def note(self, **attrs) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _BareStageTimer:
    """A stage timer while FLAGS_obs_enable is off: the always-on
    `[events, seconds]` accumulator and nothing else."""

    __slots__ = ("_reg", "_stage", "_t0")

    def __init__(self, reg, stage):
        self._reg = reg
        self._stage = stage

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._reg.stage_record(self._stage, time.perf_counter() - self._t0)
        return False


def _emit(sinks, rec: dict) -> None:
    for s in sinks:
        try:
            s(rec)
        except Exception:  # noqa: BLE001 — a broken sink never kills work
            pass


class MetricsRegistry:
    """Thread-safe typed metric store; see module docstring."""

    def __init__(self, schema=None, max_events: int = 1024):
        self._lock = threading.Lock()
        self._schema: dict[str, dict] = {}
        self._counters: dict[tuple, float] = {}
        # (series, vector): counts still on the device (`counter_defer`)
        self._deferred: list = []
        self._gauges: dict[tuple, float] = {}
        self._hists: dict[tuple, _Histogram] = {}
        self._stages: dict[str, list] = {}  # name -> [events, seconds]
        self._undeclared: set[str] = set()
        self._events: deque = deque(maxlen=max(1, int(max_events)))
        self._sinks: tuple = ()  # replaced whole, so readers take no lock
        for spec in (schema or ()):
            name, kind = spec[0], spec[1]
            help_ = spec[2] if len(spec) > 2 else ""
            labels = spec[3] if len(spec) > 3 else ()
            self.declare(name, kind, help_, labels)

    # -- schema --------------------------------------------------------------
    def declare(self, name: str, kind: str, help: str = "",
                labels=()) -> None:
        with self._lock:
            self._schema[name] = {"kind": kind, "help": help,
                                  "labels": tuple(labels)}
            self._undeclared.discard(name)

    def declared_names(self) -> frozenset:
        with self._lock:
            return frozenset(self._schema)

    def _note(self, name: str) -> None:
        # caller holds self._lock
        if name not in self._schema:
            self._undeclared.add(name)

    # -- mutators ------------------------------------------------------------
    def counter_inc(self, name: str, value: float = 1,
                    labels: dict | None = None) -> None:
        key = (name, _lkey(labels))
        with self._lock:
            self._note(name)
            self._counters[key] = self._counters.get(key, 0) + value

    def counter_defer(self, series, vector) -> None:
        """Counts that a device program produced and nobody has read:
        `vector` is a device array, `series` a list of `(name, labels,
        index)`, element `index` counting into `name{labels}`. Held as it
        is (no sync, no copy) until `snapshot()` reads it to the host, or
        `reset()` of a prefix every one of its names starts with drops it."""
        with self._lock:
            self._deferred.append((series, vector))

    def _settle(self) -> None:
        """Read the deferred vectors to the host and count them."""
        with self._lock:
            pending, self._deferred = self._deferred, []
        for series, vector in pending:
            values = np.asarray(vector, np.float64).reshape(-1)
            for name, labels, index in series:
                self.counter_inc(name, float(values[index]), labels)

    def gauge_set(self, name: str, value: float,
                  labels: dict | None = None) -> None:
        key = (name, _lkey(labels))
        with self._lock:
            self._note(name)
            self._gauges[key] = float(value)

    def histogram_observe(self, name: str, value: float,
                          labels: dict | None = None) -> None:
        if not enabled():
            return
        with self._lock:
            self._observe_locked(name, labels, value)

    def _observe_locked(self, name, labels, value) -> None:
        key = (name, _lkey(labels) if labels else ())
        self._note(name)
        h = self._hists.get(key)
        if h is None:
            h = self._hists[key] = _Histogram()
        h.observe(value)

    def stage_record(self, stage: str, seconds: float,
                     events: int = 1) -> None:
        """The profiler.record_stage/bump accumulator: exact legacy
        semantics ([events, seconds] per stage) plus, when the layer is
        enabled, a latency histogram per timed stage."""
        hist = seconds > 0.0 and enabled()
        with self._lock:
            self._stage_locked(stage, seconds, events, hist)

    def _stage_locked(self, stage, seconds, events, hist) -> None:
        self._note(stage)
        c = self._stages.get(stage)
        if c is None:
            c = self._stages[stage] = [0, 0.0]
        c[0] += events
        c[1] += seconds
        if hist:
            h = self._hists.get((stage, ()))
            if h is None:
                h = self._hists[(stage, ())] = _Histogram()
            h.observe(seconds)

    def event(self, name: str, payload: dict | None = None,
              level: str = "info") -> dict | None:
        if not enabled():
            return None
        rec = {"ts": time.time(), "type": "event", "name": name,
               "level": level}
        if payload:
            rec["payload"] = payload
        stack = _tls.stack
        if stack:
            rec["parent"] = stack[-1].name  # the span it happened under
        with self._lock:
            self._note(name)
            self._events.append(rec)
        _emit(self._sinks, rec)
        return rec

    def span(self, name: str, labels: dict | None = None, *,
             collect: dict | None = None, **attrs):
        """Named span (see `Span`): `labels` key the `<name>.seconds`
        histogram series, `attrs` go to the annotation and the sink record
        only (a request id there mints no series). No-op when the layer is
        disabled."""
        if not enabled():
            return _NULL_SPAN
        return Span(self, name, labels, attrs, collect=collect)

    def stage_timer(self, stage: str):
        """`profiler.stage_timer`: a span whose sample is the stage's
        `[events, seconds]` accumulator (always on, also with the layer
        disabled) and the stage's own histogram."""
        if not enabled():
            return _BareStageTimer(self, stage)
        return Span(self, stage, None, {}, stage=True)

    # -- readers -------------------------------------------------------------
    def stage_counters(self, reset: bool = False) -> dict:
        with self._lock:
            snap = {k: {"events": v[0], "seconds": v[1]}
                    for k, v in self._stages.items()}
            if reset:
                self._stages.clear()
        return snap

    def snapshot(self, reset: bool = False) -> dict:
        """One atomic read of everything; reset=True zeroes the store under
        the same lock (no event can land between the read and the clear)."""
        self._settle()
        with self._lock:
            out = {
                "counters": {_fmt(k): v for k, v in self._counters.items()},
                "gauges": {_fmt(k): v for k, v in self._gauges.items()},
                "histograms": {_fmt(k): h.summary()
                               for k, h in self._hists.items()},
                "stages": {k: {"events": v[0], "seconds": v[1]}
                           for k, v in self._stages.items()},
                "events": list(self._events),
                "undeclared": sorted(self._undeclared),
            }
            if reset:
                self._counters.clear()
                self._gauges.clear()
                self._hists.clear()
                self._stages.clear()
                self._events.clear()
                self._undeclared.clear()
        return out

    def reset(self, prefix: str | None = None) -> None:
        """Zero series (optionally only those whose name starts with
        `prefix`) without touching the event ring or the schema — the
        measurement boundary for scoped runs (bench arms, warmup passes)."""
        with self._lock:
            self._deferred = [] if prefix is None else [
                d for d in self._deferred
                if not all(name.startswith(prefix) for name, _, _ in d[0])]
            if prefix is None:
                self._counters.clear()
                self._gauges.clear()
                self._hists.clear()
                self._stages.clear()
                return
            for store in (self._counters, self._gauges, self._hists):
                for key in [k for k in store if k[0].startswith(prefix)]:
                    del store[key]
            for key in [k for k in self._stages if k.startswith(prefix)]:
                del self._stages[key]

    # -- sinks ---------------------------------------------------------------
    def attach_sink(self, sink) -> None:
        """`sink(record: dict)` receives every event/span record."""
        with self._lock:
            self._sinks = self._sinks + (sink,)

    def detach_sink(self, sink) -> None:
        with self._lock:
            self._sinks = tuple(s for s in self._sinks if s != sink)


# -- garbage-collector pauses --------------------------------------------------
class _GcWatch:
    """The `gc.callbacks` hook of the default registry. Every collection
    bumps `host.gc.collections{generation}` and the process-wide pause
    total and observes `host.gc.seconds` (every one, so that the series'
    maximum exists in any window that allocated at all); a generation-2
    collection is also a `host.gc` annotation on the profiler's clock. Collections never nest (the interpreter holds its `collecting`
    flag across both callbacks), so one start stamp is enough.

    A collection can start inside a section that holds the registry's lock
    (an allocation there triggers it), on the very thread that holds it: the
    hook therefore never waits for the lock, and what it could not book
    rides along with the next collection."""

    def __init__(self, reg: MetricsRegistry):
        self._reg = reg
        self._t0 = None
        self._ann = None
        self._owed: list = []  # (generation, seconds) not yet booked
        self.total_s = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = None
            if not enabled():
                return
            # no import from inside a collection: annotate only once some
            # span has resolved the class
            if info["generation"] == 2 and _TraceAnnotation is not None:
                self._ann = _TraceAnnotation("host.gc", generation=2)
                self._ann.__enter__()
            self._t0 = time.perf_counter()
            return
        if self._t0 is None:
            return
        dt = time.perf_counter() - self._t0
        self._t0 = None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.total_s += dt
        self._owed.append((info["generation"], dt))
        reg = self._reg
        if reg._lock.acquire(blocking=False):
            try:
                for gen, seconds in self._owed:
                    key = ("host.gc.collections",
                           (("generation", str(gen)),))
                    reg._note("host.gc.collections")
                    reg._counters[key] = reg._counters.get(key, 0) + 1
                    reg._observe_locked("host.gc.seconds", None, seconds)
                self._owed.clear()
            finally:
                reg._lock.release()


_gc_watch: _GcWatch | None = None


def gc_pause_seconds() -> float:
    """Seconds this process has spent inside garbage collections, on any
    thread, since the default registry was created: a caller reads it at
    both ends of an interval to learn how much of the interval was the
    collector's (a collection on any thread holds the interpreter lock)."""
    return _gc_watch.total_s if _gc_watch is not None else 0.0


# -- the process-wide default registry ----------------------------------------
_default: MetricsRegistry | None = None
_default_lock = threading.Lock()
# (wall clock at the last line of paddle_tpu/__init__.py, its seconds from
# the first) until the default registry books them
_import: tuple[float, float] | None = None


def note_import(seconds: float) -> None:
    """The package's `__init__` at its last line, with what it took from
    its first: one sample of `setup.import.seconds` and one span record
    (`setup.import`) once the default registry exists. The import itself
    makes none: no exporter's file or port and no listener in a process
    that records nothing."""
    global _import
    _import = (time.time(), seconds)
    if _default is not None:    # something recorded during the import
        _book_import(_default)


def _book_import(reg: MetricsRegistry) -> None:
    global _import
    (end, seconds), _import = _import, None
    reg.histogram_observe("setup.import.seconds", seconds)
    if reg._sinks and enabled():
        _emit(reg._sinks, {"ts": end, "type": "span", "name": "setup.import",
                           "dur_s": round(seconds, 9)})


def registry() -> MetricsRegistry:
    """The default registry, created on first use with the declared schema,
    the flag-configured exporters (FLAGS_obs_jsonl_dir JSONL stream,
    FLAGS_obs_http_port /metrics endpoint) attached and the collector's
    pauses (`_GcWatch`) and the compiler's events
    (`compile_events.CompileWatch`) booked into it."""
    global _default, _gc_watch
    if _default is None:
        with _default_lock:
            if _default is None:
                try:
                    max_ev = int(flags.get_flag("obs_max_events"))
                except KeyError:
                    max_ev = 1024
                reg = MetricsRegistry(_schema.DECLARED, max_events=max_ev)
                from . import exporters

                exporters.install_flag_exporters(reg)
                _gc_watch = _GcWatch(reg)
                gc.callbacks.append(_gc_watch)
                CompileWatch(reg).install()
                _default = reg
                if _import is not None:
                    _book_import(reg)
    return _default


def counter_inc(name, value=1, labels=None):
    registry().counter_inc(name, value, labels)


def counter_defer(series, vector):
    registry().counter_defer(series, vector)


def gauge_set(name, value, labels=None):
    registry().gauge_set(name, value, labels)


def histogram_observe(name, value, labels=None):
    registry().histogram_observe(name, value, labels)


def event(name, payload=None, level="info"):
    return registry().event(name, payload, level)


def span(name, labels=None, *, collect=None, **attrs):
    return registry().span(name, labels, collect=collect, **attrs)


def spanned(name: str):
    """Decorator: every call of the function is a `span(name)`."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with registry().span(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def snapshot(reset=False):
    return registry().snapshot(reset)


def stage_record(stage, seconds, events=1):
    registry().stage_record(stage, seconds, events)


def stage_counters(reset=False):
    return registry().stage_counters(reset)


def reset(prefix=None):
    registry().reset(prefix)


def attach_sink(sink):
    registry().attach_sink(sink)


def detach_sink(sink):
    registry().detach_sink(sink)
