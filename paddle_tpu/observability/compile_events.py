"""The compiler's own events, in the registry (ISSUE 49).

jax times what happens between `jax.jit(fn)` and an executable that runs —
the trace to a jaxpr, the lowering to an MLIR module, XLA's compile or the
persistent cache's read — and hands each duration to any
`jax.monitoring` listener with the function's name. This module is the
process's one listener pair (`CompileWatch`, installed with the default
registry, beside the collector's hook) and the one place that knows the
events' names (`pipeline.jit_compile_counter` takes its event from here).
Each fact is booked once:

  compile.trace.seconds, compile.lower.seconds, compile.backend.seconds
      histograms (exact count and sum) over every function the process
      compiles. A trace that runs inside another one (a jitted helper
      called from a lowering) is inside the outer one's seconds and is not
      booked a second time.
  compile.cache.hits, compile.cache.misses
      the persistent cache's own counts. jax says `miss` only where it
      WRITES the entry: an executable under the cache's thresholds
      (`compile_cache.MIN_COMPILE_SECS`) is compiled again by every
      process and reads neither hit nor miss.
  compile.entry
      one event a compiled function, with the span it happened under as
      `parent`: `fn` (the function's name where the executor says it is a
      lowered block's, `fn` or what `_named_after` made of a
      `Program.name`; `other` for everything else: the eager utility jits
      around a run), `name`, `trace_s`, `lower_s`, `backend_s` (on a hit
      the cache's read), `cache` = hit | miss | off, `start`, `end` on the
      stream's clock, and `op_s`: the self seconds of each framework op's
      lowering inside the trace, by path (`op_scope`). `tools/obs.py
      setup` reads these: seconds by `fn`, and which op types a
      signature's trace is made of.
"""
from __future__ import annotations

import threading
import time

import jax

from .. import flags

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_PHASES = {TRACE_EVENT: "trace", LOWER_EVENT: "lower",
           BACKEND_COMPILE_EVENT: "backend"}
# the cache's verdict on the executable being built, and its counter
_VERDICTS = {CACHE_HIT_EVENT: ("hit", "compile.cache.hits"),
             CACHE_MISS_EVENT: ("miss", "compile.cache.misses")}

OTHER_FN = "other"      # the `fn` of whatever is no lowered block's function
_is_lowered = frozenset().__contains__


def label_fns_by(is_lowered) -> None:
    """The executor's word on which function names are lowered blocks'
    (`executor.LOWERED_FN_NAMES`, which it owns and grows)."""
    global _is_lowered
    _is_lowered = is_lowered


def _bare(fun_name: str) -> str:
    """A jax event's `fun_name` as the function was called: `serving_decode`
    from the trace, `jit(serving_decode)` from the lowering and the compile."""
    return fun_name.removeprefix("jit(").removesuffix(")")


def fn_label(fun_name: str) -> str:
    """The `fn` of a jax event's `fun_name`."""
    name = _bare(fun_name)
    return name if _is_lowered(name) else OTHER_FN


def _tracing() -> bool:
    """Whether this thread is inside a jax trace (the one use of the jax
    internal that knows): False at the top level and in the
    `jax.disable_jit` replay, where ops compute and nothing is lowered."""
    return not (jax.core.trace_ctx.is_top_level()
                or jax.config.jax_disable_jit)


class _ThreadState(threading.local):
    """One thread's compile under way: what its trace and lowering took
    (`entry`, until the backend event closes it), what the cache said, and
    the op clock: the path of the op being lowered, the seconds of the ops
    that finished inside it, and the self seconds by path since the last
    trace ended."""

    def __init__(self):
        self.entry: dict | None = None
        self.cache = "off"
        self.path = ""
        self.child_s = 0.0
        self.op_s: dict[str, float] = {}


_state = _ThreadState()


class op_scope:
    """`with op_scope(scope, name):` around one op's lowering, or one
    declared piece's: `jax.named_scope(scope)`, and while a trace is under
    way the SELF wall seconds of what is lowered under it, booked by path
    into the `op_s` of the trace's `compile.entry`. What is lowered inside
    it is booked to itself, under `<name>/<inner>`: a control-flow op's
    sub-block, a stack's mode and pieces, as a device trace names them. So
    the paths of an entry sum to its lowering loops' time."""

    __slots__ = ("_scope", "_name", "_t0", "_outer")

    def __init__(self, scope: str, name: str):
        self._scope = jax.named_scope(scope)
        self._name = name

    def __enter__(self):
        self._scope.__enter__()
        if not _tracing():
            self._t0 = None
            return self
        st = _state
        self._outer = (st.path, st.child_s)
        st.path = f"{st.path}/{self._name}" if st.path else self._name
        st.child_s = 0.0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            dt = time.perf_counter() - self._t0
            st = _state
            path, own = st.path, dt - st.child_s
            st.path, outer_child_s = self._outer
            st.child_s = outer_child_s + dt
            st.op_s[path] = st.op_s.get(path, 0.0) + own
        return self._scope.__exit__(*exc)


class CompileWatch:
    """The `jax.monitoring` listener pair of a registry: every event above
    into its series, and the three phases of one function stitched into its
    `compile.entry`. The phases of one function arrive in order on the
    thread that compiles it (trace, lowering, the cache's verdict, backend),
    so the state is per thread and the backend event closes the entry."""

    def __init__(self, reg):
        self._reg = reg

    def install(self) -> None:
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)

    def on_event(self, event: str, **_) -> None:
        verdict = _VERDICTS.get(event)
        if verdict is not None and flags.get_flag("obs_enable"):
            _state.cache, counter = verdict
            self._reg.counter_inc(counter)

    def on_duration(self, event: str, duration: float, fun_name=None,
                    **_) -> None:
        phase = _PHASES.get(event)
        if phase is None or fun_name is None \
                or not flags.get_flag("obs_enable"):
            return
        if phase == "trace" and _tracing():
            return          # inside another trace, whose seconds hold these
        st = _state
        now = time.time()
        self._reg.histogram_observe(f"compile.{phase}.seconds", duration)
        name = _bare(fun_name)
        entry = st.entry
        if phase == "trace" or entry is None or entry["name"] != name \
                or (phase == "lower" and entry["lower_s"]):
            # a new function begins here: with its trace, or (an executable
            # built from a jaxpr jax still held) with a later phase
            entry = st.entry = {"fn": fn_label(fun_name), "name": name,
                                "start": now - duration,
                                "trace_s": 0.0, "lower_s": 0.0}
            if phase == "trace" and st.op_s:
                entry["op_s"], st.op_s = st.op_s, {}
        entry[phase + "_s"] = duration
        if phase != "backend":
            return
        st.entry = None
        entry["cache"], st.cache = st.cache, "off"
        entry["end"] = now
        self._reg.event("compile.entry", entry)
