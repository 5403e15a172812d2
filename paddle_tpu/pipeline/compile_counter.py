"""Compile-count hook: observe XLA compiles of the executor's step function.

The executor caches one compiled executable per (program, feed-signature);
feed bucketing exists precisely so a ragged tail batch hits that cache
instead of triggering a fresh compile. This hook turns "how many compiles
actually happened" into something a regression test can assert: it listens
to jax.monitoring's backend-compile duration event, which jax records once
per executable it builds or loads, and counts the ones for the executor's
whole-block closures (named `fn`, or after their Program where it has a
name: `executor.LOWERED_FN_NAMES`, so their events are distinguishable from
the small utility jits jax compiles around a run). The process's standing
listener (`observability/compile_events.py`, which owns the events' names)
books every compile into the registry; this one
counts those of a `with` block.

A persistent-cache hit is counted too: it skips XLA but is still a
first-use stall (trace + lower + deserialize) inside the caller's window,
which is what both kinds of caller need to know — the "compiles once per
bucket" tests count in-process cache misses, and the serving benches repeat
a pass until it ran with none.
"""
from __future__ import annotations

import contextlib

import jax

from ..observability.compile_events import BACKEND_COMPILE_EVENT

__all__ = ["jit_compile_counter"]


class _CompileCount:
    def __init__(self):
        self.events: list[str] = []

    @property
    def count(self) -> int:
        return len(self.events)


@contextlib.contextmanager
def jit_compile_counter():
    """Count XLA compiles of the executor's whole-block closures inside the
    `with` block, whatever Program they are called after, so
    `counter.count` is the number of (program, signature) compile-cache
    misses the block produced."""
    from ..executor import LOWERED_FN_NAMES as names

    result = _CompileCount()

    def listener(event, duration, fun_name=None, **_):
        if event == BACKEND_COMPILE_EVENT and fun_name is not None \
                and fun_name.removeprefix("jit(").removesuffix(")") in names:
            result.events.append(f"{fun_name} {duration:.6f}s")
            from .. import observability as obs

            obs.counter_inc("train.jit_compiles")

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield result
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
