"""DeviceLoader: background-thread prefetch that stages batches into HBM.

The reference overlaps host->device transfer with compute in
buffered_reader.cc (double buffering on a dedicated stream). Here the same
overlap comes from a python thread calling `jax.device_put` ahead of the
consumer: while the device runs step i, the thread is already transferring
the feeds of steps i+1..i+K (K = depth). The thread/queue contract is
`reader._prefetch_iter`'s — producer exceptions re-raise in the consumer and
an abandoned iteration unblocks and stops the producer (no leaked threads).

Placement is pluggable: the default casts host arrays to their declared var
dtypes and `jax.device_put`s them to the default device; `Executor.feed_placer`
builds a placement that re-uses the compiled entry's feed shardings on a mesh
(lifting this process's shard to a global array with
`jax.make_array_from_process_local_data` on multi-process meshes).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from .. import flags, profiler
from ..reader import _prefetch_iter

__all__ = ["DeviceLoader", "default_placement"]


def default_placement(feed_vars=None, device=None):
    """Placement fn for programs run without a mesh: cast each host array to
    its feed var's declared dtype (the same cast Executor.run applies, so the
    compile-cache signature is identical either way) and commit it to the
    device. jax.Arrays and SelectedRows pass through untouched."""
    from ..core.selected_rows import is_selected_rows

    dtypes = {v.name: v.np_feed_dtype for v in (feed_vars or [])}

    def place(feed: dict) -> dict:
        out = {}
        for name, v in feed.items():
            if isinstance(v, jax.Array) or is_selected_rows(v):
                out[name] = v
                continue
            arr = np.asarray(v)
            if name in dtypes:
                arr = arr.astype(dtypes[name], copy=False)
            with profiler.stage_timer("pipeline.device_put"):
                out[name] = jax.device_put(arr, device)
        return out

    return place


class DeviceLoader:
    """Iterate `source` (a zero-arg callable returning a generator of feed
    dicts) with up to `depth` batches staged in device memory ahead of the
    consumer. Usable directly in a `for feed in loader:` loop."""

    def __init__(self, source, depth: int | None = None, placement=None,
                 feed_vars=None):
        if depth is None:
            depth = int(flags.get_flag("device_prefetch_depth"))
        self._source = source
        self.depth = max(1, int(depth))
        self._place = placement or default_placement(feed_vars)

    def __iter__(self):
        source, place = self._source, self._place

        def staged():
            import threading

            from ..resilience.faults import InjectedFault, fault_point

            it = iter(source())
            while True:
                try:
                    fault_point("pipeline_stall")
                except InjectedFault:
                    # simulated wedge: the producer parks forever (hung I/O
                    # stand-in) so the consumer-side stall watchdog must
                    # fire; the parked daemon thread dies with the process
                    threading.Event().wait()
                t0 = time.perf_counter()
                try:
                    feed = next(it)
                except StopIteration:
                    return
                profiler.record_stage("pipeline.host_ingest",
                                      time.perf_counter() - t0)
                try:
                    staged_feed = place(feed)
                except (ValueError, TypeError):
                    # corrupt record: the batch died in the dtype cast /
                    # device_put — under FLAGS_feed_skip_corrupt count it
                    # and keep prefetching instead of killing the epoch
                    # through the consumer's re-raise
                    if not flags.get_flag("feed_skip_corrupt"):
                        raise
                    profiler.bump("feed.skip_corrupt")
                    continue
                yield staged_feed

        from ..resilience.watchdog import stall_window_s

        return _prefetch_iter(staged, self.depth,
                              stall_window=stall_window_s() or None,
                              stall_what="DeviceLoader batch wait")

    # reader-creator calling convention (paddle readers are zero-arg callables)
    __call__ = __iter__
