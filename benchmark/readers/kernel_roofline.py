"""A named kernel's share of the chip's memory bandwidth, in percent: the
bytes one call had to read, over the seconds one call took, over the
published peak.

    bytes a call  = counter `work` (x the configuration's `bytes_per_work`,
                    a dotted key, when `work` counts something else than
                    bytes) / counter `calls`, both over the whole window
    seconds a call = duration of the device operations whose name matches
                    `pattern` inside the traced slice / how many there were

Both sides are per call, so the slice (a few seconds at the window's end)
and the counters (the whole window) need not cover the same steps: the
numerator is what a mean call needed, never more than a call moves, and a
reading over 100% is a fault of the count. What a kernel's bytes are is the
configuration's to say (`kernel_bytes`); the counters are the program's.
Nothing to read (no trace, no such counter, no such operation: the parent
of the PR that adds the kernel) returns None.
"""
import functools
import re

from benchmark import trace_reduce


def _dotted(tree: dict, key: str):
    return functools.reduce(lambda t, k: t[k], key.split("."), tree)


def calls_in_slice(planes: dict, pattern: str,
                   window_span: str = "bench.trace_slice") -> tuple:
    """(seconds, count) of the first device's operations matching `pattern`
    that lie inside the marked slice (the whole trace without a mark)."""
    devices = {d: evs for d, evs in planes["devices"].items() if evs}
    if not devices:
        return 0.0, 0
    marks = [(s, e) for name, s, e in planes["host"] if name == window_span]
    w0 = min(s for s, _ in marks) if marks else float("-inf")
    w1 = max(e for _, e in marks) if marks else float("inf")
    rx = re.compile(pattern)
    took = [e - s for name, s, e in devices[min(devices)]
            if rx.search(name) and s >= w0 and e <= w1]
    return sum(took) / 1e9, len(took)


def read(result, pattern: str, work: str, calls: str, peak: str,
         bytes_per_work: str | None = None):
    ctx = result.ctx
    n_work, n_calls = result.counters.get(work), result.counters.get(calls)
    if not result.trace or ctx.peaks is None or not n_work or not n_calls:
        return None
    path = trace_reduce.find_xplane(ctx.trace_dir)
    if path is None:
        return None
    seconds, count = calls_in_slice(trace_reduce.read_planes(path), pattern)
    if not count:
        return None
    scale = _dotted(ctx.config, bytes_per_work) if bytes_per_work else 1
    return (n_work * scale / n_calls) / (seconds / count) / ctx.peaks[peak] \
        * 100.0
