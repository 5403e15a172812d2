"""Reduction of a jax profiler trace (`.xplane.pb`) to the numbers the
benchmark reports: device busy and idle share, the device operations that
took most time, collective time that no compute hid, and the longest idle
gaps named after what the host was doing in them.

What the planes are (PERF.md, "Reading a trace"): every chip is a plane
`/device:TPU:<n>`; its line `XLA Ops` holds one event per executed HLO
operation (fusions, custom calls, copies, collectives) with start and
duration in nanoseconds on the profiler's clock; `XLA Modules` holds one
event per executed program. The host is the plane `/host:CPU`, one line per
thread; a `jax.profiler.TraceAnnotation` is an event there under its own
name, on the same clock. Only jax is needed to read the file.

The op line is a serial timeline of the chip's core with some nesting (a
`while` holds the operations of its body). An operation's *self time* is its
duration minus what the operations nested in it cover, so no second is
counted twice. A collective's self time on that line is time in which the
core ran nothing else: the exposed part of the collective.
"""
from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# host spans a gap may be attributed to: the benchmark's own and the
# program's obs.span names
HOST_SPAN = re.compile(r"^(bench|serving|pipeline|executor|train)\.")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
TOP_N = 10


def _union(intervals: list) -> list:
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events: list) -> list:
    """(name, start, end, self_ns) for events that may nest: each event's
    duration minus the part its direct children cover."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out, stack = [], []      # stack of [name, start, end, covered]

    def close(until):
        while stack and stack[-1][2] <= until:
            name, s, e, covered = stack.pop()
            out.append((name, s, e, max(0, (e - s) - covered)))

    for name, s, e in order:
        close(s)
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0])
    close(float("inf"))
    return out


_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<tuple>\(+)?(?P<shape>\w+\[[\d,]*\])?")


def op_key(event_name: str) -> str:
    """A short, stable key for a device operation. The chip's op line names
    an event by the whole HLO instruction text (`%fusion.1180 = bf16[128,
    128,3072]{...} fusion(...)`, hundreds of characters). The key is the
    instruction's name without its numeric suffix plus the (first) output
    shape without its layout: `fusion bf16[128,128,3072]`. Twelve layers'
    copies of one operation fall under one key, and fusions XLA gave no
    better name than `fusion.N` are still told apart by what they produce.
    Names that are no HLO text (a kernel's own name) are kept as they are."""
    m = _HLO.match(event_name)
    if not m:
        return event_name[:120]
    name = re.sub(r"\.\d+$", "", m.group("name"))
    shape = m.group("shape") or ""
    return f"{name} ({shape},..)" if m.group("tuple") else \
        f"{name} {shape}".strip()


def read_planes(path: str) -> dict:
    """{'devices': {index: [(name, start_ns, end_ns)]},
        'host': [(name, start_ns, end_ns)]} from an .xplane.pb file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[int(m.group(1))] = [
                        (op_key(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if HOST_SPAN.match(ev.name):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return {"devices": devices, "host": host}


def _attribute(gap, host_spans: list) -> str:
    """The most specific host span that covers at least half of the gap."""
    gs, ge = gap
    best = None
    for name, s, e in host_spans:
        overlap = min(e, ge) - max(s, gs)
        if overlap * 2 >= ge - gs and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host.unattributed"


def reduce_planes(planes: dict, window_span: str = "bench.trace_slice"):
    """The reduction proper; `planes` as read_planes returns it. The traced
    window is the host span `window_span` when the trace has one, else the
    extent of the device events. Returns None when no device ran anything."""
    devices = {d: evs for d, evs in planes["devices"].items() if evs}
    if not devices:
        return None
    marks = [(s, e) for name, s, e in planes["host"] if name == window_span]
    if marks:
        w0, w1 = min(s for s, _ in marks), max(e for _, e in marks)
    else:
        w0 = min(s for evs in devices.values() for _, s, _ in evs)
        w1 = max(e for evs in devices.values() for _, _, e in evs)
    window = max(1, w1 - w0)
    host_spans = [h for h in planes["host"] if h[0] != window_span]

    busy_ns, per_device = [], {}
    op_self = defaultdict(float)
    exposed_ns = 0.0
    gap_ns = defaultdict(float)
    for d, evs in sorted(devices.items()):
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                   if e > w0 and s < w1]
        busy = _union([(s, e) for _, s, e in clipped])
        b = sum(e - s for s, e in busy)
        busy_ns.append(b)
        per_device[d] = {"busy_s": b / 1e9, "idle_share": 1 - b / window}
        for name, _, _, self_ns in _self_times(clipped):
            op_self[name] += self_ns / len(devices)
            if COLLECTIVE.match(name):
                exposed_ns += self_ns / len(devices)
        if d == min(devices):
            # gaps on the first chip: data-parallel chips idle together
            edges = [w0] + [t for iv in busy for t in iv] + [w1]
            for gs, ge in zip(edges[0::2], edges[1::2]):
                if ge > gs:
                    gap_ns[_attribute((gs, ge), host_spans)] += ge - gs
    busy_mean = sum(busy_ns) / len(busy_ns)
    top = sorted(op_self.items(), key=lambda kv: -kv[1])[:TOP_N]
    gaps = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:TOP_N]
    return {
        "window_s": window / 1e9,
        "busy_s": busy_mean / 1e9,
        "idle_share": 1 - busy_mean / window,
        "per_device": per_device,
        "op_self_s": {k: v / 1e9 for k, v in op_self.items()},
        "collective_exposed_s": exposed_ns / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in top],
        "idle_gaps": [[k, v / 1e9] for k, v in gaps],
    }


def find_xplane(trace_dir: str):
    """The newest .xplane.pb under a directory jax.profiler wrote to."""
    import glob
    import os

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def reduce_trace(trace_dir: str):
    path = find_xplane(trace_dir)
    return reduce_planes(read_planes(path)) if path else None
