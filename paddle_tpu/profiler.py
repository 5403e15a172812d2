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

`device_time` is the program's side of a device trace: the chip's seconds
under the names the program declares (a module after its Program, an
instruction after the framework op and the piece that lowered it), and
`device_table` the reference's table of them (`sorted_key`).
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import threading
from collections import defaultdict

import jax

from . import flags
from . import observability as _obs

__all__ = ["profiler", "start_profiler", "stop_profiler", "RecordEvent",
           "record_event", "record_stage", "stage_timer", "stage_counters",
           "bump", "device_time", "device_table", "SORTED_KEYS"]


def _resolve_dir(path: str | None) -> str:
    return path or flags.get_flag("profiler_dir")


# trace lifecycle state: start/stop must pair, and a failed start (e.g.
# os.makedirs on a read-only path) must not leave a half-open trace that
# makes every later start_profiler fail with a raw jax error
_trace_lock = threading.Lock()
_trace_active = False
_trace_dir: str | None = None      # where the active (or last) trace lands


def _begin_trace(path: str) -> None:
    global _trace_active, _trace_dir
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
        _trace_dir = path


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


def _print_table(sorted_key: str | None) -> None:
    """The reference prints its table when a profile stops; here one is
    printed when the caller names a `sorted_key`."""
    if sorted_key is None:
        return
    if sorted_key not in SORTED_KEYS:
        raise ValueError(f"sorted_key must be one of {SORTED_KEYS}, not "
                         f"{sorted_key!r}")
    print(device_table(device_time(_trace_dir), by="op",
                       sorted_key=sorted_key))


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str | None = None,
             profile_path: str | None = None):
    """`with profiler.profiler(...):` traces everything inside to an XPlane
    directory (reference fluid/profiler.py:225). With a `sorted_key`
    ('calls', 'total', 'max', 'min' or 'ave') it prints, on the way out,
    the table of device time per framework op type in that order
    (`device_table`); `state` is accepted for parity: the trace always
    covers host and device."""
    _begin_trace(_resolve_dir(profile_path))
    try:
        yield
    finally:
        _end_trace()
    _print_table(sorted_key)


def start_profiler(state: str = "All", profile_path: str | None = None):
    """Imperative start (reference fluid/profiler.py start_profiler)."""
    _begin_trace(_resolve_dir(profile_path))


def stop_profiler(sorted_key: str | None = None, profile_path: str | None = None):
    """Stop the active trace and, with a `sorted_key` ('calls', 'total',
    'max', 'min' or 'ave'), print the table of device time per framework op
    type in that order, as the reference's stop_profiler does. The trace
    lands in the directory given to start_profiler (`profile_path` is
    accepted for parity). Raises RuntimeError (naming start_profiler) when
    no trace is active instead of surfacing the raw jax error."""
    _end_trace()
    _print_table(sorted_key)


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


# -- the device's seconds under the program's names ---------------------------
# How an `XLA Ops` event reaches its path. The event's name is the
# instruction's HLO text; its instruction name (`fusion.1180`) is unique in
# its module; its module is the `XLA Modules` event that covers it in time,
# named `jit_<Program.name>(<program id>)`. The chip's profile carries every
# executed module's HloProto in the plane `/host:metadata`, keyed by that
# same name, so the map (module, instruction) -> `op_name` is read from the
# trace file itself: the executor keeps nothing for it, and a trace copied
# off a host reads the same. An `op_name` is jax's name stack
# (`jit(serving_decode)/sparse_moe_stack/decode/while/body/closed_call/
# indexer/dot_general`); what jax adds is dropped, what `executor.
# _compute_op` and the stack lowerings declared stays: name scope, op type,
# mode, piece. A fusion is booked to the op its own metadata names (XLA
# gives a fusion its root's); an instruction without metadata (a layout
# copy) goes to `unscoped/<kind and shape>`.

SORTED_KEYS = ("calls", "total", "max", "min", "ave")
UNSCOPED = "unscoped"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_METADATA_PLANE = b"/host:metadata"
_HOST_PLANE = "/host:CPU"
_INSTRUCTION = re.compile(
    r"^%?(?P<name>[^\s=]+) = (?P<tuple>\(+)?(?P<shape>\w+\[[\d,]*\])?")
# name-stack components jax adds around the program's own
_JAX_ADDED = frozenset({
    "while", "body", "cond", "scan", "closed_call", "core_closed_call",
    "core_call", "checkpoint", "remat", "remat2", "pallas_call", "pjit",
    "shard_map", "named_call", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "custom_lin", "xla_call"})
_COMPONENT = re.compile(r"^(?!branch_\d+_fun$)[\w.\-]+$")


def op_path(op_name: str) -> str:
    """The program's part of an `op_name`: the name stack without its last
    component (the primitive), without what jax added (`jit(...)`, `while`,
    `body`, `closed_call`, an einsum's spec, ...); '' when nothing is
    left. Of names XLA joined with ';' the first stands."""
    parts = op_name.split(";", 1)[0].split("/")[:-1]
    return "/".join(c for c in parts
                    if _COMPONENT.match(c) and c not in _JAX_ADDED)


def _short(event_name: str) -> tuple:
    """(instruction name, kind and shape) of an `XLA Ops` event's name:
    (`fusion.1180`, `fusion bf16[128,3072]`); a name that is no HLO text (a
    kernel's own) stands for both."""
    m = _INSTRUCTION.match(event_name)
    if not m:
        return event_name, event_name[:120]
    kind = re.sub(r"\.\d+$", "", m.group("name"))
    shape = m.group("shape") or ""
    return m.group("name"), (f"{kind} ({shape},..)" if m.group("tuple")
                             else f"{kind} {shape}".strip())


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of a serialized protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field.
    Reading the two messages below by their field numbers needs no
    generated code, and a field that is not asked for is skipped by its
    length (a trace's device planes, a module's constants)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _first(buf, number: int, default=None):
    return next((v for f, v in _fields(buf) if f == number), default)


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace") if view is not None else ""


def _module_op_names(hlo_proto) -> dict:
    """{instruction name: op_name} of one serialized HloProto
    (xla/service/hlo.proto: HloProto.hlo_module = 1; HloModuleProto.
    computations = 3; HloComputationProto.instructions = 2, id = 5,
    root_id = 6; HloInstructionProto.name = 1, opcode = 2, metadata = 7,
    id = 35, called_computation_ids = 38; OpMetadata.op_name = 2). A fusion
    without a name of its own takes its fused computation's root's."""
    names, by_id, fusions, roots = {}, {}, [], {}
    for f, comp in _fields(_first(hlo_proto, 1, b"")):
        if f != 3:
            continue
        comp_id = root_id = None
        for cf, cv in _fields(comp):
            if cf == 5:
                comp_id = cv
            elif cf == 6:
                root_id = cv
            elif cf == 2:
                name = opcode = op_name = inst_id = called = None
                for jf, jv in _fields(cv):
                    if jf == 1:
                        name = _text(jv)
                    elif jf == 2:
                        opcode = _text(jv)
                    elif jf == 7:
                        op_name = _text(_first(jv, 2))
                    elif jf == 35:
                        inst_id = jv
                    elif jf == 38 and called is None:
                        called = jv if isinstance(jv, int) \
                            else _varint(jv, 0)[0]
                names[name] = op_name or ""
                by_id[inst_id] = name
                if opcode == "fusion" and not op_name:
                    fusions.append((name, called))
        roots[comp_id] = root_id
    for name, called in fusions:
        names[name] = names.get(by_id.get(roots.get(called)), "")
    return names


def _trace_modules(xplane_bytes) -> dict:
    """{module name as the `XLA Modules` line prints it (`jit_step(<program
    id>)`): {instruction name: op_name}} from the trace's `/host:metadata`
    plane (XSpace.planes = 1; XPlane.name = 2, event_metadata = 4, a map
    entry's value = 2; XEventMetadata.name = 2, stats = 5; XStat.
    bytes_value = 6: the HloProto); {} where the profile carries none."""
    out = {}
    for f, plane in _fields(memoryview(xplane_bytes)):
        if f != 1 or bytes(_first(plane, 2, b"")) != _METADATA_PLANE:
            continue
        for pf, entry in _fields(plane):
            if pf != 4:
                continue
            meta = _first(entry, 2, b"")
            protos = [_first(stat, 6) for mf, stat in _fields(meta)
                      if mf == 5]
            protos = [p for p in protos if p is not None]
            if protos:
                out[_text(_first(meta, 2))] = _module_op_names(
                    max(protos, key=len))
    return out


def _find_xplane(trace: str) -> str | None:
    """`trace` itself if it is a file, else the newest .xplane.pb under a
    directory jax's profiler wrote to."""
    if os.path.isfile(trace):
        return trace
    found = glob.glob(os.path.join(trace, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _read_device_planes(xplane_bytes, window_span: str | None) -> tuple:
    """({chip: {"modules": [(name, start, end)], "ops": [(event name,
    start, end)]}}, (w0, w1) of the host span `window_span` or None)."""
    from jax.profiler import ProfileData

    chips, marks = {}, []
    for plane in ProfileData.from_serialized_xspace(xplane_bytes).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            chips[int(m.group(1))] = {
                key: [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ev in lines[line].events] if line in lines else []
                for key, line in (("modules", "XLA Modules"),
                                  ("ops", "XLA Ops"))}
        elif window_span and plane.name == _HOST_PLANE:
            marks += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ln in plane.lines for ev in ln.events
                      if ev.name == window_span]
    window = (min(s for s, _ in marks), max(e for _, e in marks)) \
        if marks else None
    return chips, window


def reduce_device_planes(chips: dict, op_names: dict, window=None):
    """The reduction proper. `chips` {chip: {"modules": [(name, start_ns,
    end_ns)], "ops": [(event name, start_ns, end_ns)]}}, `op_names`
    {module name: {instruction name: op_name}}, `window` (start_ns, end_ns)
    to clip to (default: the extent of the device's events). One sort and
    one sweep a chip: an operation's SELF time is its duration less what
    nests in it (a `while` holds its body), so a chip's rows sum to its
    busy seconds exactly. Returns None when no chip ran anything, else

      window_s, busy_s (mean over chips), chips (how many),
      modules {name: row}, paths {path: row}: means over chips,
      per_chip {chip: {busy_s, modules, paths}}

    a row {"self_s", "calls", "min_s", "max_s"}: self seconds, events, and
    the least and most self seconds of one event (a module's row: its
    operations' self seconds, its executions, and their extent)."""
    chips = {c: v for c, v in chips.items() if v["ops"]}
    if not chips:
        return None
    if window is None:
        window = (min(s for v in chips.values() for _, s, _ in v["ops"]),
                  max(e for v in chips.values() for _, _, e in v["ops"]))
    w0, w1 = window
    short, path_of = {}, {}

    def book(rows, key, ns):
        row = rows[key]
        row[0] += ns
        row[1] += 1
        row[2] = min(row[2], ns)
        row[3] = max(row[3], ns)

    per_chip = {}
    for chip, planes in sorted(chips.items()):
        modules = sorted((s, e, n) for n, s, e in planes["modules"])
        ops = sorted(((max(s, w0), -min(e, w1), n)
                      for n, s, e in planes["ops"] if e > w0 and s < w1))
        mod_rows = defaultdict(lambda: [0, 0, float("inf"), 0])
        path_rows = defaultdict(lambda: [0, 0, float("inf"), 0])
        stack, mi, busy = [], 0, 0        # stack of [path, module, s, e, covered]

        def close(until):
            nonlocal busy
            while stack and stack[-1][3] <= until:
                path, module, s, e, covered = stack.pop()
                self_ns = max(0, (e - s) - covered)
                busy += self_ns
                book(path_rows, path, self_ns)
                mod_rows[module][0] += self_ns

        for s, neg_e, name in ops:
            e = -neg_e
            close(s)
            while mi < len(modules) and modules[mi][1] <= s:
                mi += 1
            module = modules[mi][2] if mi < len(modules) \
                and modules[mi][0] <= s else "?"
            key = (module, name)
            path = path_of.get(key)
            if path is None:
                if name not in short:
                    short[name] = _short(name)
                inst, kind = short[name]
                path = op_path(op_names.get(module, {}).get(inst, "")) \
                    or f"{UNSCOPED}/{kind}"
                path_of[key] = path
            if stack:
                stack[-1][4] += min(e, stack[-1][3]) - s
            stack.append([path, module, s, e, 0])
        close(float("inf"))
        for s, e, name in modules:
            if e > w0 and s < w1:
                row = mod_rows[name]
                took = min(e, w1) - max(s, w0)
                row[1] += 1
                row[2] = min(row[2], took)
                row[3] = max(row[3], took)
        per_chip[chip] = {"busy_s": busy / 1e9,
                          "modules": _rows(mod_rows),
                          "paths": _rows(path_rows)}
    n = len(per_chip)
    return {"window_s": max(1, w1 - w0) / 1e9,
            "busy_s": sum(c["busy_s"] for c in per_chip.values()) / n,
            "chips": n,
            "modules": _mean_rows([c["modules"] for c in per_chip.values()]),
            "paths": _mean_rows([c["paths"] for c in per_chip.values()]),
            "per_chip": per_chip}


def _rows(raw: dict) -> dict:
    return {k: {"self_s": r[0] / 1e9, "calls": r[1],
                "min_s": (0 if r[2] == float("inf") else r[2]) / 1e9,
                "max_s": r[3] / 1e9} for k, r in raw.items()}


def _mean_rows(tables: list) -> dict:
    """Rows of several chips as one: self seconds and calls as the mean
    over ALL chips (a row one chip lacks counts 0 there), min and max over
    the chips that have it."""
    out = {}
    for key in {k for t in tables for k in t}:
        have = [t[key] for t in tables if key in t]
        out[key] = {"self_s": sum(r["self_s"] for r in have) / len(tables),
                    "calls": sum(r["calls"] for r in have) / len(tables),
                    "min_s": min(r["min_s"] for r in have),
                    "max_s": max(r["max_s"] for r in have)}
    return out


def device_time(trace: str, window_span: str | None = None):
    """The device's seconds of a trace under the program's names: `trace`
    is a directory jax's profiler wrote to or an .xplane.pb file;
    `window_span` names a host `TraceAnnotation` to clip the events to
    (the benchmark's `bench.trace_slice`). See `reduce_device_planes` for
    what comes back; None where the trace holds no device plane on which
    an operation ran (the CPU), or no trace is there."""
    path = _find_xplane(trace) if trace else None
    if path is None:
        return None
    with open(path, "rb") as f:
        raw = f.read()
    chips, window = _read_device_planes(raw, window_span)
    if not any(v["ops"] for v in chips.values()):
        return None
    return reduce_device_planes(chips, _trace_modules(raw), window)


def group_rows(report: dict, by: str = "op") -> dict:
    """`report["paths"]` (or, `by="module"`, its modules) regrouped:
    `by="path"` as they are; `"piece"` without the name scope, from the op
    type on (`sparse_moe_stack/decode/indexer`, `matmul_grad`); `"op"` the
    op type alone, everything unscoped as one row."""
    if by == "module":
        return dict(report["modules"])
    if by == "path":
        return dict(report["paths"])
    if by not in ("op", "piece"):
        raise ValueError(f"by must be module, path, piece or op, not {by!r}")
    from .ops.registry import has_op

    out = {}
    for path, row in report["paths"].items():
        parts = path.split("/")
        if parts[0] == UNSCOPED:
            key = UNSCOPED if by == "op" else path
        else:
            at = max((i for i, c in enumerate(parts) if has_op(c)),
                     default=len(parts) - 1)
            key = parts[at] if by == "op" else "/".join(parts[at:])
        have = out.get(key)
        out[key] = dict(row) if have is None else {
            "self_s": have["self_s"] + row["self_s"],
            "calls": have["calls"] + row["calls"],
            "min_s": min(have["min_s"], row["min_s"]),
            "max_s": max(have["max_s"], row["max_s"])}
    return out


TABLE_ROWS = 40     # the table's longest; what is left is summed in one line


def device_table(report, by: str = "op",
                 sorted_key: str | None = "total") -> str:
    """The reference profiler's table (fluid/profiler.py: Event, Calls,
    Total, Min, Max, Ave, Ratio) of a `device_time` report: self
    milliseconds by module, path, piece or op type, sorted by `sorted_key`
    ('calls', 'total', 'max', 'min', 'ave'; None: by name), largest first,
    the share of the traced window last."""
    if report is None:
        return ("no device plane in this trace: nothing ran on a TPU while "
                "it was taken (on the CPU a trace holds host events only)")
    rows = [(k, r["calls"], r["self_s"] * 1e3, r["min_s"] * 1e3,
             r["max_s"] * 1e3, r["self_s"] * 1e3 / max(r["calls"], 1e-9))
            for k, r in group_rows(report, by).items()]
    if sorted_key is None:
        rows.sort(key=lambda r: r[0])
    else:
        col = {"calls": 1, "total": 2, "min": 3, "max": 4, "ave": 5}
        rows.sort(key=lambda r: (-r[col[sorted_key]], r[0]))
    shown = rows[:TABLE_ROWS]
    width = max([len("Event")] + [len(r[0]) for r in shown])
    window_ms = report["window_s"] * 1e3
    lines = [f"device time by {by}: window {report['window_s']:.4f} s, busy "
             f"{report['busy_s']:.4f} s (mean of {report['chips']} chip(s)); "
             f"sorted by {sorted_key or 'name'}; self ms",
             f"{'Event':<{width}}  {'Calls':>9}  {'Total':>11}  {'Min':>9}  "
             f"{'Max':>9}  {'Ave':>9}  {'Ratio':>7}"]
    for name, calls, total, lo, hi, ave in shown:
        lines.append(f"{name:<{width}}  {calls:>9.6g}  {total:>11.3f}  "
                     f"{lo:>9.4f}  {hi:>9.4f}  {ave:>9.4f}  "
                     f"{total / window_ms:>7.2%}")
    if len(rows) > len(shown):
        rest = sum(r[2] for r in rows[len(shown):])
        lines.append(f"... {len(rows) - len(shown)} more rows, "
                     f"{rest:.3f} ms")
    return "\n".join(lines)


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
