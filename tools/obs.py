#!/usr/bin/env python
"""obs.py — read the telemetry the unified registry ships (ISSUE 13).

The observability layer writes two artifact kinds: the JSONL event/span
stream (FLAGS_obs_jsonl_dir/obs.jsonl, one canonical-encoded record per
line) and snapshot files (registry `snapshot()` dumped as JSON, or the
Prometheus text exposition). This CLI is the read side — no server, no
deps, works on a laptop against files scp'd off a TPU host.

Usage:
    python tools/obs.py tail FILE.jsonl [-n N] [--follow]
    python tools/obs.py summarize FILE.jsonl
        # per-name event counts by level + span count/p50/p95/total
    python tools/obs.py diff OLD.json NEW.json
        # counter deltas, gauge moves, histogram p99 shifts between two
        # registry snapshot() JSON files
    python tools/obs.py prom FILE.prom
        # strict-parse a Prometheus exposition file -> JSON on stdout;
        # exits 1 on any unparseable line (the round-trip check as a tool)
    python tools/obs.py ops TRACE [--by module|op|piece] [--span NAME]
        # the device's seconds of a jax profiler trace (a directory or an
        # .xplane.pb) under the program's own names: self time by compiled
        # module, framework op type or declared piece, unscoped
        # instructions one by one under `piece` (profiler.device_time /
        # device_table); --span clips to a host annotation (the
        # benchmark's slice is bench.trace_slice)
    python tools/obs.py setup FILE.jsonl [--json]
        # where a process's set-up went (a run with FLAGS_obs_jsonl_dir=d;
        # why an engine took five minutes to come up): the span tree from
        # the first record to the last `setup.*` span or compile of a
        # Program's function, with self seconds and share (`setup.import`, `setup.engine_build` >
        # `.programs` `.startup` `.pools`, `setup.decode_lattice` >
        # `.entry`, `setup.minimize` > `setup.backward`,
        # `executor.first_dispatch`, and whatever `serving.*` steps a
        # warm-up replay ran), each `compile.entry` event as trace / lower
        # / backend leaves under its span; trace, lowering, XLA compile and
        # cache read seconds by `fn` with entries, hits and misses; the ten
        # op paths with the most tracing seconds (the entries' `op_s`), the
        # first signature that lowered each beside the later ones; and
        # `unattributed`, the seconds no record names, with the widest gaps

Exit status: 0 on success, 1 on malformed input, 2 on usage error.
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _read_jsonl(path: str) -> list[dict]:
    """Parse a JSONL stream, skipping (but counting) malformed lines — a
    torn final line from a live writer must not kill the reader."""
    recs, bad = [], 0
    with open(path, "rb") as f:
        for ln in f:
            ln = ln.strip()
            if not ln:
                continue
            try:
                recs.append(json.loads(ln))
            except ValueError:
                bad += 1
    if bad:
        print(f"[obs] WARN: skipped {bad} malformed line(s) in {path}",
              file=sys.stderr)
    return recs


def cmd_tail(argv: list[str]) -> int:
    path = argv[0]
    n = 20
    if "-n" in argv:
        n = int(argv[argv.index("-n") + 1])
    follow = "--follow" in argv or "-f" in argv
    recs = _read_jsonl(path)
    for rec in recs[-n:]:
        sys.stdout.write(json.dumps(rec, sort_keys=True) + "\n")
    if not follow:
        return 0
    sys.stdout.flush()
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        while True:
            ln = f.readline()
            if not ln:
                time.sleep(0.25)
                continue
            try:
                rec = json.loads(ln)
            except ValueError:
                continue  # torn line mid-write; the next read completes it
            sys.stdout.write(json.dumps(rec, sort_keys=True) + "\n")
            sys.stdout.flush()


def _pctl(sorted_vals: list[float], q: float) -> float:
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[i]


def cmd_summarize(argv: list[str]) -> int:
    recs = _read_jsonl(argv[0])
    events: dict[str, dict[str, int]] = {}
    spans: dict[str, list[float]] = {}
    other = 0
    for rec in recs:
        kind, name = rec.get("type"), rec.get("name", "?")
        if kind == "event":
            lv = events.setdefault(name, {})
            level = rec.get("level", "info")
            lv[level] = lv.get(level, 0) + 1
        elif kind == "span":
            spans.setdefault(name, []).append(float(rec.get("dur_s", 0.0)))
        else:
            other += 1
    print(f"{len(recs)} records "
          f"({sum(sum(v.values()) for v in events.values())} events, "
          f"{sum(len(v) for v in spans.values())} spans, {other} other)")
    if events:
        print("\nevents:")
        for name in sorted(events):
            by = events[name]
            lv = " ".join(f"{k}={by[k]}" for k in sorted(by))
            print(f"  {name:<28} {sum(by.values()):>7}  ({lv})")
    if spans:
        print("\nspans:")
        print(f"  {'name':<28} {'count':>7} {'p50_ms':>9} {'p95_ms':>9} "
              f"{'total_s':>9}")
        for name in sorted(spans):
            vs = sorted(spans[name])
            print(f"  {name:<28} {len(vs):>7} "
                  f"{_pctl(vs, 0.50) * 1e3:>9.3f} "
                  f"{_pctl(vs, 0.95) * 1e3:>9.3f} {sum(vs):>9.3f}")
    return 0


def cmd_diff(argv: list[str]) -> int:
    with open(argv[0]) as f:
        old = json.load(f)
    with open(argv[1]) as f:
        new = json.load(f)
    rows: list[str] = []
    oc, nc = old.get("counters", {}), new.get("counters", {})
    for k in sorted(set(oc) | set(nc)):
        d = nc.get(k, 0) - oc.get(k, 0)
        if d:
            rows.append(f"  counter  {k:<36} {d:+g}")
    steps, pairs, tile_rows = (
        nc.get(k, 0) - oc.get(k, 0) for k in (
            "serving.moe.grouped_layer_steps", "serving.moe.grouped_pairs",
            "serving.moe.grouped_tile_rows"))
    if steps and tile_rows:
        # the grouped expert calls between the snapshots: the pairs a call
        # multiplied, and the share of its tiles' rows that held one
        rows.append(f"  derived  serving.moe.grouped: {steps:g} layer steps, "
                    f"{pairs:g} pairs, {tile_rows:g} tile rows; "
                    f"{pairs / steps:.1f} pairs a step, "
                    f"{pairs / tile_rows:.1%} of tile rows")
    og, ng = old.get("gauges", {}), new.get("gauges", {})
    for k in sorted(set(og) | set(ng)):
        a, b = og.get(k), ng.get(k)
        if a != b:
            rows.append(f"  gauge    {k:<36} {a} -> {b}")
    oh, nh = old.get("histograms", {}), new.get("histograms", {})
    for k in sorted(set(oh) | set(nh)):
        a = (oh.get(k) or {}).get("p99")
        b = (nh.get(k) or {}).get("p99")
        if a != b:
            fa = "-" if a is None else f"{a:.6g}"
            fb = "-" if b is None else f"{b:.6g}"
            rows.append(f"  hist p99 {k:<36} {fa} -> {fb}")
    if rows:
        print(f"{os.path.basename(argv[0])} -> {os.path.basename(argv[1])}:")
        print("\n".join(rows))
    else:
        print("no differences")
    return 0


def cmd_prom(argv: list[str]) -> int:
    from paddle_tpu.observability import parse_prometheus

    with open(argv[0]) as f:
        text = f.read()
    try:
        series = parse_prometheus(text)
    except ValueError as e:
        print(f"[obs] FAIL: {argv[0]}: {e}", file=sys.stderr)
        return 1
    json.dump(series, sys.stdout, indent=2, sort_keys=True)
    sys.stdout.write("\n")
    return 0


def cmd_ops(argv: list[str]) -> int:
    from paddle_tpu import profiler

    def opt(flag, default):
        return argv[argv.index(flag) + 1] if flag in argv else default

    by = opt("--by", "op")
    if by not in ("module", "op", "piece"):
        print(f"--by must be module, op or piece, not {by!r}",
              file=sys.stderr)
        return 2
    print(profiler.device_table(
        profiler.device_time(argv[0], window_span=opt("--span", None)),
        by=by))
    return 0


class _Node:
    """One span record, or one phase of a `compile.entry` event, with the
    records that closed inside it on its thread."""

    __slots__ = ("name", "parent", "start", "end", "children")

    def __init__(self, name, parent, start, end):
        self.name, self.parent = name, parent
        self.start, self.end = start, end
        self.children: list = []

    @property
    def dur(self) -> float:
        return self.end - self.start

    def holds(self, other) -> bool:
        return self is other or any(c.holds(other) for c in self.children)


def setup_report(recs: list[dict]) -> dict:
    """The set-up of the process that wrote `recs`, from its first record to
    its last record of set-up: the last `setup.*` span or compile of a
    Program's function (`fn` other than `other`: a window compiles none of
    those, while a benchmark's reference check compiles eager jits long
    after it). Span records carry their parent's NAME and close innermost
    first, so a record's children are the unclaimed records that name it
    and ended after it started; another thread's records in between stay
    where they are (two threads inside same-named spans at once cannot be
    told apart: the stream carries no thread)."""
    loose: list[_Node] = []             # unclaimed, in the order they closed
    entries: list[dict] = []
    last = None                         # the last record of set-up
    for rec in recs:
        name, kind = rec.get("name", "?"), rec.get("type")
        if kind == "event" and name == "compile.entry":
            e = rec["payload"]
            entries.append(e)
            # the three phases as leaves, laid end to end from the entry's
            # start (jax's own bookkeeping between them stays the span's)
            t = e["start"]
            for phase in ("trace", "lower", "backend"):
                d = e.get(phase + "_s", 0.0)
                if d:
                    loose.append(_Node(f"compile.{phase}", rec.get("parent"),
                                       t, t + d))
                    t += d
            if e["fn"] != "other":
                last = loose[-1]
        elif kind == "span":
            end, dur = float(rec["ts"]), float(rec["dur_s"])
            node = _Node(name, rec.get("parent"), end - dur, end)
            # an end is on the wall clock and a duration on the monotonic
            # one: they drift apart by parts in ten thousand
            since = node.start - 1e-3 - 1e-3 * dur
            others = []
            while loose and loose[-1].end >= since:
                c = loose.pop()
                (node.children if c.parent == name else others).append(c)
            node.children.reverse()
            loose.extend(reversed(others))
            loose.append(node)
            if name.startswith("setup."):
                last = node
    empty = {"whole_s": 0.0, "named_s": 0.0, "unattributed_s": 0.0,
             "tree": [], "gaps": [], "compiles": {}, "ops": []}
    if last is None:
        return empty
    roots = sorted(loose, key=lambda n: n.start)
    t1 = next(r.end for r in roots if r.holds(last))
    roots = [r for r in roots if r.end <= t1]
    whole = t1 - roots[0].start
    # named: what the outermost records cover (two threads' records may
    # overlap, and are counted once); the rest lies in the gaps
    named, gaps, edge, prev = 0.0, [], roots[0].start, roots[0]
    for r in roots:
        if r.start > edge:
            gaps.append({"after": prev.name, "before": r.name,
                         "seconds": r.start - edge})
        if r.end > edge:
            named += r.end - max(r.start, edge)
            edge, prev = r.end, r
    gaps = sorted(gaps, key=lambda g: -g["seconds"])[:5]

    # same-named siblings merge: {name: [count, total, self, children]}
    def merge(node, into):
        row = into.setdefault(node.name, [0, 0.0, 0.0, {}])
        row[0] += 1
        row[1] += node.dur
        row[2] += node.dur - sum(c.dur for c in node.children)
        for c in node.children:
            merge(c, row[3])

    top: dict = {}
    for r in roots:
        merge(r, top)
    tree = []

    def flatten(level, path):
        for name, (n, total, own, below) in level.items():
            tree.append({"path": path + [name], "count": n,
                         "total_s": total, "self_s": own})
            flatten(below, path + [name])

    flatten(top, [])

    compiles: dict[str, dict] = {}
    op_runs: dict[str, list] = {}       # fn: op path -> seconds a trace
    for e in entries:
        if e["end"] > t1 + 1e-3:
            continue
        c = compiles.setdefault(e["fn"], {
            "entries": 0, "hit": 0, "miss": 0, "off": 0, "trace_s": 0.0,
            "lower_s": 0.0, "compile_s": 0.0, "retrieval_s": 0.0})
        c["entries"] += 1
        c[e["cache"]] += 1
        c["trace_s"] += e["trace_s"]
        c["lower_s"] += e["lower_s"]
        # the backend's seconds are XLA's compile, or on a hit the read
        c["retrieval_s" if e["cache"] == "hit" else "compile_s"] += \
            e["backend_s"]
        for op, sec in e.get("op_s", {}).items():
            op_runs.setdefault(f'{e["fn"]}: {op}', []).append(sec)
    ops = sorted(({"op": op, "total_s": sum(v), "traces": len(v),
                   "first_s": v[0],
                   "later_mean_s": sum(v[1:]) / (len(v) - 1)
                   if len(v) > 1 else None}
                  for op, v in op_runs.items()),
                 key=lambda o: -o["total_s"])
    return {"whole_s": whole, "named_s": named,
            "unattributed_s": whole - named, "tree": tree, "gaps": gaps,
            "compiles": compiles, "ops": ops}


def cmd_setup(argv: list[str]) -> int:
    # a stream that rotated once keeps its first half beside it (raise
    # FLAGS_obs_jsonl_rotate_mb for a run whose set-up is to be read: a
    # second rotation drops it)
    older = argv[0] + ".1"
    recs = (_read_jsonl(older) if os.path.exists(older) else []) \
        + _read_jsonl(argv[0])
    rep = setup_report(recs)
    if "--json" in argv:
        json.dump(rep, sys.stdout, indent=1)
        sys.stdout.write("\n")
        return 0
    whole = rep["whole_s"]
    if not whole:
        print("no setup.* span and no Program's compile in the stream")
        return 0
    print(f"set-up: {whole:.3f} s from the first record to the last "
          f"setup.* span or Program compile")
    head = "span (compile.*: the phases of the compile.entry events under it)"
    print(f"\n  {head:<68} {'count':>5} {'total_s':>8} {'self_s':>8} "
          f"{'share':>6}")
    for row in rep["tree"]:
        label = "  " * (len(row["path"]) - 1) + row["path"][-1]
        print(f"  {label:<68} {row['count']:>5} {row['total_s']:>8.3f} "
              f"{row['self_s']:>8.3f} {row['self_s'] / whole:>6.1%}")
    un = rep["unattributed_s"]
    print(f"  {'unattributed (between the outermost records)':<68} {'':>5} "
          f"{'':>8} {un:>8.3f} {un / whole:>6.1%}")
    for g in rep["gaps"][:3]:
        if g["seconds"] > 0.01 * whole:
            print(f"    {g['seconds']:.3f} s between {g['after']} and "
                  f"{g['before']}")
    print(f"\n  {'compiled functions by fn':<22} {'entries':>7} {'hit':>4} "
          f"{'miss':>4} {'off':>4} {'trace_s':>8} {'lower_s':>8} "
          f"{'compile_s':>9} {'cache_read_s':>12}")
    for fn, c in sorted(rep["compiles"].items(),
                        key=lambda kv: -sum(kv[1][k] for k in (
                            "trace_s", "lower_s", "compile_s",
                            "retrieval_s"))):
        print(f"  {fn:<22} {c['entries']:>7} {c['hit']:>4} {c['miss']:>4} "
              f"{c['off']:>4} {c['trace_s']:>8.3f} {c['lower_s']:>8.3f} "
              f"{c['compile_s']:>9.3f} {c['retrieval_s']:>12.3f}")
    if rep["ops"]:
        head = "tracing seconds by op (self), top ten"
        print(f"\n  {head:<68} {'total_s':>8} {'traces':>6} {'first_s':>8} "
              f"{'later_mean_s':>12}")
        for o in rep["ops"][:10]:
            later = "-" if o["later_mean_s"] is None \
                else f"{o['later_mean_s']:.4f}"
            print(f"  {o['op']:<68} {o['total_s']:>8.3f} {o['traces']:>6} "
                  f"{o['first_s']:>8.4f} {later:>12}")
    return 0


def main() -> int:
    cmds = {"tail": (cmd_tail, 1), "summarize": (cmd_summarize, 1),
            "diff": (cmd_diff, 2), "prom": (cmd_prom, 1),
            "ops": (cmd_ops, 1), "setup": (cmd_setup, 1)}
    if len(sys.argv) < 2 or sys.argv[1] not in cmds:
        print(__doc__.strip(), file=sys.stderr)
        return 2
    fn, min_args = cmds[sys.argv[1]]
    argv = sys.argv[2:]
    if len(argv) < min_args:
        print(f"[obs] usage error: {sys.argv[1]} needs {min_args} "
              f"file argument(s)", file=sys.stderr)
        return 2
    try:
        return fn(argv)
    except OSError as e:
        print(f"[obs] FAIL: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
