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


def main() -> int:
    cmds = {"tail": (cmd_tail, 1), "summarize": (cmd_summarize, 1),
            "diff": (cmd_diff, 2), "prom": (cmd_prom, 1),
            "ops": (cmd_ops, 1)}
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
