"""One cell of BENCHMARK.json, one process, one line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

set-up (build, warm every shape, compile) -> one measured window of
`--seconds` -> correctness -> the last stdout line, a JSON object with the
keys `correct`, `attempted`, `failed`, `metrics`, `device` (`breakdown`
with `--trace 1`) and last `compared`: every number `correct` rests on
beside its limit, also the last lines of standard error. `--trace 0`
reports the cell's end-to-end metrics; `--trace 1` profiles the tail of the
window and reports its per-layer metrics.

Everything that belongs to one cell, configuration, kind of run or
per-layer metric is a file found by name; this file holds no list:

    BENCHMARK.json                         which cells and metrics exist
    benchmark/workloads/<cell>.json        config, chips, runner, traffic
                                           (`reports`: an end-to-end name
                                           of its own for a runner's number)
    benchmark/configs/<config>.json        sizes, builder, feeds, reference
    benchmark/runners/<runner>.py          run(ctx) -> RunResult
    benchmark/layer_metrics/<metric>.json  reader and its arguments (a
                                           metric `x.y` without a file
                                           reads `x.json`)
    benchmark/readers/<reader>.py          read(result, **args) -> number

It exits non-zero and prints no result unless jax finds a TPU whose
`device_kind` is in benchmark/peaks.json, with at least the chips the cell
asks for. `--rehearse` (CPU tests only) swaps in the cell's `rehearse`
overrides, runs on virtual CPU devices and says `platform: cpu`.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()          # process start, as near as Python gives it

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:           # `python benchmark/run.py` puts HERE there
    sys.path.insert(0, ROOT)

from benchmark.harness import (RunContext, layer_metric_spec,  # noqa: E402
                               load_json, merge)


def _fail(msg: str) -> int:
    print(f"benchmark/run.py: {msg}", file=sys.stderr)
    return 2


def cell_metrics(manifest: dict, kind: str, cell: str) -> list:
    """The metrics of `kind` ('end_to_end' | 'per_layer') this cell reports:
    those with no `workloads` key, or with the cell in it."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell in m["workloads"]]


def end_to_end_value(values: dict, cell: dict, name: str):
    """The runner's value for the end-to-end metric `name`. One quantity
    judged under two bounds takes two entries of BENCHMARK.json, and the
    cell's file says which of the runner's numbers it reports under the
    second name (`"reports": {"<metric>": "<runner's name>"}`); a name the
    runner does not give is a KeyError."""
    return values[cell.get("reports", {}).get(name, name)]


def main(argv=None, t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU tests only: tiny config, platform cpu")
    args = ap.parse_args(argv)
    t_start = time.perf_counter() if t_start is None else t_start

    manifest = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        return _fail(f"no workload {args.workload!r} in BENCHMARK.json")
    cell = load_json(HERE, "workloads", args.workload + ".json")
    if args.rehearse:
        cell = merge(cell, cell.get("rehearse", {}))
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4")
    config = load_json(HERE, "configs", cell["config"] + ".json")

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    peaks = load_json(HERE, "peaks.json").get(kind)
    if args.rehearse:
        if platform != "cpu":
            return _fail("--rehearse runs on the CPU only")
    elif platform != "tpu" or peaks is None:
        return _fail(f"needs a TPU listed in benchmark/peaks.json; jax found "
                     f"{platform!r} {kind!r}")
    chips = int(cell["chips"])
    if len(devices) < chips:
        return _fail(f"cell {args.workload!r} needs {chips} chips, jax found "
                     f"{len(devices)}")

    from paddle_tpu import compile_cache

    compile_cache.configure()       # <checkout>/.jax_cache, a fixed path
    if args.rehearse:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    ctx = RunContext(
        cell=cell, config=config, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), chips=chips, devices=devices[:chips],
        peaks=peaks, rehearse=args.rehearse, t_start=t_start,
        trace_dir=os.path.join(ROOT, ".bench_trace", args.workload))
    runner = importlib.import_module(f"benchmark.runners.{cell['runner']}")
    result = runner.run(ctx)
    result.ctx = ctx

    metrics = {}
    if not args.trace:
        for m in cell_metrics(manifest, "end_to_end", args.workload):
            metrics[m["name"]] = {
                "value": end_to_end_value(result.values, cell, m["name"]),
                "unit": m["unit"]}
    else:
        reported = {m["name"] for m in
                    cell_metrics(manifest, "end_to_end", args.workload)}
        for m in cell_metrics(manifest, "per_layer", args.workload):
            if m["moves"] not in reported:
                continue
            spec = layer_metric_spec(HERE, m["name"])
            reader = importlib.import_module(
                f"benchmark.readers.{spec['reader']}")
            value = reader.read(result, **spec.get("args", {}))
            if value is not None:     # nothing to read: left out of the line
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    stats = [d.memory_stats() for d in ctx.devices]
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": max(
                  (s or {}).get("peak_bytes_in_use", 0) for s in stats)}
    line = {"correct": bool(result.correct), "attempted": result.attempted,
            "failed": result.failed, "metrics": metrics, "device": device}
    if args.trace and result.trace:
        device["busy_s"] = result.trace["busy_s"]
        device["window_s"] = result.trace["window_s"]
        line["breakdown"] = {"device_ops": result.trace["device_ops"],
                             "idle_gaps": result.trace["idle_gaps"]}
    # what `correct` compared, each number beside its limit: last in the
    # line and the last lines of standard error
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in result.compared.items()}
    if result.notes:
        print("notes", json.dumps(result.notes), flush=True)
    print(json.dumps(line), flush=True)
    for k, (v, lim) in result.compared.items():
        print(f"compared {k} {v} limit {lim}", file=sys.stderr)
    print(f"correct {bool(result.correct)}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(t_start=_T0))
