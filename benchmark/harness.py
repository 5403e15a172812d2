"""What run.py, the runners and the readers share: the context a runner is
given, the result it returns, and the steps every runner takes the same way
(the profiled slice, the registry's view of the window)."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil


@dataclasses.dataclass
class RunContext:
    """What a runner is given."""
    cell: dict              # benchmark/workloads/<cell>.json
    config: dict            # benchmark/configs/<config>.json
    seed: int
    seconds: float
    trace: bool
    chips: int
    devices: list           # the jax devices the cell may use
    peaks: dict | None      # this device_kind's row of peaks.json
    rehearse: bool
    t_start: float          # perf_counter at process start
    trace_dir: str


@dataclasses.dataclass
class RunResult:
    """What a runner returns. `values` holds every end-to-end number it can
    compute (run.py keeps those BENCHMARK.json lists for the cell);
    `series`, `counters`, `histograms`, `stages` and `trace` are what the
    per-layer readers read; `compared` is every number `correct` rests on
    beside its limit, `{short name: [number, limit]}`."""
    correct: bool
    attempted: int
    failed: int
    values: dict
    series: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    histograms: dict = dataclasses.field(default_factory=dict)
    stages: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None
    notes: dict = dataclasses.field(default_factory=dict)
    compared: dict = dataclasses.field(default_factory=dict)
    ctx: RunContext | None = None


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def layer_metric_spec(here: str, name: str) -> dict:
    """benchmark/layer_metrics/<name>.json, or the file of the longest
    dotted prefix of `name` that has one: the contract gives a metric one
    `moves`, so a reading that moves one end-to-end metric in training cells
    and another in serving cells takes two entries of BENCHMARK.json
    (`x.train`, `x.serve`), and both read `x.json`."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = os.path.join(here, "layer_metrics",
                            ".".join(parts[:n]) + ".json")
        if os.path.isfile(path):
            return load_json(path)
    raise FileNotFoundError(f"no layer_metrics file for {name!r}")


def merge(base: dict, over: dict) -> dict:
    """`base` with `over` laid on top, dict by dict."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


class TraceSlice:
    """Profiles the tail of a measured window: `maybe_start(now_s)` starts
    jax's profiler once `now_s` passes `start_at_s`, `finish()` stops it and
    reduces the trace. The host span `bench.trace_slice` marks the slice on
    the profiler's clock. A run without --trace never starts anything."""

    def __init__(self, ctx: RunContext, start_at_s: float):
        self._ctx = ctx
        self._start_at = start_at_s if ctx.trace else float("inf")
        self._mark = None

    def maybe_start(self, now_s: float) -> None:
        if self._mark is not None or now_s < self._start_at:
            return
        import jax

        shutil.rmtree(self._ctx.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # spans come from TraceAnnotation
        jax.profiler.start_trace(self._ctx.trace_dir, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation("bench.trace_slice")
        self._mark.__enter__()

    def finish(self):
        """Stop profiling; the reduced trace, or None (no slice, or a trace
        in which no device operation ran, as on the CPU)."""
        if self._mark is None:
            return None
        import jax

        from benchmark import trace_reduce

        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return trace_reduce.reduce_trace(self._ctx.trace_dir)


@contextlib.contextmanager
def span(name: str):
    """A host span on the profiler's clock, around a call into a layer."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def registry_view() -> dict:
    """The program's registry as the readers take it."""
    from paddle_tpu import observability as obs

    snap = obs.snapshot()
    return {"counters": snap["counters"], "histograms": snap["histograms"],
            "stages": snap["stages"]}


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list, unrounded."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return float(ordered[int(rank) - 1])


def percentile_band(values, lo: float, hi: float) -> float:
    """Mean of the order statistics between the `lo`-th and the `hi`-th
    percentile of a non-empty list: a smoothed percentile. One order
    statistic of a few hundred step-quantized latencies jumps between runs
    (the nearest-rank p90 of 204 first-token times spread by 3-4.5% in four
    sets of six runs of the same code, the 85-95 band by 1.9-2.5%; PR 22)."""
    ordered = sorted(values)
    a = int(len(ordered) * lo // 100)
    b = max(a + 1, int(-(-len(ordered) * hi // 100)))
    return float(sum(ordered[a:b]) / (b - a))
