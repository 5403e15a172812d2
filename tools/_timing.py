"""Shared measurement protocol for the tools/ A/B harnesses and tools/tune.py.

One home for the timing loop that was copy-pasted across _rn_igemm.py /
_pipeline_ab.py / _bert_flash_ab.py, and the statistics the sweeper's
keep-or-retire verdicts are made of:

  * `timed_windows` — bench.py's exact window protocol (async-dispatched
    iters ended by a host drain read) so tool numbers stay comparable to
    bench artifacts;
  * `measure` — warmup + windows + summary stats (median-of-windows is the
    sweep estimator: robust to one-sided interference bursts where a mean
    is not, and less optimistic than min for verdicts that persist in a DB);
  * `interference_band` — relative window spread; a sweep whose band
    swamps the margin must not hand out a verdict;
  * `ab_verdict` — keep / retire / tie for a candidate vs baseline median
    under a band (gate.py's 5% interference band is the floor).
"""
from __future__ import annotations

import time

import numpy as np

__all__ = ["timed_windows", "time_call", "median", "interference_band",
           "measure", "ab_verdict", "DEFAULT_BAND", "percentile",
           "latency_stats"]

# gate.py's interference band: margins inside it are machine noise, not a
# measured win (PERF.md r4 — a single burst can outlast a timed pass)
DEFAULT_BAND = 0.05


def timed_windows(run_once, drain, iters: int, passes: int) -> list[float]:
    """bench.py's window protocol: `passes` windows of `iters`
    async-dispatched steps each, ended by a host drain read; returns the
    per-step seconds of every window so callers can keep the spread."""
    windows = []
    for _ in range(passes):
        t0 = time.perf_counter()
        for _ in range(iters):
            run_once()
        np.asarray(drain())
        windows.append((time.perf_counter() - t0) / iters)
    return windows


def time_call(fn) -> tuple[float, object]:
    """Wall-time one call (epoch-granularity arms, e.g. _pipeline_ab's
    whole-pass loops). Returns (seconds, fn's return value)."""
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def median(xs) -> float:
    return float(np.median(np.asarray(list(xs), dtype=np.float64)))


def interference_band(windows) -> float:
    """Relative spread (max-min)/median of the windows: 0.0 = perfectly
    quiet box. Compare against the verdict band — a sweep measured in a
    spread wider than its decision margin is reporting noise."""
    ws = np.asarray(list(windows), dtype=np.float64)
    if ws.size < 2:
        return 0.0
    med = float(np.median(ws))
    return float((ws.max() - ws.min()) / med) if med > 0 else 0.0


def measure(run_once, drain, iters: int, passes: int,
            warmup: int = 1) -> dict:
    """Warmup (compile + cache settle, un-timed) then `timed_windows`,
    summarized: median_s is the verdict estimator, min_s the steady-state
    throughput estimate (the bench.py convention), band the spread."""
    for _ in range(max(0, warmup)):
        run_once()
    np.asarray(drain())
    windows = timed_windows(run_once, drain, iters, passes)
    return {
        "median_s": median(windows),
        "min_s": float(min(windows)),
        "windows_s": [round(w, 6) for w in windows],
        "band": round(interference_band(windows), 4),
    }


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile (q in [0, 100])."""
    return float(np.percentile(np.asarray(list(xs), dtype=np.float64), q))


def latency_stats(seconds) -> dict:
    """Per-request latency summary for the serving load harnesses
    (tools/_serve_ab.py, the bench.py `serving` block): p50/p99 are THE
    serving SLO spellings, mean/max ride along for forensics. All ms."""
    xs = [float(s) for s in seconds]
    if not xs:
        return {"n": 0, "p50_ms": None, "p99_ms": None, "mean_ms": None,
                "max_ms": None}
    return {
        "n": len(xs),
        "p50_ms": round(1e3 * percentile(xs, 50), 3),
        "p99_ms": round(1e3 * percentile(xs, 99), 3),
        "mean_ms": round(1e3 * float(np.mean(xs)), 3),
        "max_ms": round(1e3 * max(xs), 3),
    }


def ab_verdict(base_s: float, cand_s: float,
               band: float = DEFAULT_BAND) -> str:
    """keep  — candidate beats baseline by more than the band;
    retire — candidate loses by more than the band;
    tie    — inside the band: no measured verdict, the caller keeps its
             analytic prior (a tie must never overwrite a model that has
             reasons with a coin flip that does not)."""
    if cand_s < (1.0 - band) * base_s:
        return "keep"
    if cand_s > (1.0 + band) * base_s:
        return "retire"
    return "tie"
