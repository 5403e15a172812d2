"""Measurement protocol of tools/tune.py's sweeps and tools/_mc_ab.py: the
timing loop and the statistics their keep-or-retire verdicts are made of
(the benchmark's own window is `benchmark/runners/train_steps.py`):

  * `timed_windows` — windows of async-dispatched iters, each ended by a
    host drain read;
  * `measure` — warmup + windows + summary stats (median-of-windows is the
    sweep estimator: robust to one-sided interference bursts where a mean
    is not, and less optimistic than min for verdicts that persist in a DB);
  * `interference_band` — relative window spread; a sweep whose band
    swamps the margin must not hand out a verdict;
  * `ab_verdict` — keep / retire / tie for a candidate vs baseline median
    under a band (5% is the floor).
"""
from __future__ import annotations

import time

import numpy as np

__all__ = ["timed_windows", "median", "interference_band", "measure",
           "ab_verdict", "DEFAULT_BAND"]

# the interference band: margins inside it are machine noise, not a
# measured win (a single burst can outlast a timed pass)
DEFAULT_BAND = 0.05


def timed_windows(run_once, drain, iters: int, passes: int) -> list[float]:
    """`passes` windows of `iters` async-dispatched steps each, ended by a
    host drain read; returns the per-step seconds of every window so callers
    can keep the spread."""
    windows = []
    for _ in range(passes):
        t0 = time.perf_counter()
        for _ in range(iters):
            run_once()
        np.asarray(drain())
        windows.append((time.perf_counter() - t0) / iters)
    return windows


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
    throughput estimate, band the spread."""
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
