"""Find the knee of a serving mix: the highest offered rate the engine
sustains. Run once, by hand, when a cell is defined; the rate goes into the
cell file as a number and the table into PERF.md. Never part of a check.

    python benchmark/knee_sweep.py --seconds 20 --seed 1 \
        bert_base_decoder.chat.r80:4,8,12,16,24 [<cell>:<rates> ...]

One process, one engine (every cell named must use the same configuration),
so the decode lattice compiles once. Per rate: the cell's mix with that
arrival rate and its own seed, prefill warm-up, a window, the settle. A rate
is sustained when at least 97% of the requests offered came back whole and
the queue at the window's end was no deeper than at its middle.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import (RunContext, load_json, merge,  # noqa: E402
                               percentile, percentile_band, registry_view)


def _band_mean(depth, lo, hi):
    vals = [d for t, d in depth if lo <= t < hi]
    return sum(vals) / len(vals) if vals else 0.0


def _rates(listed, verdicts: dict, refine: float):
    """The listed rates in order, less those above two that already failed,
    then (with --refine f) the midpoints between the highest rate found
    sustained below every failing one and the lowest failing one, until they
    lie within `f` of the knee of each other. `verdicts` (rate -> sustained)
    is filled by the caller as it goes."""
    for rate in listed:
        if sum(not ok for r, ok in verdicts.items() if r < rate) < 2:
            yield rate
    while refine > 0:
        bad = min((r for r, ok in verdicts.items() if not ok), default=None)
        good = max((r for r, ok in verdicts.items()
                    if ok and (bad is None or r < bad)), default=None)
        if bad is None or good is None or bad - good <= refine * good:
            return
        yield round((good + bad) / 2, 1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--schedule-seed", type=int,
                    help="replay another drawn schedule than the cells'")
    ap.add_argument("--refine", type=float, default=0.0,
                    help="bisect around the knee to this share of it")
    ap.add_argument("sweeps", nargs="+", help="<cell>:<rate>,<rate>,...")
    args = ap.parse_args(argv)

    import jax

    from paddle_tpu import compile_cache
    from benchmark.runners import serve_open_loop as sol
    from benchmark.traffic import open_loop

    compile_cache.configure()
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("knee_sweep.py: needs a TPU", file=sys.stderr)
        return 2
    plans = []
    for item in args.sweeps:
        name, rates = item.split(":")
        cell = load_json(ROOT, "benchmark", "workloads", name + ".json")
        if args.rehearse:
            cell = merge(cell, cell.get("rehearse", {}))
        plans.append((name, cell, [float(r) for r in rates.split(",")]))
    config = load_json(ROOT, "benchmark", "configs",
                       plans[0][1]["config"] + ".json")
    ctx = RunContext(cell=plans[0][1], config=config, seed=args.seed,
                     seconds=args.seconds, trace=False, chips=1,
                     devices=jax.devices()[:1], peaks=None,
                     rehearse=args.rehearse, t_start=time.perf_counter(),
                     trace_dir="")
    engine, cfg = sol.build_engine(ctx)
    engine.warmup_decode(max(int(c["traffic"]["max_total"])
                             for _, c, _ in plans))
    for name, cell, rates in plans:
        verdicts = {}
        for i, rate in enumerate(_rates(rates, verdicts, args.refine)):
            mix = merge(cell["traffic"],
                         {"arrivals": {"rate_per_s": rate}})
            if args.schedule_seed is not None:
                mix["schedule_seed"] = args.schedule_seed
            seed = args.seed + 1000 * i
            requests = open_loop.generate(mix, seed, args.seconds,
                                          cfg.vocab_size)
            engine.flush_prefix_cache()
            sol.warm_prefills(engine, requests, seed, cfg.vocab_size)
            engine.reset_stats()
            tracks, active, depth, steps, t0 = sol.drive(
                engine, requests, args.seconds)
            stats = engine.stats_snapshot()
            end = sol.settle(engine, active, t0,
                             float(mix["settle_s"]))
            s = sol.summarize(tracks, steps, args.seconds,
                              float(mix["settle_s"]))
            mid = _band_mean(depth, 0.45 * args.seconds, 0.55 * args.seconds)
            tail = _band_mean(depth, 0.9 * args.seconds, args.seconds)
            done = s["finished"] / max(1, s["offered"])
            row = {
                "cell": name, "rate_per_s": rate, "offered": s["offered"],
                "finished_share": done, "queue_mid": mid, "queue_end": tail,
                "sustained": bool(done >= 0.97 and tail <= max(mid, 1.0)),
                "serve_tok_s": s["serve_tok_s"],
                "sat_tok_s": s["sat_tok_s"],
                "loop_iter_max_s": max(s["loop_iter_s"], default=0.0),
                "ttft_p50_ms": percentile(s["ttft_s"], 50) * 1e3,
                "ttft_p90_ms": percentile(s["ttft_s"], 90) * 1e3,
                "ttft_p85_95_ms": percentile_band(s["ttft_s"], 85, 95) * 1e3,
                "itl_p99_ms": percentile(s["itl_s"], 99) * 1e3,
                "gen_late_p99_ms": percentile(s["gen_late_s"], 99) * 1e3,
                "submit_wait_p99_ms":
                    percentile(s["submit_wait_s"], 99) * 1e3,
                "batch_rows_mean": stats["tokens_per_decode_step"],
                "prefix_hit_rate": stats["prefix_cache_hit_rate"],
                "peak_pages_in_use": stats["peak_pages_in_use"],
                "decode_step_ms": sol.window_readings(
                    s, registry_view()).get("decode_step_ms"),
                "memory_peak_bytes": (jax.devices()[0].memory_stats() or {})
                .get("peak_bytes_in_use", 0), **end}
            verdicts[rate] = row["sustained"]
            print("sweep", json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
