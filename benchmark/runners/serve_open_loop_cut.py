"""Runner `serve_open_loop_cut`: `serve_open_loop` for a cell whose outputs
outlast the settle time. Engine, clock, warm-up, window, settle, the
comparison that decides `correct` and every number are that runner's,
imported; the one difference is who is judged.

A cell above its knee admits rows until the window's last second, and a row
of 1,024 output tokens at 20 ms a step needs 20 s: `settle_s` is 10. A
request that `settle` cut at its deadline while the engine was still giving
it a token a step is neither a completion nor a failure, and is left out of
`attempted` (`serve_open_loop_routed.judged`, written for the same reason
and imported here: a row that had stopped getting tokens more than
`STALLED_S` before the cut, or that the engine ended in any state but
`finished`, still counts as failed). The notes say how many were cut
(`cut_while_served`).
"""
from __future__ import annotations

import time

from benchmark.harness import (RunContext, RunResult, TraceSlice,
                               percentile, percentile_band, registry_view)
from benchmark.runners.serve_open_loop import (_depth_near, build_engine,
                                               check_sample, compared, drive,
                                               settle, summarize,
                                               warm_prefills,
                                               window_readings)
from benchmark.runners.serve_open_loop_routed import judged
from benchmark.traffic import open_loop


def run(ctx: RunContext) -> RunResult:
    from paddle_tpu.pipeline import jit_compile_counter

    traffic = ctx.cell["traffic"]
    settle_s = float(traffic["settle_s"])
    t_build = time.perf_counter()
    engine, cfg = build_engine(ctx)
    requests = open_loop.generate(traffic, ctx.seed, ctx.seconds,
                                  cfg.vocab_size)
    longest = max(len(r.prompt) + r.max_new for r in requests)
    t_lattice = time.perf_counter()
    lattice = engine.warmup_decode(longest)
    t_replay = time.perf_counter()
    replayed = warm_prefills(engine, requests, ctx.seed, cfg.vocab_size)
    engine.reset_stats()

    t_window = time.perf_counter()
    setup_s = t_window - ctx.t_start
    slice_ = TraceSlice(ctx, ctx.seconds - float(traffic["trace_slice_s"]))
    with jit_compile_counter() as compiles:
        tracks, active, depth, steps, t0 = drive(
            engine, requests, ctx.seconds, slice_)
    trace = slice_.finish()
    view = registry_view()
    stats = engine.stats_snapshot()
    cut_s = time.perf_counter() - t0 + settle_s     # settle's own deadline
    end = settle(engine, active, t0, settle_s)

    s = summarize(tracks, steps, ctx.seconds, settle_s)
    t_check = time.perf_counter()
    grade = check_sample(engine, cfg, tracks, ctx)
    wrong = {id(tr) for tr in grade.pop("wrong")}
    kept, cut = judged(tracks, traffic["accounting"], ctx.seconds, cut_s)
    failed = sum(tr.state != "finished" or id(tr) in wrong for tr in kept)
    correct = (end["leaked_pages"] == 0 and end["audit_problems"] == 0
               and compiles.count == 0 and grade["sampled"] > 0
               and not wrong)
    values = {"serve_tok_s": s["serve_tok_s"],
              "sat_tok_s": s["sat_tok_s"], "setup_s": setup_s}
    if s["ttft_s"]:
        values["ttft_p85_95_ms"] = percentile_band(s["ttft_s"], 85, 95) * 1e3
        values["ttft_mean_ms"] = sum(s["ttft_s"]) / len(s["ttft_s"]) * 1e3
    for q in (50, 95, 99) if s["itl_s"] else ():
        values[f"itl_p{q}_ms"] = percentile(s["itl_s"], q) * 1e3
    return RunResult(
        correct=correct, attempted=len(kept), failed=failed, values=values,
        series={k: s[k] for k in ("loop_iter_s", "ttft_s", "itl_s",
                                  "gen_late_s", "submit_wait_s")},
        trace=trace, **view, compared=compared(end, compiles.count, grade),
        notes={"window_compiles": compiles.count, "offered": s["offered"],
               "finished": s["finished"], "tokens": s["tokens"],
               "tok_s_by_second": s["tok_s_by_second"],
               "loop_iter_max_s": max(s["loop_iter_s"], default=0.0),
               "decode_lattice": lattice, "prefills_replayed": replayed,
               "setup_parts_s": {"import": t_build - ctx.t_start,
                                 "engine": t_lattice - t_build,
                                 "decode_lattice": t_replay - t_lattice,
                                 "prefill_replay": t_window - t_replay},
               "reference_check_s": time.perf_counter() - t_check,
               "peak_pages_in_use": stats["peak_pages_in_use"],
               "peak_state_slots_in_use":
                   stats.get("peak_state_slots_in_use", 0),
               "state_snapshots": stats.get("state.snapshots", 0),
               "state_snapshot_evictions":
                   stats.get("state.snapshot_evictions", 0),
               "preemptions": stats["preemptions"],
               "queue_depth_end": depth[-1][1] if depth else 0,
               "queue_depth_mid": _depth_near(depth, ctx.seconds / 2),
               "cut_while_served": cut,
               "window": window_readings(s, view),
               **end, **grade})
