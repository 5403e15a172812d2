"""Runner `serve_open_loop_routed`: `serve_open_loop` for an engine that
routes tokens to experts. Clock, warm-up, window, settle and every number
are that runner's, imported; the one difference is the comparison that
decides `correct`.

A top-1 router over random weights flips on rounding, so a reference that
routed for itself would leave any bfloat16 engine within a few tokens and
a tolerance wide enough to absorb a wrong expert absorbs everything. The
engine hands every finished request the expert each layer chose for every
position it computed (`GenRequest.routes`); the reference
(`check_sequences` of the configuration's reference module) follows them
and reports two numbers a request: `gap`, as in `serve_open_loop`, and
`route_margin`, by how much its own router would have chosen otherwise. A
request is wrong when either exceeds the configuration's tolerance
(`logit_tolerance`, `route_margin_tolerance`).

One more difference, in who is judged (`judged`): a request that `settle`
cut at its deadline while the engine was still giving it a token a step is
neither a completion nor a failure, and is left out of `attempted`. This
cell's outputs reach 768 tokens at about 29 ms each, 22 s, and the cell's
`settle_s` is 10: a row admitted late in the window cannot end before the
benchmark itself stops the run, at any rate the engine could reach. A row
that stopped getting tokens more than `STALLED_S` before the cut, or that
the engine ended in any state but `finished`, still counts as failed.
"""
from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark.harness import (RunContext, RunResult, TraceSlice, percentile,
                               percentile_band, registry_view)
from benchmark.runners.serve_open_loop import (SAMPLE, build_engine,
                                               compared, drive, due_early,
                                               settle, summarize,
                                               warm_prefills)
from benchmark.traffic import open_loop

STALLED_S = 1.0     # a served row gets a token a step; a step is 0.03-0.15 s


def check_sample(engine, cfg, tracks: list, ctx: RunContext) -> dict:
    """Grade a seeded sample of finished requests against the reference,
    the engine's routes followed."""
    reference = importlib.import_module(ctx.config["reference"]["module"])
    done = [tr for tr in tracks if tr.state == "finished" and tr.served
            and tr.live.routes is not None]
    rng = np.random.default_rng([ctx.seed, 7])
    picked = [done[i] for i in
              rng.choice(len(done), min(SAMPLE, len(done)), replace=False)]
    if not picked:
        return {"sampled": 0, "wrong": [], "worst_gap": None}
    params = reference.read_params(engine._scope.find_var, cfg)
    graded = reference.check_sequences(
        params, [(tr.request.prompt, tr.served, tr.live.routes)
                 for tr in picked], cfg)
    tol = float(ctx.config["reference"]["logit_tolerance"])
    margin_tol = float(ctx.config["reference"]["route_margin_tolerance"])
    return {"sampled": len(picked), "tolerance": tol,
            "route_margin_tolerance": margin_tol,
            "worst_gap": max(g["gap"] for g in graded),
            "worst_route_margin": max(g["route_margin"] for g in graded),
            "wrong": [tr for tr, g in zip(picked, graded)
                      if g["gap"] > tol or g["route_margin"] > margin_tol]}


def judged(tracks: list, accounting: str, seconds: float,
           cut_s: float) -> tuple:
    """The requests `failed` is counted over, and how many were left out
    because `settle`'s deadline (`cut_s` after the window's start) cut them
    while they were being served."""
    pool = due_early(tracks, seconds) if accounting == "due" else \
        [tr for tr in tracks if tr.token_s and tr.token_s[0] <= seconds]
    kept = [tr for tr in pool if not (
        tr.state == "unfinished" and tr.token_s
        and tr.token_s[-1] > cut_s - STALLED_S)]
    return kept, len(pool) - len(kept)


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
    if s["itl_s"]:
        values["itl_p99_ms"] = percentile(s["itl_s"], 99) * 1e3
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
               "queue_depth_end": depth[-1][1] if depth else 0,
               "preemptions": stats["preemptions"],
               "cut_while_served": cut,
               **end, **grade})
