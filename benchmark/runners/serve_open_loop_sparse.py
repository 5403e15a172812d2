"""Runner `serve_open_loop_sparse`: `serve_open_loop_routed` for an engine
whose attention reads a learned selection of its cache. Clock, warm-up
replay, window, settle, `judged` and every number are the two accepted
runners', imported; three things differ.

The comparison that decides `correct` carries a third limit. A wrong
selection (the newest 2,048 positions, the first 2,048) moves the logits of
a random model little, so the engine hands back, for requests submitted
with `keep_selection`, what every layer's attention was given at every
position the request computed (`GenRequest.selection`: the mask a window
attended under, the positions a decode row gathered); the reference
(`check_sequences` of the configuration's module) follows it as it follows
the experts, and reports `select_margin` beside `gap` and `route_margin`. A
request is wrong when any of the three passes the configuration's limit
(the selection's a layer: under random weights the indexer's scores spread
less over positions the deeper the layer, so the same rounding reads as
more of their standard deviation; configuration file).

The sample is drawn BEFORE the window, from the pinned schedule, and only
the sample is marked: `SAMPLE` requests, seeded, of the document most asked
of in the first half of the window (a request due later may not finish),
and, in warm-up, the one request that prefills that document, so that the
reference follows the engine's selection at the document's positions too
(`ahead`; selecting for itself there it drifts from a bfloat16 engine under
random weights: reference docstring). What marking costs the window is
therefore bounded: for those `SAMPLE` requests the words of one suffix
window each (`[suffix bucket, layers, G, page_size]` uint32: 7 MB at 256
tokens behind 288 pages) and `[MARK_ROWS, layers, 2,048]` int32 (393 KB) a
decode step while any of them runs cross the host link; the device work of
a step is the same marked or not (the words are an output of every window).

The check has a budget. One 33k-token forward in float32 takes the chip
35-40 s; the reference computes the document once for the sample and stops
(never under `MIN_SAMPLE`) before a forward that would end past
`reference.check_budget_s`.

The decode lattice starts at the page bucket of the shortest context the
trace holds (`warmup_decode(longest, min_context=shortest)`): every request
of this cell stands behind a 32,768-token document, and the nine buckets
below it would be compiled for nothing.
"""
from __future__ import annotations

import collections
import importlib
import time

import numpy as np

from benchmark.harness import (RunContext, RunResult, TraceSlice, percentile,
                               percentile_band, registry_view)
from benchmark.runners.serve_open_loop import (SAMPLE, build_engine,
                                               compared, drive, settle,
                                               summarize, warm_prefills)
from benchmark.runners.serve_open_loop_routed import judged
from benchmark.traffic import open_loop

MIN_SAMPLE = 4


def draw_sample(requests: list, seconds: float, seed: int) -> list:
    """The requests whose selection the window hands back: see the module
    docstring."""
    early = [r for r in requests if r.due_s < seconds / 2]
    asked = collections.Counter(r.shared_id for r in early)
    if not asked:
        return []
    document = min(asked, key=lambda d: (-asked[d], d))
    behind = [r for r in early if r.shared_id == document]
    rng = np.random.default_rng([seed, 7])
    return [behind[i] for i in sorted(rng.choice(
        len(behind), min(SAMPLE, len(behind)), replace=False))]


class Marking:
    """The engine as `warm_prefills` and `drive` see it, submitting with
    `keep_selection` the prompts `wanted(prompt)` says; `kept` holds those
    requests' records (the engine prunes its own)."""

    def __init__(self, engine, wanted):
        self._engine, self._wanted, self.kept = engine, wanted, []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def submit(self, prompt, max_new):
        keep = self._wanted(prompt)
        rid = self._engine.submit(prompt, max_new, keep_selection=keep)
        if keep:
            self.kept.append(self._engine.requests[rid])
        return rid


def first_behind(document: list):
    """`wanted` for the warm-up: the first prompt that starts with
    `document` (the one that prefills it; later ones hit the cache)."""
    seen = []

    def wanted(prompt):
        if seen or list(prompt[:len(document)]) != document:
            return False
        seen.append(True)
        return True

    return wanted


def check_sample(engine, cfg, tracks: list, ctx: RunContext, sample: list,
                 ahead) -> dict:
    """Grade the finished requests of `sample` against the reference, the
    engine's experts and selections followed (`ahead`: the selection at
    their document's positions)."""
    spec = ctx.config["reference"]
    reference = importlib.import_module(spec["module"])
    chosen = {id(r) for r in sample}
    picked = [tr for tr in tracks if id(tr.request) in chosen
              and tr.state == "finished" and tr.served
              and tr.live.routes is not None
              and tr.live.selection is not None]
    if not picked:
        return {"sampled": 0, "wrong": [], "worst_gap": None}
    limits = {"gap": float(spec["logit_tolerance"]),
              "route_margin": float(spec["route_margin_tolerance"])}
    # a limit a layer (one number: the same for all)
    by_layer = np.broadcast_to(np.asarray(spec["select_margin_tolerance"],
                                          float), (cfg.num_layers,))
    params = reference.read_params(engine._scope.find_var, cfg)
    graded = reference.check_sequences(
        params, [(tr.request.prompt, tr.served, tr.live.routes,
                  tr.live.selection, ahead) for tr in picked], cfg,
        budget_s=float(spec["check_budget_s"]), at_least=MIN_SAMPLE)
    return {"sampled": len(graded), "marked": len(sample),
            "marked_finished": len(picked),
            "tolerance": limits["gap"],
            "route_margin_tolerance": limits["route_margin"],
            "select_margin_tolerance": by_layer.tolist(),
            "worst_gap": max(g["gap"] for g in graded),
            "worst_route_margin": max(g["route_margin"] for g in graded),
            "worst_select_margin": max(g["select_margin"] for g in graded),
            "worst_select_margin_by_layer": np.max(
                [g["select_margin_by_layer"] for g in graded], 0).tolist(),
            # read, not judged (the reference's docstring says why)
            "worst_route_margin_unfollowed": max(
                g["route_margin_unfollowed"] for g in graded),
            "wrong": [tr for tr, g in zip(picked, graded)
                      if any(g[k] > limit for k, limit in limits.items())
                      or (np.asarray(g["select_margin_by_layer"])
                          > by_layer).any()]}


def run(ctx: RunContext) -> RunResult:
    from paddle_tpu.pipeline import jit_compile_counter

    traffic = ctx.cell["traffic"]
    settle_s = float(traffic["settle_s"])
    t_build = time.perf_counter()
    engine, cfg = build_engine(ctx)
    requests = open_loop.generate(traffic, ctx.seed, ctx.seconds,
                                  cfg.vocab_size)
    longest = max(len(r.prompt) + r.max_new for r in requests)
    shortest = min(len(r.prompt) for r in requests)
    t_lattice = time.perf_counter()
    lattice = engine.warmup_decode(longest, min_context=shortest)
    t_replay = time.perf_counter()
    sample = draw_sample(requests, ctx.seconds, ctx.seed)
    document = list(sample[0].prompt[:sample[0].shared_len]) if sample \
        else None
    warm = Marking(engine, first_behind(document) if sample
                   else lambda prompt: False)
    replayed = warm_prefills(warm, requests, ctx.seed, cfg.vocab_size)
    engine.reset_stats()
    chosen = {id(r.prompt) for r in sample}
    marking = Marking(engine, lambda prompt: id(prompt) in chosen)

    t_window = time.perf_counter()
    setup_s = t_window - ctx.t_start
    slice_ = TraceSlice(ctx, ctx.seconds - float(traffic["trace_slice_s"]))
    with jit_compile_counter() as compiles:
        tracks, active, depth, steps, t0 = drive(
            marking, requests, ctx.seconds, slice_)
    trace = slice_.finish()
    view = registry_view()
    stats = engine.stats_snapshot()
    cut_s = time.perf_counter() - t0 + settle_s     # settle's own deadline
    end = settle(engine, active, t0, settle_s)

    s = summarize(tracks, steps, ctx.seconds, settle_s)
    t_check = time.perf_counter()
    # what the engine holds when the window is over, before the reference
    # works beside it (`memory_peak_bytes` of the line includes both)
    in_use = (ctx.devices[0].memory_stats() or {}).get("bytes_in_use", 0)
    # the document's selection, from the request that prefilled it
    ahead = None
    if warm.kept and warm.kept[0].selection is not None:
        first, words = warm.kept[0].selection
        ahead = words[:len(document)] if first == 0 else None
    grade = check_sample(engine, cfg, tracks, ctx, sample, ahead)
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
               "engine_bytes_in_use": in_use,
               "peak_pages_in_use": stats["peak_pages_in_use"],
               "queue_depth_end": depth[-1][1] if depth else 0,
               "preemptions": stats["preemptions"],
               "cut_while_served": cut,
               **end, **grade})
