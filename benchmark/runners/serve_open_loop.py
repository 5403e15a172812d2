"""Runner `serve_open_loop`: a seeded request trace offered to one
`ServingEngine` at a fixed rate, from one thread.

Clock. tools/_serve_ab._drive timed a request from `GenRequest.arrival_t`,
which `submit` stamps: a stall before the submit was never counted. Here a
request is timed from the moment it was DUE. The engine is single-threaded
and `step()` blocks, so an arrival can only be handed over when a step
returns: `submit_wait_s` is due -> submit (the engine's blocking step, part
of every TTFT) and `gen_late_s` is the part of it that is the generator's
own (submit minus the first moment the request was due AND the engine had
given control back) — a starved generator shows there, not as a fast
server. A token is stamped when the `step()` that produced it returns (the
engine keeps no per-token time).

Warm-up, counted as set-up: `engine.warmup_decode(longest context)` for
the decode lattice, then one discarded request for every distinct (cached
prefix length, prompt length) the trace holds, with fresh unique tokens and
one output token, so every prefill and suffix-prefill program the window
will run is compiled, and the prefix cache holds the shared prompts a
long-running server would hold and nothing else of the trace. The replay is
keyed on lengths, not on the engine's compiled signature (pow2 bucket,
pages): that rule is the program's to change, and a yardstick that copied
it would miss a program the day it does. It costs set-up (127 replays,
13 s, in the chat cell) until the engine names a request's signature.

Tokens per second come from one curve: the tokens emitted by time t, a
step's tokens taken as emitted evenly over the loop iteration that ran it
(they are stamped together when it returns, so a plain count up to a fixed
instant jumps by a whole batch with the phase of the last step).
`serve_tok_s` is that curve at the window's end over the window's seconds,
on the real clock like every latency here: below its knee the engine
catches up after a stall and loses nothing. Above its knee a stall's tokens
are lost for good, and stalls are rare and long: on the chip one saturated
run in 49 lost 3.2 s inside a single loop iteration (-12% tokens per
second; normal iterations last 0.06-0.5 s; PR 22), and of the driver's two
sets of six one spread by 0.25% and one by 4.4%, which one such run
explains. Over an hour that is under 1% of the rate; in the one 30 s
window that holds it, 12%, and a set of six runs with one such run spreads
ten times as wide as a set without: no bound admits both.
`sat_tok_s`, the rate a cell above its knee is judged on, therefore
shortens the window by the excess of its ONE longest loop iteration over
its second longest. That forgives a single stall and nothing else: two
stalls, a pause that comes back, or any slower step are in the number in
full. The median over the window's seconds does not do it: a saturated
engine's rate swings by +-14% from second to second with the waves of
finishing requests (`tok_s_by_second` in the notes), so the median is
noisy and a stall still moves it by 5%. The stall itself stays visible:
the series `loop_iter_s` keeps every iteration's seconds and its maximum
is the per-layer `loop_iter_max_ms`.

After the window the driver stops submitting, aborts what is still queued,
lets running rows finish for at most `settle_s`, aborts the rest, and
audits the pool.

`correct`: no page leaked, the pool audit is clean, no compile happened in
the window, and for a seeded sample of finished requests every served
token's logit lies within the configuration's `logit_tolerance` of the best
logit at its position in the plain reference's teacher-forced forward.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib
import time

import numpy as np

from benchmark.harness import (RunContext, RunResult, TraceSlice, percentile,
                               percentile_band, registry_view, span)
from benchmark.traffic import open_loop

TERMINAL = ("finished", "aborted", "deadline_exceeded", "shed")
SAMPLE = 8


@dataclasses.dataclass
class Track:
    """One request as the client saw it; times are offsets from the
    window's start."""
    request: open_loop.Request
    submit_s: float
    free_s: float = 0.0             # first moment it could be submitted
    live: object = None             # the engine's record, None if refused
    token_s: list = dataclasses.field(default_factory=list)
    state: str = "refused"
    served: list = dataclasses.field(default_factory=list)


def build_engine(ctx: RunContext):
    from paddle_tpu.serving import DecoderConfig, ServingEngine

    spec = ctx.config["engine"]
    cfg = DecoderConfig(**spec["config_kwargs"])
    engine = ServingEngine(
        cfg, page_size=spec["page_size"], pool_pages=spec["pool_pages"],
        max_inflight=spec["max_inflight"], seed=ctx.seed,
        prefix_cache=spec["prefix_cache"], draft_k=spec["draft_k"])
    return engine, cfg


def warm_prefills(engine, requests: list, seed: int, vocab_size: int) -> int:
    """Compile every prefill program `requests` will need; see the module
    docstring. Returns how many requests it replayed."""
    seen_shared, keys, reps = set(), set(), []
    for r in requests:
        cached = r.shared_len if r.shared_id in seen_shared else 0
        seen_shared.add(r.shared_id)
        if (cached, len(r.prompt)) not in keys:
            keys.add((cached, len(r.prompt)))
            reps.append(r)
    for r in open_loop.redraw_unique(reps, seed, vocab_size):
        engine.submit(r.prompt, 1)
    engine.run_until_drained()
    engine.prune_finished()
    return len(reps)


def drive(engine, requests: list, seconds: float, slice_=None):
    """Offer `requests` for `seconds`; returns (tracks, requests still
    active, queue depth samples as (time, requests submitted and still
    without a token), every loop iteration that ran a step (submit + step +
    stamping) as (start, end, tokens it emitted), the window's start on
    perf_counter)."""
    from paddle_tpu.serving.engine import AdmissionRejected

    pending = collections.deque(requests)
    tracks, depth, steps = [], [], []
    # submitted requests, apart by whether the engine has admitted them: a
    # cell above its knee queues hundreds. One pass a step moves the newly
    # admitted over (a state read a queued request); stamping, which reads
    # and writes several fields, walks the admitted alone (stamping all of
    # them cost 0.4 ms an iteration at 700 queued)
    queued, active = [], []
    t0 = time.perf_counter()
    free_s = 0.0                    # when the engine last gave control back
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if slice_ is not None:
            slice_.maybe_start(now)
        if pending and pending[0].due_s <= now:
            with span("bench.submit"):
                while pending and pending[0].due_s <= now:
                    r = pending.popleft()
                    tr = Track(r, time.perf_counter() - t0,
                               free_s=max(free_s, r.due_s))
                    try:
                        rid = engine.submit(r.prompt, r.max_new)
                    except AdmissionRejected:
                        pass
                    else:
                        tr.live, tr.state = engine.requests[rid], "waiting"
                        queued.append(tr)
                    tracks.append(tr)
        if engine.has_work():
            with span("bench.step"):
                engine.step()
            waiting = []
            for tr in queued:
                (waiting if tr.live.state == "waiting" else active).append(tr)
            queued = waiting
            active, emitted = _stamp(active, time.perf_counter() - t0)
            depth.append((now, len(queued)
                          + sum(1 for tr in active if not tr.token_s)))
            free_s = time.perf_counter() - t0
            steps.append((now, free_s, emitted))
        else:
            with span("bench.idle"):
                next_s = pending[0].due_s if pending else seconds
                time.sleep(max(0.0, min(0.001, next_s - now)))
    return tracks, queued + active, depth, steps, t0


def _stamp(active: list, t: float) -> tuple:
    """Stamp the tokens the last step produced; returns the requests still
    active (finished ones dropped) and how many tokens were stamped."""
    with span("bench.readback"):
        still, emitted = [], 0
        for tr in active:
            n = tr.live.n_generated - len(tr.token_s)
            if n > 0:
                tr.token_s.extend([t] * n)
                emitted += n
            tr.state = tr.live.state
            if tr.state in TERMINAL:
                tr.served = list(tr.live.out_tokens)
            else:
                still.append(tr)
    return still, emitted


def settle(engine, active: list, t0: float, settle_s: float) -> dict:
    """Stop the load: abort the queue, let running rows finish for at most
    `settle_s`, abort the rest, audit."""
    for tr in active:
        if tr.state == "waiting":
            engine.abort(tr.live.rid)
    active, _ = _stamp(active, time.perf_counter() - t0)
    t_end = time.perf_counter() + settle_s
    while engine.has_work() and time.perf_counter() < t_end:
        engine.step()
        active, _ = _stamp(active, time.perf_counter() - t0)
    for tr in active:
        engine.abort(tr.live.rid)
        tr.state, tr.served = "unfinished", list(tr.live.out_tokens)
    problems, poisoned = engine.audit_pool()
    leaked = engine.leaked_pages()
    engine.prune_finished()
    return {"audit_problems": len(problems) + len(poisoned),
            "leaked_pages": leaked, "unfinished": len(active)}


def due_early(tracks: list, seconds: float) -> list:
    """The requests due in the first 90% of the window: those the tails and
    the failure count of a cell below its knee are judged on (the last
    tenth has no time to finish inside the window)."""
    return [tr for tr in tracks if tr.request.due_s < 0.9 * seconds]


def emitted_by(steps: list, times) -> np.ndarray:
    """Tokens emitted by each of `times`: a step's tokens count as emitted
    evenly from the start to the end of the loop iteration that ran it."""
    if not steps:
        return np.zeros(len(times))
    start, end, tokens = (np.array(c, float) for c in zip(*steps))
    share = (np.asarray(times, float)[:, None] - start) \
        / np.maximum(end - start, 1e-9)
    return np.clip(share, 0.0, 1.0) @ tokens


def summarize(tracks: list, steps: list, seconds: float,
              settle_s: float) -> dict:
    """The client's view of one window, on the real clock."""
    emitted = float(emitted_by(steps, [seconds])[0])
    whole = np.arange(int(seconds) + 1.0)
    # the window's part of every iteration; see the module docstring
    iter_s = sorted(min(end, seconds) - start for start, end, _ in steps)
    excess = iter_s[-1] - iter_s[-2] if len(iter_s) > 1 else 0.0
    in_window = [t for tr in tracks for t in tr.token_s if t <= seconds]
    gaps = [b - a for tr in tracks
            for a, b in zip(tr.token_s, tr.token_s[1:]) if b <= seconds]
    # a request that never got its first token missed every limit. It is a
    # sample, not a gap in the data: it counts as the longest wait the
    # driver allows (window + settle), a finite stand-in for "never" that
    # keeps the band mean a number; it is also counted under `failed`
    gave_up = seconds + settle_s
    ttft = [(tr.token_s[0] if tr.token_s else gave_up) - tr.request.due_s
            for tr in due_early(tracks, seconds)]
    return {
        "tokens": len(in_window),
        "serve_tok_s": emitted / seconds,
        "sat_tok_s": emitted / (seconds - excess),
        "tok_s_by_second": [round(float(x), 1)
                            for x in np.diff(emitted_by(steps, whole))],
        "loop_iter_s": [end - start for start, end, _ in steps],
        "ttft_s": ttft, "itl_s": gaps,
        "submit_wait_s": [tr.submit_s - tr.request.due_s for tr in tracks],
        "gen_late_s": [tr.submit_s - tr.free_s for tr in tracks],
        "offered": len(tracks),
        "finished": sum(tr.state == "finished" for tr in tracks),
    }


def check_sample(engine, cfg, tracks: list, ctx: RunContext) -> dict:
    """Grade a seeded sample of finished requests against the reference."""
    reference = importlib.import_module(ctx.config["reference"]["module"])
    done = [tr for tr in tracks if tr.state == "finished" and tr.served]
    rng = np.random.default_rng([ctx.seed, 7])
    picked = [done[i] for i in
              rng.choice(len(done), min(SAMPLE, len(done)), replace=False)]
    if not picked:
        return {"sampled": 0, "wrong": [], "worst_gap": None}
    params = reference.read_params(engine._scope.find_var, cfg)
    gaps = reference.worst_logit_gaps(
        params, [(tr.request.prompt, tr.served) for tr in picked], cfg)
    tol = float(ctx.config["reference"]["logit_tolerance"])
    return {"sampled": len(picked), "worst_gap": max(gaps), "tolerance": tol,
            "wrong": [tr for tr, g in zip(picked, gaps) if g > tol]}


def window_readings(s: dict, view: dict) -> dict:
    """What an untraced run can say of its window beside its end-to-end
    numbers, for the notes: the gaps' percentiles and the program's own
    means of a decode step and a prefill with the host's part of each."""
    out = {f"itl_p{q}_ms": percentile(s["itl_s"], q) * 1e3
           for q in (50, 90, 95, 98, 99, 99.5) if s["itl_s"]}
    for name, series in (("decode_step_ms", "serving.decode.seconds"),
                         ("decode_host_ms", "serving.decode.host_seconds"),
                         ("prefill_step_ms", "serving.prefill.seconds"),
                         ("prefill_host_ms", "serving.prefill.host_seconds")):
        h = view["histograms"].get(series)
        if h and h["count"]:
            out[name] = h["sum"] / h["count"] * 1e3
    if s["loop_iter_s"]:
        out["loop_iter_p50_ms"] = percentile(s["loop_iter_s"], 50) * 1e3
    return out


def _depth_near(depth: list, at_s: float) -> int:
    """The queue's depth at the first sample taken at or after `at_s`."""
    return next((d for t, d in depth if t >= at_s), 0)


def compared(end: dict, compiles: int, grade: dict) -> dict:
    """Every number `correct` rests on beside its limit (a number may not
    pass it), for the three serving runners: the reference's readings carry
    the names their runner's `check_sample` gave them."""
    out = {"leaked_pages": [end["leaked_pages"], 0],
           "audit_problems": [end["audit_problems"], 0],
           "window_compiles": [compiles, 0]}
    for name, key, limit in (
            ("logit_gap", "worst_gap", "tolerance"),
            ("route_margin", "worst_route_margin", "route_margin_tolerance")):
        if grade.get(key) is not None:
            out[name] = [grade[key], grade[limit]]
    for layer, pair in enumerate(zip(
            grade.get("worst_select_margin_by_layer", ()),
            grade.get("select_margin_tolerance", ()))):
        out[f"select_margin.l{layer}"] = list(pair)
    return out


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
    end = settle(engine, active, t0, settle_s)

    s = summarize(tracks, steps, ctx.seconds, settle_s)
    grade = check_sample(engine, cfg, tracks, ctx)
    wrong = {id(tr) for tr in grade.pop("wrong")}
    if traffic["accounting"] == "due":
        # below the knee: every request due in the first 90% of the window
        # must come back whole
        judged = due_early(tracks, ctx.seconds)
    else:
        # above it the queue is backlog, not failure: judge what the engine
        # admitted (gave a first token) inside the window
        judged = [tr for tr in tracks
                  if tr.token_s and tr.token_s[0] <= ctx.seconds]
    failed = sum(tr.state != "finished" or id(tr) in wrong for tr in judged)
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
        correct=correct, attempted=len(judged), failed=failed, values=values,
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
               "peak_pages_in_use": stats["peak_pages_in_use"],
               "preemptions": stats["preemptions"],
               "queue_depth_end": depth[-1][1] if depth else 0,
               "queue_depth_mid": _depth_near(depth, ctx.seconds / 2),
               "window": window_readings(s, view),
               "ttft_ms_sorted": [round(t * 1e3, 1)
                                  for t in sorted(s["ttft_s"])],
               **end, **grade})
