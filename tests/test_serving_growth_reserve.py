"""Admission that reserves a row's growth to its known end (ISSUE 55), at
the benchmark's edge: the accepted runner `serve_open_loop_cut` over the
rehearsal configuration of the Ouro cell, under traffic of this test's own
that saturates it.

`serve_open_loop.settle` aborts, when the load stops, every request whose
state is `waiting`, and the runner counts as failed every judged request
(first token inside the window) that ends in any state but `finished`. A
PREEMPTED row is `waiting` and holds tokens: until this rule an engine that
paid its rows' growth by preempting the youngest left some of them in the
queue at the cut (1-5 of about 600 judged here, every one `aborted` with
`preemptions > 0`; the driver's check of PR 54 counted 35 of 317). Nothing
of the benchmark is edited: the traffic and the sizes are overridden in
the context this test hands the runner.

Below it, the two places where the timeline of the rows' ends (ISSUE 57)
gives way: a waiter's pin on cached pages that the rows were promised, and
draft-verify steps."""
import copy
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import RunContext, load_json, merge  # noqa: E402
from benchmark.runners import serve_open_loop_cut as runner  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.serving.model import decoder_tiny  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CELL = "ouro_2_6b.reason.sat"


def _saturated(seed, tmp_path, monkeypatch):
    """The cell's rehearsal made to saturate: outputs of 16-40 tokens at
    400 requests a second into 56 pages under 16 row slots."""
    import jax

    cell = load_json(BENCH, "workloads", CELL + ".json")
    cell = merge(cell, cell["rehearse"])
    cell["traffic"]["arrivals"]["rate_per_s"] = 400.0
    cell["traffic"]["output"] = {"dist": "uniform", "min": 16, "max": 40}
    config = copy.deepcopy(load_json(BENCH, "configs",
                                     cell["config"] + ".json"))
    config["engine"].update(pool_pages=56, max_inflight=16)
    build = runner.build_engine
    engines, aborted = [], []

    def watched(ctx):
        """The runner's engine, its aborts noted as `settle` makes them
        (state, tokens in hand, preemptions): `prune_finished` drops the
        requests before the runner returns."""
        engine, cfg = build(ctx)
        abort = engine.abort

        def noted(rid):
            req = engine.requests.get(rid)
            if req is not None:
                aborted.append((req.state, req.n_generated, req.preemptions))
            abort(rid)
        engine.abort = noted
        engines.append(engine)
        return engine, cfg

    monkeypatch.setattr(runner, "build_engine", watched)
    ctx = RunContext(
        cell=cell, config=config, seed=seed, seconds=1.5, trace=False,
        chips=1, devices=jax.devices()[:1], peaks=None, rehearse=True,
        t_start=time.perf_counter(), trace_dir=str(tmp_path))
    return runner.run(ctx), engines[0], aborted


@pytest.mark.parametrize("seed", [2147483659, 55])
def test_no_row_that_holds_tokens_stands_in_the_queue_at_the_cut(
        seed, tmp_path, monkeypatch):
    result, engine, aborted = _saturated(seed, tmp_path, monkeypatch)
    notes = result.notes
    assert result.correct, result.compared
    # the engine was saturated: the queue stood, the pool was full, and
    # admissions waited for the running rows' growth
    assert notes["queue_depth_end"] > 0 and notes["peak_pages_in_use"] >= 50
    assert engine.stats["growth_held_admissions"] > 0
    # ... and some were let in by the timeline of the rows' ends (ISSUE 57)
    assert engine.stats["timeline_admissions"] > 0
    assert result.attempted > 100 and result.failed == 0
    assert notes["preemptions"] == 0 and notes["window_compiles"] == 0
    # whoever `settle` aborted with the queue had no token in hand
    queued = [a for a in aborted if a[0] == "waiting"]
    assert queued and not [a for a in queued if a[1] or a[2]]
    assert notes["leaked_pages"] == 0 and notes["audit_problems"] == 0


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 97, n).tolist()


def test_a_waiters_pin_gives_way_to_the_rows_growth():
    """Two pages that only the prefix cache holds are counted spare when a
    row is admitted beside another; a third request then hits them, pins
    them and is refused. When the rows' growth comes for the pages the pin
    is given back (`_make_room`) before anything else: no step is settled
    for it and no row preempted, and the waiter matches again."""
    head = _tokens(8, 1)
    eng = ServingEngine(decoder_tiny(), page_size=4, pool_pages=12,
                        max_inflight=4, seed=3, prefix_cache=True, draft_k=0)
    first = eng.submit(head + _tokens(1, 2), 2)
    eng.run_until_drained()
    assert eng.pool.cache_only == 2         # the head's two pages
    a = eng.submit(_tokens(5, 3), 17)       # ends at 6 pages
    b = eng.submit(_tokens(5, 4), 17)       # beside it: all 12, the two cached
    eng.step()
    assert [eng.requests[r].state for r in (a, b)] == ["running"] * 2
    c = eng.submit(head + _tokens(3, 5), 20)
    eng.step()
    waiter = eng.requests[c]
    assert waiter.state == "waiting" and len(waiter.pages) == 2
    assert eng.pool.cache_only == 0
    settle, settled = eng._settle, []

    def noted(why="settle"):
        settled.append(why)
        settle(why)
    eng._settle = noted
    eng._preempt = lambda req: pytest.fail(f"request {req.rid} preempted")
    while eng.requests[a].state == "running":
        eng.step()
    assert waiter.pages == [] or waiter.state != "waiting"
    assert "settle" not in settled
    eng.run_until_drained()
    assert all(eng.requests[r].state == "finished" for r in (first, a, b, c))
    assert eng.stats["preemptions"] == 0
    assert eng.leaked_pages() == 0 and eng.audit_pool() == ([], [])


def test_under_draft_verify_steps_the_sum_of_the_ends_decides_alone():
    """A row of a draft-verify engine takes up to `draft_k + 1` tokens a
    step, so the rows' ends are not ordered as their lengths are: the
    timeline is not reckoned, and a short request waits behind a long
    row's reservation as it did."""
    def served(draft_k):
        eng = ServingEngine(decoder_tiny(), page_size=4, pool_pages=12,
                            max_inflight=2, seed=3, prefix_cache=True,
                            draft_k=draft_k)
        eng.submit(_tokens(9, 8), 40)       # ends at 13 pages
        short = eng.submit(_tokens(5, 9), 3)
        eng.step()
        state = eng.requests[short].state
        eng.abort(0)
        eng.run_until_drained()
        assert eng.requests[short].state == "finished"
        assert eng.leaked_pages() == 0 and eng.audit_pool() == ([], [])
        return state, eng.stats["timeline_admissions"]

    assert served(0) == ("running", 1)
    assert served(2) == ("waiting", 0)
