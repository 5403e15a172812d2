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
the context this test hands the runner."""
import copy
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import RunContext, load_json, merge  # noqa: E402
from benchmark.runners import serve_open_loop_cut as runner  # noqa: E402

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
    assert result.attempted > 100 and result.failed == 0
    assert notes["preemptions"] == 0 and notes["window_compiles"] == 0
    # whoever `settle` aborted with the queue had no token in hand
    queued = [a for a in aborted if a[0] == "waiting"]
    assert queued and not [a for a in queued if a[1] or a[2]]
    assert notes["leaked_pages"] == 0 and notes["audit_problems"] == 0
