"""A step's tokens read one dispatch late (ISSUE 36): the engine enqueues
step n+1, whose rows take their input tokens from the device, and accepts
step n behind it. What it serves is token for token, route for route and
selection for selection what the blocking loop serves; a failed enqueue is
retried and a failed deferred fetch ends in the recovery pass; the counters
say which way every step program was accepted. CPU: equality and counts,
never a speed."""
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.resilience.faults import fault_scope
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import model as sv_model
from serving_helpers import preempt_youngest

# family -> (config, engine keywords)
FAMILIES = {
    "post_ln": (sv_model.decoder_tiny, dict(page_size=4, pool_pages=96)),
    "cca_moe": (sv_model.cca_moe_tiny, dict(page_size=4, pool_pages=96)),
    "sparse_moe": (sv_model.sparse_moe_tiny,
                   dict(page_size=8, pool_pages=96)),
    "hybrid_moe": (sv_model.hybrid_moe_tiny,
                   dict(page_size=4, pool_pages=128, prefix_cache=True)),
}


def _engine(family, blocking=False, **kw):
    make, base = FAMILIES[family]
    eng = ServingEngine(make(), max_inflight=6, seed=3, **{**base, **kw})
    if blocking:
        # the loop as it was: every step program read as soon as it is
        # enqueued, through the engine's own blocking form
        enqueued = eng._enqueued
        eng._enqueued = lambda step, why=None: enqueued(step, why or "forced")
    return eng


def _traffic(eng, eos_id):
    """Seeded traffic over `eng`, stepped by hand so that both loops see the
    same script: shared prefixes, rows joining and finishing on length in
    the middle, a prompt admitted on a full hit, a one-token request, a
    stop on `eos_id`, a sampled row, an abort and a forced preemption while
    a step is pending. Returns the requests in submission order."""
    ps = eng.page_size
    rng = np.random.default_rng(17)
    shared = rng.integers(1, 97, 3 * ps).tolist()
    keep = "selection" in eng._decode_io

    def prompt(n, head=()):
        return list(head) + rng.integers(1, 97, n).tolist()

    first = prompt(5, shared)
    rids = [eng.submit(first, 9, keep_selection=keep),
            eng.submit(prompt(2 * ps, shared), 4),
            eng.submit(prompt(11), 1)]                    # one token
    for _ in range(3):
        eng.step()
    rids += [eng.submit(shared, 6),                       # a full hit
             eng.submit(prompt(7, shared), 12, eos_id=eos_id),
             eng.submit(prompt(13), 8)]
    for _ in range(2):
        eng.step()
    pending = [eng._pending is not None]
    eng.abort(rids[-1])
    eng.step()
    pending.append(eng._pending is not None)
    assert preempt_youngest(eng)
    # the sampled row last: every step it is in blocks
    rids += [eng.submit(prompt(6, shared), 10, keep_selection=keep),
             eng.submit(prompt(9), 7,
                        sampling={"temperature": 0.8, "top_k": 5})]
    eng.run_until_drained()
    return [eng.requests[r] for r in rids], pending


def _same(a, b):
    if a is None or b is None:
        return a is None and b is None
    return np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_chained_loop_serves_what_the_blocking_loop_serves(family):
    # the request that stops on eos: on a token of its free run, past the
    # first, that it had not emitted before
    probe = _engine(family, blocking=True)
    free = _traffic(probe, eos_id=None)[0][4].out_tokens
    stop = next(k for k in range(1, len(free)) if free[k] not in free[:k])
    eos_id = free[stop]

    was, pending = _traffic(_engine(family, blocking=True), eos_id)
    assert pending == [False, False]
    eng = _engine(family)
    now, pending = _traffic(eng, eos_id)
    assert pending == [True, True]      # under the abort and the preemption
    assert [r.state for r in now] == [r.state for r in was]
    assert [r.state for r in now].count("aborted") == 1
    for a, b in zip(now, was):
        assert a.out_tokens == b.out_tokens, a.rid
        assert _same(a.routes, b.routes), a.rid
        sa, sb = a.selection, b.selection
        assert (sa is None) == (sb is None)
        if sa is not None:
            assert sa[0] == sb[0] and np.array_equal(sa[1], sb[1]), a.rid
    assert now[4].out_tokens == free[:stop + 1]
    assert len(now[2].out_tokens) == 1
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["prefix_full_hits"] == \
        (0 if eng.cfg.stateful else 1)      # a state row cuts a full hit
    assert eng.leaked_pages() == 0 and eng.audit_pool() == ([], [])
    assert sorted(eng._slots_free) == list(range(eng._token_slots))
    # which way the steps were accepted
    st = eng.stats
    assert st["chain.steps_deferred"] > 0 and st["chain.steps_blocking"] > 0
    # the eos row's extra step, unless the sampled row made that step block
    assert st["chain.discarded_rows"] <= 1
    assert probe.stats["chain.steps_deferred"] == 0


def test_a_stop_on_eos_is_found_one_step_late_and_the_extra_step_dropped():
    prompts = [list(range(3, 12)), list(range(20, 26))]
    free = _engine("cca_moe")
    rids = [free.submit(p, 8) for p in prompts]
    free.run_until_drained()
    tokens, routes = free.requests[rids[0]].out_tokens, \
        free.requests[rids[0]].routes
    stop = next(k for k in range(1, 8) if tokens[k] not in tokens[:k])
    eng = _engine("cca_moe")
    eng.reset_stats()
    rids = [eng.submit(p, 8, eos_id=eos)
            for p, eos in zip(prompts, (tokens[stop], None))]
    eng.run_until_drained()
    stopped, other = (eng.requests[r] for r in rids)
    assert stopped.out_tokens == tokens[:stop + 1]
    # routes of the positions it keeps, none of the dropped step's
    assert np.array_equal(stopped.routes, routes[:len(stopped.routes)])
    assert len(stopped.routes) == len(prompts[0]) + stop
    assert other.out_tokens == free.requests[1].out_tokens
    assert np.array_equal(other.routes, free.requests[1].routes)
    assert eng.stats["chain.discarded_rows"] == 1
    assert eng.stats["decode_tokens"] == stop + 8 - 1   # less both prefills'
    assert eng.leaked_pages() == 0 and eng.audit_pool() == ([], [])


def _serve(eng, seeds, new=6):
    rids = []
    for s in seeds:
        rng = np.random.default_rng(s)
        rids.append(eng.submit(rng.integers(1, 97, 5 + s % 7).tolist(), new))
    eng.run_until_drained()
    return [eng.pop_result(r) for r in rids]


def test_a_failed_enqueue_is_retried_with_a_step_pending():
    want = _serve(_engine("post_ln", prefix_cache=False), (20, 21, 22))
    eng = _engine("post_ln", prefix_cache=False, step_retries=3)
    with fault_scope("serving_step_fail:4,8") as plan:
        got = _serve(eng, (20, 21, 22))
        assert plan.stats()["fired"]
    assert got == want
    assert eng.stats["step_retries"] == 2
    assert eng.stats["recovery.passes"] == 0
    assert eng.stats["chain.steps_deferred"] > 0
    assert eng.leaked_pages() == 0


def test_an_error_at_the_deferred_fetch_ends_in_recovery(monkeypatch):
    """Step n's fetch fails after step n+1 was enqueued over its pools: no
    retry (`step_retries` stays 0), one recovery pass, every request
    replayed to the tokens of a fault-free run."""
    want = _serve(_engine("post_ln", prefix_cache=False), (30, 31, 32))
    eng = _engine("post_ln", prefix_cache=False, step_retries=3)
    run_step, calls = eng._run_step, []

    class Lost:
        def __array__(self, *args, **kwargs):
            assert len(calls) == 6      # the next program went out first
            raise RuntimeError("device lost")

    def failing(kind, *args, **kwargs):
        handles = run_step(kind, *args, **kwargs)
        calls.append(kind)
        return dict(handles, tokens=Lost()) if len(calls) == 5 else handles

    monkeypatch.setattr(eng, "_run_step", failing)
    got = _serve(eng, (30, 31, 32))
    assert calls[4] == "decode"
    assert got == want
    assert eng.stats["step_retries"] == 0
    assert eng.stats["recovery.passes"] == 1
    assert eng.stats["recovery.replayed"] >= 1
    assert eng.leaked_pages() == 0
    assert eng.pool.free_count == eng.pool.num_pages


def test_n_decode_steps_book_n_deferred_and_one_fetch_a_dispatch():
    """One request alone, N tokens: one prefill and N - 1 decode steps, all
    but the last accepted behind the next dispatch; the last, with nothing
    to dispatch behind it, at once. Never more than one wait on the device
    between two enqueues."""
    eng = _engine("post_ln")
    eng.warmup_decode(24)
    eng.reset_stats()
    order = []
    dispatch, fetch = eng._dispatch, eng._fetch
    eng._dispatch = lambda *a, **k: (order.append("d"), dispatch(*a, **k))[1]
    eng._fetch = lambda *a, **k: (order.append("f"), fetch(*a, **k))[1]
    n = 9
    rid = eng.submit(list(range(1, 8)), n)
    eng.run_until_drained()
    assert len(eng.pop_result(rid)) == n
    st = eng.stats
    assert st["decode_steps"] == n - 1 and st["prefills"] == 1
    assert st["chain.steps_deferred"] == n - 1      # the prefill and n - 2
    assert st["chain.steps_blocking"] == 1
    assert "".join(order) == "d" + "df" * (n - 1) + "f"
    snap = obs.snapshot()
    assert snap["counters"]["serving.chain.steps_deferred"] == n - 1
    assert snap["counters"]['serving.chain.steps_blocking{why="idle"}'] == 1
    assert snap["stages"]["pipeline.fetch"]["events"] == n
    assert snap["stages"]["pipeline.dispatch"]["events"] == n


def test_a_sampled_or_speculative_step_blocks_and_says_why():
    eng = _engine("post_ln")
    eng.reset_stats()
    rid = eng.submit(list(range(1, 8)), 5,
                     sampling={"temperature": 0.7, "top_k": 4})
    eng.run_until_drained()
    assert len(eng.pop_result(rid)) == 5
    counters = obs.snapshot()["counters"]
    assert counters['serving.chain.steps_blocking{why="sampled"}'] == 5
    assert eng.stats["chain.steps_deferred"] == 0
    spec = _engine("post_ln", draft_k=2)
    spec.reset_stats()
    want = _serve(_engine("post_ln"), (40,), new=8)
    assert _serve(spec, (40,), new=8) == want
    counters = obs.snapshot()["counters"]
    assert counters['serving.chain.steps_blocking{why="spec"}'] \
        == spec.stats["spec_steps"] + 1     # and the prefill before them
    assert spec.stats["chain.steps_deferred"] == 0


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_no_signature_is_added_and_nothing_compiles_after_warm_up(family):
    """The two feeds follow the row bucket: the decode lattice and the
    prefill signature set are the size they were, and a second pass over
    warmed traffic compiles nothing."""
    from paddle_tpu.pipeline import jit_compile_counter

    eng = _engine(family)
    assert eng.warmup_decode(40) == len(
        {eng._row_bucket(b) for b in range(1, 7)}) * len(
        {eng._page_bucket(eng.pool.pages_for(c)) for c in range(1, 42)})
    prompts = [list(range(1 + s, 12 + 3 * s)) for s in range(4)]

    def serve():
        rids = [eng.submit(p, 5) for p in prompts]
        eng.run_until_drained()
        return [eng.pop_result(r) for r in rids]

    first = serve()
    flush = eng.flush_prefix_cache()
    decode, prefill = (set(eng.stats[k]) for k in
                       ("decode_signatures", "prefill_signatures"))
    eng.reset_stats()
    with jit_compile_counter() as compiles:
        assert serve() == first
    assert compiles.count == 0 and flush >= 0
    assert set(eng.stats["decode_signatures"]) == decode
    assert set(eng.stats["prefill_signatures"]) == prefill
    assert all(len(sig) == 2 for sig in decode)         # (rows, pages) alone


# cell -> (its chained_step_share entry, decode lattice, prefills replayed):
# the counts the parent's rehearsal gives
CELLS = {
    "bert_base_decoder.sessions.sat": ("chained_step_share.bert", 12, 10),
    "bert_base_decoder.chat.r80": ("chained_step_share.chat", 12, 12),
    "zaya1_8b.decode.sat": ("chained_step_share.sat", 12, 10),
    "keye_vl2_30b_a3b.docs32k.sat": ("chained_step_share.sat", 3, 13),
    "laguna_xs2.agent16k.sat": ("chained_step_share.sat", 3, 17),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cells_traced_rehearsal_reports_its_chained_step_share(capsys,
                                                                 cell):
    """`benchmark/run.py --rehearse --trace 1` (a CPU run: counts, no
    speed): the line carries the cell's `chained_step_share.*`, a share of
    steps; the lattice and the replayed prefills are as many as they were;
    nothing compiles in the window and `correct` holds."""
    import json

    from benchmark import run as bench_run

    name, lattice, replayed = CELLS[cell]
    rc = bench_run.main(["--workload", cell, "--seed", "3", "--seconds", "1",
                         "--trace", "1", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(ln[len("notes "):]) for ln in lines
                 if ln.startswith("notes "))
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    share = line["metrics"][name]
    assert share["unit"] == "%" and 50.0 < share["value"] <= 100.0
    assert {k for k in line["metrics"] if k.startswith("chained_step_share")
            } == {name}
    assert notes["decode_lattice"] == lattice
    assert notes["prefills_replayed"] == replayed
    assert notes["window_compiles"] == 0
