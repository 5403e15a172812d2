"""The "looped_dense" block family (layers a token passes several times,
every visit with K/V pages of its own) behind ServingEngine, at a tiny size on
the CPU: three layers visited three times, pages of 4. The engine's prefill,
windows and decode through the cache against the plain reference's full
forward (`benchmark/reference/ouro_lm.py`), the prefix cache and
copy-on-write over every plane, rows preempted that come back, admission
that reserves every row's growth to its known end under a pool too small
for the offered rows, the loop against the same stack run once, the exit
gate, and
the wrong mechanisms of `tools/loop_faults.py`, which must each fail the same
check."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import ouro_lm as ref  # noqa: E402
from paddle_tpu.ops import looped_dense_ops as ops  # noqa: E402
from paddle_tpu.serving import DecoderConfig, ServingEngine  # noqa: E402
from paddle_tpu.serving import kv_cache  # noqa: E402
from paddle_tpu.serving import model as sv_model  # noqa: E402
from paddle_tpu.serving.model import looped_dense_tiny  # noqa: E402
from tools import loop_faults  # noqa: E402
from serving_helpers import preempting  # noqa: E402


def _engine(cfg=None, **kw):
    kw = dict(dict(page_size=4, pool_pages=128, max_inflight=4, seed=3,
                   prefix_cache=True, draft_k=0), **kw)
    return ServingEngine(cfg or looped_dense_tiny(), **kw)


def _prompts(lengths, seed=0, shared=0, vocab=97):
    rng = np.random.default_rng(seed)
    head = rng.integers(1, vocab, shared).tolist()
    return [head + rng.integers(1, vocab, n).tolist() for n in lengths]


def _serve(eng, prompts, out=6, audit=False):
    rids = [eng.submit(p, out) for p in prompts]
    while eng.has_work():
        eng.step()
        if audit:
            problems, _ = eng.audit_pool()
            assert not problems, problems
    done = [eng.requests[r] for r in rids]
    assert all(r.state == "finished" for r in done)
    return done


def _gaps(eng, prompts, done):
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    return ref.worst_logit_gaps(
        params, [(p, r.out_tokens) for p, r in zip(prompts, done)], eng.cfg)


def _assert_right(eng, prompts, done):
    assert max(_gaps(eng, prompts, done)) <= 1e-5
    assert eng.leaked_pages() == 0 and eng.audit_pool() == ([], [])


def _full(eng, tokens, cfg=None):
    """The stack's own dense forward on the engine's weights: (logits [S,
    V], exit_mass [S, T])."""
    cfg = cfg or eng.cfg
    get = eng._scope.find_var
    out = ops.looped_dense_stack_fn(
        "full", jnp.asarray([tokens], jnp.int32),
        jnp.arange(len(tokens), dtype=jnp.int32)[None], get("dec.word_emb"),
        get("dec.lm_head"), get("dec.final_norm.scale"),
        get("dec.exit_gate.w"), get("dec.exit_gate.b"),
        {k: get("dec.layers." + k) for k in ops.LAYER_PARAMS},
        ops.Geometry(**sv_model._looped_geometry(cfg)))
    return out["logits"][0], out["exit_mass"][0]


# -- the engine against the reference ----------------------------------------


def test_prefill_windows_and_decode_follow_the_references_full_forward():
    """Prompts of one window and of several (chunks of 8), decoded through
    the cache: every served token is the reference's best at its position,
    and the stack's own dense forward gives the reference's logits."""
    eng = _engine()
    prompts = _prompts((3, 8, 13, 30), seed=1)
    done = _serve(eng, prompts, out=7, audit=True)
    _assert_right(eng, prompts, done)
    assert eng.stats["prefill.chunks"] == 1 + 1 + 2 + 4
    assert eng.stats["preemptions"] == 0
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    seq = prompts[3] + list(done[3].out_tokens)
    want = ref.all_logits(params, seq, eng.cfg)
    got, _ = _full(eng, seq)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    # the logits the engine decoded from the pages are the dense forward's
    assert [int(t) for t in jnp.argmax(want, -1)[len(prompts[3]) - 1:-1]] \
        == list(done[3].out_tokens)


def test_counters_count_every_visit_of_every_layer():
    eng = _engine()
    done = _serve(eng, _prompts((5, 11), seed=2), out=4)
    st, planes = eng.stats, eng.cfg.cache_planes
    assert planes == 9
    assert st["loop.visits"] == planes * (st["decode_steps"]
                                          + st["prefill.chunks"])
    assert st["loop.decode_row_visits"] == planes * st["decode_tokens"]
    assert abs(st["loop.exit_mass"] - st["decode_tokens"]) < 1e-3
    assert all(len(r.exit_mass) == 4 for r in done)


def test_a_prefix_hit_maps_every_plane_of_the_cached_pages():
    """A second request behind a cached 12-token prompt computes its suffix
    alone and attends, in every visit of every layer, what the first one's
    windows wrote."""
    eng = _engine()
    first, second = _prompts((5, 9), seed=3, shared=12)
    a = _serve(eng, [first], out=5)
    computed = eng.stats["prefill_tokens_computed"]
    b = _serve(eng, [second], out=5, audit=True)
    assert eng.stats["prefix_hit_tokens"] == 12
    assert eng.stats["prefill_tokens_computed"] - computed == 9
    _assert_right(eng, [first, second], a + b)


def test_copy_on_write_copies_a_shared_page_in_all_planes():
    """Two requests with one whole-page prompt: the second is a full hit,
    its first decode step writes into the shared last page, which is copied
    first, in every one of the nine planes, and both decode on their own
    copy."""
    eng = _engine()
    (prompt,) = _prompts((12,), seed=4)
    a = _serve(eng, [prompt], out=6)
    b = _serve(eng, [prompt], out=6, audit=True)
    assert eng.stats["prefix_full_hits"] == 1
    assert eng.stats["cow_copies"] >= 1
    assert list(a[0].out_tokens) == list(b[0].out_tokens)
    _assert_right(eng, [prompt, prompt], a + b)
    # the copy program itself: page 5 onto page 9, plane by plane
    k = np.asarray(eng._scope.find_var("kv_cache.k")).copy()
    rng = np.random.default_rng(0)
    k[:] = rng.standard_normal(k.shape)
    eng._scope.set_var("kv_cache.k", jnp.asarray(k))
    eng._dispatch("cow", eng._cow_run,
                  {sv_model.COW_SRC_FEED: np.asarray([5], np.int32),
                   sv_model.COW_DST_FEED: np.asarray([9], np.int32)}, [])
    after = np.asarray(eng._scope.find_var("kv_cache.k"))
    P = eng.pool_pages
    assert after.shape[0] == 9 * P
    for plane in range(9):
        assert np.array_equal(after[plane * P + 9], k[plane * P + 5])
    untouched = np.ones(9 * P, bool)
    untouched[np.arange(9) * P + 9] = False
    assert np.array_equal(after[untouched], k[untouched])


def test_a_preempted_row_resumes_token_for_token():
    """Rows preempted through the engine's own `_make_room` (their pages
    dropped) wait at the head of the queue and prefill their prompt and
    what they had produced again; every request ends with the tokens a
    large pool gives, no page leaks, and the counters say what it cost."""
    prompts = _prompts((9, 14, 6, 11, 17, 8), seed=5, shared=8)
    roomy = _engine()
    want = [list(r.out_tokens) for r in _serve(roomy, prompts, out=24)]
    assert roomy.stats["preemptions"] == 0
    assert roomy.stats["preempted_tokens"] == 0
    tight = _engine(pool_pages=26)
    with preempting(tight, times=3):
        done = _serve(tight, prompts, out=24, audit=True)
    assert [list(r.out_tokens) for r in done] == want
    st = tight.stats
    assert st["preemptions"] == 3
    assert st["preempted_tokens"] >= st["preemptions"]
    assert st["pool_bound_admissions"] >= 1
    assert sum(r.preemptions for r in done) == st["preemptions"]
    # a resumed row's gate readings go on where they stopped
    assert all(len(r.exit_mass) == 24 for r in done)
    _assert_right(tight, prompts, done)


# -- admission that reserves a row's growth to its known end (ISSUE 55) ------


def test_a_pool_too_small_for_the_rows_ends_holds_the_queue_not_the_rows():
    """Six rows that end at 8-11 pages each (a shared head of two) offered
    to 26 pages under four row slots: the pool cannot carry their ends, so
    admission holds the later ones in the queue although their prompts'
    pages are free, and NO row that holds tokens is ever preempted; what
    they serve is what a roomy engine serves."""
    prompts = _prompts((9, 14, 6, 11, 17, 8), seed=5, shared=8)
    roomy = _engine()
    want = [list(r.out_tokens) for r in _serve(roomy, prompts, out=24)]
    assert roomy.stats["growth_held_admissions"] == 0
    assert roomy.stats["pool_bound_admissions"] == 0
    tight = _engine(pool_pages=26)
    done = _serve(tight, prompts, out=24, audit=True)
    assert [list(r.out_tokens) for r in done] == want
    st = tight.stats
    assert st["preemptions"] == 0 and st["preempted_tokens"] == 0
    assert st["growth_held_admissions"] >= 1
    # every admission the reservation held had waited for the pool
    assert st["pool_bound_admissions"] >= st["growth_held_admissions"]
    assert st["prefills"] == len(prompts)       # no window run twice
    assert not any(r.preemptions for r in done)
    # at least two rows stood together: the rule spends rows, not all
    assert st["peak_pages_in_use"] > 13
    _assert_right(tight, prompts, done)


def test_a_lone_request_is_admitted_whatever_its_end():
    """Nothing runs, so nothing can be owed: a request whose end lies past
    what the pool holds free (and past the pool) is admitted as before.
    Until ISSUE 57 a 3-token request behind it waited for a reservation
    that ran to the long row's cap; by the timeline of the ends it is let
    in at once and is gone long before the long row needs the pages. A
    request that would stand at 10 pages beside the first's 11 waits."""
    eng = _engine(pool_pages=12, max_inflight=3)
    long = eng.submit(_prompts((9,), seed=8)[0], 60)    # ends at 16 pages
    short = eng.submit(_prompts((5,), seed=9)[0], 3)
    again = eng.submit(_prompts((9,), seed=10)[0], 30)  # ends at 10
    eng.step()
    assert eng.requests[long].state == "running"
    assert eng.requests[short].state == "running"
    assert not eng.requests[short].held_for_growth
    assert eng.stats["timeline_admissions"] == 1
    assert eng.requests[again].state == "waiting"
    assert eng.requests[again].held_for_growth
    for _ in range(5):
        eng.step()
    assert eng.requests[short].state == "finished"
    assert eng.requests[long].n_generated >= 3
    assert eng.requests[again].state == "waiting"
    eng.abort(long)                 # its reservation goes with it
    eng.run_until_drained()
    assert eng.requests[again].state == "finished"
    assert eng.stats["growth_held_admissions"] == 1
    assert eng.stats["timeline_admissions"] == 1
    assert eng.stats["preemptions"] == 0
    assert eng.leaked_pages() == 0 and eng.audit_pool() == ([], [])


def test_a_stop_on_eos_returns_the_reservation():
    """A row allowed 50 tokens reserves 14 of 16 pages, and a second one
    allowed 44 would stand beside it at 13 of its own: neither the sum of
    the ends nor their timeline lets it in. The first stops on `eos_id`
    after a few tokens, and the waiter behind it is admitted at once, long
    before the length the reservation was made for."""
    prompt, other = _prompts((6, 6), seed=12)
    free = _serve(_engine(), [prompt], out=12)[0].out_tokens
    stop = next(k for k in range(2, 12) if free[k] not in free[:k])
    want = _serve(_engine(), [other], out=44)[0].out_tokens
    eng = _engine(pool_pages=16, max_inflight=2)
    first = eng.submit(prompt, 50, eos_id=free[stop])
    second = eng.submit(other, 44)
    steps = 0
    while eng.requests[second].state == "waiting":
        eng.step()
        steps += 1
    assert eng.requests[first].state == "finished"
    assert eng.requests[first].out_tokens == free[:stop + 1]
    assert steps <= stop + 4            # not the 50 the row was allowed
    eng.run_until_drained()
    assert eng.requests[second].out_tokens == want
    assert eng.stats["growth_held_admissions"] == 1
    assert eng.stats["timeline_admissions"] == 0
    assert eng.stats["preemptions"] == 0
    assert eng.leaked_pages() == 0 and eng.audit_pool() == ([], [])


# -- admission by the timeline of the rows' known ends (ISSUE 57) ------------


def _by_the_sum_alone(eng):
    """`eng` under the rule of ISSUE 55: a head that the sum of the ends
    refuses waits, whatever their timeline."""
    eng._ends_fit = lambda *a: False
    return eng


def _offer(eng, arrivals):
    """Serve `arrivals`, (step it is submitted at, prompt, output tokens)
    in order of step, to the end; returns the requests, and for every step
    the rids that ran after it."""
    arrivals, rids, ran = list(arrivals), [], []
    while arrivals or eng.has_work():
        while arrivals and arrivals[0][0] <= len(ran):
            _, prompt, out = arrivals.pop(0)
            rids.append(eng.submit(prompt, out))
        eng.step()
        ran.append(sorted(r.rid for r in eng._running))
    done = [eng.requests[r] for r in rids]
    assert all(r.state == "finished" for r in done)
    return done, ran


def _random_arrivals(seed, n=14):
    """Prompts of 3-30 tokens, half of them behind one head of 8, outputs
    of 2-40, the first four offered at once and the rest a few steps
    apart: rows at every point of their lives beside one another."""
    rng = np.random.default_rng(seed)
    head = rng.integers(1, 97, 8).tolist()
    at, out = 0, []
    for i in range(n):
        prompt = rng.integers(1, 97, int(rng.integers(3, 31))).tolist()
        if rng.random() < 0.5:
            prompt = head + prompt
        out.append((at, prompt, int(rng.integers(2, 41))))
        at += 0 if i < 3 else int(rng.integers(0, 6))
    return out


@pytest.mark.parametrize("seed", [57, 2147483659, 3, 11])
def test_random_lengths_in_a_tight_pool_never_reach_the_net(seed):
    """Seeded prompt and output lengths into 24 pages under six row slots
    (the longest request alone ends at 20): every request finishes with
    the tokens a roomy engine gives, every window runs once, and growth
    finds its page: neither a settled chain nor a preempted row pays for
    it, and `_make_room` is reached only to take back a waiter's pin."""
    arrivals = _random_arrivals(seed)
    want, _ = _offer(_engine(max_inflight=6), arrivals)
    tight = _engine(pool_pages=24, max_inflight=6)
    room = tight._make_room

    def only_for_a_pin(req):
        """A waiter that pins cached pages the rows were promised gives
        them back; nothing else may ask for room."""
        assert any(r.pages for r in tight._waiting), \
            f"growth of request {req.rid} found the pool dry"
        assert room(req)
        assert not any(r.pages for r in tight._waiting)
        return True
    tight._make_room = only_for_a_pin
    tight._preempt = lambda req: pytest.fail(f"request {req.rid} preempted")
    done, _ = _offer(tight, arrivals)
    assert [r.out_tokens for r in done] == [r.out_tokens for r in want]
    st = tight.stats
    assert st["preemptions"] == 0 and st["preempted_tokens"] == 0
    assert st["prefills"] == len(arrivals)
    assert st["timeline_admissions"] >= 1
    assert st["peak_pages_in_use"] <= 24
    assert tight.leaked_pages() == 0 and tight.audit_pool() == ([], [])


@pytest.mark.parametrize("early, dry", [(0, False), (1, True)])
def test_rows_counted_gone_a_step_early_find_the_pool_dry(
        early, dry, monkeypatch):
    """The timeline's two margins apart. Without the token of lookahead
    (the ladder's second rung serves so) the pages reckoned are the pages
    written, and growth still finds its page; with a row's pages counted
    back in the pool `early` = 1 step sooner, at the step after its last
    write, when the engine still holds that step unread, it finds the pool
    dry."""
    row_ahead = ServingEngine._row_ahead

    def bare(self, r, lookahead):
        gone, at, *rest = row_ahead(self, r, lookahead)
        return (gone - early, at - lookahead, *rest)

    monkeypatch.setattr(ServingEngine, "_row_ahead", bare)
    found_dry = 0
    for seed in (57, 2147483659):
        tight = _engine(pool_pages=24, max_inflight=6)
        room = tight._make_room

        def noted(req, tight=tight, room=room):
            nonlocal found_dry
            found_dry += not any(r.pages for r in tight._waiting)
            return room(req)
        tight._make_room = noted
        _offer(tight, _random_arrivals(seed))
        assert tight.stats["timeline_admissions"] >= 1
    assert bool(found_dry) == dry


def test_the_timeline_admits_short_rows_beside_a_long_one():
    """A row that ends at 18 pages and eight that end at 3, offered to 22
    pages: the sum of the ends lets one short row stand beside the long
    one at a time; the timeline lets in all that the long row's pages of
    the moment leave room for, since each is gone before it needs them.
    The same tokens, in fewer steps, from a fuller pool."""
    arrivals = [(0, _prompts((9,), seed=20)[0], 60)] + [
        (0, p, 6) for p in _prompts((5,) * 8, seed=21)]
    eng_sum = _by_the_sum_alone(_engine(pool_pages=22, max_inflight=8))
    want, ran_sum = _offer(eng_sum, arrivals)
    eng = _engine(pool_pages=22, max_inflight=8)
    done, ran = _offer(eng, arrivals)
    assert [r.out_tokens for r in done] == [r.out_tokens for r in want]
    assert eng_sum.stats["timeline_admissions"] == 0
    assert eng_sum.stats["growth_held_admissions"] >= 1
    assert eng.stats["timeline_admissions"] >= 4
    assert max(map(len, ran)) > max(map(len, ran_sum)) == 2

    def mean_occupancy(e):
        return e.stats["occupancy_sum"] / e.stats["occupancy_n"]
    assert mean_occupancy(eng) > mean_occupancy(eng_sum)
    # the short rows are done in fewer steps; the long row's are its own

    def steps_with_a_short_row(ran):
        return sum(len(rids) > 1 for rids in ran)
    assert steps_with_a_short_row(ran) < steps_with_a_short_row(ran_sum)
    assert len(ran) == len(ran_sum)
    for e in (eng, eng_sum):
        assert e.stats["preemptions"] == 0
        assert e.leaked_pages() == 0 and e.audit_pool() == ([], [])


def test_a_roomy_pool_never_reckons_the_timeline():
    """Where the sum of the ends fits, the head is admitted by it as
    before: the timeline is not asked, and the rows that run after every
    step are those of the rule of ISSUE 55."""
    arrivals = _random_arrivals(5)
    _, ran_sum = _offer(_by_the_sum_alone(_engine(max_inflight=6)), arrivals)
    eng = _engine(max_inflight=6)
    eng._ends_fit = lambda *a: pytest.fail("the timeline was reckoned")
    _, ran = _offer(eng, arrivals)
    assert ran == ran_sum
    assert eng.stats["timeline_admissions"] == 0
    assert eng.stats["growth_held_admissions"] == 0
    assert eng.stats["pool_bound_admissions"] == 0


# -- the loop ----------------------------------------------------------------


def test_one_visit_is_the_stack_run_once_and_two_are_not():
    one = _engine(looped_dense_tiny(loop_steps=1))
    prompts = _prompts((10, 21), seed=6)
    done = _serve(one, prompts, out=5)
    _assert_right(one, prompts, done)
    params = ref.read_params(one._scope.find_var, one.cfg)
    seq = prompts[1] + list(done[1].out_tokens)
    got, mass = _full(one, seq)
    once = ref.all_logits(params, seq, one.cfg, loop_steps=1)
    twice = ref.all_logits(params, seq, one.cfg, loop_steps=2)
    assert float(jnp.max(jnp.abs(got - once))) < 2e-5
    assert float(jnp.max(jnp.abs(got - twice))) > 0.1
    assert np.allclose(np.asarray(mass), 1.0)   # one visit takes all
    # the same weights under two visits: another model
    two_cfg = looped_dense_tiny(loop_steps=2)
    got2, _ = _full(one, seq, two_cfg)
    assert float(jnp.max(jnp.abs(got2 - twice))) < 2e-5


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_weights_are_stored_once_and_pools_once_a_visit(steps):
    """The parameter bytes do not depend on `loop_steps`; the pool bytes
    grow with it linearly."""
    def sizes(loop_steps):
        cfg = looped_dense_tiny(loop_steps=loop_steps)
        params = sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                     for shape, dtype, _ in
                     sv_model._looped_param_specs(cfg).values())
        pools = sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                    for _, shape, dtype in kv_cache.stacked_pool_shapes(
                        *sv_model.stacked_pool_geometry(cfg, 64, 4)))
        return params, pools

    p1, k1 = sizes(1)
    pn, kn = sizes(steps)
    assert pn == p1 and kn == steps * k1
    eng = _engine(looped_dense_tiny(loop_steps=steps), pool_pages=16)
    assert eng._scope.find_var("kv_cache.k").shape == (steps * 3 * 16, 4, 32)
    assert eng._scope.find_var("dec.layers.wqkv").shape == (3, 32, 96)


def test_the_served_configuration_is_the_issues_arithmetic():
    """At the published widths: 2,667,974,657 parameters, 1,572,864 B of
    K/V a token."""
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro_2_6b.json")) as f:
        spec = json.load(f)
    cfg = DecoderConfig(**spec["engine"]["config_kwargs"])
    assert sum(int(np.prod(shape)) for shape, _, _ in
               sv_model._looped_param_specs(cfg).values()) == 2_667_974_657
    pools = kv_cache.stacked_pool_shapes(
        *sv_model.stacked_pool_geometry(cfg, 1, 1))
    assert sum(int(np.prod(shape)) * 2 for _, shape, _ in pools) == 1_572_864
    assert cfg.cache_planes == 192 and cfg.scanned and not cfg.recurrent
    assert cfg.one_page_bucket and cfg.routed_layers == 0


# -- the exit gate -----------------------------------------------------------


def test_exit_masses_are_the_references_and_sum_to_one():
    eng = _engine()
    prompts = _prompts((7, 19), seed=7)
    done = _serve(eng, prompts, out=9)
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    gap, off = loop_faults.exit_mass_gap(ref, params,
                                         list(zip(prompts, done)), eng.cfg)
    assert gap < 2e-6 and off < 2e-6
    got = np.stack(done[1].exit_mass)
    assert got.shape == (9, 3) and (got > 0.01).all()
    # the form: p_t = lambda_t prod_{j<t} (1 - lambda_j), the last takes
    # what is left
    lam = jnp.asarray([[0.2, 0.5, 0.9]])
    assert np.allclose(np.asarray(ops.exit_mass_fn(lam)),
                       [[0.2, 0.8 * 0.5, 0.8 * 0.5]])
    _, mass = _full(eng, prompts[1] + list(done[1].out_tokens))
    assert float(jnp.max(jnp.abs(
        mass[len(prompts[1]) - 1:len(prompts[1]) + 8] - got))) < 2e-6


# -- the wrong mechanisms ----------------------------------------------------


@pytest.mark.parametrize("fault", sorted(loop_faults.FAULTS))
def test_a_planted_fault_fails_the_reference(fault):
    """The cache shared between the visits, and a visit fewer: each serves
    tokens the reference does not."""
    prompts = _prompts((6, 13, 22), seed=8, shared=8)
    with loop_faults.FAULTS[fault]():
        eng = _engine()
        done = _serve(eng, prompts, out=8)
        gaps = _gaps(eng, prompts, done)
    assert max(gaps) > 0.05, (fault, gaps)
    assert eng.leaked_pages() == 0
