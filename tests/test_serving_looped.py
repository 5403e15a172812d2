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
    what the pool holds free (and past the pool) is admitted as before, and
    a second one waits behind its reservation."""
    eng = _engine(pool_pages=12, max_inflight=2)
    long = eng.submit(_prompts((9,), seed=8)[0], 60)    # ends at 16 pages
    short = eng.submit(_prompts((5,), seed=9)[0], 3)
    eng.step()
    assert eng.requests[long].state == "running"
    assert eng.requests[short].state == "waiting"
    assert eng.requests[short].held_for_growth
    for _ in range(4):
        eng.step()
    assert eng.requests[long].n_generated >= 3
    assert eng.requests[short].state == "waiting"
    eng.abort(long)                 # its reservation goes with it
    eng.run_until_drained()
    assert eng.requests[short].state == "finished"
    assert eng.stats["growth_held_admissions"] == 1
    assert eng.stats["preemptions"] == 0
    assert eng.leaked_pages() == 0 and eng.audit_pool() == ([], [])


def test_a_stop_on_eos_returns_the_reservation():
    """A row allowed 50 tokens reserves 14 of 16 pages; it stops on
    `eos_id` after a few, and the waiter behind it is admitted at once,
    long before the length the reservation was made for."""
    prompt, other = _prompts((6, 6), seed=12)
    free = _serve(_engine(), [prompt], out=12)[0].out_tokens
    stop = next(k for k in range(2, 12) if free[k] not in free[:k])
    want = _serve(_engine(), [other], out=8)[0].out_tokens
    eng = _engine(pool_pages=16, max_inflight=2)
    first = eng.submit(prompt, 50, eos_id=free[stop])
    second = eng.submit(other, 8)
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
    assert eng.stats["preemptions"] == 0
    assert eng.leaked_pages() == 0 and eng.audit_pool() == ([], [])


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
