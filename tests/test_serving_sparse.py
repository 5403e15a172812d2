"""The "sparse_moe" block family (learned sparse attention behind an
indexer, a top-k mixture of experts) through ServingEngine, on the CPU at
toy size with seeded float32 weights, against the plain reference
`benchmark/reference/keye_lm.py` (which imports nothing from paddle_tpu).
The indexer keeps 8 positions, pages hold 8 tokens and a prompt runs in
chunks of 16, so contexts of 5-70 tokens lie on both sides of the
selection."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import keye_lm
from paddle_tpu import observability as obs
from paddle_tpu import unique_name
from paddle_tpu.executor import Executor, Scope
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.ops import decoder_common, sparse_moe_ops
from paddle_tpu.serving import DecoderConfig, ServingEngine
from paddle_tpu.serving import model as sv_model
from serving_helpers import preempting

PS = 8
TOL = 2e-4          # float32 on both sides: rounding order only


def _engine(cfg=None, **kw):
    kw.setdefault("page_size", PS)
    kw.setdefault("pool_pages", 64)
    kw.setdefault("max_inflight", 4)
    kw.setdefault("seed", 3)
    return ServingEngine(cfg or sv_model.sparse_moe_tiny(), **kw)


def _positions(words):
    """Selection words [G, page_size] -> the sorted positions they name."""
    bits = (np.asarray(words).view(np.uint32)[:, None, :]
            >> np.arange(32, dtype=np.uint32)[None, :, None]) & 1
    return np.flatnonzero(bits.reshape(-1))


def _prompts(seed, *lengths, shared=()):
    rng = np.random.default_rng(seed)
    return [list(shared) + rng.integers(1, 97, n).tolist() for n in lengths]


def _serve(eng, prompts, new=6, keep=True):
    rids = [eng.submit(p, new, keep_selection=keep) for p in prompts]
    eng.run_until_drained()
    out = [eng.requests[r] for r in rids]
    assert all(r.state == "finished" for r in out)
    assert eng.audit_pool() == ([], []) and eng.leaked_pages() == 0
    return out


def _graded(eng, prompts, done, ahead=None):
    """check_sequences on what the engine served, its experts and (for a
    marked request) its selection followed; `ahead`, the selection at the
    positions before it."""
    params = keye_lm.read_params(eng._scope.find_var, eng.cfg)
    return keye_lm.check_sequences(
        params, [(p, r.out_tokens, r.routes, r.selection, ahead)
                 for p, r in zip(prompts, done)], eng.cfg)


def _assert_right(eng, prompts, done, gap=TOL, margin=1e-4):
    cfg = eng.cfg
    for r, g in zip(done, _graded(eng, prompts, done)):
        assert r.routes.shape == (r.cache_len, cfg.num_layers,
                                  cfg.experts_per_token)
        assert g["gap"] <= gap and g["route_margin"] <= margin \
            and g["select_margin"] <= margin, g


def test_full_forward_matches_reference():
    cfg = sv_model.sparse_moe_tiny()
    prog, startup = Program(), Program()
    startup.random_seed = 7
    with program_guard(prog, startup), unique_name.guard():
        io = sv_model.build_full_forward_program(cfg)
    exe, scope = Executor(), Scope()
    exe.run(startup, scope=scope)
    tok = np.asarray(_prompts(0, 40), np.int32)
    pos = np.arange(40, dtype=np.int32)[None, :]
    logits, routes, sel = exe.run(
        prog, feed={sv_model.TOK_FEED: tok, sv_model.POS_FEED: pos},
        fetch_list=[io["logits"], io["routes"], io["selection"]],
        scope=scope)
    params = keye_lm.read_params(scope.find_var, cfg)
    x, route_margin, _, _ = keye_lm.forward(params, tok[0], cfg)
    want = np.asarray(x) @ np.asarray(params["lm_head"], np.float32)
    np.testing.assert_allclose(logits[0], want, atol=TOL)
    # the program's experts and selection, followed, leave no margin
    # the sequence as one page: words [S, L, 1, S], bit 0 a position
    sel = sel[0].view(np.uint32)
    assert sel.shape == (40, cfg.num_layers, 1, 40)
    x2, route_margin, select_margin, followed = keye_lm.forward(
        params, tok[0], cfg, routes[0], (0, sel))
    np.testing.assert_allclose(np.asarray(x2), np.asarray(x), atol=TOL)
    assert route_margin.max() <= 1e-5 and select_margin.max() <= 1e-5
    assert followed.all()
    # position 3 has four positions to attend, position 20 exactly top-k
    assert list(_positions(sel[3, 0])) == [0, 1, 2, 3]
    assert len(_positions(sel[20, 0])) == 8
    assert len(np.unique(routes)) == cfg.num_experts


@pytest.mark.parametrize("length,new", [(5, 3), (5, 12), (40, 6), (70, 9)],
                         ids=["under_topk", "across_topk", "three_chunks",
                              "five_chunks"])
def test_chunked_prefill_then_decode_matches_reference(length, new):
    eng = _engine()
    prompts = _prompts(1, length)
    done = _serve(eng, prompts, new=new)
    assert eng.stats["prefill.chunks"] == -(-length // 16)
    first, sel = done[0].selection
    assert first == 0 and sel.dtype == np.uint32 \
        and sel.shape[:2] == (done[0].cache_len, 3) and sel.shape[3] == PS
    # every position attends min(top-k, what exists), itself included
    for t in (0, length - 1, done[0].cache_len - 1):
        for layer in range(3):
            kept = _positions(sel[t, layer])
            assert len(kept) == min(8, t + 1) and (kept <= t).all()
    _assert_right(eng, prompts, done)


def test_batched_requests_of_different_lengths():
    eng = _engine()
    prompts = _prompts(2, 3, 30, 17, 50)
    done = _serve(eng, prompts, new=6)
    assert eng.stats["decode_signatures"]
    assert eng.stats["sparse.layer_steps"] > 0
    # what the indexer scored and kept, as the engine counts them
    assert 0 < eng.stats["sparse.selected_tokens"] \
        < eng.stats["sparse.context_tokens"]
    _assert_right(eng, prompts, done)


def test_prefix_hit_keeps_the_indexer_keys_with_the_page():
    shared = _prompts(3, 48)[0]                   # six whole pages
    first, second = (shared + tail for tail in _prompts(4, 7, 11))
    eng = _engine()
    _serve(eng, [first])
    hit = _serve(eng, [second])
    assert eng.stats["prefix_hit_tokens"] == 48
    assert hit[0].selection[0] == 48              # the suffix onward
    # past top-k the suffix attends document positions chosen by keys that
    # another request wrote
    assert (_positions(hit[0].selection[1][0, 0]) < 48).any()
    cold = _serve(_engine(prefix_cache=False), [second])
    assert hit[0].out_tokens == cold[0].out_tokens
    np.testing.assert_array_equal(hit[0].routes, cold[0].routes)
    np.testing.assert_array_equal(hit[0].selection[1],
                                  cold[0].selection[1][48:])
    _assert_right(eng, [second], hit)


def test_reference_shares_a_prefix_forward(monkeypatch):
    """`check_sequences` computes the tokens before a selection once for
    the sequences that share them; the two halves are the one forward."""
    shared = _prompts(3, 48)[0]
    prompts = [shared + tail for tail in _prompts(4, 7, 11, 5)]
    eng = _engine()
    ahead = _serve(eng, prompts[:1])[0].selection[1][:48]
    done = _serve(eng, prompts[1:])
    assert [r.selection[0] for r in done] == [48, 48]
    whole = _graded(eng, prompts[1:], done)
    calls = []
    forward = keye_lm.forward
    monkeypatch.setattr(keye_lm, "forward", lambda *a, **kw: (
        calls.append(kw.get("keep", 0)), forward(*a, **kw))[1])
    monkeypatch.setattr(keye_lm, "_LONG", 16)
    monkeypatch.setattr(keye_lm, "_QUERY_BLOCK", 8)
    split = _graded(eng, prompts[1:], done)
    assert calls == [48, 0, 0]            # one prefix forward, two suffixes
    for a, b in zip(whole, split):
        assert abs(a["gap"] - b["gap"]) <= 1e-5
        assert b["route_margin"] <= 1e-4 and b["select_margin"] <= 1e-4
        assert b["route_margin_unfollowed"] <= 1e-4
    # with the selection of the request that prefilled the shared tokens
    # every position is followed, and judged
    calls.clear()
    for b in _graded(eng, prompts[1:], done, ahead):
        assert b["route_margin"] <= 1e-4 and b["select_margin"] <= 1e-4
        assert b["route_margin_unfollowed"] == 0.0
    assert calls == [48, 0, 0]


def test_a_wrong_selection_ahead_fails_by_select_margin():
    """The document's positions are judged through `ahead`: the words of
    another document's prefill there read as a wrong selection."""
    shared, other = _prompts(3, 48, 48)
    eng = _engine()
    ahead = _serve(eng, [other + [5]])[0].selection[1][:48]
    _serve(eng, [shared + [5]])
    prompt = shared + _prompts(4, 7)[0]
    done = _serve(eng, [prompt])
    assert _graded(eng, [prompt], done, ahead)[0]["select_margin"] > 1.0


def test_full_hit_copies_the_page_on_write():
    prompt = _prompts(5, 32)[0]                   # four whole pages
    eng = _engine()
    _serve(eng, [prompt])
    again = _serve(eng, [prompt], new=8)
    assert eng.stats["prefix_full_hits"] == 1 and eng.stats["cow_copies"] >= 1
    cold = _serve(_engine(prefix_cache=False), [prompt], new=8)
    assert again[0].out_tokens == cold[0].out_tokens
    _assert_right(eng, [prompt], again)


@pytest.mark.parametrize("pool", ["kv_cache.index", "kv_cache.kv"])
def test_copy_on_write_moves_keys_routes_and_indexer_keys(pool):
    prompt = _prompts(6, 20)[0]
    want = _serve(_engine(), [prompt], new=10)[0]
    eng = _engine()
    rid = eng.submit(prompt, 10, keep_selection=True)
    while eng.requests[rid].n_generated < 2:
        eng.step()
    req = eng.requests[rid]
    old = list(req.pages)
    before = np.asarray(eng._scope.find_var(pool))
    assert eng._cow(req, len(req.pages) - 1)      # the page being written
    assert req.pages[-1] != old[-1] and eng.stats["cow_copies"] == 1
    after = np.asarray(eng._scope.find_var(pool))
    for layer in range(eng.cfg.num_layers):
        row = layer * eng.pool_pages
        assert np.abs(before[row + old[-1]]).max() > 0
        np.testing.assert_array_equal(after[row + req.pages[-1]],
                                      before[row + old[-1]])
    eng.run_until_drained()
    assert req.out_tokens == want.out_tokens
    np.testing.assert_array_equal(req.routes, want.routes)
    np.testing.assert_array_equal(req.selection[1], want.selection[1])
    assert eng.audit_pool() == ([], []) and eng.leaked_pages() == 0


def test_preemption_and_resume():
    prompts = _prompts(7, 20, 22)
    roomy = _serve(_engine(), prompts, new=14)
    # ten pages hold both rows to their ends (five each): both are admitted
    # and the younger is preempted by hand
    eng = _engine(pool_pages=10, prefix_cache=False)
    with preempting(eng):
        tight = _serve(eng, prompts, new=14)
    assert eng.stats["preemptions"] > 0
    assert [r.out_tokens for r in tight] == [r.out_tokens for r in roomy]
    # a preempted request is marked anew when it comes back, and its
    # selection then covers the replayed positions too
    assert any(r.preemptions and r.selection[0] == 0 for r in tight)
    _assert_right(eng, prompts, tight)


def _newest_indices(scores, limit, k):
    kk = min(int(k), scores.shape[-1])
    at = limit[..., None] - 1 - jnp.arange(kk, dtype=jnp.int32)
    return jnp.where(at >= 0, at, -1)


def _newest_mask(scores, limit, k):
    at = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    return (at < limit[..., None]) & (at >= limit[..., None] - k)


@pytest.mark.parametrize("fault", ["mask", "indices", "both"])
def test_a_wrong_selection_fails_by_select_margin(fault, monkeypatch):
    """An engine that attends "the newest k" instead of the indexer's k, in
    its windows (the mask), in its decode steps (the indices) or in both,
    serves tokens whose logits a random model barely tells apart, and is
    caught by the margin of what it hands back: each form is what its
    attention used, so a fault in one alone cannot hide behind the
    other."""
    if fault != "mask":
        monkeypatch.setattr(sparse_moe_ops, "select_indices_fn",
                            _newest_indices)
    if fault != "indices":
        monkeypatch.setattr(sparse_moe_ops, "select_mask_fn", _newest_mask)
    eng = _engine()
    prompts = _prompts(8, 40)
    done = _serve(eng, prompts, new=8)
    graded = _graded(eng, prompts, done)[0]
    assert graded["select_margin"] > 1.0, graded
    assert min(graded["select_margin_by_layer"]) > 1.0, graded
    # followed, the wrong selection reproduces the engine's own logits: the
    # logit gap alone would have passed it
    assert graded["gap"] <= TOL


@pytest.mark.parametrize("fault", ["one_more", "one_fewer", "the_future"])
def test_a_miscounted_mask_fails_by_select_margin(fault, monkeypatch):
    """A window's mask that keeps a position too many, one too few, or one
    the query cannot see reads `MISCOUNT`, whatever its scores."""
    right = sparse_moe_ops.select_mask_fn

    def wrong(scores, limit, k):
        keep = right(scores, limit, k)
        at = jnp.arange(scores.shape[-1], dtype=jnp.int32)
        if fault == "one_more":
            first_out = jnp.argmin(keep | (at >= limit[..., None]), axis=-1)
            return keep | ((at == first_out[..., None])
                           & (limit[..., None] > k))
        if fault == "one_fewer":
            return keep & (at != jnp.argmax(keep, axis=-1)[..., None])
        return keep | (at == limit[..., None])

    monkeypatch.setattr(sparse_moe_ops, "select_mask_fn", wrong)
    eng = _engine()
    prompts = _prompts(8, 40)
    done = _serve(eng, prompts, new=2)
    assert _graded(eng, prompts, done)[0]["select_margin"] \
        == keye_lm.MISCOUNT


def test_only_marked_requests_bring_their_selection_to_the_host(monkeypatch):
    eng = _engine()
    fetched = []
    fetch = eng._fetch

    def spy(kind, handles):
        outs = fetch(kind, handles)
        fetched.append([None if o is None else o.shape for o in outs])
        return outs

    monkeypatch.setattr(eng, "_fetch", spy)
    prompts = _prompts(9, 30, 30)
    rids = [eng.submit(p, 4, keep_selection=keep)
            for p, keep in zip(prompts, (True, False))]
    eng.run_until_drained()
    done = [eng.requests[r] for r in rids]
    assert done[0].selection is not None and done[1].selection is None
    # selections cross only for the marked request: a window's words [1,
    # bucket, L, G, page], the marked rows' positions [M, L, kk] a decode
    # step
    crossed = {shapes[-1] for shapes in fetched if len(shapes) == 4}
    assert crossed == {None, (1, 16, 3, 1, PS), (sv_model.MARK_ROWS, 3, 8)}
    fetched.clear()
    _serve(eng, _prompts(10, 30), new=4, keep=False)
    assert fetched and all(shapes[-1] is None for shapes in fetched)


def test_more_than_mark_rows_asking_leaves_the_rest_unmarked():
    eng = _engine(max_inflight=16, pool_pages=128)
    done = _serve(eng, _prompts(17, *[12] * 10), new=3)
    assert sum(r.selection is not None for r in done) == sv_model.MARK_ROWS


def test_chunks_are_spans_and_counted():
    obs.reset("serving.")
    eng = _engine()
    _serve(eng, _prompts(11, 40), new=2)
    snap = obs.snapshot()
    assert snap["counters"]["serving.prefill.chunks"] == 3
    assert snap["histograms"]["serving.prefill.chunk.seconds"]["count"] == 3


def test_topk_router_combines_as_a_per_token_loop():
    rng = np.random.default_rng(12)
    T, H, F, E, k = 9, 16, 24, 128, 8
    z = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    router_w = jnp.asarray(rng.standard_normal((H, E)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((1, E, H, F)) * H ** -0.5,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((1, E, F, H)) * F ** -0.5,
                     jnp.float32)
    ids, cw = decoder_common.topk_router_fn(z, router_w, k)
    got = decoder_common.moe_topk_experts_fn(z, cw, wg, wu, wd)
    probs = np.asarray(jax.nn.softmax(z @ router_w, axis=-1), np.float64)
    zs = np.asarray(z, np.float64)
    for t in range(T):
        top = np.argsort(-probs[t])[:k]
        assert set(top) == set(np.asarray(ids[t]))
        share = probs[t, top] / probs[t, top].sum()
        np.testing.assert_allclose(np.asarray(cw[t])[top], share, rtol=1e-5)
        assert np.count_nonzero(np.asarray(cw[t])) == k
        want = 0.0
        for e, s in zip(top, share):
            g = zs[t] @ np.asarray(wg[0, e], np.float64)
            u = zs[t] @ np.asarray(wu[0, e], np.float64)
            want = want + s * ((g / (1 + np.exp(-g)) * u)
                               @ np.asarray(wd[0, e], np.float64))
        np.testing.assert_allclose(np.asarray(got[t]), want, atol=1e-4)


def _sorted_indices(scores, limit, k):
    """The form decode used until PR 34, kept as the oracle: `lax.top_k`
    over the live scores (stable: ties to the lower position)."""
    live = jnp.arange(scores.shape[-1]) < limit[..., None]
    flat = jnp.where(live, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    vals, idx = jax.lax.top_k(flat, min(int(k), scores.shape[-1]))
    return jnp.where(vals > -jnp.inf, idx.astype(jnp.int32), -1)


def _selection_case(case, T, k):
    """scores [4, 1, T] and limits [4, 1] of one kind of difficulty."""
    rng = np.random.default_rng(T + k)
    scores = rng.standard_normal((4, 1, T)).astype(np.float32)
    limit = np.full((4, 1), T, np.int32)
    if case == "limits":        # below k, at a page edge, mid-page, whole
        limit[:, 0] = [max(1, k // 2 - 3), min(T, -(-(k + 1) // 128) * 128),
                       min(T, k + 77), T]
    elif case == "all_ties":
        scores[:] = 0.5
        limit[:, 0] = [T, T - 5, max(1, k - 1), 1]
    elif case == "ties_above_the_cut":
        scores[:, :, ::3] = 7.0
        scores[1, :, 1::3] = 6.0
        limit[2:, 0] = [T - 128, max(1, T // 2 + 9)]
    elif case == "negative_zero":
        scores[:] = np.where(rng.random((4, 1, T)) < 0.5, -0.0, 0.0)
        scores[1, :, ::7] = -1.0
        scores[2, :, ::5] = 1.0
    elif case == "clustered":   # one page holds the whole selection
        scores[:, :, 128:256] += 50.0
        scores[1:, :, T - 128:] += 60.0
    return jnp.asarray(scores), jnp.asarray(limit)


@pytest.mark.parametrize("T,k", [(40, 8), (40, 64), (640, 8), (640, 64),
                                 (4608, 64), (4608, 2048)])
@pytest.mark.parametrize("case", ["random", "limits", "all_ties",
                                  "ties_above_the_cut", "negative_zero",
                                  "clustered"])
def test_selection_mask_and_indices_name_the_same_set(case, T, k):
    """One selection rule in two forms: the indices a decode row gathers by
    are the set bits of the mask a window attends under, in ascending
    position with -1 behind them, and name the set the sorted form named
    (PR 29-33: `lax.top_k`). T spans one ragged group of lanes (40), five
    whole ones and the served class (several pages, k = 2,048)."""
    scores, limit = _selection_case(case, T, k)
    sel = np.asarray(jax.jit(sparse_moe_ops.select_indices_fn,
                             static_argnums=2)(scores, limit, k))
    mask = np.asarray(sparse_moe_ops.select_mask_fn(scores, limit, k))
    was = np.asarray(_sorted_indices(scores, limit, k))
    assert sel.shape == was.shape == (4, 1, min(k, T))
    for b in range(4):
        n = min(k, T, int(limit[b, 0]))
        kept, tail = sel[b, 0, :n], sel[b, 0, n:]
        assert (tail == -1).all() and (kept >= 0).all()
        assert (np.diff(kept) > 0).all()               # ascending, distinct
        assert kept.tolist() == np.flatnonzero(mask[b, 0]).tolist()
        assert kept.tolist() == sorted(was[b, 0][was[b, 0] >= 0].tolist())
    if case == "all_ties":
        assert sel[0, 0].tolist() == list(range(min(k, T)))


def test_selection_of_many_queries_a_row_keeps_its_shape():
    """`[B, S, T]` with S > 1 (no caller today; the rows are flattened and
    come back): every query under its own limit."""
    rng = np.random.default_rng(13)
    scores = jnp.asarray(rng.standard_normal((2, 5, 300)).astype(np.float32))
    limit = jnp.asarray([[300, 33, 129, 6, 1], [128, 20, 9, 256, 8]],
                        jnp.int32)
    sel = np.asarray(sparse_moe_ops.select_indices_fn(scores, limit, 16))
    mask = np.asarray(sparse_moe_ops.select_mask_fn(scores, limit, 16))
    assert sel.shape == (2, 5, 16)
    for b in range(2):
        for s in range(5):
            kept = sel[b, s][sel[b, s] >= 0]
            assert kept.tolist() == np.flatnonzero(mask[b, s]).tolist()
            assert len(kept) == min(16, int(limit[b, s]))


def test_decode_step_serves_the_sorted_forms_logits_and_words(monkeypatch):
    """Paged decode steps of the block past `index_topk` positions of
    context: with the sorted form put back in its place the engine serves
    the same tokens, logits inside the file's tolerance (a softmax over a
    set, summed in another order) and bit-identical `selection` words."""
    prompts = _prompts(31, 40, 70)
    run_step = ServingEngine._run_step

    def served():
        eng, logits = _engine(), []

        def to_host(kind, target, io, feed, greedy, *args, **kw):
            out = run_step(eng, kind, target, io, feed, False, *args, **kw)
            if kind == "decode":
                logits.append(np.asarray(out["logits"]))
            return dict(out, logits=None)

        eng._run_step = to_host
        done = _serve(eng, prompts, new=6)
        assert eng.stats["sparse.layer_steps"] > 0 and logits
        return done, logits

    now, logits = served()
    monkeypatch.setattr(sparse_moe_ops, "select_indices_fn", _sorted_indices)
    was, logits_was = served()
    for a, b in zip(now, was):
        assert a.out_tokens == b.out_tokens
        assert a.selection[0] == b.selection[0]
        assert np.array_equal(a.selection[1], b.selection[1])
    assert len(logits) == len(logits_was)
    for a, b in zip(logits, logits_was):
        np.testing.assert_allclose(a, b, atol=TOL)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_moe_experts_pallas_at_width_768(dtype, monkeypatch):
    """The served geometry's expert width: 768 = 2 tiles of 384; eight
    non-zero combine weights a row."""
    pme = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.moe_experts")
    monkeypatch.setattr(pme, "INTERPRET", True)
    assert pme._f_tile(768) == 384 and pme._f_tile(2048) == 512
    assert pme.experts_supported((64, 2048), (6, 128, 2048, 768),
                                 jnp.bfloat16)
    assert pme.experts_supported((64, 2048), (24, 16, 2048, 2048),
                                 jnp.bfloat16)
    rng = np.random.default_rng(14)
    L, E, H, F, T, k = 2, 16, 128, 768, 20, 8
    dt = jnp.dtype(dtype)
    z = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((L, E, H, F)) * H ** -0.5, dt)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((L, E, F, H)) * F ** -0.5, dt)
    cw = np.zeros((T, E), np.float32)
    for t in range(T):
        cw[t, rng.choice(E, k, replace=False)] = rng.dirichlet(np.ones(k))
    assert pme.experts_supported(z.shape, wg.shape, dt)
    tol = 2e-2 if dt.itemsize == 2 else 1e-4
    for layer in range(L):
        got = pme.moe_topk_experts(z, jnp.asarray(cw), wg, wu, wd, layer)
        want = pme._reference(z, jnp.asarray(cw), wg, wu, wd, layer)
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    via = decoder_common.moe_topk_experts_fn(z, jnp.asarray(cw), wg, wu, wd,
                                             layer=1)
    np.testing.assert_allclose(
        via, pme._reference(z, jnp.asarray(cw), wg, wu, wd, 1),
        rtol=tol, atol=tol)


# What PR 29's tree (two pools, K and V a row each; the short-context step
# through the paged kernel's XLA reference) served for `_joined_rounds`:
# out_tokens, sha1[:12] of routes, the selection's first position, sha1[:12]
# of its words. Recorded from that tree on the CPU, seed 3.
_TWO_POOL_SERVED = {
    "float32": [
        ([10, 74, 59, 38, 57], "f22eaf7b1020", 0, "acfd2bbf86ed"),
        ([50, 13, 71, 44, 72], "dc73726b185c", 32, "e0cf7939edd3"),
        ([2, 74, 8, 31], "31a8f14cb752", 0, "6dda62d43cb4"),
        ([84, 50, 65, 63, 64], "04a07272c535", 31, "382a4c8a6c3d")],
    "bfloat16": [
        ([10, 74, 59, 38, 57], "0a32a4645d59", 0, "9fc830b06514"),
        ([50, 13, 71, 22, 41], "6c1e5c3fdf9e", 32, "8a669fa6cb72"),
        ([2, 74, 8, 31], "31a8f14cb752", 0, "6dda62d43cb4"),
        ([84, 50, 65, 63, 64], "f53d62cb4112", 31, "19bd612b1441")],
}


def _digest(a):
    import hashlib
    return hashlib.sha1(np.ascontiguousarray(a).tobytes()).hexdigest()[:12]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_joined_rows_serve_what_two_pools_served(dtype):
    """Prefix sharing (a second request behind four shared pages), the
    short-context step (a request that never leaves its first page attends
    every live position of its slab), then a full hit whose last page is
    copied on write: tokens, routes and handed-back selections are those of
    the tree that kept K and V in a pool each."""
    eng = _engine(sv_model.sparse_moe_tiny(dtype=dtype))
    shared = _prompts(20, 32)[0]
    tails = _prompts(21, 7, 11)
    served = []
    for prompts in ([shared + tails[0]],
                    [shared + tails[1], _prompts(22, 3)[0]], [shared]):
        rids = [eng.submit(p, 5 if len(p) > 3 else 4, keep_selection=True)
                for p in prompts]
        eng.run_until_drained()
        served += [eng.requests[r] for r in rids]
    assert eng.stats["prefix_hit_tokens"] == 64 \
        and eng.stats["prefix_full_hits"] == 1 \
        and eng.stats["cow_copies"] == 1
    assert eng.audit_pool() == ([], []) and eng.leaked_pages() == 0
    # the short one stayed under the selection: no indexer step scored it
    assert not eng.cfg.selects_within(PS) and served[2].cache_len < PS
    assert [(r.out_tokens, _digest(r.routes), r.selection[0],
             _digest(r.selection[1])) for r in served] \
        == _TWO_POOL_SERVED[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_joined_pool_holds_the_bytes_of_the_two_pools(dtype):
    """One pool of 32-bit words where K and V had one each: the same bytes
    a token a layer (2 x kv_heads x head_dim values), so the engine's
    device memory is what it was."""
    cfg = sv_model.sparse_moe_tiny(dtype=dtype)
    eng = _engine(cfg)
    kv = eng._scope.find_var("kv_cache.kv")
    item = jnp.dtype(dtype).itemsize
    assert kv.dtype == jnp.int32 and kv.shape == (
        3 * 64, PS, 2 * cfg.kv_heads * cfg.head_dim * item // 4)
    assert kv.nbytes == 2 * 3 * 64 * PS * cfg.kv_heads * cfg.head_dim * item
    assert not eng._scope.has_var("kv_cache.k") \
        and not eng._scope.has_var("kv_cache.v")
    # the Pallas paged kernel reads two pools: this block never calls it
    assert eng._paged_decode_call(4, 1)[:2] == (0, False)


def test_bfloat16_engine_stays_inside_the_bfloat16_tolerances():
    """bfloat16 weights and pools (the indexer keys too), everything else
    float32, against the float32 reference on the same stored weights. The
    limits are this test's own (hidden 32 rounds coarser than 2,048); the
    cell's are in benchmark/configs/keye_vl2_30b_a3b.json."""
    eng = _engine(sv_model.sparse_moe_tiny(dtype="bfloat16"))
    assert eng._scope.find_var("kv_cache.index").dtype == jnp.bfloat16
    assert eng._scope.find_var("kv_cache.index").shape == (3 * 64, 8, PS)
    prompts = _prompts(15, 30, 45)
    done = _serve(eng, prompts, new=8)
    _assert_right(eng, prompts, done, gap=0.1, margin=0.3)
    other = _serve(_engine(seed=4), prompts, new=8)
    worst = max(g["gap"] for g in _graded(eng, prompts, other))
    assert worst > 0.1


def _kernel_geometry():
    """The block at a geometry `paged_indexer_supported` takes: an indexer
    of 8 heads of 128 keeping 64 positions, bfloat16 keys; the engine below
    gives it 128-token pages."""
    return sv_model.sparse_moe_tiny(
        dtype="bfloat16", index_heads=8, index_head_dim=128, index_topk=64,
        prefill_chunk=128, max_position=1024)


def test_the_paged_indexer_kernel_serves_what_the_gather_served(monkeypatch):
    """Decode rows behind three pages of context, the selection (64 of
    about 300 positions) engaged: with the indexer's scores computed by the
    paged kernel (interpreter) the engine serves the tokens and hands back
    the selections the gathered form served, and
    `serving.sparse.kernel_layer_steps` says that the kernel served every
    (layer, step); on the XLA arm, and at the rehearsal geometry the gate
    refuses, it stays 0."""
    from paddle_tpu import observability as obs
    from paddle_tpu.ops.pallas_kernels import paged_indexer

    prompts = _prompts(41, 300, 270)

    def served():
        eng = _engine(_kernel_geometry(), page_size=128, pool_pages=16)
        eng.reset_stats()      # the registry's serving.* series start at 0
        return eng, _serve(eng, prompts, new=5)

    eng, was = served()
    assert eng._scope.find_var("kv_cache.index").shape == (3 * 16, 128, 128)
    assert eng.stats["sparse.layer_steps"] > 0
    assert eng.stats["sparse.kernel_layer_steps"] == 0
    monkeypatch.setattr(paged_indexer, "INTERPRET", True)
    eng, now = served()
    steps = eng.stats["sparse.layer_steps"]
    assert eng.stats["sparse.kernel_layer_steps"] == steps > 0
    assert obs.snapshot()["counters"][
        "serving.sparse.kernel_layer_steps"] == steps
    for a, b in zip(now, was):
        assert a.out_tokens == b.out_tokens
        assert a.selection[0] == b.selection[0]
        assert np.array_equal(a.selection[1], b.selection[1])
    # 8-token pages of 2 heads of 8: the gate refuses, the gather serves
    eng = _engine()
    _serve(eng, _prompts(31, 40), new=4)
    assert eng.stats["sparse.layer_steps"] > 0
    assert eng.stats["sparse.kernel_layer_steps"] == 0


def test_page_buckets_round_to_32_past_32():
    eng = _engine(sv_model.sparse_moe_tiny(max_position=4096),
                  pool_pages=600)
    assert [eng._page_bucket(n) for n in (1, 3, 32, 33, 261, 288, 289)] \
        == [1, 4, 32, 64, 288, 288, 320]
    assert ServingEngine(sv_model.decoder_tiny(), page_size=4,
                         pool_pages=16)._page_bucket(261) == 512
    # the lattice starts at the shortest context's bucket
    assert eng.warmup_decode(70 * PS, min_context=40 * PS) == 3 * 2
    # a deployment may start its row buckets higher; nothing is raised for
    # a family
    assert [eng._row_bucket(n) for n in (1, 3, 4)] == [1, 4, 4]
    low = _engine(sv_model.sparse_moe_tiny(min_row_bucket=4))
    assert [low._row_bucket(n) for n in (1, 3, 4)] == [4, 4, 4]
    assert low.warmup_decode(20, min_context=20) == 1
    with pytest.raises(ValueError, match="power of two"):
        sv_model.sparse_moe_tiny(min_row_bucket=3)


def test_block_field_and_refusals():
    cfg = sv_model.sparse_moe_tiny()
    assert cfg.scanned and not cfg.stateful and cfg.selects
    assert cfg.page_bucket_step == 32 and cfg.selects_within(9) \
        and not cfg.selects_within(8)
    assert not sv_model.cca_moe_tiny().selects \
        and DecoderConfig().page_bucket_step == 0
    assert sv_model.cca_moe_tiny().scanned and not DecoderConfig().scanned
    with pytest.raises(ValueError, match="post_ln | cca_moe | sparse_moe"):
        DecoderConfig(block="mamba")
    with pytest.raises(ValueError, match="index_topk"):
        sv_model.sparse_moe_tiny(index_topk=0)
    with pytest.raises(ValueError, match="experts_per_token"):
        sv_model.sparse_moe_tiny(experts_per_token=9)
    with pytest.raises(ValueError, match="even num_kv_heads"):
        sv_model.sparse_moe_tiny(num_kv_heads=1, dtype="bfloat16")
    assert sv_model.sparse_moe_tiny(num_kv_heads=1).kv_heads == 1
    with pytest.raises(NotImplementedError, match="draft_k"):
        _engine(draft_k=2)
    with pytest.raises(NotImplementedError, match="tp > 1"):
        _engine(tp=2)
    with pytest.raises(NotImplementedError, match="shared pool"):
        _engine(prefill_only=True)
    eng = _engine()
    rid = eng.submit(_prompts(16, 5)[0], 4)
    eng.step()
    with pytest.raises(NotImplementedError, match="indexer keys"):
        eng.extract_for_handoff(rid)
