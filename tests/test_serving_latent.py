"""The "latent_moe" block family (multi-head latent attention over one
compressed cache row a token, read through the learned indexer; a
group-limited mixture of experts of which the engine holds a share) through
ServingEngine, on the CPU at toy size with seeded weights, against the
plain reference `benchmark/reference/deepseek_v32_lm.py` (which imports
nothing from paddle_tpu). The indexer keeps 8 positions, pages hold 8
tokens and a prompt runs in chunks of 16, so contexts of 5-70 tokens lie on
both sides of the selection; 2 of 4 expert groups are kept, a dense layer
leads two routed ones, and the engine holds 8 of 16 experts."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v32_lm as ref
from paddle_tpu import observability as obs
from paddle_tpu import unique_name
from paddle_tpu.executor import Executor, Scope
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.ops import decoder_common, latent_moe_ops
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
    return ServingEngine(cfg or sv_model.latent_moe_tiny(), **kw)


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
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    return ref.check_sequences(
        params, [(p, r.out_tokens, r.routes, r.selection, ahead)
                 for p, r in zip(prompts, done)], eng.cfg)


def _assert_right(eng, prompts, done, gap=TOL, margin=1e-4):
    cfg = eng.cfg
    for r, g in zip(done, _graded(eng, prompts, done)):
        assert r.routes.shape == (r.cache_len, cfg.routed_layers,
                                  cfg.experts_per_token)
        assert g["gap"] <= gap and g["route_margin"] <= margin \
            and g["select_margin"] <= margin, g


def _served_logits(eng, prompt, new):
    """One request through the engine's own loop, its steps' logits brought
    to the host: [new, V], the logits each served token was the argmax of
    (the last prefill window's, then every decode step's)."""
    run_step = ServingEngine._run_step
    last_chunk, decode = [], []

    def to_host(kind, target, io, feed, greedy, *args, **kw):
        out = run_step(eng, kind, target, io, feed, False, *args, **kw)
        got = np.asarray(out["logits"])[0]
        if kind == "decode":
            decode.append(got)
        else:
            last_chunk[:] = [got]
        return dict(out, logits=None)

    eng._run_step = to_host
    done = _serve(eng, [prompt], new=new)[0]
    del eng._run_step
    return done, np.stack(last_chunk + decode)[:new]


def _reference_logits(eng, prompt, done, follow=False):
    """The reference's full forward over prompt + served tokens, at the
    served positions; `follow`: the engine's experts and selection
    followed."""
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    seq = (list(prompt) + list(done.out_tokens))[:-1]
    choices = (done.routes, done.selection) if follow else ()
    return ref.logits(params, seq, eng.cfg, *choices)[len(prompt) - 1:]


# -- the programs against the reference --------------------------------------


def test_full_forward_matches_reference():
    cfg = sv_model.latent_moe_tiny()
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
    params = ref.read_params(scope.find_var, cfg)
    np.testing.assert_allclose(logits[0], ref.logits(params, tok[0], cfg),
                               atol=TOL)
    # the program's experts and selection, followed, leave no margin
    sel = sel[0].view(np.uint32)
    assert sel.shape == (40, cfg.num_layers, 1, 40)
    assert routes.shape == (1, 40, cfg.routed_layers, 2)
    x, route_margin, select_margin, followed, _ = ref.forward(
        params, tok[0].tolist(), cfg, routes[0], (0, sel))
    assert route_margin.max() <= 1e-5 and select_margin.max() <= 1e-5
    assert followed.all()
    assert list(_positions(sel[3, 0])) == [0, 1, 2, 3]
    assert len(_positions(sel[20, 0])) == 8
    # the router's choices span all experts, not the held ones alone, and
    # a token's two experts lie inside two groups of four
    assert routes.max() >= cfg.experts_held and routes.min() == 0
    assert ((routes // 4)[..., 0] != (routes // 4)[..., 1]).any()


@pytest.mark.parametrize("dtype,tol", [("float32", TOL), ("bfloat16", 0.25)])
@pytest.mark.parametrize("length,new", [(5, 3), (5, 12), (40, 6), (70, 9)],
                         ids=["under_topk", "across_topk", "three_chunks",
                              "five_chunks"])
def test_prefill_then_decode_logits_match_the_full_forward(length, new,
                                                           dtype, tol):
    """A prompt in chunks, then decode steps, through both pools: every
    served token's LOGITS against the reference's one full forward over
    prompt + served tokens (float32 on both sides; then bfloat16 weights
    and pools against the float32 reference on the same stored weights)."""
    eng = _engine(sv_model.latent_moe_tiny(dtype=dtype))
    prompt = _prompts(1, length)[0]
    done, got = _served_logits(eng, prompt, new)
    assert eng.stats["prefill.chunks"] == -(-length // 16)
    assert got.shape == (new, 97)
    want = _reference_logits(eng, prompt, done)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=tol)
        _assert_right(eng, [prompt], [done])
        first, sel = done.selection
        assert first == 0 and sel.dtype == np.uint32 and sel.shape[:2] == (
            done.cache_len, 3) and sel.shape[3] == PS
        for t in (0, length - 1, done.cache_len - 1):
            for layer in range(3):
                kept = _positions(sel[t, layer])
                assert len(kept) == min(8, t + 1) and (kept <= t).all()
    else:
        # a top-k flips on bfloat16 rounding, and a flipped expert moves
        # the logits by more than rounding does: the engine's experts and
        # selection are followed, and their margins read beside the logits
        want = _reference_logits(eng, prompt, done, follow=True)
        assert np.abs(got - want).max() <= tol
        g = _graded(eng, [prompt], [done])[0]
        assert g["gap"] <= tol and g["route_margin"] <= 0.05 \
            and g["select_margin"] <= 0.5, g


def test_batched_requests_of_different_lengths():
    eng = _engine()
    prompts = _prompts(2, 3, 30, 17, 50)
    done = _serve(eng, prompts, new=7)
    _assert_right(eng, prompts, done)
    st = eng.stats
    assert st["sparse.layer_steps"] > 0
    assert 0 < st["sparse.selected_tokens"] < st["sparse.context_tokens"]
    assert st["latent.gathered_rows"] >= st["latent.attended_tokens"] > 0
    # about half of the routed pairs fall on the 8 of 16 experts held
    assert 0.2 < st["moe.held_pairs"] / st["moe.routed_pairs"] < 0.8
    assert st["moe.layer_steps"] == st["decode_steps"] * 2


def test_the_pools_hold_one_row_a_token():
    for dtype, words in (("float32", 20), ("bfloat16", 10)):
        eng = _engine(sv_model.latent_moe_tiny(dtype=dtype))
        latent = eng._scope.find_var("kv_cache.latent")
        assert latent.shape == (3 * 64, PS, words) \
            and latent.dtype == jnp.int32
        index = eng._scope.find_var("kv_cache.index")
        assert index.shape == (3 * 64, 8, PS) and index.dtype == dtype
        assert not eng._scope.has_var("kv_cache.k")
    # the served widths: 576 bfloat16 values are 288 words, kept in 384
    from paddle_tpu.serving import kv_cache
    (name, shape, dt), _ = kv_cache.stacked_pool_shapes(
        5, 4, 128, 576, 0, "bfloat16", 128, latent=True)
    assert (name, shape, dt) == ("kv_cache.latent", (20, 128, 384), "int32")


# -- the two forms of the attention ------------------------------------------


def _geometry(cfg):
    return latent_moe_ops.Geometry(**sv_model._latent_geometry(cfg))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_expanded_and_absorbed_agree_on_the_same_cache(dtype):
    """Both forms over the same cached rows, weights and mask: the same
    numbers (float32: to rounding order; bfloat16: to the rounding of the
    per-head products, which the forms make in another order)."""
    geom = _geometry(sv_model.latent_moe_tiny())
    rng = np.random.default_rng(5)
    B, S, T = 2, 6, 24
    nh, dn, dr, rkv = geom.num_heads, geom.nope_dim, geom.rope_dim, \
        geom.kv_rank
    q_nope = jnp.asarray(rng.standard_normal((B, S, nh, dn)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((B, S, nh, dr)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((B, T, rkv)), dtype)
    r = jnp.asarray(rng.standard_normal((B, T, dr)), dtype)
    wkv_b = jnp.asarray(rng.standard_normal((rkv, nh * (dn + geom.v_dim)))
                        * rkv ** -0.5, dtype)
    mask = jnp.asarray(rng.random((B, S, T)) < 0.4).at[:, :, 0].set(True)
    want = latent_moe_ops.expanded_attention_fn(q_nope, q_rope, c, r, mask,
                                                wkv_b, geom)
    # the cache rows as the pool keeps them, read back bit for bit
    rows = latent_moe_ops.join_latent_fn(c, r, dtype)
    assert rows.shape[-1] == (rkv + dr) * jnp.dtype(dtype).itemsize // 4
    c2, r2 = latent_moe_ops.split_latent_fn(rows, dtype, rkv, dr)
    assert jnp.array_equal(c2, c) and jnp.array_equal(r2, r)
    # a pool's wider row (padded to whole tiles) reads back the same
    wide = latent_moe_ops.join_latent_fn(c, r, dtype, 128)
    assert wide.shape[-1] == 128 and all(jnp.array_equal(a, b) for a, b in
        zip(latent_moe_ops.split_latent_fn(wide, dtype, rkv, dr), (c, r)))
    flat = lambda a: a.reshape((B * S,) + a.shape[2:])       # noqa: E731
    q_lat = latent_moe_ops.absorb_queries_fn(flat(q_nope), wkv_b, geom)
    u = latent_moe_ops.absorbed_attention_fn(
        q_lat, flat(q_rope), jnp.repeat(rows, S, axis=0), flat(mask), dtype,
        geom)
    got = latent_moe_ops.expand_values_fn(u, wkv_b, geom).reshape(want.shape)
    tol = 1e-5 if dtype == jnp.float32 else 0.06
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_a_window_behind_a_long_context_reads_each_querys_own_rows(
        monkeypatch):
    """A 70-token prompt runs its later chunks behind more context than the
    selection holds: those windows take the absorbed form over per-query
    gathers (`_window_rows`), the first chunk the expanded form, and what
    they serve equals the full forward."""
    calls = []
    rows, expanded = latent_moe_ops._window_rows, \
        latent_moe_ops.expanded_attention_fn
    monkeypatch.setattr(latent_moe_ops, "_window_rows", lambda *a, **k: (
        calls.append("rows"), rows(*a, **k))[1])
    monkeypatch.setattr(
        latent_moe_ops, "expanded_attention_fn", lambda *a, **k: (
            calls.append("expanded"), expanded(*a, **k))[1])
    eng = _engine(prefix_cache=False)
    prompt = _prompts(9, 70)[0]
    done, got = _served_logits(eng, prompt, 4)
    # a 70-token request's table is 16 pages = 128 slots > 8 kept: every
    # chunk selects; a 5-token one fits one page and attends it whole
    assert "rows" in calls
    np.testing.assert_allclose(got, _reference_logits(eng, prompt, done),
                               atol=TOL)
    del calls[:]
    short = _prompts(9, 5)[0]
    done, got = _served_logits(eng, short, 2)
    assert "expanded" in calls and "rows" not in calls
    np.testing.assert_allclose(got, _reference_logits(eng, short, done),
                               atol=TOL)


def test_interleaved_and_rotate_half_rotary_pair_their_lanes():
    inv = decoder_common.yarn_inv_freq_fn(4, 1e4, (40.0, 16, 32.0, 1.0, 1.0))
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(
        4, 1e4, (40.0, 16, 32.0, 1.0)))
    x = jnp.asarray([[[1.0, 0.0, 0.0, 0.0]]])        # one token, one head
    pos = jnp.asarray([3])
    pairs = latent_moe_ops.rotary_interleaved_fn(x, pos, inv)
    halves = decoder_common.rotary_fn(x, pos, inv, 4)
    a = 3 * inv[0]
    np.testing.assert_allclose(pairs[0, 0], [np.cos(a), np.sin(a), 0, 0],
                               atol=1e-6)
    np.testing.assert_allclose(halves[0, 0], [np.cos(a), 0, np.sin(a), 0],
                               atol=1e-6)
    assert latent_moe_ops.yarn_mscale(40.0, 1.0) == pytest.approx(
        0.1 * np.log(40.0) + 1.0)


# -- pages: sharing, copy-on-write, preemption -------------------------------


def test_prefix_hit_keeps_both_pools_rows_with_the_page():
    shared = _prompts(3, 32)[0]                    # four whole pages
    eng = _engine()
    prompts = [shared + tail for tail in _prompts(4, 9, 14)]
    first = _serve(eng, prompts[:1])
    second = _serve(eng, prompts[1:])
    assert eng.stats["prefix_hit_tokens"] == 32
    assert second[0].selection[0] == 32
    cold = _serve(_engine(prefix_cache=False), prompts[1:])
    assert second[0].out_tokens == cold[0].out_tokens
    _assert_right(eng, prompts[1:], second)
    # the hit's positions follow the selection of the request that made
    # them: handed on as `ahead`, it leaves no margin either
    ahead = first[0].selection[1][:32]
    g = _graded(eng, prompts[1:], second, ahead)[0]
    assert g["gap"] <= TOL and g["select_margin"] <= 1e-4


def test_full_hit_copies_the_page_on_write():
    prompt = _prompts(5, 32)[0]
    eng = _engine()
    _serve(eng, [prompt])
    again = _serve(eng, [prompt], new=8)
    assert eng.stats["prefix_full_hits"] == 1 and eng.stats["cow_copies"] >= 1
    cold = _serve(_engine(prefix_cache=False), [prompt], new=8)
    assert again[0].out_tokens == cold[0].out_tokens
    _assert_right(eng, [prompt], again)


@pytest.mark.parametrize("pool", ["kv_cache.index", "kv_cache.latent"])
def test_copy_on_write_moves_the_rows_of_both_pools(pool):
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
    _assert_right(eng, prompts, tight)


# -- planted faults: each must fail ------------------------------------------


def _newest_indices(scores, limit, k):
    kk = min(int(k), scores.shape[-1])
    at = limit[..., None] - 1 - jnp.arange(kk, dtype=jnp.int32)
    return jnp.where(at >= 0, at, -1)


def _newest_mask(scores, limit, k):
    at = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    return (at < limit[..., None]) & (at >= limit[..., None] - k)


def _no_group_limit(z, router_w, router_bias, k, groups, groups_kept,
                    scaling):
    return decoder_common.sigmoid_router_fn(z, router_w, router_bias, k,
                                            scaling)


def _bias_weighs(z, router_w, router_bias, k, groups, groups_kept, scaling):
    ids, cw = _RIGHT_ROUTER(z, router_w, router_bias, k, groups, groups_kept,
                            scaling)
    biased = jax.nn.sigmoid(jnp.dot(z, router_w, precision="highest")) \
        + router_bias
    chosen = jnp.take_along_axis(biased, ids, axis=-1)
    weights = scaling * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    expert = jnp.arange(cw.shape[-1], dtype=jnp.int32)
    return ids, jnp.sum(jnp.where(ids[:, :, None] == expert,
                                  weights[:, :, None], 0.0), axis=1)


_RIGHT_ROUTER = decoder_common.group_limited_router_fn
_FAULTS = {
    "the_newest_k": {"select_indices_fn": _newest_indices,
                     "select_mask_fn": _newest_mask},
    "scale_without_m2": {"softmax_scale": lambda geom: (
        geom.nope_dim + geom.rope_dim) ** -0.5},
    "group_limit_ignored": {"group_limited_router_fn": _no_group_limit},
    "bias_in_the_weights": {"group_limited_router_fn": _bias_weighs},
}


@pytest.mark.parametrize("fault", sorted(_FAULTS))
def test_a_wrong_mechanism_fails_the_check(fault, monkeypatch):
    """Each wrong mechanism, planted in the served programs, is caught: by
    the margin of the choice the engine hands back where the fault is a
    wrong CHOICE (a random model's logits barely tell), by the logits
    themselves where it is a wrong NUMBER."""
    for name, wrong in _FAULTS[fault].items():
        monkeypatch.setattr(latent_moe_ops, name, wrong)
    eng = _engine()
    prompt = _prompts(8, 40)[0]
    done, got = _served_logits(eng, prompt, 8)
    graded = _graded(eng, [prompt], [done])[0]
    off = np.abs(got - _reference_logits(eng, prompt, done)).max()
    if fault == "the_newest_k":
        assert min(graded["select_margin_by_layer"]) > 1.0, graded
        assert graded["gap"] <= TOL      # followed, it reproduces itself
    elif fault == "group_limit_ignored":
        assert graded["route_margin"] > 0.05, graded
    else:
        assert off > 50 * TOL, (off, graded)


# -- the expert share --------------------------------------------------------


def test_group_limited_router_keeps_the_best_groups_and_weighs_without_bias():
    z = jnp.eye(2, 3, dtype=jnp.float32)
    # 8 experts in 4 groups of 2; token 0's logits: group 1 holds the best
    # single expert, but groups 0 and 3 the best PAIRS
    logits = np.asarray([[1.0, 0.9, 2.0, -4.0, -1.0, -1.0, 0.8, 0.7],
                         [0.0] * 8, [0.0] * 8], np.float32)
    bias = np.zeros(8, np.float32)
    ids, cw = decoder_common.group_limited_router_fn(
        z, jnp.asarray(logits), jnp.asarray(bias), 3, 4, 2, 2.5)
    assert sorted(ids[0].tolist()) == [0, 1, 6]      # not expert 2
    s = 1 / (1 + np.exp(-logits[0]))
    np.testing.assert_allclose(cw[0, [0, 1, 6]],
                               2.5 * s[[0, 1, 6]] / s[[0, 1, 6]].sum(),
                               rtol=1e-6)
    assert float(cw[0].sum()) == pytest.approx(2.5)
    # ties go to the lower group and the lower expert
    assert ids[1].tolist() == [0, 1, 2]
    # a bias moves the choice and not the weight
    bias[2] = 5.0
    ids, cw2 = decoder_common.group_limited_router_fn(
        z, jnp.asarray(logits), jnp.asarray(bias), 3, 4, 2, 2.5)
    assert 2 in ids[0].tolist() and float(cw2[0, 2]) < 2.5 * s[2] + 1e-6


def test_the_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: the routed part of every share of the experts (this
    engine's layer run once a share, given that share's experts and the
    router seen from that share) plus the shared expert counted once is the
    uncut layer, as the reference computes it with every expert held."""
    cfg = sv_model.latent_moe_tiny()
    geom = _geometry(cfg)
    H, F, E, held = cfg.hidden_size, cfg.ffn_size, cfg.num_experts, \
        cfg.experts_held
    rng = np.random.default_rng(11)
    draw = lambda *shape: jnp.asarray(                       # noqa: E731
        rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.float32)
    h = jnp.asarray(rng.standard_normal((1, 12, H)), jnp.float32)
    p = {"ffn_norm": jnp.ones((H,), jnp.float32),
         "router_w": 2.0 * draw(H, E),
         "router_bias": jnp.asarray(rng.standard_normal(E) * 0.02,
                                    jnp.float32),
         "shared_gate": draw(H, F), "shared_up": draw(H, F),
         "shared_down": draw(F, H)}
    experts = (draw(1, E, H, F), draw(1, E, H, F), draw(1, E, F, H))
    u = np.asarray(decoder_common.rms_norm_fn(h, p["ffn_norm"],
                                              geom.eps)).reshape(-1, H)
    shared = np.asarray(decoder_common.swiglu_fn(
        jnp.asarray(u), p["shared_gate"], p["shared_up"], p["shared_down"]))
    total = np.zeros_like(u)
    pairs = 0
    for share in range(E // held):
        # the share's experts first: whole groups move, so the router's
        # choice is the same seen from any share
        order = np.roll(np.arange(E), -share * held)
        mine = dict(p, router_w=p["router_w"][:, order],
                    router_bias=p["router_bias"][order])
        y, ids = latent_moe_ops._feed_forward(
            h, False, mine, tuple(w[:, order[:held]] for w in experts), 0,
            geom, "decode")
        routed = np.asarray(y - h).reshape(-1, H) - shared
        assert np.abs(routed).max() > 0
        total += routed
        pairs += int((np.asarray(ids) < held).sum())
    assert pairs == 12 * cfg.experts_per_token   # every pair on one share
    # the uncut layer by the reference: every expert held
    sz = ref.Sizes(sv_model.latent_moe_tiny(experts_held=0))
    with jax.default_matmul_precision("highest"):
        follow, weights, _ = ref._router(
            jnp.asarray(u), p["router_w"], p["router_bias"],
            jnp.full((12, cfg.experts_per_token), -1, jnp.int32), sz=sz)
        want = np.array(shared)
        for t in range(12):
            for e, w in zip(np.asarray(follow)[t], np.asarray(weights)[t]):
                z = jnp.asarray(u[t])
                want[t] += w * np.asarray(
                    (jax.nn.silu(z @ experts[0][0, e])
                     * (z @ experts[1][0, e])) @ experts[2][0, e])
    np.testing.assert_allclose(total + shared, want, atol=2e-5)


# -- the expert kernel at this width -----------------------------------------


def test_f_tile_keeps_every_tile_it_gave_and_fits_hidden_7168():
    pme = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.moe_experts")
    # the three expert shapes the benchmark's configurations had: zaya1_8b,
    # keye_vl2_30b_a3b, laguna_xs2 (hidden 2048 each)
    for ffn, tile in ((2048, 512), (768, 384), (512, 512)):
        assert pme._f_tile(ffn) == tile
        assert pme._f_tile(ffn, 2048, 2) == tile
    assert pme.experts_supported((64, 2048), (24, 16, 2048, 2048),
                                 jnp.bfloat16)
    assert pme.experts_supported((64, 2048), (6, 128, 2048, 768),
                                 jnp.bfloat16)
    assert pme.experts_supported((64, 2048), (4, 256, 2048, 512),
                                 jnp.bfloat16)
    # this configuration's: three slabs of 7168 x 512 bfloat16 are 22 MB
    assert pme._f_tile(2048, 7168, 2) == 256
    assert pme.experts_supported((128, 7168), (4, 16, 7168, 2048),
                                 jnp.bfloat16)
    assert pme.experts_supported((512, 7168), (4, 16, 7168, 2048),
                                 jnp.bfloat16)
    # no tile fits a width that only 512 divides at a hidden size past it
    assert not pme.experts_supported((8, 16384), (1, 2, 16384, 512),
                                     jnp.float32)


def test_moe_experts_pallas_at_the_narrow_tile(monkeypatch):
    """The kernel, interpreted, at a shape whose wide tile does not fit
    (three float32 slabs of 1408 x 512 are 8.65 MB): two tiles of 256
    columns an expert, against the plain sum."""
    pme = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.moe_experts")
    monkeypatch.setattr(pme, "INTERPRET", True)
    L, E, H, F, T = 2, 3, 1408, 512, 10
    assert pme._f_tile(F, H, 4) == 256 and pme._f_tile(F) == 512
    rng = np.random.default_rng(21)
    z = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((L, E, H, F)) * H ** -0.5,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((L, E, F, H)) * F ** -0.5,
                     jnp.float32)
    cw = np.zeros((T, E), np.float32)
    for t in range(T):
        cw[t, rng.choice(E, 2, replace=False)] = rng.dirichlet(np.ones(2))
    assert pme.experts_supported(z.shape, wg.shape, jnp.float32)
    got = pme.moe_topk_experts(z, jnp.asarray(cw), wg, wu, wd, 1)
    want = pme._reference(z, jnp.asarray(cw), wg, wu, wd, 1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -- the configuration -------------------------------------------------------


def test_the_paged_indexer_kernel_serves_what_the_gather_served(monkeypatch):
    """The "latent_moe" decode step at a geometry `paged_indexer_supported`
    takes (an indexer of 8 heads of 128 keeping 64 positions, bfloat16 keys
    in 128-token pages), rows behind three pages of context: with the
    scores computed by the paged kernel (interpreter) the engine serves the
    tokens and hands back the selections the gathered form
    served, `serving.sparse.kernel_layer_steps` equal to
    `serving.sparse.layer_steps`; on the XLA arm and at the rehearsal
    geometry it stays 0."""
    from paddle_tpu.ops.pallas_kernels import paged_indexer

    cfg = sv_model.latent_moe_tiny(
        dtype="bfloat16", index_heads=8, index_head_dim=128, index_topk=64,
        prefill_chunk=128, max_position=1024)
    prompts = _prompts(43, 290, 260)

    def served():
        eng = _engine(cfg, page_size=128, pool_pages=16)
        return eng, _serve(eng, prompts, new=5)

    eng, was = served()
    assert eng._scope.find_var("kv_cache.index").shape == (3 * 16, 128, 128)
    assert eng.stats["sparse.layer_steps"] > 0
    assert eng.stats["sparse.kernel_layer_steps"] == 0
    monkeypatch.setattr(paged_indexer, "INTERPRET", True)
    eng, now = served()
    assert eng.stats["sparse.kernel_layer_steps"] \
        == eng.stats["sparse.layer_steps"] > 0
    for a, b in zip(now, was):
        assert a.out_tokens == b.out_tokens
        assert a.selection[0] == b.selection[0]
        assert np.array_equal(a.selection[1], b.selection[1])
    eng = _engine()
    _serve(eng, _prompts(31, 40), new=4)
    assert eng.stats["sparse.layer_steps"] > 0
    assert eng.stats["sparse.kernel_layer_steps"] == 0


def test_the_latent_attend_kernel_serves_what_the_jnp_form_served(
        monkeypatch):
    """The "latent_moe" engine at a geometry `latent_attend_supported`
    takes (8 heads over a bfloat16 latent of 256 and 8 rotary lanes in rows
    of 256 words, an indexer keeping 128 positions), prompts of 150-210
    tokens in windows of 64: every window past the second runs behind a
    context longer than the selection (`_window_rows`), every decode row
    attends a selection of 128. With the absorbed attention computed by the
    Pallas kernel (interpreter) the engine serves the tokens the jnp form
    served, and
    `serving.latent.attend_kernel_layer_steps` equals
    `serving.sparse.layer_steps`; on the jnp arm and at the rehearsal
    geometry it stays 0."""
    from paddle_tpu.ops.pallas_kernels import latent_attend

    cfg = sv_model.latent_moe_tiny(
        dtype="bfloat16", num_heads=8, kv_lora_rank=256, rope_head_dim=8,
        index_topk=128, prefill_chunk=64, max_position=512)
    prompts = _prompts(47, 210, 150)
    calls = []
    kernel = latent_attend.latent_rows_attention

    def counted(q_lat, q_rope, rows, *rest):
        calls.append((q_lat.shape[0], rows.shape[1]))
        return kernel(q_lat, q_rope, rows, *rest)

    monkeypatch.setattr(latent_attend, "latent_rows_attention", counted)

    def served():
        eng = _engine(cfg, pool_pages=96)
        return eng, _serve(eng, prompts, new=5)

    eng, was = served()
    assert eng._scope.find_var("kv_cache.latent").shape == (3 * 96, PS, 256)
    assert eng.stats["sparse.layer_steps"] > 0 and not calls
    assert eng.stats["latent.attend_kernel_layer_steps"] == 0
    booked = obs.snapshot()["counters"].get(
        "serving.latent.attend_kernel_layer_steps", 0)
    monkeypatch.setattr(latent_attend, "INTERPRET", True)
    eng, now = served()
    # traced for a window's 64 queries and for decode rows, all over 128
    assert {k for _, k in calls} == {128} and 64 in {r for r, _ in calls} \
        and len({r for r, _ in calls}) > 1
    assert eng.stats["latent.attend_kernel_layer_steps"] \
        == eng.stats["sparse.layer_steps"] > 0
    assert obs.snapshot()["counters"][
        "serving.latent.attend_kernel_layer_steps"] - booked \
        == eng.stats["sparse.layer_steps"]
    for a, b in zip(now, was):
        assert a.out_tokens == b.out_tokens
        # a later layer selects on what the attention before it gave: a
        # sum in another order may turn a tie between two positions
        assert a.selection[0] == b.selection[0]
        assert np.mean(a.selection[1] == b.selection[1]) > 0.99
    eng = _engine()
    _serve(eng, _prompts(31, 40), new=4)
    assert eng.stats["sparse.layer_steps"] > 0
    assert eng.stats["latent.attend_kernel_layer_steps"] == 0


def test_block_field_and_refusals():
    cfg = sv_model.latent_moe_tiny()
    assert cfg.block == "latent_moe" and cfg.scanned and cfg.selects \
        and cfg.latent and not cfg.windowed and not cfg.recurrent
    assert cfg.routed_layers == 2 and cfg.held_experts == 8
    assert cfg.selects_within(9) and not cfg.selects_within(8)
    assert sv_model.latent_moe_tiny(experts_held=0).held_experts == 16
    for bad in (dict(kv_lora_rank=0), dict(rope_head_dim=3),
                dict(dense_layers=0), dict(dense_layers=3),
                dict(expert_groups=3), dict(groups_per_token=5),
                dict(experts_held=17), dict(index_head_dim=2),
                dict(yarn=(40.0, 16)), dict(prefill_chunk=0)):
        with pytest.raises(ValueError):
            sv_model.latent_moe_tiny(**bad)
    with pytest.raises(NotImplementedError):
        _engine(draft_k=2)
    assert not DecoderConfig().latent
