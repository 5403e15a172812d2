"""The "hybrid_moe" block family (window and full attention layers of
different head counts over two page pools, a gated attention output, a
sigmoid-routed mixture of experts beside a shared expert behind one dense
layer) through ServingEngine, on the CPU at toy size with seeded float32
weights, against the plain reference `benchmark/reference/laguna_lm.py`
(which imports nothing from paddle_tpu). The window is 8 positions, pages
hold 4 tokens and a prompt runs in chunks of 8, so a row's compact table in
the sliding layers' pool is 3 pages wide at every context."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna_lm
from paddle_tpu import observability as obs
from paddle_tpu import unique_name
from paddle_tpu.executor import Executor, Scope
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.ops import attention_ops, decoder_common, hybrid_moe_ops
from paddle_tpu.serving import DecoderConfig, ServingEngine, kv_cache
from paddle_tpu.serving import model as sv_model
from serving_helpers import preempting

PS, W = 4, 8
TOL = 1e-4          # float32 on both sides: rounding order only
LIVE = -(-W // PS) + 1      # window pages a decoding row can hold


def _engine(cfg=None, **kw):
    kw.setdefault("page_size", PS)
    kw.setdefault("pool_pages", 96)
    kw.setdefault("max_inflight", 4)
    kw.setdefault("seed", 3)
    kw.setdefault("prefix_cache", True)
    return ServingEngine(cfg or sv_model.hybrid_moe_tiny(), **kw)


def _prompts(seed, *lengths, shared=()):
    rng = np.random.default_rng(seed)
    return [list(shared) + rng.integers(1, 97, n).tolist() for n in lengths]


def _serve(eng, prompts, new=6):
    rids = [eng.submit(p, new) for p in prompts]
    eng.run_until_drained()
    out = [eng.requests[r] for r in rids]
    assert all(r.state == "finished" for r in out)
    assert eng.audit_pool() == ([], []) and eng.leaked_pages() == 0
    return out


def _graded(eng, prompts, done, round_to=None):
    params = laguna_lm.read_params(eng._scope.find_var, eng.cfg, round_to)
    return laguna_lm.check_sequences(
        params, [(p, r.out_tokens, r.routes) for p, r in zip(prompts, done)],
        eng.cfg)


def _assert_right(eng, prompts, done, gap=TOL, margin=1e-4):
    cfg = eng.cfg
    for r, g in zip(done, _graded(eng, prompts, done)):
        assert r.routes.shape == (r.cache_len, cfg.routed_layers,
                                  cfg.experts_per_token)
        assert g["gap"] <= gap and g["route_margin"] <= margin, g


def _full_forward(cfg, tok, seed=7):
    prog, startup = Program(), Program()
    startup.random_seed = seed
    with program_guard(prog, startup), unique_name.guard():
        io = sv_model.build_full_forward_program(cfg)
    exe, scope = Executor(), Scope()
    exe.run(startup, scope=scope)
    pos = np.arange(tok.shape[1], dtype=np.int32)[None, :]
    logits, routes = exe.run(
        prog, feed={sv_model.TOK_FEED: tok, sv_model.POS_FEED: pos},
        fetch_list=[io["logits"], io["routes"]], scope=scope)
    return scope, logits, routes


def test_full_forward_matches_reference():
    cfg = sv_model.hybrid_moe_tiny()
    tok = np.asarray(_prompts(0, 40), np.int32)
    scope, logits, routes = _full_forward(cfg, tok)
    params = laguna_lm.read_params(scope.find_var, cfg)
    x, margin = laguna_lm.forward(params, tok[0], cfg)
    want = np.asarray(x) @ np.asarray(params["lm_head"], np.float32)
    np.testing.assert_allclose(logits[0], want, atol=TOL)
    # the program's experts, followed, leave no margin: every route equal
    x2, margin = laguna_lm.forward(params, tok[0], cfg, routes[0])
    np.testing.assert_allclose(np.asarray(x2), np.asarray(x), atol=TOL)
    assert routes.shape == (1, 40, 8, 2) and margin.max() <= 1e-5
    assert len(np.unique(routes)) == cfg.num_experts


@pytest.mark.parametrize("length,new", [(3, 4), (5, 14), (21, 6), (50, 9)],
                         ids=["under_a_page", "across_the_window",
                              "three_chunks", "seven_chunks"])
def test_chunked_prefill_then_decode_matches_reference(length, new):
    eng = _engine()
    prompts = _prompts(1, length)
    done = _serve(eng, prompts, new=new)
    assert eng.stats["prefill.chunks"] == -(-length // 8)
    _assert_right(eng, prompts, done)


def test_batched_requests_of_different_lengths():
    eng = _engine()
    prompts = _prompts(2, 3, 30, 17, 50)
    done = _serve(eng, prompts, new=8)
    st = eng.stats
    assert st["decode_signatures"] and st["kv.window_pages_released"] > 0
    # what each kind of layer attended, as the engine counts it: a sliding
    # layer never more than the window
    assert st["attn.full_layer_steps"] * 2 == st["attn.window_layer_steps"]
    assert 0 < st["attn.window_context_tokens"] \
        < 2 * st["attn.full_context_tokens"]
    assert 0 < st["kv.window_row_pages"] < st["kv.global_row_pages"]
    assert st["moe.layer_steps"] == st["decode_steps"] * 8
    _assert_right(eng, prompts, done)


def test_a_prompt_in_chunks_equals_the_prompt_in_one_window():
    prompts = _prompts(3, 37)
    chunked = _engine()
    whole = _engine(sv_model.hybrid_moe_tiny(prefill_chunk=64))
    a = _serve(chunked, prompts, new=5)[0]
    b = _serve(whole, prompts, new=5)[0]
    assert chunked.stats["prefill.chunks"] == 5 \
        and whole.stats["prefill.chunks"] == 1
    assert a.out_tokens == b.out_tokens
    np.testing.assert_array_equal(a.routes, b.routes)


def test_bfloat16_engine_stays_inside_the_bfloat16_tolerances():
    """The form of the on-chip tolerances: the bfloat16 engine against the
    float32 reference passes limits that the same run against float8
    weights does not."""
    eng = _engine(sv_model.hybrid_moe_tiny(dtype="bfloat16"))
    prompts = _prompts(4, 30, 45)
    done = _serve(eng, prompts, new=8)
    for g in _graded(eng, prompts, done):
        assert g["gap"] <= 0.1 and g["route_margin"] <= 0.05, g
    low = _graded(eng, prompts, done, round_to="float8_e4m3fn")
    assert max(g["gap"] for g in low) > 0.1 \
        or max(g["route_margin"] for g in low) > 0.05, low


def test_prefix_hit_at_a_page_boundary_equals_a_cold_prefill():
    shared = _prompts(5, 24)[0]                 # six whole pages
    prompts = _prompts(6, 5, 9, shared=shared)
    cold = _serve(_engine(prefix_cache=False), prompts, new=6)
    eng = _engine()
    first = _serve(eng, prompts[:1], new=6)
    second = _serve(eng, prompts[1:], new=6)
    # the second resumed behind the six shared pages: their full-layer
    # pages mapped, and the two window pages that cover the 7 positions
    # before position 24
    assert eng.stats["prefix_hit_tokens"] == 24
    assert [r.out_tokens for r in first + second] \
        == [r.out_tokens for r in cold]
    _assert_right(eng, prompts, first + second)
    for r, c in zip(first + second, cold):
        np.testing.assert_array_equal(r.routes, c.routes)


def test_a_lookup_whose_window_tail_was_given_up_falls_back():
    shared = _prompts(7, 24)[0]
    prompts = _prompts(8, 5, 9, 6, shared=shared)
    cold = _serve(_engine(prefix_cache=False), prompts, new=4)
    eng = _engine()
    done = _serve(eng, prompts[:1], new=4)
    cache = eng.prefix_cache
    held = [n for n in cache._nodes.values() if n.wpage is not None]
    assert len(held) == 7           # every whole page of the first prompt
    # the cache gives up the window page of block 5 (positions 20..23)
    chain = sorted(cache._nodes.values(), key=lambda n: n.nid)
    eng.window_pool.release([chain[5].wpage])
    chain[5].wpage = None
    # six pages match, but positions 17..23 are not all held: the longest
    # prefix that can be resumed is five pages (its tail 3, 4 is held)
    done += _serve(eng, prompts[1:2], new=4)
    assert eng.stats["prefix_hit_tokens"] == 20
    # the request that recomputed block 5 gave the cache its window page
    assert chain[5].wpage is not None
    done += _serve(eng, prompts[2:], new=4)
    assert eng.stats["prefix_hit_tokens"] == 20 + 24
    assert [r.out_tokens for r in done] == [r.out_tokens for r in cold]
    _assert_right(eng, prompts, done)
    # with no window page at all nothing can be resumed
    for n in cache._nodes.values():
        if n.wpage is not None:
            eng.window_pool.release([n.wpage])
            n.wpage = None
    assert cache.match_resumable(prompts[0], LIVE - 1) == ([], 0, [])


def test_window_pool_pressure_takes_the_caches_window_pages_first():
    eng = _engine(window_pool_pages=12)
    prompts = _prompts(9, 30, 26, 22, 18)
    done = _serve(eng, prompts, new=10)
    assert eng.prefix_cache.stripped_window_pages > 0
    assert eng.stats["preemptions"] == 0
    _assert_right(eng, prompts, done)


def test_the_sliding_pool_holds_a_bounded_window_of_a_long_generation():
    eng = _engine(sv_model.hybrid_moe_tiny(max_position=256),
                  prefix_cache=False)
    prompts = _prompts(10, 6)
    rid = eng.submit(prompts[0], 10 * W)
    req, most = eng.requests[rid], 0
    while eng.has_work():
        eng.step()
        assert eng.audit_pool() == ([], [])
        most = max(most, len(req.wpages))
        if req.state == "running":
            # the pages that intersect [pos - (W - 1), pos], no other
            first = max(0, req.cache_len - (W - 1)) // PS
            assert req.wfirst >= first - 1 and len(req.wpages) <= LIVE + 1
    assert req.state == "finished" and len(req.out_tokens) == 10 * W
    assert most <= LIVE + 1 and eng.window_pool.pages_in_use == 0
    assert eng.stats["kv.window_pages_released"] >= 10 * W // PS - LIVE
    assert eng.leaked_pages() == 0
    _assert_right(eng, prompts, [req])


def test_reference_shares_the_forward_of_a_common_prefix(monkeypatch):
    """Sequences whose first whole multiple of `_LONG` positions agree
    (tokens and experts) are graded with that part computed once, and read
    what a forward of their own reads."""
    shared = _prompts(19, 32)[0]
    prompts = _prompts(20, 9, 5, 13, shared=shared) + _prompts(21, 38)
    eng = _engine()
    done = _serve(eng, prompts, new=7)
    whole = _graded(eng, prompts, done)
    monkeypatch.setattr(laguna_lm, "_LONG", 16)
    monkeypatch.setattr(laguna_lm, "_QUERY_BLOCK", 8)
    calls = []
    forward = laguna_lm.forward
    monkeypatch.setattr(laguna_lm, "forward", lambda *a, **kw: (
        calls.append(kw.get("keep", 0)), forward(*a, **kw))[1])
    split = _graded(eng, prompts, done)
    # two prefixes of 32 positions (the shared one once), four sequences
    assert sorted(calls) == [0, 0, 0, 0, 32, 32]
    for a, b in zip(whole, split):
        assert abs(a["gap"] - b["gap"]) <= 1e-4 and b["gap"] <= TOL
        assert b["route_margin"] <= 1e-4


def test_full_hit_copies_the_page_of_both_pools_on_write():
    prompts = _prompts(11, 16)                  # four whole pages
    eng = _engine()
    first = _serve(eng, prompts, new=5)
    again = _serve(eng, prompts, new=5)
    assert eng.stats["prefix_full_hits"] == 1 and eng.stats["cow_copies"] >= 1
    assert again[0].out_tokens == first[0].out_tokens
    np.testing.assert_array_equal(again[0].routes, first[0].routes)
    _assert_right(eng, prompts * 2, first + again)


def test_preemption_and_resume():
    prompts = _prompts(12, 18, 14, 16)
    calm = _serve(_engine(), prompts, new=12)
    # pools this small hold the later rows in the queue, and the youngest
    # of those that run is preempted by hand
    eng = _engine(pool_pages=18, window_pool_pages=10, prefix_cache=False)
    with preempting(eng):
        done = _serve(eng, prompts, new=12)
    assert eng.stats["preemptions"] > 0
    assert [r.out_tokens for r in done] == [r.out_tokens for r in calm]
    _assert_right(eng, prompts, done)


# -- planted faults: a wrong mechanism fails the check by one of its limits --


def _ignore_the_window(monkeypatch):
    band = hybrid_moe_ops.band_attention_fn
    paged = hybrid_moe_ops.paged_decode_attention_fn
    monkeypatch.setattr(
        hybrid_moe_ops, "band_attention_fn",
        lambda q, k, v, q0, k0, window, scale:
        band(q, k, v, q0, k0, 1 << 20, scale))
    monkeypatch.setattr(
        hybrid_moe_ops, "paged_decode_attention_fn",
        lambda *a, first_live=None, **kw: paged(*a, **kw))


def _window_a_full_layer(monkeypatch):
    def causal(q, k, v, q0, scale):
        zero = jnp.zeros_like(q0)
        return hybrid_moe_ops.band_attention_fn(q, k, v, q0, zero, W, scale)

    paged = hybrid_moe_ops.paged_decode_attention_fn
    monkeypatch.setattr(hybrid_moe_ops, "causal_attention_fn", causal)
    monkeypatch.setattr(
        hybrid_moe_ops, "paged_decode_attention_fn",
        lambda q, kp, vp, t, lens, first_live=None, **kw: paged(
            q, kp, vp, t, lens, first_live=jnp.maximum(lens - W, 0)
            if first_live is None else first_live, **kw))


def _leave_out_the_gate(monkeypatch):
    pre = hybrid_moe_ops._pre_attention

    def ungated(*a, **kw):
        q, k, v, gate = pre(*a, **kw)
        return q, k, v, jnp.ones_like(gate)

    monkeypatch.setattr(hybrid_moe_ops, "_pre_attention", ungated)


def _leave_out_the_shared_expert(monkeypatch):
    swiglu = hybrid_moe_ops.swiglu_fn
    monkeypatch.setattr(
        hybrid_moe_ops, "swiglu_fn",
        lambda z, g, u, d: swiglu(z, g, u, d) * (g.shape[-1] != 16))


def _leave_out_the_scaling(monkeypatch):
    router = hybrid_moe_ops.sigmoid_router_fn
    monkeypatch.setattr(
        hybrid_moe_ops, "sigmoid_router_fn",
        lambda z, w, b, k, scaling: router(z, w, b, k, 1.0))


@pytest.mark.parametrize("fault", [
    _ignore_the_window, _window_a_full_layer, _leave_out_the_gate,
    _leave_out_the_shared_expert, _leave_out_the_scaling],
    ids=lambda f: f.__name__.strip("_"))
def test_a_wrong_mechanism_fails_the_check(fault, monkeypatch):
    fault(monkeypatch)
    eng = _engine()
    prompts = _prompts(13, 30, 41)
    done = _serve(eng, prompts, new=8)
    graded = _graded(eng, prompts, done)
    assert max(g["gap"] for g in graded) > 0.01 \
        or max(g["route_margin"] for g in graded) > 0.01, graded


# -- the mechanisms ---------------------------------------------------------


def _dense_band(q, k, v, window, sm_scale):
    """The masked dense form of a sliding layer's attention."""
    S, T = q.shape[1], k.shape[1]
    qp, kp = jnp.arange(S)[:, None], jnp.arange(T)[None, :]
    mask = (kp <= qp) & (qp - kp < window)
    nh, nkv = q.shape[2], k.shape[2]
    kr, vr = (jnp.repeat(a, nh // nkv, axis=2) for a in (k, v))
    s = jnp.einsum("bshd,bthd->bhst", q, kr) * sm_scale
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e9), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, vr)


def test_band_attention_equals_the_masked_dense_form_and_holds_no_dense_product():
    rng = np.random.default_rng(14)
    S, window, nh, nkv, dh = 512, 32, 4, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((1, S, h, dh)), jnp.float32)
               for h in (nh, nkv, nkv))
    zero = jnp.zeros((1,), jnp.int32)
    band = lambda q, k, v: hybrid_moe_ops.band_attention_fn(    # noqa: E731
        q, k, v, zero, zero, window, dh ** -0.5)
    np.testing.assert_allclose(band(q, k, v),
                               _dense_band(q, k, v, window, dh ** -0.5),
                               atol=2e-5)
    # no product of the traced program spans the context: a query block of
    # 128 meets the 128 + 31 keys of its band
    sizes = {d for eqn in _dots(jax.make_jaxpr(band)(q, k, v).jaxpr)
             for var in eqn.invars + eqn.outvars for d in var.aval.shape}
    assert 128 + window - 1 in sizes and max(sizes) < S


def _dots(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dots(sub)


def test_a_windows_sliding_layers_multiply_with_their_band_only():
    """The window program at a long context: every product of a sliding
    layer is against its compact table (a window and a chunk of keys), only
    a full layer's spans the row's whole page table."""
    cfg = sv_model.hybrid_moe_tiny(max_position=1024)
    geom = hybrid_moe_ops.Geometry(**sv_model._hybrid_geometry(cfg))
    specs = sv_model._hybrid_param_specs(cfg)
    w = {k: jnp.zeros(shape, dtype) for k, (shape, dtype, _) in specs.items()}
    pages, wpages, P = 300, 12, 200             # 800 slots of context
    pools = [jnp.zeros(shape, dtype) for geometry in
             sv_model.hybrid_pool_geometry(cfg, pages, PS, wpages)
             for _, shape, dtype in
             kv_cache.stacked_pool_shapes(*geometry)]
    wt = sv_model.window_table_pages(cfg, PS, 8)

    def run(tok, pools):
        ops = hybrid_moe_ops
        return ops.hybrid_moe_stack_fn(
            "window", tok, tok, w["dec.word_emb"], w["dec.lm_head"],
            w["dec.final_norm.scale"],
            {k: w[k] for k in ops.LAYER_PARAMS},
            {kind: {k: w[f"{kind}.{k}"] for k in ops.ATTENTION_PARAMS}
             for kind in (ops.FULL, ops.SLIDE)},
            {k: w["dense." + k] for k in ops.DENSE_PARAMS},
            {k: w["moe." + k] for k in ops.MOE_PARAMS},
            tuple(w[k] for k in ops.EXPERT_PARAMS),
            sv_model.layer_plan(cfg), geom, pools=tuple(pools),
            page_table=jnp.zeros((1, P), jnp.int32),
            window_table=jnp.zeros((1, wt), jnp.int32),
            window_base=jnp.zeros((1,), jnp.int32),
            lens=jnp.full((1,), 8, jnp.int32),
            start=jnp.full((1,), 700, jnp.int32), num_pages=pages,
            window_pages=wpages)["logits"]

    jaxpr = jax.make_jaxpr(run)(jnp.zeros((1, 8), jnp.int32), pools).jaxpr
    context = [eqn for eqn in _dots(jaxpr)
               if P * PS in eqn.outvars[0].aval.shape
               or P * PS in eqn.invars[1].aval.shape]
    # scores and weighted values of the three full layers, nothing else
    assert len(context) == 2 * 3
    assert all(eqn.invars[0].aval.shape[2] in (2, 4) for eqn in context)


def test_the_forty_layer_plan_builds():
    period = ("full_attention",) + ("sliding_attention",) * 3
    cfg = sv_model.hybrid_moe_tiny(
        num_layers=40, layer_types=period * 10,
        mlp_layer_types=("dense",) + ("sparse",) * 39,
        heads_per_layer=(4, 6, 6, 6) * 10, num_heads=4)
    plan = sv_model.layer_plan(cfg)
    kinds = [a for a, _, _, _ in plan]
    assert kinds.count("full") == 10 and kinds.count("slide") == 30
    assert [f for _, _, f, _ in plan].count("dense") == 1
    assert cfg.routed_layers == 39 and plan[39] == ("slide", 29, "moe", 38)
    tok = np.asarray(_prompts(15, 12), np.int32)
    scope, logits, routes = _full_forward(cfg, tok)
    assert scope.find_var("dec.layers.slide.wq").shape == (30, 32, 48)
    assert scope.find_var("dec.layers.full.wq").shape == (10, 32, 32)
    assert scope.find_var("dec.layers.w_gate").shape == (39, 8, 32, 16)
    assert routes.shape == (1, 12, 39, 2) and np.isfinite(logits).all()
    params = laguna_lm.read_params(scope.find_var, cfg)
    x, _ = laguna_lm.forward(params, tok[0], cfg, routes[0])
    want = np.asarray(x) @ np.asarray(params["lm_head"], np.float32)
    np.testing.assert_allclose(logits[0], want, atol=5e-4)


def test_lists_that_name_no_plan_are_refused():
    with pytest.raises(ValueError, match="num_layers"):
        sv_model.hybrid_moe_tiny(num_layers=8)
    with pytest.raises(ValueError, match="head"):
        sv_model.hybrid_moe_tiny(heads_per_layer=(4,) + (6, 6, 5, 4) * 2)
    with pytest.raises(ValueError, match="every kind"):
        sv_model.hybrid_moe_tiny(mlp_layer_types=("sparse",) * 9)
    with pytest.raises(NotImplementedError):
        ServingEngine(sv_model.hybrid_moe_tiny(), page_size=PS,
                      pool_pages=16, draft_k=2)
    assert DecoderConfig().windowed is False


def test_yarn_inverse_frequencies_as_published():
    """Laguna-XS.2's full layers: 64 rotary lanes at theta 500,000 under
    factor 64 from 4,096: pairs 0-5 keep their frequency, 16-31 turn 64
    times slower, a linear ramp between; the op and the reference, each
    written out on its own, agree."""
    yarn = (64.0, 4096, 64.0, 1.0, 1.4158883083359672)
    inv = decoder_common.yarn_inv_freq_fn(64, 5e5, yarn)
    own = 5e5 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:6], own[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], own[16:] / 64, rtol=1e-6)
    r = (10 - 5) / (16 - 5)
    np.testing.assert_allclose(inv[10], own[10] * ((1 - r) + r / 64),
                               rtol=1e-6)
    np.testing.assert_allclose(
        inv, laguna_lm.inverse_frequencies(64, 5e5, yarn), rtol=1e-6)
    np.testing.assert_allclose(
        decoder_common.yarn_inv_freq_fn(128, 1e4),
        laguna_lm.inverse_frequencies(128, 1e4), rtol=1e-6)


def test_sigmoid_router_weighs_without_the_bias_and_breaks_ties_low():
    z = jnp.eye(4, dtype=jnp.float32)
    w = jnp.asarray([[2., 2., 0., -1.], [0., 1., 1., 1.],
                     [1., 0., 0., 3.], [0., 0., 0., 0.]], jnp.float32)
    bias = jnp.asarray([0., 0., 0.05, 0.], jnp.float32)
    ids, cw = decoder_common.sigmoid_router_fn(z, w, bias, 2, 2.5)
    s = np.asarray(jax.nn.sigmoid(w))
    # row 0: a tie of experts 0 and 1; row 1: the bias lifts expert 2 over
    # its equals; row 3: every score equal, the two lowest indices but for
    # the biased one
    assert ids.tolist() == [[0, 1], [2, 1], [3, 0], [2, 0]]
    np.testing.assert_allclose(np.asarray(cw).sum(axis=1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(cw[2, 3], 2.5 * s[2, 3] / (s[2, 3] + s[2, 0]),
                               rtol=1e-6)
    assert cw[1, 2] == cw[1, 1]      # weighed by the score, not score + bias


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lens,first", [
    ((700, 513, 130, 0), (189, 2, 0, 0)),
    ((640, 512, 1, 300), (129, 1, 0, 290))],
    ids=["ragged", "page_edges"])
def test_paged_window_attention_pallas_matches_xla(dtype, lens, first,
                                                   monkeypatch):
    """The grouped-query arm with a first live slot, in the interpreter,
    against the XLA path: slots before the first live one are masked,
    pages wholly before it leave the table."""
    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    monkeypatch.setattr(ppa, "INTERPRET", True)
    rng = np.random.default_rng(16)
    B, nh, nkv, dh, ps, P = 4, 16, 2, 128, 128, 6
    pool = lambda: jnp.asarray(                                 # noqa: E731
        rng.standard_normal((B * P + 3, ps, nkv * dh)), dtype)
    k_pool, v_pool = pool(), pool()
    q = jnp.asarray(rng.standard_normal((B, nh, dh)), jnp.float32)
    table = jnp.asarray(rng.permutation(B * P).reshape(B, P) + 3, jnp.int32)
    lens, first = (jnp.asarray(a, jnp.int32) for a in (lens, first))
    assert ppa.paged_supported(q.shape, k_pool.shape, dtype)
    got = ppa.paged_decode_attention(q, k_pool, v_pool, table, lens,
                                     sm_scale=dh ** -0.5, first_live=first)
    want = attention_ops._paged_attention_reference(
        q, k_pool, v_pool, table, lens, dh ** -0.5, first)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-2 if dtype == jnp.bfloat16 else 2e-5)
    # and the window matters: without it the same rows read otherwise
    whole = attention_ops._paged_attention_reference(
        q, k_pool, v_pool, table, lens, dh ** -0.5)
    assert np.abs(np.asarray(whole)[0] - np.asarray(want)[0]).max() > 1e-2


def test_moe_experts_pallas_at_256_experts(monkeypatch):
    """`moe_topk_experts` with more experts than one lane register holds
    combine weights for (256: two), in the interpreter."""
    from paddle_tpu.ops.pallas_kernels import moe_experts as pme

    monkeypatch.setattr(pme, "INTERPRET", True)
    rng = np.random.default_rng(17)
    T, E, H, F = 16, 256, 128, 128
    z = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((2, E, H, F)) * H ** -0.5,
                          jnp.bfloat16) for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((2, E, F, H)) * F ** -0.5,
                     jnp.bfloat16)
    router = jnp.asarray(rng.standard_normal((H, E)), jnp.float32)
    ids, cw = decoder_common.sigmoid_router_fn(
        z, router, jnp.zeros((E,), jnp.float32), 8, 2.5)
    assert pme.experts_supported(z.shape, wg.shape, jnp.bfloat16)
    assert int(ids.max()) >= 128        # the second register is read
    got = pme.moe_topk_experts(z, cw, wg, wu, wd, 1)
    want = pme._reference(z, cw, wg, wu, wd, 1)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_window_release_is_a_span_and_the_gauges_read_both_pools():
    eng = _engine()
    eng.reset_stats()
    _serve(eng, _prompts(18, 30), new=12)
    snap = obs.snapshot()
    assert snap["histograms"]["serving.kv.window_release.seconds"]["count"] \
        > 0
    assert snap["counters"]["serving.kv.window_pages_released"] \
        == eng.stats["kv.window_pages_released"] > 0
    assert snap["gauges"]["serving.kv.window_pages_in_use"] \
        == eng.window_pool.pages_in_use
    assert snap["gauges"]["serving.kv.global_pages_in_use"] \
        == eng.pool.pages_in_use
    assert not snap["undeclared"]


@pytest.mark.parametrize("walks", [True, False],
                         ids=["the kernel walks the list", "a row its own"])
def test_the_full_layers_tokens_follow_the_kernel_that_ran(walks,
                                                           monkeypatch):
    """Three rows behind one prompt of six whole pages (the prefix cache
    hands them the same pages), each with a question of its own, blocks of
    two pages. Where the kernel's gate answers yes,
    `attn.full_context_tokens`, `decode_context_pages` and
    `decode_grid_steps` are what the kernel's own rule says of every step's
    feeds (the shared run once); where it answers no, every row's context.
    `attn.attended_tokens` is every row's own context in both, and the
    tokens served are right in both (the program's attention is the XLA
    arm's here: the gate steers the count and the plan, not the result)."""
    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    monkeypatch.setattr(ppa, "pages_per_grid_step", lambda *a: 2)
    monkeypatch.setattr(attention_ops, "paged_decode_walks",
                        lambda *a, **k: walks)
    said, own = [], []
    count = ppa.walk_counts
    monkeypatch.setattr(ppa, "walk_counts",
                        lambda *feeds: said.append(count(*feeds)) or said[-1])
    eng = _engine()
    shared = _prompts(5, 6 * PS)[0]
    _serve(eng, [shared], new=1)            # the prompt's pages are cached
    eng.reset_stats()
    run_step = eng._run_step

    def spy(kind, target, io, feed, *args, **kwargs):
        if kind == "decode":
            live = feed[sv_model.MASK_FEED][:, 0] > 0
            own.append(feed[sv_model.POS_FEED].reshape(-1)[live] + 1)
        return run_step(kind, target, io, feed, *args, **kwargs)

    monkeypatch.setattr(eng, "_run_step", spy)
    prompts = _prompts(6, 3, 5, 9, shared=shared)
    done = _serve(eng, prompts, new=5)
    _assert_right(eng, prompts, done)
    st, full = eng.stats, eng._full_layers
    attended = full * sum(int(n.sum()) for n in own)
    pages = sum(int((-(-n // PS)).sum()) for n in own)
    assert st["attn.attended_tokens"] == attended > 0
    assert obs.snapshot()["counters"]["serving.attn.attended_tokens"] \
        == attended
    if not walks:
        assert not said and st["attn.shared_kernel_layer_steps"] == 0
        assert st["attn.full_context_tokens"] == attended
        assert st["decode_context_pages"] == pages
        return
    assert len(said) == st["decode_steps"]
    assert st["attn.full_context_tokens"] \
        == full * sum(r["tokens"] for r in said) < attended
    assert st["decode_context_pages"] == sum(r["pages"] for r in said) < pages
    assert st["decode_grid_steps"] == sum(r["blocks"] for r in said)
    assert st["attn.shared_kernel_layer_steps"] \
        == full * sum(r["shared"] for r in said) > 0
    # three rows behind six of their seven to nine pages
    assert attended / st["attn.full_context_tokens"] > 1.8
