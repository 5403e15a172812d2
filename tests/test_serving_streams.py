"""The "latent_moe" block WITHOUT an indexer and with four residual streams
(Xing4.0's layer: every cached row attended, manifold-constrained
hyper-connections around every sub-layer) through ServingEngine, on the CPU
at toy size with seeded weights, against the plain reference
`benchmark/reference/xing4_lm.py` (which imports nothing from paddle_tpu).
Pages hold 8 tokens and a prompt runs in chunks of 16; two dense layers
lead two routed ones (2 of 8 experts, all held); the residual logits are
clipped at +-1, so the clip cuts."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing4_lm as ref
from paddle_tpu import unique_name
from paddle_tpu.executor import Executor, Scope
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.ops import latent_moe_ops
from paddle_tpu.ops.pallas_kernels import paged_latent_attend
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import model as sv_model
from paddle_tpu.serving.kv_cache import INDEX_POOL, LATENT_POOL
from tools import streams_faults

PS = 8
TOL = 2e-4          # float32 on both sides: rounding order only
# bfloat16 weights and cache rows against the float32 reference on the same
# stored weights, the engine's experts followed: at 32 wide and 4 layers a
# served logit (they spread over +-3) lies up to 0.28 away (`latent_moe_tiny`
# in 3 layers: 0.25, tests/test_serving_latent.py); twice that is the limit
BF16_TOL = 0.6


def _engine(cfg=None, **kw):
    kw.setdefault("page_size", PS)
    kw.setdefault("pool_pages", 64)
    kw.setdefault("max_inflight", 4)
    kw.setdefault("seed", 3)
    return ServingEngine(cfg or sv_model.latent_streams_tiny(), **kw)


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


def _graded(eng, prompts, done):
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    return ref.check_sequences(
        params, [(p, r.out_tokens, r.routes) for p, r in zip(prompts, done)],
        eng.cfg)


def _served_logits(eng, prompts, new):
    """Requests through the engine's own loop one after another, every
    step's logits brought to the host: for each, [new, V], the logits each
    served token was the argmax of (the last prefill window's, then every
    decode step's)."""
    run_step = ServingEngine._run_step
    out = []
    for prompt in prompts:
        last_chunk, decode = [], []

        def to_host(kind, target, io, feed, greedy, *args, **kw):
            got = run_step(eng, kind, target, io, feed, False, *args, **kw)
            logits = np.asarray(got["logits"])[0]
            if kind == "decode":
                decode.append(logits)
            else:
                last_chunk[:] = [logits]
            return dict(got, logits=None)

        eng._run_step = to_host
        done = _serve(eng, [prompt], new=new)[0]
        del eng._run_step
        out.append((done, np.stack(last_chunk + decode)[:new]))
    return out


def _reference_logits(eng, prompt, done):
    """The reference's full forward over prompt + served tokens at the
    served positions, the engine's experts followed."""
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    seq = (list(prompt) + list(done.out_tokens))[:-1]
    return ref.logits(params, seq, eng.cfg, done.routes)[len(prompt) - 1:]


# -- the programs against the reference --------------------------------------


def test_full_forward_matches_reference():
    cfg = sv_model.latent_streams_tiny()
    prog, startup = Program(), Program()
    startup.random_seed = 7
    with program_guard(prog, startup), unique_name.guard():
        io = sv_model.build_full_forward_program(cfg)
    assert "selection" not in io                # nothing to hand back
    exe, scope = Executor(), Scope()
    exe.run(startup, scope=scope)
    tok = np.asarray(_prompts(0, 40), np.int32)
    pos = np.arange(40, dtype=np.int32)[None, :]
    logits, routes = exe.run(
        prog, feed={sv_model.TOK_FEED: tok, sv_model.POS_FEED: pos},
        fetch_list=[io["logits"], io["routes"]], scope=scope)
    params = ref.read_params(scope.find_var, cfg)
    np.testing.assert_allclose(logits[0], ref.logits(params, tok[0], cfg),
                               atol=TOL)
    # the program's experts, followed, leave no margin
    assert routes.shape == (1, 40, cfg.routed_layers, 2)
    _, route_margin, _ = ref.forward(params, tok[0].tolist(), cfg, routes[0])
    assert route_margin.max() <= 1e-5
    assert routes.min() == 0 and routes.max() == cfg.num_experts - 1


def test_chunked_prefill_and_decode_logits_match_the_full_forward():
    """A 40-token prompt runs as windows of 16, 16 and 8 behind one
    another, then decodes through the cache: every served position's logits
    are the reference's full forward's."""
    eng = _engine()
    prompt = _prompts(1, 40)[0]
    (done, served), = _served_logits(eng, [prompt], 8)
    assert eng.stats["prefill.chunks"] == 3
    assert done.routes.shape == (done.cache_len, 2, 2)
    np.testing.assert_allclose(served, _reference_logits(eng, prompt, done),
                               atol=TOL)
    g, = _graded(eng, [prompt], [done])
    assert g["gap"] <= TOL and g["route_margin"] <= 1e-4, g


def test_a_prefix_hit_with_copy_on_write_serves_the_same_logits():
    """Three requests behind one 24-token prefix (three pages of 8): the
    second resumes from the cached pages behind a suffix of its own, the
    third is the first's prompt again (a full hit: its last token is
    recomputed into a shared page, which is copied first), and both serve
    what the reference's full forward serves."""
    eng = _engine()
    shared = _prompts(2, 24)[0]
    prompts = _prompts(3, 8, 21, shared=shared)
    prompts.append(prompts[0])
    served = _served_logits(eng, prompts, 6)
    assert eng.stats["prefix_hit_tokens"] >= 24 + 32
    assert eng.stats["prefix_full_hits"] == 1
    assert eng.stats["cow_copies"] >= 1
    for prompt, (done, logits) in zip(prompts, served):
        np.testing.assert_allclose(
            logits, _reference_logits(eng, prompt, done), atol=TOL)
    cold = _serve(_engine(prefix_cache=False), prompts[1:], new=6)
    assert [r.out_tokens for r in cold] \
        == [done.out_tokens for done, _ in served[1:]]


def test_rows_decode_together_behind_different_contexts():
    eng = _engine()
    prompts = _prompts(4, 5, 33, 18, 47)
    done = _serve(eng, prompts, new=7)
    for g in _graded(eng, prompts, done):
        assert g["gap"] <= TOL and g["route_margin"] <= 1e-4, g
    st = eng.stats
    # every live position of every row, a layer; the family's layer steps
    assert st["sparse.layer_steps"] == 4 * st["decode_steps"]
    assert st["latent.attended_tokens"] > 0
    assert st["hc.mix_tokens"] == 8 * (st["prefill_tokens_computed"]
                                       + st["decode_tokens"])


def test_bfloat16_stays_within_the_stated_tolerance():
    """Weights and cache rows in bfloat16 (streams, mappings and Sinkhorn
    stay float32), against the float32 reference on the same weights."""
    eng = _engine(sv_model.latent_streams_tiny(dtype="bfloat16"))
    prompts = _prompts(5, 40, 9)
    worst = 0.0
    for (done, served), prompt in zip(_served_logits(eng, prompts, 6),
                                      prompts):
        worst = max(worst, float(np.abs(
            served - _reference_logits(eng, prompt, done)).max()))
    assert 1e-3 < worst <= BF16_TOL, worst
    for g in _graded(eng, prompts, [done for done, _ in
                                    _served_logits(eng, prompts, 6)]):
        assert g["gap"] <= BF16_TOL and g["route_margin"] <= 0.05, g


_HC_FUNCTIONS = ("spread_fn", "mappings_fn", "pre_mix_fn", "post_mix_fn",
                 "readout_fn")


def _stream_dtypes():
    """(function, dtype of the streams it was given, dtypes it returned) for
    every call of `hyper_connection_ops` while a bfloat16 engine traces and
    runs a window and a decode step."""
    from unittest import mock

    from paddle_tpu.ops import hyper_connection_ops as hc

    seen = []

    def spied(name):
        real = getattr(hc, name)

        def spy(x, *rest):
            out = real(x, *rest)
            outs = out if isinstance(out, tuple) else (out,)
            seen.append((name, x.dtype, [o.dtype for o in outs]))
            return out
        return spy

    with contextlib.ExitStack() as stack:
        for name in _HC_FUNCTIONS:
            stack.enter_context(mock.patch.object(hc, name, spied(name)))
        eng = _engine(sv_model.latent_streams_tiny(dtype="bfloat16"))
        assert eng._scope.find_var("dec.word_emb").dtype == jnp.bfloat16
        _serve(eng, _prompts(13, 20, 5), new=3)
    return seen


def test_the_streams_are_float32_in_every_program_under_bfloat16_weights():
    """What no comparison of served tokens can see beside an engine whose
    every sub-layer input is rounded to bfloat16 anyway
    (`tools/streams_faults.py` `streams_bfloat16` on the chip: PERF.md
    section 6): the window and decode programs of a bfloat16 configuration
    hand float32 streams from mix to mix and the mappings read float32;
    under the planted fault they do not."""
    seen = _stream_dtypes()
    names = [n for n, _, _ in seen]
    # both programs traced every function (a window and a decode step)
    assert all(names.count(n) >= 2 for n in _HC_FUNCTIONS)
    for name, dtype_in, dtypes_out in seen:
        assert all(d == jnp.float32 for d in dtypes_out), (name, dtypes_out)
        if name != "spread_fn":        # the embedding comes in as stored
            assert dtype_in == jnp.float32, (name, dtype_in)
    with streams_faults.streams_bfloat16():
        wrong = _stream_dtypes()
    assert {n for n, dtype_in, _ in wrong if dtype_in == jnp.bfloat16} \
        >= {"mappings_fn", "pre_mix_fn", "post_mix_fn", "readout_fn"}


# -- one pool -----------------------------------------------------------------


def test_the_engine_allocates_shares_copies_and_audits_one_pool():
    eng = _engine()
    scope = eng._scope
    assert scope.has_var(LATENT_POOL) and not scope.has_var(INDEX_POOL)
    assert scope.find_var(LATENT_POOL).shape == (4 * 64, PS, 20)
    for prog in (eng._decode_prog, eng._window_prog, eng._cow_prog):
        named = {v for op in prog.global_block.ops
                 for names in list(op.inputs.values())
                 + list(op.outputs.values()) for v in names}
        assert LATENT_POOL in named and INDEX_POOL not in named
    assert sv_model.MARK_FEED not in eng._decode_io["feeds"]
    assert "selection" not in eng._decode_io
    # nothing to hand back, whoever asks
    prompt = _prompts(6, 24)[0]
    rid = eng.submit(prompt, 4, keep_selection=True)
    eng.run_until_drained()
    assert eng.requests[rid].selection is None
    # a shared page, copied on write in every layer of the one pool
    rid = eng.submit(prompt, 10)
    while eng.requests[rid].n_generated < 2:
        eng.step()
    req = eng.requests[rid]
    old = list(req.pages)
    before = np.asarray(scope.find_var(LATENT_POOL))
    assert eng._cow(req, len(req.pages) - 1)      # the page being written
    assert req.pages[-1] != old[-1]
    after = np.asarray(scope.find_var(LATENT_POOL))
    for layer in range(eng.cfg.num_layers):
        row = layer * eng.pool_pages
        assert np.abs(before[row + old[-1]]).max() > 0
        np.testing.assert_array_equal(after[row + req.pages[-1]],
                                      before[row + old[-1]])
    eng.run_until_drained()
    assert eng.audit_pool() == ([], []) and eng.leaked_pages() == 0


def test_the_configuration_says_whether_there_is_an_indexer():
    tiny = sv_model.latent_streams_tiny()
    assert tiny.latent and not tiny.selects and tiny.page_bucket_step == 32
    assert sv_model.latent_moe_tiny().selects
    with pytest.raises(ValueError, match="indexer given whole"):
        sv_model.latent_streams_tiny(index_topk=8)
    with pytest.raises(ValueError, match="hc_sinkhorn_iters"):
        sv_model.latent_streams_tiny(hc_sinkhorn_iters=0)
    specs = sv_model._latent_param_specs(tiny)
    assert not any("wqi" in k or "ki_norm" in k for k in specs)
    assert specs["dense.hc_w"][0] == [2, 2, 4 * 32, 24]
    assert "dense.hc_w" not in sv_model._latent_param_specs(
        sv_model.latent_moe_tiny())


# -- the planted faults -------------------------------------------------------


def _fault_readings(name):
    """(worst logit gap, worst route margin) of three requests behind one
    shared prompt as `tools/streams_faults.py` drives and grades them, and
    the largest distance of a served logit from the reference's, for an
    engine built and run under the named fault or control."""
    context = streams_faults.FAULTS.get(name) \
        or streams_faults.CONTROLS[name]
    with context() as prepare:
        eng = _engine()
        served = streams_faults.drive(eng, eng.cfg, 32, [3, 10, 21], 6, 11,
                                      prepare)
        prompt = _prompts(12, 5, shared=served[0][0][:32])[0]
        (done, logits), = _served_logits(eng, [prompt], 6)
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    graded = ref.check_sequences(params, served, eng.cfg)
    away = float(np.abs(logits - _reference_logits(eng, prompt, done)).max())
    return (max(g["gap"] for g in graded),
            max(g["route_margin"] for g in graded), away)


@pytest.mark.parametrize("name", list(streams_faults.CONTROLS))
def test_a_right_engine_passes(name):
    gap, margin, away = _fault_readings(name)
    assert gap <= TOL and margin <= 1e-4 and away <= TOL, (gap, margin, away)


@pytest.mark.parametrize("name", list(streams_faults.FAULTS))
def test_a_planted_fault_of_the_residual_path_fails(name):
    """Each wrong mechanism leaves the reference by far more than rounding
    (the least of them, streams rounded to bfloat16 in four layers 32 wide,
    by 20 times the limit): in the served logits, and most of them in the
    experts the engine chose as well."""
    gap, margin, away = _fault_readings(name)
    # (the clip's omission overflows: its readings are not numbers)
    assert not away <= 20 * TOL, (name, gap, margin, away)
    assert not (gap <= TOL and margin <= 1e-4), (name, gap, margin, away)


# -- attention without an indexer --------------------------------------------


def _geometry(**over):
    kw = dict(num_heads=8, nope_dim=16, rope_dim=64, v_dim=16, kv_rank=256,
              rope_theta=1e4, yarn=(), softmax_mscale=1.1, eps=1e-6,
              index_heads=0, index_dim=0, index_topk=0, experts_per_token=2,
              expert_groups=1, groups_per_token=1, routed_scaling=1.0,
              experts_held=4)
    kw.update(over)
    return latent_moe_ops.Geometry(**kw)


def _pool(rng, rows, ps, geom, words):
    c = rng.standard_normal((rows, ps, geom.kv_rank)).astype(np.float32)
    r = rng.standard_normal((rows, ps, geom.rope_dim)).astype(np.float32)
    return latent_moe_ops.join_latent_fn(jnp.asarray(c), jnp.asarray(r),
                                         jnp.bfloat16, words)


@contextlib.contextmanager
def _interpreted():
    paged_latent_attend.INTERPRET = True
    try:
        yield
    finally:
        paged_latent_attend.INTERPRET = False


@pytest.mark.parametrize("pages,lens", [
    (16, [1, 0, 700, 2048, 1025]),      # two blocks of 8 pages; a padding row
    (4, [512, 129, 3]),                 # one block, one chunk
    (1, [128, 5]),                      # a table of one page
    (96, [0, 12288, 9000, 1])])         # twelve blocks; the first row padded
def test_paged_latent_attention_pallas_matches_reference(pages, lens):
    geom = _geometry()
    rng = np.random.default_rng(pages)
    pool = _pool(rng, 3 * 40, 128, geom, 256)
    B = len(lens)
    table = jnp.asarray(rng.integers(0, pool.shape[0], (B, pages)), jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    q_lat = jnp.asarray(rng.standard_normal((B, 8, 256)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((B, 8, 64)), jnp.float32)
    assert paged_latent_attend.paged_latent_attend_supported(
        q_lat.shape, pool.shape, jnp.bfloat16, 64)
    with _interpreted():
        got = paged_latent_attend.paged_latent_attention(
            q_lat, q_rope, pool, table, lens, jnp.bfloat16, geom)
    want = paged_latent_attend._reference(q_lat, q_rope, pool, table, lens,
                                          jnp.bfloat16, geom)
    # the probabilities are rounded to bfloat16 before they are normalised
    # here and after it there
    np.testing.assert_allclose(got, want, atol=0.02)
    assert not np.asarray(got[np.asarray(lens) == 0]).any()


def _behind(doc, run, own):
    """A table row of 32 entries: the first `run` pages of document `doc`
    (pages 40 * doc ..), then the pages `own`."""
    row = np.zeros(32, np.int32)
    row[:run] = 40 * doc + np.arange(run)
    row[run:run + len(own)] = own
    return row


def _shared_cases():
    """name -> (table [B, 32], lens, the run each row must be found to
    share): tables of 32 entries, so a block is 8 pages and a tile 8 rows
    (or all of them)."""
    ps = 128
    # groups of uneven size behind runs of 8, 16 and 24 pages, tails of 1-3
    # pages of their own; ten rows behind document 0 (a whole tile and a
    # rest), a group of one (document 3) and a padding row among them
    rows, lens, runs = [], [], []
    own = iter(range(200, 360))
    take = lambda n: [next(own) for _ in range(n)]           # noqa: E731
    for doc, run, members in ((0, 8, 10), (1, 16, 3), (2, 24, 2), (3, 9, 1)):
        for i in range(members):
            tail = 1 + (i + doc) % 3
            rows.append(_behind(doc, run, take(tail)))
            lens.append((run + tail) * ps - (37 * i + 5) % ps)
            runs.append(run if members > 1 else 0)
    rows.insert(4, _behind(0, 8, take(2)))     # a padding row in a span
    lens.insert(4, 0)
    runs.insert(4, 0)
    mix = np.random.default_rng(0).permutation(len(rows))
    uneven = (np.stack(rows)[mix], np.asarray(lens)[mix],
              np.asarray(runs)[mix])
    same = np.stack([_behind(0, 21, [])] * 2)
    return {
        "uneven groups": uneven,
        # one table, and the second row ends INSIDE what the first would
        # share: the run stops at its last whole page, and at a block's end
        "a row ends inside": (same, [20 * ps + 5, 11 * ps + 7], [8, 8]),
        "a row ends before a block": (same, [20 * ps + 5, 7 * ps + 9],
                                      [0, 0]),
        # equal from entry 1 on only
        "not from the first page": (
            np.stack([_behind(0, 20, []),
                      np.concatenate([[399], _behind(0, 20, [])[1:]])]),
            [20 * ps, 19 * ps + 3], [0, 0]),
        # 13 pages in common: one block is shared, five pages are tail
        "not a multiple of the block": (
            np.stack([_behind(0, 13, [300, 301, 302]),
                      _behind(0, 13, [310, 311]),
                      _behind(0, 13, [320])]),
            [16 * ps, 14 * ps + 77, 13 * ps + 1], [8, 8, 8]),
    }


@pytest.mark.parametrize("name", list(_shared_cases()))
def test_paged_latent_attention_behind_shared_pages_matches_reference(name):
    """Rows whose tables begin with the same pages attend that run once,
    stacked; what each gets is what it gets alone."""
    table, lens, runs = _shared_cases()[name]
    geom = _geometry()
    rng = np.random.default_rng(len(name))
    pool = _pool(rng, 400, 128, geom, 256)
    B = len(lens)
    groups = paged_latent_attend.row_groups(
        table, np.asarray(lens), 128, 8, np)
    assert paged_latent_attend.pages_per_grid_step(32, 128 * 256 * 4) == 8
    assert list(groups.run[np.argsort(groups.order)]) == list(runs)
    table, lens = jnp.asarray(table, jnp.int32), jnp.asarray(lens, jnp.int32)
    q_lat = jnp.asarray(rng.standard_normal((B, 8, 256)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((B, 8, 64)), jnp.float32)
    with _interpreted():
        got = paged_latent_attend.paged_latent_attention(
            q_lat, q_rope, pool, table, lens, jnp.bfloat16, geom)
    want = paged_latent_attend._reference(q_lat, q_rope, pool, table, lens,
                                          jnp.bfloat16, geom)
    np.testing.assert_allclose(got, want, atol=0.02)
    assert not np.asarray(got[np.asarray(lens) == 0]).any()


@pytest.mark.parametrize("seed", range(4))
def test_row_groups_against_a_count_by_hand(seed):
    """The grouping on random tables (rows behind a few documents for
    random stretches, random lengths, padding rows) against loops: every
    member of a group holds the group's run in whole pages and in its
    leader's ids, no run is shorter than a block or not whole blocks, a row
    that could join a lower row's group has; the pages fetched are the
    distinct (group, page) pairs; and jax.numpy gives what numpy gives."""
    rng = np.random.default_rng(seed)
    B, P, ps, G = 24, 32, 16, 4
    docs = rng.integers(0, 1000, (3, P))
    table = rng.integers(1000, 2000, (B, P))
    for b in range(B):
        n = rng.integers(0, P + 1)
        table[b, :n] = docs[rng.integers(0, 3), :n]
    lens = rng.integers(0, P * ps + 1, B) * (rng.random(B) > 0.15)
    g = paged_latent_attend.row_groups(table, lens, ps, G, np)
    for a, b in zip(g, paged_latent_attend.row_groups(
            jnp.asarray(table), jnp.asarray(lens), ps, G)):
        assert np.array_equal(a, b)
    assert sorted(g.order) == list(range(B))
    fetched = set()
    for i, b in enumerate(g.order):
        lead = g.order[g.first[i]]
        members = [r for r in range(B) if np.array_equal(
            table[r, :G], table[lead, :G]) and min(lens[r], lens[lead])
            >= G * ps]
        if lead != b or len(members) > 1:
            assert b in members and lead == members[0]
            assert g.count[i] == len(members)
            run = min(min(lens[r] // ps, next(
                (j for j in range(P) if table[r, j] != table[lead, j]), P))
                for r in members) // G * G
            assert g.run[i] == run >= G
            assert list(g.order[g.first[i]:g.first[i] + g.count[i]]) \
                == members
        else:
            assert (g.count[i], g.run[i], g.first[i]) == (1, 0, i)
        assert g.pages[i] == -(-lens[b] // ps)
        fetched |= {(lead, j) for j in range(g.run[i])}
        fetched |= {(b, j) for j in range(g.run[i], g.pages[i])}
    pool_shape = (4000, ps, 4688)       # pages of which a block takes 4
    assert paged_latent_attend.pages_per_grid_step(P, ps * 4688 * 4) == G
    assert paged_latent_attend.pages_read(table, lens, pool_shape) \
        == len(fetched)


def test_the_paged_kernel_agrees_with_the_expanded_form():
    """The absorbed form over a row's pages is the attention as the
    equations state it: per-head keys and values made from the latents."""
    geom = _geometry()
    rng = np.random.default_rng(9)
    ps, P, n = 128, 4, 300
    pool = _pool(rng, P, ps, geom, 256)
    wkv_b = jnp.asarray(rng.standard_normal((256, 8 * 32)) * 256 ** -0.5,
                        jnp.bfloat16)
    q_nope = jnp.asarray(rng.standard_normal((1, 8, 16)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((1, 8, 64)), jnp.float32)
    table = jnp.arange(P, dtype=jnp.int32)[None]
    with _interpreted():
        o = latent_moe_ops._attend_pages(
            q_nope, q_rope, pool, table, jnp.asarray([n], jnp.int32), wkv_b,
            jnp.bfloat16, geom)
    c, r = latent_moe_ops.split_latent_fn(pool.reshape(1, P * ps, -1),
                                          jnp.bfloat16, 256, 64)
    want = latent_moe_ops.expanded_attention_blocks_fn(
        q_nope[:, None], q_rope[:, None], c, r,
        jnp.asarray([[n - 1]], jnp.int32), wkv_b, geom)[:, 0]
    np.testing.assert_allclose(o, want, atol=0.03)


def test_the_gate_refuses_what_the_kernel_cannot_take():
    supported = paged_latent_attend.paged_latent_attend_supported
    assert supported((64, 32, 512), (7 * 2304, 128, 384), jnp.bfloat16, 64)
    assert not supported((4, 4, 16), (256, 8, 20), jnp.float32, 4)
    assert not supported((64, 32, 512), (7 * 2304, 128, 384), jnp.float32, 64)
    assert not supported((64, 32, 512), (7 * 2304, 8, 384), jnp.bfloat16, 64)
    assert not supported((64, 30, 512), (7 * 2304, 128, 384), jnp.bfloat16,
                         64)
    assert paged_latent_attend.pages_per_grid_step(288, 128 * 384 * 4) == 8
    assert paged_latent_attend.chunk_pages(8, 128) == 8


@pytest.mark.parametrize("T,S", [(24, 24), (64, 16), (96, 5)])
def test_key_blocks_give_the_whole_softmax(monkeypatch, T, S):
    """The window's attention over key blocks with a running softmax is the
    one-block form under the causal mask, whatever the block."""
    geom = _geometry(num_heads=4, nope_dim=8, rope_dim=4, v_dim=8,
                     kv_rank=16)
    rng = np.random.default_rng(T)
    arr = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    q_nope, q_rope = arr(2, S, 4, 8), arr(2, S, 4, 4)
    c, r, wkv_b = arr(2, T, 16), arr(2, T, 4), arr(16, 4 * 16)
    gpos = jnp.asarray(np.stack([T - S + np.arange(S),
                                 np.arange(S)]), jnp.int32)
    live = jnp.arange(T)[None, None, :] <= gpos[:, :, None]
    want = latent_moe_ops.expanded_attention_fn(q_nope, q_rope, c, r, live,
                                                wkv_b, geom)
    monkeypatch.setattr(latent_moe_ops, "_KEY_BLOCK", 8)
    assert latent_moe_ops.key_block(T) == 8
    got = latent_moe_ops.expanded_attention_blocks_fn(q_nope, q_rope, c, r,
                                                      gpos, wkv_b, geom)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_paged_kernel_serves_what_the_gathered_form_served():
    """An engine whose geometry passes the kernel's gate (bfloat16 rows in
    whole lane tiles, pages of 128) decodes through the interpreted kernel
    and serves the tokens and routes the XLA form serves."""
    cfg = sv_model.latent_streams_tiny(
        dtype="bfloat16", num_heads=8, kv_lora_rank=256, rope_head_dim=8,
        max_position=512, num_layers=3, dense_layers=1)
    prompts = _prompts(8, 150, 20)
    kw = dict(page_size=128, pool_pages=12, max_inflight=2)
    plain = _engine(cfg, **kw)
    want = _serve(plain, prompts, new=4)
    assert plain.stats["latent.attend_kernel_layer_steps"] == 0
    with _interpreted():
        eng = _engine(cfg, **kw)
        got = _serve(eng, prompts, new=4)
    st = eng.stats
    assert st["latent.attend_kernel_layer_steps"] \
        == st["sparse.layer_steps"] > 0
    for a, b in zip(got, want):
        assert a.out_tokens == b.out_tokens
        assert np.array_equal(a.routes, b.routes)


def test_the_pages_read_follow_the_arm_that_ran(monkeypatch):
    """Three rows behind one prompt of eight whole pages (the prefix cache
    hands them the same pages), each with a question of its own. Where the
    kernel's gate answers yes `serving.latent.pages_read` is what the
    kernel's grouping says of every step's feeds, a layer: the shared run
    once, so fewer pages than the rows' tables hold and a sharing above 1;
    on the XLA arm every live page of every row, a sharing of at most 1."""
    cfg = sv_model.latent_streams_tiny(
        dtype="bfloat16", num_heads=8, kv_lora_rank=256, rope_head_dim=8,
        max_position=2048, num_layers=3, dense_layers=1, prefill_chunk=256)
    shared = _prompts(5, 8 * 128 + 9)[0]
    prompts = _prompts(6, 3, 11, 40, shared=shared)
    kw = dict(page_size=128, pool_pages=24, max_inflight=3)
    said = []
    count = paged_latent_attend.pages_read

    def spied(*feeds):
        said.append(count(*feeds))
        return said[-1]

    monkeypatch.setattr(paged_latent_attend, "pages_read", spied)

    def served():
        eng = _engine(cfg, **kw)
        _serve(eng, [shared], new=1)        # the prompt's pages are cached
        eng.reset_stats()
        _serve(eng, prompts, new=4)
        st = eng.stats
        return st, st["latent.attended_tokens"] / (
            st["latent.pages_read"] * 128)

    st, sharing = served()
    assert not said and st["latent.attend_kernel_layer_steps"] == 0
    assert st["latent.pages_read"] == 3 * st["decode_context_pages"] > 0
    assert 0.8 < sharing <= 1
    with _interpreted():
        st, sharing = served()
    assert st["latent.attend_kernel_layer_steps"] \
        == st["sparse.layer_steps"] > 0
    assert st["latent.pages_read"] == 3 * sum(said) \
        < 3 * st["decode_context_pages"]
    assert sharing > 1.5

