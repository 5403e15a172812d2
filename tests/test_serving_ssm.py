"""The "parallel_ssm" block family (Falcon-H1's layer: a Mamba-2 mixer and
grouped-query attention side by side) behind ServingEngine, at a tiny size
on the CPU: the engine against the plain reference
(`benchmark/reference/falcon_h1_lm.py`), the chunked scan against the
recurrence, the slot pool and its snapshots, and the wrong mechanisms of
`tools/ssm_faults.py`, which must each fail the same check."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import falcon_h1_lm as ref  # noqa: E402
from paddle_tpu.ops import attention_ops  # noqa: E402
from paddle_tpu.ops import parallel_ssm_ops as ops  # noqa: E402
from paddle_tpu.ops.pallas_kernels import conv_update  # noqa: E402
from paddle_tpu.ops.pallas_kernels import paged_attention as ppa  # noqa: E402
from paddle_tpu.ops.pallas_kernels import ssm_update  # noqa: E402
from paddle_tpu.serving import DecoderConfig, ServingEngine  # noqa: E402
from paddle_tpu.serving import model as sv_model  # noqa: E402
from paddle_tpu.serving.kv_cache import PagedKVPool, PrefixCache  # noqa: E402
from paddle_tpu.serving.model import parallel_ssm_tiny  # noqa: E402
from tools import ssm_faults  # noqa: E402
from serving_helpers import preempting  # noqa: E402

TOL = 1e-3          # the rehearsal configuration's tolerance


def _engine(cfg=None, **kw):
    kw = dict(dict(page_size=4, pool_pages=128, max_inflight=4, seed=3,
                   prefix_cache=True, draft_k=0), **kw)
    return ServingEngine(cfg or parallel_ssm_tiny(), **kw)


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
    return [eng.pop_result(r) for r in rids]


def _gaps(eng, prompts, outs):
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    return ref.worst_logit_gaps(params, list(zip(prompts, outs)), eng.cfg)


# -- the engine against the reference ---------------------------------------


def test_prefill_then_decode_equals_the_references_forward():
    """Float32: every served token is the reference's best token, and the
    stack's own dense forward gives the reference's logits at every
    position to 1e-4."""
    eng = _engine()
    prompts = _prompts([5, 11, 3, 9, 20], shared=16)
    outs = _serve(eng, prompts, audit=True)
    assert max(_gaps(eng, prompts, outs)) < 1e-5
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    seq = np.asarray(prompts[4] + outs[4])
    want = np.asarray(ref.all_logits(params, seq, eng.cfg))
    assert [int(t) for t in want[len(prompts[4]) - 1:-1].argmax(-1)] \
        == outs[4]
    geom = ops.Geometry(**sv_model._ssm_geometry(eng.cfg))
    got = ops.parallel_ssm_stack_fn(
        "full", jnp.asarray(seq)[None], jnp.arange(len(seq))[None],
        params["emb"], params["head"], params["final_norm"],
        {k: params[k] for k in ops.LAYER_PARAMS}, geom)["logits"][0]
    assert float(np.max(np.abs(np.asarray(got) - want))) < 1e-4
    assert eng.leaked_pages() == 0
    assert eng.stats["state.restores"] == 4
    assert eng.stats["state.snapshots"] >= 2


def test_bfloat16_serves_within_the_tolerances_form():
    """bfloat16 weights and K/V: served tokens stay within a gap of the
    float32 reference's best that is small beside the logits' spread."""
    eng = _engine(parallel_ssm_tiny(dtype="bfloat16"))
    prompts = _prompts([5, 11, 9], shared=16)
    outs = _serve(eng, prompts)
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    spread = float(np.std(np.asarray(ref.all_logits(
        params, np.asarray(prompts[0] + outs[0]), eng.cfg))))
    assert max(_gaps(eng, prompts, outs)) < 0.1 * spread


def test_a_prompt_in_windows_equals_the_prompt_in_one():
    prompts = _prompts([30, 23, 17])
    chunked = _serve(_engine(), prompts)
    whole = _serve(_engine(parallel_ssm_tiny(prefill_chunk=32)), prompts)
    assert chunked == whole


def test_the_72_layer_configuration_builds_at_tiny_widths():
    cfg = parallel_ssm_tiny(num_layers=72)
    eng = _engine(cfg, pool_pages=32, max_inflight=2)
    shape = eng._scope.find_var("kv_cache.ssm").shape
    assert shape == (72 * eng.state_pool.num_pages, 4 * 16, 8)
    assert eng._scope.find_var("dec.layers.w_in").shape == (72, 32, 132)
    (out,) = _serve(eng, _prompts([6]), out=2)
    assert len(out) == 2


def test_a_config_that_names_no_mixer_is_refused():
    with pytest.raises(ValueError, match="parallel_ssm"):
        DecoderConfig(block="parallel_ssm", prefill_chunk=8)
    with pytest.raises(ValueError, match="whole pages"):
        _engine(parallel_ssm_tiny(prefill_chunk=6))


# -- the scan ----------------------------------------------------------------


def _scan_inputs(S, seed=0, B=2, H=4, P=8, G=2, N=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (B, S, H, P)),
        dt_raw=jax.random.normal(ks[1], (B, S, H)),
        bmat=jax.random.normal(ks[2], (B, S, G, N)),
        cmat=jax.random.normal(ks[3], (B, S, G, N)),
        dt_bias=jax.random.uniform(ks[4], (H,), minval=-3.0, maxval=-0.5),
        a_log=jax.random.uniform(ks[5], (H,), maxval=2.0),
        s0=jax.random.normal(ks[6], (B, H, N, P)))


@pytest.mark.parametrize("length", [8, 12, 7, 13, 3])
@pytest.mark.parametrize("start", ["zero", "carried"])
def test_the_chunked_scan_equals_the_recurrence(length, start):
    a = _scan_inputs(length, seed=length)
    if start == "zero":
        a["s0"] = jnp.zeros_like(a["s0"])
    with jax.default_matmul_precision("highest"):
        y0, s0 = ops.token_recurrence_fn(**a)
        y1, s1 = ops.ssd_scan_fn(**a, chunk=4)
    assert float(jnp.max(jnp.abs(y0 - y1))) < 1e-4
    assert float(jnp.max(jnp.abs(s0 - s1))) < 1e-4


@pytest.mark.parametrize("real", [1, 5, 8])
def test_a_padded_window_leaves_the_state_of_its_last_real_token(real):
    a = _scan_inputs(8, seed=3)
    valid = jnp.arange(8)[None, :] < real
    cut = {k: (v[:, :real] if k in ("x", "dt_raw", "bmat", "cmat") else v)
           for k, v in a.items()}
    with jax.default_matmul_precision("highest"):
        _, want = ops.token_recurrence_fn(**cut)
        y, got = ops.ssd_scan_fn(**a, chunk=4,
                                 valid=jnp.broadcast_to(valid, (2, 8)))
        y_cut, _ = ops.ssd_scan_fn(**cut, chunk=4)
    assert float(jnp.max(jnp.abs(want - got))) < 1e-5
    assert float(jnp.max(jnp.abs(y[:, :real] - y_cut))) < 1e-5
    # the convolution's tail: the rows before the first padding row
    xbc = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 6))
    tail = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 6))
    w = jax.random.normal(jax.random.PRNGKey(3), (6, 4))
    _, t = ops.causal_conv_fn(xbc, tail, w, jnp.zeros((6,)),
                              jnp.full((2,), real, jnp.int32))
    ext = jnp.concatenate([tail, xbc], axis=1)
    assert jnp.array_equal(t, ext[:, real:real + 3])


def test_ssm_decode_update_pallas_matches_reference(monkeypatch):
    """The decode update's kernel, through the interpreter, at the served
    state (256 x 128 a head): pool and y against the plain form; every
    row live, two of them on one slot, which is left out."""
    monkeypatch.setattr(ssm_update, "INTERPRET", True)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    H, N, P, G = 16, 256, 128, 2
    pool = jax.random.normal(ks[0], (12, H * N, P))
    idx = jnp.asarray([3, 7, 1, 11, 11], jnp.int32)
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (5, H)))
    dtx = jax.random.normal(ks[2], (5, H, P))
    bm = jax.random.normal(ks[3], (5, G, N))
    cm = jax.random.normal(ks[4], (5, G, N))
    assert ssm_update.update_supported(pool.shape, N, H // G)
    assert not ssm_update.update_supported((12, 4 * 16, 8), 16, 2)
    p1, y1 = ssm_update.ssm_decode_update(pool, idx, a, dtx, bm, cm)
    p2, y2 = ssm_update._reference(pool, idx, a, dtx, bm, cm)
    scale = float(jnp.max(jnp.abs(y2)))
    assert float(jnp.max(jnp.abs(p1[:11] - p2[:11]))) < 1e-5
    assert float(jnp.max(jnp.abs(y1[:3] - y2[:3]))) < 1e-6 * scale + 1e-4
    # the rows nobody named are untouched
    assert jnp.array_equal(p1[jnp.asarray([0, 2, 4, 5, 6, 8, 9, 10])],
                           pool[jnp.asarray([0, 2, 4, 5, 6, 8, 9, 10])])


def _bucket(pack, n_live, B=6):
    """A decode step's operands at a bucket of `B` rows, the first `n_live`
    on slots of their own and the padding on the scratch slot 11; heads of
    128 lanes (`pack` 1) or two of 64 side by side (`pack` 2)."""
    ks = jax.random.split(jax.random.PRNGKey(46), 6)
    H, N, P, G = 16 * pack, 128, 128 // pack, 2
    pool = jax.random.normal(ks[0], (12, H // pack * N, pack * P))
    idx = jnp.where(jnp.arange(B) < n_live,
                    jnp.asarray([3, 7, 1, 5, 9, 2]), 11).astype(jnp.int32)
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (B, H)))
    dtx = jax.random.normal(ks[2], (B, H, P))
    bm = jax.random.normal(ks[3], (B, G, N))
    cm = jax.random.normal(ks[4], (B, G, N))
    assert ssm_update.update_supported(pool.shape, N, H // G // pack)
    return pool, idx, a, dtx, bm, cm


@pytest.mark.parametrize("pack", [1, 2])
@pytest.mark.parametrize("n_live", [1, 3, 6])
def test_ssm_decode_update_moves_a_steps_live_rows_only(n_live, pack,
                                                        monkeypatch):
    """`n_live` of a bucket's 6 rows carry a request (1, B - 3, B). On each
    arm (the kernel through the interpreter, the plain form) the live rows'
    slots and `y` are, bit for bit, what a step of the live rows ALONE
    leaves on that arm; the scratch slot and every slot nobody named keep
    their bytes; a padding row's `y` is zeros. The two arms agree on the
    WHOLE pool and the whole `y` to rounding (the kernel contracts `a * S
    + B * dtx` as the plain form does not)."""
    monkeypatch.setattr(ssm_update, "INTERPRET", True)
    pool, idx, *rest = _bucket(pack, n_live)
    live = np.asarray(idx[:n_live])
    other = np.setdiff1d(np.arange(12), live)           # the scratch slot too
    arms = []
    for update in (ssm_update.ssm_decode_update, ssm_update._reference):
        p1, y1 = update(pool, idx, *rest, n_live=n_live)
        alone, y_alone = update(pool, idx[:n_live],
                                *(r[:n_live] for r in rest))
        np.testing.assert_array_equal(p1, alone)
        np.testing.assert_array_equal(y1[:n_live], y_alone)
        assert not np.any(np.asarray(y1[n_live:]))
        np.testing.assert_array_equal(p1[other], pool[other])
        assert not np.any(np.all(np.asarray(p1[live] == pool[live]),
                                 axis=(1, 2)))
        arms.append((p1, y1))
    (p1, y1), (p2, y2) = arms
    np.testing.assert_allclose(p1, p2, atol=1e-5)
    np.testing.assert_allclose(y1, y2, rtol=1e-6,
                               atol=1e-6 * float(jnp.max(jnp.abs(y2))) + 1e-4)


@pytest.mark.parametrize("pack", [1, 2])
def test_ssm_decode_update_with_no_live_row_is_a_step_of_one(pack,
                                                             monkeypatch):
    """A count of 0 (the warm-up's step) is held to 1: nothing faults, and
    row 0 is updated on both arms."""
    monkeypatch.setattr(ssm_update, "INTERPRET", True)
    pool, idx, *rest = _bucket(pack, 1)
    for update in (ssm_update.ssm_decode_update, ssm_update._reference):
        p0, y0 = update(pool, idx, *rest, n_live=jnp.int32(0))
        p1, y1 = update(pool, idx, *rest, n_live=1)
        np.testing.assert_array_equal(p0, p1)
        np.testing.assert_array_equal(y0, y1)


@pytest.mark.parametrize("n_live", [1, 5, 8])
def test_a_padding_rows_grid_steps_name_the_last_live_steps_blocks(n_live):
    """What takes the padding rows' traffic away cannot be seen on a CPU;
    this pins it: for every grid step of a row `b >= n_live`, the index
    maps of the state (in and out), `a`, `dtx`, `B` and `C` return the
    blocks of grid step `(n_live - 1, blocks - 1)`, so the pipeline sees a
    block index that repeats; a live row's steps name their own, and `y`
    is every row's own."""
    B, blocks, hb, per_group = 8, 4, 8, 16
    idx = np.asarray([13, 2, 7, 4, 9, 0, 5, 11], np.int32)
    n = np.asarray([n_live], np.int32)
    specs = ssm_update._specs(blocks, hb, 128, 128, per_group)

    def block(spec, b, j):
        return tuple(int(v) for v in spec.index_map(b, j, idx, n))

    *moved, y_rows = specs
    for b in range(B):
        for j in range(blocks):
            src = (b, j) if b < n_live else (n_live - 1, blocks - 1)
            assert block(y_rows, b, j) == (b, j, 0)
            for spec in moved:
                assert block(spec, b, j) == block(spec, *src)
            state, head_rows, group = (block(s, b, j) for s in moved)
            assert state == (int(idx[src[0]]), src[1], 0)
            assert head_rows == (src[0], src[1], 0)
            assert group == (src[0], src[1] * hb // per_group, 0, 0)


def lockstep(engine, n, kernel, monkeypatch):
    """`n` of eight fixed prompts through `engine(max_inflight=8,
    prefix_cache=False)`, decoding in lockstep (one bucket of 8 every
    step), the two decode kernels through the interpreter or not: (the
    engine, the prompts, the requests, each request's slots of the two
    state pools in every layer as they lie after the run). Shared with
    `test_serving_mixer_moe.py`."""
    monkeypatch.setattr(ssm_update, "INTERPRET", kernel)
    monkeypatch.setattr(conv_update, "INTERPRET", kernel)
    eng = engine(max_inflight=8, prefix_cache=False)
    cfg = eng.cfg
    prompts = _prompts([6] * 8, seed=46)[:n]
    rids = [eng.submit(p, 4) for p in prompts]
    slots = {}
    while eng.has_work():
        eng.step()
        for r in rids:
            if eng.requests[r].sslot is not None:
                slots.setdefault(r, eng.requests[r].sslot)
    assert eng.stats["ssm.decode_row_layers"] \
        == n * cfg.state_layers * eng.stats["decode_steps"] > 0
    per_layer = eng._scope.find_var("kv_cache.ssm").shape[0] \
        // cfg.state_layers
    rows = np.asarray([[l * per_layer + slots[r] for l in
                        range(cfg.state_layers)] for r in rids])
    states = [np.asarray(eng._scope.find_var(name))[rows]
              for name in ("kv_cache.ssm", "kv_cache.conv")]
    return eng, prompts, [eng.requests[r] for r in rids], states


def check_five_of_eight(engine, kernel, monkeypatch, right):
    """Five rows decode in a bucket of 8 (three padding rows a step): they
    are `right(eng, prompts, requests)` by the family's reference, emit the
    tokens the other arm emits and the tokens they emit as five of a FULL
    bucket, and leave their slots of both state pools as the full bucket's
    run does, bit for bit. The padding rows' share is booked on the kernel
    arm only."""
    eng, prompts, done, states = lockstep(engine, 5, kernel, monkeypatch)
    right(eng, prompts, done)
    layer_steps = eng.cfg.state_layers * eng.stats["decode_steps"]
    assert eng.stats["ssm.decode_pad_row_layers"] \
        == (3 * layer_steps if kernel else 0)
    assert eng.stats["ssm.conv_kernel_layer_steps"] \
        == (layer_steps if kernel else 0)
    full, _, done8, states8 = lockstep(engine, 8, kernel, monkeypatch)
    assert full.stats["ssm.decode_pad_row_layers"] == 0
    toks = [list(r.out_tokens) for r in done]
    assert toks == [list(r.out_tokens) for r in done8[:5]]
    for mine, theirs in zip(states, states8):
        np.testing.assert_array_equal(mine, theirs[:5])
    if kernel:
        assert toks == [list(r.out_tokens) for r in
                        lockstep(engine, 5, False, monkeypatch)[2]]


@pytest.mark.parametrize("arm", ["kernel", "xla"])
def test_five_rows_in_a_bucket_of_eight_serve_what_eight_of_eight_do(
        arm, monkeypatch):
    cfg = parallel_ssm_tiny(ssm_heads=16, ssm_head_dim=128, ssm_groups=2,
                            ssm_state=256)

    def right(eng, prompts, done):
        assert max(_gaps(eng, prompts, [list(r.out_tokens)
                                        for r in done])) <= TOL

    check_five_of_eight(lambda **kw: _engine(cfg, **kw), arm == "kernel",
                        monkeypatch, right)


@pytest.mark.parametrize("nh,nkv,padded", [(20, 4, 24), (8, 2, 8),
                                           (48, 8, 48), (64, 8, 64),
                                           (12, 4, 16), (12, 12, 12)])
def test_a_query_group_is_padded_to_whole_sublane_tiles(nh, nkv, padded):
    assert attention_ops._padded_group_heads(nh, 128, nkv * 128) == padded


def test_the_padded_group_runs_the_paged_kernel(monkeypatch):
    """20 query heads over 4 KV heads of 128 run the grouped-query arm of
    the paged decode kernel as groups of 6 (interpreter), and read what the
    XLA path reads."""
    monkeypatch.setattr(ppa, "INTERPRET", True)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    B, nh, nkv, dh, ps, pages, P = 3, 20, 4, 128, 128, 8, 2
    q = jax.random.normal(ks[0], (B, nh, dh))
    kp = jax.random.normal(ks[1], (pages, ps, nkv * dh))
    vp = jax.random.normal(ks[2], (pages, ps, nkv * dh))
    table = jnp.asarray([[0, 3], [5, 1], [2, 7]], jnp.int32)
    lens = jnp.asarray([200, 17, 129], jnp.int32)
    assert attention_ops._paged_arm(q.shape, q.dtype, kp.shape, kp.dtype,
                                    P, 1)[1] is not None
    got = attention_ops.paged_decode_attention_fn(q, kp, vp, table, lens,
                                                  sm_scale=dh ** -0.5)
    with jax.default_matmul_precision("highest"):
        want = attention_ops._paged_attention_reference(
            q, kp, vp, table, lens, dh ** -0.5)
    assert got.shape == (B, nh, dh)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-3


def test_twenty_heads_behind_one_prompt_read_it_once(monkeypatch):
    """The same 20 heads as groups of 6 where two of three rows stand
    behind one prompt of two whole pages (blocks of two pages): the
    dispatch works the step's plan out, the kernel walks its list
    (interpreter) and reads what the XLA path reads; handed the plan the
    stack worked out before its layers, it reads the same."""
    monkeypatch.setattr(ppa, "INTERPRET", True)
    B, nh, nkv, dh, ps, pages, P = 3, 20, 4, 128, 128, 12, 4
    monkeypatch.setattr(ppa, "BLOCK_BYTES", 2 * 2 * ps * nkv * dh * 4)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, nh, dh))
    kp = jax.random.normal(ks[1], (pages, ps, nkv * dh))
    vp = jax.random.normal(ks[2], (pages, ps, nkv * dh))
    table = jnp.asarray([[4, 9, 0, 3], [5, 1, 6, 0], [4, 9, 2, 7]], jnp.int32)
    lens = jnp.asarray([400, 300, 512], jnp.int32)
    assert attention_ops.paged_decode_walks(q.shape, q.dtype, kp.shape,
                                            kp.dtype, P)
    # rows 0 and 2 share pages 4, 9: one block read once, then two pages
    # (one block) each of their own; row 1 its three pages (two blocks)
    assert ppa.walk_counts(table, lens, kp.shape, 4) == {
        "pages": 2 + 2 + 2 + 3, "tokens": 256 + 144 + 256 + 300,
        "blocks": 1 + 1 + 1 + 2, "shared": True}
    got = attention_ops.paged_decode_attention_fn(q, kp, vp, table, lens,
                                                  sm_scale=dh ** -0.5)
    plan = attention_ops.paged_decode_plan_fn(q.shape, q.dtype, kp, table,
                                              lens)
    again = attention_ops.paged_decode_attention_fn(
        q, kp, vp, table + 0, lens, sm_scale=dh ** -0.5, plan=plan)
    with jax.default_matmul_precision("highest"):
        want = attention_ops._paged_attention_reference(
            q, kp, vp, table, lens, dh ** -0.5)
    assert got.shape == (B, nh, dh)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-3
    assert np.array_equal(np.asarray(got), np.asarray(again))


def test_rows_behind_one_prompt_through_the_walking_kernel(monkeypatch):
    """The scanned stack end to end with 20 heads of 128 over 4 KV heads in
    pages of 128 (interpreter; blocks of two pages; the one page bucket of
    five is no whole number of blocks): three rows behind one prompt of two
    whole pages read through the plan the stack closes its layer over. The
    logits are the reference's and the engine booked the shared block once
    and a page of its own a row."""
    monkeypatch.setattr(ppa, "INTERPRET", True)
    monkeypatch.setattr(ppa, "BLOCK_BYTES", 2 * 2 * 128 * 4 * 128 * 4)
    cfg = parallel_ssm_tiny(attn_head_dim=128, num_heads=20, num_kv_heads=4,
                            num_layers=2, max_position=640,
                            prefill_chunk=128)
    eng = _engine(cfg, page_size=128, pool_pages=24)
    prompts = _prompts([3, 5, 9], seed=6, shared=256)
    _serve(eng, [prompts[0][:256] + [1]], out=1)    # the prompt is cached
    eng.reset_stats()
    before = dict(attention_ops.dispatch_counts())
    outs = _serve(eng, prompts, out=3)
    assert max(_gaps(eng, prompts, outs)) < 1e-5
    ran = {k[2] for k, n in attention_ops.dispatch_counts().items()
           if k[0] == "paged" and n != before.get(k, 0)}
    assert ran == {"pallas_paged"}
    st = eng.stats
    assert st["decode_signatures"] == {(4, 5)}
    assert st["decode_context_pages"] == st["decode_steps"] * (2 + 3)
    assert st["decode_grid_steps"] == st["decode_steps"] * (1 + 3)


# -- the slot pool and its snapshots -------------------------------------------


def test_a_resumed_snapshot_equals_a_cold_prefill():
    prompts = _prompts([5, 9, 14], shared=16, seed=4)
    cold = [_serve(_engine(prefix_cache=False), [p])[0] for p in prompts]
    eng = _engine()
    _serve(eng, [prompts[0][:16] + [1, 2, 3]], out=2)   # the snapshots
    assert eng.prefix_cache.snapshots_held == 2
    warm = _serve(eng, prompts, audit=True)
    assert warm == cold
    assert eng.stats["state.restores"] == 3
    assert eng.stats["state.recomputed_tokens"] == 0
    assert eng.stats["prefix_hit_tokens"] == 3 * 16


def test_an_evicted_snapshot_falls_back_to_a_shallower_boundary_or_none():
    prompts = _prompts([5, 9], shared=24, seed=5)
    cold = [_serve(_engine(prefix_cache=False), [p])[0] for p in prompts]
    eng = _engine()
    _serve(eng, prompts[:1], out=2)             # snapshots at 8, 16, 24
    assert eng.prefix_cache.snapshots_held == 3
    path = eng.prefix_cache._path(prompts[0][:24])
    # the deepest snapshot goes: the hit falls back to 16, 8 tokens re-run
    eng.state_pool.release([path[5].snap])
    path[5].snap = None
    eng.prefix_cache.snapshots_held -= 1
    assert _serve(eng, prompts[1:], audit=True) == cold[1:]
    assert eng.stats["state.recomputed_tokens"] == 8
    assert eng.stats["state.restores"] == 1
    # every snapshot goes: a hit has nowhere to resume and runs cold
    eng.prefix_cache.strip_snapshots(99)
    assert eng.prefix_cache.snapshots_held == 0
    before = eng.stats["state.restores"]
    assert _serve(eng, prompts[:1], audit=True) == cold[:1]
    assert eng.stats["state.restores"] == before
    assert eng.leaked_pages() == 0


def test_two_requests_on_one_snapshot_do_not_see_each_other():
    """Resuming COPIES: two rows that resume the same snapshot in the same
    step each serve what they would alone, and the snapshot serves a third
    afterwards."""
    prompts = _prompts([5, 7, 6], shared=16, seed=6)
    alone = [_serve(_engine(prefix_cache=False), [p])[0] for p in prompts]
    eng = _engine()
    _serve(eng, [prompts[0][:16] + [1, 2, 3]], out=2)
    assert _serve(eng, prompts[:2], audit=True) == alone[:2]
    slots = {r.sslot for r in eng.requests.values() if r.sslot is not None}
    assert not slots
    assert _serve(eng, prompts[2:]) == alone[2:]
    assert eng.stats["state.restores"] == 3


def test_a_preempted_and_resumed_row_equals_an_undisturbed_one():
    prompts = _prompts([9, 13, 11, 12], seed=7)
    calm = _serve(_engine(), prompts, out=12)
    # a pool too small for four rows' growth holds the later ones in the
    # queue; the youngest that runs is preempted by hand, its slot and
    # pages released, and re-admitted later
    eng = _engine(pool_pages=17)
    with preempting(eng):
        pressed = _serve(eng, prompts, out=12, audit=True)
    assert eng.stats["preemptions"] > 0
    assert pressed == calm
    assert eng.leaked_pages() == 0


def test_slots_never_leak_over_a_long_run_with_evictions():
    """200 steps of arrivals behind three shared prompts with 7 slots (4
    live, 1 scratch, 2 snapshots) and a pool the prefix cache overflows:
    snapshots are evicted, the audit is clean after every step, and in the
    end only the scratch slot and the cache's snapshots are held."""
    eng = _engine(pool_pages=48)
    assert eng.state_pool.num_pages == 7
    rng = np.random.default_rng(8)
    heads = [rng.integers(1, 97, 16).tolist() for _ in range(3)]
    live, served = [], 0
    for step in range(200):
        if len(live) < 6 and rng.random() < 0.5:
            prompt = heads[int(rng.integers(3))] \
                + rng.integers(1, 97, int(rng.integers(2, 20))).tolist()
            live.append(eng.submit(prompt, int(rng.integers(1, 8))))
        if eng.has_work():
            eng.step()
        problems, _ = eng.audit_pool()
        assert not problems, (step, problems)
        for rid in [r for r in live if eng.requests[r].state == "finished"]:
            eng.pop_result(rid)
            live.remove(rid)
            served += 1
    eng.run_until_drained()
    assert served > 30
    assert eng.stats["state.snapshot_evictions"] > 0
    assert eng.leaked_pages() == 0
    assert eng.state_pool.pages_in_use \
        == 1 + eng.prefix_cache.snapshots_held
    assert eng.stats["peak_state_slots_in_use"] <= 7


def test_a_leaked_or_doubly_owned_slot_fails_the_audit():
    eng = _engine()
    _serve(eng, _prompts([5], shared=16), out=2)
    assert eng.audit_pool()[0] == []
    leaked = eng.state_pool.allocate(1)             # nobody's slot
    assert any("state pool" in p for p in eng.audit_pool()[0])
    assert eng.leaked_pages() == 1
    eng.state_pool.release(leaked)
    rids = [eng.submit(p, 8) for p in _prompts([6, 7], seed=9)]
    eng.step()
    a, b = (eng.requests[r] for r in rids)
    assert a.sslot is not None and a.sslot != b.sslot
    keep, b.sslot = b.sslot, a.sslot                # two rows, one slot
    assert any("state pool" in p for p in eng.audit_pool()[0])
    b.sslot = keep
    assert eng.audit_pool()[0] == []


def test_recovery_rebuilds_the_slot_pool():
    eng = _engine()
    prompts = _prompts([5, 9], shared=16, seed=10)
    want = _serve(_engine(), prompts)
    rids = [eng.submit(p, 6) for p in prompts]
    eng.step()
    eng._recover("test")
    assert eng.state_pool.pages_in_use == 1         # the scratch slot
    eng.run_until_drained()
    assert [eng.pop_result(r) for r in rids] == want
    assert eng.leaked_pages() == 0


def test_the_prefix_cache_hangs_resumes_and_gives_up_snapshots():
    pool, slots = PagedKVPool(16, 4), PagedKVPool(4, 1)
    cache = PrefixCache(pool, state_pool=slots)
    tokens = list(range(1, 13))
    pages = pool.allocate(3)
    cache.insert(tokens, pages)
    (s1,), (s3,) = slots.allocate(1), slots.allocate(1)
    cache.hang_snapshot(cache.snapshot_block(tokens, 1), s1)
    cache.hang_snapshot(cache.snapshot_block(tokens, 3), s3)
    assert cache.snapshot_block(tokens, 3) is None      # one a block
    assert cache.snapshot_block(tokens + [1, 2, 3, 4], 4) is None
    assert cache.snapshot_block(tokens, 2).snap is None
    # the limit keeps the last token out of the hit; block 2 holds none
    assert cache.match_snapshot(tokens, 3) == (pages, s3, 0)
    assert cache.match_snapshot(tokens, 2) == (pages[:1], s1, 2)
    assert cache.match_snapshot([9] * 12, 3) == ([], None, 0)
    # least recently resumed first; a pinned snapshot stays
    slots.share([s1])
    assert cache.strip_snapshots(2) == 1 and cache.snapshots_held == 1
    assert cache.match_snapshot(tokens, 3) == (pages[:1], s1, 2)
    slots.release([s1])
    # a block's snapshot goes with its pages
    pool.release(pages)
    assert cache.flush() == 3
    assert slots.pages_in_use == 0 and cache.evicted_snapshots == 1


# -- the wrong mechanisms ------------------------------------------------------


def _fault_drive():
    """Four requests behind one shared prompt, padded windows, 40 tokens
    out each (the mildest fault, a state rounded to bfloat16, moves a
    float32 engine's choice at a near tie only: this seed's drive holds
    one)."""
    cfg = parallel_ssm_tiny()
    eng = _engine(cfg)
    served = ssm_faults.drive(eng, cfg, 16, [5, 6, 5, 7], 40, 20)
    return eng, served


def test_the_right_engine_passes_the_fault_drive():
    eng, served = _fault_drive()
    assert max(_gaps(eng, *zip(*served))) < 1e-5
    assert eng.stats["state.restores"] == 4


@pytest.mark.parametrize("fault", sorted(ssm_faults.FAULTS))
def test_a_planted_fault_fails_the_check(fault):
    with ssm_faults.FAULTS[fault]():
        eng, served = _fault_drive()
    assert eng.stats["prefix_hit_tokens"] == 4 * 16
    assert max(_gaps(eng, *zip(*served))) > TOL, fault
