"""The "mixer_moe" block family (layers that are a Mamba-2 mixer, an
attention or latent experts ALONE, laid out by a pattern) behind
ServingEngine, at a tiny size on the CPU: the engine against the plain
reference (`benchmark/reference/nemotron3_lm.py`), each layer kind against
its equation, the packed slot pool and its snapshots, both new kernel arms
through the Pallas interpreter, the shares of the experts adding up to the
uncut layer, and the wrong mechanisms of `tools/mixer_faults.py`, which
must each fail the same check."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import nemotron3_lm as ref  # noqa: E402
from paddle_tpu.ops import mixer_moe_ops as ops  # noqa: E402
from paddle_tpu.ops.decoder_common import group_limited_router_fn  # noqa: E402
from paddle_tpu.ops.pallas_kernels import moe_experts as pme  # noqa: E402
from paddle_tpu.ops.pallas_kernels import ssm_update  # noqa: E402
from paddle_tpu.serving import DecoderConfig, ServingEngine  # noqa: E402
from paddle_tpu.serving import model as sv_model  # noqa: E402
from paddle_tpu.serving.model import mixer_moe_tiny  # noqa: E402
from test_serving_ssm import check_five_of_eight  # noqa: E402
from tools import mixer_faults  # noqa: E402
from serving_helpers import preempting  # noqa: E402

TOL = 1e-3          # the rehearsal configuration's tolerances
HI = jax.lax.Precision.HIGHEST


def _engine(cfg=None, **kw):
    kw = dict(dict(page_size=4, pool_pages=128, max_inflight=4, seed=3,
                   prefix_cache=True, draft_k=0), **kw)
    return ServingEngine(cfg or mixer_moe_tiny(), **kw)


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


def _tokens(done):
    return [list(r.out_tokens) for r in done]


def _graded(eng, prompts, done):
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    return ref.check_sequences(
        params, [(p, r.out_tokens, r.routes) for p, r in zip(prompts, done)],
        eng.cfg)


def _assert_right(eng, prompts, done):
    cfg = eng.cfg
    for r, g in zip(done, _graded(eng, prompts, done)):
        assert r.routes.shape == (r.cache_len, cfg.routed_layers,
                                  cfg.experts_per_token)
        assert g["gap"] <= 1e-5 and g["route_margin"] <= 1e-5, g


def _stack(cfg, params, seq):
    geom = ops.Geometry(**sv_model._mixer_geometry(cfg))
    return ops.mixer_moe_stack_fn(
        "full", jnp.asarray(seq)[None], jnp.arange(len(seq))[None],
        params["emb"], params["head"], params["final_norm"], params["norm"],
        {k: params["mix." + k] for k in ops.MIXER_PARAMS},
        {k: params["attn." + k] for k in ops.ATTENTION_PARAMS},
        {k: params["moe." + k] for k in ops.MOE_PARAMS},
        (params["w1"], params["w2"]), geom)


# -- the engine against the reference ---------------------------------------


def test_prefill_then_decode_equals_the_references_forward():
    """Float32: every served token is the reference's best token (logits,
    the engine's routes followed and leaving no margin), and the stack's
    own dense forward gives the reference's logits at every position."""
    eng = _engine()
    prompts = _prompts([5, 11, 3, 9, 20], shared=16)
    done = _serve(eng, prompts, audit=True)
    _assert_right(eng, prompts, done)
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    seq = np.asarray(prompts[4] + list(done[4].out_tokens))
    want = np.asarray(ref.all_logits(params, seq, eng.cfg))
    assert [int(t) for t in want[len(prompts[4]) - 1:-1].argmax(-1)] \
        == list(done[4].out_tokens)
    got = _stack(eng.cfg, params, seq)
    assert float(np.max(np.abs(np.asarray(got["logits"][0]) - want))) < 1e-4
    np.testing.assert_array_equal(np.asarray(got["routes"][0])[:-1],
                                  done[4].routes[:len(seq) - 1])
    assert eng.leaked_pages() == 0
    assert eng.stats["state.restores"] == 4
    assert eng.stats["state.snapshots"] >= 2
    # the three pools answer by the count of their kind: 3 mixers, 3 expert
    # layers, 1 attention layer of the 7
    st = eng.stats
    assert st["ssm.decode_layer_steps"] == 3 * st["decode_steps"]
    assert st["moe.layer_steps"] == 3 * st["decode_steps"]
    slots = eng.state_pool.num_pages
    assert eng._scope.find_var("kv_cache.ssm").shape == (3 * slots, 2 * 16,
                                                         2 * 8)
    assert eng._scope.find_var("kv_cache.k").shape[0] == 1 * 128
    # a quarter... here a half of the experts are held: 4 of 8
    assert 0.3 < st["moe.held_pairs"] / st["moe.routed_pairs"] < 0.7


def test_bfloat16_serves_within_the_tolerances_form():
    eng = _engine(mixer_moe_tiny(dtype="bfloat16"))
    prompts = _prompts([5, 11, 9], shared=16)
    done = _serve(eng, prompts)
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    spread = float(np.std(np.asarray(ref.all_logits(
        params, np.asarray(prompts[0] + list(done[0].out_tokens)),
        eng.cfg))))
    assert max(g["gap"] for g in _graded(eng, prompts, done)) < 0.1 * spread


def test_a_prompt_in_chunks_with_a_snapshot_and_a_restore():
    """A prompt in chunks leaves snapshots on the blocks its chunks end;
    requests behind the same prefix resume from them (a copy of the slot)
    and serve what a cold engine serves."""
    prompts = _prompts([5, 9, 14], shared=16, seed=4)
    cold = [_tokens(_serve(_engine(prefix_cache=False), [p]))[0]
            for p in prompts]
    eng = _engine()
    _serve(eng, [prompts[0][:16] + [1, 2, 3]], out=2)   # the snapshots
    assert eng.prefix_cache.snapshots_held == 2
    warm = _serve(eng, prompts, audit=True)
    assert _tokens(warm) == cold
    _assert_right(eng, prompts, warm)
    assert eng.stats["state.restores"] == 3
    assert eng.stats["state.recomputed_tokens"] == 0
    assert eng.stats["prefix_hit_tokens"] == 3 * 16
    whole = _serve(_engine(mixer_moe_tiny(prefill_chunk=32)), prompts)
    assert _tokens(whole) == cold


def test_a_preempted_and_resumed_row_equals_an_undisturbed_one():
    prompts = _prompts([9, 13, 11, 12], seed=7)
    calm = _tokens(_serve(_engine(), prompts, out=12))
    # a pool too small for four rows' growth holds the later ones in the
    # queue; the youngest that runs is preempted by hand, its slot and
    # pages released, and re-admitted later
    eng = _engine(pool_pages=17)
    with preempting(eng):
        pressed = _serve(eng, prompts, out=12, audit=True)
    assert eng.stats["preemptions"] > 0
    assert _tokens(pressed) == calm
    _assert_right(eng, prompts, pressed)
    assert eng.leaked_pages() == 0


@pytest.mark.parametrize("pattern,kv_layers", [("M*EM*E", 2), ("MEME", 0)],
                         ids=["two_attention_layers", "no_attention_layer"])
def test_a_pattern_with_other_counts_of_attention_layers(pattern, kv_layers):
    cfg = mixer_moe_tiny(num_layers=len(pattern), layer_pattern=pattern)
    eng = _engine(cfg, pool_pages=64)
    assert eng._scope.find_var("kv_cache.k").shape[0] == kv_layers * 64
    prompts = _prompts([7, 19], shared=8, seed=8)
    done = _serve(eng, prompts, audit=True)
    _assert_right(eng, prompts, done)
    assert done[0].routes.shape[1] == pattern.count("E")


def test_a_config_that_names_no_plan_is_refused():
    with pytest.raises(ValueError, match="layer_pattern"):
        mixer_moe_tiny(layer_pattern="MEM*EM")           # 6 of 7 layers
    with pytest.raises(ValueError, match="layer_pattern"):
        mixer_moe_tiny(layer_pattern="MXM*EME")
    with pytest.raises(ValueError, match="layer_pattern"):
        mixer_moe_tiny(num_layers=3, layer_pattern="M*M")    # no experts
    with pytest.raises(ValueError, match="latent_size"):
        mixer_moe_tiny(latent_size=0)
    with pytest.raises(ValueError, match="experts_held"):
        mixer_moe_tiny(experts_held=9)
    with pytest.raises(ValueError, match="whole pages"):
        _engine(mixer_moe_tiny(prefill_chunk=6))


def test_the_88_layer_configuration_builds_at_tiny_widths():
    pattern = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
               "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    cfg = mixer_moe_tiny(num_layers=88, layer_pattern=pattern)
    assert (cfg.state_layers, cfg.routed_layers) == (40, 40)
    eng = _engine(cfg, pool_pages=32, max_inflight=2)
    slots = eng.state_pool.num_pages
    assert eng._scope.find_var("kv_cache.ssm").shape[0] == 40 * slots
    assert eng._scope.find_var("kv_cache.k").shape[0] == 8 * 32
    assert eng._scope.find_var("dec.layers.w1").shape == (40, 4, 16, 16)
    (done,) = _serve(eng, _prompts([6]), out=2)
    assert len(done.out_tokens) == 2 and done.routes.shape[1] == 40


# -- each layer kind against its equation -----------------------------------


def _one_kind(pattern, seed=11):
    cfg = mixer_moe_tiny(num_layers=len(pattern), layer_pattern=pattern)
    eng = _engine(cfg, pool_pages=16, max_inflight=2, seed=seed)
    params = ref.read_params(eng._scope.find_var, cfg)
    tok = np.asarray(_prompts([13], seed=seed)[0])
    x0 = np.asarray(params["emb"])[tok].astype(np.float64)
    gain = np.asarray(params["norm"])[0].astype(np.float64)
    xn = x0 / np.sqrt((x0 * x0).mean(-1, keepdims=True) + 1e-5) * gain
    return cfg, params, tok, x0, xn


def _after_layer0(cfg, params, tok):
    """The residual stream after layer 0 by the stack: the final norm
    undone (its gain and RMS are known)."""
    geom = ops.Geometry(**sv_model._mixer_geometry(cfg))
    one = geom._replace(plan=geom.plan[:1])
    head = jnp.eye(cfg.hidden_size, dtype=jnp.float32)
    out = ops.mixer_moe_stack_fn(
        "full", jnp.asarray(tok)[None], jnp.arange(len(tok))[None],
        params["emb"], head, jnp.ones((cfg.hidden_size,), jnp.float32),
        params["norm"],
        {k: params["mix." + k] for k in ops.MIXER_PARAMS},
        {k: params["attn." + k] for k in ops.ATTENTION_PARAMS},
        {k: params["moe." + k] for k in ops.MOE_PARAMS},
        (params["w1"], params["w2"]), one)
    return np.asarray(out["logits"][0], np.float64)


def _unit(x):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)


def test_an_attention_layer_alone_equals_its_equation():
    """softmax(q k^T / sqrt(d)) v over the past, grouped queries, NO rotary:
    written out in numpy, float64."""
    cfg, p, tok, x0, xn = _one_kind("*ME")
    nh, nkv, dh = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    T = len(tok)
    q = (xn @ np.asarray(p["attn.wq"][0], np.float64)).reshape(T, nh, dh)
    k = (xn @ np.asarray(p["attn.wk"][0], np.float64)).reshape(T, nkv, dh)
    v = (xn @ np.asarray(p["attn.wv"][0], np.float64)).reshape(T, nkv, dh)
    o = np.zeros((T, nh, dh))
    for h in range(nh):
        g = h // (nh // nkv)
        for t in range(T):
            s = q[t, h] @ k[:t + 1, g].T / np.sqrt(dh)
            w = np.exp(s - s.max())
            o[t, h] = (w / w.sum()) @ v[:t + 1, g]
    want = x0 + o.reshape(T, -1) @ np.asarray(p["attn.wo"][0], np.float64)
    np.testing.assert_allclose(_after_layer0(cfg, p, tok), _unit(want),
                               atol=1e-4)


def test_a_mixer_layer_alone_equals_its_equation():
    """The recurrence token by token, head by head, in numpy float64."""
    cfg, p, tok, x0, xn = _one_kind("ME*")
    Hs, P, G, N, K = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state, cfg.ssm_conv)
    I, T = Hs * P, len(tok)
    m = {k: np.asarray(p["mix." + k][0], np.float64)
         for k in ops.MIXER_PARAMS}
    proj = xn @ m["w_in"]
    z, xbc, dt = proj[:, :I], proj[:, I:I + I + 2 * G * N], \
        proj[:, 2 * I + 2 * G * N:]
    ext = np.concatenate([np.zeros((K - 1, xbc.shape[1])), xbc])
    conv = m["conv_b"] + sum(m["conv_w"][:, j] * ext[j:j + T]
                             for j in range(K))
    xbc = conv / (1 + np.exp(-conv))
    xs = xbc[:, :I].reshape(T, Hs, P)
    bm = xbc[:, I:I + G * N].reshape(T, G, N)
    cm = xbc[:, I + G * N:].reshape(T, G, N)
    dt = np.log1p(np.exp(dt + m["dt_bias"]))
    a = np.exp(-dt * np.exp(m["a_log"]))
    y = np.zeros((T, Hs, P))
    for h in range(Hs):
        g, S = h // (Hs // G), np.zeros((N, P))
        for t in range(T):
            S = a[t, h] * S + np.outer(bm[t, g], dt[t, h] * xs[t, h])
            y[t, h] = cm[t, g] @ S + m["d_skip"][h] * xs[t, h]
    y = y.reshape(T, I) * (z / (1 + np.exp(-z)))        # the gate first
    grp = y.reshape(T, G, -1)
    grp = grp / np.sqrt((grp * grp).mean(-1, keepdims=True) + 1e-5)
    want = x0 + (grp.reshape(T, I) * m["ssm_norm"]) @ m["w_out"]
    np.testing.assert_allclose(_after_layer0(cfg, p, tok), _unit(want),
                               atol=1e-4)


def test_an_expert_layer_alone_equals_its_equation():
    """Sigmoid scores, the k largest of score + bias, weights scaling x
    s / sum s, W2 relu(W1 u)^2 in the latent over the HELD experts, the way
    out of the latent, the shared expert on the hidden: numpy float64."""
    cfg, p, tok, x0, xn = _one_kind("EM*")
    k, held = cfg.experts_per_token, cfg.held_experts
    m = {key: np.asarray(p["moe." + key][0], np.float64)
         for key in ops.MOE_PARAMS}
    w1, w2 = (np.asarray(p[key][0], np.float64) for key in ("w1", "w2"))
    s = 1 / (1 + np.exp(-(xn @ m["router_w"])))
    u = xn @ m["w_dn"]
    r = np.zeros_like(u)
    for t in range(len(tok)):
        chosen = np.argsort(-(s[t] + m["router_bias"]), kind="stable")[:k]
        for e in chosen:
            if e < held:
                w = cfg.routed_scaling * s[t, e] / s[t, chosen].sum()
                r[t] += w * (np.maximum(u[t] @ w1[e], 0) ** 2) @ w2[e]
    shared = (np.maximum(xn @ m["shared_in"], 0) ** 2) @ m["shared_out"]
    want = x0 + r @ m["w_up"] + shared
    np.testing.assert_allclose(_after_layer0(cfg, p, tok), _unit(want),
                               atol=1e-4)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Two chips of 4 experts each: their routed parts IN THE LATENT, with
    the projections, the shared expert and the residual counted once, are
    the uncut layer's output (the reference holding all 8 experts)."""
    cfg, p, tok, x0, xn = _one_kind("EM*")
    whole = mixer_moe_tiny(num_layers=3, layer_pattern="EM*",
                           experts_held=0)
    eng = _engine(whole, pool_pages=16, max_inflight=2, seed=11)
    full = ref.read_params(eng._scope.find_var, whole)
    assert full["w1"].shape[1] == 8
    # the same draw gives the first chip's experts the same weights
    np.testing.assert_array_equal(np.asarray(full["moe.router_w"]),
                                  np.asarray(p["moe.router_w"]))
    z = jnp.asarray(xn, jnp.float32)
    moe = {k: full["moe." + k][0] for k in ops.MOE_PARAMS}
    ids, cw = group_limited_router_fn(
        z, moe["router_w"], moe["router_bias"], cfg.experts_per_token, 1, 1,
        cfg.routed_scaling)
    u = jnp.dot(z, moe["w_dn"], precision=HI)
    parts = [ops.latent_experts_fn(u, cw[:, lo:lo + 4],
                                   full["w1"][:, lo:lo + 4],
                                   full["w2"][:, lo:lo + 4], layer=0)
             for lo in (0, 4)]
    assert all(float(jnp.max(jnp.abs(part))) > 0.01 for part in parts)
    shared = jnp.dot(ops.relu2_fn(jnp.dot(z, moe["shared_in"],
                                          precision=HI)),
                     moe["shared_out"], precision=HI)
    summed = x0 + np.asarray(jnp.dot(parts[0] + parts[1], moe["w_up"],
                                     precision=HI) + shared)
    np.testing.assert_allclose(_after_layer0(whole, full, tok),
                               _unit(summed), atol=1e-4)
    # and the first share alone is what the cut engine's layer computes
    geom = ops.Geometry(**sv_model._mixer_geometry(cfg))
    mine, _ = ops.latent_moe_fn(
        z, {k: p["moe." + k][0] for k in ops.MOE_PARAMS},
        (p["w1"], p["w2"]), 0, geom, "decode")
    np.testing.assert_allclose(
        np.asarray(mine),
        np.asarray(jnp.dot(parts[0], moe["w_up"], precision=HI) + shared),
        atol=1e-4)


# -- the two kernel arms, through the interpreter ------------------------------


def test_ssm_decode_update_packed_heads_pallas_matches_reference(monkeypatch):
    """The decode update at heads NARROWER than the state (64 under 128),
    two of a group side by side on the lanes: pool and y against the plain
    form; every row live, two of them on one slot, which is left out."""
    monkeypatch.setattr(ssm_update, "INTERPRET", True)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    H, N, P, G = 32, 128, 64, 2
    pool = jax.random.normal(ks[0], (12, H // 2 * N, 2 * P))
    idx = jnp.asarray([3, 7, 1, 11, 11], jnp.int32)
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (5, H)))
    dtx = jax.random.normal(ks[2], (5, H, P))
    bm = jax.random.normal(ks[3], (5, G, N))
    cm = jax.random.normal(ks[4], (5, G, N))
    assert ops.state_pack(P, H // G) == 2
    assert ssm_update.update_supported(pool.shape, N, H // G // 2)
    # the same heads unpacked are refused: 64 lanes fill no tile
    assert not ssm_update.update_supported((12, H * N, P), N, H // G)
    p1, y1 = ssm_update.ssm_decode_update(pool, idx, a, dtx, bm, cm)
    p2, y2 = ssm_update._reference(pool, idx, a, dtx, bm, cm)
    assert y1.shape == (5, H, P)
    scale = float(jnp.max(jnp.abs(y2)))
    assert float(jnp.max(jnp.abs(p1[:11] - p2[:11]))) < 1e-5
    assert float(jnp.max(jnp.abs(y1[:3] - y2[:3]))) < 1e-6 * scale + 1e-4
    untouched = jnp.asarray([0, 2, 4, 5, 6, 8, 9, 10])
    assert jnp.array_equal(p1[untouched], pool[untouched])
    # the plain form on the packed pool is the plain form on plain states
    plain = ssm_update.unpack_state(pool, H, N).reshape(12, H * N, P)
    p3, y3 = ssm_update._reference(plain, idx, a, dtx, bm, cm)
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ssm_update.pack_state(p3.reshape(12, H, N, P), 2)),
        np.asarray(p2), atol=1e-5)


@pytest.mark.parametrize("arm", ["kernel", "xla"])
def test_five_rows_in_a_bucket_of_eight_serve_what_eight_of_eight_do(
        arm, monkeypatch):
    """At packed heads (64 of 64 under a state of 128, two side by side)
    and a tail of whole tiles, so that both decode kernels take the step:
    `test_serving_ssm.check_five_of_eight`."""
    cfg = mixer_moe_tiny(ssm_heads=64, ssm_head_dim=64, ssm_groups=4,
                         ssm_state=128)
    check_five_of_eight(lambda **kw: _engine(cfg, **kw), arm == "kernel",
                        monkeypatch, _assert_right)


@pytest.mark.parametrize("heads,per_group,head_dim,pack", [
    (128, 16, 64, 2), (32, 16, 128, 1), (4, 2, 8, 2), (16, 4, 32, 4),
    (6, 3, 64, 1)])
def test_heads_narrower_than_the_lanes_share_a_slots_rows(
        heads, per_group, head_dim, pack):
    assert ops.state_pack(head_dim, per_group) == pack
    s = jax.random.normal(jax.random.PRNGKey(1), (3, heads, 16, head_dim))
    slots = ssm_update.pack_state(s, pack)
    assert slots.shape == (3, heads // pack * 16, pack * head_dim)
    assert jnp.array_equal(ssm_update.unpack_state(slots, heads, 16), s)
    if pack > 1:        # head 1 lies beside head 0, on the next lanes
        assert jnp.array_equal(slots[:, :16, head_dim:2 * head_dim], s[:, 1])


@pytest.mark.parametrize("tokens", [5, 40, 300])
def test_moe_relu2_experts_pallas_matches_reference(monkeypatch, tokens):
    """The ungated expert kernel through the interpreter: a share of 8 held
    experts, 3 of 16 a token, layer 1 of a stack of two, in a latent of
    128."""
    monkeypatch.setattr(pme, "INTERPRET", True)
    L, E, held, Z, F, k = 2, 16, 8, 128, 256, 3
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    u = jax.random.normal(ks[0], (tokens, Z))
    w1 = (jax.random.normal(ks[1], (L, held, Z, F)) * Z ** -0.5).astype(
        jnp.bfloat16)
    w2 = (jax.random.normal(ks[2], (L, held, F, Z)) * F ** -0.5).astype(
        jnp.bfloat16)
    vals, ids = jax.lax.top_k(jax.random.uniform(ks[3], (tokens, E)), k)
    cw = jnp.sum(jax.nn.one_hot(ids, E)
                 * (5.0 * vals / vals.sum(-1, keepdims=True))[..., None],
                 1)[:, :held]
    assert pme.experts_supported(u.shape, w1.shape, jnp.bfloat16)
    got = pme.moe_relu2_experts(u, cw, w1, w2, 1, tag="decode")
    want = pme._relu2_reference(u, cw, w1, w2, 1)
    scale = float(jnp.max(jnp.abs(want)))
    assert got.shape == (tokens, Z) and scale > 0.1
    assert float(jnp.max(jnp.abs(got - want))) < 2e-2 * scale
    # and the plain form is the sum it says it is, in float32
    a, b = w1[1].astype(jnp.float32), w2[1].astype(jnp.float32)
    ub = u.astype(jnp.bfloat16).astype(jnp.float32)
    h = jnp.maximum(jnp.einsum("tz,ezf->etf", ub, a, precision=HI), 0.0)
    by_hand = jnp.einsum("etf,efz->tz", h * h * cw.T[:, :, None], b,
                         precision=HI)
    assert float(jnp.max(jnp.abs(want - by_hand))) < 2e-2 * scale


# -- the planted faults ---------------------------------------------------------


def _fault_drive():
    cfg = mixer_moe_tiny()
    eng = _engine(cfg)
    served = mixer_faults.drive(eng, cfg, 16, [5, 6, 5, 7], 40, 20)
    return eng, served


def _worst(eng, served):
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    graded = ref.check_sequences(params, served, eng.cfg)
    return (max(g["gap"] for g in graded),
            max(g["route_margin"] for g in graded))


def test_the_right_engine_passes_the_fault_drive():
    eng, served = _fault_drive()
    gap, margin = _worst(eng, served)
    assert gap < 1e-5 and margin < 1e-5
    assert eng.stats["state.restores"] == 4


@pytest.mark.parametrize("fault", sorted(mixer_faults.FAULTS))
def test_a_planted_fault_fails_the_check(fault):
    with mixer_faults.FAULTS[fault]():
        eng, served = _fault_drive()
    assert eng.stats["prefix_hit_tokens"] == 4 * 16
    gap, margin = _worst(eng, served)
    assert gap > TOL or margin > TOL, fault
