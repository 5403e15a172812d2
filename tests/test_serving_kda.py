"""The "kda_moe" block family (Kimi Delta Attention layers that carry a
matrix state in the slot pool beside a latent-attention layer that keeps a
paged row, a dense SwiGLU or group-limited experts behind either) behind
ServingEngine, at a tiny size on the CPU: the chunked form against the token
recurrence and the reference's, the engine against the plain reference
(`benchmark/reference/ling3_lm.py`), snapshots and restores through both
caches, the one-token kernel through the Pallas interpreter, the four shares
of the experts adding up to the uncut layer, and the wrong mechanisms of
`tools/kda_faults.py`, which must each fail the same check."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import ling3_lm as ref  # noqa: E402
from paddle_tpu.ops import kda_ops as ops  # noqa: E402
from paddle_tpu.ops import latent_moe_ops  # noqa: E402
from paddle_tpu.ops.decoder_common import (  # noqa: E402
    group_limited_router_fn, moe_topk_experts_fn, swiglu_fn)
from paddle_tpu.ops.pallas_kernels import conv_update, kda_update  # noqa: E402
from paddle_tpu.serving import DecoderConfig, ServingEngine  # noqa: E402
from paddle_tpu.serving.model import kda_moe_tiny  # noqa: E402
from tools import kda_faults, mixer_faults  # noqa: E402
from serving_helpers import preempting  # noqa: E402


def _engine(cfg=None, **kw):
    kw = dict(dict(page_size=4, pool_pages=128, max_inflight=4, seed=3,
                   prefix_cache=True, draft_k=0), **kw)
    return ServingEngine(cfg or kda_moe_tiny(), **kw)


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


# -- the mechanism: three forms of one recurrence ---------------------------


@pytest.mark.parametrize("gates", ["drawn", "lower_bound", "zero", "mixed"])
def test_the_chunked_form_is_the_token_recurrence_is_the_references(gates):
    """Chunks of 64 in sub-blocks of 16 from a given state, a padded window
    and a silent tail, against one token after another and against the
    reference's `lax.scan`: with the log decay drawn, pinned at the lower
    bound (a chunk spans e^-320: the underflow case), at 0 and half of
    each."""
    rng = np.random.default_rng(1)
    B, S, H, K, V = 2, 150, 3, 16, 8
    f32 = lambda a: jnp.asarray(a, jnp.float32)             # noqa: E731
    q = ops.l2_norm_fn(f32(rng.standard_normal((B, S, H, K)))) * K ** -0.5
    k = ops.l2_norm_fn(f32(rng.standard_normal((B, S, H, K))))
    v = f32(rng.standard_normal((B, S, H, V)))
    beta = f32(rng.random((B, S, H)))
    s0 = f32(rng.standard_normal((B, H, K, V)))
    log_a = f32({"drawn": -5 * rng.random((B, S, H, K)),
                 "lower_bound": np.full((B, S, H, K), -5.0),
                 "zero": np.zeros((B, S, H, K)),
                 "mixed": np.where(rng.random((B, S, H, K)) < 0.5, -5.0,
                                   -1e-3)}[gates])
    lens = np.asarray([150, 97])
    valid = jnp.arange(S)[None, :] < lens[:, None]
    o_tok, s_tok = ops.kda_token_recurrence_fn(q, k, v, log_a, beta, s0,
                                               valid)
    o_chunk, s_chunk = jax.jit(lambda *a: ops.kda_chunk_scan_fn(
        *a, 64, 16, -5.0, valid))(q, k, v, log_a, beta, s0)
    assert bool(jnp.all(jnp.isfinite(o_chunk)))
    live = np.asarray(valid)[..., None, None]
    assert float(np.max(np.abs(np.where(live, o_tok - o_chunk, 0)))) < 5e-6
    assert float(jnp.max(jnp.abs(s_tok - s_chunk))) < 5e-6
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            n = int(lens[b])
            o_ref, s_ref = ref.delta_recurrence(
                q[b, :n], k[b, :n], v[b, :n], log_a[b, :n], beta[b, :n],
                s0[b])
            assert float(jnp.max(jnp.abs(o_ref - o_chunk[b, :n]))) < 5e-6
            assert float(jnp.max(jnp.abs(s_ref - s_chunk[b]))) < 5e-6


def test_kda_decode_update_pallas_matches_reference(monkeypatch):
    """The one-token kernel through the interpreter against its jnp form,
    at the served head (128 x 128): 5 live rows of a bucket of 8 in a pool
    of 12 slots; the padding rows' slots are left alone and their `o` is
    zeros."""
    monkeypatch.setattr(kda_update, "INTERPRET", True)
    rng = np.random.default_rng(0)
    B, H, K, V, rows = 8, 8, 128, 128, 12
    assert kda_update.update_supported((rows, H * K, V), K)
    assert not kda_update.update_supported((rows, H * 8, 8), 8)
    pool = jnp.asarray(rng.standard_normal((rows, H * K, V)), jnp.float32)
    idx = jnp.asarray(rng.permutation(rows)[:B], jnp.int32)
    q = rng.standard_normal((B, H, K)).astype(np.float32) * K ** -0.5
    k = rng.standard_normal((B, H, K)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((B, H, V)).astype(np.float32)
    a = np.exp(-5 * rng.random((B, H, K))).astype(np.float32)
    beta = rng.random((B, H)).astype(np.float32)
    got = kda_update.kda_decode_update(pool, idx, q, k, v, a, beta, 5)
    want = kda_update._reference(pool, idx, q, k, v, a, beta, 5)
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-6
    assert float(jnp.max(jnp.abs(got[1][5:]))) == 0.0
    np.testing.assert_array_equal(np.asarray(got[0][idx[5:]]),
                                  np.asarray(pool[idx[5:]]))
    # and the reference IS one step of the recurrence
    o, s = ops.kda_token_recurrence_fn(
        q[:, None], k[:, None], v[:, None], jnp.log(a)[:, None],
        beta[:, None], pool[idx].reshape(B, H, K, V))
    assert float(jnp.max(jnp.abs(o[:5, 0] - want[1][:5]))) < 2e-6
    assert float(jnp.max(jnp.abs(
        s[:5].reshape(5, H * K, V) - want[0][idx[:5]]))) < 2e-6


# -- the engine against the reference ---------------------------------------


def test_prefill_then_decode_equals_the_references_forward():
    """Float32: every served token is the reference's best token (logits,
    the engine's routes followed and leaving no margin), through chunks, a
    snapshot's restore, the slot and the pages; the stack's own dense
    forward gives the reference's logits at every position."""
    eng = _engine()
    prompts = _prompts([5, 11, 3, 9, 20], shared=16)
    done = _serve(eng, prompts, audit=True)
    _assert_right(eng, prompts, done)
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    seq = np.asarray(prompts[4] + list(done[4].out_tokens))
    want = np.asarray(ref.all_logits(params, seq, eng.cfg))
    assert [int(t) for t in want[len(prompts[4]) - 1:-1].argmax(-1)] \
        == list(done[4].out_tokens)
    from paddle_tpu.serving import model as sv_model
    geom = ops.Geometry(**sv_model._kda_geometry(eng.cfg))
    got = ops.kda_moe_stack_fn(
        "full", jnp.asarray(seq)[None], jnp.arange(len(seq))[None],
        params["emb"], params["head"], params["final_norm"], params["norm"],
        *({k: params[prefix + k] for k in keys}
          for _, prefix, keys in sv_model._KDA_GROUPS[:4]),
        tuple(params[k] for k in ops.EXPERT_PARAMS), geom)
    assert float(np.max(np.abs(np.asarray(got["logits"][0]) - want))) < 5e-4
    assert eng.leaked_pages() == 0
    st = eng.stats
    assert st["state.restores"] == 4 and st["state.snapshots"] >= 2
    # the pools answer by the count of their kind: 5 Kimi-Delta layers, 1
    # latent layer, 4 expert layers of the 6
    assert st["kda.decode_layer_steps"] == 5 * st["decode_steps"]
    assert st["kda.scan_layer_steps"] == 5 * st["prefill.chunks"]
    assert st["sparse.layer_steps"] == st["decode_steps"]
    assert st["moe.layer_steps"] == 4 * st["decode_steps"]
    assert st["ssm.decode_layer_steps"] == 0
    slots = eng.state_pool.num_pages
    assert eng._scope.find_var("kv_cache.ssm").shape == (5 * slots, 4 * 8, 8)
    assert eng._scope.find_var("kv_cache.conv").shape == (5 * slots,
                                                          3 * 4 * 24)
    assert eng._scope.find_var("kv_cache.latent").shape == (128, 4, 20)
    assert 0.3 < st["moe.held_pairs"] / st["moe.routed_pairs"] < 0.7


def test_a_prefix_hit_restored_from_a_snapshot_serves_what_a_cold_one_does():
    """A prompt in chunks leaves snapshots on the blocks its chunks end;
    requests behind the same prefix map its pages AND copy the snapshot
    into their own slot, and serve what a cold engine serves."""
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
    assert eng.leaked_pages() == 0


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


@pytest.mark.parametrize("cap", [1, 2])
def test_a_capped_iteration_admits_that_many_and_serves_the_same(cap):
    """`admit_per_step`: an iteration prefills at most that many waiters
    ahead of the rows' step; the queue keeps the rest, in order."""
    prompts = _prompts([9, 5, 13, 7], seed=11)
    free = _tokens(_serve(_engine(), prompts))
    eng = _engine(kda_moe_tiny(admit_per_step=cap))
    rids = [eng.submit(p, 6) for p in prompts]
    eng.step()
    admitted = [eng.requests[r].state != "waiting" for r in rids]
    assert admitted == [i < cap for i in range(4)]
    eng.step()
    assert sum(eng.requests[r].state != "waiting" for r in rids) \
        == min(4, 2 * cap)
    while eng.has_work():
        eng.step()
    assert _tokens([eng.requests[r] for r in rids]) == free
    assert eng.leaked_pages() == 0
    with pytest.raises(ValueError, match="admit_per_step"):
        kda_moe_tiny(admit_per_step=-1)


def test_the_kernels_of_a_decode_step_serve_what_the_jnp_forms_serve(
        monkeypatch):
    """Heads of 128 x 128 and a tail of whole tiles: both in-place kernels
    engage under the interpreter (5 rows in a bucket of 8: live rows only),
    and the engine serves what it serves without them."""
    cfg = kda_moe_tiny(ssm_heads=8, ssm_head_dim=128, ssm_state=128,
                       min_row_bucket=8)
    prompts = _prompts([5, 9, 7, 4, 6], seed=2)
    plain = _tokens(_serve(_engine(cfg, max_inflight=8), prompts, out=4))
    monkeypatch.setattr(kda_update, "INTERPRET", True)
    monkeypatch.setattr(conv_update, "INTERPRET", True)
    eng = _engine(cfg, max_inflight=8)
    assert _tokens(_serve(eng, prompts, out=4)) == plain
    st = eng.stats
    assert st["ssm.conv_kernel_layer_steps"] == st["kda.decode_layer_steps"]
    assert st["kda.decode_pad_row_layers"] > 0


# -- the plan and the shares --------------------------------------------------


def test_the_42_layer_plan_builds_and_a_config_that_names_none_is_refused():
    cfg = kda_moe_tiny(num_layers=42)
    assert cfg.mixer_kinds == ("KKKKKL" * 7) and cfg.latent_layers == 7
    assert cfg.state_layers == 35 and cfg.routed_layers == 40
    assert cfg.mlp_kinds == "DD" + "E" * 40
    assert cfg.recurrent and cfg.latent and not cfg.selects
    assert [kind for kind, _ in ref.plan(cfg)] == [
        {"K": "kda", "L": "mla"}[c] for c in cfg.mixer_kinds]
    for wrong in (dict(layer_group_size=7), dict(q_lora_rank=8),
                  dict(kda_sub_chunk=3), dict(kda_lower_bound=0.5),
                  dict(experts_held=9), dict(dense_layers=6)):
        with pytest.raises(ValueError):
            kda_moe_tiny(**wrong)


def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four chips of 2 experts each (experts 0-1, .., 6-7): their routed
    parts, with the shared expert and the residual counted once, are the
    uncut layer's output (the reference holding all 8 experts)."""
    whole = kda_moe_tiny(num_layers=2, layer_group_size=2, dense_layers=1,
                         experts_held=0)
    eng = _engine(whole, pool_pages=16, max_inflight=2, seed=11)
    full = ref.read_params(eng._scope.find_var, whole)
    assert full["w_gate"].shape[:2] == (1, 8)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((24, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe_layer(full, x, whole, 0)
        p = {k: full["moe." + k][0] for k in ops.MOE_PARAMS}
        z = ref._rms(x, p["ffn_norm"], whole.rms_norm_eps)
        ids, cw = group_limited_router_fn(
            z, p["router_w"], p["router_bias"], 2, 4, 2, 2.5)
        parts = [moe_topk_experts_fn(
            z, cw[:, lo:lo + 2], *(full[k][:, lo:lo + 2]
                                   for k in ops.EXPERT_PARAMS), layer=0)
            for lo in (0, 2, 4, 6)]
        shared = swiglu_fn(z, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
        assert sum(float(jnp.max(jnp.abs(part))) > 0.01
                   for part in parts) >= 3
        np.testing.assert_allclose(np.asarray(x + shared + sum(parts)),
                                   np.asarray(want), atol=1e-4)
        # and the first share alone is what the cut engine's layer computes
        cut = kda_moe_tiny(num_layers=2, layer_group_size=2, dense_layers=1,
                           experts_held=2)
        from paddle_tpu.serving import model as sv_model
        lg = ops.latent_geometry(ops.Geometry(**sv_model._kda_geometry(cut)))
        mine, _ = latent_moe_ops._feed_forward(
            x[None], False, p, tuple(full[k][:, :2]
                                     for k in ops.EXPERT_PARAMS), 0, lg,
            "decode")
        np.testing.assert_allclose(np.asarray(mine[0]),
                                   np.asarray(x + shared + parts[0]),
                                   atol=1e-4)


# -- the wrong mechanisms -----------------------------------------------------


def _fault_drive():
    eng = _engine(pool_pages=256)
    served = mixer_faults.drive(eng, eng.cfg, 16, [2, 3, 5, 6], 8, 5)
    problems, _ = eng.audit_pool()
    assert not problems
    return eng, served


def _worst(eng, served):
    params = ref.read_params(eng._scope.find_var, eng.cfg)
    graded = ref.check_sequences(params, served, eng.cfg)
    return (max(g["gap"] for g in graded),
            max(g["route_margin"] for g in graded))


def test_the_right_engine_passes_the_fault_drive():
    eng, served = _fault_drive()
    gap, margin = _worst(eng, served)
    assert gap <= 1e-5 and margin <= 1e-5
    assert eng.stats["state.restores"] == 4


@pytest.mark.parametrize("fault", sorted(kda_faults.FAULTS))
def test_a_planted_fault_fails_the_check(fault):
    with kda_faults.FAULTS[fault]():
        eng, served = _fault_drive()
    gap, margin = _worst(eng, served)
    assert gap > 1e-3 or margin > 1e-3, (gap, margin)
