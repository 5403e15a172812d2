"""A decode token's convolution, its tail moved on in place in the pool of
tails (`parallel_ssm_ops.conv_token_update_fn`, `pallas_kernels.
conv_update`), at three tails: Falcon-H1's 15,360 values a slot and
Nemotron-H's 30,720, which the pool keeps as whole (8, 128) tiles and the
kernel (here through the interpreter) updates a slot at a time, and the
rehearsals' 288, which stays two-dimensional on XLA's form."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import falcon_h1_lm, nemotron3_lm  # noqa: E402
from paddle_tpu.ops import parallel_ssm_ops as ops  # noqa: E402
from paddle_tpu.ops.pallas_kernels import conv_update  # noqa: E402
from paddle_tpu.serving import DecoderConfig, ServingEngine  # noqa: E402
from paddle_tpu.serving import model as sv_model  # noqa: E402
from paddle_tpu.serving.kv_cache import state_pool_shapes  # noqa: E402

K = 4
# tail -> (channels, the family served at that tail and its state-space
# widths: heads x head_dim + 2 x groups x state channels)
TAILS = {
    15360: (5120, sv_model.parallel_ssm_tiny,
            dict(ssm_heads=32, ssm_head_dim=128, ssm_groups=32)),
    30720: (10240, sv_model.mixer_moe_tiny,
            dict(ssm_heads=128, ssm_head_dim=64, ssm_groups=64)),
    288: (96, sv_model.parallel_ssm_tiny, {}),
}


def _pool_shape(rows: int, tail: int) -> tuple:
    return dict((n, s) for n, s, _ in state_pool_shapes(
        1, rows, 4, 16, 8, tail))["kv_cache.conv"]


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(conv_update, "INTERPRET", True)


def _inputs(tail: int, n: int, rows=(3, 7, 1), slots: int = 12):
    C = TAILS[tail][0]
    ks = jax.random.split(jax.random.PRNGKey(tail), 4)
    pool = jax.random.normal(ks[0], _pool_shape(slots, tail))
    xs = jax.random.normal(ks[1], (len(rows), n, C))
    w = jax.random.normal(ks[2], (C, K)) * 0.5
    b = jax.random.normal(ks[3], (C,))
    return pool, jnp.asarray(rows, jnp.int32), xs, w, b


@pytest.mark.parametrize("tail", sorted(TAILS))
def test_decode_tokens_through_the_pool_equal_the_convolution_of_the_sequence(
        tail, interpreted):
    """n tokens, one a step, leave the outputs and the tail that
    `causal_conv_fn` gives over the whole sequence behind the slots' first
    tails."""
    n = 6
    pool, idx, xs, w, b = _inputs(tail, n)
    C = xs.shape[-1]
    assert ops.conv_update_runs(pool.shape, K) == (tail != 288)
    want, want_tail = ops.causal_conv_fn(
        xs, pool[idx].reshape(len(idx), K - 1, C), w, b)
    got = []
    for t in range(n):
        pool, y = ops.conv_token_update_fn(pool, idx, xs[:, t], w, b)
        got.append(y)
    np.testing.assert_allclose(np.stack(got, 1), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(pool[idx]).reshape(len(idx), K - 1, C), want_tail)


@pytest.mark.parametrize("tail", sorted(TAILS))
def test_padding_rows_on_the_scratch_slot_disturb_no_live_slot(
        tail, interpreted):
    pool, idx, xs, w, b = _inputs(tail, 1, rows=(3, 7, 1, 11, 11, 11))
    new, y = ops.conv_token_update_fn(pool, idx, xs[:, 0], w, b)
    alone, y3 = ops.conv_token_update_fn(pool, idx[:3], xs[:3, 0], w, b)
    np.testing.assert_array_equal(new[:11], alone[:11])
    np.testing.assert_array_equal(y[:3], y3)
    untouched = jnp.asarray([0, 2, 4, 5, 6, 8, 9, 10])
    np.testing.assert_array_equal(new[untouched], pool[untouched])


@pytest.mark.parametrize("tail,taps", [(15360, 4), (30720, 4), (2048, 2),
                                       (4096, 3)])
def test_conv_decode_update_pallas_matches_reference(tail, taps,
                                                     interpreted):
    """The kernel, through the interpreter, against the plain form: the
    pool and the convolved rows; the rows nobody named are untouched."""
    C = tail // (taps - 1)
    ks = jax.random.split(jax.random.PRNGKey(taps), 4)
    pool = jax.random.normal(ks[0], (12, tail // 128, 128))
    idx = jnp.asarray([3, 7, 1, 11, 11], jnp.int32)
    x = jax.random.normal(ks[1], (5, C))
    w, b = jax.random.normal(ks[2], (C, taps)), jax.random.normal(ks[3], (C,))
    assert conv_update.update_supported(pool.shape, taps)
    p1, y1 = conv_update.conv_decode_update(pool, idx, x, w, b)
    p2, y2 = conv_update._reference(pool, idx, x, w, b)
    np.testing.assert_array_equal(p1[:11], p2[:11])
    np.testing.assert_allclose(y1[:3], y2[:3], rtol=1e-5, atol=1e-5)
    untouched = jnp.asarray([0, 2, 4, 5, 6, 8, 9, 10])
    np.testing.assert_array_equal(p1[untouched], pool[untouched])


@pytest.mark.parametrize("n_live", [0, 1, 3, 6])
def test_conv_decode_update_moves_a_steps_live_rows_only(n_live,
                                                         interpreted):
    """`n_live` of a bucket's 6 rows carry a request (1, B - 3, B; 0, the
    warm-up's step, is held to 1): the kernel, through the interpreter, and
    the plain form leave the same WHOLE pool and the same `y`; the live
    rows' are what a step of them alone leaves; the scratch slot and every
    slot nobody named keep their bytes; a padding row's `y` is zeros."""
    C, B, live_rows = 2048, 6, max(n_live, 1)
    ks = jax.random.split(jax.random.PRNGKey(46), 4)
    pool = jax.random.normal(ks[0], (12, (K - 1) * C // 128, 128))
    idx = jnp.where(jnp.arange(B) < live_rows,
                    jnp.asarray([3, 7, 1, 5, 9, 2]), 11).astype(jnp.int32)
    x = jax.random.normal(ks[1], (B, C))
    w, b = jax.random.normal(ks[2], (C, K)), jax.random.normal(ks[3], (C,))
    p1, y1 = conv_update.conv_decode_update(pool, idx, x, w, b,
                                            n_live=jnp.int32(n_live))
    p2, y2 = conv_update._reference(pool, idx, x, w, b, n_live=n_live)
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(y1[live_rows:]))
    assert not np.any(np.asarray(y2[live_rows:]))
    live = np.asarray(idx[:live_rows])
    other = np.setdiff1d(np.arange(12), live)           # the scratch slot too
    np.testing.assert_array_equal(p1[other], pool[other])
    p3, y3 = conv_update.conv_decode_update(pool, idx[:live_rows],
                                            x[:live_rows], w, b)
    np.testing.assert_array_equal(p1, p3)
    np.testing.assert_array_equal(y1[:live_rows], y3)


@pytest.mark.parametrize("n_live", [1, 5, 8])
def test_a_padding_rows_grid_step_names_the_last_live_rows_blocks(n_live):
    """For every row `b >= n_live` the index maps of the slot (in and out)
    and of the token's row return the last live row's blocks, so the
    pipeline sees a block index that repeats and moves nothing (a CPU
    cannot show the DMA going away; this pins what makes it go); `y` is
    every row's own."""
    idx = np.asarray([13, 2, 7, 4, 9, 0, 5, 11], np.int32)
    n = np.asarray([n_live], np.int32)
    slot, token, y_row = conv_update._specs(24, 8, 128)
    for b in range(8):
        src = min(b, n_live - 1)
        assert tuple(int(v) for v in slot.index_map(b, idx, n)) \
            == (int(idx[src]), 0, 0)
        assert tuple(int(v) for v in token.index_map(b, idx, n)) \
            == (src, 0, 0)
        assert tuple(int(v) for v in y_row.index_map(b, idx, n)) == (b, 0, 0)


def test_the_shape_gate_takes_whole_tiles_only():
    assert conv_update.update_supported((800, 240, 128), 4)
    assert conv_update.update_supported((480, 120, 128), 4)
    assert not conv_update.update_supported((24, 288), 4)       # a rehearsal
    assert not conv_update.update_supported((24, 12, 128), 4)   # 4 sublanes
    assert not conv_update.update_supported((24, 24, 64), 4)
    assert not conv_update.update_supported((24, 24, 128), 1)
    # off the chip and outside the interpreter nothing runs a kernel
    assert not ops.conv_update_runs((800, 240, 128), 4)


@pytest.mark.parametrize("name,tiles", [
    ("falcon_h1_34b", (480, 120, 128)),
    ("nemotron3_super_120b", (800, 240, 128)),
    ("rehearse_falcon", None), ("rehearse_nemotron", None)])
def test_state_pool_shapes_keeps_a_benchmark_tail_as_whole_tiles(name, tiles):
    """Both served configurations' pools of tails are whole (8, 128) tiles,
    the same bytes as the row they were; the rehearsals keep the row."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        engine = json.load(f)["engine"]
    cfg = DecoderConfig(**engine["config_kwargs"])
    sizes = ServingEngine.default_sizes(cfg, engine["page_size"],
                                        engine["max_inflight"])
    _, state = sv_model.ssm_pool_geometry(
        cfg, engine["pool_pages"], engine["page_size"],
        sizes["state_slots"])
    (_, ssm, _), (name_, shape, dtype) = state_pool_shapes(*state)
    assert (name_, dtype) == ("kv_cache.conv", "float32")
    assert int(np.prod(shape)) == shape[0] * state[5]
    assert shape[0] == ssm[0] == cfg.state_layers * sizes["state_slots"]
    if tiles is None:
        assert shape == (shape[0], state[5])
    else:
        assert shape == tiles
        assert shape[-1] == 128 and shape[-2] % 8 == 0
    assert ops.conv_update_runs(shape, cfg.ssm_conv) is False


def _serve(eng, prompts, out=5):
    rids = [eng.submit(p, out) for p in prompts]
    while eng.has_work():
        eng.step()
        problems, _ = eng.audit_pool()
        assert not problems, problems
    return [eng.requests[r] for r in rids]


def _worst_gap(eng, prompts, done) -> float:
    """The served tokens against the family's plain reference."""
    if eng.cfg.block == "mixer_moe":
        params = nemotron3_lm.read_params(eng._scope.find_var, eng.cfg)
        return max(max(g["gap"], g["route_margin"])
                   for g in nemotron3_lm.check_sequences(
                       params, [(p, r.out_tokens, r.routes)
                                for p, r in zip(prompts, done)], eng.cfg))
    params = falcon_h1_lm.read_params(eng._scope.find_var, eng.cfg)
    return max(falcon_h1_lm.worst_logit_gaps(
        params, [(p, list(r.out_tokens)) for p, r in zip(prompts, done)],
        eng.cfg))


@pytest.mark.parametrize("tail", sorted(TAILS))
def test_window_decode_snapshot_restore_serves_what_it_served(tail,
                                                             interpreted):
    """An engine at the tail: prompts in windows of 8, decode steps,
    snapshots at the chunk boundaries of a shared prompt and rows RESTORED
    from them serve the reference's tokens, the tokens a cold engine on
    XLA's form serves; the kernel takes every layer step where the pool is
    whole tiles and none where it is a row."""
    C, tiny, widths = TAILS[tail]
    cfg = tiny(**widths)
    rng = np.random.default_rng(tail)
    head = rng.integers(1, 97, 16).tolist()
    prompts = [head + rng.integers(1, 97, n).tolist() for n in (5, 2, 11)]

    def engine(**kw):
        return ServingEngine(cfg, page_size=4, pool_pages=128,
                             max_inflight=4, seed=3, draft_k=0, **kw)

    eng = engine(prefix_cache=True)
    shape = eng._scope.find_var("kv_cache.conv").shape
    assert int(np.prod(shape[1:])) == tail == (K - 1) * C
    assert (len(shape) == 3) == (tail != 288)
    _serve(eng, [head + [1, 2, 3]], out=2)              # the snapshots
    done = _serve(eng, prompts)
    assert eng.stats["state.restores"] == 3
    assert eng.stats["state.recomputed_tokens"] == 0
    assert _worst_gap(eng, prompts, done) <= 1e-4
    steps = eng.stats["ssm.decode_layer_steps"]
    assert steps == cfg.state_layers * eng.stats["decode_steps"] > 0
    assert eng.stats["ssm.conv_kernel_layer_steps"] \
        == (steps if tail != 288 else 0)
    assert eng.leaked_pages() == 0
    # the same requests, cold and on XLA's form of the update
    conv_update.INTERPRET = False                       # the fixture resets
    cold = engine(prefix_cache=False)
    was = _serve(cold, prompts)
    assert cold.stats["ssm.conv_kernel_layer_steps"] == 0
    assert [list(r.out_tokens) for r in done] \
        == [list(r.out_tokens) for r in was]
