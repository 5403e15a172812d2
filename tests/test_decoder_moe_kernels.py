"""The two bundled kernels the training decoder runs on the chip, through
the Pallas interpreter against their dense forms, forward and backward, at
one small shape each: the blockwise grouped-query attention behind a window
(`attention_ops.blockwise_attention`, jax's splash attention) and the
grouped expert products (`decoder_train_ops.moe_experts_train_fn`, jax's
megablox `gmm` / `tgmm`)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import attention_ops as ao
from paddle_tpu.ops import decoder_train_ops as dt


@pytest.mark.parametrize("window", [0, 100])
def test_blockwise_attention_is_the_dense_one(monkeypatch, window):
    monkeypatch.setattr(ao, "BLOCKWISE_INTERPRET", True)
    monkeypatch.setattr(ao, "BLOCKWISE_BLOCK", 128)
    monkeypatch.setattr(ao, "BLOCKWISE_BLOCK_FULL", 128)
    rng = np.random.default_rng(window)
    q, k, v = (jnp.asarray(rng.standard_normal((1, n, 256, 128)),
                           jnp.float32) for n in (4, 2, 2))
    assert ao.blockwise_supported(q.shape, k.shape)

    def dense(q, k, v):
        return jnp.sum(jnp.sin(ao.grouped_query_attention(
            q, k, v, True, 0.088, window)))

    def blockwise(q, k, v):
        return jnp.sum(jnp.sin(ao.blockwise_attention(
            q, k, v, True, 0.088, window)))

    want, dwant = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    got, dgot = jax.value_and_grad(blockwise, (0, 1, 2))(q, k, v)
    assert abs(float(want) - float(got)) < 1e-3
    for a, b in zip(dwant, dgot):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4
    # 2 x 2 blocks of 128: the causal pass visits 3, and so does a window of
    # 100 (a query at 128 still sees keys of the block before)
    assert ao.key_blocks(256, 128, True, window) == (3, 3)
    assert ao.key_blocks(8192, 512, True, 1024) == (45, 136)


def _experts(tokens=64, H=128, F=128, held=4, k=2, seed=0):
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((tokens, 8))
    cw = np.zeros((tokens, 8), np.float32)
    for t in range(tokens):
        top = np.argsort(-logits[t])[:k]
        p = np.exp(logits[t][top])
        cw[t, top] = p / p.sum()
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return (f32(rng.standard_normal((tokens, H))), f32(cw[:, 2:2 + held]),
            f32(rng.standard_normal((held, H, F)) * H ** -0.5),
            f32(rng.standard_normal((held, H, F)) * H ** -0.5),
            f32(rng.standard_normal((held, F, H)) * F ** -0.5))


def _dense_experts(z, cw, wg, wu, wd):
    out = 0
    for e in range(wg.shape[0]):
        g = z @ wg[e]
        out = out + cw[:, e, None] * ((jax.nn.silu(g) * (z @ wu[e])) @ wd[e])
    return jnp.sum(jnp.sin(out))


@pytest.mark.parametrize("bundled", [False, True])
def test_grouped_experts_are_the_dense_ones(monkeypatch, bundled):
    """Forward, dX, the router's weights and the three dW, off the chip
    (`ragged_dot`) and through megablox in the interpreter; a token with
    one, two or none of its experts held, an expert with no token."""
    monkeypatch.setattr(dt, "GROUPED_INTERPRET", bundled)
    args = _experts()
    assert {int(n) for n in np.sum(np.asarray(args[1]) != 0, axis=1)} \
        == {0, 1, 2}

    def ours(*a):
        return jnp.sum(jnp.sin(dt.moe_experts_train_fn(*a, 2)[0]))

    with jax.default_matmul_precision("highest"):
        want, dwant = jax.value_and_grad(_dense_experts, (0, 1, 2, 3, 4))(
            *args)
        got, dgot = jax.value_and_grad(ours, (0, 1, 2, 3, 4))(*args)
        counts = dt.moe_experts_train_fn(*args, 2)[1]
    assert abs(float(want) - float(got)) < 1e-4
    chosen = args[1] != 0       # an unchosen expert's weight has no gradient
    for i, (a, b) in enumerate(zip(dwant, dgot)):
        a = jnp.where(chosen, a, 0.0) if i == 1 else a
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4, i
    assert np.array_equal(np.asarray(counts),
                          np.sum(np.asarray(chosen), axis=0))


def test_the_experts_go_chunk_by_chunk_to_the_same_sum(monkeypatch):
    args = _experts(tokens=96)
    whole = jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(dt.moe_experts_train_fn(*a, 2)[0])),
        (0, 1, 2, 3, 4))(*args)
    monkeypatch.setattr(dt, "EXPERT_CHUNK_TOKENS", 32)
    assert dt._parts(96, dt.EXPERT_CHUNK_TOKENS) == 3
    chunked = jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(dt.moe_experts_train_fn(*a, 2)[0])),
        (0, 1, 2, 3, 4))(*args)
    assert abs(float(whole[0]) - float(chunked[0])) < 1e-4
    for a, b in zip(whole[1], chunked[1]):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_the_heads_loss_goes_block_by_block_to_the_same_sum(monkeypatch):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((96, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 200)) * 0.2, jnp.float32)
    labels = jnp.asarray(rng.integers(0, 200, 96), jnp.int32)
    weight = jnp.asarray(rng.random(96) < 0.9, jnp.float32)

    def dense(x, w):
        logp = jax.nn.log_softmax(x @ w, axis=-1)
        return -jnp.sum(weight * jnp.take_along_axis(
            logp, labels[:, None], axis=-1)[:, 0])

    def ours(x, w):
        return dt.head_nll_fn(x, w, labels, weight)

    want = jax.value_and_grad(dense, (0, 1))(x, w)
    monkeypatch.setattr(dt, "HEAD_BLOCK_ROWS", 32)
    assert dt._parts(96, dt.HEAD_BLOCK_ROWS) == 3
    got = jax.value_and_grad(ours, (0, 1))(x, w)
    assert abs(float(want[0]) - float(got[0])) < 1e-3
    for a, b in zip(want[1], got[1]):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4
