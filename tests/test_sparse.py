"""Sparse paths at the level of their ops: the SelectedRows embedding
gradient (reference selected_rows.h:32 + lookup_table_op sparse grad + sgd_op
SelectedRows kernel), and the joined K/V row of the "sparse_moe" block
(`ops/sparse_moe_ops.py`: a token's K and V as one row of 32-bit words that a
decode step gathers once)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu.core.selected_rows import SelectedRows
from paddle_tpu.ops import sparse_moe_ops
from paddle_tpu.ops.attention_ops import _NEG_INF, _gather_pages, _write_rows


def test_selected_rows_to_dense_merges_duplicates():
    sr = SelectedRows(
        rows=np.array([1, 3, 1], np.int32),
        values=np.array([[1.0, 2.0], [3.0, 4.0], [10.0, 20.0]], np.float32),
        height=5,
    )
    dense = np.asarray(sr.to_dense())
    expect = np.zeros((5, 2), np.float32)
    expect[1] = [11.0, 22.0]
    expect[3] = [3.0, 4.0]
    np.testing.assert_allclose(dense, expect)
    uniq, merged = sr.merged()
    np.testing.assert_array_equal(uniq, [1, 3])
    np.testing.assert_allclose(merged, [[11.0, 22.0], [3.0, 4.0]])


def _train_embedding(is_sparse, steps=5):
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            ids = L.data(name="ids", shape=[4], dtype="int64")
            y = L.data(name="y", shape=[1], dtype="float32")
            emb = L.embedding(ids, size=[50, 8], is_sparse=is_sparse,
                              param_attr=pt.ParamAttr(name="emb_w"))
            pooled = L.reduce_sum(emb, dim=1)
            pred = L.fc(pooled, size=1)
            loss = L.mean(L.square_error_cost(pred, y))
            pt.optimizer.SGD(0.1).minimize(loss)
    scope = pt.Scope()
    exe = pt.Executor()
    rng = np.random.default_rng(0)
    idv = rng.integers(0, 50, (16, 4)).astype(np.int64)
    yv = rng.standard_normal((16, 1)).astype(np.float32)
    with pt.scope_guard(scope):
        exe.run(startup)
        hist = []
        for _ in range(steps):
            (lv,) = exe.run(main, feed={"ids": idv, "y": yv},
                            fetch_list=[loss.name])
            hist.append(float(np.asarray(lv).reshape(-1)[0]))
        w = np.asarray(scope.find_var("emb_w"))
    return hist, w


def test_sparse_embedding_grad_matches_dense():
    """is_sparse=True (SelectedRows grad + sparse sgd scatter) must produce
    the exact same trajectory as the dense scatter-add path."""
    dense_hist, dense_w = _train_embedding(False)
    sparse_hist, sparse_w = _train_embedding(True)
    np.testing.assert_allclose(dense_hist, sparse_hist, rtol=1e-5)
    np.testing.assert_allclose(dense_w, sparse_w, rtol=1e-5, atol=1e-6)
    assert dense_hist[-1] < dense_hist[0]


def test_sparse_grad_with_momentum_raises():
    with pt.program_guard(pt.Program(), pt.Program()):
        ids = L.data(name="ids", shape=[4], dtype="int64")
        emb = L.embedding(ids, size=[20, 4], is_sparse=True)
        loss = L.mean(L.reduce_sum(emb, dim=1))
        pt.optimizer.Momentum(0.1, momentum=0.9).minimize(loss)
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        with pytest.raises(pt.OpError, match="SelectedRows"):
            exe.run(pt.default_main_program(),
                    feed={"ids": np.zeros((8, 4), np.int64)},
                    fetch_list=[loss.name])


# -- the joined K/V row of the "sparse_moe" block ----------------------------

NH, NKV, DH = 4, 2, 8               # sparse_moe_tiny's heads
W = NKV * DH


def _two_pool_decode_attention(q, k_pool, v_pool, page_table, sel, sm_scale):
    """The form before the rows were joined (PR 29): a selected token is
    gathered twice, from a K pool and from a V pool `[rows, page_size,
    nkv*dh]`; the products, the mask and the softmax are the served ones."""
    B, nh, dh = q.shape
    rows, ps, width = k_pool.shape
    nkv, g = width // dh, nh // (width // dh)
    have = sel >= 0
    at = jnp.maximum(sel, 0)
    page = jnp.take_along_axis(page_table, at // ps, axis=1)
    flat = jnp.clip(page, 0, rows - 1) * ps + at % ps
    k = k_pool.reshape(rows * ps, width)[flat]
    v = v_pool.reshape(rows * ps, width)[flat]
    qg = q.reshape(B, nkv, g, dh).astype(k.dtype)
    out = []
    for j in range(nkv):
        kj, vj = (a[..., j * dh:(j + 1) * dh] for a in (k, v))
        s = jnp.einsum("bgd,bkd->bgk", qg[:, j], kj,
                       preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(have[:, None, :], s, _NEG_INF)
        probs = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("bgk,bkd->bgd", probs.astype(vj.dtype), vj,
                              preferred_element_type=jnp.float32))
    return jnp.stack(out, axis=1).reshape(B, nh, dh)


def _pools(rng, pages, ps, dtype):
    """K and V pools of random rows and the pool of the same rows joined,
    written a row at a time the way a step writes them."""
    k, v = (jnp.asarray(rng.standard_normal((pages, ps, W)), dtype)
            for _ in range(2))
    words = 2 * W * jnp.dtype(dtype).itemsize // 4
    page, slot = (a.reshape(-1) for a in np.indices((pages, ps)))
    kv = _write_rows(
        jnp.zeros((pages, ps, words), jnp.int32),
        sparse_moe_ops.join_rows_fn(k.reshape(-1, W), v.reshape(-1, W),
                                    dtype), page, slot)
    return k, v, kv


def _selections(rng, case, B, live, k):
    """sel [B, kk]: `kk = min(k, context)` distinct live positions in the
    indexer's order, -1 behind them where a row has fewer."""
    kk = min(k, live)
    sel = np.stack([rng.permutation(live)[:kk] for _ in range(B)])
    if case == "padded":                 # rows of 1, 3 and kk positions
        for b, n in enumerate((1, 3, kk)):
            sel[b, n:] = -1
    elif case == "last_slot":            # the last slot of the last page
        sel[:, 0] = live - 1
        sel[0, 1:] = -1
    return jnp.asarray(sel, jnp.int32)


@pytest.mark.parametrize("case", ["all_k", "kk_under_k", "padded",
                                  "last_slot"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("ps", [4, 8, 16])
def test_joined_row_decode_attention_equals_two_pools_bit_for_bit(
        ps, dtype, case):
    rng = np.random.default_rng(30 + ps)
    B, P, pages, k = 3, 4, 24, 8
    live = ps if case == "kk_under_k" and ps < k else P * ps
    if case == "kk_under_k":
        live = min(live, 6)              # fewer positions than the top-k
    k_pool, v_pool, kv_pool = _pools(rng, pages, ps, dtype)
    table = jnp.asarray(np.stack([rng.permutation(pages)[:P]
                                  for _ in range(B)]), jnp.int32)
    sel = _selections(rng, case, B, live, k)
    q = jnp.asarray(rng.standard_normal((B, NH, DH)), jnp.float32)
    want = _two_pool_decode_attention(q, k_pool, v_pool, table, sel,
                                      DH ** -0.5)
    got = sparse_moe_ops.sparse_decode_attention_fn(
        q, kv_pool, table, sel, DH ** -0.5, dtype)
    assert got.dtype == jnp.float32 and np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("queries", [1, 5])
def test_joined_slab_window_attention_equals_two_pools_bit_for_bit(
        queries, dtype):
    """A window (and, with one query, the short-context decode step) reads
    a page's slab of joined rows once and splits it."""
    rng = np.random.default_rng(31)
    B, P, ps, pages = 2, 3, 8, 12
    k_pool, v_pool, kv_pool = _pools(rng, pages, ps, dtype)
    table = jnp.asarray(np.stack([rng.permutation(pages)[:P]
                                  for _ in range(B)]), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, queries, NH, DH)), jnp.float32)
    mask = jnp.asarray(rng.random((B, queries, P * ps)) < 0.4)
    mask = mask.at[:, :, 0].set(True)
    want = sparse_moe_ops._masked_attention(
        q, _gather_pages(k_pool, table, NKV), _gather_pages(v_pool, table,
                                                            NKV),
        mask, DH ** -0.5)
    got = sparse_moe_ops.masked_window_attention_fn(q, kv_pool, table, mask,
                                                    DH ** -0.5, dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _bf16_bits(name):
    every = np.arange(1 << 16, dtype=np.uint16)
    exponent, mantissa = (every >> 7) & 0xFF, every & 0x7F
    return {
        "every_pattern": every,
        "negative_zero": np.asarray([0x8000, 0x0000] * 8, np.uint16),
        "subnormals": every[(exponent == 0) & (mantissa != 0)],
        "infinities": np.asarray([0x7F80, 0xFF80] * 8, np.uint16),
        "nan_payloads": every[(exponent == 0xFF) & (mantissa != 0)],
    }[name]


@pytest.mark.parametrize("pattern", ["every_pattern", "negative_zero",
                                     "subnormals", "infinities",
                                     "nan_payloads"])
def test_a_bfloat16_row_written_and_gathered_keeps_every_bit(pattern):
    """K in the low halves of a row's words and V in the high halves, and
    the other way round: through the row scatter into a pool, the token
    gather and the split, no bit moves (a signalling NaN stays one)."""
    bits = _bf16_bits(pattern)
    bits = np.resize(bits, (-(-bits.size // W), W))
    other = np.roll(bits[::-1], 3, axis=1)
    ps = 4
    pages = -(-bits.shape[0] // ps)
    page, slot = (a.reshape(-1)[:bits.shape[0]]
                  for a in np.indices((pages, ps)))
    for k_bits, v_bits in ((bits, other), (other, bits)):
        k, v = (jax.lax.bitcast_convert_type(jnp.asarray(b), jnp.bfloat16)
                for b in (k_bits, v_bits))
        pool = _write_rows(jnp.zeros((pages, ps, W), jnp.int32),
                           sparse_moe_ops.join_rows_fn(k, v, "bfloat16"),
                           page, slot)
        rows = pool.reshape(pages * ps, W)[page * ps + slot]
        back = sparse_moe_ops.split_rows_fn(rows, "bfloat16")
        heads = [sparse_moe_ops.head_of_rows_fn(rows, j, DH, "bfloat16")
                 for j in range(NKV)]
        for side, want in enumerate((k_bits, v_bits)):
            got = jax.lax.bitcast_convert_type(back[side], jnp.uint16)
            np.testing.assert_array_equal(np.asarray(got), want)
            by_head = np.concatenate([np.asarray(
                jax.lax.bitcast_convert_type(h[side], jnp.uint16))
                for h in heads], axis=-1)
            np.testing.assert_array_equal(by_head, want)


def test_a_float32_row_keeps_every_bit_and_twice_the_words():
    rng = np.random.default_rng(32)
    bits = rng.integers(0, 1 << 32, (64, W), dtype=np.uint64).astype(
        np.uint32)
    bits[0, :4] = [0x80000000, 0x7FC00001, 0xFF800000, 0x00000001]
    k = jax.lax.bitcast_convert_type(jnp.asarray(bits), jnp.float32)
    v = jax.lax.bitcast_convert_type(jnp.asarray(bits[::-1]), jnp.float32)
    rows = sparse_moe_ops.join_rows_fn(k, v, "float32")
    assert rows.shape == (64, 2 * W) and rows.dtype == jnp.int32
    for got, want in zip(sparse_moe_ops.split_rows_fn(rows, "float32"),
                         (bits, bits[::-1])):
        np.testing.assert_array_equal(np.asarray(
            jax.lax.bitcast_convert_type(got, jnp.uint32)), want)
