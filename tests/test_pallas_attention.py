"""Short-seq fused attention kernel vs the jnp reference (fwd + grads).

Runs the Pallas kernels through the interpreter on the CPU test mesh; TPU
compilation was verified out-of-band (tools/_bert_flash_ab.py trains BERT
end-to-end with use_flash_attention=True). The default bench path keeps the
kernel OFF because XLA attention is faster at the bench config (PERF.md).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention_ops import _reference_attention
from paddle_tpu.ops.pallas_kernels import attention as psa


@pytest.fixture(autouse=True)
def _interpret():
    psa.INTERPRET = True
    yield
    psa.INTERPRET = False


def _rand(shape, dtype, seed):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fwd_matches_reference(causal, dtype):
    B, nh, S, dh = 2, 3, 128, 64
    q, k, v = (_rand((B, nh, S, dh), dtype, i) for i in range(3))
    sm = dh ** -0.5
    out = psa.short_seq_attention(q, k, v, causal=causal, sm_scale=sm)
    ref = _reference_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                               v.astype(jnp.float32), causal=causal,
                               sm_scale=sm)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    B, nh, S, dh = 1, 2, 128, 32
    q, k, v = (_rand((B, nh, S, dh), "float32", 10 + i) for i in range(3))
    sm = dh ** -0.5
    ct = _rand((B, nh, S, dh), "float32", 99)

    def via_kernel(q, k, v):
        return jnp.sum(psa.short_seq_attention(q, k, v, causal=causal,
                                               sm_scale=sm) * ct)

    def via_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal=causal,
                                            sm_scale=sm) * ct)

    g_kernel = jax.grad(via_kernel, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(via_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_kernel, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3, err_msg=name)


def test_head_block_respects_budget_and_divides():
    for nh in (1, 2, 3, 12, 16, 24):
        for s in (128, 256, 512, 1024):
            gh = psa._head_block(nh, s, 64, 2, 9)
            assert nh % gh == 0 and gh >= 1


def test_supported_gate():
    ok = ((2, 12, 128, 64), (2, 12, 128, 64))
    assert psa.short_seq_supported(*ok, bias=None)
    assert not psa.short_seq_supported(*ok, bias=object())
    assert not psa.short_seq_supported((2, 12, 130, 64), (2, 12, 130, 64),
                                       bias=None)
    assert not psa.short_seq_supported((2, 12, 128, 64), (2, 12, 256, 64),
                                       bias=None)
    assert not psa.short_seq_supported((2, 12, 2048, 64), (2, 12, 2048, 64),
                                       bias=None)
    # S=1024 bwd intermediates outgrow VMEM at gh=1 — must be rejected
    assert not psa.short_seq_supported((2, 12, 1024, 64), (2, 12, 1024, 64),
                                       bias=None)
    assert psa.short_seq_supported((2, 12, 512, 64), (2, 12, 512, 64),
                                   bias=None)


# -- the grouped-query paged decode kernel that walks a list of page blocks --
# (PR 50): rows whose tables begin with the same pages attend that run once

PS, DH = 128, 128


def _shared_tables(kind):
    """(table [B, P], lens [B]) with blocks of two pages; pool pages 0..3
    are kept for the padding rows' zeros."""
    own = iter(range(100, 10_000))
    fresh = lambda n: [next(own) for _ in range(n)]          # noqa: E731
    ctx = [fresh(6) for _ in range(4)]
    rows = []                                   # (pages, length)

    def behind(c, shared, mine, length):
        pages = ctx[c][:shared] + fresh(mine)
        rows.append((pages, length if length else len(pages) * PS - 37))

    if kind == "one group of 27 and three smaller":
        for c, n in enumerate((27, 5, 3, 2)):
            for i in range(n):
                behind(c, 4, 1 + i % 2, 0)
    elif kind == "a run that ends inside a member's last whole page":
        # the third row leaves the context after five pages, the fourth is
        # live for four pages and a half: the run is four, one block short
        # of what the first two share
        behind(0, 6, 1, 0), behind(0, 6, 2, 0), behind(0, 5, 2, 0)
        behind(0, 6, 0, 4 * PS + 64)
    elif kind == "a row whose length ends inside the shared run":
        # its tables names all six pages, its length three: it holds no
        # whole block beyond the first, and keeps the group to one block
        behind(0, 6, 1, 0), behind(0, 6, 1, 0), behind(0, 6, 0, 3 * PS - 5)
        behind(1, 4, 1, 0), behind(1, 4, 0, 4 * PS)   # a tail of no page
    elif kind == "a run one page short of a block":
        behind(0, 1, 2, 0), behind(0, 1, 3, 0), behind(0, 1, 1, 0)
    elif kind == "nested prefixes":
        behind(0, 6, 1, 0), behind(0, 6, 1, 0), behind(0, 2, 3, 0)
        behind(0, 4, 2, 0), behind(1, 2, 0, 2 * PS + 0)
    elif kind == "padding rows in and between groups":
        behind(0, 4, 1, 0), rows.append(([], 0)), behind(0, 4, 2, 0)
        behind(1, 4, 1, 0), rows.append(([], 0)), rows.append(([], 0))
        behind(1, 4, 1, 0), behind(0, 4, 1, 1), behind(2, 2, 1, 0)
    else:
        assert kind == "a table that shares nothing"
        for i in range(9):
            behind(i % 4, 0, 1 + i % 6, 0)
        rows.append(([], 0)), behind(0, 0, 7, 7 * PS)
    P = 8
    table = np.array([(p + [0] * P)[:P] for p, _ in rows], np.int32)
    return table, np.array([n for _, n in rows], np.int32)


def _by_hand(table, lens, block):
    """Pages and tokens a layer's call fetches, counted with sets: a run
    is what ALL rows that begin with the same block of whole pages share,
    in whole blocks."""
    whole = lens // PS
    groups = {}
    for b in range(len(lens)):
        key = tuple(table[b, :block]) if whole[b] >= block else ("own", b)
        groups.setdefault(key, []).append(b)
    pages = tokens = 0
    for members in groups.values():
        run = 0
        if len(members) > 1:
            first = table[members[0]]
            common = min(next((i for i in range(table.shape[1])
                               if table[b, i] != first[i]), table.shape[1])
                         for b in members)
            run = min(common, min(whole[b] for b in members)) \
                // block * block
        pages += run + sum(-(-lens[b] // PS) - run for b in members)
        tokens += run * PS + sum(lens[b] - run * PS for b in members)
    return int(pages), int(tokens)


def _paged_case(nh, nkv, dtype, table, lens, monkeypatch, seed=0):
    from paddle_tpu.ops.attention_ops import _paged_attention_reference
    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    monkeypatch.setattr(ppa, "INTERPRET", True)
    item = jnp.dtype(dtype).itemsize
    # blocks of two pages
    monkeypatch.setattr(ppa, "BLOCK_BYTES", 2 * 2 * PS * nkv * DH * item)
    rng = np.random.default_rng(seed)
    pool = lambda: jnp.asarray(                                 # noqa: E731
        rng.standard_normal((int(table.max()) + 1, PS, nkv * DH)), dtype)
    k_pool, v_pool = pool(), pool()
    q = jnp.asarray(rng.standard_normal((len(lens), nh, DH)), jnp.float32)
    assert ppa.walk_supported(q.shape, k_pool.shape, dtype, table.shape[1])
    assert ppa.pages_per_grid_step(table.shape[1], PS, nkv * DH, item) == 2
    args = (q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lens))
    got = np.asarray(ppa.paged_decode_attention(*args, sm_scale=DH ** -0.5))
    want = np.asarray(_paged_attention_reference(*args, DH ** -0.5))
    live = lens > 0
    np.testing.assert_allclose(got[live], want[live],
                               atol=1e-2 if item == 2 else 2e-5)
    assert not got[~live].any()                 # a padding row: zeros
    return ppa, args, got


@pytest.mark.parametrize("kind,runs", [
    ("one group of 27 and three smaller", [(4, 27), (4, 5), (4, 3), (4, 2)]),
    ("a run that ends inside a member's last whole page", [(4, 4)]),
    ("a row whose length ends inside the shared run", [(2, 3), (4, 2)]),
    ("a run one page short of a block", []),
    ("nested prefixes", [(2, 4)]),
    ("padding rows in and between groups", [(4, 2), (4, 2)]),
    ("a table that shares nothing", [])])
def test_paged_gqa_walk_matches_reference(kind, runs, monkeypatch):
    """Laguna-XS.2's head shape (48 query heads over 8 KV heads, groups of
    6 in 8 sublanes), a bfloat16 pool, blocks of two pages: what the rows
    share is read once, what each holds behind it is its own, and the
    answer is the XLA reference's. `runs`: (pages, rows) of every shared
    run the table holds."""
    table, lens = _shared_tables(kind)
    ppa, args, got = _paged_case(48, 8, jnp.bfloat16, table, lens,
                                 monkeypatch)
    read = ppa.walk_counts(table, lens, args[1].shape, 2)
    assert (read["pages"], read["tokens"]) == _by_hand(table, lens, 2)
    assert read["shared"] == bool(runs)
    # a run is read once where its rows would each have read it
    assert int((-(-lens // PS)).sum()) - read["pages"] \
        == sum(run * (rows - 1) for run, rows in runs)
    plan = ppa.walk_plan(args[3], args[4], args[1].shape, 2)
    assert int(plan.blocks[0]) == read["blocks"]
    if not runs:
        # groups of one: the parent's grid over (row, block), to rounding
        parent = np.asarray(ppa._call(*args, DH ** -0.5, True))
        np.testing.assert_allclose(got[lens > 0], parent[lens > 0],
                                   atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("nh,nkv", [(48, 8), (24, 4), (32, 2)],
                         ids=["laguna", "falcon", "nemotron"])
def test_paged_gqa_walk_at_the_families_head_shapes(nh, nkv, dtype,
                                                    monkeypatch):
    """48 over 8 (Laguna-XS.2), 24 over 4 (Falcon-H1's 20 as groups of 6)
    and 32 over 2 (Nemotron 3: groups of 16, two sublane tiles), bfloat16
    and float32 pools, over a table that holds two groups, a group of one,
    a padding row and a row of one token."""
    table, lens = _shared_tables("padding rows in and between groups")
    _paged_case(nh, nkv, dtype, table, lens, monkeypatch, seed=nh)


def test_paged_gqa_walk_gate_and_tile():
    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    pool = (2048, 128, 1024)
    assert ppa.walk_supported((64, 48, 128), pool, jnp.bfloat16, 160)
    # the `post_ln` arm (as many KV heads as query heads) keeps its grid
    assert not ppa.walk_supported((64, 12, 64), (2048, 16, 768),
                                  jnp.float32, 32)
    # rows whose running sums no longer stay resident, and a table past the
    # scalar memory a call prefetches, keep the grid too
    assert not ppa.walk_supported((2048, 48, 128), pool, jnp.bfloat16, 160)
    assert not ppa.walk_supported((64, 48, 128), pool, jnp.bfloat16,
                                  ppa.TABLE_ENTRIES // 64 + 1)
    assert [ppa.tile_rows(h) for h in (4, 5, 6, 12, 16, 32, 128)] == [
        (16, 8), (16, 8), (16, 8), (10, 0), (8, 4), (4, 2), (1, 0)]


# -- a head that is a whole lane register, one head a KV head (PR 55) --------
# (Ouro's 16 heads of 128 over 16 KV heads, bfloat16 pages of 16 tokens): the
# matrix-unit arm, on the grid and as the list walk, against the XLA gather


def _one_head_a_kv_head(rows, live, seed):
    """`rows` rows of which `live` have context: each behind one of two
    256-token prompts (16 whole pages, shared) and a ragged tail of its
    own, one row inside its prompt, one ending on a page's last slot."""
    nh, dh, ps, P = 16, 128, 16, 48
    rng = np.random.default_rng(seed)
    pages = 32 + live * 12 + 8
    k_pool, v_pool = (jnp.asarray(rng.standard_normal((pages, ps, nh * dh)),
                                  jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((rows, nh, dh)), jnp.float32)
    table = np.zeros((rows, P), np.int32)
    lens = np.zeros((rows,), np.int32)
    free = iter(rng.permutation(np.arange(32, pages)))
    for b in range(live):
        own = int(rng.integers(1, 12 * ps))
        if b == 1:
            own = 3 * ps                       # ends on a page's last slot
        n = -(-own // ps)
        table[b, :16] = (b % 2) * 16 + np.arange(16)
        table[b, 16:16 + n] = [next(free) for _ in range(n)]
        lens[b] = 16 * ps + own
    lens[2] = 100                              # inside its shared prompt
    # live rows and padding rows interleave as a bucket's never do, which
    # the kernel must not rely on
    order = rng.permutation(rows)
    return (q, k_pool, v_pool, jnp.asarray(table[order]),
            jnp.asarray(lens[order]))


@pytest.mark.parametrize("form", ["grid", "walk"])
@pytest.mark.parametrize("rows,live", [(16, 11), (32, 20)])
def test_paged_one_head_a_kv_head_matches_reference(rows, live, form,
                                                    monkeypatch):
    from paddle_tpu.ops.attention_ops import _paged_attention_reference
    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    monkeypatch.setattr(ppa, "INTERPRET", True)
    args = _one_head_a_kv_head(rows, live, seed=rows)
    q, k_pool = args[:2]
    assert ppa.paged_supported(q.shape, k_pool.shape, jnp.bfloat16)
    assert ppa.matrix_unit_arm(q.shape, k_pool.shape, jnp.bfloat16, 48)
    assert ppa.walk_supported(q.shape, k_pool.shape, jnp.bfloat16, 48)
    scale = 128 ** -0.5
    got = np.asarray(ppa._call(*args, scale, True) if form == "grid"
                     else ppa.paged_decode_attention(*args, sm_scale=scale))
    want = np.asarray(_paged_attention_reference(*args, scale))
    lens = np.asarray(args[4])
    assert (lens > 0).sum() == live
    np.testing.assert_allclose(got[lens > 0], want[lens > 0], atol=1e-2)
    assert not got[lens == 0].any()             # a padding row: zeros
    if form == "walk":
        read = ppa.walk_counts(np.asarray(args[3]), lens, k_pool.shape, 2)
        # the two prompts' 16 pages once each, not once a row
        assert read["shared"] and read["pages"] == int(
            (-(-lens // 16)).sum()) - 16 * (live - 1 - 2)
