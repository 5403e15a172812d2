"""The paged indexer kernel (`pallas_kernels.paged_indexer`) through the
Pallas interpreter on the CPU, against `indexer_scores_fn` over the gathered
pages: both served geometries (DeepSeek-V3.2's 64 heads of 128, Keye-VL2's
16 of 64; 128-token pages of bfloat16 keys), the lengths around a page's
edge, and page tables as a pool in use hands them out."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import sparse_moe_ops
from paddle_tpu.ops.pallas_kernels import paged_indexer as pi

PS = 128                # tokens a page
PAGES = 24              # pages a layer; the tables name layer 1's
GEOMETRIES = {"deepseek_j64_d128": (64, 128), "keye_j16_d64": (16, 64)}


def _tables(case):
    """(page table [B, P] of one layer's pages, lens [B]) of a case."""
    rng = np.random.default_rng(sum(map(ord, case)))
    if case in ("len0", "len1", "boundary", "boundary_plus_one",
                "full_bucket"):
        P = 4
        length = {"len0": 0, "len1": 1, "boundary": 2 * PS,
                  "boundary_plus_one": 2 * PS + 1, "full_bucket": P * PS}[case]
        # between two rows with context: the copies started a block ahead
        # cross both of its edges
        lens = [PS + 7, length, 3 * PS - 1]
        table = np.stack([rng.permutation(PAGES)[:P] for _ in lens])
    elif case == "shared_scattered":
        # rows behind one document: the same pages, not in ascending
        # order, then a page of their own; the last row in another order
        P = 8
        doc = np.asarray([17, 3, 11, 5, 20, 2])
        table = np.zeros((4, P), np.int64)
        for b in range(4):
            table[b, :6] = doc if b < 3 else doc[::-1]
            table[b, 6] = 6 + b
        lens = [6 * PS + 1, 6 * PS + 90, 5 * PS, 6 * PS + PS]
    elif case == "wide_bucket":
        # 32 pages of table (four blocks of the small-block run), no row
        # past its tenth page, and entries past a row's pages that name no
        # page of the pool at all
        P = 32
        lens = [9 * PS + 5, 0, 3, 7 * PS]
        table = rng.integers(0, PAGES, (4, P))
        table[:, 10:] = 10 ** 6
    else:
        raise KeyError(case)
    return np.asarray(table, np.int32), np.asarray(lens, np.int32)


@pytest.mark.parametrize("blocks", ["served_blocks", "small_blocks"])
@pytest.mark.parametrize("case", [
    "len0", "len1", "boundary", "boundary_plus_one", "full_bucket",
    "shared_scattered", "wide_bucket"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_paged_indexer_pallas_matches_reference(geometry, case, blocks,
                                                monkeypatch):
    """Live positions agree with the gathered form to float32 rounding of
    the sum over heads; what lies past a row's length is finite, and a row
    the scheduler padded in scores zeros. `small_blocks`: four pages a grid
    step in chunks of two, so that a row spans several blocks and a block
    several chunks."""
    J, D = GEOMETRIES[geometry]
    monkeypatch.setattr(pi, "INTERPRET", True)
    if blocks == "small_blocks":
        monkeypatch.setattr(pi, "BLOCK_BYTES", 4 * D * PS * 2)
        monkeypatch.setattr(pi, "CHUNK_PAGES", 2)
    table, lens = _tables(case)
    B, P = table.shape
    ks = jax.random.split(jax.random.PRNGKey(B * P + J), 3)
    qi = jax.random.normal(ks[0], (B, J, D), jnp.float32)
    w = jax.random.normal(ks[1], (B, J), jnp.float32) * (J * D) ** -0.5
    pool = jax.random.normal(ks[2], (2 * PAGES, D, PS),
                             jnp.float32).astype(jnp.bfloat16)
    assert pi.paged_indexer_supported(qi.shape, pool.shape, pool.dtype)
    shifted = jnp.asarray(table) + PAGES          # layer 1's rows
    got = np.asarray(pi.paged_indexer_scores(qi, w, pool, shifted,
                                             jnp.asarray(lens)))
    want = np.asarray(pi._reference(qi, w, pool, shifted, jnp.asarray(lens)))
    assert got.shape == want.shape == (B, 1, P * PS)
    live = np.arange(P * PS)[None, :] < lens[:, None]
    scale = float(np.abs(want[:, 0][live]).max()) if live.any() else 1.0
    assert np.abs(got[:, 0] - want[:, 0])[live].max(initial=0.0) \
        <= 2e-6 * scale
    assert np.isfinite(got).all()
    assert not got[lens == 0].any()


def test_the_gate_takes_the_served_geometries_and_refuses_the_rehearsals():
    bf16 = jnp.bfloat16
    # DeepSeek-V3.2-Exp and Keye-VL2 as served: 5 x 2,304 and 6 x 1,792
    # pages of 128 bfloat16 tokens
    assert pi.paged_indexer_supported((128, 64, 128), (11520, 128, 128), bf16)
    assert pi.paged_indexer_supported((64, 16, 64), (10752, 64, 128), bf16)
    # the CPU rehearsals: 2 heads of 8 over 8-token pages, float32
    assert not pi.paged_indexer_supported((4, 2, 8), (192, 8, 8),
                                          jnp.float32)
    assert not pi.paged_indexer_supported((4, 2, 8), (192, 8, 8), bf16)
    # one thing off at a time: a float32 pool, a page of 64 tokens, heads
    # that do not fill a sublane tile, keys narrower than the queries
    assert not pi.paged_indexer_supported((64, 16, 64), (10752, 64, 128),
                                          jnp.float32)
    assert not pi.paged_indexer_supported((64, 16, 64), (10752, 64, 64), bf16)
    assert not pi.paged_indexer_supported((64, 12, 64), (10752, 64, 128),
                                          bf16)
    assert not pi.paged_indexer_supported((64, 16, 64), (10752, 32, 128),
                                          bf16)
    # off the chip nothing runs without the interpreter
    assert not sparse_moe_ops.paged_indexer_runs(
        (64, 16, 64), (10752, 64, 128), bf16)


def test_a_table_past_the_scalar_memory_is_scored_in_row_groups(monkeypatch):
    """Rows x pages past `TABLE_ENTRIES` go to the kernel a group of rows
    at a time; the scores are the same."""
    monkeypatch.setattr(pi, "INTERPRET", True)
    table, lens = _tables("shared_scattered")
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    qi = jax.random.normal(ks[0], (4, 16, 64), jnp.float32)
    w = jax.random.normal(ks[1], (4, 16), jnp.float32)
    pool = jax.random.normal(ks[2], (PAGES, 64, PS),
                             jnp.float32).astype(jnp.bfloat16)
    args = (qi, w, pool, jnp.asarray(table), jnp.asarray(lens))
    whole = pi.paged_indexer_scores(*args)
    monkeypatch.setattr(pi, "TABLE_ENTRIES", 3 * table.shape[1])
    assert jnp.array_equal(pi.paged_indexer_scores(*args), whole)
