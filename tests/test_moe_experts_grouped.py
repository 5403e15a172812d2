"""The gated expert kernel's GROUPED form (`moe_experts._grouped_call`, PR 52):
a call of more rows than one token tile sorts its (row, expert) pairs by
expert and streams each held expert once, and not at all where no row chose
it. On the CPU through the Pallas interpreter at small widths, against the
plain sum over every expert (`moe_experts._reference`); the counters that
say how often the form engages, through `ServingEngine` at toy size."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import decoder_common
from paddle_tpu.ops.pallas_kernels import moe_experts as pme
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import model as sv_model

# experts a row draws among, of which the call holds the first `held`; k a
# row; the experts' width (768: two F tiles of 384, so that an expert's
# second tile walks them backwards); the layer of a stack of two
GEOMETRIES = {
    "top1_of_16": dict(E=16, held=16, k=1, F=768, layer=0),
    "top8_of_128": dict(E=128, held=128, k=8, F=256, layer=1),
    "top8_of_256": dict(E=256, held=256, k=8, F=256, layer=0),
    "16_held_of_256": dict(E=256, held=16, k=8, F=768, layer=1),
}
H = 128


def _case(rng, T, E, held, k, F, routing="uniform"):
    z = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((2, held, H, F)) * H ** -0.5)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((2, held, F, H)) * F ** -0.5)
    cw = np.zeros((T, E), np.float32)
    for t in range(T):
        ids = rng.choice(E, k, replace=False)
        if routing == "one_takes_all":
            # expert 3 takes every row; every third row k - 1 more among
            # the next dozen; every other expert takes none
            ids = np.concatenate([[3], 4 + rng.choice(12, k - 1,
                                                      replace=False)
                                  if t % 3 == 0 and k > 1 else []])
        cw[t, ids.astype(int)] = rng.dirichlet(np.ones(len(ids))) * 2.5
    return z, jnp.asarray(cw[:, :held]), wg, wu, wd


def _run(z, cw, wg, wu, wd, layer, k):
    if k == 1:
        return pme.moe_top1_experts(z, cw, wg, wu, wd, layer, tag="prefill")
    return pme.moe_topk_experts(z, cw, wg, wu, wd, layer, tag="prefill", k=k)


@pytest.mark.parametrize("T", [257, 512, 640])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_moe_experts_grouped_matches_reference(monkeypatch, geometry, T):
    monkeypatch.setattr(pme, "INTERPRET", True)
    g = dict(GEOMETRIES[geometry])
    layer = g.pop("layer")
    z, cw, *w = _case(np.random.default_rng(T), T, **g)
    if g["held"] < g["E"]:
        assert not np.asarray(cw).any(axis=1).all()    # rows that hold none
    got = _run(z, cw, *w, layer, g["k"])
    want = pme._reference(z, cw, *w, layer)
    assert got.shape == (T, H) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # another layer's weights give another answer: `layer` was read
    assert np.abs(np.asarray(_run(z, cw, *w, 1 - layer, g["k"])
                             - want)).max() > 1e-2


@pytest.mark.parametrize("geometry,dtype", [
    ("top1_of_16", "float32"), ("top8_of_128", "float32"),
    ("top1_of_16", "bfloat16")])
def test_moe_experts_grouped_one_expert_takes_every_row(monkeypatch,
                                                        geometry, dtype):
    """One expert's group spans every tile (the F tiles walked forwards and
    backwards in turn) and most experts are never fetched."""
    monkeypatch.setattr(pme, "INTERPRET", True)
    g = dict(GEOMETRIES[geometry])
    layer = g.pop("layer")
    z, cw, *w = _case(np.random.default_rng(5), 640, **g,
                      routing="one_takes_all")
    w = [x.astype(dtype) for x in w]
    counts = np.count_nonzero(np.asarray(cw), axis=0)
    assert counts[3] == 640 and (counts == 0).sum() >= g["held"] - 13
    _, visits = pme.grouped_visits(counts, np)
    assert visits[3] == -(-640 // pme.GROUP_TILE) \
        and visits.sum() <= visits[3] + 2 * 12
    tol = 1e-4 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(_run(z, cw, *w, layer, g["k"]),
                               pme._reference(z, cw, *w, layer),
                               rtol=tol, atol=tol)


def test_moe_experts_grouped_with_no_live_pair(monkeypatch):
    """No row chose a held expert: every visit is skipped and the output is
    the zeros it was set to."""
    monkeypatch.setattr(pme, "INTERPRET", True)
    z, cw, *w = _case(np.random.default_rng(1), 300, 16, 16, 1, 256)
    got = pme.moe_topk_experts(z, jnp.zeros_like(cw), *w, 0, k=2)
    assert not np.asarray(got).any()


def test_moe_experts_grouped_block_by_block(monkeypatch):
    """More tokens than one call keeps resident (its output in VMEM, its
    sorted rows' tokens in SMEM): the call goes a block of tokens at a time
    (here 256 rows: 640 rows in three blocks), each block its own sorted
    list."""
    monkeypatch.setattr(pme, "INTERPRET", True)
    monkeypatch.setattr(pme, "_SORTED_ROWS", 8 * 300)
    jax.clear_caches()
    g = dict(GEOMETRIES["top8_of_128"])
    layer = g.pop("layer")
    z, cw, *w = _case(np.random.default_rng(8), 640, **g)
    jaxpr = jax.make_jaxpr(lambda *a: _run(*a, layer, g["k"]))(z, cw, *w)
    assert str(jaxpr).count("pallas_call") == 1 and "scan" in str(jaxpr)
    np.testing.assert_allclose(_run(z, cw, *w, layer, g["k"]),
                               pme._reference(z, cw, *w, layer),
                               rtol=1e-4, atol=1e-4)
    jax.clear_caches()


def test_a_call_of_stand_in_rows_traces_at_once():
    """A Program is built on stand-in sizes (`ops/registry._DYN` = 8191 for
    a batch and for a length: 67 million rows), and its ops' shapes are
    inferred by tracing them: the grouped form traces one block however
    many there are (a Python loop over the blocks hung an engine's build
    for 43 minutes on the chip, PR 52)."""
    from paddle_tpu.ops.registry import _DYN
    S = jax.ShapeDtypeStruct
    rows = _DYN * _DYN
    out = jax.eval_shape(
        lambda z, cw, wg, wu, wd: pme.moe_topk_experts(
            z, cw, wg, wu, wd, 1, tag="prefill", k=8),
        S((rows, 256), jnp.float32), S((rows, 128), jnp.float32),
        S((2, 128, 256, 768), jnp.bfloat16),
        S((2, 128, 256, 768), jnp.bfloat16),
        S((2, 128, 768, 256), jnp.bfloat16))
    assert out.shape == (rows, 256) and out.dtype == jnp.float32


def test_the_visits_the_kernel_runs_are_the_visits_counted():
    """`grouped_visits` against a count made the long way: the sorted list
    cut into tiles, the distinct experts of each tile."""
    rng = np.random.default_rng(2)
    for counts in (rng.integers(0, 90, 128), np.array([0, 0, 700, 0, 1]),
                   np.zeros(16, np.int64), np.array([128, 128, 256])):
        owner = np.repeat(np.arange(len(counts)), counts)
        want = sum(len(np.unique(owner[at:at + pme.GROUP_TILE]))
                   for at in range(0, len(owner), pme.GROUP_TILE))
        first, visits = pme.grouped_visits(counts, np)
        assert visits.sum() == want
        np.testing.assert_array_equal(pme.grouped_visits(
            jnp.asarray(counts, jnp.int32))[1], visits)


# sha256(str(jaxpr))[:16] of a call of up to 256 rows, computed in a checkout
# of the parent (commit a61cabb) and in this tree by the lines below
SMALL_CALLS = {
    ("top1", 256, 16, 256, 512): "e029732047d4ef6a",
    ("topk", 256, 128, 128, 768): "8717b8732553fdaf",
    ("topk", 20, 256, 128, 256): "5762d9c79e50ae1a",
}


@pytest.mark.parametrize("case", sorted(SMALL_CALLS), ids=str)
def test_a_call_of_one_token_tile_traces_the_program_it_traced(case):
    name, T, E, width, F = case
    fn = pme.moe_top1_experts if name == "top1" else pme.moe_topk_experts
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        lambda z, cw, wg, wu, wd: fn(z, cw, wg, wu, wd, 1, tag="prefill"))(
        S((T, width), jnp.float32), S((T, E), jnp.float32),
        S((2, E, width, F), jnp.bfloat16), S((2, E, width, F), jnp.bfloat16),
        S((2, E, F, width), jnp.bfloat16))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] \
        == SMALL_CALLS[case]


def test_a_topk_call_of_more_rows_has_to_say_k():
    z, cw, *w = _case(np.random.default_rng(0), 300, 16, 16, 2, 256)
    with pytest.raises(ValueError, match="needs k"):
        pme.moe_topk_experts(z, cw, *w)


# -- the counters ------------------------------------------------------------


@pytest.mark.parametrize("on_chip", [True, False], ids=["chip", "cpu"])
def test_a_window_of_more_than_256_tokens_books_the_grouped_counters(
        monkeypatch, on_chip):
    """A 300-token prompt runs as one window of 512 rows: with the kernel's
    grouped form engaged (`experts_grouped` answering as it does on the
    chip) its two routed layers book their calls, the pairs of ALL 512
    rows that fell on the 8 held experts, and the rows of the tiles; a
    20-token prompt's window and the decode steps book nothing. Off the
    chip the plain sum runs and nothing is booked."""
    if on_chip:
        monkeypatch.setattr(
            decoder_common, "experts_grouped",
            lambda tokens, shape, dtype: tokens > pme._TOKEN_TILE)
    cfg = sv_model.latent_moe_tiny(prefill_chunk=512, max_position=1024)
    eng = ServingEngine(cfg, page_size=8, pool_pages=64, max_inflight=4,
                        seed=3)
    seen = []
    note = eng._note_grouped
    monkeypatch.setattr(eng, "_note_grouped",
                        lambda routes: (seen.append(routes), note(routes)))
    rng = np.random.default_rng(4)
    for n in (300, 20):
        eng.submit(rng.integers(1, 97, n).tolist(), 5)
    eng.run_until_drained()
    st = eng.stats
    assert [len(r) for r in seen] == [512, 128] and st["decode_steps"] >= 4
    if not on_chip:
        assert st["moe.grouped_layer_steps"] == st["moe.grouped_pairs"] \
            == st["moe.grouped_tile_rows"] == 0
        return
    routes = seen[0]                             # [512, 2 layers, 2]
    pairs = tile_rows = 0
    for layer in range(cfg.routed_layers):
        held = np.sort(routes[:, layer][routes[:, layer] < cfg.experts_held])
        pairs += len(held)
        tile_rows += pme.GROUP_TILE * sum(
            len(np.unique(held[at:at + pme.GROUP_TILE]))
            for at in range(0, len(held), pme.GROUP_TILE))
    assert st["moe.grouped_layer_steps"] == cfg.routed_layers
    assert st["moe.grouped_pairs"] == pairs > 300
    assert st["moe.grouped_tile_rows"] == tile_rows >= pairs
