"""The latent rows attention kernel (`pallas_kernels.latent_attend`) through
the Pallas interpreter on the CPU, against `absorbed_attention_fn` over the
same packed rows: the three callers of `latent_moe_ops._attend_rows` (a
decode step's selection, a decode step over a context that fits the
selection, a window's block of 64 queries) at CPU sizes, the unpacking bit
for bit, and the shape gate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import latent_moe_ops as lm
from paddle_tpu.ops.pallas_kernels import latent_attend as la
from paddle_tpu.ops.sparse_moe_ops import _word_values
# what a float32 result and one that carries bfloat16 roundings may differ
# from their reference by, relative to its largest value
from tools.kernel_check import TOL


NH, KV_RANK, ROPE, WORDS = 8, 256, 64, 256


def _geom(**over):
    kw = dict(num_heads=NH, nope_dim=16, rope_dim=ROPE, v_dim=16,
              kv_rank=KV_RANK, rope_theta=1e4, yarn=(1.0,),
              softmax_mscale=1.2, eps=1e-6, index_heads=2, index_dim=8,
              index_topk=256, experts_per_token=2, expert_groups=1,
              groups_per_token=1, routed_scaling=1.0, experts_held=2)
    kw.update(over)
    return lm.Geometry(**kw)


def _have(case):
    """have [R, K]: which of a query's K rows exist."""
    if case == "decode_selection":
        # a selection in ascending position, `-1` past its count: rows with
        # every slot, holes at the end, holes in the middle (what a caller
        # may hand in), and a query with a single live row
        K = 256
        sel = np.tile(np.arange(K), (5, 1))
        sel[1, 200:] = -1
        sel[2, 129:] = -1
        sel[3, 40:90] = -1
        sel[3, 128:131] = -1
        sel[4, 1:] = -1
        return sel >= 0
    if case == "whole_context":
        # three 128-token pages of slots, a causal prefix live in each row
        K = 384
        pos = np.asarray([0, 127, 128, 300, 383])
        return np.arange(K)[None, :] <= pos[:, None]
    if case == "window_block":
        # 64 queries of a window, each behind its own count of rows
        K = 128
        return np.arange(K)[None, :] < (1 + np.arange(64) * 2)[:, None]
    raise KeyError(case)


@pytest.mark.parametrize("chunk", ["served_chunk", "chunk_128"])
@pytest.mark.parametrize("case", ["decode_selection", "whole_context",
                                  "window_block"])
def test_latent_rows_attention_pallas_matches_reference(case, chunk,
                                                        monkeypatch):
    """The kernel's `u` against the XLA form's on the same words: the
    reference's roundings (bfloat16 operands, probabilities rounded before
    the sum) with sums in another order, so bfloat16's tolerance; a query
    with ONE live row gives that row's latent to float32's. The padding
    words of a row hold NaN patterns: no product may read them."""
    monkeypatch.setattr(la, "INTERPRET", True)
    if chunk == "chunk_128":
        monkeypatch.setattr(la, "CHUNK_ROWS", 128)
    have = _have(case)
    R, K = have.shape
    geom = _geom()
    ks = jax.random.split(jax.random.PRNGKey(R + K), 4)
    c = jax.random.normal(ks[0], (R, K, KV_RANK), jnp.float32)
    r = jax.random.normal(ks[1], (R, K, ROPE), jnp.float32)
    rows = lm.join_latent_fn(c, r, jnp.bfloat16, WORDS)
    side, key = lm.latent_words(KV_RANK, ROPE, jnp.bfloat16)
    assert rows.shape == (R, K, WORDS) and side + key < WORDS
    rows = rows.at[..., side + key:].set(0x7FC1FFFF)
    q_lat = jax.random.normal(ks[2], (R, NH, KV_RANK), jnp.float32) * 0.4
    q_rope = jax.random.normal(ks[3], (R, NH, ROPE), jnp.float32) * 0.4
    assert la.latent_attend_supported(q_lat.shape, rows.shape, jnp.bfloat16,
                                      ROPE)
    assert lm.latent_attend_runs(q_lat.shape, rows.shape, jnp.bfloat16, ROPE)
    have = jnp.asarray(have)
    got = np.asarray(la.latent_rows_attention(q_lat, q_rope, rows, have,
                                              jnp.bfloat16, geom))
    want = np.asarray(lm.absorbed_attention_fn(q_lat, q_rope, rows, have,
                                               jnp.bfloat16, geom))
    assert got.shape == want.shape == (R, NH, KV_RANK)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= TOL["bfloat16"] * scale
    # sums in another order and a probability's rounding gone the other
    # way are far inside it: the reading here is 1e-4 or less
    assert np.abs(got - want).max() <= 1e-3 * scale
    single = np.asarray(have).sum(-1) == 1
    if single.any():
        at = np.argmax(np.asarray(have)[single], axis=-1)
        latent = np.asarray(c.astype(jnp.bfloat16).astype(jnp.float32))
        alone = latent[np.nonzero(single)[0], at][:, None, :]
        assert np.abs(got[single] - alone).max() <= TOL["float32"] * scale


def test_the_unpacking_in_the_kernel_is_bit_exact():
    """`w << 16` and `w & 0xFFFF0000`, read as float32, are the bfloat16
    values `_word_values` takes out of a word's low and high half, bit for
    bit and on every 16-bit pattern (NaN payloads, infinities, subnormals
    and -0.0 among them): a bfloat16 is the top half of a float32."""
    rng = np.random.default_rng(7)
    every = np.arange(1 << 16, dtype=np.uint32)
    other = rng.permutation(every)
    for low, high in ((every, other), (other, every)):
        words = jax.lax.bitcast_convert_type(
            jnp.asarray(low | (high << 16)), jnp.int32)
        lo, hi = la.unpack_words(words)
        for mine, half, bits in ((lo, False, low), (hi, True, high)):
            assert mine.dtype == jnp.float32
            u = np.asarray(jax.lax.bitcast_convert_type(mine, jnp.uint32))
            assert not (u & 0xFFFF).any()
            theirs = np.asarray(jax.lax.bitcast_convert_type(
                _word_values(words, jnp.bfloat16, halves=(half,)),
                jnp.uint16))
            assert np.array_equal(u >> 16, theirs)
            assert np.array_equal(theirs, bits.astype(np.uint16))
            # and the value the MXU is handed: the same number
            value = np.asarray(mine.astype(jnp.bfloat16).astype(jnp.float32))
            same = np.asarray(_word_values(words, jnp.bfloat16,
                                           halves=(half,)).astype(jnp.float32))
            nan = np.isnan(same)
            assert np.array_equal(np.isnan(value), nan)
            assert np.array_equal(value[~nan], same[~nan])
            assert np.array_equal(np.signbit(value[~nan]),
                                  np.signbit(same[~nan]))


def test_the_gate_takes_the_served_shapes_and_refuses_the_rehearsals():
    bf16 = jnp.bfloat16
    # DeepSeek-V3.2-Exp as served: a decode step's 128 rows and a window's
    # block of 64 queries over a selection of 2,048 rows of 384 words, and
    # a decode step over a table that fits the selection
    assert la.latent_attend_supported((128, 128, 512), (128, 2048, 384), bf16)
    assert la.latent_attend_supported((64, 128, 512), (64, 2048, 384), bf16)
    assert la.latent_attend_supported((16, 128, 512), (16, 1024, 384), bf16)
    assert la.chunk_rows(2048) == 512 and la.chunk_rows(384) == 384 \
        and la.chunk_rows(640) == 128
    # the CPU rehearsal: 4 heads over a latent of 16 in float32 rows
    assert not la.latent_attend_supported((4, 4, 16), (4, 8, 20),
                                          jnp.float32, 4)
    # one thing off at a time: float32 rows, float16 rows (another
    # unpacking), a K that is no whole lane tiles, a K whose words pass a
    # block, words too narrow for the rotary key's tile, rows that are no
    # whole tiles, heads that do not fill a sublane tile, rows of other
    # queries
    assert not la.latent_attend_supported((128, 128, 512), (128, 2048, 384),
                                          jnp.float32)
    assert not la.latent_attend_supported((128, 128, 512), (128, 2048, 384),
                                          jnp.float16)
    assert not la.latent_attend_supported((128, 128, 512), (128, 2000, 384),
                                          bf16)
    assert not la.latent_attend_supported((128, 128, 512), (128, 4096, 384),
                                          bf16)
    assert not la.latent_attend_supported((128, 128, 512), (128, 2048, 256),
                                          bf16)
    assert not la.latent_attend_supported((128, 128, 512), (128, 2048, 288),
                                          bf16)
    assert not la.latent_attend_supported((128, 12, 512), (128, 2048, 384),
                                          bf16)
    assert not la.latent_attend_supported((128, 128, 512), (64, 2048, 384),
                                          bf16)
    # off the chip nothing runs without the interpreter
    assert not lm.latent_attend_runs((128, 128, 512), (128, 2048, 384), bf16,
                                     64)
