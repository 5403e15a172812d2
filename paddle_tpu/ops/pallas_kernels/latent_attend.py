"""The absorbed attention over each query's gathered latent rows: a Pallas
TPU kernel.

A decode row (or a window's query) of the "latent_moe" block attends the K
cache rows it was given, all heads against the SAME rows: `s[h, k] =
q_lat[h] . c[k] + q_rope[h] . r[k]`, softmax over k, `u[h] = sum_k p[h, k]
c[k]` (`latent_moe_ops.absorbed_attention_fn`). The rows arrive as
`gather_rows_fn` wrote them: `[R, K, words]` 32-bit words, a word holding
two bfloat16 values (`sparse_moe_ops.join_rows_fn`: value `i` of a side in
the low half of word `i`, value `i + half` in the high half). The XLA form
unpacks them into a `[R, K, kv_rank]` copy, writes the `[R, heads, K]`
float32 scores, reads them for the softmax, writes them again and reads the
latents a second time for the sum: 16.9 ms of a 60.5 ms decode step at 128
rows of 2,048 for bytes and operations worth 2.5 (PERF.md, PR 40). This
kernel reads a query's rows ONCE and writes only `u`:

  * grid (query): a query's K rows of words (3 MB at 2,048 rows of 384)
    arrive through a plain `BlockSpec` pipeline, the next query's in flight
    behind them, and are unpacked IN VMEM, `chunk` rows at a time: a
    bfloat16 value is the top half of a float32, so `w << 16` and `w &
    0xFFFF0000`, bitcast, are a word's low and high value EXACTLY (the
    conversion to the MXU's bfloat16 then drops sixteen zero bits). The
    latent's words give its two halves; the rotary key's words share one
    128-lane tile with the row's padding, whose lanes are zeroed (a select)
    before they meet the query's zero padding, so what lies there is read
    by no product.
  * one product a chunk on the MXU: the heads' queries `[nh, kv_rank +
    256]` (latent, rotary low half, rotary high half, each padded to whole
    lane tiles by the caller) against the chunk's values contracting the
    last axis of both, float32 accumulation. The chunk's latent stays in a
    VMEM scratch for the weighted sum: nothing is read twice from HBM.
  * the softmax is the reference's own over the query's whole `[nh, K]`
    scores (1 MB in VMEM): maximum, exponential, sum, normalised, then
    rounded to bfloat16 before the weighted sum as the reference rounds
    them. An online softmax over blocks of 512 or 1,024 rows was 10-30%
    slower at K = 2,048 and rounds the probabilities before they are
    normalised (PERF.md, PR 41), so a K whose words pass `BLOCK_BYTES` is
    left to the XLA form.

`q_lat` and `q_rope` are rounded ONCE to the cache dtype, as the reference
rounds them. Forward-only: serving never differentiates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention_ops import _NEG_INF     # what a missing row scores
from . import workbench

# tests flip this to run the kernel through the Pallas interpreter on CPU
INTERPRET = False

# words of one query's rows a grid step may hold (two such blocks are in
# flight): 2,048 rows of 384 words
BLOCK_BYTES = 3 * 1024 * 1024
# rows unpacked and scored at a time inside a grid step
CHUNK_ROWS = 512
# VMEM the kernel may take: two blocks of words in flight, the unpacked
# latents (2 MB at 2,048 rows of 512), a query's scores (1 MB) and the
# softmax's temporaries
VMEM_LIMIT = 32 * 1024 * 1024

_LANES = workbench.LANES


def chunk_rows(K: int) -> int:
    """Rows unpacked and scored at a time: the largest whole-tile divisor
    of a query's K rows under `CHUNK_ROWS` (0: none)."""
    return next((c for c in range(min(CHUNK_ROWS, K), 0, -1)
                 if K % c == 0 and c % _LANES == 0), 0)


def latent_attend_supported(q_shape, rows_shape, dtype=jnp.bfloat16,
                            rope_dim: int = 64) -> bool:
    """Shapes this kernel handles: q_lat `[R, nh, kv_rank]` over rows `[R,
    K, words]` of 32-bit words that hold bfloat16 values (the unpacking is
    bfloat16's: the top half of a float32). Whole tiles everywhere: the
    latent's words whole 128-lane tiles, the rotary key's inside the ONE
    tile after them, K whole lane tiles, the heads whole float32 sublane
    tiles; and a query's K rows one block of `BLOCK_BYTES`. Everything
    else (the CPU rehearsals' float32 rows, a K that does not divide) takes
    the XLA form on the same rows."""
    from ..latent_moe_ops import latent_words

    if len(q_shape) != 3 or len(rows_shape) != 3:
        return False
    if jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16):
        return False
    R, nh, kv_rank = q_shape
    R2, K, words = rows_shape
    side, key = latent_words(kv_rank, rope_dim, dtype)
    return (R == R2 and R > 0 and nh % 8 == 0 and kv_rank % 2 == 0
            and rope_dim % 2 == 0 and side > 0 and side % _LANES == 0
            and 0 < key <= _LANES and words % _LANES == 0
            and words >= side + _LANES and K > 0 and chunk_rows(K) > 0
            and K * words * 4 <= BLOCK_BYTES)


def unpack_words(w):
    """int32 words -> (low, high) float32: the two bfloat16 values a word
    holds, exactly (a bfloat16 is the top half of a float32)."""
    lo = jax.lax.bitcast_convert_type(w << 16, jnp.float32)
    hi = jax.lax.bitcast_convert_type(w & jnp.int32(-65536), jnp.float32)
    return lo, hi


def _kernel(q_ref, rows_ref, have_ref, o_ref, c_buf, s_buf, *, side, key,
            chunk, scale):
    """One grid step: query r over its K rows."""
    K = rows_ref.shape[1]
    q = q_ref[0]                                 # [nh, 2 * side + 256]
    dt = q.dtype

    def score(c, carry):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        w = rows_ref[0, at, :]                                # [chunk, words]
        tile = w[:, side:side + _LANES]
        lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
        halves = unpack_words(w[:, :side]) \
            + unpack_words(jnp.where(lane < key, tile, 0))
        k = jnp.concatenate([h.astype(dt) for h in halves], axis=-1)
        c_buf[at, :] = k[:, :2 * side]
        s_buf[:, at] = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [nh, chunk]
        return carry

    jax.lax.fori_loop(0, K // chunk, score, 0)
    s = jnp.where(have_ref[0] > 0, s_buf[...] * scale, _NEG_INF)  # [nh, K]
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = e * (1.0 / jnp.sum(e, axis=-1, keepdims=True))
    o_ref[0] = jnp.dot(p.astype(dt), c_buf[...],
                       preferred_element_type=jnp.float32)


def _pad_lanes(x):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, _LANES - x.shape[-1])])


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret"))
def _call(q_lat, q_rope, rows, have, scale, chunk, interpret):
    from ..latent_moe_ops import latent_words

    R, nh, kv_rank = q_lat.shape
    _, K, words = rows.shape
    dt = jnp.bfloat16
    side, key = latent_words(kv_rank, q_rope.shape[-1], dt)
    # the query as the rows' values lie: the latent (its halves are the
    # words' low and high values in order), then the rotary key's low and
    # high halves, each in the first lanes of a tile of its own
    q = jnp.concatenate([q_lat.astype(dt),
                         _pad_lanes(q_rope[..., :key].astype(dt)),
                         _pad_lanes(q_rope[..., key:].astype(dt))], axis=-1)
    width = q.shape[-1]
    return pl.pallas_call(
        functools.partial(_kernel, side=side, key=key, chunk=chunk,
                          scale=scale),
        grid=(R,),
        in_specs=[pl.BlockSpec((1, nh, width), lambda r: (r, 0, 0)),
                  pl.BlockSpec((1, K, words), lambda r: (r, 0, 0)),
                  pl.BlockSpec((1, 1, K), lambda r: (r, 0, 0))],
        out_specs=pl.BlockSpec((1, nh, kv_rank), lambda r: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, nh, kv_rank), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, kv_rank), dt),       # the latents
                        pltpu.VMEM((nh, K), jnp.float32)],  # the scores
        cost_estimate=pl.CostEstimate(
            flops=2 * R * nh * K * (2 * kv_rank + q_rope.shape[-1]),
            transcendentals=R * nh * K,
            bytes_accessed=R * K * words * 4 + R * nh * (
                width * 2 + kv_rank * 4)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="latent_rows_attention",
    )(q, rows, have.astype(jnp.int32)[:, None, :])


def _reference(q_lat, q_rope, rows, have, dtype, geom):
    """The XLA form: the numeric oracle, and the arm the gate refuses to."""
    from ..latent_moe_ops import absorbed_attention_fn

    return absorbed_attention_fn(q_lat, q_rope, rows, have, dtype, geom)


@workbench.register_kernel(
    "latent_rows_attention",
    reference=_reference,
    supported=latent_attend_supported,
    decision_op="attention",
    equivalence_test="test_latent_rows_attention_pallas_matches_reference",
    note="the absorbed latent attention of R queries, each over its own K "
         "gathered cache rows [R, K, words] of packed bfloat16 words, "
         "unpacked in VMEM; forward-only")
def latent_rows_attention(q_lat, q_rope, rows, have, dtype, geom):
    """What `latent_moe_ops.absorbed_attention_fn` computes, by its
    arguments: q_lat [R, nh, kv_rank], q_rope [R, nh, rope] float32; rows
    [R, K, words] int32 cache rows of `dtype` (bfloat16) values; have [R,
    K] (which of them exist) -> u [R, nh, kv_rank] float32.
    Callers gate on `latent_attend_supported`."""
    from ..latent_moe_ops import softmax_scale

    return _call(q_lat, q_rope, rows, have, float(softmax_scale(geom)),
                 chunk_rows(rows.shape[1]), bool(INTERPRET))
