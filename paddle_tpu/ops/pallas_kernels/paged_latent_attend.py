"""A decode row's absorbed latent attention over ALL its pages, read in place
from the paged latent pool: a Pallas TPU kernel.

A decode row of the "latent_moe" block WITHOUT an indexer attends every
cached row of its page table, all heads against the same rows: `s[h, t] =
q_lat[h] . c[t] + q_rope[h] . r[t]`, softmax over the live t, `u[h] = sum_t
p[h, t] c[t]` (`latent_moe_ops.absorbed_attention_fn`). Gathered through XLA
first (`latent_pool[table]`, what the short-context step does) a step of 64
rows behind 33k tokens copies 3.6 GB a layer before it reads them, and
`latent_attend.py` holds a query's whole `[heads, K]` scores in VMEM, which
33k rows do not fit. This kernel copies nothing and holds a block's scores:

  * grid (row, page BLOCK), as `paged_indexer`: a grid step covers `G`
    pages of one row (`pages_per_grid_step`). The pool `[rows, page_size,
    words]` stays in HBM (`pl.ANY`); a page is one DMA into one half of a
    two-block VMEM scratch, its index read from the row's table (scalar
    prefetch, already shifted to the layer's rows and clamped). The DMAs of
    the NEXT live block (this row's, or the first of the next row that has
    context: `paged_attention._step_ahead`) are started before this block is
    waited for, so they fly while it is scored. A live block is fetched
    WHOLE: the pages past a row's length are whatever its table names there
    (a real page: the wrapper clamps), at most `G - 1` a row, and their
    positions are masked.
  * a block's rows are unpacked IN VMEM, `chunk` pages at a time, as
    `latent_attend.unpack_words` does (a word's two bfloat16 values
    exactly); one product a chunk on the MXU, the heads' queries `[nh,
    kv_rank + 256]` (latent, rotary low half, rotary high half, each in whole
    lane tiles) against the chunk's values; the chunks past the row's
    length are skipped.
  * an ONLINE softmax across chunks and blocks: a running maximum, sum and
    weighted sum of the latents (`[nh, 128]`, `[nh, 128]`, `[nh, kv_rank]`
    float32 scratch), set at a row's block 0 and written out, normalised,
    at its last block; the probabilities are rounded to bfloat16 before the
    weighted sum, as the reference rounds them. A padding row (length 0)
    reads nothing and writes zeros.

`q_lat` and `q_rope` are rounded ONCE to the cache dtype, as the reference
rounds them. Forward-only: serving never differentiates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention_ops import _NEG_INF     # what a masked position scores
from . import workbench
from .latent_attend import _pad_lanes, unpack_words
from .paged_attention import _step_ahead

# tests flip this to run the kernel through the Pallas interpreter on CPU
INTERPRET = False

# words of one grid step's pages (the kernel keeps two such blocks: the one
# it scores and the next one's DMAs in flight): 8 pages of 128 rows of 384
BLOCK_BYTES = 3 * 512 * 1024
# rows unpacked and scored at a time inside a grid step
CHUNK_ROWS = 512
VMEM_LIMIT = 32 * 1024 * 1024
# entries of the page table one call prefetches into scalar memory
# (`paged_indexer.TABLE_ENTRIES`)
TABLE_ENTRIES = 128 * 1024

_LANES = workbench.LANES


def pages_per_grid_step(bucket_pages: int, page_bytes: int) -> int:
    """G: how many pages of one row a grid step covers: the largest divisor
    of the page bucket whose rows fit `BLOCK_BYTES`."""
    return workbench.pick_block(int(bucket_pages), int(page_bytes),
                                budget=BLOCK_BYTES, prefer_multiple=4)


def chunk_pages(group: int, page_size: int) -> int:
    """Pages unpacked and scored at a time: the largest divisor of a block's
    `group` pages whose rows stay under `CHUNK_ROWS`."""
    return max(c for c in range(1, group + 1)
               if group % c == 0 and c * page_size <= max(CHUNK_ROWS,
                                                          page_size))


def paged_latent_attend_supported(q_shape, pool_shape, dtype=jnp.bfloat16,
                                  rope_dim: int = 64) -> bool:
    """Shapes this kernel handles: q_lat `[B, nh, kv_rank]` over a pool
    `[rows, page_size, words]` of 32-bit words that hold bfloat16 values.
    Whole tiles everywhere (`latent_attend_supported`'s rule for the words;
    a page whole sublane tiles of 128 rows) and a page modest enough that a
    block of them double-buffers in VMEM. Everything else (the CPU
    rehearsals' 8-token pages of float32 rows) takes the XLA form over the
    gathered pages."""
    from ..latent_moe_ops import latent_words

    if len(q_shape) != 3 or len(pool_shape) != 3:
        return False
    if jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16):
        return False
    B, nh, kv_rank = q_shape
    _, ps, words = pool_shape
    side, key = latent_words(kv_rank, rope_dim, dtype)
    return (B > 0 and nh % 8 == 0 and kv_rank % 2 == 0 and rope_dim % 2 == 0
            and side > 0 and side % _LANES == 0 and 0 < key <= _LANES
            and words % _LANES == 0 and words >= side + _LANES
            and ps > 0 and ps % _LANES == 0
            and ps * words * 4 <= BLOCK_BYTES)


def _kernel(pt_ref, kl_ref, nxt_ref, q_ref, pool_hbm, o_ref, buf, sem,
            count_ref, m_ref, l_ref, acc_ref, *, page_size, group, chunk,
            side, key, scale):
    """One grid step: block i (pages i * group ..) of row b."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    rows, blocks = pl.num_programs(0), pl.num_programs(1)
    kv_len = kl_ref[b]
    first = i * (group * page_size)              # the block's first position
    width = chunk * page_size
    geometry = dict(page_size=page_size, group=group)

    @pl.when((b == 0) & (i == 0))
    def _call_start():
        count_ref[0] = 0

    @pl.when(i == 0)
    def _row_start():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def start(base, into):
        def one_page(slot, carry):
            pltpu.make_async_copy(pool_hbm.at[pt_ref[base + slot]],
                                  buf.at[into, slot], sem.at[into]).start()
            return carry
        jax.lax.fori_loop(0, group, one_page, 0, unroll=True)

    # a block past the row's length is neither fetched nor scored
    @pl.when(first < kv_len)
    def _block():
        n = count_ref[0]
        half = jax.lax.rem(n, 2)
        b2, i2, there = _step_ahead(kv_len, nxt_ref[b], b, i, rows,
                                    **geometry)

        @pl.when(n == 0)
        def _first_of_the_call():
            start((b * blocks + i) * group, half)

        @pl.when(there)
        def _ahead():
            start((b2 * blocks + i2) * group, 1 - half)

        # ONE wait for the block's pages: its semaphore counts them all
        pltpu.make_async_copy(pool_hbm.at[pl.ds(0, group)], buf.at[half],
                              sem.at[half]).wait()
        q = q_ref[0]                                 # [nh, 2 * side + 256]
        dt = q.dtype

        def score(c, carry):
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            w = buf[half, at].reshape(width, buf.shape[-1])   # [width, words]
            tile = w[:, side:side + _LANES]
            lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
            halves = unpack_words(w[:, :side]) \
                + unpack_words(jnp.where(lane < key, tile, 0))
            k = jnp.concatenate([h.astype(dt) for h in halves], axis=-1)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [nh, width]
            pos = first + c * width + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(pos < kv_len, s * scale, _NEG_INF)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # position 0 is live and comes first, so the maximum is a real
            # score and a masked one's exponential is 0
            p = jnp.exp(s - m_new)
            l_ref[...] = jnp.broadcast_to(
                alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
                l_ref.shape)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(dt), k[:, :2 * side],
                preferred_element_type=jnp.float32)
            return carry

        live = jnp.minimum(
            jax.lax.div(kv_len - first + (width - 1), width), group // chunk)
        jax.lax.fori_loop(0, live, score, 0)
        count_ref[0] = n + 1

    @pl.when(i == blocks - 1)
    def _row_end():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _call(q_lat, q_rope, pool, page_table, lens, scale, interpret):
    from ..latent_moe_ops import latent_words

    B, nh, kv_rank = q_lat.shape
    rows, ps, words = pool.shape
    P = page_table.shape[1]
    dt = jnp.bfloat16
    side, key = latent_words(kv_rank, q_rope.shape[-1], dt)
    group = pages_per_grid_step(P, ps * words * 4)
    chunk = chunk_pages(group, ps)
    blocks = P // group
    lens = lens.astype(jnp.int32)
    # clamp so a padded/garbage table entry names a real page
    table = jnp.clip(page_table, 0, rows - 1).astype(jnp.int32).reshape(
        B * P)
    # the next row after b that has any context (B: none)
    has = jnp.where(lens > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jnp.concatenate([jax.lax.cummin(has[::-1])[::-1][1:],
                           jnp.full((1,), B, jnp.int32)])
    # the query as the rows' values lie (`latent_attend._call`)
    q = jnp.concatenate([q_lat.astype(dt),
                         _pad_lanes(q_rope[..., :key].astype(dt)),
                         _pad_lanes(q_rope[..., key:].astype(dt))], axis=-1)
    row = lambda shape: pl.BlockSpec(                        # noqa: E731
        (1,) + shape, lambda b, i, pt, kl, nx: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, blocks),
        in_specs=[row((nh, q.shape[-1])), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row((nh, kv_rank)),
        scratch_shapes=[
            pltpu.VMEM((2, group, ps, words), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),              # a half's pages
            pltpu.SMEM((1,), jnp.int32),                # live blocks so far
            pltpu.VMEM((nh, _LANES), jnp.float32),      # running maximum
            pltpu.VMEM((nh, _LANES), jnp.float32),      # running sum
            pltpu.VMEM((nh, kv_rank), jnp.float32),     # weighted latents
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page_size=ps, group=group, chunk=chunk,
                          side=side, key=key, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, kv_rank), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * nh * P * ps * (2 * kv_rank + q_rope.shape[-1]),
            transcendentals=B * nh * P * ps,
            bytes_accessed=B * P * ps * words * 4 + B * nh * (
                q.shape[-1] * 2 + kv_rank * 4)),
        # a block's DMAs are started while the live block before it is
        # scored, across rows, and a row's blocks share its running sums:
        # the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="paged_latent_attention",
    )(table, lens, nxt, q, pool)


def _reference(q_lat, q_rope, pool, page_table, lens, dtype, geom):
    """The XLA form (`latent_moe_ops.absorbed_attention_fn` over the
    gathered pages): the numeric oracle, and the arm the gate refuses to."""
    from ..latent_moe_ops import absorbed_attention_fn

    B, P = page_table.shape
    slabs = pool[jnp.clip(page_table, 0, pool.shape[0] - 1)].reshape(
        B, P * pool.shape[1], -1)
    have = jnp.arange(slabs.shape[1], dtype=jnp.int32)[None, :] \
        < lens.astype(jnp.int32)[:, None]
    u = absorbed_attention_fn(q_lat, q_rope, slabs, have, dtype, geom)
    return jnp.where((lens > 0)[:, None, None], u, 0.0)


@workbench.register_kernel(
    "paged_latent_attention",
    reference=_reference,
    supported=paged_latent_attend_supported,
    decision_op="attention",
    equivalence_test="test_paged_latent_attention_pallas_matches_reference",
    note="the absorbed latent attention of B decode rows, each over ALL the "
         "pages of its table, read in place from the latent pool [rows, "
         "page_size, words] of packed bfloat16 words; scalar-prefetch "
         "page-table DMA, online softmax across page blocks; forward-only")
def paged_latent_attention(q_lat, q_rope, pool, page_table, lens, dtype,
                           geom):
    """q_lat [B, nh, kv_rank], q_rope [B, nh, rope] float32; pool `[rows,
    page_size, words]` int32 (the latent rows of all layers, `dtype`
    (bfloat16) values); page_table [B, P] int32, already shifted to the
    layer's rows (row b's context lives in pages `page_table[b, 0 ..
    ceil(lens[b] / page_size))`); lens [B] live positions (0: a row the
    scheduler padded in, which reads nothing and gets zeros) -> u [B, nh,
    kv_rank] float32. Callers gate on `paged_latent_attend_supported`."""
    from ..latent_moe_ops import softmax_scale

    del dtype
    B, P = page_table.shape
    scale = float(softmax_scale(geom))
    at_once = max(1, TABLE_ENTRIES // P)
    if B <= at_once:
        return _call(q_lat, q_rope, pool, page_table, lens, scale,
                     bool(INTERPRET))
    return jnp.concatenate([
        _call(q_lat[r:r + at_once], q_rope[r:r + at_once], pool,
              page_table[r:r + at_once], lens[r:r + at_once], scale,
              bool(INTERPRET)) for r in range(0, B, at_once)])
