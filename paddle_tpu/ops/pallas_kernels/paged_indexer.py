"""The lightning indexer's decode scores, straight from the paged key pool:
a Pallas TPU kernel.

A decode row of a block that SELECTS its context (`sparse_moe_ops`,
`latent_moe_ops`) scores every cached token of its page table before it
attends: `I[b, t] = sum_j w[b, j] * relu(qI[b, j, :] . kI[t, :])`. The keys
live in pages of the indexer pool, `[rows, index_dim, page_size]`: a
token's key is one LANE of its page's slab. The XLA form gathers every
row's pages into a `[B, P, D, page_size]` copy, writes the `[B, J, P *
page_size]` float32 products and reads them back to sum the heads: at 128
rows behind 33k tokens that copy and those products were 28% of the
device for a piece whose bytes cost 7% (ledger, PR 39). This kernel writes
neither:

  * grid (row, page BLOCK), as `paged_attention`: a grid step covers `G`
    pages of one row (`pages_per_grid_step`: the largest divisor of the
    page bucket whose slabs fit `BLOCK_BYTES`), in CHUNKS of `CHUNK_PAGES`.
    The pool stays in HBM (`pl.ANY`); a page is one DMA into one half of a
    two-block VMEM scratch, its index read from the row's table (scalar
    prefetch, already shifted to the layer's rows). Every row reads its
    own pages, once: no page is read for two rows at a time.
  * the DMAs of the NEXT live block (this row's, or the first of the next
    row that has context) are started WHILE this block is scored: the body
    of the loop over a block's live chunks waits for its own chunk (a
    semaphore a chunk and half, so the wait is for these pages and no
    others), scores it, and starts the same chunk of the block ahead, all
    in one straight line, so the scalar work of a DMA's start rides in the
    bundles of the products. With the starts and the waits in loops of
    their own before the products (as `paged_attention._fetch_block` has
    them) the two took turns: 2.44 ms a layer at 128 rows behind 33k
    tokens where the copies alone take 1.67 and the products alone 1.14;
    in one body 1.69 (my chip runs, PR 40). Only the first live block of a
    call starts its own copies.
  * a chunk is fetched WHOLE: the pages of a row's last live chunk past
    its length are whatever its table names there (the engine pads a table
    with page 0; a garbage entry is clamped to a real page), at most
    `CHUNK_PAGES - 1` slabs a row. What is scored past a row's length is
    then a real page's keys, finite, and `select_indices_fn` masks it.
  * a page is one product on the MXU: `qI[b]` `[J, D]` against the slab
    `[D, page_size]` as it lies (the tokens stay on the lanes, nothing is
    transposed: with the queries stationary and a page's tokens streamed
    the slab and the product pass the transpose unit, 3.88 ms against
    2.80 before the pipelining), bfloat16 operands, float32 accumulation;
    `relu`, the weights and the sum over the heads on the VPU in float32;
    the row of `page_size` scores goes to its lanes of the output block.
    Nothing is carried from block to block (there is no softmax), so a
    block past the row's length is neither fetched nor scored and its
    scores are zeros; so are the chunks past the length inside a live
    block.
  * the body is `CHUNK_PAGES` pages long and the kernel's code is that
    body three times (with, without and only the starts): a decode program
    holds a copy of the kernel for every unrolled layer
    (`paged_attention.MXU_CHUNK_TOKENS`). A chunk's pages are an UNROLLED
    `fori_loop`, so a page's lines are traced once and laid out eight times
    when the kernel is lowered: written out page by page in Python the
    kernel cost each of the three decode programs of the DeepSeek cell 1.2
    s of tracing inside the serving process (0.3 s now, the same 1.70 ms a
    call; my chip runs, PR 40).

`qI` is rounded ONCE to the pool's dtype, as `indexer_scores_fn` rounds
it; the pool is read as stored. Forward-only: serving never differentiates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import workbench
from .paged_attention import _block_pages, _step_ahead

# tests flip this to run the kernel through the Pallas interpreter on CPU
INTERPRET = False

# VMEM one grid step's key pages may take (the kernel keeps two such
# blocks: the one it scores and the next one's DMAs in flight)
BLOCK_BYTES = 2 * 1024 * 1024
# pages one pass of the loop's body waits for, scores and starts
CHUNK_PAGES = 8
# entries of the page table one call prefetches into scalar memory (1 MB on
# a v5e; 128 rows x 288 pages are 36,864): past it the rows are scored in
# groups, a call each
TABLE_ENTRIES = 128 * 1024


def paged_indexer_supported(q_shape, pool_shape,
                            pool_dtype=jnp.bfloat16) -> bool:
    """Shapes this kernel handles: qI [B, J, D] against a pool `[rows, D,
    page_size]` of a 16-bit `pool_dtype`. Whole tiles everywhere: a page's
    tokens fill whole 128-lane rows, D whole sublane tiles of the pool's
    dtype, the J heads whole float32 sublane tiles, and a page's slab is
    modest enough that a block of them double-buffers in VMEM. Everything
    else (the CPU rehearsal geometries: 8-token pages of float32) takes
    the XLA form on the same pool."""
    if len(q_shape) != 3 or len(pool_shape) != 3:
        return False
    _, J, D = q_shape
    _, width, ps = pool_shape
    if jnp.dtype(pool_dtype).itemsize != 2:
        return False
    return (width == D and ps > 0 and ps % workbench.LANES == 0
            and D % workbench.sublanes(pool_dtype) == 0 and J % 8 == 0
            and CHUNK_PAGES * D * ps * 2 <= BLOCK_BYTES)


def pages_per_grid_step(bucket_pages: int, page_bytes: int) -> int:
    """G: how many pages of one row a grid step covers: the largest divisor
    of the page bucket whose slabs fit `BLOCK_BYTES` (a divisor, so the
    output has no ragged last block)."""
    return workbench.pick_block(int(bucket_pages), int(page_bytes),
                                budget=BLOCK_BYTES, prefer_multiple=8)


# A page's arithmetic, as a jitted function of values: every decode program
# of a (rows, bucket) signature traces this kernel anew, and jit hands the
# later ones the jaxpr of the first (`paged_attention._gqa_update`).

@jax.jit
def _page_scores(q, k, w):
    """q [J, D] and a page's keys k [D, page_size] as stored, w [J, 128]
    float32 (a head's weight on every lane) -> the page's scores [1,
    page_size] float32: one product on the MXU, float32 accumulation, then
    `relu`, the weights and the sum over the heads on the VPU."""
    s = jnp.dot(q, k, preferred_element_type=jnp.float32)       # [J, ps]
    return jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)


def _kernel(pt_ref, kl_ref, nxt_ref, q_ref, w_ref, k_hbm, o_ref, k_buf, sem,
            count_ref, *, page_size, group, chunk):
    """One grid step: block i (pages i * group ..) of row b."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    rows, blocks = pl.num_programs(0), pl.num_programs(1)
    kv_len = kl_ref[b]
    first = i * (group * page_size)              # the block's first slot
    width = chunk * page_size
    geometry = dict(page_size=page_size, group=group)

    def live_chunks(length, block):
        return jax.lax.div(_block_pages(length, block, **geometry)
                           + (chunk - 1), chunk)

    @pl.when((b == 0) & (i == 0))
    def _call_start():
        count_ref[0] = 0

    def zeros(c, carry):
        at = pl.ds(pl.multiple_of(c * width, width), width)
        o_ref[0, :, at] = jnp.zeros((1, width), jnp.float32)
        return carry

    # a block past the row's length is neither fetched nor scored (a row
    # the scheduler padded in, length 0, has no other kind)
    @pl.when(first >= kv_len)
    def _dead():
        jax.lax.fori_loop(0, group // chunk, zeros, 0)

    @pl.when(first < kv_len)
    def _block():
        n = count_ref[0]
        half = jax.lax.rem(n, 2)
        b2, i2, there = _step_ahead(kv_len, nxt_ref[b], b, i, rows,
                                    **geometry)
        own = live_chunks(kv_len, i)
        ahead = jax.lax.select(there, live_chunks(kl_ref[b2], i2), 0)
        here, then = (b * blocks + i) * group, (b2 * blocks + i2) * group
        q, w = q_ref[0], w_ref[0]                    # [J, D], [J, 128]

        # a chunk's pages in an UNROLLED loop: traced once, laid out `chunk`
        # times in one straight line when the kernel is lowered (the
        # module's text says what tracing them one by one cost)

        def pages_of(c, one_page):
            first = c * chunk

            def body(u, carry):
                one_page(first + u)
                return carry
            jax.lax.fori_loop(0, chunk, body, 0, unroll=True)

        def start(base, into, c):
            def one_page(slot):
                pltpu.make_async_copy(k_hbm.at[pt_ref[base + slot]],
                                      k_buf.at[into, slot],
                                      sem.at[into, c]).start()
            pages_of(c, one_page)

        def score(c):
            # ONE wait for the chunk's pages: its semaphore counts them all
            pltpu.make_async_copy(
                k_hbm.at[pl.ds(0, chunk)],
                k_buf.at[half, pl.ds(pl.multiple_of(c * chunk, chunk), chunk)],
                sem.at[half, c]).wait()

            def one_page(slot):
                at = pl.ds(pl.multiple_of(slot * page_size, page_size),
                           page_size)
                o_ref[0, :, at] = _page_scores(q, k_buf[half, slot], w)
            pages_of(c, one_page)

        def score_and_start(c, carry):
            score(c)
            start(then, 1 - half, c)
            return carry

        def score_only(c, carry):
            score(c)
            return carry

        # the starts nobody's products cover, in ONE loop: the first live
        # block of a call has nobody before it and starts its own chunks;
        # then the chunks the block ahead has beyond this one's
        unstarted = jax.lax.select(n == 0, own, 0)
        both = jnp.minimum(own, ahead)

        def start_alone(j, carry):
            late = j >= unstarted
            start(jax.lax.select(late, then, here),
                  jax.lax.select(late, 1 - half, half),
                  j - jax.lax.select(late, unstarted - both, 0))
            return carry

        jax.lax.fori_loop(0, unstarted, start_alone, 0)
        jax.lax.fori_loop(0, both, score_and_start, 0)
        jax.lax.fori_loop(both, own, score_only, 0)
        jax.lax.fori_loop(unstarted, unstarted + ahead - both, start_alone,
                          0)
        jax.lax.fori_loop(own, group // chunk, zeros, 0)
        count_ref[0] = n + 1


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(qi, w, i_pool, page_table, lens, interpret):
    B, J, D = qi.shape
    rows, _, ps = i_pool.shape
    P = page_table.shape[1]
    group = pages_per_grid_step(P, D * ps * i_pool.dtype.itemsize)
    chunk = max(c for c in range(1, CHUNK_PAGES + 1) if group % c == 0)
    blocks = P // group
    lens = lens.astype(jnp.int32)
    # clamp so a padded/garbage table entry names a real page
    table = jnp.clip(page_table, 0, rows - 1).astype(jnp.int32).reshape(
        B * P)
    # the next row after b that has any context (B: none)
    has = jnp.where(lens > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jnp.concatenate([jax.lax.cummin(has[::-1])[::-1][1:],
                           jnp.full((1,), B, jnp.int32)])
    row = lambda shape: pl.BlockSpec(                        # noqa: E731
        (1,) + shape, lambda b, i, pt, kl, nx: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, blocks),
        in_specs=[row((J, D)), row((J, workbench.LANES)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, group * ps),
                               lambda b, i, pt, kl, nx: (b, 0, i)),
        scratch_shapes=[
            pltpu.VMEM((2, group, D, ps), i_pool.dtype),
            pltpu.SemaphoreType.DMA((2, group // chunk)),   # [half, chunk]
            pltpu.SMEM((1,), jnp.int32),                # live blocks so far
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page_size=ps, group=group, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, P * ps), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * J * D * P * ps, transcendentals=0,
            bytes_accessed=(B * P * D * ps * i_pool.dtype.itemsize
                            + B * P * ps * 4)),
        # a block's DMAs are started while the live block before it is
        # scored, across rows: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_indexer_scores",
    )(table, lens, nxt, qi.astype(i_pool.dtype),
      jnp.broadcast_to(w.astype(jnp.float32)[:, :, None],
                       (B, J, workbench.LANES)), i_pool)


def _reference(qi, w, i_pool, page_table, lens):
    """The XLA form (`sparse_moe_ops.indexer_scores_fn` over the gathered
    pages): the numeric oracle, and the arm the gate refuses to."""
    from ..sparse_moe_ops import indexer_scores_fn

    pages = jnp.clip(page_table, 0, i_pool.shape[0] - 1)
    return indexer_scores_fn(qi[:, None], w[:, None], i_pool[pages])


@workbench.register_kernel(
    "indexer_paged_scores",
    reference=_reference,
    supported=paged_indexer_supported,
    decision_op="attention",
    equivalence_test="test_paged_indexer_pallas_matches_reference",
    note="a decode step's lightning-indexer scores over the paged key pool "
         "[rows, index_dim, page_size]; scalar-prefetch page-table DMA, "
         "forward-only")
def paged_indexer_scores(qi, w, i_pool, page_table, lens):
    """One decode step's indexer scores.

    qi: [B, J, D] float32 (this step's indexer queries a row); w: [B, J]
    float32 (the heads' weights, the score's scale folded in); i_pool:
    `[rows, D, page_size]` (the indexer keys of all layers); page_table:
    [B, P] int32, already shifted to the layer's rows (row b's context
    lives in pages `page_table[b, 0 .. ceil(lens[b] / page_size))`); lens:
    [B] int32 live tokens (0: a row the scheduler padded in). Returns `I`
    [B, 1, P * page_size] float32 as `select_indices_fn` takes it: the live
    positions scored, zeros past a row's last live chunk of pages, nothing
    NaN.
    Callers gate on `paged_indexer_supported`."""
    B, P = page_table.shape
    at_once = max(1, TABLE_ENTRIES // P)
    if B <= at_once:
        return _call(qi, w, i_pool, page_table, lens, bool(INTERPRET))
    return jnp.concatenate([
        _call(qi[r:r + at_once], w[r:r + at_once], i_pool,
              page_table[r:r + at_once], lens[r:r + at_once],
              bool(INTERPRET)) for r in range(0, B, at_once)])
