"""Ragged paged decode attention: a Pallas TPU kernel over the KV-page pool.

Why this exists ("Ragged Paged Attention", arXiv:2604.15464): the serving
runtime's decode step is one query token per request attending
over that request's whole context, which lives scattered across fixed-size
pages of the preallocated HBM pool. The XLA reference path
(attention_ops._paged_attention_reference) gathers every row's pages into a
dense [B, P*ps, nh, dh] tensor first — at long contexts that materialized
gather IS the decode step's HBM bill. This kernel never materializes it:

  * grid (batch row, page BLOCK): a grid step covers `G` pages of one
    row (`pages_per_grid_step`: the pages whose K + V slabs fit
    `BLOCK_BYTES`, 16 at both serving geometries, never more than the page
    bucket). A row's pages lie scattered in the pool, so no BlockSpec can
    fetch a block: the pools stay in HBM (`pl.ANY`) and the step starts one
    DMA per LIVE page and pool into one half of a two-block VMEM scratch,
    each page index read from the request's page table (scalar prefetch).
    The DMAs of the NEXT live block (this row's, or the first block of the
    next row that has context) are started before the step waits for its
    own, so 2 G copies are in flight while a block is computed. HBM traffic
    is the used pages once, nothing else; a block past the row's context
    costs one empty grid step (about 0.3 us on a v5e). The pool is
    lane-dense, `[num_pages, ps, nh*dh]` (serving/kv_cache.pool_shape): a
    slab is whole (8, 128) tiles in the pool's own row-major layout, so the
    kernel reads the resident buffer — no conversion before the call.
  * the ragged part: rows in one batch have different context lengths
    (`kv_lens`, also scalar-prefetched). A block is computed in chunks of
    `MXU_CHUNK_TOKENS` / `LANE_CHUNK_TOKENS` slots, one online-softmax update
    a chunk (one max, one rescale of m, l, acc) over all its pages: slots
    past a row's length are masked to -1e30, a chunk or block with no live
    slot is branched around, and rows the continuous-batching scheduler
    padded in (kv_len 0) run nothing and emit zeros nobody reads — the
    batch_mask convention from PR 2.
  * heads never leave the lanes: a token's row holds head h in lanes
    h*dh..(h+1)*dh. TWO ARMS, chosen by the shapes a call sees
    (`matrix_unit_arm`):
      - the VECTOR-UNIT arm (`_chunk_update`): as many KV heads as query
        heads and a head NARROWER than a lane register (the BERT decoder's
        12 heads of 64, device op `paged_decode_attention f32[rows,1,768]`;
        also heads of 128 that fill no sublane tile of 8 or whose table's
        chunk fills no lane row). q.k is one [tokens, nh*dh] float32 VPU
        product a 128-lane column, and the per-head sum is an MXU product
        of that column with a 0/1 matrix that joins the lanes of one head
        (`_head_sums`): it leaves every lane holding its head's score. The
        product is split into the three bfloat16 pieces that hold every
        bit of a float32 and the MXU accumulates in float32, so the sum is
        the float32 sum of the same dh terms: nothing is rounded to
        bfloat16 (a butterfly of lane rotations did this until PR 38 and
        was 73% of the call).
      - the MATRIX-UNIT arm (`_gqa_update`): a head IS a whole register
        (dh = 128), so a KV head's lanes are one operand tile as they lie
        and q.k, p.v are MXU products of float32 exactness (`_dot3`: the
        pages as they lie, the float32 queries and probabilities in the
        three pieces that keep every bit, float32 accumulation and softmax
        state). Every grouped-query call (fewer KV heads than query heads:
        ZAYA1, Laguna, Falcon-H1, Nemotron; `paged_decode_attention_gqa`,
        `paged_window_attention_gqa`) and, since PR 55, a call with ONE
        query head a KV head of 128 (Ouro's 16 over 16 in bfloat16 pages of
        16 tokens; it keeps the name `paged_decode_attention`, the output
        `f32[rows,16,128]`, a head a sublane row): on the vector unit that
        call ran at 24% of the memory bandwidth, 45% of the cell's device.
    The online softmax state (m, l, acc; the per-head statistics repeated
    over the head's lanes) lives in VMEM scratch across the blocks of one
    row; the output block is written once, on the row's last grid step.

THE MATRIX-UNIT ARM WITHOUT A FIRST LIVE SLOT WALKS A LIST, NOT A GRID
(PR 50; `_walk_kernel`, under the grid's name for the call). Decode
rows that stand behind one context hold the SAME page ids in their tables
(the prefix cache hands them out), and the grid above fetches and scores
such a page once a row. Here:

  * the plan, from the page table, once a step (`walk_plan`; PR 48's rule,
    `paged_latent_attend.row_groups`): rows whose first block of `G` table
    entries are equal and whole form a group, the group's run is what ALL
    its members share, in whole blocks; a row that shares under one block
    is a group of one and its whole table is its tail. The flat list holds
    every group's shared blocks, then every row's tail blocks (a live row
    at least one, an empty one where its run is all it has), no dead
    block. The plan is the same for every layer: the stacks work it out
    before their layers (`attention_ops.paged_decode_plan_fn`).
  * ONE grid step walks the list in order, the next block's K and V DMAs
    in flight while a block is scored (the two-half scratch above). The
    online-softmax state of the shared runs (m, l, acc) is resident for
    ALL rows, laid out a KV head at a time, `[nkv, rows x heads_per_kv,
    128]` by sorted row, so that a tile of `T` rows x one KV head's query
    heads is one window of sublanes.
  * a SHARED block is scored by tiles of `T` sorted rows (`tile_rows`: 16
    at 1 to 8 heads a KV head, 8 at 16), a group's last rows by a half
    tile: for KV head j the tile's `T x heads_per_kv` query rows, their
    three bfloat16 pieces stacked (`_stack3`), against `k[:, j]` and `v[:,
    j]`, the same `_dot3` products as `_gqa_update`'s, every row of a
    product one that is kept and no `select` over heads
    (`_tile_update`); `HEADS_ABREAST` KV heads in one straight run. A
    shared chunk needs no position mask; a group's last tile masks the
    rows that are not the group's.
  * a TAIL block is `_gqa_update` for its one row, as in the grid, on a
    state of its own (`[nh, 128]`) that starts from what the row gathered
    over its group's run and is written out, normalised, behind the row's
    last tail block. A padding row (length 0) is in no block: zeros.

Only the order in which a row's chunks enter its running maximum changes;
a table that shares nothing gives groups of one and the grid's walk
without its dead steps. On the chip (my runs, PR 50, `tools/kernel_check.
py`'s step of 64 rows x 48 heads behind four contexts of 128 pages, the
grid and the walk alternating on the same arrays): 6,304 us -> 1,587 us a
call, the same rows each behind a context of its own 6,308 -> 6,273,
ZAYA1's step (nothing shared) 359 -> 357. The walk is bound by the matrix
unit: a page x tile of 16 rows costs 1.6 us (of 8 rows 1.05) where the
grid's page x row costs 0.71 us of DMA. A table under two blocks wide, a
table past the scalar memory, or rows whose state would not stay resident
(`walk_supported`) keep the grid; so do the `post_ln` arm and the arm with
a first live slot.

Decode q is a single token per row, so there is no backward pass: the kernel
is forward-only (serving never differentiates), which keeps it free of the
residual bookkeeping the short-seq training kernel needs.
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_latent_attend import TABLE_ENTRIES, row_groups

_NEG_INF = -1e30

# tests flip this to run the kernel through the Pallas interpreter on CPU
INTERPRET = False


_LANES = 128


def paged_supported(q_shape, pool_shape, pool_dtype=jnp.float32) -> bool:
    """Shapes this kernel handles: q [B, nh, dh] against a pool
    [num_pages, page_size, nkv*dh] of `pool_dtype`. A page slab must be
    whole tiles ((8, 128) of float32, (16, 128) of bfloat16: page_size a
    multiple of the sublane count, the row of 128) and modest enough to
    double-buffer in VMEM. With nkv == nh a head must sit inside one
    128-lane register (dh a power of two up to 128; which arm such a call
    takes is `matrix_unit_arm`'s to say); with fewer KV heads
    than query heads (grouped-query) a head is one register (dh 128), the
    query heads fill whole sublane tiles and a page fills whole lanes of
    the score tile. Everything else (the CPU rehearsal geometry, most unit
    tests) takes the XLA path on the same pool."""
    if len(q_shape) != 3 or len(pool_shape) != 3:
        return False
    B, nh, dh = q_shape
    num_pages, ps, width = pool_shape
    itemsize = jnp.dtype(pool_dtype).itemsize
    if itemsize not in (2, 4):
        return False
    tiled = (width % _LANES == 0 and ps % (32 // itemsize) == 0
             and ps * width * itemsize <= 2 * 1024 * 1024)
    if nh * dh == width:
        return tiled and 8 <= dh <= _LANES and dh & (dh - 1) == 0
    nkv = width // dh if dh else 0
    return (tiled and dh == _LANES and nkv * dh == width and nkv > 0
            and nh % nkv == 0 and nh % 8 == 0 and ps % _LANES == 0)


# VMEM one grid step's K + V pages may take, all together (the kernel keeps
# two such blocks: the one it computes on and the next one's DMAs in
# flight). Sixteen pages of a 64 KB grouped-query slab or of the 48 KB slab
# of 12 heads of 64.
BLOCK_BYTES = 2 * 1024 * 1024
# Slots one online-softmax update covers: a block is computed in chunks, a
# loop over the block's live chunks around ONE straight-line body (the body
# is the kernel's code, and a decode program holds a copy for every layer:
# unrolled chunk and column bodies made a decode program 4.5 MB larger and
# a serving cell's warm set-up 9 s longer; my chip run, PR 26).
# The grouped arm keeps four MXUs busy over eight 128-token pages. The arm
# with the heads side by side in the lanes pays its reductions over the
# slots and its rescales once a chunk and column, so its chunk is the whole
# block of 16 x 16 slots: 64 rows of 19-28 pages in a 32-page bucket take
# 337 us a call at 256 slots a chunk, 401 at 128 and 571 at 64, although
# the smaller chunks skip dead pages (my chip run, PR 38).
MXU_CHUNK_TOKENS = 1024
LANE_CHUNK_TOKENS = 256


def pages_per_grid_step(bucket_pages: int, page_size: int, width: int,
                        itemsize: int) -> int:
    """G: how many pages of one row a grid step covers. The largest power
    of two whose K + V slabs fit `BLOCK_BYTES`, and never more than the
    page bucket. The kernel's grid and the engine's
    `serving.decode_grid_steps` both come from here."""
    fit = max(1, BLOCK_BYTES // (2 * page_size * width * itemsize))
    return max(1, min(int(bucket_pages), 1 << (fit.bit_length() - 1)))


def grid_steps(rows: int, bucket_pages: int, page_size: int, width: int,
               itemsize: int) -> int:
    """Grid steps of one call: rows x page blocks."""
    group = pages_per_grid_step(bucket_pages, page_size, width, itemsize)
    return rows * -(-bucket_pages // group)


def _chunk_pages(group: int, page_size: int, chunk_tokens: int) -> int:
    """Pages of one online-softmax update: a divisor of the block's `group`
    pages that holds `chunk_tokens` slots at most."""
    return math.gcd(group, max(1, chunk_tokens // page_size))


def matrix_unit_arm(q_shape, pool_shape, pool_dtype, bucket_pages) -> bool:
    """Which arm a supported call takes, from its shapes alone: True where
    q.k and p.v are products on the matrix unit (`_gqa_update`), False
    where the heads lie side by side in the lanes and the products stay on
    the vector unit (`_chunk_update`). Every grouped-query call takes the
    matrix unit (the only arm it has). With as many KV heads as query
    heads it is taken where a head IS a whole 128-lane register (dh 128:
    a KV head's lanes are one operand tile as they lie), the query heads
    fill whole sublane tiles and a chunk of the call's page blocks fills
    whole lanes of the score tile; a narrower head (12 heads of 64: two
    heads a register) keeps the vector unit."""
    _, nh, dh = q_shape
    _, ps, width = pool_shape
    if nh * dh != width:
        return True
    group = pages_per_grid_step(bucket_pages, ps, width,
                                jnp.dtype(pool_dtype).itemsize)
    return (dh == _LANES and nh % 8 == 0
            and _chunk_pages(group, ps, MXU_CHUNK_TOKENS) * ps % _LANES == 0)


def _bf16_pieces(x, n):
    """`n` bfloat16 arrays that sum to float32 `x`: three hold every bit
    of a float32 mantissa."""
    pieces = []
    for _ in range(n):
        piece = x.astype(jnp.bfloat16)
        pieces.append(piece)
        x = x - piece.astype(jnp.float32)
    return pieces


def _stack3(a):
    """float32 `a` [m, k] as its three bfloat16 pieces one under the other,
    [3m, k]: ONE pass of a stored tile through the MXU multiplies all
    three (m a multiple of 8: whole float32 sublane tiles are stacked,
    then rounded, which is exact)."""
    return jnp.concatenate(
        [p.astype(jnp.float32) for p in _bf16_pieces(a, 3)],
        axis=0).astype(jnp.bfloat16)


def _dot3(a3, b, dims):
    """The float32 product of `a` (given as `_stack3(a)`) and the stored
    tile `b`, contracting `dims`: bfloat16 x bfloat16 products are exact
    in float32 and the MXU accumulates in float32, so the sum over the
    pieces is the product `Precision.HIGHEST` computes, from the same
    terms. A bfloat16 pool is one piece as it lies and passes the MXU once
    (HIGHEST passes it six times, its lower pieces all zero); a float32
    pool is split in three."""
    pieces = [b] if b.dtype == jnp.bfloat16 else _bf16_pieces(b, 3)
    out = sum(jax.lax.dot_general(a3, p, (dims, ((), ())),
                                  preferred_element_type=jnp.float32)
              for p in pieces)
    m = a3.shape[0] // 3
    return out[:m] + out[m:2 * m] + out[2 * m:]


def _head_sums(x, head_dim):
    """x [tokens, 128] float32: every lane's sum over the `head_dim`-lane
    segment it lies in (a head; segments are aligned, `head_dim` a power of
    two), on the MXU: each of x's three bfloat16 pieces times the 0/1
    matrix that is 1 where two lanes share a head. A piece times 1.0 is
    exact and the MXU accumulates in float32: the float32 sum of the
    head's terms, no bit of x dropped. The matrix is an iota comparison
    (8 registers a column; as a hoisted value it measured the same)."""
    shift = head_dim.bit_length() - 1
    row_head, lane_head = (
        jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), d) >> shift
        for d in (0, 1))
    same_head = jnp.where(row_head == lane_head, 1.0, 0.0).astype(
        jnp.bfloat16)
    return sum(jnp.dot(piece, same_head, preferred_element_type=jnp.float32)
               for piece in _bf16_pieces(x, 3))


def _page_copy(start, pools, bufs, sem, src, half, slot):
    """Start (or wait for) the K and the V DMA of pool page `src` into
    slot `slot` of half `half` of the two VMEM blocks."""
    for which, (pool, buf) in enumerate(zip(pools, bufs)):
        copy = pltpu.make_async_copy(pool.at[src], buf.at[half, slot],
                                     sem.at[which, half])
        copy.start() if start else copy.wait()


# The scalar arithmetic of a grid step, jitted for the reason given at
# `_column_update`: every decode program traces it with the same types.
# `lax.div`, not `//`: floor division of a traced integer lowers through
# `sign`, a third of this kernel's lowering time.

@functools.partial(jax.jit, static_argnames=("page_size", "group"))
def _block_pages(kv_len, i, *, page_size, group):
    """Live pages of block i of a row of `kv_len` slots: 0..group."""
    pages = jax.lax.div(kv_len + (page_size - 1), page_size)
    return jnp.maximum(jnp.minimum(pages - i * group, group), 0)


@functools.partial(jax.jit, static_argnames=("page_size", "group"))
def _step_ahead(kv_len, next_row, b, i, rows, *, page_size, group):
    """(row, block, is there one) of the next live grid step after (b, i):
    this row's next block, or block 0 of the next row that has context."""
    more = (i + 1) * (group * page_size) < kv_len
    b2 = jax.lax.select(more, b, next_row)
    return (jnp.minimum(b2, rows - 1), jax.lax.select(more, i + 1, 0),
            b2 < rows)


def _fetch_block(pt_ref, kl_ref, nxt_ref, pools, bufs, sem, count_ref, b, i,
                 *, page_size, group):
    """Inside a live grid step (b, i): start the DMAs of the next live
    block into the other half, wait for this block's, and return its half.
    Only LIVE pages are ever copied: HBM traffic is the used pages, once.
    The first live block of a call starts its own copies too (one loop, so
    one copy of the DMA code), after zeroing the V blocks: a slot that is
    never fetched then holds a zero or an older page, and its probability
    is exactly 0."""
    rows, blocks = pl.num_programs(0), pl.num_programs(1)
    geometry = dict(page_size=page_size, group=group)
    n = count_ref[0]
    half = jax.lax.rem(n, 2)

    @pl.when(n == 0)
    def _first():
        bufs[1][...] = jnp.zeros_like(bufs[1])

    b2, i2, there = _step_ahead(kl_ref[b], nxt_ref[b], b, i, rows, **geometry)
    own = _block_pages(kl_ref[b], i, **geometry)
    unstarted = jax.lax.select(n == 0, own, 0)
    ahead = jax.lax.select(there,
                           _block_pages(kl_ref[b2], i2, **geometry), 0)
    here, then = (b * blocks + i) * group, (b2 * blocks + i2) * group

    def start(j, carry):
        late = j >= unstarted             # a page of the block ahead
        slot = j - jax.lax.select(late, unstarted, 0)
        _page_copy(True, pools, bufs, sem,
                   pt_ref[jax.lax.select(late, then, here) + slot],
                   jax.lax.select(late, 1 - half, half), slot)
        return carry
    jax.lax.fori_loop(0, unstarted + ahead, start, 0)

    def wait(j, carry):
        _page_copy(False, pools, bufs, sem, pt_ref[here + j], half, j)
        return carry
    jax.lax.fori_loop(0, own, wait, 0)
    count_ref[0] = n + 1
    return half


def _kernel(pt_ref, kl_ref, nxt_ref, *refs, chunk_update, chunk_tokens,
            page_size, group, windowed=False):
    """One grid step: block i (pages i * group ..) of row b. `chunk_update`
    is the arm's online-softmax update over one chunk of the block.
    `windowed`: a fourth prefetched scalar a row, its first live slot; the
    slots of a chunk before it are masked (`n_dead`)."""
    fl_ref = refs[0] if windowed else None
    (q_ref, k_hbm, v_hbm, o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf, sem,
     count_ref) = refs[1:] if windowed else refs
    b = pl.program_id(0)
    i = pl.program_id(1)
    kv_len = kl_ref[b]
    first = i * (group * page_size)              # the block's first slot
    chunk = _chunk_pages(group, page_size, chunk_tokens)
    tokens = chunk * page_size

    @pl.when((b == 0) & (i == 0))
    def _call_start():
        count_ref[0] = 0

    @pl.when(i == 0)
    def _row_start():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a block past the row's context is neither fetched nor computed, nor
    # is a chunk past it inside a live block
    @pl.when(first < kv_len)
    def _block():
        half = _fetch_block(pt_ref, kl_ref, nxt_ref, (k_hbm, v_hbm),
                            (k_buf, v_buf), sem, count_ref, b, i,
                            page_size=page_size, group=group)
        live = jnp.minimum(kv_len - first, group * page_size)

        def one_chunk(c, carry):
            pages = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            dead = {"n_dead": fl_ref[b] - first - c * tokens} if windowed \
                else {}
            chunk_update(q_ref, k_buf.at[half, pages], v_buf.at[half, pages],
                         live - c * tokens, m_ref, l_ref, acc_ref, **dead)
            return carry
        jax.lax.fori_loop(0, jax.lax.div(live + (tokens - 1), tokens),
                          one_chunk, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _emit():
        # a padded row (kv_len 0) ran no block: it emits zeros
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


# The two arms' arithmetic, as jitted functions of values: a decode program
# of every (rows, bucket) signature traces this kernel anew, and jit hands
# the later ones the jaxpr of the first (the chunk's shapes do not depend on
# rows or bucket). Traced inline, this kernel cost the 35 decode programs
# of the grouped-query cell 6-8 s of every start (`setup_s` +12%; my chip
# runs, PR 26).

@functools.partial(jax.jit, static_argnames=("sm_scale", "head_dim"))
def _column_update(q, k, v, n_live, m_prev, l_prev, acc_prev, *, sm_scale,
                   head_dim):
    """One 128-lane column of a chunk: `q` [1, 128], `k`, `v` [tokens, 128]
    with the first `n_live` tokens live; returns the new (m, l, acc)."""
    live = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0) < n_live
    s = jnp.where(live, _head_sums(
        q.astype(jnp.float32) * sm_scale * k.astype(jnp.float32), head_dim),
        _NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)                                # [tokens, 128]
    return (m_new,
            l_prev * alpha + jnp.sum(pexp, axis=0, keepdims=True),
            acc_prev * alpha + jnp.sum(pexp * v.astype(jnp.float32), axis=0,
                                       keepdims=True))


def _chunk_update(q_ref, k_ref, v_ref, n_live, m_ref, l_ref, acc_ref, *,
                  sm_scale, head_dim):
    """`k_ref`, `v_ref` [pages, ps, nh*dh]: a chunk whose first `n_live`
    tokens are live. One query token per row: the step is bound by the page
    DMAs and the VPU, not by FLOPs, so the q.k and p.v products stay on the
    VPU in float32, one 128-lane column at a time (whole heads: dh divides
    128; a loop, so the code is one column's); only a head's sum of q.k
    passes the MXU, split so that it stays a float32 sum (`_head_sums`).
    Nothing is rounded to bfloat16."""
    pages, ps, width = k_ref.shape

    def column(c, carry):
        col = pl.ds(pl.multiple_of(c * _LANES, _LANES), _LANES)
        m_ref[:, col], l_ref[:, col], acc_ref[:, col] = _column_update(
            q_ref[0, :, col], k_ref[:, :, col].reshape(pages * ps, _LANES),
            v_ref[:, :, col].reshape(pages * ps, _LANES), n_live,
            m_ref[:, col], l_ref[:, col], acc_ref[:, col],
            sm_scale=sm_scale, head_dim=head_dim)
        return carry
    jax.lax.fori_loop(0, width // _LANES, column, 0)


@functools.partial(jax.jit, static_argnames=("sm_scale", "num_kv_heads"))
def _gqa_update(q, k, v, n_live, m_prev, l_prev, acc_prev, n_dead=None, *,
                sm_scale, num_kv_heads):
    """A chunk of the grouped-query arm: `q` [nh, dh], `k`, `v` [tokens,
    nkv*dh] with the first `n_live` tokens live (and, with `n_dead`, the
    first `n_dead` of those not: a sliding window's slots before its first
    live one); returns the new (m, l, acc). q.k and p.v are MXU products
    in float32 (`_dot3`), one KV head at a time over ALL query rows; each
    row keeps the product of its own group (the others cost no byte)."""
    (nh, dh), tokens = q.shape, k.shape[0]
    heads_per_kv = nh // num_kv_heads
    q3 = _stack3(q.astype(jnp.float32) * sm_scale)              # [3nh, dh]
    head = jax.lax.broadcasted_iota(jnp.int32, (nh, tokens), 0)
    s = jnp.full((nh, tokens), _NEG_INF, jnp.float32)
    for j in range(num_kv_heads):
        sj = _dot3(q3, k[:, j * dh:(j + 1) * dh], ((1,), (1,)))
        mine = (head >= j * heads_per_kv) & (head < (j + 1) * heads_per_kv)
        s = jax.lax.select(mine, sj, s)                      # [nh, tokens]
    slot = jax.lax.broadcasted_iota(jnp.int32, (nh, tokens), 1)
    live = slot < n_live
    if n_dead is not None:
        live &= slot >= n_dead
    s = jax.lax.select(live, s, jnp.full_like(s, _NEG_INF))
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                          # [nh, 128]
    pexp = jnp.exp(s - m_new[:, :1])
    if n_dead is not None:
        # a chunk wholly before the first live slot leaves m at -1e30 and
        # every exp(s - m) at 1: the mask, not the exponent, zeroes them
        pexp = jnp.where(live, pexp, 0.0)
    p3 = _stack3(pexp)                                       # [3nh, tokens]
    head = jax.lax.broadcasted_iota(jnp.int32, (nh, dh), 0)
    pv = jnp.zeros((nh, dh), jnp.float32)
    for j in range(num_kv_heads):
        pvj = _dot3(p3, v[:, j * dh:(j + 1) * dh], ((1,), (0,)))
        mine = (head >= j * heads_per_kv) & (head < (j + 1) * heads_per_kv)
        pv = jax.lax.select(mine, pvj, pv)                   # [nh, dh]
    return (m_new, l_prev * alpha + jnp.sum(pexp, axis=1, keepdims=True),
            acc_prev * alpha + pv)


def _gqa_chunk_update(q_ref, k_ref, v_ref, n_live, m_ref, l_ref, acc_ref, *,
                      sm_scale, num_kv_heads, n_dead=None):
    """The matrix-unit twin of `_chunk_update`: `nh` query heads (sublanes
    of one [nh, dh] tile) over `nkv` KV heads of dh = 128 lanes (fewer than
    `nh`, or as many). A head is a whole register here, so the chunk is
    one update on the MXU."""
    pages, ps, width = k_ref.shape
    dead = () if n_dead is None else (n_dead,)
    m_ref[...], l_ref[...], acc_ref[...] = _gqa_update(
        q_ref[0], k_ref[...].reshape(pages * ps, width),
        v_ref[...].reshape(pages * ps, width), n_live, m_ref[...],
        l_ref[...], acc_ref[...], *dead, sm_scale=sm_scale,
        num_kv_heads=num_kv_heads)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _call(q, k_pool, v_pool, page_table, kv_lens, sm_scale, interpret,
          first_live=None):
    B, nh, dh = q.shape
    num_pages, ps, width = k_pool.shape
    P = page_table.shape[1]
    grouped = matrix_unit_arm(q.shape, k_pool.shape, k_pool.dtype, P)
    group = pages_per_grid_step(P, ps, width, k_pool.dtype.itemsize)
    blocks = -(-P // group)
    kv_lens = kv_lens.astype(jnp.int32)
    windowed = first_live is not None
    if windowed and not grouped:
        raise NotImplementedError(
            "a first live slot is written for the matrix-unit arm only")
    if windowed:
        # the pages wholly before a row's first live slot leave its table:
        # the table turns left by that many entries, and the lengths with it
        skip = jnp.clip(first_live.astype(jnp.int32), 0, kv_lens) // ps
        at = jnp.minimum(skip[:, None] + jnp.arange(P, dtype=jnp.int32),
                         P - 1)
        page_table = jnp.take_along_axis(page_table, at, axis=1)
        kv_lens = kv_lens - skip * ps
        first_live = jnp.maximum(first_live.astype(jnp.int32) - skip * ps, 0)
    # clamp so a padded/garbage table entry names a real page; one row of
    # the flat table is `blocks * group` entries
    page_table = jnp.pad(
        jnp.clip(page_table, 0, num_pages - 1).astype(jnp.int32),
        ((0, 0), (0, blocks * group - P))).reshape(B * blocks * group)
    # the next row after b that has any context (B: none)
    has = jnp.where(kv_lens > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jnp.concatenate([jax.lax.cummin(has[::-1])[::-1][1:],
                           jnp.full((1,), B, jnp.int32)])
    if grouped:
        update = functools.partial(_gqa_chunk_update, sm_scale=float(sm_scale),
                                   num_kv_heads=width // dh)
        row_shape, lanes, out_dtype = (1, nh, dh), (nh, _LANES), jnp.float32
    else:
        update = functools.partial(_chunk_update, sm_scale=float(sm_scale),
                                   head_dim=dh)
        row_shape, lanes, out_dtype = (1, 1, width), (1, width), q.dtype
    row = pl.BlockSpec(row_shape, lambda b, i, *prefetched: (b, 0, 0)) \
        if windowed else \
        pl.BlockSpec(row_shape, lambda b, i, pt, kl, nx: (b, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if windowed else 3,
        grid=(B, blocks),
        in_specs=[row, pool, pool],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM(lanes, jnp.float32),             # running max
            pltpu.VMEM(lanes, jnp.float32),             # running denominator
            pltpu.VMEM(row_shape[1:], jnp.float32),     # running numerator
            pltpu.VMEM((2, group, ps, width), k_pool.dtype),
            pltpu.VMEM((2, group, ps, width), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),            # [K | V, half]
            pltpu.SMEM((1,), jnp.int32),                # live blocks so far
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, chunk_update=update, page_size=ps, group=group,
            chunk_tokens=MXU_CHUNK_TOKENS if grouped else LANE_CHUNK_TOKENS,
            **({"windowed": True} if windowed else {})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B,) + row_shape[1:], out_dtype),
        cost_estimate=pl.CostEstimate(
            flops=B * nh * 2 * 2 * P * ps * dh,
            bytes_accessed=(2 * B * P * ps * width * k_pool.dtype.itemsize
                            + 2 * B * nh * dh * 4),
            transcendentals=B * P * ps * (nh if grouped else width)),
        # a block's DMAs are started one live block ahead, across rows:
        # the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        # a call with a KV head a query head keeps the name a device trace
        # knows it by, whichever arm its shapes take (literal names: the
        # reductions and `test_every_pallas_call_names_its_kernel` grep them)
        name="paged_window_attention_gqa" if windowed
        else "paged_decode_attention_gqa" if nh * dh != width
        else "paged_decode_attention",
    )(page_table, kv_lens, nxt, *((first_live,) if windowed else ()),
      q.reshape((B,) + row_shape[1:]).astype(out_dtype), k_pool, v_pool)
    return out.reshape(B, nh, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# The grouped-query arm without a first live slot: ONE grid step that walks a
# flat list of page blocks, a run of pages that rows share read and scored
# once for all of them (the module docstring's second half)
# ---------------------------------------------------------------------------

# rows of a group that one product stacks on the matrix unit's rows, at most
# (a product's fixed cost, the K or V tile's way into the matrix unit, is
# shared by its rows: tiles of 4 | 8 | 16 rows took 3,069 | 2,277 | 2,160 us
# a call in the first build; a group's last rows take a half tile, so that
# a small group does not pay for sixteen)
TILE_ROWS = 16
# rows of the matrix unit one tile's three bfloat16 pieces may fill: three
# passes of 128
TILE_MXU_ROWS = 384
# KV heads of a tile scored in one straight run, so that one head's products
# are in flight while another's softmax is worked out; the loop over the
# rest keeps the body short (a decode program holds a copy a layer). The
# step of `tools/kernel_check.py` at tiles of 8 rows: 2,185 us a call with
# 1 head abreast, 1,975 with 2, 1,832 with 4, 1,771 with 8 (my chip runs,
# PR 50); with whole and half tiles 1,712 | 1,597 | 1,552 at 2 | 4 | 8.
HEADS_ABREAST = 4
WALK_VMEM_LIMIT = 48 * 1024 * 1024
# what a call keeps resident a row: its queries in both orders and its output
# (operands, double-buffered) and the shared runs' running maximum, sum and
# numerator
WALK_RESIDENT_BYTES = 28 * 1024 * 1024
# a block of the flat list, one scalar each: where its first page stands in
# the flattened table, how many pages of it are fetched, its first position,
# its rows (the first by sorted position, and how many), the positions live
# for them, a tail's row as the caller numbers it, and whether the block is
# the first (1) and the last (2) of its row's tail
_FIELDS = _BASE, _PAGES, _POS, _ROW, _COUNT, _LIMIT, _ORIG, _EDGE = tuple(
    range(8))

Walk = collections.namedtuple("Walk", "order work blocks")


def tile_rows(heads_per_kv: int) -> tuple:
    """(T, T / 2): the rows of a group whose heads one product stacks
    against a KV head, from the shape alone: as many as keep the three
    bfloat16 pieces of `T x heads_per_kv` query rows within `TILE_MXU_ROWS`,
    no more than `TILE_ROWS`, in whole sublane tiles of 8 (16 at 4 or 6
    heads a KV head, 8 at 16, 4 at 32); and the half tile a group's last
    rows take (0 where half a tile is no whole sublane tiles)."""
    unit = 8 // math.gcd(heads_per_kv, 8)
    rows = min(TILE_ROWS, TILE_MXU_ROWS // (3 * heads_per_kv))
    tile = max(unit, rows // unit * unit)
    return tile, 0 if tile // 2 % unit or tile < 2 else tile // 2


def walk_supported(q_shape, pool_shape, pool_dtype, bucket_pages) -> bool:
    """Whether the list-walking form serves a call of the matrix-unit arm
    without a first live slot: q [B, nh, dh] over `pool_shape` behind tables of
    `bucket_pages`. The table is two blocks wide or wider (a narrower one
    cannot hold a shared block and a row's own page behind it, and its grid
    has one step a row: the step's plan and the walk's longer code would
    buy nothing), it fits the scalar memory one call prefetches (`TABLE_ENTRIES`, the
    latent kernel's), and the
    rows' queries, outputs and running sums stay resident; everything else
    (a short table, thousands of rows) keeps the grid over (row, block)."""
    if not (paged_supported(q_shape, pool_shape, pool_dtype)
            and matrix_unit_arm(q_shape, pool_shape, pool_dtype,
                                bucket_pages)):
        return False
    B, nh, dh = q_shape
    _, ps, width = pool_shape
    group = pages_per_grid_step(bucket_pages, ps, width,
                                jnp.dtype(pool_dtype).itemsize)
    rows = max(B, tile_rows(nh // (width // dh))[0])
    resident = 4 * (rows * nh * (2 * dh + 2 * _LANES + dh) + 4 * B * nh * dh)
    return (int(bucket_pages) >= 2 * group
            and B * int(bucket_pages) <= TABLE_ENTRIES
            and resident <= WALK_RESIDENT_BYTES)


def _walk_blocks(g, group, xp):
    """Of `row_groups`' answer, by sorted position: the pages of each
    group's shared run (at its first row, 0 elsewhere) and of each row's
    tail, and the blocks the list gives them. A live row's tail is at least
    one block, an empty one where its run is all it has: the row's answer
    is written behind its last tail block."""
    at = xp.arange(g.first.shape[0])
    shared = xp.where(g.first == at, g.run, 0)
    tail = g.pages - g.run
    return (shared, tail, shared // group,
            xp.maximum(-(-tail // group), (g.pages > 0).astype(tail.dtype)))


def walk_plan(page_table, kv_lens, pool_shape, itemsize) -> Walk:
    """What every layer's call of one decode step needs of its tables
    (page_table [B, P], kv_lens [B]; a layer's offset does not enter):
    PR 48's rule (`paged_latent_attend.row_groups`: rows whose tables begin
    with the same `G` page ids and hold them whole form a group, the run is
    what all members share in whole blocks) and its flat list: the rows'
    `order` (a group's together), `work` [8 x slots] (`_BASE` .. `_EDGE`,
    a field's `slots` entries together) of which the first `blocks` [1] are
    real: every group's shared run block by block, then every row's tail.
    A table that shares nothing gives groups of one: every row's blocks in
    row order, the walk of the grid without its dead steps."""
    _, ps, width = pool_shape
    B, P = page_table.shape
    G = pages_per_grid_step(P, ps, width, itemsize)
    lens = kv_lens.astype(jnp.int32)
    g = row_groups(page_table.astype(jnp.int32), lens, ps, G)
    at = jnp.arange(B, dtype=jnp.int32)
    shared, tail, shared_blocks, tail_blocks = _walk_blocks(g, G, jnp)
    pages = jnp.concatenate([shared, tail])
    blocks = jnp.concatenate([shared_blocks, tail_blocks])
    ends = jnp.cumsum(blocks)
    segments = jnp.stack([
        pages, jnp.concatenate([0 * at, g.run]), jnp.concatenate([at, at]),
        jnp.concatenate([g.count, 0 * at + 1]),
        jnp.concatenate([shared * ps, lens[g.order]]),
        jnp.concatenate([g.order, g.order]), ends - blocks, blocks], axis=1)
    # block w of the list is block `w - start` of the segment it falls in
    w = jnp.arange(walk_slots(B, P, G), dtype=jnp.int32)
    s = jnp.minimum(jnp.sum(w[:, None] >= ends[None, :], axis=1), 2 * B - 1)
    pages, page0, row, count, limit, orig, start, n = segments[s].T
    k = w - start
    page = page0 + k * G
    fields = {_BASE: orig * P + page,
              _PAGES: jnp.clip(page0 + pages - page, 0, G), _POS: page * ps,
              _ROW: row, _COUNT: count, _LIMIT: limit, _ORIG: orig,
              _EDGE: (k == 0) + 2 * (k == n - 1)}
    work = jnp.concatenate([fields[f] for f in _FIELDS]).astype(jnp.int32)
    return Walk(g.order, work, ends[-1:].astype(jnp.int32))


def walk_slots(rows: int, bucket_pages: int, group: int) -> int:
    """Entries of the flat list at most: every row's blocks and one more a
    row (an empty tail)."""
    return rows * (-(-bucket_pages // group) + 1)


def walk_counts(page_table, kv_lens, pool_shape, itemsize) -> dict:
    """What ONE layer's call reads with these feeds (numpy, on the host;
    the rows the scheduler padded in left out by the caller): `pages` and
    `tokens` fetched, a group's run once and behind it every row's own,
    `blocks` the list walks, and whether any run is `shared`: the rule
    `walk_plan` follows, so the engine's counters count what the kernel
    read."""
    _, ps, width = pool_shape
    table = np.asarray(page_table)
    lens = np.asarray(kv_lens).reshape(-1).astype(np.int64)
    G = pages_per_grid_step(table.shape[1], ps, width, itemsize)
    g = row_groups(table, lens, ps, G, np)
    shared, tail, shared_blocks, tail_blocks = _walk_blocks(g, G, np)
    run = shared.sum()
    return {"pages": int(run + tail.sum()),
            "tokens": int(run * ps + (lens[g.order] - g.run * ps).sum()),
            "blocks": int(shared_blocks.sum() + tail_blocks.sum()),
            "shared": bool(run)}


@functools.partial(jax.jit, static_argnames=("sm_scale",))
def _tile_update(q, k, v, lo, hi, m_prev, l_prev, acc_prev, *, sm_scale):
    """A chunk of a shared run against one KV head: `q` [M, dh] (a tile's
    rows x the head's query heads), `k`, `v` [tokens, dh], every token live
    for every row; rows `lo .. hi` of M are the group's (a group's last
    tile holds others', which keep what they have); returns the new (m, l,
    acc). `_gqa_update`'s products and softmax, every row of a product one
    that is kept."""
    s = _dot3(_stack3(q * sm_scale), k, ((1,), (1,)))        # [M, tokens]
    at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    s = jax.lax.select((at >= lo) & (at < hi), s, jnp.full_like(s, _NEG_INF))
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                          # [M, 128]
    pexp = jnp.exp(s - m_new[:, :1])
    pv = _dot3(_stack3(pexp), v, ((1,), (0,)))               # [M, dh]
    return (m_new, l_prev * alpha + jnp.sum(pexp, axis=1, keepdims=True),
            acc_prev * alpha + pv)


def _walk_kernel(pt_ref, work_ref, n_ref, qt_ref, qr_ref, k_hbm, v_hbm, o_ref,
                 k_buf, v_buf, sem, m_ref, l_ref, acc_ref, mt_ref, lt_ref,
                 at_ref, *, page_size, group, tile, half_tile, heads_per_kv,
                 sm_scale):
    """The one grid step: every block of the flat list, in order. `qt_ref`
    [nkv, rows x heads_per_kv, dh]: the queries a KV head at a time, by
    sorted row, so that a tile of rows x a KV head's query heads is one
    window of sublanes; `qr_ref`, `o_ref` [B, nh, dh] as the caller numbers
    the rows. `m_ref`, `l_ref`, `acc_ref`: the shared runs' online softmax
    for all rows, as `qt_ref` lies; `mt_ref`, `lt_ref`, `at_ref` [nh, .]:
    that of the one row whose tail is being walked."""
    nkv, _, dh = qt_ref.shape
    rows = qt_ref.shape[1] // heads_per_kv
    slots = work_ref.shape[0] // len(_FIELDS)
    chunk = _chunk_pages(group, page_size, MXU_CHUNK_TOKENS)
    tokens = chunk * page_size
    blocks = n_ref[0]
    pools, bufs = (k_hbm, v_hbm), (k_buf, v_buf)
    field = lambda f, w: work_ref[f * slots + w]             # noqa: E731

    # a padding row is in no block: zeros. A block is fetched as far as its
    # last live page: what a chunk holds behind it is masked, and must be
    # numbers
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
    v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

    # the shared runs lead the list
    @pl.when((blocks > 0) & (field(_COUNT, 0) > 1))
    def _some_run_is_shared():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def copies(w, half, start):
        """Start (or wait for) the K and V DMAs of block w's live pages
        into `half` of the two VMEM blocks."""
        base = field(_BASE, w)

        def one_page(slot, carry):
            _page_copy(start, pools, bufs, sem, pt_ref[base + slot], half,
                       slot)
            return carry
        jax.lax.fori_loop(0, field(_PAGES, w), one_page, 0)

    def block(w, carry):
        # the next block's DMAs fly while this one is scored; the first
        # turn of the loop only starts block 0's
        half = jax.lax.rem(w + 2, 2)

        @pl.when(w + 1 < blocks)
        def _ahead():
            copies(w + 1, 1 - half, True)

        @pl.when(w >= 0)
        def _this():
            copies(w, half, False)
            score(w, half)
        return carry

    def score(w, half):
        row, count = field(_ROW, w), field(_COUNT, w)
        first, limit = field(_POS, w), field(_LIMIT, w)
        live = jnp.minimum(limit - first, group * page_size)
        chunks = jax.lax.div(live + (tokens - 1), tokens)

        def a_tail():
            orig, edge = field(_ORIG, w), field(_EDGE, w)

            @pl.when((jax.lax.rem(edge, 2) == 1) & (first == 0))
            def _a_row_of_its_own():
                mt_ref[...] = jnp.full(mt_ref.shape, _NEG_INF, jnp.float32)
                lt_ref[...] = jnp.zeros(lt_ref.shape, jnp.float32)
                at_ref[...] = jnp.zeros(at_ref.shape, jnp.float32)

            @pl.when((jax.lax.rem(edge, 2) == 1) & (first > 0))
            def _behind_a_shared_run():
                # what the row gathered over its group's run, a KV head's
                # query heads at a time
                its = pl.ds(row * heads_per_kv, heads_per_kv)

                def a_head(j, carry):
                    mine = pl.ds(j * heads_per_kv, heads_per_kv)
                    for own, run in ((mt_ref, m_ref), (lt_ref, l_ref),
                                     (at_ref, acc_ref)):
                        own[mine, :] = run[j, its, :]
                    return carry
                jax.lax.fori_loop(0, nkv, a_head, 0)

            def a_chunk(c, carry):
                pages = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
                _gqa_chunk_update(
                    qr_ref.at[pl.ds(orig, 1)], k_buf.at[half, pages],
                    v_buf.at[half, pages], live - c * tokens, mt_ref, lt_ref,
                    at_ref, sm_scale=sm_scale, num_kv_heads=nkv)
                return carry
            jax.lax.fori_loop(0, chunks, a_chunk, 0)

            @pl.when(edge >= 2)
            def _the_rows_answer():
                o_ref[orig] = at_ref[...] / jnp.maximum(lt_ref[...], 1e-30)

        def a_run():
            abreast = math.gcd(nkv, HEADS_ABREAST)
            # whole tiles while more rows are left than a half tile holds,
            # then a half tile: a group's last rows pay for half the dead
            # rows of a product
            whole = jax.lax.div(jnp.maximum(count - half_tile, 0)
                                + (tile - 1), tile)

            def a_tile(c, size, its):
                """`size` rows from sorted row `its` on over chunk c, a KV
                head at a time. The last tile ends where the rows end: it
                masks what it holds of other rows."""
                pages = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
                r = jnp.minimum(its, rows - size)
                at = pl.ds(r * heads_per_kv, size * heads_per_kv)
                lo = (its - r) * heads_per_kv
                hi = (jnp.minimum(its + size, row + count) - r) * heads_per_kv

                # some heads in one straight run: one head's products are
                # in flight while the last one's softmax is worked out
                def some_heads(i, carry):
                    for j in range(abreast):
                        j += i * abreast
                        lane = pl.ds(pl.multiple_of(j * dh, dh), dh)
                        m_ref[j, at], l_ref[j, at], acc_ref[j, at] = \
                            _tile_update(
                                qt_ref[j, at],
                                k_buf[half, pages, :, lane].reshape(tokens,
                                                                    dh),
                                v_buf[half, pages, :, lane].reshape(tokens,
                                                                    dh),
                                lo, hi, m_ref[j, at], l_ref[j, at],
                                acc_ref[j, at], sm_scale=sm_scale)
                    return carry
                jax.lax.fori_loop(0, nkv // abreast, some_heads, 0)

            def a_chunk(c, carry):
                jax.lax.fori_loop(
                    0, whole, lambda t, carry: (
                        a_tile(c, tile, row + t * tile), carry)[1], 0)
                if half_tile:
                    pl.when(whole * tile < count)(
                        lambda: a_tile(c, half_tile, row + whole * tile))
                return carry
            jax.lax.fori_loop(0, chunks, a_chunk, 0)

        # a branch a block, so that a row's chunk is one straight run of
        # products, as a group's tile is
        pl.when(count == 1)(a_tail)
        pl.when(count > 1)(a_run)

    jax.lax.fori_loop(-1, blocks, block, 0)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _walk_call(q, k_pool, v_pool, page_table, plan, sm_scale, interpret):
    B, nh, dh = q.shape
    num_pages, ps, width = k_pool.shape
    P = page_table.shape[1]
    nkv = width // dh
    heads_per_kv = nh // nkv
    tile, half_tile = tile_rows(heads_per_kv)
    rows = max(B, tile)
    group = pages_per_grid_step(P, ps, width, k_pool.dtype.itemsize)
    # clamp so a padded/garbage table entry names a real page
    table = jnp.clip(page_table, 0, num_pages - 1).astype(jnp.int32).reshape(
        B * P)
    q = q.astype(jnp.float32)
    # a KV head at a time, a group's rows together
    tiles = jnp.pad(
        q[plan.order].reshape(B, nkv, heads_per_kv, dh).transpose(1, 0, 2, 3),
        ((0, 0), (0, rows - B), (0, 0), (0, 0))
    ).reshape(nkv, rows * heads_per_kv, dh)
    whole = lambda shape: pl.BlockSpec(                      # noqa: E731
        shape, lambda i, *prefetched: (0,) * len(shape))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[whole(tiles.shape), whole(q.shape), pool, pool],
        out_specs=whole(q.shape),
        scratch_shapes=[
            pltpu.VMEM((2, group, ps, width), k_pool.dtype),
            pltpu.VMEM((2, group, ps, width), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),            # [K | V, half]
            pltpu.VMEM(tiles.shape[:2] + (_LANES,), jnp.float32),  # maximum
            pltpu.VMEM(tiles.shape[:2] + (_LANES,), jnp.float32),  # sum
            pltpu.VMEM(tiles.shape, jnp.float32),       # the runs' numerator
            pltpu.VMEM((nh, _LANES), jnp.float32),      # a tail's maximum
            pltpu.VMEM((nh, _LANES), jnp.float32),      # denominator
            pltpu.VMEM((nh, dh), jnp.float32),          # numerator
        ],
    )
    return pl.pallas_call(
        functools.partial(_walk_kernel, page_size=ps, group=group,
                          tile=tile, half_tile=half_tile,
                          heads_per_kv=heads_per_kv, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=B * nh * 2 * 2 * P * ps * dh,
            bytes_accessed=(2 * B * P * ps * width * k_pool.dtype.itemsize
                            + 2 * B * nh * dh * 4),
            transcendentals=B * P * ps * nh),
        # the blocks share the rows' running sums and each starts the DMAs
        # of the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=WALK_VMEM_LIMIT),
        interpret=interpret,
        name="paged_decode_attention_gqa" if nh * dh != width
        else "paged_decode_attention",
    )(table, plan.work, plan.blocks, tiles, q, k_pool, v_pool)


def _workbench_register():
    from . import workbench

    def _reference(q, k_pool, v_pool, page_table, kv_lens, sm_scale=1.0):
        from ..attention_ops import _paged_attention_reference

        return _paged_attention_reference(q, k_pool, v_pool, page_table,
                                          kv_lens, sm_scale)

    return workbench.register_kernel(
        "attention_paged_decode",
        reference=_reference,
        supported=paged_supported,
        decision_op="attention",
        equivalence_test="test_paged_attention_pallas_matches_reference",
        note="ragged paged decode attention (sq=1) over the KV page pool; "
             "scalar-prefetch page-table DMA, forward-only")


@_workbench_register()
def paged_decode_attention(q, k_pool, v_pool, page_table, kv_lens,
                           sm_scale=1.0, first_live=None, plan=None):
    """One decode step of ragged paged attention.

    q: [B, nh, dh] (this step's query per request row);
    k_pool/v_pool: [num_pages, page_size, nkv*dh] (the preallocated pool;
    nkv == nh, or fewer KV heads than query heads: grouped-query);
    page_table: [B, P] int32 (row b's context lives in pages
    page_table[b, 0..ceil(kv_lens[b]/page_size))); kv_lens: [B] int32 valid
    slot counts. Returns [B, nh, dh] in q's dtype. Callers gate on
    `paged_supported`. `first_live` [B] int32 (grouped-query arm only; a
    sliding-window layer): row b attends slots `first_live[b] ..
    kv_lens[b] - 1`; the slots before are masked, the pages wholly before
    are never fetched, and the call runs under the name
    `paged_window_attention_gqa`. `plan`: the step's `walk_plan(page_table,
    kv_lens, ...)` where `walk_supported` says the list-walking form serves
    the call, which a caller of several layers works out once (any layer's
    table gives the same).
    """
    if first_live is None and (plan is not None or walk_supported(
            q.shape, k_pool.shape, k_pool.dtype, page_table.shape[1])):
        if plan is None:
            plan = walk_plan(page_table, kv_lens, k_pool.shape,
                             k_pool.dtype.itemsize)
        return _walk_call(q, k_pool, v_pool, page_table, plan,
                          float(sm_scale), bool(INTERPRET)).astype(q.dtype)
    if first_live is None:
        return _call(q, k_pool, v_pool, page_table, kv_lens,
                     float(sm_scale), bool(INTERPRET))
    return _call(q, k_pool, v_pool, page_table, kv_lens,
                 float(sm_scale), bool(INTERPRET), first_live)
