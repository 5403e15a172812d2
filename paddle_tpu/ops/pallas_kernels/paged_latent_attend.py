"""Decode rows' absorbed latent attention over ALL their pages, read in place
from the paged latent pool, rows that stand behind one document reading its
pages ONCE: a Pallas TPU kernel.

A decode row of the "latent_moe" block WITHOUT an indexer attends every
cached row of its page table, all heads against the same rows: `s[h, t] =
q_lat[h] . c[t] + q_rope[h] . r[t]`, softmax over the live t, `u[h] = sum_t
p[h, t] c[t]` (`latent_moe_ops.absorbed_attention_fn`). Gathered through XLA
first (`latent_pool[table]`, what the short-context step does) a step of 64
rows behind 33k tokens copies 3.6 GB a layer before it reads them, and
`latent_attend.py` holds a query's whole `[heads, K]` scores in VMEM, which
33k rows do not fit. This kernel copies nothing and holds a chunk's scores.

WHAT IS SHARED is read off the page table (`row_groups`): rows whose tables
begin with the SAME page ids (equal ids are equal bytes of the pool) and
that are live past them form a group, the run all its members share (whole
blocks of `G` pages, every position live for every member) is the group's,
what lies behind it each row's own tail. A row that shares less than one
block is a group of one, and its whole table is its tail. The grouping is
the same for every layer, so a step works it out once (`step_plan`) and
hands it to every layer's call.

  * ONE grid step walks a flat list of BLOCKS (`step_plan`: a group's
    shared blocks, then every row's tail blocks; a block is up to `G` pages,
    `pages_per_grid_step`), as many as the tables hold and no dead one. The
    pool `[rows, page_size, words]` stays in HBM (`pl.ANY`); a page is one
    DMA into one half of a two-block VMEM scratch, its index read from the
    table (scalar prefetch, already shifted to the layer's rows and
    clamped). The DMAs of the NEXT block are started before this block is
    waited for, so they fly while it is scored. Only a block's live pages
    are fetched.
  * a block's rows are unpacked IN VMEM, `chunk` pages at a time and ONCE
    for all the rows behind them, as `latent_attend.unpack_words` does (a
    word's two bfloat16 values exactly). The queries `[B, nh, kv_rank +
    256]` (latent, rotary low half, rotary high half, each in whole lane
    tiles), SORTED so that a group's rows lie together, are resident; a
    shared chunk is scored by `TILE_ROWS` rows at a time, their heads
    stacked on the M side of both products (`[rows x nh, kv_rank + 256] x
    [.., width]`, `[rows x nh, width] x [width, kv_rank]`), a tail's chunk
    by its one row's heads. A shared chunk needs no position mask; the last
    tile of a group masks the rows that are not the group's, a tail the
    positions past its row's length.
  * an ONLINE softmax a (row, head): a running maximum, sum and weighted
    sum of the latents (`[B, nh, 128]`, `[B, nh, 128]` float32 scratch and
    the output block itself), resident for all rows, so a row's shared run
    and its tail meet in them with no join of their own; normalised at the
    end of the call. The probabilities are rounded to bfloat16 before the
    weighted sum, as the reference rounds them. A padding row (length 0)
    reads nothing and gets zeros.

`q_lat` and `q_rope` are rounded ONCE to the cache dtype, as the reference
rounds them. Forward-only: serving never differentiates.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention_ops import _NEG_INF     # what a masked position scores
from . import workbench
from .latent_attend import _pad_lanes, unpack_words

# tests flip this to run the kernel through the Pallas interpreter on CPU
INTERPRET = False

# words of one block's pages (the kernel keeps two such blocks: the one it
# scores and the next one's DMAs in flight): 8 pages of 128 rows of 384
BLOCK_BYTES = 3 * 512 * 1024
# rows unpacked and scored at a time inside a block: all of the served
# block's (at 512 a group's tile paid its fixed costs twice a block: 1.81
# ms against 1.70 a layer of the Xing4.0 cell's step)
CHUNK_ROWS = 1024
# rows of a group whose heads one product stacks on the matrix unit's rows
TILE_ROWS = 8
VMEM_LIMIT = 32 * 1024 * 1024
# what a call keeps resident a (row, head): the query (bfloat16), the
# weighted latents (float32), the running maximum and sum (a lane tile
# each), the first two double-buffered as the call's operands
RESIDENT_BYTES = 20 * 1024 * 1024
# entries of the page table one call prefetches into scalar memory
# (`paged_indexer.TABLE_ENTRIES`)
TABLE_ENTRIES = 128 * 1024

_LANES = workbench.LANES
# a block of the flat list, one scalar each: where its first page stands in
# the flattened table, how many pages of it are fetched, its first
# position, its rows (the first, sorted, and how many) and the positions
# that are live for them
_FIELDS = _BASE, _PAGES, _POS, _ROW, _COUNT, _LIMIT = tuple(range(6))

Groups = collections.namedtuple("Groups", "order first count run pages")
Plan = collections.namedtuple("Plan", "order place work blocks")


def pages_per_grid_step(bucket_pages: int, page_bytes: int) -> int:
    """G: how many pages a block covers: the largest divisor of the page
    bucket whose rows fit `BLOCK_BYTES`."""
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
    a page whole sublane tiles of 128 rows), a page modest enough that a
    block of them double-buffers in VMEM, and rows few enough that their
    queries and running sums stay resident. Everything else (the CPU
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
    resident = B * nh * (2 * 2 * (kv_rank + 2 * _LANES) + 2 * 4 * kv_rank
                         + 2 * 4 * _LANES)
    return (B > 0 and nh % 8 == 0 and kv_rank % 2 == 0 and rope_dim % 2 == 0
            and side > 0 and side % _LANES == 0 and 0 < key <= _LANES
            and words % _LANES == 0 and words >= side + _LANES
            and ps > 0 and ps % _LANES == 0
            and ps * words * 4 <= BLOCK_BYTES
            and resident <= RESIDENT_BYTES)


def row_groups(page_table, lens, page_size: int, block: int, xp=jnp):
    """Which rows of a decode step read the same pages: page_table [B, P]
    (a row's pages in order; any layer's, the grouping is the same), lens
    [B] live positions (0: a padding row), in `xp` (jax.numpy inside the
    program, numpy for the engine's count) -> Groups, every field [B] by
    SORTED position: `order` (the rows, a group's together, its lowest row
    first), `first` (where the row's group starts), `count` (its group's
    rows), `run` (the pages ALL of them share: whole blocks of `block`
    pages, whole pages of every member; 0 in a group of one) and `pages`
    (the row's live pages).

    Two rows are of one group where the first `block` entries of their
    tables are equal and both hold that many whole pages; the group's run
    ends where any member's table leaves its lowest row's or any member's
    whole pages end (nested prefixes inside a group are not looked for)."""
    B, P = page_table.shape
    rows = xp.arange(B)
    lens = lens.astype(xp.int32)
    whole = lens // page_size
    can = whole >= block
    head = page_table[:, :block]
    same = ((head[:, None, :] == head[None, :, :]).all(-1)
            & can[:, None] & can[None, :]) | (rows[:, None] == rows[None, :])
    leader = xp.argmax(same, axis=1)            # the lowest row of its class
    equal = page_table == page_table[leader]
    common = xp.where(equal.all(1), P, xp.argmin(equal, axis=1))
    member = leader[None, :] == rows[:, None]                   # [g, row]
    count = member.sum(1)
    run = xp.where(member, xp.minimum(common, whole)[None, :], P).min(1)
    run = xp.where(count > 1, run // block * block, 0)
    order = xp.argsort(leader * B + rows)
    start = xp.cumsum(count) - count            # a leader's sorted position
    lead = leader[order]
    as_int = lambda a: a.astype(xp.int32)                    # noqa: E731
    return Groups(as_int(order), as_int(start[lead]), as_int(count[lead]),
                  as_int(run[lead]), (-(-lens // page_size))[order])


def pages_read(page_table, lens, pool_shape) -> int:
    """Pool pages the kernel fetches in ONE layer of a decode step with
    these feeds (numpy, on the host): a group's run once, behind it every
    row's own, by `row_groups`, the rule the program's `step_plan` follows,
    so the engine's `serving.latent.pages_read` counts what the kernel
    read."""
    _, ps, words = pool_shape
    table = np.asarray(page_table)
    g = row_groups(table, np.asarray(lens).reshape(-1), ps,
                   pages_per_grid_step(table.shape[1], ps * words * 4), np)
    leads = g.first == np.arange(len(g.first))
    return int(g.run[leads].sum() + (g.pages - g.run).sum())


def step_plan(page_table, lens, pool_shape) -> Plan:
    """What every layer's call of one decode step needs of its tables
    (page_table [B, P], lens [B]; the layer's offset does not enter): the
    rows' `order` and each row's `place` in it, and the flat list of blocks
    the kernel walks, `work` [6 x slots] (`_BASE` .. `_LIMIT`, a field's
    `slots` = B x P / G entries together) of which the first `blocks` [1]
    are real: every group's shared run block by block, then every row's
    tail."""
    _, ps, words = pool_shape
    B, P = page_table.shape
    G = pages_per_grid_step(P, ps * words * 4)
    lens = lens.astype(jnp.int32)
    g = row_groups(page_table.astype(jnp.int32), lens, ps, G)
    at = jnp.arange(B, dtype=jnp.int32)
    shared = jnp.where(g.first == at, g.run, 0)
    # segments: B shared runs (most of them empty), then B tails; of each
    # its pages, the first of them, its rows (the first and how many), the
    # positions live for them and where its table starts
    pages = jnp.concatenate([shared, g.pages - g.run])
    blocks = -(-pages // G)
    ends = jnp.cumsum(blocks)
    segments = jnp.stack([
        pages, jnp.concatenate([0 * at, g.run]), jnp.concatenate([at, at]),
        jnp.concatenate([g.count, 0 * at + 1]),
        jnp.concatenate([shared * ps, lens[g.order]]),
        jnp.concatenate([g.order, g.order]) * P, ends - blocks], axis=1)
    # block w of the list is block `w - start` of the segment it falls in
    w = jnp.arange(B * (P // G), dtype=jnp.int32)
    s = jnp.minimum(jnp.sum(w[:, None] >= ends[None, :], axis=1), 2 * B - 1)
    pages, page0, row, count, limit, table, start = segments[s].T
    page = page0 + (w - start) * G
    fields = {_BASE: table + page,
              _PAGES: jnp.clip(page0 + pages - page, 0, G), _POS: page * ps,
              _ROW: row, _COUNT: count, _LIMIT: limit}
    work = jnp.concatenate([fields[f] for f in _FIELDS]).astype(jnp.int32)
    return Plan(g.order, jnp.argsort(g.order).astype(jnp.int32), work,
                ends[-1:].astype(jnp.int32))


def _kernel(pt_ref, work_ref, n_ref, q_ref, pool_hbm, o_ref, buf, sem, m_ref,
            l_ref, *, page_size, group, chunk, tile, side, key, scale):
    """The one grid step: every block of the flat list, in order."""
    B, nh, _ = q_ref.shape
    slots = work_ref.shape[0] // len(_FIELDS)
    width = chunk * page_size
    blocks = n_ref[0]
    dt = q_ref.dtype
    field = lambda f, w: work_ref[f * slots + w]             # noqa: E731

    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)
    # a block is fetched as far as its last live page: what a chunk holds
    # behind it is masked, and must be numbers
    buf[...] = jnp.zeros(buf.shape, buf.dtype)

    def each_page(w, one_page, at_once=None):
        """`one_page(slot, _)` over block w's pages: a whole block's
        unrolled (or `at_once()` in its place), a shorter block's as far as
        it goes."""
        pages = field(_PAGES, w)

        @pl.when(pages == group)
        def _whole():
            if at_once is None:
                jax.lax.fori_loop(0, group, one_page, 0, unroll=True)
            else:
                at_once()

        @pl.when(pages < group)
        def _short():
            jax.lax.fori_loop(0, pages, one_page, 0)

    def start(w, into):
        base = field(_BASE, w)

        def one_page(slot, carry):
            pltpu.make_async_copy(pool_hbm.at[pt_ref[base + slot]],
                                  buf.at[into, slot], sem.at[into]).start()
            return carry
        each_page(w, one_page)

    def wait(w, half):
        def one_page(slot, carry):
            pltpu.make_async_copy(pool_hbm.at[0], buf.at[half, 0],
                                  sem.at[half]).wait()
            return carry
        # ONE wait for a whole block's pages: its semaphore counts them all
        each_page(w, one_page, pltpu.make_async_copy(
            pool_hbm.at[pl.ds(0, group)], buf.at[half], sem.at[half]).wait)

    def attend(r, rows, k, live):
        """`rows` rows from sorted row r on (static: a tile's, or 1) against
        the chunk's values k [width, ..]; live(shape): the scores that
        count, or None where all do."""
        at = pl.ds(r, rows)
        flat = lambda ref: ref[at].reshape(rows * nh, -1)    # noqa: E731
        s = jax.lax.dot_general(
            flat(q_ref), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [rows * nh, width]
        if live is not None:
            s = jnp.where(live(s.shape), s, _NEG_INF)
        m_prev = flat(m_ref)[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row's position 0 is live, so in the end its maximum is a real
        # score; what a masked row gathered under `_NEG_INF` before its
        # first real score, that score's alpha (0) wipes out
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        lanes = lambda a: jnp.broadcast_to(                  # noqa: E731
            a, (rows * nh, _LANES)).reshape(rows, nh, _LANES)
        l_ref[at] = lanes(alpha * flat(l_ref)[:, :1]
                          + jnp.sum(p, axis=-1, keepdims=True))
        m_ref[at] = lanes(m_new)
        o_ref[at] = (alpha * flat(o_ref) + jnp.dot(
            p.astype(dt), k[:, :2 * side],
            preferred_element_type=jnp.float32)).reshape(rows, nh, -1)

    @pl.when(blocks > 0)
    def _first_of_the_call():
        start(0, 0)

    def block(w, carry):
        half = jax.lax.rem(w, 2)

        @pl.when(w + 1 < blocks)
        def _ahead():
            start(w + 1, 1 - half)

        wait(w, half)
        row, count = field(_ROW, w), field(_COUNT, w)
        first, limit = field(_POS, w), field(_LIMIT, w)

        def values(c):
            """The chunk's rows as the queries lie: [width, 2 * side +
            256] in the cache dtype."""
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            wd = buf[half, at].reshape(width, buf.shape[-1])  # [width, words]
            lane_tile = wd[:, side:side + _LANES]
            lane = jax.lax.broadcasted_iota(jnp.int32, lane_tile.shape, 1)
            halves = unpack_words(wd[:, :side]) \
                + unpack_words(jnp.where(lane < key, lane_tile, 0))
            return jnp.concatenate([h.astype(dt) for h in halves], axis=-1)

        def a_row(c, carry):
            left = limit - first - c * width
            attend(row, 1, values(c), lambda shape: jax.lax.broadcasted_iota(
                jnp.int32, shape, 1) < left)
            return carry

        def a_group(c, carry):
            k = values(c)
            full = jax.lax.div(count, tile)

            def whole(t, carry):
                attend(row + t * tile, tile, k, None)
                return carry
            jax.lax.fori_loop(0, full, whole, 0)

            @pl.when(full * tile < count)
            def _the_rest():
                # the last tile ends where the rows end: it masks what it
                # holds of other rows
                rest = row + full * tile
                r = jnp.minimum(rest, B - tile)

                def live(shape):
                    head = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                    return (head >= (rest - r) * nh) \
                        & (head < (row + count - r) * nh)
                attend(r, tile, k, live)
            return carry

        chunks = jax.lax.div(field(_PAGES, w) + (chunk - 1), chunk)

        # a branch a block, so that a row's chunk is one straight run of
        # unpacking and products, as a group's tile is
        @pl.when(count == 1)
        def _tail():
            jax.lax.fori_loop(0, chunks, a_row, 0)

        @pl.when(count > 1)
        def _shared():
            jax.lax.fori_loop(0, chunks, a_group, 0)
        return carry

    jax.lax.fori_loop(0, blocks, block, 0)

    def normalise(r, carry):
        o_ref[r] = o_ref[r] / jnp.maximum(l_ref[r][:, :1], 1e-30)
        return carry
    jax.lax.fori_loop(0, B, normalise, 0)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _call(q_lat, q_rope, pool, page_table, lens, plan, scale, interpret):
    from ..latent_moe_ops import latent_words

    B, nh, kv_rank = q_lat.shape
    rows, ps, words = pool.shape
    P = page_table.shape[1]
    dt = jnp.bfloat16
    side, key = latent_words(kv_rank, q_rope.shape[-1], dt)
    group = pages_per_grid_step(P, ps * words * 4)
    # clamp so a padded/garbage table entry names a real page
    table = jnp.clip(page_table, 0, rows - 1).astype(jnp.int32).reshape(
        B * P)
    # the queries as the rows' values lie (`latent_attend._call`), a
    # group's rows together
    q = jnp.concatenate([q_lat.astype(dt),
                         _pad_lanes(q_rope[..., :key].astype(dt)),
                         _pad_lanes(q_rope[..., key:].astype(dt))],
                        axis=-1)[plan.order]
    whole = lambda shape: pl.BlockSpec(                      # noqa: E731
        shape, lambda i, *prefetched: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[whole(q.shape), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole((B, nh, kv_rank)),
        scratch_shapes=[
            pltpu.VMEM((2, group, ps, words), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),              # a half's pages
            pltpu.VMEM((B, nh, _LANES), jnp.float32),   # running maximum
            pltpu.VMEM((B, nh, _LANES), jnp.float32),   # running sum
        ],
    )
    u = pl.pallas_call(
        functools.partial(_kernel, page_size=ps, group=group,
                          chunk=chunk_pages(group, ps), tile=min(TILE_ROWS, B),
                          side=side, key=key, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, kv_rank), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * nh * P * ps * (2 * kv_rank + q_rope.shape[-1]),
            transcendentals=B * nh * P * ps,
            bytes_accessed=B * P * ps * words * 4 + B * nh * (
                q.shape[-1] * 2 + kv_rank * 4)),
        # the blocks share the rows' running sums and each starts the DMAs
        # of the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="paged_latent_attention",
    )(table, plan.work, plan.blocks, q, pool)
    return jnp.where((lens > 0)[:, None, None], u[plan.place], 0.0)


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
         "page_size, words] of packed bfloat16 words; rows whose tables "
         "begin with the same pages attend that run ONCE, their heads "
         "stacked on the matrix unit's rows; one grid step over a flat list "
         "of page blocks, scalar-prefetch page-table DMA, online softmax in "
         "resident scratch; forward-only")
def paged_latent_attention(q_lat, q_rope, pool, page_table, lens, dtype,
                           geom, plan=None):
    """q_lat [B, nh, kv_rank], q_rope [B, nh, rope] float32; pool `[rows,
    page_size, words]` int32 (the latent rows of all layers, `dtype`
    (bfloat16) values); page_table [B, P] int32, already shifted to the
    layer's rows (row b's context lives in pages `page_table[b, 0 ..
    ceil(lens[b] / page_size))`); lens [B] live positions (0: a row the
    scheduler padded in, which reads nothing and gets zeros); plan: the
    step's `step_plan(page_table, lens, pool.shape)`, which a caller of
    several layers works out once (any layer's table gives the same)
    -> u [B, nh, kv_rank] float32. Callers gate on
    `paged_latent_attend_supported`."""
    from ..latent_moe_ops import softmax_scale

    del dtype
    if page_table.size > TABLE_ENTRIES:
        raise ValueError(
            f"a page table of {page_table.shape} does not fit the "
            f"{TABLE_ENTRIES} entries a call prefetches")
    if plan is None:
        plan = step_plan(page_table, lens, pool.shape)
    return _call(q_lat, q_rope, pool, page_table, lens.astype(jnp.int32),
                 plan, float(softmax_scale(geom)), bool(INTERPRET))
