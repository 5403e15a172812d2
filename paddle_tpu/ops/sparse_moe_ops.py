"""The mechanisms of a decoder layer whose attention reads a LEARNED SUBSET
of its cache, with a top-k mixture of experts behind it, and the op that
runs a stack of them (the "sparse_moe" block of serving/model.py).

Each mechanism is a plain jax function (`<name>_fn`); the one registered op
that runs them is `sparse_moe_stack`:

  * `indexer_scores`     — the "lightning indexer" of DeepSeek Sparse
                           Attention: a few small query heads, ONE cached
                           key head, `I(t, s) = sum_j w_tj relu(qI_tj .
                           kI_s)`, float32. A window scores its pages' keys
                           gathered once for all its queries; a decode step
                           scores each row's pages where they lie in the
                           pool (`decode_scores`: the Pallas kernel
                           `pallas_kernels.paged_indexer` where its shape
                           gate takes the geometry, the gathered form
                           elsewhere);
  * `select_mask` / `select_indices` — the `k` cached positions `s <= t`
                           of largest `I(t, s)` (every position while
                           `t < k`; ties go to the lower position): ONE
                           rule, found without a sort (the k-th largest
                           score by counting passes over its bits), in
                           two forms. A window attends under the mask; a
                           decode row gathers by the mask's set positions,
                           named in ascending order by a two-level
                           compaction (`_mask_positions`). Each form is
                           handed back as the attention used it: a decode
                           row's indices, a window's mask packed into words
                           (`pack_selection`);
  * `join_rows` / `split_rows` — a token's K and V as ONE row of 32-bit
                           words of ONE pool, so that whoever reads a token
                           addresses it once;
  * `sparse_decode_attention` — one query a row over the joined rows of
                           its selected positions, gathered from the paged
                           pool a token at a time, once;
  * `masked_window_attention` — a window of queries over the whole paged
                           context with everything unselected masked: for
                           hundreds of queries the selections cover most of
                           the context between them, so the pages are read
                           once and the selection is a mask;
  * `topk_router`        — softmax over all experts, the k largest,
                           renormalised to sum to one.

`sparse_moe_stack` composes them into the decoder (embedding, L layers,
final norm, untied head) in the shapes serving needs: dense oracle
(`full`), a window over the paged pool (`window`: a prompt's 512-token
chunk or the suffix behind a prefix hit; `prefill` is the same at start 0)
and the ragged decode step. As in `cca_moe_ops`, the layer is written once
and scanned over weights stacked `[L, ...]`, and the pools of all layers
are one buffer each: the joined K/V rows `[L * pages, page_size, words]`
(32-bit words; `join_rows_fn`) and the indexer keys `[L * pages, index_dim,
page_size]`, index_dim values a TOKEN, a page's tokens side by side on the
lanes (layer l's page p is row `l * pages + p`).

The experts run through `pallas_kernels.moe_experts` in its combine-weight
form: a token's row of the `[T, E]` weight matrix holds its k renormalised
probabilities, zero elsewhere.

Precision: matmul operands in the weights' dtype (bfloat16 as served),
float32 accumulation; residual stream, norms, router, rotary, the indexer's
scores and the selection, and softmax in float32.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from .attention_ops import (_DROP_PAGE, _NEG_INF, _gather_pages,
                            _write_rows)
from .decoder_common import (_mm, _page_row_index, greedy_fn,
                             moe_topk_experts_fn, rms_norm_fn,
                             rotary_partial_fn, topk_router_fn)
from ..observability.schema import piece, under_mode
from .registry import ExecContext, register_op

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

Geometry = collections.namedtuple(
    "Geometry", "num_heads num_kv_heads head_dim rope_theta eps index_heads "
                "index_dim index_topk experts_per_token")

# the stacked per-layer parameters, in the order the stack op takes them
LAYER_PARAMS = (
    "attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "wqi", "wki",
    "ki_norm_w", "ki_norm_b", "ww", "ffn_norm", "router_w")
EXPERT_PARAMS = ("w_gate", "w_up", "w_down")

# queries of a window attended together: the float32 scores of one block
# are `[heads, block, context]` (32 x 64 x 36,864 x 4 B = 302 MB)
_QUERY_BLOCK = 64
# a window whose `[heads, queries, context]` indexer scores pass this many
# values (a quarter of a GB of float32) adds the heads up one at a time
# instead. A decode step that takes the gathered form (a geometry
# `paged_indexer_supported` refuses) stays under it by scoring its rows in
# blocks (`decode_scores_fn`): one query a row, so a product a head has ONE
# row and the chip's MXU idles (5.9 ms a layer at 64 rows with the heads
# one at a time; my chip runs, PR 29). A decode step the kernel serves
# writes no such scores at all
_INDEX_SCORES_AT_ONCE = 1 << 26


# ---------------------------------------------------------------------------
# the mechanisms
# ---------------------------------------------------------------------------


def index_rotary_dim(index_dim: int) -> int:
    """Lanes of an indexer head that carry rotary: the first half."""
    return index_dim // 2


def layer_norm_fn(x, w, b, eps: float):
    xf = x.astype(_F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return (xf - mu) * jax.lax.rsqrt(var + eps) * w + b


def indexer_scores_fn(qi, w, ki_pages):
    """qi [B, S, J, D] float32, w [B, S, J] float32 (the score's scale
    folded in), ki_pages [B, P, D, page_size] as cached (a page's keys side
    by side, one LANE a token: `kv_cache.stacked_pool_shapes`) -> `I` [B,
    S, P * page_size] float32. The product contracts D and leaves a page's
    tokens on the lanes, so nothing is transposed on the way."""
    B, S, J, _ = qi.shape
    P, ps = ki_pages.shape[1], ki_pages.shape[3]
    qc = qi.astype(ki_pages.dtype)
    if B * S * J * P * ps <= _INDEX_SCORES_AT_ONCE:
        s = jnp.einsum("bsjd,bpdt->bsjpt", qc, ki_pages,
                       preferred_element_type=_F32)
        return jnp.einsum("bsjpt,bsj->bspt", jax.nn.relu(s),
                          w).reshape(B, S, P * ps)

    def add_head(acc, head):
        qj, wj = head                                  # [B, S, D], [B, S]
        s = jnp.einsum("bsd,bpdt->bspt", qj, ki_pages,
                       preferred_element_type=_F32)
        return acc + jax.nn.relu(s) * wj[..., None, None], None

    acc, _ = jax.lax.scan(add_head, jnp.zeros((B, S, P, ps), _F32),
                          (jnp.moveaxis(qc, 2, 0), jnp.moveaxis(w, 2, 0)))
    return acc.reshape(B, S, P * ps)


def paged_indexer_runs(q_shape, pool_shape, pool_dtype) -> bool:
    """Whether a decode step's scores, qI `q_shape` [B, J, D] over the
    index pool `pool_shape`, come from `pallas_kernels.paged_indexer`: its
    shape gate decides alone, where a Pallas kernel can run at all. The
    engine books `serving.sparse.kernel_layer_steps` by the same answer."""
    from .pallas_kernels import paged_indexer, workbench

    return (workbench.runnable(paged_indexer)
            and paged_indexer.paged_indexer_supported(
                tuple(q_shape), tuple(pool_shape), pool_dtype))


def _block_of(n: int, per_row: int) -> int:
    """Rows of `n` scored at once: halved while a block's scores pass
    `_INDEX_SCORES_AT_ONCE` values and the rows still divide."""
    b = n
    while b > 1 and b % 2 == 0 and b * per_row > _INDEX_SCORES_AT_ONCE:
        b //= 2
    return b


def decode_scores_fn(qi, w, i_pool, table, lens):
    """A decode step's indexer scores over its paged context: qi [B, 1, J,
    D], w [B, 1, J] float32, table [B, P] (shifted to the layer's rows),
    lens [B] (a row's live tokens, 0 for one the scheduler padded in) ->
    `I` [B, 1, P * page_size] float32. Where the kernel runs each row's
    pages are read once where they lie: neither a gathered copy nor the
    `[rows, heads, context]` products are written. Elsewhere (the CPU
    rehearsal geometries) every row gathers its own pages' keys, in blocks
    of rows whose products stay under `_INDEX_SCORES_AT_ONCE`."""
    from .pallas_kernels import paged_indexer

    B, _, J, _ = qi.shape
    P, ps = table.shape[1], i_pool.shape[2]
    if paged_indexer_runs(qi[:, 0].shape, i_pool.shape, i_pool.dtype):
        return paged_indexer.paged_indexer_scores(qi[:, 0], w[:, 0], i_pool,
                                                  table, lens)
    pages = jnp.clip(table, 0, i_pool.shape[0] - 1)
    b = _block_of(B, J * P * ps)
    if b == B:
        return indexer_scores_fn(qi, w, i_pool[pages])
    split = lambda a: a.reshape((B // b, b) + a.shape[1:])      # noqa: E731
    out = jax.lax.map(
        lambda a: indexer_scores_fn(a[0], a[1], i_pool[a[2]]),
        (split(qi), split(w), split(pages)))
    return out.reshape(B, 1, P * ps)


def write_index_keys_fn(i_pool, ki, page_table, layer_off, first, count):
    """The keys ki [B, S, D] of positions `first[b] .. first[b] +
    count[b] - 1` into the index pool `[rows, D, page_size]`, where a
    token's key is one LANE of its page's `[D, page_size]` slab: the pages
    a row's window touches are read whole, the new lanes laid over them and
    the slabs written back, so that the pool is only ever read and written
    in whole 128-lane rows and keeps the row-major layout the chip's client
    stores it in (a `[rows, page_size, 64]` pool was stored slots-minor and
    copied whole, in and out, by every step; a per-token column scatter
    made the compiler want the transposed layout). A page being written
    belongs to one row alone (copy-on-write), so slabs never collide."""
    R, D, ps = i_pool.shape
    B, S, _ = ki.shape
    P = page_table.shape[1]
    n_pages = min(P, (S + ps - 2) // ps + 1)         # a window can straddle
    ords = (first // ps)[:, None] + jnp.arange(n_pages, dtype=jnp.int32)
    rel = (ords[..., None] * ps + jnp.arange(ps, dtype=jnp.int32)
           - first[:, None, None])                   # [B, n_pages, ps]
    new = (rel >= 0) & (rel < count[:, None, None])
    touched = jnp.any(new, axis=-1) & (ords < P)
    rows = jnp.take_along_axis(page_table, jnp.clip(ords, 0, P - 1), axis=1)
    rows = jnp.where(touched, rows + layer_off, _DROP_PAGE)
    old = i_pool[jnp.clip(rows, 0, R - 1)]           # [B, n_pages, D, ps]
    at = jnp.clip(rel, 0, S - 1).reshape(B, n_pages * ps, 1)
    vals = jnp.take_along_axis(ki.astype(i_pool.dtype), at, axis=1)
    vals = jnp.swapaxes(vals.reshape(B, n_pages, ps, D), 2, 3)
    slabs = jnp.where(new[:, :, None, :], vals, old)
    return i_pool.at[rows].set(slabs, mode="drop")


def _ordered_bits(x):
    """float32 -> uint32 in the same order (-0.0 read as +0.0)."""
    u = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def _kth_largest(u, k: int):
    """u [..., T] uint32 -> [...]: its k-th largest value, found a bit at a
    time: 32 counting passes, 0.1 ms for 64 rows of 36,864 where a sort of
    the rows takes 2.16 (PERF.md, PR 34)."""
    def narrow(i, lo):
        cand = lo | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum((u >= cand[..., None]).astype(jnp.int32), -1) >= k
        return jnp.where(enough, cand, lo)

    return jax.lax.fori_loop(0, 32, narrow,
                             jnp.zeros(u.shape[:-1], jnp.uint32))


def select_mask_fn(scores, limit, k: int):
    """scores [..., T], limit [...] (positions `s < limit` exist) -> mask
    [..., T] of the `min(k, T, limit)` positions of largest score, ties to
    the lower position, found without a sort: everything above the k-th
    largest score, and of the scores equal to it the lowest positions that
    fill the k."""
    T = scores.shape[-1]
    kk = min(int(k), T)
    live = jnp.arange(T, dtype=jnp.int32) < limit[..., None]
    u = _ordered_bits(jnp.where(live, scores, -jnp.inf))
    kth = _kth_largest(u, kk)[..., None]
    # live ties only: where fewer than k positions exist the cut is the
    # dead positions' -inf, and they would crowd every row into the cumsum
    above, ties = u > kth, (u == kth) & live
    room = kk - jnp.sum(above.astype(jnp.int32), -1, keepdims=True)
    crowded = jnp.sum(ties.astype(jnp.int32), -1, keepdims=True) > room
    ties = jax.lax.cond(
        jnp.any(crowded),
        lambda: ties & (jnp.cumsum(ties.astype(jnp.int32), axis=-1) <= room),
        lambda: ties)
    return live & (above | ties)


# positions a group of the compaction below: one row of lanes
_LANES = 128


def _mask_positions(mask, kk: int):
    """mask [R, T] bool, at most `kk` set a row -> [R, kk] int32: the set
    positions in ascending order, -1 in the slots past their count. A
    two-level compaction in exact small integers (bfloat16 operands no
    larger than 128, float32 sums), with no scatter, no gather and no scan
    over T. The row is cut into groups of 128 lanes; a product with a
    triangle ranks a position among the set lanes of its group, another
    gives the count before each group. Output slot `j` lies in the last
    group whose count-before is at most `j`; it fetches that group's ranks
    with a ONE-HOT product (the chip gathers by the row, 12-15 ns each:
    131,072 gathered rank rows would cost what the sort cost) and takes the
    lane whose rank is `j` less the count-before. The chip's compiler fuses
    the one-hot, the product and the lane search into one operation:
    neither the `[R, kk, groups]` one-hot nor the `[R, kk, 128]` ranks are
    written out (0.26 ms for 64 rows of 36,864 with the cut, where the sort
    took 2.16: PERF.md, PR 34)."""
    R, T = mask.shape
    G = -(-T // _LANES)
    if G * _LANES > T:
        mask = jnp.pad(mask, ((0, 0), (0, G * _LANES - T)))
    m = mask.reshape(R, G, _LANES).astype(jnp.bfloat16)
    lane = jnp.arange(_LANES, dtype=jnp.int32)
    group = jnp.arange(G, dtype=jnp.int32)
    before = (lane[:, None] < lane[None, :]).astype(jnp.bfloat16)
    rank = jnp.einsum("rgl,lm->rgm", m, before, preferred_element_type=_F32)
    # a set lane holds its rank in the group, from 1; an unset one 0
    ranked = ((rank + 1.0) * m.astype(_F32)).astype(jnp.bfloat16)
    counts = jnp.sum(m, axis=-1, dtype=_F32).astype(jnp.bfloat16)
    upto = (group[:, None] <= group[None, :]).astype(jnp.bfloat16)
    incl = jnp.dot(counts, upto, preferred_element_type=_F32).astype(jnp.int32)
    excl = incl - counts.astype(jnp.int32)                   # count-before
    # count-before and group both rise with the group: ONE max over the two
    # packed into a number finds a slot's group and its count-before in one
    # pass over `[R, kk, G]` (two reductions cost 0.15 ms, this 0.07)
    bits = G.bit_length()
    assert kk << bits < 1 << 31, (kk, G)
    slot = jnp.arange(kk, dtype=jnp.int32)
    packed = (excl << bits) | group
    last = jnp.max(jnp.where(excl[:, None, :] <= slot[None, :, None],
                             packed[:, None, :], 0), axis=-1)      # [R, kk]
    mine, base = last & ((1 << bits) - 1), last >> bits
    onehot = (mine[..., None] == group).astype(jnp.bfloat16)
    ranks = jnp.einsum("rjg,rgl->rjl", onehot, ranked,
                       preferred_element_type=_F32)                # [R, kk, 128]
    want = (slot - base + 1).astype(_F32)[..., None]
    found = jnp.sum(jnp.where(ranks == want, lane, 0), axis=-1)
    return jnp.where(slot < incl[:, -1:], mine * _LANES + found, -1)


def select_indices_fn(scores, limit, k: int):
    """scores [B, S, T], limit [B, S] (positions `s < limit` exist) -> sel
    [B, S, kk] int32: the `kk = min(k, T)` positions `select_mask_fn`
    keeps, in ascending position, -1 where fewer exist. One selection rule
    in two forms: the mask's set bits, named."""
    T = scores.shape[-1]
    kk = min(int(k), T)
    # rows flat: a unit second-minor dimension pads to a tile of 8 (the
    # chip sorted a `[64, 1, T]` array seven times slower than `[64, T]`)
    keep = select_mask_fn(scores.reshape(-1, T), limit.reshape(-1), k)
    return _mask_positions(keep, kk).reshape(scores.shape[:-1] + (kk,))


def pack_selection_fn(keep, page_size: int):
    """keep [..., P * page_size] bool -> int32 words [..., G, page_size], G
    = ceil(P / 32): bit `p % 32` of word `[p // 32, slot]` says whether
    position `p * page_size + slot` is kept. Thirty-two PAGES share a
    word, so a page's slots stay side by side on the lanes and the packing
    is a sum over whole rows."""
    lead, P = keep.shape[:-1], keep.shape[-1] // page_size
    G = -(-P // 32)
    pages = keep.reshape(lead + (P, page_size))
    if G * 32 > P:
        pages = jnp.pad(pages, [(0, 0)] * len(lead)
                        + [(0, G * 32 - P), (0, 0)])
    bit = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[:, None]
    words = jnp.sum(jnp.where(pages.reshape(lead + (G, 32, page_size)), bit,
                              jnp.uint32(0)), axis=-2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def join_rows_fn(k, v, dtype):
    """k, v [..., W] -> the token's ONE row [..., words] of 32-bit words
    (int32, as the framework spells a word), K's words then V's, each the
    `dtype` bits of its W values. A 32-bit dtype gives W words a side. A
    16-bit one gives W / 2: lane `i` of the side's second half rides in the
    high 16 bits of the word whose low 16 are lane `i` of its first half,
    so a head stays a whole 128-lane slice of words (`kv_heads` even) and
    is read back by a mask or a shift. The chip gathers a row of 32-bit
    words at half the cost a byte of a 16-bit row, which shares every
    sublane word with its neighbour row (PERF.md section 6, PR 30)."""
    def words(x):
        x = x.astype(dtype)
        if x.dtype.itemsize == 4:
            return jax.lax.bitcast_convert_type(x, jnp.int32)
        bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        half = x.shape[-1] // 2
        return jax.lax.bitcast_convert_type(
            bits[..., :half] | (bits[..., half:] << 16), jnp.int32)

    return jnp.concatenate([words(k), words(v)], axis=-1)


def _word_values(w, dtype, halves=(False, True)):
    """The `dtype` values that 32-bit words hold: a word whole or, of a
    16-bit dtype, the low (False) and high (True) halves named, side by
    side. Integer operations until the last bitcast, so every bit pattern
    comes back as it went in."""
    if jnp.dtype(dtype).itemsize == 4:
        return jax.lax.bitcast_convert_type(w, dtype)
    w = jax.lax.bitcast_convert_type(w, jnp.uint32)
    bits = [((w >> 16) if high else (w & 0xFFFF)).astype(jnp.uint16)
            for high in halves]
    return jax.lax.bitcast_convert_type(
        bits[0] if len(bits) == 1 else jnp.concatenate(bits, axis=-1), dtype)


def split_rows_fn(rows, dtype):
    """`join_rows_fn` read back: rows [..., words] int32 -> k, v [..., W] in
    `dtype`, the same bits."""
    side = rows.shape[-1] // 2
    return (_word_values(rows[..., :side], dtype),
            _word_values(rows[..., side:], dtype))


def head_of_rows_fn(rows, j: int, dh: int, dtype):
    """K and V [..., dh] of KV head `j` alone: its `dh` words of each side
    are sliced out BEFORE they are unpacked, so the products read the
    gathered words as they lie and nothing writes K and V out again (268 MB
    a layer at the served shapes)."""
    side = rows.shape[-1] // 2
    at = j * dh % side
    return tuple(_word_values(rows[..., lo:lo + dh], dtype, (j * dh >= side,))
                 for lo in (at, side + at))


def sparse_decode_attention_fn(q, kv_pool, page_table, sel, sm_scale: float,
                               dtype):
    """q [B, nh, dh] float32; kv_pool `[rows, page_size, words]` of joined
    rows holding `dtype` values; page_table [B, P] (already shifted to the
    layer's rows); sel [B, kk] positions, -1 for none -> [B, nh, dh]
    float32: softmax over the selected positions only. A selected token is
    gathered ONCE, K and V together, from the pool seen as `[rows *
    page_size, words]` (whole tiles either way: no copy)."""
    B, nh, dh = q.shape
    rows, ps, words = kv_pool.shape
    P = page_table.shape[1]
    have = sel >= 0
    with piece("kv_gather"):
        at = jnp.maximum(sel, 0)
        # the page of every selected position, as a masked sum over the
        # table (131,072 scalar gathers a layer cost the chip 1.2 ms)
        ordinal = jnp.arange(P, dtype=jnp.int32)
        page = jnp.sum(jnp.where((at // ps)[..., None] == ordinal,
                                 page_table[:, None, :], 0), axis=-1)
        flat = jnp.clip(page, 0, rows - 1) * ps + at % ps      # [B, kk]
        tokens = kv_pool.reshape(rows * ps, words)[flat]       # [B, kk, w]
    nkv = words * 4 // 2 // jnp.dtype(dtype).itemsize // dh    # K's bytes
    with piece("attend"):
        qg = q.reshape(B, nkv, nh // nkv, dh).astype(dtype)
        out = []
        for j in range(nkv):    # a KV head is a 128-lane slice of a row
            kj, vj = head_of_rows_fn(tokens, j, dh, dtype)
            s = jnp.einsum("bgd,bkd->bgk", qg[:, j], kj,
                           preferred_element_type=_F32) * sm_scale
            s = jnp.where(have[:, None, :], s, _NEG_INF)
            probs = jax.nn.softmax(s, axis=-1)
            out.append(jnp.einsum("bgk,bkd->bgd", probs.astype(vj.dtype),
                                  vj, preferred_element_type=_F32))
        return jnp.stack(out, axis=1).reshape(B, nh, dh)


def _masked_attention(q, k, v, mask, sm_scale):
    """q [B, S, nh, dh], k/v [B, T, nkv, dh], mask [B, S, T] -> [B, S, nh,
    dh] float32, query block by query block."""
    B, S, nh, dh = q.shape
    nkv = k.shape[2]
    qg = q.reshape(B, S, nkv, nh // nkv, dh).astype(k.dtype)

    def block(args):
        qb, mb = args                       # [B, s, nkv, g, dh], [B, s, T]
        s = jnp.einsum("bsjgd,btjd->bjgst", qb, k,
                       preferred_element_type=_F32) * sm_scale
        s = jnp.where(mb[:, None, None], s, _NEG_INF)
        probs = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bjgst,btjd->bsjgd", probs.astype(v.dtype), v,
                          preferred_element_type=_F32)

    if S <= _QUERY_BLOCK or S % _QUERY_BLOCK:
        out = block((qg, mask))
    else:
        n = S // _QUERY_BLOCK
        split = lambda a: jnp.moveaxis(                      # noqa: E731
            a.reshape((B, n, _QUERY_BLOCK) + a.shape[2:]), 1, 0)
        out = jnp.moveaxis(jax.lax.map(block, (split(qg), split(mask))),
                           0, 1).reshape(B, S, nkv, nh // nkv, dh)
    return out.reshape(B, S, nh, dh)


def masked_window_attention_fn(q, kv_pool, page_table, mask,
                               sm_scale: float, dtype):
    """q [B, S, nh, dh] over the paged context of `page_table` [B, P]
    (shifted to the layer's rows), mask [B, S, P * page_size]: a page's
    slab of joined rows is gathered once and split into K and V."""
    dh = q.shape[-1]
    with piece("kv_gather"):
        k, v = split_rows_fn(
            _gather_pages(kv_pool, page_table, 1)[:, :, 0], dtype)
    heads = k.shape[:2] + (k.shape[2] // dh, dh)
    with piece("attend"):
        return _masked_attention(q, k.reshape(heads), v.reshape(heads),
                                 mask, sm_scale)


# ---------------------------------------------------------------------------
# one layer, in two halves around the attention
# ---------------------------------------------------------------------------


def _pre_attention(x, p, positions, geom: Geometry):
    """x [B, S, H] -> q [B, S, nh, dh], k, v [B, S, nkv, dh], the indexer's
    qi [B, S, J, D], ki [B, S, D] and w [B, S, J], all float32."""
    B, S, _ = x.shape
    nh, nkv, dh = geom.num_heads, geom.num_kv_heads, geom.head_dim
    J, D = geom.index_heads, geom.index_dim
    z = rms_norm_fn(x, p["attn_norm"], geom.eps)
    q = rms_norm_fn(_mm(z, p["wq"]).reshape(B, S, nh, dh), p["q_norm"],
                    geom.eps)
    k = rms_norm_fn(_mm(z, p["wk"]).reshape(B, S, nkv, dh), p["k_norm"],
                    geom.eps)
    q = rotary_partial_fn(q, positions, dh, geom.rope_theta)
    k = rotary_partial_fn(k, positions, dh, geom.rope_theta)
    v = _mm(z, p["wv"]).reshape(B, S, nkv, dh)
    rot = index_rotary_dim(D)
    qi = rotary_partial_fn(_mm(z, p["wqi"]).reshape(B, S, J, D), positions,
                           rot, geom.rope_theta)
    ki = layer_norm_fn(_mm(z, p["wki"]), p["ki_norm_w"], p["ki_norm_b"],
                       geom.eps)
    ki = rotary_partial_fn(ki[:, :, None, :], positions, rot,
                           geom.rope_theta)[:, :, 0]
    w = _mm(z, p["ww"]) * (J ** -0.5 * D ** -0.5)
    return q, k, v, qi, ki, w


def _post_attention(x, o, p, experts, layer, geom: Geometry, tag):
    """x [B, S, H], o [B, S, nh*dh] -> (y [B, S, H], ids [B, S, k])."""
    B, S, H = x.shape
    with piece("proj"):
        h = x + _mm(o, p["wo"])
        z = rms_norm_fn(h, p["ffn_norm"], geom.eps).reshape(B * S, H)
    with piece("router"):
        ids, cw = topk_router_fn(z, p["router_w"], geom.experts_per_token)
    with piece("experts"):
        y = moe_topk_experts_fn(z, cw, *experts, layer=layer, tag=tag,
                                k=geom.experts_per_token)
        return h + y.reshape(B, S, H), ids.reshape(B, S, -1)


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


@under_mode
def sparse_moe_stack_fn(mode: str, tok, pos, emb, head, final_norm,
                        layer_params: dict, experts: tuple, geom: Geometry,
                        pools=None, page_table=None, lens=None, start=None,
                        mask=None, mark=None, num_pages: int = 0):
    """Run the decoder. `mode`:

      full     tok/pos [B, S]                          -> logits [B, S, V]
      window   + page_table, start, lens (context in
               the pool; `prefill` is start 0)         -> last logits [B, V]
      decode   tok/pos [B], page_table, mask [B],
               mark [M] (rows whose selection is kept) -> logits [B, V]

    Returns a dict: logits; routes ([B, S, L, k], decode [B, L, k]);
    selection, what each layer's attention was given: the mask a window
    (or `full`: the sequence as one page) attended under, as
    `pack_selection_fn` words [B, S, L, G, page_size]; the positions the
    marked rows of a decode step gathered, [M, L, kk], -1 where fewer
    exist; and, with `pools` (the joined K/V rows, the indexer keys),
    kv_pool/i_pool as written. Traced under its mode's scope, each piece
    (observability/schema.PIECES) under its own."""
    decode = mode == "decode"
    paged = mode != "full"
    if decode:
        tok, pos = jnp.reshape(tok, (-1, 1)), jnp.reshape(pos, (-1, 1))
    with piece("embed"):
        x = emb[tok].astype(_F32)
    B, S, _ = x.shape
    L = layer_params["wq"].shape[0]
    sm_scale = geom.head_dim ** -0.5
    tag = "decode" if decode else "prefill"
    kv_dtype = layer_params["wk"].dtype     # K and V are stored as made
    rel = jnp.arange(S, dtype=jnp.int32)[None, :]
    if paged:
        page_size = pools[0].shape[1]
        page_table = page_table.astype(jnp.int32)
        context = page_table.shape[1] * page_size
        first = (pos[:, 0] if decode
                 else (start if start is not None
                       else jnp.zeros((B,), jnp.int32))).astype(jnp.int32)
        gpos = first[:, None] + rel                             # [B, S]
        valid = (jnp.reshape(mask, (-1, 1)) > 0) if decode \
            else rel < lens[:, None]
        count = valid[:, 0].astype(jnp.int32) if decode else lens
        # a decode step whose whole bucket fits the selection scores
        # nothing and attends every live position of its pages' slabs
        dense_decode = decode and context <= geom.index_topk
    else:
        gpos = jnp.broadcast_to(rel, (B, S))
        context = S
    def layer(carry, xs):
        l, p = xs
        if paged:
            x, kv_pool, i_pool = carry
            off = l * num_pages
            table = page_table + off
        else:
            (x,) = carry
        with piece("proj"):
            q, k, v, qi, ki, w = _pre_attention(x, p, pos, geom)
        if paged:
            with piece("kv_write"):
                idx = _page_row_index(page_table, gpos, page_size, off,
                                      valid)
                slot = gpos % page_size
                kv_pool = _write_rows(kv_pool, join_rows_fn(
                    k.reshape(B, S, -1), v.reshape(B, S, -1), kv_dtype),
                    idx, slot)
                i_pool = write_index_keys_fn(i_pool, ki, page_table, off,
                                             first, count)
        if paged and dense_decode:
            at = jnp.arange(context, dtype=jnp.int32)[None, :]
            live = (at <= first[:, None])[:, None]              # [B, 1, T]
            o = masked_window_attention_fn(q, kv_pool, table, live,
                                           sm_scale, kv_dtype)
            sel = jnp.where(live, at[:, None], -1)
        else:
            with piece("indexer"):
                if decode:      # each row's pages, where they lie
                    scores = decode_scores_fn(qi, w, i_pool, table,
                                              (first + 1) * count)
                elif paged:     # a window's, gathered once for its queries
                    scores = indexer_scores_fn(qi, w, i_pool[jnp.clip(
                        table, 0, i_pool.shape[0] - 1)])
                else:           # the sequence as one page
                    scores = indexer_scores_fn(
                        qi, w, jnp.swapaxes(ki.astype(emb.dtype), 1,
                                            2)[:, None])
            if decode:
                with piece("select"):
                    sel = select_indices_fn(scores, gpos + 1,
                                            geom.index_topk)
                o = sparse_decode_attention_fn(
                    q[:, 0], kv_pool, table, sel[:, 0], sm_scale,
                    kv_dtype)[:, None]
            else:
                with piece("select"):
                    keep = select_mask_fn(scores, gpos + 1, geom.index_topk)
                if paged:
                    o = masked_window_attention_fn(q, kv_pool, table, keep,
                                                   sm_scale, kv_dtype)
                else:
                    with piece("attend"):
                        o = _masked_attention(q, k.astype(emb.dtype),
                                              v.astype(emb.dtype), keep,
                                              sm_scale)
                # handed back as attended under: the mask itself
                with piece("select"):
                    sel = pack_selection_fn(keep,
                                            page_size if paged else S)
        y, ids = _post_attention(x, o.reshape(B, S, -1), p, experts, l,
                                 geom, tag)
        if decode:
            sel = sel[:, 0][mark]                               # [M, kk]
        carry = (y, kv_pool, i_pool) if paged else (y,)
        return carry, (ids, sel)

    init = (x,) + (tuple(pools) if paged else ())
    xs = (jnp.arange(L, dtype=jnp.int32), layer_params)
    carry, (routes, selection) = jax.lax.scan(layer, init, xs)
    with piece("head"):
        xn = rms_norm_fn(carry[0], final_norm, geom.eps)
        if mode == "window":
            at = jnp.clip(lens - 1, 0, S - 1)[:, None, None]
            xn = jnp.take_along_axis(xn, at, axis=1)
        logits = jnp.einsum("bsh,hv->bsv", xn.astype(head.dtype), head,
                            preferred_element_type=_F32)
    routes = jnp.moveaxis(routes, 0, -2)                  # [B, S, L, k]
    if decode:
        selection = jnp.moveaxis(selection, 0, 1)         # [M, L, kk]
    else:
        selection = jnp.moveaxis(selection, 0, 2)     # [B, S, L, G, ps]
    out = {"logits": logits if mode == "full" else logits[:, 0],
           "routes": routes[:, 0] if decode else routes,
           "selection": selection}
    if paged:
        out.update(kv_pool=carry[1], i_pool=carry[2])
    return out


# ---------------------------------------------------------------------------
# registered op
# ---------------------------------------------------------------------------


@register_op("sparse_moe_stack", grad="none")
def sparse_moe_stack_op(ctx: ExecContext):
    """The whole decoder in one op; see `sparse_moe_stack_fn`. inputs: Tok,
    Pos, Emb, Head, FinalNorm, LayerParams (the `LAYER_PARAMS`, in order),
    Experts (`EXPERT_PARAMS`), and by mode PageTable, Lens, Start, Mask,
    Mark (decode), KVPool/IPool. attrs: mode and the geometry. Outputs:
    NextToken (greedy), Logits, Routes, Selection, and the pools under
    their own names."""
    mode = ctx.attr("mode")
    geom = Geometry(*(ctx.attr(f) for f in Geometry._fields))
    params = dict(zip(LAYER_PARAMS, ctx.inputs("LayerParams")))
    paged = mode != "full"

    def opt(slot):
        return ctx.input(slot).astype(jnp.int32) if ctx.has_input(slot) \
            else None

    out = sparse_moe_stack_fn(
        "window" if mode == "prefill" else mode,
        ctx.input("Tok").astype(jnp.int32),
        ctx.input("Pos").astype(jnp.int32), ctx.input("Emb"),
        ctx.input("Head"), ctx.input("FinalNorm"), params,
        tuple(ctx.inputs("Experts")), geom,
        pools=(ctx.input("KVPool"), ctx.input("IPool")) if paged else None,
        page_table=opt("PageTable"), lens=opt("Lens"), start=opt("Start"),
        mask=ctx.input("Mask") if ctx.has_input("Mask") else None,
        mark=opt("Mark"), num_pages=int(ctx.attr("num_pages", 0)))
    res = {"Logits": out["logits"], "Routes": out["routes"],
           "Selection": out["selection"],
           "NextToken": greedy_fn(out["logits"])}
    if paged:
        res.update(KVPoolOut=out["kv_pool"], IPoolOut=out["i_pool"])
    return res
