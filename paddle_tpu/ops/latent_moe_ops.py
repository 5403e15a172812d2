"""The mechanisms of a decoder layer whose attention keeps ONE compressed
row a token (multi-head latent attention) and reads a learned subset of
those rows, with a group-limited mixture of experts of which this chip holds
a SHARE behind it, and the op that runs a stack of them (the "latent_moe"
block of serving/model.py).

Each mechanism is a plain jax function (`<name>_fn`); the one registered op
that runs them is `latent_moe_stack`:

  * `rotary_interleaved`  — rotary on lane pairs (2i, 2i + 1) of a head,
                            the pairing of the latent attention's 64 rotary
                            lanes (the indexer's are rotate-half:
                            `hybrid_moe_ops.rotary_fn`); both turn by
                            `yarn_inv_freq_fn`'s frequencies;
  * `join_latent` / `split_latent` — a token's cache row: its normalised
                            latent `c_kv` and its one rotary key `k_rope`,
                            side by side as 32-bit words of ONE pool
                            (`sparse_moe_ops.join_rows_fn`'s packing), so
                            that whoever reads a token addresses it once;
  * `expanded_attention`  — the attention as the equations state it: every
                            head's keys and values made from the latents
                            (`[k_nope_h | v_h] = W_kvb,h c_kv`), a window of
                            queries over them under a mask. Affordable
                            where the whole context fits the selection;
  * `absorbed_attention`  — the same numbers with the per-head products
                            carried to the query's side: `q_lat_h = W_uk,h^T
                            q_nope_h`, scores and the weighted sum taken IN
                            the latent, `v_h = W_uv,h u_h` after it. All
                            heads of a query read the same gathered rows;
  * `group_limited_router` — `s = sigmoid(z W_r)`; inside each group of
                            experts the two largest `s + b` are summed, the
                            best groups kept, the k largest `s + b` inside
                            them chosen (ties to the lower index), weights
                            `scaling * s_e / sum_chosen s`: the bias selects
                            and never weighs.

The indexer, the selection without a sort and its two forms (a window's
mask, a decode row's positions) are `sparse_moe_ops`', with the indexer's
queries taken from the query latent `c_q`; a decode step's scores come
from `pallas_kernels.paged_indexer`, which reads each row's key pages out
of the pool once (`sparse_moe_ops.decode_scores_fn`), a window's from its
pages' keys gathered once (`paged_scores_fn`).

`latent_moe_stack` composes them into the decoder (embedding, leading dense
layers, routed layers, final norm, untied head) in the shapes serving needs:
dense oracle (`full`: expanded attention under the indexer's mask), a
window over the paged pools (`window`; `prefill` is the same at start 0)
and the ragged decode step. WHICH FORM RUNS WHERE: a step whose page table
fits the selection attends every live position, a window in the expanded
form, a decode row in the absorbed form over its pages' slabs; behind a
longer context both run the indexer and the absorbed form over each
query's own gathered rows (a window in blocks of `_QUERY_BLOCK` queries).
The absorbed form is ONE kernel wherever `latent_attend_runs` takes the
shapes (`pallas_kernels.latent_attend`: a query's gathered rows read once
as they lie, the words unpacked in VMEM, no scores written), and
`absorbed_attention_fn` elsewhere.

TWO THINGS THE GEOMETRY MAY CHANGE. WITHOUT AN INDEXER (`index_topk` 0: no
indexer parameters, no key pool, no selection handed back) every step
attends every live position at ANY context: a window in the expanded form
over key blocks with a running softmax (`expanded_attention_blocks_fn`), a
decode row in the absorbed form over all its pages, read in place by
`pallas_kernels.paged_latent_attend` wherever `paged_attend_runs` takes the
shapes (the gathered slabs and `absorbed_attention_fn` elsewhere); rows
whose tables begin with the same pages read that run once, which the stack
works out of the step's tables before its layers (`step_plan`). WITH
`hc_mult` > 1 RESIDUAL STREAMS the layers hand on `[n, B, S, H]` float32
and every sub-layer sits between `hyper_connection_ops`' mappings and mixes
(`_mixed_input`; pieces `hc_map`, `hc_mix`); the embedding is copied into
the streams and the final norm reads their sum.

Weights are stacked by layer KIND (`dense.*` over the leading dense layers:
attention, indexer and a SwiGLU; `moe.*` over the routed ones: attention,
indexer, router and shared expert; the experts `[L_moe, held, ...]`); the
dense layers run one after another, the routed ones as one `lax.scan`. The
pools of all layers are one buffer each: the latent rows `[L * pages,
page_size, words]` (32-bit words) and the indexer keys `[L * pages,
index_dim, page_size]` (`sparse_moe_ops.write_index_keys_fn`).

THE EXPERT SHARE. The router scores all `num_experts`; the stacked expert
weights hold the first `experts_held` of them (this chip's share of a
deployment that divides the experts among chips), and the combine weights
are cut to those columns before `moe_topk_experts`. What the other chips'
experts would add is left out: the layer's output is the shared expert plus
this chip's part, and that partial result goes on to the next layer.

Precision: matmul operands in the weights' dtype (bfloat16 as served),
float32 accumulation; residual stream, norms, router (scores, bias,
selection, weights), rotary, the indexer's scores and the selection, and
softmax in float32.
"""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp

from .attention_ops import _NEG_INF, _write_rows
from .decoder_common import (_mm, _page_row_index, greedy_fn,
                             group_limited_router_fn, moe_topk_experts_fn,
                             rms_norm_fn, rotary_fn, swiglu_fn,
                             yarn_inv_freq_fn)
from . import hyper_connection_ops as hc
from ..observability.schema import piece, under_mode
from .registry import ExecContext, register_op
from .sparse_moe_ops import (_block_of, _mask_positions, _word_values,
                             decode_scores_fn, indexer_scores_fn,
                             join_rows_fn, layer_norm_fn, pack_selection_fn,
                             select_indices_fn, select_mask_fn,
                             write_index_keys_fn)

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

Geometry = collections.namedtuple(
    "Geometry", "num_heads nope_dim rope_dim v_dim kv_rank rope_theta "
                "yarn softmax_mscale eps index_heads index_dim index_topk "
                "experts_per_token expert_groups groups_per_token "
                "routed_scaling experts_held hc_mult hc_iters hc_eps "
                "hc_clamp",
    # one residual stream unless the configuration says more
    defaults=(1, 0, 0.0, ()))

# the stacked parameters, in the order the stack op takes them: a layer's
# attention and indexer (a set each for the dense and the routed layers),
# then what its feed-forward kind adds
ATTENTION_PARAMS = (
    "attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
    "wqi", "wki", "ki_norm_w", "ki_norm_b", "ww", "ffn_norm")
DENSE_PARAMS = ("w_gate", "w_up", "w_down")
MOE_PARAMS = ("router_w", "router_bias", "shared_gate", "shared_up",
              "shared_down")
EXPERT_PARAMS = ("w_gate", "w_up", "w_down")
INDEXER_PARAMS = ("wqi", "wki", "ki_norm_w", "ki_norm_b", "ww")


def attention_params(indexed: bool, streams: int) -> tuple:
    """The per-layer parameters both layer kinds share, for a configuration
    with or without an indexer (`index_topk`) and with `streams` residual
    streams (`hc_mult`): `ATTENTION_PARAMS` without the indexer's where
    there is none, then the mappings of a layer's two sub-layers, stacked
    `[layers, 2, ...]`, where there is more than one stream."""
    return tuple(k for k in ATTENTION_PARAMS
                 if indexed or k not in INDEXER_PARAMS) \
        + (hc.HC_PARAMS if streams > 1 else ())

# queries attended together. Expanded: the float32 scores of one block are
# `[heads, block, context]`. Absorbed: a block gathers `block x index_topk`
# rows (64 x 2,048 x 1,536 B as stored = 201 MB as served)
_QUERY_BLOCK = 64
# keys a window without an indexer expands and scores at a time: the float32
# scores of one block are `[heads, window, block]` (134 MB at 32 heads and a
# 2,048-token window)
_KEY_BLOCK = 512


# ---------------------------------------------------------------------------
# the mechanisms
# ---------------------------------------------------------------------------


def softmax_scale(geom: Geometry) -> float:
    """`(nope + rope)^-0.5 x m^2`, m the YaRN factor over all dimensions."""
    return (geom.nope_dim + geom.rope_dim) ** -0.5 \
        * float(geom.softmax_mscale) ** 2


def yarn_mscale(factor: float, mscale_all_dim: float) -> float:
    """`0.1 x mscale_all_dim x ln(factor) + 1` (1 without scaling)."""
    return 0.1 * mscale_all_dim * math.log(factor) + 1.0 if factor > 1 \
        else 1.0


def rotary_interleaved_fn(x, positions, inv_freq):
    """x [..., heads, d] float32, positions [...] (one a token): lanes (2i,
    2i + 1) of every head turn together by `position * inv_freq[i]`."""
    ang = positions.astype(_F32)[..., None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def join_latent_fn(c_kv, k_rope, dtype, words: int = 0):
    """c_kv [..., kv_rank], k_rope [..., rope_dim] -> the token's ONE row
    [..., words] of 32-bit words holding their `dtype` bits, the latent's
    words then the key's (`sparse_moe_ops.join_rows_fn`), then zeros up to
    `words` (the pool's row: `kv_cache.stacked_pool_shapes` pads a wide
    row to whole 128-lane tiles)."""
    rows = join_rows_fn(c_kv, k_rope, dtype)
    pad = max(int(words) - rows.shape[-1], 0)
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)]) if pad \
        else rows


def latent_words(kv_rank: int, rope_dim: int, dtype) -> tuple:
    """(words of the latent, words of the rotary key) in a cache row."""
    per = 4 // jnp.dtype(dtype).itemsize
    return kv_rank // per, rope_dim // per


def split_latent_fn(rows, dtype, kv_rank: int, rope_dim: int):
    """`join_latent_fn` read back: rows [..., words] int32 -> c_kv [...,
    kv_rank], k_rope [..., rope_dim] in `dtype`, the same bits (padding
    words past them are not read)."""
    side, key = latent_words(kv_rank, rope_dim, dtype)
    return (_word_values(rows[..., :side], dtype),
            _word_values(rows[..., side:side + key], dtype))


def _kv_b_heads(wkv_b, geom: Geometry):
    """W_kvb `[kv_rank, heads * (nope + v)]` as `[kv_rank, heads, nope +
    v]`: a head's W_uk and W_uv side by side."""
    return wkv_b.reshape(geom.kv_rank, geom.num_heads,
                         geom.nope_dim + geom.v_dim)


def expanded_attention_fn(q_nope, q_rope, c, r, mask, wkv_b,
                          geom: Geometry):
    """q_nope [B, S, nh, nope], q_rope [B, S, nh, rope] float32; the
    context's latents c [B, T, kv_rank] and rotary keys r [B, T, rope] as
    cached; mask [B, S, T] -> [B, S, nh, v] float32. Every head's keys and
    values are made from the latents, then the queries attend them block by
    block."""
    B, S = q_nope.shape[:2]
    dt, dn = c.dtype, geom.nope_dim
    scale = softmax_scale(geom)
    kv = jnp.einsum("btc,chd->bthd", c, _kv_b_heads(wkv_b, geom).astype(dt),
                    preferred_element_type=_F32).astype(dt)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    def block(args):
        qn, qr, mb = args
        s = jnp.einsum("bshd,bthd->bhst", qn.astype(dt), k_nope,
                       preferred_element_type=_F32) \
            + jnp.einsum("bshd,btd->bhst", qr.astype(dt), r,
                         preferred_element_type=_F32)
        s = jnp.where(mb[:, None], s * scale, _NEG_INF)
        probs = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhst,bthd->bshd", probs.astype(dt), v,
                          preferred_element_type=_F32)

    if S <= _QUERY_BLOCK or S % _QUERY_BLOCK:
        return block((q_nope, q_rope, mask))
    n = S // _QUERY_BLOCK
    split = lambda a: jnp.moveaxis(                          # noqa: E731
        a.reshape((B, n, _QUERY_BLOCK) + a.shape[2:]), 1, 0)
    out = jax.lax.map(block, (split(q_nope), split(q_rope), split(mask)))
    return jnp.moveaxis(out, 0, 1).reshape((B, S) + out.shape[3:])


def key_block(T: int) -> int:
    """Keys expanded at a time out of a context of T: T itself up to
    `_KEY_BLOCK`, else its largest divisor under it."""
    return next(b for b in range(min(T, _KEY_BLOCK), 0, -1) if T % b == 0)


def expanded_attention_blocks_fn(q_nope, q_rope, c, r, gpos, wkv_b,
                                 geom: Geometry):
    """`expanded_attention_fn` for queries that attend EVERY cached row at
    or before their own position (no selection), at any context: q_nope [B,
    S, nh, nope], q_rope [B, S, nh, rope] float32; c [B, T, kv_rank], r [B,
    T, rope] as cached, row t the token at position t; gpos [B, S] the
    queries' positions -> [B, S, nh, v] float32. The keys run in blocks of
    `key_block(T)`: a block's keys and values are made from its latents,
    scored by all the queries, and folded into a running softmax (maximum,
    sum, weighted values), so neither the expanded keys of the context nor
    the `[queries, context]` scores ever exist whole. Row 0 is live for
    every query, so the running maximum is a real score from the first
    block on and a masked score's exponential is 0."""
    B, S = q_nope.shape[:2]
    T = c.shape[1]
    dt, dn, nh = c.dtype, geom.nope_dim, geom.num_heads
    kb = key_block(T)
    scale = softmax_scale(geom)
    w = _kv_b_heads(wkv_b, geom).astype(dt)
    qn, qr = q_nope.astype(dt), q_rope.astype(dt)
    split = lambda a: jnp.moveaxis(                          # noqa: E731
        a.reshape((B, T // kb, kb) + a.shape[2:]), 1, 0)

    def block(carry, xs):
        m, l, acc = carry                    # [B, nh, S], same, [B, nh, S, v]
        cb, rb, t0 = xs
        kv = jnp.einsum("btc,chd->bthd", cb, w,
                        preferred_element_type=_F32).astype(dt)
        s = jnp.einsum("bshd,bthd->bhst", qn, kv[..., :dn],
                       preferred_element_type=_F32) \
            + jnp.einsum("bshd,btd->bhst", qr, rb,
                         preferred_element_type=_F32)
        live = (t0 + jnp.arange(kb, dtype=jnp.int32))[None, None, :] \
            <= gpos[:, :, None]                              # [B, S, kb]
        s = jnp.where(live[:, None], s * scale, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])       # 0 where it is masked
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhst,bthd->bhsd", p.astype(dt), kv[..., dn:],
            preferred_element_type=_F32)
        return (m_new, l * alpha + jnp.sum(p, axis=-1), acc), None

    init = (jnp.full((B, nh, S), _NEG_INF, _F32), jnp.zeros((B, nh, S), _F32),
            jnp.zeros((B, nh, S, geom.v_dim), _F32))
    (_, l, acc), _ = jax.lax.scan(
        block, init, (split(c), split(r),
                      jnp.arange(T // kb, dtype=jnp.int32) * kb))
    return jnp.moveaxis(acc / l[..., None], 1, 2)


def absorb_queries_fn(q_nope, wkv_b, geom: Geometry):
    """q_nope [R, nh, nope] float32 -> q_lat [R, nh, kv_rank] float32:
    `W_uk,h^T q_nope_h`, each head's query carried into the latent."""
    w_uk = _kv_b_heads(wkv_b, geom)[..., :geom.nope_dim]
    return jnp.einsum("rhd,chd->rhc", q_nope.astype(wkv_b.dtype), w_uk,
                      preferred_element_type=_F32)


def expand_values_fn(u, wkv_b, geom: Geometry):
    """u [R, nh, kv_rank] float32 -> [R, nh, v] float32: `W_uv,h u_h`."""
    w_uv = _kv_b_heads(wkv_b, geom)[..., geom.nope_dim:]
    return jnp.einsum("rhc,chd->rhd", u.astype(wkv_b.dtype), w_uv,
                      preferred_element_type=_F32)


def absorbed_attention_fn(q_lat, q_rope, rows, have, dtype,
                          geom: Geometry):
    """q_lat [R, nh, kv_rank], q_rope [R, nh, rope] float32; rows [R, K,
    words] cache rows holding `dtype` values; have [R, K] (which of them
    exist) -> u [R, nh, kv_rank] float32: softmax over the rows a query was
    given, `s_h = q_lat_h . c_kv + q_rope_h . k_rope`, and the probabilities'
    sum of the latents themselves."""
    c, r = split_latent_fn(rows, dtype, geom.kv_rank, geom.rope_dim)
    s = jnp.einsum("rhc,rkc->rhk", q_lat.astype(dtype), c,
                   preferred_element_type=_F32) \
        + jnp.einsum("rhd,rkd->rhk", q_rope.astype(dtype), r,
                     preferred_element_type=_F32)
    s = jnp.where(have[:, None, :], s * softmax_scale(geom), _NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("rhk,rkc->rhc", probs.astype(dtype), c,
                      preferred_element_type=_F32)


def latent_attend_runs(q_shape, rows_shape, dtype, rope_dim: int) -> bool:
    """Whether the absorbed attention of queries q_lat `q_shape` [R, nh,
    kv_rank] over cache rows `rows_shape` [R, K, words] of `dtype` values
    comes from `pallas_kernels.latent_attend`: its shape gate decides
    alone, where a Pallas kernel can run at all. The engine books
    `serving.latent.attend_kernel_layer_steps` by the same answer."""
    from .pallas_kernels import latent_attend, workbench

    return (workbench.runnable(latent_attend)
            and latent_attend.latent_attend_supported(
                tuple(q_shape), tuple(rows_shape), dtype, int(rope_dim)))


def paged_attend_runs(q_shape, pool_shape, dtype, rope_dim: int) -> bool:
    """Whether the absorbed attention of decode rows q_lat `q_shape` [B, nh,
    kv_rank] over ALL their pages, read in place from the latent pool
    `pool_shape` [rows, page_size, words] of `dtype` values, comes from
    `pallas_kernels.paged_latent_attend`: its shape gate decides alone,
    where a Pallas kernel can run at all. The engine books
    `serving.latent.attend_kernel_layer_steps` by the same answer."""
    from .pallas_kernels import paged_latent_attend, workbench

    return (workbench.runnable(paged_latent_attend)
            and paged_latent_attend.paged_latent_attend_supported(
                tuple(q_shape), tuple(pool_shape), dtype, int(rope_dim)))


def paged_attend_rows(q_shape, pool_shape, dtype, rope_dim: int) -> int:
    """Rows of a decode step of `q_shape` [B, nh, kv_rank] that ONE call of
    `pallas_kernels.paged_latent_attend` takes (its queries and running
    sums stay resident in VMEM: 64 rows of 32 heads): B where
    `paged_attend_runs` takes the step whole, else the largest half, quarter,
    .. of B it takes (the step's rows then go a group a call, a run of
    pages that rows share read once a GROUP), 0 where the kernel cannot run
    at all."""
    B = int(q_shape[0])
    rows = B
    while rows and not paged_attend_runs((rows,) + tuple(q_shape[1:]),
                                         pool_shape, dtype, rope_dim):
        rows = rows // 2 if rows % 2 == 0 else 0
    return rows


def gather_rows_fn(pool, page_table, sel):
    """The cache rows of positions sel [R, K] (-1: none) of rows whose
    pages page_table [R, P] names (already shifted to the layer's rows):
    [R, K, words], each token gathered ONCE from the pool seen as `[rows *
    page_size, words]`."""
    rows, ps, words = pool.shape
    P = page_table.shape[1]
    at = jnp.maximum(sel, 0)
    # the page of every selected position, as a masked sum over the table
    # (scalar gathers cost the chip more: sparse_moe_ops)
    ordinal = jnp.arange(P, dtype=jnp.int32)
    page = jnp.sum(jnp.where((at // ps)[..., None] == ordinal,
                             page_table[:, None, :], 0), axis=-1)
    flat = jnp.clip(page, 0, rows - 1) * ps + at % ps
    return pool.reshape(rows * ps, words)[flat]


def paged_scores_fn(qi, w, i_pool, table):
    """A window's indexer scores over its paged context: qi [B, S, J, D], w
    [B, S, J] float32, table [B, P] (shifted to the layer's rows) -> [B, S,
    P * page_size] float32. The pages' keys are gathered once for all the
    window's queries, and the queries scored in blocks, so that the
    `[queries, heads, context]` product of one block stays under
    `_INDEX_SCORES_AT_ONCE`. (A decode step reads each row's pages where
    they lie: `sparse_moe_ops.decode_scores_fn`.)"""
    B, S, J, _ = qi.shape
    P, ps = table.shape[1], i_pool.shape[2]
    keys = i_pool[jnp.clip(table, 0, i_pool.shape[0] - 1)]
    s = _block_of(S, B * J * P * ps)
    if s == S:
        return indexer_scores_fn(qi, w, keys)
    split = lambda a: jnp.moveaxis(                          # noqa: E731
        a.reshape((B, S // s, s) + a.shape[2:]), 1, 0)
    out = jax.lax.map(lambda a: indexer_scores_fn(a[0], a[1], keys),
                      (split(qi), split(w)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, P * ps)


# ---------------------------------------------------------------------------
# one layer
# ---------------------------------------------------------------------------


def _pre_attention(x, p, positions, geom: Geometry):
    """x [B, S, H] -> q_nope [B, S, nh, nope], q_rope [B, S, nh, rope], the
    cache row's two parts c_kv [B, S, kv_rank] and k_rope [B, S, rope], the
    indexer's qi [B, S, J, D], ki [B, S, D] and w [B, S, J], all float32."""
    B, S, _ = x.shape
    nh, dn, dr = geom.num_heads, geom.nope_dim, geom.rope_dim
    J, D = geom.index_heads, geom.index_dim
    inv_freq = yarn_inv_freq_fn(dr, geom.rope_theta, tuple(geom.yarn))
    z = rms_norm_fn(x, p["attn_norm"], geom.eps)
    c_q = rms_norm_fn(_mm(z, p["wq_a"]), p["q_norm"], geom.eps)
    q = _mm(c_q, p["wq_b"]).reshape(B, S, nh, dn + dr)
    q_rope = rotary_interleaved_fn(q[..., dn:], positions, inv_freq)
    c_kv, k_rope = cache_row_parts_fn(z, p, positions, inv_freq, geom)
    if not geom.index_topk:         # no indexer: every cached row is read
        return q[..., :dn], q_rope, c_kv, k_rope, None, None, None
    qi = rotary_fn(_mm(c_q, p["wqi"]).reshape(B, S, J, D), positions,
                   inv_freq, dr)
    ki = layer_norm_fn(_mm(z, p["wki"]), p["ki_norm_w"], p["ki_norm_b"],
                       geom.eps)
    ki = rotary_fn(ki[:, :, None, :], positions, inv_freq, dr)[:, :, 0]
    w = _mm(z, p["ww"]) * (J ** -0.5 * D ** -0.5)
    return q[..., :dn], q_rope, c_kv, k_rope, qi, ki, w


def _attend_rows(q_nope, q_rope, rows, have, wkv_b, dtype, geom: Geometry):
    """The absorbed form for R queries, each over the cache rows it was
    given (rows [R, K, words], have [R, K]) -> [R, nh, v] float32: one
    Pallas kernel over the rows as they lie where `latent_attend_runs`
    takes the shapes, `absorbed_attention_fn` elsewhere."""
    with piece("q_absorb"):
        q_lat = absorb_queries_fn(q_nope, wkv_b, geom)
    with piece("attend"):
        if latent_attend_runs(q_lat.shape, rows.shape, dtype,
                              geom.rope_dim):
            from .pallas_kernels import latent_attend

            u = latent_attend.latent_rows_attention(q_lat, q_rope, rows,
                                                    have, dtype, geom)
        else:
            u = absorbed_attention_fn(q_lat, q_rope, rows, have, dtype, geom)
    with piece("q_absorb"):
        return expand_values_fn(u, wkv_b, geom)


def _attend_selected(q_nope, q_rope, pool, table, sel, wkv_b, dtype,
                     geom: Geometry):
    """`_attend_rows` over each query's own positions sel [R, K] (-1: none)
    of the pages table [R, P], gathered first."""
    with piece("latent_gather"):
        rows = gather_rows_fn(pool, table, sel)
    return _attend_rows(q_nope, q_rope, rows, sel >= 0, wkv_b, dtype, geom)


def _attend_pages(q_nope, q_rope, pool, table, lens, wkv_b, dtype,
                  geom: Geometry, plan=None):
    """The absorbed form for B decode rows, each over ALL the pages of its
    table [B, P] (shifted to the layer's rows; lens [B] live positions, 0: a
    padding row), read where they lie in the pool, a run of pages that rows
    share once for all of them -> [B, nh, v] float32:
    `pallas_kernels.paged_latent_attend` (callers gate on
    `paged_attend_rows`; `plan`: `decode_plan_fn`'s of the step's tables,
    the same for every layer: one `step_plan` a group of rows, a call each;
    None: one call that plans for itself)."""
    from .pallas_kernels import paged_latent_attend

    plans = plan or (None,)
    rows = q_nope.shape[0] // len(plans)
    with piece("q_absorb"):
        q_lat = absorb_queries_fn(q_nope, wkv_b, geom)
    with piece("attend"):
        if len(plans) == 1:
            u = paged_latent_attend.paged_latent_attention(
                q_lat, q_rope, pool, table, lens, dtype, geom, plans[0])
        else:
            u = jnp.concatenate([
                paged_latent_attend.paged_latent_attention(
                    q_lat[g:g + rows], q_rope[g:g + rows], pool,
                    table[g:g + rows], lens[g:g + rows], dtype, geom, part)
                for g, part in zip(range(0, q_nope.shape[0], rows), plans)])
    with piece("q_absorb"):
        return expand_values_fn(u, wkv_b, geom)


def cache_row_parts_fn(z, p, positions, inv_freq, geom: Geometry):
    """A token's cache row before it is joined: z [B, S, H] float32 (normed)
    -> its normalised latent c_kv [B, S, kv_rank] and its one rotary key
    k_rope [B, S, rope] (interleaved lane pairs), float32."""
    kv = _mm(z, p["wkv_a"])
    c_kv = rms_norm_fn(kv[..., :geom.kv_rank], p["kv_norm"], geom.eps)
    k_rope = rotary_interleaved_fn(kv[:, :, None, geom.kv_rank:], positions,
                                   inv_freq)[:, :, 0]
    return c_kv, k_rope


def direct_queries_fn(z, p, positions, geom: Geometry):
    """The latent attention's projections WITHOUT a query latent
    (`q_lora_rank` null: the queries come off the hidden state through ONE
    matrix): z [B, S, H] float32 (already normed), p holding `wq` [H, nh *
    (nope + rope)], `wkv_a` [H, kv_rank + rope] and `kv_norm` -> q_nope [B,
    S, nh, nope], q_rope [B, S, nh, rope], and the cache row's two parts
    c_kv [B, S, kv_rank] (normalised) and k_rope [B, S, rope], all float32;
    rotary on interleaved lane pairs."""
    B, S, _ = z.shape
    nh, dn, dr = geom.num_heads, geom.nope_dim, geom.rope_dim
    inv_freq = yarn_inv_freq_fn(dr, geom.rope_theta, tuple(geom.yarn))
    q = _mm(z, p["wq"]).reshape(B, S, nh, dn + dr)
    q_rope = rotary_interleaved_fn(q[..., dn:], positions, inv_freq)
    return (q[..., :dn], q_rope,
            *cache_row_parts_fn(z, p, positions, inv_freq, geom))


def decode_plan_fn(page_table, lens, q_shape, pool_shape, dtype,
                   rope_dim: int):
    """`paged_latent_attend.step_plan` of a decode step's tables [B, P] and
    live lengths [B], one a group of `paged_attend_rows` rows (a tuple;
    None where the kernel cannot run): which rows read the same pages is
    the tables' alone, so a stack works it out once for all its latent
    layers."""
    rows = paged_attend_rows(q_shape, pool_shape, dtype, rope_dim)
    if not rows:
        return None
    from .pallas_kernels import paged_latent_attend

    with piece("attend"):
        if rows == q_shape[0]:
            return (paged_latent_attend.step_plan(page_table, lens,
                                                  pool_shape),)
        return tuple(paged_latent_attend.step_plan(
            page_table[g:g + rows], lens[g:g + rows], pool_shape)
            for g in range(0, q_shape[0], rows))


def unindexed_attention_fn(q_nope, q_rope, c_kv, k_rope, wkv_b,
                           geom: Geometry, dtype, latent_pool=None,
                           page_table=None, offset: int = 0, gpos=None,
                           valid=None, decode_lens=None, plan=None):
    """One layer's latent attention WITHOUT an indexer (`latent_moe_stack`
    without one, the latent layers of `kda_moe_stack`): every query attends
    every cached row at or before its own position gpos [B, S]. Without a
    pool (the dense oracle) the sequence itself is the context. With
    `latent_pool` the step's rows are written first (page_table [B, P]
    unshifted, `offset` the layer's first row of the pool, valid [B, S]);
    then a window (`decode_lens`
    None) attends its table's slabs in the expanded form over key blocks,
    and a decode row (S = 1, `decode_lens` [B] its live length, 0: padding)
    in the absorbed form, its pages read in place under `plan`
    (`decode_plan_fn`) or, where that is None, gathered. -> (the pool as
    written or None, o [B, S, nh, v] float32)."""
    B = q_nope.shape[0]
    if latent_pool is None:
        with piece("attend"):
            return None, expanded_attention_blocks_fn(
                q_nope, q_rope, c_kv.astype(dtype), k_rope.astype(dtype),
                gpos, wkv_b, geom)
    page_size = latent_pool.shape[1]
    table = page_table + offset
    with piece("kv_write"):
        idx = _page_row_index(page_table, gpos, page_size, offset, valid)
        latent_pool = _write_rows(
            latent_pool, join_latent_fn(c_kv, k_rope, dtype,
                                        latent_pool.shape[-1]), idx,
            gpos % page_size)
    if plan is not None:
        return latent_pool, _attend_pages(
            q_nope[:, 0], q_rope[:, 0], latent_pool, table, decode_lens,
            wkv_b, dtype, geom, plan)[:, None]
    context = page_table.shape[1] * page_size
    with piece("latent_gather"):
        slabs = latent_pool[jnp.clip(
            table, 0, latent_pool.shape[0] - 1)].reshape(B, context, -1)
    if decode_lens is not None:
        live = jnp.arange(context, dtype=jnp.int32)[None, :] <= gpos[:, :1]
        return latent_pool, _attend_rows(
            q_nope[:, 0], q_rope[:, 0], slabs, live, wkv_b, dtype,
            geom)[:, None]
    with piece("attend"):
        c, r = split_latent_fn(slabs, dtype, geom.kv_rank, geom.rope_dim)
        return latent_pool, expanded_attention_blocks_fn(
            q_nope, q_rope, c, r, gpos, wkv_b, geom)


def _feed_forward(h, kind_dense: bool, p, experts, index, geom: Geometry,
                  tag: str, add: bool = True):
    """h [B, S, H] -> (y [B, S, H], ids [B, S, k] or None): `h + F(h)`, or
    `F(h)` alone without `add` (a residual path of several streams mixes it
    in itself)."""
    B, S, H = h.shape
    with piece("proj"):
        z = rms_norm_fn(h, p["ffn_norm"], geom.eps).reshape(B * S, H)
    if kind_dense:
        with piece("dense_ffn"):
            y = swiglu_fn(z, p["w_gate"], p["w_up"],
                          p["w_down"]).reshape(B, S, H)
            return (h + y if add else y), None
    with piece("router"):
        ids, cw = group_limited_router_fn(
            z, p["router_w"], p["router_bias"], geom.experts_per_token,
            geom.expert_groups, geom.groups_per_token, geom.routed_scaling)
        held = cw[:, :geom.experts_held]    # this chip's experts' columns
    with piece("experts"):
        y = moe_topk_experts_fn(z, held, *experts, layer=index, tag=tag,
                                k=geom.experts_per_token)
    with piece("shared"):
        y = (y + swiglu_fn(z, p["shared_gate"], p["shared_up"],
                           p["shared_down"])).reshape(B, S, H)
        return (h + y if add else y), ids.reshape(B, S, -1)


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


@under_mode
def latent_moe_stack_fn(mode: str, tok, pos, emb, head, final_norm,
                        dense: dict, moe: dict, experts: tuple,
                        geom: Geometry, pools=None, page_table=None,
                        lens=None, start=None, mask=None, mark=None,
                        num_pages: int = 0):
    """Run the decoder. `mode`:

      full     tok/pos [B, S]                          -> logits [B, S, V]
      window   + page_table, start, lens (context in
               the pools; `prefill` is start 0)        -> last logits [B, V]
      decode   tok/pos [B], page_table, mask [B],
               mark [M] (rows whose selection is kept) -> logits [B, V]

    `dense` and `moe` hold `attention_params(...)` and their kind's own,
    each stacked over the layers of that kind (the dense layers lead);
    `experts` the held experts `[L_moe, held, ...]`. Returns a dict: logits;
    routes ([B, S, L_moe, k], decode [B, L_moe, k]: ids among ALL experts);
    with an indexer (`geom.index_topk`) selection, what each layer's
    attention was given, as `sparse_moe_stack` hands it back: a window's (or
    `full`'s) mask in `pack_selection_fn` words [B, S, L, G, page_size], the
    positions the marked rows of a decode step attended [M, L, kk]; and,
    with `pools` (the latent rows, then the indexer keys where there is an
    indexer), latent_pool (and i_pool) as written. With `geom.hc_mult`
    residual streams the layers hand on `[n, B, S, H]` and every sub-layer
    sits between `hyper_connection_ops`' mixes (`_mixed_input`). Traced
    under its mode's scope, each piece (observability/schema.PIECES) under
    its own."""
    decode = mode == "decode"
    paged = mode != "full"
    if decode:
        tok, pos = jnp.reshape(tok, (-1, 1)), jnp.reshape(pos, (-1, 1))
    with piece("embed"):
        x = emb[tok].astype(_F32)
    B, S, _ = x.shape
    Ld, Le = dense["attn_norm"].shape[0], moe["attn_norm"].shape[0]
    nh, topk = geom.num_heads, int(geom.index_topk)
    indexed, streams = topk > 0, int(geom.hc_mult)
    tag = "decode" if decode else "prefill"
    dtype = emb.dtype                       # the cache rows' dtype
    rel = jnp.arange(S, dtype=jnp.int32)[None, :]
    if paged:
        page_size = pools[0].shape[1]
        page_table = page_table.astype(jnp.int32)
        P = page_table.shape[1]
        context = P * page_size
        first = (pos[:, 0] if decode
                 else (start if start is not None
                       else jnp.zeros((B,), jnp.int32))).astype(jnp.int32)
        gpos = first[:, None] + rel                             # [B, S]
        valid = (jnp.reshape(mask, (-1, 1)) > 0) if decode \
            else rel < lens[:, None]
        count = valid[:, 0].astype(jnp.int32) if decode else lens
        # a step whose whole table fits the selection scores nothing and
        # attends every live position; so does every step of a
        # configuration without an indexer
        whole = not indexed or context <= topk
    else:
        gpos = jnp.broadcast_to(rel, (B, S))
        context = S
    # a decode row without an indexer reads every page of its table where it
    # lies; which rows read the same pages is the tables' alone, worked out
    # once for all layers
    # (a group of rows a call where they are more than one call keeps
    # resident: `paged_attend_rows`)
    plan = decode_plan_fn(
        page_table, (first + 1) * count, (B, nh, geom.kv_rank),
        pools[0].shape, dtype, geom.rope_dim) \
        if decode and not indexed else None
    if streams > 1:
        with piece("embed"):
            x = hc.spread_fn(x, streams)                        # [n, B, S, H]

    def layer(x, latent_pool, i_pool, l, p, kind_dense, ffn_index):
        if streams > 1:     # the attention reads a mix of the streams
            x, mix_attention = _mixed_input(x, p, 0, geom)
        with piece("proj"):
            q_nope, q_rope, c_kv, k_rope, qi, ki, w = _pre_attention(
                x, p, pos, geom)
        off = l * num_pages
        sel = None
        if not indexed:     # every cached row is read (written first)
            latent_pool, o = unindexed_attention_fn(
                q_nope, q_rope, c_kv, k_rope, p["wkv_b"], geom, dtype,
                latent_pool, page_table if paged else None, off, gpos,
                valid if paged else None,
                (first + 1) * count if decode else None, plan)
        else:
            if paged:
                table = page_table + off
                with piece("kv_write"):
                    idx = _page_row_index(page_table, gpos, page_size, off,
                                          valid)
                    latent_pool = _write_rows(
                        latent_pool, join_latent_fn(
                            c_kv, k_rope, dtype, latent_pool.shape[-1]), idx,
                        gpos % page_size)
                    i_pool = write_index_keys_fn(i_pool, ki, page_table,
                                                 off, first, count)
            if paged and whole:
                at = jnp.arange(context, dtype=jnp.int32)
                live = at[None, None, :] <= gpos[:, :, None]    # [B, S, T]
                with piece("latent_gather"):
                    slabs = latent_pool[jnp.clip(
                        table, 0, latent_pool.shape[0] - 1)].reshape(
                            B, context, -1)
                if decode:
                    o = _attend_rows(q_nope[:, 0], q_rope[:, 0], slabs,
                                     live[:, 0], p["wkv_b"], dtype,
                                     geom)[:, None]
                    sel = jnp.where(live, at, -1)               # [B, 1, T]
                else:
                    with piece("attend"):
                        c, r = split_latent_fn(slabs, dtype, geom.kv_rank,
                                               geom.rope_dim)
                        o = expanded_attention_fn(
                            q_nope, q_rope, c, r, live, p["wkv_b"], geom)
                    with piece("select"):
                        sel = pack_selection_fn(live, page_size)
            else:
                with piece("indexer"):
                    if decode:      # each row's pages, where they lie
                        scores = decode_scores_fn(qi, w, i_pool, table,
                                                  (first + 1) * count)
                    elif paged:
                        scores = paged_scores_fn(qi, w, i_pool, table)
                    else:           # the sequence as one page
                        scores = indexer_scores_fn(
                            qi, w,
                            jnp.swapaxes(ki.astype(dtype), 1, 2)[:, None])
                if decode:
                    with piece("select"):
                        sel = select_indices_fn(scores, gpos + 1, topk)
                    o = _attend_selected(q_nope[:, 0], q_rope[:, 0],
                                         latent_pool, table, sel[:, 0],
                                         p["wkv_b"], dtype, geom)[:, None]
                else:
                    with piece("select"):
                        keep = select_mask_fn(scores, gpos + 1, topk)
                    if paged:
                        o = _window_rows(q_nope, q_rope, latent_pool, table,
                                         keep, p["wkv_b"], dtype, geom)
                    else:
                        with piece("attend"):
                            o = expanded_attention_fn(
                                q_nope, q_rope, c_kv.astype(dtype),
                                k_rope.astype(dtype), keep, p["wkv_b"], geom)
                    # handed back as attended under: the mask itself
                    with piece("select"):
                        sel = pack_selection_fn(keep,
                                                page_size if paged else S)
        if streams > 1:
            with piece("proj"):
                f = _mm(o.reshape(B, S, -1), p["wo"])
            h, mix_ffn = _mixed_input(mix_attention(f), p, 1, geom)
            f, ids = _feed_forward(h, kind_dense, p, experts, ffn_index,
                                   geom, tag, add=False)
            y = mix_ffn(f)
        else:
            with piece("proj"):
                h = x + _mm(o.reshape(B, S, -1), p["wo"])
            y, ids = _feed_forward(h, kind_dense, p, experts, ffn_index,
                                   geom, tag)
        if decode and indexed:
            sel = sel[:, 0][mark]                               # [M, kk]
        return y, latent_pool, i_pool, ids, sel

    # what the layers hand on: the residual (one stream or several), then
    # the pools there are
    pools = tuple(pools) if paged else ()
    held = lambda carry: (carry + (None, None))[1:3]         # noqa: E731
    carry = (x,) + pools
    selections = []
    for l in range(Ld):
        y, latent_pool, i_pool, _, sel = layer(
            carry[0], *held(carry), l, {k: v[l] for k, v in dense.items()},
            True, l)
        carry = (y, latent_pool, i_pool)[:len(carry)]
        selections.append(sel)

    def routed(carry, xs):
        i, p = xs
        y, latent_pool, i_pool, ids, sel = layer(
            carry[0], *held(carry), Ld + i, p, False, i)
        return (y, latent_pool, i_pool)[:len(carry)], (ids, sel)

    carry, (routes, sel) = jax.lax.scan(
        routed, carry, (jnp.arange(Le, dtype=jnp.int32), moe))
    if indexed:
        selection = jnp.concatenate([jnp.stack(selections), sel])
    with piece("head"):
        xn = rms_norm_fn(hc.readout_fn(carry[0]) if streams > 1
                         else carry[0], final_norm, geom.eps)
        if mode == "window":
            at = jnp.clip(lens - 1, 0, S - 1)[:, None, None]
            xn = jnp.take_along_axis(xn, at, axis=1)
        logits = jnp.einsum("bsh,hv->bsv", xn.astype(head.dtype), head,
                            preferred_element_type=_F32)
    routes = jnp.moveaxis(routes, 0, -2)                  # [B, S, L_moe, k]
    if indexed:     # decode [M, L, kk], a window [B, S, L, G, ps]
        selection = jnp.moveaxis(selection, 0, 1 if decode else 2)
    out = {"logits": logits if mode == "full" else logits[:, 0],
           "routes": routes[:, 0] if decode else routes}
    if indexed:
        out["selection"] = selection
    if paged:
        out.update(zip(("latent_pool", "i_pool"), carry[1:]))
    return out


def _mixed_input(xs, p, which: int, geom: Geometry):
    """The residual streams xs [n, B, S, H] before sub-layer `which` (0: the
    attention, 1: the feed-forward) of a layer whose parameters are `p` ->
    (the sub-layer's input [B, S, H], `mix(f)`: the streams after it, given
    its output f [B, S, H]): `hyper_connection_ops`' mappings from the
    streams themselves, the pre-mix, and the residual and post mix."""
    n, B, S, H = xs.shape
    flat = xs.reshape(n, B * S, H)
    with piece("hc_map"):
        pre, post, res = hc.mappings_fn(
            flat, p["hc_w"][which], p["hc_a"][which], p["hc_b"][which],
            geom.hc_iters, geom.hc_eps, tuple(geom.hc_clamp))
    with piece("hc_mix"):
        u = hc.pre_mix_fn(flat, pre).reshape(B, S, H)

    def mix(f):
        with piece("hc_mix"):
            return hc.post_mix_fn(flat, res, post,
                                  f.reshape(B * S, H)).reshape(xs.shape)

    return u, mix


def _window_rows(q_nope, q_rope, pool, table, keep, wkv_b, dtype,
                 geom: Geometry):
    """A window behind a context longer than the selection: q_nope [B, S,
    nh, nope], q_rope [B, S, nh, rope], table [B, P], keep [B, S, T] (each
    query's selection) -> [B, S, nh, v] float32. Every query reads its OWN
    selected rows in the absorbed form, `_QUERY_BLOCK` queries at a time:
    a block names its positions (`_mask_positions`), gathers them and
    attends, so what is gathered at once is a block's."""
    B, S, T = keep.shape
    R = B * S
    kk = min(int(geom.index_topk), T)
    flat = lambda a: a.reshape((R,) + a.shape[2:])           # noqa: E731
    tables = jnp.repeat(table, S, axis=0)                    # [R, P]

    def block(args):
        qn, qr, tb, kp = args
        with piece("select"):
            sel = _mask_positions(kp, kk)
        return _attend_selected(qn, qr, pool, tb, sel, wkv_b, dtype, geom)

    args = (flat(q_nope), flat(q_rope), tables, flat(keep))
    b = _QUERY_BLOCK
    if R <= b or R % b:
        out = block(args)
    else:
        out = jax.lax.map(block, tuple(
            a.reshape((R // b, b) + a.shape[1:]) for a in args))
    return out.reshape((B, S) + out.shape[-2:])


# ---------------------------------------------------------------------------
# registered op
# ---------------------------------------------------------------------------


@register_op("latent_moe_stack", grad="none")
def latent_moe_stack_op(ctx: ExecContext):
    """The whole decoder in one op; see `latent_moe_stack_fn`. inputs: Tok,
    Pos, Emb, Head, FinalNorm, DenseParams (`attention_params(...)` then
    `DENSE_PARAMS`), MoeParams (`attention_params(...)` then `MOE_PARAMS`),
    Experts (`EXPERT_PARAMS`), and by mode PageTable, Lens, Start, Mask,
    Mark (decode, with an indexer), LatentPool (and IPool with an indexer).
    attrs: mode and the geometry. Outputs: NextToken (greedy), Logits,
    Routes, Selection (with an indexer), and the pools under their own
    names."""
    mode = ctx.attr("mode")
    geom = Geometry(**{f: v for f in Geometry._fields
                       if (v := ctx.attr(f)) is not None})
    paged = mode != "full"
    indexed = int(geom.index_topk) > 0
    shared = attention_params(indexed, int(geom.hc_mult))

    def opt(slot):
        return ctx.input(slot).astype(jnp.int32) if ctx.has_input(slot) \
            else None

    out = latent_moe_stack_fn(
        "window" if mode == "prefill" else mode,
        ctx.input("Tok").astype(jnp.int32),
        ctx.input("Pos").astype(jnp.int32), ctx.input("Emb"),
        ctx.input("Head"), ctx.input("FinalNorm"),
        dict(zip(shared + DENSE_PARAMS, ctx.inputs("DenseParams"))),
        dict(zip(shared + MOE_PARAMS, ctx.inputs("MoeParams"))),
        tuple(ctx.inputs("Experts")), geom,
        pools=(ctx.input("LatentPool"),) + (
            (ctx.input("IPool"),) if indexed else ()) if paged else None,
        page_table=opt("PageTable"), lens=opt("Lens"), start=opt("Start"),
        mask=ctx.input("Mask") if ctx.has_input("Mask") else None,
        mark=opt("Mark"), num_pages=int(ctx.attr("num_pages", 0)))
    res = {"Logits": out["logits"], "Routes": out["routes"]}
    if indexed:
        res["Selection"] = out["selection"]
    res["NextToken"] = greedy_fn(out["logits"])
    if paged:
        res["LatentPoolOut"] = out["latent_pool"]
        if indexed:
            res["IPoolOut"] = out["i_pool"]
    return res
