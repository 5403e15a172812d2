"""The mechanisms of a decoder whose layers are NOT one shape, and the op
that runs a stack of them (the "hybrid_moe" block of serving/model.py):
full-attention layers and sliding-window layers of different query-head
counts over the same KV heads, a sigmoid gate a head on the attention's
output, one dense SwiGLU layer in front of layers that route each token to
the top-k of a sigmoid-scored mixture of experts and add one shared expert.

Each mechanism is a plain jax function (`<name>_fn`); the one registered op
that runs them is `hybrid_moe_stack`:

  * `yarn_inv_freq`      — YaRN's inverse frequencies: below the correction
                           dimension of `beta_fast` a lane keeps its own
                           frequency, above that of `beta_slow` it is
                           interpolated (divided by `factor`), a linear
                           ramp between;
  * `rotary`             — rotate-half rotary on the first `rotary_dim`
                           lanes of every head from given inverse
                           frequencies, cos and sin times a factor;
  * `band_attention`     — a window of queries over keys that each query
                           sees only `window` positions back: query block
                           by query block, each against the slice of keys
                           its band covers and nothing else (never a
                           `[queries, context]` product);
  * `causal_attention`   — the same queries over everything before them,
                           query block by query block;
  * `sigmoid_router`     — `s = sigmoid(z W_r)`, the k largest of `s + b`
                           (ties to the lower expert), weights
                           `scaling * s_e / sum_chosen s`: the bias selects
                           and never weighs;
  * `swiglu`             — `W_d(silu(W_g z) * (W_u z))`, the dense layer
                           and the shared expert.

`hybrid_moe_stack` composes them into the decoder (embedding, the layers of
a PLAN, final norm, untied head) in the shapes serving needs: dense oracle
(`full`), a window over the paged pools (`window`; `prefill` is the same at
start 0) and the ragged decode step. The plan is a tuple of `(attention
kind, index among the layers of that kind, feed-forward kind, index among
those)`; weights are stacked by KIND (`full.*` `[L_full, ...]`, `slide.*`
`[L_slide, ...]`, `dense.*`, the experts `[L_moe, E, ...]`) and the layers
run one after another, each taking its slice.

Two pools, because the two kinds of layer need different things kept: a
full layer's K/V live as long as the row (`kv_cache.k/v`, `[L_full * pages,
page_size, nkv*dh]`, the row's page table as every family has it); a sliding
layer reads only the last `window` positions, so its K/V live in a second
stacked pool (`kv_cache.wk/wv`, `[L_slide * window_pages, ...]`) under a
COMPACT table: entry j is the page of logical page `base / page_size + j`,
`base` (a multiple of the page size) the position of the table's slot 0.
A position p lies at local slot `p - base`.

The experts run through `pallas_kernels.moe_experts` in its combine-weight
form, as in `sparse_moe_ops`.

Precision: matmul operands in the weights' dtype (bfloat16 as served),
float32 accumulation; residual stream, norms, router (scores, bias,
selection, weights), gate logits, rotary and softmax in float32.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from .attention_ops import (_gather_pages, _write_rows, kv_cache_append_fn,
                            paged_decode_attention_fn, paged_decode_plan_fn)
from .decoder_common import (_attend, _by_query_block, _mm, _page_row_index,
                             causal_attention_fn, greedy_fn,
                             moe_topk_experts_fn, rms_norm_fn, rotary_fn,
                             sigmoid_router_fn, swiglu_fn, yarn_inv_freq_fn)
from ..observability.schema import piece, under_mode
from .registry import ExecContext, register_op

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

Geometry = collections.namedtuple(
    "Geometry", "full_heads slide_heads num_kv_heads head_dim window eps "
                "full_rotary_dim full_theta yarn slide_rotary_dim "
                "slide_theta experts_per_token routed_scaling")

# the stacked parameters, in the order the stack op takes them
LAYER_PARAMS = ("attn_norm", "ffn_norm")                     # [L, H]
ATTENTION_PARAMS = ("wq", "wk", "wv", "wg", "wo")            # a kind each
DENSE_PARAMS = ("w_gate", "w_up", "w_down")
MOE_PARAMS = ("router_w", "router_bias", "shared_gate", "shared_up",
              "shared_down")
EXPERT_PARAMS = ("w_gate", "w_up", "w_down")

FULL, SLIDE, DENSE, MOE = "full", "slide", "dense", "moe"

# queries attended together (`decoder_common._QUERY_BLOCK` for a full
# layer): a sliding layer's band is `[heads, block, block + window]`
_BAND_BLOCK = 128


# ---------------------------------------------------------------------------
# the mechanisms
# ---------------------------------------------------------------------------


def band_attention_fn(q, k, v, q_pos0, k_pos0, window: int, sm_scale: float):
    """q [B, S, nh, dh] at positions `q_pos0[b] + s` over k/v [B, T, nkv,
    dh] at positions `k_pos0[b] + t`: a query at p sees the keys at
    `p - window + 1 .. p`. A block of queries is multiplied with the
    `block + window - 1` keys its band covers, sliced out of k/v, and with
    nothing else -> [B, S, nh, dh] float32."""
    B, S, nh, dh = q.shape
    T, nkv = k.shape[1], k.shape[2]
    block = S if S <= _BAND_BLOCK or S % _BAND_BLOCK else _BAND_BLOCK
    span = min(T, block + window - 1)

    def one(j, qb):
        first = q_pos0 + j * block                               # [B]
        lo = jnp.clip(first - (window - 1) - k_pos0, 0, T - span)
        cut = jax.vmap(lambda a, at: jax.lax.dynamic_slice_in_dim(
            a, at, span, axis=0))
        qp = first[:, None] + jnp.arange(block, dtype=jnp.int32)
        kp = (k_pos0 + lo)[:, None] + jnp.arange(span, dtype=jnp.int32)
        seen = (kp[:, None, :] <= qp[:, :, None]) \
            & (qp[:, :, None] - kp[:, None, :] < window)
        return _attend(qb, cut(k, lo), cut(v, lo), seen, sm_scale)

    qg = q.reshape(B, S, nkv, nh // nkv, dh).astype(k.dtype)
    return _by_query_block(one, qg, block).reshape(B, S, nh, dh)


# ---------------------------------------------------------------------------
# one layer, in two halves around the attention
# ---------------------------------------------------------------------------


def _rotary_of(kind: str, geom: Geometry):
    """(inverse frequencies, rotary lanes, factor on cos and sin)."""
    if kind == FULL:
        yarn = tuple(geom.yarn)
        return (yarn_inv_freq_fn(geom.full_rotary_dim, geom.full_theta, yarn),
                geom.full_rotary_dim, float(yarn[4]) if yarn else 1.0)
    return (yarn_inv_freq_fn(geom.slide_rotary_dim, geom.slide_theta),
            geom.slide_rotary_dim, 1.0)


def _pre_attention(x, norm, p, positions, kind: str, geom: Geometry):
    """x [B, S, H] -> q [B, S, nh, dh], k, v [B, S, nkv, dh], gate [B, S,
    nh] (float32; nh the kind's)."""
    B, S, _ = x.shape
    nkv, dh = geom.num_kv_heads, geom.head_dim
    nh = geom.full_heads if kind == FULL else geom.slide_heads
    inv_freq, rot, factor = _rotary_of(kind, geom)
    z = rms_norm_fn(x, norm, geom.eps)
    q = rotary_fn(_mm(z, p["wq"]).reshape(B, S, nh, dh), positions,
                  inv_freq, rot, factor)
    k = rotary_fn(_mm(z, p["wk"]).reshape(B, S, nkv, dh), positions,
                  inv_freq, rot, factor)
    v = _mm(z, p["wv"]).reshape(B, S, nkv, dh)
    gate = jax.nn.sigmoid(jnp.dot(z, p["wg"], precision=_HI))
    return q, k, v, gate


def _feed_forward(h, norm, kind: str, p, experts, index, geom: Geometry,
                  tag: str):
    """h [B, S, H] -> (y [B, S, H], ids [B, S, k] or None)."""
    B, S, H = h.shape
    with piece("proj"):
        z = rms_norm_fn(h, norm, geom.eps).reshape(B * S, H)
    if kind == DENSE:
        with piece("dense_ffn"):
            return h + swiglu_fn(z, p["w_gate"], p["w_up"],
                                 p["w_down"]).reshape(B, S, H), None
    with piece("router"):
        ids, cw = sigmoid_router_fn(z, p["router_w"], p["router_bias"],
                                    geom.experts_per_token,
                                    geom.routed_scaling)
    with piece("experts"):
        y = moe_topk_experts_fn(z, cw, *experts, layer=index, tag=tag,
                                k=geom.experts_per_token)
    with piece("dense_ffn"):
        y = y + swiglu_fn(z, p["shared_gate"], p["shared_up"],
                          p["shared_down"])
        return h + y.reshape(B, S, H), ids.reshape(B, S, -1)


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


@under_mode
def hybrid_moe_stack_fn(mode: str, tok, pos, emb, head, final_norm,
                        layer_params: dict, attention: dict, dense: dict,
                        moe: dict, experts: tuple, plan: tuple,
                        geom: Geometry, pools=None, page_table=None,
                        window_table=None, window_base=None, lens=None,
                        start=None, mask=None, num_pages: int = 0,
                        window_pages: int = 0):
    """Run the decoder. `mode`:

      full     tok/pos [B, S]                          -> logits [B, S, V]
      window   + page_table, window_table, window_base,
               start, lens (context in the pools;
               `prefill` is start 0)                   -> last logits [B, V]
      decode   tok/pos [B], page_table, window_table,
               window_base, mask [B]                   -> logits [B, V]

    `attention` holds the weights of a kind under `FULL` and `SLIDE`, each
    a dict of `ATTENTION_PARAMS` stacked over the layers of that kind;
    `dense`, `moe` and `experts` alike over theirs. Returns a dict: logits;
    routes ([B, S, L_moe, k], decode [B, L_moe, k]); with `pools` (K, V of
    the full layers, K, V of the sliding ones) the four as written. Traced
    under its mode's scope, each piece (observability/schema.PIECES) under
    its own."""
    decode = mode == "decode"
    paged = mode != "full"
    if decode:
        tok, pos = jnp.reshape(tok, (-1, 1)), jnp.reshape(pos, (-1, 1))
    with piece("embed"):
        x = emb[tok].astype(_F32)
    B, S, _ = x.shape
    sm_scale = geom.head_dim ** -0.5
    tag = "decode" if decode else "prefill"
    rel = jnp.arange(S, dtype=jnp.int32)[None, :]
    W, nkv = int(geom.window), geom.num_kv_heads
    if paged:
        k_pool, v_pool, wk_pool, wv_pool = pools
        page_size = k_pool.shape[1]
        page_table = page_table.astype(jnp.int32)
        window_table = window_table.astype(jnp.int32)
        base = window_base.astype(jnp.int32)                      # [B]
        first = (pos[:, 0] if decode
                 else (start if start is not None
                       else jnp.zeros((B,), jnp.int32))).astype(jnp.int32)
        gpos = first[:, None] + rel                               # [B, S]
        valid = (jnp.reshape(mask, (-1, 1)) > 0) if decode \
            else rel < lens[:, None]
        local = gpos - base[:, None]          # slots of the compact table
    # which rows' tables begin with the same pages is the tables' alone:
    # the full layers' decode calls share one plan, worked out before them
    walk = paged_decode_plan_fn(
        (B, geom.full_heads, geom.head_dim), _F32, pools[0], page_table,
        first + 1) if decode else None
    routes = []
    for l, (a_kind, a_i, f_kind, f_i) in enumerate(plan):
        p = {k: w[a_i] for k, w in attention[a_kind].items()}
        with piece("proj"):
            q, k, v, gate = _pre_attention(x, layer_params["attn_norm"][l],
                                           p, pos, a_kind, geom)
        if not paged:
            zero = jnp.zeros((B,), jnp.int32)
            kd, vd = k.astype(emb.dtype), v.astype(emb.dtype)
            with piece("attend"):
                o = causal_attention_fn(q, kd, vd, zero, sm_scale) \
                    if a_kind == FULL else \
                    band_attention_fn(q, kd, vd, zero, zero, W, sm_scale)
        elif a_kind == FULL:
            off = a_i * num_pages
            table = page_table + off
            kd, vd = k.astype(k_pool.dtype), v.astype(v_pool.dtype)
            if decode:
                with piece("kv_write"):
                    k_pool, v_pool = kv_cache_append_fn(
                        k_pool, v_pool, kd[:, 0], vd[:, 0], table, first,
                        valid[:, 0])
                with piece("attend"):
                    o = paged_decode_attention_fn(
                        q[:, 0], k_pool, v_pool, table, first + 1,
                        sm_scale=sm_scale, plan=walk)[:, None]
            else:
                with piece("kv_write"):
                    idx = _page_row_index(page_table, gpos, page_size, off,
                                          valid)
                    slot = gpos % page_size
                    k_pool = _write_rows(k_pool, kd.reshape(B, S, -1), idx,
                                         slot)
                    v_pool = _write_rows(v_pool, vd.reshape(B, S, -1), idx,
                                         slot)
                with piece("kv_gather"):
                    kg = _gather_pages(k_pool, table, nkv)
                    vg = _gather_pages(v_pool, table, nkv)
                with piece("attend"):
                    o = causal_attention_fn(q, kg, vg, first, sm_scale)
        else:
            off = a_i * window_pages
            table = window_table + off
            kd, vd = k.astype(wk_pool.dtype), v.astype(wv_pool.dtype)
            if decode:
                at = local[:, 0]
                with piece("kv_write"):
                    wk_pool, wv_pool = kv_cache_append_fn(
                        wk_pool, wv_pool, kd[:, 0], vd[:, 0], table, at,
                        valid[:, 0])
                with piece("attend"):
                    o = paged_decode_attention_fn(
                        q[:, 0], wk_pool, wv_pool, table, at + 1,
                        sm_scale=sm_scale,
                        first_live=jnp.maximum(at - (W - 1), 0))[:, None]
            else:
                with piece("kv_write"):
                    idx = _page_row_index(window_table, local, page_size,
                                          off, valid)
                    slot = local % page_size
                    wk_pool = _write_rows(wk_pool, kd.reshape(B, S, -1),
                                          idx, slot)
                    wv_pool = _write_rows(wv_pool, vd.reshape(B, S, -1),
                                          idx, slot)
                with piece("kv_gather"):
                    kg = _gather_pages(wk_pool, table, nkv)
                    vg = _gather_pages(wv_pool, table, nkv)
                with piece("attend"):
                    o = band_attention_fn(q, kg, vg, first, base, W,
                                          sm_scale)
        with piece("proj"):
            o = (o.astype(_F32) * gate[..., None]).reshape(B, S, -1)
            h = x + _mm(o, p["wo"])
        fp = {k: w[f_i] for k, w in (dense if f_kind == DENSE
                                     else moe).items()}
        x, ids = _feed_forward(h, layer_params["ffn_norm"][l], f_kind, fp,
                               experts, f_i, geom, tag)
        if ids is not None:
            routes.append(ids)
    with piece("head"):
        xn = rms_norm_fn(x, final_norm, geom.eps)
        if mode == "window":
            at = jnp.clip(lens - 1, 0, S - 1)[:, None, None]
            xn = jnp.take_along_axis(xn, at, axis=1)
        logits = jnp.einsum("bsh,hv->bsv", xn.astype(head.dtype), head,
                            preferred_element_type=_F32)
    routes = jnp.stack(routes, axis=2)                    # [B, S, L_moe, k]
    out = {"logits": logits if mode == "full" else logits[:, 0],
           "routes": routes[:, 0] if decode else routes}
    if paged:
        out["pools"] = (k_pool, v_pool, wk_pool, wv_pool)
    return out


# ---------------------------------------------------------------------------
# registered ops
# ---------------------------------------------------------------------------

_POOL_SLOTS = ("KPool", "VPool", "WKPool", "WVPool")


@register_op("hybrid_moe_stack", grad="none")
def hybrid_moe_stack_op(ctx: ExecContext):
    """The whole decoder in one op; see `hybrid_moe_stack_fn`. inputs: Tok,
    Pos, Emb, Head, FinalNorm, LayerParams (`LAYER_PARAMS`), FullParams and
    SlideParams (`ATTENTION_PARAMS` each), DenseParams, MoeParams, Experts,
    and by mode PageTable, WindowTable, WindowBase, Lens, Start, Mask and
    the four pools. attrs: mode, plan (flat: four entries a layer), the
    geometry. Outputs: NextToken (greedy), Logits, Routes, and the pools
    under their own names."""
    mode = ctx.attr("mode")
    geom = Geometry(*(ctx.attr(f) for f in Geometry._fields))
    flat = list(ctx.attr("plan"))
    plan = tuple((flat[i], int(flat[i + 1]), flat[i + 2], int(flat[i + 3]))
                 for i in range(0, len(flat), 4))
    paged = mode != "full"

    def group(slot, names):
        return dict(zip(names, ctx.inputs(slot)))

    def opt(slot):
        return ctx.input(slot).astype(jnp.int32) if ctx.has_input(slot) \
            else None

    out = hybrid_moe_stack_fn(
        "window" if mode == "prefill" else mode,
        ctx.input("Tok").astype(jnp.int32),
        ctx.input("Pos").astype(jnp.int32), ctx.input("Emb"),
        ctx.input("Head"), ctx.input("FinalNorm"),
        group("LayerParams", LAYER_PARAMS),
        {FULL: group("FullParams", ATTENTION_PARAMS),
         SLIDE: group("SlideParams", ATTENTION_PARAMS)},
        group("DenseParams", DENSE_PARAMS), group("MoeParams", MOE_PARAMS),
        tuple(ctx.inputs("Experts")), plan, geom,
        pools=tuple(ctx.input(s) for s in _POOL_SLOTS) if paged else None,
        page_table=opt("PageTable"), window_table=opt("WindowTable"),
        window_base=opt("WindowBase"), lens=opt("Lens"), start=opt("Start"),
        mask=ctx.input("Mask") if ctx.has_input("Mask") else None,
        num_pages=int(ctx.attr("num_pages", 0)),
        window_pages=int(ctx.attr("window_pages", 0)))
    res = {"Logits": out["logits"], "Routes": out["routes"],
           "NextToken": greedy_fn(out["logits"])}
    if paged:
        res.update({s + "Out": pool
                    for s, pool in zip(_POOL_SLOTS, out["pools"])})
    return res


@register_op("hybrid_copy_page", grad="none")
def hybrid_copy_page_op(ctx: ExecContext):
    """Copy-on-write for the two stacked pools: page Src of every full
    layer to page Dst (rows `l * num_pages + page` of K and V), page WSrc
    of every sliding layer to page WDst (rows `l * window_pages + page`).
    A side with nothing to copy is given the same page twice."""
    out = {}
    for slots, src, dst, pages in (
            (_POOL_SLOTS[:2], "Src", "Dst", "num_pages"),
            (_POOL_SLOTS[2:], "WSrc", "WDst", "window_pages")):
        s = ctx.input(src).astype(jnp.int32)[0]
        d = ctx.input(dst).astype(jnp.int32)[0]
        P = int(ctx.attr(pages))
        for slot in slots:
            pool = ctx.input(slot)
            rows = jnp.arange(pool.shape[0] // P, dtype=jnp.int32) * P
            out[slot + "Out"] = pool.at[rows + d].set(pool[rows + s])
    return out
