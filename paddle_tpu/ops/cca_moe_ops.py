"""The mechanisms of a compressed-convolutional-attention + top-1
mixture-of-experts decoder layer, and the op that runs a stack of them.

Each mechanism is a plain jax function (`<name>_fn`); the one registered op
that runs them is `cca_moe_stack`, so no program can append one alone:

  * `rms_norm`            — RMSNorm, float32 statistics;
  * `rotary_partial`      — rotary position embedding on the first
                            `rotary_dim` lanes of every head;
  * `l2_norm_heads`       — per-head L2 normalisation to length sqrt(dh);
  * `causal_conv_carry`   — the two causal sequence convolutions of
                            Compressed Convolutional Attention
                            (arXiv:2510.04476), kernel 2 each: depthwise,
                            then grouped by head. A kernel-2 causal
                            convolution needs the previous token's input,
                            so each takes and returns a CARRIED TAIL: what a
                            sequence continued later (decode, a suffix
                            prefill) must be handed back;
  * `moe_router`          — the MLP router with the depth-averaged state of
                            arXiv:2511.17127 (router state of the same token
                            one layer down, times a learned gamma);
  * `moe_top1_experts`    — a top-1 SwiGLU expert layer over stacked
                            `[E_local, ...]` weights that is told which
                            experts it holds (`expert_lo`), is given the
                            routing over ALL experts and computes its own
                            experts' part: the parts of disjoint holders sum
                            to the whole layer.

`cca_moe_stack` composes them into the whole decoder (embedding, L layers,
final norm, tied head) in the four shapes serving needs (dense oracle,
prefill, window over the paged pool, ragged decode). The layer is written
once and `lax.scan`ned over per-layer weights stacked `[L, ...]`, so XLA
compiles one layer whatever the depth. The K/V pools of all layers are ONE
buffer `[L * pages, page_size, nkv*dh]` (layer l's page p is row
`l * pages + p`): the scan carries it, writes it in place and hands the
paged kernels a page table shifted by `l * pages`, so no layer's pool is
ever sliced out of the stack. The same holds for the STATE pool
`[L * pages, state_width]`: one float32 row per page and layer holding the
carried tails (`c`, `a`, second value half) after the page's latest token —
final once the page is full, which is what lets a prefix hit on whole pages
restore the exact state it resumes from (serving/engine.py).

Precision: matmul operands in the weights' dtype (bfloat16 as served),
float32 accumulation; residual stream, norms, convolutions, router, rotary
and softmax in float32.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from .attention_ops import (_write_rows, grouped_query_attention,
                            kv_cache_append_fn, paged_decode_attention_fn,
                            paged_decode_plan_fn,
                            paged_prefill_attention_fn)
from .decoder_common import (_experts_backend, _mm, _page_row_index,
                             greedy_fn, rms_norm_fn, rotary_partial_fn)
from ..observability.schema import piece, under_mode
from .registry import ExecContext, register_op

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

Geometry = collections.namedtuple(
    "Geometry", "num_heads num_kv_heads head_dim rotary_dim rope_theta eps")

# the stacked per-layer parameters, in the order the stack op takes them
LAYER_PARAMS = (
    "attn_norm", "wqk", "wv", "wo", "conv0_w", "conv0_b", "conv1_w",
    "conv1_b", "k_temp", "ffn_norm", "router_in_w", "router_in_b",
    "router_gamma", "router_norm", "router_w1", "router_b1", "router_w2",
    "router_b2", "router_w3", "router_b3", "router_bias")
EXPERT_PARAMS = ("w_gate", "w_up", "w_down")


def state_width(geom: Geometry) -> int:
    """Values a layer carries from token t-1 to t: c and a (the latent
    [q~, k~] before and after the first convolution) and the second value
    half."""
    latent = (geom.num_heads + geom.num_kv_heads) * geom.head_dim
    return 2 * latent + geom.head_dim


# ---------------------------------------------------------------------------
# the mechanisms
# ---------------------------------------------------------------------------


def l2_norm_heads_fn(x, eps: float = 1e-6):
    """x [..., dh] -> the same direction at length sqrt(dh)."""
    dh = x.shape[-1]
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             / dh + eps)


def _shift(x, prev):
    """x [B, S, C] one token later along S, `prev` [B, C] entering."""
    return jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)


def causal_conv_carry_fn(c, c_prev, a_prev, w0, b0, w1, b1):
    """c [B, S, C] -> (e, a) [B, S, C]: `a_t = w0[:,0] c_{t-1} + w0[:,1] c_t
    + b0` (depthwise), `e_t = W1[0] a_{t-1} + W1[1] a_t + b1` (block
    diagonal: `w1` is [2, G, C/G, C/G]). `c_prev`/`a_prev` [B, C] are the
    tails carried in (zeros at the start of a sequence); the tails to carry
    out are `c[:, t]` and `a[:, t]` of the last token kept."""
    B, S, C = c.shape
    groups, width = w1.shape[1], w1.shape[2]
    a = w0[:, 0] * _shift(c, c_prev) + w0[:, 1] * c + b0
    a_now = a.reshape(B, S, groups, width)
    a_before = _shift(a, a_prev).reshape(B, S, groups, width)
    e = (jnp.einsum("bsgi,gio->bsgo", a_before, w1[0], precision=_HI)
         + jnp.einsum("bsgi,gio->bsgo", a_now, w1[1], precision=_HI))
    return e.reshape(B, S, C) + b1, a


def moe_router_fn(z, r_prev, p):
    """z [T, H] float32, r_prev [T, R] (the same tokens' router state one
    layer down; zeros below layer 0) -> (r [T, R], probs [T, E], choice [T]
    int32). `p`: router_in_w/_in_b, router_gamma, router_norm,
    router_w1..3/_b1..3, router_bias, eps. The balancing bias selects and
    never weighs: `choice = argmax(probs + bias)`, the weight is
    `probs[choice]`."""
    r = (jnp.dot(z, p["router_in_w"], precision=_HI) + p["router_in_b"]
         + p["router_gamma"] * r_prev)
    h = rms_norm_fn(r, p["router_norm"], p["eps"])
    h = jax.nn.gelu(jnp.dot(h, p["router_w1"], precision=_HI)
                    + p["router_b1"], approximate=False)
    h = jax.nn.gelu(jnp.dot(h, p["router_w2"], precision=_HI)
                    + p["router_b2"], approximate=False)
    s = jnp.dot(h, p["router_w3"], precision=_HI) + p["router_b3"]
    probs = jax.nn.softmax(s, axis=-1)
    choice = jnp.argmax(probs + p["router_bias"], axis=-1).astype(jnp.int32)
    return r, probs, choice


def moe_top1_experts_fn(z, probs, choice, w_gate, w_up, w_down, layer=0,
                        expert_lo: int = 0, tag: str = "decode"):
    """The part of a top-1 expert layer that the holder of experts
    `expert_lo .. expert_lo + E_local` computes. z [T, H]; probs [T, E] and
    choice [T] are the routing over ALL experts; weights are stacked
    `[L, E_local, ...]` and `layer` picks the layer. Returns float32
    [T, H]: `probs[t, choice[t]] * expert(z[t])` where the chosen expert is
    held here, zero elsewhere."""
    from .pallas_kernels import moe_experts as pme

    e_local = w_gate.shape[1]
    held = expert_lo + jnp.arange(e_local, dtype=jnp.int32)
    weight = jnp.take_along_axis(probs, choice[:, None], axis=1)   # [T, 1]
    cw = jnp.where(choice[:, None] == held[None, :], weight, 0.0)  # [T, El]
    if _experts_backend(z.shape[0], w_gate.shape, w_gate.dtype) == "pallas":
        return pme.moe_top1_experts(z, cw, w_gate, w_up, w_down, layer,
                                    tag=tag)
    return pme._reference(z, cw, w_gate, w_up, w_down, layer)


# ---------------------------------------------------------------------------
# one layer, in two halves around the attention
# ---------------------------------------------------------------------------


def _pre_attention(x, p, state_prev, positions, geom: Geometry):
    """x [B, S, H] -> q [B, S, nh, dh], k, v [B, S, nkv, dh] (float32) and
    the state rows [B, S, state_width] after every token. `state_prev`
    [B, state_width] is the state after the token before x[:, 0]."""
    B, S, _ = x.shape
    nh, nkv, dh = geom.num_heads, geom.num_kv_heads, geom.head_dim
    latent = (nh + nkv) * dh
    c_prev = state_prev[:, :latent]
    a_prev = state_prev[:, latent:2 * latent]
    v2_prev = state_prev[:, 2 * latent:]
    u = rms_norm_fn(x, p["attn_norm"], geom.eps)
    c = _mm(u, p["wqk"])                                  # [B, S, latent]
    e, a = causal_conv_carry_fn(c, c_prev, a_prev, p["conv0_w"],
                                p["conv0_b"], p["conv1_w"], p["conv1_b"])
    q_lat = c[..., :nh * dh].reshape(B, S, nkv, nh // nkv, dh)
    k_lat = c[..., nh * dh:].reshape(B, S, nkv, 1, dh)
    e_q = e[..., :nh * dh].reshape(B, S, nkv, nh // nkv, dh)
    e_k = e[..., nh * dh:].reshape(B, S, nkv, 1, dh)
    q = e_q + 0.5 * (q_lat + k_lat)
    k = e_k + 0.5 * (jnp.mean(q_lat, axis=3, keepdims=True) + k_lat)
    q = l2_norm_heads_fn(q).reshape(B, S, nh, dh)
    k = l2_norm_heads_fn(k).reshape(B, S, nkv, dh) \
        * jnp.exp(p["k_temp"])[:, None]
    q = rotary_partial_fn(q, positions, geom.rotary_dim, geom.rope_theta)
    k = rotary_partial_fn(k, positions, geom.rotary_dim, geom.rope_theta)
    v12 = _mm(u, p["wv"])                                 # [B, S, nkv*dh]
    v1, v2 = v12[..., :dh], v12[..., dh:]
    v = jnp.concatenate([v1, _shift(v2, v2_prev)], axis=-1)
    states = jnp.concatenate([c, a, v2], axis=-1)
    return q, k, v.reshape(B, S, nkv, dh), states


def _post_attention(x, o, r_prev, p, experts, layer, geom: Geometry, tag):
    """x, o [B, S, .] -> (y [B, S, H], r [B, S, R], choice [B, S])."""
    B, S, H = x.shape
    with piece("proj"):
        h = x + _mm(o, p["wo"])
        z = rms_norm_fn(h, p["ffn_norm"], geom.eps).reshape(B * S, H)
    with piece("router"):
        r, probs, choice = moe_router_fn(
            z, r_prev.reshape(B * S, -1), dict(p, eps=geom.eps))
    with piece("experts"):
        y = moe_top1_experts_fn(z, probs, choice, *experts, layer=layer,
                                tag=tag)
        return (h + y.reshape(B, S, H), r.reshape(B, S, -1),
                choice.reshape(B, S))


def _read_state(s_pool, page_table, pos_prev, page_size, layer_off):
    """[B, state_width]: the state after position `pos_prev` [B]; zeros for
    a sequence that starts here (`pos_prev` < 0)."""
    idx = _page_row_index(page_table, jnp.maximum(pos_prev, 0), page_size,
                           layer_off, True)
    rows = s_pool[jnp.clip(idx, 0, s_pool.shape[0] - 1)]
    return jnp.where((pos_prev >= 0)[:, None], rows, 0.0)


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


@under_mode
def cca_moe_stack_fn(mode: str, tok, pos, emb, final_norm, layer_params: dict,
                     experts: tuple, geom: Geometry, pools=None,
                     page_table=None, lens=None, start=None, mask=None,
                     num_pages: int = 0):
    """Run the decoder. `mode`:

      full     tok/pos [B, S]                        -> logits [B, S, V]
      prefill  + page_table, lens (cold, start 0)    -> last logits [B, V]
      window   + start [B] (context in the pool)     -> last logits [B, V]
      decode   tok/pos [B], page_table, mask [B]     -> logits [B, V]

    Returns a dict: logits, routes ([B, S, L], decode [B, L]) and, with
    pools, k_pool/v_pool/s_pool as written. Traced under its mode's scope,
    each piece (observability/schema.PIECES) under its own."""
    decode = mode == "decode"
    paged = mode != "full"
    if decode:
        # the engine feeds a decode step's tokens as a [B, 1] column
        tok, pos = jnp.reshape(tok, (-1, 1)), jnp.reshape(pos, (-1, 1))
    with piece("embed"):
        x = emb[tok].astype(_F32)
    B, S, _ = x.shape
    L = layer_params["wqk"].shape[0]
    sm_scale = geom.head_dim ** -0.5
    r0 = jnp.zeros((B, S, layer_params["router_in_b"].shape[-1]), _F32)
    tag = "decode" if decode else "prefill"
    if paged:
        page_size = pools[0].shape[1]
        page_table = page_table.astype(jnp.int32)
        first = (pos[:, 0] if decode
                 else (start if start is not None
                       else jnp.zeros((B,), jnp.int32))).astype(jnp.int32)
        gpos = first[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        if decode:
            live = jnp.reshape(mask, (-1,)) > 0
            # which rows' tables begin with the same pages is the tables'
            # alone: every layer's decode call shares one plan, a constant
            # of the scanned body
            walk = paged_decode_plan_fn(
                (B, geom.num_heads, geom.head_dim), _F32, pools[0],
                page_table, first + 1)
        else:
            valid = jnp.arange(S, dtype=jnp.int32)[None, :] < lens[:, None]
            # a page's state row is the state after its latest token: write
            # at every page end and at the window's last token
            last = jnp.arange(S, dtype=jnp.int32)[None, :] == lens[:, None] - 1

    def layer(carry, xs):
        l, p = xs
        if paged:
            x, r_prev, k_pool, v_pool, s_pool = carry
            off = l * num_pages
            with piece("state"):
                state_prev = _read_state(s_pool, page_table, first - 1,
                                         page_size, off)
        else:
            x, r_prev = carry
            state_prev = jnp.zeros((B, state_width(geom)), _F32)
        with piece("proj"):
            q, k, v, states = _pre_attention(x, p, state_prev, pos, geom)
        if paged:
            table = page_table + off
            kd = k.astype(k_pool.dtype)
            vd = v.astype(v_pool.dtype)
            if decode:
                with piece("kv_write"):
                    k_pool, v_pool = kv_cache_append_fn(
                        k_pool, v_pool, kd[:, 0], vd[:, 0], table, first,
                        live)
                with piece("state"):
                    s_idx = _page_row_index(page_table, first, page_size,
                                             off, live)
                    s_pool = s_pool.at[s_idx].set(states[:, 0], mode="drop")
                with piece("attend"):
                    o = paged_decode_attention_fn(
                        q[:, 0], k_pool, v_pool, table, first + 1,
                        sm_scale=sm_scale, plan=walk)[:, None]
            else:
                with piece("kv_write"):
                    kv_idx = _page_row_index(page_table, gpos, page_size,
                                              off, valid)
                    slot = gpos % page_size
                    k_pool = _write_rows(k_pool, kd.reshape(B, S, -1),
                                         kv_idx, slot)
                    v_pool = _write_rows(v_pool, vd.reshape(B, S, -1),
                                         kv_idx, slot)
                with piece("state"):
                    s_idx = _page_row_index(
                        page_table, gpos, page_size, off,
                        valid & (last | (slot == page_size - 1)))
                    s_pool = s_pool.at[s_idx].set(states, mode="drop")
                with piece("attend"):
                    if mode == "window":
                        o = paged_prefill_attention_fn(
                            jnp.swapaxes(q, 1, 2), k_pool, v_pool, table,
                            first, sm_scale=sm_scale)
                    else:
                        o = grouped_query_attention(
                            jnp.swapaxes(q, 1, 2).astype(kd.dtype),
                            jnp.swapaxes(kd, 1, 2), jnp.swapaxes(vd, 1, 2),
                            causal=True, sm_scale=sm_scale)
                    o = jnp.swapaxes(o, 1, 2)
        else:
            with piece("attend"):
                o = jnp.swapaxes(grouped_query_attention(
                    jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                    jnp.swapaxes(v, 1, 2), causal=True, sm_scale=sm_scale),
                    1, 2)
        y, r, choice = _post_attention(
            x, o.reshape(B, S, -1).astype(_F32), r_prev, p, experts, l, geom,
            tag)
        carry = (y, r, k_pool, v_pool, s_pool) if paged else (y, r)
        return carry, choice

    init = (x, r0) + (tuple(pools) if paged else ())
    xs = (jnp.arange(L, dtype=jnp.int32), layer_params)
    carry, routes = jax.lax.scan(layer, init, xs)
    with piece("head"):
        xn = rms_norm_fn(carry[0], final_norm, geom.eps)
        if mode in ("prefill", "window"):
            at = jnp.clip(lens - 1, 0, S - 1)[:, None, None]
            xn = jnp.take_along_axis(xn, at, axis=1)
        logits = jnp.einsum("bsh,vh->bsv", xn.astype(emb.dtype), emb,
                            preferred_element_type=_F32)
    routes = jnp.moveaxis(routes, 0, -1)                 # [B, S, L]
    out = {"logits": logits if mode == "full" else logits[:, 0],
           "routes": routes[:, 0] if decode else routes}
    if paged:
        out.update(k_pool=carry[2], v_pool=carry[3], s_pool=carry[4])
    return out


# ---------------------------------------------------------------------------
# registered ops
# ---------------------------------------------------------------------------


@register_op("cca_moe_stack", grad="none")
def cca_moe_stack_op(ctx: ExecContext):
    """The whole decoder in one op; see `cca_moe_stack_fn`. inputs: Tok,
    Pos, Emb, FinalNorm, LayerParams (the `LAYER_PARAMS`, in order),
    Experts (`EXPERT_PARAMS`), and by mode PageTable, Lens, Start, Mask,
    KPool/VPool/SPool. attrs: mode and the geometry. Outputs: NextToken
    (greedy), Logits, Routes, and the pools under their own names."""
    mode = ctx.attr("mode")
    geom = Geometry(*(ctx.attr(f) for f in Geometry._fields))
    params = dict(zip(LAYER_PARAMS, ctx.inputs("LayerParams")))
    paged = mode != "full"

    def opt(slot):
        return ctx.input(slot).astype(jnp.int32) if ctx.has_input(slot) \
            else None

    out = cca_moe_stack_fn(
        mode, ctx.input("Tok").astype(jnp.int32),
        ctx.input("Pos").astype(jnp.int32), ctx.input("Emb"),
        ctx.input("FinalNorm"), params, tuple(ctx.inputs("Experts")), geom,
        pools=(ctx.input("KPool"), ctx.input("VPool"), ctx.input("SPool"))
        if paged else None,
        page_table=opt("PageTable"), lens=opt("Lens"), start=opt("Start"),
        mask=ctx.input("Mask") if ctx.has_input("Mask") else None,
        num_pages=int(ctx.attr("num_pages", 0)))
    res = {"Logits": out["logits"], "Routes": out["routes"],
           "NextToken": greedy_fn(out["logits"])}
    if paged:
        res.update(KPoolOut=out["k_pool"], VPoolOut=out["v_pool"],
                   SPoolOut=out["s_pool"])
    return res


@register_op("cca_state_copy_page", grad="none")
def cca_state_copy_page_op(ctx: ExecContext):
    """Copy-on-write for the stacked pools: page Src of EVERY layer to page
    Dst (rows `l * num_pages + page`), K, V and the state row, or as many
    pools as the block has."""
    src = ctx.input("Src").astype(jnp.int32)[0]
    dst = ctx.input("Dst").astype(jnp.int32)[0]
    P = int(ctx.attr("num_pages"))
    out = {}
    for slot in ("KPool", "VPool", "SPool"):
        if not ctx.has_input(slot):
            continue
        pool = ctx.input(slot)
        rows = jnp.arange(pool.shape[0] // P, dtype=jnp.int32) * P
        out[slot + "Out"] = pool.at[rows + dst].set(pool[rows + src])
    return out


@register_op("stacked_gaussian_random", grad="none", needs_rng=True)
def stacked_gaussian_random(ctx: ExecContext):
    """gaussian_random for a stack too large to draw at once (a served
    mixture's expert weights, billions of values): drawn one leading index
    at a time (`lax.map`), so the float32 temporaries are one slice's, and
    from the device's own bit generator (`rbg`, seeded from the op's key),
    several times faster on the chip than counting threefry blocks. The
    same seed gives the same stack on the same backend."""
    shape = tuple(ctx.attr("shape"))
    dtype = jnp.dtype(ctx.attr("dtype", "float32"))
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    data = jax.random.key_data(ctx.rng).astype(jnp.uint32).reshape(-1)[:2]
    keys = jax.random.split(
        jax.random.wrap_key_data(jnp.concatenate([data, data]), impl="rbg"),
        shape[0])
    return {"Out": jax.lax.map(
        lambda k: (jax.random.normal(k, shape[1:], _F32) * std
                   + mean).astype(dtype), keys)}


@register_op("blocked_gaussian_random", grad="none", needs_rng=True)
def blocked_gaussian_random(ctx: ExecContext):
    """`stacked_gaussian_random` for a matrix `[..., R, W]`: zero-mean
    normals drawn `block_rows` rows at a time (`lax.map`, the device's
    `rbg` generator), the columns in blocks of `col_widths` times
    `col_scales`."""
    shape = tuple(ctx.attr("shape"))
    dtype = jnp.dtype(ctx.attr("dtype", "float32"))
    rows = int(ctx.attr("block_rows"))
    W = shape[-1]
    scale = jnp.concatenate([
        jnp.full((int(n),), float(v), _F32) for n, v in zip(
            ctx.attr("col_widths"), ctx.attr("col_scales"))])
    if scale.shape[0] != W or shape[-2] % rows:
        raise ValueError("blocked_gaussian_random: col_widths must add up "
                         "to the last dimension and block_rows divide the "
                         "one before it")
    blocks = int(np.prod(shape[:-1])) // rows
    data = jax.random.key_data(ctx.rng).astype(jnp.uint32).reshape(-1)[:2]
    keys = jax.random.split(
        jax.random.wrap_key_data(jnp.concatenate([data, data]), impl="rbg"),
        blocks)
    out = jax.lax.map(
        lambda k: (jax.random.normal(k, (rows, W), _F32) * scale
                   ).astype(dtype), keys)
    return {"Out": out.reshape(shape)}
