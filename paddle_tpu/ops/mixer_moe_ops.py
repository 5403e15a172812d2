"""The mechanisms of a decoder whose every layer is ONE sub-layer behind ONE
pre-norm: a Mamba-2 state-space mixer, a grouped-query attention or a layer
of experts that work in a LATENT (the "mixer_moe" block of serving/model.py;
Nemotron-H's layers with latent experts), laid out by a plan of one
character a layer (`M`, `*`, `E`), and the op that runs a stack of them.

Every layer `l`: `x <- x + f_l(RMSNorm_l(x))`, `f_l` by its kind:

  * `M`  the "parallel_ssm" mixer with every multiplier 1 (its convolution,
         chunked scan, one-token updates and grouped gated norm are imported
         from `parallel_ssm_ops`, not rewritten): `[z | xBC | dt] = x~ W_in`,
         `xBC <- silu(conv(xBC))`, the recurrence `S <- a S + B (x) dt x`,
         `y = C . S + D x`, `y <- RMSNorm_grouped(y * silu(z))`, `f = y
         W_out`. Heads may be NARROWER than the 128 lanes (64 over a state
         of 128): `state_pack` of them then lie side by side in a slot
         (`pallas_kernels.ssm_update.pack_state`), which is what keeps the
         one-token update a Pallas kernel;
  * `*`  `softmax(q k^T / sqrt(d)) v`, causal, grouped queries, NO rotary
         and no bias (the mixers carry position), `f = o W_o`;
  * `E`  `latent_experts`: the router reads `x~` at the hidden width
         (`latent_moe_ops.group_limited_router_fn` with one group: sigmoid
         scores, a selection bias, the chosen scores normalised times a
         scaling factor); `u = x~ W_dn` takes the token into the latent,
         `r = sum_chosen w_e W2_e relu(W1_e u)^2` (two matrices an expert,
         no gate: `pallas_kernels.moe_experts.moe_relu2_experts`), `f = r
         W_up + Ws2 relu(Ws1 x~)^2`, the shared expert on the hidden itself.
         `experts_held` says how many experts THIS chip holds (the first
         ones); the router keeps all its outputs, the sum runs over the
         held ones, and because `W_up` is linear a chip's `r` is what an
         expert-parallel group would add up IN THE LATENT.

`mixer_moe_stack` composes them (embedding, the layers one after another by
the plan over weights stacked BY KIND, final norm, untied head) in the
shapes serving needs: dense oracle (`full`), a window over the pools
(`window`; `prefill` is the same at start 0) and the ragged decode step.
K/V pools are stacked over the attention layers only, the two pools of
recurrent state (`kv_cache.STATE_POOLS`) over the mixers only; a request's
`routes` are `[positions, expert layers, k]`.

Precision: matmul operands in the weights' dtype (bfloat16 as served),
float32 accumulation; residual stream, norms, the router, the convolution
and its tail, dt, A, the decay, S and every product of the scan, and
softmax in float32.
"""
from __future__ import annotations

import collections

import jax.numpy as jnp

from .attention_ops import (_gather_pages, _write_rows, kv_cache_append_fn,
                            paged_decode_attention_fn, paged_decode_plan_fn)
from .decoder_common import (_experts_backend, _mm, _page_row_index,
                             causal_attention_fn, greedy_fn,
                             group_limited_router_fn, rms_norm_fn)
from .parallel_ssm_ops import (causal_conv_fn, conv_token_update_fn,
                               conv_window_update_fn, gated_group_norm_fn,
                               ssd_scan_fn, ssm_token_update_fn)
from ..observability.schema import piece, under_mode
from .registry import ExecContext, register_op

_F32 = jnp.float32

MIXER, ATTENTION, EXPERTS = "M", "*", "E"

Geometry = collections.namedtuple(
    "Geometry", "plan num_heads num_kv_heads head_dim eps ssm_heads "
                "ssm_head_dim ssm_groups ssm_state ssm_conv ssm_chunk "
                "state_pack experts_per_token routed_scaling experts_held")

# the stacked parameters of each kind, in the order the stack op takes them
MIXER_PARAMS = ("w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
                "ssm_norm", "w_out")
ATTENTION_PARAMS = ("wq", "wk", "wv", "wo")
MOE_PARAMS = ("router_w", "router_bias", "w_dn", "w_up", "shared_in",
              "shared_out")
EXPERT_PARAMS = ("w1", "w2")


def state_pack(head_dim: int, heads_per_group: int, lanes: int = 128) -> int:
    """Heads of one group that lie side by side in a slot of the state
    pool: as many as fill the lanes, a power of two that divides the
    group's heads (64-wide heads: 2; heads of a whole lane row: 1)."""
    pack = 1
    while pack * 2 * head_dim <= lanes and heads_per_group % (pack * 2) == 0:
        pack *= 2
    return pack


def relu2_fn(x):
    return jnp.square(jnp.maximum(x, 0.0))


def latent_experts_fn(u, cw, w1, w2, layer=0, tag: str = "decode"):
    """`sum_e cw[t, e] * W2_e relu(W1_e u_t)^2`, float32 [T, Z]; weights
    stacked `[L, E, Z, F]`, `[L, E, F, Z]`, `layer` picks the layer."""
    from .pallas_kernels import moe_experts as pme

    if _experts_backend(u.shape[0], w1.shape, w1.dtype) == "pallas":
        return pme.moe_relu2_experts(u, cw, w1, w2, layer, tag=tag)
    return pme._relu2_reference(u, cw, w1, w2, layer)


def latent_moe_fn(z, p, experts, index, geom: Geometry, tag: str):
    """z [T, H] (normed) -> (the layer's branch [T, H], ids [T, k])."""
    with piece("router"):
        ids, cw = group_limited_router_fn(
            z, p["router_w"], p["router_bias"], geom.experts_per_token, 1, 1,
            geom.routed_scaling)
        held = cw[:, :geom.experts_held]    # this chip's experts' columns
    with piece("latent_proj"):
        u = _mm(z, p["w_dn"])
    with piece("experts"):
        r = latent_experts_fn(u, held, *experts, layer=index, tag=tag)
    with piece("latent_proj"):
        f = _mm(r, p["w_up"])
    with piece("shared"):
        return f + _mm(relu2_fn(_mm(z, p["shared_in"])), p["shared_out"]), \
            ids


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


@under_mode
def mixer_moe_stack_fn(mode: str, tok, pos, emb, head, final_norm, norms,
                       mixer: dict, attention: dict, moe: dict,
                       experts: tuple, geom: Geometry, pools=None,
                       page_table=None, lens=None, start=None, mask=None,
                       state_slot=None, num_pages: int = 0,
                       num_slots: int = 0):
    """Run the decoder. `mode`:

      full     tok/pos [B, S]                          -> logits [B, S, V]
      window   + page_table, start, lens, state_slot
               (K/V and the state in the pools;
               `prefill` is start 0)                   -> last logits [B, V]
      decode   tok/pos [B], page_table, mask [B],
               state_slot [B]                          -> logits [B, V]

    `norms` `[L, H]` over all layers; `mixer`, `attention`, `moe` and
    `experts` the weights of a kind stacked over the layers of that kind.
    Returns a dict: logits; routes ([B, S, L_experts, k], decode [B,
    L_experts, k]); with `pools` (K, V, the states, the convolution tails)
    the four as written. Traced under its mode's scope, each piece
    (observability/schema.PIECES) under its own."""
    from .pallas_kernels.ssm_update import pack_state, unpack_state

    decode = mode == "decode"
    paged = mode != "full"
    if decode:
        tok, pos = jnp.reshape(tok, (-1, 1)), jnp.reshape(pos, (-1, 1))
    with piece("embed"):
        x = emb[tok].astype(_F32)
    B, S, H = x.shape
    nh, nkv, dh = geom.num_heads, geom.num_kv_heads, geom.head_dim
    Hs, P, G, N = (geom.ssm_heads, geom.ssm_head_dim, geom.ssm_groups,
                   geom.ssm_state)
    I, K = Hs * P, geom.ssm_conv
    C = I + 2 * G * N
    sm_scale = dh ** -0.5
    tag = "decode" if decode else "prefill"
    rel = jnp.arange(S, dtype=jnp.int32)[None, :]
    valid = None
    if paged:
        k_pool, v_pool, s_pool, c_pool = pools
        page_size = k_pool.shape[1]
        page_table = page_table.astype(jnp.int32)
        first = (pos[:, 0] if decode
                 else (start if start is not None
                       else jnp.zeros((B,), jnp.int32))).astype(jnp.int32)
        gpos = first[:, None] + rel                             # [B, S]
        valid = (jnp.reshape(mask, (-1, 1)) > 0) if decode \
            else rel < lens[:, None]
        count = None if decode else lens
        # a decode step's live rows come first (`engine._decode_once`)
        n_live = jnp.sum(valid, dtype=jnp.int32) if decode else None
        slot = state_slot.astype(jnp.int32)                     # [B]
        # a window at position 0 starts a sequence: its state is zeros
        fresh = (first == 0) & (not decode)
        # which rows' tables begin with the same pages is the tables'
        # alone: the attention layers' decode calls share one plan
        walk = paged_decode_plan_fn((B, nh, dh), _F32, k_pool, page_table,
                                    first + 1) if decode else None
    routes = []
    seen = {MIXER: 0, ATTENTION: 0, EXPERTS: 0}
    for l, kind in enumerate(geom.plan):
        i = seen[kind]
        seen[kind] += 1
        with piece("proj"):
            z = rms_norm_fn(x, norms[l], geom.eps)
        if kind == MIXER:
            p = {k: w[i] for k, w in mixer.items()}
            if paged:
                row = i * num_slots + slot                      # [B]
            with piece("proj"):
                proj = _mm(z, p["w_in"])
                gate, xbc, dt_raw = (proj[..., :I], proj[..., I:I + C],
                                     proj[..., I + C:])
            with piece("conv"):
                if decode:
                    c_pool, xbc = conv_token_update_fn(
                        c_pool, row, xbc[:, 0], p["conv_w"], p["conv_b"],
                        n_live)
                    xbc = xbc[:, None]
                elif paged:
                    c_pool, xbc = conv_window_update_fn(
                        c_pool, row, xbc, p["conv_w"], p["conv_b"], fresh,
                        count)
                else:
                    xbc, _ = causal_conv_fn(
                        xbc, jnp.zeros((B, K - 1, C), _F32), p["conv_w"],
                        p["conv_b"])
            xs_ = xbc[..., :I].reshape(B, S, Hs, P)
            bm = xbc[..., I:I + G * N].reshape(B, S, G, N)
            cm = xbc[..., I + G * N:].reshape(B, S, G, N)
            if decode:
                with piece("ssm_update"):
                    s_pool, y = ssm_token_update_fn(
                        s_pool, row, xs_[:, 0], dt_raw[:, 0], bm[:, 0],
                        cm[:, 0], p["dt_bias"], p["a_log"], n_live)
                    y = y[:, None]
            else:
                with piece("ssm_scan"):
                    if paged:
                        s0 = jnp.where(fresh[:, None, None, None], 0.0,
                                       unpack_state(s_pool[row], Hs, N))
                    else:
                        s0 = jnp.zeros((B, Hs, N, P), _F32)
                    y, s1 = ssd_scan_fn(xs_, dt_raw, bm, cm, p["dt_bias"],
                                        p["a_log"], s0, geom.ssm_chunk,
                                        valid)
                    if paged:
                        s_pool = s_pool.at[row].set(
                            pack_state(s1, geom.state_pack))
            with piece("proj"):
                y = y + p["d_skip"].astype(_F32)[:, None] * xs_
                y = gated_group_norm_fn(y.reshape(B, S, I), gate,
                                        p["ssm_norm"], G, geom.eps)
                f = _mm(y, p["w_out"])
        elif kind == ATTENTION:
            p = {k: w[i] for k, w in attention.items()}
            with piece("proj"):
                q = _mm(z, p["wq"]).reshape(B, S, nh, dh)
                k = _mm(z, p["wk"]).reshape(B, S, nkv, dh)
                v = _mm(z, p["wv"]).reshape(B, S, nkv, dh)
            if not paged:
                with piece("attend"):
                    o = causal_attention_fn(
                        q, k.astype(emb.dtype), v.astype(emb.dtype),
                        jnp.zeros((B,), jnp.int32), sm_scale)
            else:
                off = i * num_pages
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
                        idx = _page_row_index(page_table, gpos, page_size,
                                              off, valid)
                        at = gpos % page_size
                        k_pool = _write_rows(k_pool, kd.reshape(B, S, -1),
                                             idx, at)
                        v_pool = _write_rows(v_pool, vd.reshape(B, S, -1),
                                             idx, at)
                    with piece("kv_gather"):
                        kg = _gather_pages(k_pool, table, nkv)
                        vg = _gather_pages(v_pool, table, nkv)
                    with piece("attend"):
                        o = causal_attention_fn(q, kg, vg, first, sm_scale)
            with piece("proj"):
                f = _mm(o.astype(_F32).reshape(B, S, -1), p["wo"])
        else:
            p = {k: w[i] for k, w in moe.items()}
            f, ids = latent_moe_fn(z.reshape(B * S, H), p, experts, i, geom,
                                   tag)
            f = f.reshape(B, S, H)
            routes.append(ids.reshape(B, S, -1))
        x = x + f
    with piece("head"):
        xn = rms_norm_fn(x, final_norm, geom.eps)
        if mode == "window":
            at = jnp.clip(lens - 1, 0, S - 1)[:, None, None]
            xn = jnp.take_along_axis(xn, at, axis=1)
        logits = jnp.einsum("bsh,hv->bsv", xn.astype(head.dtype), head,
                            preferred_element_type=_F32)
    routes = jnp.stack(routes, axis=2) if routes else jnp.zeros(
        (B, S, 0, geom.experts_per_token), jnp.int32)  # [B, S, L_experts, k]
    out = {"logits": logits if mode == "full" else logits[:, 0],
           "routes": routes[:, 0] if decode else routes}
    if paged:
        out["pools"] = (k_pool, v_pool, s_pool, c_pool)
    return out


# ---------------------------------------------------------------------------
# registered op
# ---------------------------------------------------------------------------

_POOL_SLOTS = ("KPool", "VPool", "SPool", "CPool")


@register_op("mixer_moe_stack", grad="none")
def mixer_moe_stack_op(ctx: ExecContext):
    """The whole decoder in one op; see `mixer_moe_stack_fn`. inputs: Tok,
    Pos, Emb, Head, FinalNorm, Norms, MixerParams, AttentionParams,
    MoeParams, Experts (each the `*_PARAMS`, in order), and by mode
    PageTable, Lens, Start, Mask, StateSlot and the four pools. attrs:
    mode, num_pages, num_slots and the geometry. Outputs: NextToken
    (greedy), Logits, Routes, and the pools under their own names."""
    mode = ctx.attr("mode")
    geom = Geometry(*(ctx.attr(f) for f in Geometry._fields))
    paged = mode != "full"

    def opt(slot):
        return ctx.input(slot).astype(jnp.int32) if ctx.has_input(slot) \
            else None

    out = mixer_moe_stack_fn(
        "window" if mode == "prefill" else mode,
        ctx.input("Tok").astype(jnp.int32),
        ctx.input("Pos").astype(jnp.int32), ctx.input("Emb"),
        ctx.input("Head"), ctx.input("FinalNorm"), ctx.input("Norms"),
        dict(zip(MIXER_PARAMS, ctx.inputs("MixerParams"))),
        dict(zip(ATTENTION_PARAMS, ctx.inputs("AttentionParams"))),
        dict(zip(MOE_PARAMS, ctx.inputs("MoeParams"))),
        tuple(ctx.inputs("Experts")), geom,
        pools=tuple(ctx.input(s) for s in _POOL_SLOTS) if paged else None,
        page_table=opt("PageTable"), lens=opt("Lens"), start=opt("Start"),
        mask=ctx.input("Mask") if ctx.has_input("Mask") else None,
        state_slot=opt("StateSlot"),
        num_pages=int(ctx.attr("num_pages", 0)),
        num_slots=int(ctx.attr("num_slots", 0)))
    res = {"Logits": out["logits"], "Routes": out["routes"],
           "NextToken": greedy_fn(out["logits"])}
    if paged:
        res.update({s + "Out": pool
                    for s, pool in zip(_POOL_SLOTS, out["pools"])})
    return res
