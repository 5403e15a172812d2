"""A decoder whose layers run SEVERAL TIMES a token (the "looped_dense" block
of serving/model.py; a looped language model's layer), and the op that runs
it.

The stack is plain: pre-norm rotary multi-head attention and a SwiGLU, each
sub-layer between TWO RMSNorms (one before it, one on its output, the
residual added after the second). What is not plain is the loop: a token
passes the SAME `L` layers `loop_steps` times, the final norm closing every
visit, and visit `t` of layer `l` attends the keys and values that visit `t`
of layer `l` wrote for the earlier tokens. So the weights are stacked `[L,
...]`, stored once, and the K/V pools hold `loop_steps * L` PLANES of
`num_pages` pages each, plane `t * L + l` at rows `(t * L + l) * num_pages
..`: a page id names one slab in every plane, and a token's cache is
`loop_steps * L` rows.

    x = E[token]
    for t in 0 .. loop_steps - 1:
        for l in 0 .. L - 1:
            a = x + N2_l(Attn_l(N1_l(x); plane t * L + l))
            x = a + N4_l(MLP_l(N3_l(a)))
        x = h_t = N_f(x)                       (closes EVERY visit)
        lambda_t = sigmoid(w_g . h_t + b_g)    (the exit gate)
    logits = W_head h_last

`exit_mass_fn` turns the gate's `lambda_t` into the probability of leaving
after each visit (`p_t = lambda_t prod_{j<t} (1 - lambda_j)`, the last visit
taking what is left): the stack hands it back beside the logits and branches
on nothing; every token runs every visit.

`looped_dense_stack` is one registered op: a `lax.scan` over the visits
around a `lax.scan` over the layers, so a program holds ONE copy of the
layer's body whatever `loop_steps * L` is. Its modes are the other scanned
families': the dense oracle (`full`), a window over the pools (`window`;
`prefill` is the same at start 0) and the ragged decode step, whose
attention is `attention_ops.paged_decode_attention_fn` over the plane's rows
(the multi-head arm of `pallas_kernels.paged_attention` where the pool is
whole tiles, the XLA gather elsewhere).

Precision: matmul operands in the weights' dtype (bfloat16 as served),
float32 accumulation; residual stream, norms, rotary, softmax and the gate
in float32.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from .attention_ops import (_gather_pages, _write_rows, kv_cache_append_fn,
                            paged_decode_attention_fn, paged_decode_plan_fn)
from .decoder_common import (_mm, _page_row_index, causal_attention_fn,
                             greedy_fn, rms_norm_fn, yarn_inv_freq_fn)
from ..observability.schema import piece, under_mode
from .registry import ExecContext, register_op

_F32 = jnp.float32

Geometry = collections.namedtuple(
    "Geometry", "num_heads num_kv_heads head_dim rope_theta eps loop_steps")

# the stacked per-layer parameters, in the order the stack op takes them:
# `wqkv` is W_q | W_k | W_v side by side, `w_gate_up` W_gate | W_up
LAYER_PARAMS = ("attn_norm", "attn_post_norm", "wqkv", "wo", "ffn_norm",
                "ffn_post_norm", "w_gate_up", "w_down")


def exit_mass_fn(lam):
    """lam [..., T]: the gate's probability of stopping after each visit ->
    the probability of LEAVING after it, [..., T]: `lam_t prod_{j<t} (1 -
    lam_j)`, the last visit taking what is left, so they sum to 1."""
    stay = jnp.cumprod(1.0 - lam, axis=-1)
    before = jnp.concatenate([jnp.ones_like(stay[..., :1]), stay[..., :-1]],
                             axis=-1)
    return jnp.concatenate([(lam * before)[..., :-1], before[..., -1:]],
                           axis=-1)


def visit_planes_fn(t, num_layers: int):
    """The planes of the K/V pools that visit `t` (an int32 scalar) of the
    `num_layers` layers reads and writes, [num_layers]: `t * num_layers +
    l`, a plane of its own for every visit of every layer."""
    return t * num_layers + jnp.arange(num_layers, dtype=jnp.int32)


def _rotate(x, cos, sin):
    """x [B, S, heads, dh], cos/sin [B, S, 1, dh / 2]: lanes (i, i + dh/2)
    turn together."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@under_mode
def looped_dense_stack_fn(mode: str, tok, pos, emb, head, final_norm, gate_w,
                          gate_b, layer_params: dict, geom: Geometry,
                          pools=None, page_table=None, lens=None, start=None,
                          mask=None, num_pages: int = 0):
    """Run the decoder. `mode`:

      full     tok/pos [B, S]                      -> logits [B, S, V]
      window   + page_table, start, lens (K/V in
               the pools; `prefill` is start 0)    -> last logits [B, V]
      decode   tok/pos [B], page_table, mask [B]   -> logits [B, V]

    Returns a dict: logits; exit_mass, the probability of leaving after
    each visit at the positions the logits are of ([B, S, T] or [B, T]);
    with `pools` (K, V) the two as written. Traced under its mode's scope,
    each piece (observability/schema.PIECES) under its own."""
    decode = mode == "decode"
    paged = mode != "full"
    if decode:
        tok, pos = jnp.reshape(tok, (-1, 1)), jnp.reshape(pos, (-1, 1))
    with piece("embed"):
        x = emb[tok].astype(_F32)
    B, S, _ = x.shape
    L = layer_params["wqkv"].shape[0]
    T = geom.loop_steps
    nh, nkv, dh = geom.num_heads, geom.num_kv_heads, geom.head_dim
    sm_scale = dh ** -0.5
    # the rotation is the positions' alone: worked out once, outside the
    # loops
    ang = pos.astype(_F32)[..., None, None] \
        * jnp.asarray(yarn_inv_freq_fn(dh, geom.rope_theta))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    rel = jnp.arange(S, dtype=jnp.int32)[None, :]
    if paged:
        page_size = pools[0].shape[1]
        page_table = page_table.astype(jnp.int32)
        first = (pos[:, 0] if decode
                 else (start if start is not None
                       else jnp.zeros((B,), jnp.int32))).astype(jnp.int32)
        gpos = first[:, None] + rel                             # [B, S]
        valid = (jnp.reshape(mask, (-1, 1)) > 0) if decode \
            else rel < lens[:, None]
    if mode == "window":
        last = jnp.clip(lens - 1, 0, S - 1)[:, None, None]
    # a decode step's list of page blocks is its table's alone, the same
    # for every plane: worked out once, outside the loops
    plan = paged_decode_plan_fn((B, nh, dh), _F32, pools[0], page_table,
                                first + 1) if decode else None

    def layer(carry, xs):
        plane, p = xs
        if paged:
            x, k_pool, v_pool = carry
            off = plane * num_pages
            table = page_table + off
        else:
            (x,) = carry
        with piece("qkv"):
            u = rms_norm_fn(x, p["attn_norm"], geom.eps)
            qkv = _mm(u, p["wqkv"])
            q = _rotate(qkv[..., :nh * dh].reshape(B, S, nh, dh), cos, sin)
            k = _rotate(qkv[..., nh * dh:(nh + nkv) * dh].reshape(
                B, S, nkv, dh), cos, sin)
            v = qkv[..., (nh + nkv) * dh:].reshape(B, S, nkv, dh)
        if not paged:
            with piece("attend"):
                o = causal_attention_fn(q, k.astype(emb.dtype),
                                        v.astype(emb.dtype),
                                        jnp.zeros((B,), jnp.int32), sm_scale)
        else:
            kd, vd = k.astype(k_pool.dtype), v.astype(v_pool.dtype)
            if decode:
                with piece("kv_write"):
                    k_pool, v_pool = kv_cache_append_fn(
                        k_pool, v_pool, kd[:, 0], vd[:, 0], table, first,
                        valid[:, 0])
                with piece("attend"):
                    o = paged_decode_attention_fn(
                        q[:, 0], k_pool, v_pool, table, first + 1,
                        sm_scale=sm_scale, plan=plan)[:, None]
            else:
                with piece("kv_write"):
                    idx = _page_row_index(page_table, gpos, page_size, off,
                                          valid)
                    at = gpos % page_size
                    k_pool = _write_rows(k_pool, kd.reshape(B, S, -1), idx,
                                         at)
                    v_pool = _write_rows(v_pool, vd.reshape(B, S, -1), idx,
                                         at)
                with piece("kv_gather"):
                    kg = _gather_pages(k_pool, table, nkv)
                    vg = _gather_pages(v_pool, table, nkv)
                with piece("attend"):
                    o = causal_attention_fn(q, kg, vg, first, sm_scale)
        with piece("o_proj"):
            a = x + rms_norm_fn(
                _mm(o.astype(_F32).reshape(B, S, -1), p["wo"]),
                p["attn_post_norm"], geom.eps)
        with piece("mlp"):
            u = rms_norm_fn(a, p["ffn_norm"], geom.eps)
            gu = _mm(u, p["w_gate_up"])
            g, up = jnp.split(gu, 2, axis=-1)
            y = a + rms_norm_fn(
                _mm(g * jax.nn.sigmoid(g) * up, p["w_down"]),
                p["ffn_post_norm"], geom.eps)
        return ((y, k_pool, v_pool) if paged else (y,)), None

    def visit(carry, t):
        carry, _ = jax.lax.scan(layer, carry,
                                (visit_planes_fn(t, L), layer_params))
        with piece("exit_gate"):
            # the final norm closes every visit; the gate reads it
            h = rms_norm_fn(carry[0], final_norm, geom.eps)
            at = jnp.take_along_axis(h, last, axis=1) \
                if mode == "window" else h
            lam = jax.nn.sigmoid(
                jnp.sum(at * gate_w.astype(_F32), axis=-1)
                + gate_b.astype(_F32)[0])
        return (h,) + tuple(carry[1:]), lam

    init = (x,) + (tuple(pools) if paged else ())
    carry, lam = jax.lax.scan(visit, init, jnp.arange(T, dtype=jnp.int32))
    with piece("exit_gate"):
        exit_mass = exit_mass_fn(jnp.moveaxis(lam, 0, -1))     # [B, S', T]
    with piece("head"):
        xn = carry[0]
        if mode == "window":
            xn = jnp.take_along_axis(xn, last, axis=1)
        logits = jnp.einsum("bsh,hv->bsv", xn.astype(head.dtype), head,
                            preferred_element_type=_F32)
    out = {"logits": logits if mode == "full" else logits[:, 0],
           "exit_mass": exit_mass if mode == "full" else exit_mass[:, 0]}
    if paged:
        out["pools"] = carry[1:]
    return out


_POOL_SLOTS = ("KPool", "VPool")


@register_op("looped_dense_stack", grad="none")
def looped_dense_stack_op(ctx: ExecContext):
    """The whole decoder in one op; see `looped_dense_stack_fn`. inputs: Tok,
    Pos, Emb, Head, FinalNorm, GateW, GateB, LayerParams (the
    `LAYER_PARAMS`, in order), and by mode PageTable, Lens, Start, Mask and
    the two pools. attrs: mode, num_pages and the geometry. Outputs:
    NextToken (greedy), Logits, ExitMass, and the pools under their own
    names."""
    mode = ctx.attr("mode")
    geom = Geometry(*(ctx.attr(f) for f in Geometry._fields))
    params = dict(zip(LAYER_PARAMS, ctx.inputs("LayerParams")))
    paged = mode != "full"

    def opt(slot):
        return ctx.input(slot).astype(jnp.int32) if ctx.has_input(slot) \
            else None

    out = looped_dense_stack_fn(
        "window" if mode == "prefill" else mode,
        ctx.input("Tok").astype(jnp.int32),
        ctx.input("Pos").astype(jnp.int32), ctx.input("Emb"),
        ctx.input("Head"), ctx.input("FinalNorm"), ctx.input("GateW"),
        ctx.input("GateB"), params, geom,
        pools=tuple(ctx.input(s) for s in _POOL_SLOTS) if paged else None,
        page_table=opt("PageTable"), lens=opt("Lens"), start=opt("Start"),
        mask=ctx.input("Mask") if ctx.has_input("Mask") else None,
        num_pages=int(ctx.attr("num_pages", 0)))
    res = {"Logits": out["logits"], "ExitMass": out["exit_mass"],
           "NextToken": greedy_fn(out["logits"])}
    if paged:
        res.update({s + "Out": pool
                    for s, pool in zip(_POOL_SLOTS, out["pools"])})
    return res
