"""The mechanisms of a decoder whose every layer runs a Mamba-2 state-space
mixer and a grouped-query attention SIDE BY SIDE on the same normed input
(the "parallel_ssm" block of serving/model.py; Falcon-H1's layer), and the op
that runs a stack of them.

Each mechanism is a plain jax function (`<name>_fn`); the one registered op
that runs them is `parallel_ssm_stack`:

  * `causal_conv`        — the depthwise causal convolution of width
                           `ssm_conv` over a window's `xBC` rows behind the
                           `ssm_conv - 1` rows carried from the token before
                           (the TAIL), and the tail after the window's last
                           REAL token;
  * `conv_token_update`  — the same convolution for ONE token a row, the
                           tail moved on in place in the pool of tails
                           (`pallas_kernels.conv_update` on the chip, its
                           plain form elsewhere);
  * `ssd_scan`           — the recurrence `S_t = a_t S_{t-1} + dt_t x_t (x)
                           B_t`, `y_t = S_t C_t` over a window in CHUNKED
                           form (Mamba-2's SSD): inside a chunk of
                           `ssm_chunk` tokens the decay-masked `C B^T` product
                           times `dt x`, between chunks the carried state;
                           starts from a given state, and a token past the
                           window's length has `dt = 0` (decay 1, input 0), so
                           the state that comes out is the one after the last
                           real token;
  * `ssm_token_update`   — the same recurrence for ONE token a row, in place
                           in the pool of states (`pallas_kernels.ssm_update`
                           on the chip, its plain form elsewhere);
  * `token_recurrence`   — the recurrence token by token (`lax.scan`): what
                           the two above are held to in tests.

`parallel_ssm_stack` composes them with what the other families already have
(RMSNorm, rotate-half rotary, causal and paged grouped-query attention, the
paged K/V write, SwiGLU; imported, not rewritten) into the decoder
(embedding, L identical layers as ONE `lax.scan` over weights stacked `[L,
...]`, final norm, untied head) in the shapes serving needs: dense oracle
(`full`), a window over the pools (`window`; `prefill` is the same at start
0) and the ragged decode step. The config's muP multipliers are applied
where the published modelling code applies them.

What a sequence carries besides K/V lives in two pools of SLOTS, not pages
(`kv_cache.STATE_POOLS`): `S` `[L * slots, heads * N, P]` float32 (a slot's
heads one slab, the state dimension on the sublanes: see
`pallas_kernels.ssm_update`) and the
convolution's tail, `(ssm_conv - 1) * channels` float32 values a slot, kept
as whole (8, 128) tiles `[L * slots, (ssm_conv - 1) * channels / 128, 128]`
where they are whole (`kv_cache.state_pool_shapes`; `[L * slots, (ssm_conv
- 1) * channels]` otherwise). A row's
slot is a feed (`sv_sslot`); a window reads it as its initial state (zeros
where the window starts at position 0) and leaves its final state there, a
decode step updates both in place (`ssm_token_update`, `conv_token_update`)
for its live rows, which come first and are counted from the row mask;
padding rows are given a scratch slot, which a window's padding writes and
a decode step leaves alone.

Precision: matmul operands in the weights' dtype (bfloat16 as served),
float32 accumulation; residual stream, norms, the convolution and its tail,
dt, A, the decay, S and every product of the scan (`Precision.HIGHEST`),
rotary and softmax in float32.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from .attention_ops import (_gather_pages, _write_rows, kv_cache_append_fn,
                            paged_decode_attention_fn, paged_decode_plan_fn)
from .decoder_common import (_mm, _page_row_index, causal_attention_fn,
                             greedy_fn, rms_norm_fn, rotary_fn,
                             yarn_inv_freq_fn)
from ..observability.schema import piece, under_mode
from .registry import ExecContext, register_op

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

Geometry = collections.namedtuple(
    "Geometry", "num_heads num_kv_heads head_dim rope_theta eps ssm_heads "
                "ssm_head_dim ssm_groups ssm_state ssm_conv ssm_chunk "
                "embedding_multiplier lm_head_multiplier ssm_in_multiplier "
                "ssm_out_multiplier attention_in_multiplier "
                "attention_out_multiplier key_multiplier mlp_multipliers "
                "ssm_multipliers")

# the stacked per-layer parameters, in the order the stack op takes them
LAYER_PARAMS = (
    "attn_norm", "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
    "ssm_norm", "w_out", "wq", "wk", "wv", "wo", "ffn_norm", "w_gate",
    "w_up", "w_down")


def inner_width(geom: Geometry) -> int:
    return geom.ssm_heads * geom.ssm_head_dim


def conv_width(geom: Geometry) -> int:
    """Channels the convolution runs over: x, B and C side by side."""
    return inner_width(geom) + 2 * geom.ssm_groups * geom.ssm_state


def column_multipliers(geom: Geometry):
    """`ssm_multipliers` laid over the columns of `w_in`'s product."""
    I, GN = inner_width(geom), geom.ssm_groups * geom.ssm_state
    m = [float(v) for v in geom.ssm_multipliers]
    return jnp.concatenate([
        jnp.full((n,), v, _F32) for n, v in zip(
            (I, I, GN, GN, geom.ssm_heads), m)])


# ---------------------------------------------------------------------------
# the mechanisms
# ---------------------------------------------------------------------------


def causal_conv_fn(xbc, tail, conv_w, conv_b, lens=None):
    """xbc [B, S, C] float32 behind `tail` [B, K-1, C] (the K-1 rows before
    it, zeros at a sequence's start), conv_w [C, K], conv_b [C] ->
    (silu(conv) [B, S, C], the tail after row `lens[b] - 1` [B, K-1, C];
    `lens` None: after the last row)."""
    B, S, _ = xbc.shape
    K = conv_w.shape[1]
    ext = jnp.concatenate([tail.astype(_F32), xbc], axis=1)   # [B, S+K-1, C]
    out = conv_b.astype(_F32)
    for j in range(K):
        out = out + conv_w[:, j].astype(_F32) * ext[:, j:j + S]
    lens = jnp.full((B,), S, jnp.int32) if lens is None else lens
    at = lens[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    new_tail = jnp.take_along_axis(ext, at[:, :, None], axis=1)
    return out * jax.nn.sigmoid(out), new_tail


def conv_update_runs(pool_shape, taps: int) -> bool:
    """Whether a decode step's convolution of `taps` taps comes from
    `pallas_kernels.conv_update`, in place in the pool of tails
    `pool_shape`: its shape gate decides alone, where a Pallas kernel can
    run at all (rows do not enter it: a grid step is a row). The engine
    books `serving.ssm.conv_kernel_layer_steps` by the same answer."""
    from .pallas_kernels import conv_update, workbench

    return (workbench.runnable(conv_update)
            and conv_update.update_supported(tuple(pool_shape), int(taps)))


def conv_token_update_fn(c_pool, idx, xbc, conv_w, conv_b, n_live=None):
    """One token a row, in place: c_pool `[rows, (K - 1) * C / 128, 128]`
    (or `[rows, (K - 1) * C]`: `kv_cache.state_pool_shapes`), idx [B] (each
    row's slot in this layer), xbc [B, C], conv_w [C, K], conv_b [C],
    n_live (int32 scalar; None: B) the count of live rows, which come
    first -> (the pool with the live rows' tails moved on one token,
    silu(conv) [B, C], zeros in a padding row)."""
    from .pallas_kernels import conv_update

    update = conv_update.conv_decode_update \
        if conv_update_runs(c_pool.shape, conv_w.shape[1]) \
        else conv_update._reference
    return update(c_pool, idx, xbc, conv_w, conv_b, n_live)


def conv_window_update_fn(c_pool, idx, xbc, conv_w, conv_b, fresh, lens):
    """A window a row, behind the tail in the row's slot (zeros where
    `fresh` [B]: the window starts a sequence) and leaving there the tail
    after its last real token: c_pool and idx as `conv_token_update_fn`
    takes them, xbc [B, S, C], lens [B] -> (the pool, silu(conv) [B, S,
    C])."""
    B, _, C = xbc.shape
    tail = jnp.where(fresh[:, None, None], 0.0,
                     c_pool[idx].reshape(B, -1, C))
    y, tail = causal_conv_fn(xbc, tail, conv_w, conv_b, lens)
    return c_pool.at[idx].set(tail.reshape((B,) + c_pool.shape[1:])), y


def _decay_and_input(x, dt_raw, dt_bias, a_log, valid=None):
    """x [B, S, H, P], dt_raw [B, S, H] -> (log decay [B, S, H], dt * x);
    a token where `valid` is false has dt 0: decay 1, input 0."""
    dt = jax.nn.softplus(dt_raw + dt_bias.astype(_F32))
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    return -dt * jnp.exp(a_log.astype(_F32)), dt[..., None] * x


def token_recurrence_fn(x, dt_raw, bmat, cmat, dt_bias, a_log, s0,
                        valid=None):
    """The recurrence one token after another: x [B, S, H, P], dt_raw [B,
    S, H], bmat/cmat [B, S, G, N], s0 [B, H, N, P] -> (y [B, S, H, P]
    without the skip term, the state after the last token)."""
    H, G = x.shape[2], bmat.shape[2]
    la, dtx = _decay_and_input(x, dt_raw, dt_bias, a_log, valid)

    def step(s, xs):
        la_t, dtx_t, b_t, c_t = xs
        bh = jnp.repeat(b_t, H // G, axis=1)                    # [B, H, N]
        ch = jnp.repeat(c_t, H // G, axis=1)
        s = jnp.exp(la_t)[:, :, None, None] * s \
            + bh[:, :, :, None] * dtx_t[:, :, None, :]
        return s, jnp.sum(s * ch[:, :, :, None], axis=2)

    swap = lambda a: jnp.moveaxis(a, 1, 0)                      # noqa: E731
    s, y = jax.lax.scan(step, s0.astype(_F32),
                        (swap(la), swap(dtx), swap(bmat), swap(cmat)))
    return jnp.moveaxis(y, 0, 1), s


def ssd_scan_fn(x, dt_raw, bmat, cmat, dt_bias, a_log, s0, chunk: int,
                valid=None):
    """`token_recurrence_fn` in chunks of `chunk` tokens (a window that is
    no multiple of it is padded with tokens of dt 0). Inside a chunk, with
    `cum` the running sum of the log decay: y_t = sum_{s <= t} exp(cum_t -
    cum_s) (C_t . B_s) dt_s x_s + exp(cum_t) C_t . S_in; the chunk hands on
    S_out = exp(cum_last) S_in + sum_s exp(cum_last - cum_s) B_s (x) dt_s
    x_s."""
    B, S, H, P = x.shape
    G, N = bmat.shape[2:]
    Q = min(int(chunk), S)
    pad = -S % Q
    la, dtx = _decay_and_input(x, dt_raw, dt_bias, a_log, valid)
    if pad:
        la, dtx, bmat, cmat = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (la, dtx, bmat, cmat))
    n = (S + pad) // Q
    split = lambda a: jnp.moveaxis(                             # noqa: E731
        a.reshape((B, n, Q) + a.shape[2:]), 1, 0)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one(s, xs):
        la_c, dtx_c, b_c, c_c = xs          # [B, Q, H], [B, Q, H, P], ...
        cum = jnp.cumsum(la_c, axis=1)                          # [B, Q, H]
        cb = jnp.einsum("btgn,bsgn->bgts", c_c, b_c, precision=_HI)
        diff = cum[:, :, None, :] - cum[:, None, :, :]          # [B, t, s, H]
        decay = jnp.exp(jnp.where(causal[None, :, :, None], diff, -jnp.inf))
        m = jnp.repeat(cb, H // G, axis=1) * jnp.moveaxis(decay, 3, 1)
        y = jnp.einsum("bhts,bshp->bthp", m, dtx_c, precision=_HI)
        ch = jnp.repeat(c_c, H // G, axis=2)                    # [B, Q, H, N]
        y = y + jnp.einsum("bthn,bhnp->bthp",
                           ch * jnp.exp(cum)[..., None], s, precision=_HI)
        last = cum[:, -1]                                       # [B, H]
        bh = jnp.repeat(b_c, H // G, axis=2) \
            * jnp.exp(last[:, None] - cum)[..., None]
        s = jnp.exp(last)[:, :, None, None] * s \
            + jnp.einsum("bshn,bshp->bhnp", bh, dtx_c, precision=_HI)
        return s, y

    s, y = jax.lax.scan(one, s0.astype(_F32),
                        (split(la), split(dtx), split(bmat), split(cmat)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, S + pad, H, P)
    return y[:, :S], s


def _update_backend(rows: int, pool_shape, state: int,
                    heads_per_group: int) -> str:
    from .. import tuning
    from .pallas_kernels import ssm_update, workbench

    def runnable():
        return (workbench.runnable(ssm_update)
                and ssm_update.update_supported(pool_shape, state,
                                                heads_per_group))

    def analytic():
        return {"backend": "pallas" if runnable() else "xla"}

    if tuning.mode() == "off":
        backend = analytic()["backend"]
    else:
        key = tuning.canonical_key(
            "ssm_update", tuning.ssm_update_key(
                rows, pool_shape[1] // state, state, pool_shape[2]), "float32",
            tuning.device_kind())
        decision, _tier = tuning.decide(
            "ssm_update", key, prior=analytic, default={"backend": "xla"},
            validate=lambda dd: dd.get("backend") in ("xla", "pallas"))
        backend = decision.get("backend", "xla")
    return backend if backend == "xla" or runnable() else "xla"


def ssm_update_runs(rows: int, pool_shape, heads: int, head_dim: int,
                    groups: int, state: int) -> bool:
    """Whether a decode step of `rows` rows updates its states through
    `pallas_kernels.ssm_update`, in place in the pool `pool_shape` (XLA's
    gather, update and scatter otherwise). The engine books
    `serving.ssm.decode_pad_row_layers` by the same answer."""
    pack = pool_shape[2] // head_dim
    return _update_backend(int(rows), tuple(pool_shape), int(state),
                           heads // groups // pack) == "pallas"


def ssm_token_update_fn(s_pool, idx, x, dt_raw, bmat, cmat, dt_bias, a_log,
                        n_live=None):
    """One token a row, in place: s_pool [rows, H * N, P] (heads narrower
    than the lanes: `pack` of them side by side, [rows, H / pack * N, pack *
    P]; `pallas_kernels.ssm_update`), idx [B] (each row's slot in this
    layer), x [B, H, P], dt_raw [B, H], bmat/cmat [B, G, N], n_live (int32
    scalar; None: B) the count of live rows, which come first -> (the pool
    with the live rows' slots updated, y [B, H, P] without the skip term,
    zeros in a padding row)."""
    from .pallas_kernels import ssm_update

    la, dtx = _decay_and_input(x, dt_raw, dt_bias, a_log)
    B, H, P = x.shape
    G, N = bmat.shape[1:]
    update = ssm_update.ssm_decode_update \
        if ssm_update_runs(B, s_pool.shape, H, P, G, N) \
        else ssm_update._reference
    return update(s_pool, idx, jnp.exp(la), dtx, bmat, cmat, n_live)


def gated_group_norm_fn(y, z, gain, groups: int, eps: float):
    """`y * silu(z)`, then RMSNorm within each of `groups` equal groups of
    channels, times `gain` (the gate BEFORE the norm)."""
    y = y * (z * jax.nn.sigmoid(z))
    g = y.reshape(y.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + eps)
    return g.reshape(y.shape) * gain.astype(_F32)


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


@under_mode
def parallel_ssm_stack_fn(mode: str, tok, pos, emb, head, final_norm,
                          layer_params: dict, geom: Geometry, pools=None,
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

    Returns a dict: logits; with `pools` (K, V, the states, the convolution
    tails) the four as written. Traced under its mode's scope, each piece
    (observability/schema.PIECES) under its own."""
    decode = mode == "decode"
    paged = mode != "full"
    if decode:
        tok, pos = jnp.reshape(tok, (-1, 1)), jnp.reshape(pos, (-1, 1))
    with piece("embed"):
        x = emb[tok].astype(_F32) * geom.embedding_multiplier
    B, S, _ = x.shape
    L = layer_params["w_in"].shape[0]
    nh, nkv, dh = geom.num_heads, geom.num_kv_heads, geom.head_dim
    Hs, P, G, N = (geom.ssm_heads, geom.ssm_head_dim, geom.ssm_groups,
                   geom.ssm_state)
    I, C, K = inner_width(geom), conv_width(geom), geom.ssm_conv
    sm_scale = dh ** -0.5
    inv_freq = yarn_inv_freq_fn(dh, geom.rope_theta)
    columns = column_multipliers(geom)
    gate_m, down_m = (float(v) for v in geom.mlp_multipliers)
    rel = jnp.arange(S, dtype=jnp.int32)[None, :]
    valid = None
    if paged:
        page_size = pools[0].shape[1]
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
        # alone: every layer's decode call shares one plan, a constant of
        # the scanned body
        walk = paged_decode_plan_fn((B, nh, dh), _F32, pools[0], page_table,
                                    first + 1) if decode else None

    def layer(carry, xs):
        l, p = xs
        if paged:
            x, k_pool, v_pool, s_pool, c_pool = carry
            off = l * num_pages
            table = page_table + off
            row = l * num_slots + slot                          # [B]
        else:
            (x,) = carry
        with piece("proj"):
            z = rms_norm_fn(x, p["attn_norm"], geom.eps)
            proj = _mm(z * geom.ssm_in_multiplier, p["w_in"]) * columns
            gate, xbc, dt_raw = (proj[..., :I], proj[..., I:I + C],
                                 proj[..., I + C:])
            za = z * geom.attention_in_multiplier
            q = rotary_fn(_mm(za, p["wq"]).reshape(B, S, nh, dh), pos,
                          inv_freq, dh)
            k = rotary_fn((_mm(za, p["wk"]) * geom.key_multiplier).reshape(
                B, S, nkv, dh), pos, inv_freq, dh)
            v = _mm(za, p["wv"]).reshape(B, S, nkv, dh)
        with piece("conv"):
            if decode:
                c_pool, xbc = conv_token_update_fn(
                    c_pool, row, xbc[:, 0], p["conv_w"], p["conv_b"],
                    n_live)
                xbc = xbc[:, None]
            elif paged:
                c_pool, xbc = conv_window_update_fn(
                    c_pool, row, xbc, p["conv_w"], p["conv_b"], fresh, count)
            else:
                xbc, _ = causal_conv_fn(xbc, jnp.zeros((B, K - 1, C), _F32),
                                        p["conv_w"], p["conv_b"])
        xs_ = xbc[..., :I].reshape(B, S, Hs, P)
        bm = xbc[..., I:I + G * N].reshape(B, S, G, N)
        cm = xbc[..., I + G * N:].reshape(B, S, G, N)
        if decode:
            with piece("ssm_update"):
                s_pool, y = ssm_token_update_fn(
                    s_pool, row, xs_[:, 0], dt_raw[:, 0], bm[:, 0], cm[:, 0],
                    p["dt_bias"], p["a_log"], n_live)
                y = y[:, None]
        else:
            with piece("ssm_scan"):
                if paged:
                    s0 = jnp.where(fresh[:, None, None, None], 0.0,
                                   s_pool[row].reshape(B, Hs, N, P))
                else:
                    s0 = jnp.zeros((B, Hs, N, P), _F32)
                y, s1 = ssd_scan_fn(xs_, dt_raw, bm, cm, p["dt_bias"],
                                    p["a_log"], s0, geom.ssm_chunk, valid)
                if paged:
                    s_pool = s_pool.at[row].set(s1.reshape(B, Hs * N, P))
        with piece("proj"):
            y = y + p["d_skip"].astype(_F32)[:, None] * xs_
            y = gated_group_norm_fn(y.reshape(B, S, I), gate, p["ssm_norm"],
                                    G, geom.eps)
            ssm = _mm(y, p["w_out"]) * geom.ssm_out_multiplier
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
                        sm_scale=sm_scale, plan=walk)[:, None]
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
        with piece("proj"):
            att = _mm(o.astype(_F32).reshape(B, S, -1), p["wo"]) \
                * geom.attention_out_multiplier
            h = x + ssm + att
        with piece("mlp"):
            zf = rms_norm_fn(h, p["ffn_norm"], geom.eps)
            g = _mm(zf, p["w_gate"]) * gate_m
            y = h + _mm(g * jax.nn.sigmoid(g) * _mm(zf, p["w_up"]),
                        p["w_down"]) * down_m
        carry = (y, k_pool, v_pool, s_pool, c_pool) if paged else (y,)
        return carry, None

    init = (x,) + (tuple(pools) if paged else ())
    carry, _ = jax.lax.scan(layer, init,
                            (jnp.arange(L, dtype=jnp.int32), layer_params))
    with piece("head"):
        xn = rms_norm_fn(carry[0], final_norm, geom.eps)
        if mode == "window":
            at = jnp.clip(lens - 1, 0, S - 1)[:, None, None]
            xn = jnp.take_along_axis(xn, at, axis=1)
        logits = jnp.einsum("bsh,hv->bsv", xn.astype(head.dtype), head,
                            preferred_element_type=_F32) \
            * geom.lm_head_multiplier
    out = {"logits": logits if mode == "full" else logits[:, 0]}
    if paged:
        out["pools"] = carry[1:]
    return out


# ---------------------------------------------------------------------------
# registered op
# ---------------------------------------------------------------------------

_POOL_SLOTS = ("KPool", "VPool", "SPool", "CPool")


@register_op("parallel_ssm_stack", grad="none")
def parallel_ssm_stack_op(ctx: ExecContext):
    """The whole decoder in one op; see `parallel_ssm_stack_fn`. inputs:
    Tok, Pos, Emb, Head, FinalNorm, LayerParams (the `LAYER_PARAMS`, in
    order), and by mode PageTable, Lens, Start, Mask, StateSlot and the four
    pools. attrs: mode, num_pages, num_slots and the geometry. Outputs:
    NextToken (greedy), Logits, and the pools under their own names."""
    mode = ctx.attr("mode")
    geom = Geometry(*(ctx.attr(f) for f in Geometry._fields))
    params = dict(zip(LAYER_PARAMS, ctx.inputs("LayerParams")))
    paged = mode != "full"

    def opt(slot):
        return ctx.input(slot).astype(jnp.int32) if ctx.has_input(slot) \
            else None

    out = parallel_ssm_stack_fn(
        "window" if mode == "prefill" else mode,
        ctx.input("Tok").astype(jnp.int32),
        ctx.input("Pos").astype(jnp.int32), ctx.input("Emb"),
        ctx.input("Head"), ctx.input("FinalNorm"), params, geom,
        pools=tuple(ctx.input(s) for s in _POOL_SLOTS) if paged else None,
        page_table=opt("PageTable"), lens=opt("Lens"), start=opt("Start"),
        mask=ctx.input("Mask") if ctx.has_input("Mask") else None,
        state_slot=opt("StateSlot"),
        num_pages=int(ctx.attr("num_pages", 0)),
        num_slots=int(ctx.attr("num_slots", 0)))
    res = {"Logits": out["logits"],
           "NextToken": greedy_fn(out["logits"])}
    if paged:
        res.update({s + "Out": pool
                    for s, pool in zip(_POOL_SLOTS, out["pools"])})
    return res


@register_op("state_slot_copy", grad="none")
def state_slot_copy_op(ctx: ExecContext):
    """One slot of recurrent state onto another, in every layer and in
    place: rows `l * num_slots + Src` of SPool and CPool to rows `l *
    num_slots + Dst` (taking a snapshot, restoring from one). A slice and
    an update a layer and pool: as a gather of the six rows XLA cut the
    whole 2 GB pool into quarters first, 5 ms a copy on the v5e (my chip
    run, PR 37)."""
    src = ctx.input("Src").astype(jnp.int32)[0]
    dst = ctx.input("Dst").astype(jnp.int32)[0]
    slots = int(ctx.attr("num_slots"))
    out = {}
    for slot in ("SPool", "CPool"):
        pool = ctx.input(slot)
        for l in range(pool.shape[0] // slots):
            row = jax.lax.dynamic_slice_in_dim(pool, l * slots + src, 1, 0)
            pool = jax.lax.dynamic_update_slice_in_dim(
                pool, row, l * slots + dst, 0)
        out[slot + "Out"] = pool
    return out
