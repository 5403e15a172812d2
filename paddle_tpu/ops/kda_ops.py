"""The mechanisms of a decoder whose layers keep TWO kinds of cache for one
sequence: Kimi Delta Attention layers, whose cache is a MATRIX STATE a head
that every token rewrites (a slot of `kv_cache.STATE_POOLS`), beside
multi-head latent attention layers, whose cache is one compressed row a
token (pages of `kv_cache.LATENT_POOL`); a dense SwiGLU or group-limited
experts behind either (the "kda_moe" block of serving/model.py), and the op
that runs a stack of them.

Every layer `l`: `x <- x + Mixer_l(RMSNorm(x))`, `x <- x + MLP_l(RMSNorm(
x))`, the mixer by the layer's character in `mixers` (`K` | `L`), the MLP by
its character in `mlps` (`D` | `E`).

  * `K`  Kimi Delta Attention (Kimi Linear, arXiv:2510.26692). `q~, k~, v~ =
         z W_q, z W_k, z W_v`, the three side by side through ONE causal
         depthwise convolution and SiLU (`parallel_ssm_ops.causal_conv_fn`
         and its carried tail); a head: `q = L2norm(q') K^-0.5`, `k =
         L2norm(k')`. Decay, a value a head AND key channel (`kda_gate_fn`):
         `log a = lower_bound * sigmoid(exp(A_log_h) (z W_f + dt_bias))`, in
         `(lower_bound, 0)`; step `beta = sigmoid(z W_b)` a head. The state
         `S [K, V]` a head moves by the gated delta rule

             S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
             o_t = S_t^T q_t

         one token at a time in place in the pool for a decode step
         (`kda_token_update_fn`: `pallas_kernels.kda_update`, live rows
         only), in chunks for a window (`kda_chunk_scan_fn`), and as the
         plain recurrence for tests (`kda_token_recurrence_fn`). Out: `y =
         (RMSNorm_head(o) * sigmoid(z W_g)) W_o`, the norm over a head's V
         values with one learned gain `[V]` (`gated_head_norm_fn`: the gate
         AFTER the norm).
  * `L`  "latent_moe"'s attention WITHOUT a query latent and without an
         indexer (`latent_moe_ops.direct_queries_fn`,
         `.unindexed_attention_fn`: a window in the expanded form over key
         blocks, a decode row in the absorbed form over all its pages, a
         run of pages that rows share once), with a sigmoid gate a head on
         its output (`hybrid_moe_ops`' head gate).
  * `D` / `E`  "latent_moe"'s feed-forward (`latent_moe_ops._feed_forward`):
         a dense SwiGLU, or sigmoid scores with a selection bias, the best
         groups, the top k inside them, the chosen scores normalised times a
         factor, over the experts THIS chip holds (`experts_held`), beside
         one shared expert.

THE CHUNKED FORM (`kda_chunk_scan_fn`) is the WY / UT-transform form of the
public kernel (`fla/ops/kda`). With `G_t` the running sum of `log a` inside
a chunk of C tokens and `S_0` the state that enters it, the pseudo-values
`u_t = beta_t (v_t - (Diag(a_t) S_{t-1})^T k_t)` solve the unit lower
triangular system `(I + Diag(beta) A) U = Diag(beta) (V - (K . e^G) S_0)`,
`A[t, i] = sum_c k_t[c] k_i[c] e^(G_t[c] - G_i[c])` for `i < t`; then `o_t =
S_0^T (q_t . e^G_t) + sum_{i <= t} B[t, i] u_i` (B as A with q_t for k_t)
and `S_C = Diag(e^G_C) S_0 + sum_i (k_i . e^(G_C - G_i)) u_i^T`. `e^(G_t -
G_i)` is NEVER formed as `e^G_t / e^G_i`: at the lower bound of -5 a token a
64-token chunk spans `e^-320`, below float32. A's and B's rows are taken a
SUB-BLOCK of `sub` = 16 tokens at a time against the reference point `r` =
`G` at the sub-block's MIDDLE token: rows `k_t e^(G_t - r)` (an exponent in
`+-sub / 2 x lower_bound` = [-40, 40]) times columns `k_i e^(r - G_i)` (<=
0 for every earlier sub-block, in [-40, 40] inside the same one), so only
differences of logs inside a sub-block are ever exponentiated, and a key's
small channels (0.001 x `e^-40`) stay normal numbers; with the reference
before the sub-block's first token they reached `e^-80` x 0.001 and were
flushed to zero, an error of 5e-4 in `o`. The system is solved by forward
substitution inside
the 16 x 16 diagonal blocks and by blocks across them, for the right-hand
sides `beta V` and `beta (K . e^G)` at once (the second is multiplied by
`S_0` inside the scan over chunks, which is all that is sequential).

`kda_moe_stack` composes the layers (embedding, the layers one after
another by the plan over weights stacked BY KIND, final norm, untied head)
in the shapes serving needs: dense oracle (`full`), a window over the pools
(`window`; `prefill` is the same at start 0) and the ragged decode step.
The latent pool is stacked over the `L` layers only, the two pools of
recurrent state over the `K` layers only; a request's `routes` are
`[positions, expert layers, k]`.

Precision: matmul operands in the weights' dtype (bfloat16 as served),
float32 accumulation; residual stream, norms, the router and its bias, the
convolution and its tail, the gate, the decay, beta, S in the pool and in
every update, every product of the chunked form (`Precision.HIGHEST`),
rotary, softmax in float32.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from . import latent_moe_ops as lat
from .decoder_common import _mm, greedy_fn, rms_norm_fn
from .parallel_ssm_ops import (causal_conv_fn, conv_token_update_fn,
                               conv_window_update_fn)
from ..observability.schema import piece, under_mode
from .registry import ExecContext, register_op

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

KDA, LATENT = "K", "L"
DENSE, EXPERTS = "D", "E"

Geometry = collections.namedtuple(
    "Geometry", "mixers mlps num_heads nope_dim rope_dim v_dim kv_rank "
                "rope_theta eps kda_heads kda_head_dim kda_conv kda_chunk "
                "kda_sub_chunk kda_lower_bound experts_per_token "
                "expert_groups groups_per_token routed_scaling experts_held")

# the stacked parameters of each kind, in the order the stack op takes them
KDA_PARAMS = ("w_qkv", "conv_w", "w_f", "dt_bias", "a_log", "w_b", "w_g",
              "o_norm", "w_o")
LATENT_PARAMS = ("wq", "wkv_a", "kv_norm", "wkv_b", "w_gate_h", "wo")
DENSE_PARAMS = ("ffn_norm",) + lat.DENSE_PARAMS
MOE_PARAMS = ("ffn_norm",) + lat.MOE_PARAMS
EXPERT_PARAMS = lat.EXPERT_PARAMS


def latent_geometry(geom: Geometry) -> lat.Geometry:
    """What `latent_moe_ops`' functions read of a geometry: the latent
    attention's sizes (no YaRN, no indexer) and the router's."""
    return lat.Geometry(
        num_heads=geom.num_heads, nope_dim=geom.nope_dim,
        rope_dim=geom.rope_dim, v_dim=geom.v_dim, kv_rank=geom.kv_rank,
        rope_theta=geom.rope_theta, yarn=(), softmax_mscale=1.0,
        eps=geom.eps, index_heads=0, index_dim=0, index_topk=0,
        experts_per_token=geom.experts_per_token,
        expert_groups=geom.expert_groups,
        groups_per_token=geom.groups_per_token,
        routed_scaling=geom.routed_scaling, experts_held=geom.experts_held)


# ---------------------------------------------------------------------------
# the mechanisms
# ---------------------------------------------------------------------------


def l2_norm_fn(x, eps: float = 1e-6):
    """x / sqrt(sum x^2 + eps) over the last axis (the public kernel's)."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


def kda_gate_fn(f_raw, a_log, dt_bias, lower_bound: float):
    """The log decay: f_raw [..., H * K] float32 (`z W_f`), a_log [H],
    dt_bias [H * K] -> [..., H, K] = `lower_bound * sigmoid(exp(A_log_h)
    (f + dt_bias))`, in `(lower_bound, 0)` (`kda_safe_gate`)."""
    H = a_log.shape[0]
    g = (f_raw + dt_bias.astype(_F32)).reshape(f_raw.shape[:-1] + (H, -1))
    return float(lower_bound) * jax.nn.sigmoid(
        jnp.exp(a_log.astype(_F32))[:, None] * g)


def gated_head_norm_fn(o, gate_raw, gain, eps: float):
    """o [..., H, V], gate_raw [..., H * V], gain [V] -> [..., H * V]:
    RMSNorm over a head's V values times the gain, THEN times
    `sigmoid(gate)`."""
    y = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + eps) * gain.astype(_F32)
    return y.reshape(gate_raw.shape) * jax.nn.sigmoid(gate_raw)


def _silence(log_a, beta, valid):
    """A token where `valid` is false leaves the state alone: decay 1 and
    step 0."""
    if valid is None:
        return log_a, beta
    return (jnp.where(valid[..., None, None], log_a, 0.0),
            jnp.where(valid[..., None], beta, 0.0))


def kda_token_recurrence_fn(q, k, v, log_a, beta, s0, valid=None):
    """The recurrence one token after another: q, k, log_a [B, S, H, K], v
    [B, S, H, V], beta [B, S, H], s0 [B, H, K, V] -> (o [B, S, H, V], the
    state after the last token)."""
    log_a, beta = _silence(log_a, beta, valid)

    def step(s, xs):
        q_t, k_t, v_t, la_t, b_t = xs
        s = jnp.exp(la_t)[..., None] * s
        u = jnp.sum(s * k_t[..., None], axis=2)                 # [B, H, V]
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - u))[:, :, None, :]
        return s, jnp.sum(s * q_t[..., None], axis=2)

    swap = lambda a: jnp.moveaxis(a.astype(_F32), 1, 0)         # noqa: E731
    s, o = jax.lax.scan(step, s0.astype(_F32),
                        tuple(swap(a) for a in (q, k, v, log_a, beta)))
    return jnp.moveaxis(o, 0, 1), s


def kda_chunk_scan_fn(q, k, v, log_a, beta, s0, chunk: int, sub: int,
                      lower_bound: float, valid=None):
    """`kda_token_recurrence_fn` in chunks of `chunk` tokens and sub-blocks
    of `sub` (the module docstring has the algebra; `lower_bound` the least
    log decay a token, which bounds the one exponent taken upwards). A
    window that is no multiple of the chunk is padded with silent tokens; a
    window shorter than a chunk is ONE chunk of whole sub-blocks."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    log_a, beta = _silence(log_a, beta, valid)
    sub = int(sub)
    C = int(chunk) if S >= int(chunk) else -(-S // sub) * sub
    pad = -S % C
    if pad:
        q, k, v, log_a, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, log_a, beta))
    n, nb = (S + pad) // C, C // sub
    # [B, n, H, C, width]: a chunk of one head is a matrix
    arr = lambda a: jnp.moveaxis(                               # noqa: E731
        a.astype(_F32).reshape(B, n, C, H, -1), 3, 2)
    q, k, v, g, b = arr(q), arr(k), arr(v), arr(log_a), arr(beta[..., None])
    G = jnp.cumsum(g, axis=-2)                      # inclusive, <= 0
    blocks = lambda a: a.reshape(a.shape[:3] + (nb, sub, a.shape[-1]))  # noqa
    Gb = blocks(G)
    # a sub-block's reference point: G at its middle token
    half = sub // 2
    ref = Gb[..., half - 1, :]                      # [B, n, H, nb, K]
    reach = -float(lower_bound) * (sub - half)      # 40 at 16 tokens of -5
    rows = jnp.exp(Gb - ref[..., None, :])          # e^[-reach, reach]
    # columns against every sub-block's reference: later tokens (masked
    # below) held to what a sub-block's own can reach
    cols = k[..., None, :, :] * jnp.exp(jnp.minimum(
        ref[..., None, :] - G[..., None, :, :], reach))  # [B,n,H,nb,C,K]
    pair = lambda x: jnp.einsum(                                # noqa: E731
        "...tk,...ik->...ti", blocks(x) * rows, cols,
        precision=_HI).reshape(x.shape[:3] + (C, C))
    at = jnp.arange(C, dtype=jnp.int32)
    lower = jnp.where(at[:, None] > at[None, :], pair(k) * b, 0.0)
    attend = jnp.where(at[:, None] >= at[None, :], pair(q), 0.0)
    # (I + lower)^-1 of the diagonal sub-blocks, by forward substitution
    diag = jnp.stack([lower[..., r * sub:(r + 1) * sub,
                            r * sub:(r + 1) * sub] for r in range(nb)],
                     axis=3)                        # [B,n,H,nb,sub,sub]
    eye = jnp.eye(sub, dtype=_F32)
    inv = jnp.broadcast_to(eye, diag.shape)
    for t in range(1, sub):
        inv = inv.at[..., t, :].set(eye[t] - jnp.einsum(
            "...i,...ij->...j", diag[..., t, :], inv, precision=_HI))
    # the two right-hand sides at once, solved by blocks
    rhs = blocks(b * jnp.concatenate([v, k * jnp.exp(G)], axis=-1))
    solved = []
    for r in range(nb):
        acc = rhs[..., r, :, :]
        for s_, x in enumerate(solved):
            acc = acc - jnp.einsum(
                "...ti,...iw->...tw",
                lower[..., r * sub:(r + 1) * sub, s_ * sub:(s_ + 1) * sub],
                x, precision=_HI)
        solved.append(jnp.einsum("...ti,...iw->...tw", inv[..., r, :, :],
                                 acc, precision=_HI))
    solved = jnp.concatenate(solved, axis=-2)                   # [.., C, V+K]
    u_v, w = solved[..., :V], solved[..., V:]
    last = G[..., -1:, :]                                       # [B,n,H,1,K]
    q_in, k_out = q * jnp.exp(G), k * jnp.exp(last - G)

    def one(s, xs):
        u_c, w_c, q_c, k_c, b_c, decay = xs
        u = u_c - jnp.einsum("bhtk,bhkv->bhtv", w_c, s, precision=_HI)
        o = jnp.einsum("bhtk,bhkv->bhtv", q_c, s, precision=_HI) \
            + jnp.einsum("bhti,bhiv->bhtv", b_c, u, precision=_HI)
        s = decay[..., None] * s \
            + jnp.einsum("bhtk,bhtv->bhkv", k_c, u, precision=_HI)
        return s, o

    swap = lambda a: jnp.moveaxis(a, 1, 0)                      # noqa: E731
    s, o = jax.lax.scan(
        one, s0.astype(_F32),
        tuple(swap(a) for a in (u_v, w, q_in, k_out, attend,
                                jnp.exp(last[..., 0, :]))))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, S + pad, H, V)
    return o[:, :S], s


def kda_update_runs(pool_shape, key_dim: int) -> bool:
    """Whether a decode step updates its states through
    `pallas_kernels.kda_update`, in place in the pool `pool_shape` (XLA's
    gather, update and scatter otherwise): its shape gate decides alone,
    where a Pallas kernel can run at all (rows do not enter it: a grid step
    is a row)."""
    from .pallas_kernels import kda_update, workbench

    return (workbench.runnable(kda_update)
            and kda_update.update_supported(tuple(pool_shape), int(key_dim)))


def kda_token_update_fn(s_pool, idx, q, k, v, log_a, beta, n_live=None):
    """One token a row, in place: s_pool [rows, H * K, V], idx [B] (each
    row's slot in this layer), q, k, log_a [B, H, K], v [B, H, V], beta [B,
    H], n_live (int32 scalar; None: B) the count of live rows, which come
    first -> (the pool with the live rows' slots updated, o [B, H, V], zeros
    in a padding row)."""
    from .pallas_kernels import kda_update

    update = kda_update.kda_decode_update \
        if kda_update_runs(s_pool.shape, k.shape[-1]) \
        else kda_update._reference
    return update(s_pool, idx, q, k, v, jnp.exp(log_a), beta, n_live)


def head_gate_fn(o, gate_raw):
    """o [..., nh, v] times `sigmoid(gate)` a head, gate_raw [..., nh]."""
    return o * jax.nn.sigmoid(gate_raw)[..., None]


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


@under_mode
def kda_moe_stack_fn(mode: str, tok, pos, emb, head, final_norm, norms,
                     kda: dict, latent: dict, dense: dict, moe: dict,
                     experts: tuple, geom: Geometry, pools=None,
                     page_table=None, lens=None, start=None, mask=None,
                     state_slot=None, num_pages: int = 0,
                     num_slots: int = 0):
    """Run the decoder. `mode`:

      full     tok/pos [B, S]                          -> logits [B, S, V]
      window   + page_table, start, lens, state_slot
               (latent rows and the state in the
               pools; `prefill` is start 0)            -> last logits [B, V]
      decode   tok/pos [B], page_table, mask [B],
               state_slot [B]                          -> logits [B, V]

    `norms` `[L, H]` the mixers' pre-norms over all layers; `kda`, `latent`,
    `dense`, `moe` and `experts` the weights of a kind stacked over the
    layers of that kind. Returns a dict: logits; routes ([B, S, L_experts,
    k], decode [B, L_experts, k]); with `pools` (the latent rows, the
    states, the convolution tails) the three as written. Traced under its
    mode's scope, each piece (observability/schema.PIECES) under its own."""
    decode = mode == "decode"
    paged = mode != "full"
    if decode:
        tok, pos = jnp.reshape(tok, (-1, 1)), jnp.reshape(pos, (-1, 1))
    with piece("embed"):
        x = emb[tok].astype(_F32)
    B, S, H = x.shape
    lg = latent_geometry(geom)
    nh = geom.num_heads
    Hk, D = geom.kda_heads, geom.kda_head_dim
    I, taps = Hk * D, geom.kda_conv
    tag = "decode" if decode else "prefill"
    dtype = emb.dtype                       # the cache rows' dtype
    rel = jnp.arange(S, dtype=jnp.int32)[None, :]
    valid = n_live = None
    latent_pool = s_pool = c_pool = None
    plan = None
    if paged:
        latent_pool, s_pool, c_pool = pools
        page_table = page_table.astype(jnp.int32)
        first = (pos[:, 0] if decode
                 else (start if start is not None
                       else jnp.zeros((B,), jnp.int32))).astype(jnp.int32)
        gpos = first[:, None] + rel                             # [B, S]
        valid = (jnp.reshape(mask, (-1, 1)) > 0) if decode \
            else rel < lens[:, None]
        count = valid[:, 0].astype(jnp.int32) if decode else lens
        # a decode step's live rows come first (`engine._decode_once`)
        n_live = jnp.sum(valid, dtype=jnp.int32) if decode else None
        slot = state_slot.astype(jnp.int32)                     # [B]
        # a window at position 0 starts a sequence: its state is zeros
        fresh = (first == 0) & (not decode)
        # which rows read the same pages is the tables' alone, worked out
        # once for all latent layers
        if decode:
            plan = lat.decode_plan_fn(page_table, (first + 1) * count,
                                      (B, nh, geom.kv_rank),
                                      latent_pool.shape, dtype, geom.rope_dim)
    else:
        gpos = jnp.broadcast_to(rel, (B, S))
    no_bias = jnp.zeros((3 * I,), _F32)     # the convolution has none
    routes = []
    seen = {KDA: 0, LATENT: 0, DENSE: 0, EXPERTS: 0}
    for l, (mixer, mlp) in enumerate(zip(geom.mixers, geom.mlps)):
        i, j = seen[mixer], seen[mlp]
        seen[mixer] += 1
        seen[mlp] += 1
        with piece("proj"):
            z = rms_norm_fn(x, norms[l], geom.eps)
        if mixer == KDA:
            p = {k: w[i] for k, w in kda.items()}
            if paged:
                row = i * num_slots + slot                      # [B]
            with piece("proj"):
                qkv = _mm(z, p["w_qkv"])                        # [B, S, 3 I]
                gate_raw = _mm(z, p["w_g"])
            with piece("kda_gate"):
                log_a = kda_gate_fn(_mm(z, p["w_f"]), p["a_log"],
                                    p["dt_bias"], geom.kda_lower_bound)
                beta = jax.nn.sigmoid(_mm(z, p["w_b"]))         # [B, S, Hk]
            with piece("conv"):
                if decode:
                    c_pool, qkv = conv_token_update_fn(
                        c_pool, row, qkv[:, 0], p["conv_w"], no_bias, n_live)
                    qkv = qkv[:, None]
                elif paged:
                    c_pool, qkv = conv_window_update_fn(
                        c_pool, row, qkv, p["conv_w"], no_bias, fresh, lens)
                else:
                    qkv, _ = causal_conv_fn(
                        qkv, jnp.zeros((B, taps - 1, 3 * I), _F32),
                        p["conv_w"], no_bias)
            with piece("kda_gate"):
                heads = lambda a: a.reshape(B, S, Hk, D)        # noqa: E731
                q = l2_norm_fn(heads(qkv[..., :I])) * D ** -0.5
                k = l2_norm_fn(heads(qkv[..., I:2 * I]))
                v = heads(qkv[..., 2 * I:])
            if decode:
                with piece("kda_update"):
                    s_pool, o = kda_token_update_fn(
                        s_pool, row, q[:, 0], k[:, 0], v[:, 0], log_a[:, 0],
                        beta[:, 0], n_live)
                    o = o[:, None]
            else:
                with piece("kda_scan"):
                    if paged:
                        s0 = jnp.where(fresh[:, None, None, None], 0.0,
                                       s_pool[row].reshape(B, Hk, D, D))
                    else:
                        s0 = jnp.zeros((B, Hk, D, D), _F32)
                    o, s1 = kda_chunk_scan_fn(
                        q, k, v, log_a, beta, s0, geom.kda_chunk,
                        geom.kda_sub_chunk, geom.kda_lower_bound, valid)
                    if paged:
                        s_pool = s_pool.at[row].set(
                            s1.reshape(B, Hk * D, D))
            with piece("proj"):
                f = _mm(gated_head_norm_fn(o, gate_raw, p["o_norm"],
                                           geom.eps), p["w_o"])
        else:
            p = {k: w[i] for k, w in latent.items()}
            with piece("proj"):
                q_nope, q_rope, c_kv, k_rope = lat.direct_queries_fn(
                    z, p, pos, lg)
                gate_h = _mm(z, p["w_gate_h"])                  # [B, S, nh]
            if paged:
                latent_pool, o = lat.unindexed_attention_fn(
                    q_nope, q_rope, c_kv, k_rope, p["wkv_b"], lg, dtype,
                    latent_pool, page_table, i * num_pages, gpos, valid,
                    (first + 1) * count if decode else None, plan)
            else:
                _, o = lat.unindexed_attention_fn(
                    q_nope, q_rope, c_kv, k_rope, p["wkv_b"], lg, dtype,
                    gpos=gpos)
            with piece("proj"):
                f = _mm(head_gate_fn(o, gate_h).reshape(B, S, -1), p["wo"])
        x = x + f
        if mlp == DENSE:
            x, _ = lat._feed_forward(
                x, True, {k: w[j] for k, w in dense.items()}, experts, j,
                lg, tag)
        else:
            x, ids = lat._feed_forward(
                x, False, {k: w[j] for k, w in moe.items()}, experts, j, lg,
                tag)
            routes.append(ids)
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
        out["pools"] = (latent_pool, s_pool, c_pool)
    return out


# ---------------------------------------------------------------------------
# registered op
# ---------------------------------------------------------------------------

_POOL_SLOTS = ("LatentPool", "SPool", "CPool")


@register_op("kda_moe_stack", grad="none")
def kda_moe_stack_op(ctx: ExecContext):
    """The whole decoder in one op; see `kda_moe_stack_fn`. inputs: Tok,
    Pos, Emb, Head, FinalNorm, Norms, KdaParams, LatentParams, DenseParams,
    MoeParams, Experts (each the `*_PARAMS`, in order), and by mode
    PageTable, Lens, Start, Mask, StateSlot and the three pools. attrs:
    mode, num_pages, num_slots and the geometry. Outputs: NextToken
    (greedy), Logits, Routes, and the pools under their own names."""
    mode = ctx.attr("mode")
    geom = Geometry(*(ctx.attr(f) for f in Geometry._fields))
    paged = mode != "full"

    def opt(slot):
        return ctx.input(slot).astype(jnp.int32) if ctx.has_input(slot) \
            else None

    out = kda_moe_stack_fn(
        "window" if mode == "prefill" else mode,
        ctx.input("Tok").astype(jnp.int32),
        ctx.input("Pos").astype(jnp.int32), ctx.input("Emb"),
        ctx.input("Head"), ctx.input("FinalNorm"), ctx.input("Norms"),
        dict(zip(KDA_PARAMS, ctx.inputs("KdaParams"))),
        dict(zip(LATENT_PARAMS, ctx.inputs("LatentParams"))),
        dict(zip(DENSE_PARAMS, ctx.inputs("DenseParams"))),
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
