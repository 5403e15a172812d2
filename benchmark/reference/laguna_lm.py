"""Plain reference for the served stage of Laguna-XS.2
(`configs/laguna_xs2.json`): one teacher-forced causal forward over prompt +
served tokens in float32, `jax.default_matmul_precision("highest")`, no
cache, no kernel, no paged anything, one sequence at a time, independent of
paddle_tpu (it reads the engine's weights by name and nothing else).

One layer l, for token t of a sequence (x the residual stream in float32,
x~ = RMSNorm(x), eps 1e-6; n_h(l) query heads, 8 key/value heads of 128,
query head h reads key/value head g(h) = h // (n_h / 8)):

 1. q = rope_t(x~ W_q) [n_h x 128], k = rope_t(x~ W_k) [8 x 128], v = x~ W_v.
    A SLIDING layer (64 heads): rotary over the whole head, rotate-half
    pairs (i, i + 64), lane pair i turning 10,000^(-i/64) a position.
    A FULL layer (48 heads): rotary over the first 64 lanes, pairs (i, i +
    32), under YaRN: pair i turns f_i = 500,000^(-i/32) a position where
    i <= low, f_i / 64 where i >= high, and f_i ((1 - r_i) + r_i / 64) with
    r_i = (i - low) / (high - low) between; low = floor(d(beta_fast)), high
    = ceil(d(beta_slow)), d(b) = 64 ln(4096 / (2 pi b)) / (2 ln 500,000):
    low 5, high 16. cos and sin are multiplied by attention_factor 1.41589.
 2. a_h = softmax_s(q_h . k_g(h),s / sqrt(128)) v_g(h),s over s <= t, and
    in a sliding layer t - s < 512.
 3. g = sigmoid(x~ W_g) [n_h];  x <- x + (concat_h g_h a_h) W_o.
 4. layer 0:      x <- x + W_d(silu(W_g' x~) * (W_u x~)), width 8,192.
    other layers: s = sigmoid(W_r x~) [256]; T = the 8 largest of s + b,
    ties to the lower expert; w_e = 2.5 s_e / sum_T s;
    x <- x + sum_{e in T} w_e E_e(x~) + E_shared(x~), SwiGLU of width 512.
 5. after the last layer RMSNorm and the untied head.

Departures from the published description, each also in the
configuration's file: only layers 0-4 run and the final norm and head are
applied to layer 4's output; the gate is read as one sigmoid a HEAD on the
attention's output and the router as a sigmoid with a selection bias and
renormalised weights (`assumed`); no q/k norm; weights are drawn from a
seed.

THE EXPERTS ARE TEACHER-FORCED. Top-8 of 256 over random weights flips on
rounding, and a flipped expert moves an eighth of the routed sum. The
engine reports its eight experts for every (position, routed layer); the
reference follows them (weights from its own scores, renormalised over the
followed set) and reports `route_margin`: how far the best `s + b` outside
the followed set lies above the weakest inside, by its own float32 lights.
A near-tie reads the rounding of two nearly equal scores; a wrong page, a
window not honoured upstream or a dropped gate moves it to the scale of the
scores.

Memory: the float32 reference works beside 11 GB of engine. Layers are
walked one at a time, an expert's weights upcast one expert at a time (and
only over the tokens that follow it), attention runs a block of queries at
a time (a full layer's float32 scores are `[48, block, keys]`), the dense
layer and the head a block of positions or of the vocabulary at a time.
Sequences that share a long prefix of tokens and experts (a cached
document) share its forward: its keys and values are kept, a full layer's
all of them, a sliding layer's last 511.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_NORMS = ("attn_norm", "ffn_norm")
_ATTENTION = ("wq", "wk", "wv", "wg", "wo")
_DENSE = ("w_gate", "w_up", "w_down")
_MOE = ("router_w", "router_bias", "shared_gate", "shared_up", "shared_down")
_EXPERTS = ("w_gate", "w_up", "w_down")
_KINDS = {"full_attention": "full", "sliding_attention": "slide"}

_QUERY_BLOCK = 128          # queries attended together
# Few distinct shapes: each is a compile of every jitted piece. More than a
# query block of positions pad to a multiple of `_LONG`, and a sequence
# longer than that is computed in two parts, its first whole multiple of
# `_LONG` positions (shared with every sequence that has the same tokens
# and experts there: a cached document) and the rest. An expert's rows pad
# to a power of two from `_EXPERT_ROWS`.
_LONG = 4096
_POSITION_BLOCK = 2048      # positions of the dense layer at a time
_EXPERT_ROWS = 64
_VOCAB_BLOCK = 16384


def read_params(get, cfg, round_to=None) -> dict:
    """The engine's weights AS STORED (no copy, no upcast), by the names
    serving.model gives them: `get(name)` returns an array. `round_to` (a
    dtype name) makes every later upcast go through that dtype first: the
    reading of a precision below the stated one."""
    del cfg
    out = {"word_emb": get("dec.word_emb"), "lm_head": get("dec.lm_head"),
           "final_norm": get("dec.final_norm.scale"), "_round_to": round_to}
    for k in _NORMS + _EXPERTS:
        out[k] = get("dec.layers." + k)
    for group, keys in (("full", _ATTENTION), ("slide", _ATTENTION),
                        ("dense", _DENSE), ("moe", _MOE)):
        for k in keys:
            out[f"{group}.{k}"] = get(f"dec.layers.{group}.{k}")
    return out


def _f32(a, round_to=None):
    a = jnp.asarray(a)
    if round_to is not None and a.dtype != jnp.float32:
        a = a.astype(round_to)      # only what is stored below float32
    return a.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def inverse_frequencies(rotary_dim: int, theta: float, yarn=()) -> np.ndarray:
    """Equation 1's turns a position, one a lane pair (float32)."""
    half = rotary_dim // 2
    own = np.asarray([theta ** (-i / half) for i in range(half)], np.float64)
    if not yarn:
        return own.astype(np.float32)
    factor, original, beta_fast, beta_slow = (float(v) for v in yarn[:4])

    def d(beta):
        return rotary_dim * math.log(original / (2 * math.pi * beta)) \
            / (2 * math.log(theta))

    low = max(math.floor(d(beta_fast)), 0)
    high = min(math.ceil(d(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(half):
        r = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(own[i] * ((1.0 - r) + r / factor))
    return np.asarray(out, np.float32)


def _rotary(x, inv, rotary_dim, factor, offset):
    """x [T, heads, dh] at positions offset + index along T."""
    half = rotary_dim // 2
    ang = (offset + jnp.arange(x.shape[0], dtype=jnp.int32)).astype(
        jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary_dim:]], axis=-1)


def _layers(cfg) -> list:
    """(attention kind, its index among that kind, 'dense' | 'moe', its
    index among those, query heads) a layer, from the per-layer lists."""
    seen: dict = {}
    out = []
    for attn, ffn, heads in zip(cfg.layer_types, cfg.mlp_layer_types,
                                cfg.heads_per_layer):
        a, f = _KINDS[attn], "dense" if ffn == "dense" else "moe"
        out.append((a, seen.get(a, 0), f, seen.get(f, 0), int(heads)))
        seen[a] = seen.get(a, 0) + 1
        seen[f] = seen.get(f, 0) + 1
    return out


def _rotary_of(cfg, kind):
    dh = cfg.head_dim
    if kind == "full":
        rot = int(dh * cfg.partial_rotary_factor)
        yarn = tuple(cfg.yarn)
        return (jnp.asarray(inverse_frequencies(rot, float(cfg.rope_theta),
                                                yarn)),
                rot, float(yarn[4]) if yarn else 1.0)
    rot = int(dh * cfg.sliding_rotary_factor)
    return (jnp.asarray(inverse_frequencies(
        rot, float(cfg.sliding_rope_theta))), rot, 1.0)


@functools.partial(jax.jit, static_argnames=("nkv", "dh", "rot", "factor",
                                             "eps"))
def _keys(x, norm, wk, wv, inv, offset, nkv, dh, rot, factor, eps):
    """What other positions read of x [T, H]: k, v [T, nkv, dh]."""
    T = x.shape[0]
    z = _rms_norm(x, norm, eps)
    k = _rotary((z @ wk).reshape(T, nkv, dh), inv, rot, factor, offset)
    return k, (z @ wv).reshape(T, nkv, dh)


@functools.partial(jax.jit, static_argnames=("nh", "rot", "factor", "eps",
                                             "window"))
def _attend_block(q0, key0, xb, norm, wq, wg, wo, inv, kk, vv, nh, rot,
                  factor, eps, window):
    """Equations 1-3 for the block of queries xb [Q, H] at positions q0 ..
    over keys kk/vv [K, nkv, dh] at positions key0 ..: W_o of the gated
    attention [Q, H]. `window` 0: every key at or before the query."""
    Q = xb.shape[0]
    K, nkv, dh = kk.shape
    z = _rms_norm(xb, norm, eps)
    q = _rotary((z @ wq).reshape(Q, nh, dh), inv, rot, factor, q0)
    gate = jax.nn.sigmoid(z @ wg)                               # [Q, nh]
    qp = q0 + jnp.arange(Q, dtype=jnp.int32)[:, None]
    kp = key0 + jnp.arange(K, dtype=jnp.int32)[None, :]
    seen = (kp <= qp) & (kp >= 0)
    if window:
        seen &= qp - kp < window
    s = jnp.einsum("qjgd,kjd->jgqk", q.reshape(Q, nkv, nh // nkv, dh),
                   kk) * dh ** -0.5
    probs = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("jgqk,kjd->qjgd", probs, vv).reshape(Q, nh, dh)
    return (a * gate[:, :, None]).reshape(Q, nh * dh) @ wo


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_block(x, norm, wg, wu, wd, eps):
    z = _rms_norm(x, norm, eps)
    return x + (jax.nn.silu(z @ wg) * (z @ wu)) @ wd


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _router(x, norm, router_w, router_bias, sg, su, sd, forced, eps,
            scaling):
    """Equation 4 up to the routed experts. forced [T, k] expert ids (-1:
    route for yourself). Returns z, x plus the shared expert, the experts
    followed, their weights and the route margin."""
    z = _rms_norm(x, norm, eps)
    s = jax.nn.sigmoid(z @ router_w)
    select = s + router_bias
    k = forced.shape[1]
    own = jax.lax.top_k(select, k)[1]
    follow = jnp.where(forced[:, :1] >= 0, forced, own)
    inside = jnp.any(follow[:, :, None]
                     == jnp.arange(s.shape[1])[None, None, :], axis=1)
    margin = jnp.maximum(
        jnp.max(jnp.where(inside, -jnp.inf, select), axis=1)
        - jnp.min(jnp.take_along_axis(select, follow, axis=1), axis=1), 0.0)
    sf = jnp.take_along_axis(s, follow, axis=1)
    weights = scaling * sf / jnp.sum(sf, axis=1, keepdims=True)
    shared = (jax.nn.silu(z @ sg) * (z @ su)) @ sd
    return z, x + shared, follow, weights, margin


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("round_to",))
def _one_expert(y, z, rows, weight, w_gate, w_up, w_down, layer, expert,
                round_to=None):
    """y [T, H] += weight * expert(z[rows]) at `rows` (weight 0 pads); the
    expert's three matrices are taken out of the stored stacks `[L_moe, E,
    ...]` and upcast here, one expert at a time."""
    wg, wu, wd = (_f32(w[layer, expert], round_to)
                  for w in (w_gate, w_up, w_down))
    zr = z[rows]
    out = (jax.nn.silu(zr @ wg) * (zr @ wu) * weight[:, None]) @ wd
    return y.at[rows].add(out)


class Prefix:
    """What the forward of a sequence needs of the tokens before it: their
    count and, layer by layer, their K and V [n, nkv, dh] in float32 (a
    sliding layer's: the last `window - 1`, with the position of the
    first)."""

    def __init__(self, n: int, layers: list):
        self.n, self.layers = n, layers


def forward(params: dict, tokens, cfg, routes=None,
            prefix: "Prefix | None" = None, keep: int = 0):
    """tokens [n] int, the WHOLE sequence. routes [m, L_moe, k] int: the
    experts to follow at positions 0 .. m-1 (None: route for yourself).
    With `prefix` the first `prefix.n` tokens are not recomputed (they must
    be the tokens it was made from). Returns (x [n - prefix.n, H] after the
    last norm, route_margin [n - prefix.n, L_moe]) and, with `keep`, a
    `Prefix` of the first `keep` tokens as well."""
    rt = params.get("_round_to")
    nkv, dh = cfg.num_kv_heads, cfg.head_dim
    W, k_exp = int(cfg.sliding_window), cfg.experts_per_token
    eps, scaling = float(cfg.rms_norm_eps), float(cfg.routed_scaling)
    layers = _layers(cfg)
    L_moe = sum(f == "moe" for _, _, f, _, _ in layers)
    n0 = prefix.n if prefix is not None else 0
    n = len(tokens) - n0                    # tokens computed here
    T = -(-n // _LONG) * _LONG if n > _QUERY_BLOCK else -(-n // 8) * 8
    block = min(_QUERY_BLOCK, T)
    tok = np.zeros(T, np.int32)
    tok[:n] = tokens[n0:]
    forced = np.full((T, L_moe, k_exp), -1, np.int32)
    if routes is not None:
        m = max(0, min(n, len(routes) - n0))
        forced[:m] = np.asarray(routes)[n0:n0 + m].reshape(m, L_moe, k_exp)
    x = _f32(params["word_emb"][jnp.asarray(tok)], rt)
    margins, kept = [], []
    with jax.default_matmul_precision("highest"):
        for l, (a_kind, a_i, f_kind, f_i, nh) in enumerate(layers):
            inv, rot, factor = _rotary_of(cfg, a_kind)
            p = {k: _f32(params[f"{a_kind}.{k}"][a_i], rt)
                 for k in _ATTENTION}
            attn_norm = _f32(params["attn_norm"][l])
            kk, vv = _keys(x, attn_norm, p["wk"], p["wv"], inv,
                           jnp.int32(n0), nkv=nkv, dh=dh, rot=rot,
                           factor=factor, eps=eps)
            window = W if a_kind == "slide" else 0
            if keep:
                lo = max(0, keep - (W - 1)) if window else 0
                kept.append((lo, kk[lo:keep], vv[lo:keep]))
            key0 = n0
            if prefix is not None:
                key0, pk, pv = prefix.layers[l]
                kk, vv = jnp.concatenate([pk, kk]), jnp.concatenate([pv, vv])
            if window:
                # W - 1 rows of nothing in front: the band of the block at
                # q0 is rows q0 - key0 .. of the padded keys, whatever q0
                kk, vv = (jnp.pad(a, ((W - 1, 0), (0, 0), (0, 0)))
                          for a in (kk, vv))
                key0 -= W - 1
            outs = []
            for q0 in range(0, T, block):
                if q0 >= n:                 # padding attends nothing
                    outs.append(jnp.zeros((block, x.shape[1]), jnp.float32))
                    continue
                if window:
                    at = n0 + q0 - (W - 1) - key0
                    span = min(block + W - 1, kk.shape[0] - at)
                    kb, vb, kb0 = (kk[at:at + span], vv[at:at + span],
                                   key0 + at)
                else:
                    kb, vb, kb0 = kk, vv, key0
                outs.append(_attend_block(
                    jnp.int32(n0 + q0), jnp.int32(kb0), x[q0:q0 + block],
                    attn_norm, p["wq"], p["wg"], p["wo"], inv, kb, vb,
                    nh=nh, rot=rot, factor=factor, eps=eps, window=window))
            x = x + jnp.concatenate(outs)
            del outs, kk, vv
            ffn_norm = _f32(params["ffn_norm"][l])
            if f_kind == "dense":
                w = [_f32(params["dense." + k][f_i], rt) for k in _DENSE]
                step = min(_POSITION_BLOCK, T)
                x = jnp.concatenate([
                    _dense_block(x[i:i + step], ffn_norm, *w, eps=eps)
                    for i in range(0, T, step)])
                continue
            m = {k: _f32(params["moe." + k][f_i], rt) for k in _MOE}
            z, x, follow, weights, margin = _router(
                x, ffn_norm, m["router_w"], m["router_bias"],
                m["shared_gate"], m["shared_up"], m["shared_down"],
                jnp.asarray(forced[:, f_i]), eps=eps, scaling=scaling)
            follow, weights = np.asarray(follow), np.asarray(weights)
            y = jnp.zeros_like(x)
            for e in range(cfg.num_experts):
                rows, slot = np.nonzero(follow[:n] == e)
                if not len(rows):
                    continue
                pad = max(_EXPERT_ROWS,
                          1 << (len(rows) - 1).bit_length()) - len(rows)
                y = _one_expert(
                    y, z, jnp.asarray(np.pad(rows, (0, pad))),
                    jnp.asarray(np.pad(weights[rows, slot], (0, pad))),
                    *(params[k] for k in _EXPERTS), jnp.int32(f_i),
                    jnp.int32(e), round_to=rt)
            x = x + y
            margins.append(margin)
        x = _rms_norm(x, _f32(params["final_norm"]), eps)
    out = (x[:n], np.asarray(jnp.stack(margins, -1))[:n])
    return out + (Prefix(keep, kept),) if keep else out


@jax.jit
def _block_logits(x, head_block):
    return x @ head_block


def logit_gaps(params: dict, x, tokens) -> np.ndarray:
    """x [M, H] final-norm states, tokens [M] the tokens served after them:
    per row, the best logit minus the served token's, reduced over blocks
    of the vocabulary (the head is `[H, V]`, untied)."""
    head = params["lm_head"]
    V = head.shape[1]
    tokens = np.asarray(tokens)
    best = np.full(len(tokens), -np.inf, np.float32)
    own = np.zeros(len(tokens), np.float32)
    with jax.default_matmul_precision("highest"):
        for v0 in range(0, V, _VOCAB_BLOCK):
            lg = np.asarray(_block_logits(
                x, _f32(head[:, v0:v0 + _VOCAB_BLOCK],
                        params.get("_round_to"))))
            best = np.maximum(best, lg.max(axis=1))
            t = tokens - v0
            here = (t >= 0) & (t < lg.shape[1])
            own[here] = lg[np.flatnonzero(here), t[here]]
    return best - own


def check_sequences(params: dict, sequences: list, cfg) -> list:
    """For each (prompt, served, routes) — routes [>= len(prompt) +
    len(served) - 1, L_moe, k] the engine's experts by position, or None —
    a dict: `gap`, the largest amount by which a served token's logit sits
    below the best logit at its position with the engine's experts
    followed, and `route_margin`, the largest margin by which the reference
    would have routed a position of the sequence otherwise. Sequences
    whose first whole multiple of `_LONG` positions hold the same tokens
    and experts share that part's forward."""
    seqs = [((list(p) + list(s))[:-1], r) for p, s, r in sequences]
    keys = []
    for (seq, routes), (p_, _, _) in zip(seqs, sequences):
        n0 = (len(p_) - 1) // _LONG * _LONG
        keys.append((n0, np.asarray(seq[:n0]).tobytes(), b"" if routes is None
                     else np.asarray(routes)[:n0].tobytes()))
    out = [None] * len(sequences)
    held = (None, None, None)               # key, Prefix, its margin
    # sequences behind one prefix one after another: it is held once
    for i in sorted(range(len(seqs)), key=lambda i: keys[i]):
        (seq, routes), key = seqs[i], keys[i]
        prompt, served, _ = sequences[i]
        n0, prefix, before = key[0], None, 0.0
        if n0:
            if held[0] != key:
                held = (None, None, None)   # drop the old one first
                _, mg, made = forward(params, seq[:n0], cfg, routes, keep=n0)
                held = (key, made, float(mg.max(initial=0.0)))
            prefix, before = held[1], held[2]
        x, mg = forward(params, seq, cfg, routes, prefix)
        at = len(prompt) - 1 - n0 + np.arange(len(served))
        rows = max(64, 1 << (len(served) - 1).bit_length())  # few shapes
        xs = jnp.pad(x[at], ((0, rows - len(served)), (0, 0)))
        gaps = logit_gaps(params, xs, list(served)
                          + [0] * (rows - len(served)))[:len(served)]
        out[i] = {"gap": float(gaps.max()),
                  "route_margin": max(before, float(mg.max(initial=0.0)))}
    return out


def worst_logit_gaps(params: dict, sequences: list, cfg) -> list:
    """`decoder_lm.worst_logit_gaps` for (prompt, served[, routes]) tuples;
    without routes the reference routes for itself."""
    full = [(s[0], s[1], s[2] if len(s) > 2 else None) for s in sequences]
    return [c["gap"] for c in check_sequences(params, full, cfg)]
