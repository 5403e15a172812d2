"""Plain reference for the served language model of Keye-VL-2.0-30B-A3B
(`configs/keye_vl2_30b_a3b.json`): one teacher-forced causal forward over
prompt + served tokens in float32, `jax.default_matmul_precision("highest")`,
no kernel, no paged anything, one sequence at a time, independent of
paddle_tpu (it reads the engine's weights by name and nothing else).

One layer, for token t of a sequence (h_t in R^H, float32):

 1. z = RMSNorm(h).  q = W_q z (32 heads of 128), k = W_k z, v = W_v z
    (4 heads); RMSNorm over each head of q and k; rotary on the whole head
    at position t (theta 1e7, rotate-half pairs).
 2. indexer: qI = W_qI z (16 heads of 64), kI = LayerNorm(W_kI z) (one head
    of 64), rotary on the first 32 lanes of both; w = W_w z / sqrt(16 * 64);
    I(t, s) = sum_j w_tj relu(qI_tj . kI_s).
 3. S_t = the 2,048 positions s <= t of largest I(t, s), ties to the lower
    position (every position while t < 2,048).
 4. o_t = softmax_{s in S_t}(q_t . k_s / sqrt(128)) v_s, eight query heads
    a KV head, all heads of a token over the same S_t;  x = h + W_o o.
 5. u = RMSNorm(x);  p = softmax(W_r u) over 128 experts;  T = its 8
    largest;  h' = x + sum_{e in T} p_e / sum_T p * W_down,e(silu(W_gate,e
    u) * W_up,e u).
 6. after the last layer RMSNorm and the untied head.

TWO CHOICES ARE TEACHER-FORCED, each with a margin that says how wrong the
engine's choice was by this reference's own float32 lights:

  * the EXPERTS. Top-8 over random weights flips on rounding as top-1
    does, and a flipped expert moves an eighth of the FFN. The engine
    reports its eight experts for every (position, layer); the reference
    follows them (weights renormalised over the followed set from its own
    probabilities) and reports `route_margin`: how far its best expert
    outside the set lies above the weakest inside.
  * the SELECTION, for the positions a marked request computed: the
    engine hands back what each layer's attention was given there (the
    mask a window attended under, the positions a decode row gathered:
    `selection`, below), the reference attends exactly that and reports
    `select_margin`: `max(0, max_{s not in S} I(t, s) - min_{s in S} I(t,
    s))` over its own scores, in units of the standard deviation of that
    query's live scores; a set that does not hold exactly min(2,048, t +
    1) live positions reads `MISCOUNT`. A flip at rank 2,048 reads the
    rounding of two nearly equal scores; "the newest 2,048" reads several
    standard deviations, though it moves the logits of a random model
    little, which is why logits alone cannot guard the mechanism. The
    positions of a shared document were computed by ANOTHER marked
    request (the one that prefilled it): its selection is followed there
    (`ahead`). Where no selection is given the reference selects for
    itself.

A selection is `(first, words)`: uint32 `[n, layers, G, page_size]` for
positions first .. first + n - 1, bit `p % 32` of word `[p // 32, slot]`
set where position `p * page_size + slot` was attended (the engine packs
thirty-two PAGES into a word; `_unpack`).

WHAT IS JUDGED. `gap`; `route_margin` and `select_margin` over every
position whose selection was followed, in every layer. Where the
reference selects for itself (a document whose selection was not handed
back) its selection differs from a bfloat16 engine's in 50-100 of the
2,048 positions (the scores at the cut lie 2e-4 standard deviations apart,
the rounding is 3e-3), and under RANDOM weights attention is a near-uniform
average in which those hundred members weigh like any others: the hidden
states of the two computations drift apart layer by layer, which pollutes
every margin that reads such positions (chip readings, PR 29, PERF.md
section 4). That is why the document's selection is followed too; the
route margin of positions that were not followed is reported beside the
judged ones (`route_margin_unfollowed`) and not judged.

Memory and time: a float32 copy of the weights does not fit beside the
engine, and `[33k, 33k]` scores do not fit anywhere. Layers are walked one
at a time; queries run in blocks of 128 against all keys, their own
projections made inside the block (indexer scores `[128, T]` summed a head
at a time, attention scores `[8, 128, T]` a KV head), the k-th largest
score is found a bit at a time instead of by a sort, tokens are sorted by
expert on the host and an expert's weights upcast one expert at a time,
and the head is reduced over blocks of the vocabulary. A 33k-token
forward takes 30-40 s on the chip, so `check_sequences` computes a shared
prefix ONCE: attention is the only thing that crosses positions, so the
forward of a sequence behind a prefix needs the prefix's K, V and indexer
keys of every layer and nothing else (`Prefix`), and those do not depend
on what follows. The two halves together are the one full forward.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

_LAYER_KEYS = (
    "attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "wqi", "wki",
    "ki_norm_w", "ki_norm_b", "ww", "ffn_norm", "router_w")
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")
_VOCAB_BLOCK = 16384
_QUERY_BLOCK = 128
_LONG = 2048                # sequences past this pad to a multiple of it
_SUFFIX = 768               # ... and what follows a shared prefix to this
_EXPERT_ROWS = 512          # an expert's token count pads to a multiple
MISCOUNT = 1e9              # the margin of a followed set of the wrong size


def read_params(get, cfg, round_to=None) -> dict:
    """The engine's weights AS STORED (no copy, no upcast), by the names
    serving.model gives them: `get(name)` returns an array. `round_to` (a
    dtype name) makes every later upcast go through that dtype first: the
    reading of a precision below the stated one."""
    del cfg
    out = {"word_emb": get("dec.word_emb"), "lm_head": get("dec.lm_head"),
           "final_norm": get("dec.final_norm.scale"), "_round_to": round_to}
    for k in _LAYER_KEYS + _EXPERT_KEYS:
        out[k] = get("dec.layers." + k)
    return out


def _f32(a, round_to=None):
    a = jnp.asarray(a)
    if round_to is not None and a.dtype != jnp.float32:
        a = a.astype(round_to)      # only what is stored below float32
    return a.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rotary(x, rotary_dim, theta, offset=0):
    """x [T, heads, d], position = offset + index along T; lanes (i, i +
    r/2) of the first r = rotary_dim rotate together."""
    half = rotary_dim // 2
    inv = theta ** (-(jnp.arange(half, dtype=jnp.float32) * 2 / rotary_dim))
    ang = (offset + jnp.arange(x.shape[0], dtype=jnp.int32)).astype(
        jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary_dim:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("nkv", "dh", "D", "theta",
                                             "eps"))
def _keys(x, p, offset, nkv, dh, D, theta, eps):
    """What other positions read of x [T, H], the tokens at positions
    offset ..: k, v [T, nkv, dh] and the indexer key kI [T, D]."""
    T = x.shape[0]
    z = _rms_norm(x, p["attn_norm"], eps)
    k = _rms_norm((z @ p["wk"]).reshape(T, nkv, dh), p["k_norm"], eps)
    v = (z @ p["wv"]).reshape(T, nkv, dh)
    ki = z @ p["wki"]
    mu = jnp.mean(ki, axis=-1, keepdims=True)
    var = jnp.mean((ki - mu) ** 2, axis=-1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(var + eps) * p["ki_norm_w"] \
        + p["ki_norm_b"]
    ki = _rotary(ki[:, None, :], D // 2, theta, offset)[:, 0]
    return _rotary(k, dh, theta, offset), v, ki


def _kth_largest_bits(u, k):
    """u [Q, T] uint32 -> [Q, 1]: its k-th largest value along T, by 32
    counting passes (most significant bit first)."""
    def narrow(i, lo):
        cand = lo | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(u >= cand, axis=-1, keepdims=True) >= k
        return jnp.where(enough, cand, lo)

    return jax.lax.fori_loop(0, 32, narrow,
                             jnp.zeros((u.shape[0], 1), jnp.uint32))


def _own_selection(scores, live, k):
    """Equation 3 as a mask [Q, T]."""
    s = jnp.where(live, scores, -jnp.inf)
    bits = jax.lax.bitcast_convert_type(jnp.where(s == 0, 0.0, s), jnp.uint32)
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    kth = _kth_largest_bits(u, k)
    above, ties = u > kth, u == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    ties = ties & (jnp.cumsum(ties, axis=-1) <= room)
    return live & (above | ties)


def _unpack(words):
    """Selection words [Q, G, ps] uint32 -> bool [Q, G * 32 * ps], position
    by position."""
    bit = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None]
    return ((words[:, :, None, :] >> bit) & 1).astype(bool).reshape(
        words.shape[0], -1)


def _forced(segments: list, layer: int, lo: int, hi: int, shape: tuple):
    """The selection to follow at positions lo .. hi - 1 in `layer`: words
    [hi - lo, G, ps] and which rows have one."""
    words = np.zeros((hi - lo,) + shape, np.uint32)
    use = np.zeros(hi - lo, bool)
    for first, w in segments:
        a, b = max(lo, first), min(hi, first + len(w))
        if a < b:
            words[a - lo:b - lo, :w.shape[2]] = w[a - first:b - first, layer]
            use[a - lo:b - lo] = True
    return words, use


@functools.partial(jax.jit, static_argnames=("k", "nh", "J", "theta", "eps"))
def _attend_block(q0, xb, p, forced, use, kk, vv, ki, k, nh, J, theta, eps):
    """Equations 1-4 for the queries at positions q0 .. q0 + Q against
    every key. xb [Q, H] their residual stream, p the layer's parameters;
    forced [Q, G, ps] selection words to attend under where `use` [Q];
    kk/vv [T, nkv, dh], ki [T, D]. Returns (W_o o [Q, H], select_margin
    [Q])."""
    Q = xb.shape[0]
    T, nkv, dh = kk.shape
    D, g = ki.shape[1], nh // nkv
    z = _rms_norm(xb, p["attn_norm"], eps)
    qb = _rotary(_rms_norm((z @ p["wq"]).reshape(Q, nh, dh), p["q_norm"],
                           eps), dh, theta, q0)
    qib = _rotary((z @ p["wqi"]).reshape(Q, J, D), D // 2, theta, q0)
    wb = (z @ p["ww"]) * (J ** -0.5 * D ** -0.5)
    at = jnp.arange(T, dtype=jnp.int32)[None, :]
    live = at <= q0 + jnp.arange(Q, dtype=jnp.int32)[:, None]

    def add_head(acc, head):                # one indexer head at a time
        qj, wj = head                       # [Q, D], [Q]
        return acc + jax.nn.relu(qj @ ki.T) * wj[:, None], None

    scores, _ = jax.lax.scan(add_head, jnp.zeros((Q, T), jnp.float32),
                             (jnp.moveaxis(qib, 1, 0), wb.T))
    keep = _own_selection(scores, live, k)

    def given():                # the followed words as a mask [Q, T]
        bits = _unpack(forced)
        bits = bits[:, :T] if bits.shape[1] >= T else jnp.pad(
            bits, ((0, 0), (0, T - bits.shape[1])))
        return bits & live

    want = jnp.sum(keep, axis=-1)           # min(k, positions that exist)
    keep = jnp.where(use[:, None], jax.lax.cond(
        jnp.any(use), given, lambda: jnp.zeros((Q, T), bool)), keep)
    # every bit counts, also one set past the query or past the keys
    miscount = use & ((jnp.sum(_unpack(forced), axis=-1) != want)
                      | (jnp.sum(keep, axis=-1) != want))
    # how wrong the followed set is by these scores, in standard deviations
    # of the query's live scores
    n = jnp.sum(live, axis=-1)
    mean = jnp.sum(jnp.where(live, scores, 0.0), axis=-1) / n
    std = jnp.sqrt(jnp.sum(jnp.where(live, (scores - mean[:, None]) ** 2,
                                      0.0), axis=-1) / n)
    best_out = jnp.max(jnp.where(live & ~keep, scores, -jnp.inf), axis=-1)
    worst_in = jnp.min(jnp.where(keep, scores, jnp.inf), axis=-1)
    margin = jnp.where(use & jnp.isfinite(best_out),
                       jnp.maximum(best_out - worst_in, 0.0)
                       / jnp.maximum(std, 1e-30), 0.0)
    margin = jnp.where(miscount, MISCOUNT, margin)

    def head_group(args):                   # one KV head, its g query heads
        qg, k1, v1 = args                   # [g, Q, dh], [T, dh], [T, dh]
        a = jnp.einsum("gqd,td->gqt", qg, k1) * dh ** -0.5
        a = jax.nn.softmax(jnp.where(keep[None], a, -jnp.inf), axis=-1)
        return jnp.einsum("gqt,td->gqd", a, v1)

    qg = jnp.moveaxis(qb.reshape(Q, nkv, g, dh), 0, 2)      # [nkv, g, Q, dh]
    o = jax.lax.map(head_group, (qg, jnp.moveaxis(kk, 1, 0),
                                 jnp.moveaxis(vv, 1, 0)))
    return jnp.moveaxis(o, 2, 0).reshape(Q, nh * dh) @ p["wo"], margin


@functools.partial(jax.jit, static_argnames=("eps",), donate_argnums=(0, 1))
def _router(x, o, p, forced, eps):
    """Equation 5 up to the experts; o is W_o applied already. forced [T,
    k] expert ids (-1: route for yourself). Returns x after attention, u,
    the experts followed, their renormalised weights and the route
    margin."""
    x = x + o
    u = _rms_norm(x, p["ffn_norm"], eps)
    probs = jax.nn.softmax(u @ p["router_w"], axis=-1)
    k = forced.shape[1]
    own = jax.lax.top_k(probs, k)[1]
    follow = jnp.where(forced[:, :1] >= 0, forced, own)
    inside = jnp.any(follow[:, :, None]
                     == jnp.arange(probs.shape[1])[None, None, :], axis=1)
    pf = jnp.take_along_axis(probs, follow, axis=1)
    margin = jnp.maximum(jnp.max(jnp.where(inside, -jnp.inf, probs), axis=1)
                         - jnp.min(pf, axis=1), 0.0)
    return x, u, follow, pf / jnp.sum(pf, axis=1, keepdims=True), margin


@functools.partial(jax.jit, donate_argnums=(0,))
def _one_expert(y, u, rows, weight, wg, wu, wd):
    """y [T, H] += weight * expert(u[rows]) at `rows` (weight 0 pads)."""
    z = u[rows]
    out = (jax.nn.silu(z @ wg) * (z @ wu) * weight[:, None]) @ wd
    return y.at[rows].add(out)


def _padded_length(n: int) -> int:
    """Few distinct lengths: each is a compile of every jitted piece."""
    step = _LONG if n > _LONG else _SUFFIX if n > 128 else 64
    return -(-n // step) * step


class Prefix:
    """What the forward of a sequence needs of the tokens before it: their
    count (whole query blocks) and, layer by layer, their K, V [n, nkv,
    dh] and indexer keys [n, D] in float32."""

    def __init__(self, n: int, layers: list):
        self.n, self.layers = n, layers


def forward(params: dict, tokens, cfg, routes=None, selection=None,
            prefix: "Prefix | None" = None, keep: int = 0):
    """tokens [n] int, the WHOLE sequence. routes [m, L, k] int: the
    experts to follow at positions 0 .. m-1 (None: route for yourself).
    selection: one `(first, words)` or a list of them, the selection to
    follow at the positions each covers (module docstring; None: select
    for yourself). With `prefix` the first `prefix.n` tokens are not
    recomputed (they must be the tokens it was made from). Returns (x [n -
    prefix.n, H] after the last norm, route_margin and select_margin [n -
    prefix.n, L], followed [n - prefix.n]: where a selection was followed)
    and, with `keep`, a `Prefix` of the first `keep` tokens as well."""
    rt = params.get("_round_to")
    nh, nkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    J, D, topk = cfg.index_heads, cfg.index_head_dim, cfg.index_topk
    k_exp, L = cfg.experts_per_token, cfg.num_layers
    eps, theta = float(cfg.rms_norm_eps), float(cfg.rope_theta)
    n0 = prefix.n if prefix is not None else 0
    n = len(tokens) - n0                    # tokens computed here
    T = _padded_length(n)
    block = min(_QUERY_BLOCK, T)
    tok = np.zeros(T, np.int32)
    tok[:n] = tokens[n0:]
    forced_e = np.full((T, L, k_exp), -1, np.int32)
    if routes is not None:
        m = min(n, len(routes) - n0)
        forced_e[:m] = np.asarray(routes)[n0:n0 + m].reshape(m, L, k_exp)
    segments = [] if selection is None else [selection] \
        if isinstance(selection, tuple) else list(selection)
    segments = [(first, w[:n0 + n - first]) for first, w in segments]
    words_shape = (max((w.shape[2] for _, w in segments), default=1),
                   segments[0][1].shape[3] if segments else 1)
    followed = _forced(segments, 0, n0, n0 + T, words_shape)[1]
    x = _f32(params["word_emb"][jnp.asarray(tok)], rt)
    route_margins, select_margins, kept = [], [], []
    with jax.default_matmul_precision("highest"):
        for l in range(L):
            p = {key: _f32(params[key][l], rt) for key in _LAYER_KEYS}
            kk, vv, ki = _keys(x, p, jnp.int32(n0), nkv=nkv, dh=dh, D=D,
                               theta=theta, eps=eps)
            if keep:
                kept.append((kk[:keep], vv[:keep], ki[:keep]))
                # as many keys as the sequences behind this prefix will
                # show the block function: one compile for both passes
                kk, vv, ki = (jnp.pad(a, ((0, _SUFFIX),) + ((0, 0),)
                                      * (a.ndim - 1)) for a in (kk, vv, ki))
            if prefix is not None:
                kk, vv, ki = (jnp.concatenate([a, b]) for a, b in
                              zip(prefix.layers[l], (kk, vv, ki)))
            outs, margins = [], []
            for q0 in range(0, T, block):
                if q0 >= n:                 # padding attends nothing
                    outs.append(jnp.zeros((block, x.shape[1]), jnp.float32))
                    margins.append(jnp.zeros((block,), jnp.float32))
                    continue
                rows = slice(q0, q0 + block)
                words, use = _forced(segments, l, n0 + q0, n0 + q0 + block,
                                     words_shape)
                o, mg = _attend_block(
                    jnp.int32(n0 + q0), x[rows], p, jnp.asarray(words),
                    jnp.asarray(use), kk, vv, ki,
                    k=min(topk, kk.shape[0]), nh=nh, J=J,
                    theta=theta, eps=eps)
                outs.append(o)
                margins.append(mg)
            o = jnp.concatenate(outs)
            del outs                        # x and o are given up to the call
            x, u, follow, weights, r_margin = _router(
                x, o, p, jnp.asarray(forced_e[:, l]), eps)
            del o
            follow, weights = np.asarray(follow), np.asarray(weights)
            y = jnp.zeros_like(x)
            for e in range(cfg.num_experts):
                rows, slot = np.nonzero(follow[:n] == e)
                if not len(rows):
                    continue
                pad = -(-len(rows) // _EXPERT_ROWS) * _EXPERT_ROWS - len(rows)
                y = _one_expert(
                    y, u, jnp.asarray(np.pad(rows, (0, pad))),
                    jnp.asarray(np.pad(weights[rows, slot], (0, pad))),
                    *(_f32(params[key][l, e], rt) for key in _EXPERT_KEYS))
            x = x + y
            route_margins.append(r_margin)
            select_margins.append(jnp.concatenate(margins))
        x = _rms_norm(x, _f32(params["final_norm"], rt), eps)
    out = (x[:n], np.asarray(jnp.stack(route_margins, -1))[:n],
           np.asarray(jnp.stack(select_margins, -1))[:n], followed[:n])
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


def _margins(route, select, followed) -> dict:
    """What a stretch of positions adds to a sequence's readings: route
    and select margins [n, L], followed [n]."""
    judged = followed if followed.any() else np.ones(len(followed), bool)
    return {"route_margin": float(route[judged].max(initial=0.0)),
            "route_margin_unfollowed": float(route[~judged].max(initial=0.0)),
            "select_margin_by_layer": select[judged].max(axis=0).tolist()}


def check_sequences(params: dict, sequences: list, cfg,
                    budget_s: float | None = None, at_least: int = 0) -> list:
    """For each (prompt, served, routes, selection[, ahead]) — routes [>=
    len(prompt) + len(served) - 1, L, k] the engine's experts by position
    or None; selection `(first, words)` or None; ahead, words for positions
    0 .. first - 1 (the selection of the request that computed a shared
    prefix) or None — a dict: `gap`, the largest amount by which a served
    token's logit sits below the best logit at its position with the
    engine's choices followed; `route_margin` and `select_margin`, the
    largest over the positions whose selection was followed (every
    position where none was) and over the layers, with
    `select_margin_by_layer`; and, not judged, `route_margin_unfollowed`
    (module docstring). Sequences that share the tokens, experts and
    `ahead` before their selection (whole query blocks of them) share that
    part's forward; give them one after the other. With `budget_s` the
    list may come back shorter: once `at_least` are graded, a sequence is
    not started if its forward (at the pace of the last prefix and the last
    suffix) would end past the budget."""
    out, shared = [], (None, None)
    t0, prefix_s, suffix_s = time.perf_counter(), 0.0, 0.0
    for sequence in sequences:
        prompt, served, routes, selection, ahead = \
            (tuple(sequence) + (None,) * 3)[:5]
        seq = (list(prompt) + list(served))[:-1]
        first = selection[0] if selection is not None else 0
        segments = [(0, ahead)] * (ahead is not None) \
            + [selection] * (selection is not None)
        n0 = first // _QUERY_BLOCK * _QUERY_BLOCK if first > _LONG else 0
        key = (tuple(seq[:n0]), None if routes is None
               else np.asarray(routes)[:n0].tobytes(), id(ahead)) \
            if n0 else None
        ahead_s = suffix_s + (prefix_s if n0 and shared[0] != key else 0.0)
        if budget_s is not None and len(out) >= at_least \
                and time.perf_counter() - t0 + ahead_s > budget_s:
            break
        parts, prefix = [], None
        if n0:
            if shared[0] != key:
                shared, t1 = (None, None), time.perf_counter()  # drop first
                _, r, sm, f, made = forward(params, seq[:n0], cfg, routes,
                                            segments, keep=n0)
                shared = (key, (made, _margins(r, sm, f)))
                prefix_s = time.perf_counter() - t1
            prefix, before = shared[1]
            parts.append(before)
        t1 = time.perf_counter()
        x, r, sm, f = forward(params, seq, cfg, routes, segments, prefix)
        parts.append(_margins(r, sm, f))
        # served tokens pad to one row count: one compile of the head
        at = len(prompt) - 1 - n0 + np.arange(len(served))
        rows = -(-len(served) // 64) * 64
        xs = jnp.pad(x[at], ((0, rows - len(served)), (0, 0)))
        gaps = logit_gaps(params, xs, list(served)
                          + [0] * (rows - len(served)))[:len(served)]
        suffix_s = time.perf_counter() - t1
        by_layer = np.max([m["select_margin_by_layer"] for m in parts], 0)
        out.append({
            "gap": float(gaps.max()),
            "route_margin": max(m["route_margin"] for m in parts),
            "select_margin": float(by_layer.max()),
            "select_margin_by_layer": by_layer.tolist(),
            "route_margin_unfollowed": max(m["route_margin_unfollowed"]
                                           for m in parts)})
    return out


def worst_logit_gaps(params: dict, sequences: list, cfg) -> list:
    """`decoder_lm.worst_logit_gaps` for (prompt, served[, routes[,
    selection]]) tuples; what is missing the reference chooses itself."""
    return [c["gap"] for c in check_sequences(params, sequences, cfg)]
