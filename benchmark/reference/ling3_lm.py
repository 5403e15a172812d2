"""Plain reference for a served stage of Ling-3.0-flash (`bailing_hybrid`;
"kda_moe": `configs/ling3_flash.json`): one teacher-forced forward over
prompt + served tokens, float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`.

Independent of paddle_tpu: no cache, no pools, no slots, no chunks, no
kernels, no prefill/decode split; it reads the engine's weights by name and
nothing else. Pre-norm, two sub-layers a layer (RMSNorm eps 1e-6):
`x <- x + Mixer_l(RMSNorm(x))`, `x <- x + MLP_l(RMSNorm(x))`.

    mixer of layer l: latent attention where (l + 1) % layer_group_size == 0,
    Kimi Delta Attention elsewhere.

    Kimi Delta Attention (arXiv:2510.26692), 32 heads, d_k = d_v = 128:
       [q~ | k~ | v~] = x~ W_qkv;  each channel c: silu(sum_j w_c[j]
       (.)_{t-3+j}) (four taps, zero left pad, no bias)
       q = L2norm(q') 128^-0.5, k = L2norm(k'), v = v'   (a head)
       log a_t = -5 sigmoid(exp(A_log_h) (x~ W_f + dt_bias))   [heads, 128]
       b_t = sigmoid(x~ W_b)                                   [heads]
       S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
       o_t = S_t^T q_t              (THE PLAIN RECURRENCE: a `lax.scan`
                                     over positions, no chunks)
       y = (RMSNorm_head(o_t) g * sigmoid(x~ W_g)) W_o   (gain g [128], the
                                     norm BEFORE the gate)
    latent attention (arXiv:2405.04434 with NO query latent), 32 heads:
       q = x~ W_q  -> a head q_nope 128 | q_rope 64
       [c_kv | k_rope] = x~ W_kva (512 + 64),  c_kv <- RMSNorm(c_kv)
       rotary on interleaved lane pairs (2i, 2i + 1), theta 6e6, no scaling
       [k_nope_h | v_h] = c_kv W_kvb,h  (THE EXPANDED FORM)
       scores (q_nope . k_nope + q_rope . k_rope) 192^-0.5, causal softmax
       o_h <- o_h sigmoid(x~ W_gate)_h;  y = concat(o_h) W_o
    MLP of layer l: a dense SwiGLU for l < first_k_dense_replace, else
       s = sigmoid(x~ W_r) over 512 (float32), groups of 64 scored by their
       two largest s + b, the 4 best groups kept, T = the 8 largest s + b
       inside them (ties to the lower expert); w_e = 2.5 s_e / sum_T s;
       f = sum_{e in T, e held} w_e W2_e (silu(W1_e x~) * W3_e x~)
           + the shared expert's SwiGLU
    model:  x_0 = Emb[token]; logits = RMSNorm(x_L) W_head

Departures from the published description (the configuration's
`departures` and `assumed`): the stage holds the first `num_layers` layers
and applies the final norm and the head to their output; of the 512 experts
the first `experts_held` are HELD (one chip's share) and what the others
would add is left out, in the engine and here alike; the vocabulary is the
slice the engine holds; no multi-token-prediction module; no SwiGLU clamp
(the limit lists are 0 for these layers); `use_qk_norm` is read as the
RMSNorm of `c_kv` alone.

THE EXPERTS ARE TEACHER-FORCED (`zaya_lm.py`'s rule). Top-8 of 512 over
random weights flips on rounding. The engine reports its experts for every
(position, expert layer); the reference follows them (weights from its own
scores, renormalised over the followed set) and reports `route_margin`: how
far, by its own float32 lights, the best group outside the groups it had to
open lies above the weakest inside, or the best `s + b` of an open group's
unfollowed expert above the weakest followed one.

Memory: the float32 reference works beside 12 GB of engine, over sequences
of up to 14,336 positions. The sequence is walked in BLOCKS of `_BLOCK`
positions: a Kimi-Delta layer carries its convolution tail and its state
from block to block; the latent layer makes every block's keys and values
first and then attends a block of queries at a time, `_QUERY_BLOCK` queries
a product; an expert's matrices one expert at a time over the tokens that
follow it; the head a block of the vocabulary at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
_KDA = ("w_qkv", "conv_w", "w_f", "dt_bias", "a_log", "w_b", "w_g", "o_norm",
        "w_o")
_MLA = ("wq", "wkv_a", "kv_norm", "wkv_b", "w_gate_h", "wo")
_DENSE = ("ffn_norm", "w_gate", "w_up", "w_down")
_MOE = ("ffn_norm", "router_w", "router_bias", "shared_gate", "shared_up",
        "shared_down")
_EXPERTS = ("w_gate", "w_up", "w_down")

_BLOCK = 2048           # positions walked together
_QUERY_BLOCK = 128      # queries scored against the whole context at once
_EXPERT_ROWS = 64
_VOCAB_BLOCK = 8192
MISCOUNT = 1e3          # a followed set no group limit could have produced


def read_params(get, cfg, round_to=None) -> dict:
    """The engine's weights AS STORED (no copy, no upcast), by the names
    serving.model gives them (stacked by layer kind). `round_to` (a dtype
    name) makes every later upcast of what is stored below float32 go
    through that dtype first: the reading of a precision below the stated
    one (tools/reference_control.py)."""
    del cfg
    out = {"emb": get("dec.word_emb"), "head": get("dec.lm_head"),
           "final_norm": get("dec.final_norm.scale"),
           "norm": get("dec.layers.norm"), "_round_to": round_to}
    for group, keys in (("kda", _KDA), ("mla", _MLA), ("dense", _DENSE),
                        ("moe", _MOE)):
        for k in keys:
            out[f"{group}.{k}"] = get(f"dec.layers.{group}.{k}")
    for k in _EXPERTS:
        out[k] = get("dec.layers." + k)
    return out


def _up(a, round_to=None):
    a = jnp.asarray(a)
    if round_to is not None and a.dtype != _F32:
        a = a.astype(round_to)      # only what is stored below float32
    return a.astype(_F32)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(_F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, gain, eps):
    return _rms(x, gain, eps)


def _sizes(cfg) -> tuple:
    return tuple(sorted(dict(
        Hk=cfg.ssm_heads, K=cfg.ssm_state, P=cfg.ssm_head_dim,
        taps=cfg.ssm_conv, bound=float(cfg.kda_lower_bound),
        nh=cfg.num_heads, dn=cfg.attn_head_dim, dr=cfg.rope_head_dim,
        dv=cfg.v_head_dim, rank=cfg.kv_lora_rank,
        theta=float(cfg.rope_theta), eps=float(cfg.rms_norm_eps),
        k=cfg.experts_per_token, groups=cfg.expert_groups,
        kept=cfg.groups_per_token,
        scaling=float(cfg.routed_scaling)).items()))


def plan(cfg) -> list:
    """[(mixer, mlp)] a layer: "kda" | "mla", "dense" | "moe"."""
    return [("mla" if (l + 1) % cfg.layer_group_size == 0 else "kda",
             "dense" if l < cfg.dense_layers else "moe")
            for l in range(cfg.num_layers)]


# ---------------------------------------------------------------------------
# Kimi Delta Attention: the plain recurrence
# ---------------------------------------------------------------------------


def delta_recurrence(q, k, v, log_a, beta, state):
    """q, k, log_a [T, heads, K], v [T, heads, V], beta [T, heads], state
    [heads, K, V] -> (o [T, heads, V], the state after the last token):
    `S <- Diag(a) S`, `S <- S + beta k (v - S^T k)^T`, `o = S^T q`, one
    position after another."""

    def step(s, row):
        q_t, k_t, v_t, la_t, b_t = row
        s = jnp.exp(la_t)[:, :, None] * s
        held = jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - held))
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    state, o = jax.lax.scan(step, state, (q, k, v, log_a, beta))
    return o, state


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnames=("sz", "rt"))
def _kda_block(xn, tail, state, w_qkv, conv_w, w_f, dt_bias, a_log, w_b,
               w_g, o_norm, w_o, sz, rt=None):
    """A block of positions of one Kimi-Delta layer: xn [T, H] (normed)
    behind the convolution's `tail` [taps - 1, C] and the `state` [heads,
    K, V] the positions before it left -> (the branch [T, H], the tail and
    the state after the block)."""
    s = dict(sz)
    Hk, K, P, taps = s["Hk"], s["K"], s["P"], s["taps"]
    T = xn.shape[0]
    I = Hk * K
    ext = jnp.concatenate([tail, xn @ _up(w_qkv, rt)], axis=0)
    conv = jnp.zeros((T, ext.shape[1]), _F32)
    for j in range(taps):                   # the shifted products
        conv = conv + conv_w[:, j].astype(_F32) * ext[j:j + T]
    qkv = conv * jax.nn.sigmoid(conv)
    q = _unit(qkv[:, :I].reshape(T, Hk, K)) * K ** -0.5
    k = _unit(qkv[:, I:2 * I].reshape(T, Hk, K))
    v = qkv[:, 2 * I:].reshape(T, Hk, P)
    raw = (xn @ _up(w_f, rt) + dt_bias.astype(_F32)).reshape(T, Hk, K)
    log_a = s["bound"] * jax.nn.sigmoid(
        jnp.exp(a_log.astype(_F32))[None, :, None] * raw)
    beta = jax.nn.sigmoid(xn @ _up(w_b, rt))                    # [T, Hk]
    o, state = delta_recurrence(q, k, v, log_a, beta, state)
    o = _rms(o, o_norm, s["eps"]).reshape(T, Hk * P)    # the norm, THEN
    y = o * jax.nn.sigmoid(xn @ _up(w_g, rt))           # the gate
    return y @ _up(w_o, rt), ext[T:], state


# ---------------------------------------------------------------------------
# latent attention: the expanded form
# ---------------------------------------------------------------------------


def _rotate(x, positions, theta):
    """x [T, heads, d]: lanes (2i, 2i + 1) turn by position x theta^(-2i /
    d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=_F32) * 2.0 / d)
    ang = positions.astype(_F32)[:, None, None] * inv
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("sz", "rt"))
def _mla_keys(xn, pos0, wkv_a, kv_norm, wkv_b, sz, rt=None):
    """A block's keys and values: xn [T, H] at positions pos0.. -> k_nope
    [T, nh, dn], k_rope [T, dr], v [T, nh, dv]."""
    s = dict(sz)
    T = xn.shape[0]
    kv = xn @ _up(wkv_a, rt)
    c = _rms(kv[:, :s["rank"]], kv_norm, s["eps"])
    k_rope = _rotate(kv[:, None, s["rank"]:], pos0 + jnp.arange(T),
                     s["theta"])[:, 0]
    both = (c @ _up(wkv_b, rt)).reshape(T, s["nh"], s["dn"] + s["dv"])
    return both[..., :s["dn"]], k_rope, both[..., s["dn"]:]


@functools.partial(jax.jit, static_argnames=("sz", "rt"))
def _mla_block(xn, pos0, k_nope, k_rope, v, wq, w_gate_h, wo, sz, rt=None):
    """A block of queries over the whole sequence's keys: xn [T, H] at
    positions pos0.., k_nope [S, nh, dn], k_rope [S, dr], v [S, nh, dv] ->
    the branch [T, H]."""
    s = dict(sz)
    nh, dn, dr, dv = s["nh"], s["dn"], s["dr"], s["dv"]
    T, S = xn.shape[0], k_nope.shape[0]
    pos = pos0 + jnp.arange(T)
    q = (xn @ _up(wq, rt)).reshape(T, nh, dn + dr)
    q_rope = _rotate(q[..., dn:], pos, s["theta"])
    qb = min(_QUERY_BLOCK, T)

    def attend(args):
        qn, qr, at = args
        sc = (jnp.einsum("thd,shd->hts", qn, k_nope)
              + jnp.einsum("thd,sd->hts", qr, k_rope)) * (dn + dr) ** -0.5
        seen = jnp.arange(S)[None, :] <= at[:, None]
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v)

    split = lambda a: a.reshape((T // qb, qb) + a.shape[1:])   # noqa: E731
    o = jax.lax.map(attend, (split(q[..., :dn]), split(q_rope), split(pos)))
    o = o.reshape(T, nh, dv) \
        * jax.nn.sigmoid(xn @ _up(w_gate_h, rt))[:, :, None]
    return o.reshape(T, nh * dv) @ _up(wo, rt)


# ---------------------------------------------------------------------------
# the MLPs
# ---------------------------------------------------------------------------


def _swiglu(z, wg, wu, wd, rt):
    return (jax.nn.silu(z @ _up(wg, rt)) * (z @ _up(wu, rt))) @ _up(wd, rt)


@functools.partial(jax.jit, static_argnames=("eps", "rt"))
def _dense_block(x, ffn_norm, w_gate, w_up, w_down, eps, rt=None):
    return x + _swiglu(_rms(x, ffn_norm, eps), w_gate, w_up, w_down, rt)


@functools.partial(jax.jit, static_argnames=("sz", "rt"))
def _router_block(x, ffn_norm, router_w, router_bias, shared_gate, shared_up,
                  shared_down, forced, sz, rt=None):
    """The expert layer up to the routed experts, a block of positions.
    forced [T, k] expert ids (-1 in column 0: route for yourself). Returns
    the normed input, the shared expert's output, the experts followed,
    their weights and the route margin."""
    s = dict(sz)
    k, groups, kept = s["k"], s["groups"], s["kept"]
    z = _rms(x, ffn_norm, s["eps"])
    sc = jax.nn.sigmoid(z @ router_w.astype(_F32))
    T, E = sc.shape
    size = E // groups
    select = sc + router_bias.astype(_F32)
    score = jnp.sum(jax.lax.top_k(select.reshape(T, groups, size), 2)[0],
                    axis=-1)                                # [T, groups]
    which = jnp.arange(groups)

    def opened(order):
        return jnp.any(jax.lax.top_k(order, kept)[1][:, :, None] == which,
                       axis=1)

    own = jax.lax.top_k(jnp.where(jnp.repeat(opened(score), size, axis=1),
                                  select, -jnp.inf), k)[1]
    follow = jnp.where(forced[:, :1] >= 0, forced, own)
    # the groups the followed experts lie in are open; the reference's own
    # best groups fill the limit up
    theirs = jnp.any((follow // size)[:, :, None] == which, axis=1)
    open_ = opened(jnp.where(theirs, score + 4.0, score))   # score < 2
    group_margin = jnp.max(jnp.where(open_, -jnp.inf, score), axis=1) \
        - jnp.min(jnp.where(open_, score, jnp.inf), axis=1)
    inside = jnp.any(follow[:, :, None] == jnp.arange(E), axis=1)
    expert_margin = jnp.max(jnp.where(
        jnp.repeat(open_, size, axis=1) & ~inside, select, -jnp.inf), axis=1) \
        - jnp.min(jnp.where(inside, select, jnp.inf), axis=1)
    margin = jnp.where(
        jnp.sum(theirs, axis=1) > kept, MISCOUNT,
        jnp.maximum(jnp.maximum(group_margin, expert_margin), 0.0))
    sf = jnp.take_along_axis(sc, follow, axis=1)
    weights = s["scaling"] * sf / jnp.sum(sf, axis=1, keepdims=True)
    shared = _swiglu(z, shared_gate, shared_up, shared_down, rt)
    return z, shared, follow, weights, margin


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("rt",))
def _one_expert(y, z, rows, weight, wg, wu, wd, layer, expert, rt=None):
    """y [T, H] += weight * expert(z[rows]) at `rows` (weight 0 pads); the
    expert's three matrices are taken out of the stored stacks `[L_moe,
    held, ...]` and upcast here, one expert at a time (`layer` and `expert`
    are traced: one program serves every expert)."""
    out = _swiglu(z[rows], wg[layer, expert], wu[layer, expert],
                  wd[layer, expert], rt)
    return y.at[rows].add(out * weight[:, None])


def _layer(stack, i: int):
    """Layer `i` of a stack, its index an array: one program a stack, not
    one a layer."""
    return stack[jnp.int32(i)]


def moe_layer(params: dict, x, cfg, j: int, live=None, routes=None):
    """Expert layer `j` (among the expert layers) over a block of positions:
    x [T, H] the residual stream, of which the first `live` (None: all) are
    real; routes [live, k] the experts to follow (None: the reference routes
    for itself) -> (x + the shared expert + the HELD experts' part, the
    route margins [T])."""
    sz = _sizes(cfg)
    rt = params.get("_round_to")
    T, k = x.shape[0], cfg.experts_per_token
    live = T if live is None else live
    forced = np.full((T, k), -1, np.int32)
    if routes is not None:
        forced[:live] = routes
    z, y, follow, weights, margin = _router_block(
        x, *(_layer(params["moe." + key], j) for key in _MOE),
        jnp.asarray(forced), sz=sz, rt=rt)
    follow, weights = np.asarray(follow), np.asarray(weights)
    for e in range(params["w_gate"].shape[1]):  # the absent ones add nothing
        rows, slot = np.nonzero(follow[:live] == e)
        if not len(rows):
            continue
        pad = max(_EXPERT_ROWS, 1 << (len(rows) - 1).bit_length()) - len(rows)
        y = _one_expert(
            y, z, jnp.asarray(np.pad(rows, (0, pad))),
            jnp.asarray(np.pad(weights[rows, slot], (0, pad))),
            params["w_gate"], params["w_up"], params["w_down"],
            jnp.int32(j), jnp.int32(e), rt=rt)
    return x + y, np.asarray(margin)


def forward(params: dict, tokens, cfg, routes=None):
    """tokens [T] -> (the final-normed hidden states [T, H] float32, the
    route margins [T, L_moe]). `routes` [>= T, L_moe, k]: the engine's
    experts by position (None: the reference routes for itself)."""
    sz = _sizes(cfg)
    s = dict(sz)
    eps = s["eps"]
    rt = params.get("_round_to")
    n = len(tokens)
    if n > _BLOCK:
        T, step = -(-n // _BLOCK) * _BLOCK, _BLOCK
    else:       # one block, whole query blocks
        T = step = -(-n // _QUERY_BLOCK) * _QUERY_BLOCK \
            if n > _QUERY_BLOCK else n
    tok = np.zeros((T,), np.int32)
    tok[:n] = np.asarray(tokens, np.int32)
    starts = range(0, T, step)
    seen = {"kda": 0, "mla": 0, "dense": 0, "moe": 0}
    margins = []
    with jax.default_matmul_precision("highest"):
        x = [_up(params["emb"][jnp.asarray(tok[t0:t0 + step])], rt)
             for t0 in starts]
        for l, (mixer, mlp) in enumerate(plan(cfg)):
            i, j = seen[mixer], seen[mlp]
            seen[mixer] += 1
            seen[mlp] += 1
            gain = _layer(params["norm"], l)
            xn = [_norm(xb, gain, eps) for xb in x]
            if mixer == "kda":
                w = [_layer(params["kda." + key], i) for key in _KDA]
                tail = jnp.zeros((s["taps"] - 1,
                                  s["Hk"] * (2 * s["K"] + s["P"])), _F32)
                state = jnp.zeros((s["Hk"], s["K"], s["P"]), _F32)
                for b in range(len(x)):
                    f, tail, state = _kda_block(xn[b], tail, state, *w,
                                                sz=sz, rt=rt)
                    x[b] = x[b] + f
            else:
                w = {key: _layer(params["mla." + key], i) for key in _MLA}
                keys = [_mla_keys(xb, jnp.int32(t0), w["wkv_a"],
                                  w["kv_norm"], w["wkv_b"], sz=sz, rt=rt)
                        for xb, t0 in zip(xn, starts)]
                k_nope, k_rope, v = (jnp.concatenate(part, axis=0)
                                     for part in zip(*keys))
                del keys
                for b, t0 in enumerate(starts):
                    x[b] = x[b] + _mla_block(
                        xn[b], jnp.int32(t0), k_nope, k_rope, v, w["wq"],
                        w["w_gate_h"], w["wo"], sz=sz, rt=rt)
                del k_nope, k_rope, v
            del xn
            if mlp == "dense":
                w = [_layer(params["dense." + key], j) for key in _DENSE]
                x = [_dense_block(xb, *w, eps=eps, rt=rt) for xb in x]
                continue
            layer_margin = []
            for b, t0 in enumerate(starts):
                live = max(0, min(step, n - t0))    # real positions here
                x[b], margin = moe_layer(
                    params, x[b], cfg, j, live,
                    None if routes is None
                    else np.asarray(routes)[t0:t0 + live, j])
                layer_margin.append(margin)
            margins.append(np.concatenate(layer_margin)[:n])
        x = jnp.concatenate([_norm(xb, params["final_norm"], eps)
                             for xb in x], axis=0)
    return x[:n], np.stack(margins, -1) if margins \
        else np.zeros((n, 0), np.float32)


@jax.jit
def _block_logits(x, head_block):
    return x @ head_block


def logit_gaps(params: dict, x, tokens) -> np.ndarray:
    """x [M, H] final-norm states, tokens [M] the tokens served after them:
    per row, the best logit minus the served token's, reduced over blocks
    of the vocabulary (the head is `[H, V]`, untied)."""
    head = params["head"]
    tokens = np.asarray(tokens)
    best = np.full(len(tokens), -np.inf, np.float32)
    own = np.zeros(len(tokens), np.float32)
    with jax.default_matmul_precision("highest"):
        for v0 in range(0, head.shape[1], _VOCAB_BLOCK):
            lg = np.asarray(_block_logits(
                x, _up(head[:, v0:v0 + _VOCAB_BLOCK],
                       params.get("_round_to"))))
            best = np.maximum(best, lg.max(axis=1))
            t = tokens - v0
            here = (t >= 0) & (t < lg.shape[1])
            own[here] = lg[np.flatnonzero(here), t[here]]
    return best - own


def all_logits(params: dict, tokens, cfg, routes=None):
    """tokens [T] -> logits [T, V] float32 (tests, at small sizes)."""
    x, _ = forward(params, tokens, cfg, routes)
    with jax.default_matmul_precision("highest"):
        return x @ _up(params["head"], params.get("_round_to"))


def check_sequences(params: dict, sequences: list, cfg) -> list:
    """For each (prompt, served, routes) — routes [>= len(prompt) +
    len(served) - 1, L_moe, k] the engine's experts by position, or None —
    a dict: `gap`, the largest amount by which a served token's logit sits
    below the best logit at its position with the engine's experts
    followed, and `route_margin`, the largest margin by which the reference
    would have routed a position of the sequence otherwise."""
    out = []
    for prompt, served, routes in sequences:
        seq = (list(prompt) + list(served))[:-1]
        x, margins = forward(params, seq, cfg, routes)
        at = len(prompt) - 1 + np.arange(len(served))
        rows = max(64, 1 << (len(served) - 1).bit_length())  # few shapes
        xs = jnp.pad(x[at], ((0, rows - len(served)), (0, 0)))
        gaps = logit_gaps(params, xs, list(served)
                          + [0] * (rows - len(served)))[:len(served)]
        out.append({"gap": float(gaps.max()),
                    "route_margin": float(margins.max(initial=0.0))})
    return out


def worst_logit_gaps(params: dict, sequences: list, cfg) -> list:
    """`decoder_lm.worst_logit_gaps` for (prompt, served[, routes]) tuples;
    without routes the reference routes for itself."""
    full = [(s[0], s[1], s[2] if len(s) > 2 else None) for s in sequences]
    return [c["gap"] for c in check_sequences(params, full, cfg)]
