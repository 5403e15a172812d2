"""Plain reference for the served ZAYA1 decoder (`configs/zaya1_8b.json`):
one teacher-forced causal forward over prompt + served tokens in float32,
`jax.default_matmul_precision("highest")`, no cache, no kernel, no paged
anything, independent of paddle_tpu (it reads the engine's weights by name
and nothing else).

One layer, for token t of a sequence (x_t in R^H; every index t-1 reads
zero at t = 0):

 1. u = RMSNorm(x).  q~ = u W_q, k~ = u W_k,  c = [q~, k~].
 2. a_t = w0[:,0] c_{t-1} + w0[:,1] c_t + b0                (depthwise)
    e_t = W1[0] a_{t-1} + W1[1] a_t + b1   (block diagonal, one block a head)
 3. q_h = e^q_h + (q~_h + k~_j)/2,  k_j = e^k_j + (mean_{h in j} q~_h + k~_j)/2
 4. q, k L2-normalised per head to length sqrt(dh); k_j *= exp(tau_j);
    rotary on the first `partial_rotary_factor * dh` lanes at position t
 5. v_t = [u_t W_v1, u_{t-1} W_v2]
 6. causal grouped-query attention, scale dh^-0.5;  h = x + o W_o
 7. z = RMSNorm(h);  r_l = z W_r + b_r + gamma_l r_{l-1};
    s = W3 gelu(W2 gelu(W1 RMSNorm(r_l) + b1) + b2) + b3;  p = softmax(s);
    expert e = argmax(p + bias);  y = h + p_e W_down,e(silu(W_gate,e z) * W_up,e z)
 8. after the last layer RMSNorm, logits = x E^T (tied embedding).

ROUTING IS TEACHER-FORCED. Top-1 over random weights flips on rounding: a
bfloat16 engine and this float32 reference disagree on the expert of a few
tokens in a hundred, and every later token of that sequence then differs by
the scale of the logits, not of the rounding. So the engine reports the
expert it chose for every (position, layer) and the reference FOLLOWS it,
while recording how wrong that choice was by its own lights: the margin
`(p + bias)[own best] - (p + bias)[engine's]`. A near-tie has a margin of
the rounding's size; a wrong page, a stale state row or a dropped
normalisation moves it to the scale of the probabilities.

Memory: a float32 copy of the served weights does not fit beside the
engine (24 layers are 19.9 GB). Layers are walked one at a time, an expert's
weights upcast one expert at a time, and the head is reduced over blocks of
the vocabulary to the two numbers the check needs per served token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_LAYER_KEYS = (
    "attn_norm", "wqk", "wv", "wo", "conv0_w", "conv0_b", "conv1_w",
    "conv1_b", "k_temp", "ffn_norm", "router_in_w", "router_in_b",
    "router_gamma", "router_norm", "router_w1", "router_b1", "router_w2",
    "router_b2", "router_w3", "router_b3", "router_bias")
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")
_VOCAB_BLOCK = 16384
_POSITION_BLOCK = 1024
_SEQUENCE_BLOCK = 2         # sequences forwarded together


def read_params(get, cfg) -> dict:
    """The engine's weights AS STORED (no copy, no upcast), by the names
    serving.model gives them: `get(name)` returns an array."""
    del cfg
    out = {"word_emb": get("dec.word_emb"),
           "final_norm": get("dec.final_norm.scale")}
    for k in _LAYER_KEYS + _EXPERT_KEYS:
        out[k] = get("dec.layers." + k)
    return out


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _prev(x):
    """x [N, T, C] read one token back; zeros at t = 0."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _rotary(x, rotary_dim, theta):
    """x [N, T, heads, dh], position = index along T."""
    half = rotary_dim // 2
    inv = theta ** (-(jnp.arange(half, dtype=jnp.float32) * 2 / rotary_dim))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary_dim:]], axis=-1)


def _unit_heads(x):
    dh = x.shape[-1]
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) / dh + 1e-6)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "dh", "rot",
                                             "theta", "eps"))
def _attention_half(x, p, nh, nkv, dh, rot, theta, eps):
    """Equations 1-6: x [N, T, H] -> h [N, T, H]."""
    N, T, _ = x.shape
    g = nh // nkv
    u = _rms_norm(x, p["attn_norm"], eps)
    c = u @ p["wqk"]
    a = p["conv0_w"][:, 0] * _prev(c) + p["conv0_w"][:, 1] * c + p["conv0_b"]
    w1 = p["conv1_w"]                                    # [2, G, dh, dh]
    heads = lambda t: t.reshape(N, T, nh + nkv, dh)      # noqa: E731
    e = (jnp.einsum("ntgi,gio->ntgo", heads(_prev(a)), w1[0])
         + jnp.einsum("ntgi,gio->ntgo", heads(a), w1[1])
         + p["conv1_b"].reshape(nh + nkv, dh))
    cq, ck = heads(c)[:, :, :nh], heads(c)[:, :, nh:]
    q = e[:, :, :nh] + 0.5 * (cq + jnp.repeat(ck, g, axis=2))
    k = e[:, :, nh:] + 0.5 * (cq.reshape(N, T, nkv, g, dh).mean(3) + ck)
    q = _rotary(_unit_heads(q), rot, theta)
    k = _rotary(_unit_heads(k) * jnp.exp(p["k_temp"])[:, None], rot, theta)
    v12 = u @ p["wv"]
    v = jnp.stack([v12[..., :dh], _prev(v12[..., dh:])], axis=2)

    def one(args):                                       # one sequence
        q1, k1, v1 = args                                # [T, heads, dh]
        s = jnp.einsum("qhd,khd->hqk", q1,
                       jnp.repeat(k1, g, axis=1)) * dh ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                          jnp.repeat(v1, g, axis=1))

    o = jax.lax.map(one, (q, k, v)).reshape(N, T, nh * dh)
    return x + o @ p["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _router(h, r_prev, forced, p, eps):
    """Equation 7 up to the choice. forced [N, T] int (-1: route for
    yourself). Returns z, r, the expert followed, its probability and the
    margin by which the reference would have chosen otherwise."""
    z = _rms_norm(h, p["ffn_norm"], eps)
    r = z @ p["router_in_w"] + p["router_in_b"] + p["router_gamma"] * r_prev
    t = _rms_norm(r, p["router_norm"], eps)
    t = jax.nn.gelu(t @ p["router_w1"] + p["router_b1"], approximate=False)
    t = jax.nn.gelu(t @ p["router_w2"] + p["router_b2"], approximate=False)
    probs = jax.nn.softmax(t @ p["router_w3"] + p["router_b3"], axis=-1)
    select = probs + p["router_bias"]
    own = jnp.argmax(select, axis=-1)
    follow = jnp.where(forced >= 0, forced, own)
    pick = lambda a: jnp.take_along_axis(a, follow[..., None], -1)[..., 0]  # noqa: E731
    margin = jnp.max(select, axis=-1) - pick(select)
    return z, r, follow, pick(probs), margin


@jax.jit
def _one_expert(z, weight, wg, wu, wd):
    """weight [N, T]: p_e where the token follows this expert, else 0."""
    return (jax.nn.silu(z @ wg) * (z @ wu) * weight[..., None]) @ wd


def forward(params: dict, tokens, cfg, routes=None):
    """tokens [N, T] int; routes [N, T, L] int with -1 where the reference
    routes for itself (or None). Returns (x_final [N, T, H] after the last
    norm, margins [N, T, L], followed [N, T, L])."""
    nh, nkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    geom = dict(nh=nh, nkv=nkv, dh=dh,
                rot=int(dh * cfg.partial_rotary_factor),
                theta=float(cfg.rope_theta), eps=float(cfg.rms_norm_eps))
    tokens = jnp.asarray(tokens, jnp.int32)
    N, T = tokens.shape
    x = _f32(params["word_emb"][tokens])
    r = jnp.zeros((N, T, params["router_in_b"].shape[-1]), jnp.float32)
    margins, followed = [], []
    with jax.default_matmul_precision("highest"):
        for l in range(cfg.num_layers):
            p = {k: _f32(params[k][l]) for k in _LAYER_KEYS}
            h = _attention_half(x, p, **geom)
            forced = (jnp.full((N, T), -1, jnp.int32) if routes is None
                      else jnp.asarray(routes[:, :, l], jnp.int32))
            z, r, follow, p_e, margin = _router(h, r, forced, p,
                                                geom["eps"])
            y = jnp.zeros_like(h)
            for e in range(cfg.num_experts):
                wg, wu, wd = (_f32(params[k][l, e]) for k in _EXPERT_KEYS)
                y = y + _one_expert(z, jnp.where(follow == e, p_e, 0.0),
                                    wg, wu, wd)
            x = h + y
            margins.append(margin)
            followed.append(follow)
        x = _rms_norm(x, _f32(params["final_norm"]), geom["eps"])
    return x, jnp.stack(margins, -1), jnp.stack(followed, -1)


@jax.jit
def _block_logits(x, emb_block):
    return x @ _f32(emb_block).T


def logit_gaps(params: dict, x, tokens) -> np.ndarray:
    """x [M, H] final-norm states, tokens [M] the tokens served after them:
    per row, the best logit minus the served token's, reduced over blocks
    of the vocabulary."""
    emb = params["word_emb"]
    V = emb.shape[0]
    tokens = np.asarray(tokens)
    best = np.full(len(tokens), -np.inf, np.float32)
    own = np.zeros(len(tokens), np.float32)
    with jax.default_matmul_precision("highest"):
        for p0 in range(0, len(tokens), _POSITION_BLOCK):
            rows = slice(p0, p0 + _POSITION_BLOCK)
            xb = x[rows]                  # the last block padded: one shape
            xb = jnp.pad(xb, ((0, _POSITION_BLOCK - xb.shape[0]), (0, 0)))
            for v0 in range(0, V, _VOCAB_BLOCK):
                lg = np.asarray(_block_logits(
                    xb, emb[v0:v0 + _VOCAB_BLOCK]))[:len(tokens[rows])]
                best[rows] = np.maximum(best[rows], lg.max(axis=1))
                t = tokens[rows] - v0
                here = (t >= 0) & (t < lg.shape[1])
                own[rows][here] = lg[np.flatnonzero(here), t[here]]
    return best - own


def check_sequences(params: dict, sequences: list, cfg) -> list:
    """For each (prompt, served, routes) — routes [>= len(prompt) +
    len(served) - 1, L] the engine's experts by position, or None — a dict:
    `gap`, the largest amount by which a served token's logit sits below
    the best logit at its position with the engine's routes followed, and
    `route_margin`, the largest margin by which the reference would have
    routed a position of the sequence otherwise."""
    # one padded length for every block: one compile of each layer half
    T = max(len(p) + len(s) for p, s, _ in sequences) - 1
    T = -(-T // 256) * 256
    out = []
    for i in range(0, len(sequences), _SEQUENCE_BLOCK):
        out.extend(_check_block(params, sequences[i:i + _SEQUENCE_BLOCK],
                                cfg, T))
    return out


def _check_block(params: dict, sequences: list, cfg, T: int) -> list:
    tok = np.zeros((len(sequences), T), np.int32)
    forced = np.full((len(sequences), T, cfg.num_layers), -1, np.int32)
    for i, (prompt, served, routes) in enumerate(sequences):
        seq = (list(prompt) + list(served))[:-1]
        tok[i, :len(seq)] = seq
        if routes is not None:
            n = min(len(seq), len(routes))
            forced[i, :n] = np.asarray(routes)[:n]
    x, margins, _ = forward(params, tok, cfg, forced)
    margins = np.asarray(margins)
    rows, served_tokens, owner = [], [], []
    for i, (prompt, served, _) in enumerate(sequences):
        rows.append(x[i, len(prompt) - 1:len(prompt) - 1 + len(served)])
        served_tokens.extend(served)
        owner.extend([i] * len(served))
    gaps = logit_gaps(params, jnp.concatenate(rows), served_tokens)
    owner = np.asarray(owner)
    return [{"gap": float(gaps[owner == i].max()),
             "route_margin": float(
                 margins[i, :len(prompt) + len(served) - 1].max())}
            for i, (prompt, served, _) in enumerate(sequences)]


def worst_logit_gaps(params: dict, sequences: list, cfg) -> list:
    """`decoder_lm.worst_logit_gaps` for (prompt, served[, routes]) tuples;
    without routes the reference routes for itself."""
    full = [(s[0], s[1], s[2] if len(s) > 2 else None) for s in sequences]
    return [c["gap"] for c in check_sequences(params, full, cfg)]
