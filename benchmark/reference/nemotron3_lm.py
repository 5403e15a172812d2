"""Plain reference for a served stage of NVIDIA-Nemotron-3-Super-120B-A12B
("mixer_moe": `configs/nemotron3_super_120b.json`): one teacher-forced
forward over prompt + served tokens, float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`.

Independent of paddle_tpu: no cache, no pools, no chunks, no kernels, no
prefill/decode split; it reads the engine's weights by name and nothing
else. Every layer l is ONE sub-layer behind ONE pre-norm, `x <- x +
f_l(x~)`, `x~ = RMSNorm_l(x)` (eps 1e-5), `f_l` by the l-th character of
`hybrid_override_pattern`:

    M  [z | xBC | dt] = x~ W_in            (columns z | x | B | C | dt)
       xBC_t <- silu(b_c + sum_j w_c[:, j] xBC_{t-3+j})   (zero left pad)
       dt_t = softplus(dt_t + dt_bias),  a_t = exp(-dt_t exp(A_log))
       S_t = a_t S_{t-1} + dt_t x_t (x) B_t    (a `lax.scan` over tokens;
                                   head h reads group h // (heads / groups))
       y_t = S_t C_t + D x_t
       y <- RMSNorm_grouped(y * silu(z)) (the gate BEFORE the norm, 8 groups)
       f = y W_out
    *  q = x~ W_q (32 heads of 128), k, v = x~ W_k, x~ W_v (2 heads), NO
       rotary and no bias; causal softmax(q k^T / sqrt(128)) v, 16 query
       heads a KV head; f = o W_o
    E  s = sigmoid(x~ W_r) over 512; T = the 22 largest of s + b (ties to
       the lower expert; one group, so no group limit); w_e = 5 s_e /
       sum_T s; u = x~ W_dn (1,024 wide);
       r = sum_{e in T, e held} w_e W2_e relu(W1_e u)^2;
       f = r W_up + Ws2 relu(Ws1 x~)^2
    model:  x_0 = Emb[token]; logits = RMSNorm(x_L) W_head

Departures from the published description (the configuration's
`departures` and `assumed`): the stage holds the first `num_layers` layers
and applies the final norm and the head to their output; of the 512 experts
the first `experts_held` are HELD (one chip's share) and what the others
would add is left out, in the engine and here alike; the vocabulary is the
slice the engine holds; no multi-token-prediction module.

THE EXPERTS ARE TEACHER-FORCED (`zaya_lm.py`'s rule). Top-22 of 512 over
random weights flips on rounding. The engine reports its 22 experts for
every (position, expert layer); the reference follows them (weights from
its own scores, renormalised over the followed set) and reports
`route_margin`: how far the best `s + b` outside the followed set lies above
the weakest inside, by its own float32 lights.

Memory: the float32 reference works beside 13 GB of engine. Layers are
walked one at a time and a matrix is brought to float32 where it is used;
an expert's two matrices one expert at a time and only over the tokens that
follow it; attention a KV head and a block of queries at a time; the head a
block of the vocabulary at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
_MIXER = ("w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
          "ssm_norm", "w_out")
_ATTENTION = ("wq", "wk", "wv", "wo")
_MOE = ("router_w", "router_bias", "w_dn", "w_up", "shared_in", "shared_out")
_EXPERTS = ("w1", "w2")

# Few distinct shapes: each is a compile of every jitted piece. A sequence
# longer than a query block pads on the right to a multiple of `_PAD_TO`
# (padding cannot reach a causal position before it); an expert's rows pad
# to a power of two from `_EXPERT_ROWS`.
_PAD_TO = 1536
_QUERY_BLOCK = 512      # queries attended together
_EXPERT_ROWS = 64
_VOCAB_BLOCK = 16384


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
    for group, keys in (("mix", _MIXER), ("attn", _ATTENTION),
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
    Hs, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, \
        cfg.ssm_state
    nkv = cfg.num_kv_heads or cfg.num_heads
    dh = cfg.attn_head_dim or cfg.hidden_size // cfg.num_heads
    return tuple(sorted(dict(
        Hs=Hs, P=P, G=G, N=N, I=Hs * P, C=Hs * P + 2 * G * N, K=cfg.ssm_conv,
        nh=cfg.num_heads, nkv=nkv, dh=dh, eps=float(cfg.rms_norm_eps),
        k=cfg.experts_per_token, scaling=float(cfg.routed_scaling)).items()))


@functools.partial(jax.jit, static_argnames=("sz", "rt"))
def _mixer(xn, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, ssm_norm,
           w_out, sz, rt=None):
    """xn [T, H] (normed) -> the mixer's branch [T, H]."""
    s = dict(sz)
    Hs, P, G, N, I, C, K = (s[k] for k in ("Hs", "P", "G", "N", "I", "C",
                                            "K"))
    T = xn.shape[0]
    proj = xn @ _up(w_in, rt)
    z, xbc, dt = proj[:, :I], proj[:, I:I + C], proj[:, I + C:]
    ext = jnp.concatenate([jnp.zeros((K - 1, C), _F32), xbc], axis=0)
    conv = conv_b.astype(_F32)
    for j in range(K):                      # the shifted products
        conv = conv + conv_w[:, j].astype(_F32) * ext[j:j + T]
    xbc = conv * jax.nn.sigmoid(conv)
    x = xbc[:, :I].reshape(T, Hs, P)
    bm = xbc[:, I:I + G * N].reshape(T, G, N)
    cm = xbc[:, I + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + dt_bias.astype(_F32))             # [T, Hs]
    a = jnp.exp(-dt * jnp.exp(a_log.astype(_F32)))

    def step(state, row):                   # state [G, Hs / G, P, N]
        a_t, dt_t, x_t, b_t, c_t = row
        per = Hs // G
        state = a_t.reshape(G, per)[:, :, None, None] * state \
            + (dt_t[:, None] * x_t).reshape(G, per, P)[:, :, :, None] \
            * b_t[:, None, None, :]
        return state, jnp.sum(state * c_t[:, None, None, :],
                              axis=-1).reshape(Hs, P)

    _, y = jax.lax.scan(step, jnp.zeros((G, Hs // G, P, N), _F32),
                        (a, dt, x, bm, cm))
    y = (y + d_skip.astype(_F32)[:, None] * x).reshape(T, I)
    y = y * (z * jax.nn.sigmoid(z))         # the gate before the norm
    g = y.reshape(T, G, I // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + s["eps"])
    y = g.reshape(T, I) * ssm_norm.astype(_F32)
    return y @ _up(w_out, rt)


@functools.partial(jax.jit, static_argnames=("sz", "rt"))
def _attention(xn, wq, wk, wv, wo, sz, rt=None):
    """xn [T, H] (normed) -> the attention branch [T, H]: no rotary; one KV
    head (and its group of query heads) and one block of queries at a
    time."""
    s = dict(sz)
    nh, nkv, dh = s["nh"], s["nkv"], s["dh"]
    T = xn.shape[0]
    q = (xn @ _up(wq, rt)).reshape(T, nh, dh)
    k = (xn @ _up(wk, rt)).reshape(T, nkv, dh)
    v = (xn @ _up(wv, rt)).reshape(T, nkv, dh)
    g = nh // nkv
    qb = min(_QUERY_BLOCK, T)
    heads = []
    for j in range(nkv):
        blocks = []
        for q0 in range(0, T, qb):
            sc = jnp.einsum("tgd,sd->gts", q[q0:q0 + qb, j * g:(j + 1) * g],
                            k[:, j]) * dh ** -0.5
            seen = jnp.arange(T)[None, :] <= (q0 + jnp.arange(
                sc.shape[1]))[:, None]
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
            blocks.append(jnp.einsum("gts,sd->tgd", p, v[:, j]))
        heads.append(jnp.concatenate(blocks, axis=0))
    o = jnp.concatenate(heads, axis=1).reshape(T, nh * dh)
    return o @ _up(wo, rt)


@functools.partial(jax.jit, static_argnames=("sz", "rt"))
def _router(xn, router_w, router_bias, w_dn, shared_in, shared_out, forced,
            sz, rt=None):
    """The expert layer up to the routed experts. forced [T, k] expert ids
    (-1 in column 0: route for yourself). Returns the token in the latent,
    the shared expert's output, the experts followed, their weights and the
    route margin."""
    s = dict(sz)
    sc = jax.nn.sigmoid(xn @ router_w.astype(_F32))
    select = sc + router_bias.astype(_F32)
    own = jax.lax.top_k(select, s["k"])[1]
    follow = jnp.where(forced[:, :1] >= 0, forced, own)
    inside = jnp.any(follow[:, :, None]
                     == jnp.arange(sc.shape[1])[None, None, :], axis=1)
    margin = jnp.maximum(
        jnp.max(jnp.where(inside, -jnp.inf, select), axis=1)
        - jnp.min(jnp.take_along_axis(select, follow, axis=1), axis=1), 0.0)
    sf = jnp.take_along_axis(sc, follow, axis=1)
    weights = s["scaling"] * sf / jnp.sum(sf, axis=1, keepdims=True)
    u = xn @ _up(w_dn, rt)
    hid = jnp.maximum(xn @ _up(shared_in, rt), 0.0)
    return u, (hid * hid) @ _up(shared_out, rt), follow, weights, margin


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("rt",))
def _one_expert(r, u, rows, weight, w1, w2, layer, expert, rt=None):
    """r [T, Z] += weight * W2 relu(W1 u[rows])^2 at `rows` (weight 0
    pads); the expert's two matrices are taken out of the stored stacks
    `[L_experts, held, ...]` and upcast here, one expert at a time (`layer`
    and `expert` are traced: one program serves every expert)."""
    g = jnp.maximum(u[rows] @ _up(w1[layer, expert], rt), 0.0)
    return r.at[rows].add((g * g * weight[:, None])
                          @ _up(w2[layer, expert], rt))


@functools.partial(jax.jit, static_argnames=("rt",))
def _out_of_latent(r, w_up, rt=None):
    return r @ _up(w_up, rt)


def _layer(stack, i: int):
    """Layer `i` of a stack, its index an array: one program a stack, not
    one a layer."""
    return stack[jnp.int32(i)]


def forward(params: dict, tokens, cfg, routes=None):
    """tokens [T] -> (the final-normed hidden states [T, H] float32, the
    route margins [T, L_experts]). `routes` [>= T, L_experts, k]: the
    engine's experts by position (None: the reference routes for itself)."""
    sz = _sizes(cfg)
    eps = float(cfg.rms_norm_eps)
    rt = params.get("_round_to")
    n = len(tokens)
    T = -(-n // _PAD_TO) * _PAD_TO if n > _QUERY_BLOCK else n
    tok = np.zeros((T,), np.int32)
    tok[:n] = np.asarray(tokens, np.int32)
    held = params["w1"].shape[1]
    k = cfg.experts_per_token
    seen = {"M": 0, "*": 0, "E": 0}
    margins = []
    with jax.default_matmul_precision("highest"):
        x = _up(params["emb"][jnp.asarray(tok)], rt)
        for l, kind in enumerate(cfg.layer_pattern):
            i = seen[kind]
            seen[kind] += 1
            xn = _norm(x, _layer(params["norm"], l), eps)
            if kind == "M":
                f = _mixer(xn, *(_layer(params["mix." + key], i)
                                 for key in _MIXER), sz=sz, rt=rt)
            elif kind == "*":
                f = _attention(xn, *(_layer(params["attn." + key], i)
                                     for key in _ATTENTION), sz=sz, rt=rt)
            else:
                forced = np.full((T, k), -1, np.int32)
                if routes is not None:
                    forced[:n] = np.asarray(routes)[:n, i]
                u, shared, follow, weights, margin = _router(
                    xn, *(_layer(params["moe." + key], i) for key in (
                        "router_w", "router_bias", "w_dn", "shared_in",
                        "shared_out")), jnp.asarray(forced), sz=sz, rt=rt)
                follow, weights = np.asarray(follow), np.asarray(weights)
                r = jnp.zeros_like(u)
                for e in range(held):       # the absent experts add nothing
                    rows, slot = np.nonzero(follow[:n] == e)
                    if not len(rows):
                        continue
                    pad = max(_EXPERT_ROWS,
                              1 << (len(rows) - 1).bit_length()) - len(rows)
                    r = _one_expert(
                        r, u, jnp.asarray(np.pad(rows, (0, pad))),
                        jnp.asarray(np.pad(weights[rows, slot], (0, pad))),
                        params["w1"], params["w2"], jnp.int32(i),
                        jnp.int32(e), rt=rt)
                f = _out_of_latent(r, _layer(params["moe.w_up"], i),
                                   rt=rt) + shared
                margins.append(np.asarray(margin)[:n])
            x = x + f
        x = _norm(x, params["final_norm"], eps)
    return x[:n], np.stack(margins, -1)


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
    len(served) - 1, L_experts, k] the engine's experts by position, or
    None — a dict: `gap`, the largest amount by which a served token's
    logit sits below the best logit at its position with the engine's
    experts followed, and `route_margin`, the largest margin by which the
    reference would have routed a position of the sequence otherwise."""
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
