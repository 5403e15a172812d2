"""Plain reference for a served Falcon-H1 stage ("parallel_ssm"): one
teacher-forced forward over prompt + served tokens, float32 `jax.numpy`
under `jax.default_matmul_precision("highest")`.

Independent of paddle_tpu: no cache, no pools, no chunks, no kernels, no
prefill/decode split. Every layer, from the published config (x the
residual stream, `x~ = RMSNorm(x)`, eps 1e-5; the scalars are the config's
muP multipliers):

    mixer:      [z | xBC | dt] = ((ssm_in x~) W_in) * ssm_multipliers
                                 (laid over the columns z | x | B | C | dt)
                xBC_t <- silu(b_c + sum_j w_c[:, j] xBC_{t-3+j})  (four
                                 shifted products, zero left pad)
                dt_t = softplus(dt_t + dt_bias),  a_t = exp(-dt_t exp(A_log))
                S_t = a_t S_{t-1} + dt_t x_t (x) B_t   (a `lax.scan` over
                                 tokens; head h reads group h // (H / G))
                y_t = S_t C_t + D x_t
                y <- y * silu(z); RMSNorm within each group of channels
                ssm = (y W_out) * ssm_out
    attention:  q = rope((attn_in x~) Wq), k = rope((attn_in x~) Wk * key),
                v = (attn_in x~) Wv; causal softmax(q k / sqrt(dh)) v;
                att = (a Wo) * attn_out
    x <- x + ssm + att
    x <- x + (silu(gate_m * Wg x~') * (Wu x~')) Wd * down_m
    model:      x_0 = emb_m * Emb[token]; logits = head_m * RMSNorm(x_L) W_head

Departures from the published description: the stage holds the first
`num_layers` layers and applies the final norm and the head to their output
(the configuration's `departures`); the readings the config does not itself
give (the column order of `W_in` and of `ssm_multipliers`, the gate before
the grouped norm, the key multiplier before the rotation, `mlp_multipliers`
= [gate, down], `attention_in_multiplier` on the whole attention input) are
the Hugging Face `falcon_h1` modelling code's, listed under the
configuration's `assumed`.

The weights are read as stored (bfloat16 as served) and one matrix at a
time is brought to float32 where it is used: the head runs by vocabulary
block, the SwiGLU by width block, attention by KV head, so that a
3,072-token request fits beside the engine on the chip (the float32 head
alone would be 5.3 GB).

Serving is right when every token the engine emitted is, by these logits,
the best token at its position or within the stated tolerance of it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
_LAYER_KEYS = ("attn_norm", "w_in", "conv_w", "conv_b", "dt_bias", "a_log",
               "d_skip", "ssm_norm", "w_out", "wq", "wk", "wv", "wo",
               "ffn_norm", "w_gate", "w_up", "w_down")
_PAD_TO = 512           # sequences are padded on the right to a multiple
_HEAD_BLOCKS = 32       # vocabulary blocks of the head
_MLP_BLOCKS = 8         # width blocks of the SwiGLU


def read_params(get, cfg, round_to=None) -> dict:
    """The engine's weights as stored, by the names serving.model gives
    them (layers stacked on the leading axis); nothing is converted here.
    `round_to` (a dtype name) makes every later upcast of what is stored
    below float32 go through that dtype first: the reading of a precision
    below the stated one (tools/reference_control.py)."""
    del cfg
    out = {"emb": get("dec.word_emb"), "head": get("dec.lm_head"),
           "final_norm": get("dec.final_norm.scale"), "_round_to": round_to}
    out.update({k: get("dec.layers." + k) for k in _LAYER_KEYS})
    return out


def _sizes(cfg) -> dict:
    Hs, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, \
        cfg.ssm_state
    nkv = cfg.num_kv_heads or cfg.num_heads
    dh = cfg.attn_head_dim or cfg.hidden_size // cfg.num_heads
    return dict(Hs=Hs, P=P, G=G, N=N, I=Hs * P, C=Hs * P + 2 * G * N,
                K=cfg.ssm_conv, nh=cfg.num_heads, nkv=nkv, dh=dh,
                eps=float(cfg.rms_norm_eps), theta=float(cfg.rope_theta))


def _up(a, round_to=None):
    if round_to is not None and a.dtype != _F32:
        a = a.astype(round_to)      # only what is stored below float32
    return a.astype(_F32)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(_F32)


def _rope(x, theta):
    """x [T, heads, dh]: rotate-half over the whole head, position = row."""
    T, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=_F32) * 2.0 / dh)
    ang = jnp.arange(T, dtype=_F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("sz", "mult", "rt"))
def _mixer(xn, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, ssm_norm,
           w_out, sz, mult, rt=None):
    """xn [T, H] (normed) -> the mixer's branch [T, H]."""
    s = dict(sz)
    Hs, P, G, N, I, C, K = (s[k] for k in ("Hs", "P", "G", "N", "I", "C",
                                            "K"))
    ssm_in, ssm_out, m = mult
    T = xn.shape[0]
    col = jnp.concatenate([jnp.full((n,), v, _F32) for n, v in zip(
        (I, I, G * N, G * N, Hs), m)])
    proj = ((xn * ssm_in) @ _up(w_in, rt)) * col
    z, xbc, dt = proj[:, :I], proj[:, I:I + C], proj[:, I + C:]
    ext = jnp.concatenate([jnp.zeros((K - 1, C), _F32), xbc], axis=0)
    conv = conv_b.astype(_F32)
    for j in range(K):                      # the shifted products
        conv = conv + conv_w[:, j].astype(_F32) * ext[j:j + T]
    xbc = conv * jax.nn.sigmoid(conv)
    x = xbc[:, :I].reshape(T, Hs, P)
    bm = jnp.repeat(xbc[:, I:I + G * N].reshape(T, G, N), Hs // G, axis=1)
    cm = jnp.repeat(xbc[:, I + G * N:].reshape(T, G, N), Hs // G, axis=1)
    dt = jax.nn.softplus(dt + dt_bias.astype(_F32))             # [T, Hs]
    a = jnp.exp(-dt * jnp.exp(a_log.astype(_F32)))

    def step(state, row):                   # state [Hs, P, N]
        a_t, dt_t, x_t, b_t, c_t = row
        state = a_t[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((Hs, P, N), _F32),
                        (a, dt, x, bm, cm))
    y = (y + d_skip.astype(_F32)[:, None] * x).reshape(T, I)
    y = y * (z * jax.nn.sigmoid(z))         # the gate before the norm
    g = y.reshape(T, G, I // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + s["eps"])
    y = g.reshape(T, I) * ssm_norm.astype(_F32)
    return (y @ _up(w_out, rt)) * ssm_out


@functools.partial(jax.jit, static_argnames=("sz", "mult", "rt"))
def _attention(xn, wq, wk, wv, wo, sz, mult, rt=None):
    """xn [T, H] (normed) -> the attention branch [T, H], one KV head (and
    its group of query heads) at a time."""
    s = dict(sz)
    nh, nkv, dh = s["nh"], s["nkv"], s["dh"]
    attn_in, attn_out, key_m = mult
    T = xn.shape[0]
    xa = xn * attn_in
    q = _rope((xa @ _up(wq, rt)).reshape(T, nh, dh), s["theta"])
    k = _rope(((xa @ _up(wk, rt)) * key_m).reshape(T, nkv, dh),
              s["theta"])
    v = (xa @ _up(wv, rt)).reshape(T, nkv, dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    g = nh // nkv
    outs = []
    for j in range(nkv):
        sc = jnp.einsum("tgd,sd->gts", q[:, j * g:(j + 1) * g], k[:, j]) \
            * dh ** -0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("gts,sd->tgd", p, v[:, j]))
    o = jnp.concatenate(outs, axis=1).reshape(T, nh * dh)
    return (o @ _up(wo, rt)) * attn_out


@functools.partial(jax.jit, static_argnames=("mult", "rt"))
def _mlp_block(zn, w_gate, w_up, w_down, mult, rt=None):
    gate_m, down_m = mult
    g = (zn @ _up(w_gate, rt)) * gate_m
    return ((g * jax.nn.sigmoid(g)) * (zn @ _up(w_up, rt))) \
        @ _up(w_down, rt) * down_m


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, gain, eps):
    return _rms(x, gain, eps)


def _blocks(n: int, want: int) -> int:
    """How many equal blocks to cut `n` into: `want`, or the nearest
    smaller count that divides it."""
    while n % want:
        want -= 1
    return want


def hidden_states(params: dict, tokens, cfg):
    """tokens [T] -> the final-normed hidden states [T, H] float32."""
    sz = tuple(sorted(_sizes(cfg).items()))
    eps = float(cfg.rms_norm_eps)
    rt = params.get("_round_to")
    x = _up(params["emb"][jnp.asarray(tokens, jnp.int32)], rt) \
        * float(cfg.embedding_multiplier)
    F = params["w_gate"].shape[-1]
    nb = _blocks(F, _MLP_BLOCKS)
    fb = F // nb
    for l in range(cfg.num_layers):
        p = {k: params[k][l] for k in _LAYER_KEYS if k not in (
            "w_gate", "w_up", "w_down")}
        xn = _norm(x, p["attn_norm"], eps)
        ssm = _mixer(xn, p["w_in"], p["conv_w"], p["conv_b"], p["dt_bias"],
                     p["a_log"], p["d_skip"], p["ssm_norm"], p["w_out"], sz,
                     (float(cfg.ssm_in_multiplier),
                      float(cfg.ssm_out_multiplier),
                      tuple(float(v) for v in cfg.ssm_multipliers)), rt)
        att = _attention(xn, p["wq"], p["wk"], p["wv"], p["wo"], sz,
                         (float(cfg.attention_in_multiplier),
                          float(cfg.attention_out_multiplier),
                          float(cfg.key_multiplier)), rt)
        x = x + ssm + att
        zn = _norm(x, p["ffn_norm"], eps)
        for b in range(nb):
            cut = slice(b * fb, (b + 1) * fb)
            x = x + _mlp_block(zn, params["w_gate"][l][:, cut],
                               params["w_up"][l][:, cut],
                               params["w_down"][l][cut],
                               tuple(float(v) for v in cfg.mlp_multipliers),
                               rt)
    return _norm(x, params["final_norm"], eps)


@functools.partial(jax.jit, static_argnames=("rt",))
def _head_block(xs, head_block, best, served_logit, served, first, rt=None):
    """One vocabulary block of the head over the rows `xs`: the running
    best logit and the served tokens' logits (`served` are ids, `first` the
    block's first id)."""
    logits = xs @ _up(head_block, rt)
    best = jnp.maximum(best, jnp.max(logits, axis=-1))
    at = served - first
    inside = (at >= 0) & (at < logits.shape[-1])
    mine = jnp.take_along_axis(
        logits, jnp.clip(at, 0, logits.shape[-1] - 1)[:, None], axis=1)[:, 0]
    return best, jnp.where(inside, mine, served_logit)


def all_logits(params: dict, tokens, cfg):
    """tokens [T] -> logits [T, V] float32 (tests, at small sizes)."""
    with jax.default_matmul_precision("highest"):
        xn = hidden_states(params, tokens, cfg)
        return (xn @ _up(params["head"], params.get("_round_to"))) \
            * float(cfg.lm_head_multiplier)


def worst_logit_gaps(params: dict, sequences: list, cfg) -> list:
    """For each (prompt, served) pair: the largest amount by which a served
    token's logit sits below the best logit at its position. A sequence is
    padded on the right to a multiple of 512 tokens (fewer shapes to
    compile); padding cannot reach a causal position before it."""
    gaps = []
    V = params["head"].shape[-1]
    nb = _blocks(V, _HEAD_BLOCKS)
    vb = V // nb
    for prompt, served in sequences:
        seq = list(prompt) + list(served)
        tok = np.zeros((-(-len(seq) // _PAD_TO) * _PAD_TO,), np.int32)
        tok[:len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            xn = hidden_states(params, tok, cfg)
            # the served rows, the last one repeated up to a multiple of
            # 128 (fewer shapes to compile)
            n = -(-len(served) // 128) * 128
            at = np.minimum(np.arange(n), len(served) - 1)
            xs = xn[jnp.asarray(len(prompt) - 1 + at)]
            ids = jnp.asarray(np.asarray(served, np.int32)[at])
            best = jnp.full((n,), -jnp.inf, _F32)
            mine = jnp.zeros((n,), _F32)
            for b in range(nb):
                best, mine = _head_block(
                    xs, params["head"][:, b * vb:(b + 1) * vb], best, mine,
                    ids, b * vb, params.get("_round_to"))
        gap = (best - mine) * float(cfg.lm_head_multiplier)
        gaps.append(float(jnp.max(gap)))
    return gaps
