"""Plain reference for a served looped language model ("looped_dense";
Ouro's layers): one teacher-forced forward over prompt + served tokens,
float32 `jax.numpy` under `jax.default_matmul_precision("highest")`.

Independent of paddle_tpu: no cache, no pools, no pages, no scan over a
stacked cache, no kernels, no prefill/decode split: a Python loop over the
visits and the layers on a whole sequence. From the published config and the
readings listed under the configuration's `assumed` (x the residual stream,
`N(.)` an RMSNorm with a learned gain, eps 1e-6):

    x = E[token]
    for visit t = 1 .. total_ut_steps:
        for layer l = 1 .. L:                   (the SAME L layers each visit)
            u = N1_l(x)
            q, k, v = W_q u, W_k u, W_v u       (no bias; heads of 128)
            q, k <- rotary over the whole head, pairs (i, i + 64), theta 1e6
            a = x + N2_l(W_o softmax_causal(q k^T / sqrt(128)) v)
            x = a + N4_l(W_down(silu(W_gate N3_l(a)) * W_up N3_l(a)))
        x = h_t = N_f(x)                        (closes EVERY visit)
        lambda_t = sigmoid(w_g . h_t + b_g)
    logits = W_head h_last

    p_t = lambda_t prod_{j<t} (1 - lambda_j)  for t < last,
    p_last = prod_{j<last} (1 - lambda_j):    the probability of leaving
                                              after each visit (`exit_mass`)

A whole forward has no cache, so "visit t of layer l attends what visit t of
layer l wrote" is simply causal attention inside each visit of each layer:
what the served engine must reproduce from `loop_steps x layers` planes of
pages.

The weights are read as stored (bfloat16 as served; W_q | W_k | W_v side by
side in one matrix, W_gate | W_up alike) and one layer at a time is brought
to float32 inside the jitted layer, the head by vocabulary block, so that a
1,280-token request fits beside the engine on the chip.

Serving is right when every token the engine emitted is, by these logits,
the best token at its position or within the stated tolerance of it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
_LAYER_KEYS = ("attn_norm", "attn_post_norm", "wqkv", "wo", "ffn_norm",
               "ffn_post_norm", "w_gate_up", "w_down")
_PAD_TO = 256           # sequences are padded on the right to a multiple
_HEAD_BLOCKS = 8        # vocabulary blocks of the head


def read_params(get, cfg, round_to=None) -> dict:
    """The engine's weights as stored, by the names serving.model gives
    them (layers stacked on the leading axis, stored once whatever the
    number of visits); nothing is converted here. `round_to` (a dtype name)
    makes every later upcast of what is stored below float32 go through
    that dtype first: the reading of a precision below the stated one
    (tools/reference_control.py)."""
    del cfg
    out = {"emb": get("dec.word_emb"), "head": get("dec.lm_head"),
           "final_norm": get("dec.final_norm.scale"),
           "gate_w": get("dec.exit_gate.w"), "gate_b": get("dec.exit_gate.b"),
           "_round_to": round_to}
    out.update({k: get("dec.layers." + k) for k in _LAYER_KEYS})
    return out


def _up(a, round_to=None):
    if round_to is not None and a.dtype != _F32:
        a = a.astype(round_to)      # only what is stored below float32
    return a.astype(_F32)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(_F32)


def _rope(x, theta):
    """x [T, heads, dh]: pairs (i, i + dh/2) over the whole head, position =
    row."""
    T, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=_F32) * 2.0 / dh)
    ang = jnp.arange(T, dtype=_F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("sz", "rt"))
def _layer(x, l, attn_norm, attn_post_norm, wqkv, wo, ffn_norm,
           ffn_post_norm, w_gate_up, w_down, sz, rt=None):
    """One visit of layer `l` (an int32 scalar: the stacks are indexed
    inside, one compiled program for every layer) on the whole sequence x
    [T, H]."""
    nh, nkv, dh, eps, theta = sz
    T = x.shape[0]
    u = _rms(x, attn_norm[l], eps)
    qkv = u @ _up(wqkv[l], rt)
    q = _rope(qkv[:, :nh * dh].reshape(T, nh, dh), theta)
    k = _rope(qkv[:, nh * dh:(nh + nkv) * dh].reshape(T, nkv, dh), theta)
    v = qkv[:, (nh + nkv) * dh:].reshape(T, nkv, dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    g = nh // nkv
    outs = []
    for j in range(nkv):
        sc = jnp.einsum("tgd,sd->gts", q[:, j * g:(j + 1) * g], k[:, j]) \
            * dh ** -0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("gts,sd->tgd", p, v[:, j]))
    o = jnp.concatenate(outs, axis=1).reshape(T, nh * dh)
    a = x + _rms(o @ _up(wo[l], rt), attn_post_norm[l], eps)
    u = _rms(a, ffn_norm[l], eps)
    gu = u @ _up(w_gate_up[l], rt)
    F = gu.shape[-1] // 2
    gate, up = gu[:, :F], gu[:, F:]
    y = (gate * jax.nn.sigmoid(gate) * up) @ _up(w_down[l], rt)
    return a + _rms(y, ffn_post_norm[l], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _close(x, final_norm, gate_w, gate_b, eps):
    """The norm that closes a visit and the gate's probability of stopping
    there: (h [T, H], lambda [T])."""
    h = _rms(x, final_norm, eps)
    return h, jax.nn.sigmoid(h @ gate_w.astype(_F32) + gate_b.astype(_F32)[0])


def _sizes(cfg) -> tuple:
    nkv = cfg.num_kv_heads or cfg.num_heads
    dh = cfg.attn_head_dim or cfg.hidden_size // cfg.num_heads
    return (cfg.num_heads, nkv, dh, float(cfg.rms_norm_eps),
            float(cfg.rope_theta))


def visits(params: dict, tokens, cfg, loop_steps=None):
    """tokens [T] -> (the state after every visit [visits, T, H], the
    gate's lambda after every visit [visits, T]), float32."""
    sz, eps = _sizes(cfg), float(cfg.rms_norm_eps)
    rt = params.get("_round_to")
    x = _up(params["emb"][jnp.asarray(tokens, jnp.int32)], rt)
    states, lams = [], []
    for _ in range(int(loop_steps or cfg.loop_steps)):
        for l in range(cfg.num_layers):
            x = _layer(x, jnp.int32(l), *(params[k] for k in _LAYER_KEYS),
                       sz=sz, rt=rt)
        x, lam = _close(x, params["final_norm"], params["gate_w"],
                        params["gate_b"], eps)
        states.append(x)
        lams.append(lam)
    return jnp.stack(states), jnp.stack(lams)


def exit_mass(params: dict, tokens, cfg):
    """tokens [T] -> [T, visits]: the probability of leaving after each
    visit at every position; a row sums to 1."""
    with jax.default_matmul_precision("highest"):
        _, lam = visits(params, tokens, cfg)
    lam = np.asarray(lam, np.float64).T                       # [T, visits]
    stay = np.cumprod(1.0 - lam, axis=1)
    before = np.concatenate([np.ones_like(stay[:, :1]), stay[:, :-1]], axis=1)
    mass = lam * before
    mass[:, -1] = before[:, -1]
    return mass.astype(np.float32)


def all_logits(params: dict, tokens, cfg, loop_steps=None):
    """tokens [T] -> logits [T, V] float32 from the last visit's state
    (tests, at small sizes; `loop_steps` another number of visits than the
    configuration's)."""
    with jax.default_matmul_precision("highest"):
        states, _ = visits(params, tokens, cfg, loop_steps)
        return states[-1] @ _up(params["head"], params.get("_round_to"))


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


def _blocks(n: int, want: int) -> int:
    while n % want:
        want -= 1
    return want


def worst_logit_gaps(params: dict, sequences: list, cfg) -> list:
    """For each (prompt, served) pair: the largest amount by which a served
    token's logit sits below the best logit at its position. A sequence is
    padded on the right to a multiple of 256 tokens (fewer shapes to
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
            xn = visits(params, tok, cfg)[0][-1]
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
        gaps.append(float(jnp.max(best - mine)))
    return gaps
