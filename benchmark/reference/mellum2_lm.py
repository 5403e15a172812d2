"""Plain reference for next-token training of a decoder whose layers are
grouped-query attention (some behind a sliding window, rotary embeddings,
YaRN in the full layers) followed by softmax-routed top-k SwiGLU experts:
forward, loss, gradients and Adam, float32 at `highest`, dense masks, a
loop over the experts, no kernel, no sort, no capacity.

Independent of paddle_tpu: it reads the freshly initialised parameters by
name and is given the same host batches; what it returns is the loss of
each of the first steps and the parameters after them, which the trainer's
own losses and parameters must match.

The SHARE of a layer that several chips hold is handed to it as the trainer
has it (`cfg.first_expert`, `cfg.experts_held`, `cfg.vocab_size` the rows of
the vocabulary slice): the router scores all `cfg.num_experts` and keeps
its top-k over all of them; the sum runs over the held experts alone.

It has to fit beside the trainer, whose parameters and moments stay on the
chip while it runs (7.1 GB at the benchmark's size): the snapshots, Adam's
moments and the gradient summed over a step's row blocks live in HOST
memory; on the device are the parameters (2.4 GB), one block's gradient
(2.4 GB) and one block of rows, every layer's inside recomputed in the
backward pass (`jax.checkpoint`) and the attention a block of queries at a
time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_LAYER = {"norm1": ".norm1", "wq": ".attn.wq", "wk": ".attn.wk",
          "wv": ".attn.wv", "wo": ".attn.wo", "norm2": ".norm2",
          "router": ".moe.router", "w_gate": ".moe.w_gate",
          "w_up": ".moe.w_up", "w_down": ".moe.w_down"}
_QUERY_BLOCK = 512
_NEG = -1e30


def read_params(get, cfg) -> dict:
    """The trainer's parameters by the names `decoder_moe_pretrain` gives
    them, as float32 numpy arrays in host memory."""
    f32 = lambda n: np.asarray(get(n), np.float32)  # noqa: E731
    return {"embed": f32("decoder.embed"),
            "layers": [{k: f32(f"decoder.layer{i}{s}")
                        for k, s in _LAYER.items()}
                       for i in range(len(cfg.layer_types))],
            "final_norm": f32("decoder.final_norm"),
            "head": f32("decoder.head")}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def inv_freq(dim: int, theta: float, yarn=()):
    """`dim / 2` rotary frequencies; under YaRN (factor, original context,
    beta_fast, beta_slow, ...) a pair that turns more than beta_fast times
    over the original context keeps its frequency, one that turns fewer
    than beta_slow times has it divided by the factor, a linear ramp over
    the pair indices between (floored and ceiled)."""
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not yarn:
        return freq.astype(np.float32)
    factor, original, fast, slow = (float(v) for v in yarn[:4])

    def pair_that_turns(n):
        return dim * math.log(original / (n * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(fast)), 0)
    high = min(math.ceil(pair_that_turns(slow)), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (freq / factor * (1 - keep) + freq * keep).astype(np.float32)


def rotary(x, freq, factor):
    """x [S, n, dh] at positions 0..S-1, rotate-half pairs (i, i + dh/2)."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, window: int, scale: float):
    """q [S, nh, dh], k / v [S, nkv, dh]: query t sees keys max(0, t -
    window + 1) .. t (all of 0 .. t where `window` is 0), the `nh / nkv`
    query heads of a group sharing a key/value head. A block of queries at
    a time against a dense mask over every key."""
    S, nh, dh = q.shape
    nkv = k.shape[1]
    block = _QUERY_BLOCK if S % _QUERY_BLOCK == 0 else S
    kp = jnp.arange(S)

    @jax.checkpoint
    def one(args):
        first, qb = args
        qp = first + jnp.arange(block)
        seen = kp[None, :] <= qp[:, None]
        if window:
            seen &= qp[:, None] - kp[None, :] < window
        s = jnp.einsum("qjgd,kjd->jgqk", qb.reshape(block, nkv, nh // nkv,
                                                    dh), k) * scale
        p = jax.nn.softmax(jnp.where(seen, s, _NEG), axis=-1)
        return jnp.einsum("jgqk,kjd->qjgd", p, v).reshape(block, nh * dh)

    out = jax.lax.map(one, (jnp.arange(0, S, block),
                            q.reshape(S // block, block, nh, dh)))
    return out.reshape(S, nh * dh)


def route(z, router_w, k: int):
    """Combine weights [T, E]: softmax over all experts, the k largest
    (each pick the lowest index among equals) renormalised, zero elsewhere."""
    p = jax.nn.softmax(z @ router_w, axis=-1)
    left, chosen = p, jnp.zeros(p.shape, bool)
    for _ in range(k):
        pick = jax.nn.one_hot(jnp.argmax(left, axis=-1), p.shape[-1],
                              dtype=bool)
        chosen |= pick
        left = jnp.where(pick, -1.0, left)
    kept = jnp.where(chosen, p, 0.0)
    return kept / jnp.sum(kept, axis=-1, keepdims=True)


def experts(z, cw, w_gate, w_up, w_down, first: int):
    """sum over the held experts e of cw[:, first + e] * expert_e(z)."""
    out = jnp.zeros_like(z)
    for e in range(w_gate.shape[0]):
        g = z @ w_gate[e]
        out += cw[:, first + e, None] * ((jax.nn.silu(g) * (z @ w_up[e]))
                                         @ w_down[e])
    return out


def attention_half(x, p, kind: str, cfg):
    """x [S, H] -> x + W_o . Attn(...)."""
    S = x.shape[0]
    nh, nkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    full = kind == "full_attention"
    yarn = tuple(cfg.yarn) if full else ()
    freq = inv_freq(dh, cfg.rope_theta, yarn)
    factor = float(yarn[4]) if yarn else 1.0
    z = rms_norm(x, p["norm1"], cfg.rms_norm_eps)
    q = rotary((z @ p["wq"]).reshape(S, nh, dh), freq, factor)
    k = rotary((z @ p["wk"]).reshape(S, nkv, dh), freq, factor)
    v = (z @ p["wv"]).reshape(S, nkv, dh)
    a = attention(q, k, v, 0 if full else cfg.sliding_window, dh ** -0.5)
    return x + a @ p["wo"]


def experts_half(h, p, cfg):
    """h [S, H] -> the held experts' part of the routed sum (no residual)."""
    z = rms_norm(h, p["norm2"], cfg.rms_norm_eps)
    cw = route(z, p["router"], cfg.experts_per_token)
    return experts(z, cw, p["w_gate"], p["w_up"], p["w_down"],
                   cfg.first_expert)


def layer(x, p, kind: str, cfg):
    h = attention_half(x, p, kind, cfg)
    return h + experts_half(h, p, cfg)


def row_nll_sum(params, ids, cfg):
    """Sum over the S - 1 positions of one row `ids` [S] that have a next
    token of the cross-entropy with it."""
    x = params["embed"][ids]
    for p, kind in zip(params["layers"], cfg.layer_types):
        x = jax.checkpoint(functools.partial(layer, kind=kind, cfg=cfg))(x, p)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logp = jax.nn.log_softmax(x @ params["head"], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp[:-1], ids[1:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("shapes", "cfg"))
def _block_grads(flat, ids, denom, shapes, cfg):
    """(loss share, gradient share) of a block of rows `ids` [rows, S]. The
    parameters come and the gradients go as FLAT arrays: a host array of
    two or three dimensions crosses to the chip's tiled layout at a tenth
    of the speed of a flat one (45 s a step of 2.4 GB at the benchmark's
    size, my chip run, PR 58)."""
    leaves, treedef = jax.tree.flatten(flat)
    params = treedef.unflatten([a.reshape(shape)
                                for a, shape in zip(leaves, shapes)])

    def share(p):
        return sum(row_nll_sum(p, row, cfg) for row in ids) / denom

    part, grads = jax.value_and_grad(share)(params)
    return part, jax.tree.map(lambda g: g.reshape(-1), grads)


class _Frozen:
    """A hashable view of the model's config object for `static_argnames`."""

    def __init__(self, cfg):
        self.__dict__.update(
            {k: tuple(v) if isinstance(v, (list, tuple)) else v
             for k, v in vars(cfg).items()})
        self._key = tuple(sorted(self.__dict__.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key


def step_grads(params, ids, cfg, block_rows: int):
    """(mean next-token loss of the batch `ids` [B, S], its gradient as
    float32 numpy arrays), summed over blocks of `block_rows` rows on the
    host."""
    B, S = ids.shape
    frozen = _Frozen(cfg)
    shapes = tuple(a.shape for a in jax.tree.leaves(params))
    flat = jax.device_put(jax.tree.map(lambda a: a.reshape(-1), params))
    loss, total = 0.0, None
    for at in range(0, B, block_rows):
        part, grads = _block_grads(flat, jnp.asarray(
            ids[at:at + block_rows]), jnp.float32(B * (S - 1)), shapes,
            frozen)
        loss += float(part)
        grads = jax.device_get(grads)
        total = jax.tree.map(np.array, grads) if total is None \
            else jax.tree.map(lambda a, g: np.add(a, g, out=a), total, grads)
    return loss, jax.tree.map(lambda g, a: g.reshape(a.shape), total, params)


def first_steps(params: dict, batches: list, cfg, lr: float,
                block_rows: int, beta1=0.9, beta2=0.999, eps=1e-8) -> tuple:
    """(loss of each step in `batches` under Adam from `params`, the
    parameters after the last of them); moments and updates on the host."""
    params = jax.tree.map(lambda a: np.array(a, np.float32), params)
    m = jax.tree.map(np.zeros_like, params)
    v = jax.tree.map(np.zeros_like, params)
    losses = []
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, start=1):
            loss, grads = step_grads(params, np.asarray(batch["src_ids"]),
                                     cfg, block_rows)
            losses.append(loss)
            lr_t = lr * math.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
            for p, a, b, g in zip(*(jax.tree.leaves(x)
                                    for x in (params, m, v, grads))):
                # in place, one scratch array a leaf: 595 M elements a step
                a *= beta1
                a += np.multiply(g, 1 - beta1, out=(tmp := np.empty_like(g)))
                b *= beta2
                np.multiply(g, g, out=tmp)
                tmp *= 1 - beta2
                b += tmp
                np.sqrt(b, out=tmp)
                tmp += eps
                np.divide(a, tmp, out=tmp)
                tmp *= lr_t
                p -= tmp
    return losses, params
