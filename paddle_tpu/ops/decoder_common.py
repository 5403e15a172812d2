"""What the composite block families share: the primitives that carry no
family's `Geometry` in their signature and that more than one family's stack
runs. A family module (`ops/<family>_ops.py`) imports them from here; this
module imports no family. Mechanisms that ONE family owns and another is
composed of stay where they are (the indexer and the selection of
`sparse_moe_ops`, the Mamba-2 mixer and the convolution update of
`parallel_ssm_ops`, the latent layer of `latent_moe_ops`,
`hyper_connection_ops`), so the imports between the modules run one way:

    decoder_common <- a family <- the families composed of it

  * `_mm`: a matmul in the weight's dtype accumulated in float32; `greedy_fn`:
    the token every stack op closes with.
  * `rms_norm_fn`; `yarn_inv_freq_fn` / `rotary_fn`: rotary embeddings in
    half-split pairs, under YaRN where the configuration says
    (`rotary_partial_fn`: plain, from a base); `swiglu_fn`.
  * `causal_attention_fn`: a window's own keys, query block by query block
    (`_attend`, `_by_query_block`: a sliding band reuses them).
  * `_page_row_index`: a position's row in a stacked pool.
  * the routers (`topk_router_fn`: softmax, renormalised; `sigmoid_router_fn`:
    sigmoid scores, a selection bias; `group_limited_router_fn`: the same
    inside the best groups) and the gated experts behind them
    (`moe_topk_experts_fn`; `_experts_backend` answers whether the Pallas
    kernel runs them, `experts_grouped` whether in its grouped form).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .attention_ops import _DROP_PAGE, _NEG_INF
from .registry import _DYN

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

# queries attended together: a full layer's float32 scores of one block are
# `[heads, block, context]` (48 x 64 x 20,480 x 4 B = 252 MB)
_QUERY_BLOCK = 64


def _mm(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=_F32)


def greedy_fn(logits):
    """The most likely token of every row of `logits`, int32."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def rms_norm_fn(x, scale, eps: float):
    xf = x.astype(_F32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                        + eps)
    return xf * inv * scale.astype(_F32)


def yarn_inv_freq_fn(rotary_dim: int, theta: float, yarn=()) -> np.ndarray:
    """The `rotary_dim / 2` inverse frequencies of a rotary embedding,
    float32. `yarn` = (factor, original context, beta_fast, beta_slow,
    attention factor) or (): lane pair i turns `theta^(-2i/d)` a position;
    YaRN keeps that below `low`, divides it by `factor` above `high` and
    ramps linearly between, `low`/`high` the (floored/ceiled) pair indices
    that turn `beta_fast`/`beta_slow` times over the original context."""
    half = rotary_dim // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / rotary_dim)
    if not yarn:
        return inv.astype(np.float32)
    factor, original, beta_fast, beta_slow = (float(v) for v in yarn[:4])

    def correction_dim(turns):
        return rotary_dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return (inv / factor * ramp + inv * (1.0 - ramp)).astype(np.float32)


def rotary_fn(x, positions, inv_freq, rotary_dim: int, factor: float = 1.0):
    """x [..., heads, dh] float32, positions [...] (one a token): lanes
    [0, rotary_dim) of every head turn as (i, i + rotary_dim/2) pairs by
    `position * inv_freq[i]`, cos and sin times `factor`; the lanes past
    `rotary_dim` pass."""
    half = rotary_dim // 2
    ang = positions.astype(_F32)[..., None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2, rest = (x[..., :half], x[..., half:rotary_dim],
                    x[..., rotary_dim:])
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def rotary_partial_fn(x, positions, rotary_dim: int, theta: float):
    """x [..., nh, dh] float32, positions [...] int (one per token): rotate
    lanes [0, rotary_dim) of every head as (i, i + rotary_dim/2) pairs by
    position * theta^(-2i/rotary_dim); lanes past rotary_dim pass."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=_F32) * 2.0 / rotary_dim)
    ang = positions.astype(_F32)[..., None, None] * inv_freq   # [..,1,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = (x[..., :half], x[..., half:rotary_dim],
                    x[..., rotary_dim:])
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def swiglu_fn(z, w_gate, w_up, w_down):
    """z [T, H] float32 -> `W_d(silu(W_g z) * (W_u z))` float32."""
    g = _mm(z, w_gate)
    return _mm(g * jax.nn.sigmoid(g) * _mm(z, w_up), w_down)


def _attend(qg, k, v, mask, sm_scale):
    """qg [B, s, nkv, g, dh], k/v [B, T, nkv, dh], mask [B, s, T] ->
    [B, s, nkv, g, dh] float32."""
    s = jnp.einsum("bsjgd,btjd->bjgst", qg, k,
                   preferred_element_type=_F32) * sm_scale
    s = jnp.where(mask[:, None, None], s, _NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bjgst,btjd->bsjgd", probs.astype(v.dtype), v,
                      preferred_element_type=_F32)


def _by_query_block(fn, qg, block: int):
    """`fn(block index, qg's block [B, block, ...])` over the query blocks
    of qg [B, S, ...], one after another (`lax.map`); one call where S is
    no multiple of `block`."""
    B, S = qg.shape[:2]
    if S <= block or S % block:
        return fn(jnp.int32(0), qg)
    n = S // block
    split = jnp.moveaxis(qg.reshape((B, n, block) + qg.shape[2:]), 1, 0)
    out = jax.lax.map(lambda a: fn(*a),
                      (jnp.arange(n, dtype=jnp.int32), split))
    return jnp.moveaxis(out, 0, 1).reshape((B, S) + out.shape[3:])


def causal_attention_fn(q, k, v, q_pos0, sm_scale: float):
    """q [B, S, nh, dh] at positions `q_pos0[b] + s` over k/v [B, T, nkv,
    dh] at positions 0..T-1: every key at or before the query, query block
    by query block -> [B, S, nh, dh] float32."""
    B, S, nh, dh = q.shape
    T, nkv = k.shape[1], k.shape[2]
    block = S if S <= _QUERY_BLOCK or S % _QUERY_BLOCK else _QUERY_BLOCK
    kp = jnp.arange(T, dtype=jnp.int32)

    def one(j, qb):
        qp = q_pos0[:, None] + j * block + jnp.arange(block, dtype=jnp.int32)
        return _attend(qb, k, v, kp[None, None, :] <= qp[:, :, None],
                       sm_scale)

    qg = q.reshape(B, S, nkv, nh // nkv, dh).astype(k.dtype)
    return _by_query_block(one, qg, block).reshape(B, S, nh, dh)


def _page_row_index(page_table, gpos, page_size, layer_off, keep):
    """Row of a stacked pool for global position `gpos` ([B] or [B, S],
    `keep` alike) in this layer: its page's id plus the layer's offset, or
    the drop sentinel where `keep` is false."""
    P = page_table.shape[1]
    page_of = jnp.clip(gpos // page_size, 0, P - 1)
    if gpos.ndim == 1:
        idx = jnp.take_along_axis(page_table, page_of[:, None], axis=1)[:, 0]
    else:
        idx = jnp.take_along_axis(page_table, page_of, axis=1)
    return jnp.where(keep, idx + layer_off, _DROP_PAGE)


def topk_router_fn(z, router_w, k: int):
    """z [T, H] float32 -> (ids [T, k] int32, the k most probable experts
    in order; cw [T, E] float32: their probabilities renormalised to sum
    to one, zero elsewhere)."""
    probs = jax.nn.softmax(jnp.dot(z, router_w, precision=_HI), axis=-1)
    vals, ids = jax.lax.top_k(probs, k)
    weights = vals / jnp.sum(vals, axis=-1, keepdims=True)
    held = jnp.arange(probs.shape[-1], dtype=jnp.int32)
    cw = jnp.sum(jnp.where(ids[:, :, None] == held, weights[:, :, None],
                           0.0), axis=1)
    return ids.astype(jnp.int32), cw


def sigmoid_router_fn(z, router_w, router_bias, k: int, scaling: float):
    """z [T, H] float32 -> (ids [T, k] int32: the k experts of largest
    `sigmoid(z W_r) + bias`, in order, ties to the lower index; cw [T, E]
    float32: `scaling * s_e / sum_chosen s` at the chosen, zero
    elsewhere)."""
    s = jax.nn.sigmoid(jnp.dot(z, router_w, precision=_HI))
    _, ids = jax.lax.top_k(s + router_bias, k)
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    weights = scaling * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    held = jnp.arange(s.shape[-1], dtype=jnp.int32)
    cw = jnp.sum(jnp.where(ids[:, :, None] == held, weights[:, :, None],
                           0.0), axis=1)
    return ids.astype(jnp.int32), cw


def group_limited_router_fn(z, router_w, router_bias, k: int, groups: int,
                            groups_kept: int, scaling: float):
    """z [T, H] float32 -> (ids [T, k] int32: the k experts of largest
    `sigmoid(z W_r) + bias` inside the `groups_kept` groups (of `groups`
    equal, consecutive ones) whose two largest biased scores sum highest,
    in order, ties to the lower index; cw [T, E] float32: `scaling * s_e /
    sum_chosen s` at the chosen, zero elsewhere)."""
    s = jax.nn.sigmoid(jnp.dot(z, router_w, precision=_HI))
    T, E = s.shape
    biased = s + router_bias
    by_group = biased.reshape(T, groups, E // groups)
    best2 = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)      # [T, groups]
    _, kept = jax.lax.top_k(best2, groups_kept)
    open_ = jnp.any(kept[:, :, None]
                    == jnp.arange(groups, dtype=jnp.int32), axis=1)
    allowed = jnp.repeat(open_, E // groups, axis=1)
    _, ids = jax.lax.top_k(jnp.where(allowed, biased, -jnp.inf), k)
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    weights = scaling * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    expert = jnp.arange(E, dtype=jnp.int32)
    cw = jnp.sum(jnp.where(ids[:, :, None] == expert, weights[:, :, None],
                           0.0), axis=1)
    return ids.astype(jnp.int32), cw


def _experts_backend(tokens, w_gate_shape, dtype):
    from .. import tuning
    from .pallas_kernels import moe_experts as pme
    from .pallas_kernels import workbench

    _, E, H, F = w_gate_shape

    def runnable():
        return (workbench.runnable(pme)
                and pme.experts_supported((tokens, H), w_gate_shape, dtype))

    def analytic():
        return {"backend": "pallas" if runnable() else "xla"}

    if tuning.mode() == "off" or tokens % _DYN == 0:
        backend = analytic()["backend"]
    else:
        key = tuning.canonical_key(
            "moe_experts", tuning.moe_experts_key(tokens, E, H, F),
            str(jnp.dtype(dtype)), tuning.device_kind())
        decision, _tier = tuning.decide(
            "moe_experts", key, prior=analytic, default={"backend": "xla"},
            validate=lambda dd: dd.get("backend") in ("xla", "pallas"))
        backend = decision.get("backend", "xla")
    return backend if backend == "xla" or runnable() else "xla"


def experts_grouped(tokens, w_gate_shape, dtype) -> bool:
    """Whether a gated expert call of `tokens` rows takes the kernel's
    grouped form (`moe_experts._grouped_call`): the kernel runs, and the
    rows are more than its one token tile."""
    from .pallas_kernels import moe_experts as pme

    return tokens > pme._TOKEN_TILE \
        and _experts_backend(tokens, w_gate_shape, dtype) == "pallas"


def moe_topk_experts_fn(z, cw, w_gate, w_up, w_down, layer=0,
                        tag: str = "decode", k: int | None = None):
    """`sum_e cw[t, e] * expert_e(z[t])`, float32 [T, H]; weights stacked
    `[L, E, ...]`, `layer` picks the layer; `k` the router's experts a
    token (the most non-zeros a row of `cw` has: what the kernel's grouped
    form, a window of more than 256 rows, sizes its pair list by)."""
    from .pallas_kernels import moe_experts as pme

    if _experts_backend(z.shape[0], w_gate.shape, w_gate.dtype) == "pallas":
        return pme.moe_topk_experts(z, cw, w_gate, w_up, w_down, layer,
                                    tag=tag, k=k)
    return pme._reference(z, cw, w_gate, w_up, w_down, layer)
