"""A residual path of MORE THAN ONE STREAM: manifold-constrained
hyper-connections (mHC, arXiv:2512.24880 section 4, on Hyper-Connections,
arXiv:2409.19606), as plain jax functions any stack op can call around a
sub-layer `F` (which keeps its own pre-norm).

A token's residual is `X` of `n` streams of `C` values. Around every
sub-layer, from the token's own streams (`x' = RMSNorm(vec(X))`, the
streams side by side, no gain):

    H_pre  = sigmoid(a_pre  * (x' P_pre)  + b_pre)          [n]
    H_post = 2 * sigmoid(a_post * (x' P_post) + b_post)     [n]
    H_res  = SinkhornKnopp(clip(a_res * mat(x' P_res) + b_res, lo, hi))
             M = exp(.); `iters` times: M <- M / (colsum(M) + eps),
                                        M <- M / (rowsum(M) + eps)
    u      = sum_i H_pre[i] X[i]                             F's input
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] * F(u)

The embedding is copied into the n streams (`spread_fn`); the final norm
and the head read their sum (`readout_fn`).

LAYOUT. The streams are `[n, ..., C]`, the stream axis LEADING: a token's n
streams side by side on the sublanes (`[..., n, C]`) would be stored in
tiles of eight sublanes, twice the bytes at n = 4. The mappings come back
with the tokens on the LAST axis (`[n, T]`, `[n, n, T]`): Sinkhorn's forty
normalisations then run over whole lane rows, where `[T, n, n]` would fill
four lanes of 128. The three projections are ONE matrix `w [n * C, n * (n
+ 2)]` (columns: pre, post, res row-major) with `a [3]` and `b [n * (n +
2)]`; `x' w` is taken as `(vec(X) w) * rsqrt(mean(vec(X)^2) + eps)`, the
same number with one pass over the streams fewer.

Everything here is float32 (`Precision.HIGHEST` products): the mappings
decide how much of every stream survives forty layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

# the stacked parameters of one sub-layer's mappings, in the order a stack
# op takes them
HC_PARAMS = ("hc_w", "hc_a", "hc_b")


# H_post = POST_SCALE * sigmoid(.): 1 at a zero logit, as a plain residual
POST_SCALE = 2.0


def map_width(n: int) -> int:
    """Columns of a sub-layer's one projection: pre [n], post [n], res [n *
    n]."""
    return n * (n + 2)


def sinkhorn_fn(logits, iters: int, eps: float):
    """logits [n, n, ...] (row, column, then anything) -> the matrix `exp(.)`
    normalised `iters` times, columns then rows, `eps` in both
    denominators."""
    m = jnp.exp(logits)
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)    # a column's sum
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)    # a row's sum
    return m


def flat_rms_inv_fn(x, eps: float):
    """x [n, T, C] -> [T]: `rsqrt(mean(vec(X)^2) + eps)`, the RMSNorm of a
    token's streams side by side (no gain)."""
    n, _, C = x.shape
    return jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=(0, 2)) / (n * C) + eps)


def mappings_fn(x, w, a, b, iters: int, eps: float, clamp):
    """x [n, T, C] float32, w [n * C, n * (n + 2)], a [3], b [n * (n + 2)]
    -> (H_pre [n, T], H_post [n, T], H_res [n, n, T]) float32."""
    n, T, C = x.shape
    raw = jnp.einsum("ntc,nck->kt", x, w.reshape(n, C, -1), precision=_HI)
    raw = raw * flat_rms_inv_fn(x, eps)                 # x' w, [n (n + 2), T]
    b = b[:, None]
    pre = jax.nn.sigmoid(a[0] * raw[:n] + b[:n])
    post = POST_SCALE * jax.nn.sigmoid(a[1] * raw[n:2 * n] + b[n:2 * n])
    res = (a[2] * raw[2 * n:] + b[2 * n:]).reshape(n, n, T)
    return pre, post, sinkhorn_fn(clip_fn(res, clamp), iters, eps)


def clip_fn(res, clamp):
    """H_res~ held to `clamp` = (lowest, highest) before the exponential."""
    return jnp.clip(res, clamp[0], clamp[1])


def pre_mix_fn(x, pre):
    """x [n, T, C], H_pre [n, T] -> the sub-layer's input u [T, C]."""
    return jnp.sum(pre[:, :, None] * x, axis=0)


def post_mix_fn(x, res, post, f):
    """x [n, T, C], H_res [n, n, T], H_post [n, T], f = F(u) [T, C] -> the
    streams after the sub-layer [n, T, C]."""
    mixed = jnp.sum(res[:, :, :, None] * x[None], axis=1)
    return mixed + post[:, :, None] * f[None]


def spread_fn(x, n: int):
    """x [..., C] -> X_0 [n, ..., C]: the embedding in every stream."""
    return jnp.broadcast_to(x[None], (n,) + x.shape)


def readout_fn(x):
    """X [n, ..., C] -> what the final norm reads: the sum of the streams."""
    return jnp.sum(x, axis=0)
