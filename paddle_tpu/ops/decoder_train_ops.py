"""The ops a decoder of sliding-window / full attention layers and routed
experts TRAINS through (`models/decoder_moe.py`): every one a plain jax
function that `jax.vjp` differentiates, so that the registry's derived grad
ops and `RecomputeOptimizer`'s replayed segments both work through them.

  * `rms_norm`            — `decoder_common.rms_norm_fn`, float32.
  * `rotary_embedding`    — `decoder_common.rotary_fn` on `[B, S, n, dh]`
                            at positions 0..S-1, under YaRN where the attrs
                            say, float32; the heads come out first
                            (`[B, n, S, dh]`, what `fused_attention` takes).
  * `moe_router`          — `decoder_common.topk_router_fn`: softmax over
                            every expert, the k largest renormalised, as
                            combine weights `[T, E]`, float32.
  * `moe_experts`         — `sum_e cw[t, e] * expert_e(z_t)` over the
                            experts HELD here (`first_expert`, as many as the
                            weights stack), dropless: the (token, expert)
                            pairs are sorted by expert
                            (`pallas_kernels.moe_experts._sort_rows`, the
                            serving windows' plan) and the three products, dX
                            and the three dW are GROUPED products over that
                            list (jax's bundled megablox on the chip,
                            `ragged_dot` elsewhere): a group is padded to a
                            tile and never cut. What is saved for the
                            backward pass is the op's inputs; the sorted
                            rows and the gate and up products are computed
                            again there.
  * `lm_head_loss`        — final-normed rows times an untied head, mean
                            next-token cross-entropy, block of rows by
                            block: the `[T, V]` float32 logits never exist
                            whole, forward or backward (the backward computes
                            a block's logits again from the saved
                            log-sum-exp).

Precision under AMP (`contrib/mixed_precision/fp16_lists.py`): the expert
products and the head take bfloat16 operands and accumulate in float32;
norms, rotary, the router (scores, choice, weights), the combine weights,
SiLU, the softmax statistics and the loss are float32.

The counters (`Stats` outputs, `models/decoder_moe.py` declares them as
`Program.device_counters`): `moe_experts` writes float32 `[3 + held]`:
assignments, those to held experts, those the products were NOT given
(dropped: 0, there is no capacity), and every held expert's tokens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..observability.schema import piece
from .decoder_common import (rms_norm_fn, rotary_fn, topk_router_fn,
                             yarn_inv_freq_fn)
from .registry import ExecContext, register_op

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

# tests flip this to run the bundled grouped products through the Pallas
# interpreter on the CPU
GROUPED_INTERPRET = False
# rows of one grid step of a grouped product (`gmm`: rows of the sorted list;
# `tgmm`: rows contracted), the bytes its block of the weights (`gmm`) or of
# the gradient's float32 tile (`tgmm`) may take, and the bytes of a `gmm`'s
# float32 tile of the result (held three times over). Swept on the chip at
# the training cell's shapes (PR 58, `tools/kernel_check.py`'s row): 256 rows
# lose nothing to 512 and pad a group of ~500 rows by a quarter, not a half
GROUPED_ROWS = 256
GROUPED_BLOCK_BYTES = {"gmm": 4 * 1024 * 1024 + 128 * 1024,
                       "tgmm": 4 * 1024 * 1024 + 256 * 1024}
GROUPED_OUT_BYTES = 1280 * 1024
# tokens whose pairs are sorted and multiplied together: the sorted list is
# `tokens x k` rows whatever the router does (every pair may be a held
# one), so a step of 16,384 tokens at top-8 would gather 131,072 rows of the
# hidden size (0.6 GB in bfloat16, and as much again for every product's
# cotangent) where a quarter are live. A chunk streams the held experts
# once more (0.2 GB a layer). On the chip 16,384 tokens a layer, forward and
# backward: 52.6 ms in chunks of 2,048, 38.9 of 4,096, 32.7 of 8,192, 31.2
# whole (PR 58)
EXPERT_CHUNK_TOKENS = 8192
# rows of the head's logits alive at once (2,048 x 24,576 float32: 0.2 GB)
HEAD_BLOCK_ROWS = 2048


# ---------------------------------------------------------------------------
# grouped products
# ---------------------------------------------------------------------------


def _bundled() -> bool:
    from .pallas_kernels import workbench

    return workbench.on_tpu() or GROUPED_INTERPRET


def _split(extent: int, most: int) -> int:
    """The largest part of `extent`, in whole lanes of 128, that is at most
    `most` and divides it; `extent` where none does."""
    return next((t for t in range(min(extent, most) // 128 * 128, 0, -128)
                 if extent % t == 0), extent)


def _tiling(kind: str, m: int, k: int, n: int) -> tuple:
    """(rows, contraction, columns) of one grid step. `gmm` [m, k] x [G, k,
    n]: the whole contraction and as many columns as the weights' block may
    take, so that an expert's block stays in VMEM over the row tiles of its
    group and the product is bound by the matrix unit, not by reloading it.
    `tgmm` [k, m] x [m, n] -> [G, k, n]: a float32 tile of the gradient
    stays while the group's rows stream through."""
    tm = next(t for t in (GROUPED_ROWS, 128, m) if m % t == 0)
    room = GROUPED_BLOCK_BYTES[kind]
    if kind == "gmm":
        return tm, k, _split(n, max(min(room // (2 * k),
                                        GROUPED_OUT_BYTES // (4 * tm)), 128))
    tn = _split(n, 1152)
    return tm, _split(k, max(room // (4 * tn), 128)), tn


def grouped_matmul(lhs, rhs, sizes, out_dtype, transpose_rhs=False):
    """lhs [m, k] in groups of consecutive rows, `sizes [G + 1]` int32 (the
    last group: the dead rows behind the live ones, which no product
    touches), rhs [G, k, n] (`[G, n, k]` with `transpose_rhs`) -> [m, n]:
    row r of group g times `rhs[g]`, zero in the dead rows."""
    if _bundled():
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        m, k = lhs.shape
        n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
        return gmm(lhs, rhs, sizes, out_dtype, _tiling("gmm", m, k, n),
                   transpose_rhs=transpose_rhs, interpret=GROUPED_INTERPRET)
    if transpose_rhs:
        rhs = rhs.swapaxes(1, 2)
    return jax.lax.ragged_dot(lhs, rhs, sizes[:-1], precision=_HI,
                              preferred_element_type=out_dtype)


def grouped_matmul_t(lhs, rhs, sizes, out_dtype):
    """lhs [m, k], rhs [m, n], `sizes [G + 1]` as above -> [G, k, n]:
    `lhs[group g].T @ rhs[group g]` (a weight's gradient)."""
    groups = sizes.shape[0] - 1
    if _bundled():
        from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

        m, k = lhs.shape
        return tgmm(lhs.swapaxes(0, 1), rhs, sizes, out_dtype,
                    _tiling("tgmm", m, k, rhs.shape[1]),
                    num_actual_groups=groups,
                    interpret=GROUPED_INTERPRET)
    ends = jnp.cumsum(sizes[:-1])
    group = jnp.sum(jnp.arange(lhs.shape[0])[:, None] >= ends[None, :],
                    axis=1)
    member = (group[:, None] == jnp.arange(groups)[None, :]).astype(lhs.dtype)
    return jnp.einsum("mg,mk,mn->gkn", member, lhs, rhs, precision=_HI,
                      preferred_element_type=out_dtype)


# ---------------------------------------------------------------------------
# the routed experts, forward and backward
# ---------------------------------------------------------------------------


def _plan(z, cw, k: int, dtype):
    """The sorted pairs of one chunk: `_sort_rows`'s rows, weights, tokens
    and group bounds, the group sizes the products take (the dead rows as a
    last group) and, for every token, the rows of its pairs."""
    from .pallas_kernels import moe_experts as pme

    zs, ws, token, (_, _, _, lo) = pme._sort_rows(z, cw, k, dtype)
    T, E = cw.shape
    kk = min(k, E)
    sizes = jnp.concatenate(
        [lo[1:] - lo[:-1], (zs.shape[0] - lo[-1])[None]]).astype(jnp.int32)
    by_tok = jnp.argsort(token[:T * kk], stable=True).reshape(T, kk)
    return zs, ws[:, 0], token, sizes, lo, by_tok.astype(jnp.int32)


def _gate_up(zs, wg, wu, sizes):
    g = grouped_matmul(zs, wg, sizes, _F32)
    u = grouped_matmul(zs, wu, sizes, _F32)
    return g, u, jax.nn.sigmoid(g)


def _chunk_fwd(z, cw, wg, wu, wd, k: int):
    """(y [T, H] float32, the held experts' tokens [E]) of one chunk."""
    dtype = wg.dtype
    with piece("dispatch"):
        zs, ws, _, sizes, _, by_tok = _plan(z, cw, k, dtype)
    with piece("experts"):
        g, u, sg = _gate_up(zs, wg, wu, sizes)
        yo = grouped_matmul((g * sg * u).astype(dtype), wd, sizes, _F32)
    with piece("combine"):
        y = jnp.sum(ws[by_tok][:, :, None] * yo[by_tok], axis=1)
    return y, sizes[:-1].astype(_F32)


def _chunk_bwd(z, cw, wg, wu, wd, k: int, dy):
    """The cotangents of (z, cw, wg, wu, wd), float32, of one chunk."""
    dtype = wg.dtype
    E = cw.shape[1]
    with piece("dispatch"):
        zs, ws, token, sizes, lo, by_tok = _plan(z, cw, k, dtype)
        dyt = dy.astype(dtype)[token]
    with piece("experts"):
        g, u, sg = _gate_up(zs, wg, wu, sizes)
        act = g * sg
        hidden = act * u
        dh_one = grouped_matmul(dyt, wd, sizes, _F32, transpose_rhs=True)
        dws = jnp.sum(dh_one * hidden, axis=-1)
        dh = dh_one * ws[:, None]
        dg = (dh * u * (sg * (1.0 + g * (1.0 - sg)))).astype(dtype)
        du = (dh * act).astype(dtype)
        dwd = grouped_matmul_t((hidden * ws[:, None]).astype(dtype), dyt,
                               sizes, _F32)
        dwg = grouped_matmul_t(zs, dg, sizes, _F32)
        dwu = grouped_matmul_t(zs, du, sizes, _F32)
        dzs = grouped_matmul(dg, wg, sizes, _F32, transpose_rhs=True) \
            + grouped_matmul(du, wu, sizes, _F32, transpose_rhs=True)
    with piece("combine"):
        dz = jnp.sum(dzs[by_tok], axis=1)
        rows = jnp.arange(zs.shape[0], dtype=jnp.int32)
        expert = jnp.sum(rows[:, None] >= lo[None, 1:], axis=1)
        hit = (expert[by_tok][:, :, None] == jnp.arange(E, dtype=jnp.int32)) \
            & (by_tok < lo[-1])[:, :, None]
        dcw = jnp.sum(jnp.where(hit, dws[by_tok][:, :, None], 0.0), axis=1)
    return dz, dcw, dwg, dwu, dwd


def _parts(extent: int, size: int) -> int:
    """How many parts of `size` the `extent` goes in: one where it is no
    larger, or no whole multiple."""
    return extent // size if extent > size and extent % size == 0 else 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def moe_experts_train_fn(z, cw, wg, wu, wd, k: int):
    """z [T, H], cw [T, E] float32 (a token's combine weight for each of
    the E experts held here, zero for those it did not choose; at most `k`
    non-zeros a row), wg / wu [E, H, F], wd [E, F, H] -> (`sum_e cw[t, e] *
    W_d,e (silu(W_g,e z_t) * W_u,e z_t)` float32 [T, H], every held
    expert's tokens float32 [E]). Products in the weights' dtype,
    accumulated in float32; every pair with a non-zero weight is computed."""
    T = z.shape[0]
    n = _parts(T, EXPERT_CHUNK_TOKENS)
    if n == 1:
        return _chunk_fwd(z, cw, wg, wu, wd, k)
    y, counts = jax.lax.map(
        lambda a: _chunk_fwd(a[0], a[1], wg, wu, wd, k),
        (z.reshape(n, T // n, -1), cw.reshape(n, T // n, -1)))
    return y.reshape(T, -1), jnp.sum(counts, axis=0)


def _experts_fwd(z, cw, wg, wu, wd, k):
    return moe_experts_train_fn(z, cw, wg, wu, wd, k), (z, cw, wg, wu, wd)


def _experts_bwd(k, saved, cot):
    z, cw, wg, wu, wd = saved
    dy = cot[0].astype(_F32)
    T = z.shape[0]
    n = _parts(T, EXPERT_CHUNK_TOKENS)
    if n == 1:
        dz, dcw, dwg, dwu, dwd = _chunk_bwd(z, cw, wg, wu, wd, k, dy)
    else:
        def one(acc, a):
            dz, dcw, *dws = _chunk_bwd(a[0], a[1], wg, wu, wd, k, a[2])
            return tuple(x + d for x, d in zip(acc, dws)), (dz, dcw)

        zero = tuple(jnp.zeros(w.shape, _F32) for w in (wg, wu, wd))
        (dwg, dwu, dwd), (dz, dcw) = jax.lax.scan(
            one, zero, (z.reshape(n, T // n, -1), cw.reshape(n, T // n, -1),
                        dy.reshape(n, T // n, -1)))
        dz, dcw = dz.reshape(T, -1), dcw.reshape(T, -1)
    return (dz.astype(z.dtype), dcw.astype(cw.dtype), dwg.astype(wg.dtype),
            dwu.astype(wu.dtype), dwd.astype(wd.dtype))


moe_experts_train_fn.defvjp(_experts_fwd, _experts_bwd)


# ---------------------------------------------------------------------------
# the head and its loss
# ---------------------------------------------------------------------------


def _block_logits(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=_F32)


@jax.custom_vjp
def head_nll_fn(x, w, labels, weight):
    """x [T, H], w [H, V], labels [T] int32, weight [T] float32 ->
    `sum_t weight[t] * (logsumexp(x_t W) - (x_t W)[labels[t]])`, float32,
    a block of rows at a time."""
    return _head_fwd(x, w, labels, weight)[0]


def _head_fwd(x, w, labels, weight):
    T = x.shape[0]
    n = _parts(T, HEAD_BLOCK_ROWS)

    def one(a):
        xb, lb = a
        logits = _block_logits(xb, w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse, jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]

    lse, picked = jax.lax.map(
        one, (x.reshape(n, T // n, -1), labels.reshape(n, T // n)))
    lse = lse.reshape(T)
    total = jnp.sum(weight * (lse - picked.reshape(T)))
    return total, (x, w, labels, weight, lse)


def _head_bwd(saved, g):
    x, w, labels, weight, lse = saved
    T, V = x.shape[0], w.shape[1]
    n = _parts(T, HEAD_BLOCK_ROWS)

    def one(dw, a):
        xb, lb, wb, lseb = a
        p = jnp.exp(_block_logits(xb, w) - lseb[:, None])
        hot = lb[:, None] == jnp.arange(V, dtype=jnp.int32)[None, :]
        dl = ((p - hot) * (wb * g)[:, None]).astype(w.dtype)
        dx = jnp.dot(dl, w.T, preferred_element_type=_F32)
        return dw + jnp.dot(xb.astype(w.dtype).T, dl,
                            preferred_element_type=_F32), dx

    dw, dx = jax.lax.scan(
        one, jnp.zeros(w.shape, _F32),
        (x.reshape(n, T // n, -1), labels.reshape(n, T // n),
         weight.reshape(n, T // n), lse.reshape(n, T // n)))
    return dx.reshape(x.shape).astype(x.dtype), dw.astype(w.dtype), None, None


head_nll_fn.defvjp(_head_fwd, _head_bwd)


# ---------------------------------------------------------------------------
# the ops
# ---------------------------------------------------------------------------


@register_op("rms_norm")
def rms_norm(ctx: ExecContext):
    """X [..., H], Scale [H]; attr epsilon -> Out float32."""
    return {"Out": rms_norm_fn(ctx.input("X"), ctx.input("Scale"),
                               float(ctx.attr("epsilon", 1e-6)))}


@register_op("rotary_embedding")
def rotary_embedding(ctx: ExecContext):
    """X [B, S, n, dh] at positions 0..S-1; attrs theta, yarn (() or
    [factor, original context, beta_fast, beta_slow, attention factor]) ->
    Out [B, n, S, dh] float32: rotate-half pairs over the whole head."""
    x = ctx.input("X")
    dh = x.shape[-1]
    yarn = tuple(ctx.attr("yarn", ()) or ())
    inv = yarn_inv_freq_fn(dh, float(ctx.attr("theta")), yarn[:4])
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
    out = rotary_fn(x.astype(_F32), pos, inv, dh,
                    float(yarn[4]) if yarn else 1.0)
    return {"Out": out.transpose(0, 2, 1, 3)}


@register_op("moe_router")
def moe_router(ctx: ExecContext):
    """X [..., H], W [H, E]; attr top_k -> Cw [..., E] float32: the softmax
    over all E experts, its top_k largest (ties to the lower index)
    renormalised to sum to one, zero elsewhere."""
    x = ctx.input("X")
    _, cw = topk_router_fn(x.reshape(-1, x.shape[-1]).astype(_F32),
                           ctx.input("W").astype(_F32),
                           int(ctx.attr("top_k")))
    return {"Cw": cw.reshape(x.shape[:-1] + (cw.shape[-1],))}


@register_op("moe_experts")
def moe_experts(ctx: ExecContext):
    """X [..., H], Cw [..., E_all] float32, WGate / WUp [E, H, F], WDown
    [E, F, H]: the E experts `first_expert ..` of the router's E_all; attr
    top_k -> Out [..., H] float32, the part of the sum the held experts
    give, and Stats float32 [3 + E] (the module's docstring)."""
    x, cw = ctx.input("X"), ctx.input("Cw")
    wg, wu, wd = ctx.input("WGate"), ctx.input("WUp"), ctx.input("WDown")
    first, held = int(ctx.attr("first_expert", 0)), wg.shape[0]
    cw2 = cw.reshape(-1, cw.shape[-1]).astype(_F32)
    cw_held = cw2[:, first:first + held]
    y, counts = moe_experts_train_fn(
        x.reshape(-1, x.shape[-1]).astype(wg.dtype), cw_held, wg, wu, wd,
        int(ctx.attr("top_k")))
    outs = {"Out": y.reshape(x.shape)}
    if ctx.op.outputs.get("Stats"):
        given = jax.lax.stop_gradient(counts)
        held_pairs = jnp.sum(cw_held != 0, dtype=_F32)
        outs["Stats"] = jnp.concatenate([
            jnp.stack([jnp.sum(cw2 != 0, dtype=_F32), held_pairs,
                       held_pairs - jnp.sum(given)]), given])
    return outs


@register_op("lm_head_loss")
def lm_head_loss(ctx: ExecContext):
    """X [B, S, H], W [H, V], Ids [B, S] int -> Loss [] float32: the mean
    over the B x (S - 1) positions that have a next token of the
    cross-entropy of `softmax(x_s W)` with `Ids[s + 1]`."""
    x, w = ctx.input("X"), ctx.input("W")
    ids = ctx.input("Ids").astype(jnp.int32)
    B, S = ids.shape
    labels = jnp.roll(ids, -1, axis=1)
    weight = jnp.broadcast_to(
        (jnp.arange(S) < S - 1).astype(_F32)[None, :], (B, S))
    total = head_nll_fn(x.reshape(B * S, -1), w, labels.reshape(-1),
                        weight.reshape(-1))
    return {"Loss": total / (B * (S - 1))}
