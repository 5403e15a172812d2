"""Fused normalize+affine+activation(+residual) epilogue: Pallas TPU kernels.

Why this exists (PERF.md r6): ResNet-50's entire recoverable gap vs the
0.45-MFU target sits in the BN/elementwise tail — the conv's epilogue chain
normalize -> scale/bias -> (residual add) -> relu re-crosses HBM once per
fusion boundary XLA declines. PR 5 moved the BN *statistics* into the conv
epilogue (conv2d_bn); these kernels attack the remaining *apply* chain:

  * `bn_apply_act` — given per-channel statistics (the conv2d_bn epilogue
    already produced them, or jnp reductions XLA fuses into the producer),
    one kernel visit computes act((x - mean) * inv * scale + bias
    [+ residual]) — ONE read of x (+ residual), one write of y, fp32 math
    between, in both layouts (NHWC channels-last, NCHW channels-row).
    The unfused chain costs up to three extra HBM round trips when XLA
    splits the elementwise consumers from the producer.
  * `layer_norm_act` — per-row layer norm with the affine+activation in
    the same VMEM visit: row statistics are recomputed on-chip in fp32
    (one-pass, no stat residuals), so the whole LN->act chain is one read
    + one write. The backward recomputes statistics the same way and fuses
    the five per-row gradient terms.

Both kernels carry a custom VJP whose backward is itself one Pallas kernel
emitting dx plus per-tile partial sums for the parameter gradients (the
[n_tiles, C] partials reduce outside — a tiny jnp sum XLA folds away),
so training steps keep the one-read-one-write property end to end.

Dispatch contract (the r5 rule): ships OFF by default. ops/nn_ops.py routes
batch_norm/conv2d_bn/layer_norm epilogues here only when a swept tuning-DB
verdict keeps the kernel for the exact shape (or FLAGS_pallas_epilogue=on
forces it for A/B arms), and only where `epilogue_supported` accepts the
shape on a platform that can run it — everywhere else the XLA reference
below defines the numbers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import workbench

# tests flip this to run the kernels through the Pallas interpreter on CPU
INTERPRET = False

_ACTS = {
    "identity": lambda z: z,
    "": lambda z: z,
    "relu": lambda z: jnp.maximum(z, 0.0),
}

# act'(z) — the backward kernels recompute z on-chip, so the derivative
# needs no saved residuals
_ACT_GRADS = {
    "identity": lambda z: 1.0,
    "": lambda z: 1.0,
    "relu": lambda z: (z > 0.0).astype(jnp.float32),
}

ACTS = tuple(a for a in _ACTS if a)


def epilogue_supported(shape, dtype, channel_last=True, act="identity") -> bool:
    """Shapes the apply kernels handle: >=2-D floating tensors whose
    canonical 2-D row (channels for NHWC, spatial extent for NCHW) fits a
    VMEM slab at tile-rows >= 1, with a registered activation."""
    if act not in _ACTS:
        return False
    if len(shape) < 2 or not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return False
    row = shape[-1] if channel_last else _prod(shape[2:])
    rows = _prod(shape) // max(1, row)
    # fwd holds ~4 fp32 row-copies (x, z, out, residual), bwd ~6
    return (1 <= row and row * 4 * 6 <= workbench.VMEM_BUDGET
            and rows >= 1)


def _prod(xs):
    out = 1
    for v in xs:
        out *= int(v)
    return out


# ---------------------------------------------------------------------------
# bn_apply_act — normalize+affine+act(+residual) given per-channel stats
# ---------------------------------------------------------------------------


def _apply_fwd_kernel(x_ref, s_ref, b_ref, m_ref, v_ref, *rest,
                      act, has_res):
    (r_ref, o_ref) = rest if has_res else (None, rest[0])
    xf = x_ref[...].astype(jnp.float32)
    z = (xf - m_ref[...]) * (v_ref[...] * s_ref[...]) + b_ref[...]
    if has_res:
        z = z + r_ref[...].astype(jnp.float32)
    o_ref[...] = _ACTS[act](z).astype(o_ref.dtype)


def _apply_bwd_kernel(x_ref, s_ref, b_ref, m_ref, v_ref, *rest,
                      act, has_res, red_axis):
    if has_res:
        r_ref, dy_ref, dx_ref, dr_ref, p1_ref, p2_ref = rest
    else:
        dy_ref, dx_ref, p1_ref, p2_ref = rest
        r_ref = dr_ref = None
    xf = x_ref[...].astype(jnp.float32)
    xc = xf - m_ref[...]
    g = v_ref[...] * s_ref[...]
    z = xc * g + b_ref[...]
    if has_res:
        z = z + r_ref[...].astype(jnp.float32)
    dz = dy_ref[...].astype(jnp.float32) * _ACT_GRADS[act](z)
    dx_ref[...] = (dz * g).astype(dx_ref.dtype)
    if has_res:
        dr_ref[...] = dz.astype(dr_ref.dtype)
    # per-channel partials: P1 = sum dz, P2 = sum dz*(x-m); the caller
    # derives dbias/dmean from P1 and dscale/dinv from P2 (scalar algebra
    # per channel), so the kernel ships two reductions, not four
    p1_ref[...] = jnp.sum(dz, axis=red_axis, keepdims=True).reshape(
        p1_ref.shape)
    p2_ref[...] = jnp.sum(dz * xc, axis=red_axis, keepdims=True).reshape(
        p2_ref.shape)


def _apply_specs(mode, tr, row, nt):
    """(x/out spec, param spec, partial spec) for one canonical layout.

    mode "cl": x2 [R, C] channels-last — params broadcast as [1, C] rows,
    per-tile partials land in [NT, 1, C] (a (1, C) block of an [NT, C]
    array is not (8, 128)-tileable; as the two minor dims of a 3-D array it
    is the whole plane). mode "cr": x2 [R=N*C, HW] channels-row — params
    are per-row [TR, 1] columns (pre-tiled to [R, 1]), partials are
    complete per-row sums [R, 1]."""
    xspec = pl.BlockSpec((tr, row), lambda i: (i, 0))
    if mode == "cl":
        pspec = pl.BlockSpec((1, row), lambda i: (0, 0))
        partial = pl.BlockSpec((1, 1, row), lambda i: (i, 0, 0))
    else:
        pspec = pl.BlockSpec((tr, 1), lambda i: (i, 0))
        partial = pl.BlockSpec((tr, 1), lambda i: (i, 0))
    return xspec, pspec, partial


def _apply_call_fwd(x2, params, res2, act, mode, interpret):
    R, row = x2.shape
    tr = workbench.pick_block(R, row * 4 * (5 if res2 is not None else 4))
    nt = R // tr
    xspec, pspec, _ = _apply_specs(mode, tr, row, nt)
    in_specs = [xspec] + [pspec] * 4 + ([xspec] if res2 is not None else [])
    kernel = functools.partial(_apply_fwd_kernel, act=act,
                               has_res=res2 is not None)
    args = (x2, *params) + ((res2,) if res2 is not None else ())
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=in_specs,
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        cost_estimate=pl.CostEstimate(
            flops=6 * R * row, transcendentals=0,
            bytes_accessed=(2 + (1 if res2 is not None else 0))
            * R * row * x2.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="bn_apply_act_fwd",
    )(*args)


def _apply_call_bwd(x2, params, res2, dy2, act, mode, interpret):
    R, row = x2.shape
    has_res = res2 is not None
    tr = workbench.pick_block(R, row * 4 * (8 if has_res else 6))
    nt = R // tr
    xspec, pspec, partial = _apply_specs(mode, tr, row, nt)
    pshape = (nt, 1, row) if mode == "cl" else (R, 1)
    in_specs = [xspec] + [pspec] * 4 + [xspec] * (2 if has_res else 1)
    out_specs = [xspec] + ([xspec] if has_res else []) + [partial] * 2
    out_shape = ([jax.ShapeDtypeStruct(x2.shape, x2.dtype)]
                 + ([jax.ShapeDtypeStruct(x2.shape, dy2.dtype)]
                    if has_res else [])
                 + [jax.ShapeDtypeStruct(pshape, jnp.float32)] * 2)
    kernel = functools.partial(_apply_bwd_kernel, act=act, has_res=has_res,
                               red_axis=0 if mode == "cl" else 1)
    args = (x2, *params) + ((res2, dy2) if has_res else (dy2,))
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        cost_estimate=pl.CostEstimate(
            flops=10 * R * row, transcendentals=0,
            bytes_accessed=(3 + (2 if has_res else 0))
            * R * row * x2.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="bn_apply_act_bwd",
    )(*args)


@functools.lru_cache(maxsize=None)
def _make_apply(act: str, mode: str, has_res: bool, interpret: bool):
    """Cached custom-VJP apply function over canonical 2-D operands.

    Differentiable args: (x2, scale, bias, mean, inv[, res2]) — params in
    the kernel's block orientation ([1, C] rows for "cl", [R, 1] per-row
    columns for "cr"), fp32. The backward emits dx (+dres) from one kernel
    plus the two per-channel partial-sum planes it derives all four
    parameter grads from."""

    def _bwd_shared(saved, dy2):
        x2, s, b, m, v, res2 = saved
        outs = _apply_call_bwd(x2, (s, b, m, v), res2, dy2, act, mode,
                               interpret)
        if has_res:
            dx2, dr2, p1, p2 = outs
        else:
            dx2, p1, p2 = outs
            dr2 = None
        if mode == "cl":
            P1 = jnp.sum(p1, axis=0)                     # [1, C]
            P2 = jnp.sum(p2, axis=0)
        else:
            P1, P2 = p1, p2                              # [R, 1] complete
        ds = P2 * v
        db = P1
        dm = -P1 * v * s
        dv = P2 * s
        return dx2, ds, db, dm, dv, dr2

    def _fwd(x2, s, b, m, v, r2):
        return _apply_call_fwd(x2, (s, b, m, v), r2, act, mode, interpret)

    if has_res:
        @jax.custom_vjp
        def apply(x2, s, b, m, v, r2):
            return _fwd(x2, s, b, m, v, r2)

        def vjp_fwd(x2, s, b, m, v, r2):
            return _fwd(x2, s, b, m, v, r2), (x2, s, b, m, v, r2)

        def vjp_bwd(saved, dy2):
            dx2, ds, db, dm, dv, dr2 = _bwd_shared(saved, dy2)
            return dx2, ds, db, dm, dv, dr2
    else:
        @jax.custom_vjp
        def apply(x2, s, b, m, v):
            return _fwd(x2, s, b, m, v, None)

        def vjp_fwd(x2, s, b, m, v):
            return _fwd(x2, s, b, m, v, None), (x2, s, b, m, v, None)

        def vjp_bwd(saved, dy2):
            dx2, ds, db, dm, dv, _ = _bwd_shared(saved, dy2)
            return dx2, ds, db, dm, dv

    apply.defvjp(vjp_fwd, vjp_bwd)
    return apply


def bn_apply_act_reference(x, scale, bias, mean, inv, act="identity",
                           residual=None, channel_last=True):
    """The XLA composition defining the kernel's numerics: fp32 math,
    normalize -> affine -> (+residual) -> act, cast back to x.dtype."""
    cax = x.ndim - 1 if channel_last else 1
    bshape = [1] * x.ndim
    bshape[cax] = -1
    f32 = lambda a: a.astype(jnp.float32).reshape(bshape)  # noqa: E731
    z = ((x.astype(jnp.float32) - f32(mean)) * (f32(inv) * f32(scale))
         + f32(bias))
    if residual is not None:
        z = z + residual.astype(jnp.float32)
    return _ACTS[act](z).astype(x.dtype)


@workbench.register_kernel(
    "epilogue_bn_apply",
    reference=bn_apply_act_reference,
    supported=epilogue_supported,
    decision_op="epilogue",
    equivalence_test="test_bn_apply_act_matches_reference",
    note="normalize+affine+act(+residual) given per-channel stats; "
         "NHWC channels-last and NCHW channels-row layouts")
def bn_apply_act(x, scale, bias, mean, inv, act="identity", residual=None,
                 channel_last=True):
    """One-pass epilogue apply: act((x - mean) * inv * scale + bias
    [+ residual]) in fp32, returned in x.dtype. scale/bias/mean/inv are
    per-channel [C]; residual must match x's shape. Differentiable in
    x, scale, bias, mean, inv, residual. Callers gate on
    `epilogue_supported`."""
    act = act or "identity"
    shape = x.shape
    if channel_last:
        C = shape[-1]
        x2 = x.reshape(-1, C)
        params = tuple(p.astype(jnp.float32).reshape(1, C)
                       for p in (scale, bias, mean, inv))
        mode = "cl"
    else:
        N, C = shape[0], shape[1]
        hw = _prod(shape[2:])
        x2 = x.reshape(N * C, hw)
        params = tuple(jnp.tile(p.astype(jnp.float32), N).reshape(N * C, 1)
                       for p in (scale, bias, mean, inv))
        mode = "cr"
    res2 = residual.reshape(x2.shape) if residual is not None else None
    fn = _make_apply(act, mode, res2 is not None, bool(INTERPRET))
    args = (x2, *params) + ((res2,) if res2 is not None else ())
    return fn(*args).reshape(shape)


# ---------------------------------------------------------------------------
# layer_norm_act — per-row LN with affine+act in the same VMEM visit
# ---------------------------------------------------------------------------


def _ln_fwd_kernel(x_ref, s_ref, b_ref, o_ref, *, eps, act):
    xf = x_ref[...].astype(jnp.float32)
    m = jnp.mean(xf, axis=1, keepdims=True)
    xc = xf - m
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    z = xc * r * s_ref[...] + b_ref[...]
    o_ref[...] = _ACTS[act](z).astype(o_ref.dtype)


def _ln_bwd_kernel(x_ref, s_ref, b_ref, dy_ref, dx_ref, ds_ref, db_ref,
                   *, eps, act):
    xf = x_ref[...].astype(jnp.float32)
    m = jnp.mean(xf, axis=1, keepdims=True)
    xc = xf - m
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    xhat = xc * r
    z = xhat * s_ref[...] + b_ref[...]
    dz = dy_ref[...].astype(jnp.float32) * _ACT_GRADS[act](z)
    dxhat = dz * s_ref[...]
    a = jnp.mean(dxhat, axis=1, keepdims=True)
    c = jnp.mean(dxhat * xhat, axis=1, keepdims=True)
    dx_ref[...] = (r * (dxhat - a - xhat * c)).astype(dx_ref.dtype)
    ds_ref[0] = jnp.sum(dz * xhat, axis=0, keepdims=True)
    db_ref[0] = jnp.sum(dz, axis=0, keepdims=True)


@functools.lru_cache(maxsize=None)
def _make_ln(eps: float, act: str, interpret: bool):
    def call_fwd(x2, s, b):
        R, K = x2.shape
        tr = workbench.pick_block(R, K * 4 * 5)
        return pl.pallas_call(
            functools.partial(_ln_fwd_kernel, eps=eps, act=act),
            grid=(R // tr,),
            in_specs=[pl.BlockSpec((tr, K), lambda i: (i, 0)),
                      pl.BlockSpec((1, K), lambda i: (0, 0)),
                      pl.BlockSpec((1, K), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((tr, K), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            cost_estimate=pl.CostEstimate(
                flops=9 * R * K, transcendentals=R,
                bytes_accessed=2 * R * K * x2.dtype.itemsize),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
            name="layer_norm_act_fwd",
        )(x2, s, b)

    @jax.custom_vjp
    def ln(x2, s, b):
        return call_fwd(x2, s, b)

    def vjp_fwd(x2, s, b):
        return call_fwd(x2, s, b), (x2, s, b)

    def vjp_bwd(saved, dy2):
        x2, s, b = saved
        R, K = x2.shape
        tr = workbench.pick_block(R, K * 4 * 7)
        nt = R // tr
        dx2, ds_p, db_p = pl.pallas_call(
            functools.partial(_ln_bwd_kernel, eps=eps, act=act),
            grid=(nt,),
            in_specs=[pl.BlockSpec((tr, K), lambda i: (i, 0)),
                      pl.BlockSpec((1, K), lambda i: (0, 0)),
                      pl.BlockSpec((1, K), lambda i: (0, 0)),
                      pl.BlockSpec((tr, K), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((tr, K), lambda i: (i, 0)),
                       pl.BlockSpec((1, 1, K), lambda i: (i, 0, 0)),
                       pl.BlockSpec((1, 1, K), lambda i: (i, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct(x2.shape, x2.dtype),
                       jax.ShapeDtypeStruct((nt, 1, K), jnp.float32),
                       jax.ShapeDtypeStruct((nt, 1, K), jnp.float32)],
            cost_estimate=pl.CostEstimate(
                flops=16 * R * K, transcendentals=R,
                bytes_accessed=3 * R * K * x2.dtype.itemsize),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
            name="layer_norm_act_bwd",
        )(x2, s, b, dy2)
        return dx2, jnp.sum(ds_p, axis=0), jnp.sum(db_p, axis=0)

    ln.defvjp(vjp_fwd, vjp_bwd)
    return ln


def layer_norm_act_reference(x2, scale, bias, eps=1e-5, act="identity"):
    """The XLA composition defining the kernel's numerics (rows of x2
    normalized over the last dim, fp32 statistics)."""
    xf = x2.astype(jnp.float32)
    m = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - m), axis=-1, keepdims=True)
    z = (xf - m) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        z = z * scale.astype(jnp.float32).reshape(1, -1)
    if bias is not None:
        z = z + bias.astype(jnp.float32).reshape(1, -1)
    return _ACTS[act or "identity"](z).astype(x2.dtype)


@workbench.register_kernel(
    "epilogue_layer_norm",
    reference=layer_norm_act_reference,
    supported=lambda shape, dtype, act="identity": epilogue_supported(
        shape, dtype, channel_last=True, act=act),
    decision_op="epilogue",
    equivalence_test="test_layer_norm_act_matches_reference",
    note="one-pass per-row LN (+affine+act) with in-kernel fp32 statistics")
def layer_norm_act(x2, scale=None, bias=None, eps=1e-5, act="identity"):
    """Fused LN epilogue over canonical rows: x2 [R, K] normalized over K
    with affine+act in the same VMEM visit. scale/bias default to 1/0.
    Differentiable in x2, scale, bias. Callers gate on
    `epilogue_supported((R, K), dtype)`."""
    act = act or "identity"
    K = x2.shape[-1]
    s = (jnp.ones((1, K), jnp.float32) if scale is None
         else scale.astype(jnp.float32).reshape(1, K))
    b = (jnp.zeros((1, K), jnp.float32) if bias is None
         else bias.astype(jnp.float32).reshape(1, K))
    return _make_ln(float(eps), act, bool(INTERPRET))(x2, s, b)
