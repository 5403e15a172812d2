"""Neural-net ops: conv, pooling, normalization, dropout, softmax, losses,
embedding lookup.

TPU-native equivalents of /root/reference/paddle/fluid/operators/ conv_op.*,
pool_op.*, batch_norm_op.*, layer_norm_op.*, group_norm_op.cc, dropout_op.*,
softmax_op.*, cross_entropy_op.*, softmax_with_cross_entropy_op.*,
lookup_table_op.*, metrics/accuracy_op.cc, smooth_l1_loss_op, sigmoid_xent.

Layout: NCHW to match the reference's Python API contract; XLA relayouts to
TPU-preferred internally. Matmuls/convs accumulate in fp32
(`preferred_element_type`) so bf16 training keeps fp32 accumulation on the MXU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags
from .registry import ExecContext, register_op, register_grad_compute


def _pair(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v, v)


def _conv_pads(praw):
    # 2-element [ph, pw] (symmetric) or 4-element [top, bottom, left, right]
    # (asymmetric — needed e.g. by the space-to-depth ResNet stem; an
    # explicit pad op in front of the conv measures 2.4x slower on TPU v5e
    # because XLA does not fold it into the convolution).
    if isinstance(praw, (list, tuple)) and len(praw) == 4:
        return [(praw[0], praw[1]), (praw[2], praw[3])]
    p = _pair(praw)
    return [(p[0], p[0]), (p[1], p[1])]


# Implicit-GEMM cost-model constants — the measured single-chip rooflines
# this repo's perf campaign is calibrated against (PERF.md r4: matmul
# 157-162 TF/s sustained, HBM 476-522 GB/s; conv MXU efficiency ~0.7-0.75 of
# the matmul ceiling at >=half lane fill). The model only has to rank two
# lowerings of the SAME conv, so absolute calibration error mostly cancels;
# `tools/tune.py --what conv` measures both per shape.
_IGEMM_MXU_FLOPS = 157e12
_IGEMM_HBM_BPS = 450e9
_IGEMM_MXU_EFF = 0.75
_IGEMM_WIN_MARGIN = 0.9  # predicted igemm time must beat direct by >=10%


def _igemm_predict_win(n, hout, wout, cin, cout, kh, kw, itemsize) -> bool:
    """Tile-fill vs HBM-traffic model (PAPERS.md: A Learned Performance Model
    for TPUs, 2008.01040 — the fill term; TVM, 1802.04799 — the layout-
    rewrite framing): direct conv contracts K=C_in per tap (under-filling
    the 128-lane MXU when C_in < 128), implicit GEMM folds K=C_in*kh*kw but
    must materialize the kh*kw-times-larger patch tensor through HBM."""
    m = n * hout * wout
    k_fold = cin * kh * kw
    flops = 2.0 * m * k_fold * cout

    def fill(k):
        return min(1.0, k / 128.0)

    t_direct = flops / (_IGEMM_MXU_FLOPS * fill(cin) * _IGEMM_MXU_EFF)
    patch_bytes = 2.0 * m * k_fold * itemsize  # write at im2col + read at dot
    t_igemm = (flops / (_IGEMM_MXU_FLOPS * fill(k_fold) * _IGEMM_MXU_EFF)
               + patch_bytes / _IGEMM_HBM_BPS)
    return t_igemm < _IGEMM_WIN_MARGIN * t_direct


def _igemm_mode() -> str:
    mode = str(flags.get_flag("conv_implicit_gemm")).lower()
    if mode in ("on", "always", "all", "1", "true"):
        return "on"
    if mode in ("off", "never", "0", "false"):
        return "off"
    return "auto"


def _igemm_take(x, w, strides, pads, d, groups, fmt) -> bool:
    """Per-shape gate for the implicit-GEMM lowering.

    'on'/'off' stay hard forces (the A/B arms must be able to override any
    cache). 'auto' resolves through the autotuner when FLAGS_tuning_mode is
    not 'off': exact swept-DB hit -> the analytic cost model above as the
    prior -> direct conv as the conservative default. With tuning off, auto
    is the bare analytic model — bit-for-bit the PR 5 behavior."""
    mode = _igemm_mode()
    if mode == "off" or groups != 1:
        return False
    if not (jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.issubdtype(w.dtype, jnp.floating)):
        return False
    if fmt == "NCHW":
        n, cin, h, wi = x.shape
        kh, kw = w.shape[2], w.shape[3]
    else:
        n, h, wi, cin = x.shape
        kh, kw = w.shape[0], w.shape[1]
    (pt, pb), (pl, pr) = pads
    hout = (h + pt + pb - ((kh - 1) * d[0] + 1)) // strides[0] + 1
    wout = (wi + pl + pr - ((kw - 1) * d[1] + 1)) // strides[1] + 1
    if hout <= 0 or wout <= 0:
        return False
    if mode == "on":
        return True
    cout = w.shape[0] if fmt == "NCHW" else w.shape[3]
    itemsize = jnp.dtype(x.dtype).itemsize

    from .. import tuning

    if tuning.mode() == "off":
        return _igemm_predict_win(n, hout, wout, cin, cout, kh, kw, itemsize)
    key = tuning.canonical_key(
        "conv2d", tuning.conv_key(n, hout, wout, cin, cout, kh, kw,
                                  strides, d, fmt),
        str(jnp.dtype(x.dtype)), tuning.device_kind())
    decision, _tier = tuning.decide(
        "conv2d", key,
        prior=lambda: {"lowering": "igemm" if _igemm_predict_win(
            n, hout, wout, cin, cout, kh, kw, itemsize) else "direct"},
        default={"lowering": "direct"},
        # a swept verdict naming a lowering this build doesn't have falls
        # through to the prior instead of being obeyed blindly
        validate=lambda dd: dd.get("lowering") in ("direct", "igemm",
                                                   "matmul_1x1"))
    # matmul_1x1 IS the implicit-GEMM path at kh=kw=1 (the im2col collapses
    # to a reshape, leaving the bare GEMM)
    return decision.get("lowering") in ("igemm", "matmul_1x1")


def _conv2d_igemm_f32(x, w, strides, pads, d, fmt):
    """im2col + GEMM lowering, returning the fp32 accumulator [*, C_out]
    in the output layout. The kh*kw shifted strided slices of the padded
    input concatenate tap-major along the channel dim, matching a plain
    reshape of the HWIO (NHWC) / tap-major-transposed OIHW (NCHW) filter —
    so one lax.dot_general carries the whole conv with K = C_in*kh*kw.
    Backward derives via vjp: dX is the transposed GEMM scattered by the
    slice transposes (col2im), dW the patches^T @ dOut GEMM — both ride the
    MXU at the same folded fill."""
    sh, sw = strides
    dh, dw = d
    if fmt == "NCHW":
        n, cin, h, wi = x.shape
        cout, _, kh, kw = w.shape
        xp = jnp.pad(x, ((0, 0), (0, 0), pads[0], pads[1]))
        hout = (h + sum(pads[0]) - ((kh - 1) * dh + 1)) // sh + 1
        wout = (wi + sum(pads[1]) - ((kw - 1) * dw + 1)) // sw + 1
        taps = [
            jax.lax.slice(
                xp,
                (0, 0, i * dh, j * dw),
                (n, cin, i * dh + (hout - 1) * sh + 1,
                 j * dw + (wout - 1) * sw + 1),
                (1, 1, sh, sw))
            for i in range(kh) for j in range(kw)
        ]
        patches = jnp.concatenate(taps, axis=1)  # [N, kh*kw*Cin, H', W']
        wmat = jnp.transpose(w, (2, 3, 1, 0)).reshape(kh * kw * cin, cout)
        acc = jax.lax.dot_general(
            patches, wmat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [N, H', W', Cout]
        return jnp.transpose(acc, (0, 3, 1, 2))
    n, h, wi, cin = x.shape
    kh, kw, _, cout = w.shape
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    hout = (h + sum(pads[0]) - ((kh - 1) * dh + 1)) // sh + 1
    wout = (wi + sum(pads[1]) - ((kw - 1) * dw + 1)) // sw + 1
    taps = [
        jax.lax.slice(
            xp,
            (0, i * dh, j * dw, 0),
            (n, i * dh + (hout - 1) * sh + 1,
             j * dw + (wout - 1) * sw + 1, cin),
            (1, sh, sw, 1))
        for i in range(kh) for j in range(kw)
    ]
    patches = jnp.concatenate(taps, axis=-1)  # [N, H', W', kh*kw*Cin]
    wmat = w.reshape(kh * kw * cin, cout)
    return jax.lax.dot_general(
        patches.reshape(n * hout * wout, kh * kw * cin), wmat,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).reshape(n, hout, wout, cout)


def _conv2d_forward(ctx: ExecContext):
    """Shared conv lowering: returns (out_in_x_dtype, fp32_acc_or_None).
    The fp32 accumulator is only materialized on the implicit-GEMM path
    (the dot's natural output); conv2d_bn reads it for epilogue statistics."""
    x, w = ctx.input("Input"), ctx.input("Filter")
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _conv_pads(ctx.attr("paddings", [0, 0]))
    d = _pair(ctx.attr("dilations", [1, 1]))
    groups = ctx.attr("groups", 1)
    # data_format NHWC keeps the whole activation chain channels-last on
    # TPU (reference conv2d's data_format attr) and carries its weights in
    # HWIO (the layers allocate them that way): OIHW weights fed straight
    # into an NHWC conv measure ~25-40% slower (XLA picks a worse
    # algorithm) and an in-step transpose still costs ~6%/conv (PERF r5).
    fmt = ctx.attr("data_format", "NCHW")
    if _igemm_take(x, w, strides, pads, d, groups, fmt):
        acc = _conv2d_igemm_f32(x, w, strides, pads, d, fmt)
        return acc.astype(x.dtype), acc
    rhs = "OIHW" if fmt == "NCHW" else "HWIO"
    # No preferred_element_type=f32 + astype pair here: the TPU MXU already
    # accumulates bf16 convs in fp32 internally, and the astype's transpose
    # rule would hand lax's conv grad an fp32 cotangent against bf16 operands
    # (lax.conv_general_dilated requires matching dtypes), breaking AMP
    # backward passes.
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=pads,
        rhs_dilation=d,
        dimension_numbers=(fmt, rhs, fmt),
        feature_group_count=groups,
    )
    return out, None


@register_op("conv2d")
def conv2d(ctx: ExecContext):
    out, _ = _conv2d_forward(ctx)
    return {"Output": out}


@register_op("depthwise_conv2d")
def depthwise_conv2d(ctx: ExecContext):
    # reference conv_op.cc registers depthwise as its own type; groups == C_in
    return conv2d(ctx)


@register_op("conv2d_transpose")
def conv2d_transpose(ctx: ExecContext):
    x, w = ctx.input("Input"), ctx.input("Filter")
    strides = _pair(ctx.attr("strides", [1, 1]))
    p = _pair(ctx.attr("paddings", [0, 0]))
    d = _pair(ctx.attr("dilations", [1, 1]))
    # filter layout for transpose in the reference is (C_in, C_out, H, W).
    # With transpose_kernel=True jax swaps the kernel's I/O axes and flips
    # its spatial dims, so the spec must name dim 0 "O" and dim 1 "I" for
    # the post-swap conv to contract C_in against the input.
    #
    # jax's explicit padding applies to the DILATED input directly; the
    # reference output extent (in-1)*s + d*(k-1)+1 - 2p needs each side
    # padded by d*(k-1) - p (conv_transpose_op.cc output formula).
    ke = [d[i] * (w.shape[2 + i] - 1) for i in range(2)]
    out = jax.lax.conv_transpose(
        x,
        w,
        strides=strides,
        padding=[(ke[0] - p[0], ke[0] - p[0]), (ke[1] - p[1], ke[1] - p[1])],
        rhs_dilation=d,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        transpose_kernel=True,
    ).astype(x.dtype)
    return {"Output": out}


@register_op("pool2d")
def pool2d(ctx: ExecContext):
    x = ctx.input("X")
    ptype = ctx.attr("pooling_type", "max")
    k = _pair(ctx.attr("ksize", [2, 2]))
    s = _pair(ctx.attr("strides", [2, 2]))
    p = _pair(ctx.attr("paddings", [0, 0]))
    nhwc = ctx.attr("data_format", "NCHW") == "NHWC"
    hax = 1 if nhwc else 2
    if ctx.attr("global_pooling", False):
        k = (x.shape[hax], x.shape[hax + 1])
        s, p = k, (0, 0)
    if nhwc:
        window = (1,) + k + (1,)
        strides = (1,) + s + (1,)
        pads = ((0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0))
    else:
        window = (1, 1) + k
        strides = (1, 1) + s
        pads = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides, pads)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
        if ctx.attr("exclusive", True) and (p[0] or p[1]):
            ones = jnp.ones_like(x)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, pads)
            out = summed / counts
        else:
            out = summed / (k[0] * k[1])
    return {"Out": out.astype(x.dtype)}


@register_op("softmax")
def softmax(ctx: ExecContext):
    return {"Out": jax.nn.softmax(ctx.input("X"), axis=ctx.attr("axis", -1))}


@register_op("log_softmax")
def log_softmax(ctx: ExecContext):
    return {"Out": jax.nn.log_softmax(ctx.input("X"), axis=ctx.attr("axis", -1))}


def _xent_from_softmax(sm, label, soft_label, ignore_index):
    eps = 1e-12
    if soft_label:
        return -jnp.sum(label * jnp.log(sm + eps), axis=-1, keepdims=True)
    lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
    picked = jnp.take_along_axis(sm, lbl[..., None].astype(np.int32), axis=-1)
    loss = -jnp.log(picked + eps)
    if ignore_index is not None and ignore_index >= 0:
        loss = jnp.where(lbl[..., None] == ignore_index, jnp.zeros_like(loss), loss)
    return loss


@register_op("cross_entropy")
def cross_entropy(ctx: ExecContext):
    x, label = ctx.input("X"), ctx.input("Label")
    return {
        "Y": _xent_from_softmax(
            x, label, ctx.attr("soft_label", False), ctx.attr("ignore_index", -100)
        )
    }


@register_op("softmax_with_cross_entropy")
def softmax_with_cross_entropy(ctx: ExecContext):
    logits, label = ctx.input("Logits"), ctx.input("Label")
    soft = ctx.attr("soft_label", False)
    ignore = ctx.attr("ignore_index", -100)
    # fp32 statistics INTERNALLY (gray-listed under AMP): bf16 in/out,
    # fp32 softmax math — the layer_norm/batch_norm discipline
    lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    sm = jnp.exp(lsm).astype(logits.dtype)
    if soft:
        loss = -jnp.sum(label.astype(jnp.float32) * lsm, axis=-1,
                        keepdims=True)
    else:
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        loss = -jnp.take_along_axis(lsm, lbl[..., None].astype(np.int32), axis=-1)
        if ignore >= 0:
            loss = jnp.where(lbl[..., None] == ignore, jnp.zeros_like(loss), loss)
    return {"Softmax": sm, "Loss": loss.astype(logits.dtype)}


@register_grad_compute("softmax_with_cross_entropy")
def softmax_with_cross_entropy_grad(ctx: ExecContext):
    """dLogits = (softmax - onehot(label)) * dLoss — the classic fused form
    (reference softmax_with_cross_entropy_op.cu)."""
    sm = ctx.input("Softmax")
    label = ctx.input("Label")
    dloss = ctx.input("Loss@GRAD")
    soft = ctx.attr("soft_label", False)
    if soft:
        grad = (sm - label) * dloss
    else:
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        onehot = jax.nn.one_hot(lbl, sm.shape[-1], dtype=sm.dtype)
        grad = (sm - onehot) * dloss
        ignore = ctx.attr("ignore_index", -100)
        if ignore >= 0:
            grad = jnp.where((lbl == ignore)[..., None], jnp.zeros_like(grad), grad)
    return {"Logits@GRAD": grad}


def softmax_with_cross_entropy_grad_maker(op, block, no_grad_set=frozenset()):
    from ..framework import grad_var_name

    logits = op.input("Logits")[0]
    if logits in no_grad_set:
        return []
    return [
        {
            "type": "softmax_with_cross_entropy_grad",
            "inputs": {
                "Softmax": op.output("Softmax"),
                "Label": op.input("Label"),
                "Loss@GRAD": [grad_var_name(op.output("Loss")[0])],
            },
            "outputs": {"Logits@GRAD": [grad_var_name(logits)]},
            "attrs": dict(op.attrs),
        }
    ]


# wire the custom maker in (registered after the op exists)
from .registry import get_op_def  # noqa: E402

get_op_def("softmax_with_cross_entropy").grad_maker = softmax_with_cross_entropy_grad_maker


@register_op("sigmoid_cross_entropy_with_logits")
def sigmoid_cross_entropy_with_logits(ctx: ExecContext):
    x, label = ctx.input("X"), ctx.input("Label")
    # numerically stable: max(x,0) - x*z + log(1+exp(-|x|))
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = ctx.attr("ignore_index", -100)
    loss = jnp.where(label == ignore, jnp.zeros_like(loss), loss)
    if ctx.attr("normalize", False):
        n = jnp.maximum(jnp.sum((label != ignore).astype(x.dtype)), 1.0)
        loss = loss / n
    return {"Out": loss}


@register_op("smooth_l1_loss")
def smooth_l1_loss(ctx: ExecContext):
    x, y = ctx.input("X"), ctx.input("Y")
    sigma = ctx.attr("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    if ctx.has_input("InsideWeight"):
        d = d * ctx.input("InsideWeight")
    a = jnp.abs(d)
    loss = jnp.where(a < 1.0 / s2, 0.5 * d * d * s2, a - 0.5 / s2)
    if ctx.has_input("OutsideWeight"):
        loss = loss * ctx.input("OutsideWeight")
    return {"Out": jnp.sum(loss, axis=-1, keepdims=True), "Diff": d}


# ---------------------------------------------------------------------------
# Fused epilogue dispatch (ISSUE 9): normalize+affine+activation(+residual)
# ---------------------------------------------------------------------------

_EPILOGUE_ACTS = {"identity": lambda z: z,
                  "relu": lambda z: jnp.maximum(z, 0.0)}


def _epilogue_backend(kind, rows, channels, channel_pos, act, has_res,
                      dtype) -> str:
    """Which implementation carries one fused-epilogue apply: the Pallas
    kernel or the XLA composition. Same three-tier contract as the conv/
    attention levers (PR 6): FLAGS_pallas_epilogue 'on'/'off' are hard
    forces for the A/B arms; 'auto' consults the tuning DB with the XLA
    composition as the analytic prior — the kernel ships off until a swept
    verdict keeps it for the exact shape (the r5 rule). Callers still gate
    on `_epilogue_ok`, so a swept/forced kernel the platform cannot run
    degrades to XLA at dispatch."""
    mode = str(flags.get_flag("pallas_epilogue")).strip().lower()
    if mode == "off":
        return "xla"
    if mode == "on":
        return "pallas"
    from .. import tuning

    if tuning.mode() == "off":
        return "xla"
    key = tuning.canonical_key(
        "epilogue",
        tuning.epilogue_key(kind, rows, channels, channel_pos, act, has_res),
        str(jnp.dtype(dtype)), tuning.device_kind())
    decision, _tier = tuning.decide(
        "epilogue", key, prior=lambda: {"backend": "xla"},
        default={"backend": "xla"},
        validate=lambda dd: dd.get("backend") in ("xla", "pallas"))
    return decision.get("backend", "xla")


def _epilogue_ok(shape, dtype, channel_last, act) -> bool:
    from .pallas_kernels import epilogue as ep
    from .pallas_kernels import workbench

    return (workbench.runnable(ep)
            and ep.epilogue_supported(shape, dtype, channel_last, act))


def _bn_epilogue(x_for_apply, scale, bias, use_mean, inv, act, residual,
                 channel_last, bshape):
    """One fused-epilogue finish for batch_norm/conv2d_bn: dispatch per
    `_epilogue_backend`, Pallas kernel where a verdict keeps it and the
    shape/platform can run it, the fp32 jnp composition (bit-identical to
    the pre-fusion op chain) everywhere else."""
    act = act or "identity"
    C = x_for_apply.shape[-1 if channel_last else 1]
    rows = int(np.prod(x_for_apply.shape)) // max(1, C)
    backend = _epilogue_backend(
        "bn", rows, C, "last" if channel_last else "row", act,
        residual is not None, x_for_apply.dtype)
    if (backend == "pallas"
            and act in _EPILOGUE_ACTS
            and _epilogue_ok(x_for_apply.shape, x_for_apply.dtype,
                             channel_last, act)):
        from .pallas_kernels import epilogue as ep

        return ep.bn_apply_act(x_for_apply, scale, bias, use_mean, inv,
                               act=act, residual=residual,
                               channel_last=channel_last)
    y = (x_for_apply.astype(jnp.float32) - use_mean.reshape(bshape)) \
        * inv.reshape(bshape)
    y = y * scale.reshape(bshape) + bias.reshape(bshape)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    y = _EPILOGUE_ACTS.get(act, _EPILOGUE_ACTS["identity"])(y)
    return y.astype(x_for_apply.dtype)


@register_op("batch_norm", stateful_outputs=("MeanOut", "VarianceOut"))
def batch_norm(ctx: ExecContext):
    x = ctx.input("X")
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    mean, var = ctx.input("Mean"), ctx.input("Variance")
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    is_test = ctx.attr("is_test", False)
    layout = ctx.attr("data_layout", "NCHW")
    axes = tuple(i for i in range(x.ndim) if i != (1 if layout == "NCHW" else x.ndim - 1))
    bshape = [1] * x.ndim
    bshape[1 if layout == "NCHW" else x.ndim - 1] = -1

    if is_test:
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = jnp.zeros_like(mean)
        saved_var = jnp.zeros_like(var)
    else:
        xf = x.astype(jnp.float32)
        use_mean = jnp.mean(xf, axis=axes)
        use_var = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(use_mean)
        mean_out = mean * momentum + use_mean.astype(mean.dtype) * (1 - momentum)
        var_out = var * momentum + use_var.astype(var.dtype) * (1 - momentum)
        saved_mean = use_mean.astype(mean.dtype)
        saved_var = (1.0 / jnp.sqrt(use_var + eps)).astype(var.dtype)
    inv = 1.0 / jnp.sqrt(use_var.astype(jnp.float32) + eps)
    # fused epilogue (ISSUE 9): the minimize()-time pass may have folded a
    # trailing activation (attr `act`) and/or a residual add (input
    # `Residual`) into this op; _bn_epilogue dispatches the whole apply
    # chain per the tuning DB (Pallas kernel only where a swept verdict
    # keeps it — XLA composition, bit-identical to the unfused chain,
    # everywhere else)
    res = ctx.input("Residual") if ctx.has_input("Residual") else None
    y = _bn_epilogue(x, scale, bias,
                     use_mean.astype(jnp.float32), inv,
                     ctx.attr("act", ""), res,
                     channel_last=layout != "NCHW", bshape=bshape)
    return {
        "Y": y,
        "MeanOut": mean_out,
        "VarianceOut": var_out,
        "SavedMean": saved_mean,
        "SavedVariance": saved_var,
    }


@register_op("conv2d_bn", stateful_outputs=("MeanOut", "VarianceOut"))
def conv2d_bn(ctx: ExecContext):
    """Fused conv2d -> batch_norm(training) with one-pass epilogue
    statistics (passes.fuse_conv_bn_stats rewrites eligible pairs to this).

    The separate batch_norm op re-reads the conv output from HBM to reduce
    E[x]/E[x^2] — measured at 17-35% of ResNet stage time (a round-5
    probe, no ledger line). Here both statistics are computed as siblings of the
    conv's own result — on the implicit-GEMM path directly from the fp32 GEMM
    accumulator before the bf16 down-cast — so XLA's multi-output fusion can
    emit them in the producer's epilogue while the tile is still on-chip,
    instead of a second HBM traversal. Statistics stay fp32 regardless of the
    activation dtype (the AMP gray-list discipline; bf16 in/out is safe
    because nothing below fp32 ever carries a running statistic)."""
    out, acc = _conv2d_forward(ctx)
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    mean, var = ctx.input("Mean"), ctx.input("Variance")
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    fmt = ctx.attr("data_format", "NCHW")
    cax = 1 if fmt == "NCHW" else out.ndim - 1
    axes = tuple(i for i in range(out.ndim) if i != cax)
    bshape = [1] * out.ndim
    bshape[cax] = -1

    # one-pass statistics from the highest-precision view available: the
    # implicit-GEMM fp32 accumulator when the conv took that path (exact
    # pre-rounding moments), else an fp32 upcast of the conv result (the
    # same values batch_norm would see, now adjacent to the producer)
    xf = acc if acc is not None else out.astype(jnp.float32)
    use_mean = jnp.mean(xf, axis=axes)
    use_var = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(use_mean)
    mean_out = mean * momentum + use_mean.astype(mean.dtype) * (1 - momentum)
    var_out = var * momentum + use_var.astype(var.dtype) * (1 - momentum)
    inv = 1.0 / jnp.sqrt(use_var + eps)
    # fused epilogue (ISSUE 9): same contract as batch_norm — the apply
    # chain (normalize+affine[+residual][+act]) dispatches through the
    # tuning DB. The Pallas arm normalizes the fp32 accumulator view so the
    # one-read-one-write kernel sees the exact pre-rounding values the
    # statistics came from.
    res = ctx.input("Residual") if ctx.has_input("Residual") else None
    y = _bn_epilogue(xf, scale, bias, use_mean, inv,
                     ctx.attr("act", ""), res,
                     channel_last=fmt != "NCHW", bshape=bshape)
    return {
        "Y": y.astype(out.dtype),
        "MeanOut": mean_out,
        "VarianceOut": var_out,
        "SavedMean": use_mean.astype(mean.dtype),
        "SavedVariance": (1.0 / jnp.sqrt(use_var + eps)).astype(var.dtype),
    }


@register_op("layer_norm")
def layer_norm(ctx: ExecContext):
    x = ctx.input("X")
    eps = ctx.attr("epsilon", 1e-5)
    begin = ctx.attr("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    act = ctx.attr("act", "") or "identity"
    scale = ctx.input("Scale") if ctx.has_input("Scale") else None
    bias = ctx.input("Bias") if ctx.has_input("Bias") else None
    xf = x.astype(jnp.float32)
    # Mean/Variance outputs stay PLAIN jnp expressions on every backend:
    # XLA dead-code-eliminates them when nothing consumes them (the usual
    # case), and gradient contributions through them flow via this jnp
    # path even when Y comes from the Pallas kernel (whose own backward
    # recomputes row statistics on-chip and never sees these cotangents)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    R = int(np.prod(x.shape[:begin])) if begin else 1
    K = int(np.prod(x.shape[begin:]))
    backend = _epilogue_backend("ln", R, K, "last", act, False, x.dtype)
    if backend == "pallas" and _epilogue_ok((R, K), x.dtype, True, act):
        from .pallas_kernels import epilogue as ep

        y = ep.layer_norm_act(
            x.reshape(R, K),
            scale.reshape(-1) if scale is not None else None,
            bias.reshape(-1) if bias is not None else None,
            eps=eps, act=act).reshape(x.shape)
    else:
        norm_shape = x.shape[begin:]
        y = (xf - mean) / jnp.sqrt(var + eps)
        if scale is not None:
            y = y * scale.reshape(norm_shape).astype(jnp.float32)
        if bias is not None:
            y = y + bias.reshape(norm_shape).astype(jnp.float32)
        y = _EPILOGUE_ACTS.get(act, _EPILOGUE_ACTS["identity"])(y)
        y = y.astype(x.dtype)
    return {
        "Y": y,
        "Mean": mean.reshape(x.shape[:begin]).astype(jnp.float32),
        "Variance": var.reshape(x.shape[:begin]).astype(jnp.float32),
    }


@register_op("group_norm")
def group_norm(ctx: ExecContext):
    x = ctx.input("X")  # NCHW
    groups = ctx.attr("groups")
    eps = ctx.attr("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape(n, groups, c // groups, *x.shape[2:]).astype(jnp.float32)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + eps)).reshape(x.shape)
    bshape = [1, c] + [1] * (x.ndim - 2)
    if ctx.has_input("Scale"):
        y = y * ctx.input("Scale").reshape(bshape)
    if ctx.has_input("Bias"):
        y = y + ctx.input("Bias").reshape(bshape)
    return {
        "Y": y.astype(x.dtype),
        "Mean": mean.reshape(n, groups),
        "Variance": var.reshape(n, groups),
    }


@register_op("instance_norm")
def instance_norm(ctx: ExecContext):
    x = ctx.input("X")  # NCHW
    eps = ctx.attr("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) / jnp.sqrt(var + eps)
    c = x.shape[1]
    bshape = [1, c] + [1] * (x.ndim - 2)
    if ctx.has_input("Scale"):
        y = y * ctx.input("Scale").reshape(bshape)
    if ctx.has_input("Bias"):
        y = y + ctx.input("Bias").reshape(bshape)
    return {"Y": y.astype(x.dtype)}


@register_op("dropout", needs_rng=True)
def dropout(ctx: ExecContext):
    x = ctx.input("X")
    p = ctx.attr("dropout_prob", 0.5)
    impl = ctx.attr("dropout_implementation", "downgrade_in_infer")
    if ctx.attr("is_test", False):
        if impl == "upscale_in_train":
            return {"Out": x, "Mask": jnp.ones_like(x)}
        return {"Out": x * jnp.asarray(1.0 - p, x.dtype), "Mask": jnp.ones_like(x)}
    keep = jax.random.bernoulli(ctx.rng, 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        mask = keep.astype(x.dtype) / jnp.asarray(max(1.0 - p, 1e-8), x.dtype)
    else:
        mask = keep.astype(x.dtype)
    return {"Out": x * mask, "Mask": mask}


@register_grad_compute("dropout")
def dropout_grad(ctx: ExecContext):
    return {"X@GRAD": ctx.input("Out@GRAD") * ctx.input("Mask")}


def dropout_grad_maker(op, block, no_grad_set=frozenset()):
    from ..framework import grad_var_name

    x = op.input("X")[0]
    if x in no_grad_set:
        return []
    return [
        {
            "type": "dropout_grad",
            "inputs": {
                "Mask": op.output("Mask"),
                "Out@GRAD": [grad_var_name(op.output("Out")[0])],
            },
            "outputs": {"X@GRAD": [grad_var_name(x)]},
            "attrs": dict(op.attrs),
        }
    ]


get_op_def("dropout").grad_maker = dropout_grad_maker


@register_op("lookup_table")
def lookup_table(ctx: ExecContext):
    w, ids = ctx.input("W"), ctx.input("Ids")
    idsq = ids.reshape(ids.shape[:-1]) if ids.shape and ids.shape[-1] == 1 else ids
    out = jnp.take(w, idsq.astype(np.int32), axis=0)
    padding_idx = ctx.attr("padding_idx", -1)
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((idsq == padding_idx)[..., None], jnp.zeros_like(out), out)
    return {"Out": out}


@register_op("lookup_table_v2")
def lookup_table_v2(ctx: ExecContext):
    return lookup_table(ctx)


@register_grad_compute("lookup_table")
def lookup_table_grad(ctx: ExecContext):
    """W grad: dense scatter-add, or a SelectedRows row-set when is_sparse —
    the reference's SelectedRows grad path (lookup_table_op.cc grad kernel +
    selected_rows.h:32), kept fixed-shape for XLA."""
    from ..core.selected_rows import SelectedRows

    w, ids, og = ctx.input("W"), ctx.input("Ids"), ctx.input("Out@GRAD")
    if og is None:
        return {"W@GRAD": jnp.zeros_like(w)}
    idsq = ids.reshape(ids.shape[:-1]) if ids.shape and ids.shape[-1] == 1 else ids
    idsq = idsq.astype(np.int32)
    width = og.shape[-1]
    padding_idx = ctx.attr("padding_idx", -1)
    rows = idsq.reshape(-1)
    vals = og.reshape(-1, width)
    if padding_idx is not None and padding_idx >= 0:
        vals = jnp.where((rows == padding_idx)[:, None], jnp.zeros_like(vals), vals)
    if ctx.attr("is_sparse", False):
        return {"W@GRAD": SelectedRows(rows, vals, height=w.shape[0])}
    dense = jnp.zeros_like(w).at[rows].add(vals.astype(w.dtype))
    return {"W@GRAD": dense}


register_grad_compute("lookup_table_v2")(lookup_table_grad)


def _no_grad_ops_maker(op, block, no_grad_set=frozenset()):
    """Grad maker for state-plumbing ops that sit ON the gradient path but
    contribute no gradient ops of their own (the tiered cache install: the
    cache gradient is produced entirely by tiered_lookup_grad and applied by
    the optimizer to the post-install value)."""
    return []


@register_op("emb_cache_install", grad=_no_grad_ops_maker)
def emb_cache_install(ctx: ExecContext):
    """Land this batch's prefetched host rows in the device cache (tiered
    embeddings, ISSUE 10). Writes its output back to the SAME cache var name
    (the executor's rw/donation path — the PR 7 paged-KV pattern), and emits
    the PRE-install contents of the overwritten slots: those are exactly the
    evicted rows, carrying every optimizer update they ever received, which
    the engine writes back to the host tier when the step's output
    materializes. Padding entries point at the masked scratch slot."""
    cache, rows, slots = (ctx.input("Cache"), ctx.input("Rows"),
                          ctx.input("Slots"))
    slots = slots.astype(np.int32)
    evicted = jnp.take(cache, slots, axis=0)
    new_cache = cache.at[slots].set(rows.astype(cache.dtype))
    return {"Out": new_cache, "Evicted": evicted}


@register_op("tiered_lookup")
def tiered_lookup(ctx: ExecContext):
    """lookup_table over the hot-ID cache: ids were mapped to cache slots by
    the host-side resolver (embedding/engine.py), so the compiled step is one
    HBM gather. Slot `scratch_slot` (the cache's last row) marks padding /
    unresolvable positions and reads as zeros."""
    cache, slot_ids = ctx.input("Cache"), ctx.input("SlotIds")
    idsq = slot_ids.reshape(slot_ids.shape[:-1]) \
        if slot_ids.shape and slot_ids.shape[-1] == 1 else slot_ids
    idsq = idsq.astype(np.int32)
    out = jnp.take(cache, idsq, axis=0)
    scratch = int(ctx.attr("scratch_slot"))
    out = jnp.where((idsq == scratch)[..., None], jnp.zeros_like(out), out)
    return {"Out": out}


@register_grad_compute("tiered_lookup")
def tiered_lookup_grad(ctx: ExecContext):
    """Cache grad: dense scatter-add over the [slots+1, dim] cache — small by
    construction (the cache, not the table), so the optimizer's dense row
    update stays one fused XLA kernel. Scratch-slot positions (padding)
    contribute nothing, mirroring lookup_table's padding_idx contract."""
    cache, slot_ids, og = (ctx.input("Cache"), ctx.input("SlotIds"),
                           ctx.input("Out@GRAD"))
    if og is None:
        return {"Cache@GRAD": jnp.zeros_like(cache)}
    idsq = slot_ids.reshape(slot_ids.shape[:-1]) \
        if slot_ids.shape and slot_ids.shape[-1] == 1 else slot_ids
    rows = idsq.reshape(-1).astype(np.int32)
    width = og.shape[-1]
    vals = og.reshape(-1, width)
    scratch = int(ctx.attr("scratch_slot"))
    vals = jnp.where((rows == scratch)[:, None], jnp.zeros_like(vals), vals)
    dense = jnp.zeros_like(cache).at[rows].add(vals.astype(cache.dtype))
    return {"Cache@GRAD": dense}


@register_op("accuracy", grad="none")
def accuracy(ctx: ExecContext):
    idx, label = ctx.input("Indices"), ctx.input("Label")
    lbl = label.reshape(-1, 1)
    correct = jnp.any(idx == lbl, axis=1)
    num_correct = jnp.sum(correct.astype(np.int32))
    total = jnp.asarray(lbl.shape[0], np.int32)
    return {
        "Accuracy": (num_correct / total).astype(np.float32).reshape(1),
        "Correct": num_correct.reshape(1),
        "Total": total.reshape(1),
    }


@register_op("label_smooth")
def label_smooth(ctx: ExecContext):
    x = ctx.input("X")
    eps = ctx.attr("epsilon", 0.0)
    if ctx.has_input("PriorDist"):
        prior = ctx.input("PriorDist")
        return {"Out": (1 - eps) * x + eps * prior}
    return {"Out": (1 - eps) * x + eps / x.shape[-1]}


@register_op("prelu")
def prelu(ctx: ExecContext):
    x, alpha = ctx.input("X"), ctx.input("Alpha")
    mode = ctx.attr("mode", "all")
    if mode == "all":
        a = alpha.reshape(())
    elif mode == "channel":
        a = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    else:
        a = alpha.reshape((1,) + x.shape[1:])
    return {"Out": jnp.where(x >= 0, x, x * a)}


@register_op("softmax_mask_fuse_upper_triangle")
def softmax_mask_fuse_upper_triangle(ctx: ExecContext):
    """Causal-masked softmax — fused attention helper (TPU-first addition)."""
    x = ctx.input("X")
    q, k = x.shape[-2], x.shape[-1]
    mask = jnp.tril(jnp.ones((q, k), bool))
    neg = jnp.asarray(-1e9 if x.dtype != jnp.float16 else -6e4, x.dtype)
    return {"Out": jax.nn.softmax(jnp.where(mask, x, neg), axis=-1)}


@register_op("lookup_table_grad_rows", grad="none")
def lookup_table_grad_rows(ctx: ExecContext):
    """Gradient for a DISTRIBUTED lookup table (transpiler-rewritten from
    lookup_table_grad): builds the SelectedRows row-gradient from Ids +
    Out@GRAD alone — the table itself lives on the pservers and is not in
    the trainer scope (reference lookup_table rewrite,
    distribute_transpiler.py:1503)."""
    from ..core.selected_rows import SelectedRows

    ids, og = ctx.input("Ids"), ctx.input("Out@GRAD")
    height = int(ctx.attr("height"))
    idsq = ids.reshape(ids.shape[:-1]) if ids.shape and ids.shape[-1] == 1 else ids
    if og is None:
        # output's grad never materialized (grad-pruned consumer): an empty
        # row set, same degrade as lookup_table_grad's zeros
        return {"W@GRAD": SelectedRows(
            jnp.zeros((0,), jnp.int32), jnp.zeros((0, 1), jnp.float32),
            height=height)}
    width = og.shape[-1]
    rows = idsq.reshape(-1).astype(np.int32)
    vals = og.reshape(-1, width)
    padding_idx = ctx.attr("padding_idx", -1)
    if padding_idx is not None and padding_idx >= 0:
        vals = jnp.where((rows == padding_idx)[:, None],
                         jnp.zeros_like(vals), vals)
    return {"W@GRAD": SelectedRows(rows, vals, height=height)}


def data_norm(ctx: ExecContext):
    """CTR data normalization (reference data_norm_op.cc:193): channel stats
    come from ACCUMULATED batch counters, not this batch: means =
    BatchSum/BatchSize, scales = sqrt(BatchSize/BatchSquareSum); y =
    (x - means) * scales. The counters are trainable parameters whose
    "gradients" (see data_norm_grad below) are the batch's contribution."""
    x = ctx.input("X")
    bsize = ctx.input("BatchSize").astype(jnp.float32)
    bsum = ctx.input("BatchSum").astype(jnp.float32)
    bsq = ctx.input("BatchSquareSum").astype(jnp.float32)
    means = bsum / bsize
    scales = jnp.sqrt(bsize / bsq)
    y = (x.astype(jnp.float32) - means[None, :]) * scales[None, :]
    return {"Y": y.astype(x.dtype), "Means": means, "Scales": scales}


def _data_norm_grad_maker(op, block, no_grad_set=frozenset()):
    from ..framework import grad_var_name

    outs = {}
    for slot in ("X", "BatchSize", "BatchSum", "BatchSquareSum"):
        n = op.inputs[slot][0]
        if n not in no_grad_set:
            outs[slot + "@GRAD"] = [grad_var_name(n)]
    if not outs:
        return []
    return [{
        "type": "data_norm_grad",
        "inputs": {
            "X": list(op.inputs["X"]),
            "BatchSize": list(op.inputs["BatchSize"]),
            "BatchSum": list(op.inputs["BatchSum"]),
            "BatchSquareSum": list(op.inputs["BatchSquareSum"]),
            "Y@GRAD": [grad_var_name(op.outputs["Y"][0])],
        },
        "outputs": outs,
        "attrs": dict(op.attrs),
    }]


register_op("data_norm", grad=_data_norm_grad_maker)(data_norm)


@register_grad_compute("data_norm")
def data_norm_grad(ctx: ExecContext):
    """reference data_norm_op.cc:280 — dX = dY*scales; the counter 'grads'
    are the batch statistics themselves (count N, sum x, sum (x-mean)^2 +
    N*eps), which the optimizer's minus-lr step folds into the running
    accumulators (the reference trains them with a dedicated negative-lr
    stanza; parity keeps the same contract)."""
    x = ctx.input("X").astype(jnp.float32)
    bsize = ctx.input("BatchSize").astype(jnp.float32)
    bsum = ctx.input("BatchSum").astype(jnp.float32)
    bsq = ctx.input("BatchSquareSum").astype(jnp.float32)
    gy = ctx.input("Y@GRAD")
    eps = float(ctx.attr("epsilon", 1e-4))
    N = x.shape[0]
    means = bsum / bsize
    scales = jnp.sqrt(bsize / bsq)
    out = {}
    if "X@GRAD" in ctx.op.outputs:
        out["X@GRAD"] = (gy.astype(jnp.float32) *
                         scales[None, :]).astype(gy.dtype)
    if "BatchSize@GRAD" in ctx.op.outputs:
        out["BatchSize@GRAD"] = jnp.full_like(bsize, float(N))
    if "BatchSum@GRAD" in ctx.op.outputs:
        out["BatchSum@GRAD"] = x.sum(axis=0)
    if "BatchSquareSum@GRAD" in ctx.op.outputs:
        out["BatchSquareSum@GRAD"] = \
            ((x - means[None, :]) ** 2).sum(axis=0) + float(N) * eps
    return out


@register_op("spectral_norm", stateful_outputs=("UOut", "VOut"))
def spectral_norm(ctx: ExecContext):
    """reference spectral_norm_op.*: W / sigma_max(W) via power iteration.
    Weight reshaped to [h, w] around attr dim; U [h], V [w] persist across
    steps (UOut/VOut write back). Gradients flow to Weight only (u, v are
    stop-gradient auxiliaries, like the reference's)."""
    w = ctx.input("Weight")
    u = ctx.input("U").reshape(-1).astype(jnp.float32)
    v = ctx.input("V").reshape(-1).astype(jnp.float32)
    dim = int(ctx.attr("dim", 0))
    iters = int(ctx.attr("power_iters", 1))
    eps = float(ctx.attr("eps", 1e-12))
    perm = [dim] + [i for i in range(w.ndim) if i != dim]
    wm = jnp.transpose(w, perm).reshape(w.shape[dim], -1).astype(jnp.float32)

    def norm(a):
        return a / (jnp.linalg.norm(a) + eps)

    u = jax.lax.stop_gradient(u)
    v = jax.lax.stop_gradient(v)
    for _ in range(iters):
        v = norm(wm.T @ u)
        u = norm(wm @ v)
    u = jax.lax.stop_gradient(u)
    v = jax.lax.stop_gradient(v)
    sigma = u @ wm @ v
    out = (w.astype(jnp.float32) / sigma).astype(w.dtype)
    return {"Out": out, "UOut": u.astype(w.dtype), "VOut": v.astype(w.dtype)}


@register_op("tree_conv")
def tree_conv(ctx: ExecContext):
    """Tree-based convolution, TBCNN (reference tree_conv_op.* +
    math/tree2col.cc). NodesVector [B, N, F] (node i at row i-1 — edges are
    1-indexed, 0 marks padding), EdgeSet [B, E, 2] int (parent, child),
    Filter [F, 3, O, M] with the triplet order (eta_l, eta_r, eta_t).
    The reference's stack-walk patch construction becomes dense [N+1, N+1]
    eta matrices: reachability powers give rel-depth, per-edge child
    index/pclen give the continuous weights — one einsum per component."""
    feat = ctx.input("NodesVector")
    edges = ctx.input("EdgeSet").astype(jnp.int32)
    filt = ctx.input("Filter").astype(jnp.float32)
    D = int(ctx.attr("max_depth", 2))
    B, N, F = feat.shape
    E = edges.shape[1]

    def one(fb, eb):
        u, v = eb[:, 0], eb[:, 1]
        valid = (u > 0) & (v > 0)
        uc = jnp.where(valid, u, 0)
        vc = jnp.where(valid, v, 0)
        A = jnp.zeros((N + 1, N + 1), jnp.float32).at[uc, vc].add(
            jnp.where(valid, 1.0, 0.0))
        A = A.at[0, 0].set(0.0)
        # rel[u, v] = path length u->v (tree: unique), sentinel D if >= D
        reach = jnp.eye(N + 1, dtype=jnp.float32)
        rel = jnp.where(jnp.eye(N + 1, dtype=bool), 0, D)
        for r in range(1, D):
            reach = reach @ A
            rel = jnp.where((reach > 0) & (rel == D), r, rel)
        in_patch = rel < D
        # per-node child index (1-based among siblings) and parent fanout
        same_parent = (u[:, None] == u[None, :]) & valid[None, :] & \
            valid[:, None]
        earlier = same_parent & (jnp.arange(E)[None, :] < jnp.arange(E)[:, None])
        idx_e = earlier.sum(axis=1).astype(jnp.float32) + 1.0   # per edge
        pclen_e = same_parent.sum(axis=1).astype(jnp.float32)
        node_index = jnp.ones((N + 1,), jnp.float32).at[vc].set(
            jnp.where(valid, idx_e, 1.0))
        node_pclen = jnp.ones((N + 1,), jnp.float32).at[vc].set(
            jnp.where(valid, pclen_e, 1.0))
        temp = jnp.where(node_pclen <= 1.0, 0.5,
                         (node_index - 1.0) / jnp.maximum(
                             node_pclen - 1.0, 1.0))
        eta_t = (D - rel.astype(jnp.float32)) / float(D)
        # the patch ROOT enters as TreeNode(root,1,1,0): index=pclen=1
        temp_uv = jnp.where(jnp.eye(N + 1, dtype=bool), 0.5, temp[None, :])
        eta_l = (1.0 - eta_t) * temp_uv
        eta_r = (1.0 - eta_t) * (1.0 - temp_uv)
        mask = in_patch.astype(jnp.float32)
        # node existence: referenced by any valid edge (or is node 1, the root)
        exists = jnp.zeros((N + 1,), bool).at[uc].set(valid).at[vc].set(
            valid).at[1].set(True).at[0].set(False)
        mask = mask * exists[None, :] * exists[:, None]
        fpad = jnp.concatenate(
            [jnp.zeros((1, F), jnp.float32), fb.astype(jnp.float32)], axis=0)
        patches = [ (eta_l * mask) @ fpad,      # [N+1, F] component l
                    (eta_r * mask) @ fpad,
                    (eta_t * mask) @ fpad ]
        patch = jnp.stack(patches, axis=-1)[1:]  # [N, F, 3]
        return jnp.einsum("nfc,fcom->nom", patch, filt)

    out = jax.vmap(one)(feat, edges)
    return {"Out": out.astype(feat.dtype)}
