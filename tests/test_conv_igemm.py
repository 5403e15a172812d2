"""Numeric-equivalence regression tests for the round-6 conv levers:

  * implicit-GEMM conv lowering (FLAGS_conv_implicit_gemm) vs direct conv —
    forward AND gradients (the trained-weight trajectory captures the vjp),
    NHWC and NCHW, strided + padded (incl. asymmetric 4-element) + dilated +
    1x1-as-matmul cases;
  * fused one-pass BN statistics (FLAGS_bn_fuse_stats -> conv2d_bn) vs the
    two-pass conv2d + batch_norm pair, including running-stat updates and
    the AMP bf16 path;
  * the per-shape cost-model auto gate and the fusion pass's bail-out rules.

Tolerances: 1e-5 for fp32 paths (pure reassociation noise), a bf16 band for
AMP (ISSUE 5 acceptance).
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags
from paddle_tpu import layers as L


@pytest.fixture(autouse=True)
def _restore_lever_flags():
    saved = {k: flags.get_flag(k)
             for k in ("conv_implicit_gemm", "bn_fuse_stats")}
    yield
    flags.set_flags(saved)


def _set(igemm="off", fuse=False):
    flags.set_flags({"conv_implicit_gemm": igemm, "bn_fuse_stats": fuse})


def _train_conv(fmt, k, stride, pad, dil=1, bn=False, act=None, steps=2,
                cin=3, cout=8, hw=12, batch=4, seed=7):
    """Build data->conv2d[->bn]->mean, train `steps` SGD steps; return the
    per-step losses, the updated conv weight, and the program."""
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(
        (batch, cin, hw, hw) if fmt == "NCHW" else (batch, hw, hw, cin)
    ).astype(np.float32)
    with pt.program_guard(main, startup), pt.unique_name.guard():
        shape = [cin, hw, hw] if fmt == "NCHW" else [hw, hw, cin]
        img = L.data(name="img", shape=shape, dtype="float32")
        y = L.conv2d(img, num_filters=cout, filter_size=k, stride=stride,
                     padding=pad, dilation=dil, bias_attr=False, name="c",
                     data_format=fmt)
        if bn:
            y = L.batch_norm(y, act=act, name="c.bn", data_layout=fmt)
        # square the activations so the loss's curvature exercises the
        # gradient beyond a constant cotangent
        loss = L.mean(L.square(y))
        pt.optimizer.SGD(0.05).minimize(loss)
    exe = pt.Executor()
    losses = []
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        for _ in range(steps):
            (lv,) = exe.run(main, feed={"img": x}, fetch_list=[loss])
            losses.append(float(np.asarray(lv)))
        w = np.asarray(pt.global_scope().find_var("c.w_0"))
        stats = {}
        if bn:
            for n in ("c.bn.mean", "c.bn.var"):
                v = pt.global_scope().find_var(n)
                if v is not None:
                    stats[n] = np.asarray(v).copy()
    return losses, w, stats, main


CASES = [
    ("NHWC", 3, 1, 1, 1),
    ("NCHW", 3, 1, 1, 1),
    ("NHWC", 3, 2, 1, 1),          # strided
    ("NCHW", 5, 2, 2, 1),          # bigger kernel, strided
    ("NHWC", 4, 1, [2, 1, 2, 1], 1),   # asymmetric 4-element padding
    ("NCHW", 4, 2, [2, 1, 2, 1], 1),
    ("NHWC", 3, 1, 2, 2),          # dilated
    ("NHWC", 1, 1, 0, 1),          # 1x1 as [B*H*W, C] matmul
    ("NCHW", 1, 2, 0, 1),          # strided 1x1
]


@pytest.mark.parametrize("fmt,k,stride,pad,dil", CASES)
def test_igemm_matches_direct_conv_fwd_and_grad(fmt, k, stride, pad, dil):
    _set(igemm="off")
    ref_losses, ref_w, _, _ = _train_conv(fmt, k, stride, pad, dil)
    _set(igemm="on")
    ig_losses, ig_w, _, _ = _train_conv(fmt, k, stride, pad, dil)
    # step-2 loss depends on step-1 gradients: this equality IS the vjp test
    np.testing.assert_allclose(ig_losses, ref_losses, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ig_w, ref_w, rtol=1e-5, atol=1e-6)


def test_igemm_grouped_conv_falls_back_to_direct():
    # groups != 1 is ineligible: forced-on must still produce direct-conv
    # numerics (the gate, not the lowering, owns the decision)
    _set(igemm="on")
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup), pt.unique_name.guard():
        img = L.data(name="img", shape=[4, 8, 8], dtype="float32")
        y = L.conv2d(img, num_filters=4, filter_size=3, padding=1, groups=2,
                     bias_attr=False)
        loss = L.mean(y)
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        (lv,) = exe.run(main, feed={"img": np.ones((2, 4, 8, 8), np.float32)},
                        fetch_list=[loss])
    assert np.isfinite(float(np.asarray(lv)))


def test_auto_cost_model_per_shape():
    from paddle_tpu.ops.nn_ops import _igemm_predict_win

    # RN50 s0 interior 3x3 (b128, 56^2, 64->64, bf16): the 9x patch tensor
    # through HBM costs ~4x the direct conv's MXU time — must NOT take igemm
    assert not _igemm_predict_win(128, 56, 56, 64, 64, 3, 3, 2)
    # the raw 7x7-s2 stem (3->64 @ 112^2 out): K=3 direct fill is ~2% of the
    # MXU lanes; folding to K=147 pays even at 9x traffic
    assert _igemm_predict_win(128, 112, 112, 3, 64, 7, 7, 4)
    # wide-channel stages fill the lanes already — no win to buy
    assert not _igemm_predict_win(128, 14, 14, 256, 256, 3, 3, 2)


def test_auto_gate_respects_mode_flag():
    import jax.numpy as jnp

    from paddle_tpu.ops.nn_ops import _igemm_take

    x = jnp.zeros((128, 112, 112, 3), jnp.float32)
    w = jnp.zeros((7, 7, 3, 64), jnp.float32)
    args = (x, w, (2, 2), [(3, 3), (3, 3)], (1, 1), 1, "NHWC")
    _set(igemm="auto")
    assert _igemm_take(*args)
    _set(igemm="off")
    assert not _igemm_take(*args)
    _set(igemm="on")
    assert _igemm_take(*args)
    # int dtypes never take the GEMM path
    _set(igemm="on")
    assert not _igemm_take(x.astype(jnp.int32), w.astype(jnp.int32), *args[2:])


# ---------------------------------------------------------------------------
# fused one-pass BN statistics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt,stride", [("NHWC", 1), ("NCHW", 1),
                                        ("NHWC", 2), ("NCHW", 2)])
def test_fused_bn_stats_matches_two_pass(fmt, stride):
    _set(fuse=False)
    ref_losses, ref_w, ref_stats, ref_p = _train_conv(
        fmt, 3, stride, 1, bn=True, act="relu", steps=3)
    _set(fuse=True)
    fu_losses, fu_w, fu_stats, fu_p = _train_conv(
        fmt, 3, stride, 1, bn=True, act="relu", steps=3)
    types = [op.type for op in fu_p.global_block.ops]
    assert "conv2d_bn" in types and "batch_norm" not in types
    assert "batch_norm" in [op.type for op in ref_p.global_block.ops]
    np.testing.assert_allclose(fu_losses, ref_losses, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(fu_w, ref_w, rtol=1e-5, atol=1e-6)
    # running statistics (the stateful MeanOut/VarianceOut writes) must
    # track the two-pass op exactly, and must have moved off their init
    assert ref_stats and fu_stats.keys() == ref_stats.keys()
    for n in ref_stats:
        np.testing.assert_allclose(fu_stats[n], ref_stats[n],
                                   rtol=1e-5, atol=1e-6)
    assert not np.allclose(fu_stats[[n for n in fu_stats
                                     if n.endswith(".mean")][0]], 0.0)


def test_fused_bn_with_igemm_accumulator():
    # both levers together: stats come from the fp32 GEMM accumulator
    _set(igemm="off", fuse=False)
    ref_losses, ref_w, _, _ = _train_conv("NHWC", 3, 1, 1, bn=True, steps=3)
    _set(igemm="on", fuse=True)
    both_losses, both_w, _, _ = _train_conv("NHWC", 3, 1, 1, bn=True, steps=3)
    np.testing.assert_allclose(both_losses, ref_losses, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(both_w, ref_w, rtol=1e-5, atol=1e-6)


def test_fuse_pass_bails_on_shared_or_biased_or_test_bn():
    from paddle_tpu.passes import fuse_conv_bn_stats

    # (a) conv output consumed twice -> no fusion
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup), pt.unique_name.guard():
        img = L.data(name="img", shape=[8, 8, 3], dtype="float32")
        y = L.conv2d(img, num_filters=4, filter_size=3, padding=1,
                     bias_attr=False, data_format="NHWC")
        z = L.batch_norm(y, data_layout="NHWC")
        out = L.elementwise_add(z, y)  # second consumer of the conv output
    assert fuse_conv_bn_stats(main) == 0
    # (b) conv with bias: elementwise_add owns the conv output, BN reads the
    # add's output -> pattern must not match
    main2, startup2 = pt.Program(), pt.Program()
    with pt.program_guard(main2, startup2), pt.unique_name.guard():
        img = L.data(name="img", shape=[8, 8, 3], dtype="float32")
        y = L.conv2d(img, num_filters=4, filter_size=3, padding=1,
                     data_format="NHWC")  # bias on
        z = L.batch_norm(y, data_layout="NHWC")
    assert fuse_conv_bn_stats(main2) == 0
    # (c) inference-mode BN has no statistics pass to fuse
    main3, startup3 = pt.Program(), pt.Program()
    with pt.program_guard(main3, startup3), pt.unique_name.guard():
        img = L.data(name="img", shape=[8, 8, 3], dtype="float32")
        y = L.conv2d(img, num_filters=4, filter_size=3, padding=1,
                     bias_attr=False, data_format="NHWC")
        z = L.batch_norm(y, is_test=True, data_layout="NHWC")
    assert fuse_conv_bn_stats(main3) == 0
    # (d) the eligible pattern DOES fuse
    main4, startup4 = pt.Program(), pt.Program()
    with pt.program_guard(main4, startup4), pt.unique_name.guard():
        img = L.data(name="img", shape=[8, 8, 3], dtype="float32")
        y = L.conv2d(img, num_filters=4, filter_size=3, padding=1,
                     bias_attr=False, data_format="NHWC")
        z = L.batch_norm(y, data_layout="NHWC")
    assert fuse_conv_bn_stats(main4) == 1
    types = [op.type for op in main4.global_block.ops]
    assert "conv2d_bn" in types
    assert "conv2d" not in types and "batch_norm" not in types


def test_fused_bn_under_amp_bf16_band():
    """AMP path: decorate() rewrites to bf16 first, the fusion pass runs at
    minimize underneath it — the fused arm must stay inside bf16 noise of
    the two-pass arm over a short trajectory."""

    def run(fuse):
        _set(fuse=fuse)
        main, startup = pt.Program(), pt.Program()
        main.random_seed = startup.random_seed = 11
        rng = np.random.default_rng(3)
        x = rng.standard_normal((4, 10, 10, 3)).astype(np.float32)
        with pt.program_guard(main, startup), pt.unique_name.guard():
            img = L.data(name="img", shape=[10, 10, 3], dtype="float32")
            y = L.conv2d(img, num_filters=8, filter_size=3, padding=1,
                         bias_attr=False, name="c", data_format="NHWC")
            y = L.batch_norm(y, act="relu", name="c.bn", data_layout="NHWC")
            loss = L.mean(L.square(y))
            opt = pt.contrib.mixed_precision.decorate(pt.optimizer.SGD(0.05))
            opt.minimize(loss)
        if fuse:
            assert "conv2d_bn" in [op.type for op in main.global_block.ops]
        exe = pt.Executor()
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            for _ in range(3):
                (lv,) = exe.run(main, feed={"img": x}, fetch_list=[loss])
        return float(np.asarray(lv))

    ref, fused = run(False), run(True)
    assert np.isfinite(ref) and np.isfinite(fused)
    # bf16 has ~3 decimal digits; a 3-step trajectory stays within ~1%
    assert abs(fused - ref) <= 2e-2 * max(abs(ref), 1e-3)


def test_resnet_cifar_end_to_end_levers_match():
    """Whole-model check: resnet_cifar10 trained 2 steps with both levers on
    matches the baseline step-for-step (the model wiring — shortcuts,
    stride-2 blocks, global pool — picked the fused ops up unchanged).

    Step 1 is the same forward on the same weights and is held tightly.
    Step 2 comes after one lr-0.05 Momentum update that takes the loss
    from 2.73 to 0.95 on a batch of 4 — a trajectory that amplifies fp32
    summation order: the im2col matmul sums each conv in another order
    than the direct lowering and lands 3.7e-4 away (jax 0.9.0, XLA:CPU),
    and merely reversing the batch rows of the BASELINE — pure
    reassociation — is 8e-4 away one step later. A wrong lowering or a
    mis-wired block moves the loss by tenths."""
    from paddle_tpu.models import resnet

    def run(igemm, fuse):
        _set(igemm=igemm, fuse=fuse)
        main, startup = pt.Program(), pt.Program()
        main.random_seed = startup.random_seed = 9
        rng = np.random.default_rng(5)
        img = rng.standard_normal((4, 3, 32, 32)).astype(np.float32)
        lbl = rng.integers(0, 10, (4, 1)).astype(np.int64)
        with pt.program_guard(main, startup), pt.unique_name.guard():
            loss, acc, _ = resnet.resnet_cifar10()
            pt.optimizer.Momentum(0.05, 0.9).minimize(loss)
        n_fused = sum(op.type == "conv2d_bn"
                      for op in main.global_block.ops)
        exe = pt.Executor()
        out = []
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            for _ in range(2):
                (lv,) = exe.run(main, feed={"img": img, "label": lbl},
                                fetch_list=[loss])
                out.append(float(np.asarray(lv)))
        return out, n_fused

    ref, n0 = run("off", False)
    lev, n1 = run("on", True)
    assert n0 == 0
    # every conv in the cifar net feeds a training BN directly -> all fuse
    assert n1 > 10
    np.testing.assert_allclose(lev[0], ref[0], rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(lev[1], ref[1], rtol=2e-3)
