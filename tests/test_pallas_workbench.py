"""Pallas kernel workbench (ISSUE 9): substrate, fused epilogue, short-seq
attention, tuner wiring, and the registry lint.

The kernels run through the Pallas interpreter on CPU (module INTERPRET
flags), pinned against the XLA references that define their numerics —
fp32 at rtol 1e-5, a bf16 arm at bf16-rounding tolerance, masked/ragged
rows, both layouts. The dispatch tests prove the r5 contract: kernels ship
off by default, a swept DB verdict turns them on per shape, and a verdict
the platform cannot honor degrades to the reference at dispatch instead of
erroring.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import flags as pt_flags
from paddle_tpu import layers as L
from paddle_tpu import tuning
from paddle_tpu.ops.pallas_kernels import epilogue as ep
from paddle_tpu.ops.pallas_kernels import short_attention as sa
from paddle_tpu.ops.pallas_kernels import workbench as wb

rng = np.random.default_rng(0)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(ep, "INTERPRET", True)
    monkeypatch.setattr(sa, "INTERPRET", True)
    yield


def _f32(*shape):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


# ---------------------------------------------------------------------------
# workbench substrate
# ---------------------------------------------------------------------------


def test_workbench_helpers():
    assert wb.sublanes(jnp.float32) == 8 and wb.sublanes(jnp.bfloat16) == 16
    assert wb.round_up(129, 128) == 256
    # pick_block: largest fitting divisor, sublane multiples preferred
    assert wb.pick_block(1024, 1024) == 1024  # 1024 rows * 1024 B fits 3 MB
    tr = wb.pick_block(4096, 4096)
    assert 4096 % tr == 0 and tr * 4096 <= wb.VMEM_BUDGET
    assert wb.pick_block(7, 10) == 7              # whole extent fits
    assert wb.pick_block(7, wb.VMEM_BUDGET) == 1  # prime, over budget
    gh = wb.fit_heads(12, wb.VMEM_BUDGET // 3)
    assert 12 % gh == 0


def test_kernel_registry_lint():
    """The tier-1 spelling of `tools/gate.py --kernels`: every registered
    kernel carries an XLA reference, a shape gate, a wired tuning decision
    op, and an equivalence test that exists."""
    import tools.gate as gate

    assert gate.check_kernel_registry() == 0


# ---------------------------------------------------------------------------
# fused epilogue kernels
# ---------------------------------------------------------------------------


def test_bn_apply_act_matches_reference(interpret):
    """fp32 rtol 1e-5 equivalence vs the XLA reference: both layouts, with
    and without residual, identity and relu."""
    C = 16
    s, b, m = _f32(C), _f32(C), _f32(C)
    v = jnp.asarray((np.abs(rng.standard_normal(C)) + 0.5)
                    .astype(np.float32))
    for channel_last, shape in ((True, (6, 4, 4, C)), (False, (4, C, 3, 5))):
        x = _f32(*shape)
        res = _f32(*shape)
        for act in ("identity", "relu"):
            for r in (None, res):
                got = ep.bn_apply_act(x, s, b, m, v, act=act, residual=r,
                                      channel_last=channel_last)
                ref = ep.bn_apply_act_reference(
                    x, s, b, m, v, act=act, residual=r,
                    channel_last=channel_last)
                np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_bn_apply_act_bf16_arm(interpret):
    """The AMP arm: bf16 operands, fp32 kernel math, bf16-rounding
    tolerance vs the reference (which follows the same cast discipline)."""
    C = 16
    x = _f32(4, 8, C).astype(jnp.bfloat16)
    res = _f32(4, 8, C).astype(jnp.bfloat16)
    s, b, m = _f32(C), _f32(C), _f32(C)
    v = jnp.asarray((np.abs(rng.standard_normal(C)) + 0.5)
                    .astype(np.float32))
    got = ep.bn_apply_act(x, s, b, m, v, act="relu", residual=res)
    ref = ep.bn_apply_act_reference(x, s, b, m, v, act="relu", residual=res)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_bn_apply_act_grads_match(interpret):
    """The fused Pallas backward (dx + two partial-sum planes) matches the
    XLA reference's derived grads for every differentiable input."""
    C = 8
    x, res = _f32(3, C, 4, 4), _f32(3, C, 4, 4)
    s, b, m = _f32(C), _f32(C), _f32(C)
    v = jnp.asarray((np.abs(rng.standard_normal(C)) + 0.5)
                    .astype(np.float32))

    def loss(fn):
        def f(x, s, b, m, v, r):
            return jnp.sum(jnp.square(fn(x, s, b, m, v, act="relu",
                                         residual=r, channel_last=False)))
        return jax.grad(f, argnums=(0, 1, 2, 3, 4, 5))(x, s, b, m, v, res)

    for gk, gr, name in zip(loss(ep.bn_apply_act),
                            loss(ep.bn_apply_act_reference),
                            "x scale bias mean inv residual".split()):
        np.testing.assert_allclose(gk, gr, rtol=1e-4, atol=1e-4,
                                   err_msg=name)


def test_layer_norm_act_matches_reference(interpret):
    x2 = _f32(24, 64)
    s, b = _f32(64), _f32(64)
    for act in ("identity", "relu"):
        got = ep.layer_norm_act(x2, s, b, eps=1e-5, act=act)
        ref = ep.layer_norm_act_reference(x2, s, b, eps=1e-5, act=act)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # no-affine form (scale/bias default 1/0)
    got = ep.layer_norm_act(x2)
    ref = ep.layer_norm_act_reference(x2, None, None)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_layer_norm_act_grads_match(interpret):
    x2, s, b = _f32(16, 32), _f32(32), _f32(32)

    def g(fn):
        return jax.grad(lambda x, s, b: jnp.sum(jnp.square(
            fn(x, s, b))), argnums=(0, 1, 2))(x2, s, b)

    gk = g(lambda x, s, b: ep.layer_norm_act(x, s, b, act="relu"))
    gr = g(lambda x, s, b: ep.layer_norm_act_reference(x, s, b, act="relu"))
    np.testing.assert_allclose(gk[0], gr[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gk[1]).reshape(-1), gr[1],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gk[2]).reshape(-1), gr[2],
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# short-sequence (<=128) attention kernel
# ---------------------------------------------------------------------------


def test_short128_attention_matches_reference(interpret):
    """fp32 rtol 1e-5 vs the XLA reference at S = 128, 96 (non-lane-
    multiple) and 17, causal and not."""
    for S in (128, 96, 17):
        for causal in (False, True):
            q, k, v = (_f32(3, 4, S, 16) for _ in range(3))
            got = sa.short128_attention(q, k, v, causal=causal,
                                        sm_scale=0.25)
            ref = sa._reference(q, k, v, causal=causal, sm_scale=0.25)
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_short128_attention_ragged_rows(interpret):
    """kv_lens masking: partial rows match the masked reference, a fully
    masked row (len 0 — scheduler padding) emits zeros, not NaN."""
    q, k, v = (_f32(4, 2, 64, 16) for _ in range(3))
    lens = jnp.asarray(np.array([64, 13, 1, 0], np.int32))
    got = sa.short128_attention(q, k, v, sm_scale=0.25, kv_lens=lens)
    ref = sa._reference(q, k, v, sm_scale=0.25, kv_lens=lens)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    assert np.all(np.asarray(got)[3] == 0.0)


def test_short128_attention_grads_match(interpret):
    q, k, v = (_f32(2, 2, 48, 16) for _ in range(3))
    lens = jnp.asarray(np.array([48, 20], np.int32))

    def g(fn):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.square(
            fn(q, k, v))), argnums=(0, 1, 2))(q, k, v)

    gk = g(lambda q, k, v: sa.short128_attention(
        q, k, v, causal=True, sm_scale=0.25, kv_lens=lens))
    gr = g(lambda q, k, v: sa._reference(
        q, k, v, causal=True, sm_scale=0.25, kv_lens=lens))
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_short128_attention_bf16_arm(interpret):
    q, k, v = (_f32(2, 2, 32, 16).astype(jnp.bfloat16) for _ in range(3))
    got = sa.short128_attention(q, k, v, sm_scale=0.25)
    ref = sa._reference(q, k, v, sm_scale=0.25)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_short128_supported_gate():
    ok = sa.short128_supported
    assert ok((2, 4, 128, 64), (2, 4, 128, 64))
    assert ok((2, 4, 17, 8), (2, 4, 17, 8))
    assert not ok((2, 4, 129, 64), (2, 4, 129, 64))   # past the VMEM row
    assert not ok((2, 4, 64, 64), (2, 4, 128, 64))    # cross-attention
    assert not ok((2, 4, 64, 12), (2, 4, 64, 12))     # dh not sublane-mult
    assert not ok((2, 4, 64, 64), (2, 4, 64, 64), bias=object())


# ---------------------------------------------------------------------------
# tuner wiring: default-off, swept keep, dispatch-time degradation
# ---------------------------------------------------------------------------


def _seed_db(tmp_path, key, decision):
    db = tuning.TuningDB(str(tmp_path / "db.json"))
    db.put(key, decision, source="swept", note="test")
    path = db.save()
    pt_flags.set_flags({"tuning_mode": "consult", "tuning_db": path})
    tuning.invalidate_db_cache()
    return path


@pytest.fixture
def tuner_cleanup():
    saved = {k: pt_flags.get_flag(k) for k in
             ("tuning_mode", "tuning_db", "pallas_epilogue",
              "attention_force_backend")}
    yield
    pt_flags.set_flags(saved)
    tuning.invalidate_db_cache()


def test_attention_swept_keep_engages_short128(tmp_path, interpret,
                                               tuner_cleanup):
    """A swept pallas_short128 keep routes flash_attention through the
    kernel for exactly that shape; the numbers match the XLA composition."""
    from paddle_tpu.ops.attention_ops import (_reference_attention,
                                              attention_backend,
                                              flash_attention)

    q, k, v = (_f32(2, 2, 48, 16) for _ in range(3))
    key = tuning.canonical_key(
        "attention", tuning.attention_key(2, 2, 48, 48, 16, False),
        "float32", tuning.device_kind())
    _seed_db(tmp_path, key, {"backend": "pallas_short128"})
    backend, tier = attention_backend(q.shape, k.shape, q.dtype)
    assert (backend, tier) == ("pallas_short128", "db")
    got = flash_attention(q, k, v, sm_scale=0.25)
    ref = _reference_attention(q, k, v, None, False, 0.25)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_swept_unrunnable_kernel_degrades_at_dispatch(tmp_path, monkeypatch,
                                                     tuner_cleanup):
    """The ISSUE 9 degradation clause: a swept verdict naming a kernel this
    platform cannot run (INTERPRET off, no TPU) is not obeyed blindly —
    dispatch falls back to the XLA reference without error."""
    from paddle_tpu.ops.attention_ops import (_reference_attention,
                                              attention_backend,
                                              flash_attention)

    monkeypatch.setattr(sa, "INTERPRET", False)
    q, k, v = (_f32(2, 2, 48, 16) for _ in range(3))
    key = tuning.canonical_key(
        "attention", tuning.attention_key(2, 2, 48, 48, 16, False),
        "float32", tuning.device_kind())
    _seed_db(tmp_path, key, {"backend": "pallas_short128"})
    backend, _tier = attention_backend(q.shape, k.shape, q.dtype)
    assert backend == "pallas_short128"  # the DB entry IS consulted...
    got = flash_attention(q, k, v, sm_scale=0.25)  # ...but degrades here
    ref = _reference_attention(q, k, v, None, False, 0.25)
    np.testing.assert_allclose(got, ref, rtol=0, atol=0)


def test_epilogue_swept_unrunnable_degrades(tmp_path, monkeypatch,
                                            tuner_cleanup):
    """Same clause for the epilogue lever: a swept pallas keep for a shape
    the platform cannot run falls back to the XLA composition inside the
    batch_norm lowering — bit-identical output, no error."""
    from paddle_tpu.ops.nn_ops import _bn_epilogue

    monkeypatch.setattr(ep, "INTERPRET", False)
    C = 8
    x = _f32(4, 6, C)
    s, b, m = _f32(C), _f32(C), _f32(C)
    v = jnp.asarray((np.abs(rng.standard_normal(C)) + 0.5)
                    .astype(np.float32))
    key = tuning.canonical_key(
        "epilogue", tuning.epilogue_key("bn", 24, C, "last", "relu", False),
        "float32", tuning.device_kind())
    _seed_db(tmp_path, key, {"backend": "pallas"})
    pt_flags.set_flags({"pallas_epilogue": "auto"})
    got = _bn_epilogue(x, s, b, m, v, "relu", None, channel_last=True,
                       bshape=[1, 1, C])
    ref = ep.bn_apply_act_reference(x, s, b, m, v, act="relu")
    # last-bit association difference only ((x-m)*inv*s vs (x-m)*(inv*s))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_epilogue_swept_keep_engages(tmp_path, interpret, tuner_cleanup):
    """A swept pallas keep routes the batch_norm epilogue through the
    kernel (r5 contract: the DB, not a flag, turns kernels on)."""
    from paddle_tpu.ops.nn_ops import _bn_epilogue

    C = 8
    x = _f32(4, 6, C)
    s, b, m = _f32(C), _f32(C), _f32(C)
    v = jnp.asarray((np.abs(rng.standard_normal(C)) + 0.5)
                    .astype(np.float32))
    key = tuning.canonical_key(
        "epilogue", tuning.epilogue_key("bn", 24, C, "last", "relu", False),
        "float32", tuning.device_kind())
    _seed_db(tmp_path, key, {"backend": "pallas"})
    pt_flags.set_flags({"pallas_epilogue": "auto"})
    got = _bn_epilogue(x, s, b, m, v, "relu", None, channel_last=True,
                       bshape=[1, 1, C])
    ref = ep.bn_apply_act_reference(x, s, b, m, v, act="relu")
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_epilogue_candidate_recorded_in_sweep_mode(tmp_path, tuner_cleanup):
    """FLAGS_tuning_mode=sweep records the epilogue decision surface as
    candidate keys for tools/tune.py --what candidates to upgrade."""
    from paddle_tpu.ops.nn_ops import _epilogue_backend

    path = str(tmp_path / "db.json")
    pt_flags.set_flags({"tuning_mode": "sweep", "tuning_db": path,
                        "pallas_epilogue": "auto"})
    tuning.invalidate_db_cache()
    assert _epilogue_backend("bn", 96, 8, "last", "relu", True,
                             jnp.float32) == "xla"
    tuning.invalidate_db_cache()
    db = tuning.TuningDB(path)
    keys = [k for k in db.entries if k.startswith("epilogue|")]
    assert keys and db.entries[keys[0]]["source"] == "candidate"
    import re

    from tools.tune import _EPI_KEY_RE

    assert _EPI_KEY_RE.match(keys[0]), keys[0]


# ---------------------------------------------------------------------------
# minimize()-time epilogue fusion pass
# ---------------------------------------------------------------------------


def _bn_relu_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup), pt.unique_name.guard():
        img = L.data(name="img", shape=[8, 6, 6], dtype="float32")
        y = L.conv2d(img, num_filters=8, filter_size=3, padding=1,
                     bias_attr=False, name="c1")
        y = L.batch_norm(y, act="relu", name="bn1")
        s = L.conv2d(img, num_filters=8, filter_size=1, bias_attr=False,
                     name="sc")
        s = L.batch_norm(s, name="bnsc")
        out = L.relu(L.elementwise_add(y, s))
        loss = L.reduce_mean(out)
        pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    return main, startup, loss


def test_epilogue_pass_fuses_chains(tuner_cleanup):
    """FLAGS_pallas_epilogue=on: bn->relu folds to an act attr, the
    bn->add->relu residual block folds the add and relu into the norm op
    (attr act + input Residual), and no standalone relu survives."""
    pt_flags.set_flags({"pallas_epilogue": "on"})
    main, _, _ = _bn_relu_program()
    types = [op.type for op in main.global_block.ops]
    assert "relu" not in types and "elementwise_add" not in types
    fused = [op for op in main.global_block.ops
             if op.type in ("batch_norm", "conv2d_bn")]
    assert sorted(op.attr("act", "") for op in fused) == ["relu", "relu"]
    assert sum(1 for op in fused if op.input("Residual")) == 1


def test_epilogue_pass_training_equivalence(tuner_cleanup):
    """The fused program trains bit-identically to the unfused one on the
    XLA backend (the rewrite must be a pure structure change)."""
    exe = pt.Executor()
    x = rng.standard_normal((4, 8, 6, 6)).astype(np.float32)
    losses, params = {}, None
    for arm, flag in (("off", "off"), ("fused", "on")):
        pt_flags.set_flags({"pallas_epilogue": flag})
        main, startup, loss = _bn_relu_program()
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            if params is None:
                params = [np.array(pt.global_scope().find_var(p.name))
                          for p in main.all_parameters()]
            else:
                for p, val in zip(main.all_parameters(), params):
                    pt.global_scope().set_var(p.name, val)
            losses[arm] = [float(np.asarray(exe.run(
                main, feed={"img": x}, fetch_list=[loss])[0]))
                for _ in range(3)]
    np.testing.assert_allclose(losses["off"], losses["fused"],
                               rtol=1e-6, atol=1e-6)


def test_epilogue_pass_off_leaves_program_alone(tuner_cleanup):
    """Default tier-1 state (tuning off, flag auto): zero structural
    change — the rewrite only runs when a DB could ever keep the kernel."""
    pt_flags.set_flags({"pallas_epilogue": "auto", "tuning_mode": "off"})
    main, _, _ = _bn_relu_program()
    types = [op.type for op in main.global_block.ops]
    assert "relu" in types and "elementwise_add" in types


def test_epilogue_pass_respects_multi_reader(tuner_cleanup):
    """A norm output with a second reader must NOT fuse (the var would
    vanish while still being read)."""
    pt_flags.set_flags({"pallas_epilogue": "on"})
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup), pt.unique_name.guard():
        img = L.data(name="img", shape=[4, 6, 6], dtype="float32")
        y = L.batch_norm(img, name="bn")
        a = L.relu(y)
        loss = L.reduce_mean(a) + L.reduce_mean(y)  # second reader of y
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    types = [op.type for op in main.global_block.ops]
    assert "relu" in types  # fusion declined


def test_layer_norm_act_fuses_and_dispatches(interpret, tuner_cleanup):
    """layer_norm -> relu folds to the act attr and, with a swept keep for
    the exact row shape, lowers through the LN kernel with matching
    numerics end to end."""
    x = rng.standard_normal((6, 32)).astype(np.float32)

    def build():
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup), pt.unique_name.guard():
            d = L.data(name="x", shape=[32], dtype="float32")
            y = L.layer_norm(d, act="relu", name="ln")
            loss = L.reduce_mean(y)
            pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return main, startup, loss

    exe = pt.Executor()
    out = {}
    for arm in ("off", "on"):
        pt_flags.set_flags({"pallas_epilogue": arm, "tuning_mode": "off"})
        main, startup, loss = build()
        if arm == "on":
            assert "relu" not in [op.type for op in main.global_block.ops]
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            for p in main.all_parameters():
                base = np.ones(p.shape, np.float32) * (
                    0.5 if "scale" in p.name or "_w" in p.name else 0.1)
                pt.global_scope().set_var(p.name, base)
            (out[arm],) = exe.run(main, feed={"x": x}, fetch_list=[loss])
    np.testing.assert_allclose(float(out["off"]), float(out["on"]),
                               rtol=1e-5, atol=1e-6)
