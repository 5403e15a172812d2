"""Multichip collective-overlap tests (ISSUE 8) on the virtual 8-device mesh.

The exactness contracts behind the measured scaling campaign
(tools/_mc_ab.py): bucketed allreduce is BITWISE
payload-layout-invariant, ZeRO-1 sharding lands on the single-device
parameter trajectory, the 1F1B schedule's bubble accounting is explicit and
its numerics equal fill-drain's, and the PR 3 watchdog surfaces a hung
allreduce with step ids and queue depths.
"""
import warnings

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.collective import (GradAllReduce, build_buckets,
                                            resolve_bucket_mb)

N_DEV = 8


def _build_mlp(opt=None, sizes=(8, 8)):
    x = L.data(name="x", shape=[16], dtype="float32")
    y = L.data(name="y", shape=[1], dtype="float32")
    h = x
    for s in sizes:
        h = L.fc(h, size=s, act="relu")
    pred = L.fc(h, size=1)
    loss = L.mean(L.square_error_cost(pred, y))
    (opt or pt.optimizer.Momentum(0.05, 0.9)).minimize(loss)
    return loss


def _batch(seed=0, bs=32):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((bs, 16)).astype(np.float32)
    w = rng.standard_normal((16, 1)).astype(np.float32)
    return x, (x @ w).astype(np.float32)


def _train(transpile=None, target_of=None, steps=5, opt=None, fetch=True):
    """Build+train in fresh program/scope; return (loss history, params)."""
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            loss = _build_mlp(opt() if opt else None)
    if transpile is not None:
        transpile(main, startup)
    scope = pt.Scope()
    exe = pt.Executor()
    x, y = _batch()
    with pt.scope_guard(scope):
        exe.run(startup)
        target = target_of(main) if target_of else main
        hist = []
        for _ in range(steps):
            (lv,) = exe.run(target, feed={"x": x, "y": y},
                            fetch_list=[loss.name])
            hist.append(float(np.asarray(lv).reshape(-1)[0]))
        params = {p.name: np.asarray(scope.find_var(p.name))
                  for p in main.all_parameters()}
    return hist, params, main


def _collective(main):
    return pt.CompiledProgram(main).with_collective(
        mesh=make_mesh({"dp": N_DEV}))


def _transpiler(bucket_mb=None, zero1=None):
    t = GradAllReduce(bucket_mb=bucket_mb, zero1=zero1)

    def run(main, startup):
        t.transpile(startup, main, rank=0, nranks=N_DEV)

    return t, run


# -- bucketed allreduce exactness -------------------------------------------

def test_bucketed_allreduce_bitwise_loss_parity():
    """Per-grad vs one-big-bucket vs a boundary that SPLITS one layer's
    (w, b) pair: identical bitwise loss trajectories (psum per element is
    the same sum regardless of payload grouping), and all land on the
    single-device parameter trajectory (mean-allreduce oracle)."""
    single_h, single_p, _ = _train()

    arms = {}
    for name, mb in (("pergrad", 0.0), ("bucketed", 4.0),
                     ("split", 0.0001)):
        t, tr = _transpiler(bucket_mb=mb)
        arms[name] = _train(tr, _collective)
        if name == "split":
            # the tiny bucket really did split a layer: some consecutive
            # bucket pair separates one fc layer's w from its b
            assert len(t.last_buckets) > 1, t.last_buckets
            stems = [{g.split(".")[0] for g in names}
                     for _, names in t.last_buckets]
            assert any(a & b for a, b in zip(stems, stems[1:])), \
                t.last_buckets

    assert arms["pergrad"][0] == arms["bucketed"][0] == arms["split"][0], \
        {k: v[0] for k, v in arms.items()}
    for name, ref in single_p.items():
        for _, params, _ in arms.values():
            np.testing.assert_allclose(ref, params[name], rtol=1e-4,
                                       atol=1e-5)


def test_bucket_overlap_placement_below_guardrails():
    """Buckets sit at grad-READINESS points: interleaved with the backward
    ops rather than parked at the optimizer boundary — and under
    FLAGS_guard_numerics strictly below the health sentinel (a reduce above
    it would ship pre-gated gradients)."""
    from paddle_tpu import flags as pt_flags

    t, tr = _transpiler(bucket_mb=0.00005)  # ~50B buckets: one per grad
    _, _, main = _train(tr, _collective, steps=1)
    block = main.global_block
    first_opt = min(i for i, op in enumerate(block.ops)
                    if op.type == "momentum")
    positions = [p for p, _ in t.last_buckets]
    assert len(positions) > 1
    # at least one bucket reduce runs BEFORE the last backward grad op —
    # the overlap regime (per-grad baseline parks all of them at first_opt)
    last_grad = max(i for i, op in enumerate(block.ops)
                    if op.type.endswith("_grad"))
    assert min(positions) <= last_grad < first_opt, \
        (positions, last_grad, first_opt)

    saved = pt_flags.get_flag("guard_numerics")
    pt_flags.set_flags({"guard_numerics": True})
    try:
        t2, tr2 = _transpiler(bucket_mb=4.0)
        _, _, main2 = _train(tr2, _collective, steps=1)
        block2 = main2.global_block
        sentinel = [i for i, op in enumerate(block2.ops)
                    if op.type == "health_sentinel"]
        assert sentinel, [op.type for op in block2.ops]
        assert all(p > sentinel[-1] for p, _ in t2.last_buckets), \
            (t2.last_buckets, sentinel)
    finally:
        pt_flags.set_flags({"guard_numerics": saved})


def test_bucketed_allreduce_bitwise_under_amp():
    """'Below AMP': with the mixed-precision decorator the readiness points
    sit after the unscale/check ops (the last grad writers), and bucketed
    still equals per-grad BITWISE — the reduce ships post-unscale fp32
    master grads either way."""
    def amp_opt():
        return pt.contrib.mixed_precision.decorate(
            pt.optimizer.Momentum(0.05, 0.9))

    arms = {}
    for name, mb in (("pergrad", 0.0), ("bucketed", 4.0)):
        _, tr = _transpiler(bucket_mb=mb)
        arms[name] = _train(tr, _collective, steps=4, opt=amp_opt)
    assert arms["pergrad"][0] == arms["bucketed"][0]
    for n, ref in arms["pergrad"][1].items():
        assert np.array_equal(ref, arms["bucketed"][1][n]), n


def test_build_buckets_cuts_and_order():
    items = [(3, "g_late", 100), (1, "g_mid", 100), (0, "g_early", 250)]
    buckets = build_buckets(items, 300)
    assert [[n for _, n, _ in b] for b in buckets] == \
        [["g_early"], ["g_mid", "g_late"]]
    assert [[n for _, n, _ in b] for b in build_buckets(items, 0)] == \
        [["g_early"], ["g_mid"], ["g_late"]]


def test_bucket_size_resolved_through_tuning_db(tmp_path):
    """The `collective|mesh=..|payload=..` tuner wiring: a swept DB verdict
    overrides FLAGS_allreduce_bucket_mb in consult mode; off mode keeps the
    flag; and the transpiler records provenance either way."""
    from paddle_tpu import flags as pt_flags
    from paddle_tpu import tuning

    # discover this model's quantized payload key from a throwaway transpile
    probe, tr = _transpiler()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            _build_mlp()
    tr(main, startup)
    assert probe.bucket_source == "flag"  # tuning off by default
    assert probe.resolved_bucket_mb == float(
        pt_flags.get_flag("allreduce_bucket_mb"))

    key = tuning.canonical_key(
        "collective",
        tuning.collective_key(f"dp{N_DEV}", probe.last_payload_bytes),
        "float32", tuning.device_kind())
    db_path = str(tmp_path / "tuning.json")
    db = tuning.TuningDB()
    db.put(key, {"bucket_mb": 0.0001}, source="swept", note="test sweep")
    db.save(db_path)

    saved = {k: pt_flags.get_flag(k) for k in ("tuning_mode", "tuning_db")}
    pt_flags.set_flags({"tuning_mode": "consult", "tuning_db": db_path})
    tuning.invalidate_db_cache()
    try:
        t2, tr2 = _transpiler()
        main2, startup2 = pt.Program(), pt.Program()
        with pt.program_guard(main2, startup2):
            with pt.unique_name.guard():
                _build_mlp()
        tr2(main2, startup2)
        assert t2.bucket_source == "db", (t2.bucket_source, key)
        assert t2.resolved_bucket_mb == 0.0001
        assert len(t2.last_buckets) > 1  # the swept size actually applied
    finally:
        pt_flags.set_flags(saved)
        tuning.invalidate_db_cache()


# -- ZeRO-1 ------------------------------------------------------------------

def test_zero1_structure_and_parity():
    """ZeRO-1 with Adam: reduce-scatter/shard/allgather ops present, the
    rewritten update consumes shard vars, moments shard with the param,
    the indivisible bias falls back to the allreduce path — and the
    parameter trajectory still equals single-device (loss parity)."""
    single_h, single_p, _ = _train(opt=lambda: pt.optimizer.Adam(1e-2))

    t, tr = _transpiler(zero1=True)
    _, params, main = _train(tr, _collective,
                             opt=lambda: pt.optimizer.Adam(1e-2))
    types = [op.type for op in main.global_block.ops]
    assert "c_reducescatter" in types
    assert "zero1_shard" in types
    assert "c_allgather" in types
    assert t.zero1_params, "no parameter took the ZeRO-1 path"
    # the final fc bias [1] cannot shard 8 ways -> classic allreduce
    assert "c_allreduce_sum" in types
    adam_ops = [op for op in main.global_block.ops if op.type == "adam"]
    sharded = [op for op in adam_ops
               if op.input("Param")[0].endswith("@ZERO1_SHARD")]
    assert sharded, [op.input("Param") for op in adam_ops]
    for op in sharded:
        assert op.input("Moment1")[0].endswith("@ZERO1_SHARD")
        assert op.input("Grad")[0].endswith("@ZERO1_GRAD")
        # scalar beta-pow state stays replicated
        assert not op.input("Beta1Pow")[0].endswith("@ZERO1_SHARD")
    for name, ref in single_p.items():
        np.testing.assert_allclose(ref, params[name], rtol=1e-4, atol=1e-5)


def test_zero1_gspmd_degrade_is_identity():
    """The same ZeRO-1-rewritten program run WITHOUT a bound axis (GSPMD/
    single device): every inserted collective lowers to identity and the
    step equals the untranspiled program bitwise."""
    plain_h, _, _ = _train(steps=3)
    _, tr = _transpiler(zero1=True)
    z_h, _, _ = _train(tr, None, steps=3)  # no mesh: axis env unbound
    assert plain_h == z_h, (plain_h, z_h)


# -- 1F1B bubble accounting --------------------------------------------------

def _pipeline_program(schedule, M=8):
    from paddle_tpu.parallel.pipeline import build_pipeline_plan

    main, startup = pt.Program(), pt.Program()
    main.random_seed = 11
    startup.random_seed = 11
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            x = L.data(name="x", shape=[16], dtype="float32")
            y = L.data(name="y", shape=[1], dtype="float32")
            h1 = L.fc(x, size=16, act="relu")
            h2 = L.fc(h1, size=16, act="relu")
            pred = L.fc(h2, size=1)
            loss = L.mean(L.square_error_cost(pred, y))
            main._pipeline = build_pipeline_plan(
                main, loss, [h1, h2], pt.optimizer.SGD(0.05), M, startup,
                schedule=schedule)
    return main, startup, loss


def test_1f1b_bubble_accounting_and_loss_equivalence():
    """Explicit bubble accounting: both schedules report the analytic
    (S-1)/(M+S-1), GPipe's observed stalls are exactly the fill/drain
    2*(S-1) slots per stage, 1F1B's steady state stalls no more than GPipe
    and bounds the stash — while producing the IDENTICAL loss (fill-drain
    equivalence, the satellite oracle)."""
    from paddle_tpu.parallel.pipeline import bubble_fraction

    M, S = 8, 3
    x, y = _batch(bs=32)
    out = {}
    for schedule in ("gpipe", "1f1b"):
        main, startup, loss = _pipeline_program(schedule, M)
        scope = pt.Scope()
        exe = pt.Executor()
        with pt.scope_guard(scope):
            exe.run(startup)
            (lv,) = exe.run(main, feed={"x": x, "y": y},
                            fetch_list=[loss.name])
        plan = main._pipeline
        b = plan.last_bubble
        assert b["schedule"] == schedule
        assert b["analytic_frac"] == round(bubble_fraction(S, M), 4)
        assert b["num_microbatches"] == M and b["n_stages"] == S
        out[schedule] = (float(np.asarray(lv)), b, plan.last_peak_stash)
    g_loss, g_b, g_peak = out["gpipe"]
    f_loss, f_b, f_peak = out["1f1b"]
    assert g_loss == f_loss, (g_loss, f_loss)
    # gpipe: every stage idles exactly 2*(S-1) fill/drain slots
    assert g_b["stall_rounds_per_stage"] == [2 * (S - 1)] * S, g_b
    assert g_b["observed_frac"] == round(bubble_fraction(S, M), 4)
    # 1f1b: dependency stalls exist but the stash is the win
    assert sum(f_b["stall_rounds_per_stage"]) > 0
    assert f_peak <= S + 1 < M <= g_peak, (f_peak, g_peak)


def test_pipeline_schedule_flag_default():
    from paddle_tpu import flags as pt_flags

    saved = pt_flags.get_flag("pipeline_schedule")
    pt_flags.set_flags({"pipeline_schedule": "gpipe"})
    try:
        main, _, _ = _pipeline_program(schedule=None)
        assert main._pipeline.schedule == "gpipe"
    finally:
        pt_flags.set_flags({"pipeline_schedule": saved})
    main2, _, _ = _pipeline_program(schedule=None)
    assert main2._pipeline.schedule == "1f1b"


def test_pipeline_int64_feed_no_truncation_warning():
    """MULTICHIP dryrun-tail hygiene (ISSUE 8 satellite): an int64 host feed
    through the pipeline microbatch splitter is narrowed on the HOST
    (np_feed_dtype), so jax never sees an int64 astype request."""
    main, startup, loss = _pipeline_program("1f1b", M=4)
    x, y = _batch(bs=16)
    scope = pt.Scope()
    exe = pt.Executor()
    with pt.scope_guard(scope):
        exe.run(startup)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            exe.run(main, feed={"x": x.astype(np.float64),
                                "y": y.astype(np.float64)},
                    fetch_list=[loss.name])
    bad = [w for w in caught if "truncated" in str(w.message)]
    assert not bad, [str(w.message) for w in bad]


# -- collective_stall watchdog ----------------------------------------------

@pytest.mark.chaos
def test_collective_stall_surfaces_hung_allreduce():
    """The PR 3 watchdog must turn a hung allreduce into a StallError
    carrying step ids and queue depths — driven by the `collective_stall`
    fault site, which fires only for steps dispatched under the
    shard_map/with_collective regime."""
    from paddle_tpu import flags as pt_flags
    from paddle_tpu.resilience.faults import fault_scope
    from paddle_tpu.resilience.watchdog import StallError

    _, tr = _transpiler(bucket_mb=4.0)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            _build_mlp()
    tr(main, startup)
    x, y = _batch()
    scope = pt.Scope()
    exe = pt.Executor()
    saved = pt_flags.get_flag("watchdog_stall_s")
    pt_flags.set_flags({"watchdog_stall_s": 0.25})
    try:
        with pt.scope_guard(scope):
            exe.run(startup)
            compiled = _collective(main)
            exe.run(compiled, feed={"x": x, "y": y})  # warm compile
            with fault_scope("collective_stall:1") as plan:
                # a plain (gspmd) async step must NOT trip the site
                exe.run_async(main, feed={"x": x, "y": y}, scope=scope)
                exe.wait()
                assert plan.stats()["hits"].get("collective_stall", 0) == 0
                exe.run_async(compiled, feed={"x": x, "y": y}, scope=scope)
                with pytest.raises(StallError) as ei:
                    exe.wait()
            err = ei.value
            assert "collective allreduce" in str(err)
            assert err.state["inflight_step_ids"], err.state
            assert err.state["inflight_depth"] >= 1
            assert err.state["spmd_mode"] == "shard_map"
            exe.drain_quiet()
    finally:
        pt_flags.set_flags({"watchdog_stall_s": saved})


# -- tools/_mc_ab.py -----------------------------------------------------------

def test_mc_ab_record_verdict_roundtrip(tmp_path):
    """A sweep winner beating the per-grad baseline beyond the band lands
    in the tuning DB as a swept `collective|...` verdict the transpiler's
    consult path can resolve (and a tie would be rejected — the
    _timing.ab_verdict contract, exercised by the CLI run)."""
    from paddle_tpu import tuning
    from tools import _mc_ab

    class _T:
        last_payload_bytes = 2 << 20

    rows = {"4.0": {"tok_s": 100.0, "median_s": 0.8, "band": 0.01}}
    off = {"median_s": 1.0, "band": 0.01}
    db_path = str(tmp_path / "db.json")
    _mc_ab._record_verdict(db_path, 8, _T(), rows, 4.0, off)
    key = tuning.canonical_key(
        "collective", tuning.collective_key("dp8", 2 << 20),
        "float32", tuning.device_kind())
    entry = tuning.TuningDB(db_path).lookup(key)
    assert entry is not None, key
    assert entry["decision"]["bucket_mb"] == 4.0
    assert entry["source"] == "swept"


def test_mc_ab_param_drift():
    from tools._mc_ab import _param_drift

    a = {"w": np.ones((4, 4), np.float32)}
    assert _param_drift(a, {"w": np.ones((4, 4), np.float32)}) == 0.0
    b = {"w": np.ones((4, 4), np.float32) * 1.01}
    assert 0.005 < _param_drift(a, b) < 0.02
    assert _param_drift(a, {}) == float("inf")


def test_fleet_strategy_bucket_and_zero1_knobs():
    """DistributedStrategy.allreduce_bucket_mb / zero1 flow through the
    fleet CollectiveOptimizer into the transpiler."""
    from paddle_tpu.incubate.fleet import UserDefinedRoleMaker, fleet
    from paddle_tpu.incubate.fleet.base import DistributedStrategy

    mesh = make_mesh({"dp": N_DEV})
    strat = DistributedStrategy()
    strat.allreduce_bucket_mb = 0.0001
    strat.zero1 = True
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            x = L.data(name="x", shape=[16], dtype="float32")
            y = L.data(name="y", shape=[1], dtype="float32")
            loss = L.mean(L.square_error_cost(L.fc(x, size=8), y))
            fleet.init(UserDefinedRoleMaker(worker_num=N_DEV), mesh=mesh)
            opt = fleet.distributed_optimizer(
                pt.optimizer.Adam(1e-2), strategy=strat)
            opt.minimize(loss)
    types = [op.type for op in main.global_block.ops]
    assert "c_reducescatter" in types  # zero1 took the eligible params
    assert "zero1_shard" in types
