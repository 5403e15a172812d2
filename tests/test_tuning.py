"""Autotuner tests (ISSUE 6): the persistent decision DB, the three-tier
resolution (exact hit -> analytic prior -> conservative default), the lever
wirings (conv lowering, attention backend, conv+BN fusion, AMP lists,
bucket boundaries), corrupt/missing-DB fallback, sweep-mode candidate
recording, and the acceptance equivalences:

  * FLAGS_tuning_mode=consult with a swept DB reproduces the PR 5 per-shape
    igemm decisions on the PERF.md r6 cost-table shapes (and can beat them
    with a measured override);
  * a swept attention split — XLA at seq<=128, the Pallas kernel at s512,
    which is what a v5e run of 2026-07 measured on code that predates
    PRs 1-20 — resolves from the DB, and an un-runnable backend gives way
    at dispatch instead of breaking numerics.
"""
import json
import os
import warnings

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu import tuning
from paddle_tpu.ops.nn_ops import _igemm_take

def _sds(shape, dtype="float32"):
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


@pytest.fixture
def tuned(tmp_path):
    """Point the tuner at a scratch DB path (not yet written), yield it,
    and restore flags + caches afterwards."""
    snap = pt.flags.all_flags()
    db_path = str(tmp_path / "tuning_db.json")
    pt.flags.set_flags({"tuning_mode": "consult", "tuning_db": db_path})
    tuning.invalidate_db_cache()
    tuning.reset_provenance()
    yield db_path
    pt.flags.set_flags(snap)
    tuning.invalidate_db_cache()
    tuning.reset_provenance()


def _write_db(path, entries):
    db = tuning.TuningDB(path)
    for key, decision, src in entries:
        db.put(key, decision, source=src)
    db.save(path)
    tuning.invalidate_db_cache()
    return db


# -- DB mechanics ------------------------------------------------------------

def test_db_roundtrip_and_atomic_write(tmp_path):
    p = str(tmp_path / "sub" / "db.json")  # directory is created
    db = tuning.TuningDB(p)
    db.put("conv2d|k|float32|cpu", {"lowering": "igemm"},
           measured={"direct": 1.0}, note="n")
    db.save()
    raw = json.load(open(p))
    assert raw["schema"] == tuning.DB_SCHEMA
    re = tuning.TuningDB(p)
    assert re.lookup("conv2d|k|float32|cpu")["decision"] == \
        {"lowering": "igemm"}
    assert re.lookup("conv2d|k|float32|cpu")["measured"] == {"direct": 1.0}
    # no stray temp files after the atomic replace
    assert os.listdir(os.path.dirname(p)) == ["db.json"]


def test_candidate_put_never_clobbers_swept(tmp_path):
    p = str(tmp_path / "db.json")
    db = tuning.TuningDB(p)
    db.put("k", {"lowering": "igemm"}, source="swept")
    assert not db.put("k", {"lowering": "direct"}, source="candidate",
                      overwrite=False)
    assert db.lookup("k")["decision"] == {"lowering": "igemm"}


def test_db_evidence_schema_and_candidate_measured(tmp_path):
    measured = {"direct": {"median_s": 1.0, "min_s": 0.9,
                           "windows_s": [1.0, 0.9], "band": 0.11},
                "igemm": {"median_s": 0.5, "band": 0.02},
                "broken": "not a dict", "empty": {"median_s": None}}
    ev = tuning.evidence(measured)
    assert ev == {"direct": {"median_s": 1.0, "band": 0.11},
                  "igemm": {"median_s": 0.5, "band": 0.02}}
    p = str(tmp_path / "db.json")
    db = tuning.TuningDB(p)
    db.put("k", {"lowering": "direct"}, source="candidate", measured=ev)
    db.save(p)
    assert tuning.TuningDB(p).lookup("k")["measured"] == ev


@pytest.mark.parametrize("payload", [
    "{corrupt json",                       # unparseable
    json.dumps({"schema": 999, "entries": {}}),   # wrong schema
    json.dumps(["not", "an", "object"]),   # wrong top-level type
])
def test_bad_db_warns_and_degrades_to_empty(tmp_path, payload):
    p = str(tmp_path / "bad.json")
    open(p, "w").write(payload)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        db = tuning.TuningDB(p)
    assert len(db) == 0
    assert any("falling back to the analytic" in str(x.message) for x in w)


def test_missing_db_is_silently_empty(tmp_path):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        db = tuning.TuningDB(str(tmp_path / "nope.json"))
    assert len(db) == 0 and not w


# -- three-tier resolution ---------------------------------------------------

def test_decide_tiers_and_provenance(tuned):
    key = tuning.canonical_key("demo", "shape", "float32", "cpu")
    # tier 3: no DB entry, no prior
    d, tier = tuning.decide("demo", key, default={"x": 1})
    assert (d, tier) == ({"x": 1}, "default")
    # tier 2: analytic prior
    d, tier = tuning.decide("demo", key, prior=lambda: {"x": 2})
    assert (d, tier) == ({"x": 2}, "analytic")
    # tier 1: exact hit
    _write_db(tuned, [(key, {"x": 3}, "swept")])
    d, tier = tuning.decide("demo", key, prior=lambda: {"x": 2})
    assert (d, tier) == ({"x": 3}, "db")
    snap = tuning.provenance_snapshot()
    assert snap["per_op"]["demo"] == {"db": 1, "analytic": 1, "default": 1}
    assert snap["decisions"] == 3 and snap["db_hits"] == 1


def test_candidate_entries_do_not_count_as_hits(tuned):
    key = tuning.canonical_key("demo", "s", "float32", "cpu")
    _write_db(tuned, [(key, {"x": 9}, "candidate")])
    d, tier = tuning.decide("demo", key, prior=lambda: {"x": 2})
    assert (d, tier) == ({"x": 2}, "analytic")


def test_validate_rejects_unusable_db_decision(tuned):
    key = tuning.canonical_key("demo", "s", "float32", "cpu")
    _write_db(tuned, [(key, {"x": "bogus"}, "swept")])
    d, tier = tuning.decide("demo", key, prior=lambda: {"x": 2},
                            validate=lambda dd: isinstance(dd.get("x"), int))
    assert (d, tier) == ({"x": 2}, "analytic")


def test_sweep_mode_records_candidates(tuned):
    pt.flags.set_flags({"tuning_mode": "sweep"})
    key = tuning.canonical_key("demo", "swept-shape", "float32", "cpu")
    d, tier = tuning.decide("demo", key, prior=lambda: {"x": 5})
    assert (d, tier) == ({"x": 5}, "analytic")
    raw = json.load(open(tuned))
    assert raw["entries"][key] == {
        "decision": {"x": 5}, "source": "candidate",
        "note": "analytic resolution tier=analytic"}


# -- conv lowering: the PR 5 equivalence (acceptance) ------------------------

# the PERF.md r6 cost-table shapes (b128 NHWC bf16, bench configuration):
# (name, n, h, w, cin, cout, kh, kw, strides, pads, dil, table_verdict)
# table_verdict None = borderline row (the A/B decides, not the model)
PERF_COST_TABLE = [
    ("stem_7x7_s2_3ch", 128, 224, 224, 3, 64, 7, 7, (2, 2),
     [(3, 3), (3, 3)], (1, 1), True),
    ("stem_s2d_4x4_12ch", 128, 112, 112, 12, 64, 4, 4, (1, 1),
     [(2, 1), (2, 1)], (1, 1), None),
    ("s0_3x3_64ch", 128, 56, 56, 64, 64, 3, 3, (1, 1),
     [(1, 1), (1, 1)], (1, 1), False),
    ("s1_3x3_128ch", 128, 28, 28, 128, 128, 3, 3, (1, 1),
     [(1, 1), (1, 1)], (1, 1), False),
]


def _take(row, dtype="bfloat16"):
    _, n, h, w, cin, cout, kh, kw, s, pads, d, _ = row
    return _igemm_take(_sds((n, h, w, cin), dtype),
                       _sds((kh, kw, cin, cout), dtype),
                       s, pads, d, 1, "NHWC")


def _conv_db_key(row, dtype="bfloat16"):
    _, n, h, w, cin, cout, kh, kw, s, pads, d, _ = row
    hout = (h + sum(pads[0]) - ((kh - 1) * d[0] + 1)) // s[0] + 1
    wout = (w + sum(pads[1]) - ((kw - 1) * d[1] + 1)) // s[1] + 1
    return tuning.canonical_key(
        "conv2d", tuning.conv_key(n, hout, wout, cin, cout, kh, kw, s, d,
                                  "NHWC"), dtype, tuning.device_kind())


def test_analytic_model_matches_perf_cost_table():
    """With tuning off, `auto` is the bare PR 5 cost model — and its
    verdicts on the definite cost-table rows are the documented ones
    (igemm for the 3-channel raw stem, direct for s0/s1)."""
    pt.flags.set_flags({"tuning_mode": "off"})
    for row in PERF_COST_TABLE:
        verdict = row[-1]
        if verdict is not None:
            assert _take(row) is verdict, row[0]


def test_consult_with_swept_db_reproduces_pr5_decisions(tuned):
    """Acceptance: a swept DB whose entries carry the measured verdicts
    reproduces the PR 5 per-shape decisions over the cost-table shapes —
    every resolution an exact DB hit (hit-rate 1.0)."""
    pt.flags.set_flags({"tuning_mode": "off"})
    analytic = {row[0]: _take(row) for row in PERF_COST_TABLE}
    _write_db(tuned, [
        (_conv_db_key(row),
         {"lowering": "igemm" if analytic[row[0]] else "direct"}, "swept")
        for row in PERF_COST_TABLE])
    pt.flags.set_flags({"tuning_mode": "consult"})
    tuning.reset_provenance()
    for row in PERF_COST_TABLE:
        assert _take(row) is analytic[row[0]], row[0]
    snap = tuning.provenance_snapshot()
    assert snap["per_op"]["conv2d"]["db"] == len(PERF_COST_TABLE)
    assert snap["hit_rate"] == 1.0


def test_consult_swept_override_beats_prior(tuned):
    """...or beats them: a measured igemm win on a shape the model prices
    as direct (s0) is honored from the DB, while unswept shapes keep the
    analytic verdict."""
    s0, s1 = PERF_COST_TABLE[2], PERF_COST_TABLE[3]
    _write_db(tuned, [(_conv_db_key(s0), {"lowering": "igemm"}, "swept")])
    assert _take(s0) is True      # DB override
    assert _take(s1) is False     # analytic fallback (no entry)
    snap = tuning.provenance_snapshot()
    assert snap["per_op"]["conv2d"] == {"db": 1, "analytic": 1, "default": 0}


def test_consult_with_corrupt_db_falls_back_to_analytic(tuned):
    """Acceptance: a corrupt DB must not change decisions or raise."""
    open(tuned, "w").write("{definitely not json")
    pt.flags.set_flags({"tuning_mode": "off"})
    analytic = {row[0]: _take(row) for row in PERF_COST_TABLE}
    pt.flags.set_flags({"tuning_mode": "consult"})
    tuning.invalidate_db_cache()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # the one-time unreadable warning
        for row in PERF_COST_TABLE:
            assert _take(row) is analytic[row[0]], row[0]


def test_igemm_force_flags_override_the_db(tuned):
    """'on'/'off' are hard forces (the A/B arms): the DB must not win."""
    s0 = PERF_COST_TABLE[2]
    _write_db(tuned, [(_conv_db_key(s0), {"lowering": "igemm"}, "swept")])
    pt.flags.set_flags({"conv_implicit_gemm": "off"})
    assert _take(s0) is False
    pt.flags.set_flags({"conv_implicit_gemm": "on"})
    assert _take(s0) is True
    pt.flags.set_flags({"conv_implicit_gemm": "auto"})


# -- attention backend: the swept s128/s512 split (acceptance) ---------------

def _attn_key(b, nh, s, dh, dtype="float32"):
    return tuning.canonical_key(
        "attention", tuning.attention_key(b, nh, s, s, dh, False),
        dtype, tuning.device_kind())


def test_attention_split_matches_bench_r05(tuned):
    """Swept DB carrying the measured split: XLA at seq 128, Pallas at
    s512. Both resolve as exact hits regardless of the use_pallas flag the
    model was built with — the per-model flag becomes a cache entry."""
    from paddle_tpu.ops.attention_ops import attention_backend

    _write_db(tuned, [
        (_attn_key(128, 12, 128, 64), {"backend": "xla"}, "swept"),
        (_attn_key(64, 12, 512, 64), {"backend": "pallas_short"}, "swept"),
    ])
    b128, t = attention_backend((128, 12, 128, 64), (128, 12, 128, 64),
                                np.dtype("float32"), use_pallas=True)
    assert (b128, t) == ("xla", "db")
    b512, t = attention_backend((64, 12, 512, 64), (64, 12, 512, 64),
                                np.dtype("float32"), use_pallas=False)
    assert (b512, t) == ("pallas_short", "db")


def test_attention_backend_analytic_unchanged_when_off():
    pt.flags.set_flags({"tuning_mode": "off"})
    from paddle_tpu.ops.attention_ops import attention_backend

    b, tier = attention_backend((8, 4, 128, 64), (8, 4, 128, 64),
                                np.dtype("float32"))
    assert (b, tier) == ("xla", "analytic")


def test_unrunnable_swept_backend_degrades_at_dispatch(tuned):
    """A Pallas verdict replayed off-TPU must still produce exact
    attention numerics via the reference path."""
    from paddle_tpu.ops.attention_ops import (_reference_attention,
                                              flash_attention)

    b, nh, s, dh = 2, 2, 16, 8
    _write_db(tuned, [(_attn_key(b, nh, s, dh),
                       {"backend": "pallas_short"}, "swept")])
    rng = np.random.default_rng(0)
    q, k, v = (rng.standard_normal((b, nh, s, dh)).astype(np.float32)
               for _ in range(3))
    out = flash_attention(q, k, v, sm_scale=dh ** -0.5)
    ref = _reference_attention(q, k, v, sm_scale=dh ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# -- conv+BN fusion gating ---------------------------------------------------

def _conv_bn_program():
    img = L.data(name="img", shape=[8, 8, 3], dtype="float32")
    c = L.conv2d(img, num_filters=4, filter_size=3, padding=1,
                 bias_attr=False, data_format="NHWC")
    b = L.batch_norm(c, data_layout="NHWC")
    loss = L.reduce_mean(b)
    pt.optimizer.SGD(0.1).minimize(loss)
    return loss


def _op_types():
    return [op.type for op in pt.default_main_program().global_block.ops]


def test_fusion_db_entry_retires_one_shape(tuned):
    """A swept {"fuse": false} for the conv's shape keeps the pair
    unfused; with no entry the analytic prior fuses as before."""
    db = tuning.TuningDB(tuned)
    # key must match _fusion_wanted's spelling: batch -1 (declared), the
    # declared output tile, fp32
    key = tuning.canonical_key(
        "conv2d_bn_fusion",
        tuning.conv_key(-1, 8, 8, 3, 4, 3, 3, [1, 1], [1, 1], "NHWC"),
        "float32", tuning.device_kind())
    db.put(key, {"fuse": False}, source="swept")
    db.save(tuned)
    tuning.invalidate_db_cache()
    _conv_bn_program()
    types = _op_types()
    assert "conv2d_bn" not in types and "batch_norm" in types


def test_fusion_fuses_without_db_entry(tuned):
    _conv_bn_program()
    types = _op_types()
    assert "conv2d_bn" in types and "batch_norm" not in types


# -- AMP gray-list decisions -------------------------------------------------

def test_amp_gray_entry_promotes_and_demotes(tuned):
    from paddle_tpu.contrib.mixed_precision.fp16_lists import (
        AutoMixedPrecisionLists, apply_tuning_overrides)

    _write_db(tuned, [
        (tuning.canonical_key("amp_list", tuning.amp_key("pool2d"), "-",
                              tuning.device_kind()),
         {"list": "white"}, "swept"),
        (tuning.canonical_key("amp_list", tuning.amp_key("softmax"), "-",
                              tuning.device_kind()),
         {"list": "black"}, "swept"),
    ])
    lists = apply_tuning_overrides(AutoMixedPrecisionLists())
    assert "pool2d" in lists.white_list and "pool2d" not in lists.gray_list
    assert "softmax" in lists.black_list and "softmax" not in lists.gray_list
    assert "relu" in lists.gray_list  # untouched without an entry


def test_amp_custom_lists_win_over_db(tuned):
    """An op the user moved out of gray is no longer tunable."""
    from paddle_tpu.contrib.mixed_precision.fp16_lists import (
        AutoMixedPrecisionLists, apply_tuning_overrides)

    _write_db(tuned, [
        (tuning.canonical_key("amp_list", tuning.amp_key("pool2d"), "-",
                              tuning.device_kind()),
         {"list": "white"}, "swept")])
    lists = AutoMixedPrecisionLists(custom_black_list=["pool2d"])
    lists.gray_list.discard("pool2d")
    lists.black_list.add("pool2d")
    out = apply_tuning_overrides(lists)
    assert "pool2d" in out.black_list and "pool2d" not in out.white_list


# -- bucket boundaries -------------------------------------------------------

def test_bucket_boundary_db_override_and_validation(tuned):
    from paddle_tpu.data_feeder import _tuned_extent

    k = tuning.canonical_key("feed_bucket",
                             tuning.bucket_key("rx", 1, 9), "-",
                             tuning.device_kind())
    _write_db(tuned, [(k, {"pad_to": 12}, "swept")])
    assert _tuned_extent("rx", 1, 9, 16) == 12       # DB refines pow2
    # an override below the raw extent would drop data: rejected
    _write_db(tuned, [(k, {"pad_to": 4}, "swept")])
    assert _tuned_extent("rx", 1, 9, 16) == 16
    # unswept boundary keeps the prior
    assert _tuned_extent("rx", 1, 5, 8) == 8


def test_feeder_bucket_decision_recorded_in_sweep(tuned):
    pt.flags.set_flags({"tuning_mode": "sweep"})
    x = L.data(name="bx", shape=[2], dtype="float32")
    feeder = pt.DataFeeder([x], bucket_size=4)
    feed = feeder.feed([(np.zeros(2, np.float32),)] * 3)
    assert feed["bx"].shape[0] == 4
    raw = json.load(open(tuned))
    keys = [k for k in raw["entries"] if k.startswith("feed_bucket|")]
    assert keys and raw["entries"][keys[0]]["source"] == "candidate"


# -- minimize-time hook + end-to-end -----------------------------------------

def test_on_minimize_stamps_mode_and_loads_db(tuned):
    open(tuned, "w").write("{corrupt")
    tuning.invalidate_db_cache()
    with pytest.warns(UserWarning, match="unreadable"):
        loss = L.reduce_mean(L.fc(
            L.data(name="x", shape=[4], dtype="float32"), size=2))
        pt.optimizer.SGD(0.1).minimize(loss)
    assert pt.default_main_program()._tuning_mode == "consult"


def test_end_to_end_consult_trains_finite(tuned):
    """Full minimize + run under consult with a swept DB forcing the igemm
    lowering for the model's conv: decisions consult the DB at trace time
    and the step stays numerically healthy."""
    key = tuning.canonical_key(
        "conv2d", tuning.conv_key(4, 8, 8, 3, 4, 3, 3, (1, 1), (1, 1),
                                  "NHWC"),
        "float32", tuning.device_kind())
    _write_db(tuned, [(key, {"lowering": "igemm"}, "swept")])
    img = L.data(name="img", shape=[8, 8, 3], dtype="float32")
    label = L.data(name="label", shape=[1], dtype="int64")
    c = L.conv2d(img, num_filters=4, filter_size=3, padding=1,
                 data_format="NHWC")
    b = L.batch_norm(c, act="relu", data_layout="NHWC")
    p = L.pool2d(b, global_pooling=True, pool_type="avg",
                 data_format="NHWC")
    loss = L.reduce_mean(
        L.softmax_with_cross_entropy(L.fc(p, size=10), label))
    pt.optimizer.SGD(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    tuning.reset_provenance()
    rng = np.random.default_rng(0)
    feed = {"img": rng.standard_normal((4, 8, 8, 3)).astype(np.float32),
            "label": rng.integers(0, 10, (4, 1)).astype(np.int64)}
    (lv,) = exe.run(pt.default_main_program(), feed=feed, fetch_list=[loss])
    assert np.isfinite(float(np.asarray(lv)))
    snap = tuning.provenance_snapshot()
    assert snap["per_op"].get("conv2d", {}).get("db", 0) >= 1


# -- the sweeper + shared timing ---------------------------------------------

def test_timing_stats_and_verdicts():
    from tools import _timing

    assert _timing.median([3.0, 1.0, 2.0]) == 2.0
    assert _timing.interference_band([1.0]) == 0.0
    assert _timing.interference_band([1.0, 1.1]) == pytest.approx(0.0952,
                                                                  abs=1e-3)
    assert _timing.ab_verdict(1.0, 0.9) == "keep"
    assert _timing.ab_verdict(1.0, 1.2) == "retire"
    assert _timing.ab_verdict(1.0, 1.01) == "tie"
    assert _timing.ab_verdict(1.0, 0.97) == "tie"  # inside the 5% band


def test_tune_sweep_conv_writes_swept_entries(tuned, tmp_path):
    from tools import tune

    db = tuning.TuningDB(str(tmp_path / "swept.json"))
    shapes = [("tiny_3ch", 2, 12, 12, 3, 8, 3, 3, (1, 1),
               [(1, 1), (1, 1)], (1, 1))]
    tune.sweep_conv(db, shapes, "float32", iters=1, passes=2, band=0.05)
    db.save()
    raw = json.load(open(str(tmp_path / "swept.json")))
    (key,) = list(raw["entries"])
    entry = raw["entries"][key]
    assert key.startswith("conv2d|n=2 out=12x12 cin=3 cout=8 ")
    assert entry["source"] == "swept"
    assert entry["decision"]["lowering"] in ("direct", "igemm")
    assert {"direct", "igemm"} <= set(entry["measured"])
    assert "median_s" in entry["measured"]["direct"]
