"""Learned cost-model subsystem tests (ISSUE 15): the measurement store's
append/fail-open discipline, the hand features, deterministic training, the
NEW policy tier (exact DB hit > learned > analytic prior > default), the
confidence gate (holdout accuracy + feature-envelope extrapolation), the
corrupt/missing-model fail-open (warn ONCE, like the DB), cross-device
transfer, bounded online exploration (promotion evidence schema, pacing,
the executor hook), and the gate.py --costmodel check on the committed
artifacts."""
import json
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu import tuning
from paddle_tpu.tuning import learned
from paddle_tpu.tuning import policy as _policy
from paddle_tpu.tuning.learned import explore, features, model, store


@pytest.fixture
def lenv(tmp_path):
    """Scratch tuner environment: DB + measurement store + model paths all
    under tmp, consult mode, every cache/counter reset on both sides."""
    snap = pt.flags.all_flags()
    paths = {
        "db": str(tmp_path / "db.json"),
        "meas": str(tmp_path / "meas.jsonl"),
        "model": str(tmp_path / "model.json"),
    }
    pt.flags.set_flags({"tuning_mode": "consult", "tuning_db": paths["db"],
                        "tuning_measurements": paths["meas"],
                        "tuning_model": paths["model"]})
    _reset()
    yield paths
    pt.flags.set_flags(snap)
    _reset()


def _reset():
    tuning.invalidate_db_cache()
    tuning.reset_provenance()
    learned.invalidate_model_cache()
    learned.reset_counters()
    explore.reset_state()
    _policy._seen_candidates.clear()


def _conv_sk(n, hw, cin, cout, k=3):
    return tuning.conv_key(n, hw, hw, cin, cout, k, k, (1, 1), (1, 1),
                           "NHWC")


def _synthetic_records(dk):
    """A conv grid whose arm times are EXACT log-linear functions of the
    hand features: direct = flops * 1e-10, igemm = direct * (K/288)^-0.5
    (K = cin*kh*kw), so igemm wins iff cin > 32 and the ridge can fit the
    surface perfectly. The (hw=8, cin=3, cout=4) key is deliberately left
    OUT so e2e tests can query it as a genuinely unseen, in-envelope
    shape."""
    recs = []
    for hw in (8, 16):
        for cin in (3, 8, 16, 32, 64, 128):
            for cout in (4, 16, 64):
                if (hw, cin, cout) == (8, 3, 4):
                    continue
                sk = _conv_sk(4, hw, cin, cout)
                flops = 2.0 * (4 * hw * hw) * (cin * 9) * cout
                direct = flops * 1e-10
                igemm = direct * (cin * 9 / 288.0) ** -0.5
                for arm, t in (("direct", direct), ("igemm", igemm)):
                    recs.append({"schema": store.STORE_SCHEMA,
                                 "op": "conv2d", "shape_key": sk,
                                 "dtype": "float32", "device_kind": dk,
                                 "arm": arm, "median_s": t})
    return recs


def _trained(lenv, dk=None):
    dk = dk or tuning.device_kind()
    m = learned.train_model(_synthetic_records(dk), seed=0)
    learned.save_model(m, lenv["model"])
    learned.invalidate_model_cache()
    return m


# -- the measurement store ---------------------------------------------------

def test_store_roundtrip_and_median_from_windows(lenv):
    assert store.record("conv2d", "sk", "float32", "cpu", "direct",
                        windows_s=[0.003, 0.001, 0.002], source="test")
    assert store.record("conv2d", "sk", "float32", "cpu", "igemm",
                        windows_s=[0.004], median_s=0.004, band=0.01,
                        source="test")
    recs = list(store.iter_records(lenv["meas"]))
    assert len(recs) == 2
    r = recs[0]
    assert r["schema"] == store.STORE_SCHEMA
    assert r["median_s"] == pytest.approx(0.002)  # computed from windows
    assert r["min_s"] == pytest.approx(0.001)
    assert r["source"] == "test"
    assert "host" in r and r["host"]["cpus"] >= 1


def test_store_corrupt_lines_fail_open(lenv):
    store.record("conv2d", "sk", "float32", "cpu", "direct",
                 windows_s=[0.001], source="test")
    with open(lenv["meas"], "a") as f:
        f.write("{not json\n")
        f.write(json.dumps({"schema": 999, "op": "x"}) + "\n")
        f.write(json.dumps(["a", "list"]) + "\n")
    store.record("conv2d", "sk2", "float32", "cpu", "igemm",
                 windows_s=[0.002], source="test")
    recs = list(store.iter_records(lenv["meas"]))
    assert [r["shape_key"] for r in recs] == ["sk", "sk2"]


def test_store_missing_file_and_unwritable_never_raise(lenv):
    assert list(store.iter_records(str("/nonexistent/meas.jsonl"))) == []
    assert store.record("conv2d", "sk", "float32", "cpu", "direct",
                        windows_s=[0.001], source="test",
                        path="/proc/definitely/not/writable.jsonl") is False


def test_store_flag_gating(lenv):
    # auto (default): tools record, runtime only in sweep/explore
    assert store.recording_enabled(tool=True)
    assert not store.recording_enabled()           # consult-mode runtime
    pt.flags.set_flags({"tuning_mode": "sweep"})
    assert store.recording_enabled()
    pt.flags.set_flags({"tuning_mode": "explore"})
    assert store.recording_enabled()
    pt.flags.set_flags({"tuning_mode": "consult", "tuning_record": "on"})
    assert store.recording_enabled()
    pt.flags.set_flags({"tuning_record": "off"})
    assert not store.recording_enabled(tool=True)  # off is absolute
    pt.flags.set_flags({"tuning_record": "auto", "tuning_measurements": "",
                        "tuning_db": ""})
    assert not store.recording_enabled(tool=True)  # no path resolves


def test_store_record_measured_splits_canonical_key(lenv):
    key = f"conv2d|{_conv_sk(4, 8, 3, 4)}|float32|cpu"
    store.record_measured(key, {
        "direct": {"median_s": 1.0, "min_s": 0.9, "windows_s": [1.0],
                   "band": 0.02},
        "igemm": {"median_s": 0.5, "min_s": 0.5, "windows_s": [0.5],
                  "band": 0.01}}, source="explore")
    recs = list(store.iter_records(lenv["meas"]))
    assert sorted(r["arm"] for r in recs) == ["direct", "igemm"]
    assert all(r["op"] == "conv2d" and r["source"] == "explore"
               and r["device_kind"] == "cpu" for r in recs)


# -- features ----------------------------------------------------------------

def test_featurize_sanity():
    for op, sk in [("conv2d", _conv_sk(4, 8, 3, 4)),
                   ("attention", tuning.attention_key(2, 12, 128, 128, 64,
                                                      False)),
                   ("epilogue", "kind=bn rows=128 c=64 ch=last act=relu "
                                "res=0")]:
        v = features.featurize(op, sk, "float32")
        assert isinstance(v, list) and len(v) >= 5
        assert all(np.isfinite(x) for x in v)
    assert features.featurize("collective", "whatever", "float32") is None
    assert features.featurize("conv2d", "un parseable garbage",
                              "float32") is None
    assert features.decision_field("conv2d") == "lowering"
    assert features.decision_field("attention") == "backend"


# -- training + prediction ---------------------------------------------------

def test_training_deterministic_byte_identical(lenv, tmp_path):
    recs = _synthetic_records("cpu")
    m1 = learned.train_model(recs, seed=0)
    m2 = learned.train_model(recs, seed=0)
    p1, p2 = str(tmp_path / "m1.json"), str(tmp_path / "m2.json")
    learned.save_model(m1, p1)
    learned.save_model(m2, p2)
    assert open(p1, "rb").read() == open(p2, "rb").read()
    assert m1["schema"] == model.MODEL_SCHEMA
    # no stray temp files after the atomic write
    assert sorted(os.listdir(tmp_path)) >= ["m1.json", "m2.json"]


def test_model_learns_the_arm_surface(lenv):
    m = _trained(lenv, dk="cpu")
    grp = m["groups"]["conv2d|cpu"]
    assert grp["holdout"]["rank_acc"] >= model.RANK_ACC_FLOOR
    # unseen in-envelope keys on both sides of the igemm/direct boundary
    t_lo, _ = learned.predict_times(m, "conv2d", _conv_sk(4, 16, 8, 32),
                                    "float32", "cpu")
    t_hi, _ = learned.predict_times(m, "conv2d", _conv_sk(4, 16, 128, 32),
                                    "float32", "cpu")
    assert t_lo is not None and t_lo["direct"] < t_lo["igemm"]
    assert t_hi is not None and t_hi["igemm"] < t_hi["direct"]


def test_confidence_gate_rejects_10x_beyond_envelope(lenv):
    m = _trained(lenv, dk="cpu")
    # cin 10x past the widest trained channel count: extrapolation territory
    times, info = learned.predict_times(m, "conv2d",
                                        _conv_sk(4, 16, 1280, 64),
                                        "float32", "cpu")
    assert times is None
    assert info["reason"] == "envelope"


def test_cross_device_transfer_reuses_cpu_ranking(lenv):
    m = _trained(lenv, dk="cpu")
    times, info = learned.predict_times(m, "conv2d", _conv_sk(4, 16, 128, 64),
                                        "float32", "TPU v99")
    assert times is not None
    assert info.get("transfer_from") == "conv2d|cpu"
    assert times["igemm"] < times["direct"]  # ranking carried over


def test_eval_model_rescores_recorded_holdout(lenv):
    recs = _synthetic_records("cpu")
    m = learned.train_model(recs, seed=0)
    ev = learned.eval_model(m, recs)
    g = ev["groups"]["conv2d|cpu"]
    assert g["n"] == len(m["groups"]["conv2d|cpu"]["holdout_keys"])
    assert g["rank_acc"] == m["groups"]["conv2d|cpu"]["holdout"]["rank_acc"]
    assert g["analytic_rank_acc"] is not None


# -- the policy tier ---------------------------------------------------------

def test_tier_ordering_db_beats_learned_beats_analytic(lenv):
    dk = tuning.device_kind()
    _trained(lenv)
    sk = _conv_sk(4, 16, 128, 64)  # unseen, in envelope; model says igemm
    key = tuning.canonical_key("conv2d", sk, "float32", dk)
    # 1) no DB entry: the learned tier answers
    d, tier = tuning.decide("conv2d", key,
                            prior=lambda: {"lowering": "direct"},
                            default={"lowering": "direct"})
    assert (d, tier) == ({"lowering": "igemm"}, "learned")
    # 2) a swept DB entry outranks the model
    db = tuning.TuningDB(lenv["db"])
    db.put(key, {"lowering": "direct"}, source="swept")
    db.save(lenv["db"])
    tuning.invalidate_db_cache()
    d, tier = tuning.decide("conv2d", key,
                            prior=lambda: {"lowering": "direct"},
                            default={"lowering": "direct"})
    assert (d, tier) == ({"lowering": "direct"}, "db")
    # 3) out-of-envelope key falls through to the analytic prior
    far = tuning.canonical_key("conv2d", _conv_sk(4, 16, 1280, 64),
                               "float32", dk)
    d, tier = tuning.decide("conv2d", far,
                            prior=lambda: {"lowering": "direct"},
                            default={"lowering": "direct"})
    assert tier == "analytic"
    # 4) no prior either: conservative default
    d, tier = tuning.decide("conv2d", far, prior=lambda: None,
                            default={"lowering": "direct"})
    assert tier == "default"
    snap = tuning.provenance_snapshot()
    assert snap["per_op"]["conv2d"] == {"db": 1, "learned": 1,
                                        "analytic": 1, "default": 1}
    assert snap["learned"] == 1
    assert snap["tuned_rate"] == pytest.approx(0.5)  # (db+learned)/4
    ls = learned.snapshot()
    assert ls["predictions"] == 1
    assert ls["fallback_reasons"].get("envelope", 0) >= 1


def test_learned_validate_rejection_falls_through(lenv):
    dk = tuning.device_kind()
    _trained(lenv)
    key = tuning.canonical_key("conv2d", _conv_sk(4, 16, 128, 64),
                               "float32", dk)
    d, tier = tuning.decide("conv2d", key,
                            prior=lambda: {"lowering": "direct"},
                            default={"lowering": "direct"},
                            validate=lambda dec: dec == {"lowering":
                                                         "direct"})
    assert tier == "analytic"
    assert learned.snapshot()["fallback_reasons"].get("validate") == 1


def test_missing_model_is_silent_analytic(lenv):
    # lenv points tuning_model at a path that was never written
    key = tuning.canonical_key("conv2d", _conv_sk(4, 16, 128, 64),
                               "float32", tuning.device_kind())
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        d, tier = tuning.decide("conv2d", key,
                                prior=lambda: {"lowering": "direct"},
                                default={"lowering": "direct"})
    assert tier == "analytic"
    assert [x for x in w if "cost model" in str(x.message)] == []
    assert learned.snapshot()["attempts"] == 0  # a miss is not an attempt


def test_corrupt_model_warns_once_then_fails_open(lenv):
    with open(lenv["model"], "w") as f:
        f.write("{definitely not json")
    key = tuning.canonical_key("conv2d", _conv_sk(4, 16, 128, 64),
                               "float32", tuning.device_kind())
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for _ in range(3):
            d, tier = tuning.decide("conv2d", key,
                                    prior=lambda: {"lowering": "direct"},
                                    default={"lowering": "direct"})
            assert tier == "analytic"
    msgs = [x for x in w if "cost model" in str(x.message)]
    assert len(msgs) == 1
    assert "falling back to the analytic" in str(msgs[0].message)


def test_model_removal_mid_session_fails_open(lenv):
    dk = tuning.device_kind()
    _trained(lenv)
    key = tuning.canonical_key("conv2d", _conv_sk(4, 16, 128, 64),
                               "float32", dk)
    _, tier = tuning.decide("conv2d", key,
                            prior=lambda: {"lowering": "direct"},
                            default={"lowering": "direct"})
    assert tier == "learned"
    os.remove(lenv["model"])
    learned.invalidate_model_cache()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _, tier = tuning.decide("conv2d", key,
                                prior=lambda: {"lowering": "direct"},
                                default={"lowering": "direct"})
    assert tier == "analytic"
    assert [x for x in w if "cost model" in str(x.message)] == []


# -- bounded online exploration ----------------------------------------------

def _fake_measured(times):
    def _m(fn):
        t = times[fn]
        return {"median_s": t, "min_s": t, "windows_s": [t], "band": 0.0}
    return _m


def _put_candidate(lenv, key, decision):
    db = tuning.TuningDB(lenv["db"])
    db.put(key, decision, source="candidate")
    db.save(lenv["db"])
    tuning.invalidate_db_cache()
    return key


def test_explore_promotes_winner_with_sweep_evidence(lenv, monkeypatch):
    pt.flags.set_flags({"tuning_mode": "explore"})
    dk = tuning.device_kind()
    key = _put_candidate(
        lenv, tuning.canonical_key("conv2d", _conv_sk(4, 8, 3, 4),
                                   "float32", dk),
        {"lowering": "direct"})
    monkeypatch.setattr(explore, "_build_arms",
                        lambda op, sk, dt: {"direct": "d", "igemm": "g"})
    monkeypatch.setattr(explore, "_measure",
                        _fake_measured({"d": 1.0, "g": 0.5}))
    out = explore.explore_one()
    assert out is not None and out["verdict"] == "keep"
    assert out["decision"] == "igemm"
    entry = tuning.TuningDB(lenv["db"]).lookup(key)
    assert entry["source"] == "swept"
    assert entry["decision"] == {"lowering": "igemm"}
    # the promotion carries the SAME evidence schema offline sweeps write
    assert entry["measured"] == {"direct": {"median_s": 1.0, "band": 0.0},
                                 "igemm": {"median_s": 0.5, "band": 0.0}}
    assert learned.snapshot()["promotions"] == 1
    # the raw windows landed in the measurement store too
    srcs = {r["source"] for r in store.iter_records(lenv["meas"])}
    assert srcs == {"explore"}
    # a probed key is never re-probed in-process
    assert explore.explore_one() is None


def test_explore_tie_keeps_candidate_with_evidence(lenv, monkeypatch):
    pt.flags.set_flags({"tuning_mode": "explore"})
    dk = tuning.device_kind()
    key = _put_candidate(
        lenv, tuning.canonical_key("conv2d", _conv_sk(4, 8, 3, 4),
                                   "float32", dk),
        {"lowering": "direct"})
    monkeypatch.setattr(explore, "_build_arms",
                        lambda op, sk, dt: {"direct": "d", "igemm": "g"})
    monkeypatch.setattr(explore, "_measure",
                        _fake_measured({"d": 1.0, "g": 0.98}))  # inside 5%
    out = explore.explore_one()
    assert out["verdict"] == "tie"
    entry = tuning.TuningDB(lenv["db"]).lookup(key)
    assert entry["source"] == "candidate"          # the candidate stands
    assert entry["decision"] == {"lowering": "direct"}
    assert entry["measured"]["igemm"]["median_s"] == 0.98  # ...with data
    assert learned.snapshot()["promotions"] == 0


def test_explore_retires_slower_candidate(lenv, monkeypatch):
    pt.flags.set_flags({"tuning_mode": "explore"})
    dk = tuning.device_kind()
    key = _put_candidate(
        lenv, tuning.canonical_key("conv2d", _conv_sk(4, 8, 3, 4),
                                   "float32", dk),
        {"lowering": "igemm"})
    monkeypatch.setattr(explore, "_build_arms",
                        lambda op, sk, dt: {"direct": "d", "igemm": "g"})
    monkeypatch.setattr(explore, "_measure",
                        _fake_measured({"d": 0.5, "g": 1.0}))
    out = explore.explore_one()
    assert out["verdict"] == "keep"  # direct beats the igemm base
    entry = tuning.TuningDB(lenv["db"]).lookup(key)
    assert entry["source"] == "swept"
    assert entry["decision"] == {"lowering": "direct"}


def test_maybe_explore_pacing_and_mode_gate(lenv, monkeypatch):
    calls = []
    monkeypatch.setattr(explore, "explore_one",
                        lambda: calls.append(1) or None)
    # consult mode: a no-op, no step counting
    for _ in range(10):
        assert explore.maybe_explore() is None
    assert calls == []
    pt.flags.set_flags({"tuning_mode": "explore",
                        "tuning_explore_every": 3})
    for _ in range(9):
        explore.maybe_explore()
    assert len(calls) == 3  # steps 3, 6, 9
    pt.flags.set_flags({"tuning_explore_every": 0})
    explore.maybe_explore()
    assert len(calls) == 3  # every<=0 disables


def test_explore_real_probe_end_to_end(lenv):
    """No monkeypatching: a real candidate conv key is rebuilt, timed and
    resolved on this box; whatever the verdict, the entry carries measured
    evidence and the store grew explore rows."""
    pt.flags.set_flags({"tuning_mode": "explore"})
    dk = tuning.device_kind()
    key = _put_candidate(
        lenv, tuning.canonical_key(
            "conv2d", tuning.conv_key(2, 8, 8, 3, 4, 3, 3, (1, 1), (1, 1),
                                      "NHWC"), "float32", dk),
        {"lowering": "direct"})
    out = explore.explore_one()
    assert out is not None
    assert out["verdict"] in ("keep", "retire", "tie")
    entry = tuning.TuningDB(lenv["db"]).lookup(key)
    assert set(entry["measured"]) == {"direct", "igemm"}
    for ev in entry["measured"].values():
        assert ev["median_s"] > 0 and ev["band"] >= 0
    recs = list(store.iter_records(lenv["meas"]))
    assert {r["source"] for r in recs} == {"explore"}
    assert {r["arm"] for r in recs} == {"direct", "igemm"}


def test_executor_step_drives_explore_hook(lenv, monkeypatch):
    pt.flags.set_flags({"tuning_mode": "explore",
                        "tuning_explore_every": 1})
    calls = []
    monkeypatch.setattr(explore, "explore_one",
                        lambda: calls.append(1) or None)
    x = L.data(name="x", shape=[4], dtype="float32")
    y = L.scale(x, scale=2.0)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    # the probe rides the ASYNC dispatch path's idle gap (run_async), not
    # the synchronous run()
    exe.run_async(pt.default_main_program(),
                  feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[y])
    exe.wait()
    assert len(calls) >= 1


# -- candidate evidence (the db.py satellite) --------------------------------

def test_db_evidence_schema_and_candidate_measured(lenv):
    measured = {"direct": {"median_s": 1.0, "min_s": 0.9,
                           "windows_s": [1.0, 0.9], "band": 0.11},
                "igemm": {"median_s": 0.5, "band": 0.02},
                "broken": "not a dict", "empty": {"median_s": None}}
    ev = tuning.evidence(measured)
    assert ev == {"direct": {"median_s": 1.0, "band": 0.11},
                  "igemm": {"median_s": 0.5, "band": 0.02}}
    db = tuning.TuningDB(lenv["db"])
    db.put("k", {"lowering": "direct"}, source="candidate", measured=ev)
    db.save(lenv["db"])
    assert tuning.TuningDB(lenv["db"]).lookup("k")["measured"] == ev


# -- end to end + observability + gate ---------------------------------------

def test_e2e_consult_unseen_shape_uses_learned_tier(lenv):
    """The acceptance run: a consult-mode model whose conv key is NOT in
    the DB resolves from the learned tier at trace time and trains
    finite; removing the model mid-session falls back to analytic with
    zero crashes (covered per-decide by test_model_removal...)."""
    _trained(lenv)
    img = L.data(name="img", shape=[8, 8, 3], dtype="float32")
    label = L.data(name="label", shape=[1], dtype="int64")
    c = L.conv2d(img, num_filters=4, filter_size=3, padding=1,
                 data_format="NHWC")
    p = L.pool2d(c, global_pooling=True, pool_type="avg",
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
    assert snap["per_op"].get("conv2d", {}).get("learned", 0) >= 1


def test_schema_declares_learned_metrics():
    from paddle_tpu.observability import schema
    for name in ("tuning.learned.predictions", "tuning.learned.fallbacks",
                 "tuning.learned.explore_promotions"):
        assert name in schema.DECLARED_NAMES


def test_sweep_conv_feeds_the_store_with_evidence(lenv, tmp_path):
    from tools import tune
    db = tuning.TuningDB(str(tmp_path / "swept.json"))
    shapes = [("tiny", 2, 8, 8, 3, 4, 3, 3, (1, 1), [(1, 1), (1, 1)],
               (1, 1))]
    tune.sweep_conv(db, shapes, "float32", iters=1, passes=2, band=0.05)
    key = tuning.canonical_key(
        "conv2d", tuning.conv_key(2, 8, 8, 3, 4, 3, 3, (1, 1), (1, 1),
                                  "NHWC"), "float32", tuning.device_kind())
    entry = db.lookup(key)
    assert entry["source"] == "swept"
    # swept entries carry the shared evidence schema...
    for ev in entry["measured"].values():
        assert set(ev) == {"median_s", "band"}
    # ...and the raw windows landed in the measurement store
    recs = [r for r in store.iter_records(lenv["meas"])
            if r["op"] == "conv2d"]
    assert {r["arm"] for r in recs} >= {"direct", "igemm"}
    assert all(r["windows_s"] for r in recs)


def test_gate_costmodel_on_committed_artifacts():
    """The committed COSTMODEL_cpu.json must beat the analytic prior on
    the committed dataset's recorded holdout keys — the PR's acceptance
    line, enforced exactly as `python tools/gate.py --costmodel` runs
    it."""
    from tools import gate
    data = os.path.join(gate.REPO, gate.COSTMODEL_DATA)
    mdl = os.path.join(gate.REPO, gate.COSTMODEL_MODEL)
    if not (os.path.exists(data) and os.path.exists(mdl)):
        pytest.skip("committed costmodel artifacts absent")
    assert gate.check_costmodel() == 0
    ev = learned.eval_model(learned.load_model(mdl),
                            list(learned.iter_records(data)))
    for g in ev["groups"].values():
        assert g["rank_acc"] >= g["analytic_rank_acc"]


def test_gate_costmodel_fails_on_corrupt_model(tmp_path):
    from tools import gate
    data = os.path.join(gate.REPO, gate.COSTMODEL_DATA)
    if not os.path.exists(data):
        pytest.skip("committed costmodel dataset absent")
    bad = str(tmp_path / "bad_model.json")
    with open(bad, "w") as f:
        f.write("{nope")
    assert gate.check_costmodel(data_path=data, model_path=bad) == 1
