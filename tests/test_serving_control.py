"""Learned serving control tests (ISSUE 20): regime/knob spellings and
their round trips, deterministic training + proposals from a fixed store
snapshot, the confidence-gate fallback ladder, the actuator's safety
rails (staged configs adopt only at idle boundaries, geometry changes
re-warm with zero compiles left on the serving path, shadow mode never
applies), and the store-backed disagg role-split prior."""
import os

import pytest

import paddle_tpu as pt
from paddle_tpu.serving import ServingEngine, decoder_tiny
from paddle_tpu.serving import control as sv_control
from paddle_tpu.serving.control import controller as sv_controller
from paddle_tpu.serving.control import policy as sv_policy
from paddle_tpu.tuning import learned
from paddle_tpu.tuning.learned import features


ARM_FAST = {"mi": 8, "dk": 0, "pc": 1, "sp": 0,
            "sq": 8, "so": 95, "da": 2, "pd": 0}
ARM_HAND = {"mi": 4, "dk": 0, "pc": 1, "sp": 0,
            "sq": 8, "so": 95, "da": 2, "pd": 0}
ARM_SLOW = {"mi": 2, "dk": 0, "pc": 1, "sp": 1,
            "sq": 4, "so": 90, "da": 4, "pd": 0}

# eight regimes spanning every feature axis, so live-ish signals land
# INSIDE the trained envelope (the gate kills extrapolations by design)
_REGIMES = [
    dict(rate=2, p50=8, p95=16, out=4, hit=0.0, occ=0.05, q=0, hr=1.0),
    dict(rate=4, p50=8, p95=16, out=8, hit=0.2, occ=0.10, q=1, hr=1.0),
    dict(rate=8, p50=16, p95=32, out=4, hit=0.4, occ=0.20, q=2, hr=0.5),
    dict(rate=16, p50=16, p95=32, out=8, hit=0.6, occ=0.30, q=4, hr=1.0),
    dict(rate=32, p50=32, p95=64, out=16, hit=0.8, occ=0.50, q=8, hr=0.0),
    dict(rate=64, p50=32, p95=64, out=4, hit=0.9, occ=0.70, q=2, hr=0.5),
    dict(rate=128, p50=8, p95=16, out=8, hit=0.5, occ=0.80, q=1, hr=1.0),
    dict(rate=256, p50=16, p95=32, out=16, hit=0.3, occ=0.90, q=0, hr=1.0),
]
_SIG_MID = dict(rate=48, p50=16, p95=32, out=8,
                hit=0.5, occ=0.4, q=2, hr=1.0)

_CTRL_FLAGS = ("serve_control_mode", "serve_control_store",
               "serve_control_model", "serve_control_conf",
               "serve_control_epoch_s", "tuning_record",
               "tuning_measurements", "tuning_model", "tuning_mode",
               "disagg_prefill_replicas")


@pytest.fixture
def ctrl_flags():
    snap = {k: pt.flags.get_flag(k) for k in _CTRL_FLAGS}
    yield pt.flags
    pt.flags.set_flags(snap)
    sv_control.invalidate_model_cache()


def _seed_store(path, flags) -> list:
    """A deterministic store snapshot: goodput = mult * (10 + rate), with
    ARM_FAST always 2x ARM_SLOW — every key ranks the arms identically,
    so the trained group's holdout rank accuracy is exact."""
    flags.set_flags({"tuning_record": "on"})
    for sig in _REGIMES:
        for arm, mult in ((ARM_FAST, 2.0), (ARM_HAND, 1.5), (ARM_SLOW, 1.0)):
            assert sv_control.record_row(
                sig, arm, mult * (10.0 + sig["rate"]),
                source="sweep", tool=True, path=path)
    return list(learned.iter_records(path))


def _engine(**kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("pool_pages", 32)
    kw.setdefault("max_inflight", 2)
    return ServingEngine(decoder_tiny(), seed=0, **kw)


# -- spellings ---------------------------------------------------------------

def test_regime_key_round_trip():
    key = sv_control.regime_key(_SIG_MID)
    sig = sv_control.parse_regime(key)
    assert sig is not None
    assert sv_control.regime_key(sig) == key  # bucketing is idempotent
    assert sv_control.parse_regime("rate=8 p50=16") is None
    assert sv_control.parse_regime("not a regime") is None


def test_regime_key_featurizes():
    key = sv_control.regime_key(_SIG_MID)
    f = features.featurize("serving.control", key, "-")
    assert len(f) == len(features.feature_names("serving.control")) == 8


def test_knob_key_round_trip():
    key = sv_control.knob_key(ARM_FAST)
    assert sv_control.parse_knobs(key) == ARM_FAST
    assert sv_control.parse_knobs("mi=4 dk=0") is None
    assert sv_control.parse_knobs("conv:igemm") is None  # foreign arm


def test_sweep_arms_deterministic_and_hand_first():
    a1 = sv_control.sweep_arms(6, seed=3, include=ARM_HAND)
    a2 = sv_control.sweep_arms(6, seed=3, include=ARM_HAND)
    assert a1 == a2
    assert a1[0] == ARM_HAND
    keys = [sv_control.knob_key(a) for a in a1]
    assert len(set(keys)) == len(keys)
    mis = {a["mi"] for a in a1}
    assert len(mis) >= 2  # stratified over the dominant axis


# -- training + proposals from a fixed snapshot ------------------------------

def test_store_row_shape(tmp_path, ctrl_flags):
    store = str(tmp_path / "ctrl.jsonl")
    recs = _seed_store(store, ctrl_flags)
    assert len(recs) == 3 * len(_REGIMES)
    rec = recs[0]
    assert rec["op"] == "serving.control"
    assert rec["dtype"] == "-"
    assert sv_control.parse_knobs(rec["arm"]) is not None
    assert sv_control.parse_regime(rec["shape_key"]) is not None
    # seconds per goodput token: argmin time == argmax goodput
    assert rec["median_s"] == pytest.approx(
        1.0 / (2.0 * (10.0 + _REGIMES[0]["rate"])))


def test_record_row_gating(tmp_path, ctrl_flags):
    store = str(tmp_path / "gated.jsonl")
    ctrl_flags.set_flags({"tuning_record": "off"})
    assert not sv_control.record_row(_SIG_MID, ARM_FAST, 100.0,
                                     tool=True, path=store)
    ctrl_flags.set_flags({"tuning_record": "on"})
    assert not sv_control.record_row(_SIG_MID, ARM_FAST, 0.0,
                                     tool=True, path=store)  # no goodput
    assert sv_control.record_row(_SIG_MID, ARM_FAST, 100.0,
                                 tool=True, path=store)


def test_train_is_deterministic_and_proposals_reproduce(tmp_path,
                                                        ctrl_flags):
    store = str(tmp_path / "ctrl.jsonl")
    recs = _seed_store(store, ctrl_flags)
    m1 = learned.train_model(recs, seed=0)
    m2 = learned.train_model(list(learned.iter_records(store)), seed=0)
    p1, p2 = str(tmp_path / "m1.json"), str(tmp_path / "m2.json")
    learned.save_model(m1, p1)
    learned.save_model(m2, p2)
    with open(p1, "rb") as f1, open(p2, "rb") as f2:
        assert f1.read() == f2.read()  # byte-identical retrain
    group = m1["groups"]["serving.control|cpu"]
    assert group["holdout"]["rank_acc"] >= 0.6
    ctrl_flags.set_flags({"serve_control_mode": "shadow"})
    k1, i1 = sv_control.propose(_SIG_MID, model=m1)
    k2, i2 = sv_control.propose(_SIG_MID, model=m2)
    assert (k1, i1["tier"]) == (k2, "learned")
    assert k1 == ARM_FAST  # the 2x arm wins every regime


def test_confidence_gate_fallback_ladder(tmp_path, ctrl_flags):
    store = str(tmp_path / "ctrl.jsonl")
    model = learned.train_model(_seed_store(store, ctrl_flags), seed=0)
    hand = sv_control.hand_knobs()
    ctrl_flags.set_flags({"serve_control_mode": "off"})
    k, info = sv_control.propose(_SIG_MID, model=model)
    assert (k, info["reason"]) == (hand, "off")
    ctrl_flags.set_flags({"serve_control_mode": "shadow"})
    missing = str(tmp_path / "nope.json")
    ctrl_flags.set_flags({"serve_control_model": missing})
    sv_control.invalidate_model_cache()
    k, info = sv_control.propose(_SIG_MID)
    assert (k, info["reason"]) == (hand, "no_model")
    # foreign device: regimes never transfer across device kinds
    k, info = sv_control.propose(_SIG_MID, model=model, dev="tpu")
    assert (k, info["reason"]) == (hand, "no_group")
    # a confidence floor above the group's holdout accuracy refuses
    ctrl_flags.set_flags({"serve_control_conf": 1.01})
    k, info = sv_control.propose(_SIG_MID, model=model)
    assert (k, info["reason"]) == (hand, "accuracy")


# -- the actuator's safety rails ---------------------------------------------

def test_staged_config_adopts_only_at_idle_boundary():
    eng = _engine()
    eng.warmup_decode(24)
    eng.submit([1, 2, 3], 4)
    eng.step()
    assert eng.propose_config({"mi": 4, "sq": 16}) is True
    eng.step()
    # in-flight work pins the old config: no torn reconfiguration
    assert eng.max_inflight == 2 and eng.shed_queue_depth == 0
    while eng.has_work():
        eng.step()
    eng.submit([4, 5, 6], 4)  # admit boundary: idle engine adopts
    assert eng.max_inflight == 4 and eng.shed_queue_depth == 16
    assert eng.stats["control.applies"] == 1
    assert eng.stats["control.rewarmups"] == 1  # bucket geometry moved
    while eng.has_work():
        eng.step()
    # an actuated engine holds the hand-configured engine's hard zeros
    assert eng.leaked_pages() == 0 and eng.audit_pool()[0] == []


def test_rewarmup_leaves_zero_compiles_on_serving_path():
    from paddle_tpu.pipeline import jit_compile_counter

    eng = _engine()
    eng.warmup_decode(24)
    eng.submit([1, 2, 3], 4)
    while eng.has_work():
        eng.step()
    eng.propose_config({"mi": 4})
    eng.submit([7, 8, 9], 4)  # adoption + re-warmup compile here
    assert eng.max_inflight == 4
    assert eng.stats["control.rewarmups"] == 1
    with jit_compile_counter() as c:
        for i in range(3):  # fill the widened batch: every bucket to 4
            eng.submit([10 + i, 2, 3, 4, 5], 4)
        while eng.has_work():
            eng.step()
    assert c.count == 0  # the actuated geometry was fully pre-warmed


def test_same_config_proposal_clears_pending():
    eng = _engine()
    assert eng.propose_config({"mi": 4}) is True
    assert eng._pending_ecfg is not None
    assert eng.propose_config({"mi": 2}) is False  # back to current
    assert eng._pending_ecfg is None
    assert eng.maybe_adopt_config() is False
    assert eng.stats["control.applies"] == 0


def test_propose_config_clamps_and_ignores_construction_knobs():
    eng = _engine()
    before = sv_control.engine_knobs(eng)
    eng.propose_config({"mi": 0, "dk": -3, "so": 250,
                        "pc": 1 - before["pc"], "sp": 1 - before["sp"]})
    assert eng.maybe_adopt_config() is True
    assert eng.max_inflight == 1  # floor, not zero
    assert eng.draft_k == 0
    assert eng.shed_occupancy == 1.0  # percent clamped into [0, 1]
    after = sv_control.engine_knobs(eng)
    # construction-only knobs never move through the actuator
    assert (after["pc"], after["sp"]) == (before["pc"], before["sp"])


def test_controller_shadow_never_applies(ctrl_flags, monkeypatch):
    ctrl_flags.set_flags({"serve_control_mode": "shadow"})
    eng = _engine()
    monkeypatch.setattr(
        sv_policy, "propose",
        lambda sig, **kw: (dict(ARM_FAST),
                           {"tier": "learned", "arm": "fake", "times": {}}))
    ctrl = sv_controller.Controller(epoch_s=1.0)
    assert ctrl.tick(eng, now=100.0) is False  # first sight opens window
    assert ctrl.tick(eng, now=100.5) is False  # not due yet
    assert ctrl.tick(eng, now=101.5) is True
    assert ctrl.last_info[id(eng)]["tier"] == "learned"
    assert eng._pending_ecfg is None  # shadow proposes, never stages
    assert eng.stats["control.applies"] == 0


def test_controller_apply_stages_then_engine_adopts(ctrl_flags,
                                                    monkeypatch):
    ctrl_flags.set_flags({"serve_control_mode": "apply"})
    eng = _engine()
    eng.warmup_decode(24)
    monkeypatch.setattr(
        sv_policy, "propose",
        lambda sig, **kw: (dict(ARM_FAST),
                           {"tier": "learned", "arm": "fake", "times": {}}))
    ctrl = sv_controller.Controller(epoch_s=1.0)
    ctrl.tick(eng, now=100.0)
    assert ctrl.tick(eng, now=101.5) is True
    assert eng._pending_ecfg is not None  # staged, not yet live
    assert eng.max_inflight == 2
    eng.submit([1, 2, 3], 2)  # idle boundary adopts the staged config
    assert eng.max_inflight == ARM_FAST["mi"]
    assert eng.shed_queue_depth == ARM_FAST["sq"]
    assert eng.degrade_after == ARM_FAST["da"]
    while eng.has_work():
        eng.step()
    assert eng.leaked_pages() == 0 and eng.audit_pool()[0] == []


def test_controller_off_mode_skips_epochs(ctrl_flags):
    ctrl_flags.set_flags({"serve_control_mode": "off"})
    eng = _engine()
    ctrl = sv_controller.Controller(epoch_s=1.0)
    ctrl.tick(eng, now=100.0)
    assert ctrl.tick(eng, now=105.0) is False  # due, but the mode is off


def test_engine_config_snapshot_is_single_source():
    eng = _engine(shed_queue_depth=8, shed_occupancy=0.95, degrade_after=2)
    cfg = eng.engine_config
    assert (cfg.max_inflight, cfg.shed_queue_depth,
            cfg.shed_occupancy, cfg.degrade_after) == (2, 8, 0.95, 2)
    assert eng.max_inflight == 2 and eng.shed_queue_depth == 8
    assert cfg.bucket_geometry() == (2, 0)


# -- fleet: role prior + placement costs -------------------------------------

def _pd_row(pd, median_s, fleet_n=3):
    return {"op": "serving.control", "shape_key": "r",
            "arm": sv_control.knob_key(dict(ARM_HAND, pd=pd)),
            "median_s": median_s, "fleet_n": fleet_n}


def test_role_split_prior_picks_best_recorded_pd(ctrl_flags):
    ctrl_flags.set_flags({"serve_control_mode": "shadow"})
    rows = [_pd_row(1, 0.002), _pd_row(1, 0.002),
            _pd_row(2, 0.004), _pd_row(2, 0.005)]
    n_pre, info = sv_control.role_split_prior(3, records=rows)
    assert (n_pre, info["tier"]) == (1, "learned")
    # rows from another fleet size are not comparable work
    n_pre, info = sv_control.role_split_prior(
        3, records=[_pd_row(1, 0.001, fleet_n=4)])
    assert (n_pre, info["reason"]) == (0, "no_rows")


def test_role_split_prior_fallbacks(ctrl_flags):
    ctrl_flags.set_flags({"serve_control_mode": "shadow",
                          "disagg_prefill_replicas": 1})
    n_pre, info = sv_control.role_split_prior(3, records=[])
    assert (n_pre, info["reason"]) == (1, "no_rows")
    # the recorded best IS the hand flag: nothing to override
    rows = [_pd_row(1, 0.002), _pd_row(2, 0.004)]
    n_pre, info = sv_control.role_split_prior(3, records=rows)
    assert (n_pre, info["reason"]) == (1, "hand_best")
    # a best within the near-tie band defers to the flag
    rows = [_pd_row(1, 0.00100), _pd_row(2, 0.00097)]
    n_pre, info = sv_control.role_split_prior(3, records=rows)
    assert (n_pre, info["reason"]) == (1, "tie_band")
    ctrl_flags.set_flags({"serve_control_mode": "off"})
    n_pre, info = sv_control.role_split_prior(3, records=rows)
    assert (n_pre, info["reason"]) == (1, "off")


def test_router_placement_costs_neutral_unless_apply(ctrl_flags):
    from paddle_tpu.serving import FleetRouter

    ctrl_flags.set_flags({"serve_control_mode": "shadow"})
    with FleetRouter(lambda role=None: _engine(), 2,
                     heartbeat_s=30.0) as fr:
        costs = fr._placement_costs(fr.replicas)
        assert set(costs.values()) == {1.0}  # shadow: plain least-loaded
        ctrl_flags.set_flags({"serve_control_mode": "apply"})
        e0, e1 = fr.replicas[0].engine, fr.replicas[1].engine
        e0._ctrl.last_cost[id(e0)] = 0.002
        costs = fr._placement_costs(fr.replicas)
        assert set(costs.values()) == {1.0}  # one prediction missing
        e1._ctrl.last_cost[id(e1)] = 0.004
        costs = fr._placement_costs(fr.replicas)
        assert costs[fr.replicas[0].rid] == pytest.approx(0.002)
        assert costs[fr.replicas[1].rid] == pytest.approx(0.004)
