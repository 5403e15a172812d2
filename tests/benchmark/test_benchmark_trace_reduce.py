"""benchmark/trace_reduce.py: first on planes small enough to reduce by
hand, then on a trace recorded on the chip (tests/benchmark/data/, made by
record_tiny_trace.py on four v5e chips) whose numbers were read off
benchmark/trace_dump.py's listing."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tiny_trace.xplane.pb")

# one chip, times in ns; the while holds three operations of its body
DEVICE_0 = [("fusion.1", 0, 100), ("while.7", 100, 400),
            ("fusion.2", 120, 200), ("all-reduce.1", 200, 260),
            ("fusion.3", 300, 380), ("copy.4", 600, 700)]
HOST = [("bench.trace_slice", 0, 1000), ("bench.step", 350, 620),
        ("serving.decode", 380, 610), ("bench.idle", 690, 1000)]


def _reduced(devices):
    return tr.reduce_planes({"devices": devices, "host": list(HOST)})


def test_busy_is_the_union_and_the_window_is_the_marked_slice():
    r = _reduced({0: DEVICE_0})
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(500e-9)      # [0,400] and [600,700]
    assert r["idle_share"] == pytest.approx(0.5)


def test_self_time_counts_no_second_twice():
    r = _reduced({0: DEVICE_0})
    ns = {k: round(v * 1e9) for k, v in r["op_self_s"].items()}
    assert ns == {"fusion.1": 100, "while.7": 80, "fusion.2": 80,
                  "all-reduce.1": 60, "fusion.3": 80, "copy.4": 100}
    assert sum(ns.values()) == 500                   # equals the busy time
    assert r["device_ops"][0][0] in ("fusion.1", "copy.4")
    assert r["device_ops"][0][1] == pytest.approx(100e-9)


def test_collective_self_time_is_the_exposed_part():
    r = _reduced({0: DEVICE_0})
    assert r["collective_exposed_s"] == pytest.approx(60e-9)


def test_gaps_go_to_the_most_specific_host_span():
    r = _reduced({0: DEVICE_0})
    # (400, 600) lies under bench.step and serving.decode: the shorter span
    # names it; (700, 1000) lies under bench.idle
    assert r["idle_gaps"] == [["bench.idle", pytest.approx(300e-9)],
                              ["serving.decode", pytest.approx(200e-9)]]


def test_a_gap_no_span_covers_is_unattributed():
    planes = {"devices": {0: [("fusion.1", 0, 100), ("fusion.2", 900, 1000)]},
              "host": [("bench.trace_slice", 0, 1000),
                       ("bench.step", 0, 300)]}
    r = tr.reduce_planes(planes)
    assert r["idle_gaps"] == [["host.unattributed", pytest.approx(800e-9)]]


def test_chips_are_averaged_and_ops_clipped_to_the_window():
    second = [("fusion.1", -50, 150), ("fusion.9", 900, 1100)]
    r = _reduced({0: DEVICE_0, 1: second})
    assert r["per_device"][1]["busy_s"] == pytest.approx(250e-9)
    assert r["busy_s"] == pytest.approx((500e-9 + 250e-9) / 2)
    assert r["op_self_s"]["fusion.1"] == pytest.approx((100e-9 + 150e-9) / 2)


def test_without_a_marked_slice_the_window_is_the_device_extent():
    r = tr.reduce_planes({"devices": {0: DEVICE_0}, "host": []})
    assert r["window_s"] == pytest.approx(700e-9)
    assert r["idle_share"] == pytest.approx(200 / 700)


def test_no_device_events_reduce_to_nothing():
    assert tr.reduce_planes({"devices": {}, "host": list(HOST)}) is None
    assert tr.reduce_planes({"devices": {0: []}, "host": []}) is None


def test_op_key_cuts_hlo_text_to_name_and_shape():
    assert tr.op_key(
        "%fusion.1180 = (bf16[768]{0:T(1024)(128)(2,1)}, f32[128,128]{1,0}) "
        "fusion(bf16[128,128,768]{2,1,0} %copy-done.55), kind=kOutput"
    ) == "fusion (bf16[768],..)"
    assert tr.op_key(
        "%copy.112 = f32[3072,16,12,64]{3,2,1,0:T(8,128)} copy(f32[3072,16,"
        "12,64]{0,3,2,1:T(8,128)} %rw_vals_0_.1)") == "copy f32[3072,16,12,64]"
    assert tr.op_key("%slice-start.3 = ((f32[1024,1024]{1,0}), f32[256,1024]"
                     "{1,0}, s32[]) async-start(x)") \
        == "slice-start (f32[1024,1024],..)"
    assert tr.COLLECTIVE.match(tr.op_key(
        "%all-reduce-start.3 = f32[768]{0} all-reduce-start(f32[768] %x)"))
    assert tr.op_key("paged_decode_kernel") == "paged_decode_kernel"


# --- the trace recorded on four v5e chips ----------------------------------
# tests/benchmark/record_tiny_trace.py: four steps of a data-parallel
# matmul + all-reduce + update, a 3 ms sleep under bench.idle after each.
# Hand-checked against benchmark/trace_dump.py's listing and raw sums of the
# events' durations (PR 22): no operation nests or overlaps in this trace,
# so a chip's busy time is the plain sum of its 60 op durations.
BUSY_NS = {0: 395114, 1: 391816, 2: 391901, 3: 387746}
ALL_REDUCE_NS = {0: 296073, 1: 292418, 2: 292528, 3: 288610}
SLICE_NS = 22859069         # the bench.trace_slice span


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_planes(tr.read_planes(RECORDED))


def test_recorded_planes_are_found():
    planes = tr.read_planes(RECORDED)
    assert sorted(planes["devices"]) == [0, 1, 2, 3]
    assert all(len(evs) == 60 for evs in planes["devices"].values())
    assert sorted({name for name, _, _ in planes["host"]}) == \
        ["bench.idle", "bench.step", "bench.trace_slice"]


def test_recorded_idle_share(recorded):
    assert recorded["window_s"] == pytest.approx(SLICE_NS / 1e9)
    for d, ns in BUSY_NS.items():
        assert recorded["per_device"][d]["busy_s"] == pytest.approx(ns / 1e9)
    mean_busy = sum(BUSY_NS.values()) / 4
    assert recorded["busy_s"] == pytest.approx(mean_busy / 1e9)
    assert recorded["idle_share"] == pytest.approx(1 - mean_busy / SLICE_NS)
    assert recorded["idle_share"] == pytest.approx(0.98287, abs=1e-5)


def test_recorded_top_op_and_collective_time(recorded):
    name, seconds = recorded["device_ops"][0]
    assert name == "all-reduce f32[1024,1024]"
    exposed = sum(ALL_REDUCE_NS.values()) / 4 / 1e9
    assert seconds == pytest.approx(exposed)
    assert recorded["collective_exposed_s"] == pytest.approx(exposed)
    assert recorded["device_ops"][1][0] == \
        "convolution_tanh_fusion bf16[512,1024]"


def test_recorded_gaps_are_attributed(recorded):
    """The chip idles 98% of the slice: while the host sleeps (bench.idle)
    and, less, inside bench.step (dispatch before and read-back after the
    0.1 ms of device work). Every idle nanosecond of chip 0 is attributed."""
    gaps = dict(recorded["idle_gaps"])
    assert list(gaps) == ["bench.idle", "bench.step"]
    assert gaps["bench.idle"] == pytest.approx(18.696291e-3)
    assert gaps["bench.step"] == pytest.approx(3.767664e-3)
    assert sum(gaps.values()) == pytest.approx((SLICE_NS - BUSY_NS[0]) / 1e9)
