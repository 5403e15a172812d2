"""benchmark/readers/kernel_roofline.py and labelled_counter_spread.py:
first on planes small enough to reckon by hand, then on the trace recorded
on the chip (tests/benchmark/data/tiny_trace.xplane.pb)."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.readers import (kernel_roofline,  # noqa: E402
                               labelled_counter_spread)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PLANES = {
    "devices": {0: [("moe_top1_experts_decode", 100, 600),
                    ("fusion f32[8]", 600, 700),
                    ("moe_top1_experts_decode", 700, 1200),
                    ("moe_top1_experts_prefill", 1200, 2200),
                    ("moe_top1_experts_decode", 2900, 3400)],   # past the mark
                1: [("moe_top1_experts_decode", 0, 50)]},
    "host": [("bench.trace_slice", 0, 3000)],
}


def test_calls_in_slice_counts_whole_calls_of_the_first_device():
    s, n = kernel_roofline.calls_in_slice(PLANES, "^moe_top1_experts_decode")
    assert n == 2 and s == pytest.approx(1000e-9)
    assert kernel_roofline.calls_in_slice(PLANES, "^nothing") == (0.0, 0)
    unmarked = dict(PLANES, host=[])
    assert kernel_roofline.calls_in_slice(
        unmarked, "^moe_top1_experts_decode")[1] == 3


def _result(tmp_path, counters, trace=True):
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True, exist_ok=True)
    os.symlink(os.path.join(DATA, "tiny_trace.xplane.pb"),
               run / "host.xplane.pb")
    ctx = types.SimpleNamespace(
        trace_dir=str(tmp_path), peaks={"hbm_bytes_per_s": 819e9},
        config={"kernel_bytes": {"page": 4096},
                "engine": {"config_kwargs": {"num_experts": 16}}})
    return types.SimpleNamespace(ctx=ctx, counters=counters,
                                 trace={"window_s": 1.0} if trace else None)


def test_roofline_on_the_recorded_trace(tmp_path):
    """The recorded trace holds 4 `custom-call f32[1024,1024]` a chip; the
    reading is bytes a call over the seconds a call took over the peak."""
    planes = tr.read_planes(os.path.join(DATA, "tiny_trace.xplane.pb"))
    seconds, count = kernel_roofline.calls_in_slice(planes, "^custom-call")
    assert count == 4 and seconds > 0
    per_call = 1024 * 1024 * 4
    res = _result(tmp_path, {"bytes": per_call * 10, "calls": 10})
    got = kernel_roofline.read(res, "^custom-call", "bytes", "calls",
                               "hbm_bytes_per_s")
    assert got == pytest.approx(per_call / (seconds / count) / 819e9 * 100)
    assert got > 0
    # a counter of something else than bytes is scaled by the config's key
    res = _result(tmp_path / "b", {"pages": 10240, "calls": 10})
    assert kernel_roofline.read(
        res, "^custom-call", "pages", "calls", "hbm_bytes_per_s",
        bytes_per_work="kernel_bytes.page") == pytest.approx(got)


def test_nothing_to_read_returns_none(tmp_path):
    read = kernel_roofline.read
    args = ("^custom-call", "bytes", "calls", "hbm_bytes_per_s")
    full = {"bytes": 1, "calls": 1}
    assert read(_result(tmp_path / "a", {}), *args) is None     # the parent
    assert read(_result(tmp_path / "b", full, trace=False), *args) is None
    assert read(_result(tmp_path / "c", full), "^no_such_kernel",
                *args[1:]) is None
    empty = _result(tmp_path / "d", full)
    empty.ctx.trace_dir = str(tmp_path / "nowhere")
    assert read(empty, *args) is None


def test_labelled_counter_spread():
    read = labelled_counter_spread.read
    pop = "engine.config_kwargs.num_experts"
    ctx = types.SimpleNamespace(config={"engine": {"config_kwargs": {
        "num_experts": 16}}})
    res = types.SimpleNamespace(ctx=ctx, counters={
        "serving.moe.tokens{expert=0}": 30, "serving.moe.tokens{expert=7}": 10,
        "serving.moe.tokens_other": 99})
    # 40 tokens over 16 experts: mean 2.5, the busiest took 30
    assert read(res, "serving.moe.tokens", pop) == pytest.approx(12.0)
    res.counters = {f"serving.moe.tokens{{expert={e}}}": 5 for e in range(16)}
    assert read(res, "serving.moe.tokens", pop) == pytest.approx(1.0)
    res.counters = {}
    assert read(res, "serving.moe.tokens", pop) is None
