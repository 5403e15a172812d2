"""The per-layer metrics that read the span tree of a serving step and the
executor's stages (ISSUE 23): every one of them is printed by a rehearsal of
its cell, reads a series the program declares, and stands in the relation to
the older readings that makes it worth reading. A CPU rehearsal proves names
and relations; it gives no speed."""
import contextlib
import functools
import io
import json
import os
import re
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark import trace_reduce  # noqa: E402
from benchmark.harness import layer_metric_spec, load_json  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
MANIFEST = load_json(ROOT, "BENCHMARK.json")
CHAT, SAT = "bert_base_decoder.chat.r80", "bert_base_decoder.sessions.sat"
TRAIN = "bert_base.s128"
STEP_PHASES = ("step_max_ms", "device_wait_max_ms", "decode_host_ms",
               "prefill_host_ms", "admit_self_ms")
# what a rehearsal on the CPU must print; `gc_pause_max_ms` needs a
# collection inside the window (every one is observed: hundreds in a traced
# 30 s window on the chip, perhaps none in a rehearsal's second) and
# `paged_decode_share` needs a device trace
PRINTED = ([(CHAT, m) for m in STEP_PHASES]
           + [(SAT, m + ".sat") for m in STEP_PHASES]
           + [(TRAIN, "host_prepare_ms")])
NEW = sorted({m for _, m in PRINTED}
             | {"gc_pause_max_ms", "gc_pause_max_ms.sat",
                "paged_decode_share", "paged_decode_share.sat"})


@functools.lru_cache(maxsize=None)
def _traced_rehearsal(cell: str) -> dict:
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", cell, "--seed", "2147483659",
                             "--seconds", "1", "--trace", "1", "--rehearse"])
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True
    return {k: v["value"] for k, v in line["metrics"].items()}


@pytest.mark.parametrize("cell,metric", PRINTED,
                         ids=[f"{c}:{m}" for c, m in PRINTED])
def test_a_rehearsal_prints_the_metric(cell, metric):
    metrics = _traced_rehearsal(cell)
    assert metric in metrics and metrics[metric] > 0


# the older readings of the saturated BERT cell go by `.bert` since PR 32
# (the cell is judged on `sat_tok_s.bert`, a bound of its own)
@pytest.mark.parametrize("cell,sfx,old", [(CHAT, "", ""),
                                          (SAT, ".sat", ".bert")])
def test_the_new_readings_split_the_old_ones(cell, sfx, old):
    m = _traced_rehearsal(cell)
    # the host's part of a step is part of the step
    assert m["decode_host_ms" + sfx] < m["decode_step_ms" + old]
    assert m["prefill_host_ms" + sfx] < m["prefill_step_ms" + old]
    # step() runs inside the benchmark's loop iteration
    assert m["step_max_ms" + sfx] <= m["loop_iter_max_ms" + old]
    # one read-back is inside one step
    assert m["device_wait_max_ms" + sfx] <= m["step_max_ms" + sfx]
    # the collector's pauses are the process's, inside a step or not: the
    # reading may exceed the longest step (the benchmark's own loop and the
    # trace reduction allocate too), so it is only held to the window
    if "gc_pause_max_ms" + sfx in m:
        assert 0.0 < m["gc_pause_max_ms" + sfx] < 1000.0


@pytest.mark.parametrize("metric", NEW)
def test_the_metric_reads_what_the_program_declares(metric):
    from paddle_tpu.observability import schema

    entry = next(e for e in MANIFEST["per_layer"] if e["name"] == metric)
    spec = layer_metric_spec(BENCH, metric)
    args = spec["args"]
    if spec["reader"] == "registry_histogram":
        assert entry["source"] == "program_span"
        kind = dict((s[0], s[1]) for s in schema.DECLARED)[args["series"]]
        # a stage's histogram goes by the stage's own name
        assert kind in (schema.HISTOGRAM, schema.STAGE)
        assert args["stat"] in ("mean", "max") and args["scale"] == 1000.0
    elif spec["reader"] == "stage_seconds":
        assert entry["source"] == "program_span"
        assert args["stage"] in schema.STAGE_NAMES
    else:
        assert spec["reader"] == "trace_op_share"
        assert entry["source"] == "device_trace"
        re.compile(args["pattern"])
    # the twin of a serving metric points at the saturated cell's rate
    if metric.endswith(".sat"):
        assert entry["moves"] == "sat_tok_s.bert"
        assert entry["workloads"] == [SAT]


def test_the_paged_kernel_is_found_by_name_in_a_reduced_trace():
    """The name the chip's compiler gives the kernel's instruction (read
    from a v5e compile of this PR: the `name=` of the `pallas_call`), cut by
    `op_key` the way every op is, matched by the metric's pattern."""
    from benchmark.readers import trace_op_share

    hlo = ("%paged_decode_attention.1 = f32[64,12,64]{2,1,0:T(8,128)S(1)} "
           "custom-call(%copy-done.1, %copy-done.2, %copy-done, %copy.9, "
           "%copy.10), custom_call_target=\"tpu_custom_call\"")
    key = trace_reduce.op_key(hlo)
    assert key == "paged_decode_attention f32[64,12,64]"
    pattern = layer_metric_spec(BENCH, "paged_decode_share")["args"]["pattern"]
    result = types.SimpleNamespace(trace={
        "window_s": 3.0,
        "op_self_s": {key: 0.3, "copy f32[3072,16,12,64]": 2.0,
                      "_call f32[64,12,64]": 0.5}})
    assert trace_op_share.read(result, pattern) == pytest.approx(10.0)
    assert trace_op_share.read(types.SimpleNamespace(trace=None),
                               pattern) is None
