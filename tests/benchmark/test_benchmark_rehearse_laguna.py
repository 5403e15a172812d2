"""`benchmark/run.py --rehearse` for the cells PR 33 added: the Laguna-XS.2
cell's whole path on the CPU at a tiny size (routed runner, the reference
that follows the engine's experts, two page pools, the contract line) and
what its traced line can carry without a device; and `bert_base.s512`, a
cell of a configuration the benchmark had, from data files alone."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
LAGUNA = "laguna_xs2.agent16k.sat"
S512 = "bert_base.s512"


def _rehearse(capsys, cell, trace):
    rc = bench_run.main(["--workload", cell, "--seed", "2147483659",
                         "--seconds", "1", "--trace", str(trace),
                         "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(ln[len("notes "):]) for ln in lines
                 if ln.startswith("notes "))
    return rc, json.loads(lines[-1]), notes


@pytest.mark.parametrize("cell", [LAGUNA, S512])
def test_rehearsal_ends_in_the_contract_line(capsys, cell):
    rc, line, notes = _rehearse(capsys, cell, trace=0)
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"] for m in MANIFEST["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert notes["window_compiles"] == 0


def test_laguna_rehearsal_reads_both_pools_and_follows_the_routes(capsys):
    rc, line, notes = _rehearse(capsys, LAGUNA, trace=1)
    assert rc == 0 and line["correct"] is True
    assert notes["sampled"] > 0 and notes["preemptions"] == 0
    assert notes["worst_gap"] <= notes["tolerance"]
    assert notes["worst_route_margin"] <= notes["route_margin_tolerance"]
    assert notes["leaked_pages"] == 0 and notes["audit_problems"] == 0
    # the counters behind the new per-layer metrics read on the CPU; the
    # trace-fed ones (shares, rooflines) find no device operation and are
    # left out of the line, as on a parent without the kernels
    got = line["metrics"]
    # a row maps at most 3 window pages and 10-20 pages of its whole context
    assert 0.0 < got["window_over_global_pages"]["value"] < 0.5
    assert 1.0 <= got["experts_touched_mean.laguna"]["value"] <= 8.0
    assert 1.0 <= got["expert_load_max_over_mean.laguna"]["value"] <= 8.0
    assert got["prefix_hit_rate"]["value"] > 0
    assert got["prefill_chunks_per_request"]["value"] >= 1.0
    assert got["batch_rows_mean"]["value"] >= 1.0
    assert got["window_compiles"]["value"] == 0
    for name in ("decode_host_ms.laguna", "prefill_host_ms.laguna",
                 "step_max_ms.laguna", "admit_self_ms.laguna",
                 "decode_step_ms.sat", "prefill_step_ms.sat"):
        assert got[name]["value"] > 0, name
    assert not {"full_attend_share", "window_attend_share",
                "full_attend_roofline", "window_attend_roofline",
                "moe_experts_roofline.laguna",
                "moe_experts_share.laguna"} & set(got)


def test_the_new_cells_metrics_are_the_issues():
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    new = ["full_attend_share", "window_attend_share",
           "full_attend_roofline", "window_attend_roofline",
           "moe_experts_roofline.laguna", "moe_experts_share.laguna",
           "window_over_global_pages", "experts_touched_mean.laguna",
           "expert_load_max_over_mean.laguna", "decode_host_ms.laguna",
           "prefill_host_ms.laguna", "step_max_ms.laguna",
           "device_wait_max_ms.laguna", "admit_self_ms.laguna"]
    # appended at the end of the list, in this order, for the one cell
    assert [m["name"] for m in MANIFEST["per_layer"]][-len(new):] == new
    for name in new:
        assert per_layer[name]["workloads"] == [LAGUNA]
        assert per_layer[name]["moves"] == "sat_tok_s"
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells[-2:] == [S512, LAGUNA]
    config = next(c for c in MANIFEST["configs"] if c["name"] == "laguna_xs2")
    assert config["reduced"] == ["num_hidden_layers"]
