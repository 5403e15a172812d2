"""The shares that find XLA's pieces of a `sparse_moe` step by kind and
shape, run over the operation names of both trees the patterns have seen:
`tests/benchmark/data/keye_op_names.json` holds the keys (`trace_reduce.
op_key`) of the device operations of one traced run of
`keye_vl2_30b_a3b.docs32k.sat` on the tree that stands (recorded on the chip,
PR 32) and what differs in PR 29's tree, where a decode row fetched a
selected token's K and V as two bfloat16 rows (PERF.md section 3). Every
pattern of every guarded share has to find an operation on the tree that
stands (a share with a silent pattern is left out of every traced line, as
`sparse_attend_share` was from PR 30 to PR 32), and the row gather's pattern
has to name the gather of either tree."""
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

from benchmark.harness import layer_metric_spec, load_json  # noqa: E402
from benchmark.readers import trace_op_share_found  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CELL = "keye_vl2_30b_a3b.docs32k.sat"
RECORDED = load_json(os.path.dirname(os.path.abspath(__file__)), "data",
                     "keye_op_names.json")
STANDS = RECORDED["stands"]["op_self_s"]
# PR 29's tree: the same operations but for the token-row gather
PR29 = {RECORDED["pr29"]["instead_of"].get(k, k): v
        for k, v in STANDS.items()}
GUARDED = ("indexer_share", "topk_select_share", "sparse_attend_share",
           "kv_row_gather_share")
PATTERNS = [(m, p) for m in GUARDED
            for p in layer_metric_spec(BENCH, m)["args"]["patterns"]]


def _result(ops: dict):
    return types.SimpleNamespace(trace={
        "window_s": RECORDED["stands"]["window_s"], "op_self_s": ops})


@pytest.mark.parametrize("metric,pattern", PATTERNS,
                         ids=[f"{m}:{i}" for i, (m, _) in enumerate(PATTERNS)])
def test_every_pattern_finds_an_operation_on_the_tree_that_stands(metric,
                                                                  pattern):
    assert [k for k in STANDS if re.search(pattern, k)], (metric, pattern)


@pytest.mark.parametrize("metric", GUARDED)
def test_the_share_reads_on_the_recorded_names(metric):
    args = layer_metric_spec(BENCH, metric)["args"]
    share = trace_op_share_found.read(_result(STANDS), **args)
    assert share is not None and 0.0 < share < 100.0
    assert share == pytest.approx(RECORDED["stands"]["shares"][metric],
                                  rel=2e-3)


@pytest.mark.parametrize("metric", ("sparse_attend_share",
                                    "kv_row_gather_share"))
def test_the_row_gather_is_named_on_either_tree(metric):
    """PR 30 replaced two gathers of `bf16[n,512]` rows by one of
    `s32[n,512]` words; one pattern names both."""
    assert set(RECORDED["pr29"]["instead_of"]) <= set(STANDS)
    args = layer_metric_spec(BENCH, metric)["args"]
    for ops in (STANDS, PR29):
        assert trace_op_share_found.read(_result(ops), **args) is not None
    # ...and PR 29's own first pattern is silent on the tree that stands
    old = r"^fusion bf16\[\d{5,},512\]"
    assert [k for k in PR29 if re.search(old, k)]
    assert not [k for k in STANDS if re.search(old, k)]


def test_the_decode_half_is_the_gather_plus_its_products():
    """`sparse_attend_share` = `kv_row_gather_share` + the two products over
    the gathered rows + the selection's index arithmetic + the windows'
    masked attention (PERF.md section 5)."""
    whole = RECORDED["stands"]["shares"]["sparse_attend_share"]
    gather = RECORDED["stands"]["shares"]["kv_row_gather_share"]
    assert gather < whole < 3.5 * gather


def test_nothing_of_the_cell_reads_a_paged_kernel():
    """The `sparse_moe` block runs no paged decode kernel in this cell, so
    the manifest lists no `paged_decode_share` for it (PR 32 took out
    `paged_decode_share.keye`, a constant 0)."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    assert not [k for k in STANDS if k.startswith("paged_decode_attention")]
    assert not [m["name"] for m in manifest["per_layer"]
                if m["name"].startswith("paged_decode_share")
                and CELL in m.get("workloads", [CELL])]
