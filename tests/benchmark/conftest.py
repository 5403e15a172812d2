"""One accepted test of this directory pins the TAIL of BENCHMARK.json's
`per_layer` list to the entries PR 33 appended, so it fails for every later
PR that appends entries of its own, as the contract tells it to ("put new
entries at the end of their lists"), and a PR that is not a `benchmark` PR
may not edit a file this directory already has. Until a `benchmark` PR
loosens that assertion to "in this order, next to each other", the test is
expected to fail on it and is marked so here (PR 35; ROADMAP D10). The mark
is STRICT: the day that test passes again this directory's run fails until
the mark is deleted, so it cannot outlive its reason. What that test asserts
besides the tail (the fourteen entries' order, cell and `moves`, the last
two cells, the configuration's `reduced`) is asserted, unmasked, by
test_benchmark_device_names.py::test_pr33_entries_stand_together."""
import pytest

PINS_THE_TAIL = ("test_benchmark_rehearse_laguna.py::"
                 "test_the_new_cells_metrics_are_the_issues")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(PINS_THE_TAIL):
            item.add_marker(pytest.mark.xfail(
                reason="pins per_layer's tail to PR 33's entries; PR 35 "
                       "appended 13 behind them", strict=True))
