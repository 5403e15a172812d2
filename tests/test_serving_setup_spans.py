"""An engine's set-up as a span tree (ISSUE 49): `ServingEngine.__init__`
and `warmup_decode` under `setup.*`, each signature's first dispatch under
`executor.first_dispatch`, the compiler's events under it, and the one view
that reads them, `tools/obs.py setup`. A CPU run proves names, nesting,
counts and sums; it gives no speed."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import JsonlWriter
from paddle_tpu.serving import DecoderConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OBS_TOOL = os.path.join(REPO, "tools", "obs.py")

BUILD = "setup.engine_build"
LATTICE = "setup.decode_lattice"
ENTRY = LATTICE + ".entry"


@pytest.fixture(scope="module")
def setup_run(tmp_path_factory):
    """A rehearsal-size engine built, its lattice warmed, two requests
    served and the stats reset, with the stream written to a file: the
    records, the file, the lattice's count and the registry on either side
    of the reset."""
    path = str(tmp_path_factory.mktemp("obs") / "obs.jsonl")
    writer, recs = JsonlWriter(path), []
    obs.reset("setup.engine_build")
    obs.reset("setup.decode_lattice")
    obs.attach_sink(writer)
    obs.attach_sink(recs.append)
    try:
        cfg = DecoderConfig(vocab_size=211, hidden_size=32, num_layers=2,
                            num_heads=4, ffn_size=128, max_position=64)
        eng = ServingEngine(cfg, page_size=4, pool_pages=64, max_inflight=4,
                            seed=5)
        lattice = eng.warmup_decode(40)
        rng = np.random.default_rng(0)
        for n in (3, 9):
            eng.submit(rng.integers(1, 200, size=n).tolist(),
                       max_new_tokens=3)
        eng.run_until_drained()
        before = obs.snapshot()
        eng.reset_stats()
    finally:
        obs.detach_sink(writer)
        obs.detach_sink(recs.append)
        writer.close()
    return {"recs": recs, "path": path, "lattice": lattice,
            "before": before, "snap": obs.snapshot()}


def _spans(run, name):
    return [r for r in run["recs"] if r["type"] == "span"
            and r["name"] == name]


def _report(run) -> dict:
    spec = importlib.util.spec_from_file_location("obs_tool", OBS_TOOL)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool.setup_report(run["recs"])


def _entries(run) -> list[dict]:
    return [r["payload"] for r in run["recs"] if r["name"] == "compile.entry"]


def _rows(report) -> dict:
    return {tuple(r["path"]): r for r in report["tree"]}


@pytest.mark.parametrize("child", ["programs", "startup", "pools"])
def test_the_engines_build_is_a_span_with_its_three_children(setup_run,
                                                             child):
    (build,) = _spans(setup_run, BUILD)
    assert "parent" not in build
    (span,) = _spans(setup_run, f"{BUILD}.{child}")
    assert span["parent"] == BUILD
    assert span["dur_s"] <= build["dur_s"]
    rows = _rows(_report(setup_run))
    assert rows[(BUILD, f"{BUILD}.{child}")]["count"] == 1
    if child == "startup":      # the weights' program compiles under it
        assert rows[(BUILD, f"{BUILD}.startup", "pipeline.dispatch",
                     "executor.first_dispatch")]["count"] == 1


def test_the_lattice_has_an_entry_a_signature_each_with_one_first_dispatch(
        setup_run):
    n = setup_run["lattice"]
    assert n > 1
    (lattice,) = _spans(setup_run, LATTICE)
    entries = _spans(setup_run, ENTRY)
    assert len(entries) == n
    assert all(e["parent"] == LATTICE for e in entries)
    assert len({(e["attrs"]["rows"], e["attrs"]["pages"])
                for e in entries}) == n
    rows = _rows(_report(setup_run))
    under = (LATTICE, ENTRY)
    assert rows[under]["count"] == n
    assert rows[under + ("pipeline.prepare", "pipeline.compile")]["count"] == n
    first = under + ("pipeline.dispatch", "executor.first_dispatch")
    assert rows[first]["count"] == n
    # every signature was traced, lowered and compiled or read under it
    for phase in ("trace", "lower", "backend"):
        assert rows[first + (f"compile.{phase}",)]["count"] == n
    assert rows[under + ("pipeline.fetch",)]["count"] == n     # the wait
    firsts = [r for r in _spans(setup_run, "executor.first_dispatch")
              if r["attrs"]["program"] == "serving_decode"]
    assert sorted(r["attrs"]["entry"] for r in firsts) == list(range(n))
    assert sum(e["dur_s"] for e in entries) <= lattice["dur_s"]


def test_a_warm_signature_opens_no_first_dispatch(setup_run):
    """The served steps after the lattice hit its entries: the only
    first dispatches outside set-up are the two prefill programs'."""
    firsts = _spans(setup_run, "executor.first_dispatch")
    by_program = {}
    for r in firsts:
        by_program[r["attrs"]["program"]] = \
            by_program.get(r["attrs"]["program"], 0) + 1
    assert by_program.pop("serving_decode") == setup_run["lattice"]
    assert by_program.pop("fn") == 1            # the startup program
    assert set(by_program) <= {"serving_prefill", "serving_window"}
    decodes = _spans(setup_run, "serving.decode")
    assert decodes and len(firsts) < len(decodes) + setup_run["lattice"] + 4


def test_reset_stats_leaves_setup_and_compile_standing(setup_run):
    """`reset_stats()` ran at the fixture's end: `serving.`, `pipeline.`
    and `host.` are gone, the set-up's series stand, as the benchmark's
    `registry_view()` at a window's end needs them."""
    hist = setup_run["snap"]["histograms"]
    n = setup_run["lattice"]
    assert hist[BUILD + ".seconds"]["count"] == 1
    assert hist[LATTICE + ".seconds"]["count"] == 1
    assert hist[ENTRY + ".seconds"]["count"] == n
    assert hist["compile.backend.seconds"]["count"] >= n
    kept = [k for k in setup_run["before"]["histograms"]
            if k.startswith(("setup.", "compile.", "executor."))]
    assert {"compile.trace.seconds", "compile.lower.seconds"} <= set(kept)
    for k in kept:
        assert hist[k]["count"] \
            == setup_run["before"]["histograms"][k]["count"], k
    assert [k for k in setup_run["before"]["histograms"]
            if k.startswith(("serving.", "pipeline."))]
    assert hist["executor.first_dispatch.seconds"]["count"] >= n
    assert not [k for k in hist if k.startswith(("serving.", "pipeline."))]
    assert not [n for n in setup_run["snap"]["undeclared"]
                if n.startswith(("setup.", "compile.", "executor."))]


def test_the_setup_view_names_every_second_or_calls_it_unattributed(
        setup_run):
    def run(*args):
        return subprocess.run([sys.executable, OBS_TOOL, "setup", *args],
                              capture_output=True, text=True, timeout=120)

    r = run(setup_run["path"], "--json")
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout)
    roots = [row for row in rep["tree"] if len(row["path"]) == 1]
    assert {BUILD, LATTICE} <= {row["path"][0] for row in roots}
    # against the stream itself: set-up runs from the first record's start
    # to the end of the outermost span around the last compile of a
    # Program's function (a served step's prefill, after the lattice), and
    # on one thread what is named is what the parentless spans took
    spans = [r for r in setup_run["recs"] if r["type"] == "span"]
    t0 = min(r["ts"] - r["dur_s"] for r in spans)
    compiled = max(e["end"] for e in _entries(setup_run)
                   if e["fn"] != "other")
    t1 = min(r["ts"] for r in spans if "parent" not in r
             and r["ts"] >= compiled)
    assert rep["whole_s"] == pytest.approx(t1 - t0, abs=2e-3)
    outer = [r for r in spans if "parent" not in r and r["ts"] <= t1]
    assert rep["named_s"] == pytest.approx(
        sum(r["dur_s"] for r in outer), abs=2e-3)
    assert rep["unattributed_s"] == pytest.approx(
        (t1 - t0) - sum(r["dur_s"] for r in outer), abs=4e-3)
    # a root's rows hold its seconds, self second by self second
    for name in (BUILD, LATTICE):
        (raw,) = _spans(setup_run, name)
        assert sum(row["self_s"] for row in rep["tree"]
                   if row["path"][0] == name) == pytest.approx(
                       raw["dur_s"], abs=1e-6)
    assert 0 <= rep["unattributed_s"] < 0.1 * rep["whole_s"]
    decode = rep["compiles"]["serving_decode"]
    assert decode["entries"] == setup_run["lattice"]
    assert decode["hit"] + decode["miss"] + decode["off"] == decode["entries"]
    assert decode["trace_s"] > 0 and decode["lower_s"] > 0
    assert any(o["op"].startswith("serving_decode: ") and o["traces"]
               == setup_run["lattice"] for o in rep["ops"])

    r = run(setup_run["path"])
    assert r.returncode == 0, r.stderr
    for text in ("set-up:", ENTRY, "executor.first_dispatch", "unattributed",
                 "serving_decode", "cache_read_s", "tracing seconds by op"):
        assert text in r.stdout, text


def test_another_threads_records_do_not_break_the_tree(setup_run):
    """A second thread's spans written between the lattice's records (a
    housekeeping thread beside the set-up): every entry still hangs under
    the lattice, the other thread's spans are roots of their own, and what
    is named counts an overlapped second once."""
    spec = importlib.util.spec_from_file_location("obs_tool", OBS_TOOL)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    recs, n = [], 0
    for r in setup_run["recs"]:
        recs.append(r)
        if r["type"] == "span" and ENTRY in (r["name"], r.get("parent")):
            n += 1      # ended just after it, began before it did
            recs.append({"ts": r["ts"] + 1e-6, "type": "span",
                         "name": "serving.housekeeping",
                         "dur_s": r["dur_s"] + 0.01})
    alone, mixed = tool.setup_report(setup_run["recs"]), \
        tool.setup_report(recs)
    rows = _rows(mixed)
    assert rows[("serving.housekeeping",)]["count"] == n
    for path, row in _rows(alone).items():
        assert rows[path]["count"] == row["count"], path
        assert rows[path]["self_s"] == pytest.approx(row["self_s"]), path
    assert mixed["whole_s"] >= mixed["named_s"] >= alone["named_s"]
    assert mixed["unattributed_s"] >= 0
