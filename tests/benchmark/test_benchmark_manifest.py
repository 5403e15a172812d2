"""BENCHMARK.json against the contract it is written to, and against the
files it names. The harness is driven by data: a later PR adds a cell, a
configuration or a per-layer metric as new files and new entries, and edits
no file that exists — so no harness file may hold a name of the manifest."""
import glob
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import layer_metric_spec  # noqa: E402
from benchmark.harness import load_json as _load  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


MANIFEST = _load(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
LAYER_METRICS = [m["name"] for m in MANIFEST["per_layer"]]


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert MANIFEST["command"][-1] == "benchmark/run.py"
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # the full check with all 24 cells has to fit the driver's budget
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    assert 2 <= len(CELLS) <= 24 and len(set(CELLS)) == len(CELLS)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_four_chip_cells_are_rare():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert len(four) <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_has_its_files(cell):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell) and NAME.match(entry["traffic"])
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    spec = _load(BENCH, "workloads", cell + ".json")
    assert spec["config"] == entry["config"]
    assert spec["chips"] == entry["chips"]
    assert cell == f"{entry['config']}.{entry['traffic']}"
    assert os.path.isfile(os.path.join(BENCH, "runners",
                                       spec["runner"] + ".py"))
    assert entry["config"] in {c["name"] for c in MANIFEST["configs"]}
    # every cell reports setup_s, another end-to-end metric, a per-layer one
    e2e = [m["name"] for m in MANIFEST["end_to_end"]
           if cell in m.get("workloads", [cell])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in m.get("workloads", [cell]) and m["moves"] in e2e
               for m in MANIFEST["per_layer"])


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_says_which_runner_number_a_name_of_its_own_reports(cell):
    """One quantity judged under two bounds takes two end-to-end entries;
    the second name is spelt out in the cell's file (`reports`), is an
    end-to-end metric that lists the cell, and stands for a number that
    another end-to-end entry already names."""
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    reports = _load(BENCH, "workloads", cell + ".json").get("reports", {})
    for name, of in reports.items():
        assert cell in e2e[name]["workloads"], name
        assert of in e2e and of != name and e2e[of]["unit"] == e2e[name]["unit"]


def test_an_end_to_end_name_the_runner_does_not_give_is_an_error():
    from benchmark.run import end_to_end_value

    values = {"sat_tok_s": 4000.0, "setup_s": 60.0}
    cell = {"reports": {"sat_tok_s.bert": "sat_tok_s"}}
    assert end_to_end_value(values, cell, "sat_tok_s.bert") == 4000.0
    assert end_to_end_value(values, {}, "setup_s") == 60.0
    for name in ("sat_tok_s.brt", "sat_tok_s.zaya", "serve_tok_s"):
        with pytest.raises(KeyError):
            end_to_end_value(values, cell, name)
    with pytest.raises(KeyError):
        end_to_end_value(values, {}, "sat_tok_s.bert")


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_config_has_its_file(config):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{config}.json"
    spec = _load(ROOT, entry["file"])
    assert spec["source"] == entry["source"] and len(entry["source"]) <= 200
    assert spec["reduced"] == entry["reduced"]
    assert any(w["config"] == config for w in MANIFEST["workloads"])
    module = spec["reference"]["module"].replace(".", os.sep) + ".py"
    assert os.path.isfile(os.path.join(ROOT, module))


def test_bert_base_flops_follow_their_derivation():
    spec = _load(BENCH, "configs", "bert_base.json")
    kw = spec["config_kwargs"]
    h, f, v, n = (kw["hidden_size"], kw["ffn_size"], kw["vocab_size"],
                  kw["num_layers"])
    assert h == spec["published"]["hidden_size"]
    assert f == spec["published"]["intermediate_size"]
    assert n == spec["published"]["num_hidden_layers"]
    assert v == spec["published"]["vocab_size"]
    flops = spec["model_flops_per_item"]
    assert flops["constant"] == 6 * (n * (4 * h * h + 2 * h * f) + h * v)
    assert flops["times"] == {"seq_len": 12 * n * h}


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metric_entries(kind):
    names = [m["name"] for m in MANIFEST[kind]]
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST[kind]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        if kind == "end_to_end":
            assert set(m) <= {"name", "unit", "better", "bound", "source",
                              "workloads"}
            assert 0.01 <= m["bound"] <= 0.1
            assert m["source"] in ("host_clock", "device_trace")
        else:
            assert set(m) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
            assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
    assert {"setup_s"} <= e2e


@pytest.mark.parametrize("metric", LAYER_METRICS)
def test_layer_metric_has_its_reader(metric):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    spec = layer_metric_spec(BENCH, metric)
    # a metric `x.y` without a file of its own reads `x.json`
    assert metric == spec["name"] or metric.startswith(spec["name"] + ".")
    assert spec["unit"] == entry["unit"]
    assert os.path.isfile(os.path.join(BENCH, "readers",
                                       spec["reader"] + ".py"))


def test_harness_code_holds_no_name_of_the_manifest():
    """Adding a cell, a configuration or a metric needs no edit to code:
    run.py, the harness, the runners and the readers find files by name."""
    names = set(CELLS) | set(LAYER_METRICS) \
        | {c["name"] for c in MANIFEST["configs"]}
    code = [os.path.join(BENCH, f) for f in ("run.py", "harness.py")]
    code += glob.glob(os.path.join(BENCH, "readers", "*.py"))
    for path in code:
        with open(path) as f:
            src = f.read()
        held = [n for n in names if re.search(
            r"[\"']" + re.escape(n) + r"[\"']", src)]
        assert not held, f"{path} names {held}"


def test_every_data_file_belongs_to_the_manifest_or_the_rehearsal():
    """No cell or metric file that BENCHMARK.json does not list (a stale
    file would look like a cell nobody measures)."""
    on_disk = {os.path.basename(p)[:-5] for p in
               glob.glob(os.path.join(BENCH, "workloads", "*.json"))}
    assert on_disk == set(CELLS)
    on_disk = {os.path.basename(p)[:-5] for p in
               glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))}
    assert on_disk == {layer_metric_spec(BENCH, m)["name"]
                       for m in LAYER_METRICS}
