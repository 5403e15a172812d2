"""The vocabulary test (ISSUE 35): every `layer_metrics` file that names a
path or a module of the program is held, on the CPU, to what the rehearsal
configuration of each cell it lists compiles. A PR that drops a piece, a
name scope or a Program's name fails here instead of silencing a metric on
the chip. Also the reader's `None`s."""
import glob
import json
import os
import re
import sys
import types

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import layer_metric_spec  # noqa: E402
from benchmark.readers import trace_device_time_share as reader  # noqa: E402
from paddle_tpu import executor as ex  # noqa: E402
from paddle_tpu import profiler  # noqa: E402

HERE = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
READER = "trace_device_time_share"


def _entries():
    """(metric name, its reader's args, the cells it lists) for every entry
    of BENCHMARK.json this reader serves."""
    out = []
    for m in MANIFEST["per_layer"]:
        spec = layer_metric_spec(HERE, m["name"])
        if spec["reader"] == READER:
            out.append((m["name"], spec["args"], m["workloads"]))
    return out


ENTRIES = _entries()
CELLS = sorted({c for _, _, cells in ENTRIES for c in cells})


def _rehearsal_config(cell):
    with open(os.path.join(HERE, "workloads", cell + ".json")) as f:
        spec = json.load(f)
    return spec.get("rehearse", {}).get("config", spec["config"])


# cells that rehearse one configuration compile the same programs: one run
BY_CONFIG = {}
for _c in CELLS:
    BY_CONFIG.setdefault(_rehearsal_config(_c), _c)


@pytest.fixture(scope="module")
def compiled():
    """{rehearsal config: (module names, op paths)} of everything the
    cell's rehearsal compiled, read from the optimized HLO text of each
    compiled entry's first call."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    old = getattr(jax.config, flag)
    jax.config.update(flag, True)     # a cached executable keeps old names
    texts = []
    orig = ex.Executor._compile

    def spy(self, *a, **k):
        comp = orig(self, *a, **k)
        jfn, seen = comp.fn, []
        if not hasattr(jfn, "lower"):      # a host-op program's segments
            return comp

        def call(*args):
            if not seen:
                seen.append(True)
                texts.append(jfn.lower(*args).compile().as_text())
            return jfn(*args)

        comp.fn = call
        return comp

    ex.Executor._compile = spy
    out = {}
    try:
        for config, cell in BY_CONFIG.items():
            del texts[:]
            rc = bench_run.main(["--workload", cell, "--seed", "5",
                                 "--seconds", "1", "--trace", "0",
                                 "--rehearse"])
            assert rc == 0, cell
            modules = {re.match(r"HloModule (\S+?),", t).group(1)
                       for t in texts}
            paths = {profiler.op_path(n) for t in texts
                     for n in re.findall(r'op_name="([^"]+)"', t)} - {""}
            out[config] = (modules, paths)
    finally:
        ex.Executor._compile = orig
        jax.config.update(flag, old)
    return out


def test_every_cell_of_the_reader_rehearses():
    assert {"rehearse_keye", "rehearse_zaya", "rehearse_laguna",
            "rehearse_decoder", "rehearse_encoder"} == set(BY_CONFIG)


@pytest.mark.parametrize("name,args,cells", ENTRIES,
                         ids=[e[0] for e in ENTRIES])
def test_pattern_finds_what_the_rehearsal_compiles(compiled, name, args,
                                                   cells):
    assert args["of"] in ("window", "busy")
    assert ("paths" in args) != ("modules" in args), name
    for cell in cells:
        modules, paths = compiled[_rehearsal_config(cell)]
        if "modules" in args:
            found = [m for m in modules if re.search(args["modules"], m)]
        else:
            found = [p for p in paths if re.search(args["paths"], p)]
        assert found, (f"{name}: nothing the rehearsal of {cell} compiles "
                       f"matches {args}")


def test_serving_modules_are_called_after_their_programs(compiled):
    from paddle_tpu.observability import schema

    for config in ("rehearse_keye", "rehearse_zaya", "rehearse_laguna",
                   "rehearse_decoder"):
        served = {m for m in compiled[config][0] if "serving" in m}
        # a cell whose prompts all stand behind a cached prefix never runs
        # the cold prefill program, one without hits never a window
        assert "jit_serving_decode" in served, config
        assert served & {"jit_serving_prefill", "jit_serving_window"}, config
        assert served <= {"jit_" + n for n in schema.PROGRAM_NAMES}
    assert "jit_train_step" in compiled["rehearse_encoder"][0]


def test_served_stacks_declare_their_pieces(compiled):
    """What each block's decode step books its device time to: the shared
    vocabulary, the pieces the block has."""
    want = {
        "rehearse_keye": ("sparse_moe_stack", {
            "embed", "proj", "kv_write", "indexer", "select", "kv_gather",
            "attend", "router", "experts", "head"}),
        "rehearse_zaya": ("cca_moe_stack", {
            "embed", "proj", "kv_write", "state", "attend", "router",
            "experts", "head"}),
        "rehearse_laguna": ("hybrid_moe_stack", {
            "embed", "proj", "kv_write", "attend", "router", "experts",
            "dense_ffn", "head"})}
    for config, (op, pieces) in want.items():
        paths = compiled[config][1]
        got = {p.split("/")[-1] for p in paths
               if p.startswith(f"{op}/decode/")}
        assert pieces <= got, (config, pieces - got)
        modes = {p.split("/")[1] for p in paths if p.startswith(op + "/")
                 and "/" in p}
        assert {"decode", "window"} <= modes, (config, modes)


def test_metric_files_of_the_reader_are_all_listed():
    files = {os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(HERE, "layer_metrics", "*.json"))
        if json.load(open(p))["reader"] == READER}
    listed = {n for n, _, _ in ENTRIES}
    assert files == {n.split(".")[0] for n in listed}
    for m in MANIFEST["per_layer"]:
        if m["name"] in listed:
            assert m["source"] == "device_trace" and m["unit"] == "%"


PR33 = ["full_attend_share", "window_attend_share",
        "full_attend_roofline", "window_attend_roofline",
        "moe_experts_roofline.laguna", "moe_experts_share.laguna",
        "window_over_global_pages", "experts_touched_mean.laguna",
        "expert_load_max_over_mean.laguna", "decode_host_ms.laguna",
        "prefill_host_ms.laguna", "step_max_ms.laguna",
        "device_wait_max_ms.laguna", "admit_self_ms.laguna"]


ISSUE35 = ["indexer_piece_share", "select_piece_share",
           "kv_gather_piece_share", "attend_piece_share",
           "prefill_device_share", "prefill_device_share.chat",
           "prefill_device_share.bert", "optimizer_op_share",
           "mlm_head_share", "scoped_device_share.train",
           "scoped_device_share.serve", "scoped_device_share.sat",
           "scoped_device_share.bert"]


def test_pr33_entries_stand_together():
    """This PR appends to `per_layer` and changes nothing before: PR 33's
    fourteen entries stand next to each other, in their order, for their
    one cell, and this reader's entries, the issue's thirteen among them,
    come behind them. Order and adjacency only, no length and no tail:
    the next PR appends behind these as this one did behind PR 33's (the
    accepted test that pins PR 33's to the list's END is marked in
    conftest.py; everything else it asserts is asserted here)."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    at = names.index(PR33[0])
    assert names[at:at + len(PR33)] == PR33
    mine = [n for n, _, _ in ENTRIES]
    assert set(ISSUE35) <= set(mine)
    assert all(names.index(n) >= at + len(PR33) for n in mine)
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in PR33:
        assert per_layer[name]["workloads"] == ["laguna_xs2.agent16k.sat"]
        assert per_layer[name]["moves"] == "sat_tok_s"
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells.index("laguna_xs2.agent16k.sat") == \
        cells.index("bert_base.s512") + 1
    config = next(c for c in MANIFEST["configs"] if c["name"] == "laguna_xs2")
    assert config["reduced"] == ["num_hidden_layers"]


# -- (5) the reader's Nones ------------------------------------------------------

def _result(tmp_path, trace=True):
    ctx = types.SimpleNamespace(trace_dir=str(tmp_path))
    return types.SimpleNamespace(trace={"window_s": 1.0} if trace else None,
                                 ctx=ctx)


def test_reader_returns_none_without_a_trace(tmp_path):
    reader.report.cache_clear()
    assert reader.read(_result(tmp_path, trace=False), of="window",
                       paths="x") is None
    # a run that traced but left no file (or no device plane: the CPU)
    assert reader.read(_result(tmp_path), of="window", paths="x") is None


def test_reader_returns_none_not_zero_on_no_match(tmp_path, monkeypatch):
    report = {"window_s": 2.0, "busy_s": 1.0,
              "paths": {"mlm_head/matmul": {"self_s": 0.5},
                        "adam": {"self_s": 0.25},
                        "unscoped/copy f32[8]": {"self_s": 0.25}},
              "modules": {"jit_serving_decode(7)": {"self_s": 0.75},
                          "jit_serving_window(9)": {"self_s": 0.25}}}
    monkeypatch.setattr(reader, "report", lambda trace_dir: report)
    res = _result(tmp_path)
    assert reader.read(res, of="window", paths="no_such_piece") is None
    assert reader.read(res, of="window", modules="^jit_fn") is None
    assert reader.read(res, of="window", paths="(^|/)mlm_head(/|$)") == 25.0
    assert reader.read(res, of="busy", paths="^(?!unscoped(/|$))") == 75.0
    assert reader.read(res, of="window",
                       modules="^jit_serving_(?!decode)") == 12.5
    # a program without `profiler.device_time` (the parent): nothing, quietly
    monkeypatch.undo()
    reader.report.cache_clear()
    monkeypatch.delattr(profiler, "device_time")
    assert reader.read(res, of="window", paths="adam") is None
    reader.report.cache_clear()
