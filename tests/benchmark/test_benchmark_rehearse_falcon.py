"""`benchmark/run.py --rehearse` for the cell PR 37 added: the Falcon-H1
cell's whole path on the CPU at a tiny size (the `parallel_ssm` family, the
slot pool and its snapshots behind `serve_open_loop_cut`, the plain
reference `falcon_h1_lm`, the contract line), and what BENCHMARK.json says
of it. (`bert_base_decoder.chat.sat`, which ISSUE 37 asked for beside it,
was left out: its sets of six spread by more than half its bound on the
chip; PERF.md section 7.)"""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import layer_metric_spec, load_json  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
MANIFEST = load_json(ROOT, "BENCHMARK.json")
FALCON = "falcon_h1_34b.chat.sat"
# the per-layer entries this PR appended, in their order
NEW = ["ssm_update_share", "ssm_scan_share", "attend_share.falcon",
       "mlp_share.falcon", "head_share.falcon", "ssm_update_roofline",
       "paged_decode_gqa_roofline.falcon", "state_restores_per_request",
       "state_recomputed_share", "decode_host_ms.falcon",
       "prefill_host_ms.falcon", "step_max_ms.falcon",
       "device_wait_max_ms.falcon", "admit_self_ms.falcon"]
PIECES = {"ssm_update_share": "ssm_update", "ssm_scan_share": "ssm_scan",
          "attend_share.falcon": "attend", "mlp_share.falcon": "mlp",
          "head_share.falcon": "head"}


def _rehearse(capsys, cell, trace):
    rc = bench_run.main(["--workload", cell, "--seed", "2147483659",
                         "--seconds", "1", "--trace", str(trace),
                         "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(ln[len("notes "):]) for ln in lines
                 if ln.startswith("notes "))
    return rc, json.loads(lines[-1]), notes


def test_rehearsal_ends_in_the_contract_line(capsys):
    cell = FALCON
    rc, line, notes = _rehearse(capsys, cell, trace=0)
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"] for m in MANIFEST["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert notes["window_compiles"] == 0 and notes["preemptions"] == 0
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], name


def test_falcon_rehearsal_resumes_snapshots_and_agrees_with_the_reference(
        capsys):
    rc, line, notes = _rehearse(capsys, FALCON, trace=1)
    assert rc == 0 and line["correct"] is True
    assert notes["sampled"] > 0 and notes["worst_gap"] <= notes["tolerance"]
    got = line["metrics"]
    # the counters behind the new per-layer metrics read on the CPU; the
    # trace-fed ones (shares, rooflines) find no device operation and are
    # left out of the line, as on a parent without the family
    assert 0.5 <= got["state_restores_per_request"]["value"] <= 1.0
    assert 0.0 <= got["state_recomputed_share"]["value"] < 0.5
    assert got["prefix_hit_rate"]["value"] > 20
    assert got["prefill_chunks_per_request"]["value"] >= 1.0
    assert got["chained_step_share.sat"]["value"] > 50
    assert got["window_compiles"]["value"] == 0
    for name in ("decode_host_ms.falcon", "prefill_host_ms.falcon",
                 "step_max_ms.falcon", "admit_self_ms.falcon"):
        assert got[name]["value"] > 0, name
    assert not {"ssm_update_share", "ssm_scan_share", "ssm_update_roofline",
                "paged_decode_gqa_roofline.falcon"} & set(got)


def test_the_falcon_cell_is_the_issues():
    cell = load_json(BENCH, "workloads", FALCON + ".json")
    t = cell["traffic"]
    # `serve_open_loop` with one difference: a row the settle time cut while
    # it was being served is not judged (outputs reach 1,024 tokens)
    assert cell["runner"] == "serve_open_loop_cut" and cell["chips"] == 1
    assert t["schedule_seed"] == 37 and t["max_total"] == 3072
    assert t["shared"] == {"count": 4, "tokens": 1024, "zipf_a": 1.2}
    assert t["prompt"] == {"dist": "lognormal", "median": 192, "sigma": 0.8,
                           "min": 32, "max": 1024}
    assert t["output"] == {"dist": "lognormal", "median": 320, "sigma": 0.6,
                           "min": 64, "max": 1024}
    assert (t["accounting"], t["settle_s"], t["trace_slice_s"]) \
        == ("admitted", 10.0, 3.0)
    assert t["arrivals"]["process"] == "poisson"


def test_the_configuration_keeps_every_published_key_but_the_depth():
    spec = load_json(BENCH, "configs", "falcon_h1_34b.json")
    published = {
        "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_size": 5120, "intermediate_size": 21504,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_d_conv": 4, "mamba_d_head": 128, "mamba_d_ssm": 4096,
        "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
        "mamba_n_heads": 32, "max_position_embeddings": 262144,
        "mlp_expansion_factor": 8, "num_attention_heads": 20,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-05,
        "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
        "ssm_out_multiplier": 0.08838834764831845, "vocab_size": 261120,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738]}
    for key, value in published.items():
        assert spec[key] == value, key
    assert spec["reduced"] == ["num_hidden_layers"]
    assert spec["num_hidden_layers"] == 6
    kw = spec["engine"]["config_kwargs"]
    assert kw["block"] == "parallel_ssm" and kw["num_layers"] == 6
    assert (kw["hidden_size"], kw["ffn_size"], kw["vocab_size"]) \
        == (5120, 21504, 261120)
    assert (kw["num_heads"], kw["num_kv_heads"], kw["attn_head_dim"]) \
        == (20, 4, 128)
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_groups"],
            kw["ssm_state"], kw["ssm_conv"], kw["ssm_chunk"]) \
        == (32, 128, 2, 256, 4, 128)
    for name in ("embedding_multiplier", "lm_head_multiplier",
                 "ssm_in_multiplier", "ssm_out_multiplier",
                 "attention_in_multiplier", "attention_out_multiplier",
                 "key_multiplier", "mlp_multipliers", "ssm_multipliers"):
        assert kw[name] == spec[name], name
    # the bytes the rooflines divide by follow their derivation
    kb = spec["kernel_bytes"]
    assert kb["ssm_row_layer_bytes"] == 2 * (32 * 256 * 128 * 4
                                             + 3 * 5120 * 4)
    assert kb["ssd_token_layer_flops"] == (
        2 * 128 * 256 * 2 + 2 * 128 * 128 * 32 + 2 * 2 * 256 * 128 * 32)
    assert kb["kv_page_bytes"] == 128 * 2 * 4 * 128 * 2
    # weights and pools: at least 12 GB of the chip
    weights = 2 * (6 * 430_120_000 + 2 * 1_336_934_400)
    pools = 80 * 6 * 4_255_744 \
        + spec["engine"]["pool_pages"] * 6 * kb["kv_page_bytes"]
    assert weights + pools >= 12e9


def test_the_new_entries_stand_at_the_end_in_their_order():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [FALCON]
        assert per_layer[name]["moves"] == "sat_tok_s"
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells[-1] == FALCON and len(cells) == 9
    assert MANIFEST["configs"][-1]["name"] == "falcon_h1_34b"
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert MANIFEST["workloads"][-1]["chips"] == 1


@pytest.mark.parametrize("metric", sorted(PIECES))
def test_a_piece_share_names_a_piece_the_family_declares(metric):
    from paddle_tpu.observability import schema

    spec = layer_metric_spec(BENCH, metric)
    assert spec["reader"] == "trace_device_time_share"
    assert spec["args"]["of"] == "window"
    piece = PIECES[metric]
    assert piece in schema.PIECES
    for mode in ("decode", "window"):
        path = f"parallel_ssm_stack/{mode}/{piece}"
        assert re.search(spec["args"]["paths"], path)
        assert re.search(spec["args"]["paths"], "serving/" + path + "/dot")
    assert not re.search(spec["args"]["paths"],
                         "hybrid_moe_stack/decode/" + piece)


def test_the_update_roofline_reads_the_kernel_by_its_name():
    from benchmark import trace_reduce
    from paddle_tpu.observability import schema

    spec = layer_metric_spec(BENCH, "ssm_update_roofline")
    args = spec["args"]
    assert spec["reader"] == "kernel_roofline"
    declared = dict((s[0], s[1]) for s in schema.DECLARED)
    assert declared[args["work"]] == declared[args["calls"]] == schema.COUNTER
    hlo = ("%ssm_decode_update.1 = (f32[480,8192,128]{2,1,0:T(8,128)}, "
           "f32[64,32,128]{2,1,0:T(8,128)}) custom-call(%a, %b), "
           "custom_call_target=\"tpu_custom_call\"")
    assert re.search(args["pattern"], trace_reduce.op_key(hlo))
    config = load_json(BENCH, "configs", "falcon_h1_34b.json")
    keys = args["bytes_per_work"].split(".")
    assert config[keys[0]][keys[1]] == 8511488
    spec = layer_metric_spec(BENCH, "paged_decode_gqa_roofline.falcon")
    assert spec["name"] == "paged_decode_gqa_roofline"
    for name, num, den in (
            ("state_restores_per_request", "serving.state.restores",
             "serving.prefills"),
            ("state_recomputed_share", "serving.state.recomputed_tokens",
             "serving.prefill_tokens_computed")):
        args = layer_metric_spec(BENCH, name)["args"]
        assert args["numerator"] == [num] and den in args["denominator"]
        assert all(n in declared for n in
                   args["numerator"] + args["denominator"])


def test_the_rehearsal_compiles_the_names_the_shares_read():
    """What `test_benchmark_device_names.py` holds every accepted pattern
    to, for the Falcon cell's entries: the rehearsal's compiled programs
    carry the five pieces' paths, under the decode or the window mode, and
    the module of every program the engine builds is declared."""
    import jax

    from paddle_tpu import executor as ex
    from paddle_tpu import profiler
    from paddle_tpu.observability import schema

    flag = "jax_compilation_cache_include_metadata_in_key"
    old = getattr(jax.config, flag)
    jax.config.update(flag, True)
    texts, orig = [], ex.Executor._compile

    def spy(self, *a, **k):
        comp = orig(self, *a, **k)
        jfn, seen = comp.fn, []
        if not hasattr(jfn, "lower"):
            return comp

        def call(*args):
            if not seen:
                seen.append(True)
                texts.append(jfn.lower(*args).compile().as_text())
            return jfn(*args)

        comp.fn = call
        return comp

    ex.Executor._compile = spy
    try:
        rc = bench_run.main(["--workload", FALCON, "--seed", "5",
                             "--seconds", "1", "--trace", "0", "--rehearse"])
    finally:
        ex.Executor._compile = orig
        jax.config.update(flag, old)
    assert rc == 0
    modules = {re.match(r"HloModule (\S+?),", t).group(1) for t in texts}
    paths = {profiler.op_path(n) for t in texts
             for n in re.findall(r'op_name="([^"]+)"', t)} - {""}
    served = {m for m in modules if "serving" in m}
    assert {"jit_serving_decode", "jit_serving_window",
            "jit_serving_state_copy"} <= served
    assert served <= {"jit_" + n for n in schema.PROGRAM_NAMES}
    for metric in PIECES:
        rx = layer_metric_spec(BENCH, metric)["args"]["paths"]
        assert [p for p in paths if re.search(rx, p)], metric
    decode = {p.split("/")[-1] for p in paths
              if p.startswith("parallel_ssm_stack/decode/")}
    assert {"embed", "proj", "conv", "ssm_update", "kv_write", "attend",
            "mlp", "head"} <= decode
    window = {p.split("/")[-1] for p in paths
              if p.startswith("parallel_ssm_stack/window/")}
    assert {"ssm_scan", "kv_gather", "conv"} <= window
    for entry in ("prefill_device_share", "scoped_device_share.sat"):
        args = layer_metric_spec(BENCH, entry)["args"]
        found = [m for m in modules if re.search(args["modules"], m)] \
            if "modules" in args else \
            [p for p in paths if re.search(args["paths"], p)]
        assert found, entry
