"""`benchmark/run.py --rehearse` for the cell PR 29 added: the sparse
cell's whole path on the CPU at toy size (contexts of 42-70 tokens behind
40-token documents, an indexer keeping 8 positions, chunks of 16, 8 experts
top-2): the sparse runner, the reference that follows experts AND
selections, the contract line, and what its traced line can carry without
a device."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELL = "keye_vl2_30b_a3b.docs32k.sat"


def _rehearse(capsys, trace):
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483659",
                         "--seconds", "1", "--trace", str(trace),
                         "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    notes = next(json.loads(ln[len("notes "):]) for ln in lines
                 if ln.startswith("notes "))
    return rc, json.loads(lines[-1]), notes


def test_rehearsal_ends_in_the_contract_line(capsys):
    rc, line, notes = _rehearse(capsys, trace=0)
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"] for m in MANIFEST["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert want == {"sat_tok_s", "setup_s"}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert notes["window_compiles"] == 0


def test_rehearsal_follows_experts_and_selections(capsys):
    rc, line, notes = _rehearse(capsys, trace=1)
    assert rc == 0 and line["correct"] is True
    # only marked requests are graded, under all three limits
    assert 4 <= notes["sampled"] <= notes["marked_finished"]
    assert notes["worst_gap"] <= notes["tolerance"]
    assert notes["worst_route_margin"] <= notes["route_margin_tolerance"]
    assert len(notes["select_margin_tolerance"]) == 3 and all(
        got <= limit for got, limit in zip(
            notes["worst_select_margin_by_layer"],
            notes["select_margin_tolerance"]))
    # the sample was drawn before the window, and nothing else was marked;
    # the document's selection was followed too
    assert notes["marked"] == notes["marked_finished"] == 8
    assert notes["worst_route_margin_unfollowed"] == 0.0
    # the lattice started at the shortest context's page bucket
    assert notes["decode_lattice"] < 3 * 5
    got = line["metrics"]
    # contexts of 42-70 tokens keep 8: between a ninth and a fifth
    assert 11.0 <= got["selected_over_context"]["value"] <= 20.0
    assert got["prefill_chunks_per_request"]["value"] == 1.0
    assert 2.0 <= got["experts_touched_mean.keye"]["value"] <= 8.0
    assert 1.0 <= got["expert_load_max_over_mean.keye"]["value"] <= 4.0
    assert got["prefix_hit_rate"]["value"] > 50.0
    assert got["window_compiles"]["value"] == 0
    # the trace-fed ones find no device operation on the CPU and are left
    # out of the line, as on a parent without the program's new parts
    assert not {"indexer_share", "topk_select_share", "sparse_attend_share",
                "moe_experts_share.keye", "moe_experts_roofline.keye",
                "paged_decode_share.keye"} & set(got)


def test_the_cell_file_states_the_issues_traffic():
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as f:
        traffic = json.load(f)["traffic"]
    assert traffic["schedule_seed"] == 29
    assert traffic["shared"] == {"count": 4, "tokens": 32768, "zipf_a": 1.2}
    assert traffic["prompt"] == {"dist": "lognormal", "median": 96,
                                 "sigma": 0.6, "min": 32, "max": 256}
    assert traffic["output"]["median"] == 128 \
        and traffic["output"]["min"] == 32
    assert traffic["max_total"] == 33408
    assert traffic["accounting"] == "admitted"
    assert traffic["settle_s"] == 15.0 and traffic["trace_slice_s"] == 3.0


def test_the_configuration_keeps_every_published_width():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye_vl2_30b_a3b.json")) as f:
        spec = json.load(f)
    kw = spec["engine"]["config_kwargs"]
    assert spec["reduced"] == ["num_hidden_layers"]
    assert spec["num_hidden_layers"] == 48 and kw["num_layers"] == 6
    for key, field in (("hidden_size", "hidden_size"),
                       ("num_attention_heads", "num_heads"),
                       ("num_key_value_heads", "num_kv_heads"),
                       ("head_dim", "attn_head_dim"),
                       ("moe_intermediate_size", "ffn_size"),
                       ("num_experts", "num_experts"),
                       ("num_experts_per_tok", "experts_per_token"),
                       ("vocab_size", "vocab_size"),
                       ("rope_theta", "rope_theta"),
                       ("rms_norm_eps", "rms_norm_eps")):
        assert spec[key] == kw[field], key
    sa = spec["sa_config"]
    assert (sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]) \
        == (kw["index_heads"], kw["index_head_dim"], kw["index_topk"])
    assert sa["indexer_num_kv_heads"] == 1
    assert sa["q_chunk_size"] == kw["prefill_chunk"] == 512
    # the bytes the rooflines are reckoned from
    kb = spec["kernel_bytes"]
    assert kb["moe_call_bytes"] == 128 * 3 * 2048 * 768 * 2
    assert kb["indexer_token_bytes"] == kw["index_head_dim"] * 2
    assert kb["kv_token_bytes"] == 2 * 4 * 128 * 2


def test_a_renamed_piece_leaves_its_share_out_of_the_line():
    """The XLA pieces of a step are found by kind and shape; a share whose
    pieces are not ALL found reads None (the line then lacks it), never a
    smaller number that `better: lower` would book as a gain."""
    import types

    from benchmark.readers import trace_op_share_found as reader

    trace = {"window_s": 2.0,
             "op_self_s": {"fusion bf16[131072,512]": 0.4,
                           "fusion f32[64,8,2048]": 0.1,
                           "sort (f32[64,36864],..)": 0.3}}
    result = types.SimpleNamespace(trace=trace)
    both = [r"^fusion bf16\[\d{5,},512\]", r"^fusion f32\[\d+,8,2048\]"]
    assert reader.read(result, both) == 25.0
    assert reader.read(result, both + [r"^fusion bf16\[\d+,1,8,128\]"]) \
        is None
    assert reader.read(types.SimpleNamespace(trace=None), both) is None


def test_the_trace_fed_shares_of_the_cell_are_guarded():
    from benchmark.harness import layer_metric_spec

    bench = os.path.join(ROOT, "benchmark")
    for name in ("indexer_share", "topk_select_share",
                 "sparse_attend_share"):
        spec = layer_metric_spec(bench, name)
        assert spec["reader"] == "trace_op_share_found"
        assert spec["args"]["patterns"]
