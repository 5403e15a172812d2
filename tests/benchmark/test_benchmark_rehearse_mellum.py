"""`benchmark/run.py --rehearse` for the cell PR 58 added: the training
decoder's whole path on the CPU at a tiny size (`models/decoder_moe.py`
through `train_steps`, unedited: a sliding and a full grouped-query layer,
top-2 of 8 experts with 4 held, the sliced head's loss, bfloat16 AMP Adam,
the plain reference `mellum2_lm`, the contract line), a planted fault caught
by the cell's own comparison, and what BENCHMARK.json says of it. The
manifest's entries are asserted by MEMBERSHIP and relative order: nothing
here pins the end of a list or counts the cells or the configurations, behind
which the contract tells every later PR to append."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import load_json  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
MANIFEST = load_json(ROOT, "BENCHMARK.json")
CELL, CONFIG = "mellum2_12b_a2_5b.s8k", "mellum2_12b_a2_5b"
BEFORE = "bert_base.s512"       # the training cell it stands behind
# `per_layer` is full (128 of 128), so the cell brings no entry: it joins
# the lists of the accepted readers that find something in a training cell
JOINED = {"mfu": "train_items_s", "host_dispatch_ms": "train_items_s",
          "host_prepare_ms": "train_items_s",
          "device_idle_share.train": "train_items_s",
          "scoped_device_share.train": "train_items_s",
          "optimizer_op_share": "train_items_s",
          "window_compiles": "setup_s"}


def _rehearse(capsys, trace, seed="2147483659"):
    rc = bench_run.main(["--workload", CELL, "--seed", seed, "--seconds",
                         "1", "--trace", str(trace), "--rehearse"])
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
    assert want == {"train_items_s", "setup_s"}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert notes["window_compiles"] == 0 and notes["items_per_step"] == 64
    assert set(line["compared"]) == {"loss_gap", "update_cosine_short",
                                     "update_rms_off", "loss_rise",
                                     "window_compiles"}
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], name


def test_rehearsal_reads_what_the_lists_it_joined_read(capsys):
    from paddle_tpu import observability as obs

    rc, line, notes = _rehearse(capsys, trace=1)
    assert rc == 0 and line["correct"] is True
    got = line["metrics"]
    # the host's spans and the compile counter read on the CPU; the
    # trace-fed ones find no device operation and are left out, and mfu
    # has no peak to divide by
    assert got["window_compiles"]["value"] == 0
    assert got["host_dispatch_ms"]["value"] > 0
    assert got["host_prepare_ms"]["value"] > 0
    assert not {"device_idle_share.train", "scoped_device_share.train",
                "optimizer_op_share", "mfu", "mlm_head_share"} & set(got)
    assert len(MANIFEST["per_layer"]) == 128
    # the decoder's own counters, handed over as device arrays every step
    # and read after the window (the runner resets `train.` in front of it)
    counts = {k: v for k, v in obs.snapshot()["counters"].items()
              if k.startswith("train.moe.") or k.startswith("train.attn.")}
    made, held = (counts["train.moe.assignments"],
                  counts["train.moe.held_assignments"])
    assert made == notes["steps"] * 64 * 2 * 2 and 0.3 < held / made < 0.9
    assert counts["train.moe.dropped"] == 0
    assert sum(v for k, v in counts.items()
               if k.startswith("train.moe.expert_tokens{")) == held
    assert counts['train.attn.key_blocks_causal{kind="sliding"}'] \
        == notes["steps"] * 2


@pytest.mark.parametrize("fault, over", [
    ("window_off_by_one", {"loss_gap"}),
    ("capacity", {"loss_gap", "update_cosine_short"})])
def test_a_planted_fault_fails_the_cells_own_comparison(capsys, fault, over):
    from tools import decoder_faults

    with decoder_faults.FAULTS[fault]():
        rc, line, notes = _rehearse(capsys, trace=0, seed="77")
    assert rc == 0 and line["correct"] is False
    failed = {name for name, c in line["compared"].items()
              if c["value"] > c["limit"]}
    assert failed and failed <= over | {"loss_gap", "update_cosine_short"}, \
        (failed, notes)


def test_the_cell_is_the_issues():
    cell = load_json(BENCH, "workloads", CELL + ".json")
    t = cell["traffic"]
    assert cell["runner"] == "train_steps" and cell["chips"] == 1
    assert cell["config"] == CONFIG
    assert t["symbols"] == {"seq_len": 8192} and t["ring"] == 8
    assert t["rows_per_chip"] in (2, 4)
    assert t["trace_slice_s"] == 3.0 and t["reference_block_rows"] == 1
    assert cell["rehearse"]["config"] == "rehearse_mellum"


def test_the_configuration_is_the_published_one_cut_to_a_chips_share():
    spec = load_json(BENCH, "configs", CONFIG + ".json")
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "sliding_window": 1024,
        "tie_word_embeddings": False, "use_sliding_window": True}
    for key, value in published.items():
        assert spec[key] == value, key
    assert spec["layer_types"] == (["sliding_attention"] * 3
                                   + ["full_attention"]) * 7
    assert spec["mlp_layer_types"] == ["sparse"] * 28
    assert spec["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert spec["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert (spec["num_hidden_layers"], spec["num_experts"],
            spec["vocab_size"]) == (4, 16, 24576)
    assert spec["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                 "vocab_size": 98304}
    assert spec["source"] == ("https://huggingface.co/JetBrains/"
                              "Mellum2-12B-A2.5B-Instruct/blob/main/"
                              "config.json")
    for key in ("qk_norm", "rotary", "window", "router", "norms",
                "optimizer"):
        assert spec["assumed"][key], key
    assert len(spec["departures"]) >= 3 and spec["deployment"]
    assert "7 pipeline stages" in spec["reduced_how"]
    # what the trainer is built from says the same as the published keys
    kw = spec["config_kwargs"]
    assert (kw["hidden_size"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["sliding_window"], kw["expert_width"]) \
        == (2304, 32, 4, 128, 1024, 896)
    assert (kw["num_experts"], kw["experts_per_token"], kw["experts_held"],
            kw["first_expert"], kw["vocab_size"]) == (64, 8, 16, 0, 24576)
    assert kw["layer_types"] == spec["layer_types"][:4]
    full = spec["rope_parameters"]["full_attention"]
    assert kw["yarn"] == [full["factor"],
                          full["original_max_position_embeddings"],
                          full["beta_fast"], full["beta_slow"],
                          full["attention_factor"]]
    assert kw["rope_theta"] == full["rope_theta"]
    assert spec["feeds"][0]["high"] == kw["vocab_size"]
    for key in ("fused_attention", "moe_experts"):
        assert spec["kernel_flops"][key] and spec["kernel_bytes"][key]


def test_the_model_flops_are_the_derivations():
    spec = load_json(BENCH, "configs", CONFIG + ".json")
    kw = spec["config_kwargs"]
    H, F = kw["hidden_size"], kw["expert_width"]
    qo, kv = kw["num_heads"] * kw["head_dim"], \
        kw["num_kv_heads"] * kw["head_dim"]
    attention = 2 * H * qo + 2 * H * kv
    router = H * kw["num_experts"]
    expert = 3 * H * F
    held = kw["experts_per_token"] * kw["experts_held"] / kw["num_experts"]
    layers = len(kw["layer_types"])
    matmul = layers * (attention + router + held * expert) \
        + H * kw["vocab_size"]
    assert matmul == 191692800
    S, W = 8192, kw["sliding_window"]
    mean_keys = (W * (W + 1) / 2 + (S - W) * W) / S
    sliding = kw["layer_types"].count("sliding_attention")
    pair = 12 * qo
    flops = spec["model_flops_per_item"]
    assert flops["times"] == {"seq_len": pair // 2}
    assert flops["constant"] == 6 * matmul + sliding * pair * mean_keys \
        + pair // 2
    assert spec["kernel_flops"]["attend_flops_per_visited_pair"] == pair
    assert spec["kernel_flops"]["experts_flops_per_held_assignment"] \
        == 6 * expert
    # the parameters the file counts are those the builder makes
    params = layers * (attention + router + kw["experts_held"] * expert
                       + 2 * H) + 2 * kw["vocab_size"] * H + H
    assert params == 595153152 and "595,153,152" in spec["reduced_how"]


def test_the_manifest_holds_the_cell_behind_the_training_cells():
    cells = [w["name"] for w in MANIFEST["workloads"]]
    configs = [c["name"] for c in MANIFEST["configs"]]
    assert cells.index(BEFORE) < cells.index(CELL)
    assert configs.index("bert_base") < configs.index(CONFIG)
    entry = MANIFEST["workloads"][cells.index(CELL)]
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "s8k",
                     "chips": 1, "why": entry["why"]}
    assert len(entry["why"]) <= 200
    for word in ("one chip of 4", "16 of 64 experts", "1/4 vocab",
                 "no exchange"):
        assert word in entry["why"], word
    config = MANIFEST["configs"][configs.index(CONFIG)]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    train = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == "train_items_s")
    assert train["workloads"].index(BEFORE) < train["workloads"].index(CELL)
    assert train["bound"] == 0.01
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, moves in JOINED.items():
        lists = by_name[name]["workloads"]
        assert by_name[name]["moves"] == moves
        assert lists.index(BEFORE if BEFORE in lists else lists[0]) \
            < lists.index(CELL), name
    # and no other list: the head's accepted reader matches BERT's scope
    assert {m["name"] for m in MANIFEST["per_layer"]
            if CELL in m.get("workloads", [])} == set(JOINED)
