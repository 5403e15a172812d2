"""`benchmark/run.py --rehearse` for the cell PR 39 added: the
DeepSeek-V3.2-Exp cell's whole path on the CPU at a tiny size (the
`latent_moe` family, its latent and indexer-key pools behind
`serve_open_loop_sparse`, the plain reference `deepseek_v32_lm` following
the engine's experts and selection, the contract line), planted faults
caught by the cell's own comparison, and what BENCHMARK.json says of it."""
import json
import os
import re
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import layer_metric_spec, load_json  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
MANIFEST = load_json(ROOT, "BENCHMARK.json")
CELL = "deepseek_v32_exp.docs32k.sat"
# the per-layer entries this PR appended, in their order: FIVE, what the
# contract's limit of 128 left beside the 123 the list held (the issue
# names nineteen; PERF.md section 7 says which wait for a `benchmark` PR
# to retire entries, and section 5 reads the pieces from the trace)
NEW = ["latent_attention_share", "moe_experts_roofline.deepseek",
       "indexer_roofline.deepseek", "latent_attend_roofline",
       "held_route_share"]
# the readings of the other `sat_tok_s` cells this one joined
SHARED = ["ttft_p85_95_ms.sat", "loop_iter_max_ms.sat", "batch_rows_mean",
          "prefix_hit_rate", "decode_step_ms.sat", "prefill_step_ms.sat",
          "window_compiles", "device_idle_share.sat", "pool_copy_share.sat",
          "prefill_chunks_per_request", "prefill_device_share",
          "scoped_device_share.sat", "chained_step_share.sat"]
# the pieces of the sparse latent attention, which one share adds up
PIECES = ("indexer", "select", "latent_gather", "attend", "q_absorb")


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
    assert want == {"sat_tok_s", "setup_s"}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert notes["window_compiles"] == 0 and notes["preemptions"] == 0
    # the comparison that decides `correct`: the logit gap, the route
    # margin and a selection margin a layer, each beside its limit
    assert {"logit_gap", "route_margin", "select_margin.l0",
            "select_margin.l1", "select_margin.l2"} <= set(line["compared"])
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], name


def test_rehearsal_follows_experts_and_selection_and_reads_the_counters(
        capsys):
    rc, line, notes = _rehearse(capsys, trace=1)
    assert rc == 0 and line["correct"] is True
    assert notes["sampled"] > 0 and notes["marked_finished"] > 0
    assert notes["worst_gap"] <= notes["tolerance"]
    got = line["metrics"]
    # the counters behind the new per-layer metrics read on the CPU; the
    # trace-fed ones (shares, rooflines) find no device operation and are
    # left out of the line, as on a parent without the family
    assert 20 < got["held_route_share"]["value"] < 80        # 8 of 16 held
    assert got["prefix_hit_rate"]["value"] > 20
    assert got["prefill_chunks_per_request"]["value"] >= 1.0
    assert got["chained_step_share.sat"]["value"] > 50
    assert got["window_compiles"]["value"] == 0
    assert got["batch_rows_mean"]["value"] >= 1.0
    assert not {"latent_attention_share", "moe_experts_roofline.deepseek",
                "indexer_roofline.deepseek",
                "latent_attend_roofline"} & set(got)
    assert len(MANIFEST["per_layer"]) <= 128


def _newest_mask(scores, limit, k):
    at = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    return (at < limit[..., None]) & (at >= limit[..., None] - k)


def _newest_indices(scores, limit, k):
    kk = min(int(k), scores.shape[-1])
    at = limit[..., None] - 1 - jnp.arange(kk, dtype=jnp.int32)
    return jnp.where(at >= 0, at, -1)


def _ungrouped(z, router_w, router_bias, k, groups, groups_kept, scaling):
    from paddle_tpu.ops import hybrid_moe_ops

    return hybrid_moe_ops.sigmoid_router_fn(z, router_w, router_bias, k,
                                            scaling)


@pytest.mark.parametrize("fault,limit", [
    ("the_newest_k", "select_margin"), ("group_limit_ignored", "route_margin"),
    ("scale_without_m2", "logit_gap")])
def test_a_planted_fault_fails_the_cells_own_comparison(
        capsys, monkeypatch, fault, limit):
    """A wrong engine is not `correct`, by the limit that guards the
    mechanism: the selection's margin for "the newest k", the route margin
    for a router that ignores the group limit, the logit gap for a softmax
    scale without m^2 (so large a scale error that the served token is no
    longer the reference's best: 1 / 8 of the scale here, where the tests
    of the family read the logits themselves)."""
    from paddle_tpu.ops import latent_moe_ops

    wrong = {
        "the_newest_k": {"select_mask_fn": _newest_mask,
                         "select_indices_fn": _newest_indices},
        "group_limit_ignored": {"group_limited_router_fn": _ungrouped},
        "scale_without_m2": {"softmax_scale": lambda geom: 0.125 * (
            geom.nope_dim + geom.rope_dim) ** -0.5},
    }[fault]
    for name, fn in wrong.items():
        monkeypatch.setattr(latent_moe_ops, name, fn)
    rc, line, notes = _rehearse(capsys, trace=0, seed="77")
    assert rc == 0 and line["correct"] is False
    over = {name for name, c in line["compared"].items()
            if c["value"] > c["limit"]}
    assert any(name.startswith(limit) for name in over), (over, notes)


def test_the_cell_is_the_issues():
    cell = load_json(BENCH, "workloads", CELL + ".json")
    t = cell["traffic"]
    assert cell["runner"] == "serve_open_loop_sparse" and cell["chips"] == 1
    assert cell["config"] == "deepseek_v32_exp"
    assert t["schedule_seed"] == 39 and t["max_total"] == 33536
    assert t["shared"] == {"count": 4, "tokens": 32768, "zipf_a": 1.2}
    assert t["prompt"] == {"dist": "lognormal", "median": 96, "sigma": 0.6,
                           "min": 32, "max": 256}
    assert t["output"] == {"dist": "lognormal", "median": 256, "sigma": 0.6,
                           "min": 64, "max": 512}
    assert (t["accounting"], t["settle_s"], t["trace_slice_s"]) \
        == ("admitted", 15.0, 3.0)
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["rate_per_s"] > 0
    assert cell["rehearse"]["config"] == "rehearse_deepseek"
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert entry["traffic"] == "docs32k.sat" and len(entry["why"]) <= 200
    assert f"{t['arrivals']['rate_per_s']:g}/s" in entry["why"]


def test_the_configuration_keeps_every_published_width():
    spec = load_json(BENCH, "configs", "deepseek_v32_exp.json")
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
        "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 163840,
        "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "noaux_tc", "v_head_dim": 128,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"}}
    for key, value in published.items():
        assert spec[key] == value, key
    assert spec["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert (spec["num_hidden_layers"], spec["n_routed_experts"],
            spec["vocab_size"]) == (5, 16, 16160)
    assert spec["published"] == {"num_hidden_layers": 61,
                                 "n_routed_experts": 256,
                                 "vocab_size": 129280}
    assert "16 chips" in spec["reduced_how"]
    assert spec["vocab_size"] * 8 == 129280
    entry = MANIFEST["configs"][[c["name"] for c in MANIFEST["configs"]]
                                .index("deepseek_v32_exp")]
    assert entry["reduced"] == spec["reduced"]
    assert entry["source"] == spec["source"] and entry["source"].endswith(
        "DeepSeek-V3.2-Exp/blob/main/config.json")
    kw = spec["engine"]["config_kwargs"]
    assert kw["block"] == "latent_moe" and kw["num_layers"] == 5
    assert (kw["hidden_size"], kw["vocab_size"]) == (7168, 16160)
    assert (kw["q_lora_rank"], kw["kv_lora_rank"]) == (1536, 512)
    assert (kw["num_heads"], kw["attn_head_dim"], kw["rope_head_dim"],
            kw["v_head_dim"]) == (128, 128, 64, 128)
    assert (kw["index_heads"], kw["index_head_dim"], kw["index_topk"]) \
        == (64, 128, 2048)
    assert (kw["dense_layers"], kw["dense_ffn_size"], kw["ffn_size"],
            kw["shared_expert_size"]) == (1, 18432, 2048, 2048)
    # the router as published, the experts this chip holds
    assert (kw["num_experts"], kw["expert_groups"], kw["groups_per_token"],
            kw["experts_per_token"], kw["routed_scaling"]) \
        == (256, 8, 4, 8, 2.5)
    assert kw["experts_held"] == spec["n_routed_experts"] == 16
    assert kw["yarn"] == [40.0, 4096, 32.0, 1.0, 1.0]
    import math
    assert kw["softmax_mscale"] == pytest.approx(0.1 * math.log(40) + 1)
    eng = spec["engine"]
    assert (eng["page_size"], eng["pool_pages"], eng["max_inflight"]) \
        == (128, 2304, 128)
    # the bytes and operations the rooflines divide by follow their
    # derivation
    kb = spec["kernel_bytes"]
    assert kb["moe_call_bytes"] == 16 * 3 * 7168 * 2048 * 2 == 1409286144
    assert kb["indexer_token_bytes"] == 128 * 2
    assert kb["latent_row_bytes"] == (512 + 64) * 2
    assert kb["latent_row_flops"] == 2 * 128 * (576 + 512)
    assert kb["page_bytes"] == 128 * 5 * (1152 + 256) == 901120
    # weights and pool: the issue's reckoning, 9.27 GB + 2.08 GB
    mla = 7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768 \
        + 16384 * 7168
    indexer = 1536 * 8192 + 7168 * 128 + 7168 * 64
    expert = 3 * 7168 * 2048
    dense = mla + indexer + 3 * 7168 * 18432
    routed = mla + indexer + expert + 7168 * 256 + 16 * expert
    weights = 2 * (dense + 4 * routed + 2 * 16160 * 7168)
    assert 9.2e9 < weights < 9.35e9
    assert weights + eng["pool_pages"] * kb["page_bytes"] >= 11.3e9
    ref = spec["reference"]
    assert ref["module"] == "benchmark.reference.deepseek_v32_lm"
    assert len(ref["select_margin_tolerance"]) == 5


def test_the_new_entries_stand_together_in_their_order():
    """Order and adjacency only, no tail and no length: the next PR appends
    behind these as this one did behind PR 37's (tests/benchmark/conftest.py
    and tests/conftest.py tell what a pinned tail cost)."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    assert at > names.index("admit_self_ms.falcon")
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "sat_tok_s"
    for name in SHARED:
        assert per_layer[name]["workloads"][-1] == CELL or \
            CELL in per_layer[name]["workloads"], name
    assert CELL not in per_layer["topk_select_share"]["workloads"]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells.index(CELL) == cells.index("falcon_h1_34b.chat.sat") + 1
    configs = [c["name"] for c in MANIFEST["configs"]]
    assert configs.index("deepseek_v32_exp") \
        == configs.index("falcon_h1_34b") + 1
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    sat = next(m for m in MANIFEST["end_to_end"] if m["name"] == "sat_tok_s")
    assert CELL in sat["workloads"] and sat["bound"] == 0.03


@pytest.mark.parametrize("piece", PIECES)
def test_the_attention_share_adds_up_pieces_the_family_declares(piece):
    from paddle_tpu.observability import schema

    spec = layer_metric_spec(BENCH, "latent_attention_share")
    assert spec["reader"] == "trace_device_time_share"
    assert spec["args"]["of"] == "window"
    assert piece in schema.PIECES
    for mode in ("decode", "window"):
        path = f"latent_moe_stack/{mode}/{piece}"
        assert re.search(spec["args"]["paths"], path)
        assert re.search(spec["args"]["paths"], "serving/" + path + "/dot")
    assert not re.search(spec["args"]["paths"],
                         "sparse_moe_stack/decode/" + piece)
    for other in ("proj", "experts", "shared", "head", "kv_write"):
        assert not re.search(spec["args"]["paths"],
                             "latent_moe_stack/decode/" + other)


def test_the_rooflines_read_declared_counters_and_the_configs_counts():
    from benchmark import trace_reduce
    from paddle_tpu.observability import schema

    declared = dict((s[0], s[1]) for s in schema.DECLARED)
    config = load_json(BENCH, "configs", "deepseek_v32_exp.json")

    def dotted(key):
        keys = key.split(".")
        return config[keys[0]][keys[1]] if len(keys) == 2 \
            else config[keys[0]][keys[1]][keys[2]]

    spec = layer_metric_spec(BENCH, "moe_experts_roofline.deepseek")
    assert spec["reader"] == "kernel_roofline"
    args = spec["args"]
    assert declared[args["work"]] == declared[args["calls"]] == schema.COUNTER
    hlo = ("%moe_topk_experts_decode.1 = f32[128,7168]{1,0:T(8,128)} "
           "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"")
    assert re.search(args["pattern"], trace_reduce.op_key(hlo))
    assert dotted(args["bytes_per_work"]) == 1409286144
    for name, piece in (("indexer_roofline.deepseek", "indexer"),
                        ("latent_attend_roofline", "attend")):
        spec = layer_metric_spec(BENCH, name)
        assert spec["reader"] == "piece_roofline" and spec["bound"]
        args = spec["args"]
        assert re.search(args["paths"], f"latent_moe_stack/decode/{piece}")
        assert not re.search(args["paths"],
                             f"latent_moe_stack/window/{piece}")
        assert re.search(args["modules"], "jit_serving_decode(7)")
        assert dotted(args["per_call"]) == 5
        for key in ("work", "calls"):
            assert declared[args[key]] == schema.COUNTER, args[key]
        assert dotted(args["bytes_per_work"]) > 0
    assert dotted(args["ops_per_work"]) == 278528
    args = layer_metric_spec(BENCH, "held_route_share")["args"]
    assert args["numerator"] == ["serving.moe.held_pairs"]
    assert args["denominator"] == ["serving.moe.routed_pairs"]
    assert all(n in declared for n in args["numerator"] + args["denominator"])


def test_piece_roofline_reads_the_tighter_bound_and_nothing_from_nothing(
        tmp_path, monkeypatch):
    import types

    from benchmark.readers import piece_roofline

    config = {"engine": {"config_kwargs": {"num_layers": 5}},
              "kernel_bytes": {"b": 1000, "f": 4000}}
    ctx = types.SimpleNamespace(
        trace_dir=str(tmp_path), config=config,
        peaks={"hbm_bytes_per_s": 1e9, "bf16_flops": 2e9})
    result = types.SimpleNamespace(
        trace={"window_s": 1.0}, ctx=ctx,
        counters={"work": 2000, "calls": 10})
    args = dict(paths="stack/decode/attend", modules="^jit_serving_decode",
                per_call="engine.config_kwargs.num_layers", work="work",
                calls="calls", peak="hbm_bytes_per_s",
                bytes_per_work="kernel_bytes.b")
    report = {"window_s": 2.0, "busy_s": 1.0,
              "paths": {"serving/stack/decode/attend/dot": {"self_s": 0.04},
                        "stack/window/attend": {"self_s": 9.0}},
              "modules": {"jit_serving_decode(7)": {"self_s": 1.0,
                                                    "calls": 4},
                          "jit_serving_window(9)": {"self_s": 1.0,
                                                    "calls": 3}}}
    monkeypatch.setattr(piece_roofline, "report", lambda trace_dir: report)
    # 200 work a call x 1000 B over 0.04 s / (4 x 5) calls = 1e8 B/s: 10%
    assert piece_roofline.read(result, **args) == pytest.approx(10.0)
    # ... x 4000 operations against 2e9/s: 20%, the tighter bound
    assert piece_roofline.read(
        result, **args, ops_per_work="kernel_bytes.f",
        ops_peak="bf16_flops") == pytest.approx(20.0)
    assert piece_roofline.read(result, **dict(args, paths="no_such")) is None
    assert piece_roofline.read(result, **dict(args, work="missing")) is None
    monkeypatch.setattr(piece_roofline, "report", lambda trace_dir: None)
    assert piece_roofline.read(result, **args) is None
    result.trace = None
    assert piece_roofline.read(result, **args) is None


def test_the_rehearsal_compiles_the_names_the_shares_read():
    """What `test_benchmark_device_names.py` holds every accepted pattern
    to, for this cell's entries: the rehearsal's compiled programs carry
    the pieces' paths under the decode and the window mode, and the module
    of every program the engine builds is declared."""
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
        rc = bench_run.main(["--workload", CELL, "--seed", "5",
                             "--seconds", "1", "--trace", "0", "--rehearse"])
    finally:
        ex.Executor._compile = orig
        jax.config.update(flag, old)
    assert rc == 0
    modules = {re.match(r"HloModule (\S+?),", t).group(1) for t in texts}
    paths = {profiler.op_path(n) for t in texts
             for n in re.findall(r'op_name="([^"]+)"', t)} - {""}
    served = {m for m in modules if "serving" in m}
    assert {"jit_serving_decode", "jit_serving_window"} <= served
    assert served <= {"jit_" + n for n in schema.PROGRAM_NAMES}
    rx = layer_metric_spec(BENCH, "latent_attention_share")["args"]["paths"]
    assert {p.split("/")[2] for p in paths if re.search(rx, p)
            and p.startswith("latent_moe_stack/")} == set(PIECES)
    for name in ("indexer_roofline.deepseek", "latent_attend_roofline"):
        args = layer_metric_spec(BENCH, name)["args"]
        assert [p for p in paths if re.search(args["paths"], p)], name
        assert [m for m in modules if re.search(args["modules"], m)], name
    decode = {p.split("/")[-1] for p in paths
              if p.startswith("latent_moe_stack/decode/")}
    assert {"embed", "proj", "q_absorb", "indexer", "select",
            "latent_gather", "attend", "kv_write", "router", "experts",
            "shared", "dense_ffn", "head"} <= decode
    window = {p.split("/")[-1] for p in paths
              if p.startswith("latent_moe_stack/window/")}
    assert {"indexer", "select", "latent_gather", "attend", "q_absorb",
            "kv_write"} <= window
    for entry in ("prefill_device_share", "scoped_device_share.sat"):
        args = layer_metric_spec(BENCH, entry)["args"]
        found = [m for m in modules if re.search(args["modules"], m)] \
            if "modules" in args else \
            [p for p in paths if re.search(args["paths"], p)]
        assert found, entry
