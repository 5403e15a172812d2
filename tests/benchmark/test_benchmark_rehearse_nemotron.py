"""`benchmark/run.py --rehearse` for the cell PR 43 added: the
Nemotron-3-Super cell's whole path on the CPU at a tiny size (the
`mixer_moe` family, its three pools behind `serve_open_loop_routed`, the
plain reference `nemotron3_lm` following the engine's experts, the contract
line), planted faults caught by the cell's own comparison, and what
BENCHMARK.json says of it."""
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
CELL = "nemotron3_super_120b.reason.sat"
# BENCHMARK.json's `per_layer` list is full (128 of 128, the contract's
# limit), so the cell brings NO entry of its own: it joins the lists of the
# accepted metrics whose readers find something to read in it (PERF.md
# section 7 names the readings that wait for a `benchmark` PR to make room;
# `held_route_share` is one of them: an accepted test holds its list to the
# DeepSeek cell alone)
JOINED = ["experts_touched_mean", "expert_load_max_over_mean",
          "paged_decode_gqa_roofline", "ssm_update_roofline",
          "state_restores_per_request", "state_recomputed_share"]
# the readings of the other `sat_tok_s` cells this one joined
SHARED = ["ttft_p85_95_ms.sat", "loop_iter_max_ms.sat", "batch_rows_mean",
          "prefix_hit_rate", "decode_step_ms.sat", "prefill_step_ms.sat",
          "window_compiles", "device_idle_share.sat", "pool_copy_share.sat",
          "prefill_chunks_per_request", "prefill_device_share",
          "scoped_device_share.sat", "chained_step_share.sat"]
# the pieces the family's device time is read by (`tools/obs.py ops
# --by piece`; PERF.md section 5)
PIECES = ("ssm_update", "ssm_scan", "conv", "attend", "kv_write", "router",
          "latent_proj", "experts", "shared", "proj", "head", "embed")


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
    # the comparison that decides `correct`: the logit gap and the route
    # margin, each beside its limit
    assert {"logit_gap", "route_margin"} <= set(line["compared"])
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], name


def test_rehearsal_follows_the_experts_and_reads_the_counters(capsys):
    rc, line, notes = _rehearse(capsys, trace=1)
    assert rc == 0 and line["correct"] is True
    assert notes["sampled"] > 0 and notes["worst_gap"] <= notes["tolerance"]
    got = line["metrics"]
    # the counters behind the new per-layer metrics read on the CPU; the
    # trace-fed ones (shares, rooflines) find no device operation and are
    # left out of the line, as on a parent without the family
    assert "held_route_share" not in got
    assert 1.0 <= got["experts_touched_mean"]["value"] <= 4.0
    assert got["expert_load_max_over_mean"]["value"] >= 1.0
    assert 0.5 <= got["state_restores_per_request"]["value"] <= 1.0
    assert 0.0 <= got["state_recomputed_share"]["value"] < 0.5
    assert got["prefix_hit_rate"]["value"] > 20
    assert got["prefill_chunks_per_request"]["value"] >= 1.0
    assert got["chained_step_share.sat"]["value"] > 50
    assert got["window_compiles"]["value"] == 0
    assert got["batch_rows_mean"]["value"] >= 1.0
    assert got["decode_step_ms.sat"]["value"] > 0
    assert not {"ssm_update_roofline", "paged_decode_gqa_roofline",
                "device_idle_share.sat", "prefill_device_share"} & set(got)
    assert len(MANIFEST["per_layer"]) == 128


@pytest.mark.parametrize("fault", ["no_routed_scaling", "relu_not_squared",
                                   "packed_heads_swapped"])
def test_a_planted_fault_fails_the_cells_own_comparison(capsys, fault):
    """A wrong engine is not `correct` by the cell's own comparison."""
    from tools import mixer_faults

    with mixer_faults.FAULTS[fault]():
        rc, line, notes = _rehearse(capsys, trace=0, seed="77")
    assert rc == 0 and line["correct"] is False
    over = {name for name, c in line["compared"].items()
            if c["value"] > c["limit"]}
    assert over & {"logit_gap", "route_margin"}, (over, notes)


def test_the_cell_is_the_issues():
    cell = load_json(BENCH, "workloads", CELL + ".json")
    t = cell["traffic"]
    assert cell["runner"] == "serve_open_loop_routed" and cell["chips"] == 1
    assert t["schedule_seed"] == 43 and t["max_total"] == 4608
    assert t["shared"] == {"count": 4, "tokens": 2048, "zipf_a": 1.2}
    assert t["prompt"] == {"dist": "lognormal", "median": 128, "sigma": 0.8,
                           "min": 32, "max": 512}
    assert t["output"] == {"dist": "lognormal", "median": 512, "sigma": 0.6,
                           "min": 128, "max": 2048}
    assert (t["accounting"], t["settle_s"], t["trace_slice_s"]) \
        == ("admitted", 10.0, 3.0)
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["rate_per_s"] > 0


def test_the_configuration_keeps_every_published_key_but_the_three():
    spec = load_json(BENCH, "configs", "nemotron3_super_120b.json")
    published = {
        "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 4096, "intermediate_size": 2688,
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_num_heads": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "num_attention_heads": 32, "num_experts_per_tok": 22,
        "num_key_value_heads": 2, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rope_theta": 10000,
        "routed_scaling_factor": 5, "ssm_state_size": 128, "topk_group": 1,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "mtp_hybrid_override_pattern": "*E"}
    for key, value in published.items():
        assert spec[key] == value, key
    pattern = spec["hybrid_override_pattern"]
    assert len(pattern) == 88 and (pattern.count("M"), pattern.count("E"),
                                   pattern.count("*")) == (40, 40, 8)
    assert spec["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert (spec["num_hidden_layers"], spec["n_routed_experts"],
            spec["vocab_size"]) == (11, 128, 32768)
    assert spec["published_counts"]["num_hidden_layers"] == 88
    assert spec["published_counts"]["n_routed_experts"] == 512
    assert spec["published_counts"]["vocab_size"] == 131072
    kw = spec["engine"]["config_kwargs"]
    assert kw["block"] == "mixer_moe" and kw["num_layers"] == 11
    assert kw["layer_pattern"] == pattern[:11] == "MEMEMEM*EME"
    # the router is 512 wide and chooses 22; 128 experts are held
    assert (kw["num_experts"], kw["experts_held"], kw["experts_per_token"],
            kw["routed_scaling"]) == (512, 128, 22, 5.0)
    assert (kw["hidden_size"], kw["latent_size"], kw["ffn_size"],
            kw["shared_expert_size"], kw["vocab_size"]) \
        == (4096, 1024, 2688, 5376, 32768)
    assert (kw["num_heads"], kw["num_kv_heads"], kw["attn_head_dim"]) \
        == (32, 2, 128)
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_groups"],
            kw["ssm_state"], kw["ssm_conv"], kw["ssm_chunk"]) \
        == (128, 64, 8, 128, 4, 128)
    # the bytes the rooflines divide by follow their derivation
    kb = spec["kernel_bytes"]
    assert kb["ssm_row_layer_bytes"] == 2 * (128 * 128 * 64 * 4
                                             + 3 * 10240 * 4)
    assert kb["moe_call_bytes"] == 128 * 2 * 1024 * 2688 * 2
    assert kb["kv_page_bytes"] == 128 * 2 * 2 * 128 * 2
    assert kb["routed_layers"] == 5
    # weights and pools: at least 13 GB of the chip
    weights = 2 * (5 * 109_640_064 + 35_655_680 + 5 * 759_173_632
                   + 2 * 32768 * 4096)
    pools = 160 * 5 * 4_317_184 \
        + spec["engine"]["pool_pages"] * kb["kv_page_bytes"]
    assert 9.29e9 < weights < 9.31e9 and weights + pools >= 13e9
    for key in ("logit_tolerance", "route_margin_tolerance",
                "tolerance_reason"):
        assert spec["reference"][key]


def test_the_cell_stands_at_the_end_of_every_list_it_joined():
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in SHARED + JOINED:
        assert per_layer[name]["workloads"][-1] == CELL, name
        assert per_layer[name]["moves"] == "sat_tok_s" \
            or name == "window_compiles", name
    assert sorted(m["name"] for m in MANIFEST["per_layer"]
                  if CELL in m.get("workloads", ())) == sorted(SHARED + JOINED)
    sat = next(m for m in MANIFEST["end_to_end"] if m["name"] == "sat_tok_s")
    assert sat["workloads"][-1] == CELL and sat["bound"] == 0.03
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells[-1] == CELL and len(cells) == 11
    assert MANIFEST["configs"][-1]["name"] == "nemotron3_super_120b"
    assert len(MANIFEST["configs"]) == 8
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert MANIFEST["workloads"][-1]["chips"] == 1


@pytest.mark.parametrize("piece", PIECES)
def test_the_family_declares_the_pieces_its_time_is_read_by(piece):
    """Every piece the stack opens is a declared one, and the three programs
    of the rehearsal configuration name it in what they lower."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.observability import schema
    from paddle_tpu.ops import mixer_moe_ops as ops
    from paddle_tpu.serving import DecoderConfig
    from paddle_tpu.serving import model as sv_model

    assert piece in schema.PIECES
    spec = load_json(BENCH, "configs", "rehearse_nemotron.json")
    cfg = DecoderConfig(**spec["engine"]["config_kwargs"])
    geom = ops.Geometry(**sv_model._mixer_geometry(cfg))
    shapes = {k: jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
              for k, (shape, dtype, _) in
              sv_model._mixer_param_specs(cfg).items()}
    slots, pages, ps = 6, 16, 4
    kv, state = sv_model.ssm_pool_geometry(cfg, pages, ps, slots)
    from paddle_tpu.serving.kv_cache import (stacked_pool_shapes,
                                             state_pool_shapes)
    pools = tuple(jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
                  for _, shape, dtype in (stacked_pool_shapes(*kv)[:2]
                                          + state_pool_shapes(*state)))

    def run(mode, w, pools, tok, pos, **kw):
        return ops.mixer_moe_stack_fn(
            mode, tok, pos, w["dec.word_emb"], w["dec.lm_head"],
            w["dec.final_norm.scale"], w["norm"],
            {k: w["mix." + k] for k in ops.MIXER_PARAMS},
            {k: w["attn." + k] for k in ops.ATTENTION_PARAMS},
            {k: w["moe." + k] for k in ops.MOE_PARAMS},
            (w["w1"], w["w2"]), geom, pools=pools, num_pages=pages,
            num_slots=slots, **kw)

    i32 = jnp.int32
    S = jax.ShapeDtypeStruct
    decode = jax.jit(lambda w, p, *a: run(
        "decode", w, p, a[0], a[1], page_table=a[2], mask=a[3],
        state_slot=a[4])).lower(
        shapes, pools, S((4,), i32), S((4,), i32), S((4, 8), i32),
        S((4, 1), jnp.float32), S((4,), i32)).as_text(debug_info=True)
    window = jax.jit(lambda w, p, *a: run(
        "window", w, p, a[0], a[1], page_table=a[2], start=a[3], lens=a[4],
        state_slot=a[5])).lower(
        shapes, pools, S((1, 8), i32), S((1, 8), i32), S((1, 8), i32),
        S((1,), i32), S((1,), i32), S((1,), i32)).as_text(debug_info=True)
    text = {"ssm_update": decode, "ssm_scan": window}.get(piece,
                                                          decode + window)
    mode = "window" if piece == "ssm_scan" else "decode"
    assert re.search(rf"{mode}/{piece}(/|\")", text), piece


@pytest.mark.parametrize("metric,kernel,bytes_key,hlo_shapes", [
    ("ssm_update_roofline", "ssm_decode_update", "ssm_row_layer_bytes",
     "(f32[800,8192,128]{2,1,0:T(8,128)}, f32[128,64,128]{2,1,0:T(8,128)})"),
    ("paged_decode_gqa_roofline", "paged_decode_attention_gqa",
     "kv_page_bytes", "f32[128,32,128]{2,1,0:T(8,128)}")])
def test_a_roofline_it_joined_reads_the_cells_kernel_by_its_name(
        metric, kernel, bytes_key, hlo_shapes):
    from benchmark import trace_reduce
    from paddle_tpu.observability import schema

    spec = layer_metric_spec(BENCH, metric)
    args = spec["args"]
    assert spec["reader"] == "kernel_roofline"
    declared = dict((s[0], s[1]) for s in schema.DECLARED)
    assert declared[args["work"]] == declared[args["calls"]] == schema.COUNTER
    hlo = (f"%{kernel}.1 = {hlo_shapes} custom-call(%a, %b), "
           "custom_call_target=\"tpu_custom_call\"")
    assert re.search(args["pattern"], trace_reduce.op_key(hlo))
    assert args["bytes_per_work"] == "kernel_bytes." + bytes_key
    assert args["peak"] == "hbm_bytes_per_s"
    config = load_json(BENCH, "configs", "nemotron3_super_120b.json")
    assert config["kernel_bytes"][bytes_key] > 0
    # the ungated experts' kernel is a stream of its own: no accepted
    # roofline's pattern reads it (PERF.md section 7)
    for m in MANIFEST["per_layer"]:
        found = layer_metric_spec(BENCH, m["name"]).get("args", {})
        if "pattern" in found:
            assert not re.search(found["pattern"],
                                 "moe_relu2_experts_decode f32[128,1024]")
