"""`benchmark/run.py --rehearse` for the cell PR 47 added: the Xing4.0 cell's
whole path on the CPU at a tiny size (the `latent_moe` family WITHOUT an
indexer and with four residual streams, its one pool behind
`serve_open_loop_routed`, the plain reference `xing4_lm` following the
engine's experts, the contract line), planted faults of the residual path
caught by the cell's own comparison, and what BENCHMARK.json says of it."""
import contextlib
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
CELL = "xing4_29b_a4b.docs32k.sat"
# BENCHMARK.json's `per_layer` list is full (128 of 128, the contract's
# limit), so the cell brings NO entry of its own: it joins the lists of the
# accepted `.sat` readings. The two accepted entries whose readers would
# read this cell UNEDITED from its own files, `latent_attend_roofline` and
# `latent_attention_share`, it could not join: an accepted test
# (test_benchmark_rehearse_deepseek.py) holds their lists to the DeepSeek
# cell alone, as it holds `held_route_share`'s. PERF.md section 5 has their
# readings from a run with the cell in both lists (46.4% and 69.4%), and
# section 7 names them among the readings that wait for a `benchmark` PR
WAITING = ["latent_attend_roofline", "latent_attention_share",
           "held_route_share"]
# accepted readings of the experts whose readers (counter ratios over
# `serving.moe.*`, which every routed family books) read this cell unedited
JOINED = ["experts_touched_mean", "expert_load_max_over_mean"]
# the readings of the other `sat_tok_s` cells this one joined
SHARED = ["ttft_p85_95_ms.sat", "loop_iter_max_ms.sat", "batch_rows_mean",
          "prefix_hit_rate", "decode_step_ms.sat", "prefill_step_ms.sat",
          "window_compiles", "device_idle_share.sat", "pool_copy_share.sat",
          "prefill_chunks_per_request", "prefill_device_share",
          "scoped_device_share.sat", "chained_step_share.sat"]
# the pieces the family's device time is read by (`tools/obs.py ops
# --by piece`; PERF.md section 5)
PIECES = ("hc_map", "hc_mix", "attend", "q_absorb", "latent_gather",
          "kv_write", "router", "experts", "shared", "dense_ffn", "proj",
          "head", "embed")


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
    assert notes["worst_route_margin"] <= notes["route_margin_tolerance"]
    got = line["metrics"]
    # the counters behind the joined readings read on the CPU; the
    # trace-fed ones (shares, rooflines) find no device operation and are
    # left out of the line, as on a parent without the family
    assert got["prefix_hit_rate"]["value"] > 20
    assert got["prefill_chunks_per_request"]["value"] >= 1.0
    assert got["chained_step_share.sat"]["value"] > 50
    assert got["window_compiles"]["value"] == 0
    assert got["batch_rows_mean"]["value"] >= 1.0
    assert got["decode_step_ms.sat"]["value"] > 0
    # two of eight experts a token in two routed layers
    assert 1.0 <= got["experts_touched_mean"]["value"] <= 8.0
    assert got["expert_load_max_over_mean"]["value"] >= 1.0
    assert not {"latent_attend_roofline", "latent_attention_share",
                "device_idle_share.sat", "prefill_device_share",
                "held_route_share"} & set(got)
    assert len(MANIFEST["per_layer"]) <= 128        # the contract's limit


def test_the_rehearsal_counts_what_the_roofline_divides(capsys):
    """`latent_attend_roofline` divides `serving.latent.attended_tokens` by
    `serving.sparse.layer_steps`: the family without an indexer books every
    live position of every row a layer, and its layer steps."""
    from paddle_tpu import observability as obs

    rc, line, _ = _rehearse(capsys, trace=0, seed="5")
    assert rc == 0 and line["correct"] is True
    counters = obs.snapshot()["counters"]

    def total(name):
        return sum(v for k, v in counters.items()
                   if k == name or k.startswith(name + "{"))

    steps, tokens = (total("serving.sparse.layer_steps"),
                     total("serving.latent.attended_tokens"))
    assert steps > 0 and steps % 4 == 0         # four layers a decode step
    # contexts of 40-70 tokens behind one or more rows a step
    assert 30 < tokens / steps < 4 * 80
    assert total("serving.hc.mix_tokens") > 0
    assert total("serving.sparse.context_tokens") == 0      # no indexer ran


@contextlib.contextmanager
def _prepared(engine_class, prepare):
    """Every engine built inside has `prepare` (what a fault's context
    handed back, or None) called on it before it serves."""
    init = engine_class.__init__

    def built(self, *a, **k):
        init(self, *a, **k)
        if prepare is not None:
            prepare(self)

    engine_class.__init__ = built
    try:
        yield
    finally:
        engine_class.__init__ = init


@pytest.mark.parametrize("fault", ["res_identity", "one_sinkhorn_iteration",
                                   "post_without_two", "no_flat_norm",
                                   "streams_bfloat16",
                                   "readout_first_stream", "clip_left_out"])
def test_a_planted_fault_fails_the_cells_own_comparison(capsys, fault):
    """A wrong residual path is not `correct` by the cell's own
    comparison, all seven of them where both sides are float32 (beside
    bfloat16 sub-layers on the chip the streams' own rounding is one
    rounding among many: PERF.md section 6)."""
    from tools import streams_faults

    from paddle_tpu.serving import ServingEngine

    with streams_faults.FAULTS[fault]() as prepare, \
            _prepared(ServingEngine, prepare):
        rc, line, notes = _rehearse(capsys, trace=0, seed="77")
    assert rc == 0 and line["correct"] is False
    over = {name for name, c in line["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over & {"logit_gap", "route_margin"}, (over, notes)


def test_a_logit_past_the_clip_alone_is_no_fault(capsys):
    from paddle_tpu.serving import ServingEngine
    from tools import streams_faults

    with streams_faults.logit_past_clip() as prepare, \
            _prepared(ServingEngine, prepare):
        rc, line, _ = _rehearse(capsys, trace=0, seed="77")
    assert rc == 0 and line["correct"] is True


def test_the_cell_is_the_issues():
    cell = load_json(BENCH, "workloads", CELL + ".json")
    t = cell["traffic"]
    assert cell["runner"] == "serve_open_loop_routed" and cell["chips"] == 1
    assert cell["config"] == "xing4_29b_a4b"
    assert t["schedule_seed"] == 47 and t["max_total"] == 33536
    assert t["shared"] == {"count": 4, "tokens": 32768, "zipf_a": 1.2}
    assert t["prompt"] == {"dist": "lognormal", "median": 96, "sigma": 0.6,
                           "min": 32, "max": 256}
    assert t["output"] == {"dist": "lognormal", "median": 256, "sigma": 0.6,
                           "min": 64, "max": 512}
    assert (t["accounting"], t["trace_slice_s"]) == ("admitted", 3.0)
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["rate_per_s"] > 0
    # the mix of deepseek_v32_exp.docs32k.sat on purpose: the same requests
    # over two latent caches of the same row, one read through an indexer
    other = load_json(BENCH, "workloads",
                      "deepseek_v32_exp.docs32k.sat.json")["traffic"]
    for key in ("shared", "prompt", "output", "max_total", "accounting"):
        assert t[key] == other[key], key
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "every cached row" in entry["why"]
    assert t["arrivals"]["rate_per_s"] == 6.75 == 1.5 * 4.5    # the knee
    assert "6.75/s = 1.5 x knee 4.5 (PR 47)" in entry["why"]


def test_the_configuration_keeps_every_published_key_but_the_depth():
    spec = load_json(BENCH, "configs", "xing4_29b_a4b.json")
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "max_position_embeddings": 262144,
        "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    for key, value in catalog.items():
        assert spec[key] == value, key
    assert not [k for k in spec if k.startswith("index_")]
    assert spec["reduced"] == ["num_hidden_layers"]
    assert spec["num_hidden_layers"] == 7
    assert spec["published"]["num_hidden_layers"] == 40
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "xing4_29b_a4b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == spec["source"]
    assert entry["file"] == "benchmark/configs/xing4_29b_a4b.json"
    for key in ("reduced_how", "departures", "assumed", "init", "deployment",
                "engine", "kernel_bytes", "reference"):
        assert spec[key], key
    for key in ("sinkhorn_order", "hc_eps", "hc_gain", "readout",
                "rotary_pairing", "no_indexer"):
        assert spec["assumed"][key], key
    assert any("layer 6" in d for d in spec["departures"])
    assert any("multi-token-prediction" in d for d in spec["departures"])
    kw = spec["engine"]["config_kwargs"]
    assert kw["block"] == "latent_moe" and kw["num_layers"] == 7
    assert kw["dense_layers"] == 2 and "index_topk" not in kw
    # all 64 experts held (no experts_held), top-4 in one group, factor 2
    assert (kw["num_experts"], kw.get("experts_held", 0),
            kw["experts_per_token"], kw["expert_groups"],
            kw["routed_scaling"]) == (64, 0, 4, 1, 2.0)
    assert (kw["hidden_size"], kw["dense_ffn_size"], kw["ffn_size"],
            kw["shared_expert_size"], kw["vocab_size"]) \
        == (3584, 9216, 1024, 1024, 131072)
    assert (kw["num_heads"], kw["attn_head_dim"], kw["rope_head_dim"],
            kw["v_head_dim"], kw["q_lora_rank"], kw["kv_lora_rank"]) \
        == (32, 128, 64, 128, 768, 512)
    assert (kw["hc_mult"], kw["hc_sinkhorn_iters"], kw["hc_eps"],
            kw["hc_res_clamp"]) == (4, 20, 1e-06, [-30.0, 30.0])
    assert kw["yarn"] == [64.0, 4096, 32.0, 1.0, 1.0]
    import math
    assert abs(kw["softmax_mscale"] - (0.1 * math.log(64) + 1)) < 1e-12
    # the bytes the rooflines divide by follow their derivation
    kb = spec["kernel_bytes"]
    assert kb["moe_call_bytes"] == 64 * 3 * 3584 * 1024 * 2
    assert kb["latent_row_bytes"] == (512 + 64) * 2
    assert kb["latent_row_flops"] == 2 * 32 * (576 + 512)
    assert kb["hc_token_bytes"] == 2 * 4 * 3584 * 4
    assert kb["routed_layers"] == 5
    # weights and pool: at least 12.3 GB of the chip
    attention = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 \
        + 32 * 128 * 3584
    mappings = 2 * (4 * 3584 * 24 + 3 + 24)
    norms = 2 * 3584 + 768 + 512
    dense = attention + norms + mappings + 3 * 3584 * 9216
    routed = attention + norms + mappings + 3584 * 64 + 64 \
        + 65 * 3 * 3584 * 1024
    total = 2 * dense + 5 * routed + 2 * 131072 * 3584 + 3584
    assert (dense, routed, total) == (128_196_918, 744_989_046,
                                      4_920_866_746)
    pool = spec["engine"]["pool_pages"] * 128 * 384 * 4 * 7
    assert spec["engine"]["pool_pages"] == 1792
    assert 2 * total + pool >= 12.3e9
    for key in ("logit_tolerance", "route_margin_tolerance",
                "tolerance_reason"):
        assert spec["reference"][key]


def test_the_cell_joined_the_lists_its_files_can_be_read_by():
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in SHARED + JOINED:
        assert CELL in per_layer[name]["workloads"], name
        assert per_layer[name]["moves"] == "sat_tok_s" \
            or name == "window_compiles", name
    assert sorted(m["name"] for m in MANIFEST["per_layer"]
                  if CELL in m.get("workloads", ())) == sorted(SHARED + JOINED)
    # held to DeepSeek's cell by an accepted test
    for name in WAITING:
        assert per_layer[name]["workloads"] \
            == ["deepseek_v32_exp.docs32k.sat"], name
    sat = next(m for m in MANIFEST["end_to_end"] if m["name"] == "sat_tok_s")
    assert CELL in sat["workloads"] and sat["bound"] == 0.03
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells.index(CELL) \
        == cells.index("nemotron3_super_120b.reason.sat") + 1
    configs = [c["name"] for c in MANIFEST["configs"]]
    assert configs.index("xing4_29b_a4b") \
        == configs.index("nemotron3_super_120b") + 1
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert next(w for w in MANIFEST["workloads"]
                if w["name"] == CELL)["chips"] == 1


def test_the_cell_before_keeps_every_list_it_joined():
    """What PR 43's own test says of the Nemotron cell besides the tail it
    pins (tests/conftest.py: that test expects to fail since this PR
    appended behind it): the lists the cell stands in, what each moves,
    its place before this cell and the chips."""
    before = "nemotron3_super_120b.reason.sat"
    joined = SHARED + JOINED + [
        "paged_decode_gqa_roofline", "ssm_update_roofline",
        "state_restores_per_request", "state_recomputed_share"]
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert sorted(m["name"] for m in MANIFEST["per_layer"]
                  if before in m.get("workloads", ())) == sorted(joined)
    for name in joined:
        lists = per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "sat_tok_s" \
            or name == "window_compiles", name
        # nothing stands between the two cells in a list both joined
        if CELL in lists:
            assert lists.index(CELL) == lists.index(before) + 1, name
        else:
            assert lists[-1] == before, name
    sat = next(m for m in MANIFEST["end_to_end"] if m["name"] == "sat_tok_s")
    assert sat["workloads"][-2:] == [before, CELL]
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == before)
    assert entry["chips"] == 1 and entry["config"] == "nemotron3_super_120b"
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def test_the_roofline_that_waits_would_read_this_cells_own_numbers():
    """`latent_attend_roofline`'s reader takes its paths, counters and the
    configuration's keys from its accepted file; the keys resolve in THIS
    configuration's file to this architecture's row (32 heads, not 128), so
    the day its list may grow the cell joins it without an edit."""
    from paddle_tpu.observability import schema

    args = layer_metric_spec(BENCH, "latent_attend_roofline")["args"]
    declared = dict((s[0], s[1]) for s in schema.DECLARED)
    assert declared[args["work"]] == declared[args["calls"]] == schema.COUNTER
    assert (args["work"], args["calls"]) == (
        "serving.latent.attended_tokens", "serving.sparse.layer_steps")
    config = load_json(BENCH, "configs", "xing4_29b_a4b.json")
    tree = config
    for part in args["per_call"].split("."):
        tree = tree[part]
    assert tree == 7
    assert args["bytes_per_work"] == "kernel_bytes.latent_row_bytes"
    assert args["ops_per_work"] == "kernel_bytes.latent_row_flops"
    assert config["kernel_bytes"]["latent_row_flops"] * 4 == load_json(
        BENCH, "configs", "deepseek_v32_exp.json")["kernel_bytes"][
            "latent_row_flops"]
    # the kernel sits under the piece the reader's paths name
    assert re.search(args["paths"], "xla/latent_moe_stack/decode/attend/x")
    assert re.search(
        layer_metric_spec(BENCH, "latent_attention_share")["args"]["paths"],
        "latent_moe_stack/decode/q_absorb")


@pytest.mark.parametrize("piece", PIECES)
def test_the_family_declares_the_pieces_its_time_is_read_by(piece):
    """Every piece the stack opens is a declared one, and the programs of
    the rehearsal configuration name it in what they lower."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.observability import schema
    from paddle_tpu.ops import latent_moe_ops as ops
    from paddle_tpu.serving import DecoderConfig
    from paddle_tpu.serving import model as sv_model
    from paddle_tpu.serving.kv_cache import stacked_pool_shapes

    assert piece in schema.PIECES
    spec = load_json(BENCH, "configs", "rehearse_xing.json")
    cfg = DecoderConfig(**spec["engine"]["config_kwargs"])
    geom = ops.Geometry(**sv_model._latent_geometry(cfg))
    w = {k: jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
         for k, (shape, dtype, _) in
         sv_model._latent_param_specs(cfg).items()}
    pages, ps = 16, 8
    pools = tuple(jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
                  for _, shape, dtype in stacked_pool_shapes(
                      *sv_model._latent_pool_geometry(cfg, pages, ps)))
    assert len(pools) == 1
    shared = ops.attention_params(False, cfg.hc_mult)

    def run(mode, w, pools, tok, pos, **kw):
        return ops.latent_moe_stack_fn(
            mode, tok, pos, w["dec.word_emb"], w["dec.lm_head"],
            w["dec.final_norm.scale"],
            {k: w["dense." + k] for k in shared + ops.DENSE_PARAMS},
            {k: w["moe." + k] for k in shared + ops.MOE_PARAMS},
            tuple(w[k] for k in ops.EXPERT_PARAMS), geom, pools=pools,
            num_pages=pages, **kw)

    i32 = jnp.int32
    S = jax.ShapeDtypeStruct
    decode = jax.jit(lambda w, p, *a: run(
        "decode", w, p, a[0], a[1], page_table=a[2], mask=a[3])).lower(
        w, pools, S((4,), i32), S((4,), i32), S((4, 8), i32),
        S((4, 1), jnp.float32)).as_text(debug_info=True)
    window = jax.jit(lambda w, p, *a: run(
        "window", w, p, a[0], a[1], page_table=a[2], start=a[3],
        lens=a[4])).lower(
        w, pools, S((1, 16), i32), S((1, 16), i32), S((1, 8), i32),
        S((1,), i32), S((1,), i32)).as_text(debug_info=True)
    for mode, text in (("decode", decode), ("window", window)):
        if (mode, piece) == ("window", "q_absorb"):
            continue        # a window attends in the expanded form
        # (the routed layers' scanned body carries its scopes in a
        # location of its own, so the piece is looked for by itself)
        assert f"{mode}/" in text and re.search(
            rf'["/]{piece}["/]', text), (mode, piece)
