"""`benchmark/run.py --rehearse` for the cell PR 51 added: the Ling-3.0-flash
cell's whole path on the CPU at a tiny size (the `kda_moe` family: a slot of
matrix state AND pages of latent rows a sequence, behind
`serve_open_loop_routed`, the plain reference `ling3_lm` following the
engine's experts, the contract line), planted faults caught by the cell's own
comparison, and what BENCHMARK.json says of it."""
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
from benchmark.harness import load_json  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
MANIFEST = load_json(ROOT, "BENCHMARK.json")
CELL = "ling3_flash.agent8k.sat"
# BENCHMARK.json's `per_layer` list is full (128 of 128, the contract's
# limit), so the cell brings NO entry of its own (the thirteen readings
# ISSUE 51 names wait for a `benchmark` PR to make room: PERF.md section 7):
# it joins the lists of the accepted metrics whose readers find something to
# read in it
JOINED = ["experts_touched_mean", "expert_load_max_over_mean",
          "state_restores_per_request", "state_recomputed_share"]
# the readings of the other `sat_tok_s` cells this one joined
SHARED = ["ttft_p85_95_ms.sat", "loop_iter_max_ms.sat", "batch_rows_mean",
          "prefix_hit_rate", "decode_step_ms.sat", "prefill_step_ms.sat",
          "window_compiles", "device_idle_share.sat", "pool_copy_share.sat",
          "prefill_chunks_per_request", "prefill_device_share",
          "scoped_device_share.sat", "chained_step_share.sat"]
# the pieces the family's device time is read by (`tools/obs.py ops
# --by piece`; PERF.md section 5)
PIECES = ("kda_gate", "conv", "kda_update", "kda_scan", "attend", "q_absorb",
          "latent_gather", "kv_write", "router", "experts", "shared",
          "dense_ffn", "proj", "head", "embed")


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
    assert notes["leaked_pages"] == 0 and notes["audit_problems"] == 0
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
    # the counters behind the per-layer metrics it joined read on the CPU;
    # the trace-fed ones find no device operation and are left out
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
    assert not {"device_idle_share.sat", "prefill_device_share"} & set(got)
    assert len(MANIFEST["per_layer"]) == 128


@pytest.mark.parametrize("fault", ["no_delta_term", "no_head_gate",
                                   "restore_shares_slot"])
def test_a_planted_fault_fails_the_cells_own_comparison(capsys, fault):
    """A wrong engine is not `correct` by the cell's own comparison."""
    from tools import kda_faults

    with kda_faults.FAULTS[fault]():
        rc, line, notes = _rehearse(capsys, trace=0, seed="77")
    assert rc == 0 and line["correct"] is False
    over = {name for name, c in line["compared"].items()
            if c["value"] > c["limit"]}
    assert over & {"logit_gap", "route_margin"}, (over, notes)


def test_the_cell_is_the_issues():
    cell = load_json(BENCH, "workloads", CELL + ".json")
    t = cell["traffic"]
    assert cell["runner"] == "serve_open_loop_routed" and cell["chips"] == 1
    assert cell["config"] == "ling3_flash"
    assert t["schedule_seed"] == 51 and t["max_total"] == 14336
    assert t["shared"] == {"count": 4, "tokens": 8192, "zipf_a": 1.2}
    assert t["prompt"] == {"dist": "lognormal", "median": 512, "sigma": 0.8,
                           "min": 32, "max": 4096}
    assert t["output"] == {"dist": "lognormal", "median": 384, "sigma": 0.6,
                           "min": 64, "max": 2048}
    assert (t["accounting"], t["settle_s"], t["trace_slice_s"]) \
        == ("admitted", 10.0, 3.0)
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["rate_per_s"] > 0
    assert cell["rehearse"]["config"] == "rehearse_ling"


def test_the_configuration_keeps_every_published_key_but_the_three():
    spec = load_json(BENCH, "configs", "ling3_flash.json")
    published = {
        "hidden_size": 2560, "num_attention_heads": 32,
        "num_key_value_heads": 32, "head_dim": 128,
        "short_conv_kernel_size": 4, "kv_lora_rank": 512,
        "qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "qk_head_dim": 192,
        "v_head_dim": 128, "q_lora_rank": None, "intermediate_size": 6144,
        "moe_intermediate_size": 768,
        "moe_shared_expert_intermediate_size": 768, "n_group": 8,
        "topk_group": 4, "num_experts_per_tok": 8,
        "routed_scaling_factor": 2.5, "rope_theta": 6000000,
        "layer_group_size": 6, "first_k_dense_replace": 2,
        "kda_lower_bound": -5, "kda_safe_gate": True, "no_kda_lora": True,
        "rms_norm_eps": 1e-06, "num_nextn_predict_layers": 1,
        "model_type": "bailing_hybrid", "topk_method": "noaux_tc",
        "max_position_embeddings": 262144}
    for key, value in published.items():
        assert spec[key] == value, key
    assert spec["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert (spec["num_hidden_layers"], spec["num_experts"],
            spec["vocab_size"]) == (6, 128, 39296)
    assert spec["published_counts"]["num_hidden_layers"] == 42
    assert spec["published_counts"]["num_experts"] == 512
    assert spec["published_counts"]["vocab_size"] == 157184
    assert not any(spec["expert_swiglu_limit_list"][:34])
    kw = spec["engine"]["config_kwargs"]
    assert kw["block"] == "kda_moe" and kw["num_layers"] == 6
    assert kw["layer_group_size"] == 6 and kw["dense_layers"] == 2
    # the router is 512 wide in 8 groups and chooses 8 of 4; 128 are held
    assert (kw["num_experts"], kw["experts_held"], kw["experts_per_token"],
            kw["expert_groups"], kw["groups_per_token"],
            kw["routed_scaling"]) == (512, 128, 8, 8, 4, 2.5)
    assert (kw["hidden_size"], kw["dense_ffn_size"], kw["ffn_size"],
            kw["shared_expert_size"], kw["vocab_size"]) \
        == (2560, 6144, 768, 768, 39296)
    assert (kw["num_heads"], kw["attn_head_dim"], kw["rope_head_dim"],
            kw["v_head_dim"], kw["kv_lora_rank"], kw["rope_theta"]) \
        == (32, 128, 64, 128, 512, 6e6)
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"],
            kw["ssm_conv"], kw["ssm_chunk"], kw["kda_sub_chunk"],
            kw["kda_lower_bound"]) == (32, 128, 128, 4, 64, 16, -5.0)
    assert "q_lora_rank" not in kw
    # the bytes the rooflines divide by follow their derivation
    kb = spec["kernel_bytes"]
    assert kb["kda_row_layer_bytes"] == 2 * (32 * 128 * 128 * 4
                                             + 3 * 12288 * 4) == 4489216
    assert kb["moe_call_bytes"] == 128 * 3 * 2560 * 768 * 2 == 1509949440
    assert (kb["latent_row_bytes"], kb["latent_row_flops"]) == (1152, 69632)
    assert kb["routed_layers"] == 4
    assert kb["kda_scan_token_layer_flops"] == 32 * 168448
    # weights and pools: the arithmetic of `reduced_how`, 12.2 GB of the chip
    kda, mla = 63_049_888, 31_965_696
    moe = 7_209_472 + 128 * 5_898_240
    params = 2 * (kda + 47_185_920 + 5120) + 3 * (kda + moe + 5120) \
        + (mla + moe + 5120) + 2 * 39296 * 2560 + 2560
    assert params == 3_691_552_544
    from paddle_tpu.serving import DecoderConfig
    from paddle_tpu.serving import model as sv_model
    import numpy as np
    cfg = DecoderConfig(**kw)
    assert sum(int(np.prod(shape)) for shape, _, _ in
               sv_model._kda_param_specs(cfg).values()) == params
    pools = 320 * 5 * 2_244_608 + spec["engine"]["pool_pages"] * 128 * 1536
    assert 2 * params + pools >= 0.75 * 16e9
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
    sat = next(m for m in MANIFEST["end_to_end"] if m["name"] == "sat_tok_s")
    assert CELL in sat["workloads"] and sat["bound"] == 0.03
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells.index(CELL) == cells.index("xing4_29b_a4b.docs32k.sat") + 1
    configs = [c["name"] for c in MANIFEST["configs"]]
    assert configs.index("ling3_flash") == configs.index("xing4_29b_a4b") + 1
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200


def test_the_cells_before_keep_every_list_they_joined():
    """What PR 47's test says of the Nemotron cell besides the tails it
    pins (tests/conftest.py: that test expects to fail since this PR
    appended behind them), and the same of PR 47's own cell: the lists each
    stands in, what each moves, nothing between two cells in a list both
    joined, the chips."""
    nemotron, xing = ("nemotron3_super_120b.reason.sat",
                      "xing4_29b_a4b.docs32k.sat")
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    stands = {xing: SHARED + JOINED[:2],
              nemotron: SHARED + JOINED + ["paged_decode_gqa_roofline",
                                           "ssm_update_roofline"]}
    for cell, joined in stands.items():
        assert sorted(m["name"] for m in MANIFEST["per_layer"]
                      if cell in m.get("workloads", ())) == sorted(joined)
    for name in stands[nemotron]:
        lists = per_layer[name]["workloads"]
        order = [c for c in (nemotron, xing, CELL) if c in lists]
        at = lists.index(nemotron)
        assert lists[at:at + len(order)] == order, name
        assert lists[-1] == order[-1], name
    sat = next(m for m in MANIFEST["end_to_end"] if m["name"] == "sat_tok_s")
    assert sat["workloads"][-3:] == [nemotron, xing, CELL]
    for cell, config in ((nemotron, "nemotron3_super_120b"),
                         (xing, "xing4_29b_a4b")):
        entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
        assert entry["chips"] == 1 and entry["config"] == config


@pytest.mark.parametrize("piece", PIECES)
def test_the_family_declares_the_pieces_its_time_is_read_by(piece):
    """Every piece the stack opens is a declared one, and the programs of
    the rehearsal configuration name it in what they lower."""
    from paddle_tpu.observability import schema

    assert piece in schema.PIECES
    decode, window = _lowered()
    where = {"kda_update": ("decode",), "kda_scan": ("window",),
             "q_absorb": ("decode",),
             "latent_gather": ("decode", "window")}.get(
                 piece, ("decode", "window"))
    for mode, text in (("decode", decode), ("window", window)):
        if mode in where:
            assert re.search(rf'{mode}/(?:[a-z_]+/)*{piece}["/]', text), \
                (mode, piece)


_LOWERED = []


def _lowered():
    """The rehearsal configuration's decode step and window, lowered once
    for all the pieces."""
    if _LOWERED:
        return _LOWERED[0]
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import kda_ops as ops
    from paddle_tpu.serving import DecoderConfig
    from paddle_tpu.serving import model as sv_model
    from paddle_tpu.serving.kv_cache import (stacked_pool_shapes,
                                             state_pool_shapes)

    spec = load_json(BENCH, "configs", "rehearse_ling.json")
    cfg = DecoderConfig(**spec["engine"]["config_kwargs"])
    geom = ops.Geometry(**sv_model._kda_geometry(cfg))
    w = {k: jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
         for k, (shape, dtype, _) in sv_model._kda_param_specs(cfg).items()}
    slots, pages, ps = 6, 16, 4
    kv, state = sv_model.ssm_pool_geometry(cfg, pages, ps, slots)
    pools = tuple(jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
                  for _, shape, dtype in (stacked_pool_shapes(*kv)
                                          + state_pool_shapes(*state)))
    assert len(pools) == 3

    def run(mode, w, pools, tok, pos, **kw):
        return ops.kda_moe_stack_fn(
            mode, tok, pos, w["dec.word_emb"], w["dec.lm_head"],
            w["dec.final_norm.scale"], w["norm"],
            *({k: w[prefix + k] for k in keys}
              for _, prefix, keys in sv_model._KDA_GROUPS[:4]),
            tuple(w[k] for k in ops.EXPERT_PARAMS), geom, pools=pools,
            num_pages=pages, num_slots=slots, **kw)

    i32 = jnp.int32
    S = jax.ShapeDtypeStruct
    decode = jax.jit(lambda w, p, *a: run(
        "decode", w, p, a[0], a[1], page_table=a[2], mask=a[3],
        state_slot=a[4])).lower(
        w, pools, S((4,), i32), S((4,), i32), S((4, 8), i32),
        S((4, 1), jnp.float32), S((4,), i32)).as_text(debug_info=True)
    window = jax.jit(lambda w, p, *a: run(
        "window", w, p, a[0], a[1], page_table=a[2], start=a[3], lens=a[4],
        state_slot=a[5])).lower(
        w, pools, S((1, 8), i32), S((1, 8), i32), S((1, 8), i32),
        S((1,), i32), S((1,), i32), S((1,), i32)).as_text(debug_info=True)
    _LOWERED.append((decode, window))
    return _LOWERED[0]
