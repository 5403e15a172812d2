"""`benchmark/run.py --rehearse` for the cell PR 53 added: the Ouro-2.6B
cell's whole path on the CPU at a tiny size (the `looped_dense` family: three
layers visited three times, nine planes of K/V pages a page id, a pool that
binds the rows in flight, behind `serve_open_loop_cut`, the plain reference
`ouro_lm`, the contract line), planted faults caught by the cell's own
comparison, and what BENCHMARK.json says of it. Nothing here pins the END of
a list: the contract tells every later PR to append behind this cell."""
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
CELL = "ouro_2_6b.reason.sat"
BEFORE = "ling3_flash.agent8k.sat"
# BENCHMARK.json's `per_layer` list is full (128 of 128, the contract's
# limit), so the cell brings NO entry of its own (the readings ISSUE 53
# names wait for a `benchmark` PR to make room: PERF.md section 7): it
# joins the lists of the accepted metrics every `sat_tok_s` cell stands in
SHARED = ["ttft_p85_95_ms.sat", "loop_iter_max_ms.sat", "batch_rows_mean",
          "prefix_hit_rate", "decode_step_ms.sat", "prefill_step_ms.sat",
          "window_compiles", "device_idle_share.sat", "pool_copy_share.sat",
          "prefill_chunks_per_request", "prefill_device_share",
          "scoped_device_share.sat", "chained_step_share.sat"]
# the pieces the family's device time is read by (`tools/obs.py ops
# --by piece`; PERF.md section 5)
PIECES = ("embed", "qkv", "kv_write", "kv_gather", "attend", "o_proj",
          "mlp", "exit_gate", "head")


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
    # rows come and go with the pool full (24 pages of 4 tokens under four
    # row slots): whatever was preempted came back through the programs
    # the warm-up compiled, and nothing leaked
    assert notes["window_compiles"] == 0 and notes["preemptions"] >= 0
    assert notes["peak_pages_in_use"] >= 20
    assert notes["leaked_pages"] == 0 and notes["audit_problems"] == 0
    # the comparison that decides `correct`: the logit gap beside its limit
    assert "logit_gap" in line["compared"]
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], name


def test_rehearsal_reads_the_counters_of_the_lists_it_joined(capsys):
    rc, line, notes = _rehearse(capsys, trace=1)
    assert rc == 0 and line["correct"] is True
    assert notes["sampled"] > 0 and notes["worst_gap"] <= notes["tolerance"]
    got = line["metrics"]
    # the counters behind the per-layer metrics it joined read on the CPU;
    # the trace-fed ones find no device operation and are left out
    assert got["prefix_hit_rate"]["value"] > 20
    assert got["prefill_chunks_per_request"]["value"] >= 1.0
    assert got["chained_step_share.sat"]["value"] > 50
    assert got["window_compiles"]["value"] == 0
    assert got["batch_rows_mean"]["value"] >= 1.0
    assert got["decode_step_ms.sat"]["value"] > 0
    assert got["prefill_step_ms.sat"]["value"] > 0
    assert not {"device_idle_share.sat", "prefill_device_share",
                "pool_copy_share.sat", "scoped_device_share.sat"} & set(got)
    assert len(MANIFEST["per_layer"]) == 128


@pytest.mark.parametrize("fault", ["one_plane_set", "three_visits"])
def test_a_planted_fault_fails_the_cells_own_comparison(capsys, fault):
    """A wrong engine is not `correct` by the cell's own comparison: the
    cache shared between the visits, and a visit fewer."""
    from tools import loop_faults

    with loop_faults.FAULTS[fault]():
        rc, line, notes = _rehearse(capsys, trace=0, seed="77")
    assert rc == 0 and line["correct"] is False
    over = {name for name, c in line["compared"].items()
            if c["value"] > c["limit"]}
    assert over == {"logit_gap"}, (over, notes)


def test_the_cell_is_the_issues():
    cell = load_json(BENCH, "workloads", CELL + ".json")
    t = cell["traffic"]
    assert cell["runner"] == "serve_open_loop_cut" and cell["chips"] == 1
    assert cell["config"] == "ouro_2_6b"
    assert t["schedule_seed"] == 53 and t["max_total"] == 1280
    assert t["shared"] == {"count": 2, "tokens": 256, "zipf_a": 1.2}
    assert t["prompt"] == {"dist": "lognormal", "median": 64, "sigma": 0.6,
                           "min": 32, "max": 256}
    assert t["output"] == {"dist": "lognormal", "median": 192, "sigma": 0.6,
                           "min": 64, "max": 768}
    assert (t["accounting"], t["settle_s"], t["trace_slice_s"]) \
        == ("admitted", 10.0, 3.0)
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["rate_per_s"] > 0
    assert cell["rehearse"]["config"] == "rehearse_ouro"
    # the context cap holds the longest request the mix can draw
    spec = load_json(BENCH, "configs", "ouro_2_6b.json")
    assert t["shared"]["tokens"] + t["prompt"]["max"] + t["output"]["max"] \
        == t["max_total"] == spec["engine"]["config_kwargs"]["max_position"]


def test_the_configuration_is_the_published_one_and_cuts_nothing():
    spec = load_json(BENCH, "configs", "ouro_2_6b.json")
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    for key, value in published.items():
        assert spec[key] == value, key
    assert spec["layer_types"] == ["full_attention"] * 48
    assert spec["reduced"] == []
    assert spec["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                              "blob/main/config.json")
    for key in ("norm_placement", "norm_closes_each_visit", "rotary", "gate",
                "threshold_1", "page_size_reason", "pool_pages_reason"):
        assert spec["assumed"][key], key
    assert spec["deployment"] and spec["dtype"] == "bfloat16"
    kw, engine = spec["engine"]["config_kwargs"], spec["engine"]
    assert (kw["block"], kw["num_layers"], kw["loop_steps"]) \
        == ("looped_dense", 48, 4)
    assert (kw["hidden_size"], kw["num_heads"], kw["num_kv_heads"],
            kw["attn_head_dim"], kw["ffn_size"], kw["vocab_size"],
            kw["rope_theta"], kw["rms_norm_eps"]) \
        == (2048, 16, 16, 128, 5632, 49152, 1e6, 1e-6)
    assert (kw["prefill_chunk"], kw["max_position"], engine["max_inflight"],
            engine["prefix_cache"], engine["draft_k"]) \
        == (512, 1280, 32, True, 0)
    assert engine["page_size"] in (16, 32, 64)
    assert engine["page_size"] == spec["assumed"]["page_size"]
    # the bytes follow their derivation
    kb, ps = spec["kernel_bytes"], engine["page_size"]
    assert kb["kv_token_bytes"] == 2 * 16 * 128 * 2 * 192 == 1572864
    assert kb["kv_page_bytes"] == ps * kb["kv_token_bytes"]
    assert kb["kv_plane_page_bytes"] * 192 == kb["kv_page_bytes"]
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert kb["weight_stream_bytes"] == 4 * 48 * layer * 2 \
        + 49152 * 2048 * 2 + (2048 + 2049) * 4
    # weights and pool: about 12.8 GB of the chip, the pool's pages bind
    # below the row cap at the cell's lengths
    params = 48 * layer + 2 * 49152 * 2048 + 2048 + 2049
    assert params == 2_667_974_657
    pool = engine["pool_pages"] * kb["kv_page_bytes"]
    assert 7.0e9 <= pool <= 8.0e9
    assert 12.5e9 <= 2 * params + pool <= 13.5e9
    tokens = engine["pool_pages"] * ps
    assert tokens < engine["max_inflight"] * (256 + 64 + 192) / 2
    for key in ("logit_tolerance", "tolerance_reason"):
        assert spec["reference"][key]
    assert spec["reference"]["module"] == "benchmark.reference.ouro_lm"


def test_the_cell_joined_the_lists_its_files_can_be_read_by():
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in SHARED:
        lists = per_layer[name]["workloads"]
        assert CELL in lists, name
        # appended behind the cell before it, wherever later ones follow
        assert lists.index(CELL) > lists.index(BEFORE), name
        assert per_layer[name]["moves"] == "sat_tok_s" \
            or name == "window_compiles", name
    assert sorted(m["name"] for m in MANIFEST["per_layer"]
                  if CELL in m.get("workloads", ())) == sorted(SHARED)
    sat = next(m for m in MANIFEST["end_to_end"] if m["name"] == "sat_tok_s")
    assert CELL in sat["workloads"] and sat["bound"] == 0.03
    assert sat["workloads"].index(CELL) == sat["workloads"].index(BEFORE) + 1
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells.index(CELL) == cells.index(BEFORE) + 1
    configs = [c["name"] for c in MANIFEST["configs"]]
    assert configs.index("ouro_2_6b") == configs.index("ling3_flash") + 1
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "ouro_2_6b"
    assert entry["traffic"] == "reason.sat"
    assert 0 < len(entry["why"]) <= 200
    config = next(c for c in MANIFEST["configs"] if c["name"] == "ouro_2_6b")
    assert config["reduced"] == [] and len(config["why"]) <= 200
    assert config["file"] == "benchmark/configs/ouro_2_6b.json"


def test_the_cells_before_keep_every_list_they_joined():
    """What PR 51's test says of the two cells before its own besides the
    tails it pins (tests/conftest.py: that test expects to fail since this
    PR appended behind them), and the same of PR 51's cell: the lists each
    stands in, what each moves, nothing between two cells in a list both
    joined, the chips. No list's end is asked about."""
    nemotron, xing, ling = ("nemotron3_super_120b.reason.sat",
                            "xing4_29b_a4b.docs32k.sat", BEFORE)
    routed = ["experts_touched_mean", "expert_load_max_over_mean"]
    state = ["state_restores_per_request", "state_recomputed_share"]
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    stands = {ling: SHARED + routed + state,
              xing: SHARED + routed,
              nemotron: SHARED + routed + state
              + ["paged_decode_gqa_roofline", "ssm_update_roofline"]}
    for cell, joined in stands.items():
        assert sorted(m["name"] for m in MANIFEST["per_layer"]
                      if cell in m.get("workloads", ())) == sorted(joined)
    for name in stands[nemotron]:
        lists = per_layer[name]["workloads"]
        order = [c for c in (nemotron, xing, ling, CELL) if c in lists]
        at = lists.index(nemotron)
        assert lists[at:at + len(order)] == order, name
    sat = next(m for m in MANIFEST["end_to_end"] if m["name"] == "sat_tok_s")
    at = sat["workloads"].index(nemotron)
    assert sat["workloads"][at:at + 4] == [nemotron, xing, ling, CELL]
    for cell, config in ((nemotron, "nemotron3_super_120b"),
                         (xing, "xing4_29b_a4b"), (ling, "ling3_flash")):
        entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
        assert entry["chips"] == 1 and entry["config"] == config


@pytest.mark.parametrize("piece", PIECES)
def test_the_family_declares_the_pieces_its_time_is_read_by(piece):
    """Every piece the stack opens is a declared one, and the programs of
    the rehearsal configuration name it in what they lower."""
    from paddle_tpu.observability import schema

    assert piece in schema.PIECES
    decode, window = _lowered()
    where = {"kv_gather": ("window",)}.get(piece, ("decode", "window"))
    for mode, text in (("decode", decode), ("window", window)):
        if mode in where:
            # a piece of the layer's body stands under both loops:
            # decode/while/body/while/body/qkv/dot_general
            assert re.search(rf'{mode}/(?:[^"/]+/)*{piece}["/]', text), \
                (mode, piece)


@pytest.mark.parametrize("name", [
    "serving.loop.visits", "serving.loop.decode_row_visits",
    "serving.loop.exit_mass", "serving.preempted_tokens",
    "serving.pool_bound_admissions", "serving.preemptions",
    "serving.pool_occupancy"])
def test_the_counters_the_waiting_entries_read_are_declared(name):
    from paddle_tpu.observability import schema

    assert name in schema.DECLARED_NAMES


_LOWERED = []


def _lowered():
    """The rehearsal configuration's decode step and window, compiled once
    for all the pieces (the compiled text names an operation by its whole
    path, through the loops)."""
    if _LOWERED:
        return _LOWERED[0]
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import looped_dense_ops as ops
    from paddle_tpu.serving import DecoderConfig
    from paddle_tpu.serving import model as sv_model
    from paddle_tpu.serving.kv_cache import stacked_pool_shapes

    spec = load_json(BENCH, "configs", "rehearse_ouro.json")
    cfg = DecoderConfig(**spec["engine"]["config_kwargs"])
    geom = ops.Geometry(**sv_model._looped_geometry(cfg))
    w = {k: jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
         for k, (shape, dtype, _) in
         sv_model._looped_param_specs(cfg).items()}
    pages, ps = 16, 4
    pools = tuple(jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
                  for _, shape, dtype in stacked_pool_shapes(
                      *sv_model.stacked_pool_geometry(cfg, pages, ps)))
    assert len(pools) == 2 and pools[0].shape[0] == 9 * pages

    def run(mode, w, pools, tok, pos, **kw):
        return ops.looped_dense_stack_fn(
            mode, tok, pos, w["dec.word_emb"], w["dec.lm_head"],
            w["dec.final_norm.scale"], w["dec.exit_gate.w"],
            w["dec.exit_gate.b"], {k: w[k] for k in ops.LAYER_PARAMS}, geom,
            pools=pools, num_pages=pages, **kw)

    i32 = jnp.int32
    S = jax.ShapeDtypeStruct
    decode = jax.jit(lambda w, p, *a: run(
        "decode", w, p, a[0], a[1], page_table=a[2], mask=a[3])).lower(
        w, pools, S((4,), i32), S((4,), i32), S((4, 8), i32),
        S((4, 1), jnp.float32)).compile().as_text()
    window = jax.jit(lambda w, p, *a: run(
        "window", w, p, a[0], a[1], page_table=a[2], start=a[3],
        lens=a[4])).lower(
        w, pools, S((1, 8), i32), S((1, 8), i32), S((1, 8), i32),
        S((1,), i32), S((1,), i32)).compile().as_text()
    _LOWERED.append((decode, window))
    return _LOWERED[0]
