"""Which arm every configuration of the benchmark runs on the chip.

The choice of a kernel lives in code: each lever has ONE dispatch function
that answers from shapes and dtypes (`attention_ops._paged_arm`,
`decoder_common._experts_backend`, `sparse_moe_ops.paged_indexer_runs`,
`parallel_ssm_ops._update_backend`, `parallel_ssm_ops.conv_update_runs`,
`latent_moe_ops.latent_attend_runs`, `latent_moe_ops.paged_attend_runs`,
`attention_ops.attention_backend`), and with `FLAGS_tuning_mode` off, as in
every cell, nothing else has a say. This file pins those answers for the
configurations under `benchmark/configs/` (read, never written).

A case builds the program the engine would (`serving.model.build_*_program`
from the configuration's own `engine` block; the encoder's forward for
`bert_base`), and traces it from SHAPES alone with `workbench.on_tpu`
answering True: no weight is drawn, nothing is compiled or run. It watches
the lever's dispatch function while it does, and reads the Pallas calls out
of the traced program. The expected arm is the kernel under the name and
first output shape the benchmark's traced run lists it by
(`benchmark/trace_reduce.op_key`; `PERF_LEDGER.jsonl` spells the same with
`_` for every other character, `paged_decode_attention_f32_16_1_768_`), or
`"xla"` where the shape gate refuses and XLA's form runs.

Every expected kernel below is a `breakdown.device_ops` line of the ledger
at PR 41 (`conv_decode_update`: of PR 44's traced runs, PERF.md section 5),
one case for each kernel name and row bucket listed there. The
`"xla"` cases are the other side of each gate: the `rehearse_*`
configurations (4- and 8-token pages of 8-wide float32 heads), and the
training cells, whose device ops in the ledger are XLA fusions
(`convolution_bitcast_fusion bf16[1,32,12,512,64]` is s512's attention). No
configuration of the benchmark reaches the dense rule's other arms
(`flash_bundled` past 1,024 tokens, the short-sequence kernels behind
`use_pallas` or a swept verdict): ROADMAP D4 holds them, and for that one
lever ISSUE 42's "a case on each side of every gate" is NOT met here.

The two paged levers share `_paged_arm`, which does not know whether a
layer attends everything or a window, so what it answered is held to the
traced kernels for neither of them: for these two a case shows that the
function was asked, and reads the arm from the traced program alone.
"""
import contextlib
import functools
import importlib
import json
import os
import re
import types
from unittest import mock

import jax
import pytest

import paddle_tpu as pt
from paddle_tpu import executor
from paddle_tpu.ops import (attention_ops, cca_moe_ops, decoder_common,
                            kda_ops, latent_moe_ops, mixer_moe_ops,
                            parallel_ssm_ops, sparse_moe_ops)
from paddle_tpu.ops.pallas_kernels import workbench
from paddle_tpu.serving import DecoderConfig, PagedKVPool, ServingEngine
from paddle_tpu.serving import model as sv_model

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")

# lever -> (where its dispatch function is looked up, whether an answer of
# it takes the kernel (None: one function answers for two levers, see
# above), the kernels it dispatches to)
LEVERS = {
    "full_attention": (((attention_ops, "_paged_arm"),), None,
                       r"paged_decode_attention(_gqa)? "),
    "window_attention": (((attention_ops, "_paged_arm"),), None,
                         r"paged_window_attention_gqa "),
    "experts": (((decoder_common, "_experts_backend"),
                 (cca_moe_ops, "_experts_backend"),
                 (mixer_moe_ops, "_experts_backend")),
                lambda backend: backend == "pallas",
                r"moe_(top1|topk|relu2)_experts_"),
    "indexer": (((sparse_moe_ops, "paged_indexer_runs"),), bool,
                r"paged_indexer_scores "),
    "ssm_update": (((parallel_ssm_ops, "_update_backend"),),
                   lambda backend: backend == "pallas",
                   r"ssm_decode_update "),
    "conv_update": (((parallel_ssm_ops, "conv_update_runs"),), bool,
                    r"conv_decode_update "),
    "kda_update": (((kda_ops, "kda_update_runs"),), bool,
                   r"kda_decode_update "),
    "latent_attend": (((latent_moe_ops, "latent_attend_runs"),), bool,
                      r"latent_rows_attention "),
    "paged_latent_attend": (((latent_moe_ops, "paged_attend_runs"),), bool,
                            r"paged_latent_attention "),
    "attention": (((attention_ops, "attention_backend"),),
                  lambda chosen: chosen[0] != "xla",
                  r"(short_seq|short128)_attention|flash"),
}


def _config(name: str) -> dict:
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _dotted(path: str):
    module, name = path.rsplit(".", 1)
    return getattr(importlib.import_module(module), name)


def _serving_program(name: str, program: str, size: int):
    """(the block of one serving program of configuration `name`, what its
    -1 dimensions are fed): `decode` at `size` rows behind the page table
    of `max_position`, or the program a prompt's `size` tokens run through
    (`prefill`: the window program where the family chunks its prompts,
    the cold prefill elsewhere)."""
    engine = _config(name)["engine"]
    cfg = DecoderConfig(**engine["config_kwargs"])
    ps, pages, rows = (engine[k] for k in ("page_size", "pool_pages",
                                           "max_inflight"))
    decode = program == "decode"
    build = sv_model.build_decode_program if decode else \
        sv_model.build_window_program if cfg.prefill_chunk else \
        sv_model.build_prefill_program
    main = pt.Program()
    with pt.program_guard(main, pt.Program()), pt.unique_name.guard():
        # the row slots and the second pool as the engine sizes them
        build(cfg, pages, ps, **ServingEngine.default_sizes(cfg, ps, rows))
    pool = PagedKVPool(pages, ps)
    table = ServingEngine._page_bucket(
        types.SimpleNamespace(cfg=cfg, pool=pool),
        pool.pages_for(cfg.max_position)) \
        if decode or cfg.prefill_chunk else pool.pages_for(size)
    fed = {sv_model.PAGES_FEED: (size if decode else 1, table)}
    if cfg.windowed:
        fed[sv_model.WPAGES_FEED] = (fed[sv_model.PAGES_FEED][0],
                            sv_model.window_table_pages(
                                cfg, ps, 1 if decode else cfg.prefill_chunk))
    if not decode:
        fed[sv_model.TOK_FEED] = fed[sv_model.POS_FEED] = (1, size)
    return main.global_block, fed, size if decode else 1


def _training_program(name: str, rows: int, seq_len: int):
    """The forward of a training configuration (the backward asks the
    lever the same question at the same shapes)."""
    conf = _config(name)
    main = pt.Program()
    with pt.program_guard(main, pt.Program()), pt.unique_name.guard():
        _dotted(conf["builder"])(
            _dotted(conf["config_class"])(**conf["config_kwargs"]),
            seq_len=seq_len)
    return main.global_block, {}, rows


def _pallas_calls(jaxpr, found: list) -> list:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            shape = re.sub("float|bfloat", lambda m: m.group()[:-4],
                           eqn.outvars[0].aval.str_short())
            found.append(f"{eqn.params['name']} {shape}"
                         if len(eqn.outvars) == 1
                         else f"{eqn.params['name']} ({shape},..)")
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, found)
    return found


def _read_shapes(block, fed: dict, rows: int) -> dict:
    """{variable: ShapeDtypeStruct} of what the program reads and no op of
    it wrote (-1 dimensions are `rows`; `fed` names the feeds whose shapes
    the caller chose)."""
    env, written = {}, set()
    for op in block.ops:            # what the program reads and no op wrote
        for var in (v for names in op.inputs.values() for v in names):
            if var not in written and var not in env:
                v = block.var(var)
                env[var] = jax.ShapeDtypeStruct(
                    fed.get(var) or tuple(rows if d == -1 else d
                                          for d in v.shape),
                    v.np_feed_dtype)
        written.update(v for names in op.outputs.values() for v in names)
    return env


@functools.lru_cache(maxsize=None)
def _traced(name: str, program: str, size):
    """(the Pallas calls of the program under the names a device trace
    lists them by, {dispatch function: whether each answer took the
    kernel}) of one program of a configuration, traced for the chip."""
    block, fed, rows = _training_program(name, *size) \
        if program == "train" else _serving_program(name, program, size)
    env = _read_shapes(block, fed, rows)
    answers = {}

    def watched(fn, took, seen):
        def spy(*args, **kwargs):
            answer = fn(*args, **kwargs)
            seen.append(took is None or took(answer))
            return answer
        return spy

    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(workbench, "on_tpu",
                                              lambda: True))
        for where, took, _ in LEVERS.values():
            for module, attr in where:
                if (module, attr) not in answers:
                    seen = answers[module, attr] = []
                    stack.enter_context(mock.patch.object(
                        module, attr, watched(getattr(module, attr), took,
                                              seen)))
        jaxpr = jax.make_jaxpr(
            lambda env: executor._run_ops_traced(block, dict(env)))(env)
    return sorted(set(_pallas_calls(jaxpr.jaxpr, []))), answers


def _serving(name, rows, decode, prefill=()):
    """Cases of one served configuration: {lever: kernel} of its decode
    program at `rows`, and of the program each `prefill` size runs."""
    return [(name, lever, "decode", rows, arm)
            for lever, arm in decode.items()] + \
        [(name, lever, "prefill", size, arm)
         for size, arms in prefill for lever, arm in arms.items()]


CASES = [
    # bert_base_decoder.chat.r80 (8, 16, 32 rows) and .sessions.sat (16,
    # 32, 64): 12 heads of 64 side by side in the lanes of a 16-token page
    *[("bert_base_decoder", "full_attention", "decode", rows,
       f"paged_decode_attention f32[{rows},1,768]")
      for rows in (8, 16, 32, 64)],
    # zaya1_8b.decode.sat: top-1 experts, 8 query heads over 2 KV heads
    *_serving("zaya1_8b", 64, {
        "experts": "moe_top1_experts_decode f32[64,2048]",
        "full_attention": "paged_decode_attention_gqa f32[64,8,128]"},
        [(t, {"experts": f"moe_top1_experts_prefill f32[{t},2048]"})
         for t in (64, 128, 256, 512)]),
    # keye_vl2_30b_a3b.docs32k.sat: the indexer over 288 pages of 128
    *_serving("keye_vl2_30b_a3b", 64, {
        "experts": "moe_topk_experts_decode f32[64,2048]",
        "indexer": "paged_indexer_scores f32[64,1,36864]"},
        [(128, {"experts": "moe_topk_experts_prefill f32[128,2048]"})]),
    # laguna_xs2.agent16k.sat: 48 heads attend everything, 64 a window
    *_serving("laguna_xs2", 64, {
        "full_attention": "paged_decode_attention_gqa f32[64,48,128]",
        "window_attention": "paged_window_attention_gqa f32[64,64,128]",
        "experts": "moe_topk_experts_decode f32[64,2048]"},
        [(t, {"experts": f"moe_topk_experts_prefill f32[{t},2048]"})
         for t in (128, 256, 512)]),
    # falcon_h1_34b.chat.sat: 6 layers x 80 slots of state; 20 query heads
    # run as 24 (`_padded_group_heads`)
    *_serving("falcon_h1_34b", 64, {
        "ssm_update": "ssm_decode_update (f32[480,8192,128],..)",
        "conv_update": "conv_decode_update (f32[480,120,128],..)",
        "full_attention": "paged_decode_attention_gqa f32[64,24,128]"}),
    # deepseek_v32_exp.docs32k.sat: a window's queries attend in blocks of 64
    *_serving("deepseek_v32_exp", 128, {
        "experts": "moe_topk_experts_decode f32[128,7168]",
        "indexer": "paged_indexer_scores f32[128,1,36864]",
        "latent_attend": "latent_rows_attention f32[128,128,512]"},
        [(128, {"experts": "moe_topk_experts_prefill f32[128,7168]",
                "latent_attend": "latent_rows_attention f32[64,128,512]"})]),
    # nemotron3_super_120b.reason.sat: 5 mixers x 160 slots of state, two
    # 64-wide heads a lane row, a tail of 240 sublane rows a slot; the ungated experts in a latent of 1,024;
    # 32 query heads over 2 KV heads (groups of 16)
    *_serving("nemotron3_super_120b", 128, {
        "ssm_update": "ssm_decode_update (f32[800,8192,128],..)",
        "conv_update": "conv_decode_update (f32[800,240,128],..)",
        "experts": "moe_relu2_experts_decode f32[128,1024]",
        "full_attention": "paged_decode_attention_gqa f32[128,32,128]"},
        [(t, {"experts": f"moe_relu2_experts_prefill f32[{t},1024]"})
         for t in (128, 512)]),
    # xing4_29b_a4b.docs32k.sat (PR 47's traced run): no indexer, so a
    # decode row's pages are read in place; a window attends in the
    # expanded form (XLA) and asks neither attention kernel
    *_serving("xing4_29b_a4b", 64, {
        "experts": "moe_topk_experts_decode f32[64,3584]",
        "paged_latent_attend": "paged_latent_attention f32[64,32,512]"},
        [(t, {"experts": f"moe_topk_experts_prefill f32[{t},3584]"})
         for t in (128, 2048)]),
    # ling3_flash.agent8k.sat: 5 Kimi-Delta layers x 320 slots of a 32 x
    # 128 x 128 state, a tail of 288 sublane rows a slot; the one latent
    # layer's 256 decode rows go 64 a call (what a call keeps resident:
    # `latent_moe_ops.paged_attend_rows`); a window's chunked form and its
    # expanded attention are XLA's
    *_serving("ling3_flash", 256, {
        "kda_update": "kda_decode_update (f32[1600,4096,128],..)",
        "conv_update": "conv_decode_update (f32[1600,288,128],..)",
        "experts": "moe_topk_experts_decode f32[256,2560]",
        "paged_latent_attend": "paged_latent_attention f32[64,32,512]"},
        [(t, {"experts": f"moe_topk_experts_prefill f32[{t},2560]"})
         for t in (128, 2048)]),
    # ouro_2_6b.reason.sat (8, 16, 32 rows): 16 query heads over 16 KV heads
    # of 128 in bfloat16 pages of 16 tokens. One head a KV head whose head
    # IS a whole 128-lane register takes the matrix-unit arm the
    # grouped-query calls take (PR 55; `paged_attention.matrix_unit_arm`:
    # the output is `[rows, heads, 128]`, a head a sublane row; until then
    # `f32[rows,1,2048]`, the heads side by side in the lanes on the vector
    # unit, where 12 heads of 64 stay) under the name it had: 192 calls a
    # step, `paged_decode_attention`, each over a plane of its own
    *[("ouro_2_6b", "full_attention", "decode", rows,
       f"paged_decode_attention f32[{rows},16,128]") for rows in (8, 32)],
    # bert_base.s128 (and .dp4: the same rows a chip) and .s512
    ("bert_base", "attention", "train", (128, 128), "xla"),
    ("bert_base", "attention", "train", (32, 512), "xla"),
    # the rehearsals, at their own 4 rows and prompt chunk: every gate
    # refuses (8-wide heads fill no lane tile, float32 rows no latent tile)
    ("rehearse_encoder", "attention", "train", (4, 16), "xla"),
    ("rehearse_decoder", "full_attention", "decode", 4, "xla"),
    *_serving("rehearse_zaya", 4,
              {"experts": "xla", "full_attention": "xla"},
              [(16, {"experts": "xla"})]),
    *_serving("rehearse_keye", 4, {"experts": "xla", "indexer": "xla"},
              [(16, {"experts": "xla"})]),
    *_serving("rehearse_laguna", 4,
              {"full_attention": "xla", "window_attention": "xla",
               "experts": "xla"}, [(8, {"experts": "xla"})]),
    *_serving("rehearse_falcon", 4,
              {"ssm_update": "xla", "conv_update": "xla",
               "full_attention": "xla"}),
    *_serving("rehearse_deepseek", 4,
              {"experts": "xla", "indexer": "xla", "latent_attend": "xla"},
              [(16, {"experts": "xla", "latent_attend": "xla"})]),
    *_serving("rehearse_xing", 4,
              {"experts": "xla", "paged_latent_attend": "xla"},
              [(16, {"experts": "xla"})]),
    *_serving("rehearse_nemotron", 4,
              {"ssm_update": "xla", "conv_update": "xla", "experts": "xla",
               "full_attention": "xla"}, [(8, {"experts": "xla"})]),
    *_serving("rehearse_ling", 4,
              {"kda_update": "xla", "conv_update": "xla", "experts": "xla",
               "paged_latent_attend": "xla"}, [(8, {"experts": "xla"})]),
    ("rehearse_ouro", "full_attention", "decode", 4, "xla"),
]


def _case_id(case) -> str:
    name, lever, program, size, _ = case
    size = "x".join(map(str, size)) if isinstance(size, tuple) else size
    return f"{name}-{lever}-{program}{size}"


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_the_arm_a_configuration_runs(case):
    name, lever, program, size, arm = case
    kernels, answers = _traced(name, program, size)
    where, judged, pattern = LEVERS[lever]
    took = [t for target in where for t in answers[target]]
    assert took, f"{program} of {name} never asked {where}"
    assert ([k for k in kernels if re.match(pattern, k)] or ["xla"]) == [arm]
    if judged is not None:
        # the dispatch function's own word agrees with what was traced
        assert any(took) == (arm != "xla")


def test_the_paged_decode_arm_is_chosen_by_shape():
    """`dh == 128` with as many KV heads as query heads takes the matrix
    unit and the list walk; `dh == 64` (the BERT decoder's 12 heads) keeps
    the vector unit and the grid; a grouped-query call is the arm as it
    was; heads that fill no sublane tile, or a table whose chunk fills no
    lane row of the score tile, keep the vector unit too."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    ouro = ((32, 16, 128), (192 * 296, 16, 2048), jnp.bfloat16, 80)
    bert = ((64, 12, 64), (8192, 16, 768), jnp.float32, 32)
    zaya = ((64, 8, 128), (24 * 640, 128, 256), jnp.bfloat16, 16)
    for q, pool, dtype, table in (ouro, bert, zaya):
        assert ppa.paged_supported(q, pool, dtype)
    assert ppa.matrix_unit_arm(*ouro) and ppa.walk_supported(*ouro)
    assert not ppa.matrix_unit_arm(*bert) and not ppa.walk_supported(*bert)
    assert ppa.matrix_unit_arm(*zaya)
    # 12 heads of 128 fill no whole sublane tiles of 8
    assert not ppa.matrix_unit_arm((32, 12, 128), (4096, 16, 1536),
                                   jnp.bfloat16, 80)
    # a table of 4 pages of 16 tokens: a chunk of 64 slots, half a lane row
    assert not ppa.matrix_unit_arm((32, 16, 128), (4096, 16, 2048),
                                   jnp.bfloat16, 4)
    assert ppa.tile_rows(1) == (16, 8)
