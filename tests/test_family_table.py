"""What a block family IS lives in ONE place (`serving.model.FAMILIES`), and
what the families share in another (`ops/decoder_common.py`): two lints that
keep it so, and a build of every family's five programs from its row.

  * no module of `paddle_tpu/serving/` tests `DecoderConfig.block` against a
    family's name: a question about the family is a field of its row;
  * every key of `FAMILIES` builds prefill, window, decode, copy-on-write and
    the dense oracle (and the state copy where it is recurrent) from its
    `*_tiny()` configuration;
  * `ops/decoder_common.py` imports no family module, a family module
    imports only the families it is composed of, and `_mm` and the greedy
    head are defined once.
"""
import ast
import glob
import os
import re

import pytest

import paddle_tpu as pt
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import model as sv_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {"post_ln": sv_model.decoder_tiny, "cca_moe": sv_model.cca_moe_tiny,
        "sparse_moe": sv_model.sparse_moe_tiny,
        "hybrid_moe": sv_model.hybrid_moe_tiny,
        "parallel_ssm": sv_model.parallel_ssm_tiny,
        "latent_moe": sv_model.latent_moe_tiny,
        "mixer_moe": sv_model.mixer_moe_tiny,
        "kda_moe": sv_model.kda_moe_tiny,
        "looped_dense": sv_model.looped_dense_tiny}

# a family module -> the family modules it is COMPOSED of (whose mechanism
# it runs as a part of its own layers)
COMPOSED_OF = {"cca_moe_ops": set(), "sparse_moe_ops": set(),
               "hybrid_moe_ops": set(), "parallel_ssm_ops": set(),
               "looped_dense_ops": set(),
               "latent_moe_ops": {"sparse_moe_ops"},
               "mixer_moe_ops": {"parallel_ssm_ops"},
               "kda_ops": {"parallel_ssm_ops", "latent_moe_ops"}}


def test_no_module_of_serving_tests_a_familys_name():
    found = []
    for path in sorted(glob.glob(os.path.join(
            ROOT, "paddle_tpu", "serving", "*.py"))):
        for number, line in enumerate(open(path), 1):
            if re.search(r"\.block\s*(==|!=|in\b)", line):
                found.append(f"{os.path.relpath(path, ROOT)}:{number}: "
                             f"{line.strip()}")
    assert not found, "\n".join(found)


def test_the_table_and_the_tiny_configurations_name_the_same_families():
    assert set(TINY) == set(sv_model.FAMILIES)
    assert all(make().block == block for block, make in TINY.items())


@pytest.mark.parametrize("block", sorted(TINY))
def test_a_family_builds_its_programs_from_its_row(block):
    cfg = TINY[block]()
    sizes = ServingEngine.default_sizes(cfg, 4, 4)
    second = {k: v for k, v in sizes.items() if k != "token_slots"}
    builds = [(sv_model.build_prefill_program, sizes),
              (sv_model.build_window_program, sizes),
              (sv_model.build_decode_program, sizes),
              (sv_model.build_cow_program, second)]
    if cfg.recurrent:
        builds.append((sv_model.build_state_copy_program, second))
    for build, kwargs in builds:
        main = pt.Program()
        with pt.program_guard(main, pt.Program()), pt.unique_name.guard():
            io = build(cfg, 16, 4, **kwargs)
        assert main.global_block.ops and io["feeds"]
        if cfg.scanned and build is not sv_model.build_cow_program \
                and build is not sv_model.build_state_copy_program:
            # ONE composite op, the row's, beside the last token's two
            stack = [op for op in main.global_block.ops
                     if op.type == cfg.family.op]
            assert len(stack) == 1
            assert set(cfg.family.geometry(cfg)) <= set(stack[0].attrs)
    main = pt.Program()
    with pt.program_guard(main, pt.Program()), pt.unique_name.guard():
        io = sv_model.build_full_forward_program(cfg)
    assert "logits" in io and "next_token" not in io


def _imports(path: str) -> set:
    """The sibling modules of `paddle_tpu/ops/` that the module at `path`
    imports, anywhere in it."""
    seen = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.ImportFrom) and node.level == 1:
            seen.update([node.module.split(".")[0]] if node.module
                        else [alias.name for alias in node.names])
    return seen


def test_the_families_share_through_one_module_and_one_way():
    ops = os.path.join(ROOT, "paddle_tpu", "ops")
    assert not _imports(os.path.join(ops, "decoder_common.py")) \
        & set(COMPOSED_OF)
    for module, parts in COMPOSED_OF.items():
        imported = _imports(os.path.join(ops, module + ".py"))
        assert "decoder_common" in imported
        assert imported & set(COMPOSED_OF) == parts, module
    defined = {}
    for path in glob.glob(os.path.join(ops, "*.py")):
        text = open(path).read()
        for node in ast.parse(text).body:
            if isinstance(node, ast.FunctionDef) \
                    and node.name in ("_mm", "greedy_fn"):
                defined.setdefault(node.name, []).append(
                    os.path.basename(path))
        if not path.endswith("decoder_common.py"):
            assert not re.search(r"argmax\(out\[.logits.\]", text), path
    assert defined == {"_mm": ["decoder_common.py"],
                       "greedy_fn": ["decoder_common.py"]}
