"""The serving programs of every configuration the benchmark had before
PR 47, traced for the chip at their benchmark shapes, hash to what they
hashed to at PR 46 (sha256 of `str(jaxpr)`): the "latent_moe" block learnt
to run without an indexer and with several residual streams, and every
older family's decode and window programs, DeepSeek-V3.2's one-stream
path through the same stack among them, are byte for byte the programs they
were.

The programs are built as `tests/test_kernel_choice.py` builds them (from
the configuration's own `engine` block, from SHAPES alone, with
`workbench.on_tpu` answering True so that the Pallas calls are in them).
`HASHES` was computed by this file's `program_hash` in a checkout of commit
757cdc2 (PR 46) and in this tree; a change that means to alter one of
these programs recomputes its line and says so.

PR 50 recomputed FOUR lines, the decode programs of `zaya1_8b`,
`laguna_xs2`, `falcon_h1_34b` and `nemotron3_super_120b`: the grouped-query
paged decode kernel without a first live slot now walks a flat list of page
blocks and reads a run of pages that rows share once
(`paged_attention._walk_kernel`), and each of these stacks works the step's
plan out before its layers. Every other line, the four families' prefill
lines among them, is what it was.

PR 51 added FOUR lines and recomputed none of the older ones:
`xing4_29b_a4b`'s decode and window programs and the new `ling3_flash`'s.
The "latent_moe" stack WITHOUT an indexer now runs its attention through
`latent_moe_ops.unindexed_attention_fn`, the one function the "kda_moe"
stack's latent layer runs too (a decode step of more rows than one call of
the paged kernel keeps resident goes a group a call: `decode_plan_fn`). The
same operations in another order, and a window's unused `[queries, context]`
mask no longer traced: at the parent (commit de4f787) Xing's two programs
hashed to 71a1d3e6e39727b0 and 68f77b77da474590, and before the stack was
moved onto the shared function they still did in this tree. Every line with
an indexer (Keye's, DeepSeek's) is what it was.

PR 52 recomputed THREE lines, exactly those whose windows pass more than 256
rows through the gated expert kernel: `deepseek_v32_exp` prefill 512,
`xing4_29b_a4b` prefill 2048 and `ling3_flash` prefill 2048 (at the parent,
commit a61cabb: affbedfc12fc5867, eac4b33ecf6e9767, 434349f45fe03428). Such
a call now sorts its (token, expert) pairs by expert and runs the grouped
form (`moe_experts._grouped_call`: a `top_k`, a `sort`, a row gather and a
`pallas_call` over the sorted rows where one `pallas_call` walked every held
expert over every token tile). Every decode line and every other prefill
line (windows of up to 256 rows, and the families without the gated arm)
stands.

PR 53 added TWO lines and recomputed none: the new `ouro_2_6b`'s decode step
and 512-token window (the "looped_dense" family: a ninth op, pieces and
counters of its own; `engine._step_fetches` asks for an `exit_mass` only of
a program that has one). The eight other families' twenty lines are what
they were at the parent (commit c1b9e0e), which is also what keeps their
warm compile cache.

PR 55 recomputed ONE line: `ouro_2_6b` decode 32 (at the parent, commit
52011f4: d8b16d35bfc87f55). A decode call whose head is a whole 128-lane
register with as many KV heads as query heads now takes the matrix-unit arm
(`paged_attention.matrix_unit_arm`) as PR 50's list walk, its plan worked
out once before the two loops. No other configuration has such a shape:
`ouro_2_6b`'s window (no paged decode call in it) and the twenty older
lines stand.

PR 56 added TWENTY-THREE lines and recomputed none, BEFORE it replaced the
eight families' hand-written program builders with one builder over a table
(`serving.model.FAMILIES`): for each of the ten configurations its `startup`
line (no trace: the initializer ops of the startup program the engine runs,
in the order the parameters are created, each with its name, shape, dtype
and distribution; that order is the order of the weights' draw and of the
allocations of start-up) and its `cow` line (the copy-on-write program's
jaxpr), and for the three recurrent configurations a `state_copy` line
(`build_state_copy_program`'s). All twenty-three were computed by this
file's `program_hash` in a checkout of the parent (commit 95174a9), whose
builders were the per-family ones; the twenty-two older lines are what they
were there.
"""
import hashlib
from unittest import mock

import jax
import pytest

import paddle_tpu as pt
from paddle_tpu import executor
from paddle_tpu.ops.pallas_kernels import workbench
from paddle_tpu.serving import DecoderConfig, ServingEngine
from paddle_tpu.serving import model as sv_model
from tests.test_kernel_choice import _config, _read_shapes, _serving_program

# (configuration, program, rows or tokens) -> sha256(str(jaxpr))[:16]
HASHES = {
    ("bert_base_decoder", "decode", "64"): "67f5d72e4030401e",
    ("bert_base_decoder", "prefill", "256"): "dc7d3bcbe1dcf965",
    ("zaya1_8b", "decode", "64"): "c93fd3979a914686",
    ("zaya1_8b", "prefill", "256"): "5d2e4391d41ad57e",
    ("keye_vl2_30b_a3b", "decode", "64"): "9a764176abb865e3",
    ("keye_vl2_30b_a3b", "prefill", "128"): "6d1544e0c1ceb870",
    ("laguna_xs2", "decode", "64"): "1be2f4441e04e03b",
    ("laguna_xs2", "prefill", "256"): "20d0e440cb6377c5",
    ("falcon_h1_34b", "decode", "64"): "9694da0b40125e3e",
    ("falcon_h1_34b", "prefill", "512"): "26b70b79f4585ee8",
    ("deepseek_v32_exp", "decode", "128"): "0587bbd89e2a8e47",
    ("deepseek_v32_exp", "decode", "32"): "36487f961f762836",
    ("deepseek_v32_exp", "prefill", "128"): "cfe584788426341a",
    ("deepseek_v32_exp", "prefill", "512"): "39659a7d6d168116",
    ("nemotron3_super_120b", "decode", "128"): "f045b546ec04edeb",
    ("nemotron3_super_120b", "prefill", "512"): "6509dd8f853f64cd",
    ("xing4_29b_a4b", "decode", "64"): "d78c7dfb1874891b",
    ("xing4_29b_a4b", "prefill", "2048"): "41d5e91d20e55186",
    ("ling3_flash", "decode", "256"): "c9c90a4a169294bd",
    ("ling3_flash", "prefill", "2048"): "368abb70195f5e36",
    ("ouro_2_6b", "decode", "32"): "d955487264984fa1",
    ("ouro_2_6b", "prefill", "512"): "d743f065c1e242e3",
    # PR 56, computed at the parent (commit 95174a9): the weights' draw, the
    # copy-on-write step and the state copy
    ("bert_base_decoder", "startup", "0"): "647813df1f0bb392",
    ("bert_base_decoder", "cow", "1"): "aa30212903820bbe",
    ("zaya1_8b", "startup", "0"): "5ce1ec9e8a5211a0",
    ("zaya1_8b", "cow", "1"): "e52d091c8b334314",
    ("keye_vl2_30b_a3b", "startup", "0"): "56653c69176fa4ec",
    ("keye_vl2_30b_a3b", "cow", "1"): "eea6f756af060f45",
    ("laguna_xs2", "startup", "0"): "ceb805f866c044b2",
    ("laguna_xs2", "cow", "1"): "5a615955c16c94bc",
    ("falcon_h1_34b", "startup", "0"): "9239599149eb8baa",
    ("falcon_h1_34b", "cow", "1"): "7c342ecf0eb6fbf8",
    ("falcon_h1_34b", "state_copy", "1"): "80074feb4c7c3680",
    ("deepseek_v32_exp", "startup", "0"): "e42e53ce4260b278",
    ("deepseek_v32_exp", "cow", "1"): "dd17ac12483c5e35",
    ("nemotron3_super_120b", "startup", "0"): "baaff206c8603881",
    ("nemotron3_super_120b", "cow", "1"): "dad62859eb6491c4",
    ("nemotron3_super_120b", "state_copy", "1"): "fca8f7d04eeaa029",
    ("xing4_29b_a4b", "startup", "0"): "113b6c024493249e",
    ("xing4_29b_a4b", "cow", "1"): "0ec73ef469b4f66d",
    ("ling3_flash", "startup", "0"): "eb26ee89835463df",
    ("ling3_flash", "cow", "1"): "a4ed9677c77d1711",
    ("ling3_flash", "state_copy", "1"): "38327b4e1fbe7aca",
    ("ouro_2_6b", "startup", "0"): "8d13a8289b7a584b",
    ("ouro_2_6b", "cow", "1"): "b5d73dc8184995d7",
}


def _traced_hash(block, fed: dict, rows: int) -> str:
    env = _read_shapes(block, fed, rows)
    with mock.patch.object(workbench, "on_tpu", lambda: True):
        jaxpr = jax.make_jaxpr(
            lambda env: executor._run_ops_traced(block, dict(env)))(env)
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]


def _engine_sizes(name: str):
    """(cfg, pool pages, page size, the second pool as the engine sizes it)
    of configuration `name`."""
    engine = _config(name)["engine"]
    cfg = DecoderConfig(**engine["config_kwargs"])
    sizes = ServingEngine.default_sizes(cfg, engine["page_size"],
                                        engine["max_inflight"])
    del sizes["token_slots"]
    return cfg, engine["pool_pages"], engine["page_size"], sizes


def program_hash(name: str, program: str, size: int) -> str:
    if program == "startup":
        # the weights' draw: every initializer op of the startup program the
        # engine runs (the prefill program's), in the order the parameters
        # were created, with its shape, dtype and distribution
        cfg, pages, ps, second = _engine_sizes(name)
        startup = pt.Program()
        with pt.program_guard(pt.Program(), startup), pt.unique_name.guard():
            sv_model.build_prefill_program(cfg, pages, ps, **second)
        drawn = [(op.type, sorted(op.outputs.items()),
                  sorted(op.attrs.items())) for op in startup.global_block.ops]
        return hashlib.sha256(repr(drawn).encode()).hexdigest()[:16]
    if program in ("cow", "state_copy"):
        cfg, pages, ps, second = _engine_sizes(name)
        build = sv_model.build_cow_program if program == "cow" \
            else sv_model.build_state_copy_program
        main = pt.Program()
        with pt.program_guard(main, pt.Program()), pt.unique_name.guard():
            build(cfg, pages, ps, **second)
        return _traced_hash(main.global_block, {}, 1)
    return _traced_hash(*_serving_program(name, program, size))


@pytest.mark.parametrize("case", sorted(HASHES), ids="-".join)
def test_an_older_familys_program_is_the_parents(case):
    name, program, size = case
    assert program_hash(name, program, int(size)) == HASHES[case]


if __name__ == "__main__":
    import sys
    for line in sys.argv[1:]:
        name, program, size = line.split(":")
        print(f'    ("{name}", "{program}", "{size}"): '
              f'"{program_hash(name, program, int(size))}",', flush=True)
