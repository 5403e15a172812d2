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
"""
import hashlib
from unittest import mock

import jax
import pytest

from paddle_tpu import executor
from paddle_tpu.ops.pallas_kernels import workbench
from tests.test_kernel_choice import _read_shapes, _serving_program

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
}


def program_hash(name: str, program: str, size: int) -> str:
    block, fed, rows = _serving_program(name, program, size)
    env = _read_shapes(block, fed, rows)
    with mock.patch.object(workbench, "on_tpu", lambda: True):
        jaxpr = jax.make_jaxpr(
            lambda env: executor._run_ops_traced(block, dict(env)))(env)
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]


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
