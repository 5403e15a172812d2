"""The training decoder (`models/decoder_moe.py`: sliding-window and full
grouped-query attention, softmax-routed top-k experts, a sliced head) through
`Program` -> `Executor` against the plain reference
`benchmark/reference/mellum2_lm.py`, at a tiny size in float32, where no
token's choice of experts flips: three Adam steps, one step's gradients leaf
by leaf, the four shares of the experts adding up to the uncut layer, the
casts AMP places, and the counters."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import mellum2_lm as ref  # noqa: E402
from paddle_tpu.models import decoder_moe as dm  # noqa: E402
from paddle_tpu.ops import decoder_common, decoder_train_ops  # noqa: E402
from tools import decoder_faults as df  # noqa: E402

YARN = (16.0, 16, 32.0, 1.0, 1.2772588722239782)
# hidden 64, 4 heads over 2 of 16, window 8 at 32 positions, 8 experts top-2
CFG = dm.DecoderMoEConfig(layer_types=(dm.SLIDING, dm.FULL), yarn=YARN)
LEAVES = ["decoder.embed", "decoder.final_norm", "decoder.head"] + [
    f"decoder.layer{i}{s}" for i in range(2) for s in ref._LAYER.values()]


@pytest.fixture(scope="module")
def clean():
    return df.agreement(CFG)


def test_three_adam_steps_agree_with_the_reference(clean):
    # float32 against float32 at `highest`: what is left is the order of the
    # sums (the sorted experts, the blocks of the head), 1e-6 on a loss of 7
    # (measured 1e-6, 1 - 4e-10 and 1 - 1e-5); a wrong mechanism reads 2e-2,
    # 0.9 and 3e-3 (tests/test_decoder_moe_faults.py)
    assert clean["loss_gap"] < 2e-5
    assert clean["update_cosine"] > 1 - 1e-6
    assert abs(clean["update_rms_ratio"] - 1) < 1e-4
    assert all(6.5 < v < 8.0 for v in clean["losses"])      # ln 1024 = 6.93


@pytest.mark.parametrize("leaf", LEAVES)
def test_a_steps_gradient_is_the_references(clean, leaf):
    # every leaf, the router's and a sliding layer's K projection among them
    # (layer 0 is the sliding one): measured under 1e-6, a cut gradient 1.0
    assert set(clean["grad_rel"]) == set(LEAVES)
    assert clean["grad_rel"][leaf] < 1e-4, clean["grad_rel"][leaf]


def test_nothing_is_dropped_under_an_uneven_router(clean):
    c = clean["counters"]
    pairs = 3 * 2 * 32 * CFG.experts_per_token * CFG.num_layers
    assert c["train.moe.assignments"] == pairs
    assert c["train.moe.held_assignments"] == pairs     # every expert held
    assert c["train.moe.dropped"] == 0
    for layer in ("0", "1"):
        loads = [c[f'train.moe.expert_tokens{{expert="{e}",layer="{layer}"}}']
                 for e in range(CFG.num_experts)]
        assert sum(loads) == pairs / 2
        assert max(loads) > 1.2 * sum(loads) / len(loads)
    # off the chip the dense attention visits every key block
    assert c['train.attn.key_blocks_visited{kind="sliding"}'] \
        == c['train.attn.key_blocks_causal{kind="sliding"}'] == 3 * 2


def _uncut_half(seed=3, tokens=48):
    rng = np.random.default_rng(seed)
    H, E, F = CFG.hidden_size, CFG.num_experts, CFG.expert_width
    p = {"norm2": 1 + 0.1 * rng.standard_normal(H),
         "router": 2 * H ** -0.5 * rng.standard_normal((H, E)),
         "w_gate": H ** -0.5 * rng.standard_normal((E, H, F)),
         "w_up": H ** -0.5 * rng.standard_normal((E, H, F)),
         "w_down": F ** -0.5 * rng.standard_normal((E, F, H))}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    return p, jnp.asarray(rng.standard_normal((tokens, H)), jnp.float32)


def test_four_shares_add_up_to_the_uncut_layer():
    """Each of four chips holds 2 of the 8 experts; the router keeps its 8
    outputs and its top-2 on every one. The attention half counted once, the
    four shares of the experts' sum add up to the uncut reference's layer
    output, and their gradients to its gradients."""
    p, h = _uncut_half()
    cot = jnp.asarray(np.random.default_rng(4).standard_normal(h.shape),
                      jnp.float32)

    def uncut(h, p):
        return jnp.sum(cot * (h + ref.experts_half(h, p, CFG)))

    def share(h, p, first):
        z = decoder_common.rms_norm_fn(h, p["norm2"], CFG.rms_norm_eps)
        _, cw = decoder_common.topk_router_fn(z, p["router"],
                                              CFG.experts_per_token)
        y, _ = decoder_train_ops.moe_experts_train_fn(
            z, cw[:, first:first + 2], p["w_gate"][first:first + 2],
            p["w_up"][first:first + 2], p["w_down"][first:first + 2],
            CFG.experts_per_token)
        return jnp.sum(cot * y)

    def shares(h, p):
        return jnp.sum(cot * h) + sum(share(h, p, f) for f in (0, 2, 4, 6))

    with jax.default_matmul_precision("highest"):
        want, (dh, dp) = jax.jit(jax.value_and_grad(uncut, (0, 1)))(h, p)
        got, (gh, gp) = jax.jit(jax.value_and_grad(shares, (0, 1)))(h, p)
    assert abs(float(want) - float(got)) < 1e-4 * abs(float(want))
    for name, a, b in [("h", dh, gh)] + [(k, dp[k], gp[k]) for k in p]:
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
        assert rel < 1e-5, (name, rel)
    # and one share alone is NOT the layer: nothing stands in for the rest
    alone = jax.jit(jax.grad(
        lambda h: jnp.sum(cot * h) + share(h, p, 0)))(h)
    assert float(jnp.linalg.norm(alone - dh) / jnp.linalg.norm(dh)) > 0.05


def test_amp_places_the_new_ops():
    """bfloat16: the projections, the expert products, the head; float32:
    the norms, rotary, the router, the combine weights, the loss."""
    main, _, loss, _ = df.build(CFG, 32, seed=1, lr=1e-4, amp="bfloat16")
    block = main.global_block
    dtype = lambda n: block.var(n).dtype.value  # noqa: E731
    seen = set()
    for op in block.ops:
        if op.type.endswith("_grad") or op.type in seen:
            continue
        if op.type == "mul":
            assert {dtype(op.input("X")[0]), dtype(op.input("Y")[0])} \
                == {"bfloat16"}
        elif op.type == "fused_attention":
            assert [dtype(op.input(s)[0]) for s in "QKV"] == ["bfloat16"] * 3
        elif op.type == "moe_experts":
            assert [dtype(op.input(s)[0]) for s in
                    ("X", "WGate", "WUp", "WDown")] == ["bfloat16"] * 4
            assert dtype(op.input("Cw")[0]) == "float32"
            assert dtype(op.output("Out")[0]) == "float32"
        elif op.type == "lm_head_loss":
            assert [dtype(op.input(s)[0]) for s in "XW"] == ["bfloat16"] * 2
            assert dtype(op.output("Loss")[0]) == "float32"
        elif op.type in ("rms_norm", "rotary_embedding", "moe_router"):
            assert all(dtype(n) == "float32" for n in op.input_names)
            assert all(dtype(n) == "float32" for n in op.output_names)
        elif op.type == "elementwise_add":      # the residual stream
            assert all(dtype(n) == "float32" for n in op.input_names)
        else:
            continue
        seen.add(op.type)
    assert seen == {"mul", "fused_attention", "moe_experts", "lm_head_loss",
                    "rms_norm", "rotary_embedding", "moe_router",
                    "elementwise_add"}
    # the casts themselves: parameters and activations to bfloat16 in front
    # of a product, q / k back to float32 in front of the rotary op
    casts = {(dtype(op.input("X")[0]), dtype(op.output("Out")[0]))
             for op in block.ops if op.type == "cast"}
    assert casts == {("float32", "bfloat16"), ("bfloat16", "float32")}
    assert dtype(loss.name) == "float32"


def test_recompute_by_layer_gives_the_same_step():
    """`RecomputeByLayer` (a layer's inside computed again in the backward
    pass) changes what is kept, not what is computed."""
    import paddle_tpu as pt

    def losses(optimizer):
        main, startup = pt.Program(), pt.Program()
        main.random_seed = startup.random_seed = 7
        with pt.program_guard(main, startup), pt.unique_name.guard():
            loss, _ = dm.decoder_moe_pretrain(CFG, 32)
            optimizer().minimize(loss)
        exe, scope = pt.Executor(), pt.Scope()
        with pt.scope_guard(scope):
            exe.run(startup)
            return main, [float(exe.run(main, feed=b, fetch_list=[loss])[0])
                          for b in df.batches(CFG, 2, 32, 7)]

    _, plain = losses(lambda: pt.optimizer.Adam(learning_rate=1e-3))
    main, again = losses(lambda: dm.RecomputeByLayer(learning_rate=1e-3))
    assert sum(op.type == "recompute" for op in main.global_block.ops) == 2
    assert np.allclose(plain, again, rtol=0, atol=1e-5), (plain, again)
