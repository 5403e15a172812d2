"""Wrong mechanisms of the training decoder (`models/decoder_moe.py`),
planted one at a time, and the drive that holds a trainer built under each
to the plain reference (`tools/loop_faults.py`'s sibling for the trainer).

`FAULTS` maps a name to a context manager under which a Program is BUILT AND
RUN wrong in exactly one way (the ops are traced when the program first
runs, so the patch has to stand until then):

    window_ignored      a sliding layer attends every earlier key
    window_off_by_one   ... one key further back than its window
    yarn_on_sliding     the sliding layers' rotary under the full layers' YaRN
    not_renormalised    the chosen experts weighed by their softmax
                        probabilities as they are (no `norm_topk_prob`)
    last_expert_dropped a token's least likely chosen expert left out
    router_grad_cut     no gradient from the experts' sum to the router
    capacity            an expert computes its first `CAPACITY` tokens and
                        drops the rest

`float8_operands` is no fault but the control one precision down that the
benchmark cell's limits are set against:

    python tools/decoder_faults.py --control float8_operands -- \
        --workload mellum2_12b_a2_5b.s8k --seed 1 --seconds 30 --trace 0

runs `benchmark/run.py` with those arguments under it (or under a fault's
name) and prints the same line.

`agreement(cfg, ...)` builds the trainer, takes the first steps through
`Program` -> `Executor` and through the reference, and returns what
`benchmark/runners/train_steps.py` compares (the worst loss gap, the update's
cosine and length) beside the first step's gradients leaf by leaf;
`tests/test_decoder_moe_faults.py` holds every fault to them.
"""
from __future__ import annotations

import contextlib
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops import attention_ops, decoder_train_ops  # noqa: E402

CAPACITY = 12
STEPS = 3


@contextlib.contextmanager
def window_ignored():
    real = attention_ops.band_mask
    with mock.patch.object(attention_ops, "band_mask",
                           lambda sq, sk, causal, window:
                           real(sq, sk, causal, 0)):
        yield


@contextlib.contextmanager
def window_off_by_one():
    real = attention_ops.band_mask
    with mock.patch.object(attention_ops, "band_mask",
                           lambda sq, sk, causal, window:
                           real(sq, sk, causal, window + 1 if window else 0)):
        yield


@contextlib.contextmanager
def yarn_on_sliding():
    from paddle_tpu.models import decoder_moe

    real = decoder_moe.L.rotary_embedding

    def always(x, theta, yarn=(), name=None):
        return real(x, theta, yarn or _FULL_YARN[0], name)

    with mock.patch.object(decoder_moe.L, "rotary_embedding", always):
        yield


_FULL_YARN = [()]       # `agreement` notes the configuration's here


def _router(change):
    real = decoder_train_ops.topk_router_fn

    def wrong(z, router_w, k):
        ids, cw = real(z, router_w, k)
        return ids, change(z, router_w, k, ids, cw)

    return mock.patch.object(decoder_train_ops, "topk_router_fn", wrong)


@contextlib.contextmanager
def not_renormalised():
    def probs(z, router_w, k, ids, cw):
        p = jax.nn.softmax(jnp.dot(z, router_w), axis=-1)
        return jnp.where(cw != 0, p, 0.0)

    with _router(probs):
        yield


@contextlib.contextmanager
def last_expert_dropped():
    def drop(z, router_w, k, ids, cw):
        last = jnp.arange(cw.shape[-1])[None, :] == ids[:, -1:]
        return jnp.where(last, 0.0, cw)

    with _router(drop):
        yield


@contextlib.contextmanager
def router_grad_cut():
    with _router(lambda z, w, k, ids, cw: jax.lax.stop_gradient(cw)):
        yield


@contextlib.contextmanager
def capacity():
    real = decoder_train_ops.moe_experts_train_fn

    def capped(z, cw, wg, wu, wd, k):
        rank = jnp.cumsum(cw != 0, axis=0)
        return real(z, jnp.where(rank <= CAPACITY, cw, 0.0), wg, wu, wd, k)

    with mock.patch.object(decoder_train_ops, "moe_experts_train_fn", capped):
        yield


@contextlib.contextmanager
def float8_operands():
    """No fault but the CONTROL one precision down: every product the
    trainer makes in bfloat16 under AMP (the projections, the grouped expert
    products, the head) takes its operands rounded to float8_e4m3fn first.
    The benchmark cell's limits must refuse it (`python tools/
    decoder_faults.py --control`, on the chip, at the cell's size)."""
    from paddle_tpu.ops import registry

    def rounded(a):
        if a is None or a.dtype != jnp.bfloat16:
            return a
        return a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

    real_grouped = decoder_train_ops.grouped_matmul
    real_t = decoder_train_ops.grouped_matmul_t
    real_logits = decoder_train_ops._block_logits
    mul = registry.get_op_def("mul")
    real_mul = mul.compute

    def mul_rounded(ctx):
        env = dict(ctx.env)
        for slot in ("X", "Y"):
            for n in ctx.op.inputs.get(slot, []):
                env[n] = rounded(env.get(n))
        return real_mul(registry.ExecContext(ctx.op, env, ctx.rng,
                                             ctx.lowerer))

    with mock.patch.object(
            decoder_train_ops, "grouped_matmul",
            lambda lhs, rhs, *a, **k: real_grouped(rounded(lhs),
                                                   rounded(rhs), *a, **k)), \
         mock.patch.object(
            decoder_train_ops, "grouped_matmul_t",
            lambda lhs, rhs, *a, **k: real_t(rounded(lhs), rounded(rhs),
                                             *a, **k)), \
         mock.patch.object(
            decoder_train_ops, "_block_logits",
            lambda x, w: real_logits(rounded(x.astype(w.dtype)),
                                     rounded(w))), \
         mock.patch.object(mul, "compute", mul_rounded):
        yield


FAULTS = {f.__name__: f for f in (
    window_ignored, window_off_by_one, yarn_on_sliding, not_renormalised,
    last_expert_dropped, router_grad_cut, capacity)}


def build(cfg, seq_len: int, seed: int, lr: float, amp: str = ""):
    """(main, startup, loss, [(parameter name, its gradient's name)])."""
    import paddle_tpu as pt
    from paddle_tpu.models import decoder_moe

    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.program_guard(main, startup), pt.unique_name.guard():
        loss, _ = decoder_moe.decoder_moe_pretrain(cfg, seq_len)
        opt = pt.optimizer.Adam(learning_rate=lr)
        if amp:
            opt = pt.contrib.mixed_precision.decorate(opt, dest_dtype=amp)
        _, params_grads = opt.minimize(loss)
    return main, startup, loss, [(p.name, g.name) for p, g in params_grads]


def batches(cfg, rows: int, seq_len: int, seed: int, n: int = STEPS) -> list:
    rng = np.random.default_rng([seed, 58])
    return [{"src_ids": rng.integers(0, cfg.vocab_size, (rows, seq_len))
             .astype(np.int32)} for _ in range(n)]


def agreement(cfg, rows: int = 2, seq_len: int = 32, seed: int = 7,
              lr: float = 1e-4, amp: str = "", reference=None) -> dict:
    """The trainer's first `STEPS` Adam steps against the reference's:
    `loss_gap` (the worst over the steps), `update_cosine`,
    `update_rms_ratio` (the runner's `update_agreement`), `grad_rel` (every
    leaf's first-step gradient: |ours - theirs| / |theirs|) and what the
    registry counted. `reference`: a former call's `["reference"]`, for a
    trainer of the same configuration, seed and batches."""
    import paddle_tpu as pt
    from benchmark.reference import mellum2_lm as ref
    from benchmark.runners.train_steps import update_agreement
    from paddle_tpu import observability as obs

    _FULL_YARN[0] = tuple(cfg.yarn)
    main, startup, loss, pairs = build(cfg, seq_len, seed, lr, amp)
    feed = batches(cfg, rows, seq_len, seed)
    exe, scope = pt.Executor(), pt.Scope()
    obs.reset("train.")
    with pt.scope_guard(scope):
        exe.run(startup)

        def snapshot():
            return ref.read_params(lambda n: np.array(scope.find_var(n)), cfg)

        init = snapshot()
        first = exe.run(main, feed=feed[0],
                        fetch_list=[loss] + [g for _, g in pairs])
        losses = [float(first[0])] + [
            float(exe.run(main, feed=b, fetch_list=[loss])[0])
            for b in feed[1:]]
        after = snapshot()
        grads = {p: np.asarray(g, np.float64)
                 for (p, _), g in zip(pairs, first[1:])}
    counters = {k: v for k, v in obs.snapshot()["counters"].items()
                if k.startswith("train.")}
    if reference is None:
        ref_losses, ref_after = ref.first_steps(init, feed, cfg, lr=lr,
                                                block_rows=1)
        with jax.default_matmul_precision("highest"):
            _, ref_grads = ref.step_grads(init, feed[0]["src_ids"], cfg, 1)
        reference = (ref_losses, ref_after, ref_grads)
    ref_losses, ref_after, ref_grads = reference
    named = {"decoder.embed": ref_grads["embed"],
             "decoder.final_norm": ref_grads["final_norm"],
             "decoder.head": ref_grads["head"]}
    for i, layer in enumerate(ref_grads["layers"]):
        for key, suffix in ref._LAYER.items():
            named[f"decoder.layer{i}{suffix}"] = layer[key]
    grad_rel = {
        name: float(np.linalg.norm(grads[name] - g)
                    / max(np.linalg.norm(g), 1e-30))
        for name, g in named.items()}
    cosine, ratio = update_agreement(init, after, ref_after)
    return {"loss_gap": max(abs(a - b) for a, b in zip(losses, ref_losses)),
            "update_cosine": cosine, "update_rms_ratio": ratio,
            "grad_rel": grad_rel, "losses": losses, "counters": counters,
            "reference": reference}


def main(argv=None) -> int:
    import argparse

    from benchmark import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", default="float8_operands",
                    choices=sorted(FAULTS) + ["float8_operands"])
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    under = float8_operands if args.control == "float8_operands" \
        else FAULTS[args.control]
    with under():
        return bench_run.main([a for a in args.rest if a != "--"])


if __name__ == "__main__":
    sys.exit(main())
