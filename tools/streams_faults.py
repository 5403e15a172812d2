"""Wrong mechanisms of a residual path of several streams (the "latent_moe"
block with `hc_mult` > 1: `ops/hyper_connection_ops.py`), planted one at a
time, and the drive that shows each of them to the plain reference
(`tools/mixer_faults.py`'s sibling).

`FAULTS` maps a name to a context manager under which a `ServingEngine` of
the block is BUILT AND RUN wrong in exactly one way (its programs are traced
when they first run, so the patch has to stand for the engine's life):

    res_identity           H_res the identity: the streams never mix
    one_sinkhorn_iteration one normalisation of columns and rows, not
                           `hc_sinkhorn_iters`
    post_without_two       H_post = sigmoid(.), without its factor 2
    no_flat_norm           the mappings projected from the streams as they
                           are, the flat RMSNorm left out
    streams_bfloat16       the streams kept in bfloat16 from mix to mix
    readout_first_stream   the final norm reads stream 0, not the sum
    clip_left_out          the residual logits not clipped, under weights
                           that reach the clip (`logit_past_clip`: one
                           residual bias of every sub-layer raised by 100,
                           a control of its own that a right engine must
                           pass: the clip holds that logit at its limit,
                           where `exp` of 100 is no float32)

`tests/test_serving_streams.py` holds each to the reference at the tiny
size;

    python tools/streams_faults.py [--config xing4_29b_a4b] [--faults a,b]
        [--workload xing4_29b_a4b.docs32k.sat --pool-pages 384]

builds the configuration's engine (on the chip: the served widths, a small
pool) once right, once under the raised bias and once under every fault,
serves a few requests behind one shared prompt (with `--workload`: the
first eight requests of that cell's own traffic behind its first document,
what the cell's comparison samples), grades them with the configuration's
reference (the engine's routes followed) and tolerances, and prints one
`fault {...}` line each: the worst logit gap and route margin and whether
they pass the limits. Exit 1 if a right engine fails or a wrong one
passes.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops import hyper_connection_ops as hc  # noqa: E402


@contextlib.contextmanager
def res_identity():
    def identity(logits, iters, eps):
        n = logits.shape[0]
        eye = jnp.eye(n, dtype=logits.dtype).reshape(
            (n, n) + (1,) * (logits.ndim - 2))
        return jnp.broadcast_to(eye, logits.shape)

    with mock.patch.object(hc, "sinkhorn_fn", identity):
        yield


@contextlib.contextmanager
def one_sinkhorn_iteration():
    real = hc.sinkhorn_fn
    with mock.patch.object(hc, "sinkhorn_fn",
                           lambda logits, iters, eps: real(logits, 1, eps)):
        yield


@contextlib.contextmanager
def post_without_two():
    with mock.patch.object(hc, "POST_SCALE", 1.0):
        yield


@contextlib.contextmanager
def no_flat_norm():
    with mock.patch.object(
            hc, "flat_rms_inv_fn",
            lambda x, eps: jnp.ones((x.shape[1],), jnp.float32)):
        yield


@contextlib.contextmanager
def streams_bfloat16():
    def rounded(fn):        # (the mappings and mixes promote them again)
        return lambda *a: fn(*a).astype(jnp.bfloat16)

    with mock.patch.object(hc, "post_mix_fn", rounded(hc.post_mix_fn)), \
            mock.patch.object(hc, "spread_fn", rounded(hc.spread_fn)):
        yield


@contextlib.contextmanager
def readout_first_stream():
    with mock.patch.object(hc, "readout_fn", lambda x: x[0]):
        yield


def _raise_one_logit(engine) -> None:
    """The bias of residual logit (0, 1) of every sub-layer, as drawn, plus
    100, in the engine's own weights (the reference reads the same)."""
    n = engine.cfg.hc_mult
    for kind in ("dense", "moe"):
        name = f"dec.layers.{kind}.hc_b"
        b = engine._scope.find_var(name)
        engine._scope.set_var(name, b.at[..., 2 * n + 1].add(100.0))


@contextlib.contextmanager
def logit_past_clip():
    """Not a fault: weights under which one residual logit of every
    sub-layer lies far past any clip (the served init never reaches +-30).
    Yields what to do to the engine once it is built. A right engine passes
    under it; `clip_left_out` stands on it."""
    yield _raise_one_logit


@contextlib.contextmanager
def clip_left_out():
    with mock.patch.object(hc, "clip_fn", lambda res, clamp: res):
        yield _raise_one_logit


FAULTS = {f.__name__: f for f in (
    res_identity, one_sinkhorn_iteration, post_without_two, no_flat_norm,
    streams_bfloat16, readout_first_stream, clip_left_out)}
# engines that are right and must pass: the served weights, and those that
# reach the clip
CONTROLS = {"none": contextlib.nullcontext, "logit_past_clip": logit_past_clip}


def serve_behind(engine, head: list, prompts: list, outs: list,
                 prepare=None) -> list:
    """One request that leaves the shared `head` in the prefix cache, then
    `prompts` (each `head` + a part of its own) all at once, `outs[i]`
    tokens each: [(prompt, served, routes)]. `prepare`: what a fault's or
    control's context handed back, called on the engine first."""
    if prepare is not None:
        prepare(engine)
    first = engine.submit(head + [1, 2, 3], 2)
    engine.run_until_drained()
    engine.pop_result(first)
    rids = [engine.submit(p, n) for p, n in zip(prompts, outs)]
    engine.run_until_drained()
    done = [engine.requests[r] for r in rids]
    return [(p, list(r.out_tokens), r.routes) for p, r in zip(prompts, done)]


def seeded_requests(vocab_size: int, shared: int, unshared: list, out: int,
                    seed: int) -> tuple:
    """A shared prompt of `shared` seeded tokens and one request a length of
    `unshared` behind it, `out` tokens each: (head, prompts, outs)."""
    rng = np.random.default_rng([seed, 47])
    head = rng.integers(1, vocab_size, shared).tolist()
    prompts = [head + rng.integers(1, vocab_size, n).tolist()
               for n in unshared]
    return head, prompts, [out] * len(prompts)


def drive(engine, cfg, shared: int, unshared: list, out: int, seed: int,
          prepare=None):
    """`serve_behind` for `seeded_requests`."""
    return serve_behind(engine, *seeded_requests(
        cfg.vocab_size, shared, unshared, out, seed), prepare)


def cell_requests(root: str, workload: str, seed: int, vocab_size: int,
                  count: int) -> tuple:
    """The first `count` requests of the cell's own traffic (its generator,
    its lengths) behind its most asked-for document: (head, prompts,
    outs)."""
    from benchmark.harness import load_json
    from benchmark.traffic import open_loop

    traffic = load_json(root, "benchmark", "workloads",
                        workload + ".json")["traffic"]
    behind = [r for r in open_loop.generate(traffic, seed, 30.0, vocab_size)
              if r.shared_id == 0][:count]
    head = behind[0].prompt[:behind[0].shared_len]
    return head, [r.prompt for r in behind], [r.max_new for r in behind]


def main(argv=None) -> int:
    import argparse
    import importlib

    from benchmark.harness import load_json
    from paddle_tpu.serving import DecoderConfig, ServingEngine

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="xing4_29b_a4b")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--out", type=int, default=64)
    ap.add_argument("--pool-pages", type=int, default=256)
    ap.add_argument("--seed", type=int, default=2147483741)
    ap.add_argument("--workload", default="",
                    help="serve the first --requests requests of this "
                         "cell's own traffic behind its first document "
                         "(not 3 requests behind a short prompt)")
    ap.add_argument("--requests", type=int, default=8)
    a = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = load_json(root, "benchmark", "configs", a.config + ".json")
    spec = config["engine"]
    cfg = DecoderConfig(**spec["config_kwargs"])
    reference = importlib.import_module(config["reference"]["module"])
    tol = float(config["reference"]["logit_tolerance"])
    margin_tol = float(config["reference"]["route_margin_tolerance"])
    chunk = cfg.prefill_chunk
    head, prompts, outs = cell_requests(
        root, a.workload, a.seed, cfg.vocab_size, a.requests) \
        if a.workload else seeded_requests(
            cfg.vocab_size, 2 * chunk, [3, 40, chunk * 5 // 8], a.out, a.seed)
    wanted = [f for f in a.faults.split(",") if f]
    names = ["none"] + (["logit_past_clip"] if "clip_left_out" in wanted
                        else []) + wanted
    bad = 0
    for name in names:
        with (CONTROLS.get(name) or FAULTS[name])() as prepare:
            engine = ServingEngine(
                cfg, page_size=spec["page_size"],
                pool_pages=min(a.pool_pages, spec["pool_pages"]),
                max_inflight=min(8, spec["max_inflight"]), seed=a.seed,
                prefix_cache=True, draft_k=0)
            served = serve_behind(engine, head, prompts, outs, prepare)
            problems, _ = engine.audit_pool()
        params = reference.read_params(engine._scope.find_var, cfg)
        graded = reference.check_sequences(params, served, cfg)
        gap = max(g["gap"] for g in graded)
        margin = max(g["route_margin"] for g in graded)
        passes = bool(gap <= tol and margin <= margin_tol)
        print("fault", json.dumps({
            "fault": name, "worst_gap": gap, "worst_route_margin": margin,
            "gaps": [g["gap"] for g in graded],
            "route_margins": [g["route_margin"] for g in graded],
            "tolerance": tol,
            "route_margin_tolerance": margin_tol, "passes": passes,
            "audit_problems": len(problems)}), flush=True)
        bad += passes != (name in CONTROLS)
        del engine, params
        gc.collect()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
