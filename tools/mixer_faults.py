"""Wrong mechanisms of the "mixer_moe" block, planted one at a time, and the
drive that shows each of them to the plain reference (`tools/ssm_faults.py`'s
sibling for the family whose layers are a mixer, an attention or latent
experts alone).

`FAULTS` maps a name to a context manager under which a `ServingEngine` of
the block is BUILT AND RUN wrong in exactly one way (its programs are traced
when they first run, so the patch has to stand for the engine's life):

    decay_left_at_one        the decay `a` left at 1
    gate_after_norm          the gate applied after the grouped norm
    no_latent_up_projection  the routed sum's way out of the latent left
                             out (the layer adds its shared expert alone)
    no_routed_scaling        the router's scaling factor left out
    relu_not_squared         the experts' and the shared expert's ReLU not
                             squared
    restore_shares_slot      a resumed row left on the snapshot's slot (two
                             rows then share one state, and mutate the
                             snapshot)
    packed_heads_swapped     the two heads that share a slot's lane rows
                             written the other way round where a window
                             leaves its state (the one-token update then
                             reads each head's state as its neighbour's)

`tests/test_serving_mixer_moe.py` holds each to the reference at the tiny
size;

    python tools/mixer_faults.py [--config nemotron3_super_120b] [--faults a,b]

builds the configuration's engine (on the chip: the served widths) once
right and once under every fault, serves a few requests behind one shared
prompt, grades them with the configuration's reference (the engine's routes
followed) and tolerances, and prints one `fault {...}` line each: the worst
logit gap and route margin and whether they pass the limits. Exit 1 if the
right engine fails or a wrong one passes.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops import mixer_moe_ops as ops  # noqa: E402
from paddle_tpu.ops import parallel_ssm_ops  # noqa: E402
from paddle_tpu.ops.pallas_kernels import ssm_update  # noqa: E402
from paddle_tpu.serving import model as sv_model  # noqa: E402
from tools.ssm_faults import geometry_of, restore_shares_slot  # noqa: E402


@contextlib.contextmanager
def decay_left_at_one():
    real = parallel_ssm_ops._decay_and_input

    def no_decay(*a, **k):
        la, dtx = real(*a, **k)
        return jnp.zeros_like(la), dtx

    with mock.patch.object(parallel_ssm_ops, "_decay_and_input", no_decay):
        yield


@contextlib.contextmanager
def gate_after_norm():
    def norm_then_gate(y, z, gain, groups, eps):
        g = y.reshape(y.shape[:-1] + (groups, -1))
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1,
                                       keepdims=True) + eps)
        return g.reshape(y.shape) * gain.astype(jnp.float32) \
            * (z * jax.nn.sigmoid(z))

    with mock.patch.object(ops, "gated_group_norm_fn", norm_then_gate):
        yield


@contextlib.contextmanager
def no_latent_up_projection():
    def nothing(u, *a, **k):
        return jnp.zeros(u.shape, jnp.float32)

    with mock.patch.object(ops, "latent_experts_fn", nothing):
        yield


@contextlib.contextmanager
def no_routed_scaling():
    real = sv_model._mixer_geometry

    with geometry_of("mixer_moe",
                     lambda cfg: dict(real(cfg), routed_scaling=1.0)):
        yield


@contextlib.contextmanager
def relu_not_squared():
    def experts(u, cw, w1, w2, layer=0, tag="decode"):
        a = jax.lax.dynamic_index_in_dim(w1, layer, 0, keepdims=False)
        b = jax.lax.dynamic_index_in_dim(w2, layer, 0, keepdims=False)
        g = jnp.maximum(jnp.einsum("tz,ezf->etf", u.astype(a.dtype), a,
                                   preferred_element_type=jnp.float32), 0.0)
        hidden = g * cw.astype(jnp.float32).T[:, :, None]
        return jnp.einsum("etf,efz->tz", hidden.astype(a.dtype), b,
                          preferred_element_type=jnp.float32)

    with mock.patch.object(ops, "latent_experts_fn", experts), \
            mock.patch.object(ops, "relu2_fn",
                              lambda x: jnp.maximum(x, 0.0)):
        yield


@contextlib.contextmanager
def packed_heads_swapped():
    real = ssm_update.pack_state

    def swapped(s, pack):
        B, H, N, P = s.shape
        return real(s.reshape(B, H // pack, pack, N, P)[:, :, ::-1].reshape(
            s.shape), pack)

    with mock.patch.object(ssm_update, "pack_state", swapped):
        yield


FAULTS = {f.__name__: f for f in (
    decay_left_at_one, gate_after_norm, no_latent_up_projection,
    no_routed_scaling, relu_not_squared, restore_shares_slot,
    packed_heads_swapped)}


def drive(engine, cfg, shared: int, unshared: list, out: int, seed: int):
    """One request that leaves the shared prompt's snapshots behind, then
    one a length of `unshared` behind the same prompt, all at once:
    [(prompt, served, routes)] of the latter."""
    rng = np.random.default_rng([seed, 43])
    head = rng.integers(1, cfg.vocab_size, shared).tolist()
    first = engine.submit(
        head + rng.integers(1, cfg.vocab_size, unshared[0]).tolist(), 2)
    engine.run_until_drained()
    engine.pop_result(first)
    prompts = [head + rng.integers(1, cfg.vocab_size, n).tolist()
               for n in unshared]
    rids = [engine.submit(p, out) for p in prompts]
    engine.run_until_drained()
    done = [engine.requests[r] for r in rids]
    return [(p, list(r.out_tokens), r.routes) for p, r in zip(prompts, done)]


def main(argv=None, faults=None, config="nemotron3_super_120b",
         seed=2147483693, doc=None) -> int:
    """The drive as a script; another family's tool (`tools/kda_faults.py`)
    runs it over its own `faults`, configuration, seed and description."""
    import argparse
    import importlib

    from benchmark.harness import load_json
    from paddle_tpu.serving import DecoderConfig, ServingEngine

    faults = FAULTS if faults is None else faults
    ap = argparse.ArgumentParser(
        description=(doc or __doc__).split("\n\n")[0])
    ap.add_argument("--config", default=config)
    ap.add_argument("--faults", default=",".join(faults))
    ap.add_argument("--out", type=int, default=96)
    ap.add_argument("--seed", type=int, default=seed)
    a = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = load_json(root, "benchmark", "configs", a.config + ".json")
    spec = config["engine"]
    cfg = DecoderConfig(**spec["config_kwargs"])
    reference = importlib.import_module(config["reference"]["module"])
    tol = float(config["reference"]["logit_tolerance"])
    margin_tol = float(config["reference"]["route_margin_tolerance"])
    chunk = cfg.prefill_chunk
    # two suffixes shorter than the convolution's tail (their first served
    # tokens still read rows of the window before) and two padded windows
    # of over half a chunk
    unshared = [2, 3, chunk * 5 // 8, chunk * 3 // 4]
    bad = 0
    for name in ["none"] + [f for f in a.faults.split(",") if f]:
        with faults[name]() if name != "none" else contextlib.nullcontext():
            engine = ServingEngine(
                cfg, page_size=spec["page_size"],
                pool_pages=spec["pool_pages"],
                max_inflight=spec["max_inflight"], seed=a.seed,
                prefix_cache=True, draft_k=0)
            served = drive(engine, cfg, 2 * chunk, unshared, a.out, a.seed)
            problems, _ = engine.audit_pool()
        params = reference.read_params(engine._scope.find_var, cfg)
        graded = reference.check_sequences(params, served, cfg)
        gap = max(g["gap"] for g in graded)
        margin = max(g["route_margin"] for g in graded)
        passes = gap <= tol and margin <= margin_tol
        print("fault", json.dumps({
            "fault": name, "worst_gap": gap, "worst_route_margin": margin,
            "gaps": [g["gap"] for g in graded], "tolerance": tol,
            "route_margin_tolerance": margin_tol, "passes": passes,
            "audit_problems": len(problems),
            "restores": engine.stats["state.restores"]}), flush=True)
        bad += passes != (name == "none")
        del engine, params
        gc.collect()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
