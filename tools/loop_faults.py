"""Wrong mechanisms of the "looped_dense" block, planted one at a time, and
the drive that shows each of them to the plain reference
(`tools/kda_faults.py`'s sibling for the family whose layers a token passes
several times).

`FAULTS` maps a name to a context manager under which a `ServingEngine` of
the block is BUILT AND RUN wrong in exactly one way (its programs are traced
when they first run, so the patch has to stand for the engine's life):

    one_plane_set   every visit reads and writes the FIRST visit's planes of
                    K/V pages (the cache shared between the visits: the
                    tempting shortcut, a quarter of the pool): a later token
                    then attends, in every visit, what the LAST visit wrote
    three_visits    one visit fewer than the configuration's (the pools keep
                    their size): logits from the state a visit early

`tests/test_serving_looped.py` holds each to the reference at the tiny size;

    python tools/loop_faults.py [--config ouro_2_6b] [--faults a,b]

builds the configuration's engine (on the chip: the served widths, the timed
engine's page size, pool and row slots) once right and once under every
fault, serves a few requests behind one shared prompt, some of them over
several windows, grades them with the configuration's reference and
tolerance, and prints one `fault {...}` line each: the worst logit gap and
whether it passes the limit. For the right engine it also holds the exit
gate's masses the engine handed back (`request.exit_mass`, one row a served
token) to the reference's (`exit_mass_gap`, under `--mass-tolerance`) and to
a sum of 1: the accepted runner grades logits alone. Exit 1 if the right
engine fails or a wrong one passes.
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

from paddle_tpu.ops import looped_dense_ops as ops  # noqa: E402
from paddle_tpu.serving import model as sv_model  # noqa: E402
from tools.ssm_faults import geometry_of  # noqa: E402


@contextlib.contextmanager
def one_plane_set():
    with mock.patch.object(
            ops, "visit_planes_fn",
            lambda t, num_layers: jnp.arange(num_layers, dtype=jnp.int32)):
        yield


@contextlib.contextmanager
def three_visits():
    real = sv_model._looped_geometry

    def one_fewer(cfg):
        geometry = real(cfg)
        return dict(geometry, loop_steps=geometry["loop_steps"] - 1)

    with geometry_of("looped_dense", one_fewer):
        yield


FAULTS = {f.__name__: f for f in (one_plane_set, three_visits)}


def drive(engine, cfg, shared: int, unshared: list, out: int, seed: int):
    """One request that leaves the shared prompt in the prefix cache, then
    one a length of `unshared` behind the same prompt, all at once:
    [(prompt, the finished request)] of the latter."""
    rng = np.random.default_rng([seed, 53])
    head = rng.integers(1, cfg.vocab_size, shared).tolist()
    first = engine.submit(
        head + rng.integers(1, cfg.vocab_size, unshared[0]).tolist(), 2)
    engine.run_until_drained()
    engine.pop_result(first)
    prompts = [head + rng.integers(1, cfg.vocab_size, n).tolist()
               for n in unshared]
    rids = [engine.submit(p, out) for p in prompts]
    engine.run_until_drained()
    return [(p, engine.requests[r]) for p, r in zip(prompts, rids)]


def exit_mass_gap(reference, params, served: list, cfg) -> tuple:
    """(the largest difference between a mass the engine handed back and the
    reference's at the same position and visit, the largest distance of a
    row's sum from 1) over `served` [(prompt, request)]."""
    gap = off = 0.0
    for prompt, req in served:
        got = np.stack(req.exit_mass)
        want = reference.exit_mass(
            params, list(prompt) + list(req.out_tokens), cfg)
        want = want[len(prompt) - 1:len(prompt) - 1 + len(got)]
        gap = max(gap, float(np.max(np.abs(got - want))))
        off = max(off, float(np.max(np.abs(got.sum(axis=1) - 1.0))))
    return gap, off


def main(argv=None) -> int:
    import argparse
    import importlib

    from benchmark.harness import load_json
    from paddle_tpu.serving import DecoderConfig, ServingEngine

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="ouro_2_6b")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--out", type=int, default=96)
    ap.add_argument("--seed", type=int, default=2147483707)
    ap.add_argument("--mass-tolerance", type=float, default=0.02)
    a = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = load_json(root, "benchmark", "configs", a.config + ".json")
    spec = config["engine"]
    cfg = DecoderConfig(**spec["config_kwargs"])
    reference = importlib.import_module(config["reference"]["module"])
    tol = float(config["reference"]["logit_tolerance"])
    chunk = cfg.prefill_chunk
    # behind a shared prompt of half a chunk: two short suffixes, a padded
    # window, and one that crosses into a second window
    unshared = [3, chunk // 8, chunk * 3 // 8, chunk * 3 // 4]
    bad = 0
    for name in ["none"] + [f for f in a.faults.split(",") if f]:
        with FAULTS[name]() if name != "none" else contextlib.nullcontext():
            engine = ServingEngine(
                cfg, page_size=spec["page_size"],
                pool_pages=spec["pool_pages"],
                max_inflight=spec["max_inflight"], seed=a.seed,
                prefix_cache=True, draft_k=0)
            served = drive(engine, cfg, chunk // 2, unshared, a.out, a.seed)
            problems, _ = engine.audit_pool()
        params = reference.read_params(engine._scope.find_var, cfg)
        gaps = reference.worst_logit_gaps(
            params, [(p, r.out_tokens) for p, r in served], cfg)
        line = {"fault": name, "worst_gap": max(gaps), "gaps": gaps,
                "tolerance": tol, "passes": max(gaps) <= tol,
                "audit_problems": len(problems),
                "leaked_pages": engine.leaked_pages()}
        if name == "none":
            mass_gap, off = exit_mass_gap(reference, params, served, cfg)
            line.update(exit_mass_gap=mass_gap, exit_mass_sum_off=off,
                        mass_tolerance=a.mass_tolerance)
            line["passes"] = bool(line["passes"]
                                  and mass_gap <= a.mass_tolerance
                                  and off <= 1e-4)
        print("fault", json.dumps(line), flush=True)
        bad += line["passes"] != (name == "none")
        del engine, params, served
        gc.collect()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
