"""Wrong mechanisms of the "parallel_ssm" block, planted one at a time, and
the drive that shows each of them to the plain reference.

A tolerance that no wrong engine exceeds decides nothing. `FAULTS` maps a
name to a context manager under which a `ServingEngine` of the block is
BUILT AND RUN wrong in exactly one way (its programs are traced when they
first run, so the patch has to stand for the engine's life):

    state_in_bfloat16     S kept in bfloat16: what a window's chunks hand
                          on and leave in the slot, and what every decode
                          token writes back
    decay_left_at_one     the decay `a` left at 1
    conv_tail_zeroed      the convolution's tail zeroed where a window starts
    state_after_padding   a window's state (and tail) written after its
                          padding, not after its last real token
    restore_shares_slot   a resumed row left on the snapshot's slot (two rows
                          then share one state, and mutate the snapshot)
    no_attention          the attention branch left out
    no_key_multiplier     the key multiplier left out
    gate_after_norm       the gate applied after the grouped norm

`tests/test_serving_ssm.py` holds each to the reference at the tiny size;

    python tools/ssm_faults.py [--config falcon_h1_34b] [--faults a,b]

builds the configuration's engine (on the chip: the served widths) once
right and once under every fault, serves a few requests behind one shared
prompt, grades them with the configuration's reference and tolerance, and
prints one `fault {...}` line each: the worst logit gap and whether it
passes the limit. Exit 1 if the right engine fails or a wrong one passes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops import parallel_ssm_ops as ops  # noqa: E402
from paddle_tpu.ops.pallas_kernels import ssm_update  # noqa: E402
from paddle_tpu.serving import engine as sv_engine  # noqa: E402
from paddle_tpu.serving import model as sv_model  # noqa: E402


def geometry_of(block: str, geometry):
    """Programs built inside carry `geometry(cfg)` as the attributes of the
    `block` family's op (its row of `FAMILIES` with that one field
    replaced)."""
    row = dataclasses.replace(sv_model.FAMILIES[block], geometry=geometry)
    return mock.patch.dict(sv_model.FAMILIES, {block: row})


def _rounded(s):
    return s.astype(jnp.bfloat16).astype(jnp.float32)


@contextlib.contextmanager
def state_in_bfloat16():
    scan = ops.ssd_scan_fn

    def scan_rounded(x, dt_raw, bmat, cmat, dt_bias, a_log, s0, chunk,
                     valid=None):
        # the state handed from chunk to chunk goes through bfloat16
        ys, s = [], _rounded(s0)
        for c0 in range(0, x.shape[1], int(chunk)):
            cut = slice(c0, c0 + int(chunk))
            y, s = scan(x[:, cut], dt_raw[:, cut], bmat[:, cut],
                        cmat[:, cut], dt_bias, a_log, s, chunk,
                        None if valid is None else valid[:, cut])
            ys.append(y)
            s = _rounded(s)
        return jnp.concatenate(ys, axis=1), s

    def token_rounded(s_pool, idx, x, dt_raw, bmat, cmat, dt_bias, a_log,
                      n_live=None):
        la, dtx = ops._decay_and_input(x, dt_raw, dt_bias, a_log)
        pool, y = ssm_update._reference(s_pool, idx, jnp.exp(la), dtx, bmat,
                                        cmat, n_live)
        idx = jnp.clip(idx, 0, pool.shape[0] - 1)
        return pool.at[idx].set(_rounded(pool[idx])), y

    with mock.patch.object(ops, "ssd_scan_fn", scan_rounded), \
            mock.patch.object(ops, "ssm_token_update_fn", token_rounded):
        yield


@contextlib.contextmanager
def decay_left_at_one():
    real = ops._decay_and_input

    def no_decay(*a, **k):
        la, dtx = real(*a, **k)
        return jnp.zeros_like(la), dtx

    with mock.patch.object(ops, "_decay_and_input", no_decay):
        yield


@contextlib.contextmanager
def conv_tail_zeroed():
    real = ops.causal_conv_fn

    def zeroed(xbc, tail, *a, **k):
        if xbc.shape[1] > 1:            # a window, not a decode token
            tail = jnp.zeros_like(tail)
        return real(xbc, tail, *a, **k)

    with mock.patch.object(ops, "causal_conv_fn", zeroed):
        yield


@contextlib.contextmanager
def state_after_padding():
    decay, conv = ops._decay_and_input, ops.causal_conv_fn

    def every_token(x, dt_raw, dt_bias, a_log, valid=None):
        return decay(x, dt_raw, dt_bias, a_log)

    def last_row(xbc, tail, conv_w, conv_b, lens=None):
        return conv(xbc, tail, conv_w, conv_b)

    with mock.patch.object(ops, "_decay_and_input", every_token), \
            mock.patch.object(ops, "causal_conv_fn", last_row):
        yield


@contextlib.contextmanager
def restore_shares_slot():
    real = sv_engine.ServingEngine._prefill

    def on_the_snapshot(self, req):
        if req.snap is not None:
            self.state_pool.release([req.sslot])
            req.sslot, req.snap = req.snap, None    # the pin is its hold
        return real(self, req)

    with mock.patch.object(sv_engine.ServingEngine, "_prefill",
                           on_the_snapshot):
        yield


@contextlib.contextmanager
def no_attention():
    def window(q, *a, **k):
        return jnp.zeros(q.shape, jnp.float32)

    with mock.patch.object(ops, "causal_attention_fn", window), \
            mock.patch.object(ops, "paged_decode_attention_fn", window):
        yield


@contextlib.contextmanager
def no_key_multiplier():
    real = sv_model._ssm_geometry

    with geometry_of("parallel_ssm",
                     lambda cfg: dict(real(cfg), key_multiplier=1.0)):
        yield


@contextlib.contextmanager
def gate_after_norm():
    def norm_then_gate(y, z, gain, groups, eps):
        import jax

        g = y.reshape(y.shape[:-1] + (groups, -1))
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1,
                                       keepdims=True) + eps)
        return g.reshape(y.shape) * gain.astype(jnp.float32) \
            * (z * jax.nn.sigmoid(z))

    with mock.patch.object(ops, "gated_group_norm_fn", norm_then_gate):
        yield


FAULTS = {f.__name__: f for f in (
    state_in_bfloat16, decay_left_at_one, conv_tail_zeroed,
    state_after_padding, restore_shares_slot, no_attention,
    no_key_multiplier, gate_after_norm)}


def drive(engine, cfg, shared: int, unshared: list, out: int, seed: int):
    """One request that leaves the shared prompt's snapshots behind, then
    one a length of `unshared` behind the same prompt, all at once:
    [(prompt, served)] of the latter."""
    rng = np.random.default_rng([seed, 41])
    head = rng.integers(1, cfg.vocab_size, shared).tolist()
    first = engine.submit(
        head + rng.integers(1, cfg.vocab_size, unshared[0]).tolist(), 2)
    engine.run_until_drained()
    engine.pop_result(first)
    prompts = [head + rng.integers(1, cfg.vocab_size, n).tolist()
               for n in unshared]
    rids = [engine.submit(p, out) for p in prompts]
    engine.run_until_drained()
    return [(p, engine.pop_result(r)) for p, r in zip(prompts, rids)]


def main(argv=None) -> int:
    import argparse
    import importlib

    from benchmark.harness import load_json
    from paddle_tpu.serving import DecoderConfig, ServingEngine

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="falcon_h1_34b")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--out", type=int, default=96)
    ap.add_argument("--seed", type=int, default=2147483693)
    a = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = load_json(root, "benchmark", "configs", a.config + ".json")
    spec = config["engine"]
    cfg = DecoderConfig(**spec["config_kwargs"])
    reference = importlib.import_module(config["reference"]["module"])
    tol = float(config["reference"]["logit_tolerance"])
    chunk = cfg.prefill_chunk
    # two suffixes shorter than the convolution's tail (their first served
    # tokens still read rows of the window before) and two padded windows
    # of over half a chunk
    unshared = [2, 3, chunk * 5 // 8, chunk * 3 // 4]
    bad = 0
    for name in ["none"] + [f for f in a.faults.split(",") if f]:
        with FAULTS[name]() if name != "none" else contextlib.nullcontext():
            engine = ServingEngine(
                cfg, page_size=spec["page_size"],
                pool_pages=spec["pool_pages"],
                max_inflight=spec["max_inflight"], seed=a.seed,
                prefix_cache=True, draft_k=0)
            served = drive(engine, cfg, 2 * chunk, unshared, a.out, a.seed)
            problems, _ = engine.audit_pool()
        params = reference.read_params(engine._scope.find_var, cfg)
        gaps = reference.worst_logit_gaps(params, served, cfg)
        passes = max(gaps) <= tol
        print("fault", json.dumps({
            "fault": name, "worst_gap": max(gaps), "gaps": gaps,
            "tolerance": tol, "passes": passes,
            "audit_problems": len(problems),
            "restores": engine.stats["state.restores"]}), flush=True)
        bad += passes != (name == "none")
        del engine, params
        gc.collect()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
