"""Wrong mechanisms of the "kda_moe" block, planted one at a time, and the
drive that shows each of them to the plain reference (`tools/mixer_faults.py`'s
sibling for the family whose layers keep a matrix state in a slot beside a
latent row in pages).

`FAULTS` maps a name to a context manager under which a `ServingEngine` of
the block is BUILT AND RUN wrong in exactly one way (its programs are traced
when they first run, so the patch has to stand for the engine's life):

    decay_left_at_one      the Kimi-Delta decay `a` left at 1 (log a = 0)
    no_delta_term          the delta rule's read of the state left out: `S +=
                           beta k v^T` (a gated linear attention), in the
                           token update and in the chunked form alike
    beta_left_at_one       the step `beta` left at 1
    gate_before_norm       the output gate applied before the head norm
    restore_shares_slot    a resumed row left on the snapshot's slot (two
                           rows then share one state, and mutate the
                           snapshot)
    no_head_gate           the latent layer's gate a head left out
    no_routed_scaling      the router's scaling factor 2.5 left out

`tests/test_serving_kda.py` holds each to the reference at the tiny size;

    python tools/kda_faults.py [--config ling3_flash] [--faults a,b]

builds the configuration's engine (on the chip: the served widths) once right
and once under every fault, serves a few requests behind one shared prompt
(`mixer_faults.main` and `.drive`: two suffixes under the convolution's tail, two
padded windows), grades them with the configuration's reference (the engine's
routes followed) and tolerances, and prints one `fault {...}` line each: the
worst logit gap and route margin and whether they pass the limits. Exit 1 if
the right engine fails or a wrong one passes.
"""
from __future__ import annotations

import contextlib
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops import kda_ops as ops  # noqa: E402
from paddle_tpu.ops.pallas_kernels import kda_update  # noqa: E402
from paddle_tpu.serving import model as sv_model  # noqa: E402
from tools import mixer_faults  # noqa: E402
from tools.ssm_faults import geometry_of, restore_shares_slot  # noqa: E402


@contextlib.contextmanager
def decay_left_at_one():
    real = ops.kda_gate_fn

    with mock.patch.object(
            ops, "kda_gate_fn",
            lambda *a, **k: jnp.zeros_like(real(*a, **k))):
        yield


@contextlib.contextmanager
def no_delta_term():
    """`S <- Diag(a) S + beta k v^T`: nothing of the state is read before
    the write."""
    def update(s_pool, idx, q, k, v, log_a, beta, n_live=None):
        B, H, K = k.shape
        live = jnp.arange(B) < kda_update.live_count(n_live, B)
        s = jnp.exp(log_a)[..., None] * s_pool[idx].reshape(B, H, K, -1) \
            + k[..., None] * (beta[..., None] * v)[:, :, None, :]
        o = jnp.sum(s * q[..., None], axis=2)
        return s_pool.at[jnp.where(live, idx, s_pool.shape[0])].set(
            s.reshape((B,) + s_pool.shape[1:]), mode="drop"), \
            jnp.where(live[:, None, None], o, 0.0)

    def scan(q, k, v, log_a, beta, s0, chunk, sub, lower_bound, valid=None):
        log_a, beta = ops._silence(log_a, beta, valid)

        def step(s, xs):
            q_t, k_t, v_t, la_t, b_t = xs
            s = jnp.exp(la_t)[..., None] * s \
                + k_t[..., None] * (b_t[..., None] * v_t)[:, :, None, :]
            return s, jnp.sum(s * q_t[..., None], axis=2)

        swap = lambda a: jnp.moveaxis(a, 1, 0)              # noqa: E731
        s, o = jax.lax.scan(step, s0, tuple(
            swap(a) for a in (q, k, v, log_a, beta)))
        return jnp.moveaxis(o, 0, 1), s

    with mock.patch.object(ops, "kda_token_update_fn", update), \
            mock.patch.object(ops, "kda_chunk_scan_fn", scan):
        yield


@contextlib.contextmanager
def beta_left_at_one():
    real_update, real_scan = ops.kda_token_update_fn, ops.kda_chunk_scan_fn

    def update(s_pool, idx, q, k, v, log_a, beta, n_live=None):
        return real_update(s_pool, idx, q, k, v, log_a,
                           jnp.ones_like(beta), n_live)

    def scan(q, k, v, log_a, beta, *a, **kw):
        return real_scan(q, k, v, log_a, jnp.ones_like(beta), *a, **kw)

    with mock.patch.object(ops, "kda_token_update_fn", update), \
            mock.patch.object(ops, "kda_chunk_scan_fn", scan):
        yield


@contextlib.contextmanager
def gate_before_norm():
    def gate_then_norm(o, gate_raw, gain, eps):
        y = o * jax.nn.sigmoid(gate_raw).reshape(o.shape)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1,
                                       keepdims=True) + eps)
        return (y * gain.astype(jnp.float32)).reshape(gate_raw.shape)

    with mock.patch.object(ops, "gated_head_norm_fn", gate_then_norm):
        yield


@contextlib.contextmanager
def no_head_gate():
    with mock.patch.object(ops, "head_gate_fn", lambda o, gate_raw: o):
        yield


@contextlib.contextmanager
def no_routed_scaling():
    real = sv_model._kda_geometry

    with geometry_of("kda_moe",
                     lambda cfg: dict(real(cfg), routed_scaling=1.0)):
        yield


FAULTS = {f.__name__: f for f in (
    decay_left_at_one, no_delta_term, beta_left_at_one, gate_before_norm,
    restore_shares_slot, no_head_gate, no_routed_scaling)}


def main(argv=None) -> int:
    return mixer_faults.main(argv, FAULTS, "ling3_flash", 2147483699,
                             __doc__)


if __name__ == "__main__":
    sys.exit(main())
