"""Short-sequence (S <= 128) fused attention: a Pallas TPU kernel.

Why this exists (PERF.md r4/r5, ISSUE 9): the bundled flash-attention
kernel measures 42-52% SLOWER than XLA's own attention fusion at seq <= 128
on v5e — its KV-block pipeline is built for long sequences and pays its
grid/DMA overhead per tiny block. The existing short_seq kernel
(attention.py) starts at S = 128 exactly (S % 128 == 0); BERT-style
training at s64/s96 and every ragged tail below 128 had no custom arm at
all. This kernel owns that regime:

  * one grid step per batch row: the ENTIRE [nh, S, dh] Q/K/V slab of a
    row fits VMEM at S <= 128 (12 heads x 128 x 64 fp32 = 384 KB/tensor),
    so scores never touch HBM and the MXU stays pipelined across heads via
    batched dot_general — the attention.py design pushed below its 128
    floor by letting Pallas pad the [S, S] tile instead of requiring lane
    multiples.
  * ragged rows: an optional kv_lens [B] masks key slots >= len inside
    the fp32 softmax (the framework-wide batch_mask convention); a fully
    masked row emits zeros, not NaN (the paged_attention.py discipline),
    so bucket-padded batches ride through unchanged.
  * backward saves nothing but q/k/v (softmax recomputed on-chip), fusing
    all five gradient matmuls in one kernel, ragged mask included.

Dispatch: the `pallas_short128` arm of ops/attention_ops.attention_backend.
Ships OFF by default (the r5 rule) — the analytic prior keeps XLA at short
sequences because that is what was measured; only a swept tuning-DB verdict
(or FLAGS_attention_force_backend, the A/B harness override) routes here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import workbench

_NEG_INF = -1e30
# clamp floor for the row max: a fully-masked row's scores are all
# _NEG_INF; clamping m keeps exp(s - m) == 0 there so l == 0 and the
# output is emitted as zeros instead of a uniform average (or NaN)
_M_FLOOR = -0.5e30

# tests flip this to run the kernel through the Pallas interpreter on CPU
INTERPRET = False


def short128_supported(q_shape, k_shape, bias=None, dropout_rate=0.0) -> bool:
    """Shapes this kernel handles: self-attention with sq == sk <= 128
    (any length — Pallas pads the tile), dh sublane-aligned and <= 128,
    no additive bias/dropout (those change the softmax the kernel fuses)."""
    if bias is not None or dropout_rate:
        return False
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    B, nh, sq, dh = q_shape
    sk = k_shape[2]
    return sq == sk and 1 <= sq <= 128 and dh % 8 == 0 and dh <= 128


def _masked_scores(q, k, sm_scale, causal, kv_len):
    """Batched-over-heads QK^T [nh,S,dh] x [nh,S,dh] -> [nh,S,S] fp32 with
    the causal and ragged masks applied in the score domain."""
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale
    S = s.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, S, S), 2)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (1, S, S), 1)
        s = jnp.where(row >= col, s, _NEG_INF)
    if kv_len is not None:
        s = jnp.where(col < kv_len, s, _NEG_INF)
    return s


def _softmax(s):
    """Row softmax returning (p, l): fully-masked rows get p == 0, l == 0
    (see _M_FLOOR), so the caller divides by max(l, tiny) and emits zeros."""
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), _M_FLOOR)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return p, l


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal, ragged):
    if ragged:
        kl_ref, o_ref = rest
        kv_len = kl_ref[pl.program_id(0)]
    else:
        (o_ref,) = rest
        kv_len = None
    q, k, v = q_ref[0], k_ref[0], v_ref[0]              # [nh, S, dh]
    s = _masked_scores(q, k, sm_scale, causal, kv_len)
    p, l = _softmax(s)
    o = jax.lax.dot_general(p.astype(v.dtype), v,
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal, ragged):
    if ragged:
        kl_ref, do_ref, dq_ref, dk_ref, dv_ref = rest
        kv_len = kl_ref[pl.program_id(0)]
    else:
        do_ref, dq_ref, dk_ref, dv_ref = rest
        kv_len = None
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    s = _masked_scores(q, k, sm_scale, causal, kv_len)
    e, l = _softmax(s)
    p = e / jnp.maximum(l, 1e-30)                       # [nh, S, S] fp32
    pb = p.astype(q.dtype)
    # dV = P^T dO
    dv = jax.lax.dot_general(pb, do, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    # dP = dO V^T
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    # softmax vjp: dS = P (.) (dP - rowsum(dP (.) P)) — masked slots have
    # P == 0, so no second masking pass is needed
    delta = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
    dq = jax.lax.dot_general(ds, k, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    dk = jax.lax.dot_general(ds, q, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _specs(nh, s, dh, ragged, n_io):
    qspec = pl.BlockSpec((1, nh, s, dh), lambda b: (b, 0, 0, 0))
    # kv_lens rides whole in scalar memory, indexed by the grid position: a
    # per-row (1, 1) block of a [B, 1] array is not (8, 128)-tileable
    klspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [qspec] * n_io + ([klspec] if ragged else [])
    return qspec, in_specs


def _fwd(q, k, v, kv_lens, sm_scale, causal, interpret):
    B, nh, s, dh = q.shape
    ragged = kv_lens is not None
    qspec, in_specs = _specs(nh, s, dh, ragged, 3)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, ragged=ragged)
    args = (q, k, v) + ((kv_lens.astype(jnp.int32),) if ragged else ())
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=B * nh * 2 * 2 * s * s * dh,
            bytes_accessed=4 * B * nh * s * dh * q.dtype.itemsize,
            transcendentals=B * nh * s * s),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="short128_attention_fwd",
    )(*args)


def _bwd(q, k, v, kv_lens, do, sm_scale, causal, interpret):
    B, nh, s, dh = q.shape
    ragged = kv_lens is not None
    qspec, in_specs = _specs(nh, s, dh, ragged, 3)
    kernel = functools.partial(_bwd_kernel, sm_scale=sm_scale,
                               causal=causal, ragged=ragged)
    args = (q, k, v) + ((kv_lens.astype(jnp.int32),) if ragged
                        else ()) + (do,)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=in_specs + [qspec],
        out_specs=[qspec] * 3,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3,
        cost_estimate=pl.CostEstimate(
            flops=B * nh * 5 * 2 * s * s * dh,
            bytes_accessed=7 * B * nh * s * dh * q.dtype.itemsize,
            transcendentals=B * nh * s * s),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="short128_attention_bwd",
    )(*args)


@functools.lru_cache(maxsize=None)
def _make(sm_scale: float, causal: bool, ragged: bool, interpret: bool):
    if ragged:
        @jax.custom_vjp
        def attn(q, k, v, kv_lens):
            return _fwd(q, k, v, kv_lens, sm_scale, causal, interpret)

        def fwd(q, k, v, kv_lens):
            return _fwd(q, k, v, kv_lens, sm_scale, causal, interpret), \
                (q, k, v, kv_lens)

        def bwd(res, do):
            q, k, v, kv_lens = res
            dq, dk, dv = _bwd(q, k, v, kv_lens, do, sm_scale, causal,
                              interpret)
            return dq, dk, dv, None
    else:
        @jax.custom_vjp
        def attn(q, k, v):
            return _fwd(q, k, v, None, sm_scale, causal, interpret)

        def fwd(q, k, v):
            return _fwd(q, k, v, None, sm_scale, causal, interpret), \
                (q, k, v)

        def bwd(res, do):
            q, k, v = res
            return _bwd(q, k, v, None, do, sm_scale, causal, interpret)

    attn.defvjp(fwd, bwd)
    return attn


def _reference(q, k, v, causal=False, sm_scale=1.0, kv_lens=None):
    """The XLA composition defining the kernel's numerics — the
    attention_ops reference with the ragged-key mask added."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    scores = scores * sm_scale
    S = scores.shape[-1]
    col = jnp.arange(S, dtype=jnp.int32)
    if causal:
        scores = jnp.where(col[None, None, None, :] <= col[None, None, :, None],
                           scores, _NEG_INF)
    if kv_lens is not None:
        live = col[None, None, None, :] < kv_lens[:, None, None, None]
        scores = jnp.where(live, scores, _NEG_INF)
    m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), _M_FLOOR)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


@workbench.register_kernel(
    "attention_short128",
    reference=_reference,
    supported=short128_supported,
    decision_op="attention",
    equivalence_test="test_short128_attention_matches_reference",
    note="fused self-attention for sq == sk <= 128 (whole row in VMEM, "
         "ragged kv_lens masking, fused no-residual backward)")
def short128_attention(q, k, v, causal=False, sm_scale=1.0, kv_lens=None):
    """Fused attention for sequence lengths up to 128.

    q, k, v: [B, nh, S, dh] with S == Sk <= 128, dh % 8 == 0, dh <= 128
    (callers gate on `short128_supported`). kv_lens: optional [B] int32 —
    key slots >= kv_lens[b] are masked out of row b's softmax; a row with
    kv_lens 0 emits zeros. Returns [B, nh, S, dh] in q's dtype;
    differentiable in q/k/v (softmax recomputed on-chip, no residuals)."""
    fn = _make(float(sm_scale), bool(causal), kv_lens is not None,
               bool(INTERPRET))
    if kv_lens is not None:
        return fn(q, k, v, kv_lens)
    return fn(q, k, v)
