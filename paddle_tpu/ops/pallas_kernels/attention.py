"""Short-sequence fused attention: a hand-tuned Pallas TPU kernel.

Why this exists (PERF.md): BERT-base at seq 128 spends ~54 ms of a 171.8 ms
step in the attention block, only ~20 ms of which is matmul — the rest is
the [B, nh, S, S] score/softmax tensors and the [B,S,nh,dh]<->[B,nh,S,dh]
transposes round-tripping HBM between XLA fusions. jax's bundled
flash-attention kernel is tuned for long sequences (KV-block pipelines) and
measures *slower* than XLA at S<=512 on v5e.

Design — exploit that for short S the ENTIRE per-head problem fits in VMEM:
  * grid over (batch, head-block): each step DMAs [gh, S, dh] slabs of
    Q/K/V once, runs batched-over-heads QK^T -> softmax -> PV entirely
    on-chip, writes only the output. The S x S scores NEVER touch HBM.
  * batched `dot_general` over the head dim keeps the MXU pipelined
    across heads (per-head [S,dh] matmuls would drain it every head).
  * fp32 softmax statistics; bf16 MXU operands; fp32 accumulation.
  * the backward saves NO residuals beyond q/k/v: with whole rows in
    VMEM it recomputes softmax exactly, and the softmax-vjp identity
    delta = rowsum(dP (.) P) removes the need for O. One kernel fuses all
    five gradient matmuls.

Reference role: replaces the reference's scaled_dot_product_attention
composition (python/paddle/fluid/nets.py:345) on the TPU hot path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import workbench

_NEG_INF = -1e30
# per-step VMEM budget for the head-block (bytes); leaves room for double
# buffering + score scratch inside ~16 MB of VMEM
_VMEM_BUDGET = 3 * 1024 * 1024

# tests flip this to run the kernels through the Pallas interpreter on CPU
INTERPRET = False


def short_seq_supported(q_shape, k_shape, bias, dropout_rate=0.0) -> bool:
    """Shapes this kernel handles: self-attention, S multiple of 128 with
    the score matrix VMEM-resident, dh lane-friendly, no additive bias."""
    if bias is not None or dropout_rate:
        return False
    B, nh, sq, dh = q_shape
    sk = k_shape[2]
    # S cap from the bwd kernel's VMEM needs at gh=1: ~5 fp32/bf16 [S,S]
    # intermediates (s, p, pb, dp, ds) must fit alongside the slabs — fine
    # at S=512 (~5 MB), not at S=1024 (~18 MB > VMEM)
    return (sq == sk and sq % 128 == 0 and sq <= 512
            and dh % 8 == 0 and dh <= 256)


def _head_block(nh: int, s: int, dh: int, itemsize: int, n_tensors: int) -> int:
    """Largest divisor of nh whose per-step slab fits the VMEM budget."""
    per_head = s * dh * itemsize * n_tensors + 3 * s * s * 4
    gh = nh
    while gh > 1 and gh * per_head > _VMEM_BUDGET:
        gh -= 1
        while nh % gh:
            gh -= 1
    return gh


def _causal_mask(s):
    row = jax.lax.broadcasted_iota(jnp.int32, (1, s, s), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, s, s), 2)
    return row >= col


def _scores(q, k, sm_scale, causal):
    """Batched QK^T over the head dim: [gh,S,dh] x [gh,S,dh] -> [gh,S,S]."""
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        s = jnp.where(_causal_mask(s.shape[-1]), s, _NEG_INF)
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, sm_scale, causal):
    q, k, v = q_ref[0], k_ref[0], v_ref[0]            # [gh, S, dh]
    s = _scores(q, k, sm_scale, causal)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(p.astype(v.dtype), v,
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    o_ref[0] = (o / l).astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref,
                *, sm_scale, causal):
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    s = _scores(q, k, sm_scale, causal)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)        # [gh, Sq, Sk] fp32
    pb = p.astype(q.dtype)
    # dV = P^T dO  (contract the query dim per head)
    dv = jax.lax.dot_general(pb, do, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    # dP = dO V^T
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    # softmax vjp: dS = P (.) (dP - rowsum(dP (.) P)); the rowsum equals
    # rowsum(dO (.) O), so O is never needed
    delta = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
    dq = jax.lax.dot_general(ds, k, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    dk = jax.lax.dot_general(ds, q, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _hb_spec(gh, s, dh):
    return pl.BlockSpec((1, gh, s, dh), lambda b, h: (b, h, 0, 0))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))


def _fwd(q, k, v, sm_scale, causal, interpret):
    B, nh, s, dh = q.shape
    gh = _head_block(nh, s, dh, q.dtype.itemsize, 4)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(B, nh // gh),
        in_specs=[_hb_spec(gh, s, dh)] * 3,
        out_specs=_hb_spec(gh, s, dh),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=B * nh * 2 * 2 * s * s * dh,
            bytes_accessed=4 * B * nh * s * dh * q.dtype.itemsize,
            transcendentals=B * nh * s * s),
        compiler_params=_params(),
        interpret=interpret,
        name="short_seq_attention_fwd",
    )(q, k, v)


def _bwd(q, k, v, do, sm_scale, causal, interpret):
    B, nh, s, dh = q.shape
    gh = _head_block(nh, s, dh, q.dtype.itemsize, 7)
    kernel = functools.partial(_bwd_kernel, sm_scale=sm_scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(B, nh // gh),
        in_specs=[_hb_spec(gh, s, dh)] * 4,
        out_specs=[_hb_spec(gh, s, dh)] * 3,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3,
        cost_estimate=pl.CostEstimate(
            flops=B * nh * 5 * 2 * s * s * dh,
            bytes_accessed=7 * B * nh * s * dh * q.dtype.itemsize,
            transcendentals=B * nh * s * s),
        compiler_params=_params(),
        interpret=interpret,
        name="short_seq_attention_bwd",
    )(q, k, v, do)


@functools.lru_cache(maxsize=None)
def _make(sm_scale: float, causal: bool, interpret: bool):
    @jax.custom_vjp
    def attn(q, k, v):
        return _fwd(q, k, v, sm_scale, causal, interpret)

    def fwd(q, k, v):
        return _fwd(q, k, v, sm_scale, causal, interpret), (q, k, v)

    def bwd(res, do):
        q, k, v = res
        return _bwd(q, k, v, do, sm_scale, causal, interpret)

    attn.defvjp(fwd, bwd)
    return attn


def _reference(q, k, v, causal=False, sm_scale=1.0):
    """XLA reference for the registry lint/equivalence contract — the
    einsum composition from ops/attention_ops (duplicated minimally here to
    avoid a circular import)."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), sk - sq)
        scores = jnp.where(mask, scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)


@workbench.register_kernel(
    "attention_short_seq",
    reference=_reference,
    supported=short_seq_supported,
    decision_op="attention",
    equivalence_test="test_fwd_matches_reference",
    note="fused self-attention for S in {128, 256, 384, 512} (S % 128 == 0;"
         " head-blocked VMEM slabs, fused no-residual backward)")
def short_seq_attention(q, k, v, causal=False, sm_scale=1.0):
    """Fused attention for VMEM-resident sequence lengths.

    q, k, v: [B, nh, S, dh] (S == Sk, S % 128 == 0, S <= 512 — the bwd
    kernel's ~5 fp32 [S,S] intermediates outgrow VMEM past that; callers
    must gate on `short_seq_supported`). Returns
    [B, nh, S, dh] in q's dtype. Differentiable (fused Pallas backward that
    saves no score-sized residuals — softmax is recomputed on-chip).
    """
    return _make(float(sm_scale), bool(causal), bool(INTERPRET))(q, k, v)
