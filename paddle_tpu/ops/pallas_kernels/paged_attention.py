"""Ragged paged decode attention: a Pallas TPU kernel over the KV-page pool.

Why this exists (ROADMAP item 1, "Ragged Paged Attention", arXiv:2604.15464):
the serving runtime's decode step is one query token per request attending
over that request's whole context, which lives scattered across fixed-size
pages of the preallocated HBM pool. The XLA reference path
(attention_ops._paged_attention_reference) gathers every row's pages into a
dense [B, P*ps, nh, dh] tensor first — at long contexts that materialized
gather IS the decode step's HBM bill. This kernel never materializes it:

  * grid (batch row, page): the page index for each grid step comes from the
    request's page table via scalar prefetch — the BlockSpec index_map reads
    `page_table[b, p]` and DMAs exactly that [ps, nh, dh] page slab from the
    pool, so HBM traffic is the used pages once, nothing else.
  * the ragged part: rows in one batch have different context lengths
    (`kv_lens`, also scalar-prefetched). Slots past a row's length are masked
    to -1e9 inside the online-softmax update; rows the continuous-batching
    scheduler padded in (kv_len 0) produce finite garbage nobody reads — the
    batch_mask convention from PR 2.
  * online softmax state (m, l, acc) lives in VMEM scratch across the page
    steps of one row (grid dims are ("parallel", "arbitrary")); the output
    block is written once, on the row's last page step.

Decode q is a single token per row, so there is no backward pass: the kernel
is forward-only (serving never differentiates), which keeps it free of the
residual bookkeeping the short-seq training kernel needs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# tests flip this to run the kernel through the Pallas interpreter on CPU
INTERPRET = False


def paged_supported(q_shape, pool_shape) -> bool:
    """Shapes this kernel handles: q [B, nh, dh] against a pool
    [num_pages, page_size, nh, dh]. dh must be sublane-aligned; the per-page
    slab [ps, nh, dh] must be modest enough to double-buffer in VMEM."""
    if len(q_shape) != 3 or len(pool_shape) != 4:
        return False
    B, nh, dh = q_shape
    num_pages, ps, p_nh, p_dh = pool_shape
    return (nh == p_nh and dh == p_dh and dh % 8 == 0 and dh <= 256
            and ps * nh * dh * 4 <= 2 * 1024 * 1024)


def _decode_kernel(pt_ref, kl_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, sm_scale, page_size, num_pages_p):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # One query token per row: the step is bound by the page DMA, not by
    # FLOPs, so q.k and p.v run on the VPU one slot at a time over plain
    # [nh, dh] tiles. Mosaic's matmul wants the batch (head) dim leading in
    # both operands, and the page slab [ps, nh, dh] has it in the middle.
    q = q_ref[0].astype(jnp.float32) * sm_scale          # [nh, dh]
    kv_len = kl_ref[b]
    scores = []
    for j in range(page_size):
        kj = k_ref[0, j].astype(jnp.float32)             # [nh, dh]
        sj = jnp.sum(q * kj, axis=-1, keepdims=True)     # [nh, 1]
        # ragged mask: slot p*ps + j is live iff below this row's context
        scores.append(jnp.where(p * page_size + j < kv_len, sj, _NEG_INF))

    m_prev = m_ref[...]
    m_new = functools.reduce(jnp.maximum, scores, m_prev)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_ref[...] * alpha
    acc = acc_ref[...] * alpha
    for j, sj in enumerate(scores):
        pj = jnp.exp(sj - m_new)                         # [nh, 1]
        l_new = l_new + pj
        acc = acc + pj * v_ref[0, j].astype(jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc

    @pl.when(p == num_pages_p - 1)
    def _emit():
        # a padded row (kv_len 0) has every slot masked alike, so l > 0 and
        # it emits the mean of whatever its table's pages hold — finite,
        # and the scheduler's batch_mask guarantees nobody reads it
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _call(q, k_pool, v_pool, page_table, kv_lens, sm_scale, interpret):
    B, nh, dh = q.shape
    num_pages, ps = k_pool.shape[0], k_pool.shape[1]
    P = page_table.shape[1]
    # clamp so a padded/garbage table entry DMAs a real page (its slots are
    # masked by kv_lens anyway) instead of reading out of bounds
    page_table = jnp.clip(page_table, 0, num_pages - 1).astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)
    kernel = functools.partial(_decode_kernel, sm_scale=float(sm_scale),
                               page_size=ps, num_pages_p=P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, nh, dh), lambda b, p, pt, kl: (b, 0, 0)),
            pl.BlockSpec((1, ps, nh, dh),
                         lambda b, p, pt, kl: (pt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, ps, nh, dh),
                         lambda b, p, pt, kl: (pt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nh, dh), lambda b, p, pt, kl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, 1), jnp.float32),   # running max
            pltpu.VMEM((nh, 1), jnp.float32),   # running denominator
            pltpu.VMEM((nh, dh), jnp.float32),  # running numerator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=B * nh * 2 * 2 * P * ps * dh,
            bytes_accessed=(2 * B * P * ps * nh * dh * k_pool.dtype.itemsize
                            + 2 * B * nh * dh * q.dtype.itemsize),
            transcendentals=B * nh * P * ps),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table, kv_lens, q, k_pool, v_pool)


def _workbench_register():
    from . import workbench

    def _reference(q, k_pool, v_pool, page_table, kv_lens, sm_scale=1.0):
        from ..attention_ops import _paged_attention_reference

        return _paged_attention_reference(q, k_pool, v_pool, page_table,
                                          kv_lens, sm_scale)

    return workbench.register_kernel(
        "attention_paged_decode",
        reference=_reference,
        supported=paged_supported,
        decision_op="attention",
        equivalence_test="test_paged_attention_pallas_matches_reference",
        note="ragged paged decode attention (sq=1) over the KV page pool; "
             "scalar-prefetch page-table DMA, forward-only")


@_workbench_register()
def paged_decode_attention(q, k_pool, v_pool, page_table, kv_lens,
                           sm_scale=1.0):
    """One decode step of ragged paged attention.

    q: [B, nh, dh] (this step's query per request row);
    k_pool/v_pool: [num_pages, page_size, nh, dh] (the preallocated pool);
    page_table: [B, P] int32 (row b's context lives in pages
    page_table[b, 0..ceil(kv_lens[b]/page_size))); kv_lens: [B] int32 valid
    slot counts. Returns [B, nh, dh] in q's dtype. Callers gate on
    `paged_supported`.
    """
    return _call(q, k_pool, v_pool, page_table, kv_lens,
                 float(sm_scale), bool(INTERPRET))
