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
    `page_table[b, p]` and DMAs exactly that [ps, nh*dh] page slab from the
    pool, so HBM traffic is the used pages once, nothing else. The pool is
    lane-dense, `[num_pages, ps, nh*dh]` (serving/kv_cache.pool_shape): a
    slab is whole (8, 128) tiles in the pool's own row-major layout, so the
    kernel's operand IS the resident buffer — no conversion before the call.
  * the ragged part: rows in one batch have different context lengths
    (`kv_lens`, also scalar-prefetched). Slots past a row's length are masked
    to -1e30 inside the online-softmax update; rows the continuous-batching
    scheduler padded in (kv_len 0) produce finite garbage nobody reads — the
    batch_mask convention from PR 2.
  * heads never leave the lanes: a token's row holds head h in lanes
    h*dh..(h+1)*dh, q.k is one [ps, nh*dh] VPU product, and the per-head sum
    is a butterfly of lane rotations inside each dh-lane segment that leaves
    every lane holding its head's score. The online softmax state (m, l,
    acc, each [1, nh*dh], the per-head statistics repeated over the head's
    lanes) lives in VMEM scratch across the page steps of one row (grid dims
    are ("parallel", "arbitrary")); the output block is written once, on
    the row's last page step. float32 products and sums throughout: no MXU
    pass, so nothing is rounded to bfloat16.

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


_LANES = 128


def paged_supported(q_shape, pool_shape, pool_dtype=jnp.float32) -> bool:
    """Shapes this kernel handles: q [B, nh, dh] against a pool
    [num_pages, page_size, nkv*dh] of `pool_dtype`. A page slab must be
    whole tiles ((8, 128) of float32, (16, 128) of bfloat16: page_size a
    multiple of the sublane count, the row of 128) and modest enough to
    double-buffer in VMEM. With nkv == nh a head must sit inside one
    128-lane register (dh a power of two up to 128); with fewer KV heads
    than query heads (grouped-query) a head is one register (dh 128), the
    query heads fill whole sublane tiles and a page fills whole lanes of
    the score tile. Everything else (the CPU rehearsal geometry, most unit
    tests) takes the XLA path on the same pool."""
    if len(q_shape) != 3 or len(pool_shape) != 3:
        return False
    B, nh, dh = q_shape
    num_pages, ps, width = pool_shape
    itemsize = jnp.dtype(pool_dtype).itemsize
    if itemsize not in (2, 4):
        return False
    tiled = (width % _LANES == 0 and ps % (32 // itemsize) == 0
             and ps * width * itemsize <= 2 * 1024 * 1024)
    if nh * dh == width:
        return tiled and 8 <= dh <= _LANES and dh & (dh - 1) == 0
    nkv = width // dh if dh else 0
    return (tiled and dh == _LANES and nkv * dh == width and nkv > 0
            and nh % nkv == 0 and nh % 8 == 0 and ps % _LANES == 0)


def _head_sums(x, head_dim):
    """x [ps, 128]: every lane's sum over the `head_dim`-lane segment it
    lies in (a head; segments are aligned, `head_dim` a power of two).
    Butterfly all-reduce: at step s a lane adds its partner `lane ^ s`,
    fetched by one rotation in each direction."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < head_dim:
        # roll(x, k)[i] = x[i - k]: the partner sits s lanes below where
        # bit s of the lane is set, s lanes above where it is clear
        x = x + jnp.where(lane & s != 0, pltpu.roll(x, s, 1),
                          pltpu.roll(x, _LANES - s, 1))
        s *= 2
    return x


def _decode_kernel(pt_ref, kl_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, sm_scale, page_size, num_pages_p,
                   head_dim):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # One query token per row: the step is bound by the page DMA, not by
    # FLOPs, so q.k and p.v stay on the VPU in float32, one 128-lane column
    # of the [ps, nh*dh] slab at a time (whole heads: dh divides 128).
    width = k_ref.shape[2]
    slot = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (page_size, _LANES), 0)
    # ragged mask: slot p*ps + j is live iff below this row's context
    live = slot < kl_ref[b]
    for c in range(0, width, _LANES):
        col = pl.ds(c, _LANES)
        q = q_ref[0, :, col].astype(jnp.float32) * sm_scale     # [1, 128]
        k = k_ref[0, :, col].astype(jnp.float32)                # [ps, 128]
        s = jnp.where(live, _head_sums(q * k, head_dim), _NEG_INF)
        m_prev = m_ref[:, col]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)                               # [ps, 128]
        v = v_ref[0, :, col].astype(jnp.float32)
        l_ref[:, col] = l_ref[:, col] * alpha + jnp.sum(
            pexp, axis=0, keepdims=True)
        acc_ref[:, col] = acc_ref[:, col] * alpha + jnp.sum(
            pexp * v, axis=0, keepdims=True)
        m_ref[:, col] = m_new

    @pl.when(p == num_pages_p - 1)
    def _emit():
        # a padded row (kv_len 0) has every slot masked alike, so l > 0 and
        # it emits the mean of whatever its table's pages hold — finite,
        # and the scheduler's batch_mask guarantees nobody reads it
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _gqa_decode_kernel(pt_ref, kl_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, sm_scale, page_size,
                       num_pages_p, num_kv_heads):
    """Grouped-query twin of `_decode_kernel`: `nh` query heads (sublanes
    of one [nh, dh] tile) over `nkv` KV heads of dh = 128 lanes. A head is
    a whole register here, so q.k and p.v are MXU products in float32, one
    KV head at a time over ALL query rows; each row keeps the product of
    its own group (the others cost a few hundred cycles and no byte)."""
    b = pl.program_id(0)
    p = pl.program_id(1)
    nh, dh = q_ref.shape[1], q_ref.shape[2]
    group = nh // num_kv_heads

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages past the row's context repeat its last page (the index map is
    # clamped, so nothing is fetched) and compute nothing
    @pl.when(p * page_size < kl_ref[b])
    def _page():
        q = q_ref[0].astype(jnp.float32) * sm_scale              # [nh, dh]
        row_kv = jax.lax.broadcasted_iota(
            jnp.int32, (nh, page_size), 0) // group
        nt = (((1,), (1,)), ((), ()))
        s = jnp.zeros((nh, page_size), jnp.float32)
        for j in range(num_kv_heads):
            k = k_ref[0, :, pl.ds(j * dh, dh)].astype(jnp.float32)
            sj = jax.lax.dot_general(
                q, k, nt, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)              # [nh, ps]
            s = jnp.where(row_kv == j, sj, s)
        slot = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (nh, page_size), 1)
        s = jnp.where(slot < kl_ref[b], s, _NEG_INF)
        m_prev = m_ref[...]                                      # [nh, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new[:, :1])                         # [nh, ps]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=1,
                                                   keepdims=True)
        row_kv_d = jax.lax.broadcasted_iota(jnp.int32, (nh, dh), 0) // group
        pv = jnp.zeros((nh, dh), jnp.float32)
        for j in range(num_kv_heads):
            v = v_ref[0, :, pl.ds(j * dh, dh)].astype(jnp.float32)
            pvj = jnp.dot(pexp, v, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)    # [nh, dh]
            pv = jnp.where(row_kv_d == j, pvj, pv)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(p == num_pages_p - 1)
    def _emit():
        # a padded row (kv_len 0) ran no page: it emits zeros
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _gqa_call(q, k_pool, v_pool, page_table, kv_lens, sm_scale, interpret):
    B, nh, dh = q.shape
    num_pages, ps, width = k_pool.shape
    P = page_table.shape[1]
    nkv = width // dh
    # a grid step past the row's last page names that page again: pallas
    # skips the DMA of a block whose index did not change
    last = jnp.clip((kv_lens - 1) // ps, 0, P - 1)
    cols = jnp.minimum(jnp.arange(P, dtype=jnp.int32)[None, :],
                       last[:, None])
    page_table = jnp.take_along_axis(page_table, cols, axis=1)
    kernel = functools.partial(_gqa_decode_kernel, sm_scale=float(sm_scale),
                               page_size=ps, num_pages_p=P, num_kv_heads=nkv)
    row = pl.BlockSpec((1, nh, dh), lambda b, p, pt, kl: (b, 0, 0))
    page = pl.BlockSpec((1, ps, width),
                        lambda b, p, pt, kl: (pt[b, p], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[row, page, page],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((nh, _LANES), jnp.float32),   # running max
            pltpu.VMEM((nh, _LANES), jnp.float32),   # running denominator
            pltpu.VMEM((nh, dh), jnp.float32),       # running numerator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, dh), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=B * nh * 2 * 2 * P * ps * dh,
            bytes_accessed=(2 * B * P * ps * width * k_pool.dtype.itemsize
                            + 2 * B * nh * dh * 4),
            transcendentals=B * P * ps * nh),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention_gqa",
    )(page_table, kv_lens, q.astype(jnp.float32), k_pool, v_pool)
    return out.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _call(q, k_pool, v_pool, page_table, kv_lens, sm_scale, interpret):
    B, nh, dh = q.shape
    num_pages, ps, width = k_pool.shape
    P = page_table.shape[1]
    if nh * dh != width:
        page_table = jnp.clip(page_table, 0, num_pages - 1).astype(jnp.int32)
        return _gqa_call(q, k_pool, v_pool, page_table,
                         kv_lens.astype(jnp.int32), sm_scale, interpret)
    # clamp so a padded/garbage table entry DMAs a real page (its slots are
    # masked by kv_lens anyway) instead of reading out of bounds
    page_table = jnp.clip(page_table, 0, num_pages - 1).astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)
    kernel = functools.partial(_decode_kernel, sm_scale=float(sm_scale),
                               page_size=ps, num_pages_p=P, head_dim=dh)
    row = pl.BlockSpec((1, 1, width), lambda b, p, pt, kl: (b, 0, 0))
    page = pl.BlockSpec((1, ps, width),
                        lambda b, p, pt, kl: (pt[b, p], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[row, page, page],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((1, width), jnp.float32),   # running max
            pltpu.VMEM((1, width), jnp.float32),   # running denominator
            pltpu.VMEM((1, width), jnp.float32),   # running numerator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, width), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=B * nh * 2 * 2 * P * ps * dh,
            bytes_accessed=(2 * B * P * ps * width * k_pool.dtype.itemsize
                            + 2 * B * width * q.dtype.itemsize),
            transcendentals=B * P * ps * width),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table, kv_lens, q.reshape(B, 1, width), k_pool, v_pool)
    return out.reshape(B, nh, dh)


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
    k_pool/v_pool: [num_pages, page_size, nkv*dh] (the preallocated pool;
    nkv == nh, or fewer KV heads than query heads: grouped-query);
    page_table: [B, P] int32 (row b's context lives in pages
    page_table[b, 0..ceil(kv_lens[b]/page_size))); kv_lens: [B] int32 valid
    slot counts. Returns [B, nh, dh] in q's dtype. Callers gate on
    `paged_supported`.
    """
    return _call(q, k_pool, v_pool, page_table, kv_lens,
                 float(sm_scale), bool(INTERPRET))
