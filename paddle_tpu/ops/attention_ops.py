"""Fused attention ops: Pallas flash attention + ring attention (SP).

The reference has no fused attention — its scaled_dot_product_attention
(nets.py:345) materializes the full [B,nh,S,S] score matrix through separate
matmul/softmax/dropout ops. On TPU one fused op boundary for the whole
QK^T -> softmax -> PV block is the single biggest transformer win
(SURVEY.md §2.3), so:

  * `fused_attention` dispatches per measured winner (PERF.md): at train
    sizes (S <= 1024) the jnp einsum composition — XLA's attention fusion
    with fp32 softmax statistics, recompute-in-backward via the derived
    vjp; with `use_pallas` the hand-tuned short-seq Pallas kernel
    (ops/pallas_kernels/attention.py, O(S) residuals); at S > 1024 jax's
    bundled flash-attention kernel (the only O(S)-memory option there).
  * `ring_attention` is the sequence-parallel form: K/V shards rotate around
    the `sp` mesh axis via collective-permute while each device keeps a
    running online-softmax merge (m, l, acc). Pure differentiable jnp +
    lax.ppermute — XLA overlaps the permute with the local block math over
    ICI. Used under shard_map (CompiledProgram.with_collective) or inside
    GSPMD manual regions; with no axis bound it degrades to fused_attention.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

from .collective_ops import _axis
from .registry import ExecContext, register_op

_NEG_INF = -1e9


def _reference_attention(q, k, v, bias=None, causal=False, sm_scale=1.0):
    """Plain jnp attention, the numeric oracle (and the measured-fastest
    TPU path at train sizes). q,k,v: [B, nh, S, dh]. Softmax statistics are
    fp32 even for bf16 operands (the AMP white-list invariant); XLA fuses
    the boundary casts so this costs no extra HBM traffic."""
    # scores materialize in the operand dtype (bf16 under AMP — half the
    # HBM bytes); the fp32 upcast happens inside the softmax so the
    # max/exp/sum statistics are fp32 yet XLA fuses the casts for free
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale
    scores = scores.astype(jnp.float32)
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), sk - sq)
        scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)


def band_mask(sq: int, sk: int, causal: bool, window: int):
    """[sq, sk] bool or None: query i (the LAST `sq` of `sk` positions) sees
    key j at or before it where `causal`, and no further back than
    `window - 1` positions where `window` > 0."""
    if not causal and not window:
        return None
    mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), sk - sq) if causal \
        else jnp.ones((sq, sk), jnp.bool_)
    if window:
        qp = jnp.arange(sq, dtype=jnp.int32)[:, None] + (sk - sq)
        kp = jnp.arange(sk, dtype=jnp.int32)[None, :]
        mask &= qp - kp < window
    return mask


def grouped_query_attention(q, k, v, causal=False, sm_scale=1.0, window=0):
    """Dense attention of `nh` query heads over `nkv` key/value heads
    (`nh % nkv == 0`; query head h reads KV head `h // (nh // nkv)`), without
    repeating K/V. q [B, nh, Sq, dh], k/v [B, nkv, Sk, dh]; scores and
    softmax float32 whatever the operands (a bfloat16 score rounds a logit
    of 11 by 0.02). `window` > 0: a query sees the `window` last keys up to
    its own (a sliding layer)."""
    B, nh, sq, dh = q.shape
    nkv, sk = k.shape[1], k.shape[2]
    qg = q.reshape(B, nkv, nh // nkv, sq, dh)
    scores = jnp.einsum("bjgqd,bjkd->bjgqk", qg, k,
                        preferred_element_type=jnp.float32) * sm_scale
    mask = band_mask(sq, sk, causal, window)
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bjgqk,bjkd->bjgqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, nh, sq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Blockwise grouped-query attention with a backward pass (a training step at
# thousands of positions): jax's bundled splash attention
# ---------------------------------------------------------------------------

# tests flip this to run the bundled kernels through the Pallas interpreter
BLOCKWISE_INTERPRET = False
# queries and keys of one grid step, forward and backward, behind a window
# and without one. Swept on the chip at 2 x 8,192 positions, 32 heads over 4
# of 128, forward + backward (PR 58): behind a window of 1,024 blocks of 512
# take 14.9 ms (a query block visits 3 key blocks: 1,536 keys for the
# 1,024-1,535 it sees), 1,024 take 16.7, 256 take 24.1; the full causal pass
# takes 44.2 ms at 512 and 37.4 at 1,024
BLOCKWISE_BLOCK = 512
BLOCKWISE_BLOCK_FULL = 1024


def blockwise_supported(q_shape, k_shape) -> bool:
    """The bundled kernel's gate: self-attention (as many keys as queries)
    over whole blocks of 128, heads of whole lanes."""
    sq, sk, dh = q_shape[2], k_shape[2], q_shape[3]
    return (sq == sk and sq % 128 == 0 and dh % 128 == 0
            and q_shape[1] % k_shape[1] == 0)


def _blockwise_block(s: int, window: int = 0) -> int:
    """Positions of one block: the largest that divides `s`, `s` itself
    where none does (the dense paths: one block)."""
    first = BLOCKWISE_BLOCK if window else BLOCKWISE_BLOCK_FULL
    return next((b for b in (first, 512, 256, 128) if s % b == 0), s)


def key_blocks(s: int, block: int, causal: bool, window: int) -> tuple:
    """(key blocks a blockwise pass over `s` positions visits, those a
    causal pass without a window would): a block is visited where any of
    its (query, key) pairs is seen."""
    n = s // block
    visited = causal_blocks = 0
    for i in range(n):
        last_q, first_q = i * block + block - 1, i * block
        for j in range(n):
            first_k, last_k = j * block, j * block + block - 1
            seen = not causal or first_k <= last_q
            causal_blocks += first_k <= last_q
            if window and first_q - last_k >= window:
                seen = False
            visited += seen
    return visited, causal_blocks


@functools.lru_cache(maxsize=None)
def _splash_kernel(s: int, group: int, causal: bool, window: int,
                   block: int, interpret: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    if window:
        one = sm.LocalMask((s, s), (window - 1, 0 if causal else None), 0)
    elif causal:
        one = sm.CausalMask((s, s))
    else:
        one = sm.FullMask((s, s))
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    # the mask's block tables stay numpy until a trace embeds them: a cached
    # kernel must not hold arrays of the trace that first built it
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa_single_device(
            sm.MultiHeadMask([one] * group), block_sizes=sizes,
            interpret=interpret)


def blockwise_attention(q, k, v, causal=True, sm_scale=1.0, window=0):
    """q [B, nh, S, dh] over k/v [B, nkv, S, dh], block by block with an
    online softmax (float32 statistics), forward and backward, never a
    `[S, S]` score: the `nh // nkv` query heads of a key/value head run as
    one multi-query call of jax's bundled splash kernel, whose block tables
    skip the key blocks a causal or sliding mask leaves out. The kernel
    takes no scale: it is folded into q."""
    B, nh, s, dh = q.shape
    nkv = k.shape[1]
    kernel = _splash_kernel(s, nh // nkv, bool(causal), int(window),
                            _blockwise_block(s, window),
                            bool(BLOCKWISE_INTERPRET))
    qs = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
    out = jax.vmap(jax.vmap(kernel))(
        qs.reshape(B, nkv, nh // nkv, s, dh), k, v)
    return out.reshape(B, nh, s, dh)


def _blockwise_runs(q_shape, k_shape) -> bool:
    from .pallas_kernels import workbench

    return ((workbench.on_tpu() or BLOCKWISE_INTERPRET)
            and blockwise_supported(q_shape, k_shape))


def _block_multiple_ok(s: int) -> bool:
    # the bundled kernel wants seq divisible by its block sizes (>=128 lanes)
    return s % 128 == 0


def _pallas_short_ok(q_shape, k_shape, bias) -> bool:
    from .pallas_kernels import attention as psa
    from .pallas_kernels import workbench

    return (workbench.runnable(psa)
            and psa.short_seq_supported(q_shape, k_shape, bias))


def _pallas_short128_ok(q_shape, k_shape, bias) -> bool:
    from .pallas_kernels import short_attention as s128
    from .pallas_kernels import workbench

    return (workbench.runnable(s128)
            and s128.short128_supported(q_shape, k_shape, bias))


def _flash_bundled_ok(q_shape, k_shape, dtype) -> bool:
    from .pallas_kernels import workbench

    sq, sk = q_shape[2], k_shape[2]
    return (workbench.on_tpu() and _block_multiple_ok(sq)
            and _block_multiple_ok(sk) and dtype != jnp.float64)


def _backend_runnable(backend, q_shape, k_shape, bias, dtype) -> bool:
    """Can `backend` execute this shape on this platform — the re-check
    every dispatch makes after the decision."""
    if backend == "xla":
        return True
    if backend == "pallas_short":
        return _pallas_short_ok(q_shape, k_shape, bias)
    if backend == "pallas_short128":
        return _pallas_short128_ok(q_shape, k_shape, bias)
    if backend == "flash_bundled":
        return _flash_bundled_ok(q_shape, k_shape, dtype)
    return False


def _note_dispatch(kind: str, chosen: str, ran: str) -> None:
    """Make what ran observable: one count per traced dispatch under
    (kind, chosen, ran). chosen != ran is a swept-DB verdict that gave way
    to the reference because this platform cannot run it."""
    from .. import observability as obs

    obs.counter_inc("attention.dispatches",
                    labels={"kind": kind, "chosen": chosen, "ran": ran})


def dispatch_counts() -> dict:
    """{(kind, chosen, ran): traces} since the series was last reset
    (`observability.reset("attention.")`)."""
    from .. import observability as obs

    out = {}
    for key, n in obs.snapshot()["counters"].items():
        if obs.base_name(key) == "attention.dispatches":
            lab = dict(re.findall(r'(\w+)="([^"]*)"', key))
            out[(lab["kind"], lab["chosen"], lab["ran"])] = int(n)
    return out


def attention_backend(q_shape, k_shape, dtype, bias=None, causal=False,
                      use_pallas=False):
    """Which kernel carries this attention shape. Returns (backend, tier)
    with backend in {"xla", "pallas_short", "flash_bundled"}.

    The analytic prior is the measured v5e dispatch rule (PERF.md): XLA's
    own attention fusion at train sizes, the hand-tuned short-seq Pallas
    kernel when the caller forces O(S) memory (`use_pallas`) and the shape
    qualifies, the bundled flash kernel past S=1024 where the [S,S] scores
    outgrow the chip. Under FLAGS_tuning_mode=consult a swept-DB entry for
    the exact (shape, dtype, device) overrides the rule — this is where a
    measured split (on a v5e in 2026-07, before the PR 1-20 code: XLA ahead
    at seq<=128, the Pallas kernel ~9% ahead at s512) becomes a cache entry
    instead of a per-model flag. A swept backend the current build cannot
    execute gives way to the reference at dispatch (flash_attention), and
    the `attention.dispatches` counter records that it did.

    The seq<=128 regime additionally carries the `pallas_short128` arm
    (pallas_kernels/short_attention.py — ISSUE 9): the analytic prior keeps
    XLA there (that is what those runs measured), so the kernel engages
    only via a swept keep or FLAGS_attention_force_backend (the A/B harness
    lever, which precedes every tier; a forced backend that cannot run
    here raises at dispatch — an A/B arm must never time the reference
    under the kernel's name)."""
    from .. import flags as pt_flags

    B, nh, sq, dh = q_shape
    sk = k_shape[2]

    forced = str(pt_flags.get_flag("attention_force_backend")).strip()
    if forced:
        return forced, "forced"

    def analytic():
        if use_pallas and _pallas_short_ok(q_shape, k_shape, bias):
            return {"backend": "pallas_short"}
        # an O(S)-memory kernel is mandatory past S=1024 and honored
        # whenever the caller asked for one (`use_pallas`) but the
        # short-seq kernel's gate rejected the shape — falling to the
        # O(S^2) reference there would silently undo the flag's documented
        # purpose (memory-bound configs).
        if ((sq > 1024 or (use_pallas and sq > 512))
                and _flash_bundled_ok(q_shape, k_shape, dtype)):
            return {"backend": "flash_bundled"}
        return {"backend": "xla"}

    from .. import tuning

    if tuning.mode() == "off":
        return analytic()["backend"], "analytic"
    key = tuning.canonical_key(
        "attention", tuning.attention_key(B, nh, sq, sk, dh, causal),
        str(jnp.dtype(dtype)), tuning.device_kind())
    decision, tier = tuning.decide(
        "attention", key, prior=analytic, default={"backend": "xla"},
        validate=lambda dd: dd.get("backend") in ("xla", "pallas_short",
                                                  "pallas_short128",
                                                  "flash_bundled"))
    return decision.get("backend", "xla"), tier


def flash_attention(q, k, v, bias=None, causal=False, sm_scale=1.0,
                    use_pallas=False):
    """Dispatch per `attention_backend` (each branch measured on v5e,
    PERF.md):
      * "xla": the jnp einsum composition — XLA's own attention fusion is
        the fastest at S<=512 (beats both the bundled flash kernel and the
        custom short-seq Pallas kernel at train sizes);
      * "pallas_short": the hand-tuned short-seq kernel (O(S) memory with a
        no-residual fused backward — for memory-bound configs);
      * "flash_bundled": jax's bundled flash kernel (the only O(S) option
        once the [S,S] scores outgrow VMEM/HBM budgets).
    A swept-DB backend the current platform/shape cannot run (e.g. a Pallas
    verdict replayed off-TPU) gives way to the reference path here and is
    counted as such; a FORCED backend that cannot run raises.
    """
    backend, tier = attention_backend(q.shape, k.shape, q.dtype, bias,
                                      causal, use_pallas)
    ran = backend
    if not _backend_runnable(backend, q.shape, k.shape, bias, q.dtype):
        if tier == "forced":
            raise RuntimeError(
                f"FLAGS_attention_force_backend={backend!r} cannot run "
                f"q{tuple(q.shape)} k{tuple(k.shape)} {q.dtype} on "
                f"{jax.default_backend()!r}")
        ran = "xla"
    _note_dispatch("dense", backend, ran)
    if ran == "pallas_short":
        from .pallas_kernels import attention as psa

        return psa.short_seq_attention(q, k, v, causal=causal,
                                       sm_scale=float(sm_scale))
    if ran == "pallas_short128":
        from .pallas_kernels import short_attention as s128

        return s128.short128_attention(q, k, v, causal=causal,
                                       sm_scale=float(sm_scale))
    if ran == "flash_bundled":
        from jax.experimental.pallas.ops.tpu import flash_attention as fa

        return fa.flash_attention(q, k, v, ab=bias, causal=causal,
                                  sm_scale=float(sm_scale))
    return _reference_attention(q, k, v, bias, causal, sm_scale)


@register_op("fused_attention")
def fused_attention(ctx: ExecContext):
    """inputs: Q, K, V [B, nh, S, dh], optional Bias (broadcastable to
    [B, nh, Sq, Sk]); attrs: causal, sm_scale, window (0: none; else a query
    sees the `window` last keys up to its own). Output: Out [B, nh, Sq, dh]
    and Stats float32 [2]: the key blocks this call visited and those a
    causal pass would, times the batch (the dense paths visit every block).
    K and V may carry fewer heads than Q (grouped-query attention); such a
    call, and one with a window, runs block by block on the chip
    (`blockwise_attention`) and dense elsewhere."""
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    bias = ctx.input("Bias") if ctx.has_input("Bias") else None
    causal = ctx.attr("causal", False)
    window = int(ctx.attr("window", 0) or 0)
    sm_scale = ctx.attr("sm_scale", 1.0)
    blockwise = False
    if k.shape[1] != q.shape[1] or window:
        if bias is not None:
            raise NotImplementedError(
                "fused_attention: grouped-query heads and windows take no "
                "Bias")
        blockwise = _blockwise_runs(q.shape, k.shape)
        if blockwise:
            _note_dispatch("dense", "splash_bundled", "splash_bundled")
            out = blockwise_attention(q, k, v, causal, sm_scale, window)
        else:
            out = grouped_query_attention(q, k, v, causal=causal,
                                          sm_scale=sm_scale, window=window)
    else:
        out = flash_attention(q, k, v, bias, causal=causal,
                              sm_scale=sm_scale,
                              use_pallas=ctx.attr("use_pallas", False))
    outs = {"Out": out.astype(q.dtype)}
    if ctx.op.outputs.get("Stats"):
        sq = q.shape[2]
        block = _blockwise_block(sq, window)
        visited = key_blocks(sq, block, causal and blockwise,
                             window if blockwise else 0)[0]
        outs["Stats"] = q.shape[0] * jnp.asarray(
            [visited, key_blocks(sq, block, True, 0)[1]], jnp.float32)
    return outs


# ---------------------------------------------------------------------------
# Paged KV-cache decode attention (the serving/ runtime's core op)
# ---------------------------------------------------------------------------


def _pallas_paged_ok(q_shape, pool_shape, pool_dtype=jnp.float32) -> bool:
    from .pallas_kernels import paged_attention as ppa
    from .pallas_kernels import workbench

    return (workbench.runnable(ppa)
            and ppa.paged_supported(tuple(q_shape), tuple(pool_shape),
                                    pool_dtype))


def _shard_paged_shapes(q_shape, pool_shape, tp=1):
    """The PER-SHARD view of a paged decode shape under tp-way head
    sharding: GSPMD hands each shard nh/tp heads of BOTH the query and the
    pool (whose last dim `nh*dh` holds heads contiguously), so the tuning
    key and every executability check must see the same nh/tp shapes — a
    verdict decided at one head count and dispatched at another is wrong in
    both directions."""
    tp = max(1, int(tp))
    B, nh, dh = q_shape
    q = (B, max(1, int(nh) // tp), dh)
    if pool_shape is None:
        return q, None
    num_pages, ps, width = pool_shape
    return q, (num_pages, ps, max(dh, int(width) // tp))


def paged_attention_backend(batch, num_heads, kv_slots, head_dim, dtype,
                            pool_shape=None, tp=1, pool_dtype=None):
    """Which kernel carries one ragged decode-attention shape (sq=1, sk =
    the padded slot count P*page_size). Returns (backend, tier) with backend
    in {"xla", "pallas_paged"}.

    Same three-tier contract as `attention_backend` (the PR 6 lever): the
    analytic prior prefers the Pallas paged kernel wherever it can run (the
    gather-free DMA path is the whole point of paging, arXiv:2604.15464),
    a swept DB entry for the exact (b, nh, 1, sk, dh) key overrides it —
    tools/tune.py's decode sweep writes those — and a swept backend the
    current build cannot execute degrades at dispatch, never obeyed blindly.

    tp > 1 (ISSUE 11): the op traces at the GLOBAL shape but under GSPMD
    each tp shard executes nh/tp heads, so the DB key is the PER-SHARD
    shape — exactly what tools/tune.py's head-sharded decode sweep records.
    """
    (batch, num_heads, head_dim), pool_shape = _shard_paged_shapes(
        (batch, num_heads, head_dim), pool_shape, tp)

    def analytic():
        if pool_shape is not None and _pallas_paged_ok(
                (batch, num_heads, head_dim), pool_shape,
                pool_dtype or dtype):
            return {"backend": "pallas_paged"}
        return {"backend": "xla"}

    from .. import tuning
    from .registry import _DYN

    # build-time shape inference dry-runs the compute with the dynamic-batch
    # sentinel; that fake shape must not consult the DB nor be recorded as a
    # sweep candidate (it is not a real dispatch)
    if tuning.mode() == "off" or batch == _DYN:
        return analytic()["backend"], "analytic"
    key = tuning.canonical_key(
        "attention",
        tuning.attention_key(batch, num_heads, 1, kv_slots, head_dim, True),
        str(jnp.dtype(dtype)), tuning.device_kind())
    decision, tier = tuning.decide(
        "attention", key, prior=analytic, default={"backend": "xla"},
        validate=lambda dd: dd.get("backend") in ("xla", "pallas_paged"))
    return decision.get("backend", "xla"), tier


def _gather_pages(pool, page_table, nh):
    """Rows `page_table` [B, P] names, as heads: `[B, P*ps, nh, dh]` (`nh`
    the pool's own head count, the KV heads under grouped-query attention).
    Whole `[ps, nh*dh]` page rows are gathered and the (small) result is
    what gets reshaped, never the pool: the pool keeps its one layout."""
    num_pages, ps, width = pool.shape
    B, P = page_table.shape
    pt = jnp.clip(page_table, 0, num_pages - 1)
    return pool[pt].reshape(B, P * ps, nh, width // nh)


def _paged_attention_reference(q, k_pool, v_pool, page_table, kv_lens,
                               sm_scale=1.0, first_live=None):
    """XLA gather-based paged decode attention — the numeric oracle and the
    dispatch fallback. Gathers every row's pages into a dense
    [B, P*ps, nh, dh] view (XLA fuses the gather into the matmuls, but the
    materialized bytes still move); fp32 softmax statistics, slots past a
    row's kv_len masked with the framework-wide -1e9 convention so a padded
    row (kv_len 0) stays finite. Pools are `[num_pages, ps, nh*dh]`. With
    `first_live` [B] (a sliding-window layer) the slots before it are
    masked too."""
    B, nh, dh = q.shape
    P, ps = page_table.shape[1], k_pool.shape[1]
    nkv = k_pool.shape[2] // dh
    pos = jnp.arange(P * ps, dtype=jnp.int32)
    # query head h reads KV head h // (nh // nkv) (a group of 1 when the
    # pool holds every head); the scores stay float32 (bfloat16 pools round
    # them by 0.02 otherwise)
    k = _gather_pages(k_pool, page_table, nkv)
    v = _gather_pages(v_pool, page_table, nkv)
    qg = q.reshape(B, nkv, nh // nkv, dh)
    s = jnp.einsum("bjgd,bkjd->bjgk", qg.astype(k.dtype), k,
                   preferred_element_type=jnp.float32) * sm_scale
    live = pos[None, None, None, :] < kv_lens[:, None, None, None]
    if first_live is not None:
        live &= pos[None, None, None, :] >= first_live[:, None, None, None]
    s = jnp.where(live, s, _NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bjgk,bkjd->bjgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, nh, dh).astype(q.dtype)


def _padded_group_heads(nh: int, dh: int, width: int) -> int:
    """The query heads a grouped-query call runs the Pallas kernel with:
    `nh`, or, where `nh` fills no whole sublane tiles of 8, the heads of
    the smallest larger group that does (20 heads over 4 KV heads, groups
    of 5, run as 24, groups of 6: one dead query row a group, computed and
    dropped; the K/V bytes, which are the cost, do not change)."""
    nkv = width // dh if dh else 0
    if nh % 8 == 0 or not nkv or nkv >= nh or nh % nkv or nkv * dh != width:
        return nh
    group = nh // nkv
    while nkv * group % 8:
        group += 1
    return nkv * group


def _paged_arm(q_shape, q_dtype, pool_shape, pool_dtype, bucket_pages, tp):
    """(decided backend, the per-shard pool shape the Pallas kernel runs on
    or None where the XLA gather serves the shape). Executability is
    re-checked at the SAME per-shard shapes the decision saw (under tp > 1
    the global q/pool head counts are not what a shard runs), a
    grouped-query call's heads as `_padded_group_heads` pads them."""
    B, nh, dh = q_shape
    nh = _padded_group_heads(nh, dh, pool_shape[2])
    q_shape = (B, nh, dh)
    backend, _tier = paged_attention_backend(
        B, nh, bucket_pages * pool_shape[1], dh, q_dtype,
        pool_shape=pool_shape, tp=tp, pool_dtype=pool_dtype)
    shard_q, shard_pool = _shard_paged_shapes(q_shape, pool_shape, tp)
    pallas = backend == "pallas_paged" and _pallas_paged_ok(
        shard_q, shard_pool, pool_dtype)
    return backend, shard_pool if pallas else None


def paged_decode_grid_steps(q_shape, q_dtype, pool_shape, pool_dtype,
                            bucket_pages, tp=1) -> int:
    """Grid steps of ONE `paged_decode_attention_fn` call at this shape
    (rows x page blocks of the Pallas kernel, per tp shard), 0 where the
    XLA gather serves it: what the engine books as
    `serving.decode_grid_steps`."""
    from .pallas_kernels import paged_attention as ppa

    _, shard_pool = _paged_arm(tuple(q_shape), q_dtype, tuple(pool_shape),
                               pool_dtype, bucket_pages, tp)
    if shard_pool is None:
        return 0
    return ppa.grid_steps(q_shape[0], bucket_pages, shard_pool[1],
                          shard_pool[2], jnp.dtype(pool_dtype).itemsize)


def paged_decode_walks(q_shape, q_dtype, pool_shape, pool_dtype,
                       bucket_pages, tp=1) -> bool:
    """Whether ONE `paged_decode_attention_fn` call without a first live
    slot at this shape runs the grouped-query kernel that walks a flat list
    of page blocks and reads a run of pages that rows share once
    (`paged_attention.walk_supported` at the per-shard shapes the arm was
    decided at): what the stacks ask before they work out a step's plan,
    and the engine before it books what the kernel read."""
    from .pallas_kernels import paged_attention as ppa

    _, shard_pool = _paged_arm(tuple(q_shape), q_dtype, tuple(pool_shape),
                               pool_dtype, bucket_pages, tp)
    if shard_pool is None:
        return False
    B, nh, dh = q_shape
    shard_q, _ = _shard_paged_shapes(
        (B, _padded_group_heads(nh, dh, pool_shape[2]), dh), pool_shape, tp)
    return ppa.walk_supported(shard_q, shard_pool, pool_dtype, bucket_pages)


def paged_decode_plan_fn(q_shape, q_dtype, k_pool, page_table, kv_lens,
                         tp=1):
    """The plan of a decode step's full-attention calls, from its page
    table and lengths alone (any layer's table gives the same): worked out
    ONCE, before the layers, and handed to every layer's
    `paged_decode_attention_fn`; None where `paged_decode_walks` says no."""
    if not paged_decode_walks(q_shape, q_dtype, k_pool.shape, k_pool.dtype,
                              page_table.shape[1], tp):
        return None
    from .pallas_kernels import paged_attention as ppa

    return ppa.walk_plan(page_table, kv_lens, k_pool.shape,
                         jnp.dtype(k_pool.dtype).itemsize)


def paged_decode_attention_fn(q, k_pool, v_pool, page_table, kv_lens,
                              sm_scale=1.0, tp=1, first_live=None,
                              plan=None):
    """Dispatch per `paged_attention_backend`: the Pallas page-DMA kernel
    where it can run (and the tuner has not retired it for this shape), the
    XLA gather reference everywhere else — including when a swept-DB verdict
    names a kernel this platform cannot execute. `first_live` [B] int32 (a
    sliding-window layer): a row attends slots `first_live .. kv_len - 1`
    only. `plan`: the step's `paged_decode_plan_fn`, where the caller runs
    several layers over one table (the kernel works it out otherwise)."""
    backend, pallas_pool = _paged_arm(q.shape, q.dtype, k_pool.shape,
                                      k_pool.dtype, page_table.shape[1], tp)
    if pallas_pool is not None:
        from .pallas_kernels import paged_attention as ppa

        _note_dispatch("paged", backend, backend)
        B, nh, dh = q.shape
        padded = _padded_group_heads(nh, dh, k_pool.shape[2])
        if padded != nh:
            nkv = k_pool.shape[2] // dh
            q = jnp.pad(q.reshape(B, nkv, nh // nkv, dh),
                        ((0, 0), (0, 0), (0, (padded - nh) // nkv), (0, 0))
                        ).reshape(B, padded, dh)
        out = ppa.paged_decode_attention(q, k_pool, v_pool, page_table,
                                         kv_lens, sm_scale=float(sm_scale),
                                         first_live=first_live, plan=plan)
        if padded != nh:
            out = out.reshape(B, nkv, padded // nkv, dh)[
                :, :, :nh // nkv].reshape(B, nh, dh)
        return out
    _note_dispatch("paged", backend, "xla")
    return _paged_attention_reference(q, k_pool, v_pool, page_table, kv_lens,
                                      sm_scale, first_live)


# sentinel page index far past any real pool: scatters routed here are
# dropped (mode="drop"), which is how masked rows / padded positions skip
# their KV write without a branch
_DROP_PAGE = 1 << 30


def _write_rows(pool, rows, page_idx, slot):
    """pool[page_idx[i], slot[i], :] = rows[i] for every i whose page is in
    the pool; `_DROP_PAGE` rows write nothing. A row is a token's whole
    `[nh*dh]` line of the lane-dense pool, so on a donated buffer XLA keeps
    this scatter in place in the pool's own layout (`tools/pool_hlo.py`
    checks the compiled programs for a pool-sized copy)."""
    return pool.at[page_idx, slot].set(rows.astype(pool.dtype), mode="drop")


def kv_cache_append_fn(k_pool, v_pool, k, v, page_table, positions,
                       live=None):
    """Write one decode step's K/V into the paged pool.

    k/v: [B, nh, dh] (this token's projections); pools
    [num_pages, ps, nh*dh]; positions: [B] int32 — the logical slot each
    row writes (its current context length); live: [B] 0/1 mask (rows the
    scheduler padded in write nowhere). Returns the updated pools; the
    executor's donation makes the update in-place in HBM.
    """
    B = k.shape[0]
    ps = k_pool.shape[1]
    P = page_table.shape[1]
    page_of = jnp.clip(positions // ps, 0, P - 1)
    page_idx = jnp.take_along_axis(page_table, page_of[:, None], axis=1)[:, 0]
    slot = positions % ps
    if live is not None:
        page_idx = jnp.where(jnp.reshape(live, (-1,)) > 0, page_idx,
                             _DROP_PAGE)
    k_pool = _write_rows(k_pool, k.reshape(B, -1), page_idx, slot)
    v_pool = _write_rows(v_pool, v.reshape(B, -1), page_idx, slot)
    return k_pool, v_pool


def kv_cache_prefill_write_fn(k_pool, v_pool, k, v, page_table, lens,
                              start=None):
    """Write a prefill window's K/V into the paged pool.

    k/v: [B, nh, S, dh] (the prefill attention's per-layer projections, in
    head-major layout as the encoder produces them); pools
    [num_pages, ps, nh*dh].

    Without `start` (the PR 7 whole-prompt prefill): local index s writes
    slot s; lens [B] are actual prompt lengths, positions s >= lens[b]
    (bucket padding) are dropped.

    With `start` [B] int32 (ISSUE 11 — suffix prefill past a cached prefix,
    and the speculative-decode verify window): local index s writes slot
    start[b] + s, and lens[b] counts the VALID LOCAL positions, so only
    s < lens[b] writes. Rows the scheduler padded pass lens 0 and write
    nothing — the batch_mask convention without needing a second feed.
    """
    B, nh, S, dh = k.shape
    ps = k_pool.shape[1]
    P = page_table.shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)
    gpos = jnp.broadcast_to(pos[None, :], (B, S))          # [B, S]
    if start is not None:
        gpos = jnp.reshape(start, (-1,))[:, None] + gpos
    valid = pos[None, :] < lens[:, None]
    page_idx = jnp.take_along_axis(
        page_table, jnp.clip(gpos // ps, 0, P - 1), axis=1)  # [B, S]
    page_idx = jnp.where(valid, page_idx, _DROP_PAGE)
    slot = gpos % ps
    # [B, nh, S, dh] -> one [nh*dh] row per token
    k_rows = jnp.transpose(k, (0, 2, 1, 3)).reshape(B, S, nh * dh)
    v_rows = jnp.transpose(v, (0, 2, 1, 3)).reshape(B, S, nh * dh)
    k_pool = _write_rows(k_pool, k_rows, page_idx, slot)
    v_pool = _write_rows(v_pool, v_rows, page_idx, slot)
    return k_pool, v_pool


def paged_prefill_attention_fn(q, k_pool, v_pool, page_table, start,
                               sm_scale=1.0):
    """Windowed causal attention OVER THE POOL: query s of row b (global
    position start[b] + s) attends pool slots 0..start[b]+s inclusive.

    The one attention primitive both new multi-tenant stages need
    (arXiv:2104.05755's reusable-primitive argument): suffix prefill past a
    shared prefix (the suffix's K/V is appended to the pool first, so the
    whole context — cached prefix + fresh suffix — is read from one place),
    and the speculative-decode verify window (k+1 queries per row in one
    step). XLA gather reference; fp32 softmax statistics; garbage slots
    past the window are masked with the framework-wide -1e9 convention.
    q: [B, nh, S, dh] -> out [B, nh, S, dh]; pools [num_pages, ps, nh*dh].
    """
    B, nh, S, dh = q.shape
    P, ps = page_table.shape[1], k_pool.shape[1]
    nkv = k_pool.shape[2] // dh
    slot = jnp.arange(P * ps, dtype=jnp.int32)
    limit = (jnp.reshape(start, (-1,))[:, None]
             + jnp.arange(S, dtype=jnp.int32)[None, :])   # [B, S]
    mask = slot[None, None, None, :] <= limit[:, None, :, None]
    k = _gather_pages(k_pool, page_table, nkv)
    v = _gather_pages(v_pool, page_table, nkv)
    qg = q.reshape(B, nkv, nh // nkv, S, dh)
    s = jnp.einsum("bjgsd,bkjd->bjgsk", qg.astype(k.dtype), k,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask[:, :, None], s, _NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bjgsk,bkjd->bjgsd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, nh, S, dh).astype(q.dtype)


@register_op("paged_decode_attention", grad="none")
def paged_decode_attention_op(ctx: ExecContext):
    """inputs: Q [B, nh, dh], KPool/VPool [pages, ps, nh*dh], PageTable
    [B, P] int32, Positions [B] int32 (current slot index; the context this
    step attends over is 0..Positions inclusive — the just-appended token
    attends to itself); attrs: sm_scale. Output: [B, nh, dh]."""
    q = ctx.input("Q")
    kp, vp = ctx.input("KPool"), ctx.input("VPool")
    out = paged_decode_attention_fn(
        q, kp, vp, ctx.input("PageTable"),
        ctx.input("Positions").astype(jnp.int32) + 1,
        sm_scale=ctx.attr("sm_scale", 1.0),
        tp=ctx.attr("tp_degree", 1))
    return {"Out": out.astype(q.dtype)}


@register_op("kv_cache_append", grad="none")
def kv_cache_append_op(ctx: ExecContext):
    """inputs: KPool/VPool, K/V [B, nh, dh], PageTable [B, P], Positions
    [B], optional Mask [B, 1] (the batch_mask row-mask convention: masked
    rows write nothing). Outputs KPoolOut/VPoolOut — the serving programs
    name these the SAME vars as the inputs, so the executor classifies the
    pools read-write and donates their buffers (in-place HBM update)."""
    live = ctx.input("Mask") if ctx.has_input("Mask") else None
    kp, vp = kv_cache_append_fn(
        ctx.input("KPool"), ctx.input("VPool"), ctx.input("K"),
        ctx.input("V"), ctx.input("PageTable"),
        ctx.input("Positions").astype(jnp.int32), live)
    return {"KPoolOut": kp, "VPoolOut": vp}


@register_op("kv_cache_prefill_write", grad="none")
def kv_cache_prefill_write_op(ctx: ExecContext):
    """inputs: KPool/VPool, K/V [B, nh, S, dh], PageTable [B, P], Lens [B],
    optional Start [B] (windowed write at slots Start+s, Lens counts local
    valid positions — the suffix-prefill/verify regime). Same in-place
    output aliasing contract as kv_cache_append."""
    start = (ctx.input("Start").astype(jnp.int32)
             if ctx.has_input("Start") else None)
    kp, vp = kv_cache_prefill_write_fn(
        ctx.input("KPool"), ctx.input("VPool"), ctx.input("K"),
        ctx.input("V"), ctx.input("PageTable"),
        ctx.input("Lens").astype(jnp.int32), start)
    return {"KPoolOut": kp, "VPoolOut": vp}


@register_op("paged_prefill_attention", grad="none")
def paged_prefill_attention_op(ctx: ExecContext):
    """inputs: Q [B, nh, S, dh], KPool/VPool, PageTable [B, P], Start [B]
    int32 (query s's global position is Start+s; it attends pool slots
    0..Start+s inclusive — its own just-written KV included); attrs:
    sm_scale. Output: [B, nh, S, dh]."""
    q = ctx.input("Q")
    out = paged_prefill_attention_fn(
        q, ctx.input("KPool"), ctx.input("VPool"), ctx.input("PageTable"),
        ctx.input("Start").astype(jnp.int32),
        sm_scale=ctx.attr("sm_scale", 1.0))
    return {"Out": out.astype(q.dtype)}


@register_op("kv_cache_copy_page", grad="none")
def kv_cache_copy_page_op(ctx: ExecContext):
    """Copy-on-write's copy: inputs KPool/VPool, Src [1] int32, Dst [1]
    int32 — pool[Dst] := pool[Src] for K and V, in place via the same
    output-aliasing donation contract as the other cache ops. The engine
    runs this once per COW'd page BEFORE the write that would have landed
    on a shared page."""
    kp, vp = ctx.input("KPool"), ctx.input("VPool")
    src = ctx.input("Src").astype(jnp.int32)[0]
    dst = ctx.input("Dst").astype(jnp.int32)[0]
    kp = kp.at[dst].set(kp[src])
    vp = vp.at[dst].set(vp[src])
    return {"KPoolOut": kp, "VPoolOut": vp}


@register_op("gather_token_logits", grad="none")
def gather_token_logits_op(ctx: ExecContext):
    """inputs: X [B, S, V], Lens [B] — output [B, V]: row b's logits at
    position Lens[b]-1 (the last real token of a bucket-padded prefill)."""
    x = ctx.input("X")
    lens = ctx.input("Lens").astype(jnp.int32)
    idx = jnp.clip(lens - 1, 0, x.shape[1] - 1)[:, None, None]
    return {"Out": jnp.take_along_axis(x, idx, axis=1)[:, 0, :]}


@register_op("last_token_select", grad="none")
def last_token_select_op(ctx: ExecContext):
    """inputs: Tok [B, 1] (the host's feed), FromHost [B, 1], Slot [B], Last
    [N] (the token each row slot's latest step emitted, kept on the device)
    — output [B, 1]: row b's Tok where FromHost is set, Last[Slot[b]]
    otherwise: a decode step takes the token of a step the host has not
    read yet."""
    tok = ctx.input("Tok").astype(jnp.int32)
    kept = ctx.input("Last")[ctx.input("Slot").astype(jnp.int32)]
    out = jnp.where(ctx.input("FromHost").reshape(-1) != 0, tok.reshape(-1),
                    kept)
    return {"Out": out.reshape(tok.shape)}


@register_op("last_token_write", grad="none")
def last_token_write_op(ctx: ExecContext):
    """inputs: Last [N], Slot [B], Next [B] — Last with Last[Slot[b]] =
    Next[b] (padding rows name slot N - 1, which no row reads). LastOut is
    the SAME var as Last, the pools' read-write contract."""
    last = ctx.input("Last")
    return {"LastOut": last.at[ctx.input("Slot").astype(jnp.int32)].set(
        ctx.input("Next").astype(last.dtype).reshape(-1))}


# ---------------------------------------------------------------------------
# Ring attention (sequence parallelism over the `sp` axis)
# ---------------------------------------------------------------------------


def ring_attention_local(q, k, v, axis_name, causal=False, sm_scale=1.0):
    """Blockwise ring attention (Liu et al., Ring Attention; public
    algorithm). Each device holds the full batch/head dims but a 1/p slice of
    the sequence. K/V blocks rotate p times around `axis_name`; the local
    online-softmax state (acc, m, l) merges each incoming block, giving exact
    softmax attention over the full sequence with O(S/p) memory per device.

    q, k, v: [B, nh, S_local, dh] (this device's shard). Causal masking uses
    the ring rank to compute each block's global offset.
    """
    p = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    B, nh, s_loc, dh = q.shape
    q32 = q.astype(jnp.float32) * sm_scale

    def block_scores(kb, src_rank):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q32, kb.astype(jnp.float32))
        if causal:
            q_pos = rank * s_loc + jnp.arange(s_loc)[:, None]
            k_pos = src_rank * s_loc + jnp.arange(s_loc)[None, :]
            scores = jnp.where(q_pos >= k_pos, scores, _NEG_INF)
        return scores

    def step(carry, _):
        acc, m, l, kb, vb, src = carry
        s = block_scores(kb, src)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + pexp.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", pexp, vb.astype(jnp.float32))
        # rotate kv to the next device on the ring
        perm = [(i, (i + 1) % p) for i in range(p)]
        kb_next = jax.lax.ppermute(kb, axis_name, perm)
        vb_next = jax.lax.ppermute(vb, axis_name, perm)
        src_next = (src - 1) % p
        return (acc_new, m_new, l_new, kb_next, vb_next, src_next), None

    acc0 = jnp.zeros((B, nh, s_loc, dh), jnp.float32)
    m0 = jnp.full((B, nh, s_loc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, nh, s_loc), jnp.float32)
    (acc, m, l, _, _, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v, rank), None, length=p)
    return (acc / l[..., None]).astype(q.dtype)


@register_op("ring_attention")
def ring_attention(ctx: ExecContext):
    """Sequence-parallel attention over the axis bound to `ring_id` (shard_map
    regime). With no axis bound (single device / GSPMD handles it), falls back
    to fused_attention semantics on the local (full) sequence."""
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    causal = ctx.attr("causal", False)
    sm_scale = ctx.attr("sm_scale", 1.0)
    axis = _axis(ctx)
    if axis is None:
        out = flash_attention(q, k, v, None, causal=causal, sm_scale=sm_scale)
    else:
        out = ring_attention_local(q, k, v, axis, causal=causal,
                                   sm_scale=sm_scale)
    return {"Out": out.astype(q.dtype)}
