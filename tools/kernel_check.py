"""On-chip kernel check: every Pallas kernel in the workbench registry is
compiled by Mosaic and run ON THE TPU (no interpreter) at the shape a
BERT-base trainer or the serving engine engages it, and compared with its
registered XLA reference — forward, and backward where it has one.

A kernel does not stay in the tree on the strength of the interpreter:
tests/ pin each kernel to its reference in interpret mode on the CPU, this
pins the compiled kernel on the chip. `CASES` is keyed by registry name and
`tools/gate.py --kernels` fails a registered kernel that has no case here.

The reference runs under `jax.default_matmul_precision("highest")` (for the
reference only — the TPU's default fp32 matmul rounds operands to bf16,
which would be the reference's error, not the kernel's). Errors are
max-abs differences relative to the reference's max-abs value.

    python tools/kernel_check.py        # exit 0 = all matched, on a TPU
    python tools/kernel_check.py --only splash_bundled,megablox_bundled

Exits 2 off the chip, 1 on any mismatch, compile failure or missing case.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# tolerance by the dtype the kernel computes in: fp32 kernels agree with a
# highest-precision reference to rounding; bf16 operands carry 2^-8
# relative rounding into every product and into the stored result
TOL = {"float32": 2e-5, "bfloat16": 3e-2}
GRAD_TOL = {"float32": 1e-4, "bfloat16": 5e-2}


def _rand(key, shape, dtype, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def _compare(fn, ref, args, n_diff: int, dtype: str) -> dict:
    """Run fn and ref on `args`; with n_diff > 0 also pull one seeded
    cotangent back through both and compare the first n_diff gradients."""
    out = {}
    if n_diff:
        diff, rest = args[:n_diff], args[n_diff:]
        got, got_vjp = jax.vjp(lambda *d: fn(*d, *rest), *diff)
        with jax.default_matmul_precision("highest"):
            want, want_vjp = jax.vjp(lambda *d: ref(*d, *rest), *diff)
        ct = _rand(jax.random.PRNGKey(99), got.shape, got.dtype)
        got_g = got_vjp(ct)
        with jax.default_matmul_precision("highest"):
            want_g = want_vjp(ct)
        out["grad_err"] = max(_rel_err(g, w) for g, w in zip(got_g, want_g))
        out["grad_tol"] = GRAD_TOL[dtype]
    else:
        got = fn(*args)
        with jax.default_matmul_precision("highest"):
            want = ref(*args)
    out["err"] = _rel_err(got, want)
    out["tol"] = TOL[dtype]
    out["finite"] = bool(np.all(np.isfinite(np.asarray(got, np.float32))))
    out["ok"] = bool(out["finite"] and out["err"] <= out["tol"]
                     and out.get("grad_err", 0.0) <= out.get("grad_tol", 1.0))
    return out


# ---------------------------------------------------------------------------
# cases: registry name -> [(label, thunk -> result dict)]
# ---------------------------------------------------------------------------


def _paged_cases(spec):
    """The serving default: decode attention over the 12 x 64 heads of the
    BERT-base-shaped decoder, a 2048 x 16 pool of lane-dense [nh*dh] rows
    (serving/kv_cache.pool_shape)."""

    from paddle_tpu.serving.kv_cache import pool_shape

    def case(dtype):
        B, nh, dh, ps, pages, P = 4, 12, 64, 16, 2048, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = _rand(ks[0], (B, nh, dh), dtype)
        kp = _rand(ks[1], pool_shape(pages, ps, nh, dh), dtype)
        vp = _rand(ks[2], pool_shape(pages, ps, nh, dh), dtype)
        table = jax.random.permutation(ks[3], pages)[:B * P].reshape(B, P)
        lens = jnp.asarray([208, 48, 13, 1], jnp.int32)
        assert spec.supported(q.shape, kp.shape)
        return _compare(
            lambda *a: spec.fn(*a, sm_scale=dh ** -0.5),
            lambda *a: spec.reference(*a, sm_scale=dh ** -0.5),
            (q, kp, vp, table.astype(jnp.int32), lens), 0, dtype)

    return [(f"b4 nh12 dh64 pool2048x16 {d}", lambda d=d: case(d))
            for d in ("float32", "bfloat16")]


def _attention_cases(spec, B, S, ragged=False):
    """Self-attention at BERT-base heads under AMP (bf16), forward and the
    fused backward."""

    def case(causal, with_lens):
        nh, dh = 12, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (_rand(kk, (B, nh, S, dh), "bfloat16") for kk in ks)
        assert spec.supported(q.shape, k.shape, None)
        kw = dict(causal=causal, sm_scale=dh ** -0.5)
        if with_lens:
            kw["kv_lens"] = jnp.asarray(
                np.random.default_rng(2).integers(1, S + 1, B), jnp.int32)
        return _compare(lambda *a: spec.fn(*a, **kw),
                        lambda *a: spec.reference(*a, **kw),
                        (q, k, v), 3, "bfloat16")

    arms = [(False, False), (True, False)] + ([(False, True)] if ragged
                                              else [])
    return [(f"b{B} s{S} nh12 dh64 bf16 causal={c} ragged={r} fwd+bwd",
             lambda c=c, r=r: case(c, r)) for c, r in arms]


def _bn_apply_cases(spec):
    """ResNet-50 NHWC stage tails under AMP: a stage-0 3x3 output, the
    stage-0 block exit with its residual, and the last stage."""

    def case(shape, residual):
        C = shape[-1]
        ks = jax.random.split(jax.random.PRNGKey(3), 6)
        x = _rand(ks[0], shape, "bfloat16")
        scale, bias, mean = (_rand(kk, (C,), "float32") for kk in ks[1:4])
        inv = jnp.abs(_rand(ks[4], (C,), "float32")) + 0.5
        args = (x, scale, bias, mean, inv)
        if residual:
            args += (_rand(ks[5], shape, "bfloat16"),)
        assert spec.supported(shape, "bfloat16", True, "relu")

        def call(f):
            if residual:
                return lambda x, s, b, m, v, r: f(x, s, b, m, v, act="relu",
                                                  residual=r)
            return lambda x, s, b, m, v: f(x, s, b, m, v, act="relu")

        return _compare(call(spec.fn), call(spec.reference), args,
                        len(args), "bfloat16")

    return [(f"NHWC {shape} bf16 relu residual={res} fwd+bwd",
             lambda shape=shape, res=res: case(shape, res))
            for shape, res in (((128, 56, 56, 64), False),
                               ((128, 56, 56, 256), True),
                               ((128, 7, 7, 2048), True))]


def _layer_norm_cases(spec):
    """The BERT-base LN rows of one b128 s128 step, and the ffn width."""

    def case(rows, width, act):
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        x = _rand(ks[0], (rows, width), "bfloat16")
        scale = _rand(ks[1], (width,), "float32")
        bias = _rand(ks[2], (width,), "float32")
        assert spec.supported((rows, width), "bfloat16", act)
        return _compare(lambda *a: spec.fn(*a, act=act),
                        lambda *a: spec.reference(*a, act=act),
                        (x, scale, bias), 3, "bfloat16")

    return [(f"[{r}, {w}] bf16 act={a} fwd+bwd",
             lambda r=r, w=w, a=a: case(r, w, a))
            for r, w, a in ((128 * 128, 768, "identity"),
                            (128 * 128, 3072, "relu"))]


def _paged_gqa_cases(spec):
    """The grouped-query arm of the paged kernel at the ZAYA1 geometry: 8
    query heads over 2 KV heads of 128 in 128-token bfloat16 pages."""

    def case(dtype):
        B, nh, nkv, dh, ps, pages, P = 8, 8, 2, 128, 128, 96, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        q = _rand(ks[0], (B, nh, dh), "float32")
        kp = _rand(ks[1], (pages, ps, nkv * dh), dtype)
        vp = _rand(ks[2], (pages, ps, nkv * dh), dtype)
        table = jax.random.permutation(ks[3], pages)[:B * P].reshape(B, P)
        lens = jnp.asarray([1024, 900, 513, 512, 129, 128, 1, 0], jnp.int32)
        assert spec.supported(q.shape, kp.shape, dtype)
        res = _compare(
            lambda *a: spec.fn(*a, sm_scale=dh ** -0.5)[:7],
            lambda *a: spec.reference(*a, sm_scale=dh ** -0.5)[:7],
            (q, kp, vp, table.astype(jnp.int32), lens), 0, "float32")
        # q.k and p.v are float32 on both sides whatever the pool holds; the
        # reference's bfloat16 cast of q is the whole difference
        res["tol"] = 2e-2 if dtype == "bfloat16" else 2e-5
        res["ok"] = bool(res["finite"] and res["err"] <= res["tol"])
        return res

    def window_case(nh):
        # Laguna-XS.2's sliding layers (64 query heads; 48 are a full
        # layer's) over 8 KV heads of 128: a row attends its last 512 slots
        # of a six-page compact table, from a first live slot on
        B, nkv, dh, ps, pages, P, W = 8, 8, 128, 128, 64, 6, 512
        ks = jax.random.split(jax.random.PRNGKey(6), 4)
        q = _rand(ks[0], (B, nh, dh), "float32")
        kp = _rand(ks[1], (pages, ps, nkv * dh), "bfloat16")
        vp = _rand(ks[2], (pages, ps, nkv * dh), "bfloat16")
        table = jax.random.permutation(ks[3], pages)[:B * P].reshape(B, P)
        lens = jnp.asarray([768, 640, 639, 513, 512, 130, 1, 0], jnp.int32)
        first = jnp.maximum(lens - W, 0)
        assert spec.supported(q.shape, kp.shape, "bfloat16")
        res = _compare(
            lambda *a: spec.fn(*a[:5], sm_scale=dh ** -0.5,
                               first_live=a[5])[:7],
            lambda *a: _windowed_reference(*a, dh ** -0.5)[:7],
            (q, kp, vp, table.astype(jnp.int32), lens, first), 0, "float32")
        res["tol"] = 2e-2
        res["ok"] = bool(res["finite"] and res["err"] <= res["tol"])
        return res

    return [(f"b8 nh8 nkv2 dh128 ps128 {dt} ragged",
             lambda dt=dt: case(dt)) for dt in ("float32", "bfloat16")] + [
        (f"b8 nh{nh} nkv8 dh128 ps128 bfloat16 window 512 first live slot",
         lambda nh=nh: window_case(nh)) for nh in (64, 48)]


def _paged_gqa_step_cases(spec):
    """The grouped-query arm at the steps the serving cells run, TIMED (ten
    calls after the first; `us` a layer's call given the step's plan, as
    the stacks call it, `us_alone` with the plan worked out in the call;
    `sharing`: the live tokens the rows attend over the tokens the call
    fetches, 1 where each row reads its own; `roofline_pct`: the K and V
    bytes of the ATTENDED tokens against 819 GB/s, which bounds a kernel
    that reads a page once a row and which one that shares may pass), the
    first eight rows against the reference. Laguna-XS.2's full layer: 64
    rows of 48 heads over 8 KV heads behind four contexts of 128 whole
    pages (34, 15, 9 and 6 rows a context: zipf 1.2), 1-20 pages of its own
    a row in a 160-page table; the same rows each behind a context of its
    OWN, where nothing is shared and the time is the walk's; and ZAYA1's
    step: 64 rows of 8 heads over 2 KV heads behind 19-28 pages of a
    32-page table, the first four of them one of four system prompts',
    under the block of 16 a group needs. Before them the other callers'
    head shapes with a run to share (Falcon-H1, Nemotron 3, and ZAYA1
    behind prompts of a whole block), sixteen rows against the reference."""
    import time

    from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

    dh, ps = 128, 128

    def the_step(nh, nkv, P, shared_pages, own_pages, shared: bool):
        B = 64
        own = np.asarray(own_pages(np.arange(B)))
        pages = 4 * shared_pages + int(own.sum())
        ks = jax.random.split(jax.random.PRNGKey(50), 3)
        q = _rand(ks[0], (B, nh, dh), "float32")
        kp = _rand(ks[1], (pages, ps, nkv * dh), "bfloat16")
        vp = _rand(ks[2], (pages, ps, nkv * dh), "bfloat16")
        rng = np.random.default_rng(50)
        behind = np.repeat(np.arange(4), [34, 15, 9, 6])[rng.permutation(B)]
        table = np.zeros((B, P), np.int32)
        at = 4 * shared_pages
        for b in range(B):
            table[b, :shared_pages] = (
                behind[b] * shared_pages + np.arange(shared_pages) if shared
                else (b * 29 + np.arange(shared_pages)) % (4 * shared_pages))
            table[b, shared_pages:shared_pages + own[b]] = \
                at + np.arange(own[b])
            at += own[b]
        lens = (shared_pages + own - 1) * ps + 1 + (np.arange(B) * 53) % ps
        return (q, kp, vp, jnp.asarray(table), jnp.asarray(lens, jnp.int32))

    def timed(args, check_rows=8):
        q, kp, vp, table, lens = args
        assert spec.supported(q.shape, kp.shape, "bfloat16")
        scale = dh ** -0.5

        def seconds(fn, *a):
            out = jax.block_until_ready(fn(*a))
            t = time.perf_counter()
            for _ in range(10):
                last = fn(*a)
            jax.block_until_ready(last)
            return out, (time.perf_counter() - t) / 10

        itemsize = kp.dtype.itemsize
        plan = jax.jit(lambda t, n: ppa.walk_plan(
            t, n, kp.shape, itemsize))(table, lens)
        got, call_s = seconds(jax.jit(
            lambda *a: spec.fn(*a[:5], sm_scale=scale, plan=a[5])),
            *args, plan)
        _, alone_s = seconds(jax.jit(
            lambda *a: spec.fn(*a, sm_scale=scale)), *args)
        n = check_rows
        with jax.default_matmul_precision("highest"):
            want = spec.reference(q[:n], kp, vp, table[:n], lens[:n],
                                  sm_scale=scale)
        tokens = int(np.asarray(lens).sum())
        read = ppa.walk_counts(table, lens, kp.shape, itemsize)
        res = {"err": _rel_err(got[:n], want), "tol": 2e-2,
               "finite": bool(np.isfinite(np.asarray(got)).all()),
               "us": call_s * 1e6, "us_alone": alone_s * 1e6,
               "sharing": tokens / read["tokens"],
               "roofline_pct": tokens * 2 * kp.shape[2] * itemsize
               / call_s / 819e9 * 100}
        res["ok"] = bool(res["finite"] and res["err"] <= res["tol"])
        return res

    laguna = dict(nh=48, nkv=8, P=160, shared_pages=128,
                  own_pages=lambda b: b * 7 % 20 + 1)
    zaya = dict(nh=8, nkv=2, P=32, shared_pages=4,
                own_pages=lambda b: 15 + b * 7 % 10)
    # the other callers' shapes with a run to share: Falcon-H1's 20 heads as
    # groups of 6 behind prompts of one block of 8 pages, Nemotron 3's
    # groups of 16 and ZAYA1's of 4 behind one block of 16
    falcon = dict(nh=24, nkv=4, P=24, shared_pages=8,
                  own_pages=lambda b: 1 + b * 5 % 16)
    nemotron = dict(nh=32, nkv=2, P=64, shared_pages=16,
                    own_pages=lambda b: 1 + b * 7 % 20)
    shared_zaya = dict(zaya, shared_pages=16, own_pages=lambda b: 1 + b % 12)
    return [(f"b64 nh{c['nh']} nkv{c['nkv']} dh128 ps128 bfloat16 table "
             f"{c['P']} four prompts of {c['shared_pages']} pages timed",
             lambda c=c: timed(the_step(**c, shared=True), check_rows=16))
            for c in (falcon, nemotron, shared_zaya)] + [
        ("b64 nh48 nkv8 dh128 ps128 bfloat16 table 160 four contexts "
         "34/15/9/6 rows timed",
         lambda: timed(the_step(**laguna, shared=True))),
        ("b64 nh48 nkv8 dh128 ps128 bfloat16 table 160 a context a row "
         "timed", lambda: timed(the_step(**laguna, shared=False))),
        ("b64 nh8 nkv2 dh128 ps128 bfloat16 table 32 four prompts of 4 "
         "pages timed", lambda: timed(the_step(**zaya, shared=True)))]


def _windowed_reference(q, kp, vp, table, lens, first, sm_scale):
    from paddle_tpu.ops.attention_ops import _paged_attention_reference

    return _paged_attention_reference(q, kp, vp, table, lens, sm_scale,
                                      first)


def _moe_cases(spec):
    """One layer of ZAYA1's experts (16 x 2048 -> 2048 -> 2048, bfloat16)
    out of a stack of two, at a decode batch and at a prefill window; and
    one of the "sparse_moe" block's (128 x 2048 -> 768 -> 2048, top-8), at
    a decode batch and at a 512-token chunk; and one of the "hybrid_moe"
    block's (256 x 2048 -> 512 -> 2048, top-8), the same two; and a share
    of the "latent_moe" block's (16 held of 256 x 7168 -> 2048 -> 7168), at
    a decode batch and at a 512-token chunk; and the "kda_moe" block's (128
    held of 512 x 2560 -> 768 -> 2560, top-8) at windows of 512, 1,024 and
    2,048 tokens, at 4,096 (two blocks of what one call keeps resident) and
    at 2,048 of which 300 are real and the others copies of one row, as a
    window's padding is.

    Every case is timed (`us`: the call as the stacks make it). A call of
    more than 256 rows takes the grouped form, and its parts are timed
    apart on the same arrays: `us_sort_rows` (the pairs sorted by expert
    and z's rows gathered into that order: a few XLA operations, so never
    under the host's 0.2 ms to enqueue them), `us_kernel`, and `us_walk`,
    the form a call of up to 256 rows takes (every held expert over every
    token tile: what such a call ran before PR 52), alternating with
    `us`."""
    import time

    from paddle_tpu.ops.pallas_kernels import moe_experts as pme

    def seconds(fn, *a):
        out = jax.block_until_ready(fn(*a))
        t = time.perf_counter()
        for _ in range(10):
            last = fn(*a)
        jax.block_until_ready(last)
        return out, (time.perf_counter() - t) / 10

    def timed(z, cw, wg, wu, wd, k):
        layer = jnp.int32(1)
        if k == 1:
            fn = jax.jit(lambda *a: spec.fn(*a, layer))
        else:
            fn = jax.jit(lambda *a: pme.moe_topk_experts(*a, layer, k=k))
        args = (z, cw, wg, wu, wd)
        assert spec.supported(z.shape, wg.shape)
        got, call_s = seconds(fn, *args)
        with jax.default_matmul_precision("highest"):
            want = spec.reference(*args, 1)
        res = {"err": _rel_err(got, want), "tol": TOL["bfloat16"],
               "finite": bool(np.isfinite(np.asarray(got)).all()),
               "us": call_s * 1e6}
        if z.shape[0] > pme._TOKEN_TILE:
            walk = jax.jit(lambda *a: pme._call(*a, layer, "decode", False,
                                                topk=k > 1))
            walked, walk_s = seconds(walk, *args)
            _, call2_s = seconds(fn, *args)
            _, walk2_s = seconds(walk, *args)
            res.update({"us": min(call_s, call2_s) * 1e6,
                        "us_walk": min(walk_s, walk2_s) * 1e6,
                        "walk_err": _rel_err(got, walked)})
        if pme._TOKEN_TILE < z.shape[0] <= pme._resident_tokens(
                wg.shape, wg.dtype.itemsize, k):
            # one block: its two parts apart
            sort = jax.jit(lambda z, cw: pme._sort_rows(z, cw, k, wg.dtype))
            (zs, ws, token, plan), sort_s = seconds(sort, z, cw)
            _, kernel_s = seconds(jax.jit(
                lambda zs, ws, token, plan, *w: pme._grouped_experts(
                    zs, ws, token, plan, -(-z.shape[0] // 16) * 16, *w, layer,
                    "check", False, k > 1)),
                zs, ws, token, plan, wg, wu, wd)
            _, visits = pme.grouped_visits(np.diff(np.asarray(plan[3])), np)
            res.update({
                "us_sort_rows": sort_s * 1e6, "us_kernel": kernel_s * 1e6,
                "pairs": int(plan[3][-1]),
                "tile_rows": int(visits.sum()) * pme.GROUP_TILE})
        res["ok"] = bool(res["finite"] and res["err"] <= res["tol"])
        return res

    def weights(seed, E, H, F):
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        wg = _rand(ks[1], (2, E, H, F), "bfloat16", H ** -0.5)
        wu = _rand(ks[2], (2, E, H, F), "bfloat16", H ** -0.5)
        wd = _rand(ks[3], (2, E, F, H), "bfloat16", F ** -0.5)
        return ks, wg, wu, wd

    def case(tokens):
        E, H, F = 16, 2048, 2048
        ks, wg, wu, wd = weights(2, E, H, F)
        z = _rand(ks[0], (tokens, H), "float32")
        choice = jax.random.randint(ks[4], (tokens,), 0, E)
        cw = jax.nn.one_hot(choice, E) * jax.random.uniform(
            ks[5], (tokens, 1), minval=0.1, maxval=0.9)
        return timed(z, cw, wg, wu, wd, 1)

    def topk_case(tokens, seed, E, H, F, held=None, scale=1.0, real=None):
        # eight combine weights a row, drawn among E experts and summing to
        # `scale`; this chip holds the first `held` of them (all, if None),
        # so most of a row's held columns are zero and many rows hold none.
        # `real`: the rows behind it are copies of row 0, as a window's
        # padding is, so that a few experts' groups span many tiles
        k = 8
        ks, wg, wu, wd = weights(seed, held or E, H, F)
        z = _rand(ks[0], (tokens, H), "float32")
        vals, ids = jax.lax.top_k(jax.random.uniform(ks[4], (tokens, E)), k)
        if real is not None:
            pad = jnp.arange(tokens)[:, None] >= real
            z = jnp.where(pad, z[:1], z)
            ids = jnp.where(pad, jnp.arange(k)[None, :] * 3, ids)
            vals = jnp.where(pad, vals[:1], vals)
        cw = jnp.sum(jax.nn.one_hot(ids, E)
                     * (scale * vals / vals.sum(-1, keepdims=True))[..., None],
                     1)[:, :held]
        return timed(z, cw, wg, wu, wd, k)

    return [
        # the "latent_moe" geometry: a chip's 16 HELD experts of width 2048
        # at hidden 7168 (the narrow F tile: 256 columns a grid step)
        *[(f"t{t} 16 held of 256 top8 h7168 f2048 bf16 layer 1 of 2",
           lambda t=t: topk_case(t, 9, 256, 7168, 2048, 16, 2.5))
          for t in (128, 512)],
        *[(f"t{t} e16 h2048 f2048 bf16 layer 1 of 2", lambda t=t: case(t))
          for t in (64, 300)],
        # the "sparse_moe" geometry: 128 experts of width 768 (F tile 384),
        # eight renormalised combine weights a row
        *[(f"t{t} e128 top8 h2048 f768 bf16 layer 1 of 2",
           lambda t=t: topk_case(t, 3, 128, 2048, 768))
          for t in (64, 512)],
        # the "hybrid_moe" geometry: 256 experts of width 512 (one F tile an
        # expert), the combine weights in two lane registers, summing to 2.5
        *[(f"t{t} e256 top8 h2048 f512 bf16 layer 1 of 2",
           lambda t=t: topk_case(t, 7, 256, 2048, 512, None, 2.5))
          for t in (64, 512)],
        # the "kda_moe" geometry: 128 held of 512 experts of width 768 at
        # hidden 2560, a window's three sizes
        *[(f"t{t} 128 held of 512 top8 h2560 f768 bf16 layer 1 of 2",
           lambda t=t: topk_case(t, 11, 512, 2560, 768, 128, 2.5))
          for t in (512, 1024, 2048, 4096)],
        ("t2048 (300 real) 128 held of 512 top8 h2560 f768 bf16 layer 1 of 2",
         lambda: topk_case(2048, 11, 512, 2560, 768, 128, 2.5, real=300))]


def _ssm_update_cases(spec):
    """The "parallel_ssm" geometry: 32 heads of a 256 x 128 float32 state in
    2 groups, a pool of 2 layers x 20 slots, a decode step's bucket of 20
    rows: 16 live on slots of their own and 4 padding rows on the scratch
    slot, which stays as it was. The WHOLE pool and y together."""

    def case():
        rows, H, N, P, G, B = 40, 32, 256, 128, 2, 20
        ks = jax.random.split(jax.random.PRNGKey(11), 6)
        pool = _rand(ks[0], (rows, H * N, P), "float32")
        idx = jnp.concatenate([
            20 + jax.random.permutation(ks[1], 19)[:16].astype(jnp.int32),
            jnp.full((4,), 39, jnp.int32)])
        a = jax.nn.sigmoid(_rand(ks[2], (B, H), "float32") + 2.0)
        dtx = _rand(ks[3], (B, H, P), "float32", 0.1)
        bm = _rand(ks[4], (B, G, N), "float32")
        cm = _rand(ks[5], (B, G, N), "float32")
        assert spec.supported(pool.shape, N, H // G)

        def both(fn):
            new_pool, y = fn(pool, idx, a, dtx, bm, cm, n_live=16)
            return jnp.concatenate([new_pool.reshape(-1), y.reshape(-1)])

        return _compare(lambda: both(spec.fn), lambda: both(spec.reference),
                        (), 0, "float32")

    def packed_case():
        # the "mixer_moe" geometry: 128 heads of a 128 x 64 float32 state in
        # 8 groups, two heads of a group side by side on the lanes (a slot
        # is [64 * 128, 128]); 2 layers x 20 slots, rows as above
        rows, H, N, P, G, B = 40, 128, 128, 64, 8, 20
        ks = jax.random.split(jax.random.PRNGKey(12), 6)
        pool = _rand(ks[0], (rows, H // 2 * N, 2 * P), "float32")
        idx = jnp.concatenate([
            20 + jax.random.permutation(ks[1], 19)[:16].astype(jnp.int32),
            jnp.full((4,), 39, jnp.int32)])
        a = jax.nn.sigmoid(_rand(ks[2], (B, H), "float32") + 2.0)
        dtx = _rand(ks[3], (B, H, P), "float32", 0.1)
        bm = _rand(ks[4], (B, G, N), "float32")
        cm = _rand(ks[5], (B, G, N), "float32")
        assert spec.supported(pool.shape, N, H // G // 2)
        assert not spec.supported((rows, H * N, P), N, H // G)

        def both(fn):
            new_pool, y = fn(pool, idx, a, dtx, bm, cm, n_live=16)
            return jnp.concatenate([new_pool.reshape(-1), y.reshape(-1)])

        return _compare(lambda: both(spec.fn), lambda: both(spec.reference),
                        (), 0, "float32")

    return [("b20 (16 live) h32 n256 p128 g2 pool 2x20 float32", case),
            ("b20 (16 live) h128 n128 p64 g8 packed 2 a lane row pool 2x20 "
             "float32", packed_case)]


def _kda_update_cases(spec):
    """The "kda_moe" geometry (benchmark/configs/ling3_flash.json): 32 heads
    of a 128 x 128 float32 state, ONE Kimi-Delta layer's 320 slots. The
    cell's decode step, timed: 256 live rows of a 256-row bucket, and 32 of
    32 (`us` a call, ten calls after the first; `roofline_pct`: the live
    rows' states read once and written once, 4,194,304 B a row, against 819
    GB/s). Then the chunked form (`kda_ops.kda_chunk_scan_fn`, no Pallas
    kernel: XLA's block products) at a 512-token window against the token
    recurrence: `us` a window and head-layer, `mxu_pct` its 168,448 float32
    operations a token and head against a sixth of 197 TFLOP/s (six
    bfloat16 passes at Precision.HIGHEST)."""
    import time

    from paddle_tpu.ops import kda_ops

    H, K, V, slots = 32, 128, 128, 320

    def seconds(fn, *a):
        out = jax.block_until_ready(fn(*a))
        t = time.perf_counter()
        for _ in range(10):
            last = fn(*a)
        jax.block_until_ready(last)
        return out, (time.perf_counter() - t) / 10

    def step(B, live):
        ks = jax.random.split(jax.random.PRNGKey(13), 7)
        pool = _rand(ks[0], (slots, H * K, V), "float32")
        idx = jax.random.permutation(ks[1], slots)[:B].astype(jnp.int32)
        q = _rand(ks[2], (B, H, K), "float32", K ** -0.5)
        k = kda_ops.l2_norm_fn(_rand(ks[3], (B, H, K), "float32"))
        v = _rand(ks[4], (B, H, V), "float32")
        a = jnp.exp(-5.0 * jax.nn.sigmoid(_rand(ks[5], (B, H, K),
                                                "float32")))
        beta = jax.nn.sigmoid(_rand(ks[6], (B, H), "float32"))
        assert spec.supported(pool.shape, K)

        def both(fn):
            new_pool, o = fn(pool, idx, q, k, v, a, beta, live)
            return jnp.concatenate([new_pool.reshape(-1), o.reshape(-1)])

        res = _compare(lambda: both(spec.fn), lambda: both(spec.reference),
                       (), 0, "float32")
        # timed on a pool that is handed on from call to call (donated), as
        # the step program hands it on
        call = jax.jit(lambda p: spec.fn(p, idx, q, k, v, a, beta, live),
                       donate_argnums=0)
        state = {"pool": pool + 0.0}

        def once():
            state["pool"], o = call(state["pool"])
            return o

        _, call_s = seconds(once)
        res.update(us=call_s * 1e6, roofline_pct=live * 2 * H * K * V * 4
                   / call_s / 819e9 * 100)
        return res

    def window(S):
        ks = jax.random.split(jax.random.PRNGKey(14), 6)
        q = kda_ops.l2_norm_fn(_rand(ks[0], (1, S, H, K), "float32")) \
            * K ** -0.5
        k = kda_ops.l2_norm_fn(_rand(ks[1], (1, S, H, K), "float32"))
        v = _rand(ks[2], (1, S, H, V), "float32")
        log_a = -5.0 * jax.nn.sigmoid(_rand(ks[3], (1, S, H, K), "float32"))
        beta = jax.nn.sigmoid(_rand(ks[4], (1, S, H), "float32"))
        s0 = _rand(ks[5], (1, H, K, V), "float32", 0.1)
        scan = jax.jit(lambda *a: kda_ops.kda_chunk_scan_fn(
            *a, 64, 16, -5.0))
        (o, s1), call_s = seconds(scan, q, k, v, log_a, beta, s0)
        with jax.default_matmul_precision("highest"):
            want_o, want_s = jax.jit(kda_ops.kda_token_recurrence_fn)(
                q, k, v, log_a, beta, s0)
        res = {"err": max(_rel_err(o, want_o), _rel_err(s1, want_s)),
               "tol": TOL["float32"],
               "finite": bool(np.isfinite(np.asarray(o)).all()),
               "us": call_s * 1e6,
               "mxu_pct": S * H * 168448 / call_s / (197e12 / 6) * 100}
        res["ok"] = bool(res["finite"] and res["err"] <= res["tol"])
        return res

    return [("b256 (256 live) h32 k128 v128 pool 320 float32 timed",
             lambda: step(256, 256)),
            ("b32 (32 live) h32 k128 v128 pool 320 float32 timed",
             lambda: step(32, 32)),
            ("b256 (200 live) h32 k128 v128 pool 320 float32 timed",
             lambda: step(256, 200)),
            ("chunked form: window 512 h32 k128 v128 chunk 64 sub 16 timed",
             lambda: window(512))]


def _moe_relu2_cases(spec):
    """A chip's share of the "mixer_moe" block's experts: 128 HELD of 512,
    two matrices 1024 -> 2688 -> 1024 and a squared ReLU, top-22 (so a
    row's held columns are mostly zero), bfloat16, layer 1 of a stack of
    two, at a decode batch and at a 512-token chunk."""

    def case(tokens):
        L, E, Z, F, k, held = 2, 512, 1024, 2688, 22, 128
        ks = jax.random.split(jax.random.PRNGKey(13), 4)
        u = _rand(ks[0], (tokens, Z), "float32")
        w1 = _rand(ks[1], (L, held, Z, F), "bfloat16", Z ** -0.5)
        w2 = _rand(ks[2], (L, held, F, Z), "bfloat16", F ** -0.5)
        vals, ids = jax.lax.top_k(jax.random.uniform(ks[3], (tokens, E)), k)
        cw = jnp.sum(jax.nn.one_hot(ids, E)
                     * (5.0 * vals / vals.sum(-1, keepdims=True))[..., None],
                     1)[:, :held]
        assert spec.supported(u.shape, w1.shape)
        return _compare(lambda *a: spec.fn(*a, 1),
                        lambda *a: spec.reference(*a, 1),
                        (u, cw, w1, w2), 0, "bfloat16")

    return [(f"t{t} 128 held of 512 top22 z1024 f2688 bf16 layer 1 of 2",
             lambda t=t: case(t)) for t in (128, 512)]


def _conv_update_cases(spec):
    """The two served tails (Falcon-H1: three rows of 5,120 channels;
    Nemotron-H: of 10,240), a pool of 2 layers x 20 slots, a decode step's
    bucket of 20 rows: 16 live on slots of their own and 4 padding rows on
    the scratch slot, which stays as it was. The WHOLE pool and the
    convolved rows together."""

    def case(C):
        rows, K, B = 40, 4, 20
        ks = jax.random.split(jax.random.PRNGKey(13), 5)
        pool = _rand(ks[0], (rows, (K - 1) * C // 128, 128), "float32")
        idx = jnp.concatenate([
            20 + jax.random.permutation(ks[1], 19)[:16].astype(jnp.int32),
            jnp.full((4,), 39, jnp.int32)])
        x = _rand(ks[2], (B, C), "float32")
        w = _rand(ks[3], (C, K), "float32", 0.5)
        b = _rand(ks[4], (C,), "float32")
        assert spec.supported(pool.shape, K)

        def both(fn):
            new_pool, y = fn(pool, idx, x, w, b, n_live=16)
            return jnp.concatenate([new_pool.reshape(-1), y.reshape(-1)])

        return _compare(lambda: both(spec.fn), lambda: both(spec.reference),
                        (), 0, "float32")

    return [(f"b20 (16 live) c{C} k4 pool 2x20 float32",
             lambda C=C: case(C)) for C in (5120, 10240)]


def _paged_indexer_cases(spec):
    """The two serving geometries of the paged indexer kernel (DeepSeek-
    V3.2-Exp: 64 heads of 128; Keye-VL2: 16 of 64; 128-token pages of
    bfloat16 keys) behind a 96-page table of a layer's rows: rows behind
    one document (the same pages), lengths on both sides of a page's and a
    block's edge, a row with no context. The live positions are compared;
    what lies past a row's length must be finite."""

    def case(J, D):
        B, ps, pages, P = 8, 128, 256, 96
        ks = jax.random.split(jax.random.PRNGKey(12), 4)
        qi = _rand(ks[0], (B, J, D), "float32")
        w = _rand(ks[1], (B, J), "float32", (J * D) ** -0.5)
        pool = _rand(ks[2], (2 * pages, D, ps), "bfloat16")
        doc = jax.random.permutation(ks[3], pages)[:P]
        table = jnp.stack([doc if b % 2 else doc[::-1] for b in range(B)])
        table = (table + pages).astype(jnp.int32)       # layer 1's rows
        lens = jnp.asarray([96 * ps, 95 * ps + 1, 48 * ps, 48 * ps + 1,
                            8 * ps - 1, 129, 1, 0], jnp.int32)
        assert spec.supported(qi.shape, pool.shape, pool.dtype)
        live = (jnp.arange(P * ps)[None, :] < lens[:, None])[:, None]
        got = spec.fn(qi, w, pool, table, lens)
        res = _compare(lambda: jnp.where(live, got, 0.0),
                       lambda: jnp.where(live, spec.reference(
                           qi, w, pool, table, lens), 0.0), (), 0, "float32")
        res["finite"] = bool(np.all(np.isfinite(np.asarray(got))))
        res["ok"] = bool(res["ok"] and res["finite"])
        return res

    return [(f"b8 j{J} d{D} ps128 bfloat16 table 96 ragged shared pages",
             lambda J=J, D=D: case(J, D)) for J, D in ((64, 128), (16, 64))]


def _latent_attend_cases(spec):
    """The latent rows attention at the DeepSeek-V3.2-Exp cell's shapes:
    128 heads over a 512-value latent and a 64-lane rotary key in rows of
    384 words, a decode step's 128 queries and a window's block of 64, each
    over its own 2,048 gathered rows: queries with every row, with a few
    and with ONE (the context behind them is shorter than the selection),
    the rows' padding words holding NaN patterns."""
    from paddle_tpu.ops import latent_moe_ops as ops
    from paddle_tpu.serving import DecoderConfig
    from paddle_tpu.serving import model as sv_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "deepseek_v32_exp.json")) as f:
        cfg = DecoderConfig(**json.load(f)["engine"]["config_kwargs"])
    geom = ops.Geometry(**sv_model._latent_geometry(cfg))
    assert (geom.num_heads, geom.kv_rank, geom.rope_dim) == (128, 512, 64)

    def case(R):
        K, words = 2048, 384
        ks = jax.random.split(jax.random.PRNGKey(41), 4)
        rows = jax.jit(lambda c, r: ops.join_latent_fn(
            c, r, jnp.bfloat16, words).at[..., 288:].set(0x7FC1FFFF))(
                _rand(ks[0], (R, K, 512), "float32"),
                _rand(ks[1], (R, K, 64), "float32"))
        q_lat = _rand(ks[2], (R, 128, 512), "float32", 0.5)
        q_rope = _rand(ks[3], (R, 128, 64), "float32", 0.5)
        live = jnp.where(jnp.arange(R) % 5 == 3,
                         1 + jnp.arange(R) * 37 % K, K).at[-1].set(1)
        have = jnp.arange(K)[None, :] < live[:, None]
        assert spec.supported(q_lat.shape, rows.shape, jnp.bfloat16,
                              geom.rope_dim)
        return _compare(spec.fn, spec.reference,
                        (q_lat, q_rope, rows, have, jnp.bfloat16, geom), 0,
                        "bfloat16")

    return [(f"r{R} nh128 latent512 rope64 k2048 words384 bfloat16 ragged",
             lambda R=R: case(R)) for R in (128, 64)]


def _paged_latent_cases(spec):
    """The paged latent attention at the Xing4.0 cell's shapes: 32 heads
    over a 512-value latent and a 64-lane rotary key in rows of 384 words,
    pages of 128 in layer 1's rows of a two-layer pool. 16 decode rows
    behind the cell's 288-page table (rows behind one document share its
    pages; the XLA reference gathers 0.9 GB for them) and 64 rows, a decode
    step's, behind 32 pages: lengths on both sides of a page's, a chunk's
    and a block's edge, a row of ONE position and a row with no context
    (zeros), the rows' padding words holding NaN patterns. Then THE CELL'S
    DECODE STEP, timed: 64 rows behind four documents of 256 whole pages
    (34, 15, 9 and 6 rows a document), two pages of its own a row, 32,768 +
    1..256 positions (`timed`: ten calls after the first; `ms` a layer's
    call given the step's plan and `ms_alone` with the plan worked out in
    it; `sharing`, attended positions over fetched ones; `mxu_pct`, the
    configuration's `kernel_bytes.latent_row_flops` a position against the
    chip's 197 TFLOP/s; `roofline_pct`, 1,152 B a position against 819
    GB/s, which bounds a kernel that reads a page once a row and which one
    that shares may pass), its first eight rows against the reference; and
    the same step with every row behind a document of its OWN, and the two
    shapes above with tables that differ from their first entry on, where
    nothing is shared and the time is the walk's."""
    import time

    from paddle_tpu.ops import latent_moe_ops as ops
    from paddle_tpu.ops.pallas_kernels import paged_latent_attend
    from paddle_tpu.serving import DecoderConfig
    from paddle_tpu.serving import model as sv_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "xing4_29b_a4b.json")) as f:
        config = json.load(f)
    cfg = DecoderConfig(**config["engine"]["config_kwargs"])
    row_bytes = config["kernel_bytes"]["latent_row_bytes"]
    row_flops = config["kernel_bytes"]["latent_row_flops"]
    geom = ops.Geometry(**sv_model._latent_geometry(cfg))
    assert (geom.num_heads, geom.kv_rank, geom.rope_dim) == (32, 512, 64)
    ps, words = 128, 384

    def feeds(B, pages, seed, nan=True):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        pool = jax.jit(lambda c, r: ops.join_latent_fn(
            c, r, jnp.bfloat16, words).at[..., 288:].set(
                0x7FC1FFFF if nan else 0))(
                _rand(ks[0], (2 * pages, ps, 512), "float32"),
                _rand(ks[1], (2 * pages, ps, 64), "float32"))
        return (_rand(ks[2], (B, 32, 512), "float32", 0.5),
                _rand(ks[3], (B, 32, 64), "float32", 0.5), pool, ks[4])

    def ragged(B, P, shared=True):
        pages = 320
        q_lat, q_rope, pool, key = feeds(B, pages, 47)
        doc = jax.random.permutation(key, pages)[:P]
        every = jax.random.permutation(key, pages)
        table = jnp.stack([(doc if b % 2 else doc[::-1]) if shared
                           else jnp.roll(every, -5 * b)[:P] for b in range(B)])
        table = (table + pages).astype(jnp.int32)       # layer 1's rows
        edges = [P * ps, (P - 1) * ps + 1, P * ps // 2, P * ps // 2 + 1,
                 8 * ps - 1, 513, 1, 0]
        lens = jnp.asarray([edges[b % 8] if b < 8 else
                            (b * 977) % (P * ps) + 1 for b in range(B)],
                           jnp.int32)
        assert spec.supported(q_lat.shape, pool.shape, jnp.bfloat16,
                              geom.rope_dim)
        return (q_lat, q_rope, pool, table, lens, jnp.bfloat16, geom)

    def case(B, P):
        args = ragged(B, P)
        res = _compare(spec.fn, spec.reference, args, 0, "bfloat16")
        got = spec.fn(*args)
        res["zeros"] = bool(
            not np.asarray(got)[np.asarray(args[4]) == 0].any())
        res["ok"] = bool(res["ok"] and res["zeros"])
        return res

    def the_step(shared: bool):
        """The cell's plateau step, or the same rows each behind its own
        document."""
        B, P, docs, own = 64, 288, 256, 2
        pages = 4 * docs + B * own
        q_lat, q_rope, pool, _ = feeds(B, pages, 48, nan=False)
        doc_of = np.repeat(np.arange(4), [34, 15, 9, 6])
        rng = np.random.default_rng(48)
        doc_of = doc_of[rng.permutation(B)]
        table = np.zeros((B, P), np.int32)
        for b in range(B):
            behind = doc_of[b] * docs + np.arange(docs) if shared \
                else (b * 18 + np.arange(docs)) % pages
            table[b, :docs] = behind
            table[b, docs:docs + own] = 4 * docs + b * own + np.arange(own)
        lens = docs * ps + 1 + (np.arange(B) * 53) % 256
        return (q_lat, q_rope, pool, jnp.asarray(table + pages),
                jnp.asarray(lens, jnp.int32), jnp.bfloat16, geom)

    def timed(args, check_rows=8):
        """`ms`: a layer's call given the step's plan, as the stack calls
        it; `ms_alone`: a call that works its plan out itself, a step of
        one layer (the difference is what a step pays once for all its
        layers)."""
        q_lat, q_rope, pool, table, lens = args[:5]

        def seconds(fn, *a):
            out = jax.block_until_ready(fn(*a))
            t = time.perf_counter()
            for _ in range(10):
                last = fn(*a)
            jax.block_until_ready(last)
            return out, (time.perf_counter() - t) / 10

        plan = jax.jit(lambda t, n: paged_latent_attend.step_plan(
            t, n, pool.shape))(table, lens)
        got, call_s = seconds(jax.jit(
            lambda *a: spec.fn(*a[:5], jnp.bfloat16, geom, a[5])),
            *args[:5], plan)
        _, alone_s = seconds(jax.jit(
            lambda *a: spec.fn(*a, jnp.bfloat16, geom)), *args[:5])
        n = check_rows
        with jax.default_matmul_precision("highest"):
            want = spec.reference(q_lat[:n], q_rope[:n], pool, table[:n],
                                  lens[:n], jnp.bfloat16, geom)
        positions = int(np.asarray(lens).sum())
        res = {"err": _rel_err(got[:n], want), "tol": TOL["bfloat16"],
               "finite": bool(np.isfinite(np.asarray(got)).all()),
               "ms": call_s * 1e3, "ms_alone": alone_s * 1e3,
               "sharing": positions / ps / paged_latent_attend.pages_read(
                   table, lens, pool.shape),
               "mxu_pct": positions * row_flops / call_s / 197e12 * 100,
               "roofline_pct": positions * row_bytes / call_s / 819e9 * 100}
        res["ok"] = bool(res["finite"] and res["err"] <= res["tol"])
        return res

    shape = "nh32 latent512 rope64 ps128 words384 bfloat16"
    return [(f"b{B} {shape} table {P} ragged shared pages",
             lambda B=B, P=P: case(B, P))
            for B, P in ((16, 288), (64, 32))] + [
        (f"b64 {shape} table 288 four documents 34/15/9/6 rows timed",
         lambda: timed(the_step(True))),
        (f"b64 {shape} table 288 a document a row timed",
         lambda: timed(the_step(False)))] + [
        (f"b{B} {shape} table {P} ragged a document a row timed",
         lambda B=B, P=P: timed(ragged(B, P, shared=False), check_rows=4))
        for B, P in ((16, 288), (64, 32))]


def _paged_looped_step_cases(spec):
    """The looped model's decode step, TIMED (ten calls after the first):
    16 query heads over 16 KV heads of 128 (one head a KV head in a
    2,048-wide row: since PR 55 the matrix-unit arm as the list walk, the
    two prompts' pages read once; the vector-unit arm until then),
    bfloat16 pages of 16 tokens, 20 live rows of a bucket of 32
    behind one of two 256-token prompts and 40-300 tokens of their own,
    under an 80-page table, in plane 100 of a pool of 192 planes of 296
    pages (the cell's: 3.72 GB a pool, so the rows a call reads lie where
    a step's lie). The calls are timed as the stack makes them: 48 of them
    (one visit's layers, planes 100-147) in ONE program, the walk's plan
    worked out once ahead of them, so that `us` is the device's time for
    one of a step's 192 calls and not the host's dispatch of a program
    (about 200 us here: until PR 55 the case timed a call a program and
    read 249-252 us whatever the kernel took). `roofline_pct`: the K and V
    bytes of the tokens the call READ (`tokens_read`: where it walks, a
    run of pages that rows share once, `walk_counts`; else every row's own,
    `tokens`) against 819 GB/s; the first eight rows of one call against
    the reference."""
    import time

    from paddle_tpu.ops.pallas_kernels import paged_attention

    def case():
        B, live, nh, dh, ps, pages, planes, plane, calls = 32, 20, 16, 128, \
            16, 296, 192, 100, 48
        P = 1280 // ps
        ks = jax.random.split(jax.random.PRNGKey(53), 3)
        q = _rand(ks[0], (B, nh, dh), "float32")
        # drawn in bfloat16: a float32 draw of one pool is 7.4 GB
        kp, vp = (jax.random.normal(k, (planes * pages, ps, nh * dh),
                                    jnp.bfloat16) for k in ks[1:])
        rng = np.random.default_rng(53)
        # two 256-token prompts (16 pages each) that the rows stand behind,
        # and 40-300 tokens of a row's own: 3.9k of the pool's 4.7k tokens
        own = rng.integers(40, 300, live)
        lens = np.zeros((B,), np.int32)
        lens[:live] = 256 + own
        table = np.zeros((B, P), np.int32)
        free = rng.permutation(pages - 32) + 32
        at = 0
        for b in range(live):
            n = -(-int(own[b]) // ps)
            table[b, :16] = plane * pages + (b % 2) * 16 + np.arange(16)
            table[b, 16:16 + n] = plane * pages + free[at:at + n]
            at += n
        assert at <= pages - 32
        args = (q, kp, vp, jnp.asarray(table), jnp.asarray(lens))
        assert spec.supported(q.shape, kp.shape, "bfloat16")
        scale = dh ** -0.5
        walks = paged_attention.walk_supported(q.shape, kp.shape, "bfloat16",
                                               P)

        def visit(q, kp, vp, table, lens):
            plan = paged_attention.walk_plan(
                table, lens, kp.shape, kp.dtype.itemsize) if walks else None

            def layer(acc, i):
                planed = jnp.where(lens[:, None] > 0, table + i * pages, 0)
                return acc + spec.fn(q, kp, vp, planed, lens, sm_scale=scale,
                                     plan=plan), None
            return jax.lax.scan(layer, jnp.zeros(q.shape, jnp.float32),
                                jnp.arange(calls, dtype=jnp.int32))[0]

        got = jax.block_until_ready(jax.jit(
            lambda *a: spec.fn(*a, sm_scale=scale))(*args))
        fn = jax.jit(visit)
        jax.block_until_ready(fn(*args))
        t = time.perf_counter()
        for _ in range(10):
            last = fn(*args)
        jax.block_until_ready(last)
        call_s = (time.perf_counter() - t) / 10 / calls
        with jax.default_matmul_precision("highest"):
            want = spec.reference(q[:8], kp, vp, args[3][:8], args[4][:8],
                                  sm_scale=scale)
        tokens = int(lens.sum())
        read = paged_attention.walk_counts(
            table[:live], lens[:live], kp.shape,
            kp.dtype.itemsize)["tokens"] if walks else tokens
        res = {"err": _rel_err(got[:8], want), "tol": 2e-2,
               "finite": bool(np.isfinite(np.asarray(got[:live])).all()),
               "us": call_s * 1e6, "tokens": tokens, "tokens_read": read,
               "walks": bool(walks),
               "roofline_pct": read * 2 * nh * dh * 2 / call_s / 819e9
               * 100}
        res["ok"] = bool(res["finite"] and res["err"] <= res["tol"])
        return res

    return [("b32 (20 live) nh16 nkv16 dh128 ps16 bfloat16 table 80 plane "
             "100 of 192 timed", case)]


CASES = {
    "paged_latent_attention": _paged_latent_cases,
    "latent_rows_attention": _latent_attend_cases,
    "indexer_paged_scores": _paged_indexer_cases,
    "ssm_decode_update": _ssm_update_cases,
    "conv_decode_update": _conv_update_cases,
    "kda_decode_update": _kda_update_cases,
    "attention_paged_decode": lambda spec: (_paged_cases(spec)
                                            + _paged_gqa_cases(spec)
                                            + _paged_gqa_step_cases(spec)
                                            + _paged_looped_step_cases(spec)),
    "moe_top1_experts": _moe_cases,
    "moe_relu2_experts": _moe_relu2_cases,
    # bench_bert_long: b64 s512
    "attention_short_seq": lambda spec: _attention_cases(spec, 64, 512),
    # bench_bert_short: b128 s128
    "attention_short128": lambda spec: _attention_cases(spec, 128, 128,
                                                        ragged=True),
    "epilogue_bn_apply": _bn_apply_cases,
    "epilogue_layer_norm": _layer_norm_cases,
}


def _flash_bundled_case():
    """Not a workbench kernel but an arm of attention_backend (S > 1024):
    jax's bundled flash kernel against the dispatch's own reference."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    from paddle_tpu.ops.attention_ops import _reference_attention

    B, nh, S, dh = 2, 12, 2048, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (_rand(kk, (B, nh, S, dh), "bfloat16") for kk in ks)
    return _compare(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           sm_scale=dh ** -0.5),
        lambda q, k, v: _reference_attention(q, k, v, None, True,
                                             dh ** -0.5),
        (q, k, v), 3, "bfloat16")


def _seconds(fn, *a, n=10):
    import time

    out = jax.block_until_ready(fn(*a))
    t = time.perf_counter()
    for _ in range(n):
        last = fn(*a)
    jax.block_until_ready(last)
    return out, (time.perf_counter() - t) / n


_PEAK_FLOPS = 197e12        # benchmark/peaks.json, "TPU v5 lite"


def _train_attention_case(window: int):
    """Not a workbench kernel but the arm of `fused_attention` a training
    decoder runs (jax's bundled splash attention behind
    `attention_ops.blockwise_attention`): checked against the dense form at
    2,048 positions, forward and backward, and timed at the training cell's
    shape (2 rows x 8,192 positions, 32 query heads over 4 of 128), forward
    and forward + backward, against the matrix unit's time for the pairs a
    query SEES (4 x 4096 FLOPs a pair forward, 12 x 4096 with the backward:
    the kernels compute whole blocks and the backward computes the scores
    again, so 100% is not reachable)."""
    from paddle_tpu.ops import attention_ops as ao

    nh, nkv, dh = 32, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(58 + window), 3)
    small = [_rand(k, (1, n, 2048, dh), "bfloat16")
             for k, n in zip(ks, (8, 1, 1))]
    res = _compare(
        lambda q, k, v: ao.blockwise_attention(q, k, v, True, dh ** -0.5,
                                               window),
        lambda q, k, v: ao.grouped_query_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), True, dh ** -0.5,
            window).astype(q.dtype),
        tuple(small), 3, "bfloat16")
    B, S = 2, 8192
    q, k, v = (_rand(kk, (B, n, S, dh), "bfloat16")
               for kk, n in zip(ks, (nh, nkv, nkv)))
    fwd = jax.jit(lambda q, k, v: ao.blockwise_attention(
        q, k, v, True, dh ** -0.5, window))
    both = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        ao.blockwise_attention(q, k, v, True, dh ** -0.5, window)
        .astype(jnp.float32) * 0.5), (0, 1, 2)))
    _, fwd_s = _seconds(fwd, q, k, v)
    _, both_s = _seconds(both, q, k, v)
    w = window or S
    pairs = B * (w * (w + 1) // 2 + (S - w) * w)
    block = ao._blockwise_block(S, window)
    visited, causal = ao.key_blocks(S, block, True, window)
    res.update({
        "us_fwd": fwd_s * 1e6, "us_fwd_bwd": both_s * 1e6,
        "pairs_seen": pairs, "pairs_visited": B * visited * block * block,
        "key_blocks": [visited, causal],
        "roofline_fwd_pct": 100 * 4 * nh * dh * pairs / _PEAK_FLOPS / fwd_s,
        "roofline_fwd_bwd_pct":
            100 * 12 * nh * dh * pairs / _PEAK_FLOPS / both_s})
    return res


def _train_experts_case():
    """The training decoder's grouped expert products
    (`decoder_train_ops`, jax's bundled megablox) at what one chunk of the
    training cell computes: 4,096 tokens, top-8 of 64 with 16 held, 2304 ->
    896 -> 2304 in bfloat16. The whole layer, forward and backward, against
    the dense loop over the experts; then the three kinds of product alone
    (forward, dX, dW), each against the matrix unit's time for the live
    rows' 2 x 2304 x 896 FLOPs."""
    from paddle_tpu.ops import decoder_train_ops as dt
    from paddle_tpu.ops.decoder_common import topk_router_fn

    T, H, F, E, held, k = 4096, 2304, 896, 64, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(58), 6)
    z = _rand(ks[0], (T, H), "bfloat16")
    _, cw = topk_router_fn(_rand(ks[1], (T, H), "float32"),
                           _rand(ks[2], (H, E), "float32", 2 * H ** -0.5), k)
    cw = cw[:, :held]
    wg = _rand(ks[3], (held, H, F), "bfloat16", H ** -0.5)
    wu = _rand(ks[4], (held, H, F), "bfloat16", H ** -0.5)
    wd = _rand(ks[5], (held, F, H), "bfloat16", F ** -0.5)

    def dense(z, wg, wu, wd, cw):
        z, wg, wu, wd = (a.astype(jnp.float32) for a in (z, wg, wu, wd))
        out = 0
        for e in range(held):
            g = z @ wg[e]
            out = out + cw[:, e, None] * ((jax.nn.silu(g) * (z @ wu[e]))
                                          @ wd[e])
        return out

    ours = lambda z, wg, wu, wd, cw: dt.moe_experts_train_fn(  # noqa: E731
        z, cw, wg, wu, wd, k)[0]
    res = _compare(ours, dense, (z, wg, wu, wd, cw), 4, "bfloat16")
    fwd = jax.jit(ours)
    both = jax.jit(jax.grad(lambda *a: jnp.sum(ours(*a) * 0.5), (0, 1, 2, 3)))
    _, fwd_s = _seconds(fwd, z, wg, wu, wd, cw)
    _, both_s = _seconds(both, z, wg, wu, wd, cw)
    zs, _, _, sizes, _, _ = jax.jit(
        lambda z, cw: dt._plan(z, cw, k, jnp.bfloat16))(z, cw)
    live = int(jnp.sum(sizes[:-1]))
    dy = _rand(ks[1], (zs.shape[0], F), "bfloat16")
    _, gmm_s = _seconds(jax.jit(lambda a, w, s: dt.grouped_matmul(
        a, w, s, jnp.float32)), zs, wg, sizes)
    _, dx_s = _seconds(jax.jit(lambda a, w, s: dt.grouped_matmul(
        a, w, s, jnp.float32, transpose_rhs=True)), dy, wg, sizes)
    _, dw_s = _seconds(jax.jit(lambda a, b, s: dt.grouped_matmul_t(
        a, b, s, jnp.float32)), zs, dy, sizes)
    one = 2 * live * H * F / _PEAK_FLOPS
    res.update({
        "held_assignments": live, "rows": int(zs.shape[0]),
        "tiling": [list(dt._tiling("gmm", zs.shape[0], H, F)),
                   list(dt._tiling("gmm", zs.shape[0], F, H)),
                   list(dt._tiling("tgmm", zs.shape[0], H, F))],
        "us_fwd": fwd_s * 1e6, "us_fwd_bwd": both_s * 1e6,
        "roofline_fwd_pct": 100 * 3 * one / fwd_s,
        "roofline_fwd_bwd_pct": 100 * 9 * one / both_s,
        "us_product_fwd": gmm_s * 1e6, "us_product_dx": dx_s * 1e6,
        "us_product_dw": dw_s * 1e6,
        "roofline_product_pct": [100 * one / t
                                 for t in (gmm_s, dx_s, dw_s)]})
    return res


# the two bundled kernels a training decoder runs (PR 58), at the training
# cell's shapes: the yardstick a later change to either is held to
TRAIN_DECODER = [
    ("splash_bundled", "b2 s8192 nh32/4 dh128 bf16 window 1024 fwd, bwd",
     lambda: _train_attention_case(1024)),
    ("splash_bundled", "b2 s8192 nh32/4 dh128 bf16 full causal fwd, bwd",
     lambda: _train_attention_case(0)),
    ("megablox_bundled", "t4096 top8/64 held16 2304x896 bf16 fwd, dX, dW",
     _train_experts_case),
]


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"kernel_check.py: needs a TPU, jax found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from paddle_tpu import compile_cache
    from paddle_tpu.ops.pallas_kernels import all_kernels

    compile_cache.configure()
    kernels = all_kernels()
    plan = []
    failed = [f"{name}: registered kernel has no on-chip case"
              for name in sorted(kernels) if name not in CASES]
    for name in sorted(kernels):
        if name in CASES:
            plan += [(name, label, thunk)
                     for label, thunk in CASES[name](kernels[name])]
    plan.append(("flash_bundled", "b2 s2048 nh12 dh64 bf16 causal fwd+bwd",
                 _flash_bundled_case))
    plan += TRAIN_DECODER
    if "--only" in sys.argv[1:]:
        only = sys.argv[sys.argv.index("--only") + 1].split(",")
        plan = [p for p in plan if p[0] in only]
        failed = []
    results = {}
    for name, label, thunk in plan:
        # a refused compile must not hide the verdicts of the other kernels:
        # record it, go on, and fail the run at the end
        try:
            res = thunk()
        except Exception as e:  # noqa: BLE001 - reported and fails the run
            res = {"ok": False,
                   "error": f"{type(e).__name__}: {str(e)[:2000]}"}
        results.setdefault(name, {})[label] = res
        print(f"[{'ok' if res['ok'] else 'FAIL'}] {name} :: {label} :: "
              f"{json.dumps(res)}", flush=True)
        if not res["ok"]:
            failed.append(f"{name} :: {label}")
    print(json.dumps({
        "ok": not failed, "failed": failed,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "results": results}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
