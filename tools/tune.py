"""Offline A/B sweeper: populate the tuning DB with measured verdicts.

For every shape in the sweep set, each candidate implementation is timed
with the tools/_timing.py protocol (warmup, median-of-windows, interference
band) and the keep-or-retire verdict is written into the persistent decision DB
(paddle_tpu/tuning/) that FLAGS_tuning_mode=consult reads at minimize()/
trace time. A tie inside the band records the ANALYTIC decision — a noise
margin must never overwrite a cost model with a coin flip — and every entry
carries its measured medians + band so a later reader can re-judge it.

Sweeps:
  conv       — direct vs implicit-GEMM lowering per conv shape (default
               set: the four ResNet-50 conv shapes below; add yours
               with repeated --conv-shape n,h,w,cin,cout,kh,kw,sh,sw).
  attention  — XLA einsum composition vs the short-seq Pallas kernels
               (seq<=128 and the 128-multiple kernel) vs the bundled flash
               kernel per (batch, heads, seq, head_dim) (default:
               BERT-base at b128 s128 and b64 s512). Arms a platform
               cannot run (Pallas off-TPU) are skipped.
  epilogue   — XLA composition vs the fused normalize+affine+act(+residual)
               Pallas kernel (ops/pallas_kernels/epilogue.py) over the
               ResNet-50 conv OUTPUT shapes (the BN apply tail,
               NHWC + NCHW, with and without residual) and the BERT-base
               s128 layer-norm rows.
  embedding  — tiered-embedding cache geometry (ISSUE 10): slot-count and
               prefetch-width arms per table geometry, each arm a real
               one-table training loop (resolve + install + gather +
               scatter-add through the Executor — the resolution cost IS
               part of what the geometry trades), driven by a seeded zipf
               id stream. Verdicts land as 'embedding|table=..' keys the
               minimize()-time rewrite consults.
  candidates — every `candidate` conv2d / attention / epilogue / embedding
               entry a FLAGS_tuning_mode=sweep run recorded into the DB
               gets measured and upgraded.

These are per-shape microbenches — TVM-style schedule search, deliberately
NOT the chained-per-op instrument PERF.md retired (each arm here is one
jitted fwd+bwd of a single op, not a chain whose interactions poison the
sum). The end-to-end confirmation is a cell of BENCHMARK.json run with the
DB consulted against the same cell without it; no cell consults a DB today
(ROADMAP D3).

    python tools/tune.py --db TUNING_DB.json                  # full sweep
    python tools/tune.py --db x.json --what conv --iters 20
    python tools/tune.py --db x.json --what candidates        # upgrade
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu import tuning  # noqa: E402
from paddle_tpu.ops.nn_ops import (_conv2d_igemm_f32,  # noqa: E402
                                   _igemm_predict_win)
from tools import _timing  # noqa: E402

# The ResNet-50 cost-table shapes (b128 NHWC):
# raw 7x7-s2 stem, the s2d 4x4 stem, s0's 3x3 and s1's 3x3. These are the
# shapes the acceptance equivalence test replays.
RN50_CONV_SHAPES = [
    ("stem_7x7_s2_3ch", 128, 224, 224, 3, 64, 7, 7, (2, 2),
     [(3, 3), (3, 3)], (1, 1)),
    ("stem_s2d_4x4_12ch", 128, 112, 112, 12, 64, 4, 4, (1, 1),
     [(2, 1), (2, 1)], (1, 1)),
    ("s0_3x3_64ch", 128, 56, 56, 64, 64, 3, 3, (1, 1),
     [(1, 1), (1, 1)], (1, 1)),
    ("s1_3x3_128ch", 128, 28, 28, 128, 128, 3, 3, (1, 1),
     [(1, 1), (1, 1)], (1, 1)),
]

# BERT-base's two attention regimes: the headline s128 and the s512
# kernel-proof row (a v5e run of 2026-07, on code older than PRs 1-20, had
# XLA ahead at s128 and the Pallas kernel ~9% ahead at s512)
ATTENTION_SHAPES = [
    ("bert_s128", 128, 12, 128, 64, False),
    ("bert_s512", 64, 12, 512, 64, False),
]

# serving decode regimes (sq=1, long ragged sk): the shapes the serving
# engine's paged-attention lever keys on — (batch-bucket, heads,
# padded-slot-count, head_dim). The ragged kv_lens inside each arm span
# 1/4..full so the sweep times realistic occupancy, not the dense corner.
DECODE_ATTENTION_SHAPES = [
    ("decode_b8_kv1024", 8, 12, 1024, 64),
    ("decode_b32_kv512", 32, 12, 512, 64),
    ("decode_b64_kv2048", 64, 12, 2048, 64),
]

# TP-sharded serving decode (ISSUE 11): under GSPMD each tp shard executes
# nh/tp heads of the same decode shape, and paged_attention_backend keys the
# DB on that PER-SHARD shape. The per-shard shapes are recorded as
# `candidate` entries so `--what candidates` measures and upgrades them
# exactly like the PR 7 decode regimes — TP decode resolves through the DB
# like every other lever.
SERVING_TP_DEGREES = (2, 4)


# the epilogue lever's sweep set (ISSUE 9): the BN apply tail of the
# ResNet-50 conv OUTPUT shapes — (name, batch, channels,
# spatial) — expanded over layout x residual below; plus the BERT-base
# s128 LN rows. These are the shapes the ResNet-50 and BERT-base trainers
# dispatch.
EPILOGUE_BN_SHAPES = [
    ("stem_7x7_out", 128, 64, 112 * 112),
    ("s0_3x3_out", 128, 64, 56 * 56),
    ("s1_3x3_out", 128, 128, 28 * 28),
]

EPILOGUE_LN_SHAPES = [
    ("bert_s128_ln", 128 * 128, 768),
]


# the embedding sweep's table geometries (name, vocab, dim, ids_per_batch):
# a CTR-scale narrow table, a wide ranker table, and a mid shape — the three
# regimes the slots-vs-hit-rate trade actually differs across. ids_per_batch
# is the per-step lookup volume (batch x fields).
EMBEDDING_GEOMETRIES = [
    ("ctr_v200k_d16", 200_000, 16, 2048),
    ("ctr_v50k_d32", 50_000, 32, 1024),
    ("ranker_v100k_d64", 100_000, 64, 512),
]


def _out_hw(h, w, kh, kw, strides, pads, d):
    hout = (h + sum(pads[0]) - ((kh - 1) * d[0] + 1)) // strides[0] + 1
    wout = (w + sum(pads[1]) - ((kw - 1) * d[1] + 1)) // strides[1] + 1
    return hout, wout


def _measure_arms(arms: dict, iters: int, passes: int) -> dict:
    """Time every runnable arm with the shared protocol; returns
    {name: measure-dict}. Arm values are zero-arg callables returning a
    device array (the drain target)."""
    out = {}
    for name, fn in arms.items():
        holder = {}

        def run_once(fn=fn, holder=holder):
            holder["v"] = fn()

        m = _timing.measure(run_once, lambda: holder["v"], iters, passes)
        out[name] = m
        print(json.dumps({"arm": name, **m}), flush=True)
    return out


def _verdict_vs_base(measured: dict, base: str, band: float):
    """Pick the winner against the conservative base arm: the fastest
    candidate that beats base's median by more than max(band, its own
    measured spread); inside the band -> tie (analytic keeps the call)."""
    base_med = measured[base]["median_s"]
    best, best_med = base, base_med
    for name, m in measured.items():
        if name != base and m["median_s"] < best_med:
            best, best_med = name, m["median_s"]
    if best == base:
        return base, "retire"
    eff_band = max(band, measured[best]["band"], measured[base]["band"])
    v = _timing.ab_verdict(base_med, best_med, eff_band)
    return (best, "keep") if v == "keep" else (base, v)


def sweep_conv(db, shapes, dtype: str, iters: int, passes: int, band: float,
               fmt: str = "NHWC"):
    key_dtype = str(jnp.dtype(dtype))
    rhs = "HWIO" if fmt == "NHWC" else "OIHW"
    for row in shapes:
        name, n, h, w, cin, cout, kh, kw, strides, pads, d = row
        hout, wout = _out_hw(h, w, kh, kw, strides, pads, d)
        rng = np.random.default_rng(0)
        x_shape = (n, h, w, cin) if fmt == "NHWC" else (n, cin, h, w)
        w_shape = (kh, kw, cin, cout) if fmt == "NHWC" \
            else (cout, cin, kh, kw)
        x = jax.device_put(rng.standard_normal(
            x_shape, dtype=np.float32).astype(dtype))
        wt = jax.device_put((rng.standard_normal(
            w_shape, dtype=np.float32) * 0.05).astype(dtype))

        def loss_direct(xx, ww):
            out = jax.lax.conv_general_dilated(
                xx, ww, window_strides=strides, padding=pads,
                rhs_dilation=d, dimension_numbers=(fmt, rhs, fmt))
            return jnp.sum(jnp.square(out.astype(jnp.float32)))

        def loss_igemm(xx, ww):
            acc = _conv2d_igemm_f32(xx, ww, strides, pads, d, fmt)
            return jnp.sum(jnp.square(acc))

        f_direct = jax.jit(jax.grad(loss_direct, argnums=(0, 1)))
        f_igemm = jax.jit(jax.grad(loss_igemm, argnums=(0, 1)))
        print(json.dumps({"sweep": "conv", "shape": name,
                          "dims": f"{n}x{h}x{w}x{cin}->{cout} "
                                  f"k{kh}x{kw}"}), flush=True)
        measured = _measure_arms(
            {"direct": lambda: f_direct(x, wt)[1],
             "igemm": lambda: f_igemm(x, wt)[1]}, iters, passes)
        winner, verdict = _verdict_vs_base(measured, "direct", band)
        analytic = "igemm" if _igemm_predict_win(
            n, hout, wout, cin, cout, kh, kw,
            jnp.dtype(dtype).itemsize) else "direct"
        lowering = winner if verdict in ("keep", "retire") else analytic
        if verdict == "tie":
            lowering = analytic
        key = tuning.canonical_key(
            "conv2d", tuning.conv_key(n, hout, wout, cin, cout, kh, kw,
                                      strides, d, fmt),
            key_dtype, tuning.device_kind())
        db.put(key, {"lowering": lowering}, source="swept",
               measured=tuning.evidence(measured),
               note=f"{name}: verdict={verdict} analytic={analytic}")
        print(json.dumps({"shape": name, "decision": lowering,
                          "verdict": verdict, "analytic": analytic}),
              flush=True)


def sweep_attention(db, shapes, dtype: str, iters: int, passes: int,
                    band: float):
    from paddle_tpu.ops.attention_ops import (_flash_bundled_ok,
                                              _pallas_short128_ok,
                                              _pallas_short_ok,
                                              _reference_attention)

    key_dtype = str(jnp.dtype(dtype))
    for name, b, nh, s, dh, causal in shapes:
        rng = np.random.default_rng(0)
        q, k, v = (jax.device_put(rng.standard_normal(
            (b, nh, s, dh), dtype=np.float32).astype(dtype))
            for _ in range(3))
        sm = dh ** -0.5

        def mk(attn_fn):
            def loss(qq, kk, vv):
                return jnp.sum(jnp.square(
                    attn_fn(qq, kk, vv).astype(jnp.float32)))
            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            return lambda: g(q, k, v)[0]

        arms = {"xla": mk(lambda qq, kk, vv: _reference_attention(
            qq, kk, vv, None, causal, sm))}
        if _pallas_short_ok(q.shape, k.shape, None):
            from paddle_tpu.ops.pallas_kernels import attention as psa

            arms["pallas_short"] = mk(lambda qq, kk, vv:
                                      psa.short_seq_attention(
                                          qq, kk, vv, causal=causal,
                                          sm_scale=sm))
        if _pallas_short128_ok(q.shape, k.shape, None):
            from paddle_tpu.ops.pallas_kernels import short_attention as s128

            arms["pallas_short128"] = mk(lambda qq, kk, vv:
                                         s128.short128_attention(
                                             qq, kk, vv, causal=causal,
                                             sm_scale=sm))
        if _flash_bundled_ok(q.shape, k.shape, q.dtype):
            from jax.experimental.pallas.ops.tpu import flash_attention as fa

            arms["flash_bundled"] = mk(lambda qq, kk, vv: fa.flash_attention(
                qq, kk, vv, causal=causal, sm_scale=sm))
        print(json.dumps({"sweep": "attention", "shape": name,
                          "arms": sorted(arms)}), flush=True)
        if len(arms) < 2:
            print(json.dumps({"shape": name, "skipped":
                              "only the XLA arm runs on this platform"}),
                  flush=True)
            continue
        measured = _measure_arms(arms, iters, passes)
        backend, verdict = _verdict_vs_base(measured, "xla", band)
        key = tuning.canonical_key(
            "attention", tuning.attention_key(b, nh, s, s, dh, causal),
            key_dtype, tuning.device_kind())
        db.put(key, {"backend": backend}, source="swept",
               measured=tuning.evidence(measured),
               note=f"{name}: verdict={verdict}")
        print(json.dumps({"shape": name, "decision": backend,
                          "verdict": verdict}), flush=True)


def record_tp_decode_candidates(db, shapes, dtype: str,
                                tp_degrees=SERVING_TP_DEGREES) -> int:
    """Record the head-sharded decode shapes (nh/tp per shard) as
    `candidate` DB entries. Candidates never clobber swept verdicts and
    never count as hits (the PR 6 contract); `sweep_candidates` routes the
    sq=1 family through `sweep_decode_attention` and upgrades them to
    swept verdicts — after which a TP serving engine's per-shard dispatch
    is a DB hit like any other lever's."""
    from paddle_tpu import flags as pt_flags

    key_dtype = str(jnp.dtype(dtype))
    ps = int(pt_flags.get_flag("serving_page_size"))
    added = 0
    for _, b, nh, kv, dh in shapes:
        kv = max(ps, (kv // ps) * ps)
        for tp in tp_degrees:
            if nh % tp or nh // tp < 1:
                continue
            key = tuning.canonical_key(
                "attention", tuning.attention_key(b, nh // tp, 1, kv, dh,
                                                  True),
                key_dtype, tuning.device_kind())
            if db.lookup(key) is not None:
                continue
            db.put(key, {"backend": "xla"}, source="candidate")
            added += 1
    print(json.dumps({"sweep": "tp_decode_candidates", "recorded": added,
                      "tp_degrees": list(tp_degrees)}), flush=True)
    return added


def sweep_decode_attention(db, shapes, dtype: str, iters: int, passes: int,
                           band: float):
    """The serving lever's sweep: XLA gather-based paged attention vs the
    Pallas page-DMA kernel per (batch, heads, kv_slots, head_dim) decode
    shape. Keys are attention_key(b, nh, 1, kv, dh, causal=1) — exactly
    what ops/attention_ops.paged_attention_backend consults, so a swept
    verdict here IS the serving engine's dispatch for that bucket."""
    from paddle_tpu import flags as pt_flags
    from paddle_tpu.ops.attention_ops import (_paged_attention_reference,
                                              _pallas_paged_ok)
    from paddle_tpu.serving.kv_cache import pool_shape

    key_dtype = str(jnp.dtype(dtype))
    ps = int(pt_flags.get_flag("serving_page_size"))
    for name, b, nh, kv, dh in shapes:
        kv = max(ps, (kv // ps) * ps)  # whole pages
        num_pages = b * (kv // ps) + 1
        rng = np.random.default_rng(0)
        kp, vp = (jax.device_put(rng.standard_normal(
            pool_shape(num_pages, ps, nh, dh), dtype=np.float32)
            .astype(dtype)) for _ in range(2))
        q = jax.device_put(rng.standard_normal(
            (b, nh, dh), dtype=np.float32).astype(dtype))
        pt_ = jax.device_put(rng.permutation(num_pages - 1)[:b * (kv // ps)]
                             .reshape(b, kv // ps).astype(np.int32))
        kv_lens = jax.device_put(
            rng.integers(max(1, kv // 4), kv + 1, b).astype(np.int32))
        sm = dh ** -0.5

        arms = {"xla": lambda: jax.jit(_paged_attention_reference)(
            q, kp, vp, pt_, kv_lens, sm)}
        if _pallas_paged_ok(q.shape, kp.shape):
            from paddle_tpu.ops.pallas_kernels import paged_attention as ppa

            arms["pallas_paged"] = lambda: ppa.paged_decode_attention(
                q, kp, vp, pt_, kv_lens, sm_scale=sm)
        print(json.dumps({"sweep": "decode_attention", "shape": name,
                          "arms": sorted(arms)}), flush=True)
        if len(arms) < 2:
            print(json.dumps({"shape": name, "skipped":
                              "only the XLA arm runs on this platform"}),
                  flush=True)
            continue
        measured = _measure_arms(arms, iters, passes)
        backend, verdict = _verdict_vs_base(measured, "xla", band)
        key = tuning.canonical_key(
            "attention", tuning.attention_key(b, nh, 1, kv, dh, True),
            key_dtype, tuning.device_kind())
        db.put(key, {"backend": backend}, source="swept",
               measured=tuning.evidence(measured),
               note=f"{name}: verdict={verdict}")
        print(json.dumps({"shape": name, "decision": backend,
                          "verdict": verdict}), flush=True)


def sweep_epilogue(db, bn_shapes, ln_shapes, dtype: str, iters: int,
                   passes: int, band: float):
    """The fused-epilogue lever's sweep (ISSUE 9): XLA composition vs the
    Pallas apply kernel per canonical (rows, channels, layout, act,
    residual) problem — fwd+bwd jitted, one arm-set per BN shape over
    (NHWC no-res, NHWC res, NCHW res) plus the LN rows. Keys are exactly
    what ops/nn_ops._epilogue_backend consults, so a swept keep here IS
    the dispatch for that shape. Shapes whose Pallas arm cannot run on
    this platform are skipped, not recorded — absence of a verdict keeps
    the analytic XLA prior, which is already the off state."""
    jobs = []
    for name, n, c, hw in bn_shapes:
        jobs.append((f"{name}_nhwc", "bn", (n * hw, c), "last", "relu",
                     False))
        jobs.append((f"{name}_nhwc_res", "bn", (n * hw, c), "last", "relu",
                     True))
        jobs.append((f"{name}_nchw_res", "bn", (n, c, hw), "row", "relu",
                     True))
    for name, rows, k in ln_shapes:
        jobs.append((name, "ln", (rows, k), "last", "identity", False))
    _sweep_epilogue_jobs(db, jobs, dtype, iters, passes, band)


def _sweep_epilogue_jobs(db, jobs, dtype: str, iters: int, passes: int,
                         band: float):
    from paddle_tpu.ops.pallas_kernels import epilogue as ep
    from paddle_tpu.ops.pallas_kernels import workbench
    from paddle_tpu import tuning as _t

    key_dtype = str(jnp.dtype(dtype))
    for name, kind, shape, cpos, act, has_res in jobs:
        rng = np.random.default_rng(0)
        cl = cpos == "last"
        C = shape[-1] if cl else shape[1]
        rows = int(np.prod(shape)) // C
        x = jax.device_put(rng.standard_normal(
            shape, dtype=np.float32).astype(dtype))
        res = jax.device_put(rng.standard_normal(
            shape, dtype=np.float32).astype(dtype)) if has_res else None
        s, b = (jax.device_put(rng.standard_normal(C).astype(np.float32))
                for _ in range(2))
        m = jax.device_put(rng.standard_normal(C).astype(np.float32))
        v = jax.device_put((np.abs(rng.standard_normal(C)) + 0.5)
                           .astype(np.float32))

        def mk(fn, wants_res):
            if wants_res:
                def loss(xx, rr):
                    return jnp.sum(jnp.square(fn(xx, rr)
                                              .astype(jnp.float32)))
                g = jax.jit(jax.grad(loss, argnums=(0, 1)))
                return lambda: g(x, res)[0]

            def loss(xx):
                return jnp.sum(jnp.square(fn(xx).astype(jnp.float32)))
            g = jax.jit(jax.grad(loss))
            return lambda: g(x)

        if kind == "bn":
            arms = {"xla": mk(lambda xx, rr=None: ep.bn_apply_act_reference(
                xx, s, b, m, v, act=act, residual=rr, channel_last=cl),
                has_res)}
            if workbench.runnable(ep) and ep.epilogue_supported(
                    shape, jnp.dtype(dtype), cl, act):
                arms["pallas"] = mk(
                    lambda xx, rr=None: ep.bn_apply_act(
                        xx, s, b, m, v, act=act, residual=rr,
                        channel_last=cl), has_res)
        else:
            arms = {"xla": mk(lambda xx: ep.layer_norm_act_reference(
                xx, s, b, act=act), False)}
            if workbench.runnable(ep) and ep.epilogue_supported(
                    shape, jnp.dtype(dtype), True, act):
                arms["pallas"] = mk(lambda xx: ep.layer_norm_act(
                    xx, s, b, act=act), False)
        print(json.dumps({"sweep": "epilogue", "shape": name,
                          "arms": sorted(arms)}), flush=True)
        if len(arms) < 2:
            print(json.dumps({"shape": name, "skipped":
                              "only the XLA arm runs on this platform"}),
                  flush=True)
            continue
        measured = _measure_arms(arms, iters, passes)
        backend, verdict = _verdict_vs_base(measured, "xla", band)
        key = _t.canonical_key(
            "epilogue", _t.epilogue_key(kind, rows, C, cpos, act, has_res),
            key_dtype, _t.device_kind())
        db.put(key, {"backend": backend}, source="swept",
               measured=tuning.evidence(measured),
               note=f"{name}: verdict={verdict}")
        print(json.dumps({"shape": name, "decision": backend,
                          "verdict": verdict}), flush=True)


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _emb_arm_ex_s(vocab: int, dim: int, ids_per_batch: int, slots: int,
                  prefetch: int, steps_per_window: int, passes: int):
    """Time one cache-geometry arm end-to-end: a fresh one-table program
    (sum-pooled embedding -> sigmoid loss -> SGD) trained over a seeded
    zipf id stream through the REAL tiered stack — minimize()-time rewrite,
    host-side resolve, install/gather/scatter step. Returns (measure dict
    with per-step seconds, stats dict). Resolution runs inline (sync) so
    the measured cost includes the host work the geometry must amortize."""
    import paddle_tpu as pt
    from paddle_tpu import flags as ptf
    from paddle_tpu import layers as L
    from paddle_tpu.layers import tensor as T
    from paddle_tpu.param_attr import ParamAttr

    batch = max(1, min(128, ids_per_batch))
    fields = max(1, ids_per_batch // batch)
    rng = np.random.default_rng(7)
    feeds = []
    for _ in range(8):
        ids = (rng.zipf(1.5, (batch, fields)) - 1) % vocab
        feeds.append({
            "ids": ids.astype(np.int32),
            "label": rng.integers(0, 2, (batch, 1)).astype(np.float32)})

    saved = {k: ptf.get_flag(k) for k in (
        "emb_hbm_budget_mb", "emb_cache_slots", "emb_prefetch_rows")}
    ptf.set_flags({"emb_hbm_budget_mb": 1e-6, "emb_cache_slots": int(slots),
                   "emb_prefetch_rows": int(prefetch)})
    try:
        main, startup = pt.Program(), pt.Program()
        main.random_seed = startup.random_seed = 7
        with pt.program_guard(main, startup), pt.unique_name.guard():
            ids_v = T.data(name="ids", shape=[fields], dtype="int64")
            label = T.data(name="label", shape=[1], dtype="float32")
            emb = L.embedding(ids_v, size=[vocab, dim],
                              param_attr=ParamAttr(name="sweep_tbl"))
            pooled = L.reduce_sum(emb, dim=1)
            logit = L.fc(pooled, size=1)
            loss = L.mean(
                L.sigmoid_cross_entropy_with_logits(logit, label))
            pt.optimizer.SGD(0.1).minimize(loss)
        eng = main._tiered_engine
        assert eng is not None and "sweep_tbl" in eng.tables, \
            "sweep arm did not tier — budget/geometry wiring broke"
        exe = pt.Executor()
        step = [0]
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            cache_name = eng.tables["sweep_tbl"].cache_var

            def run_once():
                exe.run_async(main, feed=feeds[step[0] % len(feeds)])
                step[0] += 1

            def drain():
                exe.wait()
                return pt.global_scope().find_var(cache_name)

            m = _timing.measure(run_once, drain, steps_per_window, passes)
            eng.flush_all()
            stats = eng.stats("sweep_tbl")
        return m, stats
    finally:
        ptf.set_flags(saved)


def sweep_embedding(db, geometries, dtype: str, iters: int, passes: int,
                    band: float, table_names: dict | None = None):
    """Cache-geometry sweep (ISSUE 10): per table geometry, slot-count arms
    around the working set (the budget-derived prior is the base) then
    prefetch-width arms on the winning slot count. The swept verdict is the
    decision the minimize()-time rewrite consults for that table key;
    ties keep the analytic call per the r5 rule. `table_names` maps a
    geometry name to the REAL table name to record under (candidate
    upgrades); default records under the geometry name."""
    from paddle_tpu import tuning as _t

    key_dtype = str(jnp.dtype(dtype))
    for name, vocab, dim, ids_per_batch in geometries:
        # working-set estimate: unique ids of one zipf batch
        rng = np.random.default_rng(7)
        uniq = len(np.unique((rng.zipf(1.5, ids_per_batch) - 1) % vocab))
        base_slots = min(_pow2(max(4 * uniq, 2)), max(2, vocab))
        arm_slots = sorted({min(_pow2(max(2 * uniq, 2)), max(2, vocab)),
                            base_slots,
                            min(_pow2(max(8 * uniq, 2)), max(2, vocab))})
        print(json.dumps({"sweep": "embedding", "shape": name,
                          "uniq_per_batch": uniq,
                          "arms": [f"slots{s}" for s in arm_slots]}),
              flush=True)
        measured, stats_by = {}, {}
        for s in arm_slots:
            m, st = _emb_arm_ex_s(vocab, dim, ids_per_batch, s, 0,
                                  iters, passes)
            m["hit_rate"] = st.get("hit_rate")
            measured[f"slots{s}"] = m
            print(json.dumps({"arm": f"slots{s}", **m}), flush=True)
            stats_by[f"slots{s}"] = st
        winner, verdict = _verdict_vs_base(measured, f"slots{base_slots}",
                                           band)
        best_slots = int(winner[len("slots"):])
        # prefetch-width mini-sweep on the winning slot count: auto (pow2 of
        # the first batch's miss count) vs double that, which trades padded
        # transfer bytes against overflow recompiles
        auto_pf = int(stats_by[winner].get("prefetch_rows") or 0)
        pf_measured = {f"pf{auto_pf}": measured[winner]}
        best_pf = auto_pf
        if auto_pf:
            m2, _ = _emb_arm_ex_s(vocab, dim, ids_per_batch, best_slots,
                                  2 * auto_pf, iters, passes)
            pf_measured[f"pf{2 * auto_pf}"] = m2
            print(json.dumps({"arm": f"pf{2 * auto_pf}", **m2}), flush=True)
            pw, pv = _verdict_vs_base(pf_measured, f"pf{auto_pf}", band)
            if pv == "keep":
                best_pf = int(pw[len("pf"):])
        table = (table_names or {}).get(name, name)
        key = _t.canonical_key(
            "embedding", _t.embedding_key(table, vocab, dim), key_dtype,
            _t.device_kind())
        decision = {"slots": best_slots, "prefetch_rows": best_pf}
        db.put(key, decision, source="swept",
               measured={a: {"median_s": m["median_s"], "band": m["band"],
                             "hit_rate": m.get("hit_rate")}
                         for a, m in {**measured, **pf_measured}.items()},
               note=f"{name}: verdict={verdict} base=slots{base_slots}")
        print(json.dumps({"shape": name, "decision": decision,
                          "verdict": verdict}), flush=True)


_EMB_KEY_RE = re.compile(
    r"^embedding\|table=(\S+) vocab=(\d+) dim=(\d+)\|([\w.]+)\|")


_CONV_KEY_RE = re.compile(
    r"^conv2d\|n=(\d+) out=(\d+)x(\d+) cin=(\d+) cout=(\d+) k=(\d+)x(\d+) "
    r"s=(\d+)x(\d+) d=(\d+)x(\d+) (NHWC|NCHW)\|([\w.]+)\|")


_ATTN_KEY_RE = re.compile(
    r"^attention\|b=(\d+) nh=(\d+) sq=(\d+) sk=(\d+) dh=(\d+) "
    r"causal=(\d)\|([\w.]+)\|")


_EPI_KEY_RE = re.compile(
    r"^epilogue\|kind=(\w+) rows=(\d+) c=(\d+) ch=(last|row) act=(\w+) "
    r"res=(\d)\|([\w.]+)\|")


def sweep_candidates(db, iters, passes, band):
    """Upgrade `candidate` entries (recorded by a FLAGS_tuning_mode=sweep
    run) to measured verdicts — conv2d lowerings AND attention backends.
    Attention candidates route by shape: sq=1 keys are serving decode
    dispatches (ragged paged attention), sq==sk keys are the encoder
    self-attention regimes; anything else is skipped (no harness measures
    it honestly). Conv input extents are reconstructed pad-free from the
    output tile — the GEMM dims (M, folded K) that drive the decision are
    identical either way."""
    attn_groups: dict[str, list] = {}
    decode_groups: dict[str, list] = {}
    epi_groups: dict[str, tuple[list, list]] = {}
    emb_groups: dict[str, tuple[list, dict]] = {}
    for ckey, entry in sorted(db.entries.items()):
        if entry.get("source") != "candidate":
            continue
        gm = _EMB_KEY_RE.match(ckey)
        if gm:
            table, vocab, dim = gm.group(1), int(gm.group(2)), \
                int(gm.group(3))
            dt = gm.group(4)
            geoms, names = emb_groups.setdefault(dt, ([], {}))
            # probe the geometry with a representative per-batch lookup
            # volume — the runtime candidate records table identity + shape,
            # not the workload's batch, so the sweep supplies the load
            gname = f"candidate_{table}"
            geoms.append((gname, vocab, dim, min(2048, max(64, vocab // 8))))
            names[gname] = table
            continue
        am = _ATTN_KEY_RE.match(ckey)
        if am:
            b, nh, sq, sk, dh_, causal = map(int, am.groups()[:6])
            dt = am.group(7)
            if sq == 1:
                decode_groups.setdefault(dt, []).append(
                    (f"candidate_b{b}_kv{sk}", b, nh, sk, dh_))
            elif sq == sk:
                attn_groups.setdefault(dt, []).append(
                    (f"candidate_b{b}_s{sq}", b, nh, sq, dh_, bool(causal)))
            continue
        em = _EPI_KEY_RE.match(ckey)
        if em:
            kind, rows, c = em.group(1), int(em.group(2)), int(em.group(3))
            cpos, act, has_res = em.group(4), em.group(5), int(em.group(6))
            dt = em.group(7)
            bn_s, ln_s = epi_groups.setdefault(dt, ([], []))
            # sweep_epilogue regenerates the (layout, residual) expansion
            # from a compact shape row, so reconstruct one matching row:
            # channels-last rows collapse to (n=1, c, hw=rows); channels-row
            # keys carry rows = n (per-image spatial folded into hw)
            if kind == "ln":
                ln_s.append((f"candidate_ln_{rows}x{c}", rows, c))
            else:
                bn_s.append((f"candidate_bn_{rows}x{c}", kind, rows, c,
                             cpos, act, bool(has_res)))
            continue
    for dt, (geoms, names) in sorted(emb_groups.items()):
        sweep_embedding(db, geoms, dt, iters, passes, band,
                        table_names=names)
    for dt, shapes in sorted(attn_groups.items()):
        sweep_attention(db, shapes, dt, iters, passes, band)
    for dt, shapes in sorted(decode_groups.items()):
        sweep_decode_attention(db, shapes, dt, iters, passes, band)
    for dt, (bn_s, ln_s) in sorted(epi_groups.items()):
        # channels-row keys fold the (N, HW) split into rows = N*HW; the
        # re-measured tensor uses N=1 — total elements (what the apply cost
        # scales with) are preserved, only the param-tiling split differs
        jobs = [(nm, kind, ((rows, c) if cpos == "last" else (1, c, rows)),
                 cpos, act, has_res)
                for nm, kind, rows, c, cpos, act, has_res in bn_s]
        jobs += [(nm, "ln", (rows, c), "last", "identity", False)
                 for nm, rows, c in ln_s]
        _sweep_epilogue_jobs(db, jobs, dt, iters, passes, band)

    rows = []
    for ckey, entry in sorted(db.entries.items()):
        if entry.get("source") != "candidate":
            continue
        m = _CONV_KEY_RE.match(ckey)
        if not m:
            continue
        (n, hout, wout, cin, cout, kh, kw, sh, sw, dh_, dw_) = \
            map(int, m.groups()[:11])
        fmt, dt = m.group(12), m.group(13)
        h = (hout - 1) * sh + (kh - 1) * dh_ + 1
        w = (wout - 1) * sw + (kw - 1) * dw_ + 1
        rows.append(((dt, fmt),
                     (f"candidate_{cin}ch_{kh}x{kw}", n, h, w, cin, cout,
                      kh, kw, (sh, sw), [(0, 0), (0, 0)], (dh_, dw_))))
    if not rows:
        print(json.dumps({"sweep": "candidates", "note": "none found"}),
              flush=True)
        return
    grouped: dict[tuple, list] = {}
    for gk, row in rows:
        grouped.setdefault(gk, []).append(row)
    for (dt, fmt), shapes in sorted(grouped.items()):
        sweep_conv(db, shapes, dt, iters, passes, band, fmt=fmt)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--db", default=os.environ.get("FLAGS_tuning_db",
                                                   "TUNING_DB.json"))
    ap.add_argument("--what", default="conv,attention,epilogue",
                    help="comma list: conv, attention, epilogue, embedding, "
                         "candidates")
    on_tpu = jax.devices()[0].platform == "tpu"
    ap.add_argument("--iters", type=int, default=20 if on_tpu else 3)
    ap.add_argument("--passes", type=int, default=3 if on_tpu else 2)
    ap.add_argument("--band", type=float, default=_timing.DEFAULT_BAND)
    ap.add_argument("--dtype", default="bfloat16" if on_tpu else "float32")
    ap.add_argument("--small", action="store_true",
                    help="shrink the default shape set (batch 8, CPU smoke)")
    args = ap.parse_args()

    conv_shapes = RN50_CONV_SHAPES
    attn_shapes = ATTENTION_SHAPES
    decode_shapes = DECODE_ATTENTION_SHAPES
    epi_bn_shapes = EPILOGUE_BN_SHAPES
    epi_ln_shapes = EPILOGUE_LN_SHAPES
    emb_geometries = EMBEDDING_GEOMETRIES
    if args.small or not on_tpu:
        emb_geometries = [(nm, v // 8, d, max(64, b // 8))
                          for nm, v, d, b in EMBEDDING_GEOMETRIES]
    if args.small or not on_tpu:
        conv_shapes = [(nm, 8, h // 4, w // 4, ci, co, kh, kw, st, pd, d)
                       for nm, _, h, w, ci, co, kh, kw, st, pd, d
                       in RN50_CONV_SHAPES]
        attn_shapes = [(nm, 2, nh, s, dh, c)
                       for nm, _, nh, s, dh, c in ATTENTION_SHAPES]
        decode_shapes = [(nm, 2, nh, kv // 4, dh)
                         for nm, _, nh, kv, dh in DECODE_ATTENTION_SHAPES]
        epi_bn_shapes = [(nm, 2, c, hw // 16)
                         for nm, _, c, hw in EPILOGUE_BN_SHAPES]
        epi_ln_shapes = [(nm, rows // 64, k)
                         for nm, rows, k in EPILOGUE_LN_SHAPES]

    db = tuning.TuningDB(args.db)
    what = {w.strip() for w in args.what.split(",") if w.strip()}
    if "conv" in what:
        sweep_conv(db, conv_shapes, args.dtype, args.iters, args.passes,
                   args.band)
    if "attention" in what:
        sweep_attention(db, attn_shapes, args.dtype, args.iters,
                        args.passes, args.band)
        # the serving lever's decode regimes ride the attention sweep: same
        # op kind, same DB namespace, different (sq=1) shape family
        sweep_decode_attention(db, decode_shapes, args.dtype, args.iters,
                               args.passes, args.band)
        # TP-sharded serving (ISSUE 11): per-shard (nh/tp) decode shapes
        # land as candidates for `--what candidates` to measure
        record_tp_decode_candidates(db, decode_shapes, args.dtype)
    if "epilogue" in what:
        sweep_epilogue(db, epi_bn_shapes, epi_ln_shapes, args.dtype,
                       args.iters, args.passes, args.band)
    if "embedding" in what:
        sweep_embedding(db, emb_geometries, args.dtype, args.iters,
                        args.passes, args.band)
    if "candidates" in what:
        sweep_candidates(db, args.iters, args.passes, args.band)
    db.save(args.db)
    print(json.dumps({"db": os.path.abspath(args.db),
                      "entries": len(db)}), flush=True)


if __name__ == "__main__":
    main()
