"""Benchmark: training-step throughput on one chip, all BASELINE workloads.

Runs on a TPU only: every number it prints is a device metric, so `main()`
exits non-zero when jax finds no TPU, and `_peak_flops` raises on a
device_kind it has no published peak for. Correctness at small sizes is
what tests/ is for.

`--multichip` instead runs the measured multichip scaling campaign
(tools/_mc_ab.py: per-axis dp/tp/pp/sp tokens/s + scaling efficiency with
collective-overlap A/B arms) over the chips present — or, on a host with
none, over a virtual 8-device CPU mesh, in which case its artifact says
`platform: cpu` and carries counts and parity, not speed; see
bench_multichip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
vs_baseline = MIN over every measured workload's vs_target (BERT / RN50 /
WMT MFU each against the 0.45 north star, DeepFM examples/s against the
declared 60k ex/s floor) — the aggregate moves only when the WORST workload
moves, so no single good number can mask a miss (VERDICT r3 #4). Per-workload
vs_target values ride in the same line. See PERF.md for the measured roofline
and why each config is shaped the way it is.

Model FLOPs use the standard 6*N*T transformer estimate (N = matmul-
participating params, embeddings excluded) plus attention terms; ResNet-50
uses 3x the 8.18 GF forward (2 ops/MAC — the canonical "4.089 GFLOPs" is
GMACs; see PERF.md r4). Peak chip FLOP/s from device kind.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import numpy as np

# ResNet-50 @224 forward FLOPs per image at 2 ops/MAC (the canonical
# 4.089e9 figure counts multiply-add as one op). Single source of truth —
# the RN50 tools import this (PERF.md r4 'Finding 0').
RN50_FWD_FLOPS_PER_IMG = 2 * 4.089e9


def _timed_windows(run_once, drain, iters: int, passes: int) -> list:
    """The ONE timing protocol for every bench row: `passes` windows of
    `iters` async-dispatched steps each, ended by a host drain read; the
    per-step seconds of every window are returned so the artifact records
    interference spread and min(windows) is the steady-state estimate."""
    windows = []
    for _ in range(passes):
        t0 = time.perf_counter()
        for _ in range(iters):
            run_once()
        np.asarray(drain())
        windows.append((time.perf_counter() - t0) / iters)
    return windows


# Published per-chip peaks, keyed by the exact `device_kind` jax reports.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip). A chip this repo
# has not run on is added here with its own source when it first does.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def _peak_flops(device) -> float:
    """Peak bf16 FLOP/s of `device`; an unknown device_kind raises — an MFU
    against a made-up peak is not a measurement."""
    try:
        return DEVICE_PEAKS[device.device_kind]["bf16_flops"]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {device.device_kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)}") from None


def _bert_step_time(cfg, batch, seq_len, iters):
    """Build + time a BERT pretrain step: the ONE timing protocol shared by
    the headline bench and the s512 kernel A/B. Asserts the final loss is
    finite — a fast wrong kernel must not win a bench row."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer

    from __graft_entry__ import _example_feed

    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        avg_loss, _ = transformer.bert_pretrain(cfg, seq_len=seq_len)
        opt = pt.contrib.mixed_precision.decorate(
            pt.optimizer.Adam(learning_rate=1e-4))  # bf16 matmuls on the MXU
        opt.minimize(avg_loss)
    feed = _example_feed(cfg, batch, seq_len)
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        # warmup/compile both signatures (with and without fetch)
        exe.run(main_p, feed=feed, fetch_list=[avg_loss])
        exe.run(main_p, feed=feed)
        v = pt.global_scope().find_var("lm_head.b")
        assert v is not None, "drain var lm_head.b missing"
        np.asarray(v)  # drain
        # steady state: async dispatch, drain once at the end — the real
        # trainer pattern (a per-step loss fetch would time the host<->device
        # round trip, not the chip). Two windows, both recorded; the faster
        # one is the steady-state estimate.
        windows = _timed_windows(
            lambda: exe.run(main_p, feed=feed),
            lambda: pt.global_scope().find_var("lm_head.b"), iters, 2)
        (loss,) = exe.run(main_p, feed=feed, fetch_list=[avg_loss])
        assert np.isfinite(float(np.asarray(loss)))
    return min(windows), windows


# BERT-base hyperparameters shared by the headline bench and its s512
# kernel-proof row — one source of truth so the two stay comparable
BERT_BASE = dict(vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, ffn_size=3072, max_position=512,
                 dropout=0.0, use_tp=False)


def bench_bert(peak: float):
    from paddle_tpu.models import transformer

    # headline config: seq 128, batch 128. The s512 regime is measured by
    # bench_bert_long.
    cfg = transformer.TransformerConfig(**BERT_BASE)
    batch, seq_len, iters = 128, 128, 50

    dt, windows = _bert_step_time(cfg, batch, seq_len, iters)
    tokens = batch * seq_len
    # matmul-participating parameter count: word/position embedding tables
    # are lookups, not matmuls, so they are EXCLUDED from the 6N term; the
    # lm_head projection (H*V) is a real matmul and stays.
    H, L_, F, V = cfg.hidden_size, cfg.num_layers, cfg.ffn_size, cfg.vocab_size
    n_params = L_ * (4 * H * H + 2 * H * F) + H * V
    step_flops = 6 * n_params * tokens + 12 * L_ * H * seq_len * tokens
    mfu = (step_flops / dt) / peak
    return tokens / dt, mfu, [round(tokens / w, 1) for w in windows]


def bench_bert_long():
    """BERT-base at seq 512 — the config class the custom short-seq Pallas
    attention kernel exists for (memory-bound attention: the [B,nh,S,S]
    score residuals dominate). Reports tokens/s with the kernel OFF (XLA
    attention) and ON, so the kernel's end-to-end worth is re-measured
    every round."""
    from paddle_tpu.models import transformer

    seq, batch, iters = 512, 64, 50
    out = {}
    for flash in (False, True):
        cfg = transformer.TransformerConfig(use_flash_attention=flash,
                                            **BERT_BASE)
        dt, _ = _bert_step_time(cfg, batch, seq, iters)
        out["pallas" if flash else "xla"] = batch * seq / dt
    return out


def bench_bert_short():
    """BERT at the HEADLINE short sequence — the regime where the bundled
    flash kernel measured 42-52% SLOWER than XLA (PERF.md r4/r5) and the
    ISSUE 9 seq<=128 kernel (pallas_kernels/short_attention.py) now fields
    a custom arm. Interleaved end-to-end A/B on the bench step protocol:
    the same config timed with the attention dispatch forced to XLA and
    forced to pallas_short128 (FLAGS_attention_force_backend; a forced
    backend that cannot run raises at dispatch, so a finished arm ran its
    kernel). tools/gate.py fails an artifact whose kernel arm loses beyond
    the interference band."""
    from paddle_tpu import flags as pt_flags
    from paddle_tpu.models import transformer
    from tools import _timing

    cfg = transformer.TransformerConfig(**BERT_BASE)
    batch, seq, iters = 128, 128, 50

    out = {}
    saved = pt_flags.get_flag("attention_force_backend")
    try:
        # interleaved passes (ABAB): sequential per-arm measurement aliases
        # drift into the margin
        tok = {}
        for rep in range(2):
            for arm in ("xla", "pallas_short128"):
                pt_flags.set_flags({"attention_force_backend": arm})
                dt, _ = _bert_step_time(cfg, batch, seq, iters)
                tok.setdefault(arm, []).append(batch * seq / dt)
        out["xla_tok_s"] = round(max(tok["xla"]), 1)
        out["pallas_tok_s"] = round(max(tok["pallas_short128"]), 1)
        out["windows_tok_s"] = {a: [round(v, 1) for v in vs]
                                for a, vs in tok.items()}
    finally:
        pt_flags.set_flags({"attention_force_backend": saved})
    # a forced arm that could not run its kernel would have raised
    out["engaged"] = True
    band = max(_timing.DEFAULT_BAND,
               _timing.interference_band(tok["xla"]),
               _timing.interference_band(tok["pallas_short128"]))
    out["band"] = round(band, 4)
    out["verdict"] = _timing.ab_verdict(
        1.0 / max(tok["xla"]), 1.0 / max(tok["pallas_short128"]), band)
    out["config"] = f"base b{batch} s{seq} AMP Adam"
    return out


def bench_resnet(peak: float):
    """ResNet-50 row with an in-artifact lever A/B (PERF.md r6/r10): the
    step is timed three ways — conv levers OFF (direct conv + two-pass BN,
    the r5 configuration), ON (FLAGS_conv_implicit_gemm auto + fused
    one-pass BN statistics), and ON + the fused Pallas epilogue forced
    (FLAGS_pallas_epilogue=on: the ISSUE 9 normalize+affine+act+residual
    kernel carries every BN apply tail it can run) — and the headline takes
    the fastest arm, with all recorded so every round re-measures the
    levers end-to-end (the keep-it-honest protocol; chained microbenches
    are poisoned here, PERF.md r5). The epilogue arm also records its
    keep/retire verdict vs the levered arm on the tools/_timing.py band —
    tools/gate.py fails an artifact whose kernel arm loses beyond the
    band."""
    from paddle_tpu import flags as pt_flags
    from tools import _timing

    arms = {}
    saved = {k: pt_flags.get_flag(k)
             for k in ("conv_implicit_gemm", "bn_fuse_stats",
                       "pallas_epilogue")}
    try:
        for name, (igemm, fuse, epi) in (
                ("baseline", ("off", False, "off")),
                ("levered", ("auto", True, "off")),
                ("epilogue", ("auto", True, "on"))):
            pt_flags.set_flags({"conv_implicit_gemm": igemm,
                                "bn_fuse_stats": fuse,
                                "pallas_epilogue": epi})
            arms[name] = _resnet_arm(peak)
    finally:
        pt_flags.set_flags(saved)
    best = max(arms, key=lambda k: arms[k][0])
    img_s, mfu, windows = arms[best]
    ab = {f"{k}_img_s": round(v[0], 1) for k, v in arms.items()}
    ab["winner"] = best
    # the epilogue kernel's end-to-end verdict vs its own baseline (the
    # levered arm: identical levers, kernel off) — per-step seconds feed
    # the shared band protocol
    # interference_band is scale-invariant, so the recorded img/s windows
    # feed it directly
    band = max(_timing.DEFAULT_BAND,
               _timing.interference_band(arms["levered"][2]),
               _timing.interference_band(arms["epilogue"][2]))
    ab["epilogue_engaged"] = True  # bench runs on a TPU: the kernel can run
    ab["epilogue_band"] = round(band, 4)
    ab["epilogue_verdict"] = _timing.ab_verdict(
        1.0 / arms["levered"][0], 1.0 / arms["epilogue"][0], band)
    return img_s, mfu, windows, ab


def _resnet_arm(peak: float):
    import paddle_tpu as pt
    from paddle_tpu.models import resnet

    batch, iters, size = 128, 50, 224
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup), pt.unique_name.guard():
        from paddle_tpu import layers as L

        img = L.data(name="img", shape=[size, size, 3], dtype="float32")
        label = L.data(name="label", shape=[1], dtype="int64")
        # NHWC + s2d stem: channels-last end-to-end plus the exact
        # space-to-depth refactoring of the 7x7-s2 stem (see
        # models/resnet.py fold_stem_to_s2d) — PERF.md r5
        loss, acc, _ = resnet.resnet50(img, label, s2d_stem=True,
                                       data_format="NHWC")
        # AMP bf16 with batch_norm GRAY (not blacklisted): the BN kernel
        # keeps its statistics in fp32 internally, so bf16 in/out is safe and
        # halves the HBM traffic of the activation chain. Blacklisted-BN AMP
        # measured 2.7x SLOWER than fp32 (cast walls); gray-BN AMP measures
        # 1.7x FASTER (PERF.md round 3).
        opt = pt.contrib.mixed_precision.decorate(
            pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9))
        opt.minimize(loss)

    rng = np.random.default_rng(0)
    # device-resident feed: re-feeding 77MB of host images per step would
    # time the host link, not the chip (the input pipeline overlaps in a
    # real trainer)
    feed = {
        "img": jax.device_put(
            rng.standard_normal((batch, size, size, 3), dtype=np.float32)),
        "label": jax.device_put(
            rng.integers(0, 1000, (batch, 1)).astype(np.int32)),
    }
    # drain on a parameter the optimizer writes: its scope value after N
    # steps depends on all N, so one asarray synchronizes the whole run.
    # Derived from the program (a hardcoded name that misses find_var would
    # silently time dispatch only).
    drain = main_p.all_parameters()[-1].name
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        exe.run(main_p, feed=feed, fetch_list=[loss])
        exe.run(main_p, feed=feed)
        v = pt.global_scope().find_var(drain)
        assert v is not None, drain
        np.asarray(v)
        # 3 recorded windows: RN50 is the gate row, so its artifact
        # carries the same interference forensics as WMT/DeepFM
        windows = _timed_windows(
            lambda: exe.run(main_p, feed=feed),
            lambda: pt.global_scope().find_var(drain), iters, 3)
        dt = min(windows)
        (lv,) = exe.run(main_p, feed=feed, fetch_list=[loss])
        assert np.isfinite(float(np.asarray(lv)))
    img_s = batch / dt
    rn_windows = [round(batch / w, 1) for w in windows]
    # FLOP convention fix (r4): the canonical "4.089 GFLOPs" for RN50@224
    # counts a multiply-add as ONE op (it is 4.089 GMACs — exact per-layer
    # enumeration in tools/_rn_stagecost.py gives 8.17 GF/img at 2 ops/MAC).
    # The 197e12 chip peak and the transformer 6N formula both count 2 ops
    # per MAC, so the model FLOPs must too — r2/r3 reported RN50 MFU at
    # half its true value (PERF.md r4).
    mfu = (3 * RN50_FWD_FLOPS_PER_IMG * img_s) / peak  # train ~3x fwd
    return img_s, mfu, rn_windows


def bench_wmt(peak: float):
    """Transformer-base WMT en-de (BASELINE config 3): tokens/s counts
    src+tgt tokens per sentence pair; MFU from explicit encoder/decoder/proj
    matmul FLOPs (embedd lookups excluded) + attention terms."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=37000, hidden_size=512, num_layers=6, num_heads=8,
        ffn_size=2048, max_position=256, dropout=0.0, use_tp=False)
    batch, src_len, tgt_len, iters = 128, 128, 128, 50

    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        avg_loss, _ = transformer.transformer_wmt(
            cfg, src_len=src_len, tgt_len=tgt_len)
        opt = pt.contrib.mixed_precision.decorate(
            pt.optimizer.Adam(learning_rate=1e-4))
        opt.minimize(avg_loss)

    rng = np.random.default_rng(0)
    feed = {
        "src_ids": rng.integers(0, cfg.vocab_size, (batch, src_len)).astype(np.int32),
        "src_pos": np.tile(np.arange(src_len, dtype=np.int32), (batch, 1)),
        "tgt_ids": rng.integers(0, cfg.vocab_size, (batch, tgt_len)).astype(np.int32),
        "tgt_pos": np.tile(np.arange(tgt_len, dtype=np.int32), (batch, 1)),
        "tgt_label": rng.integers(0, cfg.vocab_size, (batch, tgt_len)).astype(np.int32),
        "tgt_weight": np.ones((batch, tgt_len), np.float32),
    }
    drain = "proj.b"
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        exe.run(main_p, feed=feed, fetch_list=[avg_loss])
        exe.run(main_p, feed=feed)
        assert pt.global_scope().find_var(drain) is not None, drain
        np.asarray(pt.global_scope().find_var(drain))
        # 3 windows with the spread recorded: it distinguishes an outlier
        # window from a regression
        windows = _timed_windows(
            lambda: exe.run(main_p, feed=feed),
            lambda: pt.global_scope().find_var(drain), iters, 3)
        dt = min(windows)
        (lv,) = exe.run(main_p, feed=feed, fetch_list=[avg_loss])
        assert np.isfinite(float(np.asarray(lv)))

    H, L_, F, V = cfg.hidden_size, cfg.num_layers, cfg.ffn_size, cfg.vocab_size
    t_src, t_tgt = batch * src_len, batch * tgt_len
    enc_params = L_ * (4 * H * H + 2 * H * F)
    dec_params = L_ * (8 * H * H + 2 * H * F)
    step_flops = (6 * enc_params * t_src + 6 * (dec_params + H * V) * t_tgt
                  + 12 * L_ * H * (src_len * t_src          # enc self
                                   + tgt_len * t_tgt        # dec self (causal)
                                   + src_len * t_tgt))      # cross
    mfu = (step_flops / dt) / peak
    wmt_windows = [round((t_src + t_tgt) / w, 1) for w in windows]
    return (t_src + t_tgt) / dt, mfu, wmt_windows


def bench_deepfm():
    """DeepFM CTR through exe.train_from_dataset (BASELINE config 5): the
    trainer-runtime path — QueueDataset file parsing (native C MultiSlot
    parser) feeding sparse-embedding training. Metric: examples/s end-to-end
    including the host data pipeline (that IS the workload for CTR)."""
    import os
    import tempfile

    import paddle_tpu as pt
    from paddle_tpu import native
    from paddle_tpu.models import deepfm

    # this cell measures ingest: the pure-Python parser fallback would be
    # timed under the native parser's name
    assert native.native_available(), "C MultiSlot parser did not build"
    n_fields, n_dense = 26, 13
    vocab, batch, lines_per_file, n_files = 100_000, 2048, 16384, 8

    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        avg_loss, _, feed_names = deepfm.deepfm(
            n_fields=n_fields, n_dense=n_dense, vocab_size=vocab)
        # SGD: the is_sparse embeddings emit SelectedRows grads (the pserver
        # wire format), which the sgd op applies as true row updates
        pt.optimizer.SGD(learning_rate=1e-3).minimize(avg_loss)
        block = main_p.global_block
        use_vars = [block.var(n) for n in feed_names]

    rng = np.random.default_rng(0)
    tmp = tempfile.mkdtemp(prefix="deepfm_bench_")
    files = []
    for fi in range(n_files):
        p = os.path.join(tmp, f"part-{fi}")
        with open(p, "w") as f:
            for _ in range(lines_per_file):
                ids = rng.integers(0, vocab, n_fields)
                dense = rng.random(n_dense).round(4)
                lbl = rng.integers(0, 2)
                f.write(f"{n_fields} {' '.join(map(str, ids))} "
                        f"{n_dense} {' '.join(map(str, dense))} 1 {lbl}\n")
        files.append(p)

    ds = pt.DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(batch)
    # 4 ingest threads (reference MultiSlotDataFeed runs many)
    ds.set_thread(4)
    ds.set_use_var(use_vars)
    ds.set_filelist(files)

    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        # warmup pass compiles; timed pass measures steady-state. Drain on a
        # trained parameter before AND after the timed pass — exe.run
        # dispatch is async, so the clock must not stop with device work
        # still in flight (same discipline as the other benches)
        drain = main_p.all_parameters()[-1].name
        assert pt.global_scope().find_var(drain) is not None, drain
        exe.train_from_dataset(main_p, ds, print_period=10**9)
        np.asarray(pt.global_scope().find_var(drain))
        # 5 timed windows with the full spread recorded: a single window
        # cannot distinguish a regression from an outlier. Best window is
        # the steady-state estimate; the spread ships in the bench JSON so
        # the artifact itself shows the measurement quality.
        windows = []
        for _ in range(5):
            t0 = time.perf_counter()
            exe.train_from_dataset(main_p, ds, print_period=10**9)
            np.asarray(pt.global_scope().find_var(drain))
            windows.append(time.perf_counter() - t0)
        dt = min(windows)
        windows_ex_s = [round(n_files * lines_per_file / w, 1)
                        for w in windows]
        # device-path reference: the same compiled step fed one resident
        # batch — no host parse, no transfer. e2e/device is the pipelined-
        # execution efficiency the async feed/dispatch subsystem is
        # accountable for (ISSUE 2 target >= 0.9; tools/gate.py flags it)
        dev_feed = {
            "sparse_ids": jax.device_put(
                rng.integers(0, vocab, (batch, n_fields)).astype(np.int32)),
            "dense_x": jax.device_put(
                rng.random((batch, n_dense)).astype(np.float32)),
            "label": jax.device_put(
                rng.integers(0, 2, (batch, 1)).astype(np.float32)),
        }
        exe.run(main_p, feed=dev_feed)  # compile this signature
        np.asarray(pt.global_scope().find_var(drain))
        dev_windows = _timed_windows(
            lambda: exe.run(main_p, feed=dev_feed),
            lambda: pt.global_scope().find_var(drain), 50, 3)
        device_ex_s = batch / min(dev_windows)
        (lv,) = exe.run(main_p, feed=dev_feed, fetch_list=[avg_loss])
        assert np.isfinite(float(np.asarray(lv)))

    # health-sentinel overhead: the SAME device-path step with the in-graph
    # numeric guard compiled in (FLAGS_guard_numerics). The sentinel rides
    # the step's own outputs (a [4] vector + [2] EMA state), so the measured
    # cost should be noise; tools/gate.py flags > 2% against this baseline
    from paddle_tpu import flags as pt_flags

    old_guard = pt_flags.get_flag("guard_numerics")
    pt_flags.set_flags({"guard_numerics": True})
    try:
        g_main, g_startup = pt.Program(), pt.Program()
        with pt.program_guard(g_main, g_startup):
            with pt.unique_name.guard():
                g_loss, _, _ = deepfm.deepfm(
                    n_fields=n_fields, n_dense=n_dense, vocab_size=vocab)
                pt.optimizer.SGD(learning_rate=1e-3).minimize(g_loss)
        with pt.scope_guard(pt.Scope()):
            exe.run(g_startup)
            g_drain = g_main.all_parameters()[-1].name
            exe.run(g_main, feed=dev_feed)  # compile
            np.asarray(pt.global_scope().find_var(g_drain))
            g_windows = _timed_windows(
                lambda: exe.run(g_main, feed=dev_feed),
                lambda: pt.global_scope().find_var(g_drain), 50, 3)
        guarded_ex_s = batch / min(g_windows)
        guard_overhead_pct = max(0.0,
                                 (1.0 - guarded_ex_s / device_ex_s) * 100.0)
    finally:
        pt_flags.set_flags({"guard_numerics": old_guard})

    for p in files:
        os.unlink(p)
    os.rmdir(tmp)
    return (n_files * lines_per_file / dt, windows_ex_s, device_ex_s,
            guard_overhead_pct)


def _tiered_parity(steps: int = 12):
    """Small-scale parameter-parity oracle for the tiered path (ISSUE 10):
    same model, same inits, same batches — N SGD steps through a 256-slot
    cache over a 512-row table (evictions + write-backs fire constantly)
    vs the dense-lookup program. Returns the max |param| drift; tools/
    gate.py hard-fails above 1e-4 (measured: float associativity only)."""
    import paddle_tpu as pt
    from paddle_tpu import flags as pt_flags
    from paddle_tpu import layers as L
    from paddle_tpu.layers import tensor as T
    from paddle_tpu.param_attr import ParamAttr

    VOCAB, DIM, FIELDS, BATCH = 512, 8, 6, 32

    def build():
        ids = T.data(name="ids", shape=[FIELDS], dtype="int64")
        label = T.data(name="label", shape=[1], dtype="float32")
        emb = L.embedding(ids, size=[VOCAB, DIM], is_sparse=True,
                          param_attr=ParamAttr(name="ptbl"))
        pooled = L.reduce_sum(emb, dim=1)
        logit = L.fc(pooled, size=1, param_attr=ParamAttr(name="pw"),
                     bias_attr=ParamAttr(name="pb"))
        return L.mean(L.sigmoid_cross_entropy_with_logits(logit, label))

    def feed(s):
        rng = np.random.default_rng(500 + s)
        return {"ids": rng.integers(0, VOCAB,
                                    (BATCH, FIELDS)).astype(np.int64),
                "label": rng.integers(0, 2, (BATCH, 1)).astype(np.float32)}

    def minimized(budget, slots):
        m, st = pt.Program(), pt.Program()
        m.random_seed = st.random_seed = 7
        pt_flags.set_flags({"emb_hbm_budget_mb": budget,
                            "emb_cache_slots": slots})
        with pt.program_guard(m, st), pt.unique_name.guard():
            loss = build()
            pt.optimizer.SGD(0.1).minimize(loss)
        return m, st, loss

    saved = {k: pt_flags.get_flag(k)
             for k in ("emb_hbm_budget_mb", "emb_cache_slots")}
    try:
        exe = pt.Executor()
        main_o, startup_o, loss_o = minimized(0.0, 0)
        sc_o = pt.Scope()
        with pt.scope_guard(sc_o):
            exe.run(startup_o)
            init = {n: np.array(np.asarray(sc_o.find_var(n)))
                    for n in ("ptbl", "pw", "pb")}
            for s in range(steps):
                exe.run(main_o, feed=feed(s), fetch_list=[loss_o])
            oracle = {n: np.asarray(sc_o.find_var(n))
                      for n in ("ptbl", "pw", "pb")}

        main_t, startup_t, loss_t = minimized(0.001, 256)
        eng = main_t._tiered_engine
        sc_t = pt.Scope()
        with pt.scope_guard(sc_t):
            exe.run(startup_t)
            eng.tables["ptbl"].host.load_rows(np.arange(VOCAB),
                                              init["ptbl"])
            eng.tables["ptbl"].host.clear_dirty()
            sc_t.set_var("pw", jax.device_put(init["pw"]))
            sc_t.set_var("pb", jax.device_put(init["pb"]))
            for s in range(steps):
                exe.run(main_t, feed=feed(s), fetch_list=[loss_t])
            exe.wait()
            table_t = eng.export_dense("ptbl", sc_t)
            drift = max(
                float(np.abs(table_t - oracle["ptbl"]).max()),
                float(np.abs(np.asarray(sc_t.find_var("pw"))
                             - oracle["pw"]).max()),
                float(np.abs(np.asarray(sc_t.find_var("pb"))
                             - oracle["pb"]).max()))
            st = eng.stats("ptbl")
        assert st["evictions"] > 0, "parity run never evicted — not tiered"
        return drift
    finally:
        pt_flags.set_flags(saved)


def bench_deepfm_giant():
    """DeepFM with an embedding table provably exceeding the configured HBM
    budget (ISSUE 10): the minimize()-time rewrite puts fm_emb on the
    two-tier path — host shards + hot-ID cache — and the feed pipeline
    resolves misses off the step. Metrics: end-to-end examples/s through
    train_from_dataset (zipf-skewed ids, the CTR regime the hot-ID cache
    exists for), cache hit rate / evictions / write-backs, host-tier bytes
    vs the budget, and the small-scale parameter-parity drift vs the
    dense-lookup oracle that tools/gate.py hard-fails on."""
    import os
    import tempfile

    import paddle_tpu as pt
    from paddle_tpu import flags as pt_flags
    from paddle_tpu import native
    from paddle_tpu.models import deepfm

    assert native.native_available(), "C MultiSlot parser did not build"
    n_fields, n_dense = 26, 13
    # fm_emb = 10M x 16 fp32 = 640 MB against a 64 MB budget: the table
    # provably exceeds the cache tier by 10x
    vocab, batch, lines_per_file, n_files = 10_000_000, 2048, 16384, 4
    budget_mb = 64.0

    saved = {k: pt_flags.get_flag(k)
             for k in ("emb_hbm_budget_mb", "emb_cache_slots")}
    pt_flags.set_flags({"emb_hbm_budget_mb": budget_mb,
                        "emb_cache_slots": 0})
    try:
        main_p, startup = pt.Program(), pt.Program()
        with pt.program_guard(main_p, startup), pt.unique_name.guard():
            avg_loss, _, feed_names = deepfm.deepfm(
                n_fields=n_fields, n_dense=n_dense, vocab_size=vocab)
            pt.optimizer.SGD(learning_rate=1e-3).minimize(avg_loss)
            block = main_p.global_block
            use_vars = [block.var(n) for n in feed_names]
        engine = main_p._tiered_engine
        assert engine is not None and "fm_emb" in engine.tables, \
            "fm_emb did not tier — check FLAGS_emb_hbm_budget_mb"
        ts = engine.tables["fm_emb"]

        rng = np.random.default_rng(0)
        tmp = tempfile.mkdtemp(prefix="deepfm_giant_")
        files = []
        for fi in range(n_files):
            p = os.path.join(tmp, f"part-{fi}")
            with open(p, "w") as f:
                for _ in range(lines_per_file):
                    # zipf-skewed ids: the production CTR distribution the
                    # frequency-based hot-ID admission exists for
                    ids = (rng.zipf(1.5, n_fields) - 1) % vocab
                    dense = rng.random(n_dense).round(4)
                    lbl = rng.integers(0, 2)
                    f.write(f"{n_fields} {' '.join(map(str, ids))} "
                            f"{n_dense} {' '.join(map(str, dense))} "
                            f"1 {lbl}\n")
            files.append(p)

        ds = pt.DatasetFactory().create_dataset("QueueDataset")
        ds.set_batch_size(batch)
        ds.set_thread(4)
        ds.set_use_var(use_vars)
        ds.set_filelist(files)

        exe = pt.Executor()
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            drain = main_p.all_parameters()[-1].name
            exe.train_from_dataset(main_p, ds, print_period=10**9)
            np.asarray(pt.global_scope().find_var(drain))
            windows = []
            for _ in range(5):
                t0 = time.perf_counter()
                exe.train_from_dataset(main_p, ds, print_period=10**9)
                np.asarray(pt.global_scope().find_var(drain))
                windows.append(time.perf_counter() - t0)
            engine.flush_all()
            stats = engine.stats("fm_emb")
            (lv,) = exe.run(main_p, feed={
                "sparse_ids": (rng.zipf(1.5, (batch, n_fields)) - 1)
                % vocab,
                "dense_x": rng.random((batch, n_dense)).astype(np.float32),
                "label": rng.integers(0, 2, (batch, 1)).astype(np.float32),
            }, fetch_list=[avg_loss])
            assert np.isfinite(float(np.asarray(lv)))

        dt = min(windows)
        n_examples = n_files * lines_per_file
        for p in files:
            os.unlink(p)
        os.rmdir(tmp)
    finally:
        pt_flags.set_flags(saved)

    parity = _tiered_parity()
    return {
        "examples_per_sec": round(n_examples / dt, 2),
        "windows_ex_s": [round(n_examples / w, 1) for w in windows],
        "cache_hit_rate": stats["hit_rate"],
        "evictions": stats.get("evictions", 0),
        "writebacks": stats.get("writebacks", 0),
        "cache_slots": stats["slots"],
        "prefetch_rows": stats["prefetch_rows"],
        "host_tier_bytes": int(sum(
            t.host.nbytes for t in engine.tables.values())),
        "table_bytes": int(ts.host.nbytes),
        "hbm_budget_mb": budget_mb,
        "cache_bytes": int((ts.slots + 1) * ts.host.dim
                           * ts.host.dtype.itemsize),
        "parity_max_abs_diff": parity,
        "config": (f"v{vocab // 10**6}M b{batch} f{n_fields} zipf1.5 "
                   f"budget{budget_mb:g}MB"),
    }


def bench_serving():
    """Served-load row (ISSUE 7): synthetic open-loop arrivals against a
    small bert-decoder through the paged-KV continuous-batching engine
    (paddle_tpu/serving/). The metrics ARE the serving SLOs: served
    tokens/s, p50/p99 request latency, first-token latency, KV-pool
    occupancy — and the zero-leak page count tools/gate.py hard-fails on.
    Open-loop (arrivals never wait for the system) because a closed loop
    self-throttles and hides queueing collapse; the workload is seeded so
    every round replays the same arrival trace."""
    from paddle_tpu.serving import DecoderConfig, ServingEngine
    from tools import _serve_ab

    cfg = DecoderConfig(vocab_size=30522, hidden_size=512, num_layers=6,
                        num_heads=8, ffn_size=2048, max_position=1024)
    engine = ServingEngine(cfg, page_size=16, pool_pages=2048,
                           max_inflight=16)
    wl = _serve_ab.synth_workload(64, cfg.vocab_size, seed=0,
                                  prompt_lens=(16, 128), max_new=32,
                                  rate=32.0)
    out = _serve_ab.run_open_loop(engine, wl)
    out["config"] = "dec6x512 b16 pool2048x16 open-loop r32"
    out["shared_prefix"] = _bench_shared_prefix()
    # ISSUE 14: overload resilience — the shared-prefix mix at 10x the r8
    # rate against shed floors + the degradation ladder, plus the same
    # trace under a bounded serving fault plan; gate.py enforces goodput
    # >= 0.7x the unloaded arm and zero leaks in every arm
    out["overload"] = _serve_ab.overload_block(True)
    return out


def _bench_shared_prefix():
    """The ISSUE 11 multi-tenant A/B: a zipf shared-system-prompt mix at
    10x the r8 request rate through three arms over the SAME seeded trace —
    the PR 7 baseline (no cache, no speculation), copy-on-write prefix
    caching, and prefix caching + speculative decoding (draft k=4, exact
    under greedy). Steady-state, compile-free measurement
    (tools/_serve_ab.run_open_loop warmup protocol). tools/gate.py
    hard-fails page/refcount leaks in ANY arm and a prefix-cache hit rate
    below floor."""
    from paddle_tpu.serving import ServingEngine
    from tools import _serve_ab

    cfg, _, user_lens = _serve_ab.ab_config(True, shared_prefix=True)
    import paddle_tpu as pt

    ps = int(pt.flags.get_flag("serving_page_size"))
    n_req, max_new, rate, sys_len = 64, 16, 640.0, 8 * ps
    wl = _serve_ab.synth_shared_prefix_workload(
        n_req, cfg.vocab_size, seed=0, n_sys_prompts=8, sys_len=sys_len,
        user_lens=user_lens, max_new=max_new, rate=rate)
    arms = {}
    for name, prefix, draft in (("baseline", False, 0),
                                ("prefix", True, 0),
                                ("prefix_spec", True, 4)):
        eng = ServingEngine(cfg, prefix_cache=prefix, draft_k=draft)
        r = _serve_ab.run_open_loop(eng, wl, warmup=True)
        arms[name] = {k: r[k] for k in (
            "served_tokens_per_sec", "prefill_tokens_computed",
            "prefix_cache_hit_rate", "spec_accept_rate",
            "tokens_per_decode_step", "kv_pages_leaked", "refcount_leaks",
            "cow_copies")}
        arms[name]["request_latency_p50_ms"] = r["request_latency"].get(
            "p50_ms")
    base = arms["baseline"]["served_tokens_per_sec"]
    return {
        "arms": arms,
        "rate_req_s": rate,
        "vs_baseline_tok_s": round(
            arms["prefix"]["served_tokens_per_sec"] / max(base, 1e-9), 3),
        "prefill_tokens_saved": (
            arms["baseline"]["prefill_tokens_computed"]
            - arms["prefix"]["prefill_tokens_computed"]),
        "config": (f"shared-prefix zipf1.2 sys{sys_len} r{rate:g} "
                   f"n{n_req}"),
    }


def bench_telemetry():
    """Telemetry-layer overhead A/B (ISSUE 13): the SAME tiny device-path
    async-dispatch step timed with FLAGS_obs_enable on vs off over the
    shared `_timed_windows` protocol. The flag gates exactly what the
    unified registry added over the PR 2 stage accumulators (histograms,
    events, spans, exporter sinks) — counters/gauges stay on in both arms —
    so the delta IS the layer's marginal cost on the hottest instrumented
    loop (run_async dispatch + window drain + per-step latency histogram).
    tools/gate.py --obs fails the artifact above 2%."""
    import paddle_tpu as pt
    from paddle_tpu import flags as pt_flags
    from paddle_tpu import layers as L
    from paddle_tpu.layers import tensor as T

    rng = np.random.default_rng(13)
    batch, dim = 4096, 256
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup), pt.unique_name.guard():
        x = T.data(name="obs_x", shape=[dim], dtype="float32")
        label = T.data(name="obs_y", shape=[1], dtype="float32")
        h = L.fc(x, size=dim, act="relu")
        logit = L.fc(h, size=1)
        loss = L.mean(L.sigmoid_cross_entropy_with_logits(logit, label))
        pt.optimizer.SGD(learning_rate=1e-3).minimize(loss)
    feed = {"obs_x": jax.device_put(
                rng.random((batch, dim), dtype=np.float32)),
            "obs_y": jax.device_put(
                rng.integers(0, 2, (batch, 1)).astype(np.float32))}
    exe = pt.Executor()
    iters, passes = 50, 3
    steps_per_s = {}
    old = pt_flags.get_flag("obs_enable")
    try:
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            drain_name = main_p.all_parameters()[-1].name
            exe.run(main_p, feed=feed)  # compile once; both arms share it
            np.asarray(pt.global_scope().find_var(drain_name))

            def run_once():
                exe.run_async(main_p, feed=feed)

            def drain():
                exe.wait()
                return pt.global_scope().find_var(drain_name)

            for arm, flag_val in (("off", False), ("on", True)):
                pt_flags.set_flags({"obs_enable": flag_val})
                windows = _timed_windows(run_once, drain, iters, passes)
                steps_per_s[arm] = batch / min(windows)
    finally:
        pt_flags.set_flags({"obs_enable": old})
    overhead_pct = max(0.0,
                       (1.0 - steps_per_s["on"] / steps_per_s["off"]) * 100.0)
    return {
        "obs_overhead_pct": round(overhead_pct, 2),
        "examples_per_sec_obs_on": round(steps_per_s["on"], 2),
        "examples_per_sec_obs_off": round(steps_per_s["off"], 2),
        "config": f"fc{dim}x2 b{batch} async-dispatch a/b",
    }


def _tuned(tuner_stats: dict, name: str, fn, *args):
    """Run one workload section with the autotuner's provenance counters
    scoped to it: every decision the build/trace makes (conv lowering,
    attention backend, fusion, AMP lists, buckets) lands in this
    workload's hit-rate row. With FLAGS_tuning_mode=off no decisions
    fire and the row records zero consults — which is exactly what
    gate.py needs to tell 'untuned run' from 'tuned run with misses'."""
    from paddle_tpu import tuning

    tuning.reset_provenance()
    t0 = time.perf_counter()
    out = fn(*args)
    tuner_stats[name] = tuning.provenance_snapshot()
    # progress goes to stderr: a run cut short still says which cells ran
    print(f"[bench] {name}: done in {time.perf_counter() - t0:.0f}s",
          file=sys.stderr, flush=True)
    return out


def bench_multichip(argv=None):
    """`bench.py --multichip`: the measured multichip scaling campaign
    (ROADMAP item 2 promoted from dryrun) — tokens/s and per-axis scaling
    efficiency for dp/tp/pp/sp on an 8-device mesh, with collective-overlap
    A/B arms (bucketed vs per-grad allreduce, ZeRO-1, 1F1B vs fill-drain)
    on the tools/_timing.py protocol, plus the parameter-trajectory parity
    oracle per axis. Prints ONE JSON line (the MULTICHIP artifact's
    scaling/overlap_ab/parity blocks; tools/gate.py --multichip consumes
    it). On a TPU host the campaign runs in THIS process over the chips
    present (a chip belongs to one process: a child could never reach it
    while this parent lives). On a host with no TPU it provisions a virtual
    8-device CPU mesh in a fresh CPU-only process — platform choice is
    locked at first backend init, so a session that already initialized
    fewer devices re-execs."""
    import os
    import subprocess

    argv = list(argv or [])
    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    if "--devices" in argv:
        n = int(argv[argv.index("--devices") + 1])
    else:
        n = len(devs) if on_tpu else 8
        argv += ["--devices", str(n)]
    if on_tpu and n > len(devs):
        print(f"bench.py --multichip: --devices {n} but this host has "
              f"{len(devs)} TPU chip(s)", file=sys.stderr)
        return 2
    if len(devs) < n:
        repo = os.path.dirname(os.path.abspath(__file__))
        from __graft_entry__ import _FORCE_ENV

        env = dict(os.environ)
        env[_FORCE_ENV] = str(n)
        code = (f"import sys; sys.path.insert(0, {repo!r}); "
                f"import __graft_entry__ as g; g._provision_cpu_mesh({n}); "
                f"from tools import _mc_ab; "
                f"sys.exit(_mc_ab.main({argv!r}))")
        r = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env)
        return r.returncode
    from tools import _mc_ab

    return _mc_ab.main(argv)


def main():
    from paddle_tpu import compile_cache
    from paddle_tpu import flags as pt_flags
    from paddle_tpu import tuning
    from paddle_tpu.tuning import learned as tuning_learned

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py: needs a TPU, jax found {dev.platform!r} — every "
              f"number this prints is a device metric (tests/ covers "
              f"correctness off the chip)", file=sys.stderr)
        return 2
    peak = _peak_flops(dev)
    compile_cache.configure()

    # learned-tier provenance is per-RUN (gate.py's fallback-rate ceiling
    # reads the artifact's aggregate), unlike the per-workload hit rates
    tuning_learned.reset_counters()

    tuner_stats: dict = {}
    tok_s, bert_mfu, bert_windows = _tuned(
        tuner_stats, "bert", bench_bert, peak)
    img_s, rn_mfu, rn_windows, rn_ab = _tuned(
        tuner_stats, "resnet50", bench_resnet, peak)
    wmt_tok_s, wmt_mfu, wmt_windows = _tuned(
        tuner_stats, "transformer_wmt", bench_wmt, peak)
    ctr_ex_s, ctr_windows, ctr_dev_ex_s, ctr_guard_pct = _tuned(
        tuner_stats, "deepfm", bench_deepfm)
    giant = _tuned(tuner_stats, "deepfm_giant", bench_deepfm_giant)
    long_ctx = _tuned(tuner_stats, "bert_s512", bench_bert_long)
    short_ab = _tuned(tuner_stats, "bert_s128_shortattn", bench_bert_short)
    serving = _tuned(tuner_stats, "serving", bench_serving)
    telemetry = bench_telemetry()

    # bench rounds feed the measurement store too (sweep/explore mode or
    # FLAGS_tuning_record=on): per-window seconds-per-item rows under the
    # run's tuning mode as the arm — A/B material for mode-on-vs-off drift
    def _rec_bench(wl, unit, windows):
        ws = [1.0 / w for w in windows if w and w > 0]
        if ws and tuning_learned.recording_enabled():
            tuning_learned.record(
                "bench", f"workload={wl}", "-", tuning.device_kind(),
                f"mode_{tuning.mode()}", windows_s=ws, source="bench",
                extras={"unit": unit})

    _rec_bench("bert", "s_per_token", bert_windows)
    _rec_bench("resnet50", "s_per_image", rn_windows)
    _rec_bench("transformer_wmt", "s_per_token", wmt_windows)
    _rec_bench("deepfm", "s_per_example", ctr_windows)

    # the registry's end-of-run name inventory rides in the artifact:
    # tools/gate.py --obs lints it against observability/schema.py, so a
    # metric added without a declaration fails the gate, not a dashboard
    from paddle_tpu import observability as obs

    _snap = obs.snapshot()
    telemetry["metric_names"] = sorted(
        {obs.base_name(k) for sect in ("counters", "gauges", "histograms")
         for k in _snap[sect]} | set(_snap["stages"]))
    telemetry["undeclared_metrics"] = _snap["undeclared"]

    # Per-workload targets. MFU workloads: the 0.45 north star
    # (BASELINE.json). DeepFM has no published number, so the declared
    # target is a no-regression floor under the round-3 measured 75k ex/s.
    # The workload is host-pipeline bound and its runs spread 68-93k ex/s
    # then, so the floor sits at 60k — below that band, above any real
    # (>25%) regression.
    DEEPFM_TARGET_EX_S = 60_000.0
    vs_target = {
        "bert": bert_mfu / 0.45,
        "resnet50": rn_mfu / 0.45,
        "transformer_wmt": wmt_mfu / 0.45,
        "deepfm": ctr_ex_s / DEEPFM_TARGET_EX_S,
    }
    # the Pallas kernel's proof row gates the aggregate too. Floor at 0.95
    # (not 1.0): a strict >=1.0 gate would flag run-to-run noise as a
    # regression.
    vs_target["bert_s512_pallas"] = \
        long_ctx["pallas"] / long_ctx["xla"] / 0.95
    vs_baseline = min(vs_target.values())

    print(json.dumps({
        "metric": "worst_workload_vs_target",
        "value": round(vs_baseline, 4),
        "unit": "ratio",
        "vs_baseline": round(vs_baseline, 4),
        "vs_target": {k: round(v, 4) for k, v in vs_target.items()},
        "bert_train_tokens_per_sec_per_chip": round(tok_s, 2),
        "bert_windows_tok_s": bert_windows,
        "bert_mfu": round(bert_mfu, 4),
        "resnet50_images_per_sec_per_chip": round(img_s, 2),
        "resnet50_windows_img_s": rn_windows,
        "resnet50_mfu": round(rn_mfu, 4),
        # the r6 conv-lever A/B, re-measured every round: implicit-GEMM
        # (auto per-shape cost model) + fused one-pass BN statistics vs the
        # r5 direct-conv/two-pass-BN step; headline takes the winner
        "resnet50_lever_ab": rn_ab,
        "transformer_wmt_tokens_per_sec_per_chip": round(wmt_tok_s, 2),
        "transformer_wmt_windows_tok_s": wmt_windows,
        "transformer_wmt_mfu": round(wmt_mfu, 4),
        "deepfm_examples_per_sec": round(ctr_ex_s, 2),
        "deepfm_windows_ex_s": ctr_windows,
        "deepfm_target_examples_per_sec": DEEPFM_TARGET_EX_S,
        # pipelined-execution efficiency: end-to-end train_from_dataset over
        # the pure device step (resident batch). The async feed/dispatch
        # pipeline owns this ratio; tools/gate.py flags < 0.9
        "deepfm_device_path_examples_per_sec": round(ctr_dev_ex_s, 2),
        "deepfm_e2e_device_ratio": round(ctr_ex_s / ctr_dev_ex_s, 4),
        # in-graph health sentinel cost vs the unguarded device path
        # (resilience/guardrails.py); tools/gate.py flags > 2%
        "deepfm_guard_overhead_pct": round(ctr_guard_pct, 2),
        # the custom short-seq Pallas attention kernel's proof row: BERT
        # seq-512 tokens/s with the kernel off vs on (on wins ~9%)
        "bert_s512_tokens_per_sec_xla_attn": round(long_ctx["xla"], 2),
        "bert_s512_tokens_per_sec_pallas_attn": round(long_ctx["pallas"], 2),
        # ISSUE 9: the seq<=128 short-attention kernel's end-to-end A/B
        # (interleaved ABAB, FLAGS_attention_force_backend arms); gate.py
        # fails if the kernel ENGAGED and lost beyond the band
        "bert_s128_shortattn_ab": short_ab,
        # ISSUE 10: DeepFM with fm_emb provably over the HBM budget on the
        # tiered host-shards + hot-ID-cache path (embedding/): end-to-end
        # examples/s, cache hit rate, host-tier bytes vs budget, and the
        # small-scale parameter-parity drift vs the dense-lookup oracle.
        # tools/gate.py hard-fails parity drift > 1e-4; the hit-rate floor
        # warns on the first artifact and gates thereafter
        "deepfm_giant": giant,
        # the serving runtime's open-loop load row (serving/): served
        # tokens/s, p50/p99 request + first-token latency, KV-pool
        # occupancy. tools/gate.py fails on leaked KV pages and on a
        # served-tokens/s drop below the floor vs the previous artifact
        "serving": serving,
        # ISSUE 13: the unified telemetry layer's overhead A/B
        # (FLAGS_obs_enable on vs off on the async dispatch loop) plus the
        # registry's metric-name inventory; tools/gate.py --obs fails
        # overhead > 2%, undeclared metric names, or schema drift
        "telemetry": telemetry,
        # autotuner provenance (paddle_tpu/tuning/): per-workload decision
        # counts and swept-DB hit-rate. tools/gate.py flags a consult-mode
        # workload that resolved mostly off the DB (running untuned)
        "tuning": {
            "mode": tuning.mode(),
            "db": str(pt_flags.get_flag("tuning_db")),
            "model": tuning_learned.model_path() or "",
            # learned-tier aggregate: predictions/fallbacks/promotions +
            # fallback_rate (gate.py --costmodel's consult-mode ceiling)
            "learned": tuning_learned.snapshot(),
            "workloads": tuner_stats,
        },
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "config": {
            "device_kind": dev.device_kind,
            "bert": "base b128 s128 AMP Adam",
            "resnet": "rn50 b128 i224 AMP Momentum",
            "wmt": "base b128 s128/128 AMP Adam",
            "deepfm": "v100k b2048 f26 d13 QueueDataset",
            "deepfm_giant": giant["config"],
            "bert_s512": "base b64 s512 AMP Adam",
        },
    }))
    return 0


if __name__ == "__main__":
    if "--multichip" in sys.argv:
        sys.exit(bench_multichip(
            [a for a in sys.argv[1:] if a != "--multichip"]))
    sys.exit(main())
