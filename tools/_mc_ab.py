"""Multichip scaling campaign + seeded collective-overlap A/B sweep.

What the parallelism axes cost and gain on a mesh of (virtual) devices, as
numbers beside the loss-parity check: one seeded BERT-shaped workload (same
global batch everywhere, so tokens/s compare) is trained under every
parallelism axis of an 8-device mesh —

    single  one device, the reference arm every efficiency divides by
    dp      fleet shard_map collective (GradAllReduce), three overlap arms:
              per-grad allreduce parked at the optimizer boundary (off),
              bucketed c_allreduce_coalesced at grad-readiness points (on),
              ZeRO-1 reduce-scatter/shard-update/allgather (zero1)
    tp      GSPMD tensor parallelism (use_tp weight annotations)
    sp      GSPMD sequence parallelism (use_sp activation annotations)
    pp      device-placed pipeline, 1F1B vs GPipe fill-drain arms, with the
            schedule's explicit bubble accounting attached

— each timed with the tools/_timing.py protocol (median-of-windows,
interference band) and checked for loss parity: the final parameters must
match the single-device trajectory (THE equivalence oracle; a fast wrong
collective must not win a row).

Efficiency convention: `speedup_vs_single` = tokens/s of the mesh arm over
tokens/s of the single-device arm at the SAME global batch. On real chips
that is the scaling win (ideal = n); on a host-platform virtual mesh every
"device" shares the same silicon, so ideal is ~1.0 and the number measures
pure partitioning/collective overhead. `efficiency` = speedup / n_devices
is the per-chip spelling for real accelerators. The speed of the dp x 4
step on the chip is the cell `bert_base.s128.dp4` of BENCHMARK.json.

    python tools/_mc_ab.py [--devices 8] [--iters 4] [--passes 2]
                           [--sweep 0,1,4] [--record DB.json] [--quick]

--sweep runs the dp arm per bucket size; --record writes the winner into a
PR 6 tuning DB as a swept `collective|mesh=..|payload=..` verdict (tie
keeps the analytic prior per _timing.ab_verdict).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools import _timing  # noqa: E402

SEED = 0


def _cfg(n_layers=4, use_tp=False, use_sp=False):
    from paddle_tpu.models import transformer

    return transformer.TransformerConfig(
        vocab_size=512, hidden_size=64, num_layers=n_layers, num_heads=4,
        ffn_size=128, max_position=128, dropout=0.0,
        use_tp=use_tp, use_sp=use_sp)


def _feed(cfg, batch, seq_len, seed=SEED):
    """Seeded feed, pre-narrowed to runtime dtypes (np_feed_dtype contract:
    no int64 reaches device_put, so the artifact tail stays free of jax's
    truncation warning)."""
    from paddle_tpu.core.types import np_feed_dtype

    rng = np.random.default_rng(seed)
    f = {
        "src_ids": rng.integers(0, cfg.vocab_size, (batch, seq_len)),
        "pos_ids": np.tile(np.arange(seq_len), (batch, 1)),
        "lm_label": rng.integers(0, cfg.vocab_size, (batch, seq_len)),
        "lm_weight": np.ones((batch, seq_len), np.float32),
    }
    return {k: np.asarray(v).astype(np_feed_dtype(np.asarray(v).dtype),
                                    copy=False) for k, v in f.items()}


def _build(cfg, seq_len, transpile=None, pipeline=None):
    """Fresh (main, startup, loss) with Adam, optionally transpiled."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer

    main, startup = pt.Program(), pt.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            loss, _ = transformer.bert_pretrain(cfg, seq_len=seq_len)
            if pipeline is not None:
                pipeline(main, startup, loss)
            else:
                pt.optimizer.Adam(learning_rate=1e-3).minimize(loss)
                if transpile is not None:
                    transpile(main, startup)
    return main, startup, loss


class _Arm:
    """One built+initialized training arm with its own program/scope, so
    competing arms can be timed in INTERLEAVED windows (A B A B ...):
    machine drift on second-to-minute scales is what sequential per-arm
    measurement aliases straight into the A/B margin (observed: the same
    pair swinging keep<->retire between runs)."""

    def __init__(self, build, target_of, feed):
        import paddle_tpu as pt

        self.main, self.startup, self.loss = build()
        self.scope = pt.Scope()
        self.exe = pt.Executor()
        with pt.scope_guard(self.scope):
            self.exe.run(self.startup)
            self.target = target_of(self.main)
        self.drain_name = self.main.all_parameters()[-1].name
        self.feed = feed
        self.windows: list[float] = []

    def _step(self):
        self.exe.run(self.target, feed=self.feed, scope=self.scope)

    def _drain(self):
        np.asarray(self.scope.find_var(self.drain_name))

    def warmup(self, n=2):
        # 2 un-timed steps: compile + the one-time XLA/thread-pool settling
        # a first window would otherwise alias into the band
        for _ in range(n):
            self._step()
        self._drain()

    def window(self, iters):
        """One timed window: async-dispatched iters ended by a host drain
        read."""
        import time

        t0 = time.perf_counter()
        for _ in range(iters):
            self._step()
        self._drain()
        w = (time.perf_counter() - t0) / iters
        self.windows.append(w)
        return w

    def stats(self):
        return {
            "median_s": _timing.median(self.windows),
            "min_s": float(min(self.windows)),
            "windows_s": [round(w, 6) for w in self.windows],
            "band": round(_timing.interference_band(self.windows), 4),
        }

    def finish(self, parity_steps=3):
        """`parity_steps` extra deterministic steps, then the parameter
        snapshot — comparable across arms that ran equal step counts."""
        losses = []
        for _ in range(parity_steps):
            (lv,) = self.exe.run(self.target, feed=self.feed,
                                 fetch_list=[self.loss.name],
                                 scope=self.scope)
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
        params = {p.name: np.asarray(self.scope.find_var(p.name))
                  for p in self.main.all_parameters()}
        return params, losses


def _measure_interleaved(arms, iters, passes):
    """ABAB...-interleave the timed windows of every arm in `arms`."""
    for a in arms:
        a.warmup()
    for _ in range(passes):
        for a in arms:
            a.window(iters)
    return [a.stats() for a in arms]


def _run_arm(build, target_of, feed, iters, passes, parity_steps=3):
    """Single-arm convenience: build + warm + time; returns
    (stats, params, losses)."""
    arm = _Arm(build, target_of, feed)
    arm.warmup()
    for _ in range(passes):
        arm.window(iters)
    params, losses = arm.finish(parity_steps)
    return arm.stats(), params, losses


def _ab_row(tokens: int, off_stats: dict, on_stats: dict) -> dict:
    """One overlap_ab block entry. The verdict compares MIN-of-windows
    (interference only ever slows a window, so best-window is the honest
    estimate and is far more stable across runs than the median of 2-3
    interleaved windows) under the wider of the two arms' bands and
    `_timing.DEFAULT_BAND`."""
    band = max(_timing.DEFAULT_BAND, off_stats["band"], on_stats["band"])
    return {
        "off_tok_s": round(tokens / off_stats["min_s"], 1),
        "on_tok_s": round(tokens / on_stats["min_s"], 1),
        "band": round(band, 4),
        "verdict": _timing.ab_verdict(off_stats["min_s"], on_stats["min_s"],
                                      band),
    }


def _param_drift(ref: dict, got: dict) -> float:
    """max over params of relative L-inf distance — the loss-parity oracle
    spelled on the trained state (local shard losses aren't comparable
    across regimes; parameter trajectories are)."""
    worst = 0.0
    for n, rv in ref.items():
        gv = got.get(n)
        if gv is None or gv.shape != rv.shape:
            return float("inf")
        scale = max(1e-6, float(np.max(np.abs(rv))))
        worst = max(worst, float(np.max(np.abs(gv - rv))) / scale)
    return worst


def campaign(n_devices=8, iters=4, passes=2, sweep=None, record=None,
             quick=False):
    import jax

    import paddle_tpu as pt
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.collective import GradAllReduce
    from paddle_tpu.parallel.pipeline import bubble_fraction

    devs = jax.devices()
    if len(devs) < n_devices:
        raise RuntimeError(
            f"campaign needs {n_devices} devices, found {len(devs)} — on a "
            f"host with no TPU, start the process with JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices}")
    platform = devs[0].platform
    if quick:
        iters, passes = max(2, iters // 2), min(passes, 2)

    seq_len, batch, M = 64, 32, 8
    tokens = batch * seq_len
    feed = _feed(_cfg(), batch, seq_len)

    def tok_s(stats):
        return round(tokens / stats["median_s"], 1)

    out: dict = {
        "metric": "multichip_scaling",
        "unit": "ratio",
        "n_devices": n_devices,
        "platform": platform,
        "config": f"bert L4 h64 b{batch} s{seq_len} Adam seed{SEED}",
        "tokens_per_step": tokens,
    }

    # -- single-device reference arm -----------------------------------------
    s_stats, s_params, s_losses = _run_arm(
        lambda: _build(_cfg(), seq_len), lambda m: m, feed, iters, passes)
    single_tok_s = tok_s(s_stats)
    out["single"] = {"tokens_per_sec": single_tok_s,
                     "band": s_stats["band"],
                     "windows_s": s_stats["windows_s"]}

    scaling: dict = {}
    overlap_ab: dict = {}
    parity: dict = {}

    def add_axis(name, stats, params, n_used, extra=None):
        row = {"tokens_per_sec": tok_s(stats),
               "n_devices": n_used,
               "speedup_vs_single": round(tok_s(stats) / single_tok_s, 4),
               "efficiency": round(tok_s(stats) / single_tok_s / n_used, 4),
               "band": stats["band"]}
        if extra:
            row.update(extra)
        scaling[name] = row
        parity[name] = round(_param_drift(s_params, params), 6)

    # -- dp: fleet collective with the three overlap arms, interleaved -------
    mesh_dp = make_mesh({"dp": n_devices})
    cf = lambda m: pt.CompiledProgram(m).with_collective(mesh=mesh_dp)  # noqa: E731

    def dp_build(bucket_mb, zero1=False, out=None):
        t = GradAllReduce(bucket_mb=bucket_mb, zero1=zero1)
        if out is not None:
            out.append(t)

        def tr(main, startup):
            t.transpile(startup, main, rank=0, nranks=n_devices)

        return lambda: _build(_cfg(), seq_len, transpile=tr)

    on_ts, z_ts = [], []
    arm_off = _Arm(dp_build(0.0), cf, feed)
    arm_on = _Arm(dp_build(None, out=on_ts), cf, feed)  # tuner/flag resolved
    arm_z = _Arm(dp_build(None, zero1=True, out=z_ts), cf, feed)
    off_stats, on_stats, z_stats = _measure_interleaved(
        [arm_off, arm_on, arm_z], iters, passes)
    off_params, _ = arm_off.finish()
    on_params, _ = arm_on.finish()
    z_params, _ = arm_z.finish()
    on_t, z_t = on_ts[0], z_ts[0]
    add_axis("dp", on_stats, on_params, n_devices, extra={
        "bucket_mb": on_t.resolved_bucket_mb,
        "bucket_source": on_t.bucket_source,
        "buckets": len(on_t.last_buckets)})
    parity["dp_overlap_off"] = round(_param_drift(s_params, off_params), 6)
    parity["dp_zero1"] = round(_param_drift(s_params, z_params), 6)
    overlap_ab["dp_bucketed"] = _ab_row(tokens, off_stats, on_stats)
    overlap_ab["dp_zero1"] = dict(_ab_row(tokens, on_stats, z_stats),
                                  zero1_params=len(z_t.zero1_params))

    # -- optional bucket-size sweep (the tools/tune.py pattern) --------------
    if sweep:
        sweep_arms = [(float(mb), _Arm(dp_build(float(mb)), cf, feed))
                      for mb in sweep]
        sweep_stats = _measure_interleaved([a for _, a in sweep_arms],
                                           iters, passes)
        rows = {}
        best_mb, best_s = None, None
        for (mb, _), st in zip(sweep_arms, sweep_stats):
            rows[str(mb)] = {"tok_s": tok_s(st), "median_s": st["median_s"],
                             "band": st["band"]}
            if best_s is None or st["median_s"] < best_s:
                best_mb, best_s = mb, st["median_s"]
        out["bucket_sweep"] = {"arms_mb": rows, "winner_mb": best_mb}
        if record:
            _record_verdict(record, n_devices, on_t, rows, best_mb, off_stats)

    # -- tp / sp: GSPMD over a single model/sequence axis --------------------
    for axis, kw in (("tp", {"use_tp": True}), ("sp", {"use_sp": True})):
        mesh = make_mesh({axis: n_devices})
        stats, params, _ = _run_arm(
            lambda: _build(_cfg(**kw), seq_len),
            lambda m: pt.CompiledProgram(m).with_data_parallel(mesh=mesh),
            feed, iters, passes)
        add_axis(axis, stats, params, n_devices)

    # -- pp: device-placed pipeline, 1F1B vs fill-drain, interleaved ---------
    n_pp = min(4, n_devices)
    place = [devs[i] for i in range(n_pp)]

    def pp_build(schedule):
        from paddle_tpu.models import transformer

        def pipe(main, startup, loss):
            cuts = transformer.last_layer_outputs[:n_pp - 1]
            pt.optimizer.PipelineOptimizer(
                pt.optimizer.Adam(learning_rate=1e-3), cut_list=[cuts],
                place_list=place, num_microbatches=M,
                schedule=schedule).minimize(loss)

        return lambda: _build(_cfg(n_layers=n_pp), seq_len, pipeline=pipe)

    arm_fd = _Arm(pp_build("gpipe"), lambda m: m, feed)
    arm_fb = _Arm(pp_build("1f1b"), lambda m: m, feed)
    # single-device reference for pp parity/speedup matches its layer count
    arm_pps = _Arm(lambda: _build(_cfg(n_layers=n_pp), seq_len),
                   lambda m: m, feed)
    fd_stats, fb_stats, pps_stats = _measure_interleaved(
        [arm_fd, arm_fb, arm_pps], iters, passes)
    fb_params, _ = arm_fb.finish()
    pps_params, _ = arm_pps.finish()
    arm_fd.finish()  # equal step counts keep the dispatch ledger honest
    pp_single_tok_s = tok_s(pps_stats)
    bubble = dict(arm_fb.main._pipeline.last_bubble)
    scaling["pp"] = {
        "tokens_per_sec": tok_s(fb_stats),
        "n_devices": n_pp,
        "speedup_vs_single": round(tok_s(fb_stats) / pp_single_tok_s, 4),
        "efficiency": round(tok_s(fb_stats) / pp_single_tok_s / n_pp, 4),
        "band": fb_stats["band"],
        "schedule": "1f1b",
        "num_microbatches": M,
        "bubble_analytic_frac": round(bubble_fraction(n_pp, M), 4),
        "bubble": bubble,
    }
    parity["pp"] = round(_param_drift(pps_params, fb_params), 6)
    overlap_ab["pp_1f1b"] = _ab_row(tokens, fd_stats, fb_stats)

    out["scaling"] = scaling
    out["overlap_ab"] = overlap_ab
    out["parity"] = parity
    out["value"] = round(min(r["speedup_vs_single"]
                             for r in scaling.values()), 4)
    out["vs_baseline"] = out["value"]
    return out


def _record_verdict(db_path, n_devices, transpiler, rows, best_mb,
                    off_stats):
    """Persist the sweep's winner as a swept tuning-DB verdict — a tie
    against the per-grad baseline keeps the analytic prior (ab_verdict's
    contract: a coin flip must not overwrite a model with reasons)."""
    from paddle_tpu import tuning

    best = rows[str(best_mb)] if str(best_mb) in rows else None
    if best is None:
        return
    verdict = _timing.ab_verdict(
        off_stats["median_s"], best["median_s"],
        max(_timing.DEFAULT_BAND, off_stats["band"], best["band"]))
    if verdict != "keep":
        print(f"[mc_ab] sweep verdict '{verdict}' vs per-grad baseline — "
              f"not recording (analytic prior stands)")
        return
    from paddle_tpu.parallel.mesh import axes_desc

    payload = getattr(transpiler, "last_payload_bytes", 1 << 20)
    key = tuning.canonical_key(
        "collective", tuning.collective_key(axes_desc(n_devices), payload),
        "float32", tuning.device_kind())
    db = tuning.TuningDB(db_path if os.path.exists(db_path) else None)
    db.put(key, {"bucket_mb": float(best_mb)}, source="swept",
           measured={m: r["median_s"] for m, r in rows.items()},
           note="tools/_mc_ab.py bucket sweep")
    db.save(db_path)
    print(f"[mc_ab] recorded {key} -> bucket_mb={best_mb} into {db_path}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--passes", type=int, default=2)
    ap.add_argument("--sweep", type=str, default="",
                    help="comma-separated bucket sizes in MB, e.g. 0,1,4")
    ap.add_argument("--record", type=str, default="",
                    help="tuning-DB path to persist the sweep winner into")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)

    from paddle_tpu import compile_cache

    compile_cache.configure()
    sweep = [float(x) for x in args.sweep.split(",") if x.strip()] or None
    out = campaign(n_devices=args.devices, iters=args.iters,
                   passes=args.passes, sweep=sweep,
                   record=args.record or None, quick=args.quick)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
