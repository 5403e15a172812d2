"""A/B: synchronous per-step feeding vs the async feed/dispatch pipeline.

Synthetic slow-host workload, CPU-runnable: the reader sleeps `--host-ms`
per batch (standing in for file parse / decode cost) before yielding numpy
feeds. Arm A runs the classic loop — host produces a batch, Executor.run
places it, a per-step fetch drains the device. Arm B runs the pipeline —
DeviceLoader stages batches from a background thread and run_async keeps up
to FLAGS_max_inflight_steps dispatched without a host drain. When host cost
and step cost are comparable, B should approach max(host, step) per batch
while A pays host + step; the printed per-stage counters show where each
arm's wall time went.

    python tools/_pipeline_ab.py [--host-ms 4] [--batches 60] [--window 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu import profiler
from paddle_tpu.pipeline import DeviceLoader
from tools import _timing

BATCH, DIM, HIDDEN = 256, 64, 512


def build_program():
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        x = L.data(name="x", shape=[DIM], dtype="float32")
        y = L.data(name="y", shape=[1], dtype="float32")
        h = L.fc(x, size=HIDDEN, act="relu")
        loss = L.reduce_mean(L.square_error_cost(L.fc(h, size=1), y))
        pt.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main_p, startup, loss


def slow_host_reader(n_batches: int, host_ms: float):
    rng = np.random.default_rng(0)

    def gen():
        for _ in range(n_batches):
            time.sleep(host_ms / 1e3)  # synthetic parse/decode cost
            yield {"x": rng.standard_normal((BATCH, DIM)).astype(np.float32),
                   "y": rng.standard_normal((BATCH, 1)).astype(np.float32)}

    return gen


def run_arm(pipelined: bool, n_batches: int, host_ms: float, window: int):
    main_p, startup, loss = build_program()
    exe = pt.Executor()
    drain = main_p.all_parameters()[-1].name
    gen = slow_host_reader(n_batches, host_ms)
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        exe.run(main_p, feed=next(iter(gen())), fetch_list=[loss])  # compile
        np.asarray(pt.global_scope().find_var(drain))
        profiler.stage_counters(reset=True)

        def epoch():
            if pipelined:
                pt.flags.set_flags({"max_inflight_steps": window})
                for feed in DeviceLoader(gen, depth=window):
                    exe.run_async(main_p, feed=feed, fetch_list=[loss])
                exe.wait()
            else:
                for feed in gen():
                    (lv,) = exe.run(main_p, feed=feed, fetch_list=[loss])
                    float(np.asarray(lv))  # the per-step host drain
            np.asarray(pt.global_scope().find_var(drain))

        dt, _ = _timing.time_call(epoch)  # shared tools/ timing protocol
    counters = {k: round(v["seconds"], 4)
                for k, v in profiler.stage_counters(reset=True).items()}
    return n_batches * BATCH / dt, counters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--host-ms", type=float, default=4.0)
    ap.add_argument("--batches", type=int, default=60)
    ap.add_argument("--window", type=int, default=4)
    args = ap.parse_args()

    from paddle_tpu import compile_cache

    compile_cache.configure()
    sync_ex_s, sync_c = run_arm(False, args.batches, args.host_ms, args.window)
    pipe_ex_s, pipe_c = run_arm(True, args.batches, args.host_ms, args.window)
    print(json.dumps({
        "metric": "pipeline_ab_examples_per_sec",
        "sync_ex_s": round(sync_ex_s, 1),
        "pipelined_ex_s": round(pipe_ex_s, 1),
        "speedup": round(pipe_ex_s / sync_ex_s, 3),
        "sync_stage_seconds": sync_c,
        "pipelined_stage_seconds": pipe_c,
        "config": {"batch": BATCH, "batches": args.batches,
                   "host_ms": args.host_ms, "window": args.window},
    }))


if __name__ == "__main__":
    main()
