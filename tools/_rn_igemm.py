"""ResNet-50 conv-lever A/B: implicit-GEMM lowering x fused one-pass BN stats.

END-TO-END ONLY, per the r5 methodology: chained per-op microbenches are
twice-proven poisoned on this stack (the r3 "conv ceiling" artifact and the
r5 xent harness-pollution finding, PERF.md) — every arm here is a full
framework train step timed with bench.py's own protocol (async dispatch,
drain-synchronized windows, best-of-N).

Arms:
    off   : direct conv + two-pass batch_norm (the r5 bench configuration)
    auto  : FLAGS_conv_implicit_gemm=auto (per-shape cost model) + fused BN
    igemm : implicit GEMM forced ON for every conv, two-pass BN (isolates
            the im2col lowering, including shapes the cost model rejects)
    bnfuse: direct conv + fused one-pass BN statistics (isolates the pass)

Run on the chip:  python tools/_rn_igemm.py [--iters 50]
Prints one JSON line per arm plus a summary; feed the numbers to PERF.md r6.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

import bench  # noqa: E402
from paddle_tpu import flags, tuning  # noqa: E402
from paddle_tpu.tuning.learned import store as learned_store  # noqa: E402
from tools import _timing  # noqa: E402

ARMS = {
    "off": ("off", False),
    "auto": ("auto", True),
    "igemm": ("on", False),
    "bnfuse": ("off", True),
}


def main():
    peak = bench._peak_flops(jax.devices()[0])
    results = {}
    for name, (igemm, fuse) in ARMS.items():
        flags.set_flags({"conv_implicit_gemm": igemm, "bn_fuse_stats": fuse})
        img_s, mfu, windows = bench._resnet_arm(peak)
        results[name] = {"img_s": round(img_s, 1), "mfu": round(mfu, 4),
                         "windows_img_s": windows,
                         "band": round(_timing.interference_band(windows), 4)}
        print(json.dumps({"arm": name, **results[name]}), flush=True)
        if learned_store.recording_enabled(tool=True):
            # windows are images/s; store seconds-per-image so the record
            # reads like every other timing row
            learned_store.record(
                "ab.resnet50", "workload=resnet50 lever=conv", "-",
                tuning.device_kind(), name,
                windows_s=[1.0 / w for w in windows if w > 0],
                band=results[name]["band"], source="ab")
    base = results["off"]["img_s"]
    # keep-or-retire per arm on the shared verdict rule (tools/_timing.py):
    # seconds-per-image medians, band floored at gate.py's 5%
    print(json.dumps({
        "summary": {k: round(v["img_s"] / base, 4) for k, v in results.items()},
        "verdicts": {k: _timing.ab_verdict(1.0 / base, 1.0 / v["img_s"])
                     for k, v in results.items() if k != "off"},
        "note": "ratios vs the 'off' arm; >1.0 = lever wins end-to-end",
    }), flush=True)


if __name__ == "__main__":
    main()
