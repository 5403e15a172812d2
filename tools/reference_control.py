"""The control reading of a serving cell's tolerances, through the
benchmark's own comparison: one run of `benchmark/run.py`, its sample graded
twice in the one process, as the runner grades it and again against the
reference on weights rounded to a precision below the stated one. The
second grading must come out wrong by at least one of the cell's limits.

    python tools/reference_control.py --round-to float8_e4m3fn -- \
        --workload keye_vl2_30b_a3b.docs32k.sat --seed 7 --seconds 30 --trace 0

prints, before the run's own lines, `control {...}`: the second grading's
readings and how many of the sampled requests it found wrong. For runners
whose `check_sample` reads the reference's weights through
`read_params(get, cfg, round_to=None)` (`serve_open_loop_sparse` with
`keye_lm` or `deepseek_v32_lm`, `serve_open_loop_routed` with `laguna_lm`,
`serve_open_loop` with `falcon_h1_lm`).
"""
from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark import run as bench_run
    from benchmark.harness import load_json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--round-to", default="float8_e4m3fn")
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    rest = [a for a in args.rest if a != "--"]
    here = os.path.join(bench_run.ROOT, "benchmark")
    cell = load_json(here, "workloads",
                     rest[rest.index("--workload") + 1] + ".json")
    config = cell["config"] if "--rehearse" not in rest \
        else cell.get("rehearse", {}).get("config", cell["config"])
    runner = importlib.import_module("benchmark.runners." + cell["runner"])
    reference = importlib.import_module(
        load_json(here, "configs", config + ".json")["reference"]["module"])
    check, read_params = runner.check_sample, reference.read_params

    def twice(*a, **kw):
        first = check(*a, **kw)
        reference.read_params = functools.partial(read_params,
                                                  round_to=args.round_to)
        try:
            control = check(*a, **kw)
        finally:
            reference.read_params = read_params
        control["wrong"] = len(control["wrong"])
        print("control", json.dumps({"round_to": args.round_to, **control}),
              flush=True)
        return first

    runner.check_sample = twice
    return bench_run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
