"""Train / evaluate the learned cost model (paddle_tpu/tuning/learned/).

The offline half of ROADMAP item 3's measured story: the measurement store
(grown as a side effect by tools/tune.py sweeps, tools/_mc_ab.py and
explore-mode probes) is the dataset; this CLI turns it into the
trained artifact the policy's learned tier consults, and re-scores a
committed artifact so gate.py --costmodel can hold the line in CI.

Subcommands:
    collect — run a small CPU-runnable conv sweep grid purely to GROW a
              dataset (the decisions go to a scratch DB and are discarded;
              the raw windows are the product). This is how the committed
              COSTMODEL_DATA_cpu.jsonl was produced.
    train   — fit the per-(op, device_kind) ridge groups (seeded holdout
              split, numpy closed form) and write the artifact atomically.
              Deterministic: same data + same seed = byte-identical file.
    eval    — re-score a model against a dataset's RECORDED holdout keys:
              learned vs analytic arm-ranking accuracy per group (the
              gate.py --costmodel floor).
    report  — dataset inventory: records / keys / arms per group.
    propose — confidence-gated serving-knob proposal for one traffic
              regime (the serving controller's ridge tier, ISSUE 20 —
              same `propose` call the live engine uses).

Usage:
    python tools/costmodel.py collect --data COSTMODEL_DATA_cpu.jsonl
    python tools/costmodel.py train --data COSTMODEL_DATA_cpu.jsonl \\
        --out COSTMODEL_cpu.json
    python tools/costmodel.py eval --model COSTMODEL_cpu.json \\
        --data COSTMODEL_DATA_cpu.jsonl
    python tools/costmodel.py report --data COSTMODEL_DATA_cpu.jsonl
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.tuning import learned  # noqa: E402

# the collect grid: CPU-runnable conv shapes spanning the decision surface
# the PR 5 analytic model reasons over — narrow-vs-full input channels
# (im2col K-folding territory), 1x1 vs 3x3 vs strided 7x7 kernels, both
# layouts — at spatial extents small enough that a full fwd+bwd sweep of
# every arm finishes in CI time. ~40 keys x 2 arms; the seeded holdout
# carves the eval set out of these.
def _collect_grid():
    shapes = []
    for hw in (16, 32):
        for cin in (3, 8, 32, 64, 128):
            for cout in (16, 64):
                for k in (1, 3):
                    pad = k // 2
                    shapes.append((
                        f"g{hw}_c{cin}x{cout}_k{k}", 4, hw, hw, cin, cout,
                        k, k, (1, 1), [(pad, pad), (pad, pad)], (1, 1)))
    # the strided-stem family (where igemm historically flips)
    for cin in (3, 12):
        shapes.append((f"stem_c{cin}", 4, 32, 32, cin, 64, 7, 7, (2, 2),
                       [(3, 3), (3, 3)], (1, 1)))
    return shapes


def cmd_collect(args) -> int:
    from paddle_tpu import flags as pt_flags
    from paddle_tpu import tuning
    from tools import tune

    scratch_db = args.db or os.path.join(
        tempfile.mkdtemp(prefix="costmodel_collect_"), "scratch_db.json")
    pt_flags.set_flags({"tuning_db": scratch_db,
                        "tuning_measurements": args.data,
                        "tuning_record": "on"})
    grid = _collect_grid()
    if args.limit:
        grid = grid[:args.limit]
    for fmt in ("NHWC", "NCHW") if args.both_layouts else ("NHWC",):
        db = tuning.TuningDB(scratch_db)
        tune.sweep_conv(db, grid, args.dtype, args.iters, args.passes,
                        args.band, fmt=fmt)
    n = sum(1 for _ in learned.iter_records(args.data))
    print(json.dumps({"collect": "done", "data": os.path.abspath(args.data),
                      "records": n, "scratch_db": scratch_db}), flush=True)
    return 0


def cmd_train(args) -> int:
    recs = list(learned.iter_records(args.data))
    if not recs:
        print(json.dumps({"error": f"no usable records in {args.data!r}"}))
        return 1
    model = learned.train_model(recs, seed=args.seed,
                                holdout_frac=args.holdout, ridge=args.ridge)
    if not model["groups"]:
        print(json.dumps({"error": "no group had enough measured keys "
                                   "(need >= 6 keys with >= 2 arms each)"}))
        return 1
    learned.save_model(model, args.out)
    print(json.dumps({
        "trained": os.path.abspath(args.out),
        "records": len(recs),
        "groups": {g: {"n_train_keys": grp["n_train_keys"],
                       "n_holdout_keys": len(grp["holdout_keys"]),
                       "arms": sorted(grp["arms"]),
                       "holdout": grp["holdout"]}
                   for g, grp in model["groups"].items()},
    }, sort_keys=True), flush=True)
    return 0


def cmd_eval(args) -> int:
    """Re-score the model on the dataset's recorded holdout keys and print
    the learned-vs-analytic comparison gate.py --costmodel enforces.
    Exit 1 only on unusable inputs — the pass/fail policy lives in the
    gate, not here."""
    try:
        model = learned.load_model(args.model)
    except ValueError as e:
        print(json.dumps({"error": f"model {args.model!r}: {e}"}))
        return 1
    if model is None:
        print(json.dumps({"error": f"model {args.model!r}: missing"}))
        return 1
    recs = list(learned.iter_records(args.data))
    ev = learned.eval_model(model, recs)
    out = {"model": os.path.abspath(args.model),
           "data": os.path.abspath(args.data),
           "records": len(recs), "groups": {}}
    for g, r in ev["groups"].items():
        beats = (r["rank_acc"] is not None
                 and r["analytic_rank_acc"] is not None
                 and r["rank_acc"] >= r["analytic_rank_acc"])
        out["groups"][g] = {**r, "learned_beats_analytic": beats}
    print(json.dumps(out, sort_keys=True), flush=True)
    return 0


def cmd_propose(args) -> int:
    """Confidence-gated serving-knob proposal for one traffic regime —
    the CLI face of the serving controller's ridge tier (ISSUE 20).
    Operators, the control gate, and the live engine re-enter the policy
    through the same `propose` call; the regime is given in the store's
    own bucketed spelling (see serving/control/regime.py)."""
    from paddle_tpu import flags as pt_flags
    from paddle_tpu.serving import control as sv_control

    try:
        model = learned.load_model(args.model)
    except ValueError as e:
        print(json.dumps({"error": f"model {args.model!r}: {e}"}))
        return 1
    if model is None:
        print(json.dumps({"error": f"model {args.model!r}: missing"}))
        return 1
    sig = sv_control.parse_regime(args.regime)
    if sig is None:
        print(json.dumps(
            {"error": f"not a regime spelling: {args.regime!r} (fields: "
                      f"{' '.join(sv_control.REGIME_FIELDS)}, e.g. "
                      f"'rate=80 p50=32 p95=32 out=16 hit=95 occ=70 q=8 "
                      f"hr=50')"}))
        return 1
    # the policy's off-mode short circuit is a runtime safety, not a CLI
    # one: an explicit `propose` invocation always wants the model's view
    old = pt_flags.get_flag("serve_control_mode")
    pt_flags.set_flags({"serve_control_mode": "shadow"})
    try:
        proposal, info = sv_control.propose(sig, model=model,
                                            dev=args.device or None)
    finally:
        pt_flags.set_flags({"serve_control_mode": old})
    print(json.dumps({"regime": sv_control.regime_key(sig),
                      "proposal": sv_control.knob_key(proposal),
                      "knobs": proposal, "info": info}, sort_keys=True),
          flush=True)
    return 0


def cmd_report(args) -> int:
    groups: dict = {}
    n = 0
    for rec in learned.iter_records(args.data):
        n += 1
        g = groups.setdefault(f"{rec['op']}|{rec['device_kind']}", {
            "records": 0, "keys": set(), "arms": set(), "sources": set()})
        g["records"] += 1
        g["keys"].add((rec["shape_key"], rec["dtype"]))
        g["arms"].add(rec["arm"])
        g["sources"].add(rec.get("source", "?"))
    print(json.dumps({
        "data": os.path.abspath(args.data),
        "records": n,
        "groups": {g: {"records": v["records"], "keys": len(v["keys"]),
                       "arms": sorted(v["arms"]),
                       "sources": sorted(v["sources"])}
                   for g, v in sorted(groups.items())},
    }, sort_keys=True), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    pc = sub.add_parser("collect", help="grow a dataset from a conv grid")
    pc.add_argument("--data", required=True)
    pc.add_argument("--db", default="",
                    help="scratch tuning DB path (default: temp dir)")
    pc.add_argument("--dtype", default="float32")
    pc.add_argument("--iters", type=int, default=3)
    pc.add_argument("--passes", type=int, default=2)
    pc.add_argument("--band", type=float, default=0.05)
    pc.add_argument("--limit", type=int, default=0,
                    help="truncate the grid (smoke runs)")
    pc.add_argument("--both-layouts", action="store_true",
                    help="sweep NCHW in addition to NHWC")
    pc.set_defaults(fn=cmd_collect)

    pt = sub.add_parser("train", help="fit and write the model artifact")
    pt.add_argument("--data", required=True)
    pt.add_argument("--out", required=True)
    pt.add_argument("--seed", type=int, default=0)
    pt.add_argument("--holdout", type=float, default=0.25)
    pt.add_argument("--ridge", type=float, default=1.0)
    pt.set_defaults(fn=cmd_train)

    pe = sub.add_parser("eval", help="re-score a model on a dataset")
    pe.add_argument("--model", required=True)
    pe.add_argument("--data", required=True)
    pe.set_defaults(fn=cmd_eval)

    pp = sub.add_parser("propose",
                        help="serving-knob proposal for one traffic regime")
    pp.add_argument("--model", required=True)
    pp.add_argument("--regime", required=True,
                    help="bucketed regime spelling, e.g. 'rate=80 p50=32 "
                         "p95=32 out=16 hit=95 occ=70 q=8 hr=50'")
    pp.add_argument("--device", default="",
                    help="device kind group to consult (default: this host)")
    pp.set_defaults(fn=cmd_propose)

    pr = sub.add_parser("report", help="dataset inventory")
    pr.add_argument("--data", required=True)
    pr.set_defaults(fn=cmd_report)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
