"""Pre-snapshot gate: the round may not end on a red suite.

Every check has a live producer in the tree: the tier-1 suite as the driver
runs it, the single-chip compile check of `__graft_entry__.entry()`, the
Pallas kernel registry, and the committed learned cost model. Exits non-zero
on any failure. Nothing here judges a speed: speeds are `BENCHMARK.json` +
`benchmark/`, recorded in `PERF_LEDGER.jsonl`.

    python tools/gate.py             # suite + entry + kernels + costmodel
    python tools/gate.py --fast      # suite only
    python tools/gate.py --chaos     # `-m chaos`: the fault-injection drills
                                     # of tools/chaos.py and the SIGKILL-
                                     # trainer liveness subset
    python tools/gate.py --kernels   # kernel-registry lint only (reference,
                                     # equivalence test, tuner key and an
                                     # on-chip case per kernel)
    python tools/gate.py --costmodel # the committed model must beat the
                                     # analytic prior on its holdout keys
"""
from __future__ import annotations

import glob
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tier-1 as the driver runs it: six xdist workers, a file per worker, under
# the same time limit, so the gate and the driver count alike
TIER1_ARGS = ["-m", "pytest", "tests/", "-q", "-m", "not slow",
              "--continue-on-collection-errors", "-p", "no:cacheprovider",
              "-p", "xdist", "-n", "6", "--dist", "loadfile",
              "-p", "no:randomly"]
TIER1_ENV = {"JAX_PLATFORMS": "cpu", "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}
TIER1_LIMIT_S = 1470

# learned cost model (ISSUE 15): the committed artifact must keep ranking
# arms on its recorded holdout keys well enough to be worth a policy tier —
# below this floor (or below the analytic prior it is supposed to beat),
# the model is stale for the committed dataset; retrain with
# tools/costmodel.py train. The floor sits under the committed model's
# measured 1.0 so box-to-box eval noise does not flap the gate.
COSTMODEL_RANK_ACC_FLOOR = 0.75
COSTMODEL_DATA = "COSTMODEL_DATA_cpu.jsonl"
COSTMODEL_MODEL = "COSTMODEL_cpu.json"


def run_suite() -> int:
    print("[gate] running the tier-1 suite ...", flush=True)
    try:
        rc = subprocess.run([sys.executable] + TIER1_ARGS + ["--tb=line"],
                            cwd=REPO, env={**os.environ, **TIER1_ENV},
                            timeout=TIER1_LIMIT_S).returncode
    except subprocess.TimeoutExpired:
        rc = 124
    if rc != 0:
        print("[gate] FAIL: test suite is red — do not snapshot", flush=True)
    return rc


def run_chaos() -> int:
    """The fast chaos subset: every `chaos`-marked test (seeded fault-plan
    survival + the kill-trainer-mid-round eviction/rejoin scenario)."""
    print("[gate] running chaos smoke (-m chaos) ...", flush=True)
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/", "-q", "-m", "chaos",
         "--tb=line"],
        cwd=REPO)
    if r.returncode != 0:
        print("[gate] FAIL: chaos smoke is red — the resilience/liveness "
              "runtime regressed", flush=True)
    return r.returncode


def check_kernel_registry() -> int:
    """Pallas kernel-workbench lint (ISSUE 9): every registered kernel must
    carry (1) a callable XLA reference, (2) a shape gate, (3) a tuning-DB
    decision op with a real key speller, (4) an equivalence test that
    actually exists in tests/, and (5) an on-chip case in
    tools/kernel_check.py (the interpreter alone keeps no kernel in the
    tree) — an unmeasured or unreferenced kernel cannot land silently (the
    keep-or-retire contract made structural)."""
    sys.path.insert(0, REPO)
    from paddle_tpu import tuning
    from paddle_tpu.ops.pallas_kernels import all_kernels
    from tools import kernel_check

    # decision op -> the tuning key speller that proves the op is wired
    key_spellers = {
        "attention": tuning.attention_key,
        "epilogue": tuning.epilogue_key,
        "conv2d": tuning.conv_key,
        "moe_experts": tuning.moe_experts_key,
        "ssm_update": tuning.ssm_update_key,
    }
    test_defs = []
    for path in glob.glob(os.path.join(REPO, "tests", "*.py")):
        with open(path) as f:
            test_defs.append(f.read())
    blob = "\n".join(test_defs)
    rc = 0
    for name, spec in sorted(all_kernels().items()):
        problems = []
        if not callable(spec.reference):
            problems.append("no XLA reference")
        if not callable(spec.supported):
            problems.append("no supported() shape gate")
        if spec.decision_op not in key_spellers:
            problems.append(
                f"decision_op {spec.decision_op!r} has no tuning key "
                f"speller (known: {sorted(key_spellers)})")
        test = spec.equivalence_test or ""
        if not test or f"def {test}" not in blob:
            problems.append(
                f"equivalence test {test!r} not defined under tests/")
        if name not in kernel_check.CASES:
            problems.append("no on-chip case in tools/kernel_check.py")
        if problems:
            print(f"[gate] FAIL: pallas kernel '{name}': "
                  + "; ".join(problems), flush=True)
            rc = 1
        else:
            print(f"[gate] kernel registry: '{name}' ok "
                  f"(op={spec.decision_op}, test={test})", flush=True)
    return rc


def run_entry() -> int:
    print("[gate] compile-checking __graft_entry__.entry() ...", flush=True)
    code = ("import __graft_entry__ as g; fn, args = g.entry(); "
            "import jax; jax.eval_shape(fn, *args); print('entry ok')")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO)
    if r.returncode != 0:
        print("[gate] FAIL: graft entry does not compile", flush=True)
    return r.returncode


def check_costmodel(data_path: str | None = None,
                    model_path: str | None = None) -> int:
    """Learned cost-model gate (ISSUE 15): the committed model artifact must
    keep beating the analytic prior on its recorded holdout keys.

    Re-scores COSTMODEL_cpu.json against COSTMODEL_DATA_cpu.jsonl with the
    same scorer tools/costmodel.py eval uses. Fails when any group's holdout
    arm-ranking accuracy drops below COSTMODEL_RANK_ACC_FLOOR or below the
    analytic prior's on the same keys (a learned tier that ranks worse than
    the formula it shadows is a regression, not a tier). Repos without the
    committed artifacts skip with a WARN."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from paddle_tpu.tuning import learned

    data_path = data_path or os.path.join(REPO, COSTMODEL_DATA)
    model_path = model_path or os.path.join(REPO, COSTMODEL_MODEL)
    if not os.path.exists(data_path) or not os.path.exists(model_path):
        print(f"[gate] WARN: costmodel artifacts missing "
              f"({COSTMODEL_DATA} / {COSTMODEL_MODEL}) — skipping",
              flush=True)
        return 0
    try:
        model = learned.load_model(model_path)
    except ValueError as e:
        print(f"[gate] FAIL: committed cost model {model_path} is "
              f"unreadable ({e}) — retrain with tools/costmodel.py train",
              flush=True)
        return 1
    if model is None:
        print(f"[gate] WARN: cost model {model_path} vanished — skipping",
              flush=True)
        return 0
    recs = list(learned.iter_records(data_path))
    ev = learned.eval_model(model, recs)
    rc = 0
    if not ev["groups"]:
        print(f"[gate] FAIL: committed cost model has no evaluable group "
              f"against {os.path.basename(data_path)} — dataset/model "
              f"drifted apart; re-run tools/costmodel.py train", flush=True)
        return 1
    for g, r in sorted(ev["groups"].items()):
        acc, ana = r.get("rank_acc"), r.get("analytic_rank_acc")
        print(f"[gate] costmodel {g}: holdout rank-acc {acc} vs analytic "
              f"{ana} over {r.get('n')} keys", flush=True)
        if acc is None:
            continue
        if acc < COSTMODEL_RANK_ACC_FLOOR:
            print(f"[gate] FAIL: learned model ranks arms correctly on only "
                  f"{acc:.0%} of {g} holdout keys "
                  f"(floor {COSTMODEL_RANK_ACC_FLOOR:.0%}) — the committed "
                  f"model is stale for the committed dataset; retrain with "
                  f"tools/costmodel.py train", flush=True)
            rc = 1
        elif ana is not None and acc < ana:
            print(f"[gate] FAIL: learned model ({acc:.0%}) ranks {g} "
                  f"holdout arms WORSE than the analytic prior ({ana:.0%}) "
                  f"it is supposed to beat — the tier is a regression; "
                  f"retrain or widen the dataset", flush=True)
            rc = 1
    return rc


def main() -> int:
    if "--chaos" in sys.argv:
        return run_chaos()
    if "--kernels" in sys.argv:
        return check_kernel_registry()
    if "--costmodel" in sys.argv:
        return check_costmodel()
    rc = run_suite()
    if "--fast" not in sys.argv:
        rc = rc or run_entry()
        rc = rc or check_kernel_registry()
        rc = rc or check_costmodel()
    if rc == 0:
        print("[gate] OK — green suite, safe to snapshot")
    return rc


if __name__ == "__main__":
    sys.exit(main())
