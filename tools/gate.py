"""Pre-snapshot gate: the round may not end on a red suite.

Every check has a live producer in the tree: the tier-1 suite as the driver
runs it, the single-chip compile check of `__graft_entry__.entry()` and the
Pallas kernel registry. Exits non-zero on any failure. Nothing here judges
a speed: speeds are `BENCHMARK.json` + `benchmark/`, recorded in
`PERF_LEDGER.jsonl`.

    python tools/gate.py             # suite + entry + kernels
    python tools/gate.py --fast      # suite only
    python tools/gate.py --chaos     # `-m chaos`: the fault-injection drills
                                     # of tools/chaos.py and the SIGKILL-
                                     # trainer liveness subset
    python tools/gate.py --kernels   # kernel-registry lint only (reference,
                                     # equivalence test, tuner key and an
                                     # on-chip case per kernel)
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


def main() -> int:
    if "--chaos" in sys.argv:
        return run_chaos()
    if "--kernels" in sys.argv:
        return check_kernel_registry()
    rc = run_suite()
    if "--fast" not in sys.argv:
        rc = rc or run_entry()
        rc = rc or check_kernel_registry()
    if rc == 0:
        print("[gate] OK — green suite, safe to snapshot")
    return rc


if __name__ == "__main__":
    sys.exit(main())
