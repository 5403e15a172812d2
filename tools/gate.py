"""Pre-snapshot gate: the round may not end on a red suite (VERDICT r3 #3).

Runs the full pytest suite plus the single-chip compile check and exits
non-zero on ANY failure, printing the failing node ids. Also inspects the
newest BENCH_r*.json artifact: a DeepFM end-to-end/device-path ratio below
0.9 means the async feed/dispatch pipeline regressed (the end-to-end path is
leaving device throughput on the table) and fails the gate. Run it before
every end-of-round snapshot commit:

    python tools/gate.py                   # full gate (suite + entry + bench)
    python tools/gate.py --fast            # suite only
    python tools/gate.py --bench FILE.json # check one bench artifact only
    python tools/gate.py --multichip [F]   # multichip campaign artifact only
                                           # (scaling-efficiency floor, loss
                                           # parity drift, overlap A/B)
    python tools/gate.py --chaos           # chaos smoke only (`-m chaos`:
                                           # fault-injection + SIGKILL-
                                           # trainer liveness subset)
    python tools/gate.py --kernels         # Pallas kernel-registry lint
                                           # only (reference + equivalence
                                           # test + tuner key per kernel)
    python tools/gate.py --obs [F.json]    # telemetry block only (registry
                                           # overhead ceiling, metric-name
                                           # schema drift, missing block)
    python tools/gate.py --costmodel       # learned cost model only: the
                                           # committed model must beat the
                                           # analytic prior on its holdout
                                           # keys, and the newest bench's
                                           # learned fallback rate must stay
                                           # under the ceiling
    python tools/gate.py --fleet [F.json]  # serving-fleet campaign artifact
                                           # only (SIGKILL arm hard zeros,
                                           # scaling floor, drain-and-retire,
                                           # bounded kill-arm TTFT)
    python tools/gate.py --disagg [F.json] # disaggregated-serving campaign
                                           # artifact only (handoff hard
                                           # zeros, bounded split-arm TTFT
                                           # vs co-located, >= 1 reaped
                                           # lease + replay in the kill arm)
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# below this, train_from_dataset is losing >10% of the measured device-path
# throughput to the host pipeline — the regression the prefetch/async-window
# subsystem exists to prevent (ISSUE 2 acceptance line)
DEEPFM_RATIO_FLOOR = 0.9

# the in-graph health sentinel (FLAGS_guard_numerics) must stay ~free: above
# this, the guard itself is the perf bug (ISSUE 4 acceptance line)
GUARD_OVERHEAD_CEIL_PCT = 2.0

# ResNet-50 is the round-6 campaign metric (ISSUE 5): flag any artifact whose
# resnet50 vs_target falls more than the interference band below the previous
# round's — a conv-lowering/BN regression, not box noise (single bursts move
# one window, not the best-of-3 protocol, PERF.md r4/r5)
RESNET_VS_TARGET_DROP = 0.95

# a consult-mode bench whose workload resolved mostly off the swept DB is
# running untuned — the DB is stale for these shapes (re-sweep with
# tools/tune.py) or keyed for another device (ISSUE 6 acceptance line).
# Since the learned tier (ISSUE 15) a model prediction counts as tuned too:
# the floor applies to tuned_rate ((db + learned) / decisions) when the
# artifact carries it, hit_rate on older snapshots.
TUNER_HIT_RATE_FLOOR = 0.5

# learned cost model (ISSUE 15): the committed artifact must keep ranking
# arms on its recorded holdout keys well enough to be worth a policy tier —
# below this floor (or below the analytic prior it is supposed to beat),
# the model is stale for the committed dataset; retrain with
# tools/costmodel.py train. The floor sits under the committed model's
# measured 1.0 so box-to-box eval noise does not flap the gate.
COSTMODEL_RANK_ACC_FLOOR = 0.75
COSTMODEL_DATA = "COSTMODEL_DATA_cpu.jsonl"
COSTMODEL_MODEL = "COSTMODEL_cpu.json"

# a consult/explore bench whose learned tier mostly fell through its
# confidence gate is carrying a model that no longer covers the workload's
# shapes (feature envelope drift, accuracy collapse) — above this fallback
# rate the tier is dead weight; retrain on a fresher measurement store.
LEARNED_FALLBACK_CEIL = 0.9

# serving runtime (ISSUE 7): flag an artifact whose open-loop served
# tokens/s falls more than this factor below the previous round's — the
# open-loop workload is seeded/identical every round, so a drop this size
# is a scheduler/kernel regression, not arrival noise. Leaked KV pages are
# a hard fail at any count: the pool never reclaims them.
SERVING_TOK_S_DROP = 0.8

# multi-tenant serving (ISSUE 11): when the shared-prefix mix runs, the
# prefix cache must actually be absorbing prefill — a hit rate below this
# floor on the zipf system-prompt workload means the page-granular index
# is broken (mis-keyed blocks, over-eager eviction), since the workload is
# built to reuse 8 templates. Refcount leaks (pages still off the free
# list after drain + cache flush) are a hard fail at any count in ANY arm.
PREFIX_HIT_RATE_FLOOR = 0.5

# serving resilience (ISSUE 14): under the 10x overload arm the engine must
# KEEP its goodput (finished-request tokens/s) by shedding — below this
# fraction of the unloaded arm's goodput, admission control is thrashing
# instead of protecting. Same floor for the faulted arm vs the overload
# arm: supervised recovery (retries, pool rebuild, replay) must cost
# bounded work, not eat the engine. Leaks hard-fail at any count in ANY
# arm — shed/expire/recovery are exactly the paths that lose pages.
OVERLOAD_GOODPUT_FLOOR = 0.7
# admitted requests' p99 TTFT under overload may not blow past this
# multiple of the unloaded arm's: shedding exists precisely so the work
# that IS admitted still sees bounded latency (unbounded queueing is the
# collapse mode the floors are armed against)
OVERLOAD_TTFT_CEIL_RATIO = 50.0

# tiered embedding engine (ISSUE 10): parameter parity vs the dense-lookup
# oracle is a hard correctness invariant — the tiered path is a data-movement
# refactor, any drift beyond float associativity means a lost update
# (write-back / install / scatter bug), never noise.
EMB_PARITY_ATOL = 1e-4
# hit-rate floor for the seeded zipf-1.5 workload: the hot-ID cache exists to
# keep the skewed head resident, and the workload replays identically every
# round, so a drop below this is an admission/eviction regression. Warns on
# the first artifact carrying the block, gates thereafter (the ISSUE 10
# phase-in rule).
EMB_HIT_RATE_FLOOR = 0.5

# multichip scaling campaign (ISSUE 8, `gate.py --multichip`). Parity first:
# every parallel arm must land on the single-device parameter trajectory —
# drift above this is a wrong collective, not noise (measured drifts sit at
# ~3e-4, pure cross-regime float reordering).
MC_PARITY_DRIFT = 5e-3
# scaling floors. On a host-platform virtual mesh every "device" shares one
# silicon, so ideal speedup_vs_single is ~1.0 and the number measures pure
# partitioning/collective overhead; the dp shard_map arm measures ~0.13
# there, so 0.05 trips only on a real scheduling regression. On
# real chips per-device efficiency is the honest floor.
MC_CPU_SPEEDUP_FLOOR = 0.05
MC_EFFICIENCY_FLOOR = 0.5

# unified telemetry layer (ISSUE 13): the registry rides every hot loop
# (async dispatch drain, serving scheduler), so its measured cost over the
# legacy accumulators must stay ~free — same ceiling as the health sentinel
OBS_OVERHEAD_CEIL_PCT = 2.0

# serving fleet (ISSUE 16, `gate.py --fleet` over FLEET_r*.json). The hard
# zeros are unconditional: a SIGKILL mid-decode may lose NO requests and
# deliver NO duplicate tokens (the router ledger is exactly-once), the
# drain arm may shed nothing, and no surviving engine may leak a page.
# Scaling: 1 -> N replicas must deliver >= FLEET_SCALING_FLOOR x tok/s —
# but only where the box has at least one core per replica; on a smaller
# box the threaded replicas timeshare one silicon and the honest floor is
# "the fleet machinery costs bounded overhead" (the multichip CPU-mesh
# precedent), FLEET_CPU_OVERHEAD_FLOOR of the single arm.
FLEET_SCALING_FLOOR = 3.0
FLEET_CPU_OVERHEAD_FLOOR = 0.7
# the kill arm's p99 TTFT may not blow past this multiple of the healthy
# fleet arm's: discovery + replay must cost a heartbeat deadline, not a
# queueing collapse (ISSUE 16 acceptance line). Death discovery is bounded
# below by the configured heartbeat deadline — a fixed constant, not a
# performance property — so the ceiling is applied AFTER granting the kill
# arm an explicit detection budget of FLEET_DETECT_BUDGET_BEATS heartbeat
# intervals (deadline + check cadence + replay dispatch + requeue behind
# the survivor's admission window). On hardware where
# step time dominates the heartbeat the budget is negligible and the pure
# ratio governs; on a CPU box with ~10ms TTFTs it keeps the check honest
# instead of impossible.
FLEET_TTFT_CEIL_RATIO = 2.0
FLEET_DETECT_BUDGET_BEATS = 4.0

# disaggregated serving (ISSUE 19, `gate.py --disagg` over DISAGG_r*.json).
# Hard zeros as for the fleet: no lost requests, no duplicate tokens, no
# leaked pages, no lease left PREPARED, a clean shared-pool audit — and the
# kill arm must have exercised the machinery (>= 1 reaped lease, >= 1
# handoff replay). The split arm's p99 TTFT is bounded against co-located,
# but a bare ratio would be dishonest: the split halves the DECODE capacity
# by construction, so under open-loop load the first token queues for a
# decode slot while the co-located yardstick (all 4 replicas decoding)
# stays nearly unloaded. The ceiling therefore grants a queueing budget
# proportional to the arm's own measured wall — the scale of one
# generation wave through the halved decode stage — on top of the pure
# ratio. A genuine pathology (handoffs stalling to the lease TTL, commits
# lost and re-reaped) blows past wall-scale TTFT and still fails.
DISAGG_TTFT_CEIL_RATIO = 3.0
DISAGG_QUEUE_BUDGET_WALL_FRAC = 0.5

# learned serving control (ISSUE 20, `gate.py --control` over
# CONTROL_r*.json from tools/_serve_ab.py --control). The learned proposal
# must actually ENGAGE (tier "learned" on every bench arm — a model that
# cannot clear its own confidence gate on its own training regimes proves
# nothing), must meet-or-beat the hand config on the overloaded arms, and
# may not regress the unloaded arm beyond the near-tie band (the same 5%
# the A/B verdicts use). Shadow mode rides the serving hot path, so its
# measured cost shares the telemetry layer's ~free ceiling. The control
# group's holdout rank accuracy floor mirrors the kernel tier's: below it
# the confidence gate would (rightly) refuse every proposal. When the
# committed sweep dataset is present, the gate also retrains from it and
# requires the artifact's proposals to reproduce exactly — the training
# path is seeded-deterministic, so a mismatch means the artifact and
# dataset drifted apart.
CONTROL_WIN_FLOOR = 1.0
CONTROL_TIE_BAND = 0.05
CONTROL_RANK_ACC_FLOOR = 0.6
CONTROL_DATA = "CONTROL_DATA_cpu.jsonl"


def run_suite() -> int:
    print("[gate] running test suite ...", flush=True)
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/", "-q", "--tb=line"],
        cwd=REPO)
    if r.returncode != 0:
        print("[gate] FAIL: test suite is red — do not snapshot", flush=True)
    return r.returncode


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


def _check_kernel_ab(data: dict, label: str) -> int:
    """ISSUE 9 acceptance: a kernel arm that ENGAGED (its Pallas kernel
    actually carried the op) and lost to its kernel-off baseline beyond the
    interference band fails the gate — a kept kernel must keep earning its
    verdict end-to-end every round. Un-engaged arms (CPU rounds: dispatch
    degraded to XLA) are informational only."""
    rc = 0
    ab = data.get("bert_s128_shortattn_ab")
    if isinstance(ab, dict) and ab.get("verdict"):
        print(f"[gate] bench {label}: s128 short-attn A/B xla "
              f"{ab.get('xla_tok_s')} vs pallas {ab.get('pallas_tok_s')} "
              f"tok/s ({ab.get('verdict')}, engaged {ab.get('engaged')}, "
              f"band {ab.get('band')})", flush=True)
        if ab.get("engaged") and ab.get("verdict") == "retire":
            print("[gate] FAIL: the engaged pallas_short128 attention arm "
                  "lost to XLA beyond the interference band — retire the "
                  "swept keep (tools/tune.py --what attention) or fix the "
                  "kernel before snapshotting", flush=True)
            rc = 1
    rn = data.get("resnet50_lever_ab")
    if isinstance(rn, dict) and rn.get("epilogue_verdict"):
        print(f"[gate] bench {label}: resnet epilogue arm "
              f"{rn.get('epilogue_img_s')} img/s vs levered "
              f"{rn.get('levered_img_s')} ({rn.get('epilogue_verdict')}, "
              f"engaged {rn.get('epilogue_engaged')}, "
              f"band {rn.get('epilogue_band')})", flush=True)
        if rn.get("epilogue_engaged") and \
                rn.get("epilogue_verdict") == "retire":
            print("[gate] FAIL: the engaged fused-epilogue arm lost to its "
                  "kernel-off baseline beyond the interference band — "
                  "retire the swept keeps (tools/tune.py --what epilogue) "
                  "or fix the kernel before snapshotting", flush=True)
            rc = 1
    return rc


def run_entry() -> int:
    print("[gate] compile-checking __graft_entry__.entry() ...", flush=True)
    code = ("import __graft_entry__ as g; fn, args = g.entry(); "
            "import jax; jax.eval_shape(fn, *args); print('entry ok')")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO)
    if r.returncode != 0:
        print("[gate] FAIL: graft entry does not compile", flush=True)
    return r.returncode


def _bench_metrics(text: str) -> dict | None:
    """Extract bench.py's metrics dict from an artifact: either the raw JSON
    line bench.py prints, or the driver's wrapper object (whose "parsed"
    field — or the stdout "tail" — carries that line)."""
    try:
        data = json.loads(text)
    except ValueError:
        data = None
    if isinstance(data, dict):
        if data.get("metric"):
            return data
        if isinstance(data.get("parsed"), dict) and data["parsed"].get("metric"):
            return data["parsed"]
        text = data.get("tail", "")
    for ln in reversed(text.splitlines()):
        ln = ln.strip()
        if ln.startswith("{") and '"metric"' in ln:
            try:
                return json.loads(ln)
            except ValueError:
                continue
    return None


def _check_resnet_regression(data: dict, prev_path: str | None,
                             label: str) -> int:
    """Fail when the newest artifact's `resnet50` vs_target dropped more
    than the interference band below the previous artifact's (ISSUE 5 round
    6). Artifacts without the per-workload vs_target dict are skipped."""
    cur = (data.get("vs_target") or {}).get("resnet50")
    if cur is None or prev_path is None:
        return 0
    try:
        with open(prev_path) as f:
            prev = _bench_metrics(f.read())
    except (OSError, ValueError):
        return 0
    prev_v = ((prev or {}).get("vs_target") or {}).get("resnet50")
    if prev_v is None:
        return 0
    ab = data.get("resnet50_lever_ab")
    print(f"[gate] bench {label}: resnet50 vs_target {cur} "
          f"(prev {prev_v}{', lever A/B ' + str(ab) if ab else ''})",
          flush=True)
    if cur < RESNET_VS_TARGET_DROP * prev_v:
        print(f"[gate] FAIL: resnet50 vs_target regressed {prev_v} -> {cur} "
              f"(> {100 * (1 - RESNET_VS_TARGET_DROP):.0f}% drop) — check "
              f"resnet50_lever_ab and resnet50_windows_img_s for which arm "
              f"moved before blaming the conv lowering", flush=True)
        return 1
    return 0


def _check_tuner_coverage(data: dict, label: str) -> int:
    """Flag a consult-mode bench run whose workloads resolved mostly off
    the swept DB (ISSUE 6): decisions fell through to the analytic prior /
    default, i.e. the workload ran untuned. Artifacts without the tuning
    block (pre-tuner) and off-mode runs are skipped; a workload that made
    zero tunable decisions has nothing to tune and passes."""
    tun = data.get("tuning")
    if not isinstance(tun, dict) or tun.get("mode") not in ("consult",
                                                            "explore"):
        return 0
    rc = 0
    for wl, stats in sorted((tun.get("workloads") or {}).items()):
        n = stats.get("decisions") or 0
        # tuned_rate ((db + learned) / decisions) supersedes hit_rate once
        # the learned tier exists: a confident model prediction is a tuned
        # decision, not a fall-through. Old artifacts only carry hit_rate.
        rate = stats.get("tuned_rate")
        if rate is None:
            rate = stats.get("hit_rate")
        if n == 0 or rate is None:
            continue
        print(f"[gate] bench {label}: tuner {wl} tuned-rate {rate} "
              f"({stats.get('db_hits', 0)} db + "
              f"{stats.get('learned', 0)} learned of {n} decisions)",
              flush=True)
        if rate < TUNER_HIT_RATE_FLOOR:
            print(f"[gate] FAIL: workload '{wl}' ran mostly untuned under "
                  f"FLAGS_tuning_mode={tun.get('mode')} (tuned-rate {rate} "
                  f"< {TUNER_HIT_RATE_FLOOR}) — the DB "
                  f"({tun.get('db') or 'unset'}) is stale/mis-keyed for "
                  f"these shapes; re-sweep with tools/tune.py or run with "
                  f"tuning off", flush=True)
            rc = 1
    lr = tun.get("learned")
    if isinstance(lr, dict) and (lr.get("attempts") or 0) > 0:
        frate = lr.get("fallback_rate")
        print(f"[gate] bench {label}: learned tier fallback-rate {frate} "
              f"({lr.get('fallbacks', 0)}/{lr.get('attempts', 0)} attempts; "
              f"reasons {lr.get('fallback_reasons') or {}})", flush=True)
        if frate is not None and frate > LEARNED_FALLBACK_CEIL:
            print(f"[gate] FAIL: the learned tier fell through its "
                  f"confidence gate on {frate:.0%} of attempts "
                  f"(> {LEARNED_FALLBACK_CEIL:.0%}) — the model "
                  f"({tun.get('model') or 'unset'}) no longer covers this "
                  f"workload's shapes; retrain with tools/costmodel.py "
                  f"train on a fresher measurement store", flush=True)
            rc = 1
    return rc


def _check_shared_prefix(sv: dict, label: str) -> int:
    """Multi-tenant serving gate (ISSUE 11): over the shared-prefix zipf
    mix, refcount/page leaks hard-fail in EVERY arm (an abort path that
    frees a page another request still maps corrupts silently — the leak
    counter is the only cheap tripwire), and the prefix-cache arm's hit
    rate must clear PREFIX_HIT_RATE_FLOOR."""
    sp = sv.get("shared_prefix")
    if not isinstance(sp, dict):
        return 0
    rc = 0
    arms = sp.get("arms") or {}
    for arm, row in arms.items():
        for field in ("kv_pages_leaked", "refcount_leaks"):
            n = row.get(field)
            if n:
                print(f"[gate] FAIL: shared-prefix arm '{arm}' reports "
                      f"{field}={n} — a refcount path (share/release/COW/"
                      f"evict) is freeing or orphaning pages it must not",
                      flush=True)
                rc = 1
    hit = (arms.get("prefix") or {}).get("prefix_cache_hit_rate")
    spec = (arms.get("prefix_spec") or {}).get("spec_accept_rate")
    print(f"[gate] bench {label}: shared-prefix vs_baseline "
          f"{sp.get('vs_baseline_tok_s')}x tok/s, prefill tokens saved "
          f"{sp.get('prefill_tokens_saved')}, hit rate {hit}, "
          f"spec accept {spec}", flush=True)
    if hit is not None and hit < PREFIX_HIT_RATE_FLOOR:
        print(f"[gate] FAIL: prefix-cache hit rate {hit} < "
              f"{PREFIX_HIT_RATE_FLOOR} on the zipf shared-prefix mix — "
              f"the page-granular index is not matching the templates it "
              f"was built to share (key drift or over-eager eviction)",
              flush=True)
        rc = 1
    return rc


def _check_overload(sv: dict, label: str) -> int:
    """Serving-resilience gate (ISSUE 14) over the three-arm overload
    block: page/refcount leaks hard-fail in every arm, overload goodput
    must clear OVERLOAD_GOODPUT_FLOOR of the unloaded arm (and the faulted
    arm the same floor of the overload arm), and admitted-request p99 TTFT
    must stay within OVERLOAD_TTFT_CEIL_RATIO of unloaded. Artifacts
    predating the block are skipped."""
    ov = sv.get("overload")
    if not isinstance(ov, dict):
        return 0
    rc = 0
    arms = ov.get("arms") or {}
    for arm, row in sorted(arms.items()):
        for field in ("kv_pages_leaked", "refcount_leaks"):
            n = row.get(field)
            if n:
                print(f"[gate] FAIL: overload arm '{arm}' reports "
                      f"{field}={n} — a shed/expire/recovery path is "
                      f"freeing or orphaning pages it must not", flush=True)
                rc = 1
    g_ratio = ov.get("goodput_vs_unloaded")
    f_ratio = ov.get("faulted_vs_overload")
    t_ratio = ov.get("ttft_p99_ratio")
    print(f"[gate] bench {label}: overload goodput {g_ratio}x unloaded, "
          f"faulted {f_ratio}x overload, shed rate {ov.get('shed_rate')}, "
          f"admitted ttft p99 ratio {t_ratio}, recoveries "
          f"{(arms.get('overload_faulted') or {}).get('recovery_passes')}",
          flush=True)
    if g_ratio is not None and g_ratio < OVERLOAD_GOODPUT_FLOOR:
        print(f"[gate] FAIL: overload goodput is {g_ratio}x the unloaded "
              f"arm (floor {OVERLOAD_GOODPUT_FLOOR}) — the shed floors / "
              f"degradation ladder are thrashing the engine instead of "
              f"protecting it (check shed_rate and ladder_climbs in the "
              f"block)", flush=True)
        rc = 1
    if f_ratio is not None and f_ratio < OVERLOAD_GOODPUT_FLOOR:
        print(f"[gate] FAIL: the faulted overload arm delivers {f_ratio}x "
              f"the fault-free overload arm (floor {OVERLOAD_GOODPUT_FLOOR})"
              f" — supervised recovery (retries, pool rebuild, replay) is "
              f"costing unbounded work", flush=True)
        rc = 1
    if t_ratio is not None and t_ratio > OVERLOAD_TTFT_CEIL_RATIO:
        print(f"[gate] FAIL: admitted-request p99 TTFT under overload is "
              f"{t_ratio}x the unloaded arm (ceiling "
              f"{OVERLOAD_TTFT_CEIL_RATIO}) — admission control is letting "
              f"the queue collapse instead of shedding", flush=True)
        rc = 1
    return rc


def _check_serving(data: dict, prev_path: str | None, label: str) -> int:
    """Serving-block gate (ISSUE 7): zero KV-page leak is a hard invariant;
    served tokens/s may not drop below SERVING_TOK_S_DROP of the previous
    artifact's (both artifacts must carry the block — pre-serving rounds
    are skipped)."""
    sv = data.get("serving")
    if not isinstance(sv, dict):
        return 0
    leaked = sv.get("kv_pages_leaked")
    cur = sv.get("served_tokens_per_sec")
    lat = sv.get("request_latency") or {}
    print(f"[gate] bench {label}: serving {cur} tok/s, p50 "
          f"{lat.get('p50_ms')} ms, p99 {lat.get('p99_ms')} ms, occupancy "
          f"peak {sv.get('kv_pool_occupancy_peak')}, leaked pages {leaked}",
          flush=True)
    if leaked:
        print(f"[gate] FAIL: the KV pool leaked {leaked} pages after the "
              f"open-loop run drained — a request path (finish/abort/"
              f"preempt) is not returning pages to the free list",
              flush=True)
        return 1
    if sv.get("refcount_leaks"):
        print(f"[gate] FAIL: {sv['refcount_leaks']} pages still off the "
              f"free list after drain + prefix-cache flush — a refcount "
              f"path (share/release/COW/evict) lost track of a holder",
              flush=True)
        return 1
    rc = _check_shared_prefix(sv, label)
    if rc:
        return rc
    rc = _check_overload(sv, label)
    if rc:
        return rc
    if cur is None or prev_path is None:
        return 0
    try:
        with open(prev_path) as f:
            prev = _bench_metrics(f.read())
    except (OSError, ValueError):
        return 0
    prev_v = ((prev or {}).get("serving") or {}).get("served_tokens_per_sec")
    if prev_v is None:
        return 0
    if cur < SERVING_TOK_S_DROP * prev_v:
        print(f"[gate] FAIL: served tokens/s regressed {prev_v} -> {cur} "
              f"(> {100 * (1 - SERVING_TOK_S_DROP):.0f}% drop on the seeded "
              f"open-loop workload) — check decode_compile_buckets and "
              f"preemptions before blaming the attention kernel",
              flush=True)
        return 1
    return 0


def _check_embedding(data: dict, prev_path: str | None, label: str) -> int:
    """Embedding-cache gate (ISSUE 10): the `deepfm_giant` block's parity
    drift vs the dense-lookup oracle hard-fails above EMB_PARITY_ATOL; the
    cache hit-rate floor WARNS when the previous artifact predates the
    block (first landing) and FAILS once a prior artifact carries it."""
    blk = data.get("deepfm_giant")
    if not isinstance(blk, dict):
        return 0
    rc = 0
    parity = blk.get("parity_max_abs_diff")
    hit = blk.get("cache_hit_rate")
    print(f"[gate] bench {label}: deepfm_giant {blk.get('examples_per_sec')}"
          f" ex/s, hit-rate {hit}, parity drift {parity}, host tier "
          f"{blk.get('host_tier_bytes')} B vs budget "
          f"{blk.get('hbm_budget_mb')} MB", flush=True)
    if parity is None or parity > EMB_PARITY_ATOL:
        print(f"[gate] FAIL: tiered-embedding parameter parity drift "
              f"{parity} exceeds {EMB_PARITY_ATOL} vs the dense-lookup "
              f"oracle — an install/write-back/scatter path is losing "
              f"updates (check evictions vs writebacks in the block before "
              f"blaming the optimizer)", flush=True)
        rc = 1
    if hit is not None and hit < EMB_HIT_RATE_FLOOR:
        prev_has_block = False
        if prev_path is not None:
            try:
                with open(prev_path) as f:
                    prev = _bench_metrics(f.read())
                prev_has_block = isinstance((prev or {}).get("deepfm_giant"),
                                            dict)
            except (OSError, ValueError):
                pass
        if prev_has_block:
            print(f"[gate] FAIL: deepfm_giant cache hit-rate {hit} fell "
                  f"below {EMB_HIT_RATE_FLOOR} on the seeded zipf workload "
                  f"— the admission/eviction policy regressed (the id "
                  f"stream is identical every round)", flush=True)
            rc = 1
        else:
            print(f"[gate] WARN: deepfm_giant cache hit-rate {hit} < "
                  f"{EMB_HIT_RATE_FLOOR} on the block's first artifact — "
                  f"recorded as the baseline; this gates from the next "
                  f"round", flush=True)
    return rc


def check_multichip(path: str | None = None) -> int:
    """`--multichip`: gate the newest MULTICHIP_r*.json campaign artifact
    (ISSUE 8) the way check_bench gates BENCH — loss/parameter parity drift
    is a hard correctness fail, the per-axis scaling floor catches a
    partitioning/collective regression, and an overlap-on arm that LOSES to
    its overlap-off baseline by more than the interference band means the
    bucketing/schedule machinery regressed. Pre-campaign artifacts (parity
    dryrun only, no `scaling` block) are skipped so old snapshots stay
    green."""
    arts = sorted(glob.glob(os.path.join(REPO, "MULTICHIP_r*.json")))
    if path is None:
        if not arts:
            print("[gate] WARN: no MULTICHIP_r*.json artifact", flush=True)
            return 0
        path = arts[-1]
    label = os.path.basename(path)
    try:
        with open(path) as f:
            data = _bench_metrics(f.read())
    except (OSError, ValueError) as e:
        print(f"[gate] WARN: cannot read multichip artifact {path}: {e}",
              flush=True)
        return 0
    if not isinstance(data, dict) or "scaling" not in data:
        print(f"[gate] WARN: {label} predates the measured campaign "
              f"(no scaling block) — skipped", flush=True)
        return 0
    rc = 0
    for arm, drift in sorted((data.get("parity") or {}).items()):
        if drift is None:
            continue
        print(f"[gate] multichip {label}: parity[{arm}] drift {drift}",
              flush=True)
        if drift > MC_PARITY_DRIFT:
            print(f"[gate] FAIL: '{arm}' diverged from the single-device "
                  f"parameter trajectory (drift {drift} > {MC_PARITY_DRIFT})"
                  f" — a wrong collective/schedule, not interference noise",
                  flush=True)
            rc = 1
    cpu = str(data.get("platform", "cpu")).lower() != "tpu"
    for axis, row in sorted((data.get("scaling") or {}).items()):
        speed = row.get("speedup_vs_single")
        eff = row.get("efficiency")
        print(f"[gate] multichip {label}: {axis} {row.get('tokens_per_sec')}"
              f" tok/s, speedup {speed}, efficiency {eff} "
              f"(n={row.get('n_devices')}, band {row.get('band')})",
              flush=True)
        if cpu and speed is not None and speed < MC_CPU_SPEEDUP_FLOOR:
            print(f"[gate] FAIL: {axis} speedup_vs_single {speed} < "
                  f"{MC_CPU_SPEEDUP_FLOOR} on the virtual CPU mesh — the "
                  f"partitioned step collapsed (check the arm's band before "
                  f"blaming the collective layout)", flush=True)
            rc = 1
        if not cpu and eff is not None and eff < MC_EFFICIENCY_FLOOR:
            print(f"[gate] FAIL: {axis} scaling efficiency {eff} < "
                  f"{MC_EFFICIENCY_FLOOR} on real chips — the axis is not "
                  f"earning its devices", flush=True)
            rc = 1
    for arm, ab in sorted((data.get("overlap_ab") or {}).items()):
        print(f"[gate] multichip {label}: overlap {arm} off "
              f"{ab.get('off_tok_s')} -> on {ab.get('on_tok_s')} tok/s "
              f"({ab.get('verdict')}, band {ab.get('band')})", flush=True)
        if ab.get("verdict") == "retire":
            if arm == "dp_zero1":
                # ZeRO-1 is an opt-in MEMORY lever (FLAGS_zero1 default
                # off): its contract is opt-state HBM / |dp|, and on shared
                # silicon the extra scatter/gather ops are honest cost —
                # record the measured loss, don't block the snapshot
                print(f"[gate] WARN: zero1 measured slower than bucketed "
                      f"allreduce on this platform (expected on a virtual "
                      f"CPU mesh; the lever buys memory, not host FLOPs)",
                      flush=True)
                continue
            print(f"[gate] FAIL: overlap arm '{arm}' LOSES to its "
                  f"overlap-off baseline by more than the interference band "
                  f"— the overlap machinery itself regressed", flush=True)
            rc = 1
    return rc


def check_fleet(path: str | None = None) -> int:
    """`--fleet`: gate the newest (or given) FLEET_r*.json campaign
    artifact (ISSUE 16, tools/_serve_ab.py --fleet). Hard zeros first —
    lost requests / duplicate tokens under the mid-pass SIGKILL, shed
    requests under drain-and-retire, leaked pages on any surviving engine
    — then the scaling floor (CPU-adjusted when the box has fewer cores
    than replicas) and the kill arm's bounded p99 TTFT. The kill arm must
    actually have exercised the machinery: >= 1 discovered death and >= 1
    replayed token, or the artifact measured nothing."""
    arts = sorted(glob.glob(os.path.join(REPO, "FLEET_r*.json")))
    if path is None:
        if not arts:
            print("[gate] WARN: no FLEET_r*.json artifact", flush=True)
            return 0
        path = arts[-1]
    label = os.path.basename(path)
    try:
        with open(path) as f:
            text = f.read()
        data = json.loads(text)
    except (OSError, ValueError) as e:
        print(f"[gate] WARN: cannot read fleet artifact {path}: {e}",
              flush=True)
        return 0
    if not isinstance(data, dict) or "arms" not in data:
        print(f"[gate] WARN: {label} carries no fleet arms — skipped",
              flush=True)
        return 0
    rc = 0
    arms = data.get("arms") or {}
    for arm, row in sorted(arms.items()):
        if row.get("kv_pages_leaked"):
            print(f"[gate] FAIL: fleet arm '{arm}' leaked "
                  f"{row['kv_pages_leaked']} KV pages on a surviving "
                  f"engine — a failover/drain path lost pages", flush=True)
            rc = 1
        if row.get("replay_divergence"):
            print(f"[gate] FAIL: fleet arm '{arm}' recorded "
                  f"{row['replay_divergence']} diverging replayed tokens "
                  f"under greedy — batch-composition invariance broke",
                  flush=True)
            rc = 1
    kill = arms.get("kill") or {}
    print(f"[gate] fleet {label}: single {arms.get('single', {}).get('tok_s')}"
          f" -> fleet {arms.get('fleet4', {}).get('tok_s')} tok/s "
          f"(x{data.get('scaling_vs_single')}, {data.get('n_replicas')} "
          f"replicas on {data.get('cores')} cores); kill arm lost "
          f"{data.get('kill_lost')}, dup {data.get('kill_duplicate_tokens')}"
          f", ttft p99 x{data.get('kill_ttft_p99_ratio')}; drain shed "
          f"{data.get('drain_shed')}, retired {data.get('drain_retired')}",
          flush=True)
    if data.get("kill_lost"):
        print(f"[gate] FAIL: the SIGKILL arm LOST {data['kill_lost']} "
              f"requests — failover replay must finish every in-flight "
              f"request on a survivor", flush=True)
        rc = 1
    if data.get("kill_duplicate_tokens"):
        print(f"[gate] FAIL: the SIGKILL arm delivered "
              f"{data['kill_duplicate_tokens']} duplicate tokens — the "
              f"router ledger's exactly-once dedup regressed", flush=True)
        rc = 1
    if not kill.get("deaths") or not kill.get("replayed_tokens"):
        print(f"[gate] FAIL: the kill arm discovered "
              f"{kill.get('deaths')} deaths / replayed "
              f"{kill.get('replayed_tokens')} tokens — the fault never "
              f"engaged, the artifact measured nothing", flush=True)
        rc = 1
    if data.get("drain_shed"):
        print(f"[gate] FAIL: drain-and-retire shed {data['drain_shed']} "
              f"requests — a planned migration must hand work off, not "
              f"drop it", flush=True)
        rc = 1
    if not data.get("drain_retired"):
        print("[gate] FAIL: the drain arm never observed the retire — "
              "the DRAINING replica did not empty out", flush=True)
        rc = 1
    scaling = data.get("scaling_vs_single")
    cores = data.get("cores") or 0
    n_rep = data.get("n_replicas") or 1
    if scaling is not None:
        if cores >= n_rep and scaling < FLEET_SCALING_FLOOR:
            print(f"[gate] FAIL: 1 -> {n_rep} replicas scaled tok/s only "
                  f"{scaling}x (floor {FLEET_SCALING_FLOOR}) with "
                  f"{cores} cores available — the router/pump layer is "
                  f"serializing the fleet", flush=True)
            rc = 1
        elif cores < n_rep and scaling < FLEET_CPU_OVERHEAD_FLOOR:
            print(f"[gate] FAIL: on {cores} core(s) the {n_rep}-replica "
                  f"fleet delivers {scaling}x the single replica (floor "
                  f"{FLEET_CPU_OVERHEAD_FLOOR}) — fleet overhead is eating "
                  f"the engine, beyond honest timesharing", flush=True)
            rc = 1
    kill_p99 = ((kill.get("ttft") or {}).get("p99_ms"))
    healthy_p99 = (((arms.get("fleet4") or {}).get("ttft") or {})
                   .get("p99_ms"))
    if kill_p99 is not None and healthy_p99 is not None:
        detect_ms = FLEET_DETECT_BUDGET_BEATS * 1000.0 \
            * float(data.get("heartbeat_s") or 0.0)
        ceil_ms = FLEET_TTFT_CEIL_RATIO * healthy_p99 + detect_ms
        if kill_p99 > ceil_ms:
            print(f"[gate] FAIL: the kill arm's p99 TTFT is {kill_p99}ms vs "
                  f"a ceiling of {FLEET_TTFT_CEIL_RATIO}x the healthy fleet "
                  f"arm ({healthy_p99}ms) + a {detect_ms:g}ms detection "
                  f"budget — death discovery/replay is stalling admitted "
                  f"traffic beyond the heartbeat deadline it must cost",
                  flush=True)
            rc = 1
    return rc


def check_disagg(path: str | None = None) -> int:
    """`--disagg`: gate the newest (or given) DISAGG_r*.json campaign
    artifact (ISSUE 19, tools/_serve_ab.py --disagg). Hard zeros across
    every arm — lost requests, duplicate tokens, leaked pages, leases left
    PREPARED, shared-pool audit problems — then the split arm's bounded
    p99 TTFT vs co-located (ratio + queueing budget, see the constants)
    and proof the kill arm exercised the orphan-recovery machinery:
    >= 1 reaped lease and >= 1 handoff replay."""
    arts = sorted(glob.glob(os.path.join(REPO, "DISAGG_r*.json")))
    if path is None:
        if not arts:
            print("[gate] WARN: no DISAGG_r*.json artifact", flush=True)
            return 0
        path = arts[-1]
    label = os.path.basename(path)
    try:
        with open(path) as f:
            data = json.loads(f.read())
    except (OSError, ValueError) as e:
        print(f"[gate] WARN: cannot read disagg artifact {path}: {e}",
              flush=True)
        return 0
    if not isinstance(data, dict) or "arms" not in data:
        print(f"[gate] WARN: {label} carries no disagg arms — skipped",
              flush=True)
        return 0
    rc = 0
    arms = data.get("arms") or {}
    for arm, row in sorted(arms.items()):
        for key, what in (
                ("lost", "lost requests"),
                ("duplicate_tokens", "duplicate delivered tokens"),
                ("kv_pages_leaked", "leaked KV pages"),
                ("replay_divergence", "diverging replayed tokens"),
                ("leases_left_prepared", "leases left PREPARED")):
            if row.get(key):
                print(f"[gate] FAIL: disagg arm '{arm}' recorded "
                      f"{row[key]} {what} — the handoff protocol must "
                      f"hold its hard zeros", flush=True)
                rc = 1
        if row.get("pool_audit_problems"):
            print(f"[gate] FAIL: disagg arm '{arm}' left a dirty "
                  f"shared-pool audit: {row['pool_audit_problems'][:4]}",
                  flush=True)
            rc = 1
    kill = arms.get("kill") or {}
    print(f"[gate] disagg {label}: coloc "
          f"{arms.get('coloc', {}).get('tok_s')} -> split "
          f"{arms.get('disagg', {}).get('tok_s')} tok/s "
          f"(x{data.get('disagg_tok_s_ratio')}); ttft p99 "
          f"x{data.get('disagg_ttft_p99_ratio')}; kill arm lost "
          f"{data.get('kill_lost')}, dup "
          f"{data.get('kill_duplicate_tokens')}, reaped "
          f"{data.get('kill_reaped_leases')} lease(s), "
          f"{data.get('kill_handoff_replays')} replay(s)", flush=True)
    if not kill.get("handoff", {}).get("reaped"):
        print("[gate] FAIL: the mid-handoff kill arm reaped no lease — "
              "the orphan-recovery path never engaged, the artifact "
              "measured nothing", flush=True)
        rc = 1
    if not data.get("kill_handoff_replays"):
        print("[gate] FAIL: the kill arm replayed no handoff — a reaped "
              "lease must turn into a replay, not a lost request",
              flush=True)
        rc = 1
    coloc_p99 = ((arms.get("coloc") or {}).get("ttft") or {}).get("p99_ms")
    for arm in ("disagg", "kill"):
        row = arms.get(arm) or {}
        p99 = (row.get("ttft") or {}).get("p99_ms")
        wall_ms = 1000.0 * float(row.get("wall_s") or 0.0)
        if p99 is None or coloc_p99 is None:
            continue
        ceil_ms = (DISAGG_TTFT_CEIL_RATIO * coloc_p99
                   + DISAGG_QUEUE_BUDGET_WALL_FRAC * wall_ms)
        if p99 > ceil_ms:
            print(f"[gate] FAIL: the '{arm}' arm's p99 TTFT is {p99}ms vs "
                  f"a ceiling of {DISAGG_TTFT_CEIL_RATIO}x the co-located "
                  f"arm ({coloc_p99}ms) + a "
                  f"{DISAGG_QUEUE_BUDGET_WALL_FRAC:g}x-wall queueing "
                  f"budget ({wall_ms:g}ms wall) — handoffs are stalling "
                  f"first tokens beyond decode-slot queueing", flush=True)
            rc = 1
    return rc


def check_control(path: str | None = None) -> int:
    """`--control`: gate the newest (or given) CONTROL_r*.json artifact
    (ISSUE 20, tools/_serve_ab.py --control). Hard zeros on leaks across
    every measured engine; tier "learned" on every bench arm; overloaded
    arms meet-or-beat the hand config; the unloaded arm inside the
    near-tie band; shadow overhead under the telemetry ceiling; the
    trained group's holdout rank accuracy above the confidence floor.
    When CONTROL_DATA_cpu.jsonl is committed, retrain from it and require
    the artifact's proposals to reproduce."""
    arts = sorted(glob.glob(os.path.join(REPO, "CONTROL_r*.json")))
    if path is None:
        if not arts:
            print("[gate] WARN: no CONTROL_r*.json artifact", flush=True)
            return 0
        path = arts[-1]
    label = os.path.basename(path)
    try:
        with open(path) as f:
            data = json.loads(f.read())
    except (OSError, ValueError) as e:
        print(f"[gate] WARN: cannot read control artifact {path}: {e}",
              flush=True)
        return 0
    if not isinstance(data, dict) or "arms" not in data:
        print(f"[gate] WARN: {label} carries no control arms — skipped",
              flush=True)
        return 0
    rc = 0
    if data.get("leaked_pages") or data.get("refcount_leaks"):
        print(f"[gate] FAIL: control campaign leaked "
              f"{data.get('leaked_pages')} page(s) / "
              f"{data.get('refcount_leaks')} refcount(s) — an actuated "
              f"engine must hold the same hard zeros as a hand one",
              flush=True)
        rc = 1
    arms = data.get("arms") or {}
    for arm, row in sorted(arms.items()):
        ratio, tier = row.get("ratio"), row.get("tier")
        print(f"[gate] control {label}: arm '{arm}' tier {tier}, learned "
              f"{(row.get('learned') or {}).get('goodput_tok_s')} vs hand "
              f"{(row.get('hand') or {}).get('goodput_tok_s')} goodput "
              f"tok/s (x{ratio}), proposal [{row.get('proposal')}]",
              flush=True)
        if tier != "learned":
            print(f"[gate] FAIL: arm '{arm}' fell back to the hand tier "
                  f"({row.get('reason')}) — the model cannot clear its own "
                  f"confidence gate on a regime it was trained on; the "
                  f"sweep is too thin or the envelope too narrow",
                  flush=True)
            rc = 1
        if ratio is None:
            continue
        floor = ((1.0 - CONTROL_TIE_BAND) if arm == "unloaded"
                 else CONTROL_WIN_FLOOR)
        if ratio < floor:
            what = ("regressed the unloaded arm"
                    if arm == "unloaded" else "lost to the hand config")
            print(f"[gate] FAIL: the learned proposal {what} on '{arm}' "
                  f"(x{ratio} < {floor:g}) — a controller that serves "
                  f"fewer goodput tokens than the flags it replaces is a "
                  f"regression", flush=True)
            rc = 1
    acc = ((data.get("model") or {}).get("holdout") or {}).get("rank_acc")
    if acc is None or acc < CONTROL_RANK_ACC_FLOOR:
        print(f"[gate] FAIL: serving.control holdout rank accuracy {acc} "
              f"is under the {CONTROL_RANK_ACC_FLOOR:.0%} confidence floor "
              f"— the committed model would refuse (or mis-rank) live "
              f"proposals; widen the sweep", flush=True)
        rc = 1
    pct = (data.get("shadow") or {}).get("shadow_overhead_pct")
    if pct is None or pct > OBS_OVERHEAD_CEIL_PCT:
        print(f"[gate] FAIL: shadow-mode controller costs {pct}% of "
              f"overload goodput (> {OBS_OVERHEAD_CEIL_PCT}%) — the "
              f"observe/propose epoch landed on the serving hot path",
              flush=True)
        rc = 1
    else:
        print(f"[gate] control {label}: shadow overhead {pct}% "
              f"(<= {OBS_OVERHEAD_CEIL_PCT}%), holdout rank-acc {acc}",
              flush=True)
    rc = _control_retrain_check(data, label) or rc
    return rc


def _control_retrain_check(data: dict, label: str) -> int:
    """Determinism half of --control: retrain from the committed sweep
    dataset and require every artifact proposal to reproduce. Training is
    seeded (sorted keys, seeded permutation, closed-form ridge), so a
    mismatch is drift between the committed dataset and artifact, not
    noise."""
    data_path = os.path.join(REPO, CONTROL_DATA)
    if not os.path.exists(data_path):
        print(f"[gate] WARN: {CONTROL_DATA} not committed — skipping the "
              f"control retrain-determinism check", flush=True)
        return 0
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from paddle_tpu import flags as pt_flags
    from paddle_tpu.serving import control as sv_control
    from paddle_tpu.tuning import learned

    recs = list(learned.iter_records(data_path))
    model = learned.train_model(recs, seed=int(data.get("seed", 0)))
    rc = 0
    old_mode = pt_flags.get_flag("serve_control_mode")
    pt_flags.set_flags({"serve_control_mode": "shadow"})
    try:
        for arm, row in sorted((data.get("arms") or {}).items()):
            sig = row.get("sig")
            if not isinstance(sig, dict):
                continue
            proposal, info = sv_control.propose(sig, model=model)
            got = sv_control.knob_key(proposal)
            want = row.get("proposal")
            if got != want:
                print(f"[gate] FAIL: retraining from {CONTROL_DATA} "
                      f"proposes [{got}] for arm '{arm}' but the artifact "
                      f"recorded [{want}] — dataset and artifact drifted "
                      f"apart; re-run tools/_serve_ab.py --control",
                      flush=True)
                rc = 1
    finally:
        pt_flags.set_flags({"serve_control_mode": old_mode})
    if rc == 0:
        print(f"[gate] control {label}: proposals reproduce from "
              f"{CONTROL_DATA} ({len(recs)} rows)", flush=True)
    return rc


def _check_obs(data: dict, label: str, require: bool = False) -> int:
    """Telemetry-block gate (ISSUE 13). Three failure modes:
      * missing block (only when `require` — artifacts predating the layer
        stay green under the plain bench gate; `--obs` demands it);
      * registry overhead above OBS_OVERHEAD_CEIL_PCT — the layer rides
        every hot loop, so measurable cost is a perf bug, not a feature;
      * metric-name drift: any name the run recorded that the declared
        schema (paddle_tpu/observability/schema.py) does not list — an
        undeclared metric is a lint error, because name drift is how
        dashboards and SLO rules silently go dark."""
    blk = data.get("telemetry")
    if not isinstance(blk, dict):
        if require:
            print(f"[gate] FAIL: {label} carries no telemetry block — "
                  f"bench.py must measure the registry A/B "
                  f"(bench_telemetry) for --obs to pass", flush=True)
            return 1
        return 0
    rc = 0
    pct = blk.get("obs_overhead_pct")
    print(f"[gate] bench {label}: telemetry overhead {pct}% "
          f"(on {blk.get('examples_per_sec_obs_on')} vs off "
          f"{blk.get('examples_per_sec_obs_off')} ex/s)", flush=True)
    if pct is None or pct > OBS_OVERHEAD_CEIL_PCT:
        print(f"[gate] FAIL: the telemetry registry costs {pct}% "
              f"(> {OBS_OVERHEAD_CEIL_PCT}%) of async-dispatch throughput "
              f"— instrumentation must stay ~free; check what landed on "
              f"the per-step path (histogram in a lock? sink doing I/O "
              f"inline?) before shipping", flush=True)
        rc = 1
    undeclared = blk.get("undeclared_metrics")
    if undeclared:
        print(f"[gate] FAIL: metrics recorded outside the declared schema: "
              f"{undeclared} — declare them in paddle_tpu/observability/"
              f"schema.py (with kind + help) or fix the call site's name",
              flush=True)
        rc = 1
    names = blk.get("metric_names")
    if names:
        sys.path.insert(0, REPO)
        from paddle_tpu.observability import schema

        drift = sorted(n for n in names
                       if n.split("{")[0] not in schema.DECLARED_NAMES
                       and not n.endswith(".seconds"))
        if drift:
            print(f"[gate] FAIL: artifact metric names not in "
                  f"observability/schema.py: {drift} — schema and emitters "
                  f"drifted apart", flush=True)
            rc = 1
        else:
            print(f"[gate] bench {label}: {len(names)} metric names, all "
                  f"declared", flush=True)
    return rc


def check_obs(path: str | None = None) -> int:
    """`--obs`: gate the newest (or given) bench artifact's telemetry block
    only, and REQUIRE the block to exist."""
    arts = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    if path is None:
        if not arts:
            print("[gate] WARN: no BENCH_r*.json artifact", flush=True)
            return 0
        path = arts[-1]
    try:
        with open(path) as f:
            data = _bench_metrics(f.read())
    except (OSError, ValueError) as e:
        print(f"[gate] WARN: cannot read bench artifact {path}: {e}",
              flush=True)
        return 0
    if data is None:
        print(f"[gate] WARN: no bench metrics line in {path}", flush=True)
        return 0
    return _check_obs(data, os.path.basename(path), require=True)


def check_bench(path: str | None = None) -> int:
    """Flag a DeepFM end-to-end/device-path regression in the bench artifact.

    Pre-pipeline artifacts (no deepfm_e2e_device_ratio field) are skipped so
    the gate stays meaningful across old snapshots."""
    prev_path = None
    arts = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    if path is None:
        if not arts:
            return 0
        path = arts[-1]
    apath = os.path.abspath(path)
    if apath in arts and arts.index(apath) > 0:
        prev_path = arts[arts.index(apath) - 1]
    try:
        with open(path) as f:
            text = f.read()
        data = _bench_metrics(text)
    except (OSError, ValueError, IndexError) as e:
        print(f"[gate] WARN: cannot read bench artifact {path}: {e}",
              flush=True)
        return 0
    if data is None:
        print(f"[gate] WARN: no bench metrics line in {path}", flush=True)
        return 0
    if _check_resnet_regression(data, prev_path, os.path.basename(path)):
        return 1
    if _check_kernel_ab(data, os.path.basename(path)):
        return 1
    if _check_tuner_coverage(data, os.path.basename(path)):
        return 1
    if _check_serving(data, prev_path, os.path.basename(path)):
        return 1
    if _check_embedding(data, prev_path, os.path.basename(path)):
        return 1
    if _check_obs(data, os.path.basename(path)):
        return 1
    ratio = data.get("deepfm_e2e_device_ratio")
    if ratio is None:
        return 0  # artifact predates the pipeline ratio
    e2e = data.get("deepfm_examples_per_sec")
    dev = data.get("deepfm_device_path_examples_per_sec")
    print(f"[gate] bench {os.path.basename(path)}: DeepFM e2e/device "
          f"ratio {ratio} (e2e {e2e} ex/s, device {dev} ex/s)", flush=True)
    if ratio < DEEPFM_RATIO_FLOOR:
        print(f"[gate] FAIL: DeepFM end-to-end path delivers only "
              f"{ratio:.0%} of device-path throughput "
              f"(floor {DEEPFM_RATIO_FLOOR}) — the feed/dispatch pipeline "
              f"regressed; judge against deepfm_windows_ex_s spread "
              f"(PERF.md r5) before blaming code", flush=True)
        return 1
    guard_pct = data.get("deepfm_guard_overhead_pct")
    if guard_pct is not None:
        print(f"[gate] bench {os.path.basename(path)}: health-sentinel "
              f"overhead {guard_pct}% vs the unguarded device path",
              flush=True)
        if guard_pct > GUARD_OVERHEAD_CEIL_PCT:
            print(f"[gate] FAIL: the in-graph health sentinel costs "
                  f"{guard_pct}% (> {GUARD_OVERHEAD_CEIL_PCT}%) of device "
                  f"throughput — the guard must stay ~free; check what the "
                  f"sentinel op compiled into (and the measurement spread) "
                  f"before blaming code", flush=True)
            return 1
    return 0


def check_costmodel(data_path: str | None = None,
                    model_path: str | None = None) -> int:
    """Learned cost-model gate (ISSUE 15): the committed model artifact must
    keep beating the analytic prior on its recorded holdout keys.

    Re-scores COSTMODEL_cpu.json against COSTMODEL_DATA_cpu.jsonl with the
    same scorer tools/costmodel.py eval uses. Fails when any group's holdout
    arm-ranking accuracy drops below COSTMODEL_RANK_ACC_FLOOR or below the
    analytic prior's on the same keys (a learned tier that ranks worse than
    the formula it shadows is a regression, not a tier). Also re-checks the
    newest bench artifact's learned fallback rate (the consult-mode half of
    the acceptance line) so `--costmodel` alone covers both. Repos without
    the committed artifacts skip with a WARN — the gate stays meaningful on
    old snapshots."""
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
    # the runtime half: the newest bench artifact's learned fallback rate
    # (also enforced on --bench via _check_tuner_coverage; harmless twice)
    arts = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    if arts:
        try:
            with open(arts[-1]) as f:
                data = _bench_metrics(f.read())
        except (OSError, ValueError, IndexError):
            data = None
        if isinstance(data, dict):
            rc = _check_tuner_coverage(data, os.path.basename(arts[-1])) or rc
    return rc


def main() -> int:
    if "--obs" in sys.argv:
        arg = sys.argv[sys.argv.index("--obs") + 1:]
        return check_obs(arg[0] if arg else None)
    if "--bench" in sys.argv:
        arg = sys.argv[sys.argv.index("--bench") + 1:]
        return check_bench(arg[0] if arg else None)
    if "--multichip" in sys.argv:
        arg = sys.argv[sys.argv.index("--multichip") + 1:]
        return check_multichip(arg[0] if arg else None)
    if "--chaos" in sys.argv:
        return run_chaos()
    if "--kernels" in sys.argv:
        return check_kernel_registry()
    if "--costmodel" in sys.argv:
        return check_costmodel()
    if "--fleet" in sys.argv:
        arg = sys.argv[sys.argv.index("--fleet") + 1:]
        return check_fleet(arg[0] if arg else None)
    if "--disagg" in sys.argv:
        arg = sys.argv[sys.argv.index("--disagg") + 1:]
        return check_disagg(arg[0] if arg else None)
    if "--control" in sys.argv:
        arg = sys.argv[sys.argv.index("--control") + 1:]
        return check_control(arg[0] if arg else None)
    rc = run_suite()
    if "--fast" not in sys.argv:
        rc = rc or run_entry()
        rc = rc or check_kernel_registry()
        rc = rc or check_bench()
        rc = rc or check_multichip()
        rc = rc or check_costmodel()
        rc = rc or check_fleet()
        rc = rc or check_disagg()
        rc = rc or check_control()
    if rc == 0:
        print("[gate] OK — green suite, safe to snapshot")
    return rc


if __name__ == "__main__":
    sys.exit(main())
