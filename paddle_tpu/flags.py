"""Global flags registry.

TPU-native equivalent of the reference's gflags hub
(/root/reference/paddle/fluid/platform/flags.cc) + the Python env bootstrap
(/root/reference/python/paddle/fluid/__init__.py:152 read_env_flags): flags are
declared here with defaults, overridden from the environment (`FLAGS_<name>`)
at import, and adjustable at runtime via `set_flags`.

Only flags that DO something on this runtime are declared; CUDA/allocator
knobs from the reference are subsumed by XLA and intentionally absent.
"""
from __future__ import annotations

import os
from typing import Any

_FLAGS: dict[str, Any] = {}
_DEFS: dict[str, tuple[type, str]] = {}


def _define(name: str, default, help: str):
    ftype = type(default)
    _DEFS[name] = (ftype, help)
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        _FLAGS[name] = _parse(ftype, env)
    else:
        _FLAGS[name] = default


def _parse(ftype, text: str):
    if ftype is bool:
        return text.strip().lower() in ("1", "true", "yes", "on")
    return ftype(text)


def get_flag(name: str):
    if name not in _FLAGS:
        raise KeyError(f"unknown flag '{name}'; known: {sorted(_FLAGS)}")
    return _FLAGS[name]


def set_flags(flags: dict):
    """Runtime override (reference fluid.core.init_gflags analogue)."""
    for k, v in flags.items():
        k = k[len("FLAGS_"):] if k.startswith("FLAGS_") else k
        if k not in _DEFS:
            raise KeyError(f"unknown flag '{k}'; known: {sorted(_DEFS)}")
        _FLAGS[k] = _parse(_DEFS[k][0], str(v)) if not isinstance(v, _DEFS[k][0]) else v


def all_flags() -> dict:
    return dict(_FLAGS)


# -- declarations ------------------------------------------------------------
_define("conv_implicit_gemm", "auto",
        "lower eligible conv2d ops as implicit-GEMM im2col matmuls: the "
        "contraction dim folds C*kh*kw (e.g. 64*9=576 — full 128-lane MXU "
        "fill where the direct conv contracts K=C=64). 'auto' (default) "
        "enables per shape where the tile-fill-vs-HBM cost model in "
        "ops/nn_ops.py predicts a win (narrow-channel convs; the model's "
        "constants are the measured PERF.md rooflines); 'on' forces every "
        "groups=1 conv (incl. 1x1-as-matmul) for A/B runs; 'off' disables")
_define("bn_fuse_stats", True,
        "fuse conv2d -> batch_norm(training) pairs into one conv2d_bn op at "
        "minimize() time (passes.fuse_conv_bn_stats): E[x]/E[x2] batch "
        "statistics are computed in the conv's epilogue from the fp32 GEMM "
        "accumulator (one pass, fp32 statistics per the AMP gray-list "
        "discipline) instead of a separate HBM traversal of the conv "
        "output — the measured 17-35%% BN-stats share of ResNet stage time "
        "(PERF.md r5)")
_define("tuning_mode", "off",
        "framework-wide autotuner (paddle_tpu/tuning/): 'off' keeps every "
        "lever on its own shape rule; 'consult' resolves tunable decisions "
        "(conv lowering, attention backend, conv+BN fusion, AMP gray ops, "
        "bucket boundaries) through the tier policy exact-DB-hit -> "
        "analytic prior -> conservative default; 'sweep' resolves "
        "analytically but records every distinct decision key into the DB "
        "as a candidate so tools/tune.py knows what to measure")
_define("tuning_db", "",
        "path of the persistent tuning decision database (schema-versioned "
        "JSON, atomic temp+rename writes; tuning/db.py). Empty = no DB: "
        "consult mode degrades to the analytic priors. A corrupt/missing "
        "file warns once and falls back to analytic — never an error")
_define("pallas_epilogue", "auto",
        "fused normalize+affine+activation(+residual) epilogue kernels "
        "(ops/pallas_kernels/epilogue.py). 'auto' (default): when "
        "FLAGS_tuning_mode is consult/sweep, minimize() rewrites eligible "
        "batch_norm/conv2d_bn/layer_norm -> activation (-> residual-add) "
        "chains into one op whose epilogue DISPATCHES through the tuning "
        "DB — the analytic prior is XLA (the plain jnp composition, "
        "bit-identical to the unfused chain), so the Pallas kernel engages "
        "only where a swept verdict keeps it; with tuning off, 'auto' "
        "changes nothing. 'on' forces the kernel wherever it can run (the "
        "A/B arms); 'off' disables the rewrite entirely")
_define("attention_force_backend", "",
        "A/B-harness override for the fused-attention dispatch: force every "
        "attention_backend decision to this arm ('xla', 'pallas_short', "
        "'pallas_short128', 'flash_bundled') regardless of the tuning DB "
        "and the analytic prior. A forced backend the platform/shape cannot "
        "run raises at dispatch (an A/B arm never times the reference under "
        "the kernel's name). Empty (default) = normal three-tier dispatch")
_define("check_nan_inf", False,
        "run eagerly and validate every op's floating outputs are finite, "
        "raising with op attribution (reference operator.cc:949)")
_define("op_callstack", True,
        "capture the Python creation stack of every Operator for error "
        "attribution (reference framework/op_call_stack.cc)")
_define("benchmark", False,
        "block on the device after every Executor.run for timing-accurate "
        "debugging (reference operator.cc:926)")
_define("profiler_dir", "/tmp/paddle_tpu_profile",
        "default trace output directory for profiler.profiler()")
# unified telemetry layer (observability/: registry, exporters, spans, SLO)
_define("obs_enable", True,
        "the observability layer's histogram/event/span machinery "
        "(observability/registry.py): ON records streaming-percentile "
        "histograms, the structured event ring, and TraceAnnotation+JSONL "
        "spans alongside every counter; OFF reduces the layer to the bare "
        "counter/gauge/stage accumulators (exactly the pre-ISSUE-13 cost — "
        "profiler.stage_counters() and the serving stats keep working "
        "either way)")
_define("obs_jsonl_dir", "",
        "directory for the JSONL telemetry stream: when set, every event "
        "and span record appends atomically to <dir>/obs.jsonl (rotated at "
        "FLAGS_obs_jsonl_rotate_mb to obs.jsonl.1). Empty (default) "
        "disables the stream; tools/obs.py tails/summarizes the file")
_define("obs_jsonl_rotate_mb", 8.0,
        "size trigger in MB for rotating the FLAGS_obs_jsonl_dir stream "
        "(os.replace to <path>.1 — the live path always holds a complete "
        "stream)")
_define("obs_prometheus_path", "",
        "when set, observability.export_prometheus() writes the registry "
        "snapshot here in Prometheus text exposition format (atomic "
        "temp+rename). Empty (default) disables the file export")
_define("obs_http_port", 0,
        "serve the live registry snapshot at http://127.0.0.1:<port>"
        "/metrics (Prometheus text) from a stdlib daemon thread; "
        "0 (default) disables the endpoint")
_define("obs_max_events", 1024,
        "capacity of the in-memory structured-event ring the registry "
        "keeps for snapshot()['events'] (the JSONL stream is unbounded; "
        "this only caps what a snapshot carries)")
_define("obs_slo_p99_ms", 0.0,
        "SLO monitor (observability/slo.py): warn/alert when the "
        "serving.request_s p99 exceeds this many milliseconds over the "
        "rolling window; <=0 (default) disables the latency rule")
_define("obs_slo_min_hit_rate", 0.0,
        "SLO monitor: warn/alert when the prefix-cache hit rate "
        "(prefix_hit_tokens over all prefill tokens) falls below this "
        "floor; <=0 (default) disables the rule")
_define("obs_slo_max_leaked_pages", 0,
        "SLO monitor: warn/alert when the serving.leaked_pages gauge "
        "exceeds this count (default 0 — any leak breaches, matching the "
        "tests' zero-leak invariant)")
# multichip collective-overlap knobs (parallel/collective.py, sharding.py,
# pipeline.py — the measured scaling campaign, see README "Multichip")
_define("allreduce_bucket_mb", 4.0,
        "gradient-bucket size in MB for the collective (shard_map) regime: "
        "GradAllReduce coalesces grads into reverse-topological buckets of "
        "about this many megabytes and inserts each bucket's mean-allreduce "
        "right where its last gradient is produced, so the reduce of "
        "already-finished buckets overlaps the remaining backward compute "
        "instead of serializing after it. <=0 restores the per-gradient "
        "allreduce inserted before the optimizer ops (the overlap-off A/B "
        "arm). Under FLAGS_tuning_mode=consult the size is resolved through "
        "the tuning DB ('collective|mesh=..|payload=..' keys, this flag is "
        "the analytic prior); tools/_mc_ab.py sweeps and records verdicts")
_define("zero1", False,
        "ZeRO-1 optimizer-state sharding for the collective regime "
        "(parallel/sharding.py apply_zero1): each eligible gradient is "
        "reduce-scattered over the data axis, the optimizer op updates only "
        "this rank's 1/nranks shard of the parameter (and of its moment "
        "accumulators), and the updated shards are allgathered back — the "
        "gathers sit at the program tail so with FLAGS_max_inflight_steps>1 "
        "they overlap the next step's first buckets. Parameters whose "
        "leading dim does not divide by nranks fall back to the bucketed "
        "allreduce path")
_define("pipeline_schedule", "1f1b",
        "default microbatch schedule for PipelineOptimizer / "
        "build_pipeline_plan when none is passed explicitly: '1f1b' "
        "(PipeDream-flush steady state — at most ~n_stages microbatches in "
        "flight, boundary stash freed as each backward completes) or "
        "'gpipe' (naive fill-drain: all forwards then all backwards, stash "
        "grows with num_microbatches). Both are numerically identical; "
        "PipelinePlan.last_bubble records the per-stage bubble accounting "
        "either way")
# async Communicator knobs (reference python/paddle/fluid/__init__.py:65-71)
_define("communicator_max_merge_var_num", 20,
        "max gradients merged into one send (reference "
        "communicator_max_merge_var_num)")
_define("communicator_send_queue_size", 20,
        "per-gradient send queue capacity; push blocks when full")
_define("communicator_independent_recv_thread", True,
        "run the parameter recv thread independently of sends")
_define("communicator_min_send_grad_num_before_recv", 20,
        "grads sent before the recv thread starts pulling params")
_define("communicator_send_wait_times", 5,
        "short waits the send thread spends collecting grads to merge")
# async feed/dispatch pipeline knobs (pipeline/, executor.run_async)
_define("max_inflight_steps", 4,
        "Executor.run_async window: dispatched-but-undrained steps allowed "
        "before the host blocks on the oldest step's completion token. "
        "1 = fully synchronous per step; <=0 = unbounded runahead")
_define("device_prefetch_depth", 2,
        "DeviceLoader: batches staged into device memory ahead of the "
        "consumer by the background transfer thread (train_from_dataset "
        "and PyReader use_double_buffer); <=0 disables device prefetch in "
        "train_from_dataset")
_define("feed_bucketing", False,
        "pad ragged tail batches up to the bucket size (DataFeeder bucket / "
        "Dataset batch_size) and attach a '<batch_mask>' row-mask feed so "
        "the (program, feed-signature) compile cache is hit instead of "
        "recompiling the last batch of every epoch; loss/metric ops must "
        "honor the mask for exact numerics (see README)")
# LLM serving runtime knobs (serving/: paged KV cache + continuous batching)
_define("serving_page_size", 16,
        "KV-cache page size in token slots (serving/kv_cache.py): every "
        "request's context is stored in fixed-size pages of the "
        "preallocated HBM pool, so no request ever owns a max-seq-len "
        "buffer. Larger pages waste tail slots; smaller pages grow the "
        "page-table/bookkeeping overhead per decode step")
_define("serving_pool_pages", 512,
        "total pages in the preallocated KV pool (per layer, K and V "
        "each). Pool bytes per layer = 2 * pages * page_size * num_heads * "
        "head_dim * dtype_size. When the free list runs dry, admission "
        "backpressures (requests queue) and mid-decode growth preempts the "
        "youngest request back to the waiting queue (recompute on "
        "re-admission)")
_define("serving_max_inflight", 8,
        "continuous-batching scheduler: max requests decoding concurrently "
        "(the decode batch bucket's ceiling). Admission stops at this many "
        "running requests even when KV pages remain")
_define("serving_sched_policy", "fcfs",
        "admission order for waiting requests: 'fcfs' (arrival order) or "
        "'sjf' (shortest context first — minimizes queue latency under "
        "mixed lengths at the cost of starving long prompts under "
        "sustained load)")
_define("serving_prefix_cache", True,
        "copy-on-write prefix caching (serving/kv_cache.PrefixCache): "
        "prompts are indexed at page granularity and later requests "
        "sharing a prefix map the cached pages with a refcount bump "
        "instead of re-prefilling; the first write to a shared page "
        "copy-on-writes it. Cached pages are evicted LRU-first under pool "
        "pressure, so the cache can only ever trade idle HBM for prefill "
        "compute")
_define("serving_draft_k", 0,
        "speculative decoding draft length (serving/engine): each decode "
        "step self-drafts k tokens per request (n-gram continuation of "
        "its own history) and verifies all k+1 positions in one batched "
        "window step — exact under greedy decoding, accepting 1..k+1 "
        "tokens per step. 0 disables (plain one-token decode)")
_define("serving_tp", 1,
        "tensor-parallel degree for the serving engine: attention heads "
        "and the KV pool shard over a `tp` device mesh "
        "(parallel/mesh.make_tp_mesh + GSPMD annotations); "
        "paged_decode_attention keys the tuning DB on the per-shard "
        "(nh/tp) shape. Must divide the model's num_heads; 1 disables")
# serving resilience knobs (deadlines, shedding, degradation, supervision —
# see README "Serving resilience")
_define("serving_deadline_s", 0.0,
        "default per-request TTL in seconds, measured from submit: a "
        "request past its deadline is expired at admission and between "
        "decode steps with every KV page returned, surfaced as the "
        "'deadline_exceeded' terminal state (partial tokens kept). "
        "Per-request `deadline_s=` on submit overrides; <=0 (default) "
        "means no deadline")
_define("serving_priority_default", 1,
        "priority class assigned to requests submitted without an explicit "
        "priority (higher = more important). Under overload the shedder "
        "evicts lowest-priority WAITING requests first; ties shed the "
        "youngest")
_define("serving_shed_occupancy", 0.0,
        "admission-control floor on KV pool occupancy: when pages_in_use / "
        "num_pages crosses this fraction, new submits shed lower-priority "
        "waiters or are rejected with a retry-after hint "
        "(AdmissionRejected) instead of queueing unboundedly. <=0 "
        "(default) disables the occupancy trigger")
_define("serving_shed_queue_depth", 0,
        "admission-control floor on waiting-queue depth: a submit that "
        "would leave more than this many requests WAITING sheds "
        "lower-priority waiters or is rejected (AdmissionRejected). <=0 "
        "(default) disables the depth trigger")
_define("serving_shed_ttft_p99_ms", 0.0,
        "SLO floor on p99 time-to-first-token in milliseconds, read from "
        "the serving.ttft_s histogram via the SloMonitor: while p99 TTFT "
        "sits above this, new submits shed or reject exactly as under the "
        "occupancy/depth triggers. Needs FLAGS_obs_enable for the "
        "histogram to populate; <=0 (default) disables the SLO trigger")
_define("serving_degrade_after", 4,
        "graceful-degradation ladder patience: consecutive overloaded "
        "scheduler steps before climbing one rung (disable speculative "
        "decode -> shrink decode lookahead -> evict prefix-cache LRU tail "
        "-> shed waiters), and consecutive calm steps before descending "
        "one. Each climb is counted (serving.ladder.*) and evented")
_define("serving_step_retries", 3,
        "engine supervisor: max attempts for one compiled "
        "prefill/decode/window/COW dispatch under the serving RetryPolicy "
        "(transient transport faults retry with millisecond backoff; the "
        "compiled step writes fixed slots so a retry is idempotent). "
        "Exhaustion triggers the recovery pass: quarantine poisoned "
        "requests, audit + rebuild the pool, replay survivors from their "
        "prompts")
_define("serving_audit_every", 16,
        "run the PagedKVPool.check_consistency invariant audit (free list "
        "and mapped ordinals partition the pool; refcounts equal live "
        "holder counts) every N scheduler steps; a dirty audit triggers "
        "the recovery pass. 1 audits every step (chaos drills); <=0 "
        "disables the periodic audit")
# serving fleet knobs (serving/fleet/: router + N engine replicas with
# failure-domain isolation — see README "Serving fleet")
_define("fleet_replicas", 1,
        "default replica count for FleetRouter(): N independent engine "
        "replicas (each its own KV pool, prefix cache, compile caches — "
        "one failure domain each) behind the health-checked router. "
        "Constructor argument overrides; 1 degenerates to a supervised "
        "single engine")
_define("fleet_heartbeat_s", 2.0,
        "per-replica heartbeat deadline in seconds: a replica whose last "
        "beat (stamped after every pump iteration, skipped by the "
        "fleet_heartbeat_slow/hang/kill fault sites) is older than this is "
        "declared DEAD and its in-flight requests fail over to survivors. "
        "Scaled by FLAGS_watchdog_scale so loaded CI boxes widen the "
        "margin without editing chaos plans; <=0 disables health checking "
        "(replicas only die by explicit retire)")
_define("fleet_failover_budget", 3,
        "max failover re-placements per request over its lifetime (the "
        "fleet RetryPolicy's max_attempts): each replica death costs the "
        "request one attempt; past the budget the request lands in the "
        "'failed' terminal state instead of hopping forever between dying "
        "replicas")
_define("fleet_affinity", True,
        "prefix-cache-affinity placement: requests hash their prompt head "
        "(FLAGS_fleet_affinity_tokens tokens) to a preferred replica so "
        "same-system-prompt traffic lands on the replica already holding "
        "those pages; an unhealthy/rejecting target degrades to "
        "least-loaded. False = pure least-loaded placement")
_define("fleet_affinity_tokens", 16,
        "prompt-head length (tokens) hashed for affinity placement; "
        "prompts shorter than this hash whole. Align to the page size so "
        "requests sharing cached pages share a routing key")
# disaggregated prefill/decode serving (serving/fleet/handoff.py — see
# README "Disaggregated serving")
_define("disagg_prefill_replicas", 0,
        "split the fleet into roles: the first N replicas become "
        "prefill-only engines and the rest decode engines, all over ONE "
        "shared PagedKVPool, with prefill->decode KV handoff via TTL'd "
        "leases (FleetRouter roles= overrides; must leave at least one "
        "decode replica). 0 = co-located serving, every replica does both "
        "stages")
_define("disagg_lease_ttl_s", 2.0,
        "KV handoff lease time-to-live in seconds: a PREPARED lease whose "
        "commit has not arrived within the TTL is reaped — its page pin "
        "returns to the shared pool and the router replays the prompt "
        "under the normal failover budget. Scaled by FLAGS_watchdog_scale "
        "(slow CI must not reap healthy handoffs); commits that lose the "
        "expiry race are rejected atomically, never half-adopted")
# tiered giant-embedding knobs (paddle_tpu/embedding/, the minimize()-time
# rewrite in passes.rewrite_tiered_embeddings — see README "Tiered
# embeddings")
_define("emb_hbm_budget_mb", 0.0,
        "per-table HBM budget in MB for embedding tables: at minimize() "
        "time every lookup_table whose table exceeds this is rewritten onto "
        "the two-tier path — host-memory shards behind a device-resident "
        "hot-ID cache sized to the budget, with miss prefetch resolved off "
        "the step on the feed pipeline. <=0 (default) disables tiering "
        "entirely: every table compiles to the existing single-gather path "
        "bitwise-unchanged")
_define("emb_cache_slots", 0,
        "hot-ID cache rows per tiered table; 0 (default) derives the slot "
        "count from FLAGS_emb_hbm_budget_mb / row bytes through the tuning "
        "DB ('embedding|table=..' keys — a swept verdict overrides the "
        "budget-derived prior). A positive value is a hard per-run force "
        "(A/B arms, tools/tune.py --what embedding)")
_define("emb_prefetch_rows", 0,
        "fixed width of the per-step miss-prefetch buffer (the install feed "
        "is part of the compile signature, so it cannot vary per batch); "
        "0 = auto — pow2 of the first batch's miss count, growing (one "
        "recompile) if a later batch overflows. A positive value forces the "
        "width; batches missing more rows still grow it rather than fail")
_define("emb_admit_min_freq", 1,
        "frequency-based cache admission: an id seen fewer than this many "
        "times total enters the cache on probation (zero accumulated "
        "frequency, first in line for eviction) instead of with its batch "
        "count — keeps one-shot ids from displacing hot rows. 1 (default) "
        "admits every miss at full weight; eviction is min-frequency with "
        "LRU tie-break either way")
_define("emb_host_shards", 1,
        "contiguous row shards per host-tier table (one numpy allocation "
        "each) — the in-process analogue of the per-pserver row partition, "
        "and the placement unit for a future multi-host tier")
_define("emb_ckpt_base_every", 4,
        "streaming delta checkpoints: a full host-tier base snapshot is "
        "written every this-many saves (atomically, to the checkpoint "
        "root); the saves between write only the rows dirtied since the "
        "base (cumulative delta in the step directory; restore = base + "
        "that one delta)")
# distributed liveness knobs (distributed/ps_rpc.py, resilience/watchdog.py)
_define("rpc_deadline", 180000,
        "pserver RPC deadline in MILLISECONDS (reference FLAGS_rpc_deadline, "
        "python/paddle/fluid/__init__.py:65-71): bounds pserver connects, "
        "every request/reply round, and — doubled, to leave the server room "
        "to evict a dead peer first — the sync barrier wait. The server's "
        "liveness monitor also derives its dead-trainer eviction deadline "
        "from this when FLAGS_heartbeat_timeout_ms is 0")
_define("heartbeat_interval_ms", 500,
        "trainer->pserver heartbeat cadence (PSClient daemon thread, "
        "auto-started at the first sync barrier); <=0 disables heartbeats")
_define("heartbeat_timeout_ms", 0,
        "server-side liveness deadline: a trainer holding up a sync round "
        "whose last heartbeat (or RPC) is older than this is EVICTED from "
        "the barrier; 0 = derive from FLAGS_rpc_deadline")
_define("watchdog_stall_s", 600.0,
        "hang watchdog window for Executor.run_async/wait completion-token "
        "drains and DeviceLoader batch waits: if no progress within this "
        "many seconds a StallError carrying the in-flight state dump is "
        "raised instead of blocking forever; <=0 disables the watchdog")
_define("watchdog_scale", 1.0,
        "global multiplier on every watchdog/heartbeat deadline "
        "(FLAGS_watchdog_stall_s windows and the fleet's "
        "FLAGS_fleet_heartbeat_s): set >1 on loaded/slow CI runners so "
        "chaos tests don't flake on scheduling noise without rewriting "
        "per-site deadlines; values <1 are clamped to 1 (the margin only "
        "ever widens)")
# resilience runtime knobs (resilience/: faults, retry, checkpoint, runner)
_define("fault_plan", "",
        "deterministic fault-injection plan for the named runtime sites "
        "(resilience/faults.py grammar, e.g. 'ckpt.write:2;ps.send:1' or "
        "'rand:p=0.1,seed=7,max=5'); empty = injection off")
# numeric guardrail knobs (resilience/guardrails.py, ops health_sentinel)
_define("guard_numerics", False,
        "append the in-graph health sentinel to every minimize(): loss "
        "finiteness, global grad norm and found_inf are computed INSIDE the "
        "compiled step (emitted with the async completion token, ~zero "
        "cost), and a non-finite/spiking step's parameter update is skipped "
        "branchlessly (the AMP found_inf skip generalized to fp32)")
_define("guard_bad_step_budget", 3,
        "StepGuard: consecutive bad (skipped) steps tolerated before the "
        "guard rewinds to the last good checkpoint; the skip itself is "
        "always in-graph and free")
_define("guard_spike_factor", 0.0,
        "health sentinel loss-spike gate: a finite loss greater than this "
        "factor times the in-graph loss EMA counts as a bad step and skips "
        "the update (e.g. 10.0); <=0 disables spike gating (non-finite "
        "gating is always on under FLAGS_guard_numerics). Baked into the "
        "program at minimize() time")
_define("guard_lr_backoff", 0.5,
        "StepGuard: multiply the learning rate by this factor after each "
        "rewind (recovery ladder: skip -> rewind -> LR backoff -> surface); "
        "1.0 disables the backoff")
_define("guard_max_rewinds", 3,
        "StepGuard: rewinds tolerated across a run before the guard stops "
        "recovering and surfaces GuardError")
_define("feed_skip_corrupt", False,
        "reader robustness: a sample/batch whose ndarray conversion raises "
        "(corrupt record) is skipped and counted on the profiler "
        "'feed.skip_corrupt' counter instead of killing the epoch "
        "(DataFeeder.feed, train_from_dataset, DeviceLoader placement)")
_define("retry_max_attempts", 4,
        "RetryPolicy: attempts per call for transient RPC/IO failures")
_define("retry_base_delay_ms", 50,
        "RetryPolicy: first backoff delay in milliseconds")
_define("retry_max_delay_ms", 2000,
        "RetryPolicy: backoff ceiling in milliseconds")
_define("retry_deadline_s", 30.0,
        "RetryPolicy: wall-clock budget for all attempts of one call; "
        "0 = unbounded")
_define("ckpt_keep_last_k", 3,
        "CheckpointManager: versioned step directories kept after GC")
_define("ckpt_save_every", 10,
        "CheckpointedRunner: checkpoint cadence in steps")
_define("runner_max_retries", 5,
        "CheckpointedRunner: per-step recovery attempts (restore+retry, "
        "cache invalidation, disable_jit) before the error surfaces")
