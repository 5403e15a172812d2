"""The declared metric schema: every name the runtime is allowed to emit.

One flat list, imported by the registry at construction. A metric recorded
under a name that is not declared here still lands (post-mortems beat
purity), but the registry tracks it in `snapshot()["undeclared"]` and
tests/test_observability.py holds the runtime's own names to the list —
adding a counter is a schema act, not just a call site.

Kinds:
  stage     — the profiler.record_stage/bump accumulators ([events, seconds]
              pairs; the PR 2 pipeline vocabulary, kept verbatim so every
              legacy call site lands unchanged)
  counter   — monotonically increasing value, optionally labeled
  gauge     — last-set value (occupancy, rates)
  histogram — streaming distribution with p50/p95/p99 (log-spaced buckets)
  event     — structured record on the event ring / JSONL stream

The names the program gives the DEVICE's time live here too (the end of
this file): a compiled Program's module name, the mode a served stack runs
in and the pieces a stack declares. They reach a device trace through the
compiled module's `op_name` metadata (`profiler.device_time` reads them
back) and cost nothing at run time.
"""
from __future__ import annotations

import functools

from .compile_events import op_scope

STAGE, COUNTER, GAUGE, HISTOGRAM, EVENT = (
    "stage", "counter", "gauge", "histogram", "event")

# (name, kind, help, label keys)
DECLARED: list[tuple] = [
    # -- pipeline stage counters (profiler.record_stage / profiler.bump) ----
    ("pipeline.host_ingest", STAGE,
     "DeviceLoader producer: host batch materialization", ()),
    ("pipeline.device_put", STAGE,
     "host->device staging transfers (DeviceLoader / feed_placer)", ()),
    ("pipeline.prepare", STAGE,
     "Executor step before its dispatch: feed cast, signature, cache "
     "lookup, state gathering, the key's seed and counter (host work "
     "only; self time excludes pipeline.compile)", ()),
    ("pipeline.compile", STAGE,
     "Executor._compile on a signature miss (inside pipeline.prepare): "
     "block analysis and building the jitted step; tracing and the XLA "
     "compile happen in that entry's first pipeline.dispatch", ()),
    ("pipeline.dispatch", STAGE,
     "Executor compiled-step dispatch (host side of one async step)", ()),
    ("pipeline.fetch", STAGE,
     "Executor.run read-back: blocked on the device plus the "
     "device-to-host copy of the fetches", ()),
    ("pipeline.window_drain", STAGE,
     "run_async window-boundary waits on the oldest completion token", ()),
    ("feed.skip_corrupt", STAGE,
     "corrupt records skipped under FLAGS_feed_skip_corrupt", ()),
    ("emb.resolved_batches", STAGE,
     "tiered-embedding batches resolved through the hot-ID cache", ()),
    ("ps.nonfinite_drop", STAGE,
     "non-finite gradient sends dropped by the pserver", ()),
    ("comm.nonfinite_drop", STAGE,
     "non-finite gradient sends dropped by the async communicator", ()),
    # -- serving runtime (serving/engine.py) --------------------------------
    ("serving.prefills", COUNTER, "prompt prefills executed", ()),
    ("serving.decode_steps", COUNTER, "batched decode steps", ()),
    ("serving.decode_tokens", COUNTER, "tokens accepted by decode", ()),
    ("serving.decode_context_pages", COUNTER,
     "pages of context the rows of a plain decode step attended over, "
     "summed over rows and steps (x a page's K+V bytes: what decode "
     "attention had to read in one layer); under the grouped-query kernel "
     "that walks the step's list of page blocks a run of pages that rows "
     "share counted ONCE (paged_attention.walk_counts over the step's "
     "tables and lengths)", ()),
    ("serving.decode_grid_steps", COUNTER,
     "grid steps of one layer's paged decode kernel (padded rows x page "
     "blocks of the bucket; under the grouped-query kernel that walks a "
     "list, the blocks of the list), summed over plain decode steps the "
     "Pallas arm served; 0 on the XLA path (decode_context_pages over "
     "this: live pages a block)", ()),
    ("serving.preemptions", COUNTER,
     "requests preempted back to the waiting queue", ()),
    ("serving.aborts", COUNTER, "requests aborted", ()),
    ("serving.prefill_tokens_computed", COUNTER,
     "prompt tokens that actually ran through prefill compute", ()),
    ("serving.prefix_hit_tokens", COUNTER,
     "prompt tokens served from the prefix cache", ()),
    ("serving.prefix_lookups", COUNTER, "prefix-cache lookups", ()),
    ("serving.prefix_full_hits", COUNTER,
     "prompts fully covered by cached pages (zero-prefill admits)", ()),
    ("serving.cow_copies", COUNTER, "copy-on-write page copies", ()),
    ("serving.spec_steps", COUNTER, "speculative draft-verify steps", ()),
    ("serving.spec_proposed", COUNTER, "draft tokens proposed", ()),
    ("serving.spec_accepted", COUNTER, "draft tokens accepted", ()),
    ("serving.pages_in_use", GAUGE, "KV pool pages currently mapped", ()),
    ("serving.pool_occupancy", GAUGE,
     "KV pool occupancy fraction (pages_in_use / num_pages)", ()),
    ("serving.leaked_pages", GAUGE,
     "pages no live request or cache entry accounts for (must be 0)", ()),
    ("serving.queue_s", HISTOGRAM,
     "request queue time: submit -> admission", ()),
    ("serving.ttft_s", HISTOGRAM,
     "time to first token: submit -> first generated token", ()),
    ("serving.request_s", HISTOGRAM,
     "request latency: submit -> finished", ()),
    ("serving.prefill.seconds", HISTOGRAM,
     "prefill span durations (also a TraceAnnotation in XPlane): feeds, the "
     "enqueue, then the fetch and accept of the step dispatched BEFORE it "
     "(ISSUE 36); not the prefill's device time", ()),
    ("serving.decode.seconds", HISTOGRAM,
     "decode-step span durations (also a TraceAnnotation in XPlane): page "
     "growth, feeds, the enqueue, then the fetch and accept of the step "
     "dispatched BEFORE it (ISSUE 36); a step's device time is "
     "jit_serving_decode's seconds a call in profiler.device_time", ()),
    # one scheduler iteration as a span tree (ISSUE 23): every span is a
    # TraceAnnotation, a <name>.seconds histogram and a JSONL record with
    # `parent` and the root's `step`
    ("serving.step.seconds", HISTOGRAM,
     "one ServingEngine.step() iteration, the root of the tree", ()),
    ("serving.housekeeping.seconds", HISTOGRAM,
     "fault points, deadline expiry, pool audit, ladder and occupancy "
     "(two spans a step)", ()),
    ("serving.admit.seconds", HISTOGRAM,
     "the admission loop: prefix match, pin, allocate with eviction, and "
     "the prefills of what it admitted", ()),
    ("serving.admit.self_seconds", HISTOGRAM,
     "serving.admit less its serving.prefill children: the scheduler's "
     "own part of admission", ()),
    ("serving.ensure_writable.seconds", HISTOGRAM,
     "page growth, preemption and copy-on-write (with its dispatch) "
     "before a decode step", ()),
    ("serving.feed_build.seconds", HISTOGRAM,
     "building the numpy feeds of one prefill or decode step", ()),
    ("serving.accept.seconds", HISTOGRAM,
     "a fetched step's routes and selections booked, sampling, the accept "
     "loop over its rows", ()),
    ("serving.settle.seconds", HISTOGRAM,
     "the pending step accepted with nothing enqueued behind it: before a "
     "sampled or speculative step, before an abort, expiry, preemption or "
     "audit touches state, or when the engine has nothing further to "
     "dispatch (its pipeline.fetch and serving.accept sit under it)", ()),
    # -- a step's tokens read one dispatch late (ISSUE 36) ------------------
    ("serving.chain.steps_deferred", COUNTER,
     "step programs (prefill, chunk, decode) accepted after the NEXT "
     "program was enqueued: the host's work overlapped the device's", ()),
    ("serving.chain.steps_blocking", COUNTER,
     "step programs accepted with nothing enqueued behind them, by why: "
     "sampled (a row's sampler reads host logits), spec (the draft reads "
     "host history), idle (nothing further to dispatch), settle (state was "
     "about to be read or changed)", ("why",)),
    ("serving.chain.discarded_rows", COUNTER,
     "rows of an accepted step whose output was dropped: the request had "
     "stopped on eos_id while the step was in flight", ()),
    ("serving.prefill.host_seconds", HISTOGRAM,
     "serving.prefill less the seconds inside pipeline.fetch (the wait for "
     "the step before): the host's work under one prefill span", ()),
    ("serving.decode.host_seconds", HISTOGRAM,
     "serving.decode less the seconds inside pipeline.fetch (the wait for "
     "the step before): the host's work under one decode span", ()),
    ("serving.slow_step", EVENT,
     "one iteration over engine.SLOW_STEP_S: step, dur_s, self seconds by "
     "span name, gc_s, rows decoded, requests admitted", ()),
    ("serving.request", EVENT,
     "per-request lifecycle record: queued/admitted/first_token/finished/"
     "aborted/deadline_exceeded/shed/rejected/quarantined",
     ("rid", "phase")),
    # -- serving resilience (ISSUE 14: deadlines/shedding/supervision) ------
    ("serving.deadline_exceeded", COUNTER,
     "requests expired past their TTL (at admission or between steps)", ()),
    ("serving.shed", COUNTER,
     "WAITING requests shed by admission control or the ladder", ()),
    ("serving.rejects", COUNTER,
     "submits rejected with AdmissionRejected (retry-after surfaced)", ()),
    ("serving.step_retries", COUNTER,
     "compiled-step dispatch retries absorbed by the supervisor", ()),
    ("serving.recovery.passes", COUNTER,
     "engine recovery passes (quarantine + pool rebuild + replay)", ()),
    ("serving.recovery.replayed", COUNTER,
     "surviving requests replayed from their prompts by recovery", ()),
    ("serving.recovery.quarantined", COUNTER,
     "poisoned requests quarantined (aborted, pages forfeited) by "
     "recovery", ()),
    ("serving.handoff_extracts", COUNTER,
     "prefilled requests extracted HANDED_OFF for disaggregated "
     "prefill->decode transfer (ISSUE 19)", ()),
    ("serving.adopts", COUNTER,
     "lease-transferred requests adopted mid-decode from a prefill "
     "engine (prefill skipped entirely)", ()),
    ("serving.ladder.spec_off", COUNTER,
     "degradation-ladder climbs to rung 1: speculative decode off", ()),
    ("serving.ladder.lookahead_shrink", COUNTER,
     "degradation-ladder climbs to rung 2: admission reserves no decode "
     "lookahead page", ()),
    ("serving.ladder.cache_evict", COUNTER,
     "degradation-ladder climbs to rung 3: prefix-cache LRU tail "
     "evicted under pressure", ()),
    ("serving.ladder.shed", COUNTER,
     "degradation-ladder climbs to rung 4: lowest-priority waiters "
     "shed", ()),
    ("serving.ladder_rung", GAUGE,
     "current degradation-ladder rung (0 = nominal .. 4 = shedding)", ()),
    ("serving.degrade", EVENT,
     "ladder transition record (rung, direction, pressure signals)", ()),
    ("serving.recovery", EVENT,
     "recovery-pass record (reason, quarantined, replayed, problems)", ()),
    ("serving.step_retry", EVENT,
     "one absorbed dispatch retry (kind, attempt, error)", ()),
    # -- serving fleet (serving/fleet/: router + replicas, ISSUE 16) --------
    ("fleet.submits", COUNTER, "requests accepted by the fleet router", ()),
    ("fleet.finished", COUNTER, "fleet requests finished", ()),
    ("fleet.failed", COUNTER,
     "fleet requests failed: failover budget exhausted or no healthy "
     "replica left to place on", ()),
    ("fleet.sheds", COUNTER,
     "submits refused fleet-wide (EVERY healthy replica shedding)", ()),
    ("fleet.rejects", COUNTER,
     "per-replica admission rejections absorbed by re-placement", ()),
    ("fleet.failovers", COUNTER,
     "budget-consuming re-placements (replica death or rejection)", ()),
    ("fleet.handoffs", COUNTER,
     "budget-free drain handoffs of waiting work off a DRAINING replica",
     ()),
    ("fleet.deaths", COUNTER,
     "replicas declared DEAD (missed heartbeats or administrative kill)",
     ()),
    ("fleet.retires", COUNTER,
     "replicas that completed drain-and-retire", ()),
    ("fleet.replayed_tokens", COUNTER,
     "already-delivered tokens a re-placement must regenerate", ()),
    ("fleet.dedup_tokens", COUNTER,
     "regenerated tokens suppressed by the router's delivered ledger "
     "(each client token delivered exactly once)", ()),
    ("fleet.replay_divergence", COUNTER,
     "replayed positions that disagreed with the ledger (possible under "
     "temperature sampling; must be 0 under greedy)", ()),
    ("fleet.affinity_hits", COUNTER,
     "placements landing on the prompt's affinity home replica", ()),
    ("fleet.affinity_misses", COUNTER,
     "placements degraded to least-loaded (home not HEALTHY)", ()),
    ("fleet.affinity_hit_rate", GAUGE,
     "affinity_hits / (hits + misses) over the router's lifetime", ()),
    ("fleet.replicas_healthy", GAUGE, "replicas currently HEALTHY", ()),
    ("fleet.replicas_draining", GAUGE, "replicas currently DRAINING", ()),
    ("fleet.replicas_dead", GAUGE, "replicas currently DEAD", ()),
    ("fleet.replica_state", GAUGE,
     "per-replica lifecycle state (0=healthy 1=draining 2=retired 3=dead)",
     ("rid",)),
    ("fleet.drain_s", HISTOGRAM,
     "drain-and-retire duration: begin_drain -> RETIRED", ()),
    ("fleet.ttft_s", HISTOGRAM,
     "fleet-level time to first DELIVERED token (failover included)", ()),
    ("fleet.request_s", HISTOGRAM,
     "fleet-level request latency: submit -> finished", ()),
    ("fleet.replica", EVENT,
     "replica lifecycle record (healthy/draining/dead/retired/crashed)",
     ()),
    ("fleet.request", EVENT,
     "fleet request lifecycle record (placed/finished/failed/rejected/"
     "budget_exhausted/unplaceable)", ()),
    # -- disaggregated prefill/decode handoff (serving/fleet/handoff.py,
    #    ISSUE 19) -----------------------------------------------------------
    ("fleet.prefill_dispatches", COUNTER,
     "prompts dispatched to a prefill-role replica (disaggregated "
     "placement: decode home chosen, prefill stage runs first)", ()),
    ("fleet.handoff.prepared", COUNTER,
     "prefill->decode handoffs published under a lease (PREPARE)", ()),
    ("fleet.handoff.committed", COUNTER,
     "handoffs adopted by a decode engine (COMMIT: lease refcount "
     "transferred, decode resumes mid-request)", ()),
    ("fleet.handoff.commit_failed", COUNTER,
     "commits rejected: unknown lease, double commit, expiry race, or a "
     "draining/bouncing adopter", ()),
    ("fleet.handoff.released", COUNTER,
     "post-commit prefill-pin releases confirmed to the prefill side", ()),
    ("fleet.handoff.dropped", COUNTER,
     "prepared messages lost in flight (disagg_handoff_drop site): the "
     "lease stays published and the reaper recovers it at TTL", ()),
    ("fleet.handoff.replays", COUNTER,
     "handed-off requests replayed from the prompt (reaped lease, failed "
     "commit, or a death mid-handoff)", ()),
    ("fleet.handoff.s", HISTOGRAM,
     "handoff latency: lease PREPARE -> decode COMMIT", ()),
    ("fleet.handoff", EVENT,
     "handoff lifecycle record (prepared/committed/reaped/abandoned)", ()),
    ("fleet.lease.granted", COUNTER,
     "KV leases granted (page tables pinned in the shared pool)", ()),
    ("fleet.lease.reaped", COUNTER,
     "leases reclaimed: TTL expiry, abandonment, or expiry at commit", ()),
    ("fleet.lease.expired_at_commit", COUNTER,
     "commits that lost the expiry race (rejected atomically; the "
     "request replays)", ()),
    ("fleet.lease.active", GAUGE, "leases currently PREPARED", ()),
    ("fleet.lease.pinned_pages", GAUGE,
     "shared-pool pages currently pinned by leases (in transit)", ()),
    # -- per-sequence state rows and expert routing (ISSUE 25) --------------
    ("serving.state.restores", COUNTER,
     "prefix hits that resumed from a page's state row, or from a "
     "snapshot copied into the row's slot", ()),
    ("serving.state.recomputed_tokens", COUNTER,
     "cached prompt tokens re-run because a hit was cut back to the last "
     "whole page before the prompt's last token, or to the deepest block "
     "whose snapshot is held", ()),
    ("serving.moe.tokens", COUNTER,
     "tokens routed to an expert, summed over layers (prefill and decode)",
     ("expert",)),
    ("serving.moe.experts_touched", COUNTER,
     "distinct experts a layer routed to in a decode step, summed over "
     "layers and steps", ()),
    ("serving.moe.layer_steps", COUNTER,
     "layer x decode-step pairs counted in serving.moe.experts_touched: "
     "the calls of the decode expert kernel", ()),
    # -- chunked prefill and learned sparse attention (ISSUE 29) ------------
    ("serving.prefill.chunk.seconds", HISTOGRAM,
     "one window of a chunked prefill (attrs rid, chunk, tokens), under "
     "serving.prefill: a prompt of a block with prefill_chunk runs as "
     "consecutive windows of that many tokens", ()),
    ("serving.prefill.chunks", COUNTER,
     "windows run by chunked prefills (serving.prefills counts the "
     "requests)", ()),
    ("serving.sparse.context_tokens", COUNTER,
     "live cached tokens the indexer scored in decode steps, summed over "
     "rows, layers and steps (x an indexer key's bytes: what the scan had "
     "to read)", ()),
    ("serving.sparse.selected_tokens", COUNTER,
     "cached tokens decode rows attended after selection, summed over "
     "rows, layers and steps (x a token's K+V bytes: what the gather had "
     "to read)", ()),
    ("serving.sparse.layer_steps", COUNTER,
     "layer x decode-step pairs in which the indexer ran (a table wider "
     "than index_topk slots); every layer x decode-step pair of a latent "
     "family without an indexer, whose rows attend all their pages", ()),
    ("serving.sparse.kernel_layer_steps", COUNTER,
     "those of serving.sparse.layer_steps whose scores the paged Pallas "
     "kernel computed straight from the key pool (paged_indexer_scores); 0 "
     "on the XLA arm (over layer_steps: how often the kernel engages)", ()),
    # -- a latent cache row and a share of the experts (ISSUE 39) -----------
    ("serving.latent.gathered_rows", COUNTER,
     "cache rows decode rows read out of the latent pool, summed over "
     "rows, layers and steps: their selection's, or every slot of a table "
     "that fits the selection (x a row's bytes: what the gather had to "
     "read)", ()),
    ("serving.latent.attended_tokens", COUNTER,
     "live cached tokens decode rows attended in the latent, summed over "
     "rows, layers and steps (x a row's bytes, or x the absorbed form's "
     "operations a token)", ()),
    ("serving.latent.attend_kernel_layer_steps", COUNTER,
     "layer x decode-step pairs whose absorbed attention the Pallas kernel "
     "computed over the gathered rows as they lie (latent_rows_attention) "
     "or, without an indexer, over the rows' pages where they lie "
     "(paged_latent_attention); 0 on the XLA arm (over sparse.layer_steps "
     "where every step selects or none does: how often the kernel "
     "engages)", ()),
    ("serving.latent.pages_read", COUNTER,
     "pool pages the absorbed attention of decode rows WITHOUT an indexer "
     "fetched, summed over layers and steps: under paged_latent_attention "
     "a run of pages that rows share counted once (its row_groups over the "
     "step's tables and lengths), on the XLA arm every live page of every "
     "row (attended_tokens over pages_read x page_size: the sharing the "
     "steps got, 1 where each row reads its own)", ()),
    # -- a residual path of several streams (ISSUE 47) ----------------------
    ("serving.hc.mix_tokens", COUNTER,
     "(token, sub-layer) pairs whose residual streams were mapped and "
     "mixed, windows and decode steps alike: two a layer a token (x the "
     "streams' bytes read once and written once: what the mix has to "
     "move)", ()),
    ("serving.moe.routed_pairs", COUNTER,
     "(token, expert) pairs the router made, summed over layers (prefill "
     "and decode), in an engine that holds a share of the experts", ()),
    ("serving.moe.held_pairs", COUNTER,
     "those of serving.moe.routed_pairs that fell on the experts this "
     "engine holds (1 / shares of them under uniform routing)", ()),
    ("serving.moe.grouped_layer_steps", COUNTER,
     "layer x window pairs whose gated expert call took the kernel's "
     "grouped form (more rows than one token tile, on the chip): the "
     "(row, expert) pairs sorted by expert, each held expert's slabs "
     "streamed at most once", ()),
    ("serving.moe.grouped_pairs", COUNTER,
     "(row, held expert) pairs those calls multiplied, the rows of a "
     "window's padding among them", ()),
    ("serving.moe.grouped_tile_rows", COUNTER,
     "rows of the tiles those calls ran: a tile of the sorted pairs is run "
     "once by every expert with a row in it (serving.moe.grouped_pairs over "
     "this: the share of the matrix unit's rows that held a pair)", ()),
    # -- window and full attention layers over two pools (ISSUE 33) ---------
    ("serving.kv.window_release.seconds", HISTOGRAM,
     "returning to the sliding layers' pool the pages a row's window has "
     "left (under serving.ensure_writable, or a prefill chunk)", ()),
    ("serving.kv.window_pages_released", COUNTER,
     "pages of the sliding layers' pool that rows let go of because their "
     "window had moved past them", ()),
    ("serving.kv.window_row_pages", COUNTER,
     "pages of the sliding layers' pool that the live rows of a decode "
     "step have mapped, summed over rows and steps (a shared page once a "
     "row that maps it)", ()),
    ("serving.kv.global_row_pages", COUNTER,
     "pages of the full layers' pool that the live rows of a decode step "
     "have mapped, summed over rows and steps", ()),
    ("serving.kv.window_pages_in_use", GAUGE,
     "pages of the sliding layers' pool currently mapped (rows and the "
     "prefix cache)", ()),
    ("serving.kv.global_pages_in_use", GAUGE,
     "pages of the full layers' pool currently mapped, for a family with "
     "two pools (serving.pages_in_use reads the same)", ()),
    ("serving.attn.full_context_tokens", COUNTER,
     "live tokens the full-attention layers' decode calls fetched, summed "
     "over those layers and steps (x a token's K+V bytes a layer: what "
     "the kernel had to read): every row's context, or, where the kernel "
     "walks the step's list of page blocks, a run that rows share ONCE "
     "and behind it every row's own", ()),
    ("serving.attn.attended_tokens", COUNTER,
     "live tokens the rows of a decode step attended in full-attention "
     "layers, every row's own context, summed over rows, those layers and "
     "steps (over full_context_tokens: the sharing the steps got, 1 where "
     "each row reads its own)", ()),
    ("serving.attn.shared_kernel_layer_steps", COUNTER,
     "full-attention layer x decode-step pairs in which some group of "
     "rows had a shared run that the kernel read once (over "
     "full_layer_steps: how often the sharing engages)", ()),
    ("serving.attn.window_context_tokens", COUNTER,
     "live tokens the rows of a decode step attended in sliding-window "
     "layers (at most the window a row), summed over rows, those layers "
     "and steps", ()),
    ("serving.attn.full_layer_steps", COUNTER,
     "full-attention layer x decode-step pairs: the calls of the paged "
     "decode kernel for full layers", ()),
    ("serving.attn.window_layer_steps", COUNTER,
     "sliding-window layer x decode-step pairs: the calls of the paged "
     "decode kernel with a first live slot", ()),
    # -- a recurrent state in slots, snapshots in the prefix cache
    #    (ISSUE 37) ------------------------------------------------------
    ("serving.state.snapshot.seconds", HISTOGRAM,
     "enqueueing the copy of a row's slot of recurrent state into a "
     "snapshot slot of the prefix cache's, at a prefill chunk's end "
     "(under serving.prefill.chunk)", ()),
    ("serving.state.restore.seconds", HISTOGRAM,
     "enqueueing the copy of a snapshot into a resumed row's own slot "
     "(under serving.prefill)", ()),
    ("serving.state.snapshots", COUNTER,
     "snapshots of a recurrent state taken and hung on a cached block", ()),
    ("serving.state.snapshot_evictions", COUNTER,
     "snapshots the prefix cache gave up: the least recently resumed for "
     "a slot someone needed, or with their block's pages", ()),
    ("serving.state.live_slots", GAUGE,
     "slots of the state pools that running rows own", ()),
    ("serving.state.snapshot_slots", GAUGE,
     "slots of the state pools that the prefix cache holds snapshots in",
     ()),
    ("serving.ssm.decode_row_layers", COUNTER,
     "row x layer pairs whose recurrent state a decode step updated, "
     "summed over steps (x a slot's bytes, read and written: what the "
     "update had to move)", ()),
    ("serving.ssm.decode_layer_steps", COUNTER,
     "layer x decode-step pairs: the calls of the one-token state update",
     ()),
    ("serving.ssm.conv_kernel_layer_steps", COUNTER,
     "layer x decode-step pairs whose convolution the Pallas kernel ran, "
     "the tail moved on in place in its slot (conv_decode_update); 0 on "
     "the XLA arm (over ssm.decode_layer_steps: how often the kernel "
     "engages)", ()),
    ("serving.ssm.decode_pad_row_layers", COUNTER,
     "padding row x layer pairs of the decode steps whose state update the "
     "Pallas kernel ran (ssm_decode_update): rows of the step's bucket "
     "that carry no request, whose grid steps move nothing; 0 on the XLA "
     "arm (over itself + ssm.decode_row_layers: the share of a bucket's "
     "traffic the kernel does not do)", ()),
    ("serving.ssm.scan_tokens", COUNTER,
     "real token x layer pairs that prefill windows scanned", ()),
    ("serving.ssm.scan_layer_steps", COUNTER,
     "layer x window pairs: the calls of the chunked scan", ()),
    # a Kimi-Delta layer's state ("kda_moe": the same slots, snapshots and
    # restores; its convolution's kernel is booked under
    # serving.ssm.conv_kernel_layer_steps, over kda.decode_layer_steps)
    ("serving.kda.decode_row_layers", COUNTER,
     "row x Kimi-Delta layer pairs whose matrix state a decode step "
     "updated, summed over steps (x a slot's bytes, read and written: "
     "what the update had to move)", ()),
    ("serving.kda.decode_layer_steps", COUNTER,
     "Kimi-Delta layer x decode-step pairs: the calls of the one-token "
     "delta-rule update (device name kda_decode_update where the Pallas "
     "kernel runs)", ()),
    ("serving.kda.decode_pad_row_layers", COUNTER,
     "padding row x layer pairs of the decode steps whose state update the "
     "Pallas kernel ran (kda_decode_update): rows of the bucket that carry "
     "no request, whose grid steps move nothing; 0 on the XLA arm", ()),
    ("serving.kda.scan_tokens", COUNTER,
     "real token x Kimi-Delta layer pairs that prefill windows ran through "
     "the chunked form", ()),
    ("serving.kda.scan_layer_steps", COUNTER,
     "Kimi-Delta layer x window pairs: the calls of the chunked form", ()),
    # layers visited several times a token ("looped_dense"), and a pool that
    # binds the rows in flight before `max_inflight` does
    ("serving.loop.visits", COUNTER,
     "layer visits the step programs ran: a decode step or a prefill window "
     "x loop_steps x layers (each a pass over one layer's weights)", ()),
    ("serving.loop.decode_row_visits", COUNTER,
     "decode row x layer visit pairs: each one paged attention call's row, "
     "over a plane of K/V pages of its own", ()),
    ("serving.loop.exit_mass", COUNTER,
     "the exit gate's probability of leaving after each visit, summed over "
     "the tokens decode steps emitted (over serving.decode_tokens: the mean; "
     "the visits sum to 1): what a threshold under 1 would let leave where",
     ("visit",)),
    ("serving.preempted_tokens", COUNTER,
     "tokens a resumed request prefilled again after a preemption dropped "
     "its pages (its prompt and what it had produced, less what the prefix "
     "cache still held)", ()),
    ("serving.pool_bound_admissions", COUNTER,
     "admissions that had waited for pages while a row slot was free: the "
     "pool, not max_inflight, held them", ()),
    ("serving.growth_held_admissions", COUNTER,
     "admissions whose request had waited at the head of the queue although "
     "its prompt's pages were free: the pages the running rows have yet to "
     "take to their known ends (prompt_len + max_new_tokens) were reserved "
     "first", ()),
    ("serving.timeline_admissions", COUNTER,
     "admissions that the sum of the running rows' known ends would have "
     "held and the timeline of those ends let in: at every decode step up "
     "to the request's own end, the pages it and the rows hold by then fit "
     "in what the rows that leave before have returned", ()),
    # -- the host's own pauses (observability/registry._GcWatch) -------------
    ("host.gc.collections", COUNTER,
     "garbage collections by generation", ("generation",)),
    ("host.gc.seconds", HISTOGRAM,
     "seconds per collection, every generation (a generation-2 collection "
     "is also a host.gc TraceAnnotation)", ()),
    # -- set-up: the compiler's own events (observability/compile_events.py)
    # and the program's spans before a window. Named `compile.` and `setup.`
    # (and `executor.`) because the measurement boundaries clear `serving.`,
    # `pipeline.`, `host.` and `train.`: these still stand at a window's end.
    ("compile.trace.seconds", HISTOGRAM,
     "jax's trace of a jitted function to a jaxpr, every function the "
     "process compiles, top-level traces only", ()),
    ("compile.lower.seconds", HISTOGRAM, "jaxpr to MLIR module", ()),
    ("compile.backend.seconds", HISTOGRAM,
     "XLA's compile or the persistent cache's read of one executable", ()),
    ("compile.cache.hits", COUNTER,
     "executables the persistent compile cache returned", ()),
    ("compile.cache.misses", COUNTER,
     "executables compiled and WRITTEN to the persistent cache (one under "
     "the cache's thresholds reads neither hit nor miss)", ()),
    ("compile.entry", EVENT,
     "one compiled function: fn (a lowered block's function name, "
     "executor.LOWERED_FN_NAMES, or `other`), name, trace_s, lower_s, "
     "backend_s (on a hit the cache's read), cache (hit | miss | off), "
     "op_s (self seconds by op path of its trace), start, end; `parent` "
     "is the span it happened under", ()),
    ("executor.first_dispatch.seconds", HISTOGRAM,
     "span: the dispatch of a compile-cache entry _prepare_step has just "
     "created (inside pipeline.dispatch; attrs program, entry): trace, "
     "lowering, compile or cache read (the compile.entry events under "
     "it), and as self time the executable's load and the enqueue", ()),
    ("setup.import.seconds", HISTOGRAM,
     "paddle_tpu/__init__.py from its first line to its last, once a "
     "process (jax's own import too where nothing loaded it before); "
     "booked, with a `setup.import` span record, when the registry is "
     "first made", ()),
    ("setup.engine_build.seconds", HISTOGRAM,
     "span: ServingEngine.__init__", ()),
    ("setup.engine_build.programs.seconds", HISTOGRAM,
     "span: the step Programs built (Python, nothing traced)", ()),
    ("setup.engine_build.startup.seconds", HISTOGRAM,
     "span: the startup Program run (weights: its compile and enqueue; "
     "the device's seconds land in the first wait after it)", ()),
    ("setup.engine_build.pools.seconds", HISTOGRAM,
     "span: the pools allocated and placed", ()),
    ("setup.decode_lattice.seconds", HISTOGRAM,
     "span: ServingEngine.warmup_decode", ()),
    ("setup.decode_lattice.entry.seconds", HISTOGRAM,
     "span: one signature of the lattice run and waited for (attrs rows, "
     "pages: the row and page bucket)", ()),
    ("setup.minimize.seconds", HISTOGRAM,
     "span: Optimizer.minimize on a static graph (passes, grad ops, clip, "
     "regularizers, update ops: Python before any trace)", ()),
    ("setup.backward.seconds", HISTOGRAM,
     "span: append_backward (inside setup.minimize)", ()),
    # -- training step telemetry (executor.py async window) -----------------
    ("train.steps", COUNTER, "async steps drained to completion", ()),
    ("train.step_latency_s", HISTOGRAM,
     "dispatch -> completion-token latency per drained step", ()),
    ("train.batches_per_sec", GAUGE,
     "train_from_dataset steady-state batch rate", ()),
    ("train.jit_compiles", COUNTER,
     "whole-block XLA compiles observed by jit_compile_counter", ()),
    # -- a training decoder's own counts (models/decoder_moe.py): vectors an
    # op writes every step, handed to the registry as device arrays
    # (`counter_defer`) and read by `snapshot()`, never inside a window
    ("train.moe.assignments", COUNTER,
     "(token, expert) pairs the routers made, every layer's (tokens x "
     "top-k x layers)", ()),
    ("train.moe.held_assignments", COUNTER,
     "those whose expert this chip holds: over train.moe.assignments the "
     "held-route share", ()),
    ("train.moe.dropped", COUNTER,
     "held pairs the grouped products were not given: 0, the experts op "
     "has no capacity", ()),
    ("train.moe.expert_tokens", COUNTER,
     "tokens each held expert computed (max over mean: the imbalance)",
     ("layer", "expert")),
    ("train.attn.key_blocks_visited", COUNTER,
     "(query block, key block) pairs the attention op computed, by layer "
     "kind (sliding | full); the dense paths off the chip visit every one",
     ("kind",)),
    ("train.attn.key_blocks_causal", COUNTER,
     "those a causal pass without a window would compute: visited over "
     "causal is what a sliding band skips", ("kind",)),
    # -- numeric guardrails (resilience/guardrails.py) ----------------------
    ("guard.events", COUNTER,
     "StepGuard verdicts by action (skip/rewind/...)", ("action",)),
    ("guard.step", EVENT,
     "structured StepGuard event (the PR 4 health-vector verdicts)", ()),
    # -- hang watchdog (resilience/watchdog.py) -----------------------------
    ("watchdog.stalls", COUNTER, "StallError raises", ()),
    ("watchdog.stall", EVENT,
     "watchdog stall dump (what/window/in-flight state)", ()),
    # -- attention dispatch (ops/attention_ops.py) --------------------------
    ("attention.dispatches", COUNTER,
     "traced attention dispatches by kind (dense/paged), the backend the "
     "decision chose and the one that ran (they differ when a swept "
     "verdict gave way to the reference)", ("kind", "chosen", "ran")),
    # -- autotuner provenance (tuning/policy.py) ----------------------------
    ("tuning.decisions", COUNTER,
     "decide() resolutions by (op, tier) — tier in db/analytic/default",
     ("op", "tier")),
    # -- tiered embeddings (embedding/engine.py) ----------------------------
    ("emb.hit_ids", COUNTER,
     "id occurrences served from the hot-ID cache", ("table",)),
    ("emb.miss_ids", COUNTER,
     "id occurrences that missed the cache (host-tier prefetch)",
     ("table",)),
    ("emb.evictions", COUNTER, "cache rows evicted (written back)",
     ("table",)),
    ("emb.writebacks", COUNTER, "dirty rows written back to the host tier",
     ("table",)),
    # -- pserver liveness (distributed/ps_rpc.py) ---------------------------
    ("ps.evictions", COUNTER,
     "trainers evicted from the sync barrier by the liveness monitor", ()),
    ("ps.rejoins", COUNTER, "evicted trainers re-admitted", ()),
    ("ps.liveness", EVENT, "evict/rejoin/grace-shutdown liveness record",
     ()),
    # -- SLO monitor (observability/slo.py) ---------------------------------
    ("slo.breaches", COUNTER, "SLO rule breaches", ("rule", "severity")),
    ("slo.breach", EVENT, "SLO breach record (rule, value, threshold)", ()),
]

DECLARED_NAMES = frozenset(spec[0] for spec in DECLARED)

# the stage names every legacy profiler.bump/record_stage call site uses —
# tests/test_observability.py greps the source tree against this set, so a
# new bump("...") literal must be declared here to stay green
STAGE_NAMES = frozenset(s[0] for s in DECLARED if s[1] == STAGE)


# -- names on the device trace ----------------------------------------------
# A device operation's path is <name scope>/<op type>[/<mode>/<piece>]
# (executor._compute_op opens the first two, a stack lowering the rest).
# tests/test_observability.py holds the literals in the tree to these lists,
# and tests/test_device_names.py the benchmark's patterns to what the
# rehearsal programs compile: dropping a name is a schema act.

# Program.name of the programs the tree builds: the executor calls a
# compiled entry's function after it, so the trace's `XLA Modules` line
# reads jit_<name> (an unnamed Program stays jit_fn)
PROGRAM_NAMES = frozenset({
    "serving_decode",    # ServingEngine: one decode or verify step
    "serving_prefill",   # a cold prompt from position 0
    "serving_window",    # suffix windows and chunks behind a cached prefix
    "serving_cow",       # copy-on-write of one page
    "serving_state_copy",  # one slot of recurrent state onto another
    "train_step",        # Optimizer.minimize: forward, backward, updates
})

# the `mode` a *_moe_stack op runs in: the first scope under its op type
STACK_MODES = frozenset({"decode", "window", "prefill", "full"})

# the pieces the three served stacks declare under their mode, one
# vocabulary for all (a block declares the pieces it has)
PIECES = frozenset({
    "embed",      # token rows of the embedding
    "proj",       # norms, the q/k/v/out products, rotary
    "kv_write",   # a step's K/V (and indexer keys) into the pools
    "indexer",    # sparse_moe: gather of the key pages and their scores
    "select",     # sparse_moe: the cut and the mask or the indices
    "kv_gather",  # rows or pages of K/V gathered out of a pool
    "latent_gather",  # latent_moe: cache rows gathered out of the pool
    "q_absorb",   # latent_moe: per-head products into and out of the latent
    "hc_map",     # latent_moe with several residual streams: a sub-layer's
                  # mappings (flat norm, projections, Sinkhorn)
    "hc_mix",     # ... the pre-mix, the residual mix and the post mix
    "shared",     # latent_moe, mixer_moe: the shared expert
    "latent_proj",  # mixer_moe: the products into and out of the experts'
                    # latent
    "attend",     # the attention itself (a Pallas kernel sits inside)
    "router",     # expert choice and combine weights
    "experts",    # the routed experts
    "dense_ffn",  # a shared expert, a dense layer
    "state",      # cca_moe: the state rows read and written
    "conv",       # parallel_ssm, mixer_moe: the depthwise convolution and
                  # its tail
    "ssm_update", # ... a decode token's state update, in place
    "ssm_scan",   # ... a window's chunked scan from its slot
    "kda_gate",   # kda_moe: a Kimi-Delta layer's decay, step and the
                  # normalised heads of q and k
    "kda_update", # ... a decode token's delta-rule update, in place
    "kda_scan",   # ... a window's chunked (WY) form from its slot
    "mlp",        # parallel_ssm, looped_dense: the layer's SwiGLU
    "qkv",        # looped_dense: a visit's pre-norm, q | k | v product and
                  # rotary
    "o_proj",     # ... the attention's output product, its norm and the
                  # residual
    "exit_gate",  # ... the norm that closes a visit and the gate's
                  # probability of stopping there
    "head",       # final norm and the vocabulary product
    "dispatch",   # a training step's experts (ops/decoder_train_ops.py):
                  # the (token, expert) pairs sorted and their rows gathered
    "combine",    # ... the pairs' products weighed back into their tokens
})


def piece(name: str):
    """`with piece("indexer"):` — `jax.named_scope` of a declared piece
    (or mode); an undeclared name raises where the program is traced. Under
    a trace its self seconds are booked like an op's, under the same path
    (`compile_events.op_scope`)."""
    if name not in PIECES and name not in STACK_MODES:
        raise ValueError(f"{name!r} is not a piece or mode declared in "
                         f"observability/schema.py")
    return op_scope(name, name)


def under_mode(stack_fn):
    """A `*_moe_stack_fn(mode, ...)` traced under the scope of its mode."""
    @functools.wraps(stack_fn)
    def scoped(mode, *args, **kwargs):
        with piece(mode):
            return stack_fn(mode, *args, **kwargs)

    return scoped
