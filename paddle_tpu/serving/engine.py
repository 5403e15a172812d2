"""Continuous-batching serving engine over the paged KV cache.

Requests arrive at any time, and the engine admits/evicts them BETWEEN
decode steps instead of running fixed generation batches:

    step():  (maybe) inject a chaos abort -> admit waiting requests while
             inflight slots allow and the pool can carry every running
             row, and the newcomer, to its known end (prefix-cache hits map
             shared pages, then prefill ONLY the uncached suffix, bucketed)
             -> grow/allocate/copy-on-write pages for the next write window
             (the pages admission reserved; the youngest request preempted
             only where a pool runs dry all the same) -> one
             ragged decode step over ALL running requests (a k-token
             draft-verify window when speculative decoding is on) ->
             retire finished rows.

The step loop reads a step's tokens ONE DISPATCH LATE (ISSUE 36). Every
step program (prefill, suffix window or last chunk, decode) is enqueued on
the device and the host does not wait for it: it keeps the step's device
handles as the one PENDING step (`_InFlight`), and right after the NEXT
program is enqueued it accepts the pending one (`pipeline.fetch` on its
tokens, routes and marked selections, then the accept loop). The one value
step n+1 needs of step n, each continuing row's input token, never leaves
the device: every program writes the token it emits to its row's slot of
`model.LAST_TOKEN`, and a decode row whose token the host has not read
takes it from there (`sv_from_host` 0). So the host builds, admits, stamps
and accepts step n while the device runs step n+1. What follows from it:

  * a row with a token in flight stands one position further
    (`GenRequest.cache_len` counts `in_flight`); routes and selections are
    booked at the positions and pages recorded at dispatch;
  * stops on length and on `max_position` are decided BEFORE dispatch, so
    such a row never runs an extra step; a stop on `eos_id` is found one
    step late, and the extra step's row is dropped at its accept
    (`serving.chain.discarded_rows`; what it wrote lies in the row's own
    page past anything the prefix cache published);
  * a step whose values the host needs before the next dispatch is
    accepted at once, chosen from the input and not by a flag: a
    non-greedy row (the host sampler reads its logits), the speculative
    path (the draft reads host history), and the last step before the
    engine has nothing further to dispatch. `abort`, preemption, deadline
    expiry, recovery, the handoff, the audits and `result` settle the
    pending step before they touch request or pool state;
  * an error that surfaces at the deferred fetch cannot be retried alone
    (step n+1 consumed step n's pools): it goes to the recovery pass, where
    an exhausted retry of the enqueue ends too.
`serving.chain.steps_deferred` / `.steps_blocking{why}` count which way
every step program was accepted.

Multi-tenant machinery (ISSUE 11), three composable stages:
  * PREFIX CACHING — prompts are indexed at page granularity
    (kv_cache.PrefixCache); a new request maps every cached full page of
    its prompt with a refcount bump (`PagedKVPool.share`) and prefills only
    the suffix through the windowed program (model.build_window_program).
    Shared pages are immutable: the first write past the shared boundary
    (e.g. a fully-cached prompt's first generated token re-writing the last
    prompt slot) triggers COPY-ON-WRITE — a fresh page, one in-place
    `kv_cache_copy_page` step, and the writer's table repointed, everyone
    else untouched.
  * SPECULATIVE DECODING — with FLAGS_serving_draft_k > 0 each decode step
    self-drafts k tokens per row (n-gram continuation of the request's own
    history) and verifies all k+1 positions in ONE batched window step;
    the greedy tokens the verify emits are accepted up to the first draft
    mismatch, so the result is EXACTLY the plain greedy sequence — only
    (potentially) several tokens per step instead of one. Rejected drafts
    cost nothing to roll back: their KV slots sit past the new context
    length and are overwritten before they can ever be attended.
  * TENSOR PARALLELISM — with tp > 1 the engine builds its programs over a
    `tp` mesh (parallel/mesh.make_tp_mesh): attention heads and the KV pool
    shard across the axis (model.apply_tp_annotations), and
    `paged_decode_attention` keys the tuning DB on the PER-SHARD shape
    (nh/tp) so TP decode resolves through the same swept verdicts as every
    other lever.

Per-sequence state besides K/V (`cfg.stateful`, the "cca_moe" block): a
layer continues a sequence at t from three tails of token t-1 that no K/V
gives back. They live in the state pool, one row per PAGE and layer holding
the state after that page's latest token (written by prefill at every page
end and at its last token, by decode as it goes, copied by copy-on-write),
so the row of a FULL page is final and a prefix hit on whole pages restores
exactly the state it resumes from. One rule follows: a hit may never cover
the prompt's last token (the "full hit" regime re-derives that slot from a
state one token back, which no row holds), so it is cut back to the last
whole page before it and that page re-runs as a suffix prefill
(`serving.state.recomputed_tokens`). Speculation, tensor parallelism and
the fleet handoff are refused for such a block at construction. A mixture
of experts also reports, with every step's tokens, the expert each layer
chose for every token it computed; the engine keeps them per page
(`_page_routes`, the host twin of the pools) and hands a finished request
its `routes`, which is what a reference needs to follow the same experts.

Learned sparse attention (the "sparse_moe" block): a per-token pool of
indexer keys rides the same page ids as the K/V rows (allocate, share,
copy-on-write and release move both), and a token's K and V are ONE row of
ONE pool (`kv_cache.JOINED_POOL`), because a decode row gathers single
tokens and the chip's gather pays by the row. A prompt longer than
`cfg.prefill_chunk` runs as CONSECUTIVE windows of that many tokens through
the window program, each attending the pool the ones before it filled (one
`serving.prefill.chunk` span each; a chunk is not yet batched with decode
rows). Page tables round up to a multiple of 32 pages past 32 instead of a
power of two (`cfg.page_bucket_step`): 261 live pages scan 288, not 512.
What each layer's attention was given is a device output of every step, in
the form the attention used: the mask a window attended under, packed into
words, and the positions a decode row gathered. The host copies it only for
MARKED requests (submitted with `keep_selection`, at most `MARK_ROWS`
running at a time), whose `selection` a reference can then follow and
judge. The block routes
`experts_per_token` experts a token, so its `routes` are `[n, layers, k]`.
Speculation, tensor parallelism and the fleet handoff are refused for it
as for "cca_moe".

Window and full attention layers (the "hybrid_moe" block): the layers that
attend everything keep their K/V under `req.pages`, as every family does;
the layers that attend a sliding window keep theirs in a SECOND pool
(`window_pool`, the same `PagedKVPool`, page ids of its own) under a compact
table, `req.wpages`, entry j the page of logical page `req.wfirst + j`. A
row maps there only the pages its window touches: a page is released in the
step after the window leaves it (`_slide_window`; a chunked prefill
releases as it goes), so a row never holds more than
`window_table_pages` of them whatever its context. Admission, growth,
copy-on-write, preemption, the leak count and the audit answer for both
pools. The prefix cache keeps a cached block's window page where one is
still held, and a prompt resumes only a prefix whose window tail is held
(`PrefixCache.match_resumable`); under pressure in the window pool alone
the cache gives up window pages before a row is made to wait.

A recurrent state (the "parallel_ssm", "mixer_moe" and "kda_moe" blocks,
`cfg.recurrent`; the pools hold `cfg.state_layers` layers, every layer of
the first, the mixers of the second and the Kimi-Delta layers of the third,
whose pages hold latent rows of its latent layers, `cfg.latent_layers`):
beside its
K/V pages a row owns ONE slot of the pools of state (`state_pool`, the same
`PagedKVPool` a third time, ids of its own, `req.sslot`), which every step
of the row rewrites in place; admission, preemption, the leak count and the
audit count slots beside pages. The prefix cache holds SNAPSHOTS: when a
prefill chunk ends on a multiple of `cfg.prefill_chunk` tokens inside the
prompt, the row's slot is copied into a free (or the least recently
resumed) slot and hung on the cached block that ends there (`_snapshot`).
A prompt resumes only at the deepest matched block whose snapshot is held
and that leaves it a token to run (`PrefixCache.match_snapshot`): the pages
matched past it are handed back and recomputed
(`serving.state.recomputed_tokens`), and the snapshot is COPIED into the
row's own slot (`serving.state.restore`), because unlike a K/V page a state
is never read-only for its reader. Copy, snapshot and restore are one
in-place device program (`model.build_state_copy_program`).

Layers a token passes several times (the "looped_dense" block): the stacked
K/V pools hold `cfg.cache_planes` = loop_steps x layers slabs a page id, so
everything the host does by page id (allocate, share, copy-on-write, release,
the audit, the leak count) answers for every visit of every layer at once,
and a token's cache is that many rows: the POOL, not `max_inflight`, bounds
the rows in flight, and the backpressure below (admission that waits until
the pool can carry every row's growth, step by step to the newcomer's end)
is the normal path: a waiter stands in the queue WITHOUT tokens, never in
and out of the pool with them. A row
that is preempted all the same (copy-on-write, a test's own hand) comes
back as a prompt of its own prompt and what it had produced, a
length no arrival has, so the family compiles one page-table width
(`cfg.one_page_bucket`) and its windows are the arrivals' programs. Every
step hands back, beside the logits, the exit gate's probability of leaving
after each visit (`request.exit_mass`, one `[visits]` row a generated token;
`serving.loop.exit_mass` by visit); nothing branches on it. The counters
`serving.pool_bound_admissions`, `serving.growth_held_admissions`,
`serving.timeline_admissions` and `serving.preempted_tokens` say what the
pool's bound cost and what the timeline of the ends gave back (any family
books them).

Compile discipline (the PR 2 machinery doing serving duty):
  * prefill compiles once per prompt-length bucket (pow2 rounding); suffix
    prefill once per (suffix-bucket, page-bucket);
  * decode compiles once per (batch-bucket, page-count-bucket) — rows are
    padded up to the batch bucket and masked with the `batch_mask` row-mask
    convention (the verify window masks via zero valid-lengths instead);
  * `stats["prefill_signatures"]/["decode_signatures"]` record exactly which
    buckets compiled, so tests can assert the open-loop run compiled decode
    at most once per bucket (via pipeline.jit_compile_counter).

Failure/backpressure semantics:
  * admission backpressure RESERVES GROWTH (ISSUE 55): `max_new_tokens` is
    known at `submit`, so a row's table never outgrows
    `pages_for(prompt_len + max_new_tokens)` (`_end`). Beside rows that go
    on, the head of the queue is admitted if the free pages (unshared
    prefix-cache pages counted free: they are evicted on demand, LRU-first)
    cover what every such row has yet to take to its end AND the head's
    own pages to its end beyond its prefix hit: the SUM of the ends, which
    bounds every instant. Where the sum does not fit, the TIMELINE of the
    ends decides (ISSUE 57, `_ends_fit`): counted in decode steps from
    now, every row takes one token a step, holds
    `pages_for(min(tokens + k + lookahead, end))` pages at step k until it
    is gone, one step after the step of its last write has been
    dispatched (the engine reads a step one dispatch late), and then
    has returned the pages that it alone holds; the head is admitted iff
    at every step up to its own end the rows' pages and its own fit in
    what is spare now and what the rows gone by then return
    (`serving.timeline_admissions`). Else it WAITS at the head, holding
    its pinned hit and no token (`serving.pool_bound_admissions`; where
    its prompt's pages were free, `serving.growth_held_admissions`). A
    lone request is admitted whatever its end; the first request that
    does not fit stops admission. The rule reads the pool, never a knob:
    where the sum of the ends fits (every deployment whose pool is not
    what bounds its rows) the timeline is not reckoned. Under
    draft-verify steps a row takes several tokens a step and the sum
    decides alone. A row that stops on `eos_id` well under its cap was
    reserved more than it wrote, at every step of the timeline too
    (ROADMAP R12 (a''));
  * mid-decode growth therefore finds its page. The net under what the
    reservation does not cover (a copy-on-write's fresh page, the sliding
    layers' pool, speculation's lookahead, an adopted handoff, cached
    pages that a waiter pinned after the rows were promised them): when a
    pool is dry all the same, the waiting requests' pins are given back
    first (they match again at their next attempt), then the pending step
    is accepted, then the YOUNGEST running request is preempted
    back to the head of the waiting queue (its refcounts released; on
    re-admission its prompt+generated prefix re-prefills past whatever the
    prefix cache still holds — recompute-style preemption, exact under
    greedy decoding);
  * abort (client gone, or the `serving_abort` chaos fault site): the
    request's refcounts release immediately; pages nobody else maps return
    to the free list — the zero-leak invariant the chaos test pins down.

Resilience layer (ISSUE 14 — see README "Serving resilience"):
  * DEADLINES — a per-request TTL checked at admission and between decode
    steps; an expired request keeps its partial tokens, returns every page,
    and finishes in the distinct `deadline_exceeded` terminal state;
  * ADMISSION CONTROL — when pool occupancy / queue depth / p99 TTFT (read
    through the SloMonitor) cross the FLAGS_serving_shed_* floors, submit()
    sheds lower-priority WAITING requests first and then rejects with
    `AdmissionRejected` (retry-after hint) instead of queueing unboundedly;
  * a graceful-DEGRADATION ladder under sustained pressure, the StepGuard
    ladder's serving twin: speculative decode off -> no decode-lookahead
    reservation at admission -> prefix-cache LRU eviction -> shed, one rung
    per FLAGS_serving_degrade_after pressured steps, descending when calm;
  * SUPERVISION — every compiled dispatch runs under a RetryPolicy (the
    `serving_step_fail` site injects there); retry exhaustion or a dirty
    `PagedKVPool.check_consistency` audit (`serving_pool_corrupt` injects
    the damage) triggers the recovery pass: quarantine poisoned requests,
    rebuild the pool pristine, replay survivors from their prompts —
    bitwise-equal to a fault-free greedy run.
"""
from __future__ import annotations

import gc
import itertools
import time

import dataclasses

import jax.numpy as jnp
import numpy as np

from .. import flags, profiler, unique_name
from .. import observability as obs
from ..data_feeder import _round_up_pow2
from ..executor import Executor, Scope
from ..framework import Program, program_guard
from ..observability.slo import hist_p99_above
from ..ops import (attention_ops, decoder_common, latent_moe_ops,
                   parallel_ssm_ops, sparse_moe_ops)
from ..resilience.faults import InjectedFault, fault_point
from ..resilience.retry import serving_policy
from . import model as sv_model
from .kv_cache import (INDEX_POOL, LATENT_POOL, STATE_POOLS, OwnedPoolView,
                       PagedKVPool, PrefixCache, create_device_pools,
                       create_stacked_pools, create_state_pools,
                       pool_var_names)
from .sampling import SamplingParams, request_rng, sample_token

__all__ = ["GenRequest", "ContinuousBatchingScheduler", "ServingEngine",
           "AdmissionRejected", "ngram_draft"]

WAITING, RUNNING, FINISHED, ABORTED = "waiting", "running", "finished", "aborted"
DEADLINE_EXCEEDED, SHED = "deadline_exceeded", "shed"
# disaggregated serving (ISSUE 19): the request left this engine through a
# KV handoff — NOT terminal; its pages stay pinned (the prefill pin) until
# the adopting side commits and the router sends release_handoff
HANDED_OFF = "handed_off"
# the states a request never leaves; pop_result/prune accept any of them
_TERMINAL = frozenset({FINISHED, ABORTED, DEADLINE_EXCEEDED, SHED})
# graceful-degradation ladder rungs, mildest first (see _update_ladder)
_LADDER_RUNGS = {1: "spec_off", 2: "lookahead_shrink",
                 3: "cache_evict", 4: "shed"}
# an iteration this long emits serving.slow_step with what it was made of:
# on the chip a normal one takes 0.06-0.5 s and a stall thousands of ms
SLOW_STEP_S = 1.0
# the fewest row slots of `model.LAST_TOKEN` (a few hundred bytes), whatever
# max_inflight. Nothing needs the room: it stays so that the compiled step
# programs are the ones PR 41 measured, and folds into
# `_round_up_pow2(max_inflight)` at the next PR that may move HLO (ROADMAP D6)
MIN_TOKEN_SLOTS = 64


class AdmissionRejected(RuntimeError):
    """submit() refused the request under overload: an explicit shed with a
    retry-after hint instead of unbounded queueing. `signals` carries the
    tripped triggers (occupancy / queue_depth / ttft_p99_s)."""

    def __init__(self, reason: str, retry_after_s: float, signals: dict):
        super().__init__(f"admission rejected ({reason}); retry after "
                         f"~{retry_after_s}s")
        self.reason = reason
        self.retry_after_s = retry_after_s
        self.signals = dict(signals)


class _StepFailure(RuntimeError):
    """A compiled dispatch failed past its retry budget; step() converts it
    into a recovery pass instead of letting it poison the batch."""

    def __init__(self, kind: str, cause: BaseException):
        super().__init__(f"{kind} dispatch failed after retries: {cause}")
        self.kind = kind
        self.cause = cause


def ngram_draft(tokens, k: int, window: int = 128) -> list[int]:
    """Self-drafting proposer: continue `tokens` with the k tokens that
    followed the most recent earlier occurrence of its tail n-gram (longest
    of 3/2/1), falling back to repeating the last token. No draft model,
    no extra weights — the request's own history is the draft distribution,
    which is exactly where decode traffic is redundant (templated outputs,
    code, quoted context). Wrong drafts only cost their share of the
    verify window; acceptance is checked exactly."""
    if k <= 0:
        return []
    toks = [int(t) for t in tokens]
    lo = max(0, len(toks) - window)
    for glen in (3, 2, 1):
        if len(toks) < glen + 1:
            continue
        tail = toks[-glen:]
        for i in range(len(toks) - glen - 1, lo - 1, -1):
            if toks[i:i + glen] == tail:
                cont = toks[i + glen:i + glen + k]
                if cont:
                    return (cont + [toks[-1]] * (k - len(cont)))[:k]
    return [toks[-1]] * k


def _selection_words(pieces: list, page_size: int) -> np.ndarray:
    """A marked request's pieces, one a step in order, as one array of
    `GenRequest.selection` words: a window's piece is words already ([n,
    layers, G, page_size] int32), a decode step's the positions its row
    gathered ([1, layers, kk], -1 none)."""
    runs = []                   # consecutive decode steps convert together
    for p in pieces:
        if p.ndim == 3 and runs and runs[-1][0].ndim == 3:
            runs[-1].append(p)
        else:
            runs.append([p])
    top = max(int(p.max(initial=0)) for p in pieces if p.ndim == 3) \
        if any(p.ndim == 3 for p in pieces) else 0
    G = max([top // page_size // 32 + 1]
            + [p.shape[2] for p in pieces if p.ndim == 4])
    out = []
    for run in runs:
        words = np.zeros((sum(map(len, run)), run[0].shape[1], G, page_size),
                         np.uint32)
        if run[0].ndim == 4:
            words[:, :, :run[0].shape[2]] = run[0].view(np.uint32)
        else:
            row = 0
            for p in run:       # kk differs where a table fits the selection
                n, layer, at = np.nonzero(p >= 0)
                page, slot = np.divmod(p[n, layer, at], page_size)
                np.bitwise_or.at(
                    words, (row + n, layer, page // 32, slot),
                    np.uint32(1) << (page % 32).astype(np.uint32))
                row += len(p)
        out.append(words)
    return np.concatenate(out)


# what a family's step programs may hand back beside the token and the
# logits, in fetch order: the experts chosen, the positions attended, the
# probability of leaving after each visit of the layers
_EXTRA_FETCHES = ("routes", "selection", "exit_mass")


@dataclasses.dataclass
class _InFlight:
    """One step program the device has been given and the host has not read
    (the engine holds at most one): its device handles and what its accept
    needs of the moment it was dispatched."""

    kind: str               # "decode" | "prefill" | "chunk" (of a prefill)
    rows: list              # the requests it computed, in row order
    tokens: object          # next_token [rows] (None: a chunk emits none)
    logits: object          # [rows, V]; None once dropped (all rows greedy)
    routes: object          # experts chosen, None where the block has none
    selection: object       # what was attended, None unless a row is marked
    # decode: each row's (position, page) the step wrote; prefill / chunk:
    # (first position, positions computed) of its one row
    at: list
    marked: list            # decode: the row indices whose selection is kept
    route_pages: object = None  # prefill / chunk: the page of each position
    exit_mass: object = None    # [rows, visits], "looped_dense" only


class GenRequest:
    """One generate request's lifetime.

    `all_tokens` is the full sequence the host has accepted so far (prompt
    + generated); `in_flight` (0 or 1) counts a token a dispatched step has
    computed and the host has not read. The KV cache holds exactly
    `cache_len` = len(all_tokens) - 1 + in_flight slots while RUNNING (the
    newest token's KV is written by the decode step that consumes it). On
    preemption the pages are dropped and the whole prefix re-prefills — no
    separate bookkeeping for "how much cache survived".
    """

    def __init__(self, rid: int, prompt, max_new_tokens: int, eos_id=None,
                 sampling: "SamplingParams | None" = None,
                 deadline_s: float | None = None, priority: int = 1):
        if not len(prompt):
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.rid = rid
        self.prompt_len = len(prompt)
        self.all_tokens: list[int] = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.sampling = sampling or SamplingParams()
        self.state = WAITING
        self.pages: list[int] = []
        self.in_flight = 0
        # the row's slot of `model.LAST_TOKEN` while it has steps to run
        self.slot: int | None = None
        # a family with sliding-window layers: the row's pages of the window
        # pool, logical pages wfirst .. wfirst + len(wpages) - 1
        self.wpages: list[int] = []
        self.wfirst = 0
        # a family with a recurrent state: the row's own slot of the state
        # pools while it runs, and the snapshot a prefix hit pinned for it
        # until it is copied there
        self.sslot: int | None = None
        self.snap: int | None = None
        self.cached_len = 0      # slots mapped from the prefix cache
        self.admit_seq = -1      # admission order; preemption evicts the newest
        self.preemptions = 0
        self.arrival_t = time.perf_counter()
        self.priority = int(priority)  # higher = more important to keep
        # wall-clock TTL: an expired request keeps its partial tokens but
        # releases every page (the deadline_exceeded terminal state)
        self.deadline_t = (self.arrival_t + float(deadline_s)
                           if deadline_s and deadline_s > 0 else None)
        self.t_first_token: float | None = None
        self.t_done: float | None = None
        # mixture-of-experts blocks: [cache_len, layers] expert ids (with
        # k > 1 experts a token [cache_len, layers, k]), one row per
        # position whose K/V the engine computed, set when it finishes
        self.routes = None
        # layers visited several times a token ("looped_dense"): for each
        # generated token the probability of leaving after each visit
        # ([visits] float32, summing to 1), as the step that emitted it
        # gave it
        self.exit_mass: list = []
        # a preemption dropped the pages: the next prefill computes again
        # what a step had computed; and whether an admission found no pages
        # while a row slot was free
        self.resumed = False
        self.waited_for_pages = False
        # ... and whether one held it back for the running rows' growth
        # while its prompt's pages were free
        self.held_for_growth = False
        # learned sparse attention: `keep_selection` asks for `selection`;
        # `marked` says that this admission records it (a slot was free)
        self.keep_selection = False
        self.marked = False
        self._select_from = 0
        self._selected: list = []   # a step's piece each, as the device gave it
        self._kept = None           # (first, pieces, page size) when finished
        self._selection = None

    @property
    def selection(self):
        """A finished MARKED request: (first position, uint32 words [n,
        layers, G, page_size]), the cached positions each layer attended at
        the n positions this admission computed, first onward: bit `p % 32`
        of word `[p // 32, slot]` is position `p * page_size + slot`
        (`sparse_moe_ops.pack_selection_fn`; a window's words are the mask
        it attended under, a decode step's are made here from the positions
        it gathered). None otherwise. Put together when first read, not
        inside a serving step."""
        if self._kept is not None:
            first, pieces, page_size = self._kept
            self._selection = (first, _selection_words(pieces, page_size))
            self._kept = None
        return self._selection

    @property
    def n_generated(self) -> int:
        return len(self.all_tokens) - self.prompt_len

    @property
    def out_tokens(self) -> list[int]:
        return self.all_tokens[self.prompt_len:]

    @property
    def cache_len(self) -> int:
        """Valid KV slots while RUNNING (last token not yet appended): the
        position the row's next step writes, a token in flight counted."""
        return len(self.all_tokens) - 1 + self.in_flight

    def is_done(self) -> bool:
        return (self.n_generated >= self.max_new_tokens
                or (self.eos_id is not None and self.n_generated > 0
                    and self.all_tokens[-1] == self.eos_id))


class ContinuousBatchingScheduler:
    """Admission ordering policy over the waiting queue."""

    def __init__(self, policy: str):
        if policy not in ("fcfs", "sjf"):
            raise ValueError(f"unknown FLAGS_serving_sched_policy "
                             f"'{policy}' (fcfs | sjf)")
        self.policy = policy

    def order(self, waiting: list[GenRequest]) -> list[GenRequest]:
        if self.policy == "sjf":
            # stable sort: equal lengths keep arrival order
            return sorted(waiting, key=lambda r: len(r.all_tokens))
        return list(waiting)


class ServingEngine:
    """Paged-KV continuous-batching runtime for one decoder model.

    Single-threaded by design (one scheduler loop owns the pool and the
    scope); the parallelism is inside the compiled steps.
    """

    @staticmethod
    def default_sizes(cfg, page_size: int, max_inflight: int) -> dict:
        """What the step programs are built with beside the KV pool, where
        no argument of the constructor says otherwise: `token_slots` always,
        `window_pages` of a family with sliding-window layers, `state_slots`
        of one with a recurrent state (the constructor's docstring has the
        reasons; tests/test_kernel_choice.py builds the benchmark's programs
        from the same answer)."""
        sizes = {"token_slots": _round_up_pow2(max(max_inflight,
                                                   MIN_TOKEN_SLOTS))}
        if cfg.windowed:
            sizes["window_pages"] = 2 * max_inflight * (
                sv_model.window_table_pages(cfg, page_size) + 1)
        if cfg.recurrent:
            sizes["state_slots"] = max_inflight + 1 \
                + max(2, max_inflight // 4 - 1)
        return sizes

    @obs.spanned("setup.engine_build")
    def __init__(self, cfg: "sv_model.DecoderConfig | None" = None,
                 page_size: int | None = None,
                 pool_pages: int | None = None,
                 max_inflight: int | None = None,
                 policy: str | None = None,
                 window_pool_pages: int | None = None,
                 state_slots: int | None = None,
                 seed: int = 0,
                 prefix_cache: bool | None = None,
                 draft_k: int | None = None,
                 tp: int | None = None,
                 deadline_s: float | None = None,
                 priority_default: int | None = None,
                 shed_occupancy: float | None = None,
                 shed_queue_depth: int | None = None,
                 shed_ttft_p99_ms: float | None = None,
                 degrade_after: int | None = None,
                 step_retries: int | None = None,
                 audit_every: int | None = None,
                 shared_pool: "PagedKVPool | None" = None,
                 shared_scope: "Scope | None" = None,
                 pool_owner: str | None = None,
                 prefill_only: bool = False):
        """Disaggregated serving (ISSUE 19): pass `shared_pool` (ONE
        `PagedKVPool` spanning the fleet — this engine sees it through an
        `OwnedPoolView` tagged `pool_owner`) plus `shared_scope` (the
        device pools and weights every role reads/writes) to build a
        role-split engine. `prefill_only=True` skips the decode stage of
        every step: requests prefill, then sit RUNNING until
        `extract_for_handoff` publishes them to a decode engine.
        `window_pool_pages` sizes the second pool of a family with
        sliding-window layers (default: twice what `max_inflight` decoding
        rows hold there while each grows into its next page, the other half
        for prefills in flight and the prefix cache's tails).
        `state_slots` sizes the pools of a family with a recurrent state
        (default: a live slot a row of `max_inflight`, one scratch slot for
        padding rows, and a quarter as many again, less one, for the prefix
        cache's snapshots)."""
        self.cfg = cfg or sv_model.decoder_tiny()
        self.page_size = int(page_size
                             or flags.get_flag("serving_page_size"))
        self.pool_pages = int(pool_pages
                              or flags.get_flag("serving_pool_pages"))
        self.scheduler = ContinuousBatchingScheduler(
            policy or str(flags.get_flag("serving_sched_policy")))
        if prefix_cache is None:
            prefix_cache = bool(flags.get_flag("serving_prefix_cache"))
        self.tp = int(tp if tp is not None else flags.get_flag("serving_tp"))
        self.seed = int(seed)
        # the runtime knobs resolve ONCE, here: the scheduling loop reads
        # these attributes, never the flags, so a flag flipped after
        # construction moves nothing. Resilience defaults keep the machinery
        # off/cheap.
        def knob(arg, flag, kind):
            return kind(arg if arg is not None else flags.get_flag(flag))

        self.max_inflight = int(max_inflight
                                or flags.get_flag("serving_max_inflight"))
        self.draft_k = knob(draft_k, "serving_draft_k", int)
        self.deadline_s = knob(deadline_s, "serving_deadline_s", float)
        self.priority_default = knob(priority_default,
                                     "serving_priority_default", int)
        self.shed_occupancy = knob(shed_occupancy, "serving_shed_occupancy",
                                   float)
        self.shed_queue_depth = knob(shed_queue_depth,
                                     "serving_shed_queue_depth", int)
        self.shed_ttft_p99_ms = knob(shed_ttft_p99_ms,
                                     "serving_shed_ttft_p99_ms", float)
        self.degrade_after = max(1, knob(degrade_after,
                                         "serving_degrade_after", int))
        self.audit_every = knob(audit_every, "serving_audit_every", int)
        if self.draft_k < 0:
            raise ValueError(f"draft_k must be >= 0, got {self.draft_k}")
        if self.cfg.scanned:
            unsupported = [what for what, on in (
                ("speculative decoding (draft_k > 0)", self.draft_k > 0),
                ("tensor parallelism (tp > 1)", self.tp > 1),
                ("a shared pool / the fleet handoff",
                 shared_pool is not None or prefill_only)) if on]
            if unsupported:
                raise NotImplementedError(
                    f"block {self.cfg.block!r} is one scanned op over "
                    f"stacked pools (state rows, indexer keys); not "
                    f"supported with it yet: " + "; ".join(unsupported))
        retries = int(step_retries if step_retries is not None
                      else flags.get_flag("serving_step_retries"))
        self._retry = serving_policy(max_attempts=max(1, retries),
                                     seed=self.seed)
        self._slo = None
        if self.shed_ttft_p99_ms > 0:
            # a private monitor over the default registry with muted
            # callbacks: the breach verdicts still land on the slo.* series,
            # the engine just reads them as one more overload signal
            self._slo = obs.SloMonitor(
                window_s=30.0, alert_after=1,
                on_warn=lambda b: None, on_alert=lambda b: None)
            self._slo.add_rule(
                "serving_ttft_p99",
                hist_p99_above("serving.ttft_s",
                               self.shed_ttft_p99_ms / 1e3),
                self.shed_ttft_p99_ms / 1e3,
                "p99 TTFT above the shed floor")
        self._ladder_rung = 0
        self._pressure_steps = 0
        self._calm_steps = 0
        self._step_i = 0
        # self seconds of the current step's spans by name (the collect dict
        # of serving.step), what the step did, and the slowest step since
        # reset_stats() with both
        self._phase_s: dict[str, float] = {}
        self._step_admitted = self._step_rows = 0
        self._slowest_step: dict | None = None
        self.prefill_only = bool(prefill_only)
        self._shared_pool = shared_pool is not None
        if shared_pool is not None:
            if (shared_pool.num_pages != self.pool_pages
                    or shared_pool.page_size != self.page_size):
                raise ValueError(
                    f"shared pool is {shared_pool.num_pages}x"
                    f"{shared_pool.page_size} but this engine asked for "
                    f"{self.pool_pages}x{self.page_size}")
            self.pool = OwnedPoolView(shared_pool,
                                      pool_owner or f"engine@{id(self)}")
        else:
            self.pool = PagedKVPool(self.pool_pages, self.page_size)
        sizes = self.default_sizes(self.cfg, self.page_size,
                                   self.max_inflight)
        # the sliding-window layers' pool: the same allocator a second time
        self.window_pool = None
        self._wtable_decode = self._wtable_chunk = 0
        if self.cfg.windowed:
            kinds = self.cfg.layer_types
            self._full_layers = kinds.count("full_attention")
            self._slide_layers = len(kinds) - self._full_layers
            self._wtable_decode = sv_model.window_table_pages(
                self.cfg, self.page_size)
            self._wtable_chunk = sv_model.window_table_pages(
                self.cfg, self.page_size, self.cfg.prefill_chunk)
            self.window_pool = PagedKVPool(
                int(window_pool_pages or sizes["window_pages"]),
                self.page_size)
        # the recurrent state's slots: the same allocator a third time, one
        # "token" a page; slot ids are its page ids
        self.state_pool = None
        self._scratch_slot = 0
        # which counters book the state's updates and scans
        self._state_kind = self.cfg.family.state_kind
        if self.cfg.recurrent:
            if self.cfg.prefill_chunk % self.page_size:
                raise ValueError(
                    f"block {self.cfg.block!r}: prefill_chunk "
                    f"{self.cfg.prefill_chunk} must be whole pages of "
                    f"{self.page_size} (a snapshot hangs on the block a "
                    f"chunk ends)")
            self.state_pool = PagedKVPool(
                int(state_slots or sizes["state_slots"]), 1)
            (self._scratch_slot,) = self.state_pool.allocate(1)
        self.prefix_cache = PrefixCache(self.pool, self.window_pool,
                                        self.state_pool) \
            if prefix_cache else None
        self._exe = Executor()
        self._scope = shared_scope if shared_scope is not None else Scope()

        self._mesh = None
        if self.tp > 1:
            from ..parallel.mesh import make_tp_mesh

            if self.cfg.num_heads % self.tp:
                raise ValueError(
                    f"serving tp degree {self.tp} must divide num_heads "
                    f"{self.cfg.num_heads} (head-sharded decode)")
            self._mesh = make_tp_mesh(self.tp)

        self._prefill_prog = Program()
        self._decode_prog = Program()
        self._window_prog = Program()
        self._cow_prog = Program()
        # the names a device trace lists the compiled steps under
        # (observability/schema.PROGRAM_NAMES): jit_serving_decode, ...
        self._prefill_prog.name = "serving_prefill"
        self._decode_prog.name = "serving_decode"
        self._window_prog.name = "serving_window"
        self._cow_prog.name = "serving_cow"
        startup = Program()
        decoy_startup = Program()  # non-prefill progs re-declare; inits unused
        self._prefill_prog.random_seed = startup.random_seed = self.seed
        second = {"window_pages": self.window_pool.num_pages} \
            if self.window_pool is not None else {}
        if self.state_pool is not None:
            second = {"state_slots": self.state_pool.num_pages}
        # the last token of every row slot, on the device (model.LAST_TOKEN):
        # a running row holds a slot while it has steps to run. Sized for
        # the row bucket; a scope shared by several engines holds one a pool
        # owner. The last entry is the padding rows'.
        self._token_slots = sizes["token_slots"]
        self._slots_free = list(range(self._token_slots))[::-1]
        self._last_token = sv_model.LAST_TOKEN if shared_scope is None \
            else f"{sv_model.LAST_TOKEN}.{getattr(self.pool, 'owner', id(self))}"
        self._pending: _InFlight | None = None
        self._dispatched = 0        # step programs enqueued, ever
        kept = {"token_slots": self._token_slots,
                "last_token": self._last_token, **second}
        with obs.span("setup.engine_build.programs"):
            with program_guard(self._prefill_prog, startup), \
                    unique_name.guard():
                self._prefill_io = sv_model.build_prefill_program(
                    self.cfg, self.pool_pages, self.page_size, **kept)
            with program_guard(self._decode_prog, decoy_startup), \
                    unique_name.guard():
                self._decode_io = sv_model.build_decode_program(
                    self.cfg, self.pool_pages, self.page_size, tp=self.tp,
                    **kept)
            with program_guard(self._window_prog, decoy_startup), \
                    unique_name.guard():
                self._window_io = sv_model.build_window_program(
                    self.cfg, self.pool_pages, self.page_size, tp=self.tp,
                    **kept)
            with program_guard(self._cow_prog, decoy_startup), \
                    unique_name.guard():
                self._cow_io = sv_model.build_cow_program(
                    self.cfg, self.pool_pages, self.page_size, **second)
            self._state_copy_run = None
            if self.state_pool is not None:
                prog = Program()
                prog.name = "serving_state_copy"
                with program_guard(prog, decoy_startup), unique_name.guard():
                    sv_model.build_state_copy_program(
                        self.cfg, self.pool_pages, self.page_size, **second)
                self._state_copy_run = self._exec_target(prog)
        # rng_counter pinned to what a FRESH scope's first run folds in:
        # on a shared scope the run counter has already advanced, and
        # letting it leak into the init keys would give every engine after
        # the first different weights — silently breaking replay exactness
        with obs.span("setup.engine_build.startup"):
            self._exe.run(startup, scope=self._scope, rng_counter=1)
        with obs.span("setup.engine_build.pools"):
            self._scope.set_var(self._last_token,
                                jnp.zeros((self._token_slots + 1,),
                                          jnp.int32))
            # a shared scope may already carry live KV (an engine added to
            # a running disaggregated fleet): re-zeroing the pools would
            # clobber every peer's context, so only the FIRST engine
            # materializes them. Identically-seeded startup runs make the
            # weight re-init above a bitwise no-op on a shared scope.
            if self.cfg.windowed:
                for geometry in sv_model.hybrid_pool_geometry(
                        self.cfg, self.pool_pages, self.page_size,
                        self.window_pool.num_pages):
                    create_stacked_pools(self._scope, *geometry)
            elif self.cfg.recurrent:
                kv, state = sv_model.ssm_pool_geometry(
                    self.cfg, self.pool_pages, self.page_size,
                    self.state_pool.num_pages)
                create_stacked_pools(self._scope, *kv)
                create_state_pools(self._scope, *state)
            elif self.cfg.scanned:
                create_stacked_pools(
                    self._scope, *sv_model.stacked_pool_geometry(
                        self.cfg, self.pool_pages, self.page_size))
            elif not self._scope.has_var(
                    pool_var_names(self.cfg.num_layers)[0][0]):
                create_device_pools(self._scope, self.cfg.num_layers,
                                    self.pool_pages, self.page_size,
                                    self.cfg.num_heads, self.cfg.head_dim,
                                    self.cfg.dtype)
        # which expert each layer chose for the token in (page, slot): the
        # host twin of the pools, for blocks that route
        self._page_routes = None
        self._grouped_rows: dict = {}  # a window's rows -> `_note_grouped`
        if "routes" in self._decode_io:
            per_token = (self.cfg.experts_per_token,) \
                if self.cfg.experts_per_token > 1 else ()
            self._page_routes = np.zeros(
                (self.pool_pages, self.page_size, self.cfg.routed_layers)
                + per_token,
                np.int8 if self.cfg.num_experts <= 128 else np.int16)
        self._paged_call_by_signature: dict[tuple[int, int], tuple] = {}
        self._indexer_kernel_runs: bool | None = None
        self._attend_kernel_runs: dict[tuple[int, int], bool] = {}
        self._conv_kernel_runs: bool | None = None
        self._ssm_kernel_runs: dict[int, bool] = {}
        self._prefill_run = self._exec_target(self._prefill_prog)
        self._decode_run = self._exec_target(self._decode_prog)
        self._window_run = self._exec_target(self._window_prog)
        self._cow_run = self._exec_target(self._cow_prog)

        self.requests: dict[int, GenRequest] = {}
        self._waiting: list[GenRequest] = []
        self._running: list[GenRequest] = []
        self._next_rid = 0
        self._admit_seq = 0
        self.stats = {
            "prefills": 0, "decode_steps": 0, "decode_tokens": 0,
            "decode_context_pages": 0, "decode_grid_steps": 0,
            "preemptions": 0, "aborts": 0,
            "prefill_signatures": set(), "decode_signatures": set(),
            "peak_pages_in_use": 0, "occupancy_sum": 0.0, "occupancy_n": 0,
            # prefix caching (ISSUE 11)
            "prefill_tokens_computed": 0, "prefix_hit_tokens": 0,
            "prefix_lookups": 0, "prefix_full_hits": 0, "cow_copies": 0,
            # speculative decoding (ISSUE 11)
            "spec_steps": 0, "spec_proposed": 0, "spec_accepted": 0,
            # resilience (ISSUE 14) — dotted keys mirror to the registry
            # verbatim through _count ("serving." + key)
            "deadline_exceeded": 0, "shed": 0, "rejects": 0,
            # disaggregated handoff (ISSUE 19)
            "adopts": 0, "handoff_extracts": 0,
            "step_retries": 0, "recovery.passes": 0,
            "recovery.replayed": 0, "recovery.quarantined": 0,
            "ladder.spec_off": 0, "ladder.lookahead_shrink": 0,
            "ladder.cache_evict": 0, "ladder.shed": 0,
            # per-sequence state rows and expert routing (ISSUE 25)
            "state.restores": 0, "state.recomputed_tokens": 0,
            "moe.experts_touched": 0, "moe.layer_steps": 0,
            # chunked prefill and learned sparse attention (ISSUE 29)
            "prefill.chunks": 0, "sparse.context_tokens": 0,
            "sparse.selected_tokens": 0, "sparse.layer_steps": 0,
            "sparse.kernel_layer_steps": 0,
            # a latent cache row and a share of the experts (ISSUE 39)
            "latent.gathered_rows": 0, "latent.attended_tokens": 0,
            "latent.attend_kernel_layer_steps": 0, "latent.pages_read": 0,
            # a residual path of several streams (ISSUE 47)
            "hc.mix_tokens": 0,
            "moe.routed_pairs": 0, "moe.held_pairs": 0,
            "moe.grouped_layer_steps": 0, "moe.grouped_pairs": 0,
            "moe.grouped_tile_rows": 0,
            # window and full attention layers over two pools (ISSUE 33)
            "kv.window_pages_released": 0, "kv.window_row_pages": 0,
            "kv.global_row_pages": 0, "attn.full_context_tokens": 0,
            "attn.attended_tokens": 0, "attn.shared_kernel_layer_steps": 0,
            "attn.window_context_tokens": 0, "attn.full_layer_steps": 0,
            "attn.window_layer_steps": 0, "peak_window_pages_in_use": 0,
            # steps read one dispatch late (ISSUE 36); steps_blocking goes
            # to the registry by `why`
            "chain.steps_deferred": 0, "chain.steps_blocking": 0,
            "chain.discarded_rows": 0,
            # a recurrent state in slots, snapshots in the prefix cache
            # (ISSUE 37)
            "state.snapshots": 0, "state.snapshot_evictions": 0,
            "ssm.decode_row_layers": 0, "ssm.decode_layer_steps": 0,
            "ssm.conv_kernel_layer_steps": 0,
            "ssm.decode_pad_row_layers": 0,
            "ssm.scan_tokens": 0, "ssm.scan_layer_steps": 0,
            # the same of a Kimi-Delta layer's matrix state (ISSUE 51)
            "kda.decode_row_layers": 0, "kda.decode_layer_steps": 0,
            "kda.decode_pad_row_layers": 0,
            "kda.scan_tokens": 0, "kda.scan_layer_steps": 0,
            "peak_state_slots_in_use": 0,
            # layers visited several times a token, and a pool that binds
            # the rows in flight (ISSUE 53)
            "loop.visits": 0, "loop.decode_row_visits": 0,
            "loop.exit_mass": 0.0, "preempted_tokens": 0,
            "pool_bound_admissions": 0, "growth_held_admissions": 0,
            # ... and admission by the timeline of the rows' ends (ISSUE 57)
            "timeline_admissions": 0,
        }

    def _page_bucket(self, n: int) -> int:
        """The page-table width a step with `n` live pages compiles for: a
        power of two, or past `cfg.page_bucket_step` pages a multiple of
        it; for a family of `cfg.one_page_bucket` the width of
        `max_position` whatever `n`."""
        step = self.cfg.page_bucket_step
        if self.cfg.one_page_bucket:
            n = max(n, self.pool.pages_for(self.cfg.max_position))
            whole = step or 32
            return n if n <= whole else -(-n // whole) * whole
        if step and n > step:
            return -(-n // step) * step
        return _round_up_pow2(n)

    def _mark_feed(self, rows=()) -> dict:
        """The `sv_mark` feed of a decode step that hands selections back
        ({} where the program has none): `rows` first, -1 for the unused
        slots."""
        if sv_model.MARK_FEED not in self._decode_io["feeds"]:
            return {}
        mark = np.full((sv_model.MARK_ROWS,), -1, np.int32)
        mark[:len(rows)] = rows
        return {sv_model.MARK_FEED: mark}

    def _slot_feed(self, rows, bb: int, decode: bool = False) -> dict:
        """Where a step of `bb` rows keeps the token each of `rows` emits
        (`sv_slot`: the row's slot of `model.LAST_TOKEN`, the spare last
        entry for padding and for a row that runs no further step) and,
        for a decode step, which rows take their input token from there
        (`sv_from_host` 0: the host has not read it yet)."""
        slot = np.full((bb,), self._token_slots, np.int32)
        for i, r in enumerate(rows):
            if r.slot is not None:
                slot[i] = r.slot
        if not decode:
            return {sv_model.SLOT_FEED: slot}
        from_host = np.ones((bb, 1), np.int32)
        from_host[:len(rows), 0] = [not r.in_flight for r in rows]
        return {sv_model.SLOT_FEED: slot, sv_model.FROM_HOST_FEED: from_host}

    def _state_feed(self, rows, bb: int) -> dict:
        """Each row's slot of the state pools for a step of `bb` rows, the
        scratch slot for padding ({} where the family has no such pool)."""
        if self.state_pool is None:
            return {}
        slot = np.full((bb,), self._scratch_slot, np.int32)
        slot[:len(rows)] = [r.sslot for r in rows]
        return {sv_model.SSLOT_FEED: slot}

    def _window_feed(self, rows, bb: int, width: int) -> dict:
        """The compact tables of `rows` in the sliding layers' pool and the
        position of each table's slot 0, for a step of `bb` rows ({} where
        the family has no such pool)."""
        if self.window_pool is None:
            return {}
        wpages = np.zeros((bb, width), np.int32)
        wbase = np.zeros((bb,), np.int32)
        for i, r in enumerate(rows):
            wpages[i, :len(r.wpages)] = r.wpages
            wbase[i] = r.wfirst * self.page_size
        return {sv_model.WPAGES_FEED: wpages, sv_model.WBASE_FEED: wbase}

    @obs.spanned("setup.decode_lattice")
    def warmup_decode(self, max_context: int | None = None,
                      min_context: int = 1) -> int:
        """Precompile the decode-step signature lattice for contexts up to
        `max_context` (default max_position): which (batch-bucket,
        page-bucket) a step hits depends on how many requests HAPPEN to be
        running — pure load timing — so organic warmup can leave signatures
        uncompiled and a mid-measurement XLA compile (~1s on CPU) then
        decides an open-loop verdict instead of the engines. Drives every
        signature with fully-masked rows (zero valid lengths): writes drop,
        outputs are ignored, no engine state moves. `min_context` starts the
        lattice at the page bucket of the shortest context the caller will
        serve (a cell whose every context is a long document skips the
        buckets below it). Returns the signature count."""
        max_context = min(int(max_context or self.cfg.max_position),
                          self.cfg.max_position)
        pbs = sorted({self._page_bucket(self.pool.pages_for(c))
                      for c in range(max(1, int(min_context)),
                                     max_context + 2)})
        bbs = sorted({self._row_bucket(b)
                      for b in range(1, self.max_inflight + 1)})
        for bb, pb in itertools.product(bbs, pbs):
            with obs.span("setup.decode_lattice.entry", rows=bb, pages=pb):
                pages = np.zeros((bb, pb), np.int32)
                if self.draft_k > 0:
                    S = self.draft_k + 1
                    feed = {sv_model.TOK_FEED: np.zeros((bb, S), np.int32),
                            sv_model.POS_FEED: np.zeros((bb, S), np.int32),
                            sv_model.PAGES_FEED: pages,
                            sv_model.START_FEED: np.zeros((bb,), np.int32),
                            sv_model.LEN_FEED: np.zeros((bb,), np.int32),
                            **self._slot_feed((), bb)}
                    self._exe.run(self._window_run, feed=feed,
                                  fetch_list=[self._window_io["tokens"],
                                              self._window_io["logits"]],
                                  scope=self._scope)
                else:
                    feed = {sv_model.TOK_FEED: np.zeros((bb, 1), np.int32),
                            sv_model.POS_FEED: np.zeros((bb,), np.int32),
                            sv_model.PAGES_FEED: pages,
                            sv_model.MASK_FEED: np.zeros((bb, 1),
                                                         np.float32),
                            **self._mark_feed(),
                            **self._slot_feed((), bb, decode=True),
                            **self._state_feed((), bb),
                            **self._window_feed((), bb, self._wtable_decode)}
                    outs = self._exe.run(
                        self._decode_run, feed=feed,
                        fetch_list=self._step_fetches(self._decode_io),
                        scope=self._scope, return_numpy=False)
                    with profiler.stage_timer("pipeline.fetch"):
                        np.asarray(outs[0])     # wait for the step
        return len(bbs) * len(pbs)

    def reset_stats(self) -> None:
        """Zero the counters (and the compile-signature sets) without
        touching the executor compile cache, the pool, or the prefix
        cache — the steady-state measurement boundary: warm the engine on
        one pass of a workload, reset, measure the second pass. The
        registry's `serving.` series reset with it, and the `pipeline.`
        stages and `host.` series a step's spans and the collector's pauses
        feed, so every view stays scoped to the same measurement window.

        The boundary is also where the set-up's heap leaves the collector's
        work: a warmed engine holds a few hundred thousand tracked objects
        that live as long as the process (the jaxprs and executables of 50
        compiled entries), and CPython's next full collection walks them
        all, one 0.3 s loop iteration somewhere in the traffic that
        follows (PERF §7). One full collection here, while nobody waits,
        then `gc.freeze()`: later collections scan what was allocated
        since. Process-wide, like the collector itself; the `unfreeze`
        first lets a second boundary reclaim what died since the last."""
        gc.unfreeze()
        gc.collect()
        gc.freeze()
        for k, v in self.stats.items():
            if isinstance(v, set):
                v.clear()
            elif isinstance(v, float):
                self.stats[k] = 0.0
            else:
                self.stats[k] = 0
        self._slowest_step = None
        for prefix in ("serving.", "pipeline.", "host."):
            obs.reset(prefix)

    def _count(self, key: str, n: int = 1, labels: dict | None = None
               ) -> None:
        """Bump a stats counter AND its registry mirror (`serving.<key>`,
        under `labels` where the series has them): the dict stays the cheap
        in-process view, the registry carries the same number out through
        snapshot/exporters."""
        self.stats[key] += n
        obs.counter_inc("serving." + key, n, labels)

    def stats_snapshot(self) -> dict:
        """The stats dict plus derived rates, every divide guarded: a
        snapshot taken before any decode/prefill/spec step reports 0.0
        rather than raising ZeroDivisionError or emitting NaN (notably
        spec_accept_rate with speculation enabled but no spec step yet).
        Signature sets become bucket counts so the result is JSON-clean."""
        st = self.stats
        out = {k: (len(v) if isinstance(v, set) else v)
               for k, v in st.items()}
        out["spec_accept_rate"] = (
            st["spec_accepted"] / st["spec_proposed"]
            if st["spec_proposed"] else 0.0)
        out["tokens_per_decode_step"] = (
            st["decode_tokens"] / st["decode_steps"]
            if st["decode_steps"] else 0.0)
        denom = st["prefix_hit_tokens"] + st["prefill_tokens_computed"]
        out["prefix_cache_hit_rate"] = (
            st["prefix_hit_tokens"] / denom if denom else 0.0)
        out["occupancy_mean"] = (
            st["occupancy_sum"] / st["occupancy_n"]
            if st["occupancy_n"] else 0.0)
        out["leaked_pages"] = self.leaked_pages()
        # the slowest iteration since reset_stats(): step, dur_s, self
        # seconds by span name (they sum to dur_s), the collector's seconds
        # inside it, rows decoded, requests admitted; None before any step
        # or with FLAGS_obs_enable off
        out["slowest_step"] = self._slowest_step
        return out

    def _exec_target(self, prog: Program):
        """The executor target for `prog`: the bare program single-chip, a
        GSPMD CompiledProgram over the tp mesh when sharded (built ONCE so
        the executor compile cache keys stay stable)."""
        if self._mesh is None:
            return prog
        from ..compiler import CompiledProgram

        sv_model.apply_tp_annotations(prog, self.cfg, self.tp)
        return CompiledProgram(prog).with_data_parallel(mesh=self._mesh)

    # -- client API ---------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, eos_id=None,
               sampling: "SamplingParams | dict | None" = None,
               deadline_s: float | None = None,
               priority: int | None = None,
               keep_selection: bool = False) -> int:
        """Queue one request. `deadline_s`/`priority` default to the
        engine-wide knobs (FLAGS_serving_deadline_s /
        FLAGS_serving_priority_default). `keep_selection` (a family whose
        attention selects) asks for `GenRequest.selection`: what each layer
        attended comes to the host with every step of this request and of
        no other. Under overload (any
        FLAGS_serving_shed_* floor tripped) this sheds WAITING requests of
        strictly lower priority to make room, and raises AdmissionRejected
        with a retry-after hint when that is not enough — explicit refusal
        instead of an unbounded queue."""
        if len(prompt) + max_new_tokens > self.cfg.max_position:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_position {self.cfg.max_position}")
        if isinstance(sampling, dict):
            sampling = SamplingParams(**sampling)
        if priority is None:
            priority = self.priority_default
        if deadline_s is None:
            deadline_s = self.deadline_s
        sig = self._overload_signals()
        if "occupancy" in sig and self.prefix_cache is not None:
            # evictable prefix-cache pages are reclaimable memory, not
            # pressure: free enough of the LRU tail to get back under the
            # floor before refusing admission. Without this, a cache that
            # grew to the pool size while the engine drained would shed
            # every future submit with no step ever running — the rung-3
            # eviction only fires on pressured STEPS, so an idle engine
            # could never climb out.
            floor_pages = int(self.shed_occupancy * self.pool.num_pages)
            need = self.pool.pages_in_use - floor_pages + 1
            if need > 0:
                self.prefix_cache.evict(need)
            sig = self._overload_signals()
        while sig and self._shed_one(max_priority=int(priority)):
            sig = self._overload_signals()
        if sig:
            retry_after = round(
                0.05 * max(1, len(self._waiting) + len(self._running)), 3)
            self._count("rejects")
            obs.event("serving.request",
                      {"rid": -1, "phase": "rejected", "signals": sig,
                       "retry_after_s": retry_after}, level="warning")
            raise AdmissionRejected(",".join(sorted(sig)), retry_after, sig)
        rid = self._next_rid
        self._next_rid += 1
        req = GenRequest(rid, prompt, max_new_tokens, eos_id, sampling,
                         deadline_s=deadline_s, priority=int(priority))
        req.keep_selection = bool(keep_selection)
        self.requests[rid] = req
        self._waiting.append(req)
        obs.event("serving.request", {"rid": rid, "phase": "queued",
                                      "prompt_len": req.prompt_len,
                                      "priority": req.priority,
                                      "max_new_tokens": req.max_new_tokens})
        return rid

    def abort(self, rid: int) -> None:
        """Drop a request wherever it is; its page refcounts release
        immediately and pages nobody else maps return to the free list
        (the zero-leak contract the chaos test asserts). A WAITING request
        leaves the admission queue AND releases any prefix-cache pages a
        failed admission attempt left pinned on it."""
        self._settle_or_recover()
        req = self.requests.get(rid)
        if req is None or req.state in _TERMINAL:
            return
        self._terminate(req, ABORTED, "aborts")

    def has_work(self) -> bool:
        return bool(self._waiting or self._running
                    or self._pending is not None)

    @property
    def decode_slots_free(self) -> int:
        """RUNNING capacity left under max_inflight — what an adopting
        replica checks before committing a lease (an adopted request
        enters RUNNING directly, so it must fit the decode batch NOW)."""
        return max(0, self.max_inflight - len(self._running))

    # -- disaggregated KV handoff (ISSUE 19) --------------------------------
    def extract_for_handoff(self, rid: int) -> dict:
        """PREPARE half of the prefill->decode handoff: pull a freshly
        prefilled RUNNING request out of the scheduler and publish its full
        transfer state (token history + page table). The request record
        stays, HANDED_OFF, with its pages still held — the PREFILL PIN the
        two-phase protocol keeps until the adopting side commits — so the
        audit and leak accounting see the pin as a live holder throughout.
        The caller (the prefill replica) grants the lease over the
        returned page table before anything else moves."""
        self._settle_or_recover()
        req = self.requests[rid]
        if self.cfg.scanned:
            raise NotImplementedError(
                f"block {self.cfg.block!r}: the handoff would have to move "
                f"state rows or indexer keys with the pages")
        if req.state != RUNNING:
            raise ValueError(
                f"request {rid} is {req.state}; only RUNNING (prefilled) "
                f"requests can hand off")
        self._running.remove(req)
        self._free_slot(req)
        req.state = HANDED_OFF
        self._count("handoff_extracts")
        obs.event("serving.request",
                  {"rid": rid, "phase": HANDED_OFF,
                   "n_generated": req.n_generated, "pages": len(req.pages)})
        return {"prompt_len": req.prompt_len,
                "all_tokens": list(req.all_tokens),
                "pages": list(req.pages),
                "max_new_tokens": req.max_new_tokens,
                "eos_id": req.eos_id, "sampling": req.sampling,
                "priority": req.priority, "deadline_t": req.deadline_t}

    def release_handoff(self, rid: int) -> None:
        """Drop the prefill pin of a HANDED_OFF request (the adopting side
        committed — its view now carries the transferred lease refcount —
        or the handoff failed terminally and the router is cleaning up).
        Idempotent: a second release, or one after this engine already
        recovered, is a no-op."""
        req = self.requests.pop(rid, None)
        if req is None or req.state != HANDED_OFF:
            return
        if req.pages:
            self.pool.release(req.pages)
            req.pages = []

    def adopt_request(self, handoff: dict) -> int:
        """COMMIT half of the handoff: admit a request whose context KV
        some OTHER engine already materialized into the shared pool — the
        page table transfers, prefill is skipped entirely. The pages'
        refcount arrives by lease transfer (the caller committed the lease
        first), so this only records the pins in the owner ledger and
        resumes decoding from wherever the prefill side stopped: with a
        first token (the next decode step continues it) or at a full
        prefix hit (the next decode step derives token one under COW —
        the same regime a local full hit takes). The only admission rule
        that re-runs is the RUNNING cap: an adopted request joins the
        decode batch immediately, so it must fit max_inflight — the
        adopting replica checks `decode_slots_free` and defers the commit
        when full, and this guard backstops it (the caller returns the
        transferred refcount to the pool on rejection, so nothing
        leaks)."""
        adopt = getattr(self.pool, "adopt_transferred", None)
        if adopt is None:
            raise RuntimeError(
                "adopt_request needs a shared pool (OwnedPoolView): a "
                "private pool cannot receive a lease-transferred refcount")
        if len(self._running) >= self.max_inflight or not self._slots_free:
            raise AdmissionRejected(
                "adopt_no_decode_slot", 0.05,
                {"running": len(self._running),
                 "max_inflight": self.max_inflight})
        toks = [int(t) for t in handoff["all_tokens"]]
        pages = list(handoff["pages"])
        prompt_len = int(handoff["prompt_len"])
        if self.pool.pages_for(max(1, len(toks) - 1)) > len(pages):
            raise ValueError(
                f"adopted table has {len(pages)} pages for "
                f"{len(toks) - 1} KV slots")
        rid = self._next_rid
        self._next_rid += 1
        req = GenRequest(rid, toks[:prompt_len], handoff["max_new_tokens"],
                         handoff.get("eos_id"), handoff.get("sampling"),
                         priority=int(handoff.get("priority", 1)))
        req.all_tokens = toks
        req.pages = pages
        req.deadline_t = handoff.get("deadline_t")
        req.state = RUNNING
        req.slot = self._slots_free.pop()
        req.admit_seq = self._admit_seq
        self._admit_seq += 1
        adopt(pages)
        self.requests[rid] = req
        self._running.append(req)
        self._count("adopts")
        obs.event("serving.request",
                  {"rid": rid, "phase": "adopted",
                   "n_generated": req.n_generated, "pages": len(pages)})
        return rid

    def result(self, rid: int) -> list[int]:
        self._settle_or_recover()
        return list(self.requests[rid].out_tokens)

    def pop_result(self, rid: int) -> list[int]:
        """Return a terminal request's generated tokens and drop its
        record. `requests` otherwise retains every completed request (full
        token list included) for the engine's lifetime — unbounded growth
        and ever-slower leak accounting under continuous serving."""
        self._settle_or_recover()
        req = self.requests[rid]
        if req.state not in _TERMINAL:
            raise ValueError(
                f"request {rid} is {req.state}; only terminal "
                f"(finished/aborted/deadline_exceeded/shed) results can "
                f"be popped")
        del self.requests[rid]
        return list(req.out_tokens)

    def prune_finished(self) -> int:
        """Drop every terminal request record (results the caller has
        already read or will never read). Returns records dropped."""
        done = [rid for rid, r in self.requests.items()
                if r.state in _TERMINAL]
        for rid in done:
            del self.requests[rid]
        return len(done)

    def leaked_pages(self) -> int:
        """Pages in use that NO live request and NO prefix-cache entry can
        account for — must be zero at every quiescent point. Over a shared
        pool the base is this OWNER's pages (the OwnedPoolView ledger), not
        the global pool: peers' pages are theirs to account for."""
        self._settle_or_recover()
        mapped: set[int] = set()
        for r in self.requests.values():
            mapped.update(r.pages)
        if self.prefix_cache is not None:
            mapped.update(n.page for n in self.prefix_cache._nodes.values())
        in_use = getattr(self.pool, "owned_pages_in_use",
                         self.pool.pages_in_use)
        leaked = in_use - len(mapped)
        if self.window_pool is not None:
            held = {p for r in self.requests.values() for p in r.wpages}
            if self.prefix_cache is not None:
                held.update(n.wpage for n in self.prefix_cache._nodes.values()
                            if n.wpage is not None)
            leaked += self.window_pool.pages_in_use - len(held)
        if self.state_pool is not None:
            held = {self._scratch_slot}
            for r in self.requests.values():
                held.update(s for s in (r.sslot, r.snap) if s is not None)
            if self.prefix_cache is not None:
                held.update(n.snap for n in self.prefix_cache._nodes.values()
                            if n.snap is not None)
            leaked += self.state_pool.pages_in_use - len(held)
        return leaked

    def flush_prefix_cache(self) -> int:
        """Evict every prefix-cache entry no live request still maps (frees
        their pages). Returns pages freed."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.flush()

    def run_until_drained(self, max_steps: int = 100_000) -> None:
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"serving loop made no exit after {max_steps} steps "
                    f"(waiting={len(self._waiting)} "
                    f"running={len(self._running)})")

    # -- the scheduler iteration --------------------------------------------
    def step(self) -> bool:
        """One continuous-batching iteration; returns True if any request
        made progress (admitted or decoded a token). Supervised: a compiled
        dispatch that exhausts its retry budget becomes a recovery pass
        (quarantine + pool rebuild + prompt replay) instead of a poisoned
        batch.

        The iteration is one span tree under `serving.step`: housekeeping,
        admission (one `serving.prefill` per admitted request), one
        `serving.decode`, and under those the feed building, the executor's
        `pipeline.prepare` / `.dispatch` of the span's OWN program and then
        the `pipeline.fetch` and `serving.accept` of the step dispatched
        BEFORE it (the pending one; its own where the step blocks). A step
        the iteration leaves nothing behind to overlap with is accepted
        under `serving.settle`. Each span's self seconds land in
        `_phase_s`, so a slow iteration says what it was made of
        (`_note_step`)."""
        self._step_i += 1
        self._step_admitted = self._step_rows = 0
        self._phase_s.clear()
        gc0 = obs.gc_pause_seconds()
        with obs.span("serving.step", collect=self._phase_s,
                      step=self._step_i) as sp:
            try:
                progressed = self._step_inner()
            except _StepFailure as e:
                self._recover(f"step_fail:{e.kind}")
                progressed = True
        self._note_step(sp.dur_s, obs.gc_pause_seconds() - gc0)
        return progressed

    def _note_step(self, dur_s: float, gc_s: float) -> None:
        """Keep the slowest iteration since reset_stats() with what it was
        made of, and emit serving.slow_step for one over SLOW_STEP_S."""
        slowest = self._slowest_step
        if not self._phase_s:
            return  # FLAGS_obs_enable off: no span, nothing to say
        if dur_s < SLOW_STEP_S and slowest is not None \
                and dur_s <= slowest["dur_s"]:
            return
        rec = {"step": self._step_i, "dur_s": dur_s,
               "phases": dict(self._phase_s), "gc_s": gc_s,
               "rows": self._step_rows, "admitted": self._step_admitted}
        if slowest is None or dur_s > slowest["dur_s"]:
            self._slowest_step = rec
        if dur_s >= SLOW_STEP_S:
            obs.event("serving.slow_step", rec, level="warning")

    def _step_inner(self) -> bool:
        with obs.span("serving.housekeeping"):
            try:
                fault_point("serving_deadline")
            except InjectedFault:
                # chaos: the oldest live request's deadline collapses to the
                # past
                victim = self._running[0] if self._running else (
                    self._waiting[0] if self._waiting else None)
                if victim is not None:
                    victim.deadline_t = time.perf_counter() - 1e-9
            try:
                fault_point("serving_pool_corrupt")
            except InjectedFault as e:
                self._corrupt_pool(e.hit)
            try:
                fault_point("serving_abort")
            except InjectedFault:
                # chaos: the oldest running request's client vanished
                # mid-decode
                victim = self._running[0] if self._running else (
                    self._waiting[0] if self._waiting else None)
                if victim is not None:
                    self.abort(victim.rid)
            self._expire_deadlines(time.perf_counter())
            if self.audit_every > 0 and self._step_i % self.audit_every == 0:
                problems, poisoned = self._audit_tables()
                if problems:
                    self._recover("pool_corrupt", poisoned=poisoned,
                                  problems=problems)
                    return True
            self._update_ladder()
        dispatched = self._dispatched
        with obs.span("serving.admit") as sp:
            admitted = self._step_admitted = self._admit()
        obs.histogram_observe("serving.admit.self_seconds", sp.self_s)
        if self._running and not self.prefill_only:
            fetch0 = self._phase_s.get("pipeline.fetch", 0.0)
            with obs.span("serving.decode", rows=len(self._running)) as sp:
                decoded = self._decode_once(sp)
            self._observe_host_seconds("serving.decode", sp, fetch0)
        else:
            # prefill-only engines stop at the prompt boundary: freshly
            # prefilled rows sit RUNNING until extract_for_handoff moves
            # them to a decode engine
            decoded = False
        if self._pending is not None and (
                self._dispatched == dispatched or not self._more_to_dispatch()):
            # nothing will be enqueued behind the pending step (every row's
            # last step, a prefill-only engine) or nothing was, this
            # iteration: the host has nothing to overlap it with
            self._settle("idle")
            decoded = True
        with obs.span("serving.housekeeping"):
            # a request that crossed its TTL inside the prefill/decode above
            # is caught here — "mid-step" expiry still releases pages this
            # step
            self._expire_deadlines(time.perf_counter())
            if not decoded and not admitted and self._waiting:
                need = min(self.pool.pages_for(len(r.all_tokens) + 1)
                           for r in self._waiting)
                if need > self.pool.num_pages:
                    raise RuntimeError(
                        f"request needs {need} pages but the pool only has "
                        f"{self.pool.num_pages} (FLAGS_serving_pool_pages / "
                        f"FLAGS_serving_page_size)")
                if not self._running and not self._shared_pool:
                    # over a SHARED pool this engine being starved is not
                    # fatal: peers (or the lease reaper) free pages it never
                    # could — keep waiting instead of declaring deadlock
                    raise RuntimeError(
                        "admission stuck: no running requests to free "
                        f"pages, yet {len(self._waiting)} waiting (free "
                        f"{self.pool.free_count}/{self.pool.num_pages} "
                        f"pages)")
            self._note_occupancy()
        return bool(admitted or decoded)

    def _leaving(self, req: GenRequest) -> bool:
        """Whether the last token `req` is due, by length or by
        `max_position`, is accepted or in flight: it runs no further step
        and leaves when the pending one is accepted."""
        return (req.n_generated + req.in_flight >= req.max_new_tokens
                or len(req.all_tokens) + req.in_flight
                >= self.cfg.max_position)

    def _more_to_dispatch(self) -> bool:
        """Whether the next iteration will (try to) enqueue a program: an
        admission's prefill, or a decode of rows that go on."""
        if self._waiting:
            return True
        return not self.prefill_only and any(
            not self._leaving(r) for r in self._running)

    def _observe_host_seconds(self, name: str, sp, fetch0: float) -> None:
        """`<name>.host_seconds`: the span's duration less the seconds it
        spent inside `pipeline.fetch` (blocked on the device and copying the
        fetches) since `fetch0` was read: the host's part of the step."""
        waited = self._phase_s.get("pipeline.fetch", 0.0) - fetch0
        obs.histogram_observe(name + ".host_seconds", sp.dur_s - waited)

    # -- internals ----------------------------------------------------------
    def _free_slot(self, req: GenRequest) -> None:
        """`req` runs no further step: its slot of `LAST_TOKEN` and its
        slot of recurrent state are free for whoever is dispatched next
        (the device runs that behind the step that last wrote them)."""
        if req.slot is not None:
            self._slots_free.append(req.slot)
            req.slot = None
        if req.sslot is not None:
            self.state_pool.release([req.sslot])
            req.sslot = None

    def _release(self, req: GenRequest) -> None:
        self._free_slot(req)
        if req.pages:
            self.pool.release(req.pages)
            req.pages = []
        if req.wpages:
            self.window_pool.release(req.wpages)
            req.wpages = []
        req.wfirst = 0
        req.cached_len = 0
        if req.snap is not None:
            self.state_pool.release([req.snap])
            req.snap = None

    def _allocate_slot(self) -> int | None:
        """A free slot of the state pools; when there is none the prefix
        cache gives up its least recently resumed snapshot."""
        got = self.state_pool.allocate(1)
        if got is None and self.prefix_cache is not None:
            self._count("state.snapshot_evictions",
                        self.prefix_cache.strip_snapshots(1))
            got = self.state_pool.allocate(1)
        return None if got is None else got[0]

    def _copy_state(self, src: int, dst: int) -> None:
        """Enqueue the copy of slot `src` of recurrent state onto `dst`, in
        every layer (behind whatever was enqueued before it)."""
        self._dispatch("state_copy", self._state_copy_run,
                       {sv_model.SCOPY_SRC_FEED: np.asarray([src], np.int32),
                        sv_model.SCOPY_DST_FEED: np.asarray([dst], np.int32)},
                       [])

    def _snapshot(self, req: GenRequest, upto: int) -> None:
        """The chunk just enqueued left in `req`'s slot the state after
        position `upto - 1`, a chunk boundary inside the prompt: copy it
        into a slot of the prefix cache's (a free one, or its least
        recently resumed) and hang that on the cached block that ends
        there, unless that block holds one or no slot can be had."""
        node = self.prefix_cache.snapshot_block(req.all_tokens[:upto],
                                                upto // self.page_size)
        slot = None if node is None else self._allocate_slot()
        if slot is None:
            return
        with obs.span("serving.state.snapshot"):
            self._copy_state(req.sslot, slot)
        self.prefix_cache.hang_snapshot(node, slot)
        self._count("state.snapshots")

    def _allocate_window(self, n: int) -> list[int] | None:
        """`_allocate` in the sliding layers' pool: when its free list runs
        dry the prefix cache gives up window pages (least recently resumed
        from first; the cached blocks stay) before giving up."""
        if n <= 0:
            return []
        got = self.window_pool.allocate(n)
        if got is None and self.prefix_cache is not None:
            self.prefix_cache.strip_window(n - self.window_pool.free_count)
            got = self.window_pool.allocate(n)
        return got

    def _slide_window(self, req: GenRequest, first_pos: int) -> None:
        """Return to the sliding layers' pool the pages of `req` that lie
        wholly before position `first_pos - (window - 1)`: no position from
        `first_pos` on attends them."""
        keep_from = max(0, first_pos - (self.cfg.sliding_window - 1)) \
            // self.page_size
        gone = min(len(req.wpages), keep_from - req.wfirst)
        if gone <= 0:
            return
        with obs.span("serving.kv.window_release"):
            self.window_pool.release(req.wpages[:gone])
            del req.wpages[:gone]
            req.wfirst += gone
            self._count("kv.window_pages_released", gone)

    def _allocate(self, n: int) -> list[int] | None:
        """allocate() with prefix-cache pressure relief: when the free list
        runs dry, evict unshared cache entries (LRU-first) before giving
        up — cached prompts are a performance bet, never a reason to queue
        live work."""
        if n <= 0:
            return []
        got = self.pool.allocate(n)
        if got is None and self.prefix_cache is not None:
            held = self.prefix_cache.evicted_snapshots
            self.prefix_cache.evict(n - self.pool.free_count)
            if self.prefix_cache.evicted_snapshots > held:
                # a block's snapshot goes with its pages
                self._count("state.snapshot_evictions",
                            self.prefix_cache.evicted_snapshots - held)
            got = self.pool.allocate(n)
        return got

    def _note_occupancy(self) -> None:
        used = self.pool.pages_in_use
        self.stats["peak_pages_in_use"] = max(
            self.stats["peak_pages_in_use"], used)
        self.stats["occupancy_sum"] += used / self.pool.num_pages
        self.stats["occupancy_n"] += 1
        obs.gauge_set("serving.pages_in_use", used)
        obs.gauge_set("serving.pool_occupancy", used / self.pool.num_pages)
        if self.window_pool is not None:
            wused = self.window_pool.pages_in_use
            self.stats["peak_window_pages_in_use"] = max(
                self.stats["peak_window_pages_in_use"], wused)
            obs.gauge_set("serving.kv.global_pages_in_use", used)
            obs.gauge_set("serving.kv.window_pages_in_use", wused)
        if self.state_pool is not None:
            self.stats["peak_state_slots_in_use"] = max(
                self.stats["peak_state_slots_in_use"],
                self.state_pool.pages_in_use)
            obs.gauge_set("serving.state.live_slots",
                          sum(r.sslot is not None for r in self._running))
            obs.gauge_set("serving.state.snapshot_slots",
                          self.prefix_cache.snapshots_held
                          if self.prefix_cache is not None else 0)

    # -- resilience: deadlines, shedding, the degradation ladder ------------
    def _terminate(self, req: GenRequest, state: str, counter: str,
                   extra: dict | None = None,
                   level: str = "warning") -> None:
        """Shared terminal transition: drop the request from whichever
        queue holds it, release every page it maps (including a WAITING
        request's pinned prefix-cache pages), stamp the state, count and
        event it."""
        if req in self._waiting:
            self._waiting.remove(req)
        if req in self._running:
            self._running.remove(req)
        self._release(req)
        req.state = state
        req.t_done = time.perf_counter()
        self._count(counter)
        payload = {"rid": req.rid, "phase": state,
                   "n_generated": req.n_generated}
        if extra:
            payload.update(extra)
        obs.event("serving.request", payload, level=level)

    def _expire_deadlines(self, now: float) -> int:
        """Expire every live request past its TTL (checked between decode
        steps and at admission, never inside a compiled step): partial
        tokens are kept, every page returns, and the terminal state is
        distinct from abort so clients can tell 'too slow' from
        'cancelled'. Returns requests expired."""
        expired = [r for r in self._running + self._waiting
                   if r.deadline_t is not None and now > r.deadline_t]
        if expired:
            # what a dispatched step computed for them is theirs to keep
            self._settle()
            expired = [r for r in expired if r.state not in _TERMINAL]
        for req in expired:
            self._terminate(req, DEADLINE_EXCEEDED, "deadline_exceeded",
                            extra={"overrun_s":
                                   round(now - req.deadline_t, 6)})
        return len(expired)

    def _overload_signals(self) -> dict:
        """The overload triggers currently tripped ({} = healthy): pool
        occupancy and waiting-queue depth read directly, p99 TTFT through
        the SloMonitor so the breach is also counted/evented on the slo.*
        series. Disabled floors (<= 0) never trip."""
        sig: dict = {}
        if self.shed_occupancy > 0:
            occ = self.pool.pages_in_use / self.pool.num_pages
            if occ >= self.shed_occupancy:
                sig["occupancy"] = round(occ, 4)
        if (self.shed_queue_depth > 0
                and len(self._waiting) >= self.shed_queue_depth):
            sig["queue_depth"] = len(self._waiting)
        if self._slo is not None:
            for b in self._slo.observe():
                if b["rule"] == "serving_ttft_p99":
                    sig["ttft_p99_s"] = round(float(b["value"]), 6)
        return sig

    def _shed_one(self, max_priority: int | None = None) -> bool:
        """Shed ONE waiting request: the lowest priority class, youngest
        arrival within it (it has lost the least). `max_priority`
        restricts victims to classes strictly below it — a submit never
        sheds its own class to make room for itself."""
        cands = (self._waiting if max_priority is None
                 else [r for r in self._waiting
                       if r.priority < max_priority])
        if not cands:
            return False
        victim = min(cands, key=lambda r: (r.priority, -r.arrival_t))
        self._terminate(victim, SHED, "shed",
                        extra={"priority": victim.priority})
        return True

    def _update_ladder(self) -> None:
        """Graceful degradation under sustained pressure (the StepGuard
        ladder's serving twin): one rung up per `degrade_after`
        consecutive overloaded steps, one rung down per equally long calm
        streak. Rungs: 1 speculative decode off, 2 admission stops
        reserving the decode-lookahead page, 3 the prefix-cache LRU tail
        is evicted each pressured step, 4 lowest-priority waiters shed."""
        sig = self._overload_signals()
        if sig:
            self._pressure_steps += 1
            self._calm_steps = 0
            if (self._ladder_rung < 4
                    and self._pressure_steps >= self.degrade_after):
                self._pressure_steps = 0
                self._ladder_rung += 1
                name = _LADDER_RUNGS[self._ladder_rung]
                self._count("ladder." + name)
                obs.gauge_set("serving.ladder_rung", self._ladder_rung)
                obs.event("serving.degrade",
                          {"rung": self._ladder_rung, "name": name,
                           "direction": "up", "signals": sig},
                          level="warning")
        else:
            self._calm_steps += 1
            self._pressure_steps = 0
            if (self._ladder_rung > 0
                    and self._calm_steps >= self.degrade_after):
                self._calm_steps = 0
                self._ladder_rung -= 1
                obs.gauge_set("serving.ladder_rung", self._ladder_rung)
                obs.event("serving.degrade",
                          {"rung": self._ladder_rung, "direction": "down"})
        if sig and self._ladder_rung >= 3 and self.prefix_cache is not None:
            self.prefix_cache.evict(1)
        if sig and self._ladder_rung >= 4:
            self._shed_one()

    # -- supervision: retried dispatch, invariant audit, recovery -----------
    def _dispatch(self, kind: str, target, feed, fetch_list) -> list:
        """Every compiled prefill/decode/window/COW step is ENQUEUED here:
        the serving_step_fail fault site, then the executor, under the
        serving RetryPolicy; returns the fetches as device handles, unread.
        Retrying the enqueue is safe — the compiled programs write fixed KV
        slots derived from the feed, so attempt N+1 overwrites attempt N's
        partial effects exactly. Retry exhaustion raises _StepFailure;
        step() turns it into the recovery pass."""
        def attempt():
            fault_point("serving_step_fail")
            return self._exe.run(target, feed=feed, fetch_list=fetch_list,
                                 scope=self._scope, return_numpy=False)

        def on_retry(n, exc):
            self._count("step_retries")
            obs.event("serving.step_retry",
                      {"kind": kind, "attempt": n, "error": repr(exc)},
                      level="warning")

        try:
            return self._retry.call(attempt, on_retry=on_retry)
        except self._retry.retryable as e:
            raise _StepFailure(kind, e) from e

    def _fetch(self, kind: str, handles) -> list:
        """Copy a dispatched step's `handles` to the host (None stays None)
        under `pipeline.fetch`: blocks until the device has produced them.
        The step cannot be retried from here (the programs enqueued behind
        it consumed its pools): an error goes to the recovery pass."""
        try:
            with profiler.stage_timer("pipeline.fetch"):
                return [None if h is None else np.asarray(h)
                        for h in handles]
        except (RuntimeError,) + self._retry.retryable as e:
            raise _StepFailure(kind + "_fetch", e) from e

    def _run_step(self, kind: str, target, io: dict, feed: dict,
                  greedy: bool, logits: str = "logits",
                  selection: bool = False) -> dict:
        """Enqueue one prefill / window / decode step; returns its device
        handles by `_InFlight` field name. One compiled program serves
        greedy and sampled rows, marked and unmarked: the logits (`[rows,
        V]` float32) and the selection are device outputs of every step;
        what no sampler and no marked request will read is let go of here,
        when the step is enqueued, not held until its accept."""
        extra = [k for k in _EXTRA_FETCHES if k in io]
        nxt, lg, *rest = self._dispatch(kind, target, feed,
                                        self._step_fetches(io, logits))
        got = dict(zip(extra, rest))
        return {"tokens": nxt, "logits": None if greedy else lg,
                "routes": got.get("routes"),
                "selection": got.get("selection") if selection else None,
                "exit_mass": got.get("exit_mass")}

    def _enqueued(self, step: _InFlight, blocking: str | None = None) -> None:
        """`step` has just been enqueued: accept the step dispatched before
        it, which the device ran meanwhile, and keep `step` as the pending
        one; or, where `blocking` says why the host needs its values before
        it dispatches again, accept it at once."""
        self._dispatched += 1
        before, self._pending = self._pending, None
        if before is not None:
            self._accept(before, None)
        if step.tokens is not None:
            for r in step.rows:
                if r.state == RUNNING:
                    r.in_flight = 1
                    if self._leaving(r):
                        # its last step: nothing reads the slot again
                        self._free_slot(r)
        if blocking is not None:
            self._accept(step, blocking)
        else:
            self._pending = step

    def _settle(self, why: str = "settle") -> None:
        """Accept the pending step now (no-op without one): whoever is about
        to read or change request or pool state sees every dispatched token
        accepted."""
        step, self._pending = self._pending, None
        if step is not None:
            with obs.span("serving.settle"):
                self._accept(step, why)

    def _settle_or_recover(self) -> None:
        """`_settle` for the entry points outside the supervised step: a
        fetch that fails runs the recovery pass here."""
        try:
            self._settle()
        except _StepFailure as e:
            self._recover(f"step_fail:{e.kind}")

    def _accept(self, step: _InFlight, why: str | None) -> None:
        """Read `step`'s outputs and do what the host does with them: book
        routes and selections at the positions recorded at dispatch, accept
        each row's token, release finished rows. `why` None: accepted
        behind the next dispatch (deferred)."""
        tokens, logits, routes, selection = self._fetch(
            step.kind, (step.tokens, step.logits, step.routes,
                        step.selection))
        # the one family that hands exit masses back reads them beside
        exit_mass = None if step.exit_mass is None \
            else self._fetch(step.kind, (step.exit_mass,))[0]
        if why is None:
            self._count("chain.steps_deferred")
        else:
            self._count("chain.steps_blocking", labels={"why": why})
        with obs.span("serving.accept"):
            if step.kind == "decode":
                self._accept_decode(step, tokens, logits, routes, selection,
                                    exit_mass)
            else:
                self._accept_prefill(step, tokens, logits, routes, selection,
                                     exit_mass)

    def _corrupt_pool(self, hit: int) -> None:
        """The serving_pool_corrupt payload: vandalize ONE piece of
        host-side bookkeeping so the audit has something real to catch —
        a phantom refcount holder, a live page pushed back on the free
        list, or a duplicate ordinal in the newest running request's
        table (that request is poisoned and must be quarantined). The
        kind cycles with the fault's hit index; no-op when nothing is
        live."""
        in_use = [p for p in range(self.pool.num_pages)
                  if self.pool.refcount(p) > 0]
        kind = hit % 3
        if kind == 0 and in_use:
            self.pool._refs[in_use[0]] += 1
        elif kind == 1 and in_use:
            self.pool._free.append(in_use[0])
        elif kind == 2:
            live = [r for r in self._running if r.pages]
            if live:
                victim = max(live, key=lambda r: r.admit_seq)
                victim.pages.append(victim.pages[0])

    def audit_pool(self) -> tuple[list[str], list[int]]:
        """Cross-check every live page table and the prefix cache against
        the pool invariants (free list and mapped ordinals partition the
        pool; refcounts equal live holder counts). Returns (problems,
        poisoned_rids): a request whose OWN table is malformed —
        out-of-range or duplicate ordinals — is poisoned, and recovery
        quarantines it instead of replaying it."""
        self._settle_or_recover()
        return self._audit_tables()

    def _audit_tables(self) -> tuple[list[str], list[int]]:
        """`audit_pool` over the tables as they stand. It reads host
        bookkeeping alone, which a pending step leaves consistent (its rows
        hold their pages until its accept), so the periodic audit inside a
        step does not settle."""
        problems: list[str] = []
        poisoned: list[int] = []
        holders: dict[int, int] = {}
        wholders: dict[int, int] = {}
        sholders: dict[int, int] = {self._scratch_slot: 1}
        for r in self.requests.values():
            if r.state not in _TERMINAL:
                for p in r.wpages:
                    wholders[p] = wholders.get(p, 0) + 1
                for slot in (r.sslot, r.snap):
                    if slot is not None:
                        sholders[slot] = sholders.get(slot, 0) + 1
                if len(set(r.wpages)) != len(r.wpages):
                    problems.append(f"request {r.rid} maps a page of the "
                                    f"window pool twice")
                    poisoned.append(r.rid)
            if not r.pages or r.state in _TERMINAL:
                continue
            bad = False
            seen: set[int] = set()
            for p in r.pages:
                if not (0 <= p < self.pool.num_pages):
                    problems.append(f"request {r.rid} maps page {p} "
                                    f"outside the pool")
                    bad = True
                    continue
                if p in seen:
                    problems.append(f"request {r.rid} maps page {p} twice")
                    bad = True
                seen.add(p)
                holders[p] = holders.get(p, 0) + 1
            if bad:
                poisoned.append(r.rid)
        if self.prefix_cache is not None:
            for node in self.prefix_cache._nodes.values():
                holders[node.page] = holders.get(node.page, 0) + 1
                if node.wpage is not None:
                    wholders[node.wpage] = wholders.get(node.wpage, 0) + 1
                if node.snap is not None:
                    sholders[node.snap] = sholders.get(node.snap, 0) + 1
        problems.extend(self.pool.check_consistency(holders))
        if self.window_pool is not None:
            problems.extend("window pool: " + p for p in
                            self.window_pool.check_consistency(wholders))
        if self.state_pool is not None:
            problems.extend("state pool: " + p for p in
                            self.state_pool.check_consistency(sholders))
        return problems, poisoned

    def _recover(self, reason: str, poisoned=(), problems=()) -> None:
        """The recovery pass: quarantine poisoned requests (their tables
        are garbage), drop every page table and the whole prefix-cache
        index, rebuild the pool pristine, and replay every survivor from
        its PROMPT. Greedy decoding is deterministic, so the replayed
        outputs are bitwise-equal to a fault-free run (the oracle test's
        contract); sampled requests re-derive the same tokens through the
        per-(seed, rid, position) rng."""
        self._count("recovery.passes")
        # what is in flight was computed over the pools being thrown away
        self._pending = None
        self._slots_free = list(range(self._token_slots))[::-1]
        for req in self.requests.values():
            req.in_flight, req.slot = 0, None
            req.sslot = req.snap = None
        obs.event("serving.recovery",
                  {"reason": reason, "problems": list(problems)[:8],
                   "quarantined": list(poisoned),
                   "running": len(self._running),
                   "waiting": len(self._waiting)}, level="error")
        for rid in poisoned:
            req = self.requests.get(rid)
            if req is None or req.state in _TERMINAL:
                continue
            if req in self._waiting:
                self._waiting.remove(req)
            if req in self._running:
                self._running.remove(req)
            req.pages = []  # garbage table; the pool rebuild reclaims it
            req.wpages, req.wfirst = [], 0
            req.cached_len = 0
            req.state = ABORTED
            req.t_done = time.perf_counter()
            self._count("recovery.quarantined")
            obs.event("serving.request",
                      {"rid": req.rid, "phase": "quarantined",
                       "n_generated": req.n_generated}, level="error")
        survivors = sorted(self._running, key=lambda r: r.admit_seq)
        self._running = []
        for req in survivors:
            del req.all_tokens[req.prompt_len:]  # replay from the prompt
            self._unmark(req)
            req.pages = []
            req.wpages, req.wfirst = [], 0
            req.cached_len = 0
            req.state = WAITING
            req.admit_seq = -1
            self._count("recovery.replayed")
        for req in self._waiting:
            req.pages = []  # admission pins die with the pool rebuild
            req.wpages, req.wfirst = [], 0
            req.cached_len = 0
        for req in self.requests.values():
            if req.state == HANDED_OFF:
                # the rebuild forfeits the prefill pin with everything
                # else; clear the table so a late release_handoff cannot
                # double-release (the LEASE still keeps the pages alive
                # for the adopting side)
                req.pages = []
        self._waiting[:0] = survivors
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        self.pool.reset()
        if self.window_pool is not None:
            self.window_pool.reset()
        if self.state_pool is not None:
            self.state_pool.reset()
            (self._scratch_slot,) = self.state_pool.allocate(1)
        post, _ = self._audit_tables()
        if post:
            raise RuntimeError(
                f"recovery left the pool inconsistent: {post[:4]}")

    def _admit(self) -> int:
        """Admit waiting requests in policy order until pages or inflight
        slots run out, or `cfg.admit_per_step` of them are in (a deployment
        whose prompts cost more than its rows' steps caps an iteration's
        prefills, so that the rows get their step). Head-of-line backpressure: the first request that
        does not fit stops admission (no starvation of big requests by
        later small ones under fcfs). Prefix-cache hits cut the PRIVATE
        page bill: cached full pages of the prompt map with a refcount
        bump instead of an allocation.

        Admission RESERVES GROWTH: beside rows that go on, the head is
        admitted only if the pool can carry every one of them, and the
        head, to its known end (`_pages_to_end`). First by the SUM of the
        ends: free pages, the prefix cache's unshared pages counted free
        as `_allocate` treats them, against what the rows still have to
        take and what the head takes beyond its prefix hit. What the rows
        owe is summed once a call and kept up as the call admits; the pool
        keeps the other side (`cache_only`). Where the sum does not fit,
        by the TIMELINE of the ends (`_ends_fit`): the rows do not stand
        at their ends together, and one that leaves returns its pages to
        those that go on. Else the head waits as it waits for pages."""
        admitted = 0
        # pages the rows that go on still take to their ends: summed at
        # the first candidate that meets such rows
        owed = None
        cap = self.cfg.admit_per_step
        for req in self.scheduler.order(self._waiting):
            if cap and admitted >= cap:
                # the rows' step first; the queue keeps the rest
                break
            # a row whose last token is in flight takes no row of the next
            # step: its place is free now, as it would be had the host
            # waited for the token
            staying = sum(not self._leaving(r) for r in self._running)
            if staying >= self.max_inflight or not self._slots_free:
                break
            if req.deadline_t is not None \
                    and time.perf_counter() > req.deadline_t:
                # expired while WAITING: never admit, return any pin
                self._terminate(req, DEADLINE_EXCEEDED, "deadline_exceeded")
                continue
            if req.pages:
                # a previous attempt already pinned this prefix hit; the
                # pin persisted across the failed admission so eviction
                # relief could not free the match out from under the waiter
                matched = req.pages
            else:
                matched = []
                if self.prefix_cache is not None:
                    self._count("prefix_lookups")
                    if self.state_pool is not None:
                        # only up to a block whose snapshot is still held,
                        # and never the prompt's last token (its logits are
                        # the first token); the snapshot is pinned like the
                        # pages until it is copied into the row's slot
                        matched, req.snap, past = \
                            self.prefix_cache.match_snapshot(
                                req.all_tokens[:req.prompt_len],
                                (len(req.all_tokens) - 1) // self.page_size)
                        if req.snap is not None:
                            self.state_pool.share([req.snap])
                        self._count("state.recomputed_tokens",
                                    past * self.page_size)
                    elif self.window_pool is not None:
                        # only a prefix whose window tail is still held
                        matched, req.wfirst, tail = \
                            self.prefix_cache.match_resumable(
                                req.all_tokens[:req.prompt_len],
                                self._wtable_decode - 1)
                        self.window_pool.share(tail)
                        req.wpages = list(tail)
                    else:
                        matched = self.prefix_cache.match(
                            req.all_tokens[:req.prompt_len])
                    # pin the hit BEFORE allocating: the cache's own ref
                    # may be these pages' only holder, and _allocate's
                    # eviction relief under pool pressure could otherwise
                    # free the matched pages and hand them right back as
                    # this request's PRIVATE pages (one physical page
                    # mapped at two ordinals)
                    if matched:
                        self.pool.share(matched)
                    matched = self._cut_hit_for_state(req, matched)
            # +1: the decode step after prefill writes one more slot (the
            # ladder's lookahead-shrink rung drops the reservation to the
            # bare context; _ensure_writable then allocates on demand)
            lookahead = 0 if self._ladder_rung >= 2 else 1
            need = self.pool.pages_for(len(req.all_tokens) + lookahead)
            to_end = max(need, self._pages_to_end(req))
            fits = by_sum = True
            if staying and not self.prefill_only:
                # a lone request is admitted whatever its end: nothing
                # runs, nothing can be owed
                if owed is None:
                    owed = self._growth_owed()
                spare = self.pool.free_count + self.pool.cache_only
                by_sum = owed + to_end - len(matched) <= spare
                # the sum of the ends bounds every instant: where it fits
                # the timeline could only agree, and is not reckoned
                fits = by_sum or self._ends_fit(req, len(matched),
                                                lookahead, spare)
                if not fits and need - len(matched) <= spare:
                    req.held_for_growth = True
            private = self._allocate(max(0, need - len(matched))) \
                if fits else None
            if private is not None and self.window_pool is not None \
                    and not self._reserve_window(req):
                self.pool.release(private)
                private = None
            if private is not None and self.state_pool is not None:
                req.sslot = self._allocate_slot()
                if req.sslot is None:
                    self.pool.release(private)
                    private = None
            if private is None:
                # keep the pin on the request: abort/shed/deadline release
                # it through _terminate, and the next attempt starts with
                # the shared pages already held
                req.pages = matched
                req.cached_len = len(matched) * self.page_size
                # a row slot was free: the pool is what holds it
                req.waited_for_pages = True
                break
            req.pages = matched + private
            req.cached_len = len(matched) * self.page_size
            self._count("prefix_hit_tokens", req.cached_len)
            if req.waited_for_pages:
                req.waited_for_pages = False
                self._count("pool_bound_admissions")
            if req.held_for_growth:
                req.held_for_growth = False
                self._count("growth_held_admissions")
            if not by_sum:
                self._count("timeline_admissions")
            if owed is not None:
                owed += to_end - len(req.pages)
            self._waiting.remove(req)
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            obs.histogram_observe("serving.queue_s",
                                  time.perf_counter() - req.arrival_t)
            obs.event("serving.request", {"rid": req.rid, "phase": "admitted",
                                          "cached_len": req.cached_len,
                                          "pages": len(req.pages)})
            # attributes, not labels: span labels flow to the histogram
            # series key, and a per-request label would mint unbounded series
            fetch0 = self._phase_s.get("pipeline.fetch", 0.0)
            with obs.span("serving.prefill", rid=req.rid,
                          tokens=len(req.all_tokens),
                          cached_len=req.cached_len) as sp:
                self._prefill(req)
            self._observe_host_seconds("serving.prefill", sp, fetch0)
            admitted += 1
        return admitted

    def _end(self, req: GenRequest) -> int:
        """Tokens of `req`'s sequence at its known end: a row never writes
        past `prompt_len + max_new_tokens` (known at `submit`), nor past
        `max_position`. Exact where the cap is the length; under a cap far
        above the usual stop the reservation holds pages nobody writes
        (ROADMAP R12 (a''): an end bounded by the stops seen)."""
        return min(req.prompt_len + req.max_new_tokens,
                   self.cfg.max_position)

    def _pages_to_end(self, req: GenRequest) -> int:
        """Pages of the pool `req`'s table holds at its end."""
        return self.pool.pages_for(self._end(req))

    def _growth_owed(self) -> int:
        """Pages the rows that go on have yet to take to reach their ends."""
        return sum(max(0, self._pages_to_end(r) - len(r.pages))
                   for r in self._running if not self._leaving(r))

    def _row_ahead(self, r: GenRequest, lookahead: int) -> tuple:
        """What the timeline of the ends (`_ends_fit`) reads of running row
        `r`: the step from which it is gone, its tokens at step 0 (the one
        in flight and the lookahead counted), its end, the pages it holds
        and those of them it alone holds."""
        at, end = len(r.all_tokens) + r.in_flight, self._end(r)
        return (max(0, end - at) + 1, at + lookahead, end, len(r.pages),
                self.pool.sole_count(r.pages))

    def _ends_fit(self, head: GenRequest, matched: int, lookahead: int,
                  spare: int) -> bool:
        """Whether the pool carries the running rows and `head`, `matched`
        pages of whose table are its pinned prefix hit, at every decode
        step up to the head's end: the test for a head that the sum of the
        ends refused (`_admit`), `spare` the pages free or the prefix
        cache's alone.

        Time is counted in decode steps from the next one, step 0. Every
        row takes one token a step whatever windows run in between, so a
        row of `at` tokens (the one in flight counted) holds
        `pages_for(min(at + k + lookahead, end))` pages at step k. It
        writes its last slot at step `end - at - 1`, and that step is
        accepted, its pages released, when the step after it has been
        enqueued: the row is counted as holding them at step `end - at`
        too and as gone from `end - at + 1`. Gone, it has returned what it
        took meanwhile and the pages it alone holds now
        (`PagedKVPool.sole_count`: free or `cache_only` afterwards, which
        `spare` counts), not its share of a prefix other requests map.
        The head joins at step 0, one token past its prompt, and after
        its own end holds nothing: what stands then was admitted without
        it. Between two departures what the rows take only rises, so the
        test is made at the last step before each, one sort and one sweep
        over the rows. A row that stops on `eos_id` leaves earlier than
        reckoned, which only returns pages sooner.

        Under draft-verify steps a row takes up to `draft_k + 1` tokens a
        step, the rows' ends are not ordered as their lengths are, and the
        sum of the ends decides alone."""
        if self.draft_k:
            return False
        ps = self.page_size
        rows = sorted(self._row_ahead(r, lookahead) for r in self._running)
        prompt, head_end = len(head.all_tokens), self._end(head)
        # the head's last step here, had the prefix cache its whole prompt
        # (its first token is then the first step's, not its prefill's)
        last = max(0, head_end - prompt)
        head_at = prompt + 1 + lookahead
        returned = gone = 0
        for k in sorted({min(left - 1, last) for left, *_ in rows} | {last}):
            while gone < len(rows) and rows[gone][0] <= k:
                returned += rows[gone][-1]
                gone += 1
            taken = max(0, -(-min(head_at + k, head_end) // ps) - matched)
            for _, at, end, held, _ in rows[gone:]:
                taken += max(0, -(-min(at + k, end) // ps) - held)
            if taken > spare + returned:
                return False
        return True

    def _reserve_window(self, req: GenRequest) -> bool:
        """Whether the sliding layers' pool has, or the prefix cache can
        give up, the pages `req` will hold there at once beyond those it
        holds: its whole context, or at most a window and a chunk. A
        prefill allocates them chunk by chunk and nothing else allocates
        meanwhile, so a reservation that holds here cannot fail there."""
        most = min(self.pool.pages_for(len(req.all_tokens) + 1) - req.wfirst,
                   self._wtable_chunk) - len(req.wpages)
        short = most - self.window_pool.free_count
        if short > 0 and self.prefix_cache is not None:
            self.prefix_cache.strip_window(short)
        return most <= self.window_pool.free_count

    def _window_for(self, req: GenRequest, first_pos: int, tokens: int,
                    allocate) -> bool:
        """Make `req`'s compact table in the sliding layers' pool cover
        positions `first_pos - (window - 1) .. first_pos + tokens - 1`:
        the pages before leave it, the pages the new positions touch are
        taken with `allocate(1)` (False when that gave None)."""
        self._slide_window(req, first_pos)
        if not req.wpages:
            req.wfirst = first_pos // self.page_size
        last = (first_pos + tokens - 1) // self.page_size
        while req.wfirst + len(req.wpages) <= last:
            got = allocate(1)
            if got is None:
                return False
            req.wpages.extend(got)
        return True

    def _cut_hit_for_state(self, req: GenRequest, matched: list) -> list:
        """A block with state rows resumes from the row of the last WHOLE
        page before the position it continues at, and the prompt's last
        token must run (its logits are the first token): a hit that covers
        it is cut back one page, the pins of the cut pages returned."""
        if not self.cfg.stateful or not matched:
            return matched
        keep = (len(req.all_tokens) - 1) // self.page_size
        if len(matched) > keep:
            self.pool.release(matched[keep:])
            self._count("state.recomputed_tokens",
                        (len(matched) - keep) * self.page_size)
            matched = matched[:keep]
        if matched:
            self._count("state.restores")
        return matched

    @staticmethod
    def _step_fetches(io: dict, logits: str = "logits") -> list:
        """The one fetch list of a step's program (it is part of the
        executor's compile signature, so warm-up and serving share it): the
        greedy token, the logits, the experts chosen where the block
        routes, and the positions attended where it selects them."""
        return [io["next_token"], io[logits]] + [
            io[k] for k in _EXTRA_FETCHES if k in io]

    def _count_routed(self, per_expert) -> None:
        for e in np.flatnonzero(per_expert):
            obs.counter_inc("serving.moe.tokens", int(per_expert[e]),
                            {"expert": str(e)})
        if self.cfg.experts_held:
            # (token, expert) pairs the router made, and those of them that
            # fell on the experts this engine holds
            self._count("moe.routed_pairs", int(per_expert.sum()))
            self._count("moe.held_pairs",
                        int(per_expert[:self.cfg.experts_held].sum()))

    def _route_pages(self, req: GenRequest, first: int, n: int):
        """The page of each of `req`'s positions first .. first + n - 1, as
        its table stands (None where the block keeps no routes)."""
        if self._page_routes is None:
            return None
        g = first + np.arange(n)
        return np.asarray(req.pages, np.int64)[g // self.page_size]

    def _note_routes(self, pages, first: int, routes) -> None:
        """Keep the experts chosen for positions first.. (one row of
        `routes` [n, layers] each) with the `pages` that hold their K/V
        (`_route_pages`, read when the step was dispatched), and count the
        routed tokens per expert."""
        g = first + np.arange(len(routes))
        self._page_routes[pages, g % self.page_size] = routes
        self._count_routed(np.bincount(routes.ravel(),
                                       minlength=self.cfg.num_experts))

    def _note_decode_routes(self, at: list, routes) -> None:
        """One decode step's routes, a row each for the (position, page)
        pairs `at` its rows wrote (inside serving.accept): kept per page as
        above, counted per expert, and how many distinct experts each layer
        touched (what a kernel that skipped the others would have to read;
        today's streams every held expert)."""
        self._page_routes[[page for _, page in at],
                          [pos % self.page_size for pos, _ in at]] = routes
        per_layer = self._routed_per_layer(routes)
        self._count_routed(per_layer.sum(axis=0))
        # of the experts this engine holds (all of them, but for a share)
        self._count("moe.experts_touched", int(np.count_nonzero(
            per_layer[:, :self.cfg.held_experts])))
        self._count("moe.layer_steps", len(per_layer))

    def _routed_per_layer(self, routes):
        """`routes` [rows, layers(, k)] -> how many of the rows each routed
        layer sent to each expert, [layers, num_experts]."""
        L, E = self.cfg.routed_layers, self.cfg.num_experts
        per_layer = np.zeros((L, E), np.int64)
        layer_of = np.arange(L).reshape((1, L) + (1,) * (routes.ndim - 2))
        np.add.at(per_layer, (np.broadcast_to(layer_of, routes.shape),
                              routes), 1)
        return per_layer

    def _note_grouped(self, routes) -> None:
        """A window's routes, EVERY row the program ran (`routes` [rows,
        layers(, k)], the bucket's padding among them: the kernel multiplies
        those rows too): where its expert calls took the kernel's grouped
        form (`decoder_common.experts_grouped`: more rows than one token tile,
        on the chip), the calls, the (row, held expert) pairs they
        multiplied and the rows of the tiles they ran."""
        from ..ops.pallas_kernels import moe_experts

        cfg = self.cfg
        rows = len(routes)
        if rows not in self._grouped_rows:
            self._grouped_rows[rows] = cfg.family.grouped_experts \
                and decoder_common.experts_grouped(
                    rows, (cfg.routed_layers, cfg.held_experts,
                           cfg.hidden_size, cfg.ffn_size), cfg.dtype)
        if not self._grouped_rows[rows]:
            return
        held = self._routed_per_layer(routes)[:, :cfg.held_experts]
        _, visits = moe_experts.grouped_visits(held, np)
        self._count("moe.grouped_layer_steps", len(held))
        self._count("moe.grouped_pairs", int(held.sum()))
        self._count("moe.grouped_tile_rows",
                    int(visits.sum()) * moe_experts.GROUP_TILE)

    def _mark(self, req: GenRequest) -> None:
        """Mark `req` (just admitted) if it asked for its selection and one
        of the decode step's `MARK_ROWS` slots is free."""
        self._unmark(req)
        req.marked = req.keep_selection and "selection" in self._decode_io \
            and sum(r.marked for r in self._running) < sv_model.MARK_ROWS
        req._select_from = min(req.cached_len, len(req.all_tokens) - 1)

    @staticmethod
    def _unmark(req: GenRequest) -> None:
        req.marked, req._selected = False, []

    @staticmethod
    def _keep_selection(req: GenRequest, sel) -> None:
        """`sel`, n positions leading: what the next n positions of marked
        `req` attended, as the step gave it (every admission starts the
        record anew, so the positions follow one another from
        `_select_from`)."""
        req._selected.append(np.asarray(sel))

    def _seq_bucket(self, n: int) -> int:
        """The window length a prompt (or suffix, or a chunked prefill's
        tail) of `n` tokens compiles for: a power of two from 8; a block
        that prefills in chunks starts at 128 (or its chunk, if smaller):
        its windows stream every expert whatever their length, and each
        length is a program to compile."""
        low = min(128, self.cfg.prefill_chunk) if self.cfg.prefill_chunk \
            else 8
        return min(self.cfg.max_position, max(low, _round_up_pow2(n)))

    def _row_bucket(self, n: int) -> int:
        """The row count a decode step of `n` rows compiles for: a power of
        two from `cfg.min_row_bucket` up to max_inflight's."""
        return min(_round_up_pow2(max(n, self.cfg.min_row_bucket)),
                   _round_up_pow2(self.max_inflight))

    def _first_token(self, req: GenRequest, nxt, last_logits) -> int:
        """The prompt's first generated token: compiled argmax for greedy
        requests, the host-side seeded sampler otherwise."""
        if req.sampling.is_greedy:
            return int(np.asarray(nxt).reshape(-1)[0])
        rng = request_rng(self.seed, req.rid, req.n_generated)
        return sample_token(np.asarray(last_logits)[0], req.sampling, rng)

    def _prefill(self, req: GenRequest) -> None:
        """Materialize one request's context KV and (unless the whole
        prompt was cached) its first new token.

        Three regimes by prefix-cache depth: cold (classic whole-prompt
        prefill), suffix (cached_len slots mapped shared — only the suffix
        runs, through the windowed program), full hit (every prompt page
        mapped — NO prefill compute at all; the next decode step re-derives
        the last prompt slot under copy-on-write and emits token one).

        The program is enqueued and left pending: the first token is
        accepted behind the next dispatch (this iteration's decode, which
        the row joins taking its token from the device, or the next
        admission's prefill). The prompt's pages are registered with the
        prefix cache here, when the program that fills them is enqueued:
        whoever maps them runs behind it on the device, and an arrival
        admitted in this same iteration finds the hit it would have."""
        n = len(req.all_tokens)
        req.state = RUNNING
        self._mark(req)
        req.slot = self._slots_free.pop()
        self._running.append(req)
        if req.resumed:
            # what a step had computed before the preemption, computed again
            req.resumed = False
            self._count("preempted_tokens", max(0, n - req.cached_len))
        if req.cached_len >= n:
            self._count("prefix_full_hits")
            self._register_prefix(req)
            return
        greedy = req.sampling.is_greedy
        if not greedy:
            self._settle("sampled")
        if req.snap is not None:
            # resume: the snapshot into the row's own slot, the pin returned
            with obs.span("serving.state.restore"):
                self._copy_state(req.snap, req.sslot)
            self.state_pool.release([req.snap])
            req.snap = None
            self._count("state.restores")
        if self.cfg.prefill_chunk:
            step = self._prefill_chunks(req, n)
        else:
            first = req.cached_len
            with obs.span("serving.feed_build"):
                sb = self._seq_bucket(n - first)
                tok = np.zeros((1, sb), np.int32)
                tok[0, :n - first] = req.all_tokens[first:]
                pos = first + np.arange(sb, dtype=np.int32)[None, :]
                pos = np.minimum(pos, self.cfg.max_position - 1)
                if first:
                    pb = _round_up_pow2(max(
                        len(req.pages), self.pool.pages_for(first + sb)))
                else:
                    pb = max(len(req.pages), self.pool.pages_for(sb))
                pages = np.zeros((1, pb), np.int32)
                pages[0, :len(req.pages)] = req.pages
                feed = {sv_model.TOK_FEED: tok, sv_model.POS_FEED: pos,
                        sv_model.PAGES_FEED: pages,
                        sv_model.LEN_FEED: np.asarray([n - first], np.int32),
                        **self._slot_feed((req,), 1)}
                if first:
                    feed[sv_model.START_FEED] = np.asarray([first], np.int32)
            if first:
                handles = self._run_step(
                    "suffix_prefill", self._window_run, self._window_io,
                    feed, greedy, "last_logits")
                self.stats["prefill_signatures"].add(("suffix", sb, pb))
            else:
                handles = self._run_step(
                    "prefill", self._prefill_run, self._prefill_io, feed,
                    greedy, "last_logits")
                self.stats["prefill_signatures"].add((sb, pb))
            self._count("prefill_tokens_computed", n - first)
            step = _InFlight("prefill", [req], at=[(first, n - first)],
                             marked=[],
                             route_pages=self._route_pages(req, first,
                                                           n - first),
                             **handles)
        self._count("prefills")
        self._register_prefix(req)
        self._enqueued(step, None if greedy else "sampled")

    def _prefill_chunks(self, req: GenRequest, n: int) -> _InFlight:
        """Positions cached_len.. of `req` as consecutive windows of
        `cfg.prefill_chunk` tokens through the window program (one
        `serving.prefill.chunk` span each): a window writes its K/V and
        indexer keys into the pool and attends the pool, so the next one
        finds them there. Every chunk runs at the page bucket of the whole
        request: one compiled program a window length. Each chunk is a step
        program like any other: enqueued, and accepted behind the next.
        Returns the last window, enqueued and not yet handed to
        `_enqueued`."""
        chunk = self.cfg.prefill_chunk
        pb = self._page_bucket(len(req.pages))
        pages = np.zeros((1, pb), np.int32)
        pages[0, :len(req.pages)] = req.pages
        for i, c0 in enumerate(range(req.cached_len, n, chunk)):
            m = min(chunk, n - c0)
            with obs.span("serving.prefill.chunk", rid=req.rid, chunk=i,
                          tokens=m):
                if self.window_pool is not None and not self._window_for(
                        req, c0, m, self._allocate_window):
                    raise RuntimeError(
                        f"request {req.rid}: the window pool "
                        f"({self.window_pool.num_pages} pages) ran dry "
                        f"inside a prefill that admission had reserved")
                with obs.span("serving.feed_build"):
                    sb = chunk if m == chunk else self._seq_bucket(m)
                    tok = np.zeros((1, sb), np.int32)
                    tok[0, :m] = req.all_tokens[c0:c0 + m]
                    pos = np.minimum(c0 + np.arange(sb, dtype=np.int32),
                                     self.cfg.max_position - 1)[None, :]
                    feed = {sv_model.TOK_FEED: tok, sv_model.POS_FEED: pos,
                            sv_model.PAGES_FEED: pages,
                            sv_model.START_FEED: np.asarray([c0], np.int32),
                            sv_model.LEN_FEED: np.asarray([m], np.int32),
                            **self._slot_feed((req,), 1),
                            **self._state_feed((req,), 1),
                            **self._window_feed((req,), 1,
                                                self._wtable_chunk)}
                handles = self._run_step(
                    "prefill_chunk", self._window_run, self._window_io,
                    feed, req.sampling.is_greedy, "last_logits",
                    selection=req.marked)
                self.stats["prefill_signatures"].add(("suffix", sb, pb))
                self._count("prefill_tokens_computed", m)
                self._count("prefill.chunks")
                self._count_mixes(m)
                self._count_visits()
                if self.state_pool is not None:
                    self._count(f"{self._state_kind}.scan_tokens",
                                m * self.cfg.state_layers)
                    self._count(f"{self._state_kind}.scan_layer_steps",
                                self.cfg.state_layers)
                    if (c0 + m) % chunk == 0 and c0 + m <= req.prompt_len \
                            and self.prefix_cache is not None:
                        # the state the chunk leaves is the state after a
                        # cacheable block: the cache takes a copy of it
                        self._register_prefix(req, c0 + m)
                        self._snapshot(req, c0 + m)
                step = _InFlight("chunk", [req], at=[(c0, m)], marked=[],
                                 route_pages=self._route_pages(req, c0, m),
                                 **handles)
                if c0 + m < n:
                    # only the last window's token is the prompt's next
                    step.tokens = step.logits = None
                    if self.window_pool is not None:
                        # before the next chunk lets go of window pages,
                        # the cache takes its reference on them
                        self._register_prefix(req, c0 + m)
                    self._enqueued(step)
        return step

    def _accept_prefill(self, step: _InFlight, tokens, logits, routes,
                        selection, exit_mass=None) -> None:
        """A prefill's (or one chunk's) outputs, inside serving.accept."""
        req, (first, n) = step.rows[0], step.at[0]
        if routes is not None:
            self._note_routes(step.route_pages, first, routes[0, :n])
            self._note_grouped(routes[0])
        if selection is not None:
            self._keep_selection(req, selection[0, :n])
        if tokens is not None:
            if exit_mass is not None:
                req.exit_mass.append(exit_mass[0])
            self._accept_token(req, self._first_token(req, tokens, logits))

    def _register_prefix(self, req: GenRequest, upto: int | None = None
                         ) -> None:
        """Index the request's full PROMPT pages (those before position
        `upto`, while a chunked prefill is under way) so later arrivals
        sharing the prompt map them instead of recomputing. The cache takes
        its own refcount per page, so the entries outlive the request; with
        a second pool also on the window pages the request still holds."""
        if self.prefix_cache is None:
            return
        n = req.prompt_len if upto is None else min(upto, req.prompt_len)
        if self.window_pool is None:
            self.prefix_cache.insert(req.all_tokens[:n], req.pages)
        else:
            self.prefix_cache.insert(
                req.all_tokens[:n], req.pages,
                {req.wfirst + j: p for j, p in enumerate(req.wpages)})

    def _accept_token(self, req: GenRequest, tok: int) -> None:
        req.in_flight = 0
        req.all_tokens.append(tok)
        now = time.perf_counter()
        if req.t_first_token is None:
            req.t_first_token = now
            obs.histogram_observe("serving.ttft_s", now - req.arrival_t)
            obs.event("serving.request",
                      {"rid": req.rid, "phase": "first_token",
                       "ttft_s": round(now - req.arrival_t, 9)})
        if req.is_done() or len(req.all_tokens) >= self.cfg.max_position:
            if req in self._running:
                self._running.remove(req)
            if self._page_routes is not None:
                g = np.arange(req.cache_len)
                req.routes = self._page_routes[
                    np.asarray(req.pages, np.int64)[g // self.page_size],
                    g % self.page_size]
            if req.marked and req._selected:
                req._kept = (req._select_from, req._selected,
                             self.page_size)
            self._unmark(req)
            self._release(req)
            req.state = FINISHED
            req.t_done = now
            obs.histogram_observe("serving.request_s", now - req.arrival_t)
            obs.event("serving.request",
                      {"rid": req.rid, "phase": "finished",
                       "n_generated": req.n_generated,
                       "preemptions": req.preemptions,
                       "request_s": round(now - req.arrival_t, 9)})

    def _cow(self, req: GenRequest, ordinal: int) -> bool:
        """Copy-on-write req's page `ordinal`: fresh page, one in-place
        device copy across every layer's K/V pools, table repointed, old
        refcount released (other holders untouched). Over two pools the
        page of EITHER pool that someone else maps is copied, in the one
        device step (the side with nothing to copy is given page 0 onto
        itself). Returns False when the pool pressure this created
        preempted `req` itself."""
        if self._page_routes is not None:
            # the page's routes are copied below: those of a pending step
            # that computed positions in it must be booked first
            self._settle()
            if req.state != RUNNING:
                return False
        windowed = self._window_shared(req, ordinal)
        # the caller's page is copied whoever maps it, unless the call is
        # for the window pool's page alone
        sides = [(self.pool, req.pages, ordinal, self._allocate,
                  sv_model.COW_SRC_FEED, sv_model.COW_DST_FEED,
                  not windowed or self.pool.refcount(req.pages[ordinal]) > 1)]
        if self.window_pool is not None:
            sides.append((self.window_pool, req.wpages, ordinal - req.wfirst,
                          self._allocate_window, sv_model.COW_WSRC_FEED,
                          sv_model.COW_WDST_FEED, windowed))
        fresh, feed = [], {}
        for pool, table, at, allocate, src, dst, copied in sides:
            new = None
            if copied:
                new = self._take_or_preempt(req, allocate)
                if new is None:       # `req` itself was preempted
                    for other, _, _, page in fresh:
                        other.release([page])
                    return False
                fresh.append((pool, table, at, new[0]))
            feed[src] = np.asarray([table[at] if new else 0], np.int32)
            feed[dst] = np.asarray([new[0] if new else 0], np.int32)
        self._dispatch("cow", self._cow_run, feed, [])
        for pool, table, at, page in fresh:
            if pool is self.pool and self._page_routes is not None:
                self._page_routes[page] = self._page_routes[table[at]]
            pool.release([table[at]])
            table[at] = page
        self._count("cow_copies")
        return True

    def _take_or_preempt(self, req: GenRequest, allocate):
        """`allocate(1)`, room made (`_make_room`) while it gives None; None
        once `req` itself is no longer running."""
        new = allocate(1)
        while new is None:
            if not self._make_room(req):
                return None
            new = allocate(1)
        return new

    def _make_room(self, req: GenRequest) -> bool:
        """A pool ran dry under `req`: take back what the waiting requests
        pin if they pin anything (the prefix hit of a head that admission
        refused: where only it and the cache hold a page, the page was
        counted spare when the rows were admitted and is theirs to evict;
        the waiter matches again at its next attempt), else accept the
        pending step if there is one (rows that finish return their pages,
        and nobody is preempted with a token in flight), else preempt the
        youngest running request. False once `req` itself is no longer
        running."""
        pinning = [r for r in self._waiting
                   if r.pages or r.wpages or r.snap is not None]
        for r in pinning:
            self._release(r)
        if pinning:
            return True
        if self._pending is not None:
            self._settle()
            return req.state == RUNNING
        victim = max(self._running, key=lambda r: r.admit_seq)
        if victim is req and len(self._running) == 1:
            raise RuntimeError(
                f"request {req.rid} needs a page but its pool is "
                f"exhausted with nothing left to preempt (the pool has "
                f"{self.pool.num_pages} pages)")
        self._preempt(victim)
        return victim is not req

    def _window_shared(self, req: GenRequest, ordinal: int) -> bool:
        """Whether logical page `ordinal` of `req` is a page of the window
        pool that someone else maps too."""
        j = ordinal - req.wfirst
        return self.window_pool is not None and 0 <= j < len(req.wpages) \
            and self.window_pool.refcount(req.wpages[j]) > 1

    def _ensure_writable(self, lookahead: int = 0) -> dict[int, int]:
        """Every running request must OWN every page its next write window
        [cache_len, cache_len + lookahead] touches, and own it EXCLUSIVELY
        (refcount 1) — shared pages copy-on-write first. On pool exhaustion
        the lookahead shrinks before anyone is preempted (speculative slots
        are optional; the required slot is cache_len's). Returns per-rid
        granted lookahead."""
        with obs.span("serving.ensure_writable"):
            return self._grow_and_cow(lookahead)

    def _grow_and_cow(self, lookahead: int) -> dict[int, int]:
        ps = self.page_size
        granted: dict[int, int] = {}
        for req in list(self._running):
            # a row whose last token is in flight writes nothing more
            if req.state != RUNNING or self._leaving(req):
                continue
            extra = lookahead
            while (req.cache_len + extra) // ps >= len(req.pages):
                got = self._allocate(1)
                if got is not None:
                    req.pages.extend(got)
                    continue
                if extra > 0:
                    extra -= 1
                    continue
                if not self._make_room(req):
                    break
            if req.state != RUNNING:
                continue
            if self.window_pool is not None and not self._window_for(
                    req, req.cache_len, 1,
                    lambda n, req=req: self._take_or_preempt(
                        req, self._allocate_window)):
                continue              # preempted for a window page
            top = min(req.cache_len + extra, len(req.pages) * ps - 1)
            ok = True
            for o in range(req.cache_len // ps, top // ps + 1):
                if self.pool.refcount(req.pages[o]) > 1 or (
                        self.window_pool is not None
                        and self._window_shared(req, o)):
                    if not self._cow(req, o):
                        ok = False
                        break
            if ok and req.state == RUNNING:
                granted[req.rid] = extra
        return granted

    def _preempt(self, req: GenRequest) -> None:
        """Back to the head of the waiting queue, pages returned. Callers
        settle the pending step first (`_make_room`): a row is never
        preempted with a token in flight."""
        self._running.remove(req)
        self._release(req)
        req.state = WAITING
        self._unmark(req)
        req.preemptions += 1
        req.resumed = True
        self._count("preemptions")
        # head of the waiting queue: a preempted request lost work, so it
        # outranks new arrivals under fcfs
        self._waiting.insert(0, req)

    def _paged_decode_call(self, bb: int, pb: int):
        """(grid steps, whether the kernel walks a list of blocks, the pool's
        shape, its item size) of one layer's paged decode call at the (rows,
        page bucket) signature; (0, False, ..) where the XLA gather serves
        it or the family reads its pages through a kernel of its own
        (`_attend_kernel`): the ops' own arithmetic, asked once a
        signature."""
        call = self._paged_call_by_signature.get((bb, pb))
        if call is None:
            cfg = self.cfg
            call = (0, False, None, 0)
            if not (cfg.selects or cfg.latent):
                pool = self._scope.find_var(
                    "kv_cache.k" if cfg.scanned
                    else pool_var_names(cfg.num_layers)[0][0])
                # the scanned blocks attend with float32 queries whatever
                # dtype their weights and pools have
                shape = ((bb, cfg.num_heads, cfg.head_dim),
                         "float32" if cfg.scanned else cfg.dtype,
                         pool.shape, pool.dtype, pb)
                call = (attention_ops.paged_decode_grid_steps(
                            *shape, tp=self.tp),
                        attention_ops.paged_decode_walks(*shape, tp=self.tp),
                        tuple(pool.shape), np.dtype(pool.dtype).itemsize)
            self._paged_call_by_signature[(bb, pb)] = call
        return call

    def _indexer_kernel(self) -> bool:
        """Whether the paged Pallas kernel scores a decode step's context
        (the XLA gather otherwise): the ops' own answer (rows do not enter
        it), asked once."""
        if self._indexer_kernel_runs is None:
            cfg = self.cfg
            pool = self._scope.find_var(INDEX_POOL)
            self._indexer_kernel_runs = sparse_moe_ops.paged_indexer_runs(
                (1, cfg.index_heads, cfg.index_head_dim), pool.shape,
                pool.dtype)
        return self._indexer_kernel_runs

    def _conv_kernel(self) -> bool:
        """Whether the Pallas kernel moves a decode step's convolution
        tails on in place (the XLA gather and scatter otherwise): the ops'
        own answer (rows do not enter it), asked once."""
        if self._conv_kernel_runs is None:
            self._conv_kernel_runs = parallel_ssm_ops.conv_update_runs(
                self._scope.find_var(STATE_POOLS[1]).shape,
                self.cfg.ssm_conv)
        return self._conv_kernel_runs

    def _ssm_kernel(self, bb: int) -> bool:
        """Whether the Pallas kernel updates the states of a decode step of
        `bb` rows in place (XLA's gather, update and scatter otherwise):
        the ops' own answer, asked once a row bucket."""
        runs = self._ssm_kernel_runs.get(bb)
        if runs is None:
            cfg = self.cfg
            pool = self._scope.find_var(STATE_POOLS[0]).shape
            runs = self._ssm_kernel_runs[bb] = \
                cfg.family.state_update_runs(cfg, bb, pool)
        return runs

    def _attend_kernel(self, bb: int, pb: int) -> int:
        """Whether the Pallas kernel computes the absorbed attention of a
        decode step of `bb` rows behind `pb` pages (`absorbed_attention_fn`
        otherwise): the ops' own answer, asked once a signature. Without an
        indexer the answer is the rows ONE call takes (`bb`, or a share of
        it where the step's rows go a group a call; 0: the XLA arm)."""
        runs = self._attend_kernel_runs.get((bb, pb))
        if runs is None:
            cfg = self.cfg
            pool = self._scope.find_var(LATENT_POOL)
            slots = pb * self.page_size
            q_shape = (bb, cfg.num_heads, cfg.kv_lora_rank)
            # without an indexer the rows' pages are read in place; behind
            # one, the rows it selected are gathered first
            runs = self._attend_kernel_runs[(bb, pb)] = \
                latent_moe_ops.latent_attend_runs(
                    q_shape, (bb, min(cfg.index_topk, slots),
                              pool.shape[-1]),
                    cfg.dtype, cfg.rope_head_dim) if cfg.selects \
                else latent_moe_ops.paged_attend_rows(
                    q_shape, pool.shape, cfg.dtype, cfg.rope_head_dim)
        return runs

    def _count_visits(self, rows: int = 0) -> None:
        """One step program of a family whose layers a token passes several
        times: every layer once a visit, and in a decode step of `rows`
        rows as many row x visit pairs (one paged attention call's row
        each)."""
        if self.cfg.family.loop_visits:
            self._count("loop.visits", self.cfg.cache_planes)
            self._count("loop.decode_row_visits",
                        rows * self.cfg.cache_planes)

    def _count_mixes(self, tokens: int) -> None:
        """`tokens` tokens through every sub-layer's mix of a residual path
        of several streams (two a layer)."""
        if self.cfg.hc_mult > 1:
            self._count("hc.mix_tokens", tokens * 2 * self.cfg.num_layers)

    def _decode_once(self, sp) -> bool:
        """One decode step under the open `serving.decode` span `sp`:
        enqueued over the running rows that go on, each taking from the
        device the token the host has not read yet; the step dispatched
        before it is accepted behind it."""
        # ladder rung 1+ falls back to plain one-token decode: the verify
        # window is the most speculative compute in the engine, so it is
        # the first thing sustained overload switches off
        if self.draft_k > 0 and self._ladder_rung < 1:
            self._settle("spec")        # the draft reads host history
            return self._decode_spec(sp)
        greedy = all(r.sampling.is_greedy for r in self._running)
        if not greedy:
            self._settle("sampled")     # the sampler reads host logits
        self._ensure_writable(0)
        rows = [r for r in self._running
                if r.state == RUNNING and not self._leaving(r)]
        if not rows:
            return False
        ps = self.page_size
        with obs.span("serving.feed_build"):
            bb = self._row_bucket(len(rows))
            pb = self._page_bucket(max(len(r.pages) for r in rows))
            tok = np.zeros((bb, 1), np.int32)
            pos = np.zeros((bb,), np.int32)
            pages = np.zeros((bb, pb), np.int32)
            mask = np.zeros((bb, 1), np.float32)
            for i, r in enumerate(rows):
                tok[i, 0] = r.all_tokens[-1]    # unread where one is in flight
                pos[i] = r.cache_len
                pages[i, :len(r.pages)] = r.pages
                mask[i, 0] = 1.0
            marked = [i for i, r in enumerate(rows) if r.marked]
            feed = {sv_model.TOK_FEED: tok, sv_model.POS_FEED: pos,
                    sv_model.PAGES_FEED: pages, sv_model.MASK_FEED: mask,
                    **self._mark_feed(marked),
                    **self._slot_feed(rows, bb, decode=True),
                    **self._state_feed(rows, bb),
                    **self._window_feed(rows, bb, self._wtable_decode)}
            at = [(r.cache_len, r.pages[r.cache_len // ps]) for r in rows]
        self._step_rows = len(rows)
        sp.note(rows=len(rows), bb=bb, pb=pb)
        self._count("decode_steps")
        self.stats["decode_signatures"].add((bb, pb))
        # what one layer's decode attention read: every row's own context,
        # or, where the kernel walks the step's list of page blocks, a run
        # of pages that rows share ONCE and behind it every row's own (its
        # own rule over these feeds; the rows padded in left out as ever)
        grid_steps, walks, pool_shape, itemsize = self._paged_decode_call(
            bb, pb)
        attended = sum(pos + 1 for pos, _ in at)
        read = {"pages": sum(pos // ps + 1 for pos, _ in at),
                "tokens": attended, "blocks": grid_steps, "shared": False}
        if walks:
            from ..ops.pallas_kernels import paged_attention

            read = paged_attention.walk_counts(
                pages[:len(rows)], pos[:len(rows)] + 1, pool_shape, itemsize)
        self._count("decode_context_pages", read["pages"])
        self._count("decode_grid_steps", read["blocks"])
        self._count_visits(len(rows))
        if self.window_pool is not None:
            full, slide = self._full_layers, self._slide_layers
            W = self.cfg.sliding_window
            self._count("kv.global_row_pages", sum(len(r.pages) for r in rows))
            self._count("kv.window_row_pages",
                        sum(len(r.wpages) for r in rows))
            self._count("attn.full_context_tokens", full * read["tokens"])
            self._count("attn.attended_tokens", full * attended)
            self._count("attn.shared_kernel_layer_steps",
                        full if read["shared"] else 0)
            self._count("attn.window_context_tokens",
                        slide * sum(min(W, pos + 1) for pos, _ in at))
            self._count("attn.full_layer_steps", full)
            self._count("attn.window_layer_steps", slide)
        if self.state_pool is not None:
            # the state update's rows and calls under its own kind's name:
            # a Mamba-2 mixer's or a Kimi-Delta layer's
            kind = self._state_kind
            self._count(f"{kind}.decode_row_layers",
                        len(rows) * self.cfg.state_layers)
            self._count(f"{kind}.decode_layer_steps", self.cfg.state_layers)
            self._count("ssm.conv_kernel_layer_steps",
                        self.cfg.state_layers if self._conv_kernel() else 0)
            self._count(f"{kind}.decode_pad_row_layers",
                        (bb - len(rows)) * self.cfg.state_layers
                        if self._ssm_kernel(bb) else 0)
        if self.cfg.selects_within(pb * ps):
            L, k = self.cfg.num_layers, self.cfg.index_topk
            self._count("sparse.context_tokens",
                        L * sum(pos + 1 for pos, _ in at))
            self._count("sparse.selected_tokens",
                        L * sum(min(k, pos + 1) for pos, _ in at))
            self._count("sparse.layer_steps", L)
            self._count("sparse.kernel_layer_steps",
                        L if self._indexer_kernel() else 0)
        if self.cfg.latent:
            # cache rows the rows' attention read out of the latent pool
            # (their selection's, or every slot of a table that fits it or
            # has no indexer over it) and how many of them were live
            # positions
            L, k = self.cfg.latent_layers, self.cfg.index_topk
            select = self.cfg.selects_within(pb * ps)
            if not self.cfg.selects:
                # the family's layer steps, as where every step selects
                self._count("sparse.layer_steps", L)
            self._count_mixes(len(rows))
            self._count("latent.gathered_rows", L * (
                sum(min(k, pos + 1) for pos, _ in at) if select
                else len(at) * pb * ps))
            self._count("latent.attended_tokens", L * sum(
                min(k, pos + 1) if select else pos + 1 for pos, _ in at))
            kernel = self._attend_kernel(bb, pb)
            self._count("latent.attend_kernel_layer_steps",
                        L if kernel else 0)
            if not self.cfg.selects:
                from ..ops.pallas_kernels import paged_latent_attend

                # pool pages the rows' attention fetched: the kernel reads
                # a run of pages that the rows of ONE call share once for
                # all of them (its own rule over these feeds), the XLA arm
                # each row's
                lens = (pos + 1) * mask[:, 0].astype(np.int32)
                shape = self._scope.find_var(LATENT_POOL).shape
                self._count("latent.pages_read", L * (
                    sum(paged_latent_attend.pages_read(
                        pages[g:g + kernel], lens[g:g + kernel], shape)
                        for g in range(0, bb, kernel))
                    if kernel else sum(pos // ps + 1 for pos, _ in at)))
        handles = self._run_step("decode", self._decode_run, self._decode_io,
                                 feed, greedy, selection=bool(marked))
        self._enqueued(_InFlight("decode", rows, at=at, marked=marked,
                                 **handles),
                       None if greedy else "sampled")
        return True

    def _accept_decode(self, step: _InFlight, tokens, logits, routes,
                       selection, exit_mass=None) -> None:
        """A decode step's outputs, inside serving.accept. A row that
        stopped on `eos_id` while this step was in flight ran it for
        nothing: its output is dropped."""
        live = [i for i, r in enumerate(step.rows) if r.state == RUNNING]
        self._count("chain.discarded_rows", len(step.rows) - len(live))
        tokens = tokens.reshape(-1)
        if routes is not None and live:
            self._note_decode_routes([step.at[i] for i in live],
                                     routes[live])
        for j, i in enumerate(step.marked):
            if step.rows[i].state == RUNNING:
                self._keep_selection(step.rows[i], selection[j][None])
        if exit_mass is not None and live:
            # what each visit's gate would let leave, over the rows' tokens
            for t, mass in enumerate(exit_mass[live].sum(axis=0)):
                self._count("loop.exit_mass", float(mass),
                            labels={"visit": str(t + 1)})
        for i in live:
            r = step.rows[i]
            if r.sampling.is_greedy:
                t = int(tokens[i])
            else:
                rng = request_rng(self.seed, r.rid, r.n_generated)
                t = sample_token(logits[i], r.sampling, rng)
            self._count("decode_tokens")
            if exit_mass is not None:
                r.exit_mass.append(exit_mass[i])
            self._accept_token(r, t)

    def _decode_spec(self, sp) -> bool:
        """One draft-verify window step: propose k tokens per row
        (ngram_draft over the row's own history), run all k+1 positions
        through the windowed program in ONE compiled step, and accept the
        verify's greedy tokens up to the first draft mismatch — bitwise the
        plain greedy sequence, 1..k+1 tokens per step. The draft reads each
        row's accepted history, so the step before is settled (the caller)
        and this one is read as soon as it is enqueued."""
        k = self.draft_k
        S = k + 1
        granted = self._ensure_writable(k)
        rows = [r for r in self._running if r.state == RUNNING
                and r.rid in granted]
        if not rows:
            return False
        with obs.span("serving.feed_build"):
            plans = []
            for r in rows:
                n_valid = min(S,
                              self.cfg.max_position - len(r.all_tokens),
                              r.max_new_tokens - r.n_generated,
                              granted.get(r.rid, 0) + 1)
                plans.append((r, max(1, n_valid),
                              ngram_draft(r.all_tokens, k)))
            bb = min(_round_up_pow2(len(rows)),
                     _round_up_pow2(self.max_inflight))
            pb = _round_up_pow2(max(len(r.pages) for r in rows))
            tok = np.zeros((bb, S), np.int32)
            pos = np.zeros((bb, S), np.int32)
            pages = np.zeros((bb, pb), np.int32)
            start = np.zeros((bb,), np.int32)
            lens = np.zeros((bb,), np.int32)
            for i, (r, n_valid, drafts) in enumerate(plans):
                tok[i, 0] = r.all_tokens[-1]
                tok[i, 1:] = drafts
                pos[i] = np.minimum(r.cache_len + np.arange(S),
                                    self.cfg.max_position - 1)
                pages[i, :len(r.pages)] = r.pages
                start[i] = r.cache_len
                lens[i] = n_valid
            feed = {sv_model.TOK_FEED: tok, sv_model.POS_FEED: pos,
                    sv_model.PAGES_FEED: pages, sv_model.START_FEED: start,
                    sv_model.LEN_FEED: lens, **self._slot_feed((), bb)}
        self._step_rows = len(rows)
        sp.note(rows=len(rows), bb=bb, pb=pb)
        toks, lg = self._dispatch(
            "verify_window", self._window_run, feed,
            [self._window_io["tokens"], self._window_io["logits"]])
        if all(r.sampling.is_greedy for r, _, _ in plans):
            lg = None
        toks, lg = self._fetch("verify_window", (toks, lg))
        self._dispatched += 1
        self._count("chain.steps_blocking", labels={"why": "spec"})
        with obs.span("serving.accept"):
            self._count("decode_steps")
            self._count("spec_steps")
            self.stats["decode_signatures"].add((bb, pb))
            for i, (r, n_valid, drafts) in enumerate(plans):
                if not r.sampling.is_greedy:
                    # sampling rows take exactly one (seeded) token per
                    # step; draft acceptance is a greedy-only contract
                    rng = request_rng(self.seed, r.rid, r.n_generated)
                    t = sample_token(lg[i, 0], r.sampling, rng)
                    self._count("decode_tokens")
                    self._accept_token(r, t)
                    continue
                m = 0
                while m < n_valid - 1 \
                        and int(drafts[m]) == int(toks[i, m]):
                    m += 1
                self._count("spec_proposed", n_valid - 1)
                self._count("spec_accepted", m)
                for j in range(m + 1):
                    if r.state != RUNNING:
                        break
                    self._count("decode_tokens")
                    self._accept_token(r, int(toks[i, j]))
        return True
