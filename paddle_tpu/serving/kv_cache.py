"""Paged KV-cache manager: fixed-size pages over a preallocated HBM pool.

The serving problem this solves ("Ragged Paged Attention",
arXiv:2604.15464): a max-seq-len KV buffer per request wastes
(max_len - actual_len) slots of HBM per request, which is what actually caps
concurrent requests — not compute. Instead:

  * the DEVICE side is one preallocated pool per layer,
    [num_pages, page_size, num_heads * head_dim] for K and V each
    (`pool_shape`: a token's heads side by side in ONE lane-dense row),
    living in the serving scope as persistable vars the compiled
    prefill/decode steps read AND write (the executor donates the buffers,
    so every append is an in-place HBM scatter, never a reallocation);
  * the HOST side (this module) is pure bookkeeping: a free-list of page
    ids, a PER-PAGE REFCOUNT, and a per-request page table (list of page
    ids). allocate/share/release are O(pages moved); nothing here touches
    the device.

Multi-tenancy (ISSUE 11) rides the refcounts: requests sharing a system
prompt map the SAME physical pages into their page tables (`share` — a
refcount bump, not a copy), and the `PrefixCache` below keeps prompt pages
alive past their request's lifetime so later arrivals reuse them. A page
returns to the free list only when its LAST holder releases it; a holder
that wants to WRITE a shared page must copy-on-write first (engine.py).

Admission control is the caller's job (engine.py): `can_allocate` is the
backpressure predicate — when the free list runs dry, new requests queue
instead of OOMing the pool, and mid-decode growth preempts rather than
corrupts.

Disaggregated serving (ISSUE 19) adds two more pieces of pure bookkeeping:

  * LEASES — a page can be pinned by a named lease (`lease_grant`), the
    in-transit holder class of the prefill->decode KV handoff: the pin
    keeps the pages alive while neither engine's request table maps them,
    `lease_transfer` hands the refcount to the adopting side without a
    release/share round-trip, and `check_consistency` models leases as
    first-class holders so a mid-handoff audit neither false-flags nor
    misses them;
  * `OwnedPoolView` — a per-engine facade over ONE shared pool that
    mirrors the allocator API while keeping the owner's own holder
    ledger. The ledger belongs to the pool layer (what a disaggregated
    memory node tracks per client), so when a replica dies the router
    reclaims its pins through `forfeit()` without ever touching the dead
    engine.
"""
from __future__ import annotations

import heapq

import jax.numpy as jnp

__all__ = ["PagedKVPool", "PrefixCache", "OwnedPoolView", "pool_var_names",
           "pool_shape", "create_device_pools", "declare_pool_vars",
           "STACKED_POOLS", "INDEX_POOL", "JOINED_POOL", "LATENT_POOL",
           "WINDOW_POOLS",
           "STATE_POOLS", "stacked_pool_shapes", "state_pool_shapes",
           "declare_stacked_pools", "declare_state_pools",
           "create_stacked_pools", "create_state_pools"]


def pool_var_names(num_layers: int) -> list[tuple[str, str]]:
    """The (K, V) pool var names per layer — the one spelling shared by the
    program builders (model.py), the scope initializer, and tests."""
    return [(f"kv_cache.k{i}", f"kv_cache.v{i}") for i in range(num_layers)]


def pool_shape(num_pages: int, page_size: int, num_heads: int,
               head_dim: int) -> tuple[int, int, int]:
    """THE shape of one K or V pool: `[num_pages, page_size, nh * dh]`, a
    token's heads side by side in one row (head h is columns
    `h*dh : (h+1)*dh`). Every program, op and kernel reads and writes the
    pool in this shape and never reshapes the pool itself (only what it
    gathered from it), so the buffer keeps ONE device layout from step to
    step. Why not `[pages, page_size, nh, dh]`: under the TPU's (8, 128)
    tiling two small minor dims (12, 64) pad to (16, 128), 2.67x, so the
    chip's client stored that buffer pages-minor-most while the scatter and
    the Pallas kernel wanted it row-major, and every decode and prefill
    step copied all 24 pools there and back (86% of device time; PERF.md,
    PR 24). With `page_size` a multiple of 8 and `nh * dh` a multiple of
    128 the row-major layout is an exact number of tiles: unpadded, the
    client's default, what a Pallas block `(1, page_size, nh*dh)` DMAs as
    is, and indifferent to `num_kv_heads != num_heads`."""
    return (int(num_pages), int(page_size), int(num_heads) * int(head_dim))


def declare_pool_vars(block, num_layers: int, num_pages: int, page_size: int,
                      num_heads: int, head_dim: int, dtype: str = "float32"):
    """Declare the pool vars in a program block (both the prefill and the
    decode program must see them so the executor's def-use analysis
    classifies them read-write and donates their buffers). Under TP,
    model.apply_tp_annotations shards their last dim (heads are contiguous
    in it) afterwards."""
    shape = list(pool_shape(num_pages, page_size, num_heads, head_dim))
    for kn, vn in pool_var_names(num_layers):
        for name in (kn, vn):
            block.create_var(name=name, shape=shape, dtype=dtype,
                             persistable=True, stop_gradient=True)


def create_device_pools(scope, num_layers: int, num_pages: int,
                        page_size: int, num_heads: int, head_dim: int,
                        dtype: str = "float32") -> None:
    """Preallocate the zeroed device pools into `scope` (once, at engine
    construction — this is the only allocation the cache ever does)."""
    shape = pool_shape(num_pages, page_size, num_heads, head_dim)
    for kn, vn in pool_var_names(num_layers):
        for name in (kn, vn):
            scope.set_var(name, jnp.zeros(shape, jnp.dtype(dtype)))


# -- the stacked pools of the scanned block families -------------------------
# (model.py "cca_moe", "sparse_moe"): all layers in ONE K and ONE V buffer, a
# layer's page p at row `l * num_pages + p`, and beside them what the family
# keeps besides K/V: one float32 STATE row a page ("cca_moe") or one
# indexer key a TOKEN ("sparse_moe"). A family that GATHERS single tokens
# ("sparse_moe") keeps a token's K and V in ONE row of ONE pool instead of
# two. The allocator below is the same one: a page id names a K/V slab and
# its state row or indexer-key slab in every layer, so share, copy-on-write
# and release move them together.
STACKED_POOLS = ("kv_cache.k", "kv_cache.v", "kv_cache.state")
INDEX_POOL = "kv_cache.index"
JOINED_POOL = "kv_cache.kv"
# A family whose attention keeps ONE compressed row a token ("latent_moe":
# a latent and its one rotary key, 576 values where K and V of every head
# would be 32,768) keeps it as 32-bit words of one pool, gathered a token
# at a time like the joined rows, beside the indexer keys.
LATENT_POOL = "kv_cache.latent"
# A family with sliding-window layers ("hybrid_moe") keeps THEIR K and V in
# a second pair of stacked pools with page ids of their own (a second
# `PagedKVPool`): a sliding layer needs the last `window` tokens of a row
# and nothing older, so a row maps there only the pages its window touches,
# while its full-attention layers keep every page in the pools above.
WINDOW_POOLS = ("kv_cache.wk", "kv_cache.wv")
# A family with a RECURRENT state ("parallel_ssm": a state-space layer's `S`
# and its convolution's tail) keeps it in pools of SLOTS, not pages: the
# state is rewritten in place by every token, does not grow, and is larger
# than the K/V of the tokens that made it (4.26 MB a layer a sequence
# beside a 128-token page's 262 KB), so one a page is not affordable. A
# slot id names a row `l * slots + id` of both pools in every layer; the
# ids are handed out by a third `PagedKVPool` (pages of one "token"): a
# running row owns ONE live slot, the prefix cache owns SNAPSHOTS (a slot
# copied from a row's at a chunk boundary, hung on the cached block that
# ends there), and resuming from one COPIES it into the row's own slot,
# because unlike a K/V page a state is never read-only for its reader.
# Both pools keep a slot as whole (8, 128) tiles where its widths are
# (`state_pool_shapes`: `S` `[rows, heads * N, P]`, the tail `[rows,
# tail_width / 128, 128]`), so that a decode step's kernels move ONE slot as
# a block and nothing else.
STATE_POOLS = ("kv_cache.ssm", "kv_cache.conv")


def stacked_pool_shapes(num_layers: int, num_pages: int, page_size: int,
                        kv_width: int, state_width: int, dtype: str,
                        index_width: int = 0, joined: bool = False,
                        names: tuple = STACKED_POOLS, latent: bool = False):
    """[(name, shape, dtype)] of the stacked pools. K and V rows are
    `kv_width = num_kv_heads * head_dim` wide (`pool_shape`'s lane-dense
    row), in a pool each or, `joined`, side by side in one row of 32-bit
    words of one pool (`sparse_moe_ops.join_rows_fn`: the same bytes, and a
    token read with one address); `latent`, `kv_width` is the width of a
    token's ONE compressed row, kept as words of `LATENT_POOL` alone (past
    128 words the row is padded to whole 128-lane tiles). With
    `state_width` a state pool holds one
    float32 row a page: the state after the page's latest token, final once
    the page is full. With
    `index_width` an index pool holds `index_width` values a token in the
    K/V pools' dtype, `[rows, index_width, page_size]`: a page's tokens side
    by side on the lanes, so that a page of 128 slots is whole 128-lane
    rows whatever the key's width (`pool_shape` says why a 64-wide minor
    dimension would not do) and the scores' matmul reads it as it lies."""
    # `names`: the K, V (and state) pools' names, for a second set of them
    rows = int(num_layers) * int(num_pages)
    kv = (rows, int(page_size), int(kv_width))
    if latent:
        # rows wider than a 128-lane tile are whole tiles: the chip's
        # client stores a `[rows, 128, 288]` array slots-minor, and every
        # step would copy the pool into the row-major form and back
        words = int(kv_width) * jnp.dtype(dtype).itemsize // 4
        if words > 128:
            words = -(-words // 128) * 128
        pools = [(LATENT_POOL, kv[:2] + (words,), "int32")]
    elif joined:
        words = 2 * int(kv_width) * jnp.dtype(dtype).itemsize // 4
        pools = [(JOINED_POOL, kv[:2] + (words,), "int32")]
    else:
        pools = [(names[0], kv, dtype), (names[1], kv, dtype)]
    if state_width:
        pools.append((names[2], (rows, int(state_width)), "float32"))
    if index_width:
        pools.append((INDEX_POOL, (rows, int(index_width), int(page_size)),
                      dtype))
    return pools


def state_pool_shapes(num_layers: int, num_slots: int, heads: int,
                      state: int, head_dim: int, tail_width: int,
                      pack: int = 1):
    """[(name, shape, dtype)] of the pools of recurrent state: `S`, `[rows,
    heads * state, head_dim]` (a slot's heads one slab, the state dimension
    on the sublanes: `pallas_kernels.ssm_update` says why; `pack` heads
    narrower than the lanes side by side in whole 128-lane rows: `[rows,
    heads / pack * state, pack * head_dim]`), and the
    convolution's tail, the last `conv - 1` pre-convolution rows one behind
    the other, `tail_width` values a slot: as whole (8, 128) tiles, `[rows,
    tail_width / 128, 128]`, where they are whole (`tail_width % 1024 ==
    0`: a decode step then reads and writes ONE slot as a block,
    `pallas_kernels.conv_update`), `[rows, tail_width]` otherwise. Both
    float32, `rows = num_layers * num_slots` (the layers that hold a
    state)."""
    rows, tail_width = int(num_layers) * int(num_slots), int(tail_width)
    tail = (rows, tail_width // 128, 128) if tail_width % 1024 == 0 \
        else (rows, tail_width)
    return [(STATE_POOLS[0], (rows, int(heads) // int(pack) * int(state),
                              int(pack) * int(head_dim)), "float32"),
            (STATE_POOLS[1], tail, "float32")]


def declare_state_pools(block, *geometry) -> None:
    for name, shape, dtype in state_pool_shapes(*geometry):
        block.create_var(name=name, shape=list(shape), dtype=dtype,
                         persistable=True, stop_gradient=True)


def create_state_pools(scope, *geometry) -> None:
    for name, shape, dtype in state_pool_shapes(*geometry):
        scope.set_var(name, jnp.zeros(shape, jnp.dtype(dtype)))


def declare_stacked_pools(block, *geometry) -> None:
    for name, shape, dtype in stacked_pool_shapes(*geometry):
        block.create_var(name=name, shape=list(shape), dtype=dtype,
                         persistable=True, stop_gradient=True)


def create_stacked_pools(scope, *geometry) -> None:
    for name, shape, dtype in stacked_pool_shapes(*geometry):
        scope.set_var(name, jnp.zeros(shape, jnp.dtype(dtype)))


class PagedKVPool:
    """Refcounted free-list allocator over `num_pages` page ids.

    Deliberately not thread-safe: the continuous-batching engine owns it
    from one scheduler thread (the compiled steps carry the parallelism).
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages <= 0 or page_size <= 0:
            raise ValueError(
                f"pool needs positive pages/page_size, got {num_pages}/"
                f"{page_size} (FLAGS_serving_pool_pages / "
                f"FLAGS_serving_page_size)")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        # LIFO free list: recently-freed pages are re-used first, keeping
        # the pool's hot working set small
        self._free: list[int] = list(range(self.num_pages - 1, -1, -1))
        self._refs: list[int] = [0] * self.num_pages
        # in-transit holder class (ISSUE 19): lease id -> pinned page table
        self._leases: dict[str, list[int]] = {}
        # pages a prefix cache indexes (`index` / `unindex`) and how many of
        # them nobody else holds, kept as refcounts pass through 1: what
        # `PrefixCache.evict` could give back, read by admission in O(1)
        self._indexed: set[int] = set()
        self.cache_only = 0

    # -- sizing ---------------------------------------------------------------
    def pages_for(self, n_tokens: int) -> int:
        """Pages a context of `n_tokens` slots needs (ceil)."""
        return max(1, -(-int(n_tokens) // self.page_size))

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def occupancy(self) -> float:
        return self.pages_in_use / self.num_pages

    def refcount(self, page: int) -> int:
        return self._refs[page]

    def sole_count(self, pages) -> int:
        """How many of `pages` one of their holders would hand back by
        releasing them: those that nobody else maps but a prefix cache,
        free or `cache_only` afterwards."""
        refs, indexed = self._refs, self._indexed
        return sum(refs[p] - (p in indexed) == 1 for p in pages)

    # -- allocation -----------------------------------------------------------
    def can_allocate(self, n: int) -> bool:
        return n <= len(self._free)

    def allocate(self, n: int) -> list[int] | None:
        """Pop `n` page ids at refcount 1, or None (backpressure — never a
        partial grab, so a failed admission leaves the pool exactly as it
        found it)."""
        if n > len(self._free):
            return None
        got = self._free[-n:]
        del self._free[-n:]
        for p in got:
            self._refs[p] = 1
        return got

    def share(self, pages: list[int]) -> None:
        """Add one holder to each page (prefix reuse: a refcount bump, not a
        copy). Only live pages can be shared — sharing a free page would
        resurrect garbage."""
        for p in pages:
            if not (0 <= p < self.num_pages):
                raise ValueError(f"sharing page {p} outside pool "
                                 f"[0, {self.num_pages})")
            if self._refs[p] <= 0:
                raise ValueError(f"sharing free page {p} (refcount 0)")
        for p in pages:
            if self._refs[p] == 1 and p in self._indexed:
                self.cache_only -= 1
            self._refs[p] += 1

    def release(self, pages: list[int]) -> int:
        """Drop one holder from each page; pages whose refcount hits zero
        return to the free list. Returns how many pages were actually freed.
        Releasing below zero (a double-free) raises BEFORE any mutation."""
        counts: dict[int, int] = {}
        for p in pages:
            if not (0 <= p < self.num_pages):
                raise ValueError(f"freeing page {p} outside pool "
                                 f"[0, {self.num_pages})")
            counts[p] = counts.get(p, 0) + 1
        for p, c in counts.items():
            if c > self._refs[p]:
                raise ValueError(
                    f"double-free of page {p} (releasing {c} holders, "
                    f"refcount {self._refs[p]})")
        freed = 0
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)
                freed += 1
            elif self._refs[p] == 1 and p in self._indexed:
                self.cache_only += 1
        return freed

    def index(self, page: int) -> None:
        """A prefix cache's holder on live `page`: `share`, and from here
        on `cache_only` counts the page whenever that holder is its last."""
        self.share([page])
        self._indexed.add(page)

    def unindex(self, pages) -> None:
        """Forget that a cache indexes `pages` (those of them it does):
        before the cache releases its holder, or drops it unreleased ahead
        of a rebuild."""
        for p in pages:
            if p in self._indexed:
                self._indexed.discard(p)
                if self._refs[p] == 1:
                    self.cache_only -= 1

    def free(self, pages: list[int]) -> None:
        """Single-holder spelling of `release` (the PR 7 API)."""
        self.release(pages)

    # -- leases: the in-transit holder class (ISSUE 19) -----------------------
    def lease_grant(self, lease_id: str, pages: list[int]) -> None:
        """Pin `pages` under a named lease (one extra holder per page, via
        `share` — only live pages can be leased). The lease is the handoff
        protocol's safety net: it keeps the pages alive even if BOTH the
        granting and the adopting engine die mid-transfer."""
        if lease_id in self._leases:
            raise ValueError(f"lease {lease_id!r} already granted")
        self.share(pages)
        self._leases[lease_id] = list(pages)

    def lease_transfer(self, lease_id: str) -> list[int]:
        """Commit a lease: drop the lease record WITHOUT releasing the
        refcount — ownership of the pin moves to the adopting holder (its
        page table / owner ledger), so the handoff is a pure metadata move
        with no release/share window where the pages could be freed."""
        if lease_id not in self._leases:
            raise KeyError(f"lease {lease_id!r} not held")
        return self._leases.pop(lease_id)

    def lease_release(self, lease_id: str) -> int:
        """Reap a lease: drop the record AND its pin (the orphaned-prepare
        path — commit never arrived). Returns pages actually freed."""
        if lease_id not in self._leases:
            raise KeyError(f"lease {lease_id!r} not held")
        return self.release(self._leases.pop(lease_id))

    def lease_pages(self, lease_id: str) -> list[int]:
        return list(self._leases[lease_id])

    @property
    def leased_page_count(self) -> int:
        return sum(len(p) for p in self._leases.values())

    # -- invariant audit (ISSUE 14) -------------------------------------------
    def check_consistency(self,
                          holders: "dict[int, int] | None" = None
                          ) -> list[str]:
        """Audit the pool invariants; returns the violations found ([] =
        clean). The two invariants every allocate/share/release must
        preserve:

          * the free list and the mapped pages PARTITION the pool: every
            page is either on the free list with refcount 0 or off it with
            refcount > 0, exactly once;
          * with `holders` (page id -> how many live page-table/cache
            entries map it, built by the engine), each page's refcount
            equals its holder count — a phantom holder pins HBM forever, a
            missing one frees a page someone still reads. Leased pages
            (ISSUE 19) count as one holder per lease pin, so a page that is
            mid-handoff — pinned by a lease while no request table maps
            it — audits clean, and a forged lease record (a pin the
            refcount never backed) audits dirty.

        Pure read; the recovery pass runs it before and after a rebuild."""
        problems: list[str] = []
        free_set = set(self._free)
        lease_holds: dict[int, int] = {}
        for lid, pages in self._leases.items():
            for p in pages:
                if not (0 <= p < self.num_pages):
                    problems.append(f"lease {lid!r} pins page {p} outside "
                                    f"the pool [0, {self.num_pages})")
                    continue
                lease_holds[p] = lease_holds.get(p, 0) + 1
        for p, c in sorted(lease_holds.items()):
            if self._refs[p] < c:
                problems.append(
                    f"page {p} carries {c} lease pins but refcount "
                    f"{self._refs[p]} (forged or duplicate lease)")
        if len(free_set) != len(self._free):
            dupes = sorted({p for p in self._free if self._free.count(p) > 1})
            problems.append(f"free list holds duplicate entries {dupes[:8]}")
        for p in sorted(free_set):
            if not (0 <= p < self.num_pages):
                problems.append(f"free list holds page {p} outside the pool "
                                f"[0, {self.num_pages})")
            elif self._refs[p] != 0:
                problems.append(f"page {p} is on the free list with "
                                f"refcount {self._refs[p]}")
        for p in range(self.num_pages):
            r = self._refs[p]
            if r < 0:
                problems.append(f"page {p} has negative refcount {r}")
            elif r == 0 and p not in free_set:
                problems.append(f"page {p} has refcount 0 but is missing "
                                f"from the free list")
        alone = sum(self._refs[p] == 1 for p in self._indexed)
        if alone != self.cache_only:
            problems.append(f"cache_only counts {self.cache_only} pages but "
                            f"{alone} indexed pages have one holder")
        if holders is not None:
            for p in range(self.num_pages):
                h = holders.get(p, 0) + lease_holds.get(p, 0)
                if self._refs[p] > 0 and self._refs[p] != h:
                    leased = lease_holds.get(p, 0)
                    suffix = f" (of which {leased} leased)" if leased else ""
                    problems.append(f"page {p} refcount {self._refs[p]} != "
                                    f"{h} live holders{suffix}")
                elif self._refs[p] == 0 and h:
                    problems.append(f"page {p} is free but {h} live holders "
                                    f"still map it")
        return problems

    def reset(self) -> None:
        """Rebuild the pristine state: every page free at refcount 0 — the
        recovery pass's pool rebuild. The caller must drop every page table
        and prefix-cache entry FIRST (their page ids are garbage after
        this); the device pools need no touch, replayed prefills overwrite
        them."""
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._refs = [0] * self.num_pages
        self._leases = {}
        self._indexed = set()
        self.cache_only = 0


class OwnedPoolView:
    """Per-engine facade over ONE shared `PagedKVPool` (disaggregated
    serving, ISSUE 19).

    Mirrors the allocator surface the engine and its PrefixCache use
    (allocate/share/release/free/refcount/can_allocate/pages_for), while
    keeping an OWNER LEDGER: how many holders this owner has on each page.
    The ledger buys three things a raw shared pool cannot give:

      * a per-engine audit (`check_consistency`) scoped to the engine's
        own holdings — another engine's pages are not "phantom holders";
      * per-engine leak accounting (`owned_pages_in_use`) while occupancy
        and backpressure still read the honest GLOBAL pool pressure;
      * dead-replica reclamation (`forfeit`) — the ledger is pool-layer
        state (what a disaggregated memory node tracks per client), so
        the router can return a SIGKILLed replica's pins to the free list
        without ever touching the dead engine.

    `adopt_transferred` records pins whose refcount arrived by
    `PagedKVPool.lease_transfer` — the commit half of the KV handoff.
    Not thread-safe, like the pool underneath: disaggregated fleets run
    the inline pump (one scheduler thread owns the shared pool).
    """

    def __init__(self, pool: PagedKVPool, owner: str):
        self.pool = pool
        self.owner = str(owner)
        self._held: dict[int, int] = {}

    # -- delegated sizing/pressure (GLOBAL: backpressure must be honest) ----
    @property
    def num_pages(self) -> int:
        return self.pool.num_pages

    @property
    def page_size(self) -> int:
        return self.pool.page_size

    @property
    def free_count(self) -> int:
        return self.pool.free_count

    @property
    def pages_in_use(self) -> int:
        return self.pool.pages_in_use

    @property
    def owned_pages_in_use(self) -> int:
        """Distinct pages this owner holds (the per-engine leak base)."""
        return len(self._held)

    # the serving_pool_corrupt chaos payload vandalizes these directly
    @property
    def _refs(self):
        return self.pool._refs

    @property
    def _free(self):
        return self.pool._free

    def occupancy(self) -> float:
        return self.pool.occupancy()

    def pages_for(self, n_tokens: int) -> int:
        return self.pool.pages_for(n_tokens)

    def refcount(self, page: int) -> int:
        return self.pool.refcount(page)

    def sole_count(self, pages) -> int:
        return self.pool.sole_count(pages)

    def can_allocate(self, n: int) -> bool:
        return self.pool.can_allocate(n)

    # -- ledgered mutations --------------------------------------------------
    def _note(self, pages, d: int) -> None:
        for p in pages:
            c = self._held.get(p, 0) + d
            if c > 0:
                self._held[p] = c
            else:
                self._held.pop(p, None)

    def allocate(self, n: int) -> list[int] | None:
        got = self.pool.allocate(n)
        if got is not None:
            self._note(got, +1)
        return got

    def share(self, pages: list[int]) -> None:
        self.pool.share(pages)
        self._note(pages, +1)

    def release(self, pages: list[int]) -> int:
        freed = self.pool.release(pages)
        self._note(pages, -1)
        return freed

    def free(self, pages: list[int]) -> None:
        self.release(pages)

    # the prefix cache's holders: the count is the POOL's, every owner's
    # cache in it
    @property
    def cache_only(self) -> int:
        return self.pool.cache_only

    def index(self, page: int) -> None:
        self.pool.index(page)
        self._note([page], +1)

    def unindex(self, pages) -> None:
        self.pool.unindex(pages)

    def adopt_transferred(self, pages: list[int]) -> None:
        """Record pins whose refcount was moved here by `lease_transfer`
        (handoff commit): ledger only — the pool refcount already counts
        them, bumping it again would pin the pages forever."""
        for p in pages:
            if self.pool.refcount(p) <= 0:
                raise ValueError(f"adopting free page {p} (refcount 0)")
        self._note(pages, +1)

    def forfeit(self) -> int:
        """Return EVERY pin this owner holds to the shared pool (the owner
        died — its requests, admission pins, and prefix-cache refs will
        never release themselves). Lease pins are the HandoffManager's,
        not the owner's, so in-transit pages survive the forfeit. Returns
        pages actually freed."""
        freed = 0
        # a dead owner's cache unindexes nothing itself
        self.pool.unindex(self._held)
        for p, c in list(self._held.items()):
            freed += self.pool.release([p] * c)
        self._held.clear()
        return freed

    def reset(self) -> None:
        """The engine recovery pass's pool rebuild, owner-scoped: drop this
        owner's pins only — resetting the SHARED pool underneath would
        vandalize every other engine's live state."""
        self.forfeit()

    # -- owner-scoped audit --------------------------------------------------
    def check_consistency(self,
                          holders: "dict[int, int] | None" = None
                          ) -> list[str]:
        """Global partition + lease invariants from the shared pool, plus
        the owner-scoped holder check: `holders` (built by THIS engine)
        must equal the owner ledger exactly, and the ledger can never
        exceed the global refcount."""
        problems = list(self.pool.check_consistency(None))
        if holders is not None:
            for p, c in sorted(self._held.items()):
                h = holders.get(p, 0)
                if h != c:
                    problems.append(
                        f"[{self.owner}] page {p}: owner ledger holds {c} "
                        f"but {h} live holders map it")
                if self.pool.refcount(p) < c:
                    problems.append(
                        f"[{self.owner}] page {p}: owner ledger holds {c} "
                        f"exceeding pool refcount {self.pool.refcount(p)}")
            for p, h in sorted(holders.items()):
                if h and p not in self._held:
                    problems.append(
                        f"[{self.owner}] page {p} mapped by {h} live "
                        f"holders but absent from the owner ledger")
        return problems


class _PrefixNode:
    __slots__ = ("nid", "page", "key", "parent_id", "children", "last_use",
                 "wpage", "wlast_use", "snap", "slast_use")

    def __init__(self, nid, page, key, parent_id):
        self.nid = nid
        self.page = page
        self.key = key              # (parent_id, token_block) — exact match
        self.parent_id = parent_id
        self.children = 0
        self.last_use = 0
        self.wpage = None           # the block's page of the window pool
        self.wlast_use = 0
        self.snap = None            # the slot of the state after the block
        self.slast_use = 0


class PrefixCache:
    """Prefix index keyed on token-prefix hashes at PAGE granularity.

    A trie over full token blocks: node (parent, tuple_of_page_size_tokens)
    -> physical page id holding exactly that block's KV. The cache itself
    holds one refcount on every indexed page, so prompt pages survive their
    request and later requests with the same system prompt map them with a
    `share` instead of re-prefilling (the copy-on-write discipline in
    engine.py keeps them immutable). Keys are EXACT token tuples chained
    through parent ids — a hash collision can therefore never map the wrong
    page (correctness does not ride Python's hash).

    Eviction is LRU over leaf nodes whose page nobody else holds
    (refcount 1 == the cache's own ref): evicting a shared page would free
    no HBM anyway, and an interior node can't go before its children or the
    chain below it would dangle. The LRU order lives in a lazy min-heap of
    (last_use, nid) stamps — every touch pushes a fresh stamp, pops discard
    stale ones — so `evict(need)` is O((popped + need) log n) instead of a
    full O(nodes) scan per freed page (a scheduler-thread stall at exactly
    the pool-pressure moments eviction runs).

    Two pools (`window_pool`, a family with sliding-window layers): a node
    keeps its block's page of the full layers' pool and, WHERE ONE IS STILL
    HELD, its page of the sliding layers' pool (`wpage`, one more cache
    refcount, in that pool). A prefix of n pages can be resumed only if the
    window pages covering the window before position `n * page_size` are
    all held (`match_resumable`): the K/V a sliding layer needs to go on.
    The cache's reference keeps those tail pages alive after their request
    ends; a node's two pages are evicted together; and under pressure in
    the window pool alone the cache gives up window pages (never nodes),
    least recently RESUMED FROM first (`strip_window`): a lookup stamps the
    tail it hands out, not the path to it, so the interior of a long
    shared prompt goes first and its tail last.

    Recurrent state (`state_pool`, a family whose sequences carry a state
    every token rewrites): a node MAY hold a SNAPSHOT, the slot (`snap`, the
    cache's own, one refcount in the state pool) of the state after its
    block's last token, hung there by the engine (`hang_snapshot`) when a
    prefill chunk ended on that block. It is the same rule with a tail of
    one: a prefix can be resumed only at a block whose snapshot is held
    (`match_snapshot`), the blocks matched past it are recomputed; a node's
    snapshot goes with its pages; and under pressure in the state pool the
    cache gives up snapshots (never nodes), least recently resumed from
    first (`strip_snapshots`).
    """

    def __init__(self, pool: PagedKVPool, window_pool=None, state_pool=None):
        self.pool = pool
        self.window_pool = window_pool
        self.state_pool = state_pool
        self._wheap: list[tuple[int, int]] = []  # (wlast_use, nid), lazy
        self._sheap: list[tuple[int, int]] = []  # (slast_use, nid), lazy
        self.stripped_window_pages = 0
        self.stripped_snapshots = 0
        self.snapshots_held = 0
        self.page_size = pool.page_size
        self._nodes: dict[tuple, _PrefixNode] = {}
        self._by_id: dict[int, _PrefixNode] = {}
        self._heap: list[tuple[int, int]] = []   # (last_use, nid), lazy
        self._next_id = 1
        self._clock = 0
        self.lookups = 0
        self.hit_pages = 0
        self.inserted_pages = 0
        self.evicted_pages = 0
        self.evicted_snapshots = 0      # gone with their block's pages

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _touch(self, node: _PrefixNode) -> None:
        node.last_use = self._tick()
        heapq.heappush(self._heap, (node.last_use, node.nid))

    @property
    def pages_held(self) -> int:
        return len(self._nodes)

    def match(self, tokens) -> list[int]:
        """Longest chain of cached pages covering a prefix of `tokens`
        (full blocks only). Bumps LRU stamps on the path."""
        self.lookups += 1
        path = self._path(tokens)
        for node in path:
            self._touch(node)
        self.hit_pages += len(path)
        return [node.page for node in path]

    def _path(self, tokens) -> list:
        """The chain of cached nodes covering a prefix of `tokens` (full
        blocks), untouched."""
        path: list[_PrefixNode] = []
        pid = 0
        for i in range(len(tokens) // self.page_size):
            block = tuple(int(t) for t in
                          tokens[i * self.page_size:(i + 1) * self.page_size])
            node = self._nodes.get((pid, block))
            if node is None:
                break
            path.append(node)
            pid = node.nid
        return path

    def _touch_held(self, node: _PrefixNode, stamp: str, heap: list) -> None:
        setattr(node, stamp, self._tick())
        heapq.heappush(heap, (getattr(node, stamp), node.nid))

    def _touch_window(self, node: _PrefixNode) -> None:
        self._touch_held(node, "wlast_use", self._wheap)

    def _resumable(self, path: list, tail: int, held: str) -> tuple:
        """(n, how many of its last blocks are the tail): the longest
        prefix of `path` whose last min(tail, n) nodes all hold `held` (a
        window page, a snapshot); (0, 0) if none does."""
        for end in range(len(path), 0, -1):
            want = min(tail, end)
            if all(getattr(node, held) is not None
                   for node in path[end - want:end]):
                return end, want
        return 0, 0

    def match_resumable(self, tokens, tail: int) -> tuple:
        """`match` for two pools: the longest cached prefix of `tokens`
        (full blocks) whose last `tail` blocks (all of them, if it has
        fewer) each hold a window page, as (pages, first block of the tail,
        the tail's window pages); a longer match whose window tail was
        given up falls back to the longest shorter one that can resume, or
        to ([], 0, [])."""
        self.lookups += 1
        path = self._path(tokens)
        n, held = self._resumable(path, tail, "wpage")
        for node in path[:n]:
            self._touch(node)
        for node in path[n - held:n]:
            self._touch_window(node)
        self.hit_pages += n
        return ([node.page for node in path[:n]], n - held,
                [node.wpage for node in path[n - held:n]])

    def match_snapshot(self, tokens, limit: int) -> tuple:
        """`match` for a recurrent state: the longest cached prefix of
        `tokens` of at most `limit` blocks whose LAST block holds a
        snapshot, as (pages, that snapshot's slot or None, blocks matched
        past it that the caller recomputes); a longer match whose snapshot
        was given up falls back to the deepest boundary that still holds
        one, or to ([], None, blocks matched)."""
        self.lookups += 1
        path = self._path(tokens)
        n, _ = self._resumable(path[:limit], 1, "snap")
        for node in path[:n]:
            self._touch(node)
        if n:
            self._touch_held(path[n - 1], "slast_use", self._sheap)
        self.hit_pages += n
        return ([node.page for node in path[:n]],
                path[n - 1].snap if n else None, len(path) - n)

    def snapshot_block(self, tokens, blocks: int):
        """The cached block that ends `tokens[:blocks * page_size]`, if it
        is cached and holds no snapshot yet: where `hang_snapshot` may hang
        one. None otherwise."""
        path = self._path(tokens[:blocks * self.page_size])
        if len(path) != blocks or path[-1].snap is not None:
            return None
        return path[-1]

    def hang_snapshot(self, node: _PrefixNode, slot: int) -> None:
        """Hang `slot` (allocated by the caller: its one refcount becomes
        the cache's) on `node`, a block `snapshot_block` gave."""
        node.snap = int(slot)
        self.snapshots_held += 1
        self._touch_held(node, "slast_use", self._sheap)

    def _strip(self, need: int, heap: list, held: str, stamp: str,
               pool) -> int:
        """Give up to `need` of the `held` ids (window pages, snapshots)
        back to `pool`'s free list, least recently resumed from first,
        nodes kept: only ids nobody else maps (refcount 1 == the cache's
        own). Returns ids freed."""
        freed = 0
        skipped: list[tuple[int, int]] = []
        while freed < need and heap:
            at, nid = heapq.heappop(heap)
            node = self._by_id.get(nid)
            if node is None or getattr(node, held) is None \
                    or getattr(node, stamp) != at:
                continue
            if pool.refcount(getattr(node, held)) != 1:
                skipped.append((at, nid))
                continue
            pool.release([getattr(node, held)])
            setattr(node, held, None)
            freed += 1
        for entry in skipped:
            heapq.heappush(heap, entry)
        return freed

    def strip_window(self, need: int) -> int:
        """Give up to `need` window pages back to the window pool's free
        list, least recently resumed from first, nodes kept: only pages
        nobody else maps (refcount 1 == the cache's own). Returns pages
        freed."""
        freed = self._strip(need, self._wheap, "wpage", "wlast_use",
                            self.window_pool)
        self.stripped_window_pages += freed
        return freed

    def strip_snapshots(self, need: int) -> int:
        """`strip_window` for snapshots: up to `need` slots back to the
        state pool, least recently resumed from first; a snapshot a waiting
        request has pinned stays. Returns slots freed."""
        freed = self._strip(need, self._sheap, "snap", "slast_use",
                            self.state_pool)
        self.stripped_snapshots += freed
        self.snapshots_held -= freed
        return freed

    def insert(self, tokens, pages: list[int], window_pages=None) -> int:
        """Index `tokens`' full blocks onto `pages` (pages[i] must hold
        block i's KV, already written). New nodes take a cache refcount via
        pool.index; blocks already indexed are left on their existing page
        (first writer wins — both copies hold identical KV). Returns the
        number of pages newly indexed. `window_pages` {block: its page of
        the window pool}, for the blocks the writer still holds there: a
        node without one takes it (one refcount in that pool), new or
        not."""
        pid = 0
        added = 0
        for i in range(len(tokens) // self.page_size):
            block = tuple(int(t) for t in
                          tokens[i * self.page_size:(i + 1) * self.page_size])
            key = (pid, block)
            node = self._nodes.get(key)
            if node is None:
                self.pool.index(pages[i])
                node = _PrefixNode(self._next_id, pages[i], key, pid)
                self._next_id += 1
                self._nodes[key] = node
                self._by_id[node.nid] = node
                if pid:
                    self._by_id[pid].children += 1
                added += 1
                self.inserted_pages += 1
            if window_pages and node.wpage is None and i in window_pages:
                self.window_pool.share([window_pages[i]])
                node.wpage = window_pages[i]
                self._touch_window(node)
            self._touch(node)
            pid = node.nid
        return added

    def evict(self, need: int) -> int:
        """Release up to `need` pages back to the free list, LRU-first over
        evictable leaves. Returns pages actually freed (may be < need when
        every remaining page is still mapped by a live request).

        Pops the stamp heap: stale stamps (node gone, or re-touched since)
        are discarded; stamps of nodes that are currently NOT evictable
        (interior, or a live request still maps the page) are set aside and
        reinserted afterwards, so a node that becomes evictable later —
        its request released the page, or its children were dropped — is
        still reachable through its standing stamp."""
        freed = 0
        skipped: list[tuple[int, int]] = []
        while freed < need and self._heap:
            stamp, nid = heapq.heappop(self._heap)
            node = self._by_id.get(nid)
            if node is None or node.last_use != stamp:
                continue                     # stale: dropped or re-touched
            if node.children or self.pool.refcount(node.page) != 1:
                skipped.append((stamp, nid))
                continue
            self._drop(node)
            freed += 1
        for entry in skipped:
            heapq.heappush(self._heap, entry)
        return freed

    def _drop(self, node: _PrefixNode) -> None:
        del self._nodes[node.key]
        del self._by_id[node.nid]
        if node.parent_id:
            parent = self._by_id[node.parent_id]
            parent.children -= 1
            if parent.children == 0:
                # the parent just became a leaf: restore its stamp so the
                # SAME evict pass can cascade up the chain (its original
                # stamp may sit in `skipped` until the pass ends)
                heapq.heappush(self._heap, (parent.last_use, parent.nid))
        self.pool.unindex([node.page])
        self.pool.release([node.page])
        if node.wpage is not None:
            self.window_pool.release([node.wpage])
        if node.snap is not None:
            self.state_pool.release([node.snap])
            self.evicted_snapshots += 1
            self.snapshots_held -= 1
        self.evicted_pages += 1

    def clear(self) -> int:
        """Drop the WHOLE index without releasing any page (recovery path:
        the pool underneath is about to be rebuilt, so the cache's
        refcounts no longer mean anything — releasing them would double-
        mutate state the rebuild resets anyway). Returns entries dropped.
        Use `flush` everywhere else."""
        n = len(self._nodes)
        self.pool.unindex([node.page for node in self._nodes.values()])
        self._nodes.clear()
        self._by_id.clear()
        self._heap.clear()
        self._wheap.clear()
        self._sheap.clear()
        self.snapshots_held = 0
        return n

    def flush(self) -> int:
        """Evict every evictable entry (end-of-run accounting / tests):
        afterwards the only indexed pages left are ones a live request
        still maps."""
        total = 0
        while True:
            freed = self.evict(len(self._nodes) or 1)
            total += freed
            if freed == 0:
                return total
