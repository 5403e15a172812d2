"""Multi-tenant serving tests (ISSUE 11): refcounted pages + page-granular
prefix cache with copy-on-write, speculative draft-verify decoding (exact
under the greedy oracle), the temperature/top-k/top-p sampling suite with
seeded determinism, and TP-sharded decode through per-shard tuner keys."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import tuning
from paddle_tpu.serving import (PagedKVPool, PrefixCache, SamplingParams,
                                ServingEngine, decoder_tiny, ngram_draft,
                                sample_token)


def _prompts(cfg, seed, lens):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, cfg.vocab_size, n)) for n in lens]


def _generate(cfg, prompts, max_new=6, **engine_kw):
    eng = ServingEngine(cfg, page_size=4, pool_pages=64, max_inflight=4,
                        **engine_kw)
    rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_drained()
    return eng, [eng.result(r) for r in rids]


# -- pool refcounts -----------------------------------------------------------

def test_pool_refcount_share_release():
    """share bumps a holder, release drops one; a page returns to the free
    list only when the LAST holder releases it — and over-releasing raises
    before any mutation."""
    pool = PagedKVPool(8, 4)
    got = pool.allocate(2)
    assert [pool.refcount(p) for p in got] == [1, 1]
    pool.share(got)
    assert [pool.refcount(p) for p in got] == [2, 2]
    assert pool.release(got) == 0, "a held page must not free"
    assert pool.free_count == 6
    assert pool.release(got) == 2
    assert pool.free_count == 8
    with pytest.raises(ValueError, match="double-free"):
        pool.release([got[0]])
    with pytest.raises(ValueError, match="sharing free page"):
        pool.share([got[0]])
    # a single release call over-counting a page must raise pre-mutation
    more = pool.allocate(1)
    before = pool.free_count
    with pytest.raises(ValueError, match="double-free"):
        pool.release([more[0], more[0]])
    assert pool.free_count == before and pool.refcount(more[0]) == 1


# -- prefix cache mechanics ---------------------------------------------------

def test_prefix_cache_match_insert_evict_lru():
    """Page-granular trie: full blocks match longest-prefix-wins; eviction
    is LRU over leaves whose page only the cache holds, and never touches
    a page a request still maps."""
    pool = PagedKVPool(16, 4)
    cache = PrefixCache(pool)
    toks = list(range(1, 13))                      # 3 full blocks
    pages = pool.allocate(3)
    assert cache.insert(toks, pages) == 3
    assert [pool.refcount(p) for p in pages] == [2, 2, 2]
    assert cache.match(toks) == pages
    assert cache.match(toks[:7]) == pages[:1], "partial block must not match"
    assert cache.match([99] + toks[1:]) == []
    # the request releases; pages persist under the cache's refcount
    pool.release(pages)
    assert pool.free_count == 16 - 3
    # a second chain, older LRU stamp than the refreshed first chain
    other = pool.allocate(2)
    cache.insert(list(range(50, 58)), other)
    pool.release(other)
    cache.match(toks)                              # refresh chain 1
    freed = cache.evict(1)
    assert freed == 1
    assert cache.match(list(range(50, 58))) == other[:1], (
        "LRU evicts the stale chain's LEAF first")
    # pages shared with a "request" are not evictable
    pool.share([pages[0]])
    cache.evict(16)
    assert cache.match(toks[:4]) == pages[:1], "mapped page was evicted"
    pool.release([pages[0]])
    assert cache.flush() == 1
    assert pool.free_count == 16


# -- shared-prefix serving ----------------------------------------------------

def test_shared_prefix_requests_share_pages_and_match_plain_engine():
    """Concurrent requests sharing a system prompt: the later admissions
    map the earlier request's pages (refcount > 1, prefill computes only
    the suffix) and generation matches the prefix-cache-off engine."""
    cfg = decoder_tiny()
    rng = np.random.default_rng(11)
    sysp = list(rng.integers(1, cfg.vocab_size, 12))
    prompts = [sysp + list(rng.integers(1, cfg.vocab_size, 3))
               for _ in range(3)]
    _, want = _generate(cfg, prompts, prefix_cache=False)
    eng, got = _generate(cfg, prompts, prefix_cache=True)
    assert got == want
    st = eng.stats
    assert st["prefix_hit_tokens"] >= 2 * 12 // 4 * 4, "no pages shared"
    total = sum(len(p) for p in prompts)
    assert st["prefill_tokens_computed"] < total, (
        "prefix hits did not reduce prefill compute")
    assert eng.leaked_pages() == 0
    eng.flush_prefix_cache()
    assert eng.pool.free_count == eng.pool.num_pages


def test_full_prefix_hit_cow_and_isolation():
    """A page-aligned identical prompt full-hits: ZERO prefill compute, the
    first decode write copy-on-writes the shared tail page, and the copy
    leaves the original request's pages (and the cache's) untouched —
    tokens exactly match the cache-off engine for both."""
    cfg = decoder_tiny()
    rng = np.random.default_rng(5)
    prompt = list(rng.integers(1, cfg.vocab_size, 8))   # 2 full pages (ps 4)
    _, want = _generate(cfg, [prompt], max_new=5, prefix_cache=False)

    eng = ServingEngine(cfg, page_size=4, pool_pages=64, max_inflight=4,
                        prefix_cache=True)
    r1 = eng.submit(prompt, max_new_tokens=5)
    eng.run_until_drained()
    computed_before = eng.stats["prefill_tokens_computed"]
    r2 = eng.submit(prompt, max_new_tokens=5)
    eng.run_until_drained()
    assert eng.result(r1) == want[0]
    assert eng.result(r2) == want[0]
    st = eng.stats
    assert st["prefix_full_hits"] == 1
    assert st["prefill_tokens_computed"] == computed_before, (
        "a full hit must not compute any prefill")
    assert st["cow_copies"] >= 1, "the shared-boundary write never COW'd"
    assert eng.leaked_pages() == 0
    eng.flush_prefix_cache()
    assert eng.pool.free_count == eng.pool.num_pages


def test_prefix_cache_evicts_under_pool_pressure():
    """A pool mostly full of cached prompts still admits new work: unshared
    cache entries evict LRU-first instead of backpressuring live requests."""
    cfg = decoder_tiny()
    rng = np.random.default_rng(9)
    eng = ServingEngine(cfg, page_size=4, pool_pages=12, max_inflight=2,
                        prefix_cache=True)
    for _ in range(4):  # leaves ~8 cached pages in a 12-page pool
        eng.submit(list(rng.integers(1, 97, 8)), max_new_tokens=2)
        eng.run_until_drained()
    held = eng.prefix_cache.pages_held
    assert held >= 6
    eng.submit(list(rng.integers(1, 97, 20)), max_new_tokens=3)
    eng.run_until_drained()
    assert eng.prefix_cache.evicted_pages > 0, "pressure never evicted"
    assert eng.leaked_pages() == 0


def test_admit_pins_matched_prefix_pages_before_allocation():
    """Pool-pressure admission with a prefix hit: _allocate's eviction
    relief must never reclaim the pages match() just returned (the cache's
    own ref may be their only holder). Without the share-before-allocate
    pin, the evicted page comes straight back off the LIFO free list as one
    of the SAME request's private pages — one physical page mapped at two
    ordinals, silent KV corruption."""
    cfg = decoder_tiny()
    rng = np.random.default_rng(17)
    hot = list(rng.integers(1, cfg.vocab_size, 8))    # kept running
    cold = list(rng.integers(1, cfg.vocab_size, 8))   # cached, idle
    tail = list(rng.integers(1, cfg.vocab_size, 12))

    def run(prefix_cache):
        eng = ServingEngine(cfg, page_size=4, pool_pages=8, max_inflight=4,
                            prefix_cache=prefix_cache)
        r1 = eng.submit(hot, max_new_tokens=8)
        eng.step()
        r2 = eng.submit(cold, max_new_tokens=1)
        steps = 0
        while eng.requests[r2].state != "finished":
            eng.step()
            steps += 1
            assert steps < 100
        # let r1 grow to its 4th page: free pages drop to 2, so admitting
        # cold+tail (6 pages, 2 matched) must reclaim BOTH matched pages
        # through the eviction-relief path
        while len(eng.requests[r1].pages) < 4:
            eng.step()
            steps += 1
            assert steps < 100
        # cold's prompt pages sit in the cache at refcount 1 (the only
        # evictable entries — hot's pages are pinned by the running r1);
        # without the pin, eviction frees them and the LIFO free list hands
        # one back inside a prefill-written ordinal of the same request
        r3 = eng.submit(cold + tail, max_new_tokens=4)
        while eng.has_work():
            eng.step()
            steps += 1
            assert steps < 200
            for r in eng.requests.values():
                assert len(set(r.pages)) == len(r.pages), (
                    f"request {r.rid} maps a physical page at two "
                    f"ordinals: {r.pages}")
            assert eng.leaked_pages() == 0
        return eng, [eng.result(r) for r in (r1, r2, r3)]

    _, want = run(prefix_cache=False)
    eng, got = run(prefix_cache=True)
    assert got == want, "pressure admission diverged from the cache-off run"
    eng.flush_prefix_cache()
    assert eng.pool.free_count == eng.pool.num_pages


# -- speculative decoding -----------------------------------------------------

def test_ngram_draft_proposes_history_continuation():
    toks = [1, 2, 3, 9, 1, 2, 3]
    assert ngram_draft(toks, 3) == [9, 1, 2]
    assert ngram_draft([7], 2) == [7, 7], "no history: repeat-last fallback"
    assert ngram_draft(toks, 0) == []


def test_spec_decode_exact_vs_plain_greedy():
    """draft_k in {1..3} generates BITWISE the plain greedy sequence (the
    verify accepts only tokens the target model itself emits) — across
    mixed prompt lengths batched together."""
    cfg = decoder_tiny()
    prompts = _prompts(cfg, 7, (3, 9, 17))
    _, want = _generate(cfg, prompts, prefix_cache=False, draft_k=0)
    for k in (1, 3):
        eng, got = _generate(cfg, prompts, prefix_cache=True, draft_k=k)
        assert got == want, f"draft_k={k} diverged from plain greedy"
        assert eng.stats["spec_steps"] > 0
        assert eng.leaked_pages() == 0


def test_spec_decode_accepts_on_repetitive_sequences():
    """On a sequence that repeats (as real LLM decode settles into
    templated spans) the n-gram self-draft picks the repetition up, so
    accepted tokens > 0 and FEWER decode steps than tokens generated — the
    whole point of the draft-verify window — while the output stays
    bitwise the plain greedy sequence. The repetition is built, not hoped
    for: with the head's weight zeroed and its bias favouring one token,
    greedy decode emits that token whatever the installation's matmuls
    draw."""
    cfg = decoder_tiny()
    prompt = list(np.random.default_rng(3).integers(1, cfg.vocab_size, 5))
    bias = np.zeros(cfg.vocab_size, np.float32)
    bias[7] = 1.0
    out = {}
    for k in (0, 3):
        eng = ServingEngine(cfg, page_size=4, pool_pages=64, max_inflight=4,
                            prefix_cache=False, draft_k=k)
        w = eng._scope.find_var("dec.lm_head.w")
        eng._scope.set_var("dec.lm_head.w", jnp.zeros_like(w))
        eng._scope.set_var("dec.lm_head.b", jnp.asarray(bias))
        rid = eng.submit(prompt, max_new_tokens=16)
        eng.run_until_drained()
        out[k] = eng.result(rid)
    assert out[3] == out[0] == [7] * 16
    st = eng.stats
    assert st["spec_accepted"] > 0, "no draft ever accepted"
    assert st["decode_steps"] < 16, (
        f"{st['decode_steps']} steps for 16 tokens — speculation never "
        f"batched an acceptance")


# -- sampling suite -----------------------------------------------------------

def test_sampling_filters_reduce_to_greedy():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal(32).astype(np.float32)
    top = int(np.argmax(logits))
    assert sample_token(logits, SamplingParams(), rng) == top
    assert sample_token(logits, SamplingParams(temperature=0.7, top_k=1),
                        rng) == top
    assert sample_token(logits, SamplingParams(temperature=0.7,
                                               top_p=1e-6), rng) == top
    # top-k filter really restricts support
    p = SamplingParams(temperature=1.5, top_k=4)
    keep = set(np.argsort(-logits)[:4])
    draws = {sample_token(logits, p, np.random.default_rng(i))
             for i in range(64)}
    assert draws <= keep and len(draws) > 1
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)


def test_sampling_seeded_determinism_across_batch_buckets():
    """Same engine seed => same sampled tokens, run-to-run AND across
    engines whose max_inflight (hence batch-bucket packing + recompiles)
    differs; a different seed diverges."""
    cfg = decoder_tiny()
    prompts = _prompts(cfg, 21, (5, 9, 6, 12))
    samp = {"temperature": 0.9, "top_k": 8, "top_p": 0.9}

    def run(seed, inflight):
        eng = ServingEngine(cfg, page_size=4, pool_pages=64,
                            max_inflight=inflight, seed=seed)
        rids = [eng.submit(p, max_new_tokens=5, sampling=samp)
                for p in prompts]
        eng.run_until_drained()
        return [eng.result(r) for r in rids]

    a = run(0, 4)
    assert run(0, 4) == a, "same seed, same packing: must replay"
    assert run(0, 2) == a, (
        "determinism must not depend on batch-bucket packing")
    assert run(1, 4) != a, "different seed never diverged (rng unused?)"


def test_sampling_rows_mix_with_greedy_and_spec_rows():
    """A sampling request batched with greedy rows under speculative
    decoding: whatever the sampler draws can never leak into the greedy
    rows (row-independent compute), and the sampling row itself is
    deterministic per its seed stream."""
    cfg = decoder_tiny()
    prompts = _prompts(cfg, 31, (6, 10))

    def run(top_k):
        eng = ServingEngine(cfg, page_size=4, pool_pages=64, max_inflight=4,
                            draft_k=2, seed=3)
        g = [eng.submit(p, max_new_tokens=5) for p in prompts]
        s = eng.submit(prompts[0], max_new_tokens=5,
                       sampling=SamplingParams(temperature=1.1, top_k=top_k))
        eng.run_until_drained()
        return [eng.result(r) for r in g], eng.result(s)

    greedy1, sampled1 = run(top_k=6)
    greedy2, sampled2 = run(top_k=6)
    greedy3, sampled3 = run(top_k=48)
    assert greedy1 == greedy2 and sampled1 == sampled2, "replay broke"
    assert greedy3 == greedy1, (
        "the sampling row's draws leaked into greedy rows")
    assert sampled3 != sampled1, "top_k filter had no effect on support"


# -- tensor-parallel serving --------------------------------------------------

def test_tp_engine_matches_single_shard():
    """tp=2 over the host-device mesh: head-sharded prefill+decode emits
    exactly the tp=1 tokens (GSPMD correctness), with the KV pools
    annotated on their last dim [nh*dh], where heads are contiguous."""
    cfg = decoder_tiny()
    prompts = _prompts(cfg, 13, (5, 11))
    _, want = _generate(cfg, prompts, prefix_cache=False)
    eng, got = _generate(cfg, prompts, prefix_cache=False, tp=2)
    assert got == want
    pool_var = eng._decode_prog.global_block.var("kv_cache.k0")
    assert pool_var.sharding == (None, None, "tp")


def test_tp_decode_consults_per_shard_tuner_key(tmp_path):
    """The per-shard contract: under tp the decode-attention lever keys the
    DB on nh/tp — a swept entry for the SHARD shape drives (and hits) the
    dispatch, exactly what tools/tune.py's TP candidates upgrade into."""
    from paddle_tpu.ops import attention_ops as ao

    snap = pt.flags.all_flags()
    db_path = str(tmp_path / "db.json")
    try:
        pt.flags.set_flags({"tuning_mode": "consult", "tuning_db": db_path})
        tuning.invalidate_db_cache()
        db = tuning.TuningDB(db_path)
        key = tuning.canonical_key(
            "attention", tuning.attention_key(4, 6, 1, 256, 64, True),
            "float32", tuning.device_kind())
        db.put(key, {"backend": "xla"}, source="swept")
        db.save(db_path)
        tuning.invalidate_db_cache()
        backend, tier = ao.paged_attention_backend(
            4, 12, 256, 64, np.dtype("float32"), tp=2)
        assert (backend, tier) == ("xla", "db"), (
            "tp=2 dispatch must consult the nh/tp shard key")
        _, tier_full = ao.paged_attention_backend(
            4, 12, 256, 64, np.dtype("float32"), tp=1)
        assert tier_full != "db", "tp=1 must NOT hit the shard key"
    finally:
        pt.flags.set_flags(snap)
        tuning.invalidate_db_cache()


def test_tune_records_tp_decode_candidates(tmp_path):
    """tools/tune.py records the head-sharded decode shapes as candidate
    entries under their per-shard keys (and never clobbers a swept one)."""
    from tools import tune

    db = tuning.TuningDB(str(tmp_path / "db.json"))
    shapes = [("d", 8, 12, 512, 64)]
    swept_key = tuning.canonical_key(
        "attention", tuning.attention_key(8, 6, 1, 512, 64, True),
        "float32", tuning.device_kind())
    db.put(swept_key, {"backend": "pallas_paged"}, source="swept")
    added = tune.record_tp_decode_candidates(db, shapes, "float32",
                                             tp_degrees=(2, 4))
    assert added == 1, "tp=2 key is swept already; only tp=4 should land"
    cand_key = tuning.canonical_key(
        "attention", tuning.attention_key(8, 3, 1, 512, 64, True),
        "float32", tuning.device_kind())
    assert db.lookup(cand_key)["source"] == "candidate"
    assert db.lookup(swept_key)["source"] == "swept"


# -- chaos: abort + speculation + sharing ------------------------------------

@pytest.mark.chaos
def test_abort_under_speculation_keeps_refcounts_balanced():
    """Aborts injected while speculative windows are in flight over shared
    prefixes: lookahead pages, COW copies and shared mappings all release
    exactly once — the accounting balances every cycle."""
    from paddle_tpu.resilience.faults import fault_scope

    cfg = decoder_tiny()
    eng = ServingEngine(cfg, page_size=4, pool_pages=32, max_inflight=4,
                        prefix_cache=True, draft_k=3)
    rng = np.random.default_rng(17)
    sysp = list(rng.integers(1, 97, 8))
    for cycle in range(3):
        with fault_scope("serving_abort:1,3") as plan:
            rids = [eng.submit(sysp + list(rng.integers(1, 97, n)),
                               max_new_tokens=6) for n in (0, 4, 9)]
            eng.run_until_drained()
            assert plan.stats()["fired"]
        assert {eng.requests[r].state for r in rids} <= {"finished",
                                                         "aborted"}
        assert eng.leaked_pages() == 0, f"cycle {cycle} orphaned pages"
    eng.flush_prefix_cache()
    assert eng.pool.free_count == eng.pool.num_pages
