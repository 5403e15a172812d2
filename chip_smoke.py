"""Chip smoke: the quickest proof that the system still starts on the TPU.

One process, the two main paths, through the entry points a user calls, at
the full width of BERT-base (depth kept too — it fits):

  * trainer — `transformer.bert_pretrain` + bf16 AMP Adam `minimize`, shaped
    exactly as the cell `bert_base.s128` (TRAINER_CFG, batch 128, seq 128),
    `Executor.run(startup)` then a handful of `Executor.run(main)` steps on
    one repeated seeded batch: every loss finite, the last below the first,
    a trained parameter resident on the chip;
  * server — `ServingEngine(DecoderConfig())` (the 12 x 768 decoder) over a
    2048 x 16 page pool: a few `submit()`s whose prompts fall in different
    prefill buckets, `run_until_drained()`, `pop_result()`: every request
    returns its `max_new_tokens`, no page leaks, a clean pool audit, and
    every generated token is, by the dense oracle's own logits
    (`build_full_forward_program`), within ORACLE_LOGIT_TOL of the oracle's
    best token;
  * pool layout — the decode, prefill, window and copy-on-write programs
    compiled at the serving cells' geometry (3072 x 16 pool, 64 rows): none
    holds an instruction that copies a whole KV pool (`tools/pool_hlo.py`);
  * with four or more chips, the trainer again as a dp x 4 GSPMD program at
    the same widths: feed and parameters on four distinct devices, device
    memory of the same order on all four.

Each phase also checks that the attention backend that ran is the one the
dispatch chose. The script exits non-zero and prints no result when jax
finds no TPU, and when any check fails (a failed check raises; nothing is
caught). Each phase prints its own line (backend that ran, compile vs run
seconds); the last stdout line is the result alone:
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.

    python3 chip_smoke.py
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time

import jax
import numpy as np

import paddle_tpu as pt
from paddle_tpu import compile_cache
from paddle_tpu import observability as obs
from paddle_tpu.models import transformer
from paddle_tpu.ops import attention_ops
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.serving import DecoderConfig, ServingEngine
from paddle_tpu.serving import model as sv_model
from paddle_tpu.serving.kv_cache import (INDEX_POOL, JOINED_POOL,
                                         STACKED_POOLS, WINDOW_POOLS,
                                         pool_shape)
from tools.pool_hlo import (kernel_calls, pool_sized_copies,
                            serving_program_hlos, sorts_over,
                            token_row_gathers)

# How far below the dense oracle's best logit the logit of a token the
# engine generated may sit. The engine and the oracle run different
# programs (paged fp32 VPU attention vs dense MXU attention) at the TPU's
# default fp32 matmul precision, which rounds operands to bf16: their
# logits differ by ~1e-2, so a near-tie may resolve differently — but never
# by more than that noise. A wrong page, mask or position moves the gap to
# the scale of the logit spread (order 1).
ORACLE_LOGIT_TOL = 0.05

# BERT-base at its published widths, as benchmark/configs/bert_base.json
# trains it: no dropout (a repeatable loss), no tp annotations
TRAINER_CFG = dataclasses.replace(transformer.bert_base(), dropout=0.0,
                                  use_tp=False)


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def _require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _check_dispatches(kind: str, expected: str) -> dict:
    """Every attention dispatch of `kind` traced since the phase began ran
    the backend the decision chose, and that backend is `expected`."""
    counts = {k: n for k, n in attention_ops.dispatch_counts().items()
              if k[0] == kind}
    _require(counts, f"no {kind} attention dispatch was traced")
    for (_, chosen, ran), n in counts.items():
        _require(chosen == ran,
                 f"{kind} attention: dispatch chose {chosen!r} but {ran!r} "
                 f"ran ({n} traces)")
        _require(chosen == expected,
                 f"{kind} attention: {chosen!r} ran, the dispatch rule "
                 f"says {expected!r}")
    return {"backend": expected, "recheck_held": True,
            "traces": sum(counts.values())}


def _bytes_in_use(devices):
    """Per-device bytes in use, or None where the backend keeps no such
    count (the CPU one does not; main() insists on it for the chip)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return [s["bytes_in_use"] for s in stats]


def trainer_phase(cfg, batch: int, seq_len: int, steps: int,
                  dp: int = 1) -> dict:
    """BERT pretraining, bf16 AMP Adam, `steps` steps on one repeated
    seeded batch of `dp * batch` rows; with dp > 1 as a GSPMD data-parallel
    CompiledProgram over the first dp devices."""
    from __graft_entry__ import _example_feed

    obs.reset("attention.")
    t0 = time.perf_counter()
    main_p, startup = pt.Program(), pt.Program()
    main_p.random_seed = startup.random_seed = 21
    with pt.program_guard(main_p, startup), pt.unique_name.guard():
        avg_loss, _ = transformer.bert_pretrain(cfg, seq_len=seq_len)
        pt.contrib.mixed_precision.decorate(
            pt.optimizer.Adam(learning_rate=1e-4)).minimize(avg_loss)
    feed = _example_feed(cfg, dp * batch, seq_len, seed=21)
    target = main_p
    if dp > 1:
        target = pt.CompiledProgram(main_p).with_data_parallel(
            loss_name=avg_loss.name, mesh=make_mesh({"dp": dp}))
    devices = jax.devices()[:dp]
    before = _bytes_in_use(devices)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        (first,) = exe.run(target, feed=feed, fetch_list=[avg_loss])
        losses = [float(np.asarray(first))]
        t_first = time.perf_counter()
        out = {}
        if dp > 1:
            # the compiled entry exists now: staged feeds carry its shardings
            feed = exe.feed_placer(target)(feed)
            for name, arr in feed.items():
                shards = arr.addressable_shards
                _require(
                    {s.device for s in shards} == set(devices)
                    and all(s.data.shape[0] == batch for s in shards),
                    f"feed {name!r} is not split {batch} rows to each of "
                    f"{devices}: {[(s.device, s.data.shape) for s in shards]}")
        for _ in range(steps - 1):
            (lv,) = exe.run(target, feed=feed, fetch_list=[avg_loss])
            losses.append(float(np.asarray(lv)))
        t_end = time.perf_counter()
        params = [p.name for p in main_p.all_parameters()]
        for name in params:
            held = scope.find_var(name).devices()
            _require(held == set(devices),
                     f"parameter {name!r} lives on {held}, not on {devices}")
        if dp > 1 and before is not None:
            grown = [a - b for a, b in zip(_bytes_in_use(devices), before)]
            _require(min(grown) > 0 and max(grown) <= 2 * min(grown),
                     f"this phase's device memory is not of one order "
                     f"across {devices}: {grown}")
            out["bytes_in_use_grown"] = grown
    _require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    shape = (batch, cfg.num_heads, seq_len, cfg.hidden_size // cfg.num_heads)
    expected, _tier = attention_ops.attention_backend(
        shape, shape, "bfloat16", causal=cfg.causal,
        use_pallas=cfg.use_flash_attention)
    out.update({
        "config": f"L{cfg.num_layers} h{cfg.hidden_size} nh{cfg.num_heads} "
                  f"ffn{cfg.ffn_size} v{cfg.vocab_size} b{dp}x{batch} "
                  f"s{seq_len} bf16-AMP Adam",
        "losses": [round(v, 4) for v in losses],
        "param_platform": devices[0].platform,
        "params_checked": len(params),
        "attention": _check_dispatches("dense", expected),
        "first_step_s": round(t_first - t0, 2),
        "later_steps_s": round(t_end - t_first, 2),
    })
    return out


def server_phase(cfg: DecoderConfig, page_size: int, pool_pages: int,
                 prompt_lens, max_new: int) -> dict:
    """Greedy-serve one request per entry of `prompt_lens`, each generating
    `max_new` tokens, and grade every generated token against the dense
    oracle's logits."""
    obs.reset("attention.")
    t0 = time.perf_counter()
    eng = ServingEngine(cfg, page_size=page_size, pool_pages=pool_pages,
                        max_inflight=len(prompt_lens), seed=21)
    rng = np.random.default_rng(21)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in prompt_lens]
    rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    # the first scheduler step admits and prefills every request and takes
    # one decode step: it holds every compile of the run
    eng.step()
    t_first = time.perf_counter()
    eng.run_until_drained()
    t_end = time.perf_counter()
    problems, poisoned = eng.audit_pool()
    _require(not problems and not poisoned,
             f"pool audit: {problems} poisoned={poisoned}")
    stats = eng.stats_snapshot()
    results = [eng.pop_result(rid) for rid in rids]
    for n, toks in zip(prompt_lens, results):
        _require(len(toks) == max_new,
                 f"prompt of {n} returned {len(toks)} tokens, not {max_new}")
    _require(eng.leaked_pages() == 0, f"{eng.leaked_pages()} pages leaked")
    _require(stats["prefill_signatures"] >= 2,
             "prompts did not fall in different prefill buckets")

    # dense oracle: one teacher-forced forward over prompt + generated
    # tokens (right padding cannot reach a causal position before it)
    seqs = [p + r for p, r in zip(prompts, results)]
    width = max(len(s) for s in seqs)
    tok = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        tok[i, :len(s)] = s
    pos = np.tile(np.arange(width, dtype=np.int32), (len(seqs), 1))
    full = pt.Program()
    with pt.program_guard(full, pt.Program()), pt.unique_name.guard():
        io = sv_model.build_full_forward_program(cfg)
    (logits,) = eng._exe.run(
        full, feed={sv_model.TOK_FEED: tok, sv_model.POS_FEED: pos},
        fetch_list=[io["logits"]], scope=eng._scope)
    logits = np.asarray(logits, np.float32)
    _require(np.all(np.isfinite(logits)), "oracle logits are not finite")
    worst, agree, total = 0.0, 0, 0
    for i, (p, r) in enumerate(zip(prompts, results)):
        for j, t in enumerate(r):
            row = logits[i, len(p) + j - 1]
            gap = float(row.max() - row[t])
            worst = max(worst, gap)
            agree += int(gap == 0.0)
            total += 1
    _require(worst <= ORACLE_LOGIT_TOL,
             f"a served token sits {worst:.4f} below the oracle's best "
             f"logit (tolerance {ORACLE_LOGIT_TOL})")

    bb = 1 << (len(prompt_lens) - 1).bit_length()
    pages = -(-(max(prompt_lens) + max_new) // page_size)
    pb = 1 << (pages - 1).bit_length()
    expected, _tier = attention_ops.paged_attention_backend(
        bb, cfg.num_heads, pb * page_size, cfg.head_dim, cfg.dtype,
        pool_shape=pool_shape(pool_pages, page_size, cfg.num_heads,
                              cfg.head_dim))
    return {
        "config": f"L{cfg.num_layers} h{cfg.hidden_size} nh{cfg.num_heads} "
                  f"v{cfg.vocab_size} {cfg.dtype} pool{pool_pages}x"
                  f"{page_size} prompts{list(prompt_lens)} new{max_new}",
        "tokens": total,
        "oracle_argmax_agree": agree,
        "oracle_worst_logit_gap": round(worst, 5),
        "oracle_logit_tol": ORACLE_LOGIT_TOL,
        "prefill_buckets": stats["prefill_signatures"],
        "decode_buckets": stats["decode_signatures"],
        "decode_steps": stats["decode_steps"],
        "leaked_pages": 0,
        "paged_attention": _check_dispatches("paged", expected),
        "first_step_s": round(t_first - t0, 2),
        "later_steps_s": round(t_end - t_first, 2),
    }


def pool_layout_phase(cfg: DecoderConfig, page_size: int, pool_pages: int,
                      rows: int, device=None) -> dict:
    """The KV pool has one device layout: compile the decode (`rows` rows of
    a full context), prefill, window and copy-on-write programs, for this
    chip or for `device` (a described one), and require that none writes a
    fresh pool-sized array — a relayout copy of a whole pool (K or V, or the
    joined rows and the per-token indexer-key pool of a block that has
    them) — and that a block that selects gathers a token once."""
    eng = ServingEngine(cfg, page_size=page_size, pool_pages=pool_pages,
                        max_inflight=rows, seed=21)
    # a scanned block keeps every layer's pages in one buffer
    layers = cfg.num_layers if cfg.scanned else 1
    sizes = {layers * int(np.prod(pool_shape(pool_pages, page_size,
                                             cfg.kv_heads, cfg.head_dim)))}
    if cfg.selects:
        # K and V joined in one pool of 32-bit words, and the indexer keys
        sizes = {int(np.prod(eng._scope.find_var(name).shape))
                 for name in (JOINED_POOL, INDEX_POOL)}
    if cfg.windowed:
        # the full layers' pools and the sliding layers' second pair
        sizes = {int(np.prod(eng._scope.find_var(name).shape))
                 for name in STACKED_POOLS[:2] + WINDOW_POOLS}
    pages = eng._page_bucket(eng.pool.pages_for(cfg.max_position))
    texts = serving_program_hlos(eng, rows=rows, pages=pages, prompt=128,
                                 device=device)
    copies = {}
    for name, text in texts.items():
        found = [c for n in sorted(sizes) for c in pool_sized_copies(text, n)]
        _require(not found,
                 f"the compiled {name} program moves a whole KV pool "
                 f"{len(found)} times, first: {found[0] if found else None}")
        copies[name] = len(found)
    out = {"config": f"L{cfg.num_layers} nh{cfg.num_heads} "
                     f"dh{cfg.head_dim} pool{pool_pages}x{page_size} "
                     f"rows{rows}",
           "pool_sized_copies": copies}
    if cfg.selects:
        # a decode row fetches a selected token's K and V as one row
        words = eng._scope.find_var(JOINED_POOL).shape[-1]
        out["token_row_gathers"] = {
            name: len(token_row_gathers(text, words))
            for name, text in texts.items()}
        _require(out["token_row_gathers"]["decode"] == 1,
                 "the compiled decode program gathers a selected token "
                 f"{out['token_row_gathers']['decode']} times a layer")
        # and names its selection without sorting a row's context
        out["context_sorts"] = {
            name: len(sorts_over(text, pages * page_size))
            for name, text in texts.items()}
        _require(out["context_sorts"]["decode"] == 0,
                 "the compiled decode program sorts a row's whole context")
        # and a decode row's indexer scores come from the paged kernel
        # where its gate takes the geometry (PR 40)
        out["paged_indexer_calls"] = {
            name: kernel_calls(text, "paged_indexer_scores")
            for name, text in texts.items()}
    return out


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU, jax found {dev.platform!r}",
              file=sys.stderr)
        return 2
    cache_dir = compile_cache.configure()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__} on {device}; compile cache {cache_dir}",
          flush=True)

    phases = {}
    phases["trainer"] = trainer_phase(TRAINER_CFG, batch=128, seq_len=128,
                                      steps=6)
    _require(phases["trainer"]["param_platform"] == "tpu",
             "trained parameters are not on the TPU")
    print("trainer", json.dumps(phases["trainer"]), flush=True)
    gc.collect()

    phases["server"] = server_phase(
        DecoderConfig(), page_size=16, pool_pages=2048,
        prompt_lens=(40, 5, 100, 200), max_new=8)
    print("server", json.dumps(phases["server"]), flush=True)
    gc.collect()

    # the serving cells' geometry (benchmark/configs/bert_base_decoder.json)
    phases["pool_layout"] = pool_layout_phase(
        DecoderConfig(), page_size=16, pool_pages=3072, rows=64)
    print("pool_layout", json.dumps(phases["pool_layout"]), flush=True)
    gc.collect()

    if len(jax.devices()) >= 4:
        phases["trainer_dp4"] = trainer_phase(
            TRAINER_CFG, batch=128, seq_len=128, steps=4, dp=4)
        _require("bytes_in_use_grown" in phases["trainer_dp4"],
                 "the chips report no memory statistics")
        print("trainer_dp4", json.dumps(phases["trainer_dp4"]), flush=True)

    print("summary", json.dumps({"jax": jax.__version__, "phases": phases}))
    # the result line: exactly these keys, the device as jax reports it
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
