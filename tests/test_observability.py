"""Unified telemetry layer tests (ISSUE 13): registry semantics under
concurrency, the legacy-shim contracts (profiler stage counters, serving
stats), exporter round-trips (JSONL bytes, Prometheus text), SLO
escalation, and the gate/CLI tooling on top."""
import json
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.observability import (JsonlWriter, MetricsRegistry,
                                      SloMonitor, jsonl_line,
                                      parse_prometheus, prometheus_text,
                                      schema, write_prometheus)
from paddle_tpu.observability.slo import gauge_above

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- registry core ------------------------------------------------------------

def test_counters_gauges_and_labeled_series():
    reg = MetricsRegistry(schema.DECLARED)
    reg.counter_inc("serving.prefills")
    reg.counter_inc("serving.prefills", 4)
    reg.gauge_set("serving.pool_occupancy", 0.25)
    reg.counter_inc("emb.hit_ids", 7, labels={"table": "emb_a"})
    reg.counter_inc("emb.hit_ids", 1, labels={"table": "emb_b"})
    snap = reg.snapshot()
    assert snap["counters"]["serving.prefills"] == 5
    assert snap["gauges"]["serving.pool_occupancy"] == 0.25
    # a (name, labels) pair is one series, rendered Prometheus-style
    assert snap["counters"]['emb.hit_ids{table="emb_a"}'] == 7
    assert snap["counters"]['emb.hit_ids{table="emb_b"}'] == 1
    assert obs.base_name('emb.hit_ids{table="emb_a"}') == "emb.hit_ids"


def test_histogram_percentiles_within_bucket_tolerance():
    reg = MetricsRegistry(schema.DECLARED)
    vals = [0.001 * (i + 1) for i in range(100)]  # 1ms .. 100ms uniform
    for v in vals:
        reg.histogram_observe("serving.ttft_s", v)
    h = reg.snapshot()["histograms"]["serving.ttft_s"]
    assert h["count"] == 100
    assert h["min"] == pytest.approx(0.001)
    assert h["max"] == pytest.approx(0.100)
    assert h["sum"] == pytest.approx(sum(vals))
    # log buckets are 10^(1/8) wide, so a quantile is within ~15% true
    assert h["p50"] == pytest.approx(0.050, rel=0.20)
    assert h["p99"] == pytest.approx(0.099, rel=0.20)
    # quantiles never escape the observed range
    assert h["min"] <= h["p50"] <= h["p99"] <= h["max"]


def test_undeclared_names_record_but_are_flagged():
    reg = MetricsRegistry(schema.DECLARED)
    reg.counter_inc("serving.prefills")      # declared: clean
    reg.counter_inc("rogue.metric")          # undeclared: lands AND flags
    snap = reg.snapshot()
    assert snap["counters"]["rogue.metric"] == 1
    assert snap["undeclared"] == ["rogue.metric"]
    reg.declare("rogue.metric", schema.COUNTER, "now blessed")
    assert reg.snapshot()["undeclared"] == []


def test_snapshot_reset_is_atomic_under_8_threads():
    """8 writers hammer one counter + one histogram while a reader does
    snapshot(reset=True) concurrently; nothing is lost or double-counted
    across the reset boundaries."""
    reg = MetricsRegistry(schema.DECLARED)
    N, THREADS = 500, 8
    stop = threading.Event()
    seen = {"count": 0.0, "hist": 0}

    def writer():
        for _ in range(N):
            reg.counter_inc("train.steps")
            reg.histogram_observe("train.step_latency_s", 0.01)

    def reader():
        while not stop.is_set():
            snap = reg.snapshot(reset=True)
            seen["count"] += snap["counters"].get("train.steps", 0)
            seen["hist"] += snap["histograms"].get(
                "train.step_latency_s", {}).get("count", 0)

    ws = [threading.Thread(target=writer) for _ in range(THREADS)]
    r = threading.Thread(target=reader)
    r.start()
    for w in ws:
        w.start()
    for w in ws:
        w.join()
    stop.set()
    r.join()
    final = reg.snapshot()
    seen["count"] += final["counters"].get("train.steps", 0)
    seen["hist"] += final["histograms"].get(
        "train.step_latency_s", {}).get("count", 0)
    assert seen["count"] == N * THREADS
    assert seen["hist"] == N * THREADS


def test_reset_prefix_scopes_the_clear():
    reg = MetricsRegistry(schema.DECLARED)
    reg.counter_inc("serving.prefills")
    reg.counter_inc("train.steps")
    reg.stage_record("pipeline.dispatch", 0.1)
    reg.reset("serving.")
    snap = reg.snapshot()
    assert "serving.prefills" not in snap["counters"]
    assert snap["counters"]["train.steps"] == 1
    assert snap["stages"]["pipeline.dispatch"]["events"] == 1


# -- legacy shim contracts ----------------------------------------------------

def test_profiler_stage_shims_keep_pr2_semantics():
    profiler.stage_counters(reset=True)  # scope: drop whatever ran before
    profiler.record_stage("pipeline.dispatch", 0.25, events=2)
    profiler.bump("feed.skip_corrupt", 3)
    c = profiler.stage_counters()
    assert c["pipeline.dispatch"] == {"events": 2, "seconds": 0.25}
    assert c["feed.skip_corrupt"] == {"events": 3, "seconds": 0.0}
    # the same accumulators are visible through the unified snapshot
    snap = obs.snapshot()
    assert snap["stages"]["pipeline.dispatch"]["seconds"] == 0.25
    # reset=True zeroes (epoch-scoped reads), as PR 2 call sites expect
    assert profiler.stage_counters(reset=True)["pipeline.dispatch"][
        "events"] == 2
    assert profiler.stage_counters() == {}


def _tree_literals(pattern):
    """Every match of `pattern` in paddle_tpu/*.py, outside observability/
    (the layer's own docs show `bump("...")` examples)."""
    pat, used = re.compile(pattern), set()
    for dirpath, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        if os.path.basename(dirpath) == "observability":
            continue
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn)) as f:
                    used |= set(pat.findall(f.read()))
    return used


def test_every_legacy_stage_literal_is_declared():
    """Source-scan regression: every bump("x")/record_stage("x") literal in
    the tree must name a declared stage — adding a stage is a schema act."""
    used = _tree_literals(
        r'(?:\bbump|\brecord_stage|\bstage_timer)\(\s*"([^"]+)"')
    assert used, "source scan found no stage call sites"
    undeclared = sorted(used - schema.STAGE_NAMES)
    assert not undeclared, (
        f"stage literals not declared in observability/schema.py: "
        f"{undeclared}")


def test_stats_snapshot_spec_rate_guard():
    """Speculation configured but no spec step run yet: the derived rates
    must read 0.0 — never ZeroDivisionError, never NaN."""
    from paddle_tpu.serving import ServingEngine, decoder_tiny

    eng = ServingEngine(decoder_tiny(), page_size=4, pool_pages=16,
                        max_inflight=2, draft_k=2)
    ss = eng.stats_snapshot()
    assert ss["spec_accept_rate"] == 0.0
    assert ss["tokens_per_decode_step"] == 0.0
    assert ss["prefix_cache_hit_rate"] == 0.0
    assert ss["occupancy_mean"] == 0.0
    assert all(np.isfinite(v) for v in ss.values()
               if isinstance(v, (int, float)))


def test_serving_engine_mirrors_stats_into_registry():
    """A live run: every registry serving.* counter equals the engine's
    stats dict entry, and the occupancy gauges match the pool."""
    from paddle_tpu.serving import ServingEngine, decoder_tiny

    obs.reset("serving.")  # scope: earlier tests share the process registry
    cfg = decoder_tiny()
    eng = ServingEngine(cfg, page_size=4, pool_pages=32, max_inflight=4)
    rng = np.random.default_rng(3)
    for n in (3, 9):
        eng.submit(list(rng.integers(1, cfg.vocab_size, n)),
                   max_new_tokens=4)
    eng.run_until_drained()
    snap = obs.snapshot()
    for key in ("prefills", "decode_steps", "decode_tokens",
                "prefill_tokens_computed", "prefix_lookups"):
        assert snap["counters"].get("serving." + key, 0) == eng.stats[key], key
    assert snap["gauges"]["serving.pages_in_use"] == (
        eng.pool.num_pages - eng.pool.free_count)
    # histograms + request events rode along (flag default: enabled)
    assert snap["histograms"]["serving.ttft_s"]["count"] == 2
    assert snap["histograms"]["serving.request_s"]["count"] == 2
    phases = [e["payload"]["phase"] for e in snap["events"]
              if e["name"] == "serving.request"]
    for ph in ("queued", "admitted", "first_token", "finished"):
        assert ph in phases, f"missing lifecycle phase {ph}"

    # Prometheus round-trip on the live snapshot: render -> strict-parse
    text = prometheus_text(snap)
    parsed = parse_prometheus(text)
    assert parsed["serving_prefills"] == eng.stats["prefills"]
    assert parsed['serving_ttft_s_count'] == 2


def test_every_piece_literal_is_declared_and_every_piece_is_used():
    """The names a device trace books time to (ISSUE 35) drift like metric
    names do: a `piece("x")` in the tree must be in schema.PIECES, and a
    declared piece some stack must open (no second list in ops/)."""
    used = _tree_literals(r'\bpiece\(\s*"([^"]+)"')
    assert used == schema.PIECES, (
        f"undeclared: {sorted(used - schema.PIECES)}; "
        f"declared and unused: {sorted(schema.PIECES - used)}")
    # a stack's mode reaches `piece` as a variable, through `under_mode`
    assert _tree_literals(r'\n@(under_mode)\n') == {"under_mode"}
    assert not schema.PIECES & schema.STACK_MODES


def test_every_program_name_literal_is_declared():
    used = _tree_literals(r'\.name = "(\w+)"')
    assert used == schema.PROGRAM_NAMES, (
        f"undeclared: {sorted(used - schema.PROGRAM_NAMES)}; declared and "
        f"given to no Program: {sorted(schema.PROGRAM_NAMES - used)}")


# -- profiler trace-lifecycle guards ------------------------------------------

def test_stop_profiler_without_start_names_the_fix():
    with pytest.raises(RuntimeError, match="start_profiler"):
        pt.profiler.stop_profiler()


def test_failed_trace_start_leaves_no_half_open_state(tmp_path, monkeypatch):
    def boom(path, exist_ok=False):
        raise OSError("read-only filesystem")

    monkeypatch.setattr(profiler.os, "makedirs", boom)
    with pytest.raises(OSError, match="read-only"):
        with profiler.profiler(profile_path=str(tmp_path / "trace")):
            pass  # pragma: no cover — begin fails before the body
    monkeypatch.undo()
    # nothing half-open: the lifecycle flag is clean and stop still gives
    # the instructive error, not a raw jax one
    assert profiler._trace_active is False
    with pytest.raises(RuntimeError, match="start_profiler"):
        profiler.stop_profiler()


# -- exporters ----------------------------------------------------------------

def test_jsonl_writer_rotation_and_byte_roundtrip(tmp_path):
    path = str(tmp_path / "obs.jsonl")
    w = JsonlWriter(path, rotate_bytes=4096)
    for i in range(120):
        w.write({"ts": float(i), "type": "event", "name": "serving.request",
                 "level": "info", "payload": {"rid": i, "pad": "x" * 40}})
    w.close()
    assert os.path.exists(path + ".1"), "size rotation never triggered"
    rids = []
    for p in (path + ".1", path):
        with open(p, "rb") as f:
            for line in f:
                rec = json.loads(line)
                assert jsonl_line(rec) == line  # byte-for-byte contract
                rids.append(rec["payload"]["rid"])
    # the two retained files hold a contiguous, complete tail of the
    # stream ending at the newest record (older generations were rotated
    # away, never torn mid-line)
    assert rids == list(range(rids[0], 120))


def test_prometheus_file_roundtrip_and_strict_parse(tmp_path):
    reg = MetricsRegistry(schema.DECLARED)
    reg.counter_inc("train.steps", 17)
    reg.gauge_set("serving.pool_occupancy", 0.5)
    reg.counter_inc("tuning.decisions", labels={"op": "fc", "tier": "db"})
    reg.stage_record("pipeline.dispatch", 1.5, events=3)
    reg.histogram_observe("serving.ttft_s", 0.02)
    path = str(tmp_path / "metrics.prom")
    text = write_prometheus(path, reg.snapshot())
    with open(path) as f:
        assert f.read() == text  # temp+rename wrote exactly the render
    parsed = parse_prometheus(text)
    assert parsed["train_steps"] == 17
    assert parsed['tuning_decisions{op="fc",tier="db"}'] == 1
    assert parsed["pipeline_dispatch_events"] == 3
    assert parsed["pipeline_dispatch_seconds_total"] == 1.5
    assert parsed["serving_ttft_s_count"] == 1
    with pytest.raises(ValueError, match="unparseable"):
        parse_prometheus("this is not exposition format\n")


def test_http_exporter_serves_live_snapshot():
    import urllib.request

    reg = MetricsRegistry(schema.DECLARED)
    reg.counter_inc("train.steps", 5)
    try:
        server = obs.start_http_exporter(reg, port=0)
    except OSError as e:  # sandboxed runner without loopback bind
        pytest.skip(f"cannot bind loopback: {e}")
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert parse_prometheus(body)["train_steps"] == 5
    finally:
        server.shutdown()


# -- SLO monitor --------------------------------------------------------------

def test_slo_monitor_escalates_warn_to_alert():
    reg = MetricsRegistry(schema.DECLARED)
    hits = []
    mon = SloMonitor(registry=reg, window_s=60.0, alert_after=2,
                     on_warn=lambda b: hits.append(("warn", b)),
                     on_alert=lambda b: hits.append(("alert", b)))
    mon.add_rule("leak", gauge_above("serving.leaked_pages", 0.0), 0)
    reg.gauge_set("serving.leaked_pages", 0.0)
    assert mon.observe(now=0.0) == []          # healthy: no breach
    reg.gauge_set("serving.leaked_pages", 3.0)
    mon.observe(now=1.0)
    mon.observe(now=2.0)
    assert [s for s, _ in hits] == ["warn", "alert"]
    assert hits[1][1]["value"] == 3.0
    snap = reg.snapshot()
    assert snap["counters"]['slo.breaches{rule="leak",severity="warn"}'] == 1
    assert snap["counters"]['slo.breaches{rule="leak",severity="alert"}'] == 1
    levels = [e["level"] for e in snap["events"] if e["name"] == "slo.breach"]
    assert levels == ["warning", "error"]


def test_slo_breaches_age_out_of_the_window():
    reg = MetricsRegistry(schema.DECLARED)
    sev = []
    mon = SloMonitor(registry=reg, window_s=10.0, alert_after=2,
                     on_warn=lambda b: sev.append("warn"),
                     on_alert=lambda b: sev.append("alert"))
    mon.add_rule("leak", gauge_above("serving.leaked_pages", 0.0), 0)
    reg.gauge_set("serving.leaked_pages", 1.0)
    mon.observe(now=0.0)
    mon.observe(now=20.0)  # first breach aged out: still a warn
    assert sev == ["warn", "warn"]


# -- CLI tooling --------------------------------------------------------------

def test_obs_cli_tail_summarize_diff_prom(tmp_path):
    stream = tmp_path / "obs.jsonl"
    with open(stream, "wb") as f:
        for i in range(5):
            f.write(jsonl_line({"ts": float(i), "type": "event",
                                "name": "serving.request", "level": "info",
                                "payload": {"rid": i, "phase": "queued"}}))
        for d in (0.01, 0.02, 0.03):
            f.write(jsonl_line({"ts": 9.0, "type": "span",
                                "name": "serving.decode", "dur_s": d}))

    def run(*args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "obs.py"), *args],
            capture_output=True, text=True, timeout=60)

    r = run("tail", str(stream), "-n", "2")
    assert r.returncode == 0, r.stderr
    assert len(r.stdout.strip().splitlines()) == 2

    r = run("summarize", str(stream))
    assert r.returncode == 0, r.stderr
    assert "serving.request" in r.stdout and "serving.decode" in r.stdout
    assert "8 records" in r.stdout

    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps({"counters": {"train.steps": 5},
                               "gauges": {}, "histograms": {}}))
    new.write_text(json.dumps({"counters": {"train.steps": 9},
                               "gauges": {"serving.pool_occupancy": 0.5},
                               "histograms": {}}))
    r = run("diff", str(old), str(new))
    assert r.returncode == 0, r.stderr
    assert "+4" in r.stdout and "serving.pool_occupancy" in r.stdout

    prom = tmp_path / "m.prom"
    reg = MetricsRegistry(schema.DECLARED)
    reg.counter_inc("train.steps", 2)
    write_prometheus(str(prom), reg.snapshot())
    r = run("prom", str(prom))
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout)["train_steps"] == 2
    prom.write_text("garbage line here\n")
    assert run("prom", str(prom)).returncode == 1
    assert run("nosuchcmd").returncode == 2


# -- the compiler's own events (ISSUE 49) --------------------------------------

def _entries(recs, **where):
    """The payloads of the `compile.entry` events that match `where`."""
    return [r["payload"] for r in recs if r["name"] == "compile.entry"
            and all(r["payload"][k] == v for k, v in where.items())]


@pytest.mark.parametrize("phase", ["trace", "lower", "backend"])
def test_a_named_program_books_each_compile_phase_once(phase):
    """A Program run twice: one `compile.entry` under its name, with one
    sample of the phase in the histogram, and none on the second run; an
    eager jit's entry says `other`; the `fn`s are the lowered blocks' names
    and `other`, nothing else."""
    import jax

    from paddle_tpu import executor

    name = f"probe_{phase}_step"
    main, startup = pt.Program(), pt.Program()
    main.name = name
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[5], dtype="float32")
        out = pt.layers.fc(x, size=3)
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    series = f"compile.{phase}.seconds"

    def count():
        return obs.snapshot()["histograms"].get(series, {"count": 0})["count"]

    recs = []
    obs.attach_sink(recs.append)
    try:
        feed = {"x": np.ones((2, 5), np.float32)}
        before = count()
        exe.run(main, feed=feed, fetch_list=[out], scope=scope)
        (entry,) = _entries(recs, fn=name)
        assert entry[phase + "_s"] > 0
        assert count() == before + 1
        exe.run(main, feed=feed, fetch_list=[out], scope=scope)
        assert len(_entries(recs, fn=name)) == 1 and count() == before + 1
        jax.jit(lambda v: v * 3 + len(name))(np.ones(3, np.float32))
        assert len(_entries(recs, fn="other")) == 1
        assert count() == before + 2
    finally:
        obs.detach_sink(recs.append)
    assert name in executor.LOWERED_FN_NAMES
    assert {e["fn"] for e in _entries(recs)} == {name, "other"}


def test_a_program_named_other_keeps_its_own_fn():
    """`other` is what is NOT a lowered block's: a Program of that name is
    compiled under a name of its own, and `jit_compile_counter` counts it."""
    from paddle_tpu import executor
    from paddle_tpu.pipeline import jit_compile_counter

    main, startup = pt.Program(), pt.Program()
    main.name = "other"
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[6], dtype="float32")
        out = pt.layers.fc(x, size=2)
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    recs = []
    obs.attach_sink(recs.append)
    try:
        with jit_compile_counter() as compiles:
            exe.run(main, feed={"x": np.ones((2, 6), np.float32)},
                    fetch_list=[out], scope=scope)
    finally:
        obs.detach_sink(recs.append)
    assert compiles.count == 1
    assert "other" not in executor.LOWERED_FN_NAMES
    assert len(_entries(recs, fn="other_")) == 1
    assert not _entries(recs, fn="other")


def test_a_compile_entry_event_carries_its_phases_cache_and_parent():
    main, startup = pt.Program(), pt.Program()
    main.name = "probe_entry_step"
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[7], dtype="float32")
        out = pt.layers.fc(x, size=2)
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    recs = []
    obs.attach_sink(recs.append)
    try:
        exe.run(main, feed={"x": np.ones((3, 7), np.float32)},
                fetch_list=[out], scope=scope)
    finally:
        obs.detach_sink(recs.append)
    (entry,) = [r for r in recs if r["name"] == "compile.entry"
                and r["payload"]["fn"] == main.name]
    p = entry["payload"]
    assert entry["parent"] == "executor.first_dispatch"
    assert p["cache"] in ("hit", "miss", "off")
    assert min(p["trace_s"], p["lower_s"], p["backend_s"]) > 0
    assert p["end"] - p["start"] >= \
        p["trace_s"] + p["lower_s"] + p["backend_s"] - 1e-3
    assert set(p["op_s"]) == {"mul", "elementwise_add"}
    (first,) = [r for r in recs if r["name"] == "executor.first_dispatch"]
    assert first["parent"] == "pipeline.dispatch"
    assert first["attrs"] == {"program": main.name, "entry": 0}
    # the ordinal counts the entries ever made of the Program: it does not
    # repeat when the cache has dropped some (as its LRU does past 64)
    exe.invalidate_cache(main)
    obs.attach_sink(recs.append)
    try:
        exe.run(main, feed={"x": np.ones((3, 7), np.float32)},
                fetch_list=[out], scope=scope)
    finally:
        obs.detach_sink(recs.append)
    assert [r["attrs"]["entry"] for r in recs
            if r["name"] == "executor.first_dispatch"] == [0, 1]
    # the cache's own verdicts reach the counters (the suite caches every
    # executable: tests/conftest.py)
    counters = obs.snapshot()["counters"]
    assert counters.get("compile.cache.hits", 0) \
        + counters.get("compile.cache.misses", 0) > 0


def test_the_op_scope_books_self_seconds_by_path():
    """Under a trace the scopes' SELF seconds reach the entry's `op_s` by
    path; at the top level the same scopes book nothing."""
    import time

    import jax

    from paddle_tpu.observability.compile_events import op_scope

    def f(v):
        with op_scope("outer_probe", "outer_probe"):
            time.sleep(0.02)
            with op_scope("inner_probe", "inner_probe"):
                time.sleep(0.03)
        return v + 1

    recs = []
    obs.attach_sink(recs.append)
    try:
        f(np.ones(2, np.float32))           # eager: no trace, no seconds
        jax.jit(f)(np.ones(2, np.float32))
    finally:
        obs.detach_sink(recs.append)
    (entry,) = [e for e in _entries(recs) if "op_s" in e]
    op_s = entry["op_s"]
    assert set(op_s) == {"outer_probe", "outer_probe/inner_probe"}
    assert 0.03 <= op_s["outer_probe/inner_probe"] < 0.045
    assert 0.02 <= op_s["outer_probe"] < 0.03   # its own sleep, not the inner's
    assert entry["trace_s"] >= sum(op_s.values())


def test_the_disable_jit_replay_books_no_op_seconds():
    import jax

    from paddle_tpu.observability import compile_events

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[4], dtype="float32")
        out = pt.layers.fc(x, size=2)
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    assert not compile_events._state.op_s
    with jax.disable_jit():
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[out], scope=scope)
    assert not compile_events._state.op_s


def test_measurement_boundaries_leave_setup_and_compile_standing():
    """What the training runner clears at its window's start
    (`obs.reset("pipeline.")`, `obs.reset("train.")`) takes no `setup.*`,
    `compile.*` or `executor.*` series with it."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data(name="x", shape=[9], dtype="float32")
        loss = pt.layers.mean(pt.layers.fc(x, size=1))
        pt.optimizer.SGD(0.1).minimize(loss)
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed={"x": np.ones((2, 9), np.float32)},
            fetch_list=[loss], scope=scope)

    def standing():
        snap = obs.snapshot()
        return {k: v if kind == "counters" else v["count"]
                for kind in ("counters", "histograms")
                for k, v in snap[kind].items()
                if k.startswith(("setup.", "compile.", "executor."))}

    before = standing()
    assert {"setup.minimize.seconds", "setup.backward.seconds",
            "executor.first_dispatch.seconds", "compile.trace.seconds",
            "compile.lower.seconds", "compile.backend.seconds"} <= set(before)
    assert "pipeline.dispatch" in obs.snapshot()["stages"]
    obs.reset("pipeline.")
    obs.reset("train.")
    assert "pipeline.dispatch" not in obs.snapshot()["stages"]
    assert standing() == before


def test_the_import_is_one_sample_booked_when_the_registry_is_made(tmp_path):
    """Importing the package makes no registry (no exporter's file, no
    listener); the first use does, and books the import's seconds once,
    with a `setup.import` span record in the stream."""
    code = (
        "import os, sys, json\n"
        "import paddle_tpu\n"
        "from paddle_tpu import observability as obs\n"
        "reg = sys.modules['paddle_tpu.observability.registry']\n"
        "assert reg._default is None and not os.listdir(sys.argv[1])\n"
        "with obs.span('setup.minimize'):\n"
        "    pass\n"
        "h = obs.snapshot()['histograms']['setup.import.seconds']\n"
        "print(json.dumps(h))\n")
    r = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path)], capture_output=True,
        text=True, timeout=300, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "FLAGS_obs_jsonl_dir": str(tmp_path)})
    assert r.returncode == 0, r.stderr
    hist = json.loads(r.stdout.strip().splitlines()[-1])
    assert hist["count"] == 1 and hist["sum"] > 0
    recs = [json.loads(ln) for ln in
            (tmp_path / "obs.jsonl").read_text().splitlines()]
    assert [r["name"] for r in recs if r["type"] == "span"] \
        == ["setup.import", "setup.minimize"]
    assert recs[0]["dur_s"] == pytest.approx(hist["sum"], abs=1e-6)
    assert recs[0]["ts"] <= recs[1]["ts"] - recs[1]["dur_s"]


def test_setup_and_compile_names_are_declared():
    new = {"compile.trace.seconds", "compile.lower.seconds",
           "compile.backend.seconds", "compile.cache.hits",
           "compile.cache.misses", "compile.entry", "executor.first_dispatch.seconds",
           "setup.import.seconds", "setup.engine_build.seconds",
           "setup.engine_build.programs.seconds",
           "setup.engine_build.startup.seconds",
           "setup.engine_build.pools.seconds", "setup.decode_lattice.seconds",
           "setup.decode_lattice.entry.seconds", "setup.minimize.seconds",
           "setup.backward.seconds"}
    assert new <= schema.DECLARED_NAMES
    # every span literal of these families in the tree is one of them
    spans = _tree_literals(r'(?:\bspan|\bspanned)\(\s*"((?:setup|executor)\.[^"]+)"')
    assert spans and {s + ".seconds" for s in spans} <= new
    assert not [n for n in obs.snapshot()["undeclared"]
                if n.startswith(("setup.", "compile.", "executor."))]
