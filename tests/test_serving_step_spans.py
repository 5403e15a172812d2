"""One scheduler iteration as one span tree (ISSUE 23): `ServingEngine.step()`
down to the executor's wait on the device, on the profiler's clock, in the
registry and in the JSONL stream; the collector's pauses; the slow-step
record. A CPU run proves names, nesting, counts and sums; it gives no speed."""
import gc
import glob
import importlib
import os
import re
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags
from paddle_tpu import layers as L
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.serving import DecoderConfig, ServingEngine
from paddle_tpu.serving import engine as sv_engine
from paddle_tpu.serving import model as sv_model

# the module: `observability.registry` the attribute is the accessor function
obs_registry = importlib.import_module("paddle_tpu.observability.registry")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# child -> the parents it may hang under (the table of ISSUE 23; since
# ISSUE 36 a step program's fetch and accept hang under the span that
# enqueued the NEXT program, or under `serving.settle` where nothing was
# enqueued behind it)
STEP_CHILDREN = ("serving.prefill", "serving.decode")
ACCEPTS_UNDER = STEP_CHILDREN + ("serving.settle",)
TREE = {
    "serving.step": (None,),
    "serving.housekeeping": ("serving.step",),
    "serving.admit": ("serving.step",),
    "serving.prefill": ("serving.admit",),
    "serving.decode": ("serving.step",),
    "serving.ensure_writable": ("serving.decode",),
    "serving.feed_build": STEP_CHILDREN,
    "serving.settle": ("serving.step",),  # idle: no program will follow
    "serving.accept": ACCEPTS_UNDER,
    "pipeline.prepare": STEP_CHILDREN + ("serving.ensure_writable",),
    "pipeline.compile": ("pipeline.prepare",),
    "pipeline.dispatch": STEP_CHILDREN + ("serving.ensure_writable",),
    # ISSUE 49: the dispatch that traces, lowers and compiles a new entry
    "executor.first_dispatch": ("pipeline.dispatch",),
    "pipeline.fetch": ACCEPTS_UNDER,
}
# what an engine's constructor opens, outside any step (ISSUE 49)
BUILD = {"setup.engine_build", "setup.engine_build.programs",
         "setup.engine_build.startup", "setup.engine_build.pools"}


def _engine(hidden=32, layers=2, **kw):
    cfg = DecoderConfig(vocab_size=211, hidden_size=hidden, num_layers=layers,
                        num_heads=4, ffn_size=4 * hidden, max_position=64)
    return ServingEngine(cfg, page_size=4, pool_pages=64, max_inflight=4,
                         seed=5, **kw)


def _serve(eng, n=6, new=6, seed=0, shared=0):
    rng = np.random.default_rng(seed)
    head = rng.integers(1, 200, size=shared).tolist()
    rids = [eng.submit(head + rng.integers(1, 200, size=5 + i).tolist(), new)
            for i in range(n)]
    eng.run_until_drained()
    return [eng.pop_result(r) for r in rids]


@pytest.fixture
def records():
    got = []
    obs.attach_sink(got.append)
    yield got
    obs.detach_sink(got.append)


def _spans(records):
    return [r for r in records if r["type"] == "span"]


def test_a_step_is_one_tree_with_exactly_the_declared_names(records):
    eng = _engine()
    _serve(eng, shared=8)               # cold: compiles, prefix registration
    _serve(eng, seed=1, shared=8)       # suffix prefills, copy-on-write
    # the engine's build with its start-up run is the only work outside a
    # step
    assert {r["name"] for r in _spans(records) if "step" not in r} \
        <= {n for n in TREE if n.startswith(("pipeline.", "executor."))} \
        | BUILD
    spans = [r for r in _spans(records) if "step" in r]
    assert {r["name"] for r in spans} == set(TREE)
    by_step = {}
    for r in spans:
        assert r["parent"] if r["name"] != "serving.step" \
            else "parent" not in r, r
        assert r.get("parent") in TREE[r["name"]], r
        by_step.setdefault(r["step"], []).append(r)
    for step, rs in by_step.items():
        roots = [r for r in rs if r["name"] == "serving.step"]
        assert len(roots) == 1 and roots[0]["attrs"] == {"step": step}
        assert rs[-1] is roots[0]       # children close before the root
        assert [r["name"] for r in rs].count("serving.housekeeping") == 2
        inside = sum(r["dur_s"] for r in rs
                     if r.get("parent") == "serving.step")
        assert inside <= roots[0]["dur_s"] + 1e-6
    assert sorted(by_step) == list(range(min(by_step), max(by_step) + 1))
    # attributes ride the record, never the histogram's key
    pre = next(r for r in spans if r["name"] == "serving.prefill")
    assert set(pre["attrs"]) == {"rid", "tokens", "cached_len"}
    dec = next(r for r in spans if r["name"] == "serving.decode")
    assert set(dec["attrs"]) == {"rows", "bb", "pb"}
    hists = obs.snapshot()["histograms"]
    assert "serving.prefill.seconds" in hists
    assert not [k for k in hists if k.startswith("serving.") and "{" in k]
    assert obs.snapshot()["undeclared"] == []


def _children(spans):
    """{index of a span record: indices of its direct children, in order}.
    A record is written when its span closes, children first, and spans of
    one name do not overlap on the one thread: a span's children are the
    records before it that name it as parent and that no earlier span of
    its name has taken."""
    kids, loose = {}, []
    for i, r in enumerate(spans):
        kids[i] = [j for j in loose if spans[j].get("parent") == r["name"]]
        loose = [j for j in loose if j not in kids[i]] + [i]
    return kids


def test_the_leaves_cover_a_step_and_host_plus_fetch_is_the_decode(records):
    """A step program is read one dispatch late: its `pipeline.fetch` and
    `serving.accept` are children of the `serving.decode` /
    `serving.prefill` that enqueued the NEXT program (after that span's own
    `pipeline.dispatch`), or of a `serving.settle`. Stated on counts,
    parentage and the order of the records, no clock compared with a clock
    (ROADMAP D9)."""
    eng = _engine()
    _serve(eng)
    eng.reset_stats()
    records.clear()
    _serve(eng, seed=1)
    spans = _spans(records)
    names = [r["name"] for r in spans]
    kids = _children(spans)
    st = eng.stats
    programs = st["prefills"] + st["decode_steps"]
    # one fetch and one accept a step program, wherever they hang
    assert names.count("pipeline.fetch") == names.count("serving.accept") \
        == programs == st["chain.steps_deferred"] + st["chain.steps_blocking"]
    assert programs > 0 and st["chain.steps_blocking"] > 0
    deferred = blocking = 0
    for i, r in enumerate(spans):
        below = [spans[j]["name"] for j in kids[i]]
        if r["name"] in STEP_CHILDREN:
            # its own program goes out first; at most one fetch follows, the
            # pending step's, and that step's accept right behind it
            assert below.count("pipeline.dispatch") == 1, below
            assert below.count("pipeline.fetch") <= 1, below
            if "pipeline.fetch" in below:
                at = below.index("pipeline.fetch")
                assert below.index("pipeline.dispatch") < at
                assert below[at + 1] == "serving.accept"
                deferred += 1
        elif r["name"] == "serving.settle":
            assert below == ["pipeline.fetch", "serving.accept"]
            blocking += 1
    assert deferred == st["chain.steps_deferred"]
    assert blocking == st["chain.steps_blocking"]
    # serving.decode = the host's part + the wait for the step before, a
    # step: host_seconds is the span less its seconds inside pipeline.fetch
    snap = obs.snapshot()
    h = snap["histograms"]

    def fetch_below(i):
        return sum(spans[j]["dur_s"] if spans[j]["name"] == "pipeline.fetch"
                   else fetch_below(j) for j in kids[i])

    for name in STEP_CHILDREN:
        whole, host = h[name + ".seconds"], h[name + ".host_seconds"]
        assert whole["count"] == host["count"] > 0
        waited = sum(fetch_below(i) for i, r in enumerate(spans)
                     if r["name"] == name)
        assert whole["sum"] == pytest.approx(host["sum"] + waited,
                                             abs=1e-6 * whole["count"])
    # admit's self time is admit less the prefills under it
    pre, admit_self = h["serving.prefill.seconds"], \
        h["serving.admit.self_seconds"]
    assert admit_self["count"] == h["serving.admit.seconds"]["count"]
    assert admit_self["sum"] == pytest.approx(
        h["serving.admit.seconds"]["sum"] - pre["sum"], abs=1e-6)
    # the stages keep their exact [events, seconds] next to the histogram
    for stage in ("pipeline.prepare", "pipeline.dispatch", "pipeline.fetch"):
        assert snap["stages"][stage]["events"] == h[stage]["count"]
        assert snap["stages"][stage]["seconds"] == pytest.approx(
            h[stage]["sum"])
    assert snap["stages"]["pipeline.dispatch"]["events"] == programs \
        + st["cow_copies"]


def test_the_spans_are_on_the_profilers_clock_and_nest(tmp_path, monkeypatch):
    from benchmark.trace_reduce import find_xplane, read_planes

    # a CPU loop of tiny steps runs past the annotation budget: lift it here
    monkeypatch.setattr(obs_registry, "ANNOTATED_SPANS_PER_S", 1e9)
    monkeypatch.setattr(obs_registry, "ANNOTATED_SPANS_BURST", 1e9)
    eng = _engine()
    _serve(eng)
    with profiler.profiler(profile_path=str(tmp_path)):
        _serve(eng, seed=1)
    host = read_planes(find_xplane(str(tmp_path)))["host"]
    by_name = {}
    for name, s, e in host:
        by_name.setdefault(name, []).append((s, e))
    # the traced pass is warm: it compiles nothing, so neither span of a
    # new entry is in it
    assert set(TREE) - {"pipeline.compile", "executor.first_dispatch"} \
        <= set(by_name)

    def inside(child, parent):
        return all(any(ps <= s and e <= pe for ps, pe in by_name[parent])
                   for s, e in by_name[child])

    for child, parent in (("serving.admit", "serving.step"),
                          ("serving.decode", "serving.step"),
                          ("serving.prefill", "serving.admit"),
                          ("serving.ensure_writable", "serving.decode"),
                          ("pipeline.fetch", "serving.step"),
                          ("serving.accept", "serving.step")):
        assert inside(child, parent), (child, parent)
    assert len(by_name["serving.step"]) == len(by_name["serving.decode"])


class _FakeAnnotation:
    opened = []

    def __init__(self, name, **attrs):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.opened.append(self.name)

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("trees_per_s,annotated", [(10, "all"), (2000, "some")])
def test_a_fast_loop_is_annotated_by_whole_trees_within_the_budget(
        monkeypatch, trees_per_s, annotated):
    """The profiler's timeline takes `ANNOTATED_SPANS_PER_S` spans a second
    from one thread: a slow loop is annotated whole, a fast one by whole
    trees, and the registry sees every span of both."""

    monkeypatch.setattr(obs_registry, "_TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.opened = []
    st = obs_registry._tls
    st.budget, st.budget_at = 0.0, time.perf_counter()  # a loop long under way
    before = obs.snapshot()["histograms"].get(
        "serving.step.seconds", {}).get("count", 0)
    trees = 3 * trees_per_s                      # three seconds of it
    for i in range(trees):
        st.budget_at -= 1.0 / trees_per_s         # that much time has passed
        with obs.span("serving.step", step=i):
            with obs.span("serving.decode", rows=1):
                with profiler.stage_timer("pipeline.fetch"):
                    pass
            with obs.span("serving.housekeeping"):
                pass
    opened = _FakeAnnotation.opened
    assert opened[:4] == ["serving.step", "serving.decode", "pipeline.fetch",
                          "serving.housekeeping"] * (len(opened) > 0)
    assert len(opened) % 4 == 0
    assert all(opened[i:i + 4] == opened[:4] for i in range(0, len(opened), 4))
    if annotated == "all":
        assert len(opened) == 4 * trees
    else:
        rate = len(opened) / 3.0
        assert 0.9 * obs_registry.ANNOTATED_SPANS_PER_S <= rate \
            <= 1.2 * obs_registry.ANNOTATED_SPANS_PER_S  # + the loop's own time
    after = obs.snapshot()["histograms"]["serving.step.seconds"]["count"]
    assert after - before == trees


def test_a_collection_inside_a_phase_is_booked_to_the_collector(monkeypatch):
    eng = _engine()
    _serve(eng)
    eng.reset_stats()
    gc.unfreeze()   # the boundary froze the heap: the whole of it to walk,
    grow = sv_engine.ServingEngine._grow_and_cow
    ballast = [[i] for i in range(200_000)]   # a collection worth timing

    def collecting(self, lookahead):
        gc.collect()
        return grow(self, lookahead)

    monkeypatch.setattr(sv_engine.ServingEngine, "_grow_and_cow", collecting)
    before = obs.gc_pause_seconds()
    _serve(eng, n=2, seed=1)
    del ballast
    snap = obs.snapshot()
    pauses = snap["histograms"]["host.gc.seconds"]
    assert pauses["count"] >= 1 and pauses["max"] > 1e-3
    assert snap["counters"]['host.gc.collections{generation="2"}'] >= 1
    slow = eng.stats_snapshot()["slowest_step"]
    assert 1e-3 < slow["gc_s"] <= slow["dur_s"]
    assert slow["gc_s"] <= obs.gc_pause_seconds() - before
    # the pause sits in the phase it interrupted
    assert slow["phases"]["serving.ensure_writable"] >= 0.9 * slow["gc_s"]
    assert sum(slow["phases"].values()) == pytest.approx(slow["dur_s"])


def test_the_measurement_boundary_takes_the_setup_heap_off_the_collector():
    """`reset_stats` is where set-up ends: what lives then is frozen, so a
    full collection inside the traffic that follows walks only what was
    allocated since (0.3 s over a warmed engine's 328k objects otherwise:
    PERF §6, PR 35), and the collection the boundary itself makes is not
    booked to the window. A second boundary reclaims what died since."""
    import weakref

    class Node:
        pass

    eng = _engine()
    _serve(eng)
    ring = Node()
    ring.me = ring                      # garbage only a collection frees
    dead = weakref.ref(ring)
    assert gc.get_freeze_count() == 0
    eng.reset_stats()
    frozen = gc.get_freeze_count()
    assert frozen > 10_000
    pauses = obs.snapshot()["histograms"].get("host.gc.seconds")
    assert pauses is None or pauses["count"] == 0
    del ring
    gc.collect()
    assert dead() is not None           # frozen: a collection walks past it
    eng.reset_stats()                   # unfreeze, collect, freeze again
    assert dead() is None
    assert gc.get_freeze_count() > 10_000
    _serve(eng, n=2, seed=1)            # and the engine serves on


def test_a_phase_past_the_threshold_emits_the_slow_step_naming_it(
        monkeypatch, records):
    eng = _engine()
    _serve(eng, n=3, seed=2)            # the same lengths: every program
    eng.reset_stats()
    records.clear()
    monkeypatch.setattr(sv_engine, "SLOW_STEP_S", 0.05)
    note = sv_engine.ServingEngine._note_occupancy
    state = {"slept": False}

    def sleepy(self):
        if not state["slept"] and self._step_rows:
            state["slept"] = True
            time.sleep(0.08)
        return note(self)

    monkeypatch.setattr(sv_engine.ServingEngine, "_note_occupancy", sleepy)
    _serve(eng, n=3, seed=1)
    events = [r for r in records if r["name"] == "serving.slow_step"]
    assert len(events) == 1 and events[0]["level"] == "warning"
    rec = events[0]["payload"]
    assert set(rec) == {"step", "dur_s", "phases", "gc_s", "rows",
                        "admitted"}
    assert rec["dur_s"] >= 0.08 and rec["rows"] >= 1
    assert max(rec["phases"], key=rec["phases"].get) == \
        "serving.housekeeping", rec
    assert rec["phases"]["serving.housekeeping"] >= 0.08
    assert eng.stats_snapshot()["slowest_step"] == rec
    step_max = obs.snapshot()["histograms"]["serving.step.seconds"]["max"]
    assert step_max == pytest.approx(rec["dur_s"])


def test_reset_stats_scopes_host_and_pipeline_series_too():
    eng = _engine()
    _serve(eng)
    gc.collect()
    snap = obs.snapshot()
    assert snap["stages"]["pipeline.fetch"]["events"] > 0
    assert any(k.startswith("host.gc.collections") for k in snap["counters"])
    assert eng.stats_snapshot()["slowest_step"] is not None
    eng.reset_stats()
    snap = obs.snapshot()
    for store in ("counters", "histograms", "stages", "gauges"):
        left = [k for k in snap[store]
                if k.startswith(("serving.", "pipeline.", "host."))]
        # a collection may fall between the reset and the snapshot
        assert [k for k in left if not k.startswith("host.gc.")] == [], store
    assert eng.stats_snapshot()["slowest_step"] is None


def test_with_the_layer_off_no_span_is_recorded_and_tokens_are_the_same(
        records):
    served = _serve(_engine())
    assert _spans(records)
    records.clear()
    obs.reset()
    flags.set_flags({"obs_enable": False})
    try:
        eng = _engine()
        quiet = _serve(eng)
        snap = obs.snapshot()
    finally:
        flags.set_flags({"obs_enable": True})
    assert quiet == served
    assert records == [] and snap["histograms"] == {}
    assert eng.stats_snapshot()["slowest_step"] is None
    # the always-on accumulators keep counting
    assert snap["stages"]["pipeline.fetch"]["events"] > 0
    assert snap["counters"]["serving.decode_steps"] > 0


def test_every_executor_run_counts_one_event_per_stage():
    x = L.data(name="x", shape=[4], dtype="float32")
    loss = L.mean(L.fc(x, size=3))
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    obs.reset("pipeline.")
    feed = {"x": np.ones((2, 4), np.float32)}
    for _ in range(3):
        exe.run(pt.default_main_program(), feed=feed, fetch_list=[loss])
    exe.run_async(pt.default_main_program(), feed=feed, fetch_list=[loss])
    exe.wait()
    stages = obs.snapshot()["stages"]
    assert stages["pipeline.prepare"]["events"] == 4
    assert stages["pipeline.dispatch"]["events"] == 4
    assert stages["pipeline.fetch"]["events"] == 3      # run_async reads none
    assert stages["pipeline.compile"]["events"] == 1    # one signature
    assert all(stages[s]["seconds"] > 0 for s in
               ("pipeline.prepare", "pipeline.dispatch", "pipeline.fetch"))


def test_stage_timer_and_span_share_one_stack(records):
    phases = {}
    with obs.span("serving.step", collect=phases, step=41) as root:
        with profiler.stage_timer("pipeline.window_drain"):
            with obs.span("serving.accept", {"kind": "x"}, rid=7) as leaf:
                time.sleep(0.002)
            leaf.note(rows=3)           # after exit: too late for the record
    inner, stage, outer = _spans(records)[-3:]
    assert (inner["name"], inner["parent"], inner["step"]) == \
        ("serving.accept", "pipeline.window_drain", 41)
    assert inner["labels"] == {"kind": "x"} and inner["attrs"] == {"rid": 7}
    assert (stage["parent"], stage["step"]) == ("serving.step", 41)
    assert "parent" not in outer
    assert root.dur_s >= stage["dur_s"] >= inner["dur_s"] >= 0.002
    assert sum(phases.values()) == pytest.approx(root.dur_s)
    assert phases["serving.accept"] == pytest.approx(leaf.dur_s)
    assert root.self_s == pytest.approx(phases["serving.step"])
    snap = obs.snapshot()
    assert 'serving.accept.seconds{kind="x"}' in snap["histograms"]
    assert snap["stages"]["pipeline.window_drain"]["events"] >= 1


def test_a_collection_under_the_registrys_lock_does_not_deadlock():
    """An allocation inside a locked section can start a collection on the
    thread that holds the lock: the hook must not wait for it."""
    reg = obs.registry()
    with reg._lock:
        gc.collect()
    gc.collect()        # books what the first could not
    gens = obs.snapshot()["counters"]
    assert gens['host.gc.collections{generation="2"}'] >= 2


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    REPO, "paddle_tpu", "ops", "pallas_kernels", "*.py"))),
    ids=os.path.basename)
def test_every_pallas_call_names_its_kernel(path):
    """A kernel without `name=` shows in a trace under the name of whatever
    Python function wrapped it, and no reduction finds it after a refactor."""
    with open(path) as f:
        src = f.read()
    calls = [m.end() for m in re.finditer(r"pl\.pallas_call\(", src)]
    for start in calls:
        depth, i = 1, start
        while depth:
            depth += {"(": 1, ")": -1}.get(src[i], 0)
            i += 1
        assert re.search(r"\bname=\"[a-z0-9_]+\"", src[start:i]), \
            f"{os.path.basename(path)}: pallas_call without name= at {start}"


def test_the_paged_decode_kernel_is_found_by_its_name_in_the_lowering():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    q = jnp.zeros((2, 2, 64), jnp.float32)
    pool = jnp.zeros((4, 8, 2 * 64), jnp.float32)   # [pages, ps, nh*dh]
    table = jnp.zeros((2, 2), jnp.int32)
    lens = jnp.ones((2,), jnp.int32)
    text = jax.jit(lambda *a: pa._call(*a, 1.0, True)).lower(
        q, pool, pool, table, lens).as_text(debug_info=True)
    assert "paged_decode_attention" in text
