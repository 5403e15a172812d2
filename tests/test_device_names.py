"""The device's seconds under the program's own names (ISSUE 35): what the
executor and the stack lowerings write into a compiled module's `op_name`
metadata, and the reduction of a device trace that reads it back
(`profiler.device_time`, `device_table`). All on the CPU: compiled text for
the names, hand-made planes for the reduction, the repo's recorded four-chip
trace for the file format."""
import os
import re

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu import profiler
from paddle_tpu.layers import tensor as T
from paddle_tpu.observability import schema

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_TRACE = os.path.join(REPO, "tests", "benchmark", "data",
                          "tiny_trace.xplane.pb")


@pytest.fixture
def fresh_metadata():
    """jax leaves `op_name`s out of the compile cache's key, so a cached
    executable carries the names of the tree that compiled it: read
    compiled text with the metadata in the key."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    old = getattr(jax.config, flag)
    jax.config.update(flag, True)
    yield
    jax.config.update(flag, old)


def compiled_text(exe, program, feed, fetch_list):
    """The optimized HLO text of the entry `exe.run` would dispatch."""
    names = [v.name for v in fetch_list]
    comp, feed_vals, ro, rw, seed, *_ = exe._prepare_step(
        program, feed, names, pt.global_scope(), None, "gspmd", None)
    return comp.fn.lower(tuple(feed_vals), ro, rw, seed).compile().as_text()


def paths_of(text):
    return {profiler.op_path(n)
            for n in re.findall(r'op_name="([^"]+)"', text)} - {""}


# -- (1) names where the work is lowered ---------------------------------------

def test_instructions_carry_name_scope_and_op_type(fresh_metadata):
    x = L.data(name="x", shape=[8], dtype="float32")
    y = L.data(name="y", shape=[1], dtype="float32")
    with pt.name_scope("tower"):
        h = L.fc(x, size=4, act="relu")
        with pt.name_scope("out"):
            pred = L.fc(h, size=1)
    loss = L.mean(L.square_error_cost(pred, y))
    pt.optimizer.Adam(0.01).minimize(loss)
    main = pt.default_main_program()
    assert main.name == "train_step"      # minimize names an unnamed Program
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    feed = {"x": np.ones((4, 8), np.float32), "y": np.ones((4, 1), np.float32)}
    text = compiled_text(exe, main, feed, [loss])
    assert text.startswith("HloModule jit_train_step")
    paths = paths_of(text)
    # <name scope>/<op type>, the inner scope under the outer
    assert "tower/mul" in paths and "tower/out/mul" in paths
    # a grad op carries the scope of the op it differentiates
    assert "tower/mul_grad" in paths and "tower/out/mul_grad" in paths
    # ops appended outside any scope stand under their type alone
    assert {"adam", "square_error_cost", "square_error_cost_grad"} <= paths
    assert "tower/square_error_cost" not in paths
    # every instruction that carries a name carries an op's: nothing of
    # jax's own survives in a path
    for p in paths:
        assert not set(p.split("/")) & profiler._JAX_ADDED, p
    main.name = "probe step"              # a caller's name stands, made safe
    main._bump_version()
    assert compiled_text(exe, main, feed, [loss]).startswith(
        "HloModule jit_probe_step")


def test_an_unnamed_program_keeps_jit_fn(fresh_metadata):
    x = L.data(name="x", shape=[4], dtype="float32")
    out = L.scale(x, scale=2.0)
    text = compiled_text(pt.Executor(), pt.default_main_program(),
                         {"x": np.ones((2, 4), np.float32)}, [out])
    assert text.startswith("HloModule jit_fn")
    assert pt.default_main_program().clone().name is None


def test_compile_counter_counts_an_entry_under_any_program_name():
    """`pipeline.jit_compile_counter()` is what the benchmark's
    `window_compiles` reads: a compiled entry called after its Program must
    count as the ones called `fn` do."""
    from paddle_tpu.pipeline import jit_compile_counter

    x = L.data(name="x", shape=[4], dtype="float32")
    out = L.scale(x, scale=3.0)
    main = pt.default_main_program()
    exe = pt.Executor()
    feed = {"x": np.ones((2, 4), np.float32)}
    with jit_compile_counter() as unnamed:
        exe.run(main, feed=feed, fetch_list=[out])
    main.name = "serving_decode"
    main._bump_version()
    with jit_compile_counter() as named:
        exe.run(main, feed=feed, fetch_list=[out])
        exe.run(main, feed=feed, fetch_list=[out])      # cached: no compile
    assert (unnamed.count, named.count) == (1, 1), (
        unnamed.events, named.events)
    assert named.events[0].startswith("jit(serving_decode)")


def test_custom_grad_maker_keeps_the_forward_scope():
    """backward.py hands a grad op its forward op's name scope whatever the
    op's grad maker copied."""
    x = L.data(name="x", shape=[6], dtype="float32")
    with pt.name_scope("enc"):
        h = L.fc(x, size=6)
        h = L.layer_norm(h)
        h = L.dropout(h, dropout_prob=0.1)
        h = L.softmax(h)
    pt.optimizer.SGD(0.1).minimize(L.mean(h))
    grads = [op for op in pt.default_main_program().global_block.ops
             if op.type.endswith("_grad") and op.type != "mean_grad"]
    assert len(grads) >= 5
    for op in grads:
        assert op.attrs.get("op_namescope") == "enc", op.type


def test_sub_block_ops_carry_their_own_names(fresh_metadata):
    x = L.data(name="x", shape=[4], dtype="float32")
    i = T.fill_constant(shape=[1], dtype="int64", value=0)
    n = T.fill_constant(shape=[1], dtype="int64", value=3)
    acc = T.fill_constant(shape=[1, 4], dtype="float32", value=0.0)
    cond = L.less_than(i, n)
    w = L.While(cond)
    with w.block():
        with pt.name_scope("loop"):
            s = L.reduce_sum(x, dim=0, keep_dim=True)
            L.assign(L.elementwise_add(acc, s), acc)
        L.increment(i, value=1, in_place=True)
        L.less_than(i, n, cond=cond)
    pred = L.data(name="p", shape=[], dtype="bool")
    with pt.name_scope("pick"):
        out = L.cond(pred, lambda: L.scale(acc, scale=2.0),
                     lambda: L.tanh(acc))
    feed = {"x": np.ones((2, 4), np.float32), "p": np.asarray(True)}
    paths = paths_of(compiled_text(pt.Executor(), pt.default_main_program(),
                                   feed, [out]))
    assert "loop/elementwise_add" in paths      # inside the while's block
    assert any(p.endswith("pick/tanh") for p in paths)   # inside a branch
    assert not any("body" in p.split("/") for p in paths)


def test_op_path_drops_what_jax_adds():
    assert profiler.op_path(
        "jit(serving_decode)/sparse_moe_stack/decode/while/body/closed_call/"
        "indexer/jit(clip)/max") == "sparse_moe_stack/decode/indexer"
    assert profiler.op_path(
        "jit(train_step)/mlm_head/matmul_grad/transpose(jvp(bsh,hv->bsv))/"
        "dot_general") == "mlm_head/matmul_grad"
    assert profiler.op_path(
        "jit(f)/s/select/cond/branch_1_fun/jit(cumsum)/"
        "select_mask_fn.<locals>.<lambda>/add") == "s/select"
    assert profiler.op_path("jit(f)/a/x/reshape;b/y/reshape") == "a/x"
    assert profiler.op_path("jit(fn)/mul") == "" == profiler.op_path("w")


def test_the_vocabulary_is_no_op_type():
    """`group_rows` finds a path's op type as its last registered one: a
    piece or a mode must never be one."""
    from paddle_tpu.ops.registry import has_op

    for name in schema.PIECES | schema.STACK_MODES:
        assert not has_op(name), name
    with pytest.raises(ValueError, match="not a piece"):
        schema.piece("attention")


# -- (3) the reduction on hand-made planes --------------------------------------

def _hlo_text(name):
    return f"%{name} = f32[8,128]{{1,0:T(8,128)}} fusion(f32[8,128] %p)"


def _planes():
    """Two chips; on each: module A(1) runs `fusion.3` (the indexer) and a
    `while` that holds two body fusions and a gap, module B(2) runs ANOTHER
    `fusion.3` (the head), a copy without metadata, and a kernel named by
    its `pallas_call`. Chip 1 is chip 0 shifted, with a slower `fusion.3`."""
    def chip(shift, slow):
        return {
            "modules": [("jit_serving_decode(1)", 100 + shift, 1100 + shift),
                        ("jit_serving_window(2)", 2000 + shift, 2600 + shift)],
            "ops": [
                (_hlo_text("fusion.3"), 100 + shift, 300 + shift + slow),
                ("%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %t)",
                 400 + shift, 1000 + shift),
                (_hlo_text("fusion.7"), 450 + shift, 650 + shift),
                (_hlo_text("fusion.8"), 700 + shift, 950 + shift),
                (_hlo_text("fusion.3"), 2000 + shift, 2100 + shift),
                ("%copy.4 = bf16[64,512]{1,0} copy(bf16[64,512] %x)",
                 2100 + shift, 2150 + shift),
                ("paged_decode_attention_gqa", 2200 + shift, 2500 + shift),
            ]}
    names = {
        "jit_serving_decode(1)": {
            "fusion.3": "jit(serving_decode)/sparse_moe_stack/decode/while/"
                        "body/closed_call/indexer/dot_general",
            "while.1": "jit(serving_decode)/sparse_moe_stack/decode/while",
            "fusion.7": "jit(serving_decode)/sparse_moe_stack/decode/while/"
                        "body/closed_call/select/reduce_sum",
            "fusion.8": "jit(serving_decode)/sparse_moe_stack/decode/while/"
                        "body/closed_call/experts/pallas_call"},
        "jit_serving_window(2)": {
            "fusion.3": "jit(serving_window)/sparse_moe_stack/window/head/"
                        "bsh,hv->bsv/dot_general",
            "copy.4": "",
            "paged_decode_attention_gqa":
                "jit(serving_window)/blk/sparse_moe_stack/window/while/body/"
                "closed_call/attend/pallas_call"}}
    return {0: chip(0, 0), 1: chip(5, 40)}, names


def test_reduction_tells_modules_apart_and_books_self_time_once():
    chips, names = _planes()
    r = profiler.reduce_device_planes(chips, names)
    assert r["chips"] == 2 and r["window_s"] == pytest.approx(2405e-9)
    p0 = r["per_chip"][0]["paths"]
    # two modules both hold a `fusion.3`: each goes to its own module's op
    assert p0["sparse_moe_stack/decode/indexer"]["self_s"] == \
        pytest.approx(200e-9)
    assert p0["sparse_moe_stack/window/head"]["self_s"] == \
        pytest.approx(100e-9)
    # a while's self time is its extent less its body's: 600 - 200 - 250
    assert p0["sparse_moe_stack/decode"]["self_s"] == pytest.approx(150e-9)
    assert p0["sparse_moe_stack/decode/select"]["self_s"] == \
        pytest.approx(200e-9)
    # no metadata: kept under `unscoped`, by kind and shape, never dropped
    assert p0["unscoped/copy bf16[64,512]"]["self_s"] == pytest.approx(50e-9)
    # a kernel's event carries its own name; its path keeps the name scope
    assert p0["blk/sparse_moe_stack/window/attend"]["calls"] == 1
    # self time sums to busy time exactly, by path and by module, per chip
    for chip, busy in ((0, 1250), (1, 1290)):
        c = r["per_chip"][chip]
        assert c["busy_s"] * 1e9 == pytest.approx(busy, abs=1e-6)
        for rows in (c["paths"], c["modules"]):
            assert sum(x["self_s"] for x in rows.values()) * 1e9 == \
                pytest.approx(busy, abs=1e-6)
    # the mean over chips; min and max over both
    assert r["busy_s"] * 1e9 == pytest.approx(1270)
    row = r["paths"]["sparse_moe_stack/decode/indexer"]
    assert row["self_s"] * 1e9 == pytest.approx(220) and row["calls"] == 1
    assert (row["min_s"], row["max_s"]) == \
        (pytest.approx(200e-9), pytest.approx(240e-9))
    mods = r["modules"]
    assert mods["jit_serving_decode(1)"]["self_s"] * 1e9 == pytest.approx(820)
    assert mods["jit_serving_window(2)"]["calls"] == 1
    assert sum(x["self_s"] for x in r["paths"].values()) == \
        pytest.approx(r["busy_s"])


def test_reduction_clips_to_a_window_and_groups():
    chips, names = _planes()
    r = profiler.reduce_device_planes({0: chips[0]}, names, window=(500, 2050))
    p = r["paths"]
    assert "sparse_moe_stack/decode/indexer" not in p       # before it
    assert p["sparse_moe_stack/decode/select"]["self_s"] == \
        pytest.approx(150e-9)                               # 500..650
    assert p["sparse_moe_stack/window/head"]["self_s"] == \
        pytest.approx(50e-9)                                # 2000..2050
    assert r["window_s"] == pytest.approx(1550e-9)
    by_op = profiler.group_rows(profiler.reduce_device_planes(chips, names),
                                "op")
    assert set(by_op) == {"sparse_moe_stack", "unscoped"}
    by_piece = profiler.group_rows(
        profiler.reduce_device_planes(chips, names), "piece")
    assert "sparse_moe_stack/window/attend" in by_piece     # scope dropped
    assert profiler.reduce_device_planes({0: {"modules": [], "ops": []}},
                                         {}) is None


def test_recorded_trace_carries_its_modules_hlo():
    """The chip's profile holds every executed module's HloProto in its
    `/host:metadata` plane, under the name the `XLA Modules` line prints:
    the map from an instruction to its op is read from the file itself."""
    with open(TINY_TRACE, "rb") as f:
        mods = profiler._trace_modules(f.read())
    (name, ops), = mods.items()
    assert re.fullmatch(r"jit_step\(\d+\)", name)
    assert ops["convolution_tanh_fusion"] == "jit(step)/dot_general"
    assert ops["all-reduce"] == "jit(step)/dot_general"
    assert ops["copy-start"] == ""
    r = profiler.device_time(TINY_TRACE)
    assert r["chips"] == 4 and set(r["modules"]) == {name}
    assert r["modules"][name]["calls"] == 4
    # the recorded program declared no scope: every second is unscoped, by
    # kind and shape, and the seconds are all there
    assert all(p.startswith("unscoped/") for p in r["paths"])
    assert sum(x["self_s"] for x in r["paths"].values()) == \
        pytest.approx(r["busy_s"])
    # the benchmark's own reduction of the same file finds the same seconds
    from benchmark import trace_reduce

    planes = trace_reduce.read_planes(TINY_TRACE)
    planes["host"] = []
    assert trace_reduce.reduce_planes(planes)["busy_s"] == \
        pytest.approx(r["busy_s"])


# -- (4) the reference's table --------------------------------------------------

def test_table_is_ordered_by_sorted_key():
    chips, names = _planes()
    r = profiler.reduce_device_planes(chips, names)

    def order(key):
        body = profiler.device_table(r, by="path",
                                     sorted_key=key).splitlines()[2:]
        return [ln.split("  ")[0].strip() for ln in body]

    total, calls = order("total"), order("calls")
    assert total[0] == "blk/sparse_moe_stack/window/attend"     # 300 ns
    assert set(total) == set(calls) and total != calls
    # every path ran once a chip but... none twice: ties break by name
    assert calls == sorted(calls)
    assert order("max")[0] == "blk/sparse_moe_stack/window/attend"
    assert order("min")[0] == "blk/sparse_moe_stack/window/attend"
    header = profiler.device_table(r, by="module",
                                   sorted_key="ave").splitlines()
    assert "sorted by ave" in header[0]
    assert header[1].split() == ["Event", "Calls", "Total", "Min", "Max",
                                 "Ave", "Ratio"]
    assert header[2].startswith("jit_serving_decode(1)")


def test_stop_profiler_prints_the_table_and_says_so_without_a_device(
        tmp_path, capsys):
    x = L.data(name="x", shape=[4], dtype="float32")
    out = L.scale(x, scale=2.0)
    exe = pt.Executor()
    with profiler.profiler(sorted_key="total",
                           profile_path=str(tmp_path / "a")):
        exe.run(pt.default_main_program(),
                feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[out])
    assert "no device plane" in capsys.readouterr().out
    profiler.start_profiler(profile_path=str(tmp_path / "b"))
    profiler.stop_profiler()                    # no key: prints nothing
    assert capsys.readouterr().out == ""
    profiler.start_profiler(profile_path=str(tmp_path / "c"))
    with pytest.raises(ValueError, match="sorted_key"):
        profiler.stop_profiler(sorted_key="name")
    assert profiler._trace_active is False


def test_stop_profiler_reads_the_trace_it_stopped(tmp_path, capsys,
                                                  monkeypatch):
    """On a trace with a device plane the table's order follows
    `sorted_key`: 'calls' reorders what 'total' ordered."""
    chips, names = _planes()
    chips[0]["ops"].append((_hlo_text("fusion.7"), 1010, 1020))
    chips[0]["ops"].append((_hlo_text("fusion.7"), 1030, 1040))
    report = profiler.reduce_device_planes({0: chips[0]}, names)
    monkeypatch.setattr(profiler, "device_time", lambda trace: report)
    firsts = {}
    for key in ("total", "calls"):
        profiler.start_profiler(profile_path=str(tmp_path / key))
        profiler.stop_profiler(sorted_key=key)
        table = capsys.readouterr().out.splitlines()
        assert f"sorted by {key}" in table[0]
        firsts[key] = [ln.split()[0] for ln in table[2:]]
    assert firsts["total"] == ["sparse_moe_stack", "unscoped"]
    chips[0]["ops"] += [("%copy.4 = bf16[64,512]{1,0} copy(%x)",
                         3000 + 10 * i, 3001 + 10 * i) for i in range(9)]
    report = profiler.reduce_device_planes({0: chips[0]}, names)
    profiler.start_profiler(profile_path=str(tmp_path / "again"))
    profiler.stop_profiler(sorted_key="calls")
    table = capsys.readouterr().out.splitlines()
    assert [ln.split()[0] for ln in table[2:]] == ["unscoped",
                                                   "sparse_moe_stack"]
