"""Async feed/dispatch pipeline tests: DeviceLoader prefetch contract
(producer errors propagate, no leaked threads), PyReader use_double_buffer
routing, bucketed-padding numerics (masked loss is exact on real rows),
async-window determinism (same trajectory for window 1 and 4), and the
ragged-tail recompile regression (exactly one compile under
FLAGS_feed_bucketing)."""
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu import profiler
from paddle_tpu.data_feeder import ROW_MASK_NAME, pad_feed_to_bucket
from paddle_tpu.pipeline import DeviceLoader, jit_compile_counter


@pytest.fixture
def restore_flags():
    snap = pt.flags.all_flags()
    yield
    pt.flags.set_flags(snap)


def _threads_settle(base, deadline_s=5.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if threading.active_count() <= base:
            return True
        time.sleep(0.05)
    return threading.active_count() <= base


# -- DeviceLoader contract ---------------------------------------------------

def test_device_loader_stages_to_device_in_order():
    def src():
        for i in range(5):
            yield {"x": np.full((2, 3), i, np.float32)}

    out = list(DeviceLoader(src, depth=2))
    assert len(out) == 5
    for i, d in enumerate(out):
        assert isinstance(d["x"], jax.Array)
        np.testing.assert_array_equal(np.asarray(d["x"]), np.full((2, 3), i))


def test_device_loader_casts_to_feed_var_dtypes():
    x = L.data(name="dl_x", shape=[3], dtype="float32")

    def src():
        yield {"dl_x": np.ones((2, 3), np.float64), "extra": np.arange(2)}

    (d,) = list(DeviceLoader(src, depth=1, feed_vars=[x]))
    assert d["dl_x"].dtype == np.float32  # declared var dtype, not float64
    assert isinstance(d["extra"], jax.Array)  # unknown keys still staged


def test_device_loader_propagates_producer_errors_no_leaked_threads():
    base = threading.active_count()

    def bad():
        yield {"x": np.zeros(4, np.float32)}
        raise ValueError("boom")

    with pytest.raises(ValueError, match="boom"):
        list(DeviceLoader(lambda: bad(), depth=2))
    assert _threads_settle(base), "DeviceLoader left its stage thread running"


def test_device_loader_abandoned_iteration_stops_thread():
    base = threading.active_count()

    def src():
        for i in range(1000):
            yield {"x": np.full(4, i, np.float32)}

    it = iter(DeviceLoader(src, depth=2))
    next(it)
    it.close()  # consumer abandons mid-stream
    assert _threads_settle(base), "abandoned DeviceLoader leaked its thread"


def test_device_loader_records_stage_counters():
    profiler.stage_counters(reset=True)
    list(DeviceLoader(lambda: iter([{"x": np.zeros(4, np.float32)}] * 3),
                      depth=1))
    snap = profiler.stage_counters()
    assert snap["pipeline.host_ingest"]["events"] == 3
    assert snap["pipeline.device_put"]["events"] == 3


# -- PyReader use_double_buffer ----------------------------------------------

def _pyreader(double_buffer):
    x = L.data(name="px", shape=[4], dtype="float32")
    r = pt.PyReader(feed_list=[x], capacity=4,
                    use_double_buffer=double_buffer)
    r.decorate_sample_list_generator(
        lambda: iter([[(np.full(4, i, np.float32),)] * 2 for i in range(4)]))
    return r


def test_pyreader_double_buffer_yields_device_arrays():
    feeds = list(_pyreader(True)())
    assert len(feeds) == 4
    assert all(isinstance(d["px"], jax.Array) for d in feeds)


def test_pyreader_without_double_buffer_yields_host_arrays():
    feeds = list(_pyreader(False)())
    assert all(isinstance(d["px"], np.ndarray) for d in feeds)


def test_pyreader_double_buffer_still_propagates_errors():
    x = L.data(name="pe", shape=[4], dtype="float32")
    r = pt.PyReader(feed_list=[x], capacity=2, use_double_buffer=True)

    def bad():
        yield [(np.zeros(4, np.float32),)]
        raise ValueError("boom")

    r.decorate_sample_list_generator(lambda: bad())
    with pytest.raises(ValueError, match="boom"):
        for _ in r():
            pass


# -- bucketed padding --------------------------------------------------------

def test_pad_feed_to_bucket_shapes_and_mask():
    feed = pad_feed_to_bucket(
        {"a": np.ones((3, 2), np.float32), "b": np.ones((3, 1), np.int64)}, 5)
    assert feed["a"].shape == (5, 2) and feed["b"].shape == (5, 1)
    np.testing.assert_array_equal(feed["a"][3:], 0)
    np.testing.assert_array_equal(
        feed[ROW_MASK_NAME].ravel(), [1, 1, 1, 0, 0])


def _masked_regression_program():
    """Loss that honors the row-mask convention:
    sum(per_row * mask) / sum(mask)."""
    x = L.data(name="x", shape=[4], dtype="float32")
    y = L.data(name="y", shape=[1], dtype="float32")
    m = L.data(name=ROW_MASK_NAME, shape=[1], dtype="float32")
    per_row = L.square_error_cost(L.fc(x, size=1), y)
    loss = L.elementwise_div(L.reduce_sum(L.elementwise_mul(per_row, m)),
                             L.reduce_sum(m))
    pt.optimizer.SGD(0.1).minimize(loss)
    return x, y, loss


def test_bucketed_padding_numerics_match_unpadded():
    x, y, loss = _masked_regression_program()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    rng = np.random.default_rng(0)
    samples = [(rng.standard_normal(4, dtype=np.float32),
                rng.standard_normal(1, dtype=np.float32)) for _ in range(3)]
    w_name = main.all_parameters()[0].name
    exe = pt.Executor()

    results = []
    for bucket in (3, 4):  # 3 = no padding; 4 = one zero row + mask
        feeder = pt.DataFeeder([x, y], bucket_size=bucket)
        feed = feeder.feed(samples)
        assert feed["x"].shape[0] == bucket
        with pt.scope_guard(pt.Scope()) as scope:
            exe.run(startup)
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
            results.append((float(np.asarray(lv)),
                            np.asarray(scope.find_var(w_name))))
    (loss_a, w_a), (loss_b, w_b) = results
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-6)
    np.testing.assert_allclose(w_a, w_b, rtol=1e-6)


def test_dataset_split_batch_buckets_tail(restore_flags):
    ds = pt.DatasetFactory().create_dataset("QueueDataset")
    v = L.data(name="slot0", shape=[2], dtype="float32")
    ds.set_use_var([v])
    ds.set_batch_size(4)
    pt.flags.set_flags({"feed_bucketing": True})
    feed = ds._split_batch(np.arange(6, dtype=np.float64).reshape(3, 2))
    assert feed["slot0"].shape == (4, 2)
    np.testing.assert_array_equal(feed[ROW_MASK_NAME].ravel(), [1, 1, 1, 0])


# -- recompile regression (jax compile-count hook) ---------------------------

def test_ragged_tail_epoch_compiles_once_under_bucketing():
    x, y, loss = _masked_regression_program()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    rng = np.random.default_rng(1)

    def batches(sizes):
        return [[(rng.standard_normal(4, dtype=np.float32),
                  rng.standard_normal(1, dtype=np.float32))
                 for _ in range(n)] for n in sizes]

    exe = pt.Executor()
    exe.run(startup)
    feeder = pt.DataFeeder([x, y], bucket_size=4)
    with jit_compile_counter() as c:
        for b in batches([4, 4, 2]):  # epoch with a ragged tail
            exe.run(main, feed=feeder.feed(b), fetch_list=[loss])
    assert c.count == 1, f"expected 1 whole-block compile, saw {c.events}"

    # control: without bucketing the tail's exact shape forces a fresh
    # compile (the full-batch signature is already cached from above, so the
    # tail is the only new one)
    plain = pt.DataFeeder([x, y])
    with jit_compile_counter() as c2:
        for b in batches([4, 2]):
            feed = plain.feed(b)
            feed[ROW_MASK_NAME] = np.ones((len(b), 1), np.float32)
            exe.run(main, feed=feed, fetch_list=[loss])
    assert c2.count == 1, f"hook missed the tail recompile: {c2.events}"


# -- async dispatch window ---------------------------------------------------

def _dropout_program():
    x = L.data(name="dx", shape=[8], dtype="float32")
    h = L.dropout(L.fc(x, size=8, act="relu"), dropout_prob=0.5)
    loss = L.reduce_mean(L.square(h))
    pt.optimizer.SGD(0.1).minimize(loss)
    return loss


def test_async_window_determinism_across_sizes(restore_flags):
    """Window 1 (fully synchronous) and window 4 (async runahead) must walk
    the identical trajectory: rng_counter pins the per-step PRNG keys, so
    dropout masks do not depend on dispatch timing."""
    loss = _dropout_program()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    w_name = main.all_parameters()[0].name
    feed = {"dx": np.linspace(-1, 1, 16, dtype=np.float32).reshape(2, 8)}
    exe = pt.Executor()

    trajectories = []
    for window in (1, 4):
        pt.flags.set_flags({"max_inflight_steps": window})
        with pt.scope_guard(pt.Scope()) as scope:
            exe.run(startup)
            for i in range(6):
                outs = exe.run_async(main, feed=feed, fetch_list=[loss],
                                     rng_counter=100 + i)
                assert isinstance(outs[0], jax.Array)  # deferred fetch handle
            assert len(exe._inflight) <= window
            exe.wait()
            assert not exe._inflight
            trajectories.append(np.asarray(scope.find_var(w_name)))
    np.testing.assert_array_equal(trajectories[0], trajectories[1])


def test_run_async_handles_materialize_to_fetch_values():
    x = L.data(name="ax", shape=[2], dtype="float32")
    out = L.reduce_sum(x)
    exe = pt.Executor()
    (h,) = exe.run_async(pt.default_main_program(),
                         feed={"ax": np.ones((3, 2), np.float32)},
                         fetch_list=[out])
    exe.wait()
    assert float(np.asarray(h)) == pytest.approx(6.0)


# -- train_from_dataset async path -------------------------------------------

def _slot_file(tmp_path, rows, seed=0):
    rng = np.random.default_rng(seed)
    p = tmp_path / "part-0"
    with open(p, "w") as f:
        for _ in range(rows):
            vals = " ".join(f"{v:.4f}" for v in rng.random(4))
            f.write(f"4 {vals} 1 {rng.integers(0, 2)}\n")
    return str(p)


def _dataset_program():
    x = L.data(name="x", shape=[4], dtype="float32")
    y = L.data(name="y", shape=[1], dtype="float32")
    loss = L.reduce_mean(L.square_error_cost(L.fc(x, size=1), y))
    pt.optimizer.SGD(0.1).minimize(loss)
    return [x, y], loss


def test_train_from_dataset_async_matches_sync(tmp_path, restore_flags):
    use_vars, loss = _dataset_program()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    w_name = main.all_parameters()[0].name
    path = _slot_file(tmp_path, rows=10)  # batches of 4, 4, 2
    exe = pt.Executor()

    finals = []
    for window, depth in ((1, 0), (4, 2)):  # sync reference vs full pipeline
        pt.flags.set_flags({"max_inflight_steps": window,
                            "device_prefetch_depth": depth})
        ds = pt.DatasetFactory().create_dataset("QueueDataset")
        ds.set_batch_size(4)
        ds.set_use_var(use_vars)
        ds.set_filelist([path])
        with pt.scope_guard(pt.Scope()) as scope:
            exe.run(startup)
            exe.train_from_dataset(main, ds, fetch_list=[loss],
                                   print_period=10**9)
            finals.append(np.asarray(scope.find_var(w_name)))
    np.testing.assert_array_equal(finals[0], finals[1])


def test_train_from_dataset_throughput_print_excludes_first_batch(
        tmp_path, capsys, restore_flags):
    """Satellite fix: the printed batch/s window opens after batch 1 (the
    compile), and the rate divides by the batches inside the window."""
    use_vars, loss = _dataset_program()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    path = _slot_file(tmp_path, rows=16)  # 4 full batches
    ds = pt.DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(4)
    ds.set_use_var(use_vars)
    ds.set_filelist([path])
    exe = pt.Executor()
    exe.run(startup)
    exe.train_from_dataset(main, ds, fetch_list=[loss], print_period=3)
    printed = capsys.readouterr().out
    assert "batch 3 (" in printed and "batch/s" in printed
    # first batch is never inside a printed window
    assert "batch 1 (" not in printed


def test_train_from_dataset_no_leaked_threads(tmp_path, restore_flags):
    base = threading.active_count()
    use_vars, loss = _dataset_program()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    ds = pt.DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(4)
    ds.set_use_var(use_vars)
    ds.set_filelist([_slot_file(tmp_path, rows=12)])
    pt.flags.set_flags({"device_prefetch_depth": 2})
    exe = pt.Executor()
    exe.run(startup)
    exe.train_from_dataset(main, ds, print_period=10**9)
    assert _threads_settle(base), "prefetch stack leaked threads"


# -- bucket-boundary regressions (ISSUE 6: the tuner records these) ----------

def _ragged_sum_program():
    """Ragged-dim-tolerant program honoring the row mask: the ragged x is
    reduced over its padded dim (zero padding is sum-neutral) before the
    static-width fc."""
    x = L.data(name="rx", shape=[-1], dtype="float32")
    y = L.data(name="ry", shape=[1], dtype="float32")
    m = L.data(name=ROW_MASK_NAME, shape=[1], dtype="float32")
    h = L.reduce_sum(x, dim=1, keep_dim=True)
    per_row = L.square_error_cost(L.fc(h, size=1), y)
    loss = L.elementwise_div(L.reduce_sum(L.elementwise_mul(per_row, m)),
                             L.reduce_sum(m))
    pt.optimizer.SGD(0.1).minimize(loss)
    return x, y, loss


def test_batch_exactly_on_bucket_size_compiles_once():
    """A batch landing EXACTLY on bucket_size must share the bucketed
    signature (no pad rows, mask all ones — and critically no rounding past
    the bucket), so a full-then-ragged epoch is one compile. Guards the
    boundary the tuner records as a feed_bucket decision."""
    x, y, loss = _masked_regression_program()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    rng = np.random.default_rng(2)

    def batch(n):
        return [(rng.standard_normal(4, dtype=np.float32),
                 rng.standard_normal(1, dtype=np.float32))
                for _ in range(n)]

    exe = pt.Executor()
    exe.run(startup)
    feeder = pt.DataFeeder([x, y], bucket_size=4)
    exact = feeder.feed(batch(4))  # lands exactly on the bucket
    assert exact["x"].shape[0] == 4
    np.testing.assert_array_equal(exact[ROW_MASK_NAME].ravel(), [1, 1, 1, 1])
    with jit_compile_counter() as c:
        exe.run(main, feed=exact, fetch_list=[loss])
        exe.run(main, feed=feeder.feed(batch(4)), fetch_list=[loss])
        exe.run(main, feed=feeder.feed(batch(2)), fetch_list=[loss])
    assert c.count == 1, f"boundary batch broke the signature: {c.events}"


def test_one_past_pow2_ragged_boundary_compiles_once():
    """Ragged-dim rounding boundaries: max extent 8 (a power of two) stays
    8; max extent 9 (one past the boundary) rounds to 16 — ONE fresh
    compile that every later batch up to 16 then reuses. Guards the pow2
    decisions the tuner starts recording (data_feeder._tuned_extent)."""
    x, y, loss = _ragged_sum_program()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    rng = np.random.default_rng(3)

    def ragged(lens):
        return [(rng.standard_normal(n, dtype=np.float32),
                 rng.standard_normal(1, dtype=np.float32)) for n in lens]

    exe = pt.Executor()
    exe.run(startup)
    feeder = pt.DataFeeder([x, y], bucket_size=2)
    at8 = feeder.feed(ragged([8, 5]))
    assert at8["rx"].shape == (2, 8)  # exactly-pow2 max does NOT round up
    with jit_compile_counter() as c:
        exe.run(main, feed=at8, fetch_list=[loss])
        exe.run(main, feed=feeder.feed(ragged([6, 8])), fetch_list=[loss])
    assert c.count == 1, f"pow2-exact extent recompiled: {c.events}"

    past = feeder.feed(ragged([9, 4]))
    assert past["rx"].shape == (2, 16)  # one past the boundary: next pow2
    with jit_compile_counter() as c2:
        exe.run(main, feed=past, fetch_list=[loss])
        exe.run(main, feed=feeder.feed(ragged([13, 11])), fetch_list=[loss])
        exe.run(main, feed=feeder.feed(ragged([16, 2])), fetch_list=[loss])
    assert c2.count == 1, f"16-bucket shapes fragmented: {c2.events}"
