"""The step's PRNG key is derived INSIDE the compiled step (ISSUE 28).

`Executor._prepare_step` hands every compiled entry the program's seed and
the run counter as one np.uint32[2]; the lowered function opens with
`fold_in(PRNGKey(seed), counter)` (`executor._step_key`). Two things follow
and are held here: the ops draw the bits they drew when the executor derived
the key eagerly, on every lowered form; and a warm `Executor.run` does no jax
work on the host but its one dispatch. A CPU run proves bits and counts; it
gives no speed."""
import contextlib
import glob

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.model import cca_moe_tiny, decoder_tiny

ROWS, WIDTH, DRAW = 8, 16, (4, 8)
SEEDS = {"unset": None, "zero": 0, "seven": 7, "over_int32": 2 ** 31 + 5}
PATHS = ("jit", "gspmd", "shard_map", "segmented")


def _random_program(path, seed):
    """A dropout of ones (the fetch IS the mask) and a uniform draw; the
    segmented form puts a host op between them, so each sits in a jit
    segment of its own (indices 0 and 2)."""
    main, startup = pt.Program(), pt.Program()
    if seed is not None:
        main.random_seed = seed
    with pt.program_guard(main, startup), pt.unique_name.guard():
        x = L.data(name="x", shape=[WIDTH], dtype="float32")
        mask = L.dropout(x, 0.5)
        if path == "segmented":
            mask = L.Print(mask, summarize=1)
        draw = L.uniform_random(list(DRAW), min=-1.0, max=1.0)
    target = main
    if path == "gspmd":
        target = pt.CompiledProgram(main).with_data_parallel(
            mesh=make_mesh({"dp": 8}))
    elif path == "shard_map":
        target = pt.CompiledProgram(main).with_collective(
            mesh=make_mesh({"dp": 8}))
    return main, target, [mask, draw]


def _expected(seed, counter, path):
    """What the two ops draw from fold_in(PRNGKey(seed), counter), by the
    executor's rule (one split an op; a jit segment starts from the key
    folded with its index) and each op's own (`ops/nn_ops.dropout`,
    `ops/tensor_ops.uniform_random`)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed or 0), counter)
    if path == "segmented":
        k_mask = jax.random.split(jax.random.fold_in(key, 0))[1]
        k_draw = jax.random.split(jax.random.fold_in(key, 2))[1]
    else:
        key, k_mask = jax.random.split(key)
        k_draw = jax.random.split(key)[1]
    # under shard_map every shard draws its own row block from the same key
    rows = ROWS // 8 if path == "shard_map" else ROWS
    mask = jax.random.bernoulli(k_mask, 0.5, (rows, WIDTH))
    draw = jax.random.uniform(k_draw, DRAW, jnp.float32, -1.0, 1.0)
    return np.asarray(mask, np.float32), np.asarray(draw)


def _check(got, seed, counter, path):
    mask, draw = _expected(seed, counter, path)
    np.testing.assert_array_equal(np.asarray(got[0]), mask)
    np.testing.assert_array_equal(np.asarray(got[1]), draw)


@pytest.mark.parametrize("counter", ["scope", "explicit"])
@pytest.mark.parametrize("seed", list(SEEDS))
@pytest.mark.parametrize("path", PATHS)
def test_ops_draw_the_bits_of_the_eager_key(path, seed, counter):
    seed = SEEDS[seed]
    _, target, fetch = _random_program(path, seed)
    exe, scope = pt.Executor(), pt.Scope()
    feed = {"x": np.ones((ROWS, WIDTH), np.float32)}
    if counter == "scope":
        for run in (1, 2, 3):
            got = exe.run(target, feed=feed, fetch_list=fetch, scope=scope)
            _check(got, seed, run, path)
        assert scope._run_counter == 3
    else:
        for given in (2 ** 30 + 17, 5):
            got = exe.run(target, feed=feed, fetch_list=fetch, scope=scope,
                          rng_counter=given)
            _check(got, seed, given, path)


def test_a_seed_set_after_the_compile_is_seen_without_another():
    main, target, fetch = _random_program("jit", None)
    exe, scope = pt.Executor(), pt.Scope()
    feed = {"x": np.ones((ROWS, WIDTH), np.float32)}
    _check(exe.run(target, feed=feed, fetch_list=fetch, scope=scope),
           None, 1, "jit")
    entries = dict(exe._cache[main])
    main.random_seed = 11
    _check(exe.run(target, feed=feed, fetch_list=fetch, scope=scope),
           11, 2, "jit")
    assert dict(exe._cache[main]) == entries


# -- nothing eager in a warm run ----------------------------------------------


@contextlib.contextmanager
def _host_jax_work(monkeypatch, tmp_path):
    """What the block asks of jax from the host, two hooks because neither
    sees everything: `binds`, every primitive bound from Python (an eager
    lax or random call, a `device_put`, a jit whose fast path missed), and
    `executions`, the device programs launched, read from the profiler's
    host events (a warm jitted `jnp` function runs from C++ and binds
    nothing in Python)."""
    work = {"binds": [], "executions": None}
    bind = jax.extend.core.Primitive.bind

    def counting_bind(prim, *args, **params):
        work["binds"].append(prim.name)
        return bind(prim, *args, **params)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with monkeypatch.context() as patch:
        patch.setattr(jax.extend.core.Primitive, "bind", counting_bind)
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            yield work
        finally:
            jax.profiler.stop_trace()
    (trace,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    work["executions"] = sum(
        event.name.endswith("Executable::Execute")
        for plane in jax.profiler.ProfileData.from_file(trace).planes
        for line in plane.lines for event in line.events)


def test_the_hooks_see_eager_and_compiled_work(monkeypatch, tmp_path):
    """The yardstick of the tests below: the parent's derivation shows in
    both hooks, a warm jitted call in one."""
    x = jnp.ones((4,))
    double = jax.jit(lambda v: v * 2)
    double(x), jnp.add(x, x), jax.random.fold_in(jax.random.PRNGKey(0), 1)
    with _host_jax_work(monkeypatch, tmp_path / "eager") as eager:
        jax.random.fold_in(jax.random.PRNGKey(0), 1)
    assert "random_seed" in eager["binds"] and "random_fold_in" in eager["binds"]
    assert eager["executions"] >= 2
    with _host_jax_work(monkeypatch, tmp_path / "warm") as warm:
        double(x), jnp.add(x, x)
    assert warm["binds"] == [] and warm["executions"] == 2


def _train_step():
    x = L.data(name="x", shape=[WIDTH], dtype="float32")
    y = L.data(name="y", shape=[1], dtype="float32")
    hidden = L.dropout(L.fc(x, size=8, act="relu"), 0.25)
    loss = L.mean(L.square_error_cost(L.fc(hidden, size=1), y))
    pt.optimizer.Adam(0.01).minimize(loss)
    exe, main = pt.Executor(), pt.default_main_program()
    exe.run(pt.default_startup_program())
    feed = {"x": np.ones((ROWS, WIDTH), np.float32),
            "y": np.ones((ROWS, 1), np.float32)}

    def step():
        exe.run(main, feed=feed, fetch_list=[loss])

    step()
    return exe, step, [main]


def _engine_step(cfg, phase):
    """`ServingEngine.step()` whole, every signature it will use compiled.
    decode: one request mid-output, mid-page. prefill: a second prompt of a
    length already served (no shared prefix), which the step admits and
    then decodes with."""
    eng = ServingEngine(cfg, page_size=4, pool_pages=64, max_inflight=4,
                        seed=3)
    eng.submit([5, 6, 7, 8, 9, 10, 11, 12], max_new_tokens=12)
    if phase == "prefill":
        eng.run_until_drained()
        eng.submit([20, 21, 22, 23, 24, 25, 26, 27], max_new_tokens=12)
        return eng._exe, eng.step, [eng._prefill_run, eng._decode_run]
    eng.step()
    eng.step()
    return eng._exe, eng.step, [eng._decode_run]


CASES = {
    "train_dropout": _train_step,
    "post_ln_decode": lambda: _engine_step(decoder_tiny(), "decode"),
    "cca_moe_decode": lambda: _engine_step(cca_moe_tiny(), "decode"),
    "post_ln_prefill": lambda: _engine_step(decoder_tiny(), "prefill"),
    "cca_moe_prefill": lambda: _engine_step(cca_moe_tiny(), "prefill"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_a_warm_run_asks_jax_for_its_dispatch_alone(case, monkeypatch,
                                                    tmp_path):
    """On a signature hit every `Executor.run` of the step binds no
    primitive on the host and launches one device program: its compiled
    entry. For the serving cases the block is one whole
    `ServingEngine.step()`."""
    exe, step, expected_runs = CASES[case]()
    runs = []
    run = exe.run

    def counted_run(target, **kwargs):
        runs.append(target)
        return run(target, **kwargs)

    monkeypatch.setattr(exe, "run", counted_run)
    with _host_jax_work(monkeypatch, tmp_path) as work:
        step()
    assert runs == expected_runs
    assert work["binds"] == []
    assert work["executions"] == len(runs)
