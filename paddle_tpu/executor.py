"""Executor: whole-block lowering of a Program to one compiled XLA computation.

TPU-native replacement for the reference's op-by-op C++ interpreter
(/root/reference/paddle/fluid/framework/executor.cc:172 Run, :431 hot loop) and
its Python front (/root/reference/python/paddle/fluid/executor.py:295).

Where the reference dispatches each op to a place-specialized kernel and
blocks on the device at the end (executor.cc:438), this executor:
  * traces the entire block through the ops' JAX computes into ONE jaxpr,
  * jit-compiles it per (program version, feed-shape signature) — the compile
    cache is the analogue of the reference's ExecutorPrepareContext reuse,
  * donates parameter/optimizer-state buffers so updates are in-place in HBM
    (the reference's var reuse / inplace passes, memory_optimize_pass/),
  * optionally compiles with GSPMD shardings over a device mesh (see
    compiler.py) — replacing ParallelExecutor + the multi-device SSA graph.

The Scope is a flat name -> jax.Array map (the reference's hierarchical Scope
collapses: temps never outlive a run because they live only inside the traced
function, which is exactly the eager-deletion GC behaviour executor.cc:86).

Randomness: ops that need RNG receive fresh subkeys split from a per-run key
derived from (program.random_seed, scope run counter) — counter-based PRNG is
the TPU-native equivalent of the reference's per-op seed attrs. The host only
names the two integers (`_prepare_step`, one np.uint32[2] that rides the
dispatch like a feed); the key itself is derived INSIDE the compiled step
(`_step_key`, the first thing every lowered form does), so a step costs the
host no eager jax call and a program without a random op pays nothing.
"""
from __future__ import annotations

import collections
import logging
import re
import time
import warnings
import weakref
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import flags, profiler
from . import observability as obs
from .framework import OpError, Program, Variable, default_main_program
from .observability.compile_events import OTHER_FN, label_fns_by, op_scope
from .ops.registry import ExecContext, get_op_def
from .resilience.faults import fault_point
from .resilience.guardrails import GUARD_HEALTH_NAME

__all__ = ["Scope", "Executor", "global_scope", "scope_guard"]

logger = logging.getLogger("paddle_tpu.executor")

_SKIP_OPS = ("feed", "fetch")


def _op_path(op) -> str:
    """What the device's time under `op` is booked to: the name scope the
    op was appended under, then its type (`encoder/mlm_head/matmul`)."""
    scope = op.attrs.get("op_namescope")
    return f"{scope}/{op.type}" if scope else op.type


def _compute_op(opdef, ctx, op):
    """Run one op's compute with creation-stack attribution on failure,
    under `jax.named_scope` of the op's path: every instruction the op
    lowers to carries it in the compiled module's `op_name` metadata, where
    `profiler.device_time` finds it again. Under a trace (the jitted
    block, a traced sub-block) the scope is entered once, at lowering, and
    a step runs none of it; a host op between two jitted segments is
    computed eagerly, so there the scope is entered on every step (a
    context manager's cost, as the op's own Python is). The persistent
    compile cache leaves `op_name` out of its key: an executable loaded
    from it carries the names of the tree that compiled it.

    While a trace is under way the op's self wall seconds go to the
    `op_s` of the trace's `compile.entry` (`op_scope`): trace time only, so
    neither an eager host op nor the `jax.disable_jit` replay books any."""
    try:
        with op_scope(_op_path(op), op.type):
            return opdef.compute(ctx)
    except OpError:
        raise
    except Exception as e:
        raise OpError(op, e) from e


def _maybe_check_finite(op, outs):
    """FLAGS_check_nan_inf debug mode (reference operator.cc:949): under
    jax.disable_jit() values are concrete, so validate every floating output;
    tracers (normal jitted path) are skipped."""
    if not flags.get_flag("check_nan_inf"):
        return
    for slot, val in outs.items():
        vals = val if isinstance(val, (list, tuple)) else [val]
        for v in vals:
            if v is None or isinstance(v, jax.core.Tracer):
                continue
            arr = np.asarray(v)
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                raise OpError(
                    op,
                    FloatingPointError(
                        f"output slot '{slot}' contains nan/inf "
                        f"(FLAGS_check_nan_inf)"),
                )


_nan_inf_jit_warned = False


def _warn_check_nan_inf_keeps_jit():
    """FLAGS_check_nan_inf used to silently force eager semantics on the
    compiled path — every real training run that set it lost XLA. Now the
    jit path is kept and this one-time warning points at the tools that do
    work compiled."""
    global _nan_inf_jit_warned
    if _nan_inf_jit_warned:
        return
    _nan_inf_jit_warned = True
    warnings.warn(
        "FLAGS_check_nan_inf cannot validate per-op outputs inside a "
        "compiled XLA step; keeping the jit path. For always-on numeric "
        "health at full speed use the in-graph sentinel "
        "(FLAGS_guard_numerics + resilience.guardrails.StepGuard); for "
        "eager per-op attribution wrap the run in jax.disable_jit() — the "
        "guard's blame replay does exactly that after a rewind.",
        stacklevel=4)


def _apply_numeric_faults(feed_names, feed_vals):
    """`numeric_nan` / `numeric_spike` fault sites (resilience/faults.py):
    the compiled step is opaque, so the feed is the injection boundary. A
    planted NaN propagates into the loss and every gradient slot; a 1e4x
    feed scale drives the finite loss spike the sentinel's EMA gate must
    catch. Values change, shapes don't — the compile-cache signature (and
    therefore the step's executable) is untouched."""
    from .core.selected_rows import is_selected_rows
    from .resilience.faults import InjectedFault

    mode = None
    try:
        fault_point("numeric_nan")
    except InjectedFault:
        mode = "nan"
    try:
        fault_point("numeric_spike")
    except InjectedFault:
        mode = mode or "spike"
    if mode is None:
        return feed_vals
    out = list(feed_vals)
    for i, v in enumerate(out):
        if is_selected_rows(v):
            continue
        arr = np.asarray(v)
        if arr.dtype.kind != "f" or arr.size == 0:
            continue
        arr = np.array(arr)  # private copy; v may be a staged device array
        if mode == "nan":
            arr.reshape(-1)[0] = np.nan
        else:
            arr *= 1e4
        out[i] = arr
        break
    return out


_scope_uid = 0


class Scope:
    """Flat name -> device array store (reference framework/scope.h:46)."""

    def __init__(self):
        global _scope_uid
        _scope_uid += 1
        self._uid = _scope_uid  # stable identity for compile-cache keys
        self._vars: dict[str, Any] = {}
        self._run_counter = 0

    def var_names(self):
        return list(self._vars)

    def has_var(self, name: str) -> bool:
        return name in self._vars

    def find_var(self, name: str):
        return self._vars.get(name)

    def set_var(self, name: str, value):
        self._vars[name] = value

    def erase(self, names: Sequence[str]):
        for n in names:
            self._vars.pop(n, None)

    def drop_all(self):
        self._vars.clear()


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope() -> Scope:
    return _scope_stack[-1]


class scope_guard:
    def __init__(self, scope: Scope):
        self.scope = scope

    def __enter__(self):
        _scope_stack.append(self.scope)
        return self.scope

    def __exit__(self, *a):
        _scope_stack.pop()


class _Compiled:
    """One compiled (program, signature) entry."""

    def __init__(self, fn, feed_names, ro_names, rw_names, fetch_names):
        self.fn = fn
        self.feed_names = feed_names
        self.ro_names = ro_names
        self.rw_names = rw_names
        self.fetch_names = fetch_names
        # set when the mesh spans multiple processes: (feed, ro, rw)
        # NamedShardings used to lift host values to global arrays
        self.global_shardings = None
        # mesh programs: {feed name: NamedSharding} for the DeviceLoader
        # prefetcher, so staged batches already carry the entry's layout
        self.feed_shardings = None
        # single-process mesh programs: the (ro, rw) NamedShardings the step
        # was compiled for. State is committed to them BEFORE the call: an
        # array's mesh is part of its type (jax 0.9.0), so a step first
        # called with the single-device state a startup run leaves would be
        # traced and compiled a second time when its own mesh-resident
        # outputs come back as inputs.
        self.state_shardings = None
        # (Program.name, ordinal of the signature within its Program) until
        # the entry's first dispatch, which is the one that traces, lowers
        # and compiles or loads it (`executor.first_dispatch`); then None
        self.first = None


def _on_mesh(v, sharding):
    """`v` committed to `sharding` (itself when it already is)."""
    if isinstance(v, jax.Array) and v.sharding == sharding:
        return v
    return jax.device_put(v, sharding)


def _has_host_ops(block) -> bool:
    from .ops.registry import has_op

    return any(
        has_op(op.type) and get_op_def(op.type).host
        for op in block.ops
        if op.type not in _SKIP_OPS
    )


def _split_segments(ops):
    """Partition ops into alternating ("jit", [ops...]) / ("host", [op])
    segments (SURVEY §7: blocks with host ops lower as jit segments around
    them — RPC send/recv, print, py_func)."""
    segs, cur = [], []
    for op in ops:
        if get_op_def(op.type).host:
            if cur:
                segs.append(("jit", cur))
                cur = []
            segs.append(("host", [op]))
        else:
            cur.append(op)
    if cur:
        segs.append(("jit", cur))
    return segs


def _analyze_block(block, feed_names: list[str], scope: Scope):
    """Def-use analysis: which names come from the scope (ro/rw state)."""
    defined = set(feed_names)
    external: list[str] = []
    written: list[str] = []
    written_set = set()
    for op in block.ops:
        if op.type in _SKIP_OPS:
            continue
        for n in op.input_names:
            if n and n not in defined:
                defined.add(n)
                external.append(n)
        for n in op.output_names:
            if n:
                defined.add(n)
                if n not in written_set:
                    written_set.add(n)
                    written.append(n)

    def _persistable(n):
        try:
            return block.var(n).persistable
        except KeyError:
            return False

    rw, ro = [], []
    for n in external:
        if n in written_set:
            rw.append(n)
        elif n.endswith("@GRAD") and not scope.has_var(n):
            # optional grad input never produced by the backward pass (e.g. a
            # forward output that doesn't reach the loss): grad kernels treat
            # a missing cotangent as zeros — don't demand it from the scope
            continue
        else:
            ro.append(n)
    # persistable outputs that were never read still flow back to the scope
    # (startup-program initialization pattern)
    extra_w = [n for n in written if n not in rw and (_persistable(n) or scope.has_var(n))]
    return ro, rw, extra_w


def _step_token(*groups):
    """Cheap scalar that completes exactly when the step's outputs do — the
    async-window handle. It cannot be a state array itself: the NEXT step
    donates those buffers, so a retained reference would be deleted before
    the window drains it. A fresh 1-element reduction over the first entry
    of every output leaf is never donated and costs nothing."""
    tok = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(groups):
        if getattr(leaf, "size", 0):
            v = jnp.ravel(leaf)[0]
            if jnp.iscomplexobj(v):
                v = jnp.real(v)
            tok = tok + v.astype(jnp.float32)
    return tok


def _step_key(seed_counter, segment=None):
    """The step's PRNG key, derived under the trace from the np.uint32[2]
    (program.random_seed, run counter) `_prepare_step` hands every compiled
    entry: fold_in(PRNGKey(seed), counter), bit for bit what the two calls
    give for the Python integers. The seed is a value, not a traced
    constant: `program.random_seed` is not part of the compile signature.
    `segment` folds in a host-op program's jit-segment index."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed_counter[0]),
                             seed_counter[1])
    return key if segment is None else jax.random.fold_in(key, segment)


# every name a lowered block's function goes by: `fn`, and what
# `_named_after` gave (pipeline.jit_compile_counter counts compiles of
# these; the compiler's events carry them as `fn`, everything else as
# `other`)
LOWERED_FN_NAMES = {"fn"}
label_fns_by(LOWERED_FN_NAMES.__contains__)


def _named_after(fn, program, suffix: str = ""):
    """`fn` called after its Program, if the Program has a name: jax names
    a jitted function's module `jit_<name>`, which is what the `XLA
    Modules` line of a device trace prints. An unnamed Program's entries
    stay `jit_fn`."""
    if program.name:
        name = re.sub(r"\W", "_", program.name) + suffix
        if name == OTHER_FN:    # reserved: what is NOT a lowered block's
            name += "_"
        fn.__name__ = fn.__qualname__ = name
        LOWERED_FN_NAMES.add(name)
    return fn


def _lower(block, feed_names, ro_names, rw_names, extra_w, fetch_names, axis_env=None):
    ops = [op for op in block.ops if op.type not in _SKIP_OPS]

    def fn(feed_vals, ro_vals, rw_vals, seed_counter):
        key = _step_key(seed_counter)
        env: dict[str, Any] = {}
        if axis_env is not None:
            from .ops.collective_ops import AXIS_ENV_KEY

            env[AXIS_ENV_KEY] = axis_env
        env.update(zip(ro_names, ro_vals))
        env.update(zip(rw_names, rw_vals))
        env.update(zip(feed_names, feed_vals))

        def lowerer(block_idx):
            # control-flow sub-block lowering hook (while/cond ops); the RNG
            # key arrives via sub_env['__rng_key'] set by the control-flow op
            sub = block.program.blocks[block_idx]
            return lambda sub_env: _run_ops_traced(sub, sub_env)

        for op in ops:
            opdef = get_op_def(op.type)
            rng = None
            if opdef.needs_rng:
                key_new, sub = jax.random.split(env.get("__rng_key", key))
                env["__rng_key"] = key_new
                rng = sub
            ctx = ExecContext(op, env, rng=rng, lowerer=lowerer)
            outs = _compute_op(opdef, ctx, op)
            _maybe_check_finite(op, outs)
            for slot, val in outs.items():
                names = op.outputs.get(slot, [])
                vals = val if isinstance(val, (list, tuple)) else [val]
                for n, v in zip(names, vals):
                    if n and v is not None:
                        env[n] = v
        fetches = tuple(env[n] for n in fetch_names)
        new_rw = tuple(env[n] for n in rw_names)
        new_extra = tuple(env[n] for n in extra_w)
        return fetches, new_rw, new_extra, _step_token(fetches, new_rw,
                                                       new_extra)

    return _named_after(fn, block.program)


class _SegmentedFn:
    """Executes a block containing host ops: jit segments on-device, host ops
    (RPC send/recv, listen_and_serv, print) eagerly between them. Same
    call contract as the whole-block jitted fn."""

    def __init__(self, block, feed_names, ro_names, rw_names, extra_w, fetch_names):
        self.feed_names = feed_names
        self.ro = ro_names
        self.rw = rw_names
        self.extra = extra_w
        self.fetch = fetch_names
        ops = [op for op in block.ops if op.type not in _SKIP_OPS]
        raw_segs = _split_segments(ops)
        need_later: list[set] = [set()] * len(raw_segs)
        acc = set(fetch_names) | set(rw_names) | set(extra_w)
        for i in range(len(raw_segs) - 1, -1, -1):
            need_later[i] = set(acc)
            acc |= {n for op in raw_segs[i][1] for n in op.input_names if n}
        self.segments = []
        for i, (kind, seg_ops) in enumerate(raw_segs):
            if kind == "host":
                self.segments.append(("host", seg_ops, None, None, None))
                continue
            defined = set()
            in_names = []
            for op in seg_ops:
                for n in op.input_names:
                    if n and n not in defined and n not in in_names:
                        in_names.append(n)
                defined.update(n for n in op.output_names if n)
            out_names = [n for n in dict.fromkeys(
                n for op in seg_ops for n in op.output_names if n)
                if n in need_later[i]]
            fn = jax.jit(self._make_segment_fn(
                block, seg_ops, in_names, out_names, i))
            self.segments.append(("jit", seg_ops, in_names, out_names, fn))

    @staticmethod
    def _make_segment_fn(block, seg_ops, in_names, out_names, index):
        def fn(in_vals, seed_counter):
            env: dict[str, Any] = {
                "__rng_key": _step_key(seed_counter, segment=index)}
            env.update({n: v for n, v in zip(in_names, in_vals) if v is not None})

            def lowerer(block_idx):
                sub = block.program.blocks[block_idx]
                return lambda sub_env: _run_ops_traced(sub, sub_env)

            for op in seg_ops:
                opdef = get_op_def(op.type)
                rng = None
                if opdef.needs_rng:
                    key_new, sub = jax.random.split(env["__rng_key"])
                    env["__rng_key"] = key_new
                    rng = sub
                ctx = ExecContext(op, env, rng=rng, lowerer=lowerer)
                outs = _compute_op(opdef, ctx, op)
                _maybe_check_finite(op, outs)
                for slot, val in outs.items():
                    names = op.outputs.get(slot, [])
                    vals = val if isinstance(val, (list, tuple)) else [val]
                    for n, v in zip(names, vals):
                        if n and v is not None:
                            env[n] = v
            return tuple(env.get(n) for n in out_names)

        return _named_after(fn, block.program, f"_seg{index}")

    def __call__(self, feed_vals, ro_vals, rw_vals, seed_counter):
        env: dict[str, Any] = {}
        env.update(zip(self.ro, ro_vals))
        env.update(zip(self.rw, rw_vals))
        env.update(zip(self.feed_names, feed_vals))
        for kind, seg_ops, in_names, out_names, fn in self.segments:
            if kind == "jit":
                vals = fn(tuple(env.get(n) for n in in_names), seed_counter)
                for n, v in zip(out_names, vals):
                    if v is not None:
                        env[n] = v
            else:
                op = seg_ops[0]
                opdef = get_op_def(op.type)
                ctx = ExecContext(op, env, rng=None, lowerer=None)
                outs = _compute_op(opdef, ctx, op)
                for slot, val in outs.items():
                    names = op.outputs.get(slot, [])
                    vals = val if isinstance(val, (list, tuple)) else [val]
                    for n, v in zip(names, vals):
                        if n and v is not None:
                            env[n] = v
        fetches = tuple(env[n] for n in self.fetch)
        new_rw = tuple(env[n] for n in self.rw)
        new_extra = tuple(env[n] for n in self.extra)
        # host-op programs execute synchronously segment by segment — there
        # is no async step to bound, so no completion token
        return fetches, new_rw, new_extra, None


def _run_ops_traced(block, env, key=None):
    """Trace a sub-block's ops against an existing env (control flow).
    Provides its own lowerer so control-flow ops nest arbitrarily. The RNG
    key threads through env['__rng_key'] (control-flow ops place a fresh
    per-iteration key there) and the evolved key is written back so nested
    randomness never repeats."""
    key = env.pop("__rng_key", key)
    if key is None:
        key = jax.random.PRNGKey(0)

    def lowerer(block_idx):
        sub = block.program.blocks[block_idx]
        return lambda sub_env: _run_ops_traced(sub, sub_env)

    for op in block.ops:
        opdef = get_op_def(op.type)
        rng = None
        if opdef.needs_rng:
            key, rng = jax.random.split(key)
        env["__rng_key"] = key
        ctx = ExecContext(op, env, rng=rng, lowerer=lowerer)
        outs = _compute_op(opdef, ctx, op)
        _maybe_check_finite(op, outs)
        for slot, val in outs.items():
            names = op.outputs.get(slot, [])
            vals = val if isinstance(val, (list, tuple)) else [val]
            for n, v in zip(names, vals):
                if n and v is not None:
                    env[n] = v
    return env


def _spans_processes(mesh) -> bool:
    """True when the mesh covers devices of more than one JAX process (a
    multi-host pod, or the launcher's localhost multi-process CPU job)."""
    return mesh is not None and len({d.process_index for d in mesh.devices.flat}) > 1


def _to_global(v, sharding):
    """Place one host/local value as a global array over a multi-process mesh.

    Feeds carry this process's shard of the global batch (the launcher's
    per-trainer data split, reference launch.py env contract); state is
    replicated, so every process supplies the full value. Both cases are
    exactly `jax.make_array_from_process_local_data`'s contract.
    """
    if isinstance(v, jax.Array):
        if v.sharding.device_set == sharding.device_set:
            return v  # already global on this mesh
        v = np.asarray(v)  # single-device/local array: re-place globally
    return jax.make_array_from_process_local_data(sharding, np.asarray(v))


class Executor:
    """Reference executor.py:295 contract: run(program, feed, fetch_list)."""

    def __init__(self, place=None):
        self.place = place
        # program -> {signature: _Compiled}
        self._cache: "weakref.WeakKeyDictionary[Program, dict]" = weakref.WeakKeyDictionary()
        # entries ever compiled of a Program (an `executor.first_dispatch`'s
        # ordinal: the cache's own size repeats once it evicts)
        self._entries_made = weakref.WeakKeyDictionary()
        # (step id, completion token, health vector) of dispatched-but-
        # undrained async steps (run_async window, bounded by
        # FLAGS_max_inflight_steps); the ids feed the hang watchdog's state
        # dump, the health vectors feed the StepGuard at drain time
        self._inflight: collections.deque = collections.deque()
        self._dispatch_seq = 0
        # numeric-guardrail policy (resilience/guardrails.StepGuard): fed
        # each drained step's in-graph health vector; may raise GuardRewind
        self._step_guard = None

    # -- public API ---------------------------------------------------------
    def run(
        self,
        program: Program | None = None,
        feed: dict | None = None,
        fetch_list: Sequence | None = None,
        scope: Scope | None = None,
        return_numpy: bool = True,
        rng_counter: int | None = None,
    ):
        """rng_counter: caller-controlled replacement for the scope run
        counter in the PRNG key derivation (`_step_key`, inside the compiled
        step; it travels there as an unsigned 32-bit value). Two runs of
        programs sharing a random_seed and an op prefix draw IDENTICAL
        per-op keys when given the same counter — how the pipeline backward
        replay reproduces the forward's dropout masks exactly
        (parallel/pipeline.py)."""
        outs, _, _ = self._run_impl(program, feed, fetch_list, scope,
                                    return_numpy, rng_counter)
        return outs

    def set_step_guard(self, guard) -> None:
        """Attach a resilience.guardrails.StepGuard: every drained async
        step's in-graph health vector is handed to it; a bad-step-budget
        overrun surfaces as GuardRewind from run_async/wait (which
        train_from_dataset handles in place)."""
        self._step_guard = guard

    def run_async(
        self,
        program: Program | None = None,
        feed: dict | None = None,
        fetch_list: Sequence | None = None,
        scope: Scope | None = None,
        rng_counter: int | None = None,
    ):
        """Dispatch one step and return DEVICE-ARRAY fetch handles — no host
        sync. The returned arrays materialize on first np.asarray (a deferred
        fetch); state updates chain forward through the scope exactly as with
        run(), including buffer donation.

        Runahead is bounded: each dispatch enqueues the step's completion
        token, and once more than FLAGS_max_inflight_steps tokens are
        pending the host blocks on the OLDEST one — the only place the async
        trainer loop ever waits on the device (window boundary drain)."""
        outs, token, health = self._run_impl(program, feed, fetch_list,
                                             scope, False, rng_counter)
        if token is not None:
            self._dispatch_seq += 1
            if self._step_guard is not None and health is not None:
                # keep the batch around until its (window-delayed) health
                # verdict lands — the blame replay needs the poison feed
                self._step_guard.note_dispatch(self._dispatch_seq, feed)
            self._inflight.append(
                (self._dispatch_seq, token, health,
                 getattr(self, "_last_spmd_mode", "gspmd"),
                 time.perf_counter()))
            window = int(flags.get_flag("max_inflight_steps"))
            if window > 0:
                while len(self._inflight) > window:
                    with profiler.stage_timer("pipeline.window_drain"):
                        self._drain_oldest()
        return outs

    def wait(self):
        """Block until every run_async step dispatched so far has completed
        on the device (epoch boundary / before reading trained state).
        Bounded by the hang watchdog: a wedged step raises StallError with
        an in-flight state dump instead of blocking forever."""
        while self._inflight:
            self._drain_oldest()

    def _drain_oldest(self):
        """Wait for the OLDEST dispatched step's completion token under the
        resilience watchdog (FLAGS_watchdog_stall_s): no device progress
        within the window raises StallError carrying the step ids still in
        flight, the window depth, and the per-stage profiler counters. The
        `pipeline_stall` fault site simulates the wedge so the path is
        testable on a healthy host; on StallError the queue is left intact
        for forensics."""
        from .resilience.faults import InjectedFault, fault_point
        from .resilience.watchdog import Watchdog, runtime_state

        step_id, token, health, spmd_mode, t_dispatch = self._inflight[0]
        stalled = False
        try:
            fault_point("pipeline_stall")
            if spmd_mode == "shard_map":
                # a collective program's completion token resolves only when
                # every rank's psum/gather lands — a lost/hung partner wedges
                # exactly here. The site lets chaos drills prove the watchdog
                # surfaces a hung allreduce with step ids + queue depths.
                fault_point("collective_stall")
        except InjectedFault:
            stalled = True  # behave as if the device never completes
        wd = Watchdog()
        is_ready = getattr(token, "is_ready", None)
        if not stalled and (not wd.enabled or is_ready is None):
            jax.block_until_ready(token)
        else:
            def state():
                return runtime_state(
                    oldest_step=step_id,
                    inflight_step_ids=[e[0] for e in self._inflight],
                    inflight_depth=len(self._inflight),
                    spmd_mode=spmd_mode,
                    max_inflight_steps=int(
                        flags.get_flag("max_inflight_steps")))

            what = (f"Executor async step {step_id}"
                    if spmd_mode != "shard_map" else
                    f"Executor async step {step_id} (collective allreduce)")
            wd.wait((lambda: False) if stalled else is_ready, state,
                    what=what)
        self._inflight.popleft()
        # dispatch->completion latency: includes device queueing under the
        # runahead window, which is the number the async loop actually
        # experiences at each drain point
        obs.counter_inc("train.steps")
        obs.histogram_observe("train.step_latency_s",
                              time.perf_counter() - t_dispatch)
        if health is not None and self._step_guard is not None:
            # token resolved above, so this 4-float read never blocks on
            # compute; observe() may raise GuardRewind (budget exhausted)
            self._step_guard.observe(self, step_id, np.asarray(health))

    def drain_quiet(self):
        """Complete every in-flight step WITHOUT guard/watchdog policy:
        the rewind path discards the window dispatched after a poison step
        (their state writes are about to be overwritten by the checkpoint
        restore), so their health verdicts must not re-trigger the guard."""
        while self._inflight:
            token = self._inflight.popleft()[1]
            try:
                jax.block_until_ready(token)
            except Exception:  # noqa: BLE001 — discard path
                pass

    def _run_impl(
        self,
        program: Program | None,
        feed: dict | None,
        fetch_list: Sequence | None,
        scope: Scope | None,
        return_numpy: bool,
        rng_counter: int | None,
    ):
        from .compiler import CompiledProgram  # lazy; avoids cycle

        mesh = None
        spmd_mode = "gspmd"
        if isinstance(program, CompiledProgram):
            mesh = program._mesh
            spmd_mode = program._spmd_mode
            program = program._program
        # run_async tags each inflight entry with the regime it dispatched
        # under, so the drain watchdog can attribute a wedge to a hung
        # collective (the collective_stall fault site) vs a plain step
        self._last_spmd_mode = spmd_mode
        if program is None:
            program = default_main_program()
        feed = feed or {}
        scope = scope or global_scope()
        fetch_names = [v.name if isinstance(v, Variable) else str(v) for v in (fetch_list or [])]

        if getattr(program, "_pipeline", None) is not None:
            # pipeline-optimized program: delegate the whole GPipe microbatch
            # schedule (parallel/pipeline.py)
            if mesh is not None:
                raise NotImplementedError(
                    "combining PipelineOptimizer with a CompiledProgram mesh "
                    "is not supported yet — run the pipeline program "
                    "directly (dp-sharding inside stages is planned)")
            return program._pipeline.run_step(self, scope, feed,
                                              fetch_names), None, None

        with profiler.stage_timer("pipeline.prepare"):
            (comp, feed_vals, ro_vals, rw_vals, seed_counter, emb_engine,
             emb_ticket) = self._prepare_step(
                 program, feed, fetch_names, scope, mesh, spmd_mode,
                 rng_counter)

        # FLAGS_check_nan_inf per-op validation only works on concrete
        # values: under jax.disable_jit() (the guard's blame replay, debug
        # sessions) _maybe_check_finite fires with op attribution during the
        # trace below. On the compiled path the flag used to silently force
        # eager semantics; now the jit path is KEPT and a one-time warning
        # points at the in-graph health sentinel instead.
        check_nan = flags.get_flag("check_nan_inf")
        eager = bool(jax.config.jax_disable_jit)
        if check_nan and not eager:
            _warn_check_nan_inf_keeps_jit()
        with profiler.stage_timer("pipeline.dispatch"):
            args = (tuple(feed_vals), ro_vals, rw_vals, seed_counter)
            first = comp.first      # read once: Predictor clones share entries
            if first is None:
                fetches, new_rw, new_extra, token = comp.fn(*args)
            else:
                comp.first = None
                with obs.span("executor.first_dispatch", program=first[0],
                              entry=first[1]):
                    fetches, new_rw, new_extra, token = comp.fn(*args)
        if check_nan and eager and getattr(comp, "spmd_mode",
                                           "gspmd") == "shard_map":
            # under shard_map the body values stay tracers even with
            # disable_jit, so per-op attribution is unavailable — fall back
            # to a whole-step output check
            for group, names in ((fetches, comp.fetch_names),
                                 (new_rw, comp.rw_names)):
                for n, v in zip(names, group):
                    arr = np.asarray(v)
                    if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                        raise RuntimeError(
                            f"FLAGS_check_nan_inf: non-finite value in "
                            f"'{n}' (per-op attribution is unavailable "
                            f"under shard_map/with_collective)")
        if flags.get_flag("benchmark"):
            jax.block_until_ready((fetches, new_rw))  # reference operator.cc:926

        for n, v in zip(comp.rw_names, new_rw):
            scope.set_var(n, v)
        for n, v in zip(comp.extra_w, new_extra):
            scope.set_var(n, v)

        if program.device_counters:
            # a step's count vectors (layers.device_counter) go to the
            # registry as the device arrays they are: `snapshot()` reads
            # them, after whatever window the caller measures
            src = getattr(comp, "counter_src", None)
            if src is None:     # resolve once per compiled entry
                src = comp.counter_src = [
                    (comp.extra_w.index(n), series)
                    for n, series in program.device_counters.items()
                    if n in comp.extra_w]
            for idx, series in src:
                obs.counter_defer(series, new_extra[idx])

        if emb_engine is not None and emb_ticket is not None:
            # hand the step's evicted-row output handles to the engine (no
            # sync — write-back lands when the device array materializes)
            emb_engine.note_dispatched(emb_ticket, scope)

        # the in-graph health vector (resilience/guardrails.py) rides the
        # step's outputs: hand the DEVICE array back so reading it after the
        # completion token resolves costs a 4-float transfer, no sync here
        health = None
        src = getattr(comp, "health_src", "?")
        if src == "?":  # resolve once per compiled entry
            src = None
            if GUARD_HEALTH_NAME in comp.extra_w:
                src = ("extra", comp.extra_w.index(GUARD_HEALTH_NAME))
            elif GUARD_HEALTH_NAME in comp.rw_names:
                src = ("rw", comp.rw_names.index(GUARD_HEALTH_NAME))
            comp.health_src = src
        if src is not None:
            group, idx = src
            health = (new_extra if group == "extra" else new_rw)[idx]

        if return_numpy:
            # blocks until the device has produced the fetches, then copies
            # them to the host
            with profiler.stage_timer("pipeline.fetch"):
                outs = [np.asarray(x) for x in fetches]
            return outs, token, health
        return list(fetches), token, health

    def _prepare_step(self, program, feed, fetch_names, scope, mesh,
                      spmd_mode, rng_counter):
        """Everything one step needs before its dispatch (the
        `pipeline.prepare` stage): feeds cast to their declared dtypes, the
        compile signature and the cache entry it names (`pipeline.compile`
        on a miss), the state gathered from the scope and the two integers
        the compiled step derives its PRNG key from (`_step_key`): seed and
        run counter as one np.uint32[2]. Host work only: on a signature hit
        nothing here binds a jax primitive or launches a device program."""
        from .core.selected_rows import is_selected_rows

        # tiered embeddings (embedding/engine.py): feeds staged by the
        # DeviceLoader arrive pre-resolved carrying a ticket (popped here —
        # it must not reach the compile signature); raw feeds resolve inline
        # so the synchronous exe.run path and the parity oracles work too
        emb_engine = getattr(program, "_tiered_engine", None)
        emb_ticket = None
        if emb_engine is not None and feed:
            feed, emb_ticket = emb_engine.prepare_feed(feed)

        block = program.global_block
        feed_names = sorted(feed)
        feed_vals = []
        for n in feed_names:
            v = feed[n]
            if not isinstance(v, jax.Array) and not is_selected_rows(v):
                # host data: cast to the var's declared RUNTIME dtype
                # (int64/float64 declarations narrow to 32-bit here, the
                # explicit form of the x64-off truncation device_put would
                # apply anyway); device arrays and SelectedRows (pserver
                # sparse grads) pass through
                v = np.asarray(v)
                try:
                    var = block.var(n)
                    v = v.astype(var.np_feed_dtype, copy=False)
                except KeyError:
                    pass
            feed_vals.append(v)

        # stable keys: Scope carries a uid (id() of a dead object can be
        # reused, silently aliasing cache entries); a mesh is keyed by its
        # layout, so two equal meshes share a compile
        mesh_key = None
        if mesh is not None:
            mesh_key = (
                tuple(mesh.axis_names),
                tuple(mesh.devices.shape),
                tuple(d.id for d in mesh.devices.flat),
            )
        def _sig_of(v):
            # dtypes by object: they hash and compare like their names, and
            # str() of one costs more than the rest of the signature
            if is_selected_rows(v):
                return ("sr", tuple(v.rows.shape), tuple(v.values.shape),
                        v.values.dtype, v.height)
            return (tuple(v.shape), v.dtype)

        sig = (
            program._version,
            tuple((n,) + _sig_of(fv) for n, fv in zip(feed_names, feed_vals)),
            tuple(fetch_names),
            mesh_key,
            spmd_mode,
            scope._uid,  # extra_w write-back analysis depends on scope contents
        )
        prog_cache = self._cache.setdefault(program, {})
        comp = prog_cache.get(sig)
        if comp is None:
            with profiler.stage_timer("pipeline.compile"):
                comp = self._compile(
                    program, block, feed_names, feed_vals, fetch_names,
                    scope, mesh, spmd_mode)
            comp.spmd_mode = spmd_mode
            made = self._entries_made.get(program, 0)
            self._entries_made[program] = made + 1
            comp.first = (program.name or "fn", made)
            prog_cache[sig] = comp
            # bound the per-program cache (each entry pins a compiled XLA
            # executable); evict least-recently-used beyond 64 signatures
            while len(prog_cache) > 64:
                prog_cache.pop(next(iter(prog_cache)))
        else:
            # LRU refresh, race-tolerant: cloned Predictors share this
            # executor across threads, and a bare pop(sig) can KeyError when
            # two runs refresh the same entry concurrently
            prog_cache.pop(sig, None)
            prog_cache[sig] = comp

        # per-step fault site (resilience/faults.py): fires once per executed
        # step, before any state is read or donated — an injected "collective
        # partner lost" fault leaves the scope untouched and retryable
        fault_point("collective.step")
        feed_vals = _apply_numeric_faults(feed_names, feed_vals)

        ro_vals = tuple(self._fetch_state(scope, n) for n in comp.ro_names)
        rw_vals = tuple(self._fetch_state(scope, n) for n in comp.rw_names)
        if comp.global_shardings is not None:
            # multi-process mesh: feeds are this process's batch shard, state
            # is replicated — lift everything to global arrays
            feed_sh, ro_sh, rw_sh = comp.global_shardings
            feed_vals = [_to_global(v, s) for v, s in zip(feed_vals, feed_sh)]
            ro_vals = tuple(_to_global(v, s) for v, s in zip(ro_vals, ro_sh))
            rw_vals = tuple(_to_global(v, s) for v, s in zip(rw_vals, rw_sh))
        elif comp.state_shardings is not None:
            ro_sh, rw_sh = comp.state_shardings
            placed = tuple(_on_mesh(v, s) for v, s in zip(ro_vals, ro_sh))
            for n, old, new in zip(comp.ro_names, ro_vals, placed):
                if new is not old:
                    scope.set_var(n, new)  # read-only state moves once
            ro_vals = placed
            rw_vals = tuple(_on_mesh(v, s) for v, s in zip(rw_vals, rw_sh))
            # feeds too: a host batch and a batch the DeviceLoader staged
            # with this entry's shardings must reach jit as one type
            feed_vals = [
                v if is_selected_rows(v)
                else _on_mesh(v, comp.feed_shardings[n])
                for n, v in zip(feed_names, feed_vals)]
        scope._run_counter += 1
        counter = scope._run_counter if rng_counter is None else int(rng_counter)
        # low 32 bits of each, what PRNGKey / fold_in keep of a Python int
        seed_counter = np.array(
            [(program.random_seed or 0) & 0xFFFFFFFF, counter & 0xFFFFFFFF],
            np.uint32)
        return (comp, feed_vals, ro_vals, rw_vals, seed_counter, emb_engine,
                emb_ticket)

    def train_from_dataset(
        self,
        program=None,
        dataset=None,
        scope: Scope | None = None,
        thread: int = 0,
        debug: bool = False,
        fetch_list=None,
        fetch_info=None,
        print_period: int = 100,
        guard=None,
    ):
        """Consume a Dataset end-to-end (reference executor.py:894 +
        Executor::RunFromDataset, executor.cc:142).

        guard: optional resilience.guardrails.StepGuard — installed via
        set_step_guard for the run; bad-step-budget overruns are handled IN
        the loop (checkpoint rewind + LR backoff + blame replay, then the
        epoch continues past the poison batch).

        The reference spins `thread` device workers each running the program
        over its own data feed (trainer.h MultiTrainer, device_worker.h
        HogwildWorker). On TPU one XLA stream consumes every batch — host
        threads inside the Dataset overlap file parse/shuffle with device
        steps, which is where the parallelism actually helps here.
        """
        if dataset is None:
            raise RuntimeError("dataset is need and should be initialized")
        if guard is not None:
            self.set_step_guard(guard)
        if thread:
            # reference semantics: min(dataset thread_num, thread) — but an
            # unconfigured dataset (thread_num=1 default) takes the explicit
            # request rather than silently clamping it to 1
            dataset.set_thread(
                min(dataset.thread_num, thread)
                if dataset.thread_num > 1 else thread)
        dataset._prepare_to_run()
        try:
            self._run_from_dataset(
                program, dataset, scope, debug, fetch_list, fetch_info,
                print_period)
        finally:
            dataset._finish_to_run()

    def infer_from_dataset(
        self,
        program=None,
        dataset=None,
        scope: Scope | None = None,
        thread: int = 0,
        debug: bool = False,
        fetch_list=None,
        fetch_info=None,
        print_period: int = 100,
    ):
        """reference executor.py:817 — identical loop; the program itself has
        no optimizer ops, so nothing updates."""
        self.train_from_dataset(
            program, dataset, scope, thread, debug, fetch_list, fetch_info,
            print_period)

    def _run_from_dataset(self, program, dataset, scope, debug, fetch_list,
                          fetch_info, print_period):
        """The async trainer loop: batches flow through the DeviceLoader
        prefetcher (transfer overlaps compute), each step dispatches through
        run_async (the host never blocks except at FLAGS_max_inflight_steps
        window boundaries), and fetched values stay device arrays until a
        print period actually reads them."""
        from .pipeline import DeviceLoader

        fetch_list = fetch_list or []
        names = [v.name if isinstance(v, Variable) else str(v)
                 for v in fetch_list]
        if fetch_info is not None and len(fetch_info) != len(names):
            raise ValueError(
                f"fetch_info has {len(fetch_info)} entries for "
                f"{len(names)} fetch_list variables")
        labels = list(fetch_info or names)
        depth = int(flags.get_flag("device_prefetch_depth"))
        if depth > 0:
            batches = iter(DeviceLoader(dataset._iter_batches, depth=depth,
                                        placement=self.feed_placer(program)))
        else:
            batches = dataset._iter_batches()
        from .resilience.guardrails import GuardRewind

        def _rewind(gr):
            # StepGuard budget overrun: restore + LR backoff + blame replay,
            # then keep consuming the epoch — the data cursor has already
            # moved past the poison batch, which is exactly the skip we want
            if self._step_guard is None:
                raise gr
            self._step_guard.rewind(self, gr)

        t0 = None
        n_batches = 0
        try:
            for feed in batches:
                try:
                    outs = self.run_async(program, feed=feed,
                                          fetch_list=fetch_list, scope=scope)
                except GuardRewind as gr:
                    _rewind(gr)
                    continue
                except (ValueError, TypeError) as e:
                    if not flags.get_flag("feed_skip_corrupt"):
                        raise
                    # corrupt record: the batch died in ndarray conversion/
                    # dtype cast BEFORE dispatch (state untouched) — count
                    # it and keep the epoch alive
                    profiler.bump("feed.skip_corrupt")
                    # the print is load-bearing (tests grep stdout); the
                    # logger carries the structured copy
                    print(f"[executor] skipping corrupt batch "
                          f"(FLAGS_feed_skip_corrupt): {e}", flush=True)
                    logger.warning(
                        "skipping corrupt batch: %s", e,
                        extra={"corrupt_batch": {"batch": n_batches + 1,
                                                 "error": str(e)}})
                    continue
                n_batches += 1
                if n_batches == 1:
                    # the first batch carries the XLA compile: let it finish
                    # and start the throughput window AFTER it, so the
                    # reported batch/s measures steady state, not compilation
                    self.wait()
                    t0 = time.perf_counter()
                    continue
                if (debug or names) and n_batches % print_period == 0:
                    msg = ", ".join(
                        f"{lbl}: {np.asarray(o).reshape(-1)[:8]}"
                        for lbl, o in zip(labels, outs))
                    dt = time.perf_counter() - t0
                    rate = (n_batches - 1) / dt if dt > 0 else float("inf")
                    if rate != float("inf"):
                        obs.gauge_set("train.batches_per_sec", rate)
                    print(f"batch {n_batches} ({rate:.1f} batch/s) "
                          f"{msg}", flush=True)
                    logger.info(
                        "trainer progress batch=%d rate=%.1f", n_batches,
                        rate, extra={"trainer_progress": {
                            "batch": n_batches, "batches_per_sec": rate}})
        finally:
            # epoch boundary: drain the window so trained state is final
            # before the dataset's _finish_to_run hook (and so an exception
            # doesn't leave steps silently in flight). A bad step at the
            # epoch tail can still trip the guard here — same handling
            while True:
                try:
                    self.wait()
                    break
                except GuardRewind as gr:
                    _rewind(gr)

    def feed_placer(self, program=None):
        """Placement fn for the DeviceLoader prefetcher: cast host batches to
        their declared var dtypes (the same cast run() applies, so the
        compile-cache signature matches) and stage them into device memory.
        Once a compiled entry for this feed-name set exists, staged arrays
        carry its feed shardings; on a multi-process mesh the local shard is
        lifted to a global array via make_array_from_process_local_data."""
        from .compiler import CompiledProgram
        from .core.selected_rows import is_selected_rows

        mesh = None
        prog = program
        if isinstance(prog, CompiledProgram):
            mesh = prog._mesh
            prog = prog._program
        if prog is None:
            prog = default_main_program()
        block = prog.global_block
        multiproc = _spans_processes(mesh)

        emb_engine = getattr(prog, "_tiered_engine", None)
        if emb_engine is not None:
            from .embedding.engine import TICKET_KEY
        else:
            TICKET_KEY = None

        def place(feed: dict) -> dict:
            if emb_engine is not None and TICKET_KEY not in feed:
                # the async miss prefetch (ISSUE 10): resolve the batch's
                # unique-ID set against the host tier ON THIS background
                # thread, so the derived slot/prefetch feeds stage to the
                # device with the batch and the compiled step never touches
                # host memory
                feed = emb_engine.resolve_feed(feed)
            names = sorted(feed)
            comp = None
            cache = self._cache.get(prog)
            if cache:
                # compiled entries never see the ticket (popped pre-compile)
                sig_names = [n for n in names if n != TICKET_KEY]
                for c in reversed(list(cache.values())):
                    if list(c.feed_names) == sig_names:
                        comp = c
                        break
            out = {}
            for n in names:
                v = feed[n]
                if n == TICKET_KEY:
                    out[n] = v  # host-side ticket: never staged
                    continue
                if is_selected_rows(v):
                    out[n] = v
                    continue
                if not isinstance(v, jax.Array):
                    v = np.asarray(v)
                    try:
                        v = v.astype(block.var(n).np_feed_dtype, copy=False)
                    except KeyError:
                        pass
                sh = comp.feed_shardings.get(n) if (
                    comp is not None and comp.feed_shardings) else None
                with profiler.stage_timer("pipeline.device_put"):
                    if sh is not None:
                        out[n] = _to_global(v, sh) if multiproc \
                            else jax.device_put(v, sh)
                    elif mesh is None:
                        out[n] = v if isinstance(v, jax.Array) \
                            else jax.device_put(v)
                    else:
                        # mesh program before its first compile: leave the
                        # batch on host; run() places it and later batches
                        # get staged
                        out[n] = v
            return out

        return place

    def invalidate_cache(self, program=None):
        """Drop compiled executables for `program` (or all programs).

        Recovery hook for the resilience runner (resilience/runner.py): a
        poisoned cached executable — or donated-buffer bookkeeping left
        inconsistent by a step that died mid-run — recompiles from the
        Program IR on the next run instead of failing forever."""
        if program is None:
            self._cache = weakref.WeakKeyDictionary()
        else:
            from .compiler import CompiledProgram

            if isinstance(program, CompiledProgram):
                program = program._program
            self._cache.pop(program, None)

    def close(self):
        """Notify pservers this trainer is done (reference executor.cc:95
        SendComplete via exe.close())."""
        from .distributed.ps_rpc import PSClient

        for client in list(PSClient._instances.values()):
            client.send_complete()
            client.close()
        PSClient._instances.clear()

    # -- internals ----------------------------------------------------------
    def _fetch_state(self, scope: Scope, name: str):
        v = scope.find_var(name)
        if v is None:
            raise RuntimeError(
                f"Variable '{name}' has no value in scope — run the startup "
                "program first (reference: executor.cc:105 CreateVariables)."
            )
        return v

    def _compile(
        self, program, block, feed_names, feed_vals, fetch_names, scope, mesh, spmd_mode="gspmd"
    ):
        # fires only on a cache miss — exactly the boundary where an XLA
        # compile OOM / coordinator timeout would surface on a pod
        fault_point("executor.compile")
        ro_names, rw_names, extra_w = _analyze_block(block, feed_names, scope)

        if _has_host_ops(block):
            if mesh is not None:
                raise NotImplementedError(
                    "host ops (send/recv/listen_and_serv) cannot run under a "
                    "device mesh; the pserver path is host-RPC over DCN")
            fn = _SegmentedFn(block, feed_names, ro_names, rw_names, extra_w,
                              fetch_names)
            comp = _Compiled(fn, feed_names, ro_names, rw_names, fetch_names)
            comp.extra_w = extra_w
            return comp

        if mesh is not None and spmd_mode == "shard_map":
            # fleet/transpiler regime: bind mesh axes so c_* collective ops
            # emit real psum/all_gather (replaces the reference's per-device
            # graph replication + NCCL op handles)
            from jax.sharding import PartitionSpec as P

            from .parallel.mesh import get_comm_context

            ctx = get_comm_context()
            data_axis_name = mesh.axis_names[0]
            # explicitly-registered rings must name a real mesh axis (silent
            # fallback would reduce over the wrong group); unregistered rings
            # default to the mesh's first (data) axis
            axis_env = {}
            for ring in sorted(set(range(8)) | set(ctx.registered_rings())):
                if ring in ctx.registered_rings():
                    ax = ctx.axis_of(ring)
                    if ax not in mesh.axis_names:
                        raise ValueError(
                            f"collective ring {ring} is registered to mesh axis "
                            f"'{ax}', which is not in this mesh {mesh.axis_names}"
                        )
                    axis_env[ring] = ax
                else:
                    axis_env[ring] = data_axis_name
            for ax in mesh.axis_names:
                axis_env.setdefault(ax, ax)
            fn = _lower(
                block, feed_names, ro_names, rw_names, extra_w, fetch_names, axis_env=axis_env
            )
            data_axis = mesh.axis_names[0]

            def _feed_spec(n):
                try:
                    var = block.var(n)
                except KeyError:
                    return P(data_axis)
                # per-var annotations (annotate_sharding) win: sequence-
                # parallel feeds shard the SEQ dim, not the batch dim.
                # strict: an unknown axis must not silently replicate
                if getattr(var, "sharding", None) is not None:
                    from .parallel.sharding import annotation_spec

                    return annotation_spec(mesh, var, strict=True)
                rank = len(var.shape)
                if rank == 0:
                    return P()
                return P(*([data_axis] + [None] * (rank - 1)))

            in_specs = (
                tuple(_feed_spec(n) for n in feed_names),
                tuple(P() for _ in ro_names),
                tuple(P() for _ in rw_names),
                P(),
            )
            out_specs = (
                tuple(P() for _ in fetch_names),
                tuple(P() for _ in rw_names),
                tuple(P() for _ in extra_w),
                P(),  # async completion token
            )
            sfn = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=False)
            jfn = jax.jit(sfn, donate_argnums=(2,))
            comp = _Compiled(jfn, feed_names, ro_names, rw_names, fetch_names)
            comp.extra_w = extra_w
            from jax.sharding import NamedSharding

            comp.feed_shardings = {
                n: NamedSharding(mesh, _feed_spec(n)) for n in feed_names}
            state_sh = (tuple(NamedSharding(mesh, P()) for _ in ro_names),
                        tuple(NamedSharding(mesh, P()) for _ in rw_names))
            if _spans_processes(mesh):
                comp.global_shardings = (
                    tuple(comp.feed_shardings[n] for n in feed_names),
                    *state_sh)
            else:
                comp.state_shardings = state_sh
            return comp

        fn = _lower(block, feed_names, ro_names, rw_names, extra_w, fetch_names)
        jit_kwargs: dict = {"donate_argnums": (2,)}
        in_sh = None
        if mesh is not None:
            from .parallel.sharding import build_shardings

            in_sh, out_sh = build_shardings(
                mesh, block, feed_names, ro_names, rw_names, extra_w, fetch_names
            )
            jit_kwargs["in_shardings"] = in_sh
            jit_kwargs["out_shardings"] = out_sh
        jfn = jax.jit(fn, **jit_kwargs)
        comp = _Compiled(jfn, feed_names, ro_names, rw_names, fetch_names)
        comp.extra_w = extra_w
        if in_sh is not None:
            comp.feed_shardings = dict(zip(feed_names, in_sh[0]))
            if _spans_processes(mesh):
                comp.global_shardings = in_sh[:3]
            else:
                comp.state_shardings = in_sh[1:3]
        return comp
