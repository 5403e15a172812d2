"""Autodiff by program transformation: `append_backward`.

TPU-native re-design of /root/reference/python/paddle/fluid/backward.py
(append_backward:558, _addup_repetitive_outputs_:135, _find_op_path_:780).
The contract is identical — walk the forward op list in reverse, emit one grad
op per forward op (via each op's grad maker), sum repeated gradients, and
return (param, grad_var) pairs for the optimizer — but grad *kernels* are
derived from the forward JAX computes via vjp (see ops/registry.py), so this
file only orchestrates naming and topology, never math.
"""
from __future__ import annotations

from . import observability as obs
from .framework import Program, Variable, grad_var_name
from .ops.registry import default_grad_maker, get_op_def

__all__ = ["append_backward", "gradients", "grad_ready_index"]


def grad_ready_index(block, grad_name: str, before: int) -> int:
    """Index of the LAST op writing `grad_name` strictly below op `before`.

    This is the earliest program point where a gradient is final and may be
    bucketed onto a collective (parallel/collective.py): "last writer"
    rather than "grad-op producer" because AMP's unscale/check ops, clip,
    regularizers and the guardrail sentinel all rewrite gradients in place
    AFTER the raw grad op — a reduce inserted above any of them would ship
    a stale value. Returns -1 when nothing below `before` writes the name
    (the caller falls back to inserting at `before`)."""
    last = -1
    for i in range(min(before, len(block.ops))):
        if grad_name in block.ops[i].output_names:
            last = i
    return last


def _find_op_path(block, target_names) -> list[int]:
    """Indices of ops that (transitively) produce any target from data/params.

    Mirrors the reference's _find_op_path_ (backward.py:780): a backward sweep
    collecting ops whose outputs are needed.
    """
    needed = set(target_names) if not isinstance(target_names, str) else {target_names}
    path = []
    for i in range(len(block.ops) - 1, -1, -1):
        op = block.ops[i]
        if any(n in needed for n in op.output_names):
            path.append(i)
            needed.update(n for n in op.input_names if n)
    path.reverse()
    return path


@obs.spanned("setup.backward")
def append_backward(
    loss: Variable,
    parameter_list: list[str] | None = None,
    no_grad_set: set[str] | None = None,
    callbacks=None,
):
    """Append grad ops for `loss` to its program; return [(param, grad)] pairs.

    Reference: backward.py:558. Only single-block programs are differentiated
    in-line; control-flow sub-blocks differentiate through their op's vjp
    (the while/cond op kernels are themselves JAX-traceable).
    """
    program: Program = loss.block.program
    block = program.global_block
    no_grad = set(no_grad_set or ())
    for v in block.vars.values():
        if v.stop_gradient and not v.persistable:
            no_grad.add(v.name)

    op_path = _find_op_path(block, loss.name)

    # 1. seed: d loss / d loss = 1
    loss_grad = grad_var_name(loss.name)
    block.create_var(name=loss_grad, shape=loss.shape, dtype=loss.dtype)
    block.append_op(
        "fill_constant",
        outputs={"Out": [loss_grad]},
        attrs={"shape": list(loss.shape), "value": 1.0, "dtype": loss.dtype.value},
    )

    # 2. reverse sweep, with repeated-grad accumulation
    available_grads = _backward_sweep(block, op_path, {loss_grad}, no_grad)

    # 3. collect (param, grad) pairs
    if parameter_list is not None:
        params = [block.var(p) if isinstance(p, str) else p for p in parameter_list]
    else:
        params = [p for p in program.all_parameters() if getattr(p, "trainable", True)]
    result = []
    for p in params:
        g = grad_var_name(p.name)
        if g in available_grads:
            result.append((p, block.var(g)))
    return result


def _backward_sweep(block, op_path, seed_grads: set, no_grad: set) -> set:
    """Reverse sweep over `op_path` emitting grad ops; returns all grad var
    names made available. `seed_grads` are pre-seeded cotangent var names."""
    available_grads = set(seed_grads)
    pending_sum: dict[str, list[str]] = {}  # fwd var -> partial grad var names

    ops_snapshot = [block.ops[i] for i in op_path]
    for op in reversed(ops_snapshot):
        opdef = get_op_def(op.type) if _has(op.type) else None
        if not any(grad_var_name(n) in available_grads for n in op.output_names):
            # no grad flows into this op's outputs
            continue
        if opdef is None or opdef.no_grad:
            # forward-only op ON the gradient path: silently skipping would
            # freeze every upstream parameter with no diagnostic. Raise unless
            # the op has no differentiable inputs (pure sources like
            # fill_constant are harmless).
            if _has_differentiable_inputs(op, block, no_grad):
                raise RuntimeError(
                    f"op '{op.type}' lies on the gradient path"
                    f" but has no gradient (forward-only). Parameters upstream "
                    f"of it would silently stop training. Use a differentiable "
                    f"alternative (e.g. static_rnn instead of while), or mark "
                    f"its inputs stop_gradient=True if this is intended.")
            continue
        maker = opdef.grad_maker or default_grad_maker
        specs = maker(op, block, frozenset(no_grad))
        for spec in specs:
            # rename repeated-grad outputs: if a grad var was already produced
            # by another consumer — or appears twice within THIS spec (e.g.
            # elementwise_mul(x, x) emits X@GRAD and Y@GRAD for the same var) —
            # emit into a temp and sum (reference _addup_repetitive_outputs_
            # backward.py:135)
            outputs = {}
            renames = []
            local_seen: set[str] = set()
            for slot, names in spec["outputs"].items():
                new_names = []
                for n in names:
                    if n and (n in available_grads or n in local_seen):
                        tmp = n + "@RENAME@" + str(len(pending_sum.get(n, [])))
                        pending_sum.setdefault(n, [n]).append(tmp)
                        renames.append((n, tmp))
                        new_names.append(tmp)
                    else:
                        if n:
                            local_seen.add(n)
                        new_names.append(n)
                outputs[slot] = new_names
            attrs = dict(spec.get("attrs", {}))
            if op.attrs.get("op_namescope"):
                # a grad op belongs to the scope of the op it differentiates
                # (a custom grad maker may not copy the forward's attrs)
                attrs.setdefault("op_namescope", op.attrs["op_namescope"])
            block.append_op(spec["type"], spec["inputs"], outputs, attrs)
            for slot, names in outputs.items():
                for n in names:
                    if n:
                        available_grads.add(n)
            # fold pending sums immediately when a rename happened
            for orig, tmp in renames:
                parts = pending_sum[orig]
                if len(parts) >= 2:
                    block.append_op(
                        "sum",
                        inputs={"X": list(parts)},
                        outputs={"Out": [orig]},
                    )
                    pending_sum[orig] = [orig]
    return available_grads


def _has(t):
    try:
        get_op_def(t)
        return True
    except KeyError:
        return False


def _has_differentiable_inputs(op, block, no_grad: set) -> bool:
    from .core.types import is_floating

    for n in op.input_names:
        if not n or n in no_grad:
            continue
        try:
            v = block.var(n)
        except KeyError:
            continue
        if is_floating(v.dtype) and not v.stop_gradient:
            return True
    return False


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """Compute grads of targets w.r.t. inputs (reference backward.py:938
    calc_gradient): supports multiple targets and per-target seed cotangents.
    A missing/None target_gradient seeds with ones (matching the reference)."""
    tgts = list(targets) if isinstance(targets, (list, tuple)) else [targets]
    ins = list(inputs) if isinstance(inputs, (list, tuple)) else [inputs]
    tgs = (list(target_gradients)
           if isinstance(target_gradients, (list, tuple))
           else [target_gradients] * len(tgts))
    if len(tgs) != len(tgts):
        raise ValueError(
            f"target_gradients has {len(tgs)} entries for {len(tgts)} targets")

    program: Program = tgts[0].block.program
    block = program.global_block
    # asking for d(target)/d(input) implies the input is differentiable, even
    # for data vars (which default to stop_gradient=True); restored after the
    # sweep so later append_backward calls on this program are unaffected
    saved_sg = [(v, v.stop_gradient) for v in ins]
    for v in ins:
        v.stop_gradient = False
    try:
        return _calc_gradients(block, tgts, ins, tgs, no_grad_set)
    finally:
        for v, sg in saved_sg:
            v.stop_gradient = sg


def _calc_gradients(block, tgts, ins, tgs, no_grad_set):
    no_grad = set(no_grad_set or ())
    for v in block.vars.values():
        if v.stop_gradient and not v.persistable:
            no_grad.add(v.name)

    op_path = _find_op_path(block, {t.name for t in tgts})

    seeds = set()
    for t, tg in zip(tgts, tgs):
        g = grad_var_name(t.name)
        block.create_var(name=g, shape=t.shape, dtype=t.dtype)
        if tg is None:
            # fill_any_like handles batch-polymorphic (-1) target shapes
            block.append_op(
                "fill_any_like",
                inputs={"X": [t.name]},
                outputs={"Out": [g]},
                attrs={"value": 1.0},
            )
        else:
            if len(tg.shape) != len(t.shape) or any(
                td not in (-1, gd) and gd != -1
                for td, gd in zip(t.shape, tg.shape)
            ):
                raise ValueError(
                    f"target_gradient for '{t.name}' has shape "
                    f"{tuple(tg.shape)}, expected {tuple(t.shape)}")
            block.append_op("assign", {"X": [tg.name]}, {"Out": [g]}, {})
        seeds.add(g)

    available = _backward_sweep(block, op_path, seeds, no_grad)
    out = []
    for v in ins:
        g = grad_var_name(v.name)
        out.append(block.var(g) if g in available and block.has_var(g) else None)
    return out
