"""Declarative Program IR: Variable / Operator / Block / Program.

This is the TPU-native re-design of the reference's graph-construction core
(/root/reference/python/paddle/fluid/framework.py: Variable:383, Operator:1034,
Block:1483, Program:2826) and its C++/proto IR
(/root/reference/paddle/fluid/framework/framework.proto).

Key contract kept from the reference:
  * A Program is a list of Blocks; a Block is an ordered list of Operators over
    named Variables; parameters are persistable Variables in block 0.
  * Layers append Operators; autodiff (`append_backward`) and distributed
    transpilers are *program transformations* that append/rewrite ops.
  * `program_guard` switches the default main/startup programs.

Key TPU-first departures:
  * No protobuf / no C++ OpDesc mirror: ops and vars are light Python objects
    serializable to JSON (`Program.to_dict`). The executor lowers a whole block
    to one XLA computation via JAX tracing, so there is no per-op C++ runtime
    descriptor to keep in sync.
  * No LoD: variable-length data is handled by padding/bucketing + segment ids
    (XLA requires static shapes); `Variable.shape` may use -1 only for the
    leading (batch) dim, which becomes a distinct compile-cache entry per
    concrete shape.
  * Each Variable may carry a `sharding` annotation (a tuple of mesh-axis names
    or None per dim) consumed by the GSPMD lowering in executor/compiler —
    this replaces the reference's multi-device SSA graph replication
    (/root/reference/paddle/fluid/framework/ir/multi_devices_graph_pass/).
"""
from __future__ import annotations

import contextlib
import copy
from typing import Any, Sequence

import numpy as np

from . import unique_name
from .core.types import DType, VarKind, np_dtype, np_feed_dtype

__all__ = [
    "Variable",
    "Parameter",
    "Operator",
    "Block",
    "Program",
    "default_main_program",
    "default_startup_program",
    "program_guard",
    "grad_var_name",
    "name_scope",
]

GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


_PKG_DIR = __file__.rsplit("/", 1)[0] + "/"


def _capture_callstack():
    """Trimmed user-code creation stack for one Operator (reference
    framework/op_call_stack.cc attaches this to runtime errors). Frames inside
    paddle_tpu itself are dropped so the stack points at the line of *user*
    code that built the op; capped at 8 frames. Disable via
    FLAGS_op_callstack=0 (costs ~10us/op at build time)."""
    from . import flags

    if not flags.get_flag("op_callstack"):
        return None
    import traceback

    frames = []
    for f, ln, fn, txt in traceback.extract_stack()[:-2]:
        if f.startswith(_PKG_DIR):
            continue
        frames.append((f, ln, fn, txt))
    return frames[-8:]


_name_scope_stack: list[str] = []


class Variable:
    """A named, typed, statically-shaped value in a Block.

    Reference: framework.py:383. A Variable is pure metadata — the runtime
    value lives in a Scope (executor.py) keyed by name.
    """

    def __init__(
        self,
        block: "Block",
        name: str | None = None,
        shape: Sequence[int] | None = None,
        dtype="float32",
        kind: VarKind = VarKind.DENSE_TENSOR,
        persistable: bool = False,
        stop_gradient: bool = False,
        is_data: bool = False,
        initializer=None,
        sharding: tuple | None = None,
    ):
        self.block = block
        self.name = name if name is not None else unique_name.generate("_generated_var")
        self.shape = tuple(int(s) for s in shape) if shape is not None else ()
        self.dtype = DType.parse(dtype)
        self.kind = kind
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.initializer = initializer
        self.sharding = sharding  # per-dim mesh axis names (GSPMD annotation)
        self.op: "Operator | None" = None  # op that (last) writes this var

    # -- introspection ------------------------------------------------------
    @property
    def np_dtype(self):
        return np_dtype(self.dtype)

    @property
    def np_feed_dtype(self):
        """Dtype FEED arrays cast to: int64/float64 declarations narrow to
        their 32-bit runtime forms when jax x64 is off (core.types
        .np_feed_dtype) — the explicit form of the truncation device_put
        would apply anyway, minus jax's per-astype warning."""
        return np_feed_dtype(self.dtype)

    @property
    def ndim(self):
        return len(self.shape)

    def to_dict(self):
        d = {
            "name": self.name,
            "shape": list(self.shape),
            "dtype": self.dtype.value,
            "kind": self.kind.value,
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "is_data": self.is_data,
            "sharding": list(self.sharding) if self.sharding else None,
            "is_parameter": isinstance(self, Parameter),
        }
        if getattr(self, "is_opt_state", False):
            d["is_opt_state"] = True  # ZeRO tag must survive serialization
        return d

    def __repr__(self):
        return (
            f"Var({self.name}: {self.dtype.value}{list(self.shape)}"
            + (", persistable" if self.persistable else "")
            + ")"
        )

    # -- operator sugar (builds ops in the var's block) ---------------------
    def _binary(self, other, op):
        from .layers import nn as _nn  # lazy, avoids cycle

        return _nn._elementwise_binary(op, self, other)

    def __add__(self, other):
        return self._binary(other, "elementwise_add")

    def __radd__(self, other):
        return self._binary(other, "elementwise_add")

    def __sub__(self, other):
        return self._binary(other, "elementwise_sub")

    def __mul__(self, other):
        return self._binary(other, "elementwise_mul")

    def __rmul__(self, other):
        return self._binary(other, "elementwise_mul")

    def __truediv__(self, other):
        return self._binary(other, "elementwise_div")

    def __rsub__(self, other):
        from .layers import nn as _nn

        return _nn._elementwise_binary("elementwise_sub", other, self)

    def __rtruediv__(self, other):
        from .layers import nn as _nn

        return _nn._elementwise_binary("elementwise_div", other, self)

    def __neg__(self):
        from .layers import nn as _nn

        return _nn.scale(self, scale=-1.0)


class Parameter(Variable):
    """A trainable persistable Variable (reference framework.py:3651)."""

    def __init__(self, block, shape, dtype, **kwargs):
        self.trainable = kwargs.pop("trainable", True)
        self.regularizer = kwargs.pop("regularizer", None)
        self.gradient_clip_attr = kwargs.pop("gradient_clip_attr", None)
        self.do_model_average = kwargs.pop("do_model_average", None)
        self.optimize_attr = kwargs.pop("optimize_attr", {"learning_rate": 1.0})
        super().__init__(
            block, shape=shape, dtype=dtype, persistable=True, **kwargs
        )

    def __repr__(self):
        return f"Param({self.name}: {self.dtype.value}{list(self.shape)})"


class Operator:
    """One op invocation: type + named input/output slots + attrs.

    Reference: framework.py:1034 / framework.proto OpDesc:43. Inputs/outputs
    map slot name -> list of variable names. Attrs are JSON-serializable
    values; a `sub_block` attr holds a Block index (control flow).
    """

    def __init__(
        self,
        block: "Block",
        type: str,
        inputs: dict[str, list[str]] | None = None,
        outputs: dict[str, list[str]] | None = None,
        attrs: dict[str, Any] | None = None,
    ):
        self.block = block
        self.type = type
        self.inputs = {k: list(v) for k, v in (inputs or {}).items()}
        self.outputs = {k: list(v) for k, v in (outputs or {}).items()}
        self.attrs = dict(attrs or {})
        # diagnostics (filled by Block.append_op — NOT here, so clone() and
        # from_dict() don't overwrite stacks or pick up foreign name scopes):
        # Python creation stack (reference op_call_stack.cc) + recorded
        # shape-inference failure, attached to later runtime errors
        self._callstack: list | None = None
        self._infer_error: str | None = None

    def callstack_str(self) -> str:
        """Render the creation stack (user frames) for error messages."""
        if not self._callstack:
            return "  <op creation stack not captured; FLAGS_op_callstack=0>"
        return "".join(
            f"  File \"{f}\", line {ln}, in {fn}\n    {txt}\n"
            for f, ln, fn, txt in self._callstack
        ).rstrip("\n")

    def input(self, slot: str) -> list[str]:
        return self.inputs.get(slot, [])

    def output(self, slot: str) -> list[str]:
        return self.outputs.get(slot, [])

    @property
    def input_names(self) -> list[str]:
        return [n for ns in self.inputs.values() for n in ns]

    @property
    def output_names(self) -> list[str]:
        return [n for ns in self.outputs.values() for n in ns]

    def attr(self, name: str, default=None):
        return self.attrs.get(name, default)

    def to_dict(self):
        def _clean(v):
            if isinstance(v, (np.integer,)):
                return int(v)
            if isinstance(v, (np.floating,)):
                return float(v)
            if isinstance(v, (list, tuple)):
                return [_clean(x) for x in v]
            return v

        return {
            "type": self.type,
            "inputs": self.inputs,
            "outputs": self.outputs,
            "attrs": {k: _clean(v) for k, v in self.attrs.items()},
        }

    def __repr__(self):
        ins = {k: v for k, v in self.inputs.items()}
        outs = {k: v for k, v in self.outputs.items()}
        return f"Op({self.type}, in={ins}, out={outs})"


class OpError(RuntimeError):
    """An op failed to lower/execute; carries the op's Python creation stack
    (the reference's EnforceNotMet + op_call_stack.cc attribution)."""

    def __init__(self, op: "Operator", cause: BaseException):
        self.op = op
        self.cause = cause
        scope = op.attrs.get("op_namescope")
        parts = [
            f"Operator '{op.type}'" + (f" (scope {scope})" if scope else "")
            + f" failed: {type(cause).__name__}: {cause}",
            f"  op: {op!r}",
        ]
        if op._infer_error is not None:
            parts.append(
                f"  note: shape inference had already failed at build time "
                f"with: {op._infer_error}")
        parts.append("Op creation stack (most recent call last):")
        parts.append(op.callstack_str())
        super().__init__("\n".join(parts))


class Block:
    """Ordered op list + var table (reference framework.py:1483)."""

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: dict[str, Variable] = {}
        self.ops: list[Operator] = []

    @property
    def parent_block(self) -> "Block | None":
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    # -- var management -----------------------------------------------------
    def create_var(self, **kwargs) -> Variable:
        name = kwargs.get("name")
        if name is not None and name in self.vars:
            return self.vars[name]
        v = Variable(self, **kwargs)
        self.vars[v.name] = v
        return v

    def create_parameter(self, shape, dtype, **kwargs) -> Parameter:
        # parameters always live in the top block (reference block.py semantics)
        top = self.program.blocks[0]
        p = Parameter(top, shape, dtype, **kwargs)
        top.vars[p.name] = p
        return p

    def var(self, name: str) -> Variable:
        """Find a var here or in ancestor blocks (scope-chain lookup)."""
        b: Block | None = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent_block
        raise KeyError(f"Variable '{name}' not found in block {self.idx}")

    def has_var(self, name: str) -> bool:
        try:
            self.var(name)
            return True
        except KeyError:
            return False

    def all_parameters(self) -> list[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # -- op management ------------------------------------------------------
    def append_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        op._callstack = _capture_callstack()
        if _name_scope_stack:
            op.attrs.setdefault("op_namescope", "/".join(_name_scope_stack))
        self.ops.append(op)
        for name in op.output_names:
            if name in self.vars:
                self.vars[name].op = op
        self.program._bump_version()
        # eager shape/dtype inference so layers can chain immediately
        from .ops.registry import infer_op  # lazy import

        infer_op(op, self)
        return op

    def _insert_op(self, index: int, type: str, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        op._callstack = _capture_callstack()
        if _name_scope_stack:
            op.attrs.setdefault("op_namescope", "/".join(_name_scope_stack))
        self.ops.insert(index, op)
        self.program._bump_version()
        from .ops.registry import infer_op

        infer_op(op, self)
        return op

    def prepend_op(self, type: str, inputs=None, outputs=None, attrs=None):
        return self._insert_op(0, type, inputs, outputs, attrs)

    def to_dict(self):
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "vars": {n: v.to_dict() for n, v in self.vars.items()},
            "ops": [op.to_dict() for op in self.ops],
        }


class Program:
    """A whole trainable/serializable program (reference framework.py:2826).

    `name` (optional; observability/schema.PROGRAM_NAMES lists the tree's
    own) is what the executor calls the program's compiled entries: a
    device trace's `XLA Modules` line reads `jit_<name>`."""

    name: str | None = None

    def __init__(self):
        self.blocks: list[Block] = [Block(self, 0)]
        self._current_block_idx = 0
        self.random_seed = 0
        self._version = 0  # bumped on mutation; part of the executor compile key
        self._lr_schedulers = []  # populated by learning_rate_scheduler layers
        # persistable var -> [(metric, labels, index)]: vectors of one
        # step's counts that ops write and the executor hands to the
        # registry as device handles (layers.device_counter)
        self.device_counters: dict = {}

    # -- block management ---------------------------------------------------
    @property
    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self._current_block_idx]

    def _create_block(self, parent_idx: int | None = None) -> Block:
        parent = self._current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self._current_block_idx = b.idx
        return b

    def _rollback(self):
        self._current_block_idx = self.current_block().parent_idx

    def _bump_version(self):
        self._version += 1

    # -- queries ------------------------------------------------------------
    def all_parameters(self) -> list[Parameter]:
        return self.global_block.all_parameters()

    def list_vars(self):
        for blk in self.blocks:
            yield from blk.vars.values()

    # -- clone / serialization ---------------------------------------------
    def clone(self, for_test: bool = False) -> "Program":
        """Deep-copy the program. With for_test=True, flip training-only attrs
        (is_test) the way the reference's clone(for_test=True) does."""
        p = Program.__new__(Program)
        p.name = self.name
        p.blocks = []
        p._current_block_idx = self._current_block_idx
        p.random_seed = self.random_seed
        p._version = 0
        p._lr_schedulers = list(self._lr_schedulers)
        p.device_counters = dict(self.device_counters)
        for blk in self.blocks:
            nb = Block(p, blk.idx, blk.parent_idx)
            p.blocks.append(nb)
        for blk, nb in zip(self.blocks, p.blocks):
            for name, v in blk.vars.items():
                nv = copy.copy(v)
                nv.block = nb
                nb.vars[name] = nv
            for op in blk.ops:
                nop = Operator(nb, op.type, op.inputs, op.outputs, copy.deepcopy(op.attrs))
                nop._callstack = op._callstack  # keep original creation site
                nop._infer_error = op._infer_error
                if for_test and "is_test" in nop.attrs:
                    nop.attrs["is_test"] = True
                if for_test and nop.type == "dropout":
                    nop.attrs["is_test"] = True
                nb.ops.append(nop)
        return p

    def to_dict(self):
        return {
            "version": 1,
            "random_seed": self.random_seed,
            "blocks": [b.to_dict() for b in self.blocks],
        }

    @staticmethod
    def from_dict(d: dict) -> "Program":
        p = Program.__new__(Program)
        p.blocks = []
        p._current_block_idx = 0
        p.random_seed = d.get("random_seed", 0)
        p._version = 0
        p._lr_schedulers = []
        p.device_counters = {}      # not serialised: a loaded program counts none
        for bd in d["blocks"]:
            b = Block(p, bd["idx"], bd["parent_idx"])
            p.blocks.append(b)
        for bd, b in zip(d["blocks"], p.blocks):
            for name, vd in bd["vars"].items():
                common = dict(
                    name=vd["name"],
                    kind=VarKind(vd["kind"]),
                    stop_gradient=vd["stop_gradient"],
                    is_data=vd.get("is_data", False),
                    sharding=tuple(vd["sharding"]) if vd.get("sharding") else None,
                )
                if vd.get("is_parameter"):
                    v = Parameter(b, vd["shape"], vd["dtype"], **common)
                else:
                    v = Variable(b, shape=vd["shape"], dtype=vd["dtype"],
                                 persistable=vd["persistable"], **common)
                if vd.get("is_opt_state"):
                    v.is_opt_state = True
                b.vars[name] = v
            for od in bd["ops"]:
                b.ops.append(Operator(b, od["type"], od["inputs"], od["outputs"], od["attrs"]))
        return p

    def __repr__(self):
        n_ops = sum(len(b.ops) for b in self.blocks)
        return f"Program(blocks={len(self.blocks)}, ops={n_ops}, version={self._version})"


# -- default program machinery (reference framework.py:3790+) ---------------
_main_program = Program()
_startup_program = Program()


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


def switch_main_program(program: Program) -> Program:
    global _main_program
    old = _main_program
    _main_program = program
    return old


def switch_startup_program(program: Program) -> Program:
    global _startup_program
    old = _startup_program
    _startup_program = program
    return old


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Program | None = None):
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)


@contextlib.contextmanager
def name_scope(prefix: str):
    """Debug/profiling name scoping (reference framework.py name_scope): ops
    appended inside carry an `op_namescope` attr ("outer/inner"), visible in
    serialized programs and error messages. It must NOT reset the unique-name
    counters, or re-entering the same scope would collide parameter names."""
    _name_scope_stack.append(str(prefix))
    try:
        yield
    finally:
        _name_scope_stack.pop()
