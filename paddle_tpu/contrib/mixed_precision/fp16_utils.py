"""Program rewriting for mixed precision: insert casts around white/black ops.

Reference: /root/reference/python/paddle/fluid/contrib/mixed_precision/
fp16_utils.py (rewrite_program:139, _insert_cast_op:60). Same transformation,
bfloat16-first: white ops get their float32 inputs cast to the low dtype
(cast vars are reused per (name, dtype)), black ops get low-dtype inputs cast
back to float32. Parameters stay float32 in the scope — the in-program cast
IS the master-weight scheme: the optimizer updates fp32 params, the forward
consumes their low-precision view, and XLA fuses the cast into the consumer.
"""
from __future__ import annotations

from ...core.types import DType
from ...framework import Operator, Program

__all__ = ["rewrite_program", "cast_var_suffix"]

_LOW = {"bfloat16": "@BF16", "float16": "@FP16"}

# Input slots that alias persistable running state (the op's stateful
# outputs write back to the same vars). Harmonize-down must NEVER cast
# these: a bf16 EMA update `mean*0.9 + x*0.1` rounds away increments below
# ~0.4% of the running value, so the statistics quantize/stall over
# training, and the "fp32" stat vars would flip dtype in checkpoints.
# Also a white op's float32 side input that is no matmul operand: the
# router's combine weights, which the experts op applies in float32.
_STATE_SLOTS = {
    "moe_experts": {"Cw"},
    "batch_norm": {"Mean", "Variance"},
    "conv2d_bn": {"Mean", "Variance"},
    "fake_quantize_dequantize_moving_average_abs_max": {"InScale"},
}


def cast_var_suffix(dest_dtype: str) -> str:
    return _LOW.get(dest_dtype, "@LOW")


def _cast_input(block, op_idx, name, dest_dtype, cache):
    """Insert (or reuse) `cast(name) -> name@SUFFIX` before op_idx; returns
    the cast var name and how many ops were inserted (0 or 1)."""
    try:
        src = block.var(name)
    except KeyError:
        return name, 0
    if dest_dtype == "float32":
        if src.dtype not in (DType.BF16, DType.FP16):
            return name, 0
    elif src.dtype != DType.FP32:
        return name, 0  # only fp32 tensors get a low-precision view
    key = (name, dest_dtype)
    if key in cache:
        return cache[key], 0
    suffix = "@FP32" if dest_dtype == "float32" else cast_var_suffix(dest_dtype)
    cast_name = name + suffix
    if not block.has_var(cast_name):
        block.create_var(name=cast_name, shape=src.shape, dtype=dest_dtype,
                         stop_gradient=src.stop_gradient)
    block._insert_op(
        op_idx, "cast", {"X": [name]}, {"Out": [cast_name]},
        {"in_dtype": src.dtype.value, "out_dtype": dest_dtype},
    )
    cache[key] = cast_name
    return cast_name, 1


def rewrite_program(main_program: Program, amp_lists, dest_dtype="bfloat16"):
    """Walk every block's (forward) op list, casting white-op inputs to
    `dest_dtype` and black-op inputs back to float32. Returns the number of
    casts inserted. Must run BEFORE append_backward so grad ops derive
    through the casts. Control-flow sub-blocks are rewritten too — the FLOPs
    of an RNN/scan model live there."""
    n_casts = 0
    for block in main_program.blocks:
        n_casts += _rewrite_block(block, amp_lists, dest_dtype)
        _hoist_casts_through_layout(block)
    main_program._bump_version()
    return n_casts


# Dtype-transparent single-input ops that only move data. A down-cast
# sitting BELOW such an op is hoisted above it so the data movement happens
# at low precision: an fp32 2x2 space-to-depth repack of the 77 MB ResNet
# input measured +1.0 ms/step vs the same repack in bf16 (XLA does not sink
# converts through transposes on its own; /tmp probe, PERF.md r5).
_LAYOUT_OPS = {"reshape2", "transpose2", "squeeze2", "unsqueeze2",
               "flatten2", "space_to_depth", "depth_to_space",
               "pixel_shuffle", "shuffle_channel"}


def _hoist_casts_through_layout(block):
    from ...ops.registry import infer_op

    changed = True
    while changed:
        changed = False
        # producer index and consumer count per var name, current op order
        producer = {}
        consumers: dict = {}
        for idx, op in enumerate(block.ops):
            for n in op.input_names:
                consumers[n] = consumers.get(n, 0) + 1
            for n in op.output_names:
                producer[n] = idx
        for ci, op in enumerate(block.ops):
            if op.type != "cast":
                continue
            if op.attr("out_dtype") not in ("bfloat16", "float16"):
                continue
            (src,) = op.input("X")
            pi = producer.get(src)
            if pi is None:
                continue
            p = block.ops[pi]
            if p.type not in _LAYOUT_OPS or consumers.get(src, 0) != 1:
                continue
            (px,) = p.input("X")
            if not block.has_var(px) or block.var(px).dtype != DType.FP32:
                continue
            (dst,) = op.output("Out")
            # rewire: cast(px) ABOVE p; p consumes the cast and writes
            # directly into the cast op's output var; drop the old cast.
            # The hoisted cast var must be FRESH: px@BF16 may already exist
            # with its own producer (a white op elsewhere also consumes px),
            # and adding a second producer makes append_backward sum both
            # branches' cast_grads into px@GRAD — silently 1.5x gradients
            # (r5 code review, confirmed by repro).
            low = px + cast_var_suffix(op.attr("out_dtype")) + "@HOIST"
            n = 0
            while block.has_var(low + (f"{n}" if n else "")):
                n += 1
            low = low + (f"{n}" if n else "")
            src_var = block.var(px)
            block.create_var(name=low, shape=src_var.shape,
                             dtype=op.attr("out_dtype"),
                             stop_gradient=src_var.stop_gradient)
            del block.ops[ci]
            block._insert_op(pi, "cast", {"X": [px]}, {"Out": [low]},
                             {"in_dtype": "float32",
                              "out_dtype": op.attr("out_dtype")})
            p.inputs["X"] = [low]
            p.outputs["Out"] = [dst]
            infer_op(p, block)
            # keep the layout op's ORIGINAL fp32 output fetchable: a user
            # may fetch it by name even though no op consumes it. The
            # repair upcast is dead code unless fetched — XLA DCEs it.
            p_idx = block.ops.index(p)
            block._insert_op(p_idx + 1, "cast", {"X": [dst]},
                             {"Out": [src]},
                             {"in_dtype": op.attr("out_dtype"),
                              "out_dtype": "float32"})
            changed = True
            break


def _mixed_float_inputs(block, op) -> bool:
    """True when the op reads BOTH a low-precision and an fp32 float input —
    the case where jnp promotion would silently drag the activation back up."""
    seen = set()
    exempt = _STATE_SLOTS.get(op.type, ())
    for slot, names in op.inputs.items():
        if slot in exempt:
            continue
        for n in names:
            if not n or not block.has_var(n):
                continue
            dt = block.var(n).dtype
            if dt in (DType.FP32, DType.BF16, DType.FP16):
                seen.add(dt)
    return DType.FP32 in seen and (DType.BF16 in seen or DType.FP16 in seen)


def _rewrite_block(block, amp_lists, dest_dtype):
    from ...ops.registry import infer_op

    cache: dict = {}
    i = 0
    n_casts = 0
    while i < len(block.ops):
        op = block.ops[i]
        target = None
        if op.type in amp_lists.white_list:
            target = dest_dtype
        elif op.type in amp_lists.black_list:
            target = "float32"
        elif op.type != "cast" and _mixed_float_inputs(block, op):
            # gray/unlisted op mixing bf16 activations with fp32 side inputs
            # (bias add, residual add against an fp32 stream, LN gain/bias):
            # harmonize DOWN. Without this every such op promotes to fp32 and
            # the whole residual/FFN stream materializes at 2x width — the
            # single largest HBM cost found in the r2 perf audit (PERF.md).
            target = dest_dtype
        if target is None:
            # gray op: no casts, but RE-INFER its output dtype so bf16-ness
            # propagates through metadata — otherwise a black op downstream
            # of white->gray sees stale fp32 metadata and never casts back
            infer_op(op, block)
            _invalidate(cache, op)
            i += 1
            continue
        inserted_here = 0
        exempt = _STATE_SLOTS.get(op.type, ())
        for slot, names in list(op.inputs.items()):
            if slot in exempt:
                continue
            new_names = []
            for name in names:
                if not name:
                    new_names.append(name)
                    continue
                new_name, inserted = _cast_input(block, i, name, target, cache)
                new_names.append(new_name)
                inserted_here += inserted
                i += inserted
            op.inputs[slot] = new_names
        # re-infer this op's output dtype under the new input dtypes
        infer_op(op, block)
        _invalidate(cache, op)
        n_casts += inserted_here
        i += 1
    return n_casts


def _invalidate(cache: dict, op):
    """A redefined var's cached low-precision view is stale — drop it so the
    next consumer re-casts the NEW value."""
    for out in op.output_names:
        if not out:
            continue
        for key in [k for k in cache if k[0] == out]:
            del cache[key]
