"""Collective ops: the `c_*` family over ICI mesh axes.

TPU-native equivalents of /root/reference/paddle/fluid/operators/collective/
(c_allreduce_op.h:60 calls ncclAllReduce on ring `ring_id`; c_allgather,
c_reducescatter, c_broadcast, c_comm_init_all, c_sync_*_stream ops).

Two execution regimes (SURVEY.md §2.3):
  * GSPMD (default, `CompiledProgram.with_data_parallel`): XLA's partitioner
    inserts the gradient allreduce from shardings, so an explicit
    c_allreduce in the program must NOT reduce again — it lowers to identity.
  * shard_map (`CompiledProgram.with_collective`, the fleet/transpiler path):
    the executor binds mesh axes and sets the `__axis_env__` env key; here the
    ops emit real `lax.psum`/`all_gather`/`psum_scatter`/`ppermute` on the
    axis registered for their `ring_id` (mesh axes replace NCCL rings,
    reference collective_helper.h:50).

Sync ops are no-ops: XLA's dataflow replaces stream ordering
(c_sync_calc_stream / c_sync_comm_stream exist only for API parity).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import ExecContext, register_op

AXIS_ENV_KEY = "__axis_env__"  # env key: dict ring_id/axis info set by executor


def _axis(ctx: ExecContext):
    env = ctx.env.get(AXIS_ENV_KEY)
    if env is None:
        return None
    ring = ctx.attr("ring_id", 0)
    return env.get(ring, env.get(0))


def _axis_index(axis):
    return jax.lax.axis_index(axis)


def _allreduce(red):
    def compute(ctx: ExecContext):
        from ..core.selected_rows import is_selected_rows

        x = ctx.input("X")
        if is_selected_rows(x):
            # SelectedRows grads belong to the pserver path (sparse send);
            # psum would sum row INDICES across ranks — reject loudly instead
            raise TypeError(
                f"c_allreduce_{red}: SelectedRows gradients cannot ride a "
                "collective allreduce — use the parameter-server path "
                "(DistributeTranspiler) for is_sparse=True embeddings, or "
                "build the model with is_sparse=False for collective mode")
        axis = _axis(ctx)
        if axis is None:
            return {"Out": x}  # GSPMD regime: partitioner owns the reduction
        if red == "sum":
            out = jax.lax.psum(x, axis)
            if ctx.attr("avg", False):
                # fused mean-allreduce: the 1/nranks scale lives INSIDE the op
                # so it only applies when a real reduction happens (a separate
                # scale op would corrupt grads in the GSPMD identity regime)
                out = out / jax.lax.axis_size(axis)
            return {"Out": out}
        if red == "max":
            return {"Out": jax.lax.pmax(x, axis)}
        if red == "min":
            return {"Out": jax.lax.pmin(x, axis)}
        if red == "prod":
            # gather + prod: exp(psum(log)) NaNs on zero/negative elements
            return {"Out": jnp.prod(jax.lax.all_gather(x, axis), axis=0)}
        raise ValueError(red)

    return compute


register_op("c_allreduce_sum")(_allreduce("sum"))
register_op("c_allreduce_max", grad="none")(_allreduce("max"))
register_op("c_allreduce_min", grad="none")(_allreduce("min"))
register_op("c_allreduce_prod", grad="none")(_allreduce("prod"))
register_op("allreduce")(_allreduce("sum"))  # legacy dygraph DP op


@register_op("c_allreduce_coalesced", grad="none")
def c_allreduce_coalesced(ctx: ExecContext):
    """Bucketed mean-allreduce (the fuse_all_reduce_op_pass analogue, done
    in the program instead of the SSA graph): every gradient in the X list
    rides ONE flattened psum, so a bucket costs one collective launch and
    its reduce can overlap the backward compute that produces the NEXT
    bucket. Sum order per element is identical to the per-gradient
    c_allreduce_sum (psum over the same axis), so bucketing is bitwise
    payload-layout-invariant — the exactness contract the parity tests pin.
    Under GSPMD (no bound axis) it passes every input through untouched,
    matching c_allreduce_sum's identity regime."""
    from ..core.selected_rows import is_selected_rows

    xs = ctx.inputs("X")
    for x in xs:
        if is_selected_rows(x):
            raise TypeError(
                "c_allreduce_coalesced: SelectedRows gradients cannot ride "
                "a coalesced collective — use the parameter-server path for "
                "is_sparse=True embeddings, or build the model with "
                "is_sparse=False for collective mode")
    axis = _axis(ctx)
    if axis is None:
        return {"Out": list(xs)}
    # one VARIADIC psum: jax reduces the whole tuple in a single XLA
    # all-reduce (multi-operand), so the bucket pays one collective launch
    # with zero flatten/concat/split copies — per element the sum is the
    # same psum c_allreduce_sum emits, hence the bitwise parity contract
    red = jax.lax.psum(tuple(xs), axis)
    if ctx.attr("avg", False):
        n = jax.lax.axis_size(axis)
        red = tuple(r / n for r in red)
    return {"Out": list(red)}


@register_op("zero1_shard", grad="none")
def zero1_shard(ctx: ExecContext):
    """This rank's 1/nranks leading-dim slice of X (ZeRO-1 optimizer-state
    sharding, parallel/sharding.py): rank i of the ring's axis owns rows
    [i*k, (i+1)*k). Under GSPMD (no bound axis) it degrades to identity —
    the whole ZeRO-1 rewrite then collapses to the plain update, which is
    the correct single-program semantics there."""
    x = ctx.input("X")
    axis = _axis(ctx)
    if axis is None:
        return {"Out": x}
    n = jax.lax.axis_size(axis)
    k = x.shape[0] // n
    idx = _axis_index(axis)
    return {"Out": jax.lax.dynamic_slice_in_dim(x, idx * k, k, axis=0)}


@register_op("c_allgather")
def c_allgather(ctx: ExecContext):
    x = ctx.input("X")
    axis = _axis(ctx)
    if axis is None:
        return {"Out": x}
    return {"Out": jax.lax.all_gather(x, axis, axis=0, tiled=True)}


@register_op("c_reducescatter")
def c_reducescatter(ctx: ExecContext):
    from ..core.selected_rows import is_selected_rows

    x = ctx.input("X")
    if is_selected_rows(x):
        raise TypeError(
            "c_reducescatter: SelectedRows gradients cannot ride a "
            "reduce-scatter — use the parameter-server path for "
            "is_sparse=True embeddings (ZeRO-1 shards dense grads only)")
    axis = _axis(ctx)
    if axis is None:
        return {"Out": x}
    out = jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    if ctx.attr("avg", False):
        # fused mean like c_allreduce_sum's `avg`: the scale only applies
        # when a real reduction runs (identity in the GSPMD regime above)
        out = out / jax.lax.axis_size(axis)
    return {"Out": out}


@register_op("c_broadcast")
def c_broadcast(ctx: ExecContext):
    x = ctx.input("X")
    axis = _axis(ctx)
    if axis is None:
        return {"Out": x}
    root = ctx.attr("root", 0)
    # broadcast root's value: select root's shard on every member
    idx = jax.lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return {"Out": jax.lax.psum(masked, axis)}


@register_op("c_collective_permute")
def c_collective_permute(ctx: ExecContext):
    """Ring permute (TPU-first addition; backs ring attention / pipeline).
    attr `shift`: +1 sends to the next rank on the ring."""
    x = ctx.input("X")
    axis = _axis(ctx)
    if axis is None:
        return {"Out": x}
    n = jax.lax.axis_size(axis)
    shift = ctx.attr("shift", 1)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return {"Out": jax.lax.ppermute(x, axis, perm)}


@register_op("local_sgd_sync", grad="none")
def local_sgd_sync(ctx: ExecContext):
    """LocalSGD periodic sync, fused and branchless (reference
    transpiler/collective.py:269): every `k_steps` steps, allreduce-average the
    (param - snapshot) deltas and fold them back; other steps pass through.
    Inputs: Param, Snapshot, Step (int64 scalar, already incremented).
    Outputs: ParamOut, SnapshotOut."""
    p = ctx.input("Param")
    snap = ctx.input("Snapshot")
    step = ctx.input("Step")
    k = ctx.attr("k_steps", 1)
    axis = _axis(ctx)
    delta = p - snap
    if axis is not None:
        delta = jax.lax.psum(delta, axis) / jax.lax.axis_size(axis)
    synced = snap + delta
    do_sync = (step % k) == 0
    new_p = jnp.where(do_sync, synced, p)
    new_snap = jnp.where(do_sync, synced, snap)
    return {"ParamOut": new_p, "SnapshotOut": new_snap}


@register_op("c_sync_calc_stream", grad="none")
def c_sync_calc_stream(ctx: ExecContext):
    return {"Out": ctx.input("X")}


@register_op("c_sync_comm_stream", grad="none")
def c_sync_comm_stream(ctx: ExecContext):
    return {"Out": ctx.input("X")}


@register_op("c_comm_init_all", grad="none")
def c_comm_init_all(ctx: ExecContext):
    """NCCL-ring bootstrap has no TPU analogue (the mesh IS the communicator,
    reference c_comm_init_all_op.cc / gen_nccl_id RPC dance); no-op."""
    return {}


@register_op("c_gen_nccl_id", grad="none")
def c_gen_nccl_id(ctx: ExecContext):
    return {}
