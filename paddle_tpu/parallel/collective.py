"""Collective program transpilers: GradAllReduce / LocalSGD.

TPU-native re-design of /root/reference/python/paddle/fluid/transpiler/
collective.py (Collective:36, GradAllReduce:178, LocalSGD:269): same program
rewrite — find the grad vars produced by the backward pass, insert
mean-allreduce collectives between backward and optimizer ops — but the
inserted ops lower to mesh-axis psum under shard_map execution (or to
identity under GSPMD, where the partitioner already reduces).

Overlap (the multichip scaling campaign): instead of one `c_allreduce_sum`
per gradient parked before the optimizer ops (every reduce serializes after
the whole backward), GradAllReduce coalesces gradients into
reverse-topological BUCKETS of ~FLAGS_allreduce_bucket_mb megabytes and
inserts each bucket's `c_allreduce_coalesced` at the point where its last
member gradient is final (backward.grad_ready_index — below AMP unscale,
clip, and the guardrail sentinel), so a finished bucket's reduce overlaps
the backward compute still producing the next one (the reference's
fuse_all_reduce_op_pass + all_reduce_deps_pass, done in the program). The
bucket size is a per-(mesh, payload) schedule choice — under
FLAGS_tuning_mode it resolves through the PR 6 tuning DB
(`collective|mesh=..|payload=..` keys, swept by tools/_mc_ab.py) with the
flag as the analytic prior. With FLAGS_zero1, eligible gradients take the
ZeRO-1 reduce-scatter/shard-update/allgather path instead
(parallel/sharding.apply_zero1); the remainder still buckets here.
"""
from __future__ import annotations

import numpy as np

from ..framework import Program

__all__ = ["Collective", "GradAllReduce", "LocalSGD", "build_buckets",
           "resolve_bucket_mb"]

OPTIMIZER_OP_TYPES = {
    "sgd",
    "momentum",
    "lars_momentum",
    "adagrad",
    "adam",
    "adamax",
    "decayed_adagrad",
    "adadelta",
    "rmsprop",
    "ftrl",
    "lamb",
}


class Collective:
    def __init__(self, nrings: int = 1):
        self.nrings = nrings
        self.nranks = 1

    def transpile(self, startup_program: Program, main_program: Program, rank: int, endpoints=None, current_endpoint=None, wait_port=True, nranks: int | None = None):
        self.nranks = nranks if nranks is not None else (len(endpoints) if endpoints else 1)
        self.rank = rank
        self._transpile_main(main_program)
        self._transpile_startup(startup_program)

    def _transpile_startup(self, program: Program):
        pass  # mesh construction replaces comm-init ops (c_comm_init_all no-op)

    def _transpile_main(self, program: Program):
        raise NotImplementedError


def _grad_op_positions(block):
    """[(index, param_name, grad_name)] of optimizer ops' (param, grad)."""
    out = []
    for i, op in enumerate(block.ops):
        if op.type in OPTIMIZER_OP_TYPES:
            out.append((i, op.input("Param")[0], op.input("Grad")[0]))
    return out


def _grad_bytes(block, name: str) -> int:
    try:
        v = block.var(name)
    except KeyError:
        return 0
    shape = [abs(d) if d else 1 for d in v.shape] or [1]
    try:
        itemsize = np.dtype(v.np_dtype).itemsize
    except (TypeError, ValueError):
        itemsize = 4
    return int(np.prod(shape)) * itemsize


def resolve_bucket_mb(nranks: int, payload_bytes: int,
                      bucket_mb: float | None = None) -> tuple[float, str]:
    """Bucket size for this (mesh, payload), as (mb, provenance tier).

    Explicit `bucket_mb` (the transpiler/DistributedStrategy argument) wins
    outright. Otherwise under FLAGS_tuning_mode != off the decision routes
    through the three-tier tuner — `collective|mesh=..|payload=..` exact DB
    hit, else FLAGS_allreduce_bucket_mb as the analytic prior — so
    tools/_mc_ab.py sweeps land here; with tuning off the flag applies
    directly (pre-tuner behavior)."""
    from .. import flags

    if bucket_mb is not None:
        return float(bucket_mb), "explicit"
    flag_mb = float(flags.get_flag("allreduce_bucket_mb"))
    from .. import tuning
    from .mesh import axes_desc

    if tuning.mode() == "off":
        return flag_mb, "flag"
    key = tuning.canonical_key(
        "collective", tuning.collective_key(axes_desc(nranks), payload_bytes),
        "float32", tuning.device_kind())
    decision, tier = tuning.decide(
        "collective", key,
        prior=lambda: {"bucket_mb": flag_mb},
        default={"bucket_mb": flag_mb},
        validate=lambda d: "bucket_mb" in d)
    return float(decision.get("bucket_mb", flag_mb)), tier


def build_buckets(items, bucket_bytes: int):
    """Greedy reverse-topological bucketing: `items` is [(ready_index,
    grad_name, nbytes)] — grads in the order the backward FINISHES them
    (ascending last-writer index = descending layer depth, the DDP
    convention) — cut into consecutive groups of <= bucket_bytes (one
    oversized grad still gets its own bucket). bucket_bytes <= 0 degrades
    to one bucket per grad (the overlap-off arm)."""
    buckets: list[list] = []
    cur: list = []
    cur_bytes = 0
    for it in sorted(items, key=lambda t: (t[0], t[1])):
        if bucket_bytes <= 0:
            buckets.append([it])
            continue
        if cur and cur_bytes + it[2] > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(it)
        cur_bytes += it[2]
    if cur:
        buckets.append(cur)
    return buckets


class GradAllReduce(Collective):
    """Insert mean-allreduce on every gradient consumed by an optimizer op
    (reference transpiler/collective.py:208 inserts scale(1/nranks) +
    c_allreduce_sum; here the scale is fused INTO the op via the `avg` attr so
    it only applies when a real reduction runs — a standalone scale would
    shrink grads nranks-fold in the GSPMD regime where the allreduce lowers to
    identity).

    bucket_mb: gradient-bucket size in MB (None = resolve through the tuner /
    FLAGS_allreduce_bucket_mb; <= 0 = per-gradient reduces inserted before
    the optimizer ops, the overlap-off arm). zero1: route eligible params
    through ZeRO-1 sharding (None = FLAGS_zero1)."""

    def __init__(self, nrings: int = 1, bucket_mb: float | None = None,
                 zero1: bool | None = None):
        super().__init__(nrings)
        self.bucket_mb = bucket_mb
        self.zero1 = zero1
        # introspection for tests and tools: [(insert_pos, [grad names])] of the
        # last transpile, plus the resolved size and its provenance tier
        self.last_buckets: list[tuple[int, list[str]]] = []
        self.resolved_bucket_mb: float | None = None
        self.bucket_source: str = "none"
        self.zero1_params: list[str] = []

    def _transpile_main(self, program: Program):
        from .. import flags
        from ..backward import grad_ready_index

        block = program.global_block
        targets = _grad_op_positions(block)
        if not targets:
            return
        first_opt = targets[0][0]

        zero1 = (bool(flags.get_flag("zero1")) if self.zero1 is None
                 else bool(self.zero1))
        if zero1:
            from .sharding import _SHARD_SUFFIX, apply_zero1

            self.zero1_params = apply_zero1(program, self.nranks)
            # re-scan: zero1 rewrote its ops (Param/Grad now name shards) and
            # shifted indices; the shard-suffixed ops are already handled
            targets = [t for t in _grad_op_positions(block)
                       if not t[1].endswith(_SHARD_SUFFIX)]
            if not targets:
                self.last_buckets = []
                return
            first_opt = targets[0][0]

        items = []
        for _, _, g in targets:
            ready = grad_ready_index(block, g, first_opt)
            items.append((ready if ready >= 0 else first_opt - 1, g,
                          _grad_bytes(block, g)))
        payload = sum(b for _, _, b in items)
        self.last_payload_bytes = payload
        mb, tier = resolve_bucket_mb(self.nranks, payload, self.bucket_mb)
        self.resolved_bucket_mb, self.bucket_source = mb, tier
        buckets = build_buckets(items, int(mb * (1 << 20)))

        # per-bucket insert point: right after the bucket's LAST member is
        # final (overlap regime). bucket_mb <= 0 keeps the historical
        # placement — every per-grad reduce parked at the optimizer boundary,
        # i.e. serialized after the whole backward (the A/B baseline).
        inserts = []  # (position, [grad names])
        ring = 0
        for bucket in buckets:
            pos = (first_opt if mb <= 0
                   else max(r for r, _, _ in bucket) + 1)
            inserts.append((pos, [g for _, g, _ in bucket], ring))
            ring = (ring + 1) % self.nrings
        # insert bottom-up so earlier positions stay valid. Single-member
        # buckets keep the classic c_allreduce_sum spelling (same kernel,
        # and the fleet-regime assertions/tools that look for it still hold)
        self.last_buckets = []
        for pos, names, ring in sorted(inserts, key=lambda t: -t[0]):
            if len(names) == 1:
                block._insert_op(
                    pos, "c_allreduce_sum", {"X": names}, {"Out": names},
                    {"ring_id": ring, "avg": True})
            else:
                block._insert_op(
                    pos, "c_allreduce_coalesced", {"X": names},
                    {"Out": names}, {"ring_id": ring, "avg": True})
            self.last_buckets.append((pos, names))
        self.last_buckets.reverse()


class LocalSGD(Collective):
    """Per-step local updates + periodic param averaging (reference
    transpiler/collective.py:269): snapshot params, train K local steps, then
    allreduce (param - snapshot) deltas and re-apply."""

    def __init__(self, nrings: int = 1, k_steps: int = 1):
        super().__init__(nrings)
        self.k_steps = k_steps

    def _transpile_main(self, program: Program):
        block = program.global_block
        params = [p.name for p in program.all_parameters()]
        if not params:
            return
        # persistable step counter, incremented each run
        step_name = "@LOCAL_SGD_STEP@"
        block.create_var(name=step_name, shape=[], dtype="int64",
                         persistable=True, stop_gradient=True)
        block.append_op("increment", {"X": [step_name]}, {"Out": [step_name]},
                        {"step": 1.0})
        for p in params:
            snap = p + "@SNAPSHOT"
            pv = block.var(p)
            block.create_var(name=snap, shape=pv.shape, dtype=pv.dtype,
                             persistable=True, stop_gradient=True)
            block.append_op(
                "local_sgd_sync",
                {"Param": [p], "Snapshot": [snap], "Step": [step_name]},
                {"ParamOut": [p], "SnapshotOut": [snap]},
                {"k_steps": self.k_steps, "ring_id": 0},
            )

    def _transpile_startup(self, program: Program):
        block = program.global_block
        block.create_var(name="@LOCAL_SGD_STEP@", shape=[], dtype="int64",
                         persistable=True)
        block.append_op("fill_constant", {}, {"Out": ["@LOCAL_SGD_STEP@"]},
                        {"shape": [], "dtype": "int64", "value": 0.0})
        # snapshot starts equal to the freshly-initialized params
        for p in program.all_parameters():
            snap = p.name + "@SNAPSHOT"
            block.create_var(name=snap, shape=p.shape, dtype=p.dtype,
                             persistable=True)
            block.append_op("assign", {"X": [p.name]}, {"Out": [snap]}, {})
