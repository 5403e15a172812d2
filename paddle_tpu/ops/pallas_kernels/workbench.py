"""Kernel-workbench substrate: the conventions every Pallas kernel shares.

A small reusable custom-kernel layer ("Tensor Processing Primitives",
arXiv:2104.05755) rather than a pile of one-off files: this module is that
layer's spine — the pieces attention.py / paged_attention.py each
re-invented privately, factored once:

  * block-shape helpers — `pick_block` (largest divisor under a VMEM
    budget, sublane-friendly), `fit_heads` (the attention head-block rule),
    and the lane/sublane constants, so kernels size their slabs against the
    same ~16 MB VMEM model instead of private magic numbers.
  * the kernel REGISTRY — `register_kernel` records, for every kernel the
    workbench ships, its jax-callable entry point, the XLA reference that
    defines its numerics, the `supported` shape gate the dispatcher must
    consult, the tuning-DB op kind its decisions key under, and the name of
    its equivalence test. `tools/gate.py check_kernel_registry` (and the
    tier-1 lint test) fail the build when any kernel is missing one of
    those — an unmeasured or unreferenced kernel cannot land silently,
    which is the TVM-flavored keep-or-retire contract (arXiv:1802.04799)
    made structural.

Every kernel module keeps its own `INTERPRET` flag (tests flip it to run
the kernel through the Pallas interpreter on CPU); `runnable` centralizes
the "TPU or interpreter" dispatch gate.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax

LANES = 128
# per-step VMEM slab budget (bytes): leaves room for double buffering and
# fp32 score/stat scratch inside the ~16 MB of VMEM per core
VMEM_BUDGET = 3 * 1024 * 1024


def sublanes(dtype) -> int:
    """Min sublane tile for a dtype (fp32 8, bf16 16, int8/fp8 32)."""
    import jax.numpy as jnp

    size = jnp.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(size, 8)


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pick_block(extent: int, row_bytes: int,
               budget: int = VMEM_BUDGET, prefer_multiple: int = 8) -> int:
    """Largest divisor of `extent` whose slab (divisor * row_bytes) fits
    `budget`, preferring sublane multiples. Divisor-only so grids never
    overrun the array edge — kernels with per-block reductions must not see
    padding garbage rows. Degrades to 1 (always a divisor)."""
    cap = max(1, budget // max(1, row_bytes))
    divisors = [c for c in range(1, min(extent, cap) + 1) if extent % c == 0]
    preferred = [c for c in divisors if c % prefer_multiple == 0]
    return (preferred or divisors)[-1]


def fit_heads(nh: int, per_head_bytes: int,
              budget: int = VMEM_BUDGET) -> int:
    """Largest divisor of nh whose per-step slab fits the budget — the
    attention head-block rule (attention.py) shared with any kernel that
    batches a head-like dim through the MXU."""
    gh = nh
    while gh > 1 and gh * per_head_bytes > budget:
        gh -= 1
        while nh % gh:
            gh -= 1
    return max(1, gh)


def on_tpu() -> bool:
    # a backend that fails to initialise raises here and stops the run: it
    # must not quietly turn every kernel off
    return jax.default_backend() == "tpu"


def runnable(module) -> bool:
    """The dispatch gate every kernel shares: a Pallas kernel runs on a TPU
    backend or under the module's interpreter flag, nowhere else."""
    return on_tpu() or bool(getattr(module, "INTERPRET", False))


# ---------------------------------------------------------------------------
# Kernel registry — the lint surface tools/gate.py checks
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class KernelSpec:
    """One workbench kernel's accountability record.

    name            — registry key (stable; PERF.md verdicts cite it)
    fn              — the jax-callable kernel entry point
    reference       — the XLA composition defining the kernel's numerics
                      (equivalence tests pin fn against it)
    supported       — shape gate callable; dispatchers must consult it and
                      fall back to `reference` when it rejects
    decision_op     — tuning-DB op kind the kernel's keep/retire verdicts
                      key under ("attention", "epilogue", ...); every
                      kernel MUST resolve through tuning.decide so a swept
                      verdict can keep or retire it per shape
    equivalence_test— name of the tier-1 test function pinning fn ==
                      reference (gate.py greps tests/ for its definition)
    default_on      — False (the r5 rule): a kernel ships off until a
                      swept DB verdict keeps it. True only for kernels that
                      already earned an end-to-end keep (bundled dispatch
                      rules replay the measured PERF.md split).
    """

    name: str
    fn: Callable
    reference: Callable
    supported: Callable
    decision_op: str
    equivalence_test: str
    default_on: bool = False
    note: str = ""


_KERNELS: dict[str, KernelSpec] = {}


def register_kernel(name: str, *, reference, supported, decision_op,
                    equivalence_test, default_on=False, note=""):
    """Decorator registering a kernel entry point with its full
    accountability record (see KernelSpec). gate.py's registry lint fails
    on any kernel whose record is incomplete."""

    def deco(fn):
        _KERNELS[name] = KernelSpec(
            name=name, fn=fn, reference=reference, supported=supported,
            decision_op=decision_op, equivalence_test=equivalence_test,
            default_on=default_on, note=note)
        return fn

    return deco


def all_kernels() -> dict[str, KernelSpec]:
    """Every registered kernel (import side effect: pulls in the kernel
    modules so their registrations run)."""
    from . import (attention, conv_update, epilogue,  # noqa: F401
                   kda_update, latent_attend, moe_experts, paged_attention,
                   paged_indexer, paged_latent_attend, short_attention,
                   ssm_update)

    return dict(_KERNELS)
