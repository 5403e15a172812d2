"""Hand-written Pallas TPU kernels (the compute-path native layer).

XLA fuses most of the framework's ops well; these kernels exist for the
cases where measurement (PERF.md) showed XLA leaving throughput on the
table. The package is organized as a small kernel WORKBENCH (module `workbench`):
shared block-shape/VMEM helpers and a
registry in which every kernel records its XLA reference, shape gate,
tuning-DB decision op, and equivalence test — `tools/gate.py
check_kernel_registry` fails the build on any kernel missing one, so no
unmeasured kernel can land silently. Each kernel module exposes a plain
jax-callable function with a custom VJP so the op registry's
derived-gradient machinery works through it, and dispatches through the
tuning layer (keep-or-retire per shape, degradation to the reference when
the platform cannot run the kernel).

The serving kernels are forward-only and are imported where they are
dispatched, not from here: `paged_attention` (a decode row's attention over
its pages of the K/V pools; `attention_ops.paged_decode_attention_fn`; its
grouped-query arm walks a flat list of page blocks in one grid step and
reads a run of pages that rows share once, by `paged_latent_attend`'s
`row_groups`),
`paged_indexer` (a decode row's lightning-indexer scores over its pages of
the key pool, where XLA gathered the pages and wrote the per-head scores;
`sparse_moe_ops.decode_scores_fn`, its shape gate the only switch),
`latent_attend` (the absorbed latent attention over each query's gathered
cache rows, unpacked in VMEM; `latent_moe_ops._attend_rows`, its shape gate
the only switch), `paged_latent_attend` (the same attention for the decode
rows of a configuration WITHOUT an indexer: all the pages of each row's
table, read in place from the latent pool, ONE grid step over a flat list
of page blocks; rows whose tables begin with the same pages attend that run
once, their heads stacked on the matrix unit's rows, an online softmax in
scratch resident for all rows; `latent_moe_ops._attend_pages`, its shape
gate the only switch and the page table the only word on what is shared),
`moe_experts` (the routed experts' stream), `ssm_update` (a decode
token's state update in place in its slot) and `conv_update` (the same
token's causal convolution, its tail moved on in place in its slot of the
pool of tails `[rows, tail_width / 128, 128]`, where XLA's scatter passed
over the whole pool; `parallel_ssm_ops.conv_token_update_fn`, its shape
gate the only switch) and `kda_update` (a decode token of a Kimi-Delta
linear-attention layer: decay by key channel and the delta rule's rank-one
write, in place in the same slot pool; `kda_ops.kda_token_update_fn`, its
shape gate the only switch).
"""
from . import workbench
from .attention import short_seq_attention, short_seq_supported
from .epilogue import (bn_apply_act, bn_apply_act_reference,
                       epilogue_supported, layer_norm_act,
                       layer_norm_act_reference)
from .short_attention import short128_attention, short128_supported
from .workbench import all_kernels, register_kernel

__all__ = [
    "workbench", "all_kernels", "register_kernel",
    "short_seq_attention", "short_seq_supported",
    "short128_attention", "short128_supported",
    "bn_apply_act", "bn_apply_act_reference", "epilogue_supported",
    "layer_norm_act", "layer_norm_act_reference",
]
