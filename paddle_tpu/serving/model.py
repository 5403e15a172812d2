"""Serving program builders. NINE block families exist (the sixth in two
forms: behind an indexer, or read whole under several residual streams), and
`DecoderConfig.block` selects one:

  * `"post_ln"` (the default; every other field at its default is the "bert
    decoder": BERT-base geometry, causal masking): post-LN, multi-head
    attention, GELU FFN, learned positions (`max_position` rows). Fields:
    vocab_size, hidden_size, num_layers, num_heads, ffn_size, max_position,
    dtype.
  * `"cca_moe"` (ZAYA1's layer, `ops/cca_moe_ops.py`): RMSNorm,
    compressed-convolutional grouped-query attention in a latent with
    carried convolution state, partial rotary, a top-1 mixture of SwiGLU
    experts behind an MLP router, embedding tied to the head. It reads, on
    top of the fields above (`ffn_size` is one expert's width,
    `max_position` only the context cap): num_kv_heads, attn_head_dim,
    num_experts, router_hidden_size, cca_time0/1, partial_rotary_factor,
    rope_theta, rms_norm_eps. Its layers are ONE op scanned over weights
    stacked `[L, ...]` and its pools are stacked too (`kv_cache.
    STACKED_POOLS`, with one state row a page).
  * `"sparse_moe"` (`ops/sparse_moe_ops.py`): RMSNorm pre-norm,
    grouped-query attention with per-head q/k norms and full rotary that
    reads only the `index_topk` cached positions a learned indexer scores
    highest (a few small query heads over one cached key head, kept in a
    per-token pool, `kv_cache.INDEX_POOL`, beside ONE pool of joined K/V
    rows, `kv_cache.JOINED_POOL`), a renormalised top-k
    mixture of SwiGLU experts behind a linear router, an untied head. It
    reads num_kv_heads, attn_head_dim, num_experts, experts_per_token,
    index_heads, index_head_dim, index_topk, prefill_chunk, rope_theta,
    rms_norm_eps. Scanned and stacked like "cca_moe". A prompt runs as
    consecutive windows of `prefill_chunk` tokens through the window
    program (its prefill program is that window at start 0), and every
    program also returns what each layer's attention was given
    (`selection`: a window's mask in packed words, a decode row's
    gathered positions).
  * `"hybrid_moe"` (`ops/hybrid_moe_ops.py`): layers of MORE THAN ONE
    SHAPE, laid out by a plan read from the per-layer lists `layer_types`
    ("full_attention" | "sliding_attention"), `mlp_layer_types` ("dense" |
    "sparse") and `heads_per_layer` (`layer_plan`): full-attention layers
    (`num_heads` query heads, partial rotary under YaRN) and
    sliding-window layers (their own head count, the last
    `sliding_window` positions, plain rotary) over the same KV heads, a
    sigmoid gate a head on the attention's output; a dense SwiGLU
    (`dense_ffn_size`) or the top-k of a sigmoid-scored mixture of experts
    (`ffn_size` wide, their sum times `routed_scaling`) beside one shared
    expert (`shared_expert_size`). Weights are stacked by layer KIND and
    the layers run one after another. The sliding layers keep their K/V in
    a second pair of stacked pools (`kv_cache.WINDOW_POOLS`, page ids and a
    compact page table of their own: feeds `sv_wpages`, `sv_wbase`), the
    full layers in the first. Prompts run in `prefill_chunk`-token windows
    as "sparse_moe"'s do.
  * `"parallel_ssm"` (`ops/parallel_ssm_ops.py`; Falcon-H1's layer): ONE
    pre-norm, then a Mamba-2 state-space mixer (`ssm_heads` heads of
    `ssm_head_dim` over `ssm_groups` groups of `ssm_state`, a depthwise
    convolution `ssm_conv` wide, scanned in chunks of `ssm_chunk`) and a
    grouped-query attention with full rotary side by side on the same
    normed input, their outputs summed into the residual, then a pre-norm
    and a SwiGLU (`ffn_size`); an untied head; the config's muP
    multipliers (`*_multiplier`, `mlp_multipliers` = (gate, down),
    `ssm_multipliers` over z | x | B | C | dt). Scanned and stacked like
    "cca_moe". Besides K/V a sequence carries a RECURRENT STATE that every
    token rewrites in place: it lives in pools of slots, not pages
    (`kv_cache.STATE_POOLS`; a row's slot is the feed `sv_sslot`), and
    `build_state_copy_program` copies one slot onto another (a snapshot
    the prefix cache keeps, a restore from one). Prompts run in
    `prefill_chunk`-token windows as "sparse_moe"'s do.
  * `"latent_moe"` (`ops/latent_moe_ops.py`; DeepSeek-V3.2's layer):
    multi-head LATENT attention, whose cache row is a token's normalised
    latent (`kv_lora_rank` values) and its one rotary key (`rope_head_dim`)
    as words of ONE pool (`kv_cache.LATENT_POOL`), read through the
    "sparse_moe" indexer (its queries taken from the query latent,
    `q_lora_rank`; its keys in `kv_cache.INDEX_POOL`) in two forms over the
    same weights: expanded per-head K/V where a window's whole table fits
    the selection, absorbed projections (every head reading the same
    gathered rows) for decode rows and windows behind a longer context;
    rotary under YaRN with the softmax scale times `softmax_mscale`^2;
    `dense_layers` leading SwiGLU layers (`dense_ffn_size`), then layers
    that route each token to `experts_per_token` of `num_experts`
    sigmoid-scored experts chosen inside `groups_per_token` of
    `expert_groups` groups, beside one shared expert. `experts_held` says
    how many of the experts THIS engine holds (the first ones: one chip's
    share of a deployment that divides them); the router keeps all its
    outputs and the layer computes its own experts' part. Weights stacked
    by layer kind, the routed layers scanned. Prompts run in
    `prefill_chunk`-token windows and every program hands back its
    `selection`, as "sparse_moe"'s do.
    TWO THINGS THE CONFIGURATION MAY CHANGE (Xing4.0's layer has both).
    WITHOUT AN INDEXER (`index_heads`, `index_head_dim`, `index_topk` all
    0): every query attends every cached row at or before it, a window in
    the expanded form over key blocks, a decode row in the absorbed form
    over ALL its pages, read in place and a run of pages that rows share
    once for all of them (`pallas_kernels.paged_latent_attend`);
    no `kv_cache.INDEX_POOL` is allocated, shared, copied on write or
    audited, the indexer's five parameters do not exist and no `selection`
    is handed back (`DecoderConfig.selects` is False, `.latent` True).
    WITH `hc_mult` > 1 RESIDUAL STREAMS: the residual is `hc_mult` float32
    streams, mixed around every sub-layer (two a layer) by
    manifold-constrained hyper-connections (`ops/hyper_connection_ops.py`:
    `hc_sinkhorn_iters`, `hc_eps`, `hc_res_clamp`); the embedding is copied
    into the streams and the final norm reads their sum.
  * `"mixer_moe"` (`ops/mixer_moe_ops.py`; Nemotron-H's layers with latent
    experts): every layer is ONE sub-layer behind ONE pre-norm, its kind the
    layer's character in `layer_pattern`: `M` a Mamba-2 mixer
    ("parallel_ssm"'s, every multiplier 1; its heads may be narrower than
    the 128 lanes, `ssm_head_dim` 64 over `ssm_state` 128: two of them then
    share a slot's lane rows), `*` a grouped-query attention with no rotary,
    `E` the top `experts_per_token` of `num_experts` sigmoid-scored experts
    (one group, a selection bias, `routed_scaling`) that are two matrices
    and a squared ReLU each in a latent of `latent_size` (`ffn_size` wide),
    between a projection down from and up to the hidden size, beside a
    shared expert (`shared_expert_size`) on the hidden itself;
    `experts_held` as "latent_moe"'s. Weights are stacked by layer KIND and
    the layers run one after another. The three pools answer by the COUNT
    OF THEIR KIND: K/V pages over the `*` layers, slots of recurrent state
    (`kv_cache.STATE_POOLS`, `build_state_copy_program`) over the `M`
    layers, a request's routes over the `E` layers. Prompts run in
    `prefill_chunk`-token windows as "sparse_moe"'s do.
  * `"kda_moe"` (`ops/kda_ops.py`; a hybrid of linear and latent attention,
    `bailing_hybrid`'s layers): TWO sub-layers a layer, each behind a
    pre-norm, and TWO kinds of cache for one sequence. The mixer of layer
    `l` is a multi-head latent attention where `(l + 1) % layer_group_size
    == 0` ("latent_moe"'s WITHOUT a query latent, `q_lora_rank` 0, and
    without an indexer, a sigmoid gate a head on its output; its cache row
    in `kv_cache.LATENT_POOL`, stacked over these layers only) and Kimi
    Delta Attention elsewhere: `ssm_heads` heads whose state is a matrix
    `[ssm_state, ssm_head_dim]` (keys x values) that every token decays a
    KEY CHANNEL at a time (log decay in `(kda_lower_bound, 0)`) and writes
    by the delta rule, behind one causal convolution `ssm_conv` wide over q
    | k | v; a window runs it in chunks of `ssm_chunk` tokens and
    sub-blocks of `kda_sub_chunk`, a decode step one token in place in the
    slot (`kv_cache.STATE_POOLS` over these layers only,
    `build_state_copy_program`). The MLP is a dense SwiGLU
    (`dense_ffn_size`) in the first `dense_layers` layers and "latent_moe"'s
    group-limited experts with `experts_held` behind them. `recurrent` AND
    `latent` are both true: a row holds a slot and pages, and a prefix hit
    needs the pages and a snapshot at the same boundary. Weights are
    stacked by layer KIND and the layers run one after another. Prompts run
    in `prefill_chunk`-token windows as "sparse_moe"'s do.
  * `"looped_dense"` (`ops/looped_dense_ops.py`; a looped language model's
    layers): a plain pre-norm rotary multi-head SwiGLU stack whose every
    sub-layer stands between TWO RMSNorms (before it and on its output),
    and whose `num_layers` layers a token passes `loop_steps` TIMES, the
    final norm closing every visit. The weights are stacked `[num_layers,
    ...]` and stored once; visit `t` of layer `l` attends what the same
    visit of the same layer wrote, so the stacked K/V pools hold
    `loop_steps * num_layers` planes (`cfg.cache_planes`; plane `t *
    num_layers + l`) and a page is that many slabs deep: copy-on-write,
    the prefix cache's sharing and the audit answer for all of them
    through the one page id. A gate reads the normed state after every
    visit; the programs hand back the probability of leaving after each
    (`exit_mass`, summing to 1) beside the logits and branch on nothing.
    It reads num_kv_heads, attn_head_dim, rope_theta, rms_norm_eps,
    loop_steps, prefill_chunk. One `lax.scan` over the visits around one
    over the layers. Prompts run in `prefill_chunk`-token windows, each
    window through every visit before the next, as "sparse_moe"'s do.

Every family is expressed several times over ONE weight namespace:

  * `build_prefill_program` — whole-prompt forward (dense causal attention:
    with bucket padding on the right, every query position attends only to
    real tokens, so no pad bias is needed) that ALSO scatters each layer's
    K/V into the paged pool in-graph (`kv_cache_prefill_write`) and emits
    the greedy next token of the last real position. One XLA compile per
    prompt-length bucket (the PR 2 shape-bucketing convention).
  * `build_decode_program` — one ragged decode step: single query token per
    request row, `kv_cache_append` writes its K/V into the row's current
    page slot, `paged_decode_attention` attends over the row's page table,
    argmax emits the next token. One compile per (batch-bucket,
    page-count-bucket); padded rows ride the `batch_mask` row-mask
    convention from PR 2.
  * `build_full_forward_program` — the dense oracle (no cache, all-position
    logits) the equivalence tests replay generation against.

Every parameter name is explicit (no unique_name counters), so the three
programs resolve the SAME scope entries — prefill trains nothing, decode
reads what prefill's startup initialized (or what a checkpoint restored).
"""
from __future__ import annotations

import collections
from collections.abc import Callable
from dataclasses import dataclass
from types import MappingProxyType

import jax.numpy as jnp

from .. import layers as L
from ..framework import default_main_program
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from ..initializer import (BlockedNormal, Constant, Normal, StackedNormal,
                           Uniform)
from ..ops import (cca_moe_ops, hybrid_moe_ops, kda_ops, latent_moe_ops,
                   looped_dense_ops, mixer_moe_ops, parallel_ssm_ops,
                   sparse_moe_ops)
from .kv_cache import (INDEX_POOL, JOINED_POOL, LATENT_POOL, STACKED_POOLS,
                       STATE_POOLS, WINDOW_POOLS, declare_pool_vars,
                       declare_stacked_pools, declare_state_pools,
                       pool_var_names)

__all__ = ["DecoderConfig", "decoder_tiny", "cca_moe_tiny",
           "sparse_moe_tiny", "hybrid_moe_tiny", "parallel_ssm_tiny",
           "latent_moe_tiny", "latent_streams_tiny", "mixer_moe_tiny",
           "kda_moe_tiny", "looped_dense_tiny", "layer_plan",
           "build_prefill_program",
           "build_decode_program", "build_window_program",
           "build_state_copy_program",
           "build_full_forward_program", "apply_tp_annotations"]

# feed names shared by the engine and the programs
TOK_FEED = "sv_tok"
POS_FEED = "sv_pos"
PAGES_FEED = "sv_pages"
LEN_FEED = "sv_len"
START_FEED = "sv_start"   # first global slot of a prefill/verify window
MASK_FEED = "batch_mask"  # the PR 2 row-mask convention (data_feeder)
COW_SRC_FEED = "sv_cow_src"  # copy-on-write: source page id
COW_DST_FEED = "sv_cow_dst"  # copy-on-write: destination page id
MARK_FEED = "sv_mark"     # "sparse_moe" decode: rows whose selection is kept
# "hybrid_moe": a row's compact page table in the sliding layers' pool, and
# the global position of that table's slot 0 (a multiple of the page size)
WPAGES_FEED = "sv_wpages"
WBASE_FEED = "sv_wbase"
COW_WSRC_FEED = "sv_cow_wsrc"   # copy-on-write in the sliding layers' pool
COW_WDST_FEED = "sv_cow_wdst"
# "parallel_ssm", "mixer_moe": each row's slot in the pools of recurrent
# state (padding rows name a scratch slot nobody reads; only a window's
# padding writes it), and the state copy's two slots
SSLOT_FEED = "sv_sslot"
SCOPY_SRC_FEED = "sv_scopy_src"
SCOPY_DST_FEED = "sv_scopy_dst"
# how many rows of a decode step can have their selection handed back
MARK_ROWS = 8
# the token each row slot's latest step emitted, kept on the device: the one
# value a decode step needs of the step before it. Every step program writes
# the tokens it emits to its rows' slots (`sv_slot` [B]); a decode row takes
# its input token from its slot where `sv_from_host` [B, 1] is 0, so the
# host dispatches step n+1 before it has read step n. int32 [slots + 1]:
# the last entry is where padding rows write and nobody reads.
LAST_TOKEN = "serving.last_token"
SLOT_FEED = "sv_slot"
FROM_HOST_FEED = "sv_from_host"


@dataclass
class DecoderConfig:
    """Geometry of the served decoder (BERT-base shaped by default)."""

    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_size: int = 3072
    max_position: int = 512
    dtype: str = "float32"
    # which block family (see the module docstring); the fields below are
    # read by "cca_moe" and "sparse_moe" only
    block: str = "post_ln"
    num_kv_heads: int = 0          # 0: as many as num_heads
    attn_head_dim: int = 0         # 0: hidden_size // num_heads
    num_experts: int = 0
    router_hidden_size: int = 0
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 1.0
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    # "sparse_moe" only
    experts_per_token: int = 1
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    prefill_chunk: int = 0         # 0: a prompt is one prefill program
    # "hybrid_moe" only: the per-layer lists the layer plan is read from
    # (`num_heads` is a full layer's head count; `rope_theta` and
    # `partial_rotary_factor` are a full layer's rotary, `yarn` its scaling:
    # (factor, original context, beta_fast, beta_slow, attention factor))
    layer_types: tuple = ()
    mlp_layer_types: tuple = ()
    heads_per_layer: tuple = ()
    sliding_window: int = 0
    sliding_rope_theta: float = 10000.0
    sliding_rotary_factor: float = 1.0
    yarn: tuple = ()
    shared_expert_size: int = 0
    dense_ffn_size: int = 0
    routed_scaling: float = 1.0
    # "parallel_ssm" only: the state-space mixer's sizes and the config's
    # muP multipliers (`mlp_multipliers` = (gate, down); `ssm_multipliers`
    # over the columns z | x | B | C | dt of the mixer's input projection)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    mlp_multipliers: tuple = (1.0, 1.0)
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    # "latent_moe" only (it also reads the indexer's fields, `yarn`,
    # `shared_expert_size`, `dense_ffn_size` and `routed_scaling` above;
    # `attn_head_dim` is a head's width WITHOUT rotary)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    rope_head_dim: int = 0
    v_head_dim: int = 0
    softmax_mscale: float = 1.0    # scores x this squared
    dense_layers: int = 0          # leading layers with a dense SwiGLU
    expert_groups: int = 1
    groups_per_token: int = 1
    experts_held: int = 0          # 0: all of num_experts
    # "latent_moe": the residual path. `hc_mult` streams (1: the plain `x +
    # f(norm(x))`), mixed around every sub-layer by manifold-constrained
    # hyper-connections (`ops/hyper_connection_ops.py`): Sinkhorn
    # iterations, the epsilon of the flat RMSNorm and of both
    # normalisations, and the clip of the residual mapping's logits
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    # "mixer_moe" only (it also reads the `ssm_*` sizes, `num_kv_heads`,
    # `attn_head_dim`, `num_experts`, `experts_per_token`, `experts_held`,
    # `routed_scaling`, `shared_expert_size`; `ffn_size` is one expert's
    # width IN the latent): a character a layer, `M` mixer | `*` attention |
    # `E` experts, and the width of the experts' latent
    layer_pattern: str = ""
    latent_size: int = 0
    # "kda_moe" only (it also reads `ssm_heads`, `ssm_head_dim` (a head's
    # values), `ssm_state` (its key channels), `ssm_conv`, `ssm_chunk`, the
    # latent attention's `kv_lora_rank`, `rope_head_dim`, `v_head_dim` and
    # `attn_head_dim`, and "latent_moe"'s feed-forward fields): every
    # `layer_group_size`-th layer is latent attention, the others Kimi Delta
    # Attention; the chunked form's sub-block and the least log decay a
    # token
    layer_group_size: int = 0
    kda_sub_chunk: int = 16
    kda_lower_bound: float = -5.0
    # "looped_dense" only (it also reads `num_kv_heads`, `attn_head_dim`,
    # `rope_theta`, `rms_norm_eps`, `prefill_chunk`): how many times a token
    # passes the `num_layers` layers, each visit with K/V pages of its own
    loop_steps: int = 1
    # a deployment's choice, any family: the fewest rows a decode step is
    # compiled for (a power of two). Steps of fewer live rows pay for that
    # many; every row bucket below it is a program less to compile
    min_row_bucket: int = 1
    # a deployment's choice, any family: the most waiting requests ONE loop
    # iteration admits (and prefills) ahead of its decode step; 0: all that
    # fit. Where prompts cost more of the device than the rows' steps, an
    # iteration that admits all its waiters leaves the rows one step in
    # hundreds of milliseconds, and every pause of the host is made up out
    # of their steps alone; under a cap the queue carries it
    admit_per_step: int = 0

    def __post_init__(self):
        if self.block not in FAMILIES:
            raise ValueError(f"unknown DecoderConfig.block {self.block!r} "
                             f"({' | '.join(FAMILIES)})")
        for name in ("layer_types", "mlp_layer_types", "heads_per_layer",
                     "yarn", "mlp_multipliers", "ssm_multipliers",
                     "hc_res_clamp"):
            setattr(self, name, tuple(getattr(self, name)))
        if self.min_row_bucket < 1 \
                or self.min_row_bucket & (self.min_row_bucket - 1):
            raise ValueError("min_row_bucket must be a power of two")
        if self.admit_per_step < 0:
            raise ValueError("admit_per_step must be 0 (no cap) or more")
        self.family.validate(self)

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def family(self) -> "Family":
        """The row of `FAMILIES` this configuration's `block` names: every
        answer below that depends on the family is read from it."""
        return FAMILIES[self.block]

    @property
    def stateful(self) -> bool:
        """Whether a sequence carries state besides its K/V (one row a page
        in the state pool)."""
        return self.family.stateful

    @property
    def scanned(self) -> bool:
        """Whether the layers are one scanned op over stacked weights and
        stacked pools (`kv_cache.STACKED_POOLS`). Speculation, tensor
        parallelism and the fleet handoff are not written for that form."""
        return bool(self.family.op)

    @property
    def cache_planes(self) -> int:
        """Slabs of K/V a page id names in the stacked pools: one a layer,
        or, where a token passes the layers `loop_steps` times
        ("looped_dense"), one a VISIT of a layer."""
        return self.family.cache_planes(self)

    @property
    def recurrent(self) -> bool:
        """Whether a sequence carries a state that every token rewrites in
        place (a slot of `kv_cache.STATE_POOLS`, not a row a page): the
        prefix cache resumes it from snapshots only."""
        return self.family.second_pool == "state_slots"

    @property
    def state_layers(self) -> int:
        """Layers that hold a recurrent state (the layers of
        `kv_cache.STATE_POOLS`): every layer of "parallel_ssm", the mixers
        of "mixer_moe", the Kimi-Delta layers of "kda_moe"."""
        return self.family.state_layers(self)

    @property
    def mixer_kinds(self) -> str:
        """"kda_moe": a character a layer, `L` latent attention where `(l +
        1) % layer_group_size == 0`, `K` Kimi Delta Attention elsewhere."""
        return "".join(
            kda_ops.LATENT if (l + 1) % self.layer_group_size == 0
            else kda_ops.KDA for l in range(self.num_layers))

    @property
    def mlp_kinds(self) -> str:
        """"kda_moe": a character a layer, `D` a dense SwiGLU in the first
        `dense_layers` layers, `E` the experts behind them."""
        return kda_ops.DENSE * self.dense_layers \
            + kda_ops.EXPERTS * (self.num_layers - self.dense_layers)

    @property
    def windowed(self) -> bool:
        """Whether some layers attend a sliding window only and keep their
        K/V in a second pool under page ids of their own."""
        return self.family.second_pool == "window_pages"

    @property
    def routed_layers(self) -> int:
        """Layers that route tokens to experts (the middle axis of a
        request's `routes`)."""
        return self.family.routed_layers(self)

    @property
    def held_experts(self) -> int:
        """Experts this engine holds weights for (the first ones of
        `num_experts`): all of them unless `experts_held` says fewer."""
        return self.experts_held or self.num_experts

    @property
    def selects(self) -> bool:
        """Whether attention reads a learned selection of the cache: an
        indexer scores every slot of a row's page table, a pool of its
        own holds its keys, a token's K and V (or its one latent row) are
        one row of one pool, and every step reports what it attended.
        "latent_moe" without an indexer (`index_topk` 0) attends every
        cached row and has none of these."""
        return self.family.selects(self)

    @property
    def latent(self) -> bool:
        """Whether a token's cache row is ONE compressed row (a latent and
        its rotary key in `kv_cache.LATENT_POOL`) that every head reads."""
        return any(pool == LATENT_POOL for _, pool in self.family.pools)

    @property
    def latent_layers(self) -> int:
        """Layers whose cache is such a row (the layers of
        `kv_cache.LATENT_POOL`): every layer of "latent_moe", every
        `layer_group_size`-th of "kda_moe"."""
        return self.family.latent_layers(self)

    def selects_within(self, slots: int) -> bool:
        """Whether a decode step over a page table of `slots` slots runs
        the indexer (a table that fits the selection is attended whole)."""
        return self.selects and slots > self.index_topk

    @property
    def page_bucket_step(self) -> int:
        """0: page tables round up to a power of two. n: past n pages
        they round to a multiple of n, for a family whose every step scans
        its whole table (the dead part stays under an eighth); the rows of
        `FAMILIES` say why each has the step it has."""
        return self.family.page_bucket_step

    @property
    def one_page_bucket(self) -> bool:
        """Whether every step is compiled at ONE page-table width, that of
        `max_position`; the rows of `FAMILIES` say why."""
        return self.family.one_page_bucket


def decoder_tiny() -> DecoderConfig:
    return DecoderConfig(vocab_size=97, hidden_size=32, num_layers=2,
                         num_heads=2, ffn_size=64, max_position=64)


def cca_moe_tiny(**over) -> DecoderConfig:
    """The "cca_moe" block at test size: 4 query heads over 2 KV heads of
    8, 4 experts of width 32."""
    kw = dict(vocab_size=97, hidden_size=32, num_layers=3, num_heads=4,
              num_kv_heads=2, attn_head_dim=8, ffn_size=32, num_experts=4,
              router_hidden_size=16, partial_rotary_factor=0.5,
              rope_theta=5e6, max_position=64, block="cca_moe")
    kw.update(over)
    return DecoderConfig(**kw)


def sparse_moe_tiny(**over) -> DecoderConfig:
    """The "sparse_moe" block at test size: 4 query heads over 2 KV heads
    of 8, an indexer of 2 heads of 8 keeping 8 positions, 8 experts of
    width 32 and 2 a token, prompts in chunks of 16."""
    kw = dict(vocab_size=97, hidden_size=32, num_layers=3, num_heads=4,
              num_kv_heads=2, attn_head_dim=8, ffn_size=32, num_experts=8,
              experts_per_token=2, index_heads=2, index_head_dim=8,
              index_topk=8, prefill_chunk=16, rope_theta=1e7,
              rms_norm_eps=1e-6, max_position=128, block="sparse_moe")
    kw.update(over)
    return DecoderConfig(**kw)


def hybrid_moe_tiny(**over) -> DecoderConfig:
    """The "hybrid_moe" block at test size: a dense layer and two periods
    of (full, sliding x 3), 4 and 6 query heads over 2 KV heads of 8, a
    window of 8, 8 experts of width 16 and 2 a token beside a shared one,
    prompts in chunks of 8."""
    kw = dict(vocab_size=97, hidden_size=32, num_layers=9, num_heads=4,
              num_kv_heads=2, attn_head_dim=8, ffn_size=16, num_experts=8,
              experts_per_token=2, shared_expert_size=16, dense_ffn_size=64,
              routed_scaling=2.5, sliding_window=8, prefill_chunk=8,
              layer_types=("full_attention",) + 2 * (
                  ("sliding_attention",) * 3 + ("full_attention",)),
              mlp_layer_types=("dense",) + ("sparse",) * 8,
              heads_per_layer=(4,) + 2 * ((6,) * 3 + (4,)),
              partial_rotary_factor=0.5, rope_theta=5e5,
              yarn=(64.0, 16, 64.0, 1.0, 1.4158883083359672),
              rms_norm_eps=1e-6, max_position=128, block="hybrid_moe")
    kw.update(over)
    return DecoderConfig(**kw)


def latent_moe_tiny(**over) -> DecoderConfig:
    """The "latent_moe" block at test size: 4 heads of 8 + 4 rotary lanes
    over a latent of 16 (values of 8), queries from a latent of 24, an
    indexer of 2 heads of 8 keeping 8 positions, a dense layer then two
    routed ones: 2 of 16 experts a token inside 2 of 4 groups, beside a
    shared expert, of which this engine holds the first 8; prompts in
    chunks of 16."""
    kw = dict(vocab_size=97, hidden_size=32, num_layers=3, num_heads=4,
              attn_head_dim=8, rope_head_dim=4, v_head_dim=8,
              q_lora_rank=24, kv_lora_rank=16, index_heads=2,
              index_head_dim=8, index_topk=8, prefill_chunk=16,
              dense_layers=1, dense_ffn_size=64, ffn_size=16,
              shared_expert_size=16, num_experts=16, experts_held=8,
              experts_per_token=2, expert_groups=4, groups_per_token=2,
              routed_scaling=2.5, rope_theta=1e4,
              yarn=(40.0, 16, 32.0, 1.0, 1.0),
              softmax_mscale=latent_moe_ops.yarn_mscale(40.0, 1.0),
              rms_norm_eps=1e-6, max_position=128, block="latent_moe")
    kw.update(over)
    return DecoderConfig(**kw)


def latent_streams_tiny(**over) -> DecoderConfig:
    """The "latent_moe" block WITHOUT an indexer and with four residual
    streams at test size: `latent_moe_tiny`'s attention read whole, two
    dense layers then two routed ones (2 of 8 experts a token in one group,
    all held), the residual logits clipped at +-1 so that the clip cuts."""
    kw = dict(index_heads=0, index_head_dim=0, index_topk=0, num_layers=4,
              dense_layers=2, num_experts=8, experts_held=0,
              expert_groups=1, groups_per_token=1, hc_mult=4,
              hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp=(-1.0, 1.0))
    kw.update(over)
    return latent_moe_tiny(**kw)


def parallel_ssm_tiny(**over) -> DecoderConfig:
    """The "parallel_ssm" block at test size: 4 query heads over 2 KV heads
    of 8 beside 4 state-space heads of 8 in 2 groups of state 16, a
    convolution 4 wide, scan chunks of 4, prompts in chunks of 8; every
    multiplier another value than 1."""
    kw = dict(vocab_size=97, hidden_size=32, num_layers=3, num_heads=4,
              num_kv_heads=2, attn_head_dim=8, ffn_size=64, ssm_heads=4,
              ssm_head_dim=8, ssm_groups=2, ssm_state=16, ssm_conv=4,
              ssm_chunk=4, prefill_chunk=8, rope_theta=1e6,
              embedding_multiplier=1.5, lm_head_multiplier=0.75,
              ssm_in_multiplier=0.8, ssm_out_multiplier=1.25,
              attention_in_multiplier=0.9, attention_out_multiplier=1.2,
              key_multiplier=0.7, mlp_multipliers=(0.85, 1.1),
              ssm_multipliers=(0.9, 1.2, 0.8, 1.1, 0.7),
              max_position=128, block="parallel_ssm")
    kw.update(over)
    return DecoderConfig(**kw)


def mixer_moe_tiny(**over) -> DecoderConfig:
    """The "mixer_moe" block at test size: the pattern MEM*EME (3 mixers, 3
    expert layers, 1 attention); 4 state-space heads of 8 in 2 groups of
    state 16 (heads narrower than the state: two share a slot's rows), a
    convolution 4 wide, scan chunks of 4; 4 query heads over 2 KV heads of
    8; 3 of 8 experts a token, 16 wide in a latent of 16, of which this
    engine holds the first 4, beside a shared expert of 24; prompts in
    chunks of 8."""
    kw = dict(vocab_size=97, hidden_size=32, num_layers=7,
              layer_pattern="MEM*EME", num_heads=4, num_kv_heads=2,
              attn_head_dim=8, ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
              ssm_state=16, ssm_conv=4, ssm_chunk=4, prefill_chunk=8,
              num_experts=8, experts_held=4, experts_per_token=3,
              latent_size=16, ffn_size=16, shared_expert_size=24,
              routed_scaling=2.5, max_position=128, block="mixer_moe")
    kw.update(over)
    return DecoderConfig(**kw)


def kda_moe_tiny(**over) -> DecoderConfig:
    """The "kda_moe" block at test size: six layers, five Kimi-Delta (4
    heads, a state of 8 key channels x 8 values, chunks of 8 in sub-blocks
    of 4) and one latent attention (4 heads of 8 + 4 rotary over a latent of
    16), two dense layers then 2 of 8 experts in 2 of 4 groups, 4 held."""
    kw = dict(vocab_size=97, hidden_size=32, num_layers=6, num_heads=4,
              attn_head_dim=8, rope_head_dim=4, v_head_dim=8,
              kv_lora_rank=16, rope_theta=6e6, rms_norm_eps=1e-6,
              layer_group_size=6, ssm_heads=4, ssm_head_dim=8, ssm_state=8,
              ssm_conv=4, ssm_chunk=8, kda_sub_chunk=4, dense_layers=2,
              dense_ffn_size=48, num_experts=8, experts_held=4,
              experts_per_token=2, expert_groups=4, groups_per_token=2,
              routed_scaling=2.5, ffn_size=16, shared_expert_size=16,
              prefill_chunk=8, max_position=128, block="kda_moe")
    kw.update(over)
    return DecoderConfig(**kw)


def looped_dense_tiny(**over) -> DecoderConfig:
    """Toy sizes of the "looped_dense" block for the CPU tests: three layers
    visited three times, 4 heads of 8, prompts in chunks of 8."""
    return DecoderConfig(**{**dict(
        block="looped_dense", vocab_size=97, hidden_size=32, num_layers=3,
        num_heads=4, num_kv_heads=4, attn_head_dim=8, ffn_size=64,
        loop_steps=3, rope_theta=1e6, rms_norm_eps=1e-6, prefill_chunk=8,
        max_position=128), **over})


# -- the "cca_moe" family ----------------------------------------------------


def _validate_cca(cfg: DecoderConfig) -> None:
    if (cfg.cca_time0, cfg.cca_time1) != (2, 2):
        raise ValueError("block 'cca_moe' carries one token of convolution "
                         "state: cca_time0 and cca_time1 must be 2")
    if cfg.kv_heads != 2 or cfg.num_heads % 2:
        raise ValueError("block 'cca_moe' builds its values from two halves "
                         "(this token, the last): num_kv_heads must be 2")
    if cfg.num_experts < 1 or cfg.router_hidden_size < 1:
        raise ValueError("block 'cca_moe' needs num_experts and "
                         "router_hidden_size")


def _cca_geometry(cfg: DecoderConfig) -> dict:
    return {"num_heads": cfg.num_heads, "num_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim,
            "rotary_dim": int(cfg.head_dim * cfg.partial_rotary_factor),
            "rope_theta": float(cfg.rope_theta),
            "eps": float(cfg.rms_norm_eps)}


def _cca_pool_geometry(cfg: DecoderConfig, num_pages: int, page_size: int):
    state = cca_moe_ops.state_width(cca_moe_ops.Geometry(**_cca_geometry(cfg)))
    return (cfg.num_layers, num_pages, page_size,
            cfg.kv_heads * cfg.head_dim, state, cfg.dtype)


def stacked_pool_geometry(cfg: DecoderConfig, num_pages: int,
                          page_size: int) -> tuple:
    """`kv_cache.stacked_pool_shapes`' arguments for a scanned family whose
    only pools are paged (the row's `pool_geometry`)."""
    return cfg.family.pool_geometry(cfg, num_pages, page_size)


def _cca_param_specs(cfg: DecoderConfig) -> dict:
    """name -> (shape, dtype, initializer) of every parameter, layers
    stacked on the leading axis. Matrices are drawn at fan_in^-0.5, the two
    projections back into the residual stream at 0.5x (attention) and 2x
    (an expert's output is weighed by a probability of 0.1-0.4) of that, so
    that both branches of every layer move the logits; the large ones are
    in `cfg.dtype`, everything a norm, convolution or the router reads in
    float32."""
    L, H, F, E = cfg.num_layers, cfg.hidden_size, cfg.ffn_size, \
        cfg.num_experts
    nh, nkv, dh, R = cfg.num_heads, cfg.kv_heads, cfg.head_dim, \
        cfg.router_hidden_size
    lat, groups = (nh + nkv) * dh, nh + nkv
    f32, big = "float32", cfg.dtype
    near_one = Normal(1.0, 0.05)
    return {
        "dec.word_emb": ([cfg.vocab_size, H], big, Normal(0.0, 0.02)),
        "dec.final_norm.scale": ([H], f32, near_one),
        "attn_norm": ([L, H], f32, near_one),
        "wqk": ([L, H, lat], big, Normal(0.0, H ** -0.5)),
        "wv": ([L, H, nkv * dh], big, Normal(0.0, H ** -0.5)),
        "wo": ([L, nh * dh, H], big, Normal(0.0, 0.5 * (nh * dh) ** -0.5)),
        "conv0_w": ([L, lat, 2], f32, Normal(0.0, 0.5)),
        "conv0_b": ([L, lat], f32, Normal(0.0, 0.02)),
        "conv1_w": ([L, 2, groups, dh, dh], f32,
                    Normal(0.0, (2 * dh) ** -0.5)),
        "conv1_b": ([L, lat], f32, Normal(0.0, 0.02)),
        "k_temp": ([L, nkv], f32, Normal(0.0, 0.1)),
        "ffn_norm": ([L, H], f32, near_one),
        "router_in_w": ([L, H, R], f32, Normal(0.0, H ** -0.5)),
        "router_in_b": ([L, R], f32, Normal(0.0, 0.02)),
        "router_gamma": ([L], f32, Normal(0.5, 0.1)),
        "router_norm": ([L, R], f32, near_one),
        "router_w1": ([L, R, R], f32, Normal(0.0, R ** -0.5)),
        "router_b1": ([L, R], f32, Normal(0.0, 0.02)),
        "router_w2": ([L, R, R], f32, Normal(0.0, R ** -0.5)),
        "router_b2": ([L, R], f32, Normal(0.0, 0.02)),
        "router_w3": ([L, R, E], f32, Normal(0.0, 2.0 * R ** -0.5)),
        "router_b3": ([L, E], f32, Constant(0.0)),
        "router_bias": ([L, E], f32, Normal(0.0, 0.01)),
        "w_gate": ([L, E, H, F], big, StackedNormal(0.0, H ** -0.5)),
        "w_up": ([L, E, H, F], big, StackedNormal(0.0, H ** -0.5)),
        "w_down": ([L, E, F, H], big, StackedNormal(0.0, 2.0 * F ** -0.5)),
    }


def cca_param_name(key: str) -> str:
    """The scope name of a `_cca_param_specs` key."""
    return key if key.startswith("dec.") else "dec.layers." + key


# -- the "sparse_moe" family -------------------------------------------------


def _validate_sparse(cfg: DecoderConfig) -> None:
    if min(cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
           cfg.prefill_chunk) < 1 or cfg.index_head_dim % 4:
        raise ValueError(
            "block 'sparse_moe' needs index_heads, index_head_dim (a "
            "multiple of 4: half of it carries rotary), index_topk and "
            "prefill_chunk")
    if not 1 <= cfg.experts_per_token <= cfg.num_experts:
        raise ValueError("block 'sparse_moe' needs num_experts >= "
                         "experts_per_token >= 1")
    if cfg.num_heads % cfg.kv_heads:
        raise ValueError("num_kv_heads must divide num_heads")
    if cfg.kv_heads % 2 and jnp.dtype(cfg.dtype).itemsize == 2:
        raise ValueError(
            "block 'sparse_moe' in a 16-bit dtype needs an even num_kv_heads: "
            "the halves of a token's K (and V) share 32-bit words "
            "(sparse_moe_ops.join_rows_fn)")


def _sparse_geometry(cfg: DecoderConfig) -> dict:
    return {"num_heads": cfg.num_heads, "num_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "rope_theta": float(cfg.rope_theta),
            "eps": float(cfg.rms_norm_eps), "index_heads": cfg.index_heads,
            "index_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
            "experts_per_token": cfg.experts_per_token}


def _sparse_pool_geometry(cfg: DecoderConfig, num_pages: int,
                          page_size: int):
    return (cfg.num_layers, num_pages, page_size,
            cfg.kv_heads * cfg.head_dim, 0, cfg.dtype, cfg.index_head_dim,
            True)


def _sparse_param_specs(cfg: DecoderConfig) -> dict:
    """name -> (shape, dtype, initializer), layers stacked on the leading
    axis. Matrices are drawn at fan_in^-0.5; the attention's way back into
    the residual stream at 0.5x and an expert's output (weighed by a share
    of about 1/k) at 2x of that, so that both branches of every layer move
    the logits; the router at 2x, so that its probabilities differ by more
    than rounding. The large ones are in `cfg.dtype`; norms and the router
    in float32."""
    L, H, F, E = cfg.num_layers, cfg.hidden_size, cfg.ffn_size, \
        cfg.num_experts
    nh, nkv, dh = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    J, D = cfg.index_heads, cfg.index_head_dim
    f32, big = "float32", cfg.dtype
    near_one = Normal(1.0, 0.05)
    fan = Normal(0.0, H ** -0.5)
    return {
        "dec.word_emb": ([cfg.vocab_size, H], big, Normal(0.0, 0.02)),
        "dec.lm_head": ([H, cfg.vocab_size], big, fan),
        "dec.final_norm.scale": ([H], f32, near_one),
        "attn_norm": ([L, H], f32, near_one),
        "wq": ([L, H, nh * dh], big, fan),
        "wk": ([L, H, nkv * dh], big, fan),
        "wv": ([L, H, nkv * dh], big, fan),
        "wo": ([L, nh * dh, H], big, Normal(0.0, 0.5 * (nh * dh) ** -0.5)),
        "q_norm": ([L, dh], f32, near_one),
        "k_norm": ([L, dh], f32, near_one),
        "wqi": ([L, H, J * D], big, fan),
        "wki": ([L, H, D], big, fan),
        "ki_norm_w": ([L, D], f32, near_one),
        "ki_norm_b": ([L, D], f32, Normal(0.0, 0.02)),
        "ww": ([L, H, J], big, fan),
        "ffn_norm": ([L, H], f32, near_one),
        "router_w": ([L, H, E], f32, Normal(0.0, 2.0 * H ** -0.5)),
        "w_gate": ([L, E, H, F], big, StackedNormal(0.0, H ** -0.5)),
        "w_up": ([L, E, H, F], big, StackedNormal(0.0, H ** -0.5)),
        "w_down": ([L, E, F, H], big, StackedNormal(0.0, 2.0 * F ** -0.5)),
    }


# -- the "hybrid_moe" family -------------------------------------------------

_ATTENTION_KINDS = {"full_attention": hybrid_moe_ops.FULL,
                    "sliding_attention": hybrid_moe_ops.SLIDE}
_FFN_KINDS = {"dense": hybrid_moe_ops.DENSE, "sparse": hybrid_moe_ops.MOE}


def _validate_hybrid(cfg: DecoderConfig) -> None:
    layer_plan(cfg)        # raises on lists that name no plan
    if min(cfg.sliding_window, cfg.prefill_chunk,
           cfg.shared_expert_size) < 1 or cfg.yarn and len(cfg.yarn) != 5:
        raise ValueError(
            "block 'hybrid_moe' needs sliding_window, prefill_chunk, "
            "shared_expert_size and yarn as () or (factor, original "
            "context, beta_fast, beta_slow, attention factor)")
    if not 1 <= cfg.experts_per_token <= cfg.num_experts:
        raise ValueError("block 'hybrid_moe' needs num_experts >= "
                         "experts_per_token >= 1")


def layer_plan(cfg: DecoderConfig) -> tuple:
    """The layers of a "hybrid_moe" decoder, derived from its per-layer
    lists: a tuple of (attention kind, index among the layers of that
    kind, feed-forward kind, index among those), `hybrid_moe_ops`' kinds."""
    lists = (cfg.layer_types, cfg.mlp_layer_types, cfg.heads_per_layer)
    if {len(x) for x in lists} != {cfg.num_layers}:
        raise ValueError(
            f"block 'hybrid_moe' needs layer_types, mlp_layer_types and "
            f"heads_per_layer of num_layers = {cfg.num_layers} entries each")
    counts: dict = {}
    plan = []
    for attn, ffn, heads in zip(*lists):
        if attn not in _ATTENTION_KINDS or ffn not in _FFN_KINDS:
            raise ValueError(f"unknown layer kind {attn!r} / {ffn!r}")
        a, f = _ATTENTION_KINDS[attn], _FFN_KINDS[ffn]
        if a == hybrid_moe_ops.FULL and heads != cfg.num_heads:
            raise ValueError(
                f"a full-attention layer has {heads} heads, num_heads says "
                f"{cfg.num_heads}")
        if a == hybrid_moe_ops.SLIDE and heads != sliding_heads(cfg):
            raise ValueError("sliding layers of different head counts")
        if heads % cfg.kv_heads:
            raise ValueError("num_kv_heads must divide every head count")
        plan.append((a, counts.get(a, 0), f, counts.get(f, 0)))
        counts[a] = counts.get(a, 0) + 1
        counts[f] = counts.get(f, 0) + 1
    if not all(counts.get(k) for k in (hybrid_moe_ops.FULL,
                                       hybrid_moe_ops.SLIDE,
                                       hybrid_moe_ops.DENSE,
                                       hybrid_moe_ops.MOE)):
        raise ValueError("block 'hybrid_moe' needs a layer of every kind: "
                         "full and sliding attention, dense and sparse")
    return tuple(plan)


def sliding_heads(cfg: DecoderConfig) -> int:
    """Query heads of a sliding layer (the first one's)."""
    return next(h for t, h in zip(cfg.layer_types, cfg.heads_per_layer)
                if t == "sliding_attention")


def _kind_count(cfg: DecoderConfig, kind: str) -> int:
    return sum(kind in (a, f) for a, _, f, _ in layer_plan(cfg))


def window_table_pages(cfg: DecoderConfig, page_size: int,
                       tokens: int = 1) -> int:
    """Width of a row's compact table in the sliding layers' pool for a
    step that computes `tokens` positions: the pages that `sliding_window -
    1` positions back and `tokens` positions on can touch."""
    return -(-(cfg.sliding_window + tokens - 2) // page_size) + 1


def _hybrid_geometry(cfg: DecoderConfig) -> dict:
    dh = cfg.head_dim
    return {"full_heads": cfg.num_heads, "slide_heads": sliding_heads(cfg),
            "num_kv_heads": cfg.kv_heads, "head_dim": dh,
            "window": cfg.sliding_window, "eps": float(cfg.rms_norm_eps),
            "full_rotary_dim": int(dh * cfg.partial_rotary_factor),
            "full_theta": float(cfg.rope_theta),
            "yarn": [float(v) for v in cfg.yarn],
            "slide_rotary_dim": int(dh * cfg.sliding_rotary_factor),
            "slide_theta": float(cfg.sliding_rope_theta),
            "experts_per_token": cfg.experts_per_token,
            "routed_scaling": float(cfg.routed_scaling)}


def hybrid_pool_geometry(cfg: DecoderConfig, num_pages: int, page_size: int,
                         window_pages: int) -> tuple:
    """`kv_cache.stacked_pool_shapes`' arguments, the full layers' pools
    and the sliding layers'."""
    width = cfg.kv_heads * cfg.head_dim
    return ((_kind_count(cfg, hybrid_moe_ops.FULL), num_pages, page_size,
             width, 0, cfg.dtype),
            (_kind_count(cfg, hybrid_moe_ops.SLIDE), window_pages, page_size,
             width, 0, cfg.dtype, 0, False, WINDOW_POOLS))


def _hybrid_param_specs(cfg: DecoderConfig) -> dict:
    """name -> (shape, dtype, initializer), stacked by layer kind: norms
    over all layers, `full.*` and `slide.*` over the attention layers of
    that kind, `dense.*` over the dense layers, the router, the shared
    expert and the experts over the routed ones. Matrices are drawn at
    fan_in^-0.5, the attention's way back into the residual stream at 0.5x
    and an expert's output (weighed by about scaling / k) at 2x of that;
    the router at 2x, so that its sigmoids differ by more than rounding;
    the queries at 3x, so that attention over random keys is peaked, as a
    trained model's is, and not a near-uniform average of thousands of
    values (which is next to nothing, whatever the window: at 1x a window
    not honoured moved no logit by more than rounding does; my chip runs,
    PR 33). The large ones are in `cfg.dtype`; norms, the gate and the
    router in float32."""
    L, H, F, E = cfg.num_layers, cfg.hidden_size, cfg.ffn_size, \
        cfg.num_experts
    nkv, dh = cfg.kv_heads, cfg.head_dim
    Fd, Fs = cfg.dense_ffn_size, cfg.shared_expert_size
    n = {kind: _kind_count(cfg, kind) for kind in (
        hybrid_moe_ops.FULL, hybrid_moe_ops.SLIDE, hybrid_moe_ops.DENSE,
        hybrid_moe_ops.MOE)}
    f32, big = "float32", cfg.dtype
    near_one = Normal(1.0, 0.05)
    fan = Normal(0.0, H ** -0.5)
    specs = {
        "dec.word_emb": ([cfg.vocab_size, H], big, Normal(0.0, 0.02)),
        "dec.lm_head": ([H, cfg.vocab_size], big, fan),
        "dec.final_norm.scale": ([H], f32, near_one),
        "attn_norm": ([L, H], f32, near_one),
        "ffn_norm": ([L, H], f32, near_one),
    }
    for kind, nh in ((hybrid_moe_ops.FULL, cfg.num_heads),
                     (hybrid_moe_ops.SLIDE, sliding_heads(cfg))):
        k = n[kind]
        specs.update({
            f"{kind}.wq": ([k, H, nh * dh], big, Normal(0.0, 3.0 * H ** -0.5)),
            f"{kind}.wk": ([k, H, nkv * dh], big, fan),
            f"{kind}.wv": ([k, H, nkv * dh], big, fan),
            f"{kind}.wg": ([k, H, nh], f32, fan),
            f"{kind}.wo": ([k, nh * dh, H], big,
                           Normal(0.0, 0.5 * (nh * dh) ** -0.5))})
    Ld, Le = n[hybrid_moe_ops.DENSE], n[hybrid_moe_ops.MOE]
    specs.update({
        "dense.w_gate": ([Ld, H, Fd], big, fan),
        "dense.w_up": ([Ld, H, Fd], big, fan),
        "dense.w_down": ([Ld, Fd, H], big, Normal(0.0, Fd ** -0.5)),
        "moe.router_w": ([Le, H, E], f32, Normal(0.0, 2.0 * H ** -0.5)),
        "moe.router_bias": ([Le, E], f32, Normal(0.0, 0.01)),
        "moe.shared_gate": ([Le, H, Fs], big, fan),
        "moe.shared_up": ([Le, H, Fs], big, fan),
        "moe.shared_down": ([Le, Fs, H], big, Normal(0.0, Fs ** -0.5)),
        "w_gate": ([Le, E, H, F], big, StackedNormal(0.0, H ** -0.5)),
        "w_up": ([Le, E, H, F], big, StackedNormal(0.0, H ** -0.5)),
        "w_down": ([Le, E, F, H], big, StackedNormal(0.0, 2.0 * F ** -0.5)),
    })
    return specs


def _declare_paged_pools(cfg, num_pages, page_size, second=0):
    declare_stacked_pools(default_main_program().global_block,
                          *stacked_pool_geometry(cfg, num_pages, page_size))


def _declare_hybrid_pools(cfg, num_pages, page_size, window_pages):
    for geometry in hybrid_pool_geometry(cfg, num_pages, page_size,
                                         window_pages):
        declare_stacked_pools(default_main_program().global_block, *geometry)


# -- the "latent_moe" family -------------------------------------------------


def _validate_expert_groups(cfg: DecoderConfig) -> None:
    """The group-limited router's fields ("latent_moe", "kda_moe")."""
    if not 1 <= cfg.experts_per_token <= cfg.num_experts \
            or cfg.num_experts % cfg.expert_groups \
            or not 1 <= cfg.groups_per_token <= cfg.expert_groups \
            or cfg.num_experts // cfg.expert_groups < 2 \
            or cfg.experts_per_token > cfg.groups_per_token \
            * (cfg.num_experts // cfg.expert_groups) \
            or not 1 <= cfg.held_experts <= cfg.num_experts:
        raise ValueError(
            f"block {cfg.block!r} needs num_experts in expert_groups equal "
            "groups of at least two, groups_per_token of them holding "
            "experts_per_token, and 1 <= experts_held <= num_experts")


def _validate_latent(cfg: DecoderConfig) -> None:
    indexer = (cfg.index_heads, cfg.index_head_dim, cfg.index_topk)
    if min(cfg.q_lora_rank, cfg.kv_lora_rank, cfg.rope_head_dim,
           cfg.v_head_dim, cfg.prefill_chunk, cfg.shared_expert_size) < 1 \
            or cfg.rope_head_dim % 2 or cfg.kv_lora_rank % 2 \
            or any(indexer) and (
                min(indexer) < 1 or cfg.index_head_dim < cfg.rope_head_dim) \
            or cfg.yarn and len(cfg.yarn) != 5:
        raise ValueError(
            "block 'latent_moe' needs q_lora_rank, kv_lora_rank and "
            "rope_head_dim (even), v_head_dim, prefill_chunk, "
            "shared_expert_size, yarn as () or five values, and an indexer "
            "given whole or not at all: index_heads, index_head_dim (at "
            "least rope_head_dim: its first lanes carry the rotary) and "
            "index_topk")
    if cfg.hc_mult < 1 or cfg.hc_mult > 1 and (
            cfg.hc_sinkhorn_iters < 1 or cfg.hc_eps <= 0
            or len(cfg.hc_res_clamp) != 2
            or cfg.hc_res_clamp[0] >= cfg.hc_res_clamp[1]):
        raise ValueError(
            "block 'latent_moe' with hc_mult > 1 residual streams needs "
            "hc_sinkhorn_iters >= 1, hc_eps > 0 and hc_res_clamp as "
            "(lowest, highest)")
    if not 1 <= cfg.dense_layers < cfg.num_layers or cfg.dense_ffn_size < 1:
        raise ValueError(
            "block 'latent_moe' needs 1 <= dense_layers < num_layers "
            "(dense layers lead, routed ones follow) and dense_ffn_size")
    _validate_expert_groups(cfg)


def _latent_geometry(cfg: DecoderConfig) -> dict:
    return {"num_heads": cfg.num_heads, "nope_dim": cfg.head_dim,
            "rope_dim": cfg.rope_head_dim, "v_dim": cfg.v_head_dim,
            "kv_rank": cfg.kv_lora_rank,
            "rope_theta": float(cfg.rope_theta),
            "yarn": [float(v) for v in cfg.yarn],
            "softmax_mscale": float(cfg.softmax_mscale),
            "eps": float(cfg.rms_norm_eps), "index_heads": cfg.index_heads,
            "index_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
            "experts_per_token": cfg.experts_per_token,
            "expert_groups": cfg.expert_groups,
            "groups_per_token": cfg.groups_per_token,
            "routed_scaling": float(cfg.routed_scaling),
            "experts_held": cfg.held_experts,
            "hc_mult": cfg.hc_mult, "hc_iters": cfg.hc_sinkhorn_iters,
            "hc_eps": float(cfg.hc_eps),
            "hc_clamp": [float(v) for v in cfg.hc_res_clamp]}


def _latent_pool_geometry(cfg: DecoderConfig, num_pages: int,
                          page_size: int):
    return (cfg.num_layers, num_pages, page_size,
            cfg.kv_lora_rank + cfg.rope_head_dim, 0, cfg.dtype,
            cfg.index_head_dim, False, STACKED_POOLS, True)


def _latent_groups(cfg: DecoderConfig) -> tuple:
    shared = latent_moe_ops.attention_params(cfg.selects, cfg.hc_mult)
    return _EMB_HEAD_NORM + (
        ("DenseParams", "dense.", shared + latent_moe_ops.DENSE_PARAMS),
        ("MoeParams", "moe.", shared + latent_moe_ops.MOE_PARAMS),
        ("Experts", "", latent_moe_ops.EXPERT_PARAMS))


# standard deviations of a sub-layer's biases `hc_b`: pre [n], post [n],
# res [n * n] (`_hc_param_specs` says why)
HC_BIAS_SPREAD = (2.0, 2.0, 1.2)


def _latent_param_specs(cfg: DecoderConfig) -> dict:
    """name -> (shape, dtype, initializer), stacked by layer kind: `dense.*`
    over the leading dense layers and `moe.*` over the routed ones (each its
    attention, indexer and norms, then its own feed-forward), the held
    experts `[L_moe, held, ...]`. Matrices are drawn at fan_in^-0.5 (the
    fan the normed latent where one is the input); the queries' way out of
    their latent at 2x, so that attention over random keys is peaked, as a
    trained model's is; the attention's way back into the residual stream
    at 0.5x and an expert's output (weighed by about scaling / k) at 2x;
    the router at 2x, so that its sigmoids differ by more than rounding,
    and its selection bias at 0.02, so that it decides some choices and
    not all. The large ones are in `cfg.dtype`; norms, the router and its
    bias in float32. A configuration without an indexer has none of its
    five parameters; one with `hc_mult` > 1 residual streams has, a layer,
    the mappings of its two sub-layers in float32 (`hc_w` [2, n H, n (n +
    2)], `hc_a` [2, 3], `hc_b` [2, n (n + 2)]: `_hc_param_specs`)."""
    H, F, E = cfg.hidden_size, cfg.ffn_size, cfg.num_experts
    nh, dn, dr, dv = cfg.num_heads, cfg.head_dim, cfg.rope_head_dim, \
        cfg.v_head_dim
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    J, D = cfg.index_heads, cfg.index_head_dim
    Fd, Fs, held = cfg.dense_ffn_size, cfg.shared_expert_size, \
        cfg.held_experts
    Ld, Le = cfg.dense_layers, cfg.num_layers - cfg.dense_layers
    f32, big = "float32", cfg.dtype
    near_one = Normal(1.0, 0.05)

    def fan(n, scale=1.0):
        return StackedNormal(0.0, scale * n ** -0.5)

    specs = {
        "dec.word_emb": ([cfg.vocab_size, H], big, Normal(0.0, 0.02)),
        "dec.lm_head": ([H, cfg.vocab_size], big, Normal(0.0, H ** -0.5)),
        "dec.final_norm.scale": ([H], f32, near_one),
    }
    for kind, n in (("dense", Ld), ("moe", Le)):
        specs.update({f"{kind}.{key}": spec for key, spec in {
            "attn_norm": ([n, H], f32, near_one),
            "wq_a": ([n, H, rq], big, fan(H)),
            "q_norm": ([n, rq], f32, near_one),
            "wq_b": ([n, rq, nh * (dn + dr)], big, fan(rq, 2.0)),
            "wkv_a": ([n, H, rkv + dr], big, fan(H)),
            "kv_norm": ([n, rkv], f32, near_one),
            "wkv_b": ([n, rkv, nh * (dn + dv)], big, fan(rkv)),
            "wo": ([n, nh * dv, H], big, fan(nh * dv, 0.5)),
            "wqi": ([n, rq, J * D], big, fan(rq)),
            "wki": ([n, H, D], big, fan(H)),
            "ki_norm_w": ([n, D], f32, near_one),
            "ki_norm_b": ([n, D], f32, Normal(0.0, 0.02)),
            "ww": ([n, H, J], big, fan(H)),
            "ffn_norm": ([n, H], f32, near_one),
            **_hc_param_specs(cfg, n)}.items()
            if cfg.selects or key not in latent_moe_ops.INDEXER_PARAMS})
    specs.update({
        "dense.w_gate": ([Ld, H, Fd], big, fan(H)),
        "dense.w_up": ([Ld, H, Fd], big, fan(H)),
        "dense.w_down": ([Ld, Fd, H], big, fan(Fd)),
        "moe.router_w": ([Le, H, E], f32, Normal(0.0, 2.0 * H ** -0.5)),
        "moe.router_bias": ([Le, E], f32, Normal(0.0, 0.02)),
        "moe.shared_gate": ([Le, H, Fs], big, fan(H)),
        "moe.shared_up": ([Le, H, Fs], big, fan(H)),
        "moe.shared_down": ([Le, Fs, H], big, fan(Fs)),
        "w_gate": ([Le, held, H, F], big, fan(H)),
        "w_up": ([Le, held, H, F], big, fan(H)),
        "w_down": ([Le, held, F, H], big, fan(F, 2.0)),
    })
    return specs


def _hc_param_specs(cfg: DecoderConfig, layers: int) -> dict:
    """The mappings of `layers` layers' two sub-layers in float32 (nothing
    for one stream), seeded so that the residual path MATTERS to what is
    served. `hc_w` N(0, (n H)^-0.5): over the normalised streams each raw
    projection is N(0, 1) a token, and `hc_a` N(0.6, 0.05) keeps every
    mapping a smooth function of the token. `hc_b` is drawn a block of
    columns (`HC_BIAS_SPREAD`): the pre and post biases N(0, 2), so that a
    sub-layer reads mostly one or two of the streams (H_pre 0.05-0.95) and
    writes mostly into one or two (H_post 0.1-1.9): the streams then hold
    different things, and what a later sub-layer reads depends on how H_res
    carried them (a doubly stochastic H_res keeps the streams' SUM, which
    is all the head reads, so under even H_pre a wrong H_res shows
    nowhere); the residual biases N(0, 1.2): logits spread by 1.34, entries
    of H_res from 0.02 to 0.6, columns 22% off one after ONE Sinkhorn
    iteration, 6% after two, 2% after three (medians) and 1e-6 after the
    configured twenty (one token in a hundred is still 7e-4 off: twenty
    iterations are what the configuration gives, not a limit).
    `tests/test_hyper_connections.py` measures these."""
    n = cfg.hc_mult
    if n < 2:
        return {}
    k = n * (n + 2)
    pre, post, res = HC_BIAS_SPREAD
    return {
        "hc_w": ([layers, 2, n * cfg.hidden_size, k], "float32",
                 StackedNormal(0.0, (n * cfg.hidden_size) ** -0.5)),
        "hc_a": ([layers, 2, 3], "float32", Normal(0.6, 0.05)),
        "hc_b": ([layers, 2, k], "float32", BlockedNormal(
            columns=[(n, pre), (n, post), (n * n, res)]))}


# -- the "parallel_ssm" family -----------------------------------------------


def _validate_ssm(cfg: DecoderConfig) -> None:
    if min(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
           cfg.ssm_chunk, cfg.prefill_chunk) < 1 \
            or cfg.ssm_conv < 2 or cfg.ssm_heads % cfg.ssm_groups \
            or cfg.num_heads % cfg.kv_heads \
            or len(cfg.mlp_multipliers) != 2 or len(cfg.ssm_multipliers) != 5:
        raise ValueError(
            "block 'parallel_ssm' needs ssm_heads (a multiple of "
            "ssm_groups), ssm_head_dim, ssm_state, ssm_chunk, ssm_conv >= 2, "
            "prefill_chunk, num_kv_heads dividing num_heads, two "
            "mlp_multipliers and five ssm_multipliers")


def _ssm_geometry(cfg: DecoderConfig) -> dict:
    return {"num_heads": cfg.num_heads, "num_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "rope_theta": float(cfg.rope_theta),
            "eps": float(cfg.rms_norm_eps), "ssm_heads": cfg.ssm_heads,
            "ssm_head_dim": cfg.ssm_head_dim, "ssm_groups": cfg.ssm_groups,
            "ssm_state": cfg.ssm_state, "ssm_conv": cfg.ssm_conv,
            "ssm_chunk": cfg.ssm_chunk,
            **{name: float(getattr(cfg, name)) for name in (
                "embedding_multiplier", "lm_head_multiplier",
                "ssm_in_multiplier", "ssm_out_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "key_multiplier")},
            "mlp_multipliers": [float(v) for v in cfg.mlp_multipliers],
            "ssm_multipliers": [float(v) for v in cfg.ssm_multipliers]}


def ssm_pool_geometry(cfg: DecoderConfig, num_pages: int, page_size: int,
                      num_slots: int) -> tuple:
    """(`kv_cache.stacked_pool_shapes`' arguments for the paged pools,
    `kv_cache.state_pool_shapes`' for the recurrent state) of a recurrent
    family (the row's `pool_geometry`)."""
    return cfg.family.pool_geometry(cfg, num_pages, page_size, num_slots)


def _ssm_pool_geometry(cfg, num_pages, page_size, num_slots):
    geom = parallel_ssm_ops.Geometry(**_ssm_geometry(cfg))
    return ((cfg.num_layers, num_pages, page_size,
             cfg.kv_heads * cfg.head_dim, 0, cfg.dtype),
            (cfg.num_layers, num_slots, cfg.ssm_heads, cfg.ssm_state,
             cfg.ssm_head_dim,
             (cfg.ssm_conv - 1) * parallel_ssm_ops.conv_width(geom)))


def _ssm_param_specs(cfg: DecoderConfig) -> dict:
    """name -> (shape, dtype, initializer), layers stacked on the leading
    axis. The config's multipliers are small (0.011 on keys, 0.0375 and
    0.088 on the two branches, 0.0078 on the logits): under a plain
    fan_in^-0.5 draw attention is a uniform average and both branches a
    rounding error, and a wrong engine reads like a right one. So every
    matrix is drawn at `target / (its multipliers x fan_in^0.5)`, the
    target the standard deviation of what comes out of it: the embedding 1
    (the residual stream enters at RMS 1); queries 2 and keys 1 (attention
    logits of standard deviation 2 over sqrt(head_dim)); values 1; the
    mixer's z and x 1, B and C 2 (so that `C . S`, not the skip term,
    carries the mixer's output), dt 0.5 around a bias drawn log-uniform
    over 0.001-0.1 (`softplus`), `exp(A_log)` uniform in the exponent over
    1-16 (a decay of 0.2-0.999 a token), the skip near 1; the two branches'
    way back into the residual 2 (attention: its input is a weighted mean
    of values, about a quarter of their size) and 0.5 (the mixer: its input
    is normed); the SwiGLU's gate and up 1, its way back 1; the head 2.5:
    each branch comes to half the residual's RMS (measured on the chip:
    0.50, 0.47 and 0.60; PERF.md section 4). A mixer at 1 beside branches
    at 0.3 was tried: it parts a wrong state from a right one no better and
    float8 weights from bfloat16 ones half as well.
    The large ones are in `cfg.dtype`; norms, the convolution and the
    per-head scalars in float32."""
    L, H, F, V = cfg.num_layers, cfg.hidden_size, cfg.ffn_size, \
        cfg.vocab_size
    nh, nkv, dh = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    Hs, P, GN = cfg.ssm_heads, cfg.ssm_head_dim, \
        cfg.ssm_groups * cfg.ssm_state
    I = Hs * P
    C = I + 2 * GN
    f32, big = "float32", cfg.dtype
    near_one = Normal(1.0, 0.05)
    root = H ** -0.5
    m = [cfg.ssm_in_multiplier * v for v in cfg.ssm_multipliers]
    a_in = cfg.attention_in_multiplier
    gate_m, down_m = cfg.mlp_multipliers
    return {
        "dec.word_emb": ([V, H], big, BlockedNormal(
            1.0 / cfg.embedding_multiplier, block_rows=_draw_rows(V))),
        "dec.lm_head": ([H, V], big, BlockedNormal(
            2.5 * root / cfg.lm_head_multiplier, block_rows=_draw_rows(H))),
        "dec.final_norm.scale": ([H], f32, near_one),
        "attn_norm": ([L, H], f32, near_one),
        "w_in": ([L, H, I + C + Hs], big, BlockedNormal(columns=[
            (I, root / m[0]), (I, root / m[1]), (GN, 2.0 * root / m[2]),
            (GN, 2.0 * root / m[3]), (Hs, 0.5 * root / m[4])])),
        "conv_w": ([L, C, cfg.ssm_conv], f32, Normal(0.0, 0.5)),
        "conv_b": ([L, C], f32, Normal(0.0, 0.02)),
        "dt_bias": ([L, Hs], f32, Uniform(-6.9, -2.25)),
        "a_log": ([L, Hs], f32, Uniform(0.0, 2.77)),
        "d_skip": ([L, Hs], f32, Normal(1.0, 0.1)),
        "ssm_norm": ([L, I], f32, near_one),
        "w_out": ([L, I, H], big, BlockedNormal(
            0.5 * I ** -0.5 / cfg.ssm_out_multiplier)),
        "wq": ([L, H, nh * dh], big, Normal(0.0, 2.0 * root / a_in)),
        "wk": ([L, H, nkv * dh], big,
               Normal(0.0, root / (a_in * cfg.key_multiplier))),
        "wv": ([L, H, nkv * dh], big, Normal(0.0, root / a_in)),
        "wo": ([L, nh * dh, H], big, Normal(
            0.0, 2.0 * (nh * dh) ** -0.5 / cfg.attention_out_multiplier)),
        "ffn_norm": ([L, H], f32, near_one),
        "w_gate": ([L, H, F], big, BlockedNormal(root / gate_m)),
        "w_up": ([L, H, F], big, BlockedNormal(root)),
        "w_down": ([L, F, H], big, BlockedNormal(F ** -0.5 / down_m)),
    }


def _draw_rows(rows: int) -> int:
    """Rows of a `[rows, width]` matrix drawn at a time: the largest power
    of two up to 1,024 that divides them."""
    n = 1024
    while rows % n:
        n //= 2
    return n


def _declare_ssm_pools(cfg, num_pages, page_size, state_slots):
    block = default_main_program().global_block
    kv, state = ssm_pool_geometry(cfg, num_pages, page_size, state_slots)
    declare_stacked_pools(block, *kv)
    declare_state_pools(block, *state)


# -- the "mixer_moe" family --------------------------------------------------


def _validate_mixer(cfg: DecoderConfig) -> None:
    kinds = collections.Counter(cfg.layer_pattern)
    if len(cfg.layer_pattern) != cfg.num_layers \
            or set(kinds) - set("M*E") or not kinds["M"] or not kinds["E"]:
        raise ValueError(
            "block 'mixer_moe' needs layer_pattern of num_layers characters "
            "of 'M' (mixer), '*' (attention) and 'E' (experts), with at "
            "least one 'M' and one 'E'")
    if min(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
           cfg.ssm_chunk, cfg.prefill_chunk, cfg.latent_size,
           cfg.shared_expert_size) < 1 \
            or cfg.ssm_conv < 2 or cfg.ssm_heads % cfg.ssm_groups \
            or cfg.num_heads % cfg.kv_heads:
        raise ValueError(
            "block 'mixer_moe' needs ssm_heads (a multiple of ssm_groups), "
            "ssm_head_dim, ssm_state, ssm_chunk, ssm_conv >= 2, "
            "prefill_chunk, latent_size, shared_expert_size and "
            "num_kv_heads dividing num_heads")
    if not 1 <= cfg.experts_per_token <= cfg.num_experts \
            or not 1 <= cfg.held_experts <= cfg.num_experts:
        raise ValueError(
            "block 'mixer_moe' needs num_experts >= experts_per_token >= 1 "
            "and 1 <= experts_held <= num_experts")


def _state_pack(cfg: DecoderConfig) -> int:
    return mixer_moe_ops.state_pack(cfg.ssm_head_dim,
                                    cfg.ssm_heads // cfg.ssm_groups)


def _mixer_geometry(cfg: DecoderConfig) -> dict:
    return {"plan": cfg.layer_pattern, "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
            "eps": float(cfg.rms_norm_eps), "ssm_heads": cfg.ssm_heads,
            "ssm_head_dim": cfg.ssm_head_dim, "ssm_groups": cfg.ssm_groups,
            "ssm_state": cfg.ssm_state, "ssm_conv": cfg.ssm_conv,
            "ssm_chunk": cfg.ssm_chunk,
            "state_pack": _state_pack(cfg),
            "experts_per_token": cfg.experts_per_token,
            "routed_scaling": float(cfg.routed_scaling),
            "experts_held": cfg.held_experts}


def _mixer_pool_geometry(cfg, num_pages, page_size, num_slots):
    # by the count of a kind: K/V over the attention layers, the state over
    # the mixers, narrow heads packed on the lanes
    tail = (cfg.ssm_conv - 1) * (
        cfg.ssm_heads * cfg.ssm_head_dim
        + 2 * cfg.ssm_groups * cfg.ssm_state)
    return ((cfg.layer_pattern.count(mixer_moe_ops.ATTENTION), num_pages,
             page_size, cfg.kv_heads * cfg.head_dim, 0, cfg.dtype),
            (cfg.state_layers, num_slots, cfg.ssm_heads, cfg.ssm_state,
             cfg.ssm_head_dim, tail, _state_pack(cfg)))


def _mixer_param_specs(cfg: DecoderConfig) -> dict:
    """name -> (shape, dtype, initializer), stacked by layer kind: `norm`
    over all layers (one pre-norm a layer), `mix.*` over the mixers,
    `attn.*` over the attention layers, `moe.*` over the expert layers, the
    held experts `[L_experts, held, ...]`. Every matrix is drawn at `target
    x fan_in^-0.5`, the target the standard deviation of its product: the
    embedding 1 (the residual stream enters at RMS 1); the mixer as
    "parallel_ssm"'s (z and x 1, B and C 2, dt 0.5 around a bias drawn
    log-uniform over 0.001-0.1, `exp(A_log)` over 1-16, the skip near 1, the
    convolution N(0, 0.5) with a bias of 0.02, the way back 0.5); queries 2,
    keys and values 1 (no rotary: attention logits of standard deviation 2
    over sqrt(head_dim)), their way back 2 (its input is a weighted mean of
    values); the router 2 in float32 with a selection bias of 0.02, so that
    its sigmoids neither saturate nor tie and the bias decides some choices
    ("latent_moe"'s); the way into the latent 1, an expert's first matrix 1
    (a squared ReLU of a unit normal has RMS 1.22), its second 1, the way
    out of the latent 1 (about scaling x 1.22 / sqrt(k) x sqrt(held share):
    0.65 of the residual's RMS at 22 of 512 with a quarter held); the
    shared expert 1 and 0.5; the head 2.5. The large ones are in
    `cfg.dtype`; norms, the router and its bias, the convolution and the
    per-head scalars in float32."""
    H, V, F, Z = cfg.hidden_size, cfg.vocab_size, cfg.ffn_size, \
        cfg.latent_size
    nh, nkv, dh = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    Hs, GN = cfg.ssm_heads, cfg.ssm_groups * cfg.ssm_state
    I = Hs * cfg.ssm_head_dim
    C = I + 2 * GN
    E, held, Fs = cfg.num_experts, cfg.held_experts, cfg.shared_expert_size
    kinds = collections.Counter(cfg.layer_pattern)
    Lm, La, Le = (kinds[k] for k in (
        mixer_moe_ops.MIXER, mixer_moe_ops.ATTENTION, mixer_moe_ops.EXPERTS))
    f32, big = "float32", cfg.dtype
    near_one = Normal(1.0, 0.05)
    root = H ** -0.5

    def fan(n, scale=1.0):
        return StackedNormal(0.0, scale * n ** -0.5)

    return {
        "dec.word_emb": ([V, H], big, BlockedNormal(
            1.0, block_rows=_draw_rows(V))),
        "dec.lm_head": ([H, V], big, BlockedNormal(
            2.5 * root, block_rows=_draw_rows(H))),
        "dec.final_norm.scale": ([H], f32, near_one),
        "norm": ([cfg.num_layers, H], f32, near_one),
        "mix.w_in": ([Lm, H, I + C + Hs], big, BlockedNormal(columns=[
            (I, root), (I, root), (GN, 2.0 * root), (GN, 2.0 * root),
            (Hs, 0.5 * root)])),
        "mix.conv_w": ([Lm, C, cfg.ssm_conv], f32, Normal(0.0, 0.5)),
        "mix.conv_b": ([Lm, C], f32, Normal(0.0, 0.02)),
        "mix.dt_bias": ([Lm, Hs], f32, Uniform(-6.9, -2.25)),
        "mix.a_log": ([Lm, Hs], f32, Uniform(0.0, 2.77)),
        "mix.d_skip": ([Lm, Hs], f32, Normal(1.0, 0.1)),
        "mix.ssm_norm": ([Lm, I], f32, near_one),
        "mix.w_out": ([Lm, I, H], big, BlockedNormal(0.5 * I ** -0.5)),
        "attn.wq": ([La, H, nh * dh], big, fan(H, 2.0)),
        "attn.wk": ([La, H, nkv * dh], big, fan(H)),
        "attn.wv": ([La, H, nkv * dh], big, fan(H)),
        "attn.wo": ([La, nh * dh, H], big, fan(nh * dh, 2.0)),
        "moe.router_w": ([Le, H, E], f32, Normal(0.0, 2.0 * root)),
        "moe.router_bias": ([Le, E], f32, Normal(0.0, 0.02)),
        "moe.w_dn": ([Le, H, Z], big, fan(H)),
        "moe.w_up": ([Le, Z, H], big, fan(Z)),
        "moe.shared_in": ([Le, H, Fs], big, fan(H)),
        "moe.shared_out": ([Le, Fs, H], big, fan(Fs, 0.5)),
        "w1": ([Le, held, Z, F], big, fan(Z)),
        "w2": ([Le, held, F, Z], big, fan(F)),
    }


_MIXER_GROUPS = (("MixerParams", "mix.", mixer_moe_ops.MIXER_PARAMS),
                 ("AttentionParams", "attn.", mixer_moe_ops.ATTENTION_PARAMS),
                 ("MoeParams", "moe.", mixer_moe_ops.MOE_PARAMS),
                 ("Experts", "", mixer_moe_ops.EXPERT_PARAMS))


# -- the "kda_moe" family ----------------------------------------------------


def _validate_kda(cfg: DecoderConfig) -> None:
    if min(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.kda_sub_chunk,
           cfg.prefill_chunk, cfg.kv_lora_rank, cfg.rope_head_dim,
           cfg.v_head_dim, cfg.shared_expert_size, cfg.dense_ffn_size) < 1 \
            or cfg.ssm_conv < 2 or cfg.q_lora_rank \
            or cfg.ssm_chunk % cfg.kda_sub_chunk \
            or cfg.kda_sub_chunk % 2 or cfg.kda_lower_bound >= 0 \
            or cfg.kda_lower_bound * cfg.kda_sub_chunk < -160 \
            or cfg.rope_head_dim % 2 or cfg.kv_lora_rank % 2:
        raise ValueError(
            "block 'kda_moe' needs ssm_heads, ssm_head_dim, ssm_state, "
            "ssm_conv >= 2, ssm_chunk in whole kda_sub_chunk (even; "
            "kda_lower_bound < 0 times half of it is an exponent float32 "
            "must hold), prefill_chunk, kv_lora_rank and rope_head_dim "
            "(even), v_head_dim, shared_expert_size, dense_ffn_size and NO "
            "q_lora_rank")
    if not 2 <= cfg.layer_group_size <= cfg.num_layers \
            or not 0 <= cfg.dense_layers < cfg.num_layers:
        raise ValueError(
            "block 'kda_moe' needs 2 <= layer_group_size <= num_layers (a "
            "latent layer among every few, at least one of each kind) and "
            "0 <= dense_layers < num_layers")
    _validate_expert_groups(cfg)


def _kda_geometry(cfg: DecoderConfig) -> dict:
    return {"mixers": cfg.mixer_kinds, "mlps": cfg.mlp_kinds,
            "num_heads": cfg.num_heads, "nope_dim": cfg.head_dim,
            "rope_dim": cfg.rope_head_dim, "v_dim": cfg.v_head_dim,
            "kv_rank": cfg.kv_lora_rank,
            "rope_theta": float(cfg.rope_theta),
            "eps": float(cfg.rms_norm_eps), "kda_heads": cfg.ssm_heads,
            "kda_head_dim": cfg.ssm_head_dim, "kda_conv": cfg.ssm_conv,
            "kda_chunk": cfg.ssm_chunk, "kda_sub_chunk": cfg.kda_sub_chunk,
            "kda_lower_bound": float(cfg.kda_lower_bound),
            "experts_per_token": cfg.experts_per_token,
            "expert_groups": cfg.expert_groups,
            "groups_per_token": cfg.groups_per_token,
            "routed_scaling": float(cfg.routed_scaling),
            "experts_held": cfg.held_experts}


def _kda_pool_geometry(cfg, num_pages, page_size, num_slots):
    # ONE pool of latent rows over the latent layers; a Kimi-Delta head's
    # state [keys, values], the tail over q | k | v
    return ((cfg.latent_layers, num_pages, page_size,
             cfg.kv_lora_rank + cfg.rope_head_dim, 0, cfg.dtype, 0, False,
             STACKED_POOLS, True),
            (cfg.state_layers, num_slots, cfg.ssm_heads, cfg.ssm_state,
             cfg.ssm_head_dim, (cfg.ssm_conv - 1) * cfg.ssm_heads
             * (2 * cfg.ssm_state + cfg.ssm_head_dim)))


def _kda_param_specs(cfg: DecoderConfig) -> dict:
    """name -> (shape, dtype, initializer), stacked by layer kind: `norm`
    the mixers' pre-norms over all layers, `kda.*` over the Kimi-Delta
    layers, `mla.*` over the latent layers, `dense.*` and `moe.*` over the
    layers of either MLP (each with its pre-norm), the held experts
    `[L_moe, held, ...]`. Every matrix is drawn at `target x fan_in^-0.5`,
    the target the standard deviation of its product: the embedding 1 (the
    residual stream enters at RMS 1); q, k and v 1 before a convolution
    N(0, 0.5) of four taps (q and k are normalised a head, so only their
    direction matters); the decay's matrix 2 around `dt_bias` uniform over
    [-3, 1] with `exp(A_log)` uniform in the exponent over 0.5-2: `log a`
    from -4 to -0.1 a channel, so some channels forget inside a sub-block
    and some remember a window; `beta`'s matrix 2 (steps of 0.1-0.9); the
    output gate 1, the norm's gain near 1, the way back 2 (a gated normed
    head is about half a unit). The latent layer as "latent_moe"'s with the
    queries straight off the hidden state at 2 (peaked attention), its head
    gate's matrix 2, its way back 1; the feed-forwards, the router (2, in
    float32, a selection bias of 0.02) and the head (2.5) as "latent_moe"'s.
    The large ones are in `cfg.dtype`; norms, the router and its bias, the
    convolution and the per-channel scalars in float32."""
    H, V, F, E = cfg.hidden_size, cfg.vocab_size, cfg.ffn_size, \
        cfg.num_experts
    nh, dn, dr, dv = cfg.num_heads, cfg.head_dim, cfg.rope_head_dim, \
        cfg.v_head_dim
    rkv = cfg.kv_lora_rank
    Hk, K, P = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
    Fd, Fs, held = cfg.dense_ffn_size, cfg.shared_expert_size, \
        cfg.held_experts
    Lk, Ll = cfg.state_layers, cfg.latent_layers
    Ld, Le = cfg.dense_layers, cfg.num_layers - cfg.dense_layers
    f32, big = "float32", cfg.dtype
    near_one = Normal(1.0, 0.05)
    root = H ** -0.5

    def fan(n, scale=1.0):
        return StackedNormal(0.0, scale * n ** -0.5)

    return {
        "dec.word_emb": ([V, H], big, BlockedNormal(
            1.0, block_rows=_draw_rows(V))),
        "dec.lm_head": ([H, V], big, BlockedNormal(
            2.5 * root, block_rows=_draw_rows(H))),
        "dec.final_norm.scale": ([H], f32, near_one),
        "norm": ([cfg.num_layers, H], f32, near_one),
        "kda.w_qkv": ([Lk, H, Hk * (2 * K + P)], big, fan(H)),
        "kda.conv_w": ([Lk, Hk * (2 * K + P), cfg.ssm_conv], f32,
                       Normal(0.0, 0.5)),
        "kda.w_f": ([Lk, H, Hk * K], big, fan(H, 2.0)),
        "kda.dt_bias": ([Lk, Hk * K], f32, Uniform(-3.0, 1.0)),
        "kda.a_log": ([Lk, Hk], f32, Uniform(-0.7, 0.7)),
        "kda.w_b": ([Lk, H, Hk], big, fan(H, 2.0)),
        "kda.w_g": ([Lk, H, Hk * P], big, fan(H)),
        "kda.o_norm": ([Lk, P], f32, near_one),
        "kda.w_o": ([Lk, Hk * P, H], big, fan(Hk * P, 2.0)),
        "mla.wq": ([Ll, H, nh * (dn + dr)], big, fan(H, 2.0)),
        "mla.wkv_a": ([Ll, H, rkv + dr], big, fan(H)),
        "mla.kv_norm": ([Ll, rkv], f32, near_one),
        "mla.wkv_b": ([Ll, rkv, nh * (dn + dv)], big, fan(rkv)),
        "mla.w_gate_h": ([Ll, H, nh], big, fan(H, 2.0)),
        "mla.wo": ([Ll, nh * dv, H], big, fan(nh * dv)),
        "dense.ffn_norm": ([Ld, H], f32, near_one),
        "dense.w_gate": ([Ld, H, Fd], big, fan(H)),
        "dense.w_up": ([Ld, H, Fd], big, fan(H)),
        "dense.w_down": ([Ld, Fd, H], big, fan(Fd)),
        "moe.ffn_norm": ([Le, H], f32, near_one),
        "moe.router_w": ([Le, H, E], f32, Normal(0.0, 2.0 * root)),
        "moe.router_bias": ([Le, E], f32, Normal(0.0, 0.02)),
        "moe.shared_gate": ([Le, H, Fs], big, fan(H)),
        "moe.shared_up": ([Le, H, Fs], big, fan(H)),
        "moe.shared_down": ([Le, Fs, H], big, fan(Fs)),
        "w_gate": ([Le, held, H, F], big, fan(H)),
        "w_up": ([Le, held, H, F], big, fan(H)),
        "w_down": ([Le, held, F, H], big, fan(F, 2.0)),
    }


_KDA_GROUPS = (("KdaParams", "kda.", kda_ops.KDA_PARAMS),
               ("LatentParams", "mla.", kda_ops.LATENT_PARAMS),
               ("DenseParams", "dense.", kda_ops.DENSE_PARAMS),
               ("MoeParams", "moe.", kda_ops.MOE_PARAMS),
               ("Experts", "", kda_ops.EXPERT_PARAMS))


# -- the "looped_dense" family -----------------------------------------------


def _validate_looped(cfg: DecoderConfig) -> None:
    if min(cfg.loop_steps, cfg.prefill_chunk) < 1 \
            or cfg.num_heads % cfg.kv_heads or cfg.head_dim % 2:
        raise ValueError(
            "block 'looped_dense' needs loop_steps, prefill_chunk, "
            "num_kv_heads dividing num_heads and an even head")


def _looped_geometry(cfg: DecoderConfig) -> dict:
    return {"num_heads": cfg.num_heads, "num_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "rope_theta": float(cfg.rope_theta),
            "eps": float(cfg.rms_norm_eps), "loop_steps": cfg.loop_steps}


def _looped_pool_geometry(cfg: DecoderConfig, num_pages: int,
                          page_size: int):
    # a plane a VISIT of a layer: `cache_planes` where the others say layers
    return (cfg.cache_planes, num_pages, page_size,
            cfg.kv_heads * cfg.head_dim, 0, cfg.dtype)


def _looped_param_specs(cfg: DecoderConfig) -> dict:
    """name -> (shape, dtype, initializer), layers stacked on the leading
    axis and stored ONCE whatever `loop_steps` is. Every sub-layer's output
    is normed before it joins the residual, so a matrix's scale decides
    only what its own product looks like: W_q at 2 and W_k at 1 over
    fan_in^0.5 (attention logits of standard deviation 2 after the division
    by sqrt(head_dim)), everything else at fan_in^-0.5, the head at 2.5
    (logits of standard deviation 2.5 on a state of RMS 1). The gains of the
    two norms BEHIND a sub-layer are drawn around 0.25, not 1: a sub-layer
    then adds a quarter of the state's size, and 384 sub-layers a token
    carry a bfloat16 rounding to the logits without blowing it up (on the
    chip at the served sizes, the bfloat16 engine against the float32
    reference: worst logit gap 0.19-0.69 under gains of 1, 0.24-0.44 under
    0.5, 0.02-0.14 under 0.25, where float8 weights read 3.4-3.8 and either
    planted fault 5.7-9.9; my chip run, PR 53), and the 96 sub-layers of a
    visit still make 86% of the state that leaves it. The gate's
    weights are drawn at fan_in^-0.5 around a bias of -1: a probability of
    stopping of 0.1-0.5 a visit, so that every visit's share of the exit
    mass is a number a wrong gate moves. The large ones in `cfg.dtype`;
    norms and the gate in float32."""
    L, H, F, V = cfg.num_layers, cfg.hidden_size, cfg.ffn_size, \
        cfg.vocab_size
    nh, nkv, dh = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    f32, big = "float32", cfg.dtype
    near_one, behind = Normal(1.0, 0.05), Normal(0.25, 0.0125)
    root = H ** -0.5
    return {
        "dec.word_emb": ([V, H], big, BlockedNormal(
            1.0, block_rows=_draw_rows(V))),
        "dec.lm_head": ([H, V], big, BlockedNormal(
            2.5 * root, block_rows=_draw_rows(H))),
        "dec.final_norm.scale": ([H], f32, near_one),
        "dec.exit_gate.w": ([H], f32, Normal(0.0, root)),
        "dec.exit_gate.b": ([1], f32, Constant(-1.0)),
        "attn_norm": ([L, H], f32, near_one),
        "attn_post_norm": ([L, H], f32, behind),
        "wqkv": ([L, H, (nh + 2 * nkv) * dh], big, BlockedNormal(columns=[
            (nh * dh, 2.0 * root), (nkv * dh, root), (nkv * dh, root)])),
        "wo": ([L, nh * dh, H], big, BlockedNormal((nh * dh) ** -0.5)),
        "ffn_norm": ([L, H], f32, near_one),
        "ffn_post_norm": ([L, H], f32, behind),
        "w_gate_up": ([L, H, 2 * F], big, BlockedNormal(root)),
        "w_down": ([L, F, H], big, BlockedNormal(F ** -0.5)),
    }


def build_state_copy_program(cfg: DecoderConfig, num_pages: int,
                             page_size: int, state_slots: int):
    """Build (in the current default main program) the copy of one slot of
    recurrent state onto another, in every layer and in place: taking a
    snapshot, restoring from one. Feeds sv_scopy_src/sv_scopy_dst [1]
    int32; fetches nothing."""
    src = L.data(name=SCOPY_SRC_FEED, shape=[], dtype="int32")
    dst = L.data(name=SCOPY_DST_FEED, shape=[], dtype="int32")
    _declare_ssm_pools(cfg, num_pages, page_size, state_slots)
    pools = dict(zip(("SPool", "CPool"), STATE_POOLS))
    LayerHelper("state_slot_copy").append_op(
        "state_slot_copy",
        dict({k: [v] for k, v in pools.items()}, Src=[src], Dst=[dst]),
        {k + "Out": [v] for k, v in pools.items()},
        {"num_slots": int(state_slots)})
    return {"feeds": [SCOPY_SRC_FEED, SCOPY_DST_FEED]}


# -- the composite families: ONE builder over a row of `FAMILIES` -------------

# (op slot, key prefix, keys) of the variables a composite op reads first
# (the "cca_moe" head is its embedding)
_EMB = ("Emb", "", ("dec.word_emb",))
_FINAL_NORM = ("FinalNorm", "", ("dec.final_norm.scale",))
_EMB_HEAD_NORM = (_EMB, ("Head", "", ("dec.lm_head",)), _FINAL_NORM)
# (op slot, dtype, name in a builder's result) of what a composite op writes
_TOKEN_LOGITS = (("NextToken", "int32", "next_token"),
                 ("Logits", "float32", "logits"))
_ROUTES = ("Routes", "int32", "routes")
_SELECTION = ("Selection", "int32", "selection")
_EXIT_MASS = ("ExitMass", "float32", "exit_mass")
# the op attribute that carries the size of a family's second pool
_SECOND_ATTR = {"window_pages": "window_pages", "state_slots": "num_slots"}


def _pools(cfg: DecoderConfig) -> tuple:
    """(op slot, pool) of the family's pools; the indexer's keys only behind
    an indexer ("latent_moe" without one allocates no `INDEX_POOL`)."""
    return tuple((slot, pool) for slot, pool in cfg.family.pools
                 if pool != INDEX_POOL or cfg.selects)


def _stack(cfg: DecoderConfig, mode: str, tok, pos, num_pages: int = 0,
           page_size: int = 0, second: int = 0, **feeds):
    """Append the one composite op of a program of `cfg`'s family; returns
    its outputs under their names in a builder's result, and the feeds it
    declared itself (`extra_feeds`). Parameters are created in the order of
    the row's `param_specs` in every program: that order is the startup
    program's, so the order of the weights' draw and of start-up's
    allocations (`tests/test_program_hashes.py` pins it)."""
    fam = cfg.family
    cached = mode != "full"
    extra = {}
    if mode == "decode" and cfg.selects:
        # [MARK_ROWS] int32: the rows whose selection comes back, -1 unused
        extra["Mark"] = L.data(name=MARK_FEED, shape=[MARK_ROWS],
                               dtype="int32", append_batch_size=False)
    if cached and cfg.windowed:
        # a row's compact table in the sliding layers' pool
        extra["WindowTable"] = L.data(name=WPAGES_FEED, shape=[1],
                                      dtype="int32")
        extra["WindowBase"] = L.data(name=WBASE_FEED, shape=[], dtype="int32")
    helper = LayerHelper(fam.op)
    params = {key: helper.create_parameter(
        ParamAttr(name=cca_param_name(key), initializer=init), shape, dtype)
        for key, (shape, dtype, init) in fam.param_specs(cfg).items()}
    groups = fam.groups(cfg) if callable(fam.groups) else fam.groups
    inputs = {"Tok": [tok], "Pos": [pos]}
    inputs.update({slot: [params[prefix + k] for k in keys]
                   for slot, prefix, keys in groups})
    inputs.update({slot: [var] for slot, var in {**feeds, **extra}.items()})
    # no selection to hand back without an indexer
    outputs = tuple(o for o in _TOKEN_LOGITS + fam.outputs
                    if o is not _SELECTION or cfg.selects)
    outs = {slot: [helper.create_variable_for_type_inference(dtype)]
            for slot, dtype, _ in outputs}
    attrs = dict(fam.geometry(cfg), mode=mode, num_pages=int(num_pages))
    if fam.second_pool:
        attrs[_SECOND_ATTR[fam.second_pool]] = int(second)
    if cached:
        fam.declare_pools(cfg, num_pages, page_size, second)
        if cfg.recurrent:
            extra["StateSlot"] = L.data(name=SSLOT_FEED, shape=[],
                                        dtype="int32")
            inputs["StateSlot"] = [extra["StateSlot"]]
        for slot, name in _pools(cfg):
            inputs[slot] = [name]
            outs[slot + "Out"] = [name]
    helper.append_op(fam.op, inputs, outs, attrs)
    io = {name: outs[slot][0] for slot, _, name in outputs}
    if extra:
        io["extra_feeds"] = [var.name for var in extra.values()]
    return io


def _window_io(out: dict) -> dict:
    out["last_logits"] = out.pop("logits")
    return out


def _composite_prefill(cfg, num_pages, page_size, tok, pos, pages, lens,
                       second=0):
    return _window_io(_stack(cfg, "prefill", tok, pos, num_pages, page_size,
                             second, PageTable=pages, Lens=lens))


def _composite_window(cfg, num_pages, page_size, tp, tok, pos, pages, start,
                      lens, second=0):
    # a prompt's chunk, the suffix behind a prefix hit or a resumed
    # snapshot, or a preempted row's way back: no verify window. A family
    # with a state row a page ("cca_moe") restores the row of the page
    # before Start, so Start is a page boundary
    return _window_io(_stack(cfg, "window", tok, pos, num_pages, page_size,
                             second, PageTable=pages, Start=start, Lens=lens))


def _composite_decode(cfg, num_pages, page_size, tp, tok, pos, pages, mask,
                      second=0):
    return _stack(cfg, "decode", tok, pos, num_pages, page_size, second,
                  PageTable=pages, Mask=mask)


def _composite_full(cfg, tok, pos):
    out = _stack(cfg, "full", tok, pos)
    del out["next_token"]
    return out


def _composite_cow(cfg, num_pages, page_size, src, dst, second=0):
    # the page's slab in every plane of every PAGED pool of the family: its
    # K/V or joined or latent rows, its indexer keys, its state row (a slot
    # of recurrent state is never shared: resuming from a snapshot copies
    # it, `build_state_copy_program`). `cca_state_copy_page` copies rows `l
    # * num_pages + page` of up to three pools, whatever they hold
    cfg.family.declare_pools(cfg, num_pages, page_size, second)
    paged = [pair for pair in _pools(cfg) if pair[1] not in STATE_POOLS]
    op, more = "cca_state_copy_page", {}
    pools = dict(zip(("KPool", "VPool", "SPool"), (p for _, p in paged)))
    attrs = {"num_pages": int(num_pages)}
    if cfg.windowed:
        # and a page of the sliding layers' pools, under ids of its own
        op, pools = "hybrid_copy_page", dict(paged)
        more = {"WSrc": L.data(name=COW_WSRC_FEED, shape=[], dtype="int32"),
                "WDst": L.data(name=COW_WDST_FEED, shape=[], dtype="int32")}
        attrs["window_pages"] = int(second)
    LayerHelper(op).append_op(
        op, {k: [v] for k, v in {**pools, "Src": src, "Dst": dst,
                                 **more}.items()},
        {k + "Out": [v] for k, v in pools.items()}, attrs)
    return [var.name for var in more.values()]


_COMPOSITE = {"prefill": _composite_prefill, "window": _composite_window,
              "decode": _composite_decode, "full": _composite_full,
              "cow": _composite_cow}


def _proj(x, size, name, act=None):
    return L.fc(x, size=size, num_flatten_dims=len(x.shape) - 1,
                param_attr=ParamAttr(name=name + ".w"),
                bias_attr=ParamAttr(name=name + ".b"), act=act)


def _ln(x, name):
    return L.layer_norm(x, begin_norm_axis=2,
                        param_attr=ParamAttr(name=name + ".scale"),
                        bias_attr=ParamAttr(name=name + ".bias"))


def _embed(tok, pos, cfg: DecoderConfig):
    emb = L.embedding(tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=ParamAttr(name="dec.word_emb"),
                      dtype=cfg.dtype)
    pe = L.embedding(pos, size=[cfg.max_position, cfg.hidden_size],
                     param_attr=ParamAttr(name="dec.pos_emb"),
                     dtype=cfg.dtype)
    return _ln(L.elementwise_add(emb, pe), "dec.emb_ln")


def _ffn_block(x, cfg: DecoderConfig, name):
    h = _proj(x, cfg.ffn_size, name + ".ffn.in", act="gelu")
    f = _proj(h, cfg.hidden_size, name + ".ffn.out")
    return _ln(L.elementwise_add(x, f), name + ".ln2")


def _qkv_heads_seq(x, cfg: DecoderConfig, name):
    """[B, S, H] -> q, k, v each [B, nh, S, dh] (prefill / full forward)."""
    nh, dh = cfg.num_heads, cfg.head_dim
    qkv = _proj(x, 3 * cfg.hidden_size, name + ".qkv")
    qkv = L.reshape(qkv, shape=[0, 0, 3, nh, dh])
    qkv = L.transpose(qkv, perm=[2, 0, 3, 1, 4])       # [3, B, nh, S, dh]
    q = L.squeeze(L.slice(qkv, axes=[0], starts=[0], ends=[1]), axes=[0])
    k = L.squeeze(L.slice(qkv, axes=[0], starts=[1], ends=[2]), axes=[0])
    v = L.squeeze(L.slice(qkv, axes=[0], starts=[2], ends=[3]), axes=[0])
    return q, k, v


def _head(x, cfg: DecoderConfig):
    return _proj(x, cfg.vocab_size, "dec.lm_head")


def _greedy(logits_2d):
    return L.argmax(logits_2d, axis=1)


def _layer_names(i: int) -> str:
    return f"dec.layer{i}"


def _prefill_layer(x, i, cfg: DecoderConfig, pages, lens, write_cache: bool):
    name = _layer_names(i)
    nh, dh = cfg.num_heads, cfg.head_dim
    q, k, v = _qkv_heads_seq(x, cfg, name + ".mha")
    if write_cache:
        kn, vn = pool_var_names(cfg.num_layers)[i]
        helper = LayerHelper("kv_cache_prefill_write")
        helper.append_op(
            "kv_cache_prefill_write",
            {"KPool": [kn], "VPool": [vn], "K": [k], "V": [v],
             "PageTable": [pages], "Lens": [lens]},
            {"KPoolOut": [kn], "VPoolOut": [vn]}, {})
    ctxv = L.fused_attention(q, k, v, causal=True, sm_scale=dh ** -0.5)
    ctxv = L.reshape(L.transpose(ctxv, perm=[0, 2, 1, 3]),
                     shape=[0, 0, cfg.hidden_size])
    a = _proj(ctxv, cfg.hidden_size, name + ".mha.out")
    x = _ln(L.elementwise_add(x, a), name + ".ln1")
    return _ffn_block(x, cfg, name)


def _second_pool(window_pages: int, state_slots: int = 0) -> dict:
    """The size of a family's second pool (`Family.second_pool` says which
    of the two it has), as the keyword its builders take it under."""
    size = int(state_slots or window_pages)
    return {"second": size} if size else {}


def _last_token_state(last_token: str, token_slots: int):
    """Declare the `LAST_TOKEN` state (under the engine's name for it) and
    the feed that names each row's slot in it."""
    default_main_program().global_block.create_var(
        name=last_token, shape=[int(token_slots) + 1], dtype="int32",
        persistable=True, stop_gradient=True)
    return L.data(name=SLOT_FEED, shape=[], dtype="int32")


def _keep_last_token(io: dict, last_token: str, slot, feeds: list) -> dict:
    """Append the write of the step's `next_token` to its rows' slots; the
    family body's outputs plus the program's feed names: the shared ones,
    any the family declared itself (`extra_feeds`) and the slot feed."""
    LayerHelper("last_token_write").append_op(
        "last_token_write",
        {"Last": [last_token], "Slot": [slot], "Next": [io["next_token"]]},
        {"LastOut": [last_token]}, {})
    io = dict(io)
    return dict(io, feeds=feeds + io.pop("extra_feeds", []) + [SLOT_FEED])


def build_prefill_program(cfg: DecoderConfig, num_pages: int, page_size: int,
                          window_pages: int = 0, token_slots: int = 1,
                          last_token: str = LAST_TOKEN,
                          state_slots: int = 0):
    """Build (in the current default main program) the bucketed prefill.

    Feeds: sv_tok/sv_pos [B, S_bucket] int32, sv_pages [B, P] int32,
    sv_len [B] int32 (real prompt lengths — bucket padding past them is
    never written to the cache and, thanks to causal masking, never read by
    a real position), sv_slot [B] int32 (where the next token is kept on
    the device, `LAST_TOKEN`). Fetch: next token ids [B] (greedy)."""
    tok = L.data(name=TOK_FEED, shape=[cfg.max_position], dtype="int32")
    pos = L.data(name=POS_FEED, shape=[cfg.max_position], dtype="int32")
    pages = L.data(name=PAGES_FEED, shape=[1], dtype="int32")
    lens = L.data(name=LEN_FEED, shape=[], dtype="int32")
    slot = _last_token_state(last_token, token_slots)
    return _keep_last_token(cfg.family.builders["prefill"](
        cfg, num_pages, page_size, tok, pos, pages, lens,
        **_second_pool(window_pages, state_slots)), last_token, slot,
        [TOK_FEED, POS_FEED, PAGES_FEED, LEN_FEED])


def _post_ln_prefill(cfg, num_pages, page_size, tok, pos, pages, lens):
    declare_pool_vars(default_main_program().global_block, cfg.num_layers,
                      num_pages, page_size, cfg.num_heads, cfg.head_dim,
                      cfg.dtype)
    x = _embed(tok, pos, cfg)
    for i in range(cfg.num_layers):
        x = _prefill_layer(x, i, cfg, pages, lens, write_cache=True)
    logits = _head(x, cfg)                             # [B, S, V]
    helper = LayerHelper("gather_token_logits")
    last = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op("gather_token_logits",
                     {"X": [logits], "Lens": [lens]}, {"Out": [last]}, {})
    return {"next_token": _greedy(last), "last_logits": last}


def _window_layer(x, i, cfg: DecoderConfig, pages, start, lens, tp: int):
    """One decoder layer over a WINDOW of S query tokens whose context lives
    in the paged pool: write the window's K/V at slots start+s (s < lens,
    local), then attend over the pool — cached prefix, fresh window and all.
    Shared by suffix prefill (ISSUE 11 prefix caching) and the speculative
    verify step (S = draft k + 1)."""
    name = _layer_names(i)
    dh = cfg.head_dim
    q, k, v = _qkv_heads_seq(x, cfg, name + ".mha")
    kn, vn = pool_var_names(cfg.num_layers)[i]
    helper = LayerHelper("kv_cache_prefill_write")
    helper.append_op(
        "kv_cache_prefill_write",
        {"KPool": [kn], "VPool": [vn], "K": [k], "V": [v],
         "PageTable": [pages], "Lens": [lens], "Start": [start]},
        {"KPoolOut": [kn], "VPoolOut": [vn]}, {})
    helper = LayerHelper("paged_prefill_attention")
    att = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "paged_prefill_attention",
        {"Q": [q], "KPool": [kn], "VPool": [vn],
         "PageTable": [pages], "Start": [start]},
        {"Out": [att]}, {"sm_scale": dh ** -0.5, "tp_degree": tp})
    ctxv = L.reshape(L.transpose(att, perm=[0, 2, 1, 3]),
                     shape=[0, 0, cfg.hidden_size])
    a = _proj(ctxv, cfg.hidden_size, name + ".mha.out")
    x = _ln(L.elementwise_add(x, a), name + ".ln1")
    return _ffn_block(x, cfg, name)


def build_window_program(cfg: DecoderConfig, num_pages: int, page_size: int,
                         tp: int = 1, window_pages: int = 0,
                         token_slots: int = 1,
                         last_token: str = LAST_TOKEN,
                         state_slots: int = 0):
    """Build (in the current default main program) the windowed forward the
    two ISSUE 11 stages share:

      * suffix prefill — a prompt whose first Start slots are already in
        the pool (prefix-cache hit) runs ONLY its uncached suffix through
        the model; the window's K/V is appended at slots Start+s and the
        window attends over the whole pooled context, so the prefill
        compute drops from O(prompt) to O(suffix);
      * speculative verify — S = k+1 query tokens per row ([last_token,
        draft_1..draft_k]) in ONE batched step; `tokens` holds the greedy
        next token at every window position, which the engine compares
        against the drafts for exact greedy acceptance.

    Feeds: sv_tok/sv_pos [B, S] int32, sv_pages [B, P] int32, sv_start [B]
    int32 (global slot of window position 0), sv_len [B] int32 (valid LOCAL
    window positions; 0 = padded row, writes nothing), sv_slot [B] int32
    (where `next_token` is kept on the device, `LAST_TOKEN`). Fetches:
    `next_token` [B] (greedy token after local position Lens-1 — the suffix
    prefill's output), `tokens` [B, S] (greedy token after every window
    position — the verify output), `logits` [B, S, V] (the sampling
    suite's input)."""
    tok = L.data(name=TOK_FEED, shape=[cfg.max_position], dtype="int32")
    pos = L.data(name=POS_FEED, shape=[cfg.max_position], dtype="int32")
    pages = L.data(name=PAGES_FEED, shape=[1], dtype="int32")
    start = L.data(name=START_FEED, shape=[], dtype="int32")
    lens = L.data(name=LEN_FEED, shape=[], dtype="int32")
    slot = _last_token_state(last_token, token_slots)
    return _keep_last_token(cfg.family.builders["window"](
        cfg, num_pages, page_size, tp, tok, pos, pages, start, lens,
        **_second_pool(window_pages, state_slots)), last_token, slot,
        [TOK_FEED, POS_FEED, PAGES_FEED, START_FEED, LEN_FEED])


def _post_ln_window(cfg, num_pages, page_size, tp, tok, pos, pages, start,
                    lens):
    declare_pool_vars(default_main_program().global_block, cfg.num_layers,
                      num_pages, page_size, cfg.num_heads, cfg.head_dim,
                      cfg.dtype)
    x = _embed(tok, pos, cfg)
    for i in range(cfg.num_layers):
        x = _window_layer(x, i, cfg, pages, start, lens, tp)
    logits = _head(x, cfg)                             # [B, S, V]
    helper = LayerHelper("gather_token_logits")
    last = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op("gather_token_logits",
                     {"X": [logits], "Lens": [lens]}, {"Out": [last]}, {})
    return {"next_token": _greedy(last),
            "last_logits": last,
            "tokens": L.argmax(logits, axis=2),
            "logits": logits}


def build_cow_program(cfg: DecoderConfig, num_pages: int, page_size: int,
                      window_pages: int = 0, state_slots: int = 0):
    """Build (in the current default main program) the copy-on-write step:
    one `kv_cache_copy_page` per layer — pool[Dst] := pool[Src] for K and V,
    in place. Feeds sv_cow_src/sv_cow_dst [1] int32; fetches nothing (the
    pools are the output, via the donation contract). Compiled exactly once
    per engine — COW cost is one tiny device step, not a recompile."""
    src = L.data(name=COW_SRC_FEED, shape=[], dtype="int32")
    dst = L.data(name=COW_DST_FEED, shape=[], dtype="int32")
    more = cfg.family.builders["cow"](
        cfg, num_pages, page_size, src, dst,
        **_second_pool(window_pages, state_slots))
    return {"feeds": [COW_SRC_FEED, COW_DST_FEED] + (more or [])}


def _post_ln_cow(cfg, num_pages, page_size, src, dst):
    declare_pool_vars(default_main_program().global_block, cfg.num_layers,
                      num_pages, page_size, cfg.num_heads, cfg.head_dim,
                      cfg.dtype)
    for kn, vn in pool_var_names(cfg.num_layers):
        helper = LayerHelper("kv_cache_copy_page")
        helper.append_op(
            "kv_cache_copy_page",
            {"KPool": [kn], "VPool": [vn], "Src": [src], "Dst": [dst]},
            {"KPoolOut": [kn], "VPoolOut": [vn]}, {})


def build_decode_program(cfg: DecoderConfig, num_pages: int, page_size: int,
                         tp: int = 1, window_pages: int = 0,
                         token_slots: int = 1,
                         last_token: str = LAST_TOKEN,
                         state_slots: int = 0):
    """Build (in the current default main program) the ragged decode step.

    Feeds: sv_tok [B, 1] int32 (each row's latest token), sv_pos [B] int32
    (the slot that token occupies — the row's context length so far),
    sv_pages [B, P] int32, batch_mask [B, 1] float32 (0 rows are scheduler
    padding: their KV write is dropped and their output token ignored),
    sv_slot [B] int32 and sv_from_host [B, 1] int32: a row whose
    sv_from_host is 0 takes its token from `LAST_TOKEN[sv_slot]`, where the
    step before left it, not from sv_tok; every row's next token is kept
    there in turn. Fetch: next token ids [B]."""
    tok = L.data(name=TOK_FEED, shape=[], dtype="int32")
    pos = L.data(name=POS_FEED, shape=[], dtype="int32")
    pages = L.data(name=PAGES_FEED, shape=[1], dtype="int32")
    mask = L.data(name=MASK_FEED, shape=[1], dtype="float32")
    slot = _last_token_state(last_token, token_slots)
    from_host = L.data(name=FROM_HOST_FEED, shape=[1], dtype="int32")
    helper = LayerHelper("last_token_select")
    chained = helper.create_variable_for_type_inference("int32")
    helper.append_op("last_token_select",
                     {"Tok": [tok], "FromHost": [from_host], "Slot": [slot],
                      "Last": [last_token]}, {"Out": [chained]}, {})
    return _keep_last_token(cfg.family.builders["decode"](
        cfg, num_pages, page_size, tp, chained, pos, pages, mask,
        **_second_pool(window_pages, state_slots)), last_token, slot,
        [TOK_FEED, POS_FEED, PAGES_FEED, MASK_FEED, FROM_HOST_FEED])


def _post_ln_decode(cfg, num_pages, page_size, tp, tok, pos, pages, mask):
    declare_pool_vars(default_main_program().global_block, cfg.num_layers,
                      num_pages, page_size, cfg.num_heads, cfg.head_dim,
                      cfg.dtype)
    nh, dh = cfg.num_heads, cfg.head_dim
    # flat [B] ids (a [B, 1] feed would hit lookup_table's trailing-1 LoD
    # squeeze and come back 2-D); the singleton seq dim reappears after
    emb = L.embedding(tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=ParamAttr(name="dec.word_emb"),
                      dtype=cfg.dtype)                 # [B, H]
    pe = L.embedding(pos, size=[cfg.max_position, cfg.hidden_size],
                     param_attr=ParamAttr(name="dec.pos_emb"),
                     dtype=cfg.dtype)
    x = L.unsqueeze(L.elementwise_add(emb, pe), axes=[1])   # [B, 1, H]
    x = _ln(x, "dec.emb_ln")
    for i in range(cfg.num_layers):
        name = _layer_names(i)
        qkv = _proj(x, 3 * cfg.hidden_size, name + ".mha.qkv")  # [B, 1, 3H]
        qkv = L.reshape(qkv, shape=[0, 3, nh, dh])
        q = L.squeeze(L.slice(qkv, axes=[1], starts=[0], ends=[1]), axes=[1])
        k = L.squeeze(L.slice(qkv, axes=[1], starts=[1], ends=[2]), axes=[1])
        v = L.squeeze(L.slice(qkv, axes=[1], starts=[2], ends=[3]), axes=[1])
        kn, vn = pool_var_names(cfg.num_layers)[i]
        helper = LayerHelper("kv_cache_append")
        helper.append_op(
            "kv_cache_append",
            {"KPool": [kn], "VPool": [vn], "K": [k], "V": [v],
             "PageTable": [pages], "Positions": [pos], "Mask": [mask]},
            {"KPoolOut": [kn], "VPoolOut": [vn]}, {})
        helper = LayerHelper("paged_decode_attention")
        att = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op(
            "paged_decode_attention",
            {"Q": [q], "KPool": [kn], "VPool": [vn],
             "PageTable": [pages], "Positions": [pos]},
            {"Out": [att]}, {"sm_scale": dh ** -0.5, "tp_degree": tp})
        a = _proj(L.reshape(att, shape=[0, 1, cfg.hidden_size]),
                  cfg.hidden_size, name + ".mha.out")
        x = _ln(L.elementwise_add(x, a), name + ".ln1")
        x = _ffn_block(x, cfg, name)
    logits = L.squeeze(_head(x, cfg), axes=[1])        # [B, V]
    return {"next_token": _greedy(logits), "logits": logits}


# per-dim mesh-axis layout of the decoder's TP-sharded parameters
# (Megatron-style: qkv/ffn-in split their OUTPUT features, the projections
# back to hidden split their INPUT features so the row-parallel matmul's
# psum is the only collective per block). GSPMD treats these as layout
# hints, never correctness: an unannotated or oddly-divisible tensor simply
# replicates.
_TP_PARAM_LAYOUT = [
    (".mha.qkv.w", (None, "{tp}")), (".mha.qkv.b", ("{tp}",)),
    (".mha.out.w", ("{tp}", None)),
    (".ffn.in.w", (None, "{tp}")), (".ffn.in.b", ("{tp}",)),
    (".ffn.out.w", ("{tp}", None)),
]


def apply_tp_annotations(program, cfg: DecoderConfig, tp: int) -> int:
    """Annotate a built serving program's vars for tensor parallelism over
    the `tp` mesh axis (parallel/mesh.MODEL_AXIS): attention/FFN weights
    per _TP_PARAM_LAYOUT and the KV pool vars on their last dim
    `[.., nh*dh]`, in which heads are contiguous, so a tp shard holds whole
    heads — the layout "Ragged Paged Attention" (arXiv:2604.15464)
    head-sharded decode assumes. Returns how many vars were annotated.
    Dims that `tp` does not divide are left replicated (GSPMD stays correct
    either way)."""
    from ..parallel.mesh import MODEL_AXIS
    from ..parallel.sharding import annotate_sharding

    done = 0
    block = program.global_block
    for name, var in block.vars.items():
        for suffix, spec in _TP_PARAM_LAYOUT:
            if not name.endswith(suffix):
                continue
            axes = tuple(MODEL_AXIS if a == "{tp}" else a for a in spec)
            if all(a is None or (var.shape[d] % tp == 0)
                   for d, a in enumerate(axes)):
                annotate_sharding(var, axes)
                done += 1
        if name.startswith("kv_cache.") and cfg.num_heads % tp == 0:
            annotate_sharding(var, (None, None, MODEL_AXIS))
            done += 1
    return done


def build_full_forward_program(cfg: DecoderConfig):
    """The dense no-cache oracle: feeds sv_tok/sv_pos [B, S], fetches the
    all-position logits [B, S, V]. Same weight names as the serving
    programs, so running it in the engine's scope replays generation
    exactly (tests, and the debugging path for kernel mismatches)."""
    tok = L.data(name=TOK_FEED, shape=[cfg.max_position], dtype="int32")
    pos = L.data(name=POS_FEED, shape=[cfg.max_position], dtype="int32")
    return dict(cfg.family.builders["full"](cfg, tok, pos),
                feeds=[TOK_FEED, POS_FEED])


def _post_ln_full(cfg, tok, pos):
    x = _embed(tok, pos, cfg)
    for i in range(cfg.num_layers):
        x = _prefill_layer(x, i, cfg, None, None, write_cache=False)
    return {"logits": _head(x, cfg)}


@dataclass(frozen=True)
class Family:
    """What a block family IS, for everything in `serving/` that has to know:
    one row of `FAMILIES`. A composite family (every one but "post_ln") is
    ONE op that `_stack` appends to each program, and its row describes that
    op; the facts `DecoderConfig`'s properties (which say what each means)
    and the engine ask for stand beside it, as functions of `cfg` where the
    configuration's values decide. A new family is a row, its `_geometry`,
    `_param_specs` and `validate`."""

    op: str = ""    # the composite op's type; "": `builders` of its own
    geometry: Callable = None       # cfg -> the op's attributes
    # cfg -> {key: (shape, dtype, initializer)}, in the order of the draw
    param_specs: Callable = None
    validate: Callable = lambda cfg: None       # raises ValueError
    # the op's parameter inputs in its order, (op slot, key prefix, keys) a
    # slot (or cfg -> those rows)
    groups: tuple | Callable = ()
    # (op slot, dtype, name in a builder's result) of what the op writes
    # beside the next token and the logits; a window hands all of them back
    outputs: tuple = ()
    # (op slot, pool) of the pools the op updates in place, and what
    # declares them from `pool_geometry`'s answer
    pools: tuple = ()
    pool_geometry: Callable = None
    declare_pools: Callable = _declare_paged_pools
    # what a sequence holds beside its pages: "" | "window_pages" (sliding
    # layers' K/V under page ids of their own: `windowed`) | "state_slots"
    # (a state every token rewrites in place: `recurrent`)
    second_pool: str = ""
    # the five program bodies the `build_*` functions hand over to
    builders: MappingProxyType = MappingProxyType(_COMPOSITE)
    stateful: bool = False
    cache_planes: Callable = lambda cfg: cfg.num_layers
    state_layers: Callable = lambda cfg: 0
    routed_layers: Callable = lambda cfg: cfg.num_layers
    latent_layers: Callable = lambda cfg: 0
    selects: Callable = lambda cfg: False
    page_bucket_step: int = 0
    one_page_bucket: bool = False
    # the engine's: which counters book a state update ("ssm" | "kda");
    # (cfg, rows, state pool's shape) -> whether the Pallas kernel updates a
    # decode step's states; whether a window's expert calls may take the
    # grouped form; whether a step counts visits of a loop
    state_kind: str = "ssm"
    state_update_runs: Callable = None
    grouped_experts: bool = False
    loop_visits: bool = False


def _ssm_update_runs(cfg, rows, pool):
    return parallel_ssm_ops.ssm_update_runs(
        rows, pool, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
        cfg.ssm_state)


_KV_POOLS = tuple(zip(("KPool", "VPool"), STACKED_POOLS))
_STATE_POOLS = tuple(zip(("SPool", "CPool"), STATE_POOLS))

FAMILIES = {
    "post_ln": Family(builders=MappingProxyType({
        "prefill": _post_ln_prefill, "window": _post_ln_window,
        "cow": _post_ln_cow, "decode": _post_ln_decode,
        "full": _post_ln_full})),
    "cca_moe": Family(
        op="cca_moe_stack", geometry=_cca_geometry,
        param_specs=_cca_param_specs, validate=_validate_cca,
        groups=(
            _EMB, _FINAL_NORM, ("LayerParams", "", cca_moe_ops.LAYER_PARAMS),
            ("Experts", "", cca_moe_ops.EXPERT_PARAMS)),
        outputs=(_ROUTES,), pools=_KV_POOLS + (("SPool", STACKED_POOLS[2]),),
        pool_geometry=_cca_pool_geometry, stateful=True,
        grouped_experts=True),
    "sparse_moe": Family(
        op="sparse_moe_stack", geometry=_sparse_geometry,
        param_specs=_sparse_param_specs, validate=_validate_sparse,
        groups=_EMB_HEAD_NORM + (
            ("LayerParams", "", sparse_moe_ops.LAYER_PARAMS),
            ("Experts", "", sparse_moe_ops.EXPERT_PARAMS)),
        outputs=(_ROUTES, _SELECTION),
        # a token's K and V in one row of one pool: this block gathers tokens
        pools=(("KVPool", JOINED_POOL), ("IPool", INDEX_POOL)),
        pool_geometry=_sparse_pool_geometry,
        selects=lambda cfg: True, page_bucket_step=32, grouped_experts=True),
    "hybrid_moe": Family(
        op="hybrid_moe_stack", geometry=lambda cfg: dict(
            _hybrid_geometry(cfg),
            plan=[str(v) for layer in layer_plan(cfg) for v in layer]),
        param_specs=_hybrid_param_specs, validate=_validate_hybrid,
        groups=_EMB_HEAD_NORM + (
            ("LayerParams", "", hybrid_moe_ops.LAYER_PARAMS),
            ("FullParams", "full.", hybrid_moe_ops.ATTENTION_PARAMS),
            ("SlideParams", "slide.", hybrid_moe_ops.ATTENTION_PARAMS),
            ("DenseParams", "dense.", hybrid_moe_ops.DENSE_PARAMS),
            ("MoeParams", "moe.", hybrid_moe_ops.MOE_PARAMS),
            ("Experts", "", hybrid_moe_ops.EXPERT_PARAMS)),
        outputs=(_ROUTES,),
        pools=_KV_POOLS + tuple(zip(("WKPool", "WVPool"), WINDOW_POOLS)),
        pool_geometry=hybrid_pool_geometry,
        declare_pools=_declare_hybrid_pools, second_pool="window_pages",
        routed_layers=lambda cfg: sum(
            kind == "sparse" for kind in cfg.mlp_layer_types),
        # its layers are unrolled: a compile of every layer for each (row
        # bucket, page bucket) program (ten page buckets below 19k tokens
        # are seventy decode programs), and its paged decode kernels pass a
        # block of dead pages in a grid step of a third of a microsecond
        one_page_bucket=True, grouped_experts=True),
    "parallel_ssm": Family(
        op="parallel_ssm_stack", geometry=_ssm_geometry,
        param_specs=_ssm_param_specs, validate=_validate_ssm,
        groups=_EMB_HEAD_NORM + (
            ("LayerParams", "", parallel_ssm_ops.LAYER_PARAMS),),
        pools=_KV_POOLS + _STATE_POOLS, pool_geometry=_ssm_pool_geometry,
        declare_pools=_declare_ssm_pools, second_pool="state_slots",
        state_layers=lambda cfg: cfg.num_layers,
        routed_layers=lambda cfg: 0,
        # every layer compiles once (a scan) but a step streams 7.8 GB of
        # weights whatever the table's width: six page buckets would be six
        # times the programs to warm for nothing a step could gain
        one_page_bucket=True, state_update_runs=_ssm_update_runs),
    "latent_moe": Family(
        op="latent_moe_stack", geometry=_latent_geometry,
        param_specs=_latent_param_specs, validate=_validate_latent,
        groups=_latent_groups, outputs=(_ROUTES, _SELECTION),
        # a token's latent and rotary key in one row of one pool and, behind
        # an indexer, its indexer key
        pools=(("LatentPool", LATENT_POOL), ("IPool", INDEX_POOL)),
        pool_geometry=_latent_pool_geometry,
        routed_layers=lambda cfg: cfg.num_layers - cfg.dense_layers,
        latent_layers=lambda cfg: cfg.num_layers,
        selects=lambda cfg: cfg.index_topk > 0, page_bucket_step=32,
        grouped_experts=True),
    "mixer_moe": Family(
        op="mixer_moe_stack", geometry=_mixer_geometry,
        param_specs=_mixer_param_specs, validate=_validate_mixer,
        groups=_EMB_HEAD_NORM + (("Norms", "", ("norm",)),) + _MIXER_GROUPS,
        outputs=(_ROUTES,), pools=_KV_POOLS + _STATE_POOLS,
        pool_geometry=_mixer_pool_geometry,
        declare_pools=_declare_ssm_pools, second_pool="state_slots",
        state_layers=lambda cfg: cfg.layer_pattern.count(
            mixer_moe_ops.MIXER),
        routed_layers=lambda cfg: cfg.layer_pattern.count(
            mixer_moe_ops.EXPERTS),
        one_page_bucket=True, state_update_runs=_ssm_update_runs),
    "kda_moe": Family(
        op="kda_moe_stack", geometry=_kda_geometry,
        param_specs=_kda_param_specs, validate=_validate_kda,
        groups=_EMB_HEAD_NORM + (("Norms", "", ("norm",)),) + _KDA_GROUPS,
        outputs=(_ROUTES,),
        pools=(("LatentPool", LATENT_POOL),) + _STATE_POOLS,
        pool_geometry=_kda_pool_geometry, declare_pools=_declare_ssm_pools,
        second_pool="state_slots",
        state_layers=lambda cfg: cfg.num_layers - cfg.latent_layers,
        routed_layers=lambda cfg: cfg.num_layers - cfg.dense_layers,
        latent_layers=lambda cfg: cfg.num_layers // cfg.layer_group_size,
        page_bucket_step=32, one_page_bucket=True, state_kind="kda",
        state_update_runs=lambda cfg, rows, pool: kda_ops.kda_update_runs(
            pool, cfg.ssm_state),
        grouped_experts=True),
    "looped_dense": Family(
        op="looped_dense_stack", geometry=_looped_geometry,
        param_specs=_looped_param_specs, validate=_validate_looped,
        groups=_EMB_HEAD_NORM + (
            ("GateW", "", ("dec.exit_gate.w",)),
            ("GateB", "", ("dec.exit_gate.b",)),
            ("LayerParams", "", looped_dense_ops.LAYER_PARAMS)),
        outputs=(_EXIT_MASS,), pools=_KV_POOLS,
        pool_geometry=_looped_pool_geometry,
        # a plane a VISIT of a layer, each with K/V pages of its own
        cache_planes=lambda cfg: cfg.num_layers * cfg.loop_steps,
        routed_layers=lambda cfg: 0,
        # 16 pages: whole grid steps of its paged decode kernel at pages of
        # 16, 32 or 64 tokens (16, 8 or 4 pages a step:
        # `paged_attention.pages_per_grid_step`), so its one page bucket is
        # the context cap's own width and not a block of dead steps wider
        page_bucket_step=16,
        # the family whose POOL bounds the rows in flight: a row that lost
        # its pages comes back as a prompt of its own prompt and everything
        # it had produced, at lengths no arrival has, and under one width
        # its windows are the programs the arrivals' windows compiled (a
        # window's length is its only other shape)
        one_page_bucket=True, loop_visits=True),
}
