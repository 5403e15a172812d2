"""LLM serving runtime: paged KV cache + continuous batching + ragged
paged decode attention ("Ragged Paged Attention", arXiv:2604.15464 for the
kernel, "Tensor Processing Primitives", arXiv:2104.05755 for the
reusable-primitive framing).

Five pieces, one runtime:
  * `kv_cache`   — fixed-size pages over a preallocated HBM pool (device
                   side: persistable pool vars the compiled steps update in
                   place; host side: refcounted free-list + per-request page
                   tables + the page-granular PrefixCache that lets requests
                   sharing a system prompt map the SAME physical pages);
  * `model`      — the served decoder expressed as bucketed prefill /
                   windowed suffix-prefill+verify / ragged decode programs
                   over one explicit weight namespace (plus the dense
                   oracle for equivalence tests, the COW page-copy step,
                   and the GSPMD tp annotations). NINE block families,
                   selected by `DecoderConfig.block` (`model`'s docstring
                   has each in full; three are told here, and
                   `"hybrid_moe"`, `"parallel_ssm"`, `"latent_moe"`,
                   `"mixer_moe"`, `"kda_moe"` and `"looped_dense"` there: layers of more
                   than one shape over two pools, a recurrent state in a
                   pool of slots, a latent cache row a token read through
                   the indexer with a share of the experts held, layers
                   that are a mixer, an attention or experts in a latent
                   ALONE, each pool sized by the count of its kind, and
                   linear-attention layers whose matrix state lives in a
                   slot beside a latent-attention layer's paged rows, and
                   layers a token passes several times, each visit with
                   K/V pages of its own): `"post_ln"` (the
                   default: BERT-base run causally; fields vocab_size,
                   hidden_size, num_layers, num_heads, ffn_size,
                   max_position, dtype) and `"cca_moe"` (ZAYA1's layer:
                   compressed-convolutional grouped-query attention with
                   carried convolution state, partial rotary, top-1 experts
                   behind an MLP router, RMSNorm, tied head; adds
                   num_kv_heads, attn_head_dim, num_experts,
                   router_hidden_size, cca_time0/1, partial_rotary_factor,
                   rope_theta, rms_norm_eps) and `"sparse_moe"` (RMSNorm
                   pre-norm, grouped-query attention over the index_topk
                   cached positions a learned indexer scores highest, a
                   renormalised top-k mixture of experts behind a linear
                   router, untied head; adds experts_per_token,
                   index_heads, index_head_dim, index_topk,
                   prefill_chunk). The second keeps one state row a page
                   beside the K/V pools, the third one indexer key a
                   token beside ONE pool that holds a token's K and V as
                   one row; both report the experts they chose with every
                   step's tokens, the third also the positions it attended
                   (engine docstring);
  * `engine`     — the continuous-batching scheduler: admit/evict between
                   decode steps, copy-on-write prefix reuse, speculative
                   draft-verify decode (exact under greedy), backpressure
                   on pool exhaustion, recompute-style preemption,
                   chaos-abort page reclamation with refcount accounting;
  * `sampling`   — per-request temperature/top-k/top-p with per-(seed,
                   request, token) determinism across batch-bucket
                   recompiles;
  * `fleet`      — N engine replicas behind one router: heartbeat health
                   checking, prefix-affinity placement, failover replay
                   with exactly-once token delivery, drain-and-retire
                   (FLAGS_fleet_*, README "Serving fleet").

Knobs: FLAGS_serving_page_size, FLAGS_serving_pool_pages,
FLAGS_serving_max_inflight, FLAGS_serving_sched_policy,
FLAGS_serving_prefix_cache, FLAGS_serving_draft_k, FLAGS_serving_tp (see
README "Serving"), each read once, when an engine is built. Load: the serving cells of BENCHMARK.json
(benchmark/runners/serve_open_loop.py drives an engine open loop from
benchmark/traffic/open_loop.py's schedules).
"""
from .engine import (AdmissionRejected, ContinuousBatchingScheduler,
                     GenRequest, ServingEngine, ngram_draft)
from .kv_cache import (OwnedPoolView, PagedKVPool, PrefixCache,
                       create_device_pools, pool_var_names)
from .model import (DecoderConfig, build_decode_program,
                    build_full_forward_program, build_prefill_program,
                    build_window_program, cca_moe_tiny, decoder_tiny)
from .sampling import SamplingParams, sample_token
from .fleet import (EngineReplica, FleetRequest, FleetRouter,
                    HandoffManager, KVLease, NoHealthyReplica,
                    disagg_fleet_factory)

__all__ = [
    "EngineReplica", "FleetRouter", "FleetRequest", "NoHealthyReplica",
    "HandoffManager", "KVLease", "disagg_fleet_factory",
    "ServingEngine", "GenRequest", "ContinuousBatchingScheduler",
    "AdmissionRejected", "OwnedPoolView",
    "PagedKVPool", "PrefixCache", "pool_var_names", "create_device_pools",
    "DecoderConfig", "decoder_tiny", "cca_moe_tiny", "build_prefill_program",
    "build_decode_program", "build_window_program",
    "build_full_forward_program", "SamplingParams", "sample_token",
    "ngram_draft",
]
