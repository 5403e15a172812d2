"""Framework-wide autotuner: a persistent per-(op, shape, dtype, device_kind)
decision cache with measured A/B sweeps.

Every per-shape perf lever resolves through ONE three-tier policy
(`policy.decide`) —

    exact swept-DB hit  ->  analytic prior  ->  conservative default

Levers wired through it: conv2d lowering (direct vs implicit-GEMM, incl.
1x1-as-matmul), attention backend (XLA fusion vs the short-seq Pallas
kernel vs the bundled flash kernel), conv+BN epilogue fusion
(passes.fuse_conv_bn_stats), AMP gray-op list membership, feed-bucketing
boundaries, the top-1 expert kernel and the state-space update kernel. The
DB is populated offline by `tools/tune.py` (median-of-windows timing,
interference band, keep-or-retire verdict per shape) and consulted at
minimize()/trace time under FLAGS_tuning_mode=consult;
`provenance_snapshot()` says how much of a run resolved on swept decisions.
With the mode off (the default) each lever runs its own shape rule, and
`tests/test_kernel_choice.py` pins the arm every benchmark configuration
gets from it.
"""
from .db import (DB_SCHEMA, TuningDB, amp_key, attention_key, bucket_key,
                 canonical_key, collective_key, conv_key, embedding_key,
                 epilogue_key, evidence, moe_experts_key,
                 ssm_update_key)
from .policy import (decide, device_kind, get_db, invalidate_db_cache, mode,
                     on_minimize, provenance_snapshot, reset_provenance,
                     sweep_enabled)

__all__ = [
    "DB_SCHEMA", "TuningDB", "canonical_key", "conv_key", "attention_key",
    "bucket_key", "amp_key", "collective_key", "epilogue_key",
    "embedding_key", "moe_experts_key", "ssm_update_key", "evidence",
    "decide", "mode", "sweep_enabled", "get_db", "invalidate_db_cache",
    "device_kind", "provenance_snapshot", "reset_provenance", "on_minimize",
]
