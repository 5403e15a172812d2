"""The post-LN transformer block both references share, in plain float32
`jax.numpy`: fused QKV projection, softmax attention, exact (erf) GELU,
LayerNorm eps 1e-5 — as `paddle_tpu.models.transformer` and
`paddle_tpu.serving.model` build it (the departures from the published
BERT are listed in the configs' `departures`). No kernel, no cache, no
mixed precision; callers hold `jax.default_matmul_precision("highest")`.
Layers are stacked on a leading axis and scanned, so the reference compiles
in seconds whatever the depth.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
LAYER_KEYS = ("qkv_w", "qkv_b", "out_w", "out_b", "ln1_w", "ln1_b",
              "in_w", "in_b", "ffn_w", "ffn_b", "ln2_w", "ln2_b")


def layer_norm(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


def _block(x, p, num_heads: int, causal: bool):
    b, s, h = x.shape
    dh = h // num_heads
    qkv = (x @ p["qkv_w"] + p["qkv_b"]).reshape(b, s, 3, num_heads, dh)
    q, k, v = (jnp.transpose(qkv[:, :, i], (0, 2, 1, 3)) for i in range(3))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * dh ** -0.5
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    ctx = jnp.transpose(ctx, (0, 2, 1, 3)).reshape(b, s, h)
    x = layer_norm(x + ctx @ p["out_w"] + p["out_b"], p["ln1_w"], p["ln1_b"])
    f = jax.nn.gelu(x @ p["in_w"] + p["in_b"], approximate=False)
    return layer_norm(x + f @ p["ffn_w"] + p["ffn_b"],
                      p["ln2_w"], p["ln2_b"])


def stack(x, layers: dict, num_heads: int, causal: bool):
    """Run `x` [B, S, H] through the stacked layers (leading axis L)."""
    def body(carry, p):
        return _block(carry, p, num_heads, causal), None
    out, _ = jax.lax.scan(body, x, layers)
    return out


def gather_layers(get, layer_name, suffixes: dict, num_layers: int) -> dict:
    """Stack per-layer parameters read by name. `get(name)` returns an
    array; `layer_name(i)` the i-th layer's prefix; `suffixes` maps each of
    LAYER_KEYS to the parameter's suffix under that prefix."""
    return {k: jnp.stack([jnp.asarray(get(layer_name(i) + suffixes[k]),
                                      jnp.float32)
                          for i in range(num_layers)])
            for k in LAYER_KEYS}
