"""Plain reference for the served decoder: one teacher-forced causal
forward over prompt + served tokens, float32, all-position logits.

Independent of paddle_tpu: no paged cache, no prefill/decode split, no
batching tricks. Serving is right when every token the engine emitted is,
by these logits, the best token at its position or within the stated
tolerance of it (random weights make near-ties; an argmax comparison would
flip on rounding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .post_ln_stack import gather_layers, layer_norm, stack

_SUFFIXES = {"qkv_w": ".mha.qkv.w", "qkv_b": ".mha.qkv.b",
             "out_w": ".mha.out.w", "out_b": ".mha.out.b",
             "ln1_w": ".ln1.scale", "ln1_b": ".ln1.bias",
             "in_w": ".ffn.in.w", "in_b": ".ffn.in.b",
             "ffn_w": ".ffn.out.w", "ffn_b": ".ffn.out.b",
             "ln2_w": ".ln2.scale", "ln2_b": ".ln2.bias"}


def read_params(get, cfg) -> dict:
    """The engine's weights, by the names serving.model gives them; `cfg`
    is the decoder's config object (num_layers, num_heads, max_position)."""
    f32 = lambda n: jnp.asarray(get(n), jnp.float32)  # noqa: E731
    return {
        "word_emb": f32("dec.word_emb"), "pos_emb": f32("dec.pos_emb"),
        "emb_ln_w": f32("dec.emb_ln.scale"), "emb_ln_b": f32("dec.emb_ln.bias"),
        "layers": gather_layers(get, lambda i: f"dec.layer{i}", _SUFFIXES,
                                cfg.num_layers),
        "head_w": f32("dec.lm_head.w"), "head_b": f32("dec.lm_head.b"),
    }


@functools.partial(jax.jit, static_argnames=("num_heads",))
def _logits(params, tokens, num_heads: int):
    pos = jnp.arange(tokens.shape[1])[None, :]
    x = params["word_emb"][tokens] + params["pos_emb"][pos]
    x = layer_norm(x, params["emb_ln_w"], params["emb_ln_b"])
    x = stack(x, params["layers"], num_heads, causal=True)
    return x @ params["head_w"] + params["head_b"]


def worst_logit_gaps(params: dict, sequences: list, cfg) -> list:
    """For each (prompt, served) pair: the largest amount by which a served
    token's logit sits below the best logit at its position. Right padding
    to max_position cannot reach a causal position before it."""
    tok = np.zeros((len(sequences), cfg.max_position), np.int32)
    for i, (prompt, served) in enumerate(sequences):
        seq = list(prompt) + list(served)
        tok[i, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(_logits(params, jnp.asarray(tok),
                                    num_heads=cfg.num_heads))
    gaps = []
    for i, (prompt, served) in enumerate(sequences):
        worst = 0.0
        for j, t in enumerate(served):
            row = logits[i, len(prompt) + j - 1]
            worst = max(worst, float(row.max() - row[t]))
        gaps.append(worst)
    return gaps
