"""Plain reference for masked-LM pretraining of the BERT-shaped encoder:
forward, weighted cross-entropy, gradients and Adam, float32 throughout.

Independent of paddle_tpu: it reads the freshly initialised parameters by
name and is given the same host batches; what it returns is the loss of
each of the first steps and the parameters after them, which the trainer's
own losses and parameters must match.
The batch is processed in row blocks (gradients summed over blocks, one
Adam update per step), so the reference fits beside the trainer's state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .post_ln_stack import gather_layers, layer_norm, stack

_SUFFIXES = {"qkv_w": ".mha.qkv.w", "qkv_b": ".mha.qkv.b",
             "out_w": ".mha.out.w", "out_b": ".mha.out.b",
             "ln1_w": ".ln1.w_0", "ln1_b": ".ln1.b_0",
             "in_w": ".ffn.in.w", "in_b": ".ffn.in.b",
             "ffn_w": ".ffn.out.w", "ffn_b": ".ffn.out.b",
             "ln2_w": ".ln2.w_0", "ln2_b": ".ln2.b_0"}
DENOM_EPS = 1e-6     # bert_pretrain adds it to the weight sum


def read_params(get, cfg) -> dict:
    """The trainer's parameters, by the names bert_pretrain gives them;
    `cfg` is the model's config object (num_layers, num_heads)."""
    f32 = lambda n: jnp.asarray(get(n), jnp.float32)  # noqa: E731
    return {
        "word_emb": f32("encoder.word_emb"), "pos_emb": f32("encoder.pos_emb"),
        "emb_ln_w": f32("encoder.emb_ln.w_0"),
        "emb_ln_b": f32("encoder.emb_ln.b_0"),
        "layers": gather_layers(get, lambda i: f"encoder.layer{i}",
                                _SUFFIXES, cfg.num_layers),
        "head_w": f32("lm_head.w"), "head_b": f32("lm_head.b"),
    }


def weighted_nll_sum(params, batch, num_heads: int):
    """Sum over the block of lm_weight * cross-entropy(logits, lm_label)."""
    x = params["word_emb"][batch["src_ids"]] + params["pos_emb"][batch["pos_ids"]]
    x = layer_norm(x, params["emb_ln_w"], params["emb_ln_b"])
    x = stack(x, params["layers"], num_heads, causal=False)
    logits = x @ params["head_w"] + params["head_b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["lm_label"][..., None], axis=-1)
    return jnp.sum(nll[..., 0] * batch["lm_weight"])


@functools.partial(jax.jit, static_argnames=("num_heads", "block_rows"))
def _adam_step(params, m, v, t, batch, lr, num_heads: int, block_rows: int,
               beta1=0.9, beta2=0.999, eps=1e-8):
    denom = jnp.sum(batch["lm_weight"]) + DENOM_EPS
    blocks = jax.tree.map(
        lambda a: a.reshape((-1, block_rows) + a.shape[1:]), batch)

    def one_block(acc, blk):
        loss, grads = jax.value_and_grad(
            lambda p: weighted_nll_sum(p, blk, num_heads) / denom)(params)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], grads)), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(one_block, (jnp.float32(0), zero), blocks)
    t = t + 1
    m = jax.tree.map(lambda a, g: beta1 * a + (1 - beta1) * g, m, grads)
    v = jax.tree.map(lambda a, g: beta2 * a + (1 - beta2) * g * g, v, grads)
    lr_t = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
    params = jax.tree.map(
        lambda p, a, b: p - lr_t * a / (jnp.sqrt(b) + eps), params, m, v)
    return loss, params, m, v, t


def first_steps(params: dict, batches: list, cfg, lr: float,
                block_rows: int) -> tuple:
    """(loss of each step in `batches` under Adam from `params`, the
    parameters after the last of them)."""
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    t = jnp.float32(0)
    losses = []
    with jax.default_matmul_precision("highest"):
        for batch in batches:
            batch = {k: jnp.asarray(a) for k, a in batch.items()}
            loss, params, m, v, t = _adam_step(
                params, m, v, t, batch, jnp.float32(lr),
                num_heads=cfg.num_heads, block_rows=block_rows)
            losses.append(float(loss))
    return losses, params
