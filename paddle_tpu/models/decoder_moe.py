"""A decoder that TRAINS: pre-norm layers of grouped-query attention, some
of them behind a sliding window, each followed by a softmax-routed top-k
mixture of SwiGLU experts; rotary embeddings, under YaRN in the full layers;
final RMSNorm, untied head, next-token cross-entropy. Built from `layers.*`
op by op as `models/transformer.py` builds BERT (a norm op, the projections
as `mul`, a rotary op, one attention op, a router op, one expert op, one
head-and-loss op), so that `append_backward`, AMP's lists, clipping and
`RecomputeOptimizer` see every parameter and every boundary.

    h = x + W_o . Attn(rot(W_q n1(x)), rot(W_k n1(x)), W_v n1(x))
    y = h + sum_{e in top-k} w_e . W_down,e (silu(W_gate,e z) * W_up,e z),
        z = n2(h), p = softmax(W_r z) over ALL experts, w_e = p_e / sum p

One chip's share of a layer that several chips hold: `experts_held` of the
`num_experts` the router scores (`first_expert` on), and `vocab_size` is the
slice of the vocabulary whose embedding and head rows live here. The router
keeps every output and its top-k; the experts op computes the part of the
sum the held experts give and nothing stands in for the rest.

Scopes on a device trace (`<name scope>/<op type>[/<piece>]`, a grad op's
type ends in `_grad`): `decoder_moe/embed`, `decoder_moe/<sliding|full>/
{norm, qkv, rotary, attend, o_proj}`, `decoder_moe/{ffn_norm, router}`,
`decoder_moe/moe_experts[_grad]/{dispatch, experts, combine}` and
`decoder_moe/head_loss`.
"""
from __future__ import annotations

import dataclasses

from .. import layers as L
from ..framework import name_scope
from ..initializer import Normal
from ..param_attr import ParamAttr

__all__ = ["DecoderMoEConfig", "decoder_moe_pretrain", "RecomputeByLayer",
           "last_layer_outputs"]

SLIDING, FULL = "sliding_attention", "full_attention"
# a matrix is drawn N(0, fan_in^-1), the router's N(0, (ROUTER_SCALE *
# H^-0.5)^2): at 2 its logits (a standard deviation of 2) neither saturate
# nor tie, the served families' draw
ROUTER_SCALE = 2.0


@dataclasses.dataclass
class DecoderMoEConfig:
    vocab_size: int = 1024          # the rows of embedding and head held
    hidden_size: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    layer_types: tuple = (SLIDING, FULL)
    sliding_window: int = 8
    rope_theta: float = 500000.0
    # (factor, original context, beta_fast, beta_slow, attention factor) of
    # the full layers' YaRN, or ()
    yarn: tuple = ()
    rms_norm_eps: float = 1e-6
    num_experts: int = 8            # the router's outputs
    experts_per_token: int = 2
    expert_width: int = 32
    experts_held: int | None = None   # None: all of them
    first_expert: int = 0

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        self.yarn = tuple(self.yarn or ())
        if self.experts_held is None:
            self.experts_held = self.num_experts

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)


# the layers' outputs of the MOST RECENT build: what `RecomputeByLayer`
# checkpoints (`transformer.last_layer_outputs`' contract)
last_layer_outputs: list = []


def _attr(name: str, fan_in: int, scale: float = 1.0) -> ParamAttr:
    return ParamAttr(name=name,
                     initializer=Normal(0.0, scale * fan_in ** -0.5))


def _proj(x, size: int, name: str):
    return L.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                param_attr=_attr(name, int(x.shape[-1])))


def _f32(x):
    """A product's result back on the float32 residual stream (under AMP a
    `mul` gives bfloat16, and an add of the two would round the stream)."""
    return L.cast(x, "float32")


def _attention(x, i: int, cfg: DecoderMoEConfig, seq_len: int):
    kind = "sliding" if cfg.layer_types[i] == SLIDING else "full"
    nh, nkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    name = f"decoder.layer{i}.attn"
    with name_scope(kind):
        with name_scope("norm"):
            z = L.rms_norm(x, cfg.rms_norm_eps,
                           ParamAttr(name=f"decoder.layer{i}.norm1"))
        with name_scope("qkv"):
            q = L.reshape(_proj(z, nh * dh, name + ".wq"),
                          shape=[0, seq_len, nh, dh])
            k = L.reshape(_proj(z, nkv * dh, name + ".wk"),
                          shape=[0, seq_len, nkv, dh])
            v = L.reshape(_proj(z, nkv * dh, name + ".wv"),
                          shape=[0, seq_len, nkv, dh])
            v = L.transpose(v, perm=[0, 2, 1, 3])
        with name_scope("rotary"):
            yarn = cfg.yarn if kind == "full" else ()
            q = L.rotary_embedding(q, cfg.rope_theta, yarn)
            k = L.rotary_embedding(k, cfg.rope_theta, yarn)
        with name_scope("attend"):
            stats = L.device_counter(
                f"decoder.layer{i}.attn.stats", 2,
                [("train.attn.key_blocks_visited", {"kind": kind}, 0),
                 ("train.attn.key_blocks_causal", {"kind": kind}, 1)])
            a = L.fused_attention(
                q, k, v, causal=True, sm_scale=dh ** -0.5,
                window=cfg.sliding_window if kind == "sliding" else 0,
                stats=stats)
        with name_scope("o_proj"):
            a = L.reshape(L.transpose(a, perm=[0, 2, 1, 3]),
                          shape=[0, seq_len, nh * dh])
            return L.elementwise_add(x, _f32(_proj(a, cfg.hidden_size,
                                                   name + ".wo")))


def _experts(h, i: int, cfg: DecoderMoEConfig):
    H, F = cfg.hidden_size, cfg.expert_width
    name = f"decoder.layer{i}.moe"
    with name_scope("ffn_norm"):
        z = L.rms_norm(h, cfg.rms_norm_eps,
                       ParamAttr(name=f"decoder.layer{i}.norm2"))
    with name_scope("router"):
        cw = L.moe_router(z, cfg.num_experts, cfg.experts_per_token,
                          _attr(name + ".router", H, ROUTER_SCALE))
    stats = L.device_counter(
        name + ".stats", 3 + cfg.experts_held,
        [("train.moe.assignments", {}, 0),
         ("train.moe.held_assignments", {}, 1),
         ("train.moe.dropped", {}, 2)]
        + [("train.moe.expert_tokens",
            {"layer": str(i), "expert": str(cfg.first_expert + e)}, 3 + e)
           for e in range(cfg.experts_held)])
    y = L.moe_experts(
        z, cw, cfg.experts_held, F, cfg.experts_per_token, cfg.first_expert,
        gate_attr=_attr(name + ".w_gate", H),
        up_attr=_attr(name + ".w_up", H),
        down_attr=_attr(name + ".w_down", F), stats=stats)
    return L.elementwise_add(h, y)


def decoder_moe_pretrain(cfg: DecoderMoEConfig, seq_len: int = 32):
    """Next-token pretraining program: returns (loss, feeds dict). One
    feed, `src_ids` [B, seq_len] int32 over the held vocabulary slice; the
    labels are the ids one position on, derived inside the loss op."""
    src_ids = L.data(name="src_ids", shape=[seq_len], dtype="int32")
    last_layer_outputs.clear()
    with name_scope("decoder_moe"):
        with name_scope("embed"):
            x = L.embedding(
                src_ids, size=[cfg.vocab_size, cfg.hidden_size],
                param_attr=ParamAttr(name="decoder.embed",
                                     initializer=Normal(0.0, 1.0)))
        for i in range(cfg.num_layers):
            x = _experts(_attention(x, i, cfg, seq_len), i, cfg)
            last_layer_outputs.append(x)
        with name_scope("head_loss"):
            x = L.rms_norm(x, cfg.rms_norm_eps,
                           ParamAttr(name="decoder.final_norm"))
            loss = L.lm_head_loss(x, src_ids, cfg.vocab_size,
                                  _attr("decoder.head", cfg.hidden_size))
    return loss, {"src_ids": src_ids}


def RecomputeByLayer(**kwargs):
    """Adam(**kwargs) behind a `RecomputeOptimizer` that keeps the input of
    every layer of the decoder just built and computes the layer's inside
    again in the backward pass. A function, so that a configuration file can
    name it where it names an optimizer's class."""
    from ..optimizer import Adam, RecomputeOptimizer

    opt = RecomputeOptimizer(Adam(**kwargs))
    opt._set_checkpoints(list(last_layer_outputs))
    return opt
