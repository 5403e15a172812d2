"""Transformer encoder / BERT-style pretraining — BASELINE configs 3 & 4
(reference: fluid book machine-translation transformer and ERNIE/BERT built on
fluid layers; attention primitive at reference python/paddle/fluid/nets.py:345
scaled_dot_product_attention).

TPU-first design notes:
  * Megatron-style tensor parallelism comes from GSPMD annotations on the
    projection weights (SURVEY.md §2.3): QKV/FFN-in shard the output dim over
    the `tp` mesh axis, attention-out/FFN-out shard the input dim — XLA's
    sharding propagator inserts the all-reduces the reference would have
    needed hand-written DistFC logic for.
  * Sequence parallelism = sharding the sequence dim of the token stream over
    the `sp` axis; the attention score matmul forces an all-gather that XLA
    places on ICI.
  * Everything is static-shaped (padded seq_len); bf16-friendly.
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import layers as L
from ..framework import default_main_program, name_scope
from ..param_attr import ParamAttr
from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS
from ..parallel.sharding import annotate_sharding

__all__ = ["TransformerConfig", "bert_base", "bert_tiny", "transformer_encoder",
           "bert_pretrain", "multi_head_attention", "positionwise_ffn",
           "wmt_base", "transformer_wmt", "cross_attention"]


@dataclass
class TransformerConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_size: int = 3072
    max_position: int = 512
    dropout: float = 0.1
    # parallelism intent: annotate weights/feeds with these mesh axes; harmless
    # when the program runs on a mesh lacking the axis (annotations filtered)
    use_tp: bool = True
    use_sp: bool = False
    # fused (Pallas flash) attention — used when there is no attention-prob
    # dropout and no additive mask (those paths keep the unfused ops).
    # Default OFF: measured on v5e, XLA's own attention fusion beats the
    # bundled Pallas kernel at train sizes (seq<=2048: 16ms vs 36ms fwd+bwd
    # for B8/h12/S2048/d64); flash pays off when the [B,nh,S,S] score tensor
    # no longer fits HBM (long-context), where it is the only option.
    use_flash_attention: bool = False
    causal: bool = False
    dtype: str = "float32"


def bert_base() -> TransformerConfig:
    return TransformerConfig()


def bert_tiny(use_tp: bool = True, use_sp: bool = False) -> TransformerConfig:
    return TransformerConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                             num_heads=4, ffn_size=128, max_position=64,
                             dropout=0.0, use_tp=use_tp, use_sp=use_sp)


def _annot(spec):
    """Return a hook that annotates the named main-program var after creation."""
    def apply(name):
        block = default_main_program().global_block
        annotate_sharding(block.var(name), spec)
    return apply


def _fc(x, size, prefix, w_spec=None, b_spec=None, act=None, cfg=None):
    num_flatten = len(x.shape) - 1
    w_name, b_name = prefix + ".w", prefix + ".b"
    out = L.fc(
        x, size=size, num_flatten_dims=num_flatten,
        param_attr=ParamAttr(name=w_name), bias_attr=ParamAttr(name=b_name),
        act=act,
    )
    if cfg is not None and cfg.use_tp:
        if w_spec is not None:
            _annot(w_spec)(w_name)
        if b_spec is not None:
            _annot(b_spec)(b_name)
    return out


def _attn_core(q, k, v, attn_bias, cfg: TransformerConfig, causal, dh):
    """The attention block proper, [B,nh,Sq,dh] x [B,nh,Sk,dh] -> [B,nh,Sq,dh].

    One fused-attention op boundary whenever semantics allow (no additive
    bias, no attention-prob dropout): the op dispatches to the measured
    winner per shape — XLA fusion at train sizes, Pallas for long context.
    cfg.use_flash_attention forces an O(S)-memory kernel. Shared by self-
    and cross-attention so the dispatch policy lives in exactly one place.
    """
    if attn_bias is None and not cfg.dropout:
        return L.fused_attention(q, k, v, causal=causal, sm_scale=dh ** -0.5,
                                 use_pallas=cfg.use_flash_attention)
    scores = L.matmul(q, k, transpose_y=True, alpha=dh ** -0.5)
    if attn_bias is not None:
        scores = L.elementwise_add(scores, attn_bias)
    if causal:
        # fused causal-mask+softmax op (probs directly)
        helper = L.nn.LayerHelper("causal_softmax")
        probs = helper.create_variable_for_type_inference(scores.dtype)
        helper.append_op("softmax_mask_fuse_upper_triangle",
                         {"X": [scores.name]}, {"Out": [probs.name]}, {})
    else:
        probs = L.softmax(scores)
    if cfg.dropout:
        probs = L.dropout(probs, dropout_prob=cfg.dropout,
                          dropout_implementation="upscale_in_train")
    return L.matmul(probs, v)


def multi_head_attention(x, cfg: TransformerConfig, attn_bias=None, name="attn"):
    """Self-attention: fused QKV projection, [B,S,H] -> [B,S,H].

    TP: QKV weight [H, 3H] shards dim 1; out-proj [H, H] shards dim 0 — the
    classic Megatron column/row-parallel pair, expressed as annotations.
    """
    B_, S, H = -1, x.shape[-2], cfg.hidden_size
    nh, dh = cfg.num_heads, cfg.hidden_size // cfg.num_heads

    qkv = _fc(x, 3 * H, name + ".qkv", w_spec=(None, MODEL_AXIS),
              b_spec=(MODEL_AXIS,), cfg=cfg)
    qkv = L.reshape(qkv, shape=[0, S, 3, nh, dh])
    qkv = L.transpose(qkv, perm=[2, 0, 3, 1, 4])  # [3, B, nh, S, dh]
    q = L.squeeze(L.slice(qkv, axes=[0], starts=[0], ends=[1]), axes=[0])
    k = L.squeeze(L.slice(qkv, axes=[0], starts=[1], ends=[2]), axes=[0])
    v = L.squeeze(L.slice(qkv, axes=[0], starts=[2], ends=[3]), axes=[0])

    ctxv = _attn_core(q, k, v, attn_bias, cfg, causal=cfg.causal, dh=dh)
    ctxv = L.transpose(ctxv, perm=[0, 2, 1, 3])
    ctxv = L.reshape(ctxv, shape=[0, S, H])
    out = _fc(ctxv, H, name + ".out", w_spec=(MODEL_AXIS, None), cfg=cfg)
    return out


def positionwise_ffn(x, cfg: TransformerConfig, name="ffn"):
    h = _fc(x, cfg.ffn_size, name + ".in", w_spec=(None, MODEL_AXIS),
            b_spec=(MODEL_AXIS,), act="gelu", cfg=cfg)
    if cfg.dropout:
        h = L.dropout(h, dropout_prob=cfg.dropout,
                      dropout_implementation="upscale_in_train")
    return _fc(h, cfg.hidden_size, name + ".out", w_spec=(MODEL_AXIS, None), cfg=cfg)


def _encoder_layer(x, cfg: TransformerConfig, attn_bias, name):
    # post-LN as in BERT/original transformer
    a = multi_head_attention(x, cfg, attn_bias, name=name + ".mha")
    if cfg.dropout:
        a = L.dropout(a, dropout_prob=cfg.dropout,
                      dropout_implementation="upscale_in_train")
    x = L.layer_norm(L.elementwise_add(x, a), begin_norm_axis=2,
                     name=name + ".ln1")
    f = positionwise_ffn(x, cfg, name=name + ".ffn")
    if cfg.dropout:
        f = L.dropout(f, dropout_prob=cfg.dropout,
                      dropout_implementation="upscale_in_train")
    return L.layer_norm(L.elementwise_add(x, f), begin_norm_axis=2,
                        name=name + ".ln2")


# per-layer outputs of the MOST RECENT transformer_encoder build — the
# natural checkpoint set for RecomputeOptimizer._set_checkpoints. Snapshot
# it (list(...)) right after the build: a second encoder build (eval tower,
# second program) overwrites it, and _set_checkpoints with stale vars from a
# different program fails loudly at minimize().
last_layer_outputs: list = []


def transformer_encoder(src_ids, pos_ids, cfg: TransformerConfig,
                        input_mask=None, name="encoder"):
    """Token+position embedding -> N encoder layers. Returns [B,S,H]."""
    emb = L.embedding(src_ids, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=ParamAttr(name=name + ".word_emb"),
                      dtype=cfg.dtype)
    pos = L.embedding(pos_ids, size=[cfg.max_position, cfg.hidden_size],
                      param_attr=ParamAttr(name=name + ".pos_emb"),
                      dtype=cfg.dtype)
    x = L.elementwise_add(emb, pos)
    x = L.layer_norm(x, begin_norm_axis=2, name=name + ".emb_ln")
    if cfg.dropout:
        x = L.dropout(x, dropout_prob=cfg.dropout,
                      dropout_implementation="upscale_in_train")

    attn_bias = None
    if input_mask is not None:
        # input_mask [B,S] 1/0 -> additive bias [B,1,1,S]
        neg = L.scale(input_mask, scale=-1.0, bias=1.0)
        neg = L.scale(neg, scale=-1e9)
        attn_bias = L.unsqueeze(L.unsqueeze(neg, axes=[1]), axes=[1])

    last_layer_outputs.clear()
    for i in range(cfg.num_layers):
        x = _encoder_layer(x, cfg, attn_bias, name=f"{name}.layer{i}")
        last_layer_outputs.append(x)
    return x


def bert_pretrain(cfg: TransformerConfig, seq_len: int = 128):
    """Masked-LM pretraining program: returns (avg_loss, feeds dict).

    Feeds: src_ids, pos_ids [B,S] int64; lm_label [B,S] int64 (ids at masked
    positions, -ignored elsewhere via mask weighting); lm_weight [B,S] float32.
    """
    src_ids = L.data(name="src_ids", shape=[seq_len], dtype="int64")
    pos_ids = L.data(name="pos_ids", shape=[seq_len], dtype="int64")
    lm_label = L.data(name="lm_label", shape=[seq_len], dtype="int64")
    lm_weight = L.data(name="lm_weight", shape=[seq_len], dtype="float32")

    if cfg.use_sp:
        block = default_main_program().global_block
        for n in ("src_ids", "pos_ids", "lm_label", "lm_weight"):
            annotate_sharding(block.var(n), (DATA_AXIS, SEQ_AXIS))

    enc = transformer_encoder(src_ids, pos_ids, cfg)  # [B,S,H]
    # the head and its loss under one scope: a device trace books the H x V
    # product, the softmax and their grad ops to `mlm_head`
    with name_scope("mlm_head"):
        logits = _fc(enc, cfg.vocab_size, "lm_head",
                     w_spec=(None, MODEL_AXIS), b_spec=(MODEL_AXIS,),
                     cfg=cfg)                           # [B,S,V]
        label = L.unsqueeze(lm_label, axes=[2])
        loss = L.softmax_with_cross_entropy(logits, label)  # [B,S,1]
        loss = L.squeeze(loss, axes=[2])
        weighted = L.elementwise_mul(loss, lm_weight)
        denom = L.elementwise_add(L.reduce_sum(lm_weight), _const_eps())
        avg_loss = L.elementwise_div(L.reduce_sum(weighted), denom)
    feeds = {"src_ids": src_ids, "pos_ids": pos_ids,
             "lm_label": lm_label, "lm_weight": lm_weight}
    return avg_loss, feeds


def _const_eps():
    from ..layers.tensor import fill_constant
    return fill_constant(shape=[], dtype="float32", value=1e-6)


# ---------------------------------------------------------------------------
# Encoder-decoder Transformer (WMT en-de, BASELINE config 3; reference: the
# fluid book machine-translation transformer model family)
# ---------------------------------------------------------------------------


def wmt_base() -> TransformerConfig:
    """Transformer-base: 6+6 layers, d_model 512, 8 heads, ffn 2048, joint
    37k BPE vocab (Vaswani et al. table 3 'base')."""
    return TransformerConfig(vocab_size=37000, hidden_size=512, num_layers=6,
                             num_heads=8, ffn_size=2048, max_position=256,
                             dropout=0.1, use_tp=False)


def cross_attention(x, mem, cfg: TransformerConfig, attn_bias=None,
                    name="xattn"):
    """Encoder-decoder attention: queries from the decoder stream `x`
    [B,St,H], keys/values from encoder memory `mem` [B,Ss,H]."""
    St, Ss, H = x.shape[-2], mem.shape[-2], cfg.hidden_size
    nh, dh = cfg.num_heads, cfg.hidden_size // cfg.num_heads

    q = _fc(x, H, name + ".q", w_spec=(None, MODEL_AXIS),
            b_spec=(MODEL_AXIS,), cfg=cfg)
    kv = _fc(mem, 2 * H, name + ".kv", w_spec=(None, MODEL_AXIS),
             b_spec=(MODEL_AXIS,), cfg=cfg)
    q = L.transpose(L.reshape(q, shape=[0, St, nh, dh]), perm=[0, 2, 1, 3])
    kv = L.transpose(L.reshape(kv, shape=[0, Ss, 2, nh, dh]),
                     perm=[2, 0, 3, 1, 4])
    k = L.squeeze(L.slice(kv, axes=[0], starts=[0], ends=[1]), axes=[0])
    v = L.squeeze(L.slice(kv, axes=[0], starts=[1], ends=[2]), axes=[0])
    ctxv = _attn_core(q, k, v, attn_bias, cfg, causal=False, dh=dh)
    ctxv = L.reshape(L.transpose(ctxv, perm=[0, 2, 1, 3]), shape=[0, St, H])
    return _fc(ctxv, H, name + ".out", w_spec=(MODEL_AXIS, None), cfg=cfg)


def _decoder_layer(x, mem, cfg: TransformerConfig, self_bias, cross_bias,
                   name):
    import dataclasses

    causal_cfg = dataclasses.replace(cfg, causal=True)
    a = multi_head_attention(x, causal_cfg, self_bias, name=name + ".self")
    if cfg.dropout:
        a = L.dropout(a, dropout_prob=cfg.dropout,
                      dropout_implementation="upscale_in_train")
    x = L.layer_norm(L.elementwise_add(x, a), begin_norm_axis=2,
                     name=name + ".ln1")
    c = cross_attention(x, mem, cfg, cross_bias, name=name + ".cross")
    if cfg.dropout:
        c = L.dropout(c, dropout_prob=cfg.dropout,
                      dropout_implementation="upscale_in_train")
    x = L.layer_norm(L.elementwise_add(x, c), begin_norm_axis=2,
                     name=name + ".ln2")
    f = positionwise_ffn(x, cfg, name=name + ".ffn")
    if cfg.dropout:
        f = L.dropout(f, dropout_prob=cfg.dropout,
                      dropout_implementation="upscale_in_train")
    return L.layer_norm(L.elementwise_add(x, f), begin_norm_axis=2,
                        name=name + ".ln3")


def _embed_stream(ids, pos_ids, cfg, name, word_emb_name=None):
    emb = L.embedding(ids, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=ParamAttr(name=word_emb_name or
                                           name + ".word_emb"),
                      dtype=cfg.dtype)
    pos = L.embedding(pos_ids, size=[cfg.max_position, cfg.hidden_size],
                      param_attr=ParamAttr(name=name + ".pos_emb"),
                      dtype=cfg.dtype)
    x = L.scale(emb, scale=cfg.hidden_size ** 0.5)
    x = L.elementwise_add(x, pos)
    if cfg.dropout:
        x = L.dropout(x, dropout_prob=cfg.dropout,
                      dropout_implementation="upscale_in_train")
    return x


def transformer_wmt(cfg: TransformerConfig, src_len: int = 128,
                    tgt_len: int = 128, label_smooth_eps: float = 0.1,
                    use_src_mask: bool = False):
    """Training program for WMT translation: returns (avg_loss, feeds dict).

    Feeds (all [B, len]): src_ids/src_pos int64, tgt_ids/tgt_pos int64 (the
    shifted-right decoder input), tgt_label int64, tgt_weight float32 (0 on
    padding). With `use_src_mask` an extra src_mask [B, src_len] float32
    (1=token, 0=pad) feed masks encoder self-attention AND decoder
    cross-attention, so padded source positions cannot contaminate the
    memory (tgt_weight only masks the loss). Label-smoothed cross entropy
    averaged over non-pad tokens — the reference transformer book model's
    loss. Source and target share the joint-BPE word embedding table.
    """
    src_ids = L.data(name="src_ids", shape=[src_len], dtype="int64")
    src_pos = L.data(name="src_pos", shape=[src_len], dtype="int64")
    tgt_ids = L.data(name="tgt_ids", shape=[tgt_len], dtype="int64")
    tgt_pos = L.data(name="tgt_pos", shape=[tgt_len], dtype="int64")
    tgt_label = L.data(name="tgt_label", shape=[tgt_len], dtype="int64")
    tgt_weight = L.data(name="tgt_weight", shape=[tgt_len], dtype="float32")

    src_bias = None
    extra_feeds = []
    if use_src_mask:
        src_mask = L.data(name="src_mask", shape=[src_len], dtype="float32")
        extra_feeds.append(src_mask)
        # [B,S] 1/0 -> additive bias [B,1,1,S] (broadcasts over heads + query)
        neg = L.scale(src_mask, scale=-1.0, bias=1.0)
        neg = L.scale(neg, scale=-1e9)
        src_bias = L.unsqueeze(L.unsqueeze(neg, axes=[1]), axes=[1])

    mem = _embed_stream(src_ids, src_pos, cfg, "enc", word_emb_name="word_emb")
    for i in range(cfg.num_layers):
        mem = _encoder_layer(mem, cfg, src_bias, name=f"enc.layer{i}")

    x = _embed_stream(tgt_ids, tgt_pos, cfg, "dec", word_emb_name="word_emb")
    for i in range(cfg.num_layers):
        x = _decoder_layer(x, mem, cfg, None, src_bias, name=f"dec.layer{i}")

    logits = _fc(x, cfg.vocab_size, "proj", w_spec=(None, MODEL_AXIS),
                 b_spec=(MODEL_AXIS,), cfg=cfg)        # [B,St,V]
    if label_smooth_eps:
        # dense one_hot -> label_smooth -> soft-label CE. The algebraic
        # fusion smoothCE = (1-eps)*hardCE + eps*(lse - mean_v(x)) was
        # built and MEASURED SLOWER (446.4k vs 465.3k tok/s, r5): XLA
        # already generates the one-hot as an iota-compare inside the CE
        # fusion (nothing dense materializes), while the "fused" form's
        # separate max/sum-exp reductions do not CSE against the CE's
        # internal statistics. Equivalence test kept in test_models.py.
        onehot = L.one_hot(tgt_label, cfg.vocab_size)  # [B,St,V]
        soft = L.label_smooth(onehot, epsilon=label_smooth_eps)
        loss = L.softmax_with_cross_entropy(logits, soft, soft_label=True)
    else:
        loss = L.softmax_with_cross_entropy(
            logits, L.unsqueeze(tgt_label, axes=[2]))
    loss = L.squeeze(loss, axes=[2])                   # [B,St]
    weighted = L.elementwise_mul(loss, tgt_weight)
    denom = L.elementwise_add(L.reduce_sum(tgt_weight), _const_eps())
    avg_loss = L.elementwise_div(L.reduce_sum(weighted), denom)
    feeds = {v.name: v for v in (src_ids, src_pos, tgt_ids, tgt_pos,
                                 tgt_label, tgt_weight, *extra_feeds)}
    return avg_loss, feeds
