"""Op lists steering automatic mixed precision.

Reference: /root/reference/python/paddle/fluid/contrib/mixed_precision/
fp16_lists.py (AutoMixedPrecisionLists:19, white_list:60, black_list:67,
gray_list:77). The split is the same idea retuned for TPU: white ops are the
MXU FLOP carriers (matmul/conv) that should run in bfloat16; black ops are
numerically-sensitive reductions/exponentials kept in float32; everything
else (gray) follows its inputs — our JAX kernels are dtype-polymorphic, so
gray needs no rewriting at all."""
from __future__ import annotations

__all__ = ["AutoMixedPrecisionLists", "white_list", "black_list", "gray_list",
           "apply_tuning_overrides"]

white_list = {
    "mul",
    "matmul",
    "conv2d",
    "depthwise_conv2d",
    "conv2d_transpose",
    # MXU carrier with fp32 softmax statistics inside the kernel
    "fused_attention",
    # a training decoder's grouped expert products and its head
    # (ops/decoder_train_ops.py): bfloat16 operands, float32 accumulation;
    # SiLU, the combine weights (fp16_utils._STATE_SLOTS keeps `Cw` out of
    # the cast), the log-sum-exp and the loss are float32 inside
    "moe_experts",
    "lm_head_loss",
}

black_list = {
    "exp",
    "square",
    "log",
    "mean",
    "sum",
    "cos_sim",
    "log_softmax",
    "sigmoid_cross_entropy_with_logits",
    "cross_entropy",
    "reduce_sum",
    "reduce_mean",
    "squared_l2_norm",
    # a training decoder's norms, rotary embeddings and router: float32 in
    # and out, so that a token's choice of experts does not hang on a
    # bfloat16 rounding of the router's own arithmetic
    "rms_norm",
    "rotary_embedding",
    "moe_router",
}

# layer_norm/softmax/batch_norm are gray, not black (a departure from the
# reference's CUDA lists): all three kernels already keep their statistics in
# fp32 registers internally (nn_ops.layer_norm and batch_norm upcast;
# softmax's max-subtraction bounds the bf16 exp), so forcing fp32 at the op
# BOUNDARY only added HBM-sized cast round-trips — around every BN in
# ResNet-50 this measured 2.7x slower than no AMP at all (PERF.md).
gray_list = {
    "elementwise_add", "elementwise_sub", "elementwise_mul", "elementwise_div",
    "relu", "gelu", "tanh", "sigmoid", "leaky_relu", "dropout", "pool2d",
    "transpose2", "reshape2", "concat", "split", "slice", "squeeze2",
    "unsqueeze2", "stack", "scale", "lookup_table", "lookup_table_v2",
    "layer_norm", "softmax", "softmax_mask_fuse_upper_triangle",
    "batch_norm",
    # fused conv+BN (passes.fuse_conv_bn_stats) normally post-dates the AMP
    # rewrite, but a manually-fused program must follow the batch_norm rule:
    # fp32 statistics live INSIDE the kernel, boundaries follow the inputs
    "conv2d_bn",
    # gray since r5: the op upcasts to fp32 INTERNALLY (classic path) or
    # keeps fp32 statistics in-kernel (Pallas path) — black-listing it
    # doubled the lm-head logits traffic at BERT vocab sizes
    "softmax_with_cross_entropy",
}


def apply_tuning_overrides(lists: "AutoMixedPrecisionLists"):
    """Gray-list membership as a tunable decision (FLAGS_tuning_mode):
    an op the hand lists leave gray ("follow your inputs") can be promoted
    to white (bf16 boundaries — more MXU/HBM savings) or demoted to black
    (fp32 boundaries — numerically fragile at some site) by a swept-DB
    entry, per device kind. Only ops still gray are touched, so a user's
    custom_white_list/custom_black_list moves always win; the analytic
    prior is "stay gray" (the measured hand-tuned split above), so with no
    DB entry the lists are byte-identical to the pre-tuner ones."""
    from ... import tuning

    if tuning.mode() == "off":
        return lists
    for op in sorted(lists.gray_list):
        key = tuning.canonical_key("amp_list", tuning.amp_key(op), "-",
                                   tuning.device_kind())
        decision, _tier = tuning.decide(
            "amp_list", key,
            prior=lambda: {"list": "gray"},
            default={"list": "gray"},
            validate=lambda dd: dd.get("list") in ("white", "black", "gray"))
        target = decision.get("list", "gray")
        if target != "gray":
            lists.gray_list.discard(op)
            (lists.white_list if target == "white"
             else lists.black_list).add(op)
    return lists


class AutoMixedPrecisionLists:
    def __init__(self, custom_white_list=None, custom_black_list=None):
        if custom_white_list and custom_black_list:
            both = set(custom_white_list) & set(custom_black_list)
            if both:
                raise ValueError(f"ops in both custom lists: {both}")
        self.white_list = set(white_list)
        self.black_list = set(black_list)
        self.gray_list = set(gray_list)
        if custom_white_list:
            for t in custom_white_list:
                self.white_list.add(t)
                self.black_list.discard(t)
        if custom_black_list:
            for t in custom_black_list:
                self.black_list.add(t)
                self.white_list.discard(t)
