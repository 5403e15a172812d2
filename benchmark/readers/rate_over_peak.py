"""An end-to-end rate as a share of the chips' published peak, in percent:
rate x work per item / (chips x peak). With the configuration's
`model_flops_per_item` and the bf16 peak it is the model FLOP/s
utilization: recomputed operations do not count, idle time does."""


def read(result, rate: str, work: str, peak: str):
    ctx = result.ctx
    if ctx.peaks is None or rate not in result.values:
        return None
    spec = ctx.config[work]
    symbols = ctx.cell["traffic"]["symbols"]
    per_item = spec["constant"] + sum(
        k * symbols[name] for name, k in spec.get("times", {}).items())
    return result.values[rate] * per_item / (ctx.chips * ctx.peaks[peak]) * 100.0
