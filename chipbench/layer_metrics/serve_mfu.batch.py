"""The whole serve step's share of the chip's bf16 peak: model operations
of the output tokens produced in the window (2 x weights per token plus
attention over each token's context) over the window and the peak."""

from chipbench import costs, peaks


def read(ctx):
    spans = ctx.window["counters"]["emitted_spans"]
    if not spans:
        return None
    flops = costs.decode_flops(ctx.config["model"], spans)
    pk = peaks.peak(ctx.device_kind)["bf16_flops"]
    return flops / ctx.window["window_s"] / (pk * ctx.n_devices) * 100.0
