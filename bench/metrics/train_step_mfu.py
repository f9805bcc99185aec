"""Model FLOP/s utilisation of the traced training steps: useful model
operations (6 per matrix parameter per trained token plus attention; no
recomputation, no padding slots) times trained tokens, over the traced window,
over chips times the chip's bf16 peak.  Layer: the model step."""


def read(ctx):
    if ctx.kind != "train" or not ctx.trace.devices:
        return None
    rate = ctx.flops_per_token * ctx.tokens / ctx.trace.window_s
    return 100.0 * rate / (ctx.chips * ctx.peak["bf16_flops"])
