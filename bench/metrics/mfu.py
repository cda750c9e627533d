"""mfu (%): the FLOPs the window's traffic requires of the model (the
architecture's ``step_flops``: for a llama-style decoder, linear layers of
every distinct prompt token and decode forward, attention over causal
contexts, the LM head where a logit is needed), over the traced window times
the chip's bf16 peak."""


def read(ctx):
    if ctx.trace_summary is None or ctx.peak is None:
        return None
    flops = ctx.arch.step_flops(ctx)
    window = ctx.trace_summary["window_s"]
    return 100.0 * flops / (window * ctx.peak["bf16_flops_per_s"])
