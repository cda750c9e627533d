"""prefill_attn_roofline (%): the least time the chip needs for the prefill
attention the traffic requires (the architecture's ``prefill_attn_work``),
over the device time of the paged prefill kernel's ops in the trace."""

from bench import work

# the Pallas call under its jitted wrapper's name (kernels/chunked_prefill.py: paged_prefill_attention), as the
# trace names it
KERNEL = r"^paged_prefill_attention$"


def read(ctx):
    if ctx.trace_summary is None or ctx.peak is None:
        return None
    seconds = ctx.kernel_seconds(KERNEL)
    if seconds <= 0:
        return None
    least, bound = work.roofline_seconds(*ctx.arch.prefill_attn_work(ctx),
                                         ctx.peak)
    ctx.log(f"metric: prefill_attn_roofline bound by {bound}: least "
            f"{least!r} s over kernel {seconds!r} s")
    return 100.0 * least / seconds
