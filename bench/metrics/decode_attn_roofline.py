"""decode_attn_roofline (%): the least time the chip needs for the decode
attention the traffic requires (the architecture's ``decode_attn_work``:
shared prompt K/V read once per step), over the device time of the paged
decode kernel's ops in the trace."""

from bench import work

# the Pallas call under its jitted wrapper's name (kernels/gqa_decode.py: paged_gqa_decode_attention), as the
# trace names it
KERNEL = r"^paged_gqa_decode_attention$"


def read(ctx):
    if ctx.trace_summary is None or ctx.peak is None:
        return None
    seconds = ctx.kernel_seconds(KERNEL)
    if seconds <= 0:
        return None
    least, bound = work.roofline_seconds(*ctx.arch.decode_attn_work(ctx),
                                         ctx.peak)
    ctx.log(f"metric: decode_attn_roofline bound by {bound}: least "
            f"{least!r} s over kernel {seconds!r} s")
    return 100.0 * least / seconds
