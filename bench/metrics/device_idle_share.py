"""device_idle_share (%): the share of the traced window (first "protocol
round" span to the end of the last) in which no op ran on the chip."""


def read(ctx):
    s = ctx.trace_summary
    if s is None:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
