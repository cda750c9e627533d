"""decode_step_ms (ms): device time of the serve loop per iteration.  The
union of the serve loop's ``while`` op intervals in the traced window
(``programs.decode_loop_seconds``), over the loop iterations that started
in it (``programs.decode_steps``: the paged decode kernel runs once per
attention layer in each)."""

from bench import programs


def read(ctx):
    window = programs.window_ops(ctx)
    if window is None:
        return None
    ops, lo, hi = window
    steps = programs.decode_steps(ops, lo, hi, ctx.dims.attn_layers)
    seconds = programs.decode_loop_seconds(ops, lo, hi)
    if steps <= 0 or seconds <= 0:
        return None
    ctx.log(f"metric: decode_step_ms: loop {seconds!r} s over {steps} "
            "iterations")
    return 1e3 * seconds / steps
