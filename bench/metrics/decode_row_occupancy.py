"""decode_row_occupancy (%): decode forwards whose token was served
(``work.Counts.decode_forwards``), over the rows the serve loop stepped:
``dims.slots`` rows in each loop iteration of the traced window
(``programs.decode_steps``)."""

from bench import programs


def read(ctx):
    window = programs.window_ops(ctx)
    if window is None:
        return None
    steps = programs.decode_steps(*window, ctx.dims.attn_layers)
    if steps <= 0:
        return None
    return 100.0 * ctx.counts.decode_forwards / (ctx.dims.slots * steps)
