"""tasks_per_s: MinionS tasks that ended ``ok`` (their local fan-out fully
answered), over the host-clock length of the whole closed-loop calls in the
window."""


def read(ctx):
    return ctx.tasks / ctx.window_s
