"""setup_s: process start to the first timed call (imports, weights made on
the device, engine built, warm-up calls with their compiles or cache loads),
on the host clock."""


def read(ctx):
    return ctx.setup_s
