"""host_transfers_per_job: device-to-host result transfers the engine
counted (``EngineUsage.host_transfers``) per worker job of the window."""


def read(ctx):
    if not ctx.jobs:
        return None
    return ctx.usage["host_transfers"] / len(ctx.jobs)
