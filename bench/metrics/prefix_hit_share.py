"""prefix_hit_share (%): prompt tokens the paged engine served from shared
full pages (``EngineUsage.prefix_hit_tokens``, radix hits and pages borrowed
within a wave), over every prompt token of the window's jobs."""


def read(ctx):
    prompt = sum(len(j.prompt) for j in ctx.jobs)
    if not prompt:
        return None
    return 100.0 * ctx.usage["prefix_hit_tokens"] / prompt
