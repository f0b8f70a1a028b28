"""Host planning and decompression: ``DecodeStats.plan_s`` per million
file rows.  plan_s is summed over the plan pool's threads, so this is
thread time, not wall time."""


def read(ctx):
    if not ctx.window.rows or not ctx.stats.plan_s:
        return None
    return ctx.stats.plan_s * 1e3 / (ctx.window.rows / 1e6)
