"""Host-to-device staging: ``DecodeStats.transfer_s`` (host clock
around the staging calls) per million file rows."""


def read(ctx):
    if not ctx.window.rows or not ctx.stats.transfer_s:
        return None
    return ctx.stats.transfer_s * 1e3 / (ctx.window.rows / 1e6)
