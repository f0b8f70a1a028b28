"""Decode kernels: the union of device-operation intervals in the
trace, per million file rows."""


def read(ctx):
    if ctx.trace is None or not ctx.window.rows:
        return None
    return ctx.trace["busy_s"] * 1e3 / (ctx.window.rows / 1e6)
