"""Host planning and decompression: ``DecodeStats.plan_wait_s``, the
consumer's wall waiting on its unit's plan tasks (the ``tpq.plan_wait``
span), per million file rows.  None where the program has no such
field."""


def read(ctx):
    v = getattr(ctx.stats, "plan_wait_s", None)
    if v is None or not ctx.window.rows:
        return None
    return v * 1e3 / (ctx.window.rows / 1e6)
