"""Dispatch and drain: ``DecodeStats.drain_s``, the consumer's wall in
the ``block_until_ready`` on each unit's buffers (the ``tpq.drain``
span), per million file rows.  None where the program has no such
field."""


def read(ctx):
    v = getattr(ctx.stats, "drain_s", None)
    if v is None or not ctx.window.rows:
        return None
    return v * 1e3 / (ctx.window.rows / 1e6)
