"""Dispatch and drain: ``DecodeStats.dispatch_s``, the consumer's wall
enqueueing each column's page programs (the per-column
``tpq.dispatch`` spans), per million file rows.  Read only where the
program also has ``drain_s``: before it, ``dispatch_s`` held the drain
too and measured something else."""


def read(ctx):
    st = ctx.stats
    if not hasattr(st, "drain_s") or not ctx.window.rows:
        return None
    return st.dispatch_s * 1e3 / (ctx.window.rows / 1e6)
