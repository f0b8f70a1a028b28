"""Dispatch and drain: ``DecodeStats.programs_dispatched``, the device
programs the columns' ``finish()`` enqueued (chunk programs; on the
per-page path page kernels, slices, concatenates and validity
programs), per million file rows.  None where the program has no such
counter."""


def read(ctx):
    v = getattr(ctx.stats, "programs_dispatched", None)
    if v is None or not ctx.window.rows:
        return None
    return v / (ctx.window.rows / 1e6)
