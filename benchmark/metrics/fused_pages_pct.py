"""Dispatch and drain: ``DecodeStats.pages_fused`` over ``pages``, the
share of the window's data pages decoded inside a chunk program
instead of by a program of their own.  None where the program has no
such counter."""


def read(ctx):
    st = ctx.stats
    fused = getattr(st, "pages_fused", None)
    if fused is None or not st.pages:
        return None
    return fused * 100.0 / st.pages
