"""Dispatch and drain: ``DecodeStats.chunks_fused`` over ``chunks``,
the share of the window's column chunks decoded by one chunk program
instead of one program per page.  None where the program has no such
counter."""


def read(ctx):
    st = ctx.stats
    fused = getattr(st, "chunks_fused", None)
    if fused is None or not st.chunks:
        return None
    return fused * 100.0 / st.chunks
