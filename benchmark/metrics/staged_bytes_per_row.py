"""Host-to-device staging: bytes shipped through the batched stager
(``DecodeStats.bytes_staged``, an exact count) per file row."""


def read(ctx):
    if not ctx.window.rows or not ctx.stats.bytes_staged:
        return None
    return ctx.stats.bytes_staged / ctx.window.rows
