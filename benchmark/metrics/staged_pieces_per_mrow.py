"""Host-to-device staging: ``DecodeStats.pieces_staged``, the arrays
the batched stager handed to ``jax.device_put``, per million file rows.
None where the program has no such counter."""


def read(ctx):
    v = getattr(ctx.stats, "pieces_staged", None)
    if v is None or not ctx.window.rows:
        return None
    return v / (ctx.window.rows / 1e6)
