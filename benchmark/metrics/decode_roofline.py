"""Decode kernels: the least bytes of the row groups the window decoded
(``benchmark/least_bytes.py``, from footer and schema) over the chip's
HBM bandwidth (``peaks.json``), as a share of the device's busy time in
the trace.  Busy time covers every device operation, so the share is of
the device's whole decode work."""


def read(ctx):
    least = ctx.window.least_bytes
    if not least or ctx.trace is None or not ctx.trace["busy_s"]:
        return None
    least_s = least / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / ctx.trace["busy_s"]
