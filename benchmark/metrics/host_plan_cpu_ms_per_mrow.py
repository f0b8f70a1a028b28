"""Host planning and decompression: ``DecodeStats.plan_cpu_s``, the
plan pool's thread CPU seconds inside the ``tpq.plan`` spans
(``time.thread_time``), per million file rows.  Against
``host_plan_thread_ms_per_mrow`` (the same spans' wall) it splits plan
time into CPU and waiting (GIL, locks, I/O).  None where the program
has no such field."""


def read(ctx):
    v = getattr(ctx.stats, "plan_cpu_s", None)
    if v is None or not ctx.window.rows:
        return None
    return v * 1e3 / (ctx.window.rows / 1e6)
