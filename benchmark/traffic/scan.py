"""Closed-loop full scan: one consumer reads every row group of every
file, round-robin over the files, opening a new ``FileReader`` per
file as a loader opening its next file does.

A batch is one row group, counted once its buffers are ready on the
device.  Its wait runs from asking for it to that moment, so the first
batch of a file carries the file's open and footer.
"""

from __future__ import annotations

import time

import jax

from benchmark.compare import compare_column
from benchmark.harness import Window, annotate, p95


def _columns(ctx) -> list:
    cols = ctx.cell["mix"].get("columns", "all")
    return list(ctx.files[0][2]) if cols == "all" else cols


def prepare(ctx) -> None:
    from benchmark.least_bytes import least_bytes

    ctx.extra["columns"] = _columns(ctx)
    rg_rows = ctx.cell["spec"]["writer"]["row_group_size"]
    # per file: the row range and the least bytes of each row group
    ctx.extra["row_groups"] = [
        [(lo, min(lo + rg_rows, rows)) for lo in range(0, rows, rg_rows)]
        for _, rows, _ in ctx.files]
    ctx.extra["least"] = [least_bytes(path, ctx.extra["columns"], cols)
                          for path, _, cols in ctx.files]


def _buffers(out: dict) -> list:
    return [x for c in out.values() for x in c._buffers()]


def _pass(ctx, i: int):
    """One pass over file ``i``: yields ``(rg, out)`` once ready."""
    from tpuparquet.io.reader import FileReader

    with annotate("bench.open_file"):
        reader = FileReader(ctx.files[i][0], *ctx.extra["columns"])
    gen = ctx.read(reader)
    try:
        while True:
            with annotate("bench.next_batch"):
                item = next(gen, None)
            if item is None:
                return
            with annotate("bench.block"):
                jax.block_until_ready(_buffers(item[1]))
            yield item
    finally:
        gen.close()
        reader.close()


def warm(ctx) -> None:
    """Every file once: every shape the window decodes compiles here."""
    for i in range(len(ctx.files)):
        for _ in _pass(ctx, i):
            pass


def _rows_short(ctx, i: int, rg: int, out: dict) -> int:
    """Rows the batch lacks against its row group, by its worst
    column."""
    lo, hi = ctx.extra["row_groups"][i][rg]
    return max(abs(c.num_values - (hi - lo)) for c in out.values()) \
        + abs(len(out) - len(ctx.extra["columns"]))


def window(ctx, seconds: float) -> Window:
    rng = ctx.rng(1)
    kept, seen, waits = {}, {}, []
    rows = least = failed = 0
    n_files = len(ctx.files)
    t_start = time.perf_counter()
    deadline = t_start + seconds
    i = 0
    last = None
    done = False
    while not done:
        f = i % n_files
        expect = 0
        t = time.perf_counter()
        passing = _pass(ctx, f)
        for rg, out in passing:
            now = time.perf_counter()
            waits.append(now - t)
            lo, hi = ctx.extra["row_groups"][f][rg]
            rows += hi - lo
            least += ctx.extra["least"][f][rg]
            if rg != expect or _rows_short(ctx, f, rg, out):
                failed += 1
            expect = rg + 1
            # one output of every distinct row group the window decodes,
            # its occurrence drawn from the seed (a reservoir of one per
            # row group): the comparison runs after the window
            seen[f, rg] = seen.get((f, rg), 0) + 1
            if rng.integers(0, seen[f, rg]) == 0:
                kept[f, rg] = (f, rg, out)
            del out
            last = now
            if now >= deadline:
                done = True
                break
            t = time.perf_counter()
        passing.close()
        if not done and expect != len(ctx.extra["row_groups"][f]):
            # a pass that ended early: every row group not delivered
            failed += len(ctx.extra["row_groups"][f]) - expect
        done = done or time.perf_counter() >= deadline
        i += 1
    window_s = (last or time.perf_counter()) - t_start
    return Window(attempted=len(waits), rows=rows, window_s=window_s,
                  end_to_end={"rows_per_s": rows / window_s,
                              "batch_p95_ms": p95(waits)},
                  kept=list(kept.values()), failed=failed,
                  least_bytes=least)


def check(ctx, win: Window, tally) -> None:
    """Compare the kept batches with the generator's arrays, one at a
    time, freeing each as it goes."""
    while win.kept:
        f, rg, out = win.kept.pop()
        lo, hi = ctx.extra["row_groups"][f][rg]
        ref = ctx.files[f][2]
        for name in ctx.extra["columns"]:
            exp_vals, exp_defs = ref[name].rows(lo, hi)
            got = out.get(name)
            if got is None:
                tally.add("values", hi - lo)
                continue
            compare_column(tally, got.to_numpy(), exp_vals, exp_defs)
        tally.batches_compared += 1
        del out
