"""The comparison that decides ``correct``, and its control.

Every number compared is a count of disagreements between what the
timed path delivered and the generator's arrays, so each limit is 0.
The control puts the reference in the program's place, computed one
precision step down (``lower_precision``), and has to fail.
"""

from __future__ import annotations

import numpy as np

# The number compared, with its limit: an exact comparison.  One
# number, so that the control, which moves values only, and every fault
# are read against the same limit.
LIMITS = {"mismatches": 0}


class Tally:
    """Disagreements summed over everything compared in one run, by
    kind: values (and byte strings), levels, and batches whose row
    group, order or row count is wrong."""

    def __init__(self):
        self.kinds = {"values": 0, "levels": 0, "batches": 0}
        self.rows_compared = 0
        self.batches_compared = 0

    def add(self, kind: str, n: int) -> None:
        self.kinds[kind] += int(n)

    @property
    def correct(self) -> bool:
        return sum(self.kinds.values()) <= LIMITS["mismatches"]

    def checks(self) -> dict:
        return {"mismatches": {"value": sum(self.kinds.values()),
                               "limit": LIMITS["mismatches"]}}


def _fixed_mismatch(got: np.ndarray, exp: np.ndarray) -> int:
    """Values that differ bit for bit, plus the length difference."""
    n = min(len(got), len(exp))
    w = exp.dtype.itemsize
    a = np.ascontiguousarray(got[:n]).view(f"u{w}")
    b = np.ascontiguousarray(exp[:n]).view(f"u{w}")
    return int(np.count_nonzero(a != b)) + abs(len(got) - len(exp))


def _bytes_mismatch(got, exp, chunk: int = 1 << 18) -> int:
    """Strings whose length or bytes differ, plus the count
    difference.  Both sides are padded to a matrix a chunk of rows at a
    time, so a wrong offset cannot shift the comparison of later rows."""
    g_off = np.asarray(got[0], dtype=np.int64)
    g_data = np.asarray(got[1], dtype=np.uint8)
    e_off, e_data = exp
    n = min(len(g_off), len(e_off)) - 1
    bad = 0
    for lo in range(0, max(n, 0), chunk):
        hi = min(lo + chunk, n)
        g_len = np.diff(g_off[lo:hi + 1])
        e_len = np.diff(e_off[lo:hi + 1])
        cols = np.arange(int(max(g_len.max(), e_len.max(), 0)))
        row_bad = ((g_len != e_len)
                   | (_padded(g_off[lo:hi], g_len, g_data, cols)
                      != _padded(e_off[lo:hi], e_len, e_data, cols))
                   .any(axis=1))
        bad += int(np.count_nonzero(row_bad))
    return bad + abs(len(g_off) - len(e_off))


def _padded(starts, lens, data, cols):
    if len(data) == 0:
        return np.zeros((len(starts), len(cols)), dtype=np.uint8)
    idx = np.clip(starts[:, None] + cols, 0, len(data) - 1)
    return np.where(cols < lens[:, None], data[idx], 0)


def compare_column(tally: Tally, got, exp_vals, exp_defs) -> None:
    """Compare one decoded column (``DeviceColumn.to_numpy()``'s
    values, rep levels, def levels) with the reference rows."""
    values, rep, defs = got
    if isinstance(exp_vals, tuple):
        tally.add("values",
                  _bytes_mismatch((values.offsets, values.data),
                                  exp_vals))
    else:
        tally.add("values", _fixed_mismatch(np.asarray(values), exp_vals))
    defs = np.asarray(defs)
    n = min(len(defs), len(exp_defs))
    tally.add("levels",
              np.count_nonzero(defs[:n] != exp_defs[:n])
              + abs(len(defs) - len(exp_defs)))
    # flat schemas: every rep level is 0
    tally.add("levels", np.count_nonzero(np.asarray(rep)))
    tally.rows_compared += len(exp_defs)


# -- the control --------------------------------------------------------

class _Bytes:
    """A stand-in for the program's ByteArrayColumn."""

    def __init__(self, offsets, data):
        self.offsets, self.data = offsets, data


def lower_precision(values):
    """The reference one precision step below the configuration's:
    float64 through float32, int64 through int32, int32 through int16,
    and strings cut to a fixed width of 32 bytes (the padded layout a
    device might be tempted to use)."""
    if isinstance(values, tuple):
        from benchmark.datagen import _ranges

        offs, data = values
        lens = np.minimum(np.diff(offs), 32)
        new = np.zeros(len(lens) + 1, dtype=np.int64)
        np.cumsum(lens, out=new[1:])
        return _Bytes(new, data[_ranges(offs[:-1], lens)])
    cast = {np.dtype(np.float64): np.float32,
            np.dtype(np.int64): np.int32,
            np.dtype(np.int32): np.int16}.get(values.dtype)
    if cast is None:
        return values
    return values.astype(cast).astype(values.dtype)

