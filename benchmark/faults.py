"""The timed path broken on purpose: the control and the faults that
``correct`` has to catch.  Each wraps the device read the window
drives and alters what it delivers, where it is produced.

- ``control``: the reference in the program's place one precision
  step down (``compare.lower_precision``) on every column;
- ``altered``: one value of each batch changed;
- ``half``: each batch delivers the first half of its rows;
- ``dropped``: each file's last row group never arrives.
"""

from __future__ import annotations

import numpy as np

from benchmark.compare import lower_precision


class _Col:
    """A decoded column whose host copy and row count are rewritten."""

    def __init__(self, col, fn, num_values=None):
        self._col, self._fn = col, fn
        self.num_values = (col.num_values if num_values is None
                           else num_values)

    def _buffers(self):
        return self._col._buffers()

    def to_numpy(self, limit=None):
        return self._fn(self._col.to_numpy(limit))


def _flip(triple):
    values, rep, defs = triple
    if hasattr(values, "offsets"):
        data = np.array(values.data, copy=True)
        if len(data):
            data[len(data) // 2] ^= 1
        values = type(values)(values.offsets, data)
    else:
        values = np.array(values, copy=True)
        if len(values):
            raw = values.view(f"u{values.dtype.itemsize}")
            raw[len(raw) // 2] ^= 1
    return values, rep, defs


def _first_half(triple, n):
    values, rep, defs = triple
    kept = int(np.count_nonzero(defs[:n])) if defs.any() else n
    if hasattr(values, "offsets"):
        offs = values.offsets[:kept + 1]
        values = type(values)(offs, values.data[:int(offs[-1])])
    else:
        values = values[:kept]
    return values, rep[:n], defs[:n]


def _lower(triple):
    values, rep, defs = triple
    if hasattr(values, "offsets"):
        values = lower_precision((np.asarray(values.offsets, np.int64),
                                  np.asarray(values.data)))
    else:
        values = lower_precision(np.asarray(values))
    return values, rep, defs


def _per_batch(read, rewrite):
    def wrapped(reader, **kw):
        for rg, out in read(reader, **kw):
            yield rg, rewrite(out)
    return wrapped


def control(read):
    return _per_batch(read, lambda out: {k: _Col(c, _lower)
                                         for k, c in out.items()})


def altered(read):
    def one(out):
        first = next(iter(out))
        return {k: _Col(c, _flip) if k == first else c
                for k, c in out.items()}
    return _per_batch(read, one)


def half(read):
    def one(out):
        return {k: _Col(c, lambda t, n=c.num_values // 2:
                        _first_half(t, n), c.num_values // 2)
                for k, c in out.items()}
    return _per_batch(read, one)


def dropped(read):
    def wrapped(reader, **kw):
        items = list(read(reader, **kw))
        yield from items[:-1]
    return wrapped


FAULTS = {"control": control, "altered": altered, "half": half,
          "dropped": dropped}
