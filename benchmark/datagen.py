"""What the seeded generators share: the column container that is both
the file's content and the reference, and the one writer.

The reference of every cell is the arrays a generator drew from the
seed.  Nothing here decodes Parquet: pyarrow only encodes them.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np


@dataclasses.dataclass
class Column:
    """One generated column of one file.

    Fixed-width columns hold ``values``, one entry per row (a null row
    holds 0).  Byte-array columns hold ``offsets`` (rows + 1, a null
    row has length 0) and ``data``.  ``valid`` is the per-row validity
    of a nullable column, None for a required one.  ``arrow`` is the
    pyarrow type name the column is written as.
    """

    arrow: str
    values: np.ndarray | None = None
    offsets: np.ndarray | None = None
    data: np.ndarray | None = None
    valid: np.ndarray | None = None
    nullable: bool = True

    @property
    def is_bytes(self) -> bool:
        return self.offsets is not None

    def rows(self, lo: int, hi: int):
        """Rows ``[lo, hi)`` as the device path returns them: the
        non-null values packed (fixed width: an array; byte arrays:
        ``(offsets, data)`` rebased to 0) and the def levels."""
        valid = (np.ones(hi - lo, dtype=bool) if self.valid is None
                 else self.valid[lo:hi])
        defs = (np.zeros(hi - lo, dtype=np.int32) if not self.nullable
                else valid.astype(np.int32))
        if not self.is_bytes:
            return self.values[lo:hi][valid], defs
        lens = np.diff(self.offsets[lo:hi + 1])[valid]
        offs = np.zeros(len(lens) + 1, dtype=np.int64)
        np.cumsum(lens, out=offs[1:])
        data = self.data[self.offsets[lo]:self.offsets[hi]]
        return (offs, data), defs


def _ranges(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenated ``arange(s, s + n)`` for every (start, len) pair."""
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    ends = np.cumsum(lens)
    idx = np.ones(total, dtype=np.int64)
    nz = lens > 0
    first = (ends - lens)[nz]
    idx[first[0]] = starts[nz][0]
    if len(first) > 1:
        prev_end = (starts + lens)[nz][:-1]
        idx[first[1:]] = starts[nz][1:] - prev_end + 1
    return np.cumsum(idx)


def _prime_at_least(n: int) -> int:
    n = max(n, 2)
    while any(n % d == 0 for d in range(2, int(n ** 0.5) + 1)):
        n += 1
    return n


def relabel(rng, k: np.ndarray) -> np.ndarray:
    """A seeded bijection of integers onto about their own range,
    ``lo + (a * (k - lo) + b) mod p`` with ``p`` the first prime over
    the range: equal values stay equal and distinct ones distinct, so a
    column keeps the dictionary, runs and pages of the fixed draw it
    came from and only the values move."""
    lo, hi = int(k.min()), int(k.max())
    p = _prime_at_least(hi - lo + 1)
    a, b = int(rng.integers(1, p)), int(rng.integers(0, p))
    return lo + (a * (k - lo) + b) % p


def pick(rng, labels, codes: np.ndarray) -> np.ndarray:
    """``labels`` shuffled by the seed, taken at the fixed ``codes``."""
    return np.asarray(labels)[rng.permutation(len(labels))][codes]


def pick_same_length(rng, labels: list[bytes],
                     codes: np.ndarray) -> np.ndarray:
    """Codes into ``labels`` after a seeded shuffle among the labels of
    equal length, taken at the fixed ``codes``: every row keeps its
    byte length, so each page decodes to the same number of bytes."""
    lens = np.array([len(x) for x in labels])
    order = np.arange(len(labels))
    for n in np.unique(lens):
        same = np.flatnonzero(lens == n)
        order[same] = same[rng.permutation(len(same))]
    return order[codes]


def strings_from_pool(pool: list[bytes], codes: np.ndarray,
                      valid: np.ndarray | None = None) -> tuple:
    """Offsets and bytes of ``pool[codes[i]]`` per row; null rows are
    empty."""
    plens = np.array([len(p) for p in pool], dtype=np.int64)
    pstart = np.zeros(len(pool), dtype=np.int64)
    np.cumsum(plens[:-1], out=pstart[1:])
    blob = np.frombuffer(b"".join(pool), dtype=np.uint8)
    lens = plens[codes]
    if valid is not None:
        lens = np.where(valid, lens, 0)
    offs = np.zeros(len(codes) + 1, dtype=np.int64)
    np.cumsum(lens, out=offs[1:])
    return offs, blob[_ranges(pstart[codes], lens)]


def _arrow_array(c: Column):
    import pyarrow as pa

    n = (len(c.offsets) - 1) if c.is_bytes else len(c.values)
    bitmap = None
    if c.valid is not None:
        bitmap = pa.py_buffer(np.packbits(c.valid, bitorder="little"))
    if c.is_bytes:
        return pa.Array.from_buffers(
            pa.large_string(), n,
            [bitmap, pa.py_buffer(c.offsets), pa.py_buffer(c.data)]
        ).cast(pa.string())
    typ = {"int32": pa.int32(), "int64": pa.int64(),
           "double": pa.float64(), "date32": pa.date32(),
           "timestamp_us": pa.timestamp("us")}.get(c.arrow)
    if typ is None and c.arrow.startswith("decimal"):
        p, s = (int(x) for x in c.arrow[len("decimal("):-1].split(","))
        typ = pa.decimal128(p, s)
        # the unscaled integers are the decimal's own representation
        raw = pa.Array.from_buffers(pa.int64(), n,
                                    [bitmap, pa.py_buffer(c.values)])
        return raw.view(pa.decimal64(p, s)).cast(typ)
    return pa.Array.from_buffers(typ, n, [bitmap,
                                          pa.py_buffer(c.values)])


def write_parquet(path: str, columns: dict[str, Column],
                  writer: dict) -> int:
    """Write the columns with pyarrow and the config's writer settings;
    returns the file's size in bytes."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    fields, arrays = [], []
    for name, c in columns.items():
        arr = _arrow_array(c)
        fields.append(pa.field(name, arr.type, nullable=c.nullable))
        arrays.append(arr)
    table = pa.Table.from_arrays(arrays, schema=pa.schema(fields))
    pq.write_table(table, path, **writer)
    return os.path.getsize(path)
