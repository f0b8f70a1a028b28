"""The chunk program: every page of a column chunk decoded in one
device program (``kernels/decode.chunk_program``), bit-identical to the
per-page path, with a compile key that holds no exact page count.

The per-page path is what a chunk takes when its pages do not all
record a page op, or when they fall into more groups than
``_MAX_CHUNK_GROUPS``; the comparisons set that bound below zero to
decode the same file both ways.
"""

from __future__ import annotations

import io

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from tpuparquet.io.reader import FileReader
from tpuparquet.kernels import device as D
from tpuparquet.kernels.decode import chunk_program
from tpuparquet.stats import collect_stats

N = 11_000
ROWS_PER_PAGE = 2_000  # 6 pages per chunk, the last one short


def _file(table, **kw) -> bytes:
    kw.setdefault("max_rows_per_page", ROWS_PER_PAGE)
    kw.setdefault("row_group_size", len(table))
    kw.setdefault("compression", "snappy")
    buf = io.BytesIO()
    pq.write_table(table, buf, **kw)
    return buf.getvalue()


def _read(data: bytes, columns=(), per_page=False, monkeypatch=None):
    if per_page:
        monkeypatch.setattr(D, "_MAX_CHUNK_GROUPS", -1)
    try:
        with collect_stats() as st, FileReader(io.BytesIO(data),
                                               *columns) as r:
            out = D.read_row_group_device(r, 0)
    finally:
        if per_page:
            monkeypatch.undo()
    return out, st


def _table(seed: int = 7):
    rng = np.random.default_rng(seed)
    valid = rng.random(N) > 0.1
    hole = valid.copy()
    hole[ROWS_PER_PAGE : 2 * ROWS_PER_PAGE] = False  # one all-null page
    words = np.array([b"a", b"bb", b"ccc", b"dddd", b""], dtype=object)
    return pa.table({
        "dict_i64": pa.array(rng.integers(0, 300, N), mask=~valid),
        "dict_f64": pa.array(rng.integers(0, 1500, N) / 8.0, mask=~valid),
        "plain_f64": pa.array(rng.normal(0.0, 1e6, N), mask=~valid),
        "dict_str": pa.array(words[rng.integers(0, 5, N)].tolist(),
                             type=pa.binary(), mask=~valid),
        # the dictionary fills mid-chunk: later pages are PLAIN
        "fallback_f64": pa.array(rng.normal(0.0, 1e6, N), mask=~valid),
        # the dictionary grows page by page: index widths 8..11
        "widths_i64": pa.array(np.arange(N) // 8),
        "all_null_page": pa.array(rng.integers(0, 50, N), mask=~hole),
        "required_i32": pa.array(rng.integers(0, 90, N).astype(np.int32)),
        "dict_f32": pa.array(rng.integers(0, 700, N).astype(np.float32),
                             mask=~valid),
        # 5-byte values: two u32 lanes, the second padded
        "dict_flba": pa.array(
            [bytes([i % 7, i % 11, 3, 4, i % 13]) for i in
             rng.integers(0, 1000, N)], type=pa.binary(5), mask=~valid),
    }, schema=pa.schema([
        pa.field("dict_i64", pa.int64()),
        pa.field("dict_f64", pa.float64()),
        pa.field("plain_f64", pa.float64()),
        pa.field("dict_str", pa.binary()),
        pa.field("fallback_f64", pa.float64()),
        pa.field("widths_i64", pa.int64()),
        pa.field("all_null_page", pa.int64()),
        pa.field("required_i32", pa.int32(), nullable=False),
        pa.field("dict_f32", pa.float32()),
        pa.field("dict_flba", pa.binary(5)),
    ]))


COLUMNS = ["dict_i64", "dict_f64", "plain_f64", "dict_str",
           "fallback_f64", "widths_i64", "all_null_page", "required_i32",
           "dict_f32", "dict_flba"]


def _write_main(table) -> bytes:
    return _file(table,
                 use_dictionary=[c for c in COLUMNS if c != "plain_f64"],
                 dictionary_pagesize_limit=16 << 10)


@pytest.fixture(scope="module")
def main_file():
    return _write_main(_table())


@pytest.fixture(scope="module")
def both_paths(main_file):
    mp = pytest.MonkeyPatch()
    fused, st_fused = _read(main_file)
    per_page, st_page = _read(main_file, per_page=True, monkeypatch=mp)
    return fused, st_fused, per_page, st_page


def _host(col):
    """Every buffer of a column, on the host, at its logical length."""
    vals, rep, dl = col.to_numpy()
    out = {"def": np.asarray(dl), "rep": np.asarray(rep)}
    if col.offsets is not None:
        out["offsets"] = np.asarray(vals.offsets)
        out["bytes"] = np.asarray(vals.data)
    else:
        out["values"] = np.asarray(vals).view(np.uint8)
    if col._def_p is not None:
        out["mask"] = np.asarray(col.mask)
        out["positions"] = np.asarray(col.positions)
    return out


def test_file_has_several_pages_per_chunk(main_file):
    meta = pq.ParquetFile(io.BytesIO(main_file)).metadata
    assert meta.num_row_groups == 1
    pf = pq.ParquetFile(io.BytesIO(main_file))
    encodings = {c: set(meta.row_group(0).column(i).encodings)
                 for i, c in enumerate(COLUMNS)}
    assert "PLAIN" in encodings["fallback_f64"]
    assert "RLE_DICTIONARY" in encodings["fallback_f64"]
    assert "RLE_DICTIONARY" not in encodings["plain_f64"]
    assert pf.read().num_rows == N


@pytest.mark.parametrize("column", COLUMNS)
def test_fused_matches_per_page_bit_for_bit(both_paths, column):
    fused, _, per_page, _ = both_paths
    got, want = _host(fused[column]), _host(per_page[column])
    assert got.keys() == want.keys()
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    assert fused[column].n_packed == per_page[column].n_packed
    assert fused[column].num_values == per_page[column].num_values


@pytest.mark.parametrize("column", COLUMNS)
def test_fused_matches_pyarrow(main_file, both_paths, column):
    fused = both_paths[0][column]
    ref = pq.read_table(io.BytesIO(main_file), columns=[column])[column]
    vals, _, dl = fused.to_numpy()
    valid = ~np.asarray(ref.is_null())
    if column != "required_i32":
        np.testing.assert_array_equal(np.asarray(dl) == 1, valid)
    if column == "dict_str":
        got = [bytes(vals.data[vals.offsets[i]:vals.offsets[i + 1]])
               for i in range(len(vals.offsets) - 1)]
        assert got == [v for v in ref.to_pylist() if v is not None]
    elif column == "dict_flba":
        assert [bytes(r) for r in np.asarray(vals)] == \
            [v for v in ref.to_pylist() if v is not None]
    else:
        np.testing.assert_array_equal(
            np.asarray(vals), ref.drop_null().to_numpy())


def test_every_chunk_is_fused(both_paths):
    _, st, _, st_page = both_paths
    assert st.chunks == len(COLUMNS)
    assert st.chunks_fused == len(COLUMNS)
    assert st.programs_dispatched == len(COLUMNS)
    assert st_page.chunks_fused == 0
    assert st_page.programs_dispatched > 6 * len(COLUMNS)


def test_staging_is_the_same_on_both_paths(both_paths):
    """Both paths ship the same arrays; the fused path's page arrays go
    stacked, one piece per group and role, with their padding slots
    (``tests/test_group_staging.py`` counts both exactly)."""
    _, st, _, st_page = both_paths
    assert st_page.stacked_arrays == 0 < st.stacked_arrays
    assert st.pieces_staged < st_page.pieces_staged
    assert st.bytes_staged >= st_page.bytes_staged


def test_per_page_program_count(monkeypatch):
    """A required dictionary column of 6 pages: 6 page kernels, 6
    slices to the exact page lengths and one concatenate."""
    rng = np.random.default_rng(3)
    t = pa.table({"x": pa.array(rng.integers(0, 90, N).astype(np.int32))},
                 schema=pa.schema([pa.field("x", pa.int32(),
                                            nullable=False)]))
    data = _file(t)
    _, st = _read(data, per_page=True, monkeypatch=monkeypatch)
    assert st.pages == 6
    assert (st.chunks_fused, st.programs_dispatched) == (0, 13)
    _, st = _read(data)
    assert (st.chunks_fused, st.programs_dispatched) == (1, 1)


def _counts_table(null_every: int, rows: int):
    """Every ``null_every``-th row null; dictionaries that are whole
    after the first page, so the index widths never change."""
    rng = np.random.default_rng(21)
    valid = np.arange(rows) % null_every != 0
    words = np.array([b"a", b"bb", b"ccc", b"dddd", b""], dtype=object)
    return pa.table({
        "dict_i64": pa.array(rng.permutation(rows) % 256, mask=~valid),
        "plain_f64": pa.array(rng.normal(0.0, 1e6, rows), mask=~valid),
        "dict_str": pa.array(words[rng.permutation(rows) % 5].tolist(),
                             type=pa.binary(), mask=~valid),
    })


def test_same_buckets_compile_no_new_chunk_program():
    """Two files whose pages hold other exact counts (other nulls, a
    shorter last page) in the same buckets share every chunk program."""
    def read(t):
        return _read(_file(t, use_dictionary=["dict_i64", "dict_str"]))

    read(_counts_table(10, N))
    before = chunk_program._cache_size()
    out, st = read(_counts_table(11, N - 37))
    assert st.chunks_fused == st.chunks == 3
    assert chunk_program._cache_size() == before
    assert out["dict_i64"].num_values == N - 37
    assert out["dict_i64"].n_packed == (N - 37) - len(range(0, N - 37, 11))


def test_single_page_chunk_takes_the_per_page_path(monkeypatch):
    t = _table().slice(0, ROWS_PER_PAGE)
    data = _write_main(t)
    out, st = _read(data)
    assert st.pages == st.chunks == len(COLUMNS)
    assert st.chunks_fused == 0
    ref, _ = _read(data, per_page=True, monkeypatch=monkeypatch)
    for c in COLUMNS:
        got, want = _host(out[c]), _host(ref[c])
        for k in want:
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_degraded_replan_takes_the_per_page_path(main_file, both_paths):
    with collect_stats() as st, FileReader(io.BytesIO(main_file)) as r:
        with D.cpu_fallback_values():
            out = D.read_row_group_device(r, 0)
    assert st.pages_degraded == st.pages
    assert st.chunks_fused == 0
    fused = both_paths[0]
    for c in COLUMNS:
        got, want = _host(out[c]), _host(fused[c])
        for k in ("values", "offsets", "bytes", "def"):
            if k in want:
                np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_delta_page_takes_the_per_page_path():
    rng = np.random.default_rng(5)
    vals = np.cumsum(rng.integers(0, 9, N))
    t = pa.table({"delta": pa.array(vals),
                  "dict": pa.array(rng.integers(0, 40, N))})
    data = _file(t, use_dictionary=["dict"],
                 column_encoding={"delta": "DELTA_BINARY_PACKED"})
    out, st = _read(data, columns=("delta",))
    assert st.chunks == 1 and st.pages == 6
    assert st.chunks_fused == 0
    np.testing.assert_array_equal(out["delta"].to_numpy()[0], vals)
    out, st = _read(data, columns=("dict",))
    assert st.chunks_fused == 1
