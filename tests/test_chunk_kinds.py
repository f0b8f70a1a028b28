"""The chunk program's page kinds beyond the dictionary and raw PLAIN
fixed-width ones: PLAIN BYTE_ARRAY pages staged raw (``"plain_bytes"``,
also after a dictionary fills mid-chunk), and PLAIN fixed-width pages
under the byte-plane (``"planes"``) and delta-lane (``"delta"``)
transports.  Each column of the table below forces one of them; each
is decoded in one chunk program, bit-identical to the per-page path
and equal to pyarrow's read, with a compile key that a second file of
other exact page counts and byte counts does not change.

The TPC-H lineitem generator of the benchmark (scale 0.01) closes the
file: every chunk of both of its parts is fused, and equals the
generator's arrays.
"""

from __future__ import annotations

import io
import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from tpuparquet.io.reader import FileReader
from tpuparquet.kernels import device as D
from tpuparquet.kernels.decode import chunk_program
from tpuparquet.obs import trace
from tpuparquet.stats import collect_stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS_PER_PAGE = 10_000  # delta lanes pay only on pages this long
N = 56_000              # 6 pages per chunk, the last one short

# column -> the value kinds of its chunk program's groups
KINDS = {
    "sorted_i64": "delta",
    "sorted_i32": "delta",
    "small_i64": "planes",
    "fallback_str": "dict_bytes,plain_bytes",
    "nullable_str": "plain_bytes",
}
COLUMNS = list(KINDS)
WORDS = np.array([b"alpha", b"beta", b"gamma", b"delta", b"epsilon",
                  b"zeta", b"eta", b"theta", b"iota", b"kappa"],
                 dtype=object)


def _strings(rng, n):
    """Nearly distinct strings of 10-36 bytes: a row tag, then words."""
    tags = rng.permutation(n)
    return [b"%07d " % t + b" ".join(WORDS[rng.integers(0, 10, k)])
            for t, k in zip(tags, rng.integers(1, 5, n))]


def _table(seed: int, rows: int):
    rng = np.random.default_rng(seed)
    valid = rng.random(rows) > 0.15
    return pa.table({
        # a key that repeats or steps by one: 1-bit deltas, which ship
        # fewer bytes than its byte planes
        "sorted_i64": pa.array(np.cumsum(rng.integers(0, 2, rows))),
        "sorted_i32": pa.array(np.cumsum(rng.integers(0, 2, rows))
                               .astype(np.int32) - 7),
        "small_i64": pa.array(rng.integers(0, 1 << 20, rows)),
        "fallback_str": pa.array(_strings(rng, rows), type=pa.binary()),
        "nullable_str": pa.array(_strings(rng, rows), type=pa.binary(),
                                 mask=~valid),
    }, schema=pa.schema([
        pa.field("sorted_i64", pa.int64(), nullable=False),
        pa.field("sorted_i32", pa.int32(), nullable=False),
        pa.field("small_i64", pa.int64(), nullable=False),
        pa.field("fallback_str", pa.binary(), nullable=False),
        pa.field("nullable_str", pa.binary()),
    ]))


def _file(seed: int, rows: int = N) -> bytes:
    buf = io.BytesIO()
    pq.write_table(_table(seed, rows), buf, row_group_size=rows,
                   max_rows_per_page=ROWS_PER_PAGE, compression="snappy",
                   use_dictionary=["fallback_str"],
                   dictionary_pagesize_limit=48 << 10)
    return buf.getvalue()


def _read(data: bytes, column: str, per_page=False, monkeypatch=None,
          kinds=None):
    if per_page:
        monkeypatch.setattr(D, "_MAX_CHUNK_GROUPS", -1)
    chunk_column = D._chunk_column
    if kinds is not None:
        def record(plan, *a):
            kinds.append(plan.kinds)
            return chunk_column(plan, *a)

        D._chunk_column = record
    try:
        with collect_stats() as st, FileReader(io.BytesIO(data),
                                               column) as r:
            out = D.read_row_group_device(r, 0)[column]
    finally:
        D._chunk_column = chunk_column
        if per_page:
            monkeypatch.undo()
    return out, st


@pytest.fixture(scope="module")
def main_file():
    return _file(1)


@pytest.fixture(scope="module")
def reads(main_file):
    """Each column read alone, fused and per page, with the kinds of
    its chunk program."""
    mp = pytest.MonkeyPatch()
    out = {}
    for c in COLUMNS:
        kinds = []
        fused, st = _read(main_file, c, kinds=kinds)
        per_page, st_page = _read(main_file, c, per_page=True,
                                  monkeypatch=mp)
        out[c] = (fused, st, per_page, st_page, kinds)
    return out


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


def test_file_forces_each_kind(main_file):
    md = pq.ParquetFile(io.BytesIO(main_file)).metadata.row_group(0)
    enc = {md.column(i).path_in_schema: set(md.column(i).encodings)
           for i in range(md.num_columns)}
    assert {"PLAIN", "RLE_DICTIONARY"} <= enc["fallback_str"]
    for c in ("sorted_i64", "sorted_i32", "small_i64", "nullable_str"):
        assert "RLE_DICTIONARY" not in enc[c], c


@pytest.mark.parametrize("column", COLUMNS)
def test_chunk_is_fused_with_its_kinds(reads, column):
    _, st, _, st_page, kinds = reads[column]
    # the dictionary's fill ends a page early: one more
    assert st.pages == (7 if column == "fallback_str" else 6)
    assert st.chunks == 1
    assert st.chunks_fused == 1 and st.programs_dispatched == 1
    assert kinds == [KINDS[column]]
    assert st_page.chunks_fused == 0
    # the transport is the planner's on both paths
    assert (st.pages_device_delta_lanes, st.pages_device_planes) == \
        (st_page.pages_device_delta_lanes, st_page.pages_device_planes)
    if column.startswith("sorted_"):
        assert st.pages_device_delta_lanes == 6
    if column == "small_i64":
        assert st.pages_device_planes == 6


@pytest.mark.parametrize("column", COLUMNS)
def test_fused_matches_per_page_bit_for_bit(reads, column):
    fused, st, per_page, st_page, _ = reads[column]
    got, want = _host(fused), _host(per_page)
    assert got.keys() == want.keys()
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    assert fused.n_packed == per_page.n_packed
    assert fused.num_values == per_page.num_values
    # the fused path ships the page arrays stacked per group and role
    assert st_page.stacked_arrays == 0 < st.stacked_arrays
    assert st.pieces_staged < st_page.pieces_staged
    assert st.bytes_staged >= st_page.bytes_staged


@pytest.mark.parametrize("column", COLUMNS)
def test_fused_matches_pyarrow(main_file, reads, column):
    fused = reads[column][0]
    ref = pq.read_table(io.BytesIO(main_file), columns=[column])[column]
    vals, _, dl = fused.to_numpy()
    if column == "nullable_str":
        np.testing.assert_array_equal(np.asarray(dl) == 1,
                                      ~np.asarray(ref.is_null()))
    if column.endswith("_str"):
        got = [bytes(vals.data[vals.offsets[i]:vals.offsets[i + 1]])
               for i in range(len(vals.offsets) - 1)]
        assert got == [v for v in ref.to_pylist() if v is not None]
    else:
        np.testing.assert_array_equal(np.asarray(vals), ref.to_numpy())


@pytest.mark.parametrize("column", COLUMNS)
def test_other_exact_counts_compile_no_new_chunk_program(main_file,
                                                          column):
    """Another seed writes other bytes per page, and 5 fewer rows a
    shorter last page: the same buckets, so the same programs."""
    _read(main_file, column)
    before = chunk_program._cache_size()
    other = _file(2, N - 5)
    out, st = _read(other, column)
    assert st.chunks_fused == st.chunks == 1
    assert chunk_program._cache_size() == before
    ref = pq.read_table(io.BytesIO(other), columns=[column])[column]
    assert out.num_values == N - 5
    assert out.n_packed == N - 5 - ref.null_count


@pytest.mark.parametrize("column", COLUMNS)
def test_pages_fused_counts_the_chunks_pages(reads, column):
    _, st, _, st_page, _ = reads[column]
    assert st.pages_fused == st.pages >= 6
    assert st_page.pages_fused == 0
    assert st.as_dict()["pages_fused"] == st.pages


def test_dispatch_span_names_the_kinds(main_file):
    trace.set_tracing(True)
    try:
        with trace.trace_scope("kinds"):
            for _, cols in D.read_row_groups_device(
                    FileReader(io.BytesIO(main_file))):
                for c in cols.values():
                    c.block_until_ready()
        spans = trace.snapshot_spans()
    finally:
        trace.set_tracing(False)
        trace._init_from_env()
        trace._ctx.set(None)
    got = {s["column"]: s["kinds"] for s in spans
           if s["name"] == "dispatch"}
    assert got == KINDS


@pytest.mark.parametrize("rows", [1, 31, 32, 4095, 40_000, 300_000])
@pytest.mark.parametrize("dtype", [np.uint8, np.int32, np.uint32])
def test_staged_shape_is_what_put_gives(rows, dtype):
    stager = D._Stager()
    h = stager.add(np.ones(rows, dtype))
    h_exact = stager.add(np.ones(rows, dtype), pad=False)
    staged = stager.put()
    assert D._staged_shape(stager, h) == staged[h].shape
    assert D._staged_shape(stager, h_exact) == (rows,)


def test_lineitem_generator_fuses_every_chunk(tmp_path):
    """The benchmark's TPC-H lineitem parts at scale 0.01: every chunk
    of every column decodes in one chunk program, equal to the
    generator's arrays."""
    sys.path.insert(0, ROOT)
    from benchmark.harness import load

    gen = load(os.path.join(ROOT, "benchmark", "configs",
                            "tpch-lineitem-sf1", "generate.py"),
               "lineitem_generate")
    files = gen.generate(2500000101, str(tmp_path), scale=0.01)
    assert len(files) == 2
    for path, rows, cols in files:
        with collect_stats() as st, FileReader(path) as r:
            units = [out for _, out in D.read_row_groups_device(r)]
        assert st.chunks == 16 * len(units)
        assert st.chunks_fused == st.chunks
        assert st.pages_fused == st.pages
        for name, want in cols.items():
            vals = [u[name].to_numpy()[0] for u in units]
            if want.values is not None:
                got = np.concatenate([np.asarray(v) for v in vals])
                np.testing.assert_array_equal(
                    got.view(want.values.dtype), want.values,
                    err_msg=name)
                continue
            lens = np.concatenate([np.diff(np.asarray(v.offsets))
                                   for v in vals])
            np.testing.assert_array_equal(lens, np.diff(want.offsets),
                                          err_msg=name)
            got = np.concatenate([np.asarray(v.data)[:v.offsets[-1]]
                                  for v in vals])
            np.testing.assert_array_equal(got, want.data, err_msg=name)
