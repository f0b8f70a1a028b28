"""The dictionary BYTE_ARRAY gather: a row gather where every
dictionary entry has one length, and otherwise a running count of each
value's source shift over the output bytes.

Each case is checked three ways: the kernel against the per-byte
binary search over the output offsets (the plain reference, kept
below) and against pyarrow, on the page's valid bytes; then a file of
the case's values read through the chunk program and through the
per-page kernels, against each other and against pyarrow, with the
``DecodeStats`` counters of the byte-array dictionary pages.  The
benchmark's TPC-H lineitem generator closes the file.
"""

from __future__ import annotations

import io
import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax
import jax.numpy as jnp

from tpuparquet.io.reader import FileReader
from tpuparquet.kernels import device as D
from tpuparquet.kernels.decode import _dict_bytes_gather, bucket
from tpuparquet.stats import collect_stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _comments(k: int, lo: int, hi: int, seed: int = 5) -> list:
    rng = np.random.default_rng(seed)
    return [bytes(rng.integers(97, 123, n, dtype=np.uint8))
            for n in rng.integers(lo, hi, k)]


# name -> (dictionary entries, values a page, index slots a page):
# slots past the values are padding, as a bucketed index expansion has
CASES = {
    "fixed-1": ([b"A", b"N", b"R"], 20_000, 32_768),
    "fixed-3": ([b"abc", b"def", b"ghi", b"jkl"], 5_000, 8_192),
    "empty-first": ([b"", b"x", b"yy", b"zzz"], 3_000, 4_096),
    "empty-middle": ([b"aa", b"", b"bbbb", b"c"], 3_000, 4_096),
    "empty-last": ([b"aa", b"b", b"cccc", b""], 3_000, 4_096),
    "all-empty": ([b"", b""], 500, 512),
    "padded-slots": ([b"REG AIR", b"AIR", b"RAIL", b"FOB"], 700, 1_024),
    "one-value": ([b"TRUCK", b"MAIL"], 1, 32),
    "block-boundary": ([b"q" * 1020, b"0123456789", b"z"], 40, 64),
    "cap-32Ki": ([b"N", b"NO", b"", b"YES"], 20_000, 32_768),
    "cap-1Mi": (_comments(40, 30, 50), 20_000, 32_768),
}


def _fixed(entries) -> int:
    lens = {len(e) for e in entries}
    return lens.pop() if len(lens) == 1 else 0


def _indices(name, entries, nn, icnt, seed=0):
    """A page's index slots: ``nn`` values, then padding that reaches
    past the dictionary (the gather clamps it)."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(entries), icnt).astype(np.int32)
    if name == "block-boundary":
        idx[:3] = [0, 1, 2]  # the second value spans bytes 1020-1029
    idx[nn:] = rng.integers(0, len(entries) + 9, icnt - nn)
    return idx


def _staged_dictionary(entries):
    """Offsets and bytes as the stager pads them (zeros to a bucket);
    an all-empty dictionary's blob stays empty."""
    offs = np.zeros(len(entries) + 1, np.int32)
    np.cumsum([len(e) for e in entries], out=offs[1:])
    data = np.frombuffer(b"".join(entries), np.uint8)
    offs = np.pad(offs, (0, bucket(offs.size) - offs.size))
    if data.size:
        data = np.pad(data, (0, bucket(data.size) - data.size))
    return offs, data


def _search_gather(dict_offsets, dict_data, idx, non_null, total_bytes):
    """The reference: every output byte finds its value by a binary
    search over the output offsets."""
    n_dict = dict_offsets.shape[0] - 1
    idx = jnp.clip(idx, 0, max(n_dict - 1, 0))
    lens = dict_offsets[1:] - dict_offsets[:-1]
    valid = jnp.arange(idx.shape[0], dtype=jnp.int32) < non_null
    out = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(jnp.where(valid, lens[idx], 0))])
    if dict_data.shape[0] == 0:
        return jnp.zeros((total_bytes,), jnp.uint8)
    b = jnp.arange(total_bytes, dtype=jnp.int32)
    val = jnp.minimum(jnp.searchsorted(out[1:], b, side="right"),
                      idx.shape[0] - 1)
    src = dict_offsets[idx[val]] + (b - out[val])
    return dict_data[jnp.clip(src, 0, dict_data.shape[0] - 1)]


def _pyarrow_bytes(entries, idx) -> bytes:
    vals = pa.array(entries, pa.binary()).take(pa.array(idx))
    return b"".join(vals.to_pylist())


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_search_and_pyarrow(name):
    entries, nn, icnt = CASES[name]
    idx = _indices(name, entries, nn, icnt)
    want = _pyarrow_bytes(entries, idx[:nn])
    cap = bucket(max(len(want), 1))
    offs, data = _staged_dictionary(entries)
    ref = np.asarray(jax.jit(_search_gather, static_argnums=4)(
        offs, data, idx, np.int32(nn), cap))
    assert ref[:len(want)].tobytes() == want
    gather = jax.jit(_dict_bytes_gather, static_argnums=(4, 5))
    for width in {0, _fixed(entries)}:
        got = np.asarray(gather(offs, data, idx, np.int32(nn), cap, width))
        assert got.shape == (cap,) and got.dtype == np.uint8
        assert got[:len(want)].tobytes() == want, width


def _file(name) -> bytes:
    """A column of the case's values over four pages of at least 2,000
    rows and the case's values a page (so its byte cap), its entries
    first so the writer's dictionary keeps their order; a nullable
    column for the padded case, a last page of one value for the
    one-value case."""
    entries, nn, _ = CASES[name]
    per_page = max(nn, 2_000)
    rows = 3 * per_page + (1 if name == "one-value" else 700)
    rng = np.random.default_rng(11)
    idx = np.concatenate([np.arange(len(entries)),
                          rng.integers(0, len(entries),
                                       rows - len(entries))])
    mask = None
    if name == "padded-slots":
        mask = rng.random(rows) < 0.3
        mask[:len(entries)] = False
    col = pa.array([entries[i] for i in idx], pa.binary(), mask=mask)
    nullable = mask is not None
    table = pa.table({"s": col}, schema=pa.schema(
        [pa.field("s", pa.binary(), nullable=nullable)]))
    buf = io.BytesIO()
    pq.write_table(table, buf, row_group_size=rows,
                   max_rows_per_page=per_page, compression="snappy",
                   use_dictionary=True)
    return buf.getvalue()


def _read(data, monkeypatch, per_page: bool):
    if per_page:
        monkeypatch.setattr(D, "_MAX_CHUNK_GROUPS", -1)
    try:
        with collect_stats() as st, FileReader(io.BytesIO(data), "s") as r:
            col = D.read_row_group_device(r, 0)["s"]
    finally:
        monkeypatch.undo()
    vals, _, dl = col.to_numpy()
    offs = np.asarray(vals.offsets)
    return (offs, np.asarray(vals.data)[:offs[-1]],
            None if dl is None else np.asarray(dl)), st


@pytest.mark.parametrize("name", list(CASES))
def test_chunk_program_and_page_kernels(name, monkeypatch):
    data = _file(name)
    fused, st = _read(data, monkeypatch, per_page=False)
    paged, st_page = _read(data, monkeypatch, per_page=True)
    assert st.chunks_fused == 1 and st_page.chunks_fused == 0
    for got, want in zip(fused, paged):
        np.testing.assert_array_equal(got, want)
    ref = pq.read_table(io.BytesIO(data))["s"].to_pylist()
    offs, blob, _ = fused
    got = [blob[offs[i]:offs[i + 1]].tobytes()
           for i in range(len(offs) - 1)]
    assert got == [v for v in ref if v is not None]
    fixed = _fixed(CASES[name][0])
    for s in (st, st_page):
        assert s.pages == 4
        assert s.dict_bytes_pages == s.pages
        assert s.dict_bytes_fixed_pages == (s.pages if fixed else 0)
        assert s.as_dict()["dict_bytes_fixed_pages"] == \
            s.dict_bytes_fixed_pages


def test_lineitem_generator_fixed_share(tmp_path):
    """The benchmark's TPC-H lineitem parts at scale 0.01, fused: every
    string column equals the generator's arrays, and the one-byte flag
    and status columns are the fixed-length pages, about half of the
    byte-array dictionary pages."""
    sys.path.insert(0, ROOT)
    from benchmark.harness import load

    gen = load(os.path.join(ROOT, "benchmark", "configs",
                            "tpch-lineitem-sf1", "generate.py"),
               "lineitem_generate")
    path, _, cols = gen.generate(2500000101, str(tmp_path), scale=0.01)[0]
    fixed = dict_pages = 0
    for name, want in cols.items():
        with collect_stats() as st, FileReader(path, name) as r:
            units = [out[name] for _, out in D.read_row_groups_device(r)]
        assert st.chunks_fused == st.chunks
        fixed += st.dict_bytes_fixed_pages
        dict_pages += st.dict_bytes_pages
        if name in ("l_returnflag", "l_linestatus"):
            assert st.dict_bytes_fixed_pages == st.pages, name
        elif want.values is not None:
            assert st.dict_bytes_pages == 0, name
            continue
        else:
            assert st.dict_bytes_fixed_pages == 0, name
        assert 0 < st.dict_bytes_pages <= st.pages, name
        vals = [u.to_numpy()[0] for u in units]
        lens = np.concatenate([np.diff(np.asarray(v.offsets))
                               for v in vals])
        np.testing.assert_array_equal(lens, np.diff(want.offsets))
        got = np.concatenate([np.asarray(v.data)[:v.offsets[-1]]
                              for v in vals])
        np.testing.assert_array_equal(got, want.data, err_msg=name)
    assert 0.35 <= fixed / dict_pages <= 0.55
