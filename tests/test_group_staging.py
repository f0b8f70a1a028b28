"""Group staging: a fused chunk's page arrays ship inside one host buffer
per page group and role (``_Stager.stack``), one ``device_put`` piece
each, and the chunk program takes those stacked arrays as they are.

Every value kind the chunk program fuses is read both ways, fused and
per page (``_MAX_CHUNK_GROUPS`` below zero), and must agree bit for bit
with each other and with pyarrow: values, levels, validity, byte-array
offsets and bytes.  The counters say what shipped: ``pieces_staged`` is
the shared dictionary arrays plus one piece per group and role, and
``stacked_arrays`` the page arrays those pieces replaced.
"""

from __future__ import annotations

import io

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from tpuparquet.io.reader import FileReader
from tpuparquet.kernels import device as D
from tpuparquet.stats import collect_stats

ROWS_PER_PAGE = 10_000  # delta lanes pay only on pages this long
N = 56_000              # 6 pages per chunk: 5 full ones in 8 slots

# column -> the value kinds of its chunk program's groups
KINDS = {
    "dict_i64": "dict",
    "flag_str": "dict_bytes",
    "mode_str": "dict_bytes",
    "plain_f64": "plain",
    "nullable_str": "plain_bytes",
    "small_i64": "planes",
    "sorted_i64": "delta",
}
COLUMNS = list(KINDS)
# columns whose every staged array is one piece on the per-page path
SMALL = ["dict_i64", "flag_str", "mode_str", "plain_f64", "sorted_i64"]
WORDS = np.array([b"MAIL", b"SHIP", b"AIR", b"REG AIR", b"TRUCK",
                  b"RAIL", b"FOB"], dtype=object)


def _table(seed: int = 3):
    rng = np.random.default_rng(seed)
    valid = rng.random(N) > 0.15
    tags = rng.permutation(N)
    strings = [b"%06d " % t + b" ".join(WORDS[rng.integers(0, 7, k)])
               for t, k in zip(tags, rng.integers(1, 4, N))]
    return pa.table({
        "dict_i64": pa.array(rng.integers(0, 300, N), mask=~valid),
        # entries of one length: the fixed-length row gather
        "flag_str": pa.array(np.array([b"A", b"N", b"R"], dtype=object)[
            rng.integers(0, 3, N)].tolist(), type=pa.binary()),
        "mode_str": pa.array(WORDS[rng.integers(0, 7, N)].tolist(),
                             type=pa.binary(), mask=~valid),
        "plain_f64": pa.array(rng.normal(0.0, 1e6, N), mask=~valid),
        "nullable_str": pa.array(strings, type=pa.binary(), mask=~valid),
        "small_i64": pa.array(rng.integers(0, 1 << 20, N)),
        "sorted_i64": pa.array(np.cumsum(rng.integers(0, 2, N))),
    }, schema=pa.schema([
        pa.field("dict_i64", pa.int64()),
        pa.field("flag_str", pa.binary(), nullable=False),
        pa.field("mode_str", pa.binary()),
        pa.field("plain_f64", pa.float64()),
        pa.field("nullable_str", pa.binary()),
        pa.field("small_i64", pa.int64(), nullable=False),
        pa.field("sorted_i64", pa.int64(), nullable=False),
    ]))


def _file(table, **kw) -> bytes:
    buf = io.BytesIO()
    pq.write_table(table, buf, row_group_size=len(table),
                   max_rows_per_page=ROWS_PER_PAGE, **kw)
    return buf.getvalue()


class _Spy:
    """What ``_Stager.stack`` packed (per group: pages, slots and a copy
    of each role's buffer) and the plans ``_chunk_column`` decoded."""

    def __init__(self, mp):
        self.groups, self.plans = [], []
        stack, chunk_column = D._Stager.stack, D._chunk_column

        def spy_stack(stager, pages, slots, arena):
            hs = stack(stager, pages, slots, arena)
            self.groups.append((len(pages), slots,
                                [stager.arrays[h].copy() for h in hs]))
            return hs

        def spy_column(plan, *a):
            self.plans.append(plan)
            return chunk_column(plan, *a)

        mp.setattr(D._Stager, "stack", spy_stack)
        mp.setattr(D, "_chunk_column", spy_column)

    def roles(self) -> int:
        return sum(len(bufs) for _, _, bufs in self.groups)

    def page_arrays(self) -> int:
        return sum(pages * len(bufs) for pages, _, bufs in self.groups)

    def padding_bytes(self) -> int:
        return sum((slots - pages) * buf[0].nbytes
                   for pages, slots, bufs in self.groups for buf in bufs)

    def shared(self) -> int:
        return sum(len(p.shared) for p in self.plans)


def _read(data: bytes, columns, per_page=False, pipelined=False):
    """``{column: DeviceColumn}``, the collector and the spy of one row
    group read fused or per page, by ``read_row_group_device`` or
    ``pipelined_reads``."""
    mp = pytest.MonkeyPatch()
    spy = _Spy(mp)
    if per_page:
        mp.setattr(D, "_MAX_CHUNK_GROUPS", -1)
    try:
        with collect_stats() as st, FileReader(io.BytesIO(data),
                                               *columns) as r:
            if pipelined:
                [(_, out)] = list(D.pipelined_reads([r], [(0, 0)]))
            else:
                out = D.read_row_group_device(r, 0)
    finally:
        mp.undo()
    return out, st, spy


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


def _assert_same(fused, per_page):
    got, want = _host(fused), _host(per_page)
    assert got.keys() == want.keys()
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    assert fused.n_packed == per_page.n_packed
    assert fused.num_values == per_page.num_values


def _assert_pyarrow(col, ref, nullable: bool):
    vals, _, dl = col.to_numpy()
    if nullable:
        np.testing.assert_array_equal(np.asarray(dl) == 1,
                                      ~np.asarray(ref.is_null()))
    want = ref.drop_null()
    if col.offsets is not None:
        offs = np.asarray(vals.offsets)
        blob = np.asarray(vals.data)
        got = [blob[offs[i]:offs[i + 1]].tobytes()
               for i in range(len(offs) - 1)]
        assert got == want.to_pylist()
    else:
        np.testing.assert_array_equal(np.asarray(vals), want.to_numpy())


@pytest.fixture(scope="module")
def main_file():
    return _file(_table(), compression="snappy",
                 use_dictionary=["dict_i64", "flag_str", "mode_str"])


@pytest.fixture(scope="module")
def reads(main_file):
    """Each column read alone, fused and per page."""
    return {c: (_read(main_file, [c]), _read(main_file, [c], per_page=True))
            for c in COLUMNS}


@pytest.mark.parametrize("column", COLUMNS)
def test_each_kind_ships_stacked(reads, column):
    (_, st, spy), (_, st_page, spy_page) = reads[column]
    assert [p.kinds for p in spy.plans] == [KINDS[column]]
    assert st.chunks_fused == st.chunks == 1
    assert st.pages == 6
    assert st.stacked_arrays > 0
    assert (st_page.chunks_fused, st_page.stacked_arrays) == (0, 0)
    assert spy_page.groups == []


@pytest.mark.parametrize("column", COLUMNS)
def test_fused_matches_per_page_and_pyarrow(main_file, reads, column):
    (fused, _, _), (per_page, _, _) = reads[column]
    _assert_same(fused[column], per_page[column])
    table = pq.read_table(io.BytesIO(main_file), columns=[column])
    _assert_pyarrow(fused[column], table[column],
                    table.schema.field(column).nullable)


@pytest.mark.parametrize("column", ["dict_i64", "mode_str",
                                    "nullable_str"])
def test_nullable_column_stacks_its_levels(reads, column):
    """A nullable chunk's level groups stack too: each page's def-level
    stream and run table, in as many slots as its value groups."""
    (fused, st, spy), _ = reads[column]
    [plan] = spy.plans
    assert plan.lev and plan.sig[-1] == 1
    lev_pages = sum(pages for pages, _, bufs in spy.groups[:len(plan.lev)])
    assert lev_pages == st.pages
    assert fused[column].def_levels is not None


@pytest.mark.parametrize("column", COLUMNS)
def test_padding_slots_repeat_the_first_page(reads, column):
    """Five full pages take a group of 8 slots: the three padding slots
    hold the group's first page, as the device stack gave them."""
    (_, st, spy), _ = reads[column]
    padded = [(pages, slots, bufs) for pages, slots, bufs in spy.groups
              if pages < slots]
    assert padded
    for pages, slots, bufs in padded:
        assert slots == D._page_slots(pages)
        for buf in bufs:
            assert buf.shape[0] == slots
            for k in range(pages, slots):
                np.testing.assert_array_equal(buf[k], buf[0])
    # every data page sits in one value group
    [plan] = spy.plans
    assert sum(p for p, _, _ in spy.groups[len(plan.lev):]) == st.pages


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["read_row_group_device", "pipelined_reads"])
def test_pieces_are_shared_arrays_plus_group_roles(main_file, pipelined):
    """One read of every column: one piece per shared array and per
    group role, and the page arrays those roles hold counted as
    stacked; the wire is the per-page path's plus the padding slots."""
    fused, st, spy = _read(main_file, COLUMNS, pipelined=pipelined)
    per_page, st_page, _ = _read(main_file, COLUMNS, per_page=True,
                                 pipelined=pipelined)
    assert st.chunks_fused == len(COLUMNS)
    assert st.pieces_staged == spy.shared() + spy.roles()
    assert st.stacked_arrays == spy.page_arrays()
    assert st.bytes_staged == st_page.bytes_staged + spy.padding_bytes()
    for c in COLUMNS:
        _assert_same(fused[c], per_page[c])


@pytest.mark.parametrize("column", SMALL)
def test_stacked_arrays_are_the_per_page_pieces(reads, column):
    """Where every page array is one piece on the per-page path, the
    fused path ships the shared arrays and one piece per group role in
    place of those page pieces."""
    (_, st, spy), (_, st_page, _) = reads[column]
    assert st_page.pieces_staged == spy.shared() + st.stacked_arrays
    assert st.pieces_staged == spy.shared() + spy.roles()
    assert st.pieces_staged < st_page.pieces_staged


def test_stacked_buffer_over_16_mib_ships_whole():
    """Eleven 2 MB pages of PLAIN strings stack into one buffer of 16
    slots, past ``_PIECE_BYTES``: it ships as one piece, where the
    per-page path cuts each page into five."""
    rng = np.random.default_rng(5)
    rows = 11 * ROWS_PER_PAGE
    letters = rng.integers(97, 123, (rows, 200), dtype=np.uint8)
    table = pa.table({"s": pa.array([r.tobytes() for r in letters],
                                    type=pa.binary())},
                     schema=pa.schema([pa.field("s", pa.binary(),
                                                nullable=False)]))
    data = _file(table, compression="none", use_dictionary=False,
                 data_page_size=8 << 20)
    fused, st, spy = _read(data, ["s"])
    per_page, st_page, _ = _read(data, ["s"], per_page=True)
    [plan] = spy.plans
    assert plan.kinds == "plain_bytes"
    big = [buf for pages, slots, bufs in spy.groups for buf in bufs
           if buf.nbytes > D._PIECE_BYTES]
    assert len(big) == 1 and big[0].shape[0] == 16
    assert st.pieces_staged == spy.roles()
    assert st.stacked_arrays == st.pages == 11
    assert st_page.pieces_staged == 5 * 11
    _assert_same(fused["s"], per_page["s"])
    vals = fused["s"].to_numpy()[0]
    np.testing.assert_array_equal(np.diff(np.asarray(vals.offsets)), 200)
    np.testing.assert_array_equal(
        np.asarray(vals.data)[: rows * 200].reshape(rows, 200), letters)
