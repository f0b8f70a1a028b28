"""The one unit pipeline in ``kernels/device.py``.

A unit is planned by ``_submit_unit`` and finished by ``_finish_unit``
whichever driver runs it: the window (``pipelined_reads``, filtered or
not) and the one-unit reader (``read_row_group_device``).  Pinned here:

* every unit the window yields equals the one-unit reader's decode of
  the same row group bit for bit — values, levels, offsets, bytes —
  and both count the same pages, values, staged bytes and row groups,
  with and without a filter, on a one-worker pool and a wide one;
* a corrupt page surfaces from both drivers as a ``ScanError`` that
  names its row group.
"""

import io

import numpy as np
import pytest

from tpuparquet import FileReader, FileWriter, collect_stats
from tpuparquet.errors import ScanError
from tpuparquet.faults import inject_faults
from tpuparquet.filter import col
from tpuparquet.kernels.device import pipelined_reads, read_row_group_device

SCHEMA = ("message t { required int64 a; required double b; "
          "optional binary s (STRING); }")
RG_ROWS = 1000


def _a(j: int) -> int:
    # row group 0 holds a < 250 (pruned whole by a >= 500), row group 1
    # straddles 500 (late-materialized), row groups 2-3 survive whole
    if j < RG_ROWS:
        return j // 4
    if j < 2 * RG_ROWS:
        return j - RG_ROWS
    return j


@pytest.fixture(scope="module")
def data() -> bytes:
    buf = io.BytesIO()
    w = FileWriter(buf, SCHEMA)
    for j in range(4 * RG_ROWS):
        w.add_data({"a": _a(j), "b": j * 0.5,
                    "s": f"v{j % 13}" if j % 3 else None})
        if (j + 1) % RG_ROWS == 0:
            w.flush_row_group()
    w.close()
    return buf.getvalue()


def _views(c):
    """Every logical buffer of a DeviceColumn, on the host."""
    return {name: None if x is None else np.asarray(x)
            for name, x in (("data", c.data), ("offsets", c.offsets),
                            ("mask", c.mask), ("positions", c.positions),
                            ("rep", c.rep_levels), ("def", c.def_levels))}


def _assert_same_unit(got, want):
    assert list(got) == list(want)
    for path in want:
        g, w = got[path], want[path]
        assert (g.ptype, g.num_values, g.n_packed, g.n_bytes) == \
            (w.ptype, w.num_values, w.n_packed, w.n_bytes), path
        gv, wv = _views(g), _views(w)
        for name in wv:
            if wv[name] is None:
                assert gv[name] is None, (path, name)
                continue
            assert gv[name].dtype == wv[name].dtype, (path, name)
            assert gv[name].tobytes() == wv[name].tobytes(), (path, name)


def _counts(st):
    return (st.pages, st.values, st.bytes_staged, st.row_groups)


@pytest.mark.parametrize("threads", ["1", "8"])
@pytest.mark.parametrize("filt", [None, "a>=500"], ids=["plain", "filtered"])
def test_window_equals_one_unit_reader(data, filt, threads, monkeypatch):
    monkeypatch.setenv("TPQ_PLAN_THREADS", threads)
    f = None if filt is None else col("a") >= 500
    reader = FileReader(io.BytesIO(data))
    n = reader.row_group_count()
    assert n == 4
    with collect_stats() as st_window:
        window = dict(pipelined_reads([reader], [(0, i) for i in range(n)],
                                      filter=f))
    with collect_stats() as st_single:
        single = {i: read_row_group_device(reader, i, filter=f)
                  for i in range(n)}
    assert sorted(window) == list(range(n))
    for i in range(n):
        _assert_same_unit(window[i], single[i])
    assert _counts(st_window) == _counts(st_single)
    assert st_window.row_groups == n
    if f is not None:
        # the filter really cut: a pruned group and a partial one
        assert window[0]["a"].num_values == 0
        assert 0 < window[1]["a"].num_values < RG_ROWS


def test_corrupt_page_names_its_row_group_from_both_drivers(data):
    reader = FileReader(io.BytesIO(data))
    with inject_faults() as inj:
        inj.inject("kernels.device.page_payload", "corrupt", times=1000,
                   match={"column": "a"})
        with pytest.raises(ScanError) as from_window:
            list(pipelined_reads([reader], [(0, 2), (0, 3)]))
        with pytest.raises(ScanError) as from_single:
            read_row_group_device(reader, 3)
    assert from_window.value.row_group == 2
    assert from_window.value.column == "a"
    assert from_single.value.row_group == 3
    assert from_single.value.column == "a"
