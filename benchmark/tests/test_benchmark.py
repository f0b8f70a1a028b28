"""The benchmark's own checks, on the CPU at a small size:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- every cell, driven whole with the chip check skipped, reads correct;
- the control and each fault the cells can have, planted under the
  timed path, read not correct;
- the least-bytes count and the trace reduction on a small file and a
  small recorded trace.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.compare import Tally, _bytes_mismatch, \
    compare_column  # noqa: E402
from benchmark.datagen import Column, write_parquet  # noqa: E402
from benchmark.faults import FAULTS  # noqa: E402
from benchmark.least_bytes import least_bytes  # noqa: E402
from benchmark.trace_reduce import reduce_planes  # noqa: E402

CELLS = [w["name"] for w in
         json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
SCALE = 0.01


def _run(cell, seed, read=None):
    return run.run(["--workload", cell, "--seed", str(seed),
                    "--seconds", "1", "--trace", "0"],
                   require_tpu=False, scale=SCALE, read=read)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reads_correct(cell):
    r = _run(cell, 2**31 + 12345)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    names = {m["name"] for m in run.load_cell(cell)["end_to_end"]}
    assert set(r["metrics"]) == names


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_reads_not_correct(cell, fault):
    from tpuparquet.kernels.device import read_row_groups_device

    r = _run(cell, 2**31 + 777, read=FAULTS[fault](read_row_groups_device))
    assert not r["correct"], (fault, r["checks"])


def test_no_tpu_no_result():
    with pytest.raises(run.NoResult, match="needs a TPU"):
        run.run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0"])


def test_bytes_mismatch_counts_rows():
    offs = np.array([0, 2, 5, 5, 9])
    data = np.frombuffer(b"abcdefghi", np.uint8)
    assert _bytes_mismatch((offs, data), (offs, data)) == 0
    bad = data.copy()
    bad[3] ^= 1
    assert _bytes_mismatch((offs, bad), (offs, data)) == 1
    shifted = np.array([0, 3, 5, 5, 9])
    assert _bytes_mismatch((shifted, data), (offs, data)) == 2


def test_compare_column_levels_and_length():
    t = Tally()
    exp = np.arange(10, dtype=np.float64)
    defs = np.ones(10, np.int32)
    compare_column(t, (exp[:9], np.zeros(10, np.int32), defs), exp, defs)
    assert t.kinds["values"] == 1
    compare_column(t, (exp, np.zeros(10, np.int32), defs * 0), exp, defs)
    assert t.kinds["levels"] == 10


def test_least_bytes_small_file(tmp_path):
    n = 5000
    rng = np.random.default_rng(0)
    offs = np.arange(n + 1, dtype=np.int64) * 3
    cols = {"a": Column("int64", values=rng.integers(0, 9, n)),
            "b": Column("double", values=rng.random(n),
                        valid=rng.random(n) < 0.9),
            "s": Column("string", offsets=offs,
                        data=np.full(3 * n, ord("x"), np.uint8))}
    path = str(tmp_path / "f.parquet")
    write_parquet(path, cols, {"row_group_size": 2000})
    import pyarrow.parquet as pq

    md = pq.ParquetFile(path).metadata
    got = least_bytes(path, ["a", "b", "s"], cols)
    assert len(got) == 3
    for r, lb in enumerate(got):
        rg = md.row_group(r)
        rows = rg.num_rows
        valid_b = cols["b"].valid[2000 * r:2000 * r + rows]
        want = sum(rg.column(j).total_compressed_size for j in range(3))
        want += rows * 8 + int(valid_b.sum()) * 8 + rows * 3
        assert lb == want


def _planes():
    ms = 1_000_000
    host = [("bench.window", 0, 100 * ms),
            ("bench.next_batch", 10 * ms, 40 * ms),
            ("bench.open_file", 60 * ms, 80 * ms)]
    ops = [("fusion.1", 20 * ms, 30 * ms), ("copy.2", 25 * ms, 50 * ms),
           ("fusion.1", 90 * ms, 120 * ms)]
    mods = [("jit_decode(7)", 20 * ms, 50 * ms),
            ("jit_other(3)", 90 * ms, 120 * ms)]
    return [("/host:CPU", "python", host),
            ("/device:TPU:0", "XLA Ops", ops),
            ("/device:TPU:0", "XLA Modules", mods)]


def test_trace_reduce_synthetic():
    r = reduce_planes(_planes())
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [20, 50] and [90, 100] inside the window
    assert r["busy_s"] == pytest.approx(0.04)
    assert r["device_ops"][0] == ["jit_decode", pytest.approx(0.03)]
    names = dict((n, s) for n, s in r["idle_gaps"])
    # gaps [0, 20] (mid 10: next_batch starts at 10), [50, 90] (mid 70)
    assert r["idle_by_span"] == {"bench.next_batch": pytest.approx(0.02),
                                 "bench.open_file": pytest.approx(0.04)}
    assert len(names) == 2


def test_trace_reduce_no_device_op():
    """A window in which the device ran nothing reads busy 0, idle
    throughout, under the host span that covers it."""
    planes = [p for p in _planes() if p[1] != "XLA Ops"]
    r = reduce_planes(planes)
    assert r["busy_s"] == 0 and r["devices"] == 0
    assert r["window_s"] == pytest.approx(0.1)
    assert r["idle_gaps"] == [["bench.other", pytest.approx(0.1)]]


def test_trace_reduce_recorded():
    """A 1.5 s traced window of taxi.full-scan recorded on a TPU v5 lite
    (my chip run, PR 22), cut to the device plane's op and program lines
    and the benchmark's host spans.  The run itself reduced the whole
    trace to busy 0.745633 s of a 1.696062 s window."""
    path = os.path.join(ROOT, "benchmark", "testdata",
                        "taxi_trace.json.gz")
    with gzip.open(path) as f:
        planes = json.load(f)
    r = reduce_planes(planes)
    assert r["busy_s"] == pytest.approx(0.745633, abs=1e-6)
    assert r["window_s"] == pytest.approx(1.696062, abs=1e-6)
    assert r["devices"] == 1
    assert r["device_ops"][0][0] == "jit_page_dict_bytes_tbl"
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
