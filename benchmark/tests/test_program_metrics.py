"""The per-layer metrics that read the program's stage spans and
counters, on the CPU at a small size:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

A traced run needs the chip (``peaks.json`` has TPU entries only), so
the readers get a context holding a small pipelined read's
``DecodeStats`` and window, as ``harness.Context.measure`` would.
"""

from __future__ import annotations

import json
import os
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.datagen import Column, write_parquet  # noqa: E402
from benchmark.harness import BENCH, Window, load  # noqa: E402

READERS = ["plan_wait_ms_per_mrow", "host_plan_cpu_ms_per_mrow",
           "staged_pieces_per_mrow", "dispatch_ms_per_mrow",
           "drain_ms_per_mrow"]
# the consumer's stages, which run in turn on one thread
CONSUMER = ["plan_wait_ms_per_mrow", "transfer_ms_per_mrow",
            "dispatch_ms_per_mrow", "drain_ms_per_mrow"]


def _reader(name):
    return load(os.path.join(BENCH, "metrics", name + ".py"),
                "bench_metric_" + name)


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    import jax

    from tpuparquet.io.reader import FileReader
    from tpuparquet.kernels.device import read_row_groups_device
    from tpuparquet.stats import collect_stats

    rng = np.random.default_rng(7)
    n = 40_000
    valid = rng.random(n) > 0.1
    cols = {
        "id": Column("int64", rng.integers(0, 1 << 40, n),
                     nullable=False),
        "fare": Column("double", rng.normal(20.0, 5.0, n),
                       nullable=False),
        "kind": Column("int32",
                       np.where(valid, rng.integers(0, 6, n), 0)
                       .astype(np.int32), valid=valid),
    }
    path = str(tmp_path_factory.mktemp("metrics") / "f.parquet")
    write_parquet(path, cols, {"row_group_size": 10_000,
                               "compression": "snappy"})

    def read():
        with FileReader(path) as r:
            for _rg, out in read_row_groups_device(r):
                jax.block_until_ready(
                    [x for c in out.values() for x in c._buffers()])

    read()  # compile outside the window
    with collect_stats() as st:
        t = time.perf_counter()
        read()
        window_s = time.perf_counter() - t
    win = Window(attempted=st.row_groups, rows=n, window_s=window_s,
                 end_to_end={}, kept=[])
    return types.SimpleNamespace(stats=st, window=win)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_program(ctx, name):
    v = _reader(name).read(ctx)
    assert v is not None and v > 0


def test_consumer_stages_fit_the_window(ctx):
    total = sum(_reader(m).read(ctx) for m in CONSUMER)
    mrows = ctx.window.rows / 1e6
    assert total <= ctx.window.window_s * 1e3 / mrows


def test_plan_cpu_within_plan_wall(ctx):
    cpu = _reader("host_plan_cpu_ms_per_mrow").read(ctx)
    assert cpu <= _reader("host_plan_thread_ms_per_mrow").read(ctx)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_the_field(name):
    """A program without the stage fields (the commit before them)
    gives nothing, and nothing raises."""
    old = types.SimpleNamespace(plan_s=1.0, transfer_s=1.0,
                                dispatch_s=1.0, bytes_staged=10)
    win = Window(attempted=1, rows=1000, window_s=1.0, end_to_end={},
                 kept=[])
    assert _reader(name).read(
        types.SimpleNamespace(stats=old, window=win)) is None


def test_benchmark_lists_each_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in READERS:
        m = per_layer[name]
        assert m["workloads"] == ["taxi.full-scan"]
        assert m["moves"] == "rows_per_s"
