"""The per-layer metrics that read the chunk-program counters
(``DecodeStats.chunks_fused``, ``programs_dispatched``), on the CPU at
a small size:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The readers get a context holding a small pipelined read's
``DecodeStats`` and window, as ``harness.Context.measure`` would, on a
file whose chunks have several pages each.
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

READERS = ["fused_chunks_pct", "dispatch_programs_per_mrow"]


def _reader(name):
    return load(os.path.join(BENCH, "metrics", name + ".py"),
                "bench_metric_" + name)


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    import jax

    from tpuparquet.io.reader import FileReader
    from tpuparquet.kernels.device import read_row_groups_device
    from tpuparquet.stats import collect_stats

    rng = np.random.default_rng(11)
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
    path = str(tmp_path_factory.mktemp("chunk_metrics") / "f.parquet")
    # several pages per chunk, so each chunk decodes as one program
    write_parquet(path, cols, {"row_group_size": 10_000,
                               "max_rows_per_page": 2_500,
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


def test_every_chunk_of_the_read_is_fused(ctx):
    assert _reader("fused_chunks_pct").read(ctx) == 100.0
    assert ctx.stats.programs_dispatched == ctx.stats.chunks


def test_readers_read_given_counters():
    from tpuparquet.stats import DecodeStats

    win = Window(attempted=1, rows=2_000_000, window_s=1.0,
                 end_to_end={}, kept=[])
    ctx = types.SimpleNamespace(
        stats=DecodeStats(chunks=40, chunks_fused=38,
                          programs_dispatched=150), window=win)
    assert _reader("fused_chunks_pct").read(ctx) == 95.0
    assert _reader("dispatch_programs_per_mrow").read(ctx) == 75.0
    ctx.stats = DecodeStats()
    assert _reader("fused_chunks_pct").read(ctx) is None
    assert _reader("dispatch_programs_per_mrow").read(ctx) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_the_field(name):
    """A program without the counters (the commit before them) gives
    nothing, and nothing raises."""
    old = types.SimpleNamespace(chunks=19, plan_s=1.0, dispatch_s=1.0)
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
        assert m["layer"] == "dispatch and drain"
