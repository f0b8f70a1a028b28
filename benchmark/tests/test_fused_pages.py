"""The ``fused_pages_pct`` reader (``DecodeStats.pages_fused`` over
``pages``) and the ``lineitem.full-scan`` cell's chunk programs, on
the CPU at a small size:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The readers get a context holding a read's ``DecodeStats`` and window,
as ``harness.Context.measure`` would.
"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import BENCH, Window, load  # noqa: E402

CELL = "lineitem.full-scan"


def _reader(name):
    return load(os.path.join(BENCH, "metrics", name + ".py"),
                "bench_metric_" + name)


def _ctx(stats, rows=1000):
    win = Window(attempted=1, rows=rows, window_s=1.0, end_to_end={},
                 kept=[])
    return types.SimpleNamespace(stats=stats, window=win)


@pytest.fixture(scope="module")
def lineitem_ctx(tmp_path_factory):
    """Both parts of the cell's configuration at 1% scale, read as the
    cell's traffic reads them: every column, a new reader per file."""
    import jax

    from tpuparquet.io.reader import FileReader
    from tpuparquet.kernels.device import read_row_groups_device
    from tpuparquet.stats import collect_stats

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    gen = load(os.path.join(ROOT, os.path.dirname(cfg["file"]),
                            "generate.py"), "bench_config_generate")
    files = gen.generate(2**31 + 4242,
                         str(tmp_path_factory.mktemp("lineitem")),
                         scale=0.01)
    with collect_stats() as st:
        for path, _, _ in files:
            with FileReader(path) as r:
                for _, out in read_row_groups_device(r):
                    jax.block_until_ready(
                        [x for c in out.values() for x in c._buffers()])
    return _ctx(st, rows=sum(n for _, n, _ in files))


def test_lineitem_reads_every_chunk_fused(lineitem_ctx):
    st = lineitem_ctx.stats
    assert st.chunks == 2 * 16 and st.pages > st.chunks
    assert _reader("fused_chunks_pct").read(lineitem_ctx) == 100.0
    assert _reader("fused_pages_pct").read(lineitem_ctx) == 100.0


def test_reader_reads_given_counters():
    from tpuparquet.stats import DecodeStats

    ctx = _ctx(DecodeStats(pages=400, pages_fused=300))
    assert _reader("fused_pages_pct").read(ctx) == 75.0
    ctx.stats = DecodeStats()
    assert _reader("fused_pages_pct").read(ctx) is None


def test_reader_is_silent_without_the_field():
    """A program without the counter (the commit before it) gives
    nothing, and nothing raises."""
    old = types.SimpleNamespace(pages=120, chunks=19, chunks_fused=19)
    assert _reader("fused_pages_pct").read(_ctx(old)) is None


def test_benchmark_lists_the_reader_and_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {m["name"]: m for m in bench["per_layer"]}["fused_pages_pct"]
    assert m["workloads"] == ["taxi.full-scan", CELL]
    assert (m["moves"], m["layer"], m["unit"], m["better"]) == \
        ("rows_per_s", "dispatch and drain", "%", "higher")
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tpch-lineitem-sf1", "full-scan", 1)
    for metric in bench["per_layer"]:
        assert CELL in metric["workloads"], metric["name"]
