"""The unit pipeline's stage spans (``obs.trace.stage``).

Each stage boundary of the device read — plan, plan wait, transfer,
dispatch, drain — is one call that feeds the profiler's clock (a
``tpq.<stage>`` TraceAnnotation), its ``DecodeStats`` field, the
causal span when tracing is on and the event log.  Pinned here:

* off (no tracer, no profiler, no collector) nothing is recorded, and
  the recorder-guard pass still holds over ``kernels/device.py``;
* under a collector every stage field fills, on every read path;
* under ``jax.profiler.trace`` the plan spans land on a pool thread's
  line and the consumer's stages on the consumer's line;
* with tracing on the doctor's unit rows still sum to the unit wall,
  a consumer's wait counting only where no plan of its unit runs.
"""

import glob
import io
import os
import time

import numpy as np
import pytest

from tpuparquet import FileReader, FileWriter, collect_stats
from tpuparquet.kernels.device import (
    read_row_group_device,
    read_row_groups_device,
)
from tpuparquet.obs import attribution, profiler, recorder, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEMA = ("message t { required int64 a; required double b; "
          "optional binary s (STRING); }")

CONSUMER_STAGES = ("plan_wait", "transfer", "dispatch", "drain")


def make_file(rows=3000, rg_rows=1000) -> bytes:
    buf = io.BytesIO()
    w = FileWriter(buf, SCHEMA, max_row_group_size=rg_rows * 24)
    for j in range(rows):
        w.add_data({"a": j, "b": j * 0.25,
                    "s": f"v{j % 11}" if j % 4 else None})
    w.close()
    return buf.getvalue()


@pytest.fixture(scope="module")
def data():
    return make_file()


def n_groups(data) -> int:
    return FileReader(io.BytesIO(data)).row_group_count()


@pytest.fixture(autouse=True)
def tracing_off():
    trace.set_tracing(False)
    trace._ctx.set(None)
    yield
    trace.set_tracing(False)
    trace._init_from_env()
    trace._ctx.set(None)


def drain(gen):
    for _k, cols in gen:
        for c in cols.values():
            c.block_until_ready()


def read_pipelined(data):
    drain(read_row_groups_device(FileReader(io.BytesIO(data))))


def read_single(data):
    r = FileReader(io.BytesIO(data))
    for rg in range(r.row_group_count()):
        read_row_group_device(r, rg)


def read_filtered(data):
    from tpuparquet.filter import col

    drain(read_row_groups_device(FileReader(io.BytesIO(data)),
                                 filter=col("a") >= 500))


# ----------------------------------------------------------------------
# Off: nothing recorded
# ----------------------------------------------------------------------

def test_off_records_nothing(data, monkeypatch):
    profiler.set_profiling(False)
    ring = recorder.set_ring(512)
    try:
        def boom(*a, **k):
            raise AssertionError("profiler touched while off")

        monkeypatch.setattr(profiler, "stage_begin", boom)
        monkeypatch.setattr(profiler, "ctx_push", boom)
        from tpuparquet.stats import current_stats

        assert current_stats() is None
        read_pipelined(data)
        assert trace.snapshot_spans() == []
        kinds = {e["kind"] for e in ring.snapshot()}
        # the device planner keeps no per-page or per-stage records
        assert "page" not in kinds
        assert not any(k.startswith("span:") for k in kinds)
    finally:
        recorder._init_from_env()


def test_recorder_guard_holds_over_device_py():
    from tools.analyze.astutil import RepoTree
    from tools.analyze.recorderguard import run

    rel = "tpuparquet/kernels/device.py"
    with open(os.path.join(ROOT, rel)) as f:
        assert run(RepoTree({rel: f.read()})) == []


def test_no_stage_flight_records_left():
    """The stage spans replace the flight records that duplicated
    them: no ``span:*`` record in the library, no per-page record in
    the device planner."""
    for path in glob.glob(os.path.join(ROOT, "tpuparquet", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            assert 'flight("span:' not in f.read(), path
    with open(os.path.join(ROOT, "tpuparquet", "kernels",
                           "device.py")) as f:
        assert 'flight("page"' not in f.read()


# ----------------------------------------------------------------------
# Collector fields
# ----------------------------------------------------------------------

def test_pipelined_read_fills_every_stage_field(data):
    with collect_stats() as st:
        read_pipelined(data)
    assert st.row_groups == n_groups(data) > 1
    for f in ("plan_s", "plan_cpu_s", "plan_wait_s", "transfer_s",
              "dispatch_s", "drain_s"):
        assert getattr(st, f) > 0, f
    assert st.pieces_staged > 0
    assert st.plan_cpu_s <= st.plan_s
    # the consumer's stages run in turn on one thread, inside the scope
    assert sum(getattr(st, s + "_s") for s in CONSUMER_STAGES) \
        <= st.wall_s


@pytest.mark.parametrize("read", [read_single, read_filtered],
                         ids=["single", "filtered"])
def test_other_paths_feed_the_same_fields(data, read, monkeypatch):
    # a pool of two: the single-unit path waits on its column plans
    monkeypatch.setenv("TPQ_PLAN_THREADS", "2")
    with collect_stats() as st:
        read(data)
    for f in ("plan_s", "plan_wait_s", "transfer_s"):
        assert getattr(st, f) > 0, f
    assert 0 < st.plan_cpu_s <= st.plan_s
    if read is read_single:
        assert st.dispatch_s > 0 and st.drain_s > 0
        assert st.pieces_staged > 0


def test_pieces_counted_beside_bytes(data):
    with collect_stats() as st:
        read_pipelined(data)
    # every piece carries at least one byte
    assert 0 < st.pieces_staged <= st.bytes_staged


def test_fields_merge_and_print():
    from tpuparquet.stats import DecodeStats

    a, b = DecodeStats(), DecodeStats()
    a.plan_cpu_s, b.plan_cpu_s = 0.25, 0.5
    a.pieces_staged, b.pieces_staged = 3, 4
    b.plan_wait_s, b.drain_s, b.transfer_s = 0.125, 0.0625, 1.0
    a.merge_from(b)
    assert (a.plan_cpu_s, a.pieces_staged) == (0.75, 7)
    assert (a.plan_wait_s, a.drain_s) == (0.125, 0.0625)
    d = a.as_dict()
    for k in ("plan_cpu_s", "plan_wait_s", "drain_s", "pieces_staged"):
        assert k in d
    text = a.summary()
    assert "plan wait 0.125s" in text and "drain 0.062s" in text


def test_profile_phase_print_shows_wait_and_drain(data, tmp_path,
                                                  capsys):
    from tpuparquet.cli.parquet_tool import main

    path = tmp_path / "f.parquet"
    path.write_bytes(data)
    assert main(["profile", str(path)]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines()
                if ln.startswith("phases:"))
    for p in ("plan", "plan wait", "transfer", "dispatch", "drain",
              "wall"):
        assert f" {p} " in line, p
    assert main(["profile", "--json", str(path)]) == 0
    import json

    phases = json.loads(capsys.readouterr().out)["phases"]
    assert {"plan_wait_s", "drain_s"} <= set(phases)


# ----------------------------------------------------------------------
# The profiler's clock
# ----------------------------------------------------------------------

def test_stage_spans_on_the_profiler_lines(data, tmp_path):
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    read_pipelined(data)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("test.consumer"):
            read_pipelined(data)
    found = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                      recursive=True)
    assert found
    lines = {}
    for plane in ProfileData.from_file(found[0]).planes:
        for i, line in enumerate(plane.lines):
            names = {e.name for e in line.events}
            if any(n.startswith(("tpq.", "test.")) for n in names):
                lines[(plane.name, i)] = names
    consumer = [k for k, names in lines.items()
                if "test.consumer" in names]
    assert len(consumer) == 1
    mine = lines[consumer[0]]
    for s in CONSUMER_STAGES:
        assert "tpq." + s in mine, s
    assert "tpq.plan" not in mine
    assert any("tpq.plan" in names for k, names in lines.items()
               if k != consumer[0])


def test_annotation_without_a_trace_is_cheap():
    n = 5000
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(n):
            with trace.stage("drain"):
                pass
        best = min(best, time.perf_counter() - t)
    per = best / n
    # a few microseconds at most, against stages of milliseconds
    assert per < 50e-6


# ----------------------------------------------------------------------
# Causal spans and the doctor
# ----------------------------------------------------------------------

def test_traced_unit_rows_sum_to_wall(data):
    trace.set_tracing(True)
    with trace.trace_scope("t"):
        read_pipelined(data)
    spans = trace.snapshot_spans()
    names = {s["name"] for s in spans}
    assert {"plan", "plan_wait", "transfer", "dispatch",
            "drain", "unit"} <= names
    units = {s["span"] for s in spans if s["name"] == "unit"}
    # every consumer stage parents under its unit; dispatch is per
    # column
    for s in spans:
        if s["name"] in CONSUMER_STAGES:
            assert s["parent"] in units, s
    assert sum(1 for s in spans if s["name"] == "dispatch") \
        == 3 * n_groups(data)
    rows = attribution.unit_reports(spans)
    assert len(rows) == n_groups(data)
    for r in rows:
        assert sum(r["stages_s"].values()) == pytest.approx(
            r["dur_s"], abs=1e-5)


def _unit(children, dur=1.0):
    spans = [{"trace": "t", "span": 1, "parent": None, "name": "scan",
              "t0": 0.0, "dur": dur, "tid": 1, "status": "ok"},
             {"trace": "t", "span": 2, "parent": 1, "name": "unit",
              "t0": 0.0, "dur": dur, "tid": 1, "status": "ok",
              "unit": 0}]
    for i, (name, t0, d, tid) in enumerate(children):
        spans.append({"trace": "t", "span": 3 + i, "parent": 2,
                      "name": name, "t0": t0, "dur": d, "tid": tid,
                      "status": "ok"})
    return spans


def test_wait_counts_only_where_no_plan_runs():
    # the consumer waits 0.0-0.6 while its two column plans run on the
    # pool, one after another, 0.1-0.3 and 0.3-0.5: the wait keeps
    # 0.0-0.1 and 0.5-0.6
    spans = _unit([("plan_wait", 0.0, 0.6, 1), ("plan", 0.1, 0.2, 2),
                   ("plan", 0.3, 0.2, 3), ("transfer", 0.6, 0.1, 1),
                   ("dispatch", 0.7, 0.1, 1), ("drain", 0.8, 0.15, 1)])
    (row,) = attribution.unit_reports(spans)
    st = row["stages_s"]
    assert st["plan"] == pytest.approx(0.4)
    assert st["plan_wait"] == pytest.approx(0.2)
    assert st["drain"] == pytest.approx(0.15)
    assert st["driver"] == pytest.approx(0.05)
    assert sum(st.values()) == pytest.approx(1.0)
    assert row["bound"] == "plan"


def test_parallel_plans_count_once():
    # two column plans run side by side 0.0-0.5 on two threads: the
    # unit's plan bucket is their wall, not their thread-seconds
    spans = _unit([("plan", 0.0, 0.5, 2), ("plan", 0.0, 0.5, 3),
                   ("transfer", 0.5, 0.2, 1)], dur=0.8)
    (row,) = attribution.unit_reports(spans)
    assert row["stages_s"]["plan"] == pytest.approx(0.5)
    assert sum(row["stages_s"].values()) == pytest.approx(0.8)
    # the scan totals stay thread-seconds
    d = attribution.diagnose(spans)
    assert d["stages_s"]["plan"] == pytest.approx(1.0)


def test_new_stages_have_verdicts():
    assert attribution.STAGE_OF["plan_wait"] == "plan_wait"
    assert attribution.STAGE_OF["drain"] == "drain"
    assert attribution.VERDICT_OF["plan_wait"] == "plan-bound"
    assert attribution.VERDICT_OF["drain"] == "decode-bound"
    # drain is decode work in the counter view; a wait is not work
    cpu = attribution.stage_seconds({"drain_s": 0.5,
                                     "plan_wait_s": 2.0})
    assert cpu["drain"] == 0.5 and "plan_wait" not in cpu


def test_error_inside_a_stage_closes_its_span():
    trace.set_tracing(True)
    with collect_stats() as st, trace.trace_scope("t"):
        with pytest.raises(RuntimeError):
            with trace.stage("transfer", "transfer_s", columns=1):
                raise RuntimeError("boom")
    spans = [s for s in trace.snapshot_spans()
             if s["name"] == "transfer"]
    assert len(spans) == 1 and spans[0]["status"] == "error"
    assert spans[0]["columns"] == 1
    assert st.transfer_s > 0
    # the ambient context is the root again
    root = next(s for s in trace.snapshot_spans() if s["name"] == "scan")
    assert spans[0]["parent"] == root["span"]


def test_note_reaches_span_and_event_log():
    trace.set_tracing(True)
    with collect_stats(events=True) as st, trace.trace_scope("t"):
        with trace.stage("plan", "plan_s", cpu="plan_cpu_s",
                         column="a") as sp:
            np.arange(1000).sum()
            sp.note(cache="hit")
    (span,) = [s for s in trace.snapshot_spans() if s["name"] == "plan"]
    assert (span["column"], span["cache"]) == ("a", "hit")
    (ev,) = st.events.spans
    assert ev["name"] == "plan"
    assert ev["args"] == {"column": "a", "cache": "hit"}
    assert 0 <= st.plan_cpu_s and st.plan_s > 0
