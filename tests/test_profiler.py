"""Round-20 continuous sampling profiler.

Covers the round's acceptance criteria at test scale:

* exact state algebra — bucket/stack counts and the three profiler
  counters merge elementwise (``merge_profile_states``, per-host
  ``allgather_profiles``), bucket totals conserve the process
  counters, and the native export round-trips;
* samples correlate with the causal trace — every tagged sample's
  ``(trace, span)`` names a real span of the scan it was taken
  during, and hot-site stage hints tag samples while the work runs;
* off-CPU classification — a contended lock acquire samples as
  ``[lock-wait <site>]`` at the round-19 lockcheck site identity, and
  a seeded ``io.chunk.hang`` stall samples as
  ``[io-wait io.reader.chunk_read]`` under the ``read`` stage;
* the doctor's consistency contract — per-stage sampled seconds stay
  inside the span-derived stage walls on a real traced scan, and the
  dominant stage has a non-trivial top frame;
* scan results are byte-identical with the profiler on vs off, with
  exact counter conservation;
* teardown ordering — the profiler's exit flush serializes with the
  snapshot writer's through the shared ``live._flush_lock``;
* the profiler-off hot path is structurally zero-cost.
"""

import json
import threading
import time

import numpy as np
import pytest

from tpuparquet import FileWriter, collect_stats
from tpuparquet import lockcheck
from tpuparquet.faults import inject_faults
from tpuparquet.obs import attribution, live, trace
from tpuparquet.obs import profiler as profiler_mod
from tpuparquet.obs.profiler import (
    Profiler,
    collapsed_lines,
    diff_states,
    load_profile_file,
    merge_profile_states,
    profile_consistency,
    top_frames,
    write_profile_file,
)
from tpuparquet.shard.distributed import allgather_profiles
from tpuparquet.shard.scan import ShardedScan

SCHEMA = ("message t { required int64 a; required double b; "
          "optional binary s (STRING); }")


def write_file(path, rows=400, rg_rows=100, seed=0):
    with open(path, "wb") as f:
        w = FileWriter(f, SCHEMA, max_row_group_size=rg_rows * 24)
        for j in range(rows):
            w.add_data({"a": j + seed, "b": (j + seed) * 0.5,
                        "s": f"r{j}" if j % 3 else None})
        w.close()
    return str(path)


@pytest.fixture
def corpus(tmp_path):
    return [write_file(tmp_path / f"f{i}.parquet", seed=i * 1000)
            for i in range(2)]


@pytest.fixture(autouse=True)
def fresh_profiling():
    """Every test starts disarmed on fresh registries; the env
    defaults (stage 16 runs this suite under ``TPQ_PROFILE=1``) are
    restored after so later suites in the same process keep their
    armed sampler."""
    live.reset_registry()
    attribution.reset_ledgers()
    profiler_mod.set_profiling(False)
    trace.set_tracing(False)
    trace._ctx.set(None)
    yield
    profiler_mod.set_profiling(False)
    trace.set_tracing(False)
    trace._init_from_env()
    trace._ctx.set(None)
    profiler_mod._init_from_env()


def _busy(stop):
    x = 0
    while not stop.is_set():
        x += 1
    return x


def _stacks(state):
    for lb, stages in state["buckets"].items():
        for stg, b in stages.items():
            for stack, cnt in b["stacks"].items():
                yield lb, stg, stack, cnt


# ----------------------------------------------------------------------
# state algebra
# ----------------------------------------------------------------------

def _host_state(samples, offcpu, drops, stacks, label="scan",
                stage="read", period=0.02):
    return {
        "period_s": period, "hz": 1.0 / period,
        "counters": {"profile_samples": samples,
                     "profile_samples_offcpu": offcpu,
                     "profile_drops": drops},
        "buckets": {label: {stage: {
            "samples": sum(stacks.values()),
            "offcpu": offcpu,
            "stacks": dict(stacks)}}},
    }


class TestStateAlgebra:
    def test_merge_is_exact_elementwise(self):
        a = _host_state(6, 2, 1, {"f;g": 4, "f;h": 2})
        b = _host_state(9, 0, 0, {"f;g": 5, "f;k": 4})
        c = _host_state(3, 1, 2, {"q;r": 3}, label="", stage="write",
                        period=0.01)
        m = merge_profile_states([a, {}, b, c])
        assert m["counters"] == {"profile_samples": 18,
                                 "profile_samples_offcpu": 3,
                                 "profile_drops": 3}
        rd = m["buckets"]["scan"]["read"]
        assert rd["stacks"] == {"f;g": 9, "f;h": 2, "f;k": 4}
        assert rd["samples"] == 15
        assert m["buckets"][""]["write"]["stacks"] == {"q;r": 3}
        # the period comes from the first state carrying one
        assert m["period_s"] == 0.02

    def test_profiler_merge_state_matches_module_fold(self):
        a = _host_state(6, 2, 1, {"f;g": 4, "f;h": 2})
        b = _host_state(9, 0, 0, {"f;g": 5, "f;k": 4})
        p = Profiler(hz=50.0)
        p.merge_state(a)
        p.merge_state(b)
        folded = merge_profile_states([a, b])
        got = p.to_state()
        assert got["counters"] == folded["counters"]
        assert got["buckets"] == folded["buckets"]

    def test_bucket_totals_conserve_counters(self):
        """After a real sampling run, the buckets ARE the ledger: the
        per-bucket samples sum to the process counter exactly, and
        every bucket's stack counts sum to its sample count."""
        p = profiler_mod.set_profiling(True, hz=50, start=False)
        stop = threading.Event()
        ts = [threading.Thread(target=_busy, args=(stop,))
              for _ in range(3)]
        for t in ts:
            t.start()
        try:
            for _ in range(20):
                p.sample_once()
        finally:
            stop.set()
            for t in ts:
                t.join(2)
        st = p.to_state()
        assert st["counters"]["profile_samples"] > 0
        bucket_samples = bucket_offcpu = 0
        for stages in st["buckets"].values():
            for b in stages.values():
                bucket_samples += b["samples"]
                bucket_offcpu += b["offcpu"]
                assert sum(b["stacks"].values()) == b["samples"]
        assert bucket_samples == st["counters"]["profile_samples"]
        assert bucket_offcpu == st["counters"]["profile_samples_offcpu"]

    def test_native_export_roundtrips(self, tmp_path):
        a = _host_state(6, 2, 1, {"f;g": 4, "f;h": 2})
        path = str(tmp_path / "p.prof")
        assert write_profile_file(a, path)
        doc = load_profile_file(path)
        assert doc["format"] == "tpq-profile"
        # the loaded envelope works directly as a state
        m = merge_profile_states([doc, a])
        assert m["counters"]["profile_samples"] == 12
        assert m["buckets"]["scan"]["read"]["stacks"]["f;g"] == 8

    def test_collapsed_and_chrome_exports(self, tmp_path):
        a = _host_state(6, 2, 1, {"f;g": 4, "f;h": 2})
        lines = collapsed_lines(a)
        assert lines == sorted(lines)
        assert "scan;read;f;g 4" in lines
        cpath = str(tmp_path / "p.collapsed")
        assert write_profile_file(a, cpath)
        with open(cpath) as f:
            assert f.read().splitlines() == lines
        jpath = str(tmp_path / "p.chrome.json")
        assert write_profile_file(a, jpath)
        with open(jpath) as f:
            doc = json.load(f)
        assert any(e.get("name") == "g" for e in doc["traceEvents"])
        with pytest.raises(ValueError):
            load_profile_file(cpath)

    def test_diff_states_localizes_growth(self):
        a = _host_state(10, 0, 0, {"f;g": 5, "f;h": 5})
        b = _host_state(10, 0, 0, {"f;g": 9, "f;h": 1})
        rows = diff_states(a, b)
        by = {r["frame"]: r for r in rows}
        assert by["g"]["delta"] == pytest.approx(0.4)
        assert by["h"]["delta"] == pytest.approx(-0.4)
        assert by["f"]["delta"] == pytest.approx(0.0)

    def test_consistency_noise_floor_is_poisson_scale(self):
        # few samples on a short stage: counting noise (3 sqrt(n)
        # samples) must not trip the doctor ...
        a = _host_state(18, 0, 0, {"f;g": 18}, period=0.005)
        assert profile_consistency(a, {"read": 0.06}) == []
        # ... but a genuine 2x disagreement with MANY samples still
        # does — the sqrt term vanishes relative to n
        b = _host_state(4000, 0, 0, {"f;g": 4000}, period=0.005)
        warns = profile_consistency(b, {"read": 10.0})
        assert len(warns) == 1 and "read" in warns[0]

    def test_allgather_profiles_single_process(self):
        p = profiler_mod.set_profiling(True, hz=50, start=False)
        stop = threading.Event()
        t = threading.Thread(target=_busy, args=(stop,))
        t.start()
        try:
            for _ in range(5):
                p.sample_once()
        finally:
            stop.set()
            t.join(2)
        mine = p.to_state()
        fleet = allgather_profiles()
        assert fleet["counters"] == mine["counters"]
        assert fleet["buckets"] == mine["buckets"]
        # an unarmed host contributes an empty payload that folds to 0
        profiler_mod.set_profiling(False)
        empty = allgather_profiles()
        assert empty["counters"]["profile_samples"] == 0
        assert empty["buckets"] == {}


# ----------------------------------------------------------------------
# sampling mechanics: stage hints + wait markers
# ----------------------------------------------------------------------

class TestSamplingMechanics:
    def _one_sample_with(self, p, setup):
        """Run a worker that calls ``setup`` then busy-waits; sample
        it once and return the state."""
        ready = threading.Event()
        stop = threading.Event()
        toks = []

        def worker():
            toks.append(setup())
            ready.set()
            _busy(stop)

        t = threading.Thread(target=worker)
        t.start()
        assert ready.wait(2)
        try:
            assert p.sample_once() >= 1
        finally:
            stop.set()
            t.join(2)
        return p.to_state()

    def test_stage_hint_tags_samples(self):
        p = profiler_mod.set_profiling(True, hz=50, start=False)
        st = self._one_sample_with(
            p, lambda: profiler_mod.stage_begin("write"))
        assert "write" in st["buckets"][""]
        assert st["buckets"][""]["write"]["samples"] >= 1

    def test_untagged_thread_lands_in_other(self):
        p = profiler_mod.set_profiling(True, hz=50, start=False)
        st = self._one_sample_with(p, lambda: None)
        assert "other" in st["buckets"][""]

    def test_io_wait_marks_offcpu_and_defaults_read_stage(self):
        p = profiler_mod.set_profiling(True, hz=50, start=False)
        st = self._one_sample_with(
            p, lambda: profiler_mod.wait_begin("io", "tests.demo"))
        b = st["buckets"][""]["read"]
        assert b["offcpu"] >= 1
        assert any(s.endswith("[io-wait tests.demo]")
                   for s in b["stacks"])
        assert st["counters"]["profile_samples_offcpu"] >= 1

    def test_nested_wait_restores_outer(self):
        p = profiler_mod.set_profiling(True, hz=50, start=False)
        outer_tok = profiler_mod.wait_begin("io", "outer")
        inner_tok = profiler_mod.wait_begin("lock", "inner")
        tid = threading.get_ident()
        assert p._waits[tid] == ("lock", "inner")
        profiler_mod.wait_end(inner_tok)
        assert p._waits[tid] == ("io", "outer")
        profiler_mod.wait_end(outer_tok)
        assert tid not in p._waits

    def test_stage_end_none_token_is_noop(self):
        # the hot-site finally runs with ptok=None when the profiler
        # was off at entry — both *_end twins must absorb it
        profiler_mod.stage_end(None)
        profiler_mod.wait_end(None)


# ----------------------------------------------------------------------
# trace correlation on a real scan
# ----------------------------------------------------------------------

class TestTraceCorrelation:
    def test_samples_name_real_spans_of_the_scan(self, tmp_path,
                                                 monkeypatch):
        from tpuparquet.kernels import device as device_mod

        paths = [write_file(tmp_path / f"c{i}.parquet", rows=3000,
                            seed=i * 100) for i in range(2)]
        trace.set_tracing(True)
        p = profiler_mod.set_profiling(True, hz=100, start=False)
        scan = ShardedScan(paths)
        # hold the last unit's first plan task until the consumer has
        # sampled at the unit before it: a worker is then inside that
        # unit's span whatever the pool's speed (the window submits
        # unit k+1 before it yields unit k).  Late, because the
        # sampler keeps only its newest 512 samples in ``recent``.
        fi_last, rg_last = scan.units[-1]
        reader_last = scan.readers[fi_last]
        real_plan = device_mod._plan_one_column
        entered, release = threading.Event(), threading.Event()

        def held_plan(reader, rg_index, *args, **kwargs):
            if reader is reader_last and rg_index == rg_last \
                    and not entered.is_set():
                entered.set()
                release.wait(10)
            return real_plan(reader, rg_index, *args, **kwargs)

        monkeypatch.setattr(device_mod, "_plan_one_column", held_plan)
        try:
            for k, cols in scan.run_iter():
                held = k == len(scan.units) - 2
                if held:
                    assert entered.wait(10), "the last unit never planned"
                # drive the sampler from the consumer while the worker
                # pool decodes the next units concurrently
                p.sample_once()
                p.sample_once()
                if held:
                    release.set()
                for c in cols.values():
                    c.block_until_ready()
        finally:
            release.set()
        profiler_mod.set_profiling(False)
        spans = trace.snapshot_spans()
        span_ids = {(s["trace"], s["span"]) for s in spans}
        trace_ids = {s["trace"] for s in spans}
        tagged = [r for r in p.recent if r["trace"] is not None]
        assert tagged, "no sample landed inside a traced unit"
        # every tagged sample names THIS scan's trace and a real span
        assert {r["trace"] for r in tagged} <= trace_ids
        assert {(r["trace"], r["span"]) for r in tagged} <= span_ids
        # and the tags reached the buckets as the scan's label
        st = p.to_state()
        assert "scan" in st["buckets"]

    def test_doctor_consistency_on_a_traced_scan(self, tmp_path):
        """The acceptance pin: on a real traced scan with the sampler
        armed, every stage's sampled seconds (samples x period) stay
        inside the span-derived stage wall, and the dominant stage has
        a non-trivial top frame."""
        paths = [write_file(tmp_path / f"d{i}.parquet", rows=3000,
                            seed=i * 100) for i in range(2)]
        trace.set_tracing(True)
        p = profiler_mod.set_profiling(True, hz=200, start=True)
        scan = ShardedScan(paths)
        for _k, cols in scan.run_iter():
            for c in cols.values():
                c.block_until_ready()
        profiler_mod.set_profiling(False)
        state = p.to_state()
        assert state["counters"]["profile_samples"] > 0
        spans = trace.snapshot_spans()
        roots = [s for s in spans if s["name"] == "scan"]
        assert roots
        tid = roots[0]["trace"]
        d = attribution.diagnose(
            [s for s in spans if s["trace"] == tid])
        assert profile_consistency(state, d["stages_s"]) == []
        rows = top_frames(state, label=d["label"],
                          stage=d["bound_stage"], n=5) \
            or top_frames(state, stage=d["bound_stage"], n=5)
        if rows:  # the dominant stage was sampled: name its frame
            assert rows[0]["self"] >= 1
            assert rows[0]["frame"]


# ----------------------------------------------------------------------
# off-CPU attribution
# ----------------------------------------------------------------------

class TestOffCpu:
    def test_contended_lock_attributes_to_lockcheck_site(self):
        """Arming installs the wait hooks into the round-19 lockcheck
        wrappers: a CONTENDED acquire brackets the blocking wait, so
        samples taken while a thread queues on the lock land on the
        lock's creation-site identity."""
        site = "tests/test_profiler.py:lockdemo"
        lk = lockcheck._CheckedLock(threading.Lock(), site)
        p = profiler_mod.set_profiling(True, hz=50, start=False)
        held = threading.Event()
        release = threading.Event()

        def holder():
            lk.acquire()
            held.set()
            release.wait(5)
            lk.release()

        def contender():
            lk.acquire()
            lk.release()

        t1 = threading.Thread(target=holder)
        t1.start()
        assert held.wait(2)
        t2 = threading.Thread(target=contender)
        t2.start()
        try:
            leaf = f"[lock-wait {site}]"
            found = False
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline and not found:
                p.sample_once()
                found = any(s.endswith(leaf)
                            for _l, _g, s, _c in _stacks(p.to_state()))
                if not found:
                    time.sleep(0.005)
        finally:
            release.set()
            t2.join(5)
            t1.join(5)
        assert found, "no off-CPU sample landed on the lock site"
        assert p.to_state()["counters"]["profile_samples_offcpu"] >= 1

    def test_uncontended_acquire_never_marks_offcpu(self):
        lk = lockcheck._CheckedLock(threading.Lock(),
                                    "tests/test_profiler.py:free")
        p = profiler_mod.set_profiling(True, hz=50, start=False)
        lk.acquire()
        lk.release()
        assert p._waits == {}

    def test_seeded_io_hang_attributes_to_chunk_read(self, tmp_path):
        """The acceptance pin: under a seeded ``io.chunk.hang`` the
        blocked thread samples as ``[io-wait io.reader.chunk_read]``
        in the ``read`` stage."""
        path = write_file(tmp_path / "h.parquet", rows=400)
        p = profiler_mod.set_profiling(True, hz=100, start=False)
        leaf = "[io-wait io.reader.chunk_read]"

        def scan():
            for _k, cols in ShardedScan([path]).run_iter():
                for c in cols.values():
                    c.block_until_ready()

        with collect_stats(), inject_faults() as inj:
            inj.inject("io.chunk.hang", "hang", times=1, seconds=0.8)
            t = threading.Thread(target=scan)
            t.start()
            try:
                found_stage = None
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline \
                        and found_stage is None:
                    p.sample_once()
                    for _l, stg, s, _c in _stacks(p.to_state()):
                        if s.endswith(leaf):
                            found_stage = stg
                            break
                    time.sleep(0.005)
            finally:
                t.join(10)
        assert found_stage is not None, \
            "no sample landed in the hung chunk read"
        assert found_stage == "read"


# ----------------------------------------------------------------------
# parity + conservation (profiler on vs off)
# ----------------------------------------------------------------------

class TestParity:
    def test_scan_bytes_and_counters_identical(self, corpus):
        def leg():
            live.reset_registry()
            out = []
            for k, cols in ShardedScan(corpus).run_iter():
                out.append((k, {c: v.to_numpy()
                                for c, v in cols.items()}))
            counters = live.registry().snapshot()["counters"]
            return out, counters

        off_out, off_c = leg()
        profiler_mod.set_profiling(True, hz=200, start=True)
        on_out, on_c = leg()
        profiler_mod.set_profiling(False)
        assert [k for k, _ in on_out] == [k for k, _ in off_out]
        for (_, a), (_, b) in zip(off_out, on_out):
            assert set(a) == set(b)
            for name in a:
                av, ar, ad = a[name]
                bv, br, bd = b[name]
                np.testing.assert_array_equal(ar, br)
                np.testing.assert_array_equal(ad, bd)
                if hasattr(av, "offsets"):
                    assert av == bv
                else:
                    np.testing.assert_array_equal(av, bv)

        def ints(d):
            # integer counters are exact event counts; seconds-valued
            # counters legitimately differ run to run
            return {k: v for k, v in d.items()
                    if isinstance(v, int)
                    and not k.startswith("profile_")}

        assert ints(on_c) == ints(off_c)


# ----------------------------------------------------------------------
# teardown ordering (the shared live._flush_lock)
# ----------------------------------------------------------------------

class TestTeardownOrdering:
    def _sampled_profiler(self):
        p = profiler_mod.set_profiling(True, hz=50, start=False)
        stop = threading.Event()
        t = threading.Thread(target=_busy, args=(stop,))
        t.start()
        try:
            p.sample_once()
        finally:
            stop.set()
            t.join(2)
        return p

    def test_final_flush_serializes_with_snapshot_flush(
            self, tmp_path, monkeypatch):
        """The regression pin for the round-17 interleaving hazard:
        while the snapshot writer's final flush holds
        ``live._flush_lock``, the profiler's exit flush must WAIT —
        the export lands only after the lock releases."""
        export = tmp_path / "p.prof"
        monkeypatch.setenv("TPQ_PROFILE_EXPORT", str(export))
        self._sampled_profiler()
        acquired = threading.Event()
        release = threading.Event()

        def hold():
            with live._flush_lock:
                acquired.set()
                release.wait(5)

        h = threading.Thread(target=hold)
        h.start()
        assert acquired.wait(2)
        done = threading.Event()
        f = threading.Thread(
            target=lambda: (profiler_mod.final_flush(), done.set()))
        f.start()
        time.sleep(0.1)
        try:
            assert not done.is_set()
            assert not export.exists()
        finally:
            release.set()
            f.join(5)
            h.join(5)
        assert done.is_set()
        doc = load_profile_file(str(export))
        assert doc["counters"]["profile_samples"] >= 1

    def test_both_exit_flushes_coexist(self, tmp_path, monkeypatch):
        """Both atexit flushes armed (metrics snapshot + profile):
        running them back to back — either order — produces both
        files intact."""
        pexp = tmp_path / "p.prof"
        mexp = tmp_path / "m.json"
        monkeypatch.setenv("TPQ_PROFILE_EXPORT", str(pexp))
        monkeypatch.setenv("TPQ_METRICS_EXPORT", str(mexp))
        self._sampled_profiler()
        live._final_flush()
        profiler_mod.final_flush()
        assert load_profile_file(str(pexp))["format"] == "tpq-profile"
        with open(mexp) as f:
            json.load(f)
        profiler_mod.final_flush()
        live._final_flush()
        assert load_profile_file(str(pexp))["format"] == "tpq-profile"


# ----------------------------------------------------------------------
# the off path is structurally zero-cost
# ----------------------------------------------------------------------

class TestZeroCost:
    def test_profile_off_structurally_zero_cost(self, corpus,
                                                monkeypatch):
        """With ``TPQ_PROFILE`` off (the default), no scan/trace/
        write path may reach the profiler at all — every hot site's
        ``_profiler._active is not None`` guard short-circuits first.
        Proven by making every entry point explode (tracing is armed
        too, so the tracer's mirror-hook guards are exercised): a
        single unguarded touch fails the scan."""
        profiler_mod.set_profiling(False)
        assert profiler_mod.profiler() is None

        def boom(*a, **k):
            raise AssertionError(
                "profiler touched with TPQ_PROFILE off")

        for meth in ("start", "sample_once", "brief", "to_state",
                     "merge_state"):
            monkeypatch.setattr(Profiler, meth, boom)
        for fn in ("ctx_push", "ctx_pop", "span_note", "stage_begin",
                   "wait_begin"):
            monkeypatch.setattr(profiler_mod, fn, boom)
        trace.set_tracing(True)
        scan = ShardedScan(corpus)
        results = [o for _k, o in scan.run_iter()]
        assert len(results) == len(scan.units)


# ----------------------------------------------------------------------
# CLI consumers
# ----------------------------------------------------------------------

class TestCli:
    def _export(self, tmp_path, name="p.prof", **kw):
        state = _host_state(**kw) if kw else _host_state(
            6, 2, 1, {"f;g": 4, "f;h": 2})
        path = str(tmp_path / name)
        assert write_profile_file(state, path)
        return path

    def test_flame_renders_top_frames(self, tmp_path, capsys):
        from tpuparquet.cli.parquet_tool import main as pt_main

        path = self._export(tmp_path)
        assert pt_main(["flame", path]) == 0
        out = capsys.readouterr().out
        assert "6 samples" in out
        assert "g" in out and "h" in out

    def test_flame_diff_ranks_deltas(self, tmp_path, capsys):
        from tpuparquet.cli.parquet_tool import main as pt_main

        a = self._export(tmp_path, "a.prof",
                         samples=10, offcpu=0, drops=0,
                         stacks={"f;g": 5, "f;h": 5})
        b = self._export(tmp_path, "b.prof",
                         samples=10, offcpu=0, drops=0,
                         stacks={"f;g": 9, "f;h": 1})
        assert pt_main(["flame", "--diff", a, b]) == 0
        out = capsys.readouterr().out
        assert "+40.00%" in out or "+40.0" in out

    def test_flame_stage_filter_and_no_match(self, tmp_path, capsys):
        from tpuparquet.cli.parquet_tool import main as pt_main

        path = self._export(tmp_path)
        assert pt_main(["flame", "--stage", "read", path]) == 0
        capsys.readouterr()
        assert pt_main(["flame", "--stage", "nope", path]) == 1
