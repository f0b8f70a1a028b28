"""The measured window and everything after it: counters, peak
memory, the comparison, the metrics and the result line."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

from benchmark.compare import Tally

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WORK = os.path.join(ROOT, ".bench_work")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load(path: str, name: str):
    """A module of the benchmark found by its file's name."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def annotate(name: str):
    """A host span on the profiler's clock (a no-op when no trace
    runs): the names the trace reduction gives the device's idle
    gaps."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def p95(samples_s: list) -> float | None:
    """95th percentile of every sample, in milliseconds."""
    if not samples_s:
        return None
    return float(np.percentile(np.asarray(samples_s) * 1e3, 95))


@dataclasses.dataclass
class Window:
    """What a traffic loop's window returns."""

    attempted: int          # batches or queries completed
    rows: int               # file rows they covered
    window_s: float         # from the window's start to the last ready
    end_to_end: dict        # metric name -> value, besides setup_s
    kept: list              # outputs kept for the comparison
    failed: int = 0         # batches or queries that failed a check
    least_bytes: int | None = None


@dataclasses.dataclass
class Context:
    args: object
    cell: dict
    files: list             # [(path, rows, {column: Column})]
    device: dict
    read: object = None     # the device read under test
    stats: object = None    # DecodeStats of the window
    window: Window | None = None
    trace: dict | None = None
    peaks: dict | None = None
    extra: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.read is None:
            from tpuparquet.kernels.device import read_row_groups_device

            self.read = read_row_groups_device

    def rng(self, *stream):
        """A generator drawn from the seed and a named stream."""
        return np.random.default_rng([self.args.seed, *stream])

    def measure(self, loop, counter, setup_s: float) -> dict:
        import jax

        from tpuparquet.stats import collect_stats

        args = self.args
        trace_dir = None
        if args.trace:
            trace_dir = os.path.join(WORK, "trace", args.workload)
            shutil.rmtree(trace_dir, ignore_errors=True)
            # device ops and the benchmark's own host spans; no Python
            # call tracing, which would slow the host it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        c0 = counter.snapshot()
        try:
            with collect_stats() as st, annotate("bench.window"):
                win = loop.window(self, args.seconds)
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
        c1 = counter.snapshot()
        self.stats, self.window = st, win
        say(f"window {win.window_s:.6f} s: {win.attempted} done, "
            f"{win.rows} rows; inside the window {c1[0] - c0[0]} "
            f"compiles (persistent-cache misses), {c1[1] - c0[1]} "
            f"compile requests, {c1[2] - c0[2]} traces")
        mem = jax.local_devices()[0].memory_stats() or {}
        peak = mem.get("peak_bytes_in_use")
        say(f"peak_bytes_in_use {peak}; transport mix: pages {st.pages}, "
            f"dict-or-hybrid/plain on device "
            f"{st.pages - st.pages_host_values - st.pages_degraded}, "
            f"device_snappy {st.pages_device_snappy}, "
            f"planes {st.pages_device_planes}, "
            f"delta_lanes {st.pages_device_delta_lanes}, "
            f"host_values {st.pages_host_values}, "
            f"degraded {st.pages_degraded}, pruned {st.pages_pruned}; "
            f"plan_s {st.plan_s:.6f} transfer_s {st.transfer_s:.6f} "
            f"bytes_staged {st.bytes_staged}")
        device = dict(self.device, memory_peak_bytes=peak)
        if trace_dir is not None:
            from benchmark.trace_reduce import reduce_dir

            t = time.perf_counter()
            self.trace = reduce_dir(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
            device["busy_s"] = self.trace["busy_s"]
            device["window_s"] = self.trace["window_s"]
            say(f"trace reduced in {time.perf_counter() - t:.3f} s: "
                f"busy {self.trace['busy_s']:.6f} s of "
                f"{self.trace['window_s']:.6f} s; idle by host span "
                f"{json.dumps(self.trace['idle_by_span'])}")

        tally = Tally()
        t = time.perf_counter()
        loop.check(self, win, tally)
        tally.add("batches", win.failed)
        say(f"compared {tally.batches_compared} outputs, "
            f"{tally.rows_compared} rows x columns, in "
            f"{time.perf_counter() - t:.3f} s; checked "
            f"{win.attempted} for rows; mismatches by kind "
            f"{json.dumps(tally.kinds)}")

        if args.trace:
            metrics = self._per_layer()
        else:
            metrics = {}
            for m in self.cell["end_to_end"]:
                v = setup_s if m["name"] == "setup_s" \
                    else win.end_to_end.get(m["name"])
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out = {"correct": tally.correct, "attempted": win.attempted,
               "failed": win.failed, "metrics": metrics,
               "device": device}
        if self.trace is not None:
            out["breakdown"] = {"device_ops": self.trace["device_ops"],
                                "idle_gaps": self.trace["idle_gaps"]}
        out["checks"] = tally.checks()
        say(f"setup_s {setup_s:.6f}; {counter.compiles} compiles, "
            f"{counter.requests} compile requests taking "
            f"{counter.request_s:.6f} s in the run")
        for k, c in out["checks"].items():
            say(f"check {k} {c['value']} limit {c['limit']}")
        return out

    def _per_layer(self) -> dict:
        """Each per-layer metric of the cell, from its own reader."""
        with open(os.path.join(BENCH, "peaks.json")) as f:
            peaks = json.load(f)["devices"]
        if self.device["kind"] not in peaks:
            raise KeyError(f"no peaks for device kind "
                           f"{self.device['kind']!r} in peaks.json")
        self.peaks = peaks[self.device["kind"]]
        out = {}
        for m in self.cell["per_layer"]:
            reader = load(os.path.join(BENCH, "metrics",
                                       m["name"] + ".py"),
                          "bench_metric_" + m["name"])
            v = reader.read(self)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out
