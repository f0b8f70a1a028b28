"""One run of one benchmark cell on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

The cell's entry in BENCHMARK.json names its configuration (a spec and
a seeded generator under ``benchmark/configs/``) and its traffic (a
mix of parameters under ``benchmark/traffic/<name>.json``, driven by
the loop ``benchmark/traffic/<kind>.py`` it names).  Per-layer metrics
are readers under ``benchmark/metrics/<metric>.py``.  Adding a cell
adds files; no file here changes.

A run: generate the cell's files from the seed; decode them once so
every shape compiles (set-up); measure for ``--seconds``; compare what
the window delivered with the generator's arrays; print one JSON line.
With ``--trace 1`` the window runs under the JAX profiler and the line
carries the per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import BENCH, WORK, Context, load, say  # noqa: E402


class NoResult(Exception):
    """The run cannot report: no chip, a missing program, bad args."""


def load_cell(name: str) -> dict:
    """The cell's BENCHMARK.json entries, spec, traffic mix and
    metrics."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise NoResult(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        spec = json.load(f)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        mix = json.load(f)

    def mine(m):
        return name in m.get("workloads", [name])

    return {"workload": w, "config": cfg, "spec": spec, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def init_jax(chips: int, require_tpu: bool) -> dict:
    """Compile cache, then the device check; returns ``device``."""
    import jax

    # JAX_COMPILATION_CACHE_DIR, where set, is JAX's own; otherwise a
    # fixed path in the checkout.  Every program is cached: the decode
    # path is many small programs under JAX's 1 s default.
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(WORK, "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu and dev["platform"] != "tpu":
        raise NoResult(f"needs a TPU; JAX found {dev['platform']!r}")
    if dev["count"] < chips:
        raise NoResult(f"needs {chips} chips; JAX found {dev['count']}")
    return dev


class CompileCounter:
    """Compilations, from JAX's own events: a compile request is
    answered from the persistent cache or compiled (a miss)."""

    def __init__(self):
        import jax

        self.compiles = self.requests = self.traces = 0
        self.request_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_span)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.compiles += 1

    def _on_span(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.request_s += secs
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.requests, self.traces


def run(argv=None, *, require_tpu: bool = True, scale: float = 1.0,
        read=None) -> dict:
    """One run; returns the result line as a dict.

    ``scale`` (row counts) and ``read`` (the device read under test)
    are for the CPU tests under ``benchmark/tests`` alone."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    try:
        import tpuparquet.kernels.device  # noqa: F401
    except ImportError as e:
        raise NoResult(f"the program is not in this checkout ({e})")
    device = init_jax(cell["workload"]["chips"], require_tpu)
    say(f"device platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    counter = CompileCounter()
    import tpuparquet.native as native

    if native._lib() is None:
        say("WARNING: native library did not load")

    cfg_dir = os.path.dirname(os.path.join(ROOT, cell["config"]["file"]))
    gen = load(os.path.join(cfg_dir, "generate.py"),
               "bench_config_generate")
    loop = load(os.path.join(BENCH, "traffic", cell["mix"]["kind"] + ".py"),
                "bench_traffic_loop")
    data_dir = os.path.join(WORK, "data", args.workload)
    shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(data_dir)
    try:
        t = time.perf_counter()
        files = gen.generate(args.seed, data_dir, scale=scale)
        gen_s = time.perf_counter() - t
        say(f"generated {len(files)} files, "
            f"{sum(r for _, r, _ in files)} rows, "
            f"{sum(os.path.getsize(p) for p, _, _ in files)} bytes "
            f"in {gen_s:.3f} s")
        ctx = Context(args=args, cell=cell, files=files, device=device,
                      read=read)
        loop.prepare(ctx)
        t = time.perf_counter()
        c0 = counter.snapshot()
        loop.warm(ctx)
        c1 = counter.snapshot()
        say(f"warm-up {time.perf_counter() - t:.3f} s: {c1[0] - c0[0]} "
            f"compiles, {c1[1] - c0[1]} compile requests, "
            f"{c1[2] - c0[2]} traces")
        setup_s = time.perf_counter() - _T_START
        result = ctx.measure(loop, counter, setup_s)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except NoResult as e:
        say(f"FAIL: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: the PJRT/arrow teardown can abort
    # after the last line is printed; every thread the run started has
    # been joined by now
    os._exit(rc)
