#!/usr/bin/env python
"""Always-on telemetry overhead bench: recorder/metrics on vs off.

Decodes the 50M-value taxi shape (``bench.build_config2``) through a
``ShardedScan`` under three telemetry configurations:

* ``off``        — recorder disabled, live metrics disabled, no
                   collector: the bare hot path (what a no-obs build
                   would run).
* ``always_on``  — the DEFAULT shipping configuration: flight
                   recorder armed, live metrics folding at unit
                   boundaries, causal tracing compiled in but OFF
                   (``TPQ_TRACE`` unset), still no user collector.
                   Its delta vs ``off`` staying at the r07-recorded
                   noise level is the proof that the round-16 trace
                   hot-site guards cost nothing when disabled.
* ``trace_on``   — ``always_on`` plus the causal tracer ARMED
                   (``TPQ_TRACE=1``, sample 1.0): what a diagnosis
                   session pays.
* ``profile_on`` — ``always_on`` plus the round-20 sampling profiler
                   ARMED at its default rate (``TPQ_PROFILE=1``):
                   what a live flamegraph costs while it runs.
* ``collected``  — a full ``collect_stats(events=True)`` scope on top
                   (the post-hoc regime's known cost, for scale).

Reports min/median walls over ``--reps`` repetitions and the
``always_on`` overhead vs ``off`` in percent — the number
``BENCH_NOTES_r07.md`` records and the CI stage bounds
(``--assert-overhead PCT`` exits nonzero past the bound).

Usage::

    JAX_PLATFORMS=cpu python tools/bench_obs.py \
        [--values 50000000] [--reps 3] [--assert-overhead 25] [--out F]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _decode_once(buf):
    from tpuparquet.shard.scan import ShardedScan

    buf.seek(0)
    scan = ShardedScan([buf])
    n = 0
    for _k, cols in scan.run_iter():
        for c in cols.values():
            c.block_until_ready()
        n += 1
    return n


def _run_leg(buf, name: str, reps: int) -> dict:
    from tpuparquet.obs import live, profiler, recorder, trace

    from tpuparquet.stats import collect_stats

    walls = []
    for _ in range(reps):
        trace.set_tracing(False)
        profiler.set_profiling(False)
        if name == "off":
            recorder.set_ring(0)
            os.environ["TPQ_LIVE_METRICS"] = "0"
            ctx = None
        elif name == "always_on":
            recorder.set_ring(recorder.ring_default() or 256)
            os.environ["TPQ_LIVE_METRICS"] = "1"
            ctx = None
        elif name == "trace_on":
            # the round-16 causal tracer ARMED on top of the shipping
            # default: spans per unit/stage/chunk, whole-trace
            # sampling at 1.0 — the worst case the TPQ_TRACE knob buys
            recorder.set_ring(recorder.ring_default() or 256)
            os.environ["TPQ_LIVE_METRICS"] = "1"
            trace.set_tracing(True)
            ctx = None
        elif name == "profile_on":
            # the round-20 sampling profiler ARMED at the default
            # rate: sys._current_frames() walks on a jittered grid,
            # stage/wait tagging live at every hot site
            recorder.set_ring(recorder.ring_default() or 256)
            os.environ["TPQ_LIVE_METRICS"] = "1"
            profiler.set_profiling(True)
            ctx = None
        else:  # collected
            recorder.set_ring(recorder.ring_default() or 256)
            os.environ["TPQ_LIVE_METRICS"] = "1"
            ctx = collect_stats(events=True)
        live.reset_registry()
        t0 = time.perf_counter()
        if ctx is None:
            units = _decode_once(buf)
        else:
            with ctx:
                units = _decode_once(buf)
        walls.append(time.perf_counter() - t0)
    profiler.set_profiling(False)
    return {"leg": name, "units": units, "reps": reps,
            "wall_s_min": round(min(walls), 4),
            "wall_s_median": round(statistics.median(walls), 4),
            "wall_s_all": [round(w, 4) for w in walls]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--values", type=int, default=50_000_000,
                    help="total values in the taxi-shaped corpus")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--assert-overhead", type=float, default=None,
                    metavar="PCT",
                    help="exit 1 if always_on exceeds off by more "
                         "than PCT%% (on min walls)")
    ap.add_argument("--out", default="",
                    help="also write the JSON report here")
    ap.add_argument("--device", action="store_true",
                    help="measure on the default (device) backend "
                         "instead of pinning CPU")
    args = ap.parse_args(argv)

    if not args.device:
        # telemetry overhead is a HOST-side property: pin the CPU
        # backend via jax.config, so the guard measures the hot path it
        # was calibrated against even on a TPU-attached host
        import jax

        jax.config.update("jax_platforms", "cpu")

    import bench

    buf = bench.build_config2(n_values=args.values)
    # one warmup decode: jit compilation must not land in any leg
    _decode_once(buf)

    legs = [_run_leg(buf, name, args.reps)
            for name in ("off", "always_on", "trace_on", "profile_on",
                         "collected")]
    by = {leg["leg"]: leg for leg in legs}
    base = by["off"]["wall_s_min"]
    overhead = {
        name: round((by[name]["wall_s_min"] / base - 1.0) * 100, 2)
        for name in ("always_on", "trace_on", "profile_on",
                     "collected")
    }
    report = {
        "bench": "obs_overhead",
        "values": args.values,
        "legs": legs,
        "overhead_pct_vs_off_min": overhead,
    }
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    if args.assert_overhead is not None \
            and overhead["always_on"] > args.assert_overhead:
        print(f"bench_obs: always_on overhead "
              f"{overhead['always_on']}% exceeds the "
              f"{args.assert_overhead}% bound", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
