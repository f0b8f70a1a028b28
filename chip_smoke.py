"""Chip smoke run: the device decode path on a TPU, end to end.

    python chip_smoke.py                # one chip (no arguments)
    python chip_smoke.py --four-chips   # only the four-chip sharded scan

One process; JAX is initialised once.  Without arguments it builds
``bench.py``'s config 2 (NYC-Taxi-shaped, Snappy + dictionary/hybrid) at
``bench.TARGET`` (50M) values and decodes it through the entry points a
user calls: ``read_row_groups_device``, then ``ShardedScan`` on a
one-device mesh and ``gather_column``.  Each is checked against the CPU
oracle with ``bench.parity()``'s rule (elementwise on the row-group-0
prefix, device checksums on every value of every row group; gathered
columns compare in full).  Then every device decode branch runs at
small size (``tools/check_device_paths.check_all``).  No phase may
degrade to the host: no degraded page or unit, no ``host`` /
``host-degraded`` page on config 2, and the native library loaded.

``--four-chips`` runs only the path that exists across chips: a
``ShardedScan`` over four config-2 files (50M values in all) on
``make_mesh(4)`` with ``gather_column`` on every column, two config-4
files through ``gather_byte_column``, the SPMD dictionary-decode step,
and a check that units landed on all four devices.

Lines before the last are a smoke run's informational lines, not
metrics.  The last line is ``{"ok": true, "device": {...}}``.  A failed
phase raises and exits nonzero, and no such line is printed; so does a
platform other than TPU, and a directory without the rest of the repo.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
FOUR = 4  # chips of the --four-chips host (a 2x2 v5e)


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def _say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# checks shared by the phases
# --------------------------------------------------------------------------

def _device_pages(st):
    return [e for e in st.events.pages if e.transport != "cpu"]


def _check_not_degraded(st, what: str, host_ok: bool = True) -> dict:
    """No page or unit fell back to the CPU oracle.  ``host_ok=False``
    also refuses the host-assembly path (the HOST_ASSEMBLY_EXCEPTIONS
    routing): config 2's columns must decode on device transports."""
    from tpuparquet.obs import event_summary

    _check(st.pages_degraded == 0 and st.units_degraded == 0,
           f"{what}: degraded to the CPU oracle "
           f"({st.pages_degraded} pages, {st.units_degraded} units)")
    pages = _device_pages(st)
    _check(pages, f"{what}: no page decoded on the device path")
    refused = {"host-degraded"} if host_ok else {"host-degraded", "host"}
    bad = [e for e in pages if e.transport in refused]
    _check(not bad, f"{what}: {len(bad)} pages on a host transport, "
           f"first {bad[0].column}[{bad[0].page}] -> {bad[0].transport}"
           if bad else "")
    return event_summary(st.events)


def _max_def(reader, path: str) -> int:
    return reader.schema.leaf(path).max_def_level


def _check_gathered_fixed(vals, counts, oracle, reader, path) -> None:
    """A replicated ``gather_column`` result against the CPU oracle,
    every value of every unit (nulls are zero-filled slots)."""
    _check(len(counts) == len(oracle), f"gather {path}: unit count")
    mdef = _max_def(reader, path)
    for u, cpu in enumerate(oracle):
        cd = cpu[path]
        want = np.asarray(cd.values)
        dl = np.asarray(cd.def_levels)
        _check(int(counts[u]) == len(dl),
               f"gather {path} unit {u}: {counts[u]} slots, want {len(dl)}")
        got = (np.ascontiguousarray(vals[u, : counts[u]])
               .astype(np.uint32).view(np.uint8).view(want.dtype)
               .reshape(-1))
        if len(want) == len(dl):
            dense = want
        else:
            dense = np.zeros(len(dl), dtype=want.dtype)
            dense[dl == mdef] = want
        _check(np.array_equal(got, dense),
               f"gather {path} unit {u}: values differ from the oracle")


def _check_gathered_bytes(out, oracle, reader, path) -> None:
    """A replicated ``gather_byte_column`` result against the oracle:
    per-unit offsets (nulls are zero-length) and every data byte."""
    offs, data, row_counts, byte_counts = out
    mdef = _max_def(reader, path)
    for u, cpu in enumerate(oracle):
        cd = cpu[path]
        dl = np.asarray(cd.def_levels)
        _check(int(row_counts[u]) == len(dl),
               f"gather {path} unit {u}: row count")
        lens = np.zeros(len(dl), dtype=np.int64)
        lens[dl == mdef] = np.diff(np.asarray(cd.values.offsets))
        want_offs = np.concatenate([[0], np.cumsum(lens)])
        _check(np.array_equal(
            np.asarray(offs[u, : len(dl) + 1], dtype=np.int64), want_offs),
            f"gather {path} unit {u}: offsets differ from the oracle")
        want_data = np.asarray(cd.values.data, dtype=np.uint8)
        _check(int(byte_counts[u]) == want_data.size
               and np.array_equal(data[u, : want_data.size], want_data),
               f"gather {path} unit {u}: bytes differ from the oracle")


def _check_units(scan, results, oracle) -> None:
    """bench.parity()'s rule over a scan's per-unit results:
    elementwise on a unit-0 prefix, checksums on every value."""
    import bench
    from tpuparquet.cpu.plain import ByteArrayColumn

    for u, cpu in enumerate(oracle):
        for path, cd in cpu.items():
            col = results[u][path]
            if u == 0:
                k = min(col.num_values, bench._ELEMWISE_VALUES)
                vals, rep, dl = col.to_numpy(limit=k)
                _check(np.array_equal(dl, cd.def_levels[:k])
                       and np.array_equal(rep, cd.rep_levels[:k]),
                       f"scan unit 0 {path}: levels differ")
                if isinstance(cd.values, ByteArrayColumn):
                    woffs = np.asarray(cd.values.offsets[: len(vals) + 1])
                    want = ByteArrayColumn(
                        woffs, cd.values.data[: int(woffs[-1])])
                    _check(vals == want, f"scan unit 0 {path}: values differ")
                else:
                    _check(np.array_equal(
                        np.asarray(vals),
                        np.asarray(cd.values)[: len(vals)]),
                        f"scan unit 0 {path}: values differ")
            want = bench._cpu_checksum(cd)
            got = bench._device_checksum(col)
            _check(want == got, f"scan unit {u} {path}: checksum "
                   f"cpu={want} device={got}")


def _scan_oracle(scan) -> list:
    return [scan.readers[fi].read_row_group_arrays(rg)
            for fi, rg in scan.units]


# --------------------------------------------------------------------------
# phases (tests/test_chip_smoke.py drives them at small size on the CPU)
# --------------------------------------------------------------------------

def phase_config2(n_values: int) -> dict:
    """Config 2 through read_row_groups_device, then ShardedScan on a
    one-device mesh and gather_column; returns informational numbers."""
    import jax

    import bench
    from tpuparquet import FileReader
    from tpuparquet.kernels.device import read_row_groups_device
    from tpuparquet.shard.mesh import make_mesh
    from tpuparquet.shard.scan import ShardedScan, gather_column
    from tpuparquet.stats import collect_stats

    t0 = time.perf_counter()
    buf = bench.build_config2(n_values=n_values)
    reader = FileReader(buf)
    n = bench.total_values(reader)
    _say(f"[set-up] config 2 built: {n} values, "
         f"{len(buf.getbuffer())} bytes, "
         f"{reader.row_group_count()} row groups, "
         f"{time.perf_counter() - t0:.3f} s")

    def decode_all() -> float:
        t = time.perf_counter()
        outs = [out for _, out in read_row_groups_device(reader)]
        jax.block_until_ready(
            [b for o in outs for c in o.values() for b in c._buffers()])
        return time.perf_counter() - t

    first = decode_all()
    second = decode_all()
    with collect_stats(events=True) as st:
        bench.parity(reader)
    mix = _check_not_degraded(st, "config 2 read_row_groups_device",
                              host_ok=False)
    _say(f"[check] config 2 parity passed: {n} values, "
         f"{reader.row_group_count()} row groups, pages_degraded="
         f"{st.pages_degraded}, units_degraded={st.units_degraded}")

    buf.seek(0)
    with ShardedScan([buf], mesh=make_mesh(1)) as scan:
        with collect_stats(events=True) as sst:
            results = scan.run()
        _check_not_degraded(sst, "config 2 ShardedScan", host_ok=False)
        oracle = _scan_oracle(scan)
        _check_units(scan, results, oracle)
        for path in oracle[0]:
            vals, counts = gather_column(scan.mesh, results, path)
            _check_gathered_fixed(vals, counts, oracle, scan.readers[0],
                                  path)
    _say(f"[check] config 2 ShardedScan + gather_column parity passed "
         f"on a 1-device mesh: {len(results)} units, "
         f"{len(oracle[0])} columns")
    return {"n_values": n, "first_pass_s": first, "second_pass_s": second,
            "transports": mix}


def phase_device_paths() -> None:
    """Every device decode branch at small size, bit-exact, per-page
    transports asserted, nothing degraded."""
    sys.path.insert(0, _REPO)
    from tools.check_device_paths import check_all

    failures = check_all(True, log=lambda m: _say(f"[branch] {m}"))
    _check(failures == 0, f"{failures} device decode branches failed")


def phase_four_chips(n_values: int, n_devices: int = FOUR) -> dict:
    """The multi-file scan sharded over a mesh, its gathers and the SPMD
    dictionary-decode step, each against the CPU oracle."""
    import jax

    import bench
    from tpuparquet.cpu.dictionary import encode_dict_indices
    from tpuparquet.shard.mesh import make_mesh, sharded_dict_decode
    from tpuparquet.shard.scan import (ShardedScan, gather_byte_column,
                                       gather_column)
    from tpuparquet.stats import collect_stats

    mesh = make_mesh(n_devices)
    want_devs = set(jax.devices()[:n_devices])
    n_files = 4
    t0 = time.perf_counter()
    bufs = [bench.build_config2(n_values=n_values // n_files, n_groups=4,
                                seed=100 + i) for i in range(n_files)]
    _say(f"[set-up] {n_files} config-2 files built, "
         f"{time.perf_counter() - t0:.3f} s")
    with ShardedScan(bufs, mesh=mesh) as scan:
        with collect_stats(events=True) as st:
            t = time.perf_counter()
            results = scan.run()
            jax.block_until_ready(
                [b for r in results for c in r.values()
                 for b in c._buffers()])
            scan_s = time.perf_counter() - t
        mix = _check_not_degraded(st, "4-chip config-2 ShardedScan",
                                  host_ok=False)
        placed = set()
        for u, r in enumerate(results):
            dev = scan.device_for(u)
            placed.add(dev)
            for c in r.values():
                for b in c._buffers():
                    _check(b.devices() == {dev},
                           f"unit {u}: buffer on {b.devices()}, "
                           f"device_for says {dev}")
        _check(placed == want_devs,
               f"units landed on {len(placed)} of {n_devices} devices")
        oracle = _scan_oracle(scan)
        _check(sum(bench.total_values(r) for r in scan.readers) > 0,
               "empty scan")
        _check_units(scan, results, oracle)
        for path in oracle[0]:
            vals, counts = gather_column(mesh, results, path)
            _check_gathered_fixed(vals, counts, oracle, scan.readers[0],
                                  path)
    _say(f"[check] 4-chip config-2 scan: {len(results)} units on "
         f"{len(placed)} devices, gather_column parity passed on every "
         f"column")

    bufs4 = [bench.build_config4(n_values=n_values // 4, n_groups=4,
                                 seed=40 + i) for i in range(2)]
    with ShardedScan(bufs4, mesh=mesh) as scan:
        with collect_stats(events=True) as st4:
            results = scan.run()
        _check_not_degraded(st4, "4-chip config-4 ShardedScan")
        _check({scan.device_for(u) for u in range(len(results))}
               == want_devs, "config-4 units missed a device")
        oracle = _scan_oracle(scan)
        _check_units(scan, results, oracle)
        for path in ("vendor", "note"):
            _check_gathered_bytes(
                gather_byte_column(mesh, results, path), oracle,
                scan.readers[0], path)
        for path in ("fare", "tip"):
            vals, counts = gather_column(mesh, results, path)
            _check_gathered_fixed(vals, counts, oracle, scan.readers[0],
                                  path)
    _say(f"[check] 4-chip config-4 scan: {len(results)} units, "
         "gather_byte_column parity passed on vendor and note")

    rng = np.random.default_rng(7)
    width, lanes = 8, 2
    dictionary = rng.integers(0, 2**32, size=(1 << width, lanes),
                              dtype=np.uint32)
    streams, counts, expected = [], [], []
    for count in [1 << 18] * (2 * n_devices) + [12_345]:
        idx = rng.integers(0, 1 << width, size=count, dtype=np.uint32)
        streams.append(encode_dict_indices(idx, 1 << width)[1:])
        counts.append(count)
        expected.append(dictionary[idx])
    out = sharded_dict_decode(mesh, streams, counts, width, dictionary)
    _check(len(out) >= len(expected), "sharded_dict_decode lost streams")
    for i, (got, exp) in enumerate(zip(out, expected)):
        _check(np.array_equal(got, exp),
               f"sharded_dict_decode stream {i} differs")
    _say(f"[check] SPMD dictionary decode: {len(expected)} streams, "
         f"{sum(counts)} values bit-exact")
    return {"scan_s": scan_s, "transports": mix}


# --------------------------------------------------------------------------
# process set-up and entry point
# --------------------------------------------------------------------------

def _init_jax(n_chips: int) -> dict:
    """Compile cache, then the device check; returns the device dict."""
    import jax

    import bench

    # JAX reads JAX_COMPILATION_CACHE_DIR itself; only without it does
    # the run pick the fixed repo path bench.py's children use.  Every
    # program is cached, not only those that compile for over a second
    # (JAX's default): the decode path is hundreds of small programs.
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = bench.device_info()
    _say(f"device platform={dev['platform']} kind={dev['kind']} "
         f"count={dev['count']}")
    _check(dev["platform"] == "tpu",
           f"needs a TPU; JAX found platform {dev['platform']!r}")
    _check(dev["count"] >= n_chips,
           f"needs {n_chips} chips; JAX found {dev['count']}")
    return dev


def _compile_clock():
    """Seconds JAX spent tracing, lowering and compiling, summed from
    its own monitoring events."""
    import jax

    total = [0.0]

    def listen(event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            total[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded multi-file scan on 4 chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, _REPO)
    try:
        import bench
        import tpuparquet.native as native
    except ImportError as e:
        _say(f"FAIL: the repository is not beside this script ({e})")
        return 2

    try:
        dev = _init_jax(FOUR if args.four_chips else 1)
        compile_s = _compile_clock()
        _check(native._lib() is not None,
               "native library did not load (no compiler, or the build "
               "from the committed sources failed)")
        _say("native library loaded")
        t0 = time.perf_counter()
        if args.four_chips:
            info = phase_four_chips(bench.TARGET)
            _say(f"[smoke, not a metric] 4-chip scan wall "
                 f"{info['scan_s']:.6f} s; transports "
                 f"{json.dumps(info['transports'])}")
        else:
            info = phase_config2(bench.TARGET)
            _say(f"[smoke, not a metric] config 2 device decode wall: "
                 f"first pass {info['first_pass_s']:.6f} s (compiles "
                 f"included), second pass {info['second_pass_s']:.6f} s; "
                 f"transports {json.dumps(info['transports'])}")
            phase_device_paths()
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        _say(f"[smoke, not a metric] compile s {compile_s():.6f}; "
             f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}; "
             f"wall {time.perf_counter() - t0:.3f} s")
        cache = jax.config.jax_compilation_cache_dir
        n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        _say(f"compile cache {cache}: {n_cached} entries")
    except SmokeFailure as e:
        _say(f"FAIL: {e}")
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: the PJRT/arrow C++ teardown can abort
    # after the last line is printed (bench.py does the same)
    os._exit(rc)
