"""Multi-process MultiHostScan at scale: the distributed-backend twin of
``tools/scan_at_scale.py`` (round-3 verdict item 5 asked for at-scale
evidence beyond tiny-shape dryruns).

N real processes coordinate over ``jax.distributed`` (Gloo on the CPU
backend), each decoding its strided slice of the global
(file x row-group) unit list through the pipelined device path, then
all-gathering per-unit checksums.  The parent verifies the gathered
result against a single-process oracle and records throughput + peak
RSS as JSON.

    python tools/multihost_at_scale.py [values_per_rowgroup] [n_procs]

Writes MULTIHOST_SCALE_r05.json at the repo root.
"""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

N_FILES = 3
RG_PER_FILE = 2


def build_files(n_per_rg: int):
    import io

    from tpuparquet import CompressionCodec, FileWriter

    bufs = []
    for seed in (401, 402, 403):
        r = np.random.default_rng(seed)
        buf = io.BytesIO()
        w = FileWriter(
            buf,
            "message m { required int64 a; optional int32 b; }",
            codec=CompressionCodec.SNAPPY,
        )
        for _ in range(RG_PER_FILE):
            bm = r.random(n_per_rg) >= 0.3
            w.write_columns(
                {"a": r.integers(-(2**40), 2**40, size=n_per_rg),
                 "b": r.integers(0, 50, size=int(bm.sum()),
                                 dtype=np.int32)},
                masks={"b": bm},
            )
        w.close()
        buf.seek(0)
        bufs.append(buf)
    return bufs


def unit_checksum(cols) -> int:
    total = 0
    for path in sorted(cols):
        vals, rep, dl = cols[path].to_numpy()
        u = np.ascontiguousarray(vals).view(np.uint8).astype(np.uint64)
        total += int((u * (np.arange(u.size, dtype=np.uint64) % 997 + 1))
                     .sum() % (1 << 62))
        total += int(dl.astype(np.uint64).sum())
    return total & ((1 << 62) - 1)


def child(port: str, pid: int, out_path: str, n_per_rg: int,
          n_procs: int) -> None:
    from tools._pin import pin_cpu

    pin_cpu()
    import jax
    from tpuparquet.shard.distributed import (
        MultiHostScan,
        allgather_host,
        initialize,
    )

    initialize(coordinator_address=f"localhost:{port}",
               num_processes=n_procs, process_id=pid)
    assert jax.process_count() == n_procs
    files = build_files(n_per_rg)
    t0 = time.perf_counter()
    scan = MultiHostScan(files)
    results = scan.run()
    local = np.zeros(len(scan.global_units), dtype=np.int64)
    for j, out in enumerate(results):
        gidx = scan.global_units.index(scan.local_units[j])
        local[gidx] = unit_checksum(out)
    gathered = allgather_host(local).reshape(n_procs, -1).sum(axis=0)
    scan_s = time.perf_counter() - t0
    if pid == 0:
        import resource

        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
        with open(out_path, "w") as f:
            json.dump({"checksums": gathered.tolist(),
                       "scan_s": round(scan_s, 2),
                       "peak_rss_mb": round(rss, 1),
                       "local_units": len(results)}, f)
    print(f"proc {pid}: {len(results)} local units in {scan_s:.1f}s",
          flush=True)


def main() -> None:
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        child(sys.argv[2], int(sys.argv[3]), sys.argv[4],
              int(sys.argv[5]), int(sys.argv[6]))
        return
    n_per_rg = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000
    n_procs = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    out = os.path.join(_REPO, "_mh_scale_proc0.json")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child",
             str(port), str(pid), out, str(n_per_rg), str(n_procs)],
            cwd=_REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for pid in range(n_procs)
    ]
    logs = [p.communicate(timeout=1800)[0] for p in procs]
    for pid, (p, log) in enumerate(zip(procs, logs)):
        if p.returncode != 0:
            print(log)
            raise SystemExit(f"child {pid} failed rc={p.returncode}")
    with open(out) as f:
        rec = json.load(f)
    os.remove(out)

    # single-process oracle over the same deterministic files, in the
    # scan's own global unit order
    from tools._pin import pin_cpu

    pin_cpu()
    from tpuparquet import FileReader
    from tpuparquet.kernels.device import read_row_group_device
    from tpuparquet.shard.scan import scan_units

    readers = [FileReader(b) for b in build_files(n_per_rg)]
    units = scan_units(readers)
    want = [unit_checksum(read_row_group_device(readers[fi], rgi))
            for fi, rgi in units]
    assert want == rec["checksums"], "multi-host checksums != oracle"

    total = n_per_rg * 2 * N_FILES * RG_PER_FILE  # 2 columns
    record = {
        "processes": n_procs,
        "n_files": N_FILES,
        "rowgroups_per_file": RG_PER_FILE,
        "values_per_rowgroup": n_per_rg * 2,
        "total_values": total,
        "scan_s": rec["scan_s"],
        "values_per_sec": round(total / rec["scan_s"], 1),
        "peak_rss_mb_proc0": rec["peak_rss_mb"],
        "parity": "ok",
        "backend": f"cpu, {n_procs}-process jax.distributed (Gloo)",
    }
    # sub-scale smoke runs must not clobber the canonical record
    name = ("MULTIHOST_SCALE_r05.json" if n_per_rg >= 2_000_000
            else "MULTIHOST_SCALE_smoke.json")
    path = os.path.join(_REPO, name)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
