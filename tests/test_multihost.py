"""Two-process MultiHostScan integration test (real jax.distributed).

SURVEY.md §5 "distributed communication backend": the multi-host scan
drives two actual processes coordinated over localhost (Gloo
collectives on the CPU backend), decoding a strided slice each and
exchanging per-unit checksums + row counts.  The parent verifies the
gathered global result against a single-process oracle — the same
division of labor a multi-host TPU pod uses, minus the DCN.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_scan(tmp_path):
    port = _free_port()
    out = tmp_path / "proc0.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # children use their own device counts
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    child = os.path.join(_REPO, "tests", "multihost_child.py")
    procs = [
        subprocess.Popen(
            [sys.executable, child, str(port), str(pid), str(out)],
            cwd=_REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for pid in (0, 1)
    ]
    logs = []
    try:
        for p in procs:
            stdout, _ = p.communicate(timeout=300)
            logs.append(stdout)
            if (p.returncode != 0
                    and "aren't implemented on the CPU backend" in stdout):
                # some jaxlib builds cannot run multiprocess collectives
                # on the CPU backend at all (no Gloo) — an environment
                # capability gap, not a scan regression
                import pytest

                pytest.skip("jax CPU backend lacks multiprocess "
                            "collectives in this image")
            assert p.returncode == 0, f"child failed:\n{stdout[-3000:]}"
    finally:
        # a failed/timed-out child leaves its peer blocked in a Gloo
        # collective waiting forever; never leak it
        for p in procs:
            if p.poll() is None:
                p.kill()
    got = json.loads(out.read_text())

    # single-process oracle over the same deterministic files
    sys.path.insert(0, os.path.join(_REPO, "tests"))
    import multihost_child as mh

    bufs = mh.build_files()
    from tpuparquet import FileReader
    from tpuparquet.kernels.device import read_row_group_device
    from tpuparquet.shard.scan import scan_units

    readers = [FileReader(b) for b in bufs]
    units = scan_units(readers)
    assert [tuple(u) for u in got["units"]] == units
    want_counts = [readers[fi].meta.row_groups[rgi].num_rows
                   for fi, rgi in units]
    assert got["counts"] == want_counts
    want = [mh.unit_checksum(read_row_group_device(readers[fi], rgi))
            for fi, rgi in units]
    assert got["checksums"] == want, "\n".join(logs)

    # fleet telemetry (allgather_stats): the children asserted the
    # fleet totals equal the sum of their per-host as_dict outputs;
    # the parent pins the absolute fleet numbers against the footers —
    # every unit decoded exactly once across the two processes
    fleet = got["fleet_stats"]
    assert fleet["row_groups"] == len(units)
    assert fleet["values"] == sum(
        cc.meta_data.num_values
        for r in readers for rg in r.meta.row_groups
        for cc in rg.columns)
    assert fleet["chunks"] == sum(
        len(rg.columns) for r in readers for rg in r.meta.row_groups)
