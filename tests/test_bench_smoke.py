"""bench.py harness smoke test (tiny scale, CPU backend).

A harness bug found on the chip costs chip time (round 3 lost a run to
a checksum phase that was never driven end-to-end off-chip).  This
drives every config builder, the timing paths, the parity gate, and
the JSON contract at small scale on every test run, in the labelled
CPU smoke mode (TPQ_BENCH_CPU=1).
"""

import json
import subprocess
import sys
import os

def test_bench_ladder_smoke():
    env = dict(os.environ)
    env.update({
        "TPQ_BENCH_TARGET": "60000",
        "TPQ_BENCH_CPU": "1",
        "JAX_PLATFORMS": "cpu",
    })
    out = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.strip().splitlines() if ln]
    # five per-config lines + the headline record
    assert len(lines) == 6, out.stdout
    head = json.loads(lines[-1])
    assert head["unit"] == "values/sec"
    assert set(head["configs"]) == {
        "1-plain-int64-uncompressed",
        "2-taxi-dict-snappy",
        "3-delta-int64-nested-list",
        "4-wide-string-dict-float64-v2",
        "5-multifile-sharded-scan",
    }
    for cfg in head["configs"].values():
        assert cfg["n_values"] > 0
        assert cfg["cpu_vps"] > 0 and cfg["device_vps"] > 0
    # round-5 orchestration contract: a complete ladder is ok:true and
    # carries the write-side anchors for configs 2 and 4
    assert head["ok"] is True
    assert head["source"] == "cpu-smoke"
    for cfg_name in ("2-taxi-dict-snappy", "4-wide-string-dict-float64-v2"):
        assert head["configs"][cfg_name]["write_vs_pyarrow"] > 0
    # incremental persistence: the partial record exists, labeled with
    # the smoke backend (NOT "device" -- review finding), all 5 configs
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "BENCH_PARTIAL.json")) as f:
        partial = json.load(f)
    assert partial["backend"] == "cpu-smoke"
    assert set(partial["configs"]) == set(head["configs"])


def test_bench_fails_without_a_tpu():
    """A device run on a CPU-only box exits nonzero and prints no
    record: no replayed chip record, no CPU numbers in its place."""
    env = dict(os.environ)
    env.update({"TPQ_BENCH_TARGET": "60000", "JAX_PLATFORMS": "cpu"})
    env.pop("TPQ_BENCH_CPU", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "bench.py"], cwd=repo,
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode != 0, out.stdout[-2000:]
    assert "no TPU found" in out.stderr
    for ln in out.stdout.splitlines():
        rec = json.loads(ln)
        assert "value" not in rec and rec.get("ok") is not True
