"""chip_smoke.py off the chip: its phases at small size on the CPU's
virtual devices, and its refusal to report anything without a TPU.

The phases are imported and called here, in the test's own process
(pinned to the CPU by conftest), so the script needs no CPU option.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402


def _ok_lines(stdout: str) -> list:
    out = []
    for ln in stdout.splitlines():
        try:
            rec = json.loads(ln)
        except ValueError:
            continue
        if isinstance(rec, dict) and rec.get("ok") is True:
            out.append(rec)
    return out


def test_config2_phase_small():
    info = chip_smoke.phase_config2(60_000)
    assert info["n_values"] >= 60_000
    assert info["transports"]["pages"] > 0
    assert "host" not in info["transports"]["transports"]


def test_four_chip_phase_on_virtual_devices():
    info = chip_smoke.phase_four_chips(160_000)
    assert info["transports"]["pages"] > 0


def test_device_paths_phase():
    chip_smoke.phase_device_paths()


def test_degraded_page_fails_the_check():
    """A scan that quietly fell back to the CPU oracle must fail the
    smoke check, not pass it."""
    from tpuparquet.stats import DecodeStats

    st = DecodeStats()
    st.pages_degraded = 1
    with pytest.raises(chip_smoke.SmokeFailure, match="degraded"):
        chip_smoke._check_not_degraded(st, "x")


@pytest.mark.parametrize("alone", [False, True],
                         ids=["cpu-platform", "script-alone"])
def test_refuses_without_tpu_or_repo(tmp_path, alone):
    """Under JAX_PLATFORMS=cpu, and copied into a directory that holds
    nothing else of the repo, the script exits nonzero and never prints
    an ok line."""
    script = os.path.join(_REPO, "chip_smoke.py")
    cwd = _REPO
    if alone:
        shutil.copy(script, tmp_path / "chip_smoke.py")
        script, cwd = str(tmp_path / "chip_smoke.py"), str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0, out.stdout[-2000:]
    assert not _ok_lines(out.stdout)
    assert '"ok": true' not in out.stdout
