"""The shipped example must keep running end-to-end (it doubles as the
README's live demo of the whole stack)."""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tpu_pipeline_example():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples",
                                      "tpu_pipeline.py")],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "device decode:" in out.stdout
    assert "device-encoded round trip:" in out.stdout
    assert "sharded scan:" in out.stdout
