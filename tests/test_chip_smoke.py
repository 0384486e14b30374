"""The two entry points that measure on the device refuse to run without
it: no CPU result may pass for a chip result (chip_smoke.py,
benchmark/run.py)."""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, tmp_path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, script), *args],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=240,
        env=env)


def test_chip_smoke_refuses_cpu(tmp_path):
    r = _run("chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert "platform is 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout          # no result line


def test_benchmark_refuses_cpu(tmp_path):
    r = _run("benchmark/run.py", tmp_path,
             "--workload", "gpt2m_1chip", "--seed", "0", "--seconds", "1")
    assert r.returncode != 0
    assert "no TPU: platform is 'cpu'" in r.stderr
    assert "{" not in r.stdout             # no JSON result line
