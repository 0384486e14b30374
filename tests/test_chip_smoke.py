"""The two entry points that measure on the device refuse to run without
it: no CPU result may pass for a chip result (chip_smoke.py, bench.py)."""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "HVD_BENCH_ALLOW_CPU"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, script)], cwd=str(tmp_path),
        capture_output=True, text=True, timeout=240, env=env)


def test_chip_smoke_refuses_cpu(tmp_path):
    r = _run("chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert "platform is 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout          # no result line


def test_bench_refuses_cpu_without_allow(tmp_path):
    r = _run("bench.py", tmp_path)
    assert r.returncode != 0
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["value"] == 0.0
    assert "platform is 'cpu'" in rec["error"]
