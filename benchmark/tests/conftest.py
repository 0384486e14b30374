"""Tests of the benchmark's own code, run by hand on the CPU:

    python3 -m pytest benchmark/tests -q

(tier-1 collects ``tests/`` only.) Four virtual CPU devices, set before jax
is imported, so that the four-chip cell's path can be driven too.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.environ.get("TMPDIR", "/tmp"),
                 "horovod_tpu_benchmark_test_cache"))

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
