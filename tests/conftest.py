"""Test harness: an 8-device virtual CPU mesh.

Mirrors the reference's tier-2 strategy (SURVEY.md §4): the reference runs its
test files under ``horovodrun -np 2 -H localhost:2`` so N local processes
exercise the full negotiation/collective stack; here N virtual XLA CPU devices
exercise the full mesh/collective stack in one process.
"""

import os
import tempfile

# Force CPU even when the environment pins a TPU platform (tests model the
# multi-chip mesh with virtual CPU devices; chip_smoke.py and
# benchmark/run.py use the real chip). Subprocesses the tests spawn inherit
# it.
os.environ["JAX_PLATFORMS"] = "cpu"
# One fixed persistent-compile-cache directory OUTSIDE the checkout for the
# whole suite and every worker it spawns: hvd.init() always arms the cache,
# and the checkout's own .horovod_compile_cache must not fill up with
# CPU-tier entries (the chip tool copies the tree as it stands).
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    tempfile.gettempdir(), "horovod_tpu_test_compile_cache")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_enable_concurrency_optimized_scheduler" not in _flags:
    # The CPU thunk scheduler's concurrency optimization can enter
    # data-independent collectives in different orders on different
    # virtual devices and deadlock the in-process rendezvous (programs
    # with parallel collective chains, e.g. the 1F1B pipeline's forward
    # and backward hops). TPU compiles a total collective order; make the
    # CPU tier match. See docs/troubleshooting.md.
    _flags = (_flags
              + " --xla_cpu_enable_concurrency_optimized_scheduler=false")
os.environ["XLA_FLAGS"] = _flags

import jax  # noqa: E402

# In case a pytest plugin imported jax before the env above was set.
jax.config.update("jax_platforms", "cpu")

import signal  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Per-test timeout (pytest-timeout is not installed in this image, so the
# guard is implemented here): a wedged test must fail in minutes, not block
# the suite until a cluster-level timeout. SIGALRM fires in the main thread
# — where pytest runs tests — and interrupts subprocess waits, sleeps, and
# device gets alike. Override per test with @pytest.mark.timeout(seconds)
# or suite-wide with HVD_TEST_TIMEOUT (reference analog: per-step `timeout`
# wrappers in .buildkite/gen-pipeline.sh:126-149).
# ---------------------------------------------------------------------------
_DEFAULT_TEST_TIMEOUT = float(os.environ.get("HVD_TEST_TIMEOUT", "300"))


def _reap_orphaned_workers():
    """Session-start hygiene: kill `horovod_tpu.runner.task` orphans left
    by PRIOR timed-out runs (pytest dies under `timeout -k`, its worker
    clusters re-parent to init and poll their dead KV forever — skewing
    every timing and perf baseline on this 2-core box; see
    the ROADMAP re-anchor note @ PR 10). Orphans-only (ppid 1), so a
    concurrently running suite's live workers are never touched.
    HVD_REAP_WORKERS=0 opts out."""
    if os.environ.get("HVD_REAP_WORKERS", "1") != "1":
        return
    try:
        import importlib.util
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "reap_workers.py")
        spec = importlib.util.spec_from_file_location("_reap_workers", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        import sys
        mod.reap(orphans_only=True, out=sys.stderr)
    except Exception as e:  # noqa: BLE001 — hygiene must never fail tests
        print(f"reap_workers skipped: {e}")


def pytest_configure(config):
    _reap_orphaned_workers()
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test timeout override "
        "(default %ss, suite-wide env HVD_TEST_TIMEOUT)"
        % int(_DEFAULT_TEST_TIMEOUT))
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run "
        "(multi-interpreter cold starts etc.)")


class _PhaseTimeout:
    """SIGALRM guard for one runtest phase; no-op when already expired."""

    def __init__(self, item, phase):
        m = item.get_closest_marker("timeout")
        self.seconds = float(m.args[0]) if m and m.args \
            else _DEFAULT_TEST_TIMEOUT
        self.item, self.phase = item, phase

    def _fire(self, signum, frame):
        pytest.fail(
            f"{self.item.nodeid} {self.phase} exceeded "
            f"{self.seconds:.0f}s (HVD_TEST_TIMEOUT / @pytest.mark.timeout)",
            pytrace=False)

    def __enter__(self):
        if self.seconds > 0:
            self._prev = signal.signal(signal.SIGALRM, self._fire)
            signal.setitimer(signal.ITIMER_REAL, self.seconds)
        return self

    def __exit__(self, *exc):
        if self.seconds > 0:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, self._prev)
        return False


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _PhaseTimeout(item, "setup"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _PhaseTimeout(item, "call"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    with _PhaseTimeout(item, "teardown"):
        return (yield)


@pytest.fixture(scope="session")
def hvd():
    import horovod_tpu as hvd
    hvd.init()
    return hvd


_clusters = {}


@pytest.fixture(scope="session")
def shared_cluster():
    """Factory for persistent multi-process clusters keyed by
    (hosts, extra_env): tests with the same topology share one spawn +
    jax.distributed bootstrap (the reference's one-horovodrun-per-file
    pattern, gen-pipeline.sh:126-149). Torn down at session end."""
    from cluster import LocalCluster   # tests/ is on sys.path (rootdir)

    def get(hosts, extra_env=None):
        key = (hosts, tuple(sorted((extra_env or {}).items())))
        c = _clusters.get(key)
        if c is not None and c.dead:
            # A timed-out cluster is wedged: respawn rather than letting
            # every later same-topology test burn its own full timeout.
            c.stop(timeout=5)
            c = None
        if c is None:
            c = _clusters[key] = LocalCluster(hosts, extra_env=extra_env)
        return c

    yield get
    for c in _clusters.values():
        try:
            c.stop()
        except Exception:
            pass
    _clusters.clear()


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


@pytest.fixture(params=["bwd_dqkv", "pair"])
def flash_backward(request, monkeypatch):
    """Each backward path of the flash kernels, whatever the call's shapes
    (``backward_path`` picks it from them): ``bwd_dqkv``, the one kernel a
    call takes while its dQ fits the VMEM budget, and ``pair`` (``bwd_dq``
    + ``bwd_dkv``), which a call past that budget takes."""
    import importlib
    fa = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
    kernels = (("bwd_dqkv",) if request.param == "bwd_dqkv"
               else ("bwd_dq", "bwd_dkv"))
    monkeypatch.setattr(fa, "backward_path", lambda *shape: kernels)
    return request.param
