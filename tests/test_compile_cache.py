"""Persistent XLA compilation cache.

``hvd.init()`` always arms it: at ``JAX_COMPILATION_CACHE_DIR`` when that
is set (jax reads it itself and no code sets another directory), else at
``HOROVOD_COMPILE_CACHE_DIR``, else at the fixed
``<checkout>/.horovod_compile_cache`` whatever the cwd. Elastic
re-rendezvous and repeat launches then find their compiles on disk.
tests/conftest.py points the JAX variable at one directory outside the
checkout for the whole suite.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cc_events():
    """{event: value} of compile_cache_events_total."""
    from horovod_tpu.metrics import instruments as ins

    fam = ins.REGISTRY.snapshot().get("compile_cache_events_total")
    out = {"request": 0.0, "hit": 0.0}
    for s in (fam or {"series": []})["series"]:
        out[s["labels"]["event"]] = s["value"]
    return out


class TestPersistentCompileCache:
    def test_config_precedence(self, monkeypatch, tmp_path):
        from horovod_tpu.common.config import (DEFAULT_COMPILE_CACHE_DIR,
                                               Config)

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/jax-cc")
        monkeypatch.setenv("HOROVOD_COMPILE_CACHE_DIR", "/tmp/hvd-cc")
        assert Config.from_env().compile_cache_dir == "/tmp/jax-cc"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert Config.from_env().compile_cache_dir == "/tmp/hvd-cc"
        monkeypatch.delenv("HOROVOD_COMPILE_CACHE_DIR")
        # Unset: the fixed checkout path, from the package location.
        assert DEFAULT_COMPILE_CACHE_DIR == os.path.join(
            _REPO, ".horovod_compile_cache")
        monkeypatch.chdir(tmp_path)
        assert Config.from_env().compile_cache_dir == \
            DEFAULT_COMPILE_CACHE_DIR
        assert Config().compile_cache_dir == DEFAULT_COMPILE_CACHE_DIR

    def test_init_left_the_jax_variable_in_charge(self, hvd):
        """The suite runs with JAX_COMPILATION_CACHE_DIR set (conftest):
        after init the cache lives exactly there, armed, and the
        checkout's default directory was not created by it."""
        want = os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert hvd.config().compile_cache_dir == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_setup_sets_no_directory_when_jax_has_it(self, monkeypatch):
        from horovod_tpu.common import basics

        updates = []
        real = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda k, v: (updates.append(k), real(k, v))[1])
        basics._setup_compile_cache(jax.config.jax_compilation_cache_dir)
        assert "jax_compilation_cache_dir" not in updates

    def test_recompile_after_cache_clear_is_all_hits(self, hvd):
        """Compile a distinctively-shaped program, drop every in-process
        program cache (what an elastic reset does), and re-dispatch: every
        compile request must be served from the persistent cache — zero
        fresh XLA compiles."""
        from horovod_tpu.ops import collective_ops as co

        x = jnp.full((hvd.size(), 13), 3.25, jnp.float32)
        np.asarray(hvd.allreduce(x, op=hvd.Sum))   # compiles + writes
        co.clear_program_caches()                  # the restart analog
        before = _cc_events()
        np.testing.assert_allclose(
            np.asarray(hvd.allreduce(x, op=hvd.Sum)),
            np.full((hvd.size(), 13), 3.25 * hvd.size(), np.float32),
            rtol=1e-6)
        after = _cc_events()
        requests = after["request"] - before["request"]
        hits = after["hit"] - before["hit"]
        assert requests > 0, "no compile went through the cache layer"
        assert requests == hits, (
            f"{requests - hits:.0f} fresh XLA compile(s) on the "
            f"post-clear pass — the persistent cache missed")

    def test_unset_means_checkout_path_whatever_the_cwd(self, tmp_path):
        """No variable set, cwd elsewhere: init arms the cache at the
        package-relative default. (The subprocess stops before any
        compile, so nothing is written into the checkout.)"""
        code = (
            "import jax\n"
            "import horovod_tpu as hvd\n"
            "hvd.init()\n"
            "print('CCDIR', jax.config.jax_compilation_cache_dir)\n")
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_COMPILATION_CACHE_DIR",
                            "HOROVOD_COMPILE_CACHE_DIR")}
        env["PYTHONPATH"] = _REPO
        existed = os.path.isdir(os.path.join(_REPO, ".horovod_compile_cache"))
        r = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                           capture_output=True, text=True, timeout=240,
                           env=env)
        try:
            assert r.returncode == 0, r.stderr[-2000:]
            assert f"CCDIR {_REPO}/.horovod_compile_cache" in r.stdout
            assert not os.path.exists(
                os.path.join(str(tmp_path), ".horovod_compile_cache"))
        finally:
            if not existed:
                try:
                    os.rmdir(os.path.join(_REPO, ".horovod_compile_cache"))
                except OSError:
                    pass

    @pytest.mark.slow
    def test_init_cycle_across_processes_zero_fresh_compiles(self, tmp_path):
        """The acceptance cycle, with real process boundaries: a cold
        init() -> collective -> shutdown() run populates the cache; a
        SECOND interpreter doing the same performs zero fresh XLA
        compiles (every request is a hit). Two subprocesses so no
        in-process jit cache can mask a miss."""
        code = (
            "import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            "import numpy as np\n"
            "import jax.numpy as jnp\n"
            "import horovod_tpu as hvd\n"
            "hvd.init()\n"
            "x = jnp.ones((hvd.size(), 11), jnp.float32)\n"
            "np.asarray(hvd.allreduce(x, op=hvd.Sum))\n"
            "from horovod_tpu.metrics import instruments as ins\n"
            "fam = ins.REGISTRY.snapshot()['compile_cache_events_total']\n"
            "ev = {s['labels']['event']: s['value'] "
            "for s in fam['series']}\n"
            "hvd.shutdown()\n"
            "print('CCSTATS', int(ev.get('request', 0)), "
            "int(ev.get('hit', 0)))\n")
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))

        def run():
            r = subprocess.run([sys.executable, "-c", code],
                               capture_output=True, text=True, timeout=240,
                               env=env)
            assert r.returncode == 0, r.stderr[-2000:]
            line = [ln for ln in r.stdout.splitlines()
                    if ln.startswith("CCSTATS")][0]
            _, requests, hits = line.split()
            return int(requests), int(hits)

        req1, hit1 = run()       # cold: populates the cache
        assert req1 > 0
        req2, hit2 = run()       # warm restart: all hits
        assert req2 > 0
        assert req2 == hit2, (
            f"second pass performed {req2 - hit2} fresh XLA compile(s) "
            f"(requests={req2}, hits={hit2})")
        assert os.listdir(tmp_path), "no cache entry where the variable says"
