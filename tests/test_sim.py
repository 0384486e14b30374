"""hvdsim (ISSUE 19): the event-driven scale digital twin — scale
guards at thread-infeasible worlds, bit-identical determinism under
chaos, elastic membership on the virtual clock, the autopilot prior
export/import seam, and the twin-pretrained convergence A/B against
the cold-start guard."""

import json
import os
import subprocess
import sys
import time

import pytest

from horovod_tpu.autotune.parameter_manager import ParameterManager
from horovod_tpu.chaos.plan import ChaosPlan, FaultSpec, TriggerCursor
from horovod_tpu.common.control_plane import LocalKV, exchange_plan
from horovod_tpu.sim import (FLAT_WORLD_CAP, LatencyModel, SimTimeout,
                             Simulator, TwinJob, flat_reference,
                             twin_exchange)
from horovod_tpu.sim import autopilot as sim_autopilot

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Simulator core: virtual clock, parking, timeouts.
# ---------------------------------------------------------------------------


class TestSimulatorCore:
    def test_get_parks_until_put_lands_and_clock_is_virtual(self):
        sim = Simulator(latency=LatencyModel(kv_us=5.0, dcn_us=50.0))
        seen = {}

        def getter(rank):
            v = yield ("get", "k", True, 10.0)
            seen["value"] = v
            seen["t"] = sim.now

        def putter(rank):
            yield ("advance", 1.0)
            yield ("put", "k", "hello", True)

        sim.spawn(0, getter(0))
        sim.spawn(1, putter(1))
        sim.run()
        assert seen["value"] == "hello"
        # Woken strictly after the 1 s advance plus the priced cross put,
        # in virtual time — no wall clock involved.
        assert seen["t"] >= 1.0
        assert sim.stats["timeouts"] == 0

    def test_get_times_out_with_simtimeout(self):
        sim = Simulator()
        out = {}

        def getter(rank):
            try:
                yield ("get", "never", False, 0.5)
            except SimTimeout:
                out["timed_out_at"] = sim.now

        sim.spawn(0, getter(0))
        sim.run()
        assert out["timed_out_at"] >= 0.5
        assert sim.stats["timeouts"] == 1

    def test_latency_model_env_knobs(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SIM_KV_US", "11")
        monkeypatch.setenv("HOROVOD_SIM_DCN_US", "77")
        m = LatencyModel.from_env()
        assert m.kv_us == 11.0 and m.dcn_us == 77.0
        assert m.seconds(False) == pytest.approx(11e-6)
        assert m.seconds(True) >= 77e-6
        # Garbage values fall back to defaults rather than raising.
        monkeypatch.setenv("HOROVOD_SIM_KV_US", "not-a-number")
        assert LatencyModel.from_env().kv_us == LatencyModel().kv_us


class TestLocalKVObserver:
    def test_observer_sees_sets_and_gets(self):
        events = []
        kv = LocalKV(observer=lambda op, key: events.append((op, key)))
        kv.set("a", "1")
        assert kv.get("a", 1000) == "1"
        assert ("set", "a") in events
        assert ("get", "a") in events

    def test_observer_default_is_off(self):
        kv = LocalKV()
        kv.set("a", "1")
        assert kv.get("a", 1000) == "1"


# ---------------------------------------------------------------------------
# Scale guards: the acceptance numbers at n=16384 and n=65536.
# ---------------------------------------------------------------------------


class TestTwinScaleGuard:
    @pytest.mark.timeout(120)
    @pytest.mark.parametrize("world,slices", [(16384, 64), (65536, 256)])
    def test_per_role_gets_match_exchange_plan(self, world, slices):
        plan = exchange_plan(world, slices)
        r = twin_exchange(world, slices)
        # Member KV load is O(1) in world size; leader load is
        # slice_size-1 local + num_slices-1 cross, exactly as planned.
        assert r["member_gets_per_round"] == plan["member_gets"] == 1
        assert (r["leader_gets_per_round"] == plan["leader_gets"]
                == (world // slices - 1) + (slices - 1))
        assert r["gets_total"] == plan["round_gets_total"]
        # Payload identity: every virtual rank decodes the same flat
        # reference the all-thread exchange would have produced.
        assert r["identical"]
        assert r["result"] == flat_reference(world, 0)

    def test_flat_is_capped_not_silently_slow(self):
        with pytest.raises(ValueError):
            twin_exchange(FLAT_WORLD_CAP * 2, 0, strategy="flat")

    def test_flat_parity_at_small_world(self):
        r = twin_exchange(64, 0, strategy="flat")
        plan = exchange_plan(64, 1)
        assert r["gets_total"] == plan["round_gets_total"]
        assert r["identical"]
        assert r["result"] == flat_reference(64, 0)


# ---------------------------------------------------------------------------
# Determinism: same (seed, world, slices, plan) -> bit-identical runs.
# ---------------------------------------------------------------------------


def _chaos_plan(seed=7):
    return ChaosPlan([
        FaultSpec(site="http_kv.request", kind="delay", p=0.02,
                  delay_ms=25),
        FaultSpec(site="negotiation.exchange", kind="crash", rank=37,
                  at=[1], max_fires=1),
    ], seed=seed)


class TestTwinDeterminism:
    @pytest.mark.timeout(120)
    def test_twin_job_reports_are_bit_identical(self):
        runs = [TwinJob(256, 8, rounds=4,
                        plan=ChaosPlan.from_dict(_chaos_plan().to_dict()),
                        record_trail=True).run()
                for _ in range(2)]
        assert (json.dumps(runs[0], sort_keys=True)
                == json.dumps(runs[1], sort_keys=True))
        # The chaos actually fired: rank 37 died and was remediated.
        assert 37 in runs[0]["dead"]
        assert runs[0]["final_world"] < 256
        assert runs[0]["chaos_fires"]

    def test_exchange_trails_are_bit_identical(self):
        trails = [twin_exchange(128, 8, rounds=2, record_trail=True)["trail"]
                  for _ in range(2)]
        assert trails[0] == trails[1]
        assert trails[0]  # non-empty: (round, t_us, rank, op, key) rows

    def test_seed_changes_the_run(self):
        a = TwinJob(256, 8, rounds=3, plan=_chaos_plan(seed=1)).run()
        b = TwinJob(256, 8, rounds=3, plan=_chaos_plan(seed=2)).run()
        assert a["chaos_fires"] != b["chaos_fires"]


# ---------------------------------------------------------------------------
# Elastic membership at simulated scale.
# ---------------------------------------------------------------------------


class TestTwinElastic:
    @pytest.mark.timeout(120)
    def test_crash_times_out_rounds_until_policy_removes(self):
        plan = ChaosPlan([FaultSpec(site="negotiation.exchange",
                                    kind="crash", rank=100, at=[1],
                                    max_fires=1)], seed=3)
        job = TwinJob(255, 8, rounds=5, plan=plan, hysteresis=2)
        report = job.run()
        rounds = report["rounds"]
        # Round 0 healthy; rank 100 dies entering round 1; the policy's
        # hysteresis (2 failed rounds on the *virtual* clock) then
        # removes it and the remaining rounds re-layout green.
        assert rounds[0]["ok"]
        assert not rounds[1]["ok"] and not rounds[2]["ok"]
        assert [m["rank"] for m in report["membership"]] == [100]
        assert report["membership"][0]["cause"] == "dead"
        assert report["final_world"] == 254
        assert rounds[-1]["ok"]
        # 254 ranks / 8 slices is indivisible -> flat re-layout, same
        # collapse rule as topology.slice_layout.
        assert rounds[-1]["strategy"] == "flat"
        assert rounds[-1]["worst_gets"] == 253
        # Remediation timestamps advance on the virtual clock only.
        assert report["membership"][0]["t"] > 0
        assert report["virtual_s"] < 1e4

    def test_trigger_cursor_is_pure_and_seeded(self):
        plan = _chaos_plan()
        a = TriggerCursor(plan)
        b = TriggerCursor(plan)
        for rank in range(64):
            a.decide("http_kv.request", rank, step=0)
            b.decide("http_kv.request", rank, step=0)
        assert a.log == b.log


# ---------------------------------------------------------------------------
# Autopilot prior seam: export/import + twin pretraining.
# ---------------------------------------------------------------------------


def _pm(cats=None, max_samples=4):
    return ParameterManager(
        initial_threshold=64 * 1024, initial_cycle_ms=1.0,
        warmup_samples=0, steps_per_sample=1,
        bayes_opt_max_samples=max_samples, max_move_log2=1.0,
        categorical_knobs=cats or {"strategy": ["flat", "hierarchical",
                                                "torus", "torus_qcross"]})


class TestPriorSeam:
    def _converge(self, pm, scorer):
        epochs = 0
        while pm.tuning and epochs < 200:
            thr, _cyc, cats = pm.suggest()
            pm.observe(scorer(thr, cats))
            epochs += 1
        return epochs

    @staticmethod
    def _score(thr, cats):
        bonus = {"flat": 0.0, "hierarchical": 2e6, "torus": 3e6,
                 "torus_qcross": 8e6}[cats.get("strategy", "flat")]
        return 1e6 + bonus + thr / 1e3

    def test_export_import_round_trip_skips_the_sweep(self):
        src = _pm()
        self._converge(src, self._score)
        prior = src.export_observations()
        assert prior["version"] == 1
        assert prior["best"]["categoricals"]["strategy"] == "torus_qcross"

        dst = _pm()
        consumed = dst.import_observations(prior)
        assert consumed > 0
        # The categorical sweep is pre-resolved: first suggestion is
        # already the winning combo, no warm/discard passes left.
        assert dst.suggest()[2]["strategy"] == "torus_qcross"
        assert dst.tuning  # numeric BO still runs live

    def test_space_mismatch_is_rejected(self):
        src = _pm()
        self._converge(src, self._score)
        prior = src.export_observations()
        dst = _pm(cats={"strategy": ["flat", "hierarchical"]})
        with pytest.raises(ValueError):
            dst.import_observations(prior)

    def test_pretrain_freezes_and_finds_the_hierarchy(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_PEAK_DCN_GBS", "0.05")
        res = sim_autopilot.pretrain(8, 2, strategy="flat",
                                     bayes_opt_max_samples=4)
        assert res["frozen"]
        assert res["winner"]["categoricals"]["strategy"] == "torus_qcross"
        assert res["epochs"] <= 40
        assert res["prior"]["version"] == 1

    def test_controller_prior_load_is_fail_soft(self, tmp_path):
        from horovod_tpu.autopilot.controller import AutopilotController
        from horovod_tpu.common.config import Config
        cfg = Config()
        cfg.autopilot_prior = str(tmp_path / "missing.json")
        ctrl = AutopilotController(cfg)
        pm = _pm()
        ctrl._load_prior(pm)          # missing file: warn, start cold
        assert pm.tuning
        bad = tmp_path / "bad.json"
        bad.write_text("{\"version\": 99}")
        cfg.autopilot_prior = str(bad)
        ctrl._load_prior(pm)          # wrong version: warn, start cold
        assert pm.tuning


# ---------------------------------------------------------------------------
# CLI battery: lint-style exit codes inside the tier-1 budget.
# ---------------------------------------------------------------------------


class TestTwinCLI:
    @pytest.mark.timeout(120)
    def test_battery_exits_zero_inside_budget(self, capsys):
        """The battery runs in-process, the TestSelfLint pattern: the
        30 s budget times the battery itself, not a cold interpreter's
        JAX import — a subprocess measurement conflates the two and
        flakes under late-suite memory pressure."""
        from horovod_tpu.sim.__main__ import main
        t0 = time.monotonic()
        rc = main([])
        dt = time.monotonic() - t0
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "FAIL" not in out, out
        assert out.count("ok:") >= 4, out
        assert dt < 30.0, f"twin battery took {dt:.1f}s (budget 30s)"

    @pytest.mark.timeout(300)
    def test_pretrain_entrypoint_writes_prior(self, tmp_path):
        """`python -m horovod_tpu.sim --pretrain` exits 0 and writes a
        loadable prior artifact (the CI-shell surface). No wall budget
        here — the cold JAX import is not the battery's cost; the budget
        lives in the in-process leg above."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        prior = tmp_path / "prior.json"
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.sim",
             "--pretrain", str(prior), "--world", "8", "--slices", "2"],
            capture_output=True, text=True, timeout=280,
            cwd=_REPO, env=env)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        with open(prior) as f:
            assert json.load(f)["version"] == 1

    def test_usage_error_exits_two(self):
        from horovod_tpu.sim.__main__ import main
        assert main(["--bogus-flag"]) == 2


# ---------------------------------------------------------------------------
# Convergence A/B: twin-prior-seeded controller vs the cold start.
# ---------------------------------------------------------------------------


@pytest.fixture
def detuned(hvd, monkeypatch):
    """Same deliberately detuned 2-slice layout as test_autopilot's
    convergence guard, with the scarce modeled DCN so the DCN-priced
    score separates hierarchy levers (registry/caches clean both
    sides). The DCN peak is an order scarcer than that guard's 0.05:
    this test runs late in the suite where multi-second step-time
    stalls are routine, and the flat strategy's modeled DCN penalty
    (~6 s/epoch at 0.002 GB/s) must dominate measured-wall noise so
    the sweep's winner is decided by bytes, not box weather."""
    from horovod_tpu.metrics import instruments as ins
    from horovod_tpu.ops import fusion, wire
    rt = fusion.get_runtime()
    prev = (rt.threshold, rt._cycle_s, rt.strategy, rt.cross_wire,
            rt.wire_dtype, rt._parameter_manager, rt._overlap_mode,
            rt._overlap_pinned)
    monkeypatch.setenv("HOROVOD_MESH_SLICES", "2")
    monkeypatch.setenv("HOROVOD_PEAK_DCN_GBS", "0.002")

    def _detune():
        wire.clear_wire_registry()
        wire.clear_strategy_registry()
        wire.reset_error_feedback()
        ins.reset_tier_split()
        rt.threshold = 64 * 1024
        rt._cycle_s = 0.001
        rt.strategy = "flat"
        rt.cross_wire = ""
        rt.wire_dtype = None
        rt._parameter_manager = None

    _detune()
    yield rt, _detune
    (rt.threshold, rt._cycle_s, rt.strategy, rt.cross_wire,
     rt.wire_dtype, rt._parameter_manager, rt._overlap_mode,
     rt._overlap_pinned) = prev
    wire.clear_wire_registry()
    wire.clear_strategy_registry()
    wire.reset_error_feedback()
    ins.reset_tier_split()


class TestTwinPriorConvergence:
    """ISSUE 19 acceptance: a controller warm-started from the twin's
    pretrained prior must freeze in measurably fewer decision epochs
    than the cold start on the same forced 2-slice 8-dev layout — both
    landing on the quantized hierarchical config."""

    K = 28

    def _epoch(self, hvd, xs, step):
        for _ in range(2):
            hvd.grouped_allreduce_async(
                xs, op=hvd.Average, name="twin_prior_guard").synchronize()
            step[0] += 1
            hvd.step_marker(step[0])

    def _drive(self, hvd, ctrl, xs, step):
        for e in range(self.K):
            self._epoch(hvd, xs, step)
            ctrl.tick()
            if ctrl.frozen and ctrl._cross_trial is None:
                return e + 1
        return self.K

    @pytest.mark.timeout(600)
    def test_prior_seeded_freezes_faster_than_cold(self, hvd, detuned,
                                                   monkeypatch, tmp_path):
        import numpy as np
        import jax.numpy as jnp
        from horovod_tpu.autopilot.controller import AutopilotController
        from horovod_tpu.common import basics

        rt, redetune = detuned
        cfg = basics.config()
        monkeypatch.setattr(cfg, "autotune_warmup_samples", 0)
        monkeypatch.setattr(cfg, "autotune_bayes_opt_max_samples", 4)
        monkeypatch.setattr(cfg, "autopilot_prior", "", raising=False)

        n = hvd.size()
        rng = np.random.default_rng(0)
        xs = [jnp.asarray(rng.standard_normal((n, 64 * 1024)),
                          jnp.float32) for _ in range(6)]
        step = [0]

        # Arm A: cold start — full categorical sweep runs live.
        cold = AutopilotController(cfg)
        cold_epochs = self._drive(hvd, cold, xs, step)
        assert cold.frozen, cold.decisions()
        assert rt.strategy == "torus_qcross", cold.decisions()
        assert rt.cross_wire == "int8", cold.decisions()

        # Arm B: pretrain the twin on the same layout/space, export the
        # prior, re-detune, and warm-start a fresh controller from it.
        res = sim_autopilot.pretrain(n, 2, strategy="flat",
                                     bayes_opt_max_samples=4)
        assert res["frozen"], res["history"]
        assert res["winner"]["categoricals"]["strategy"] == "torus_qcross"
        prior_path = tmp_path / "prior.json"
        sim_autopilot.write_prior(str(prior_path), res)

        redetune()
        monkeypatch.setattr(cfg, "autopilot_prior", str(prior_path))
        warm = AutopilotController(cfg)
        prior_epochs = self._drive(hvd, warm, xs, step)
        assert warm.frozen, warm.decisions()
        assert rt.strategy == "torus_qcross", warm.decisions()
        assert rt.cross_wire == "int8", warm.decisions()

        # The prior skips the live categorical sweep entirely (4 combos
        # x 3 windows); the warm arm should need several epochs fewer.
        assert prior_epochs <= cold_epochs - 4, \
            (prior_epochs, cold_epochs, warm.decisions())
