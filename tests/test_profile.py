"""Step profiler: per-step attribution ledger, MFU/roofline, JSONL
round-trip, on-demand capture, and the online straggler/regression
watchdog (incl. the 8-process acceptance scenario: the watchdog names a
chaos-delayed rank WHILE THE JOB RUNS)."""

import json
import os
import subprocess
import sys
import time

import cloudpickle
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Job functions below are shipped to spawned cluster workers by VALUE —
# the workers cannot import the test module by name (the tests/cluster.py
# idiom).
cloudpickle.register_pickle_by_value(sys.modules[__name__])

H8 = ",".join(["localhost:1"] + [f"127.0.0.{i}:1" for i in range(1, 8)])


class TestStepLedgerUnit:
    def _mk(self):
        from horovod_tpu.profile.ledger import StepLedger
        return StepLedger(history=16)

    def test_marker_to_marker_windows_and_residual(self):
        led = self._mk()
        assert led.on_step(0) is None          # first marker opens
        led.add_dispatch("allreduce", 0.010, 0.002, 4096)
        led.add_fusion_flush(0.008, 0.005, defer_s=0.001,
                             wire_dtype="bfloat16", wire_bytes=2048)
        led.add_control_plane(0.001)
        time.sleep(0.03)
        rec = led.on_step(1)
        att = rec["attribution"]
        assert rec["step"] == 1
        assert att["collective"] == pytest.approx(0.010)
        assert att["host_dispatch"] == pytest.approx(0.002)
        assert att["fusion"] == pytest.approx(0.003)   # wall - collective
        assert att["control_plane"] == pytest.approx(0.001)
        # residual = wall - attributed, never negative
        assert att["compute"] >= 0.0
        assert rec["wall_s"] >= 0.03
        assert rec["bytes_by_op"] == {"allreduce": 4096}
        assert rec["wire_bytes_by_dtype"] == {"bfloat16": 2048}
        assert rec["fusion_defer_s"] == pytest.approx(0.001)
        assert rec["collectives"] == 1 and rec["fused_flushes"] == 1

    def test_residual_clamped_when_attribution_exceeds_wall(self):
        led = self._mk()
        led.on_step(0)
        # Cycle-thread flushes overlap main-thread compute, so attributed
        # time can exceed wall: compute must clamp at zero, not go
        # negative.
        led.add_dispatch("allreduce", 10.0, 1.0, 0)
        rec = led.on_step(1)
        assert rec["attribution"]["compute"] == 0.0

    def test_auto_marks_suppressed_after_explicit(self):
        led = self._mk()
        led.on_step(None)                      # auto opens (step 1)
        assert led.on_step(None)["step"] == 2  # auto closes
        led.on_step(7)                         # explicit takes over
        assert led.on_step(None) is None       # auto now suppressed
        assert led.on_step(8)["step"] == 8

    def test_reset_window_discards_open_window_and_bumps_epoch(self):
        led = self._mk()
        led.on_step(0)
        led.add_dispatch("allreduce", 5.0, 5.0, 0)   # poisoned open window
        led.reset_window()
        led.on_step(1)                               # reopens post-reset
        led.add_dispatch("allreduce", 0.001, 0.001, 8)
        rec = led.on_step(2)
        # The pre-reset accumulation leaked nowhere: the post-reset record
        # carries only its own window, at the bumped epoch.
        assert rec["epoch"] == 1
        assert rec["attribution"]["collective"] == pytest.approx(0.001)
        # Completed records survive a reset (reports outlive rendezvous).
        led.reset_window()
        assert [r["step"] for r in led.records()] == [2]

    def test_non_int_step_ignored(self):
        led = self._mk()
        led.on_step(0)
        assert led.on_step("not-a-step") is None
        assert led.on_step(1)["step"] == 1


class TestRoofline:
    def test_peaks_table_and_env_override(self, monkeypatch):
        from horovod_tpu.profile import roofline
        peaks = roofline.chip_peaks("v5e")
        assert peaks["bf16_tflops"] == 197.0 and peaks["chip"] == "v5e"
        monkeypatch.setenv("HOROVOD_PEAK_TFLOPS", "123.5")
        assert roofline.chip_peaks("v5e")["bf16_tflops"] == 123.5

    def test_mfu_and_wire_utilization_math(self):
        from horovod_tpu.profile import roofline
        peaks = {"bf16_tflops": 100.0, "ici_gbs": 10.0, "dcn_gbs": 1.0}
        frac, achieved = roofline.mfu(50e12, 1.0, peaks)
        assert frac == pytest.approx(0.5) and achieved == pytest.approx(50.0)
        frac, gbs = roofline.wire_utilization(5e9, 1.0, peaks)
        assert frac == pytest.approx(0.5) and gbs == pytest.approx(5.0)
        frac, _ = roofline.wire_utilization(5e8, 1.0, peaks,
                                            cross_host=True)
        assert frac == pytest.approx(0.5)
        assert roofline.mfu(None, 1.0, peaks) == (None, None)

    def test_flops_from_compiled(self, hvd):
        from horovod_tpu.profile import roofline
        compiled = jax.jit(
            lambda a, b: a @ b).lower(jnp.ones((64, 64)),
                                      jnp.ones((64, 64))).compile()
        flops = roofline.flops_from_compiled(compiled)
        # 64^3 * 2 FLOPs, give or take XLA's accounting.
        assert flops is None or flops > 1e4

    def test_detect_chip_cpu_tier(self, hvd):
        from horovod_tpu.profile import roofline
        assert roofline.detect_chip() == "cpu"
        assert roofline.chip_peaks()["chip"] == "cpu"
        assert roofline.chip_peaks().get("estimate") is True

    def test_detect_chip_known_and_unknown_tpu_kinds(self, monkeypatch):
        """The v5e's own device_kind maps to its row; a TPU kind missing
        from the table raises instead of borrowing another row's peaks."""
        from horovod_tpu.profile import roofline

        class Dev:
            platform = "tpu"

            def __init__(self, kind):
                self.device_kind = kind

        monkeypatch.setattr(jax, "devices", lambda: [Dev("TPU v5 lite")])
        assert roofline.detect_chip() == "v5e"
        monkeypatch.setattr(jax, "devices", lambda: [Dev("TPU v9 ultra")])
        with pytest.raises(ValueError, match="TPU v9 ultra"):
            roofline.detect_chip()
        with pytest.raises(ValueError, match="TPU v9 ultra"):
            roofline.chip_peaks()


class TestWatchdogUnit:
    def test_regression_detector_fires_on_outlier_step(self):
        from horovod_tpu.profile import watchdog
        watchdog.reset()
        base = len(watchdog.findings())
        rec = {"wall_s": 0.01, "attribution": {"host_dispatch": 0.0},
               "step": 0, "rank": 0}
        for i in range(12):
            watchdog.observe(dict(rec, step=i))
        spike = dict(rec, step=12, wall_s=1.0)
        watchdog.observe(spike)
        found = watchdog.findings()[base:]
        kinds = [f["kind"] for f in found]
        assert "regression" in kinds, found
        reg = [f for f in found if f["kind"] == "regression"][-1]
        assert reg["step"] == 12 and reg["z"] > 4

    def test_steady_steps_produce_no_findings(self):
        from horovod_tpu.profile import watchdog
        watchdog.reset()
        base = len(watchdog.findings())
        for i in range(20):
            watchdog.observe({"wall_s": 0.01 + 1e-4 * (i % 3),
                              "attribution": {"host_dispatch": 1e-5},
                              "step": i, "rank": 0})
        assert len(watchdog.findings()) == base

    def test_robust_z_denominator_floored(self):
        from horovod_tpu.profile.watchdog import _robust_z
        # Identical history (MAD 0) must not produce infinite z for a
        # microsecond wobble.
        z, _ = _robust_z(1.1e-5, [1e-5] * 10)
        assert z < 4


class TestStepReportIntegration:
    """Single-controller 8-virtual-device integration: real eager sync +
    fused async collectives between markers."""

    def _run_steps(self, hvd, n=3, start=0):
        for i in range(start, start + n):
            x = jnp.ones((hvd.size(), 16), jnp.float32) * (i + 1)
            np.asarray(hvd.allreduce(x, op=hvd.Sum))
            hs = [hvd.allreduce_async(x, op=hvd.Sum, name=f"pr{i}.{j}")
                  for j in range(8)]
            for h in hs:
                h.synchronize()
            hvd.step_marker(i + 1)

    def test_step_report_three_nonzero_categories(self, hvd):
        hvd.step_marker(0)
        self._run_steps(hvd, n=3)
        rec = hvd.step_report()
        assert rec is not None
        att = rec["attribution"]
        nonzero = [c for c in ("host_dispatch", "collective", "fusion")
                   if att.get(c, 0.0) > 0.0]
        assert len(nonzero) >= 3, att
        assert rec["collectives"] >= 1
        assert rec["bytes_by_op"].get("allreduce", 0) > 0
        summary = hvd.step_report_summary()
        assert summary["steps"] >= 3
        assert summary["attribution_mean_s"]["collective"] > 0

    def test_mfu_fields_with_explicit_flops(self, hvd):
        hvd.set_flops_per_step(1e9)
        try:
            hvd.step_marker(100)
            self._run_steps(hvd, n=1, start=100)
            rec = hvd.step_report()
            assert rec["flops_per_step"] == 1e9
            assert rec["flops_source"] == "explicit"
            assert 0 < rec["mfu"]
            assert rec["achieved_tflops"] > 0
            assert rec["chip"] == "cpu"
        finally:
            hvd.set_flops_per_step(None)

    def test_step_time_lands_in_metrics_histogram(self, hvd):
        from horovod_tpu.metrics import instruments as ins
        before = ins.REGISTRY.snapshot().get("step_time_seconds")
        n0 = before["series"][0]["count"] if before and before["series"] \
            else 0
        hvd.step_marker(200)
        self._run_steps(hvd, n=2, start=200)
        fam = ins.REGISTRY.snapshot()["step_time_seconds"]
        assert fam["series"][0]["count"] >= n0 + 2

    def test_jsonl_stream_round_trips_through_report_cli(self, hvd,
                                                         tmp_path):
        from horovod_tpu.profile import ledger
        path = str(tmp_path / "steps.jsonl")
        prev = ledger._report_path
        ledger.reset_window()       # a window left open by a prior test
        ledger._report_path = path  # must not close into OUR stream
        try:
            hvd.step_marker(300)
            self._run_steps(hvd, n=3, start=300)
        finally:
            ledger._report_path = prev
        lines = [json.loads(l) for l in open(path) if l.strip()]
        assert [r["step"] for r in lines] == [301, 302, 303]
        assert all("attribution" in r and "wall_s" in r for r in lines)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.profile.report", path],
            capture_output=True, text=True, timeout=240, env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "host_dispat" in r.stdout and "collective" in r.stdout
        assert "per-rank summary" in r.stdout
        rj = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.profile.report",
             "--json", path],
            capture_output=True, text=True, timeout=240, env=env)
        assert rj.returncode == 0, rj.stderr[-2000:]
        parsed = json.loads(rj.stdout)
        assert parsed["records"] == 3
        assert parsed["attribution_median_s"]["collective"] > 0

    def test_debug_steps_endpoint(self, hvd):
        from urllib.request import urlopen

        from horovod_tpu.metrics import server as msrv
        port = msrv.start_http_server(port=0, addr="127.0.0.1")
        try:
            hvd.step_marker(400)
            self._run_steps(hvd, n=1, start=400)
            body = urlopen(
                f"http://127.0.0.1:{port}/debug/steps?last=4",
                timeout=10).read().decode()
            payload = json.loads(body)
            assert payload["summary"]["steps"] >= 1
            assert payload["records"][-1]["attribution"]["collective"] >= 0
        finally:
            msrv.stop_http_server()

    def test_debug_profile_capture_endpoint(self, hvd, tmp_path,
                                            monkeypatch):
        from urllib.request import urlopen

        from horovod_tpu.metrics import server as msrv
        monkeypatch.setenv("HOROVOD_PROFILE_DIR", str(tmp_path))
        port = msrv.start_http_server(port=0, addr="127.0.0.1")
        try:
            body = urlopen(
                f"http://127.0.0.1:{port}/debug/profile?ms=50",
                timeout=60).read().decode()
            payload = json.loads(body)
            assert payload["ms"] == 50
            d = payload["path"]
            assert os.path.isdir(d)
            # clock_sync anchors the capture to the flight/timeline wall
            # clock (start + stop lines).
            sync = [json.loads(l) for l in
                    open(os.path.join(d, "clock_sync.json"))]
            assert [s["event"] for s in sync] == ["start", "stop"]
        finally:
            msrv.stop_http_server()

    def test_step_window_capture(self, hvd, tmp_path):
        from horovod_tpu.profile import capture, ledger
        assert capture.configure_window("2:4", str(tmp_path))
        prev = ledger._capture_armed
        ledger._capture_armed = True
        try:
            hvd.step_marker(1)
            for i in range(2, 6):
                x = jnp.ones((hvd.size(), 4), jnp.float32)
                np.asarray(hvd.allreduce(x, op=hvd.Sum))
                hvd.step_marker(i)
            assert capture.active() is None      # stopped at step 4
            dirs = [d for d in os.listdir(tmp_path)
                    if d.startswith("steps2_4")]
            assert dirs, os.listdir(tmp_path)
        finally:
            ledger._capture_armed = prev
            capture._window = None

    def test_invalid_profile_steps_window_rejected(self):
        from horovod_tpu.profile import capture
        assert not capture.configure_window("")
        assert not capture.configure_window("5")
        assert not capture.configure_window("5:5")
        assert not capture.configure_window("b:a")


def _watchdog_job(n_steps, delay_rank, delay_ms):
    """Runs on every worker of the 8-process cluster: a chaos `delay` on
    one rank's collective.dispatch site, a training loop with step
    markers, low-cadence watchdog publish — returns (rank, records,
    findings, straggler_metric)."""
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import chaos
    from horovod_tpu.chaos import ChaosPlan, FaultSpec
    from horovod_tpu.profile import ledger, watchdog

    watchdog.reset()
    watchdog._publish_every = 4
    watchdog._read_timeout_ms = 15000
    chaos.install(ChaosPlan([FaultSpec(
        site="collective.dispatch", kind="delay", every=1,
        rank=delay_rank, delay_ms=delay_ms)]))
    try:
        hvd.step_marker(0)
        for i in range(1, n_steps + 1):
            x = jnp.ones((1, 8), jnp.float32) * i
            np.asarray(hvd.allreduce(x, op=hvd.Sum))
            hvd.step_marker(i)
    finally:
        chaos.uninstall()
        watchdog._publish_every = 16
        watchdog._read_timeout_ms = 250
    snap = hvd.metrics_snapshot().get("step_profiler_events_total", {})
    stragglers = sum(
        s["value"] for s in snap.get("series", ())
        if s["labels"].get("kind") == "straggler")
    return (hvd.cross_rank(), ledger.step_report(last=None),
            watchdog.findings(), stragglers)


class TestWatchdogNamesDelayedRank:
    """The acceptance scenario: on the 8-process CPU tier, a chaos
    ``delay`` on ONE rank's dispatch site must be named as a straggler BY
    THE RUNNING JOB (watchdog findings + metrics counter), with per-step
    attribution non-zero on every rank."""

    @pytest.mark.timeout(600)
    def test_eight_process_straggler_named_online(self, shared_cluster):
        delay_rank, n_steps = 3, 9
        results = shared_cluster(H8).run(
            _watchdog_job, args=(n_steps, delay_rank, 60.0), timeout=420)
        assert len(results) == 8
        named, named_metric = set(), 0.0
        for rank, records, findings, straggler_metric in results:
            # Per-step attribution exists on every rank with non-zero
            # host-dispatch and collective categories.
            assert len(records) >= n_steps - 1, (rank, len(records))
            att = records[-1]["attribution"]
            assert att["collective"] > 0, (rank, att)
            assert att["host_dispatch"] > 0, (rank, att)
            for f in findings:
                if f["kind"] == "straggler":
                    named.add(f["rank"])
            named_metric += straggler_metric
        assert delay_rank in named, \
            f"watchdog never named rank {delay_rank}: {named}"
        assert named_metric >= 1
        # The delayed rank's own host-dispatch median dwarfs its peers'
        # (the chaos sleep lands in ITS dispatch path; the peers book the
        # wait under `collective`) — the signal the naming rests on.
        med = {}
        for rank, records, _, _ in results:
            hosts = sorted(r["attribution"]["host_dispatch"]
                           for r in records)
            med[rank] = hosts[len(hosts) // 2]
        others = [v for r, v in med.items() if r != delay_rank]
        assert med[delay_rank] > 5 * max(others), med


def _elastic_profile_train(script_path, total_steps):
    import os

    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu import elastic
    from horovod_tpu.profile import ledger

    hvd.init()
    state = elastic.TpuState(trees={"w": jnp.zeros((4,))}, step=0)
    elastic.attach_listener(state)

    @elastic.run
    def loop(state):
        while state.step < total_steps:
            if state.step == 3 and hvd.process_count() == 2 \
                    and hvd.cross_rank() == 1:
                with open(script_path, "w") as f:
                    f.write("#!/bin/sh\necho localhost:1\n")
                os._exit(1)
            g = hvd.allreduce(jnp.ones((1, 4)), op=hvd.Sum)
            state.w = state.w + g[0]
            state.step += 1
            state.commit()          # commit marks the step for the ledger
        return ledger.step_report(last=None)

    return loop(state)


class TestLedgerUnderElasticReset:
    """Step reports must survive a rendezvous without double-counting or
    leaking recovery traffic into post-restore steps (acceptance
    criterion; extends the test_elastic_failure scenario)."""

    @pytest.mark.timeout(600)
    def test_no_double_count_across_rendezvous(self, hvd, tmp_path):
        from horovod_tpu.runner import run_elastic

        script = tmp_path / "discover.sh"
        script.write_text("#!/bin/sh\necho localhost:1\necho 127.0.0.1:1\n")
        script.chmod(0o755)
        total_steps = 6

        results = run_elastic(_elastic_profile_train,
                              args=(str(script), total_steps),
                              min_np=1, host_discovery_script=str(script))
        assert len(results) == 1           # only the survivor reports
        records = results[0]
        by_epoch = {}
        for r in records:
            by_epoch.setdefault(r["epoch"], []).append(r["step"])
        # Reports survived the reset: steps from BOTH sides of the
        # rendezvous are retained, split across epochs...
        assert len(by_epoch) >= 2, by_epoch
        # ...with no step recorded twice within an epoch (no
        # double-count), and nothing lost: the union covers every
        # committed step exactly once per epoch.
        for epoch, steps in by_epoch.items():
            assert len(steps) == len(set(steps)), (epoch, steps)
        all_steps = sorted(s for steps in by_epoch.values()
                           for s in steps)
        assert all_steps == sorted(set(all_steps)), all_steps
        assert max(all_steps) == total_steps
        # The first post-restore record must not have absorbed the
        # multi-second recovery (reset_window discarded the open window):
        # every record's wall is a step, not a rendezvous.
        recovery_epoch = max(by_epoch)
        post = [r for r in records if r["epoch"] == recovery_epoch]
        assert all(r["wall_s"] < 30.0 for r in post), \
            [(r["step"], r["wall_s"]) for r in post]
        assert all(r["attribution"]["compute"] >= 0.0 for r in records)


class TestTimelineClockAlignment:
    """Satellite: the Chrome-trace timeline and the flight recorder's
    Perfetto output share a wall-clock anchor and merge into one view."""

    def test_timeline_emits_clock_sync_and_step_brackets(self, tmp_path):
        from horovod_tpu.timeline import Timeline
        path = str(tmp_path / "tl.json")
        before = time.time() * 1e6
        tl = Timeline(path, native=False)
        tl.mark_step(7)
        tl.close()
        data = json.load(open(path))
        evs = data["traceEvents"]
        sync = [e for e in evs if e.get("name") == "clock_sync"]
        assert sync and sync[0]["ph"] == "M"
        assert before <= sync[0]["args"]["wall_t0_us"] <= time.time() * 1e6
        steps = [e for e in evs if e.get("cat") == "step"]
        assert steps and steps[0]["name"] == "STEP 7"

    def test_flight_trace_merges_timeline_on_one_axis(self, tmp_path):
        from horovod_tpu.flight import analyze
        from horovod_tpu.timeline import Timeline

        # A flight trace whose events happen NOW (write_trace anchors its
        # clock_sync at the earliest event time).
        t0 = time.time()
        events = [
            {"kind": "dispatch", "rank": 0, "op": "allreduce", "ps": "g",
             "seq": 1, "t": t0},
            {"kind": "complete", "rank": 0, "op": "allreduce", "ps": "g",
             "seq": 1, "t": t0 + 0.010, "dur": 0.010},
        ]
        trace_path = str(tmp_path / "flight.json")
        analyze.write_trace(events, trace_path)

        tl_path = str(tmp_path / "tl.json")
        tl = Timeline(tl_path, native=False)
        span_at_us = 5000.0
        tl.record("op", "X", "ALLREDUCE", span_at_us, dur_us=100.0)
        tl.close()

        merged = analyze.merge_timeline(trace_path, tl_path)
        assert merged == 1
        data = json.load(open(trace_path))
        evs = data["traceEvents"]
        tl_ev = [e for e in evs if e.get("name") == "op"][0]
        assert tl_ev["pid"] >= 10000
        # The merged event's ts sits on the flight trace's axis: the
        # timeline started within a second of t0, so the rebased span
        # lands near span_at_us (± the construction skew), not at raw
        # span_at_us + an epoch.
        assert abs(tl_ev["ts"] - span_at_us) < 5e6
        # and the trace's own spans are still anchored at ~0.
        flight_span = [e for e in evs
                       if e.get("cat") == "collective"][0]
        assert flight_span["ts"] < 1e6

    def test_merge_without_anchor_is_refused(self, tmp_path):
        from horovod_tpu.flight import analyze
        trace_path = str(tmp_path / "flight.json")
        analyze.write_trace(
            [{"kind": "step", "rank": 0, "t": time.time()}], trace_path)
        legacy = str(tmp_path / "legacy.json")
        with open(legacy, "w") as f:
            json.dump({"traceEvents": [
                {"name": "op", "ph": "X", "ts": 1.0, "pid": 0}]}, f)
        assert analyze.merge_timeline(trace_path, legacy) == 0
