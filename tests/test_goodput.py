"""Fake-clock unit battery for the goodput/badput accounting plane.

Every :class:`GoodputLedger` test drives the ``now=`` seam with explicit
times — NO wall-clock sleeps, so the conservation assertions are exact
(tolerance 1e-9, not "within scheduler noise"). The journal/report tests
use a tmp dir; the one subprocess test (SIGKILL durability — the record
the store exists for) polls the journal file instead of sleeping for a
fixed interval.

The 8-process end-to-end leg (seeded kill + windowed straggler, brackets
against the injection ledger) is the slow soak in test_chaos_soak.py;
this file is the fast tier-1 coverage of the same state machine.
"""

import json
import os
import signal
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from horovod_tpu.chaos.plan import ChaosPlan, FaultSpec
from horovod_tpu.common.config import Config
from horovod_tpu.goodput import history
from horovod_tpu.goodput import ledger as goodput_mod
from horovod_tpu.goodput import report
from horovod_tpu.goodput.ledger import (BADPUT_CATEGORIES, CATEGORIES,
                                        PRODUCTIVE, GoodputLedger,
                                        ServingGoodput)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rec(comm=0.0, cross=0.0, host=0.0):
    """A closed step-window record with the profiler's attribution shape."""
    return {"attribution": {"collective": comm, "host_dispatch": host,
                            "cross_wait": cross}}


def _steps(led, t, n, dt=1.0, comm=0.0, first=1):
    """Drive ``n`` clean step windows of ``dt`` seconds; returns (t, next
    step number)."""
    for i in range(n):
        t += dt
        led.on_step_boundary(_rec(comm=comm), step=first + i, now=t)
    return t, first + n


@pytest.fixture
def fresh_module():
    """Module singletons reset + armed, restored afterwards (the module
    wrappers are process-global)."""
    saved = goodput_mod.armed
    goodput_mod.reset()
    goodput_mod.armed = True
    yield goodput_mod
    goodput_mod.armed = saved
    goodput_mod.reset()
    history._journal = None


# ---------------------------------------------------------------------------
# Conservation: every second booked exactly once, at any read point.
# ---------------------------------------------------------------------------


class TestConservation:
    def test_clean_run_decomposition(self):
        led = GoodputLedger()
        led.start(0.0)
        # Bootstrap/compile until the first boundary opens step windows.
        led.on_step_boundary(None, step=0, now=5.0)
        t, _ = _steps(led, 5.0, 10, dt=1.0, comm=0.1)
        snap = led.assert_conservation(t, tol=1e-9)
        assert snap["categories"]["init_compile"] == pytest.approx(5.0)
        assert snap["categories"][PRODUCTIVE] == pytest.approx(10.0)
        assert snap["goodput_ratio"] == pytest.approx(10.0 / 15.0)
        assert snap["steps"] == 10 and snap["resets"] == 0
        assert snap["conservation_error"] <= 1e-9

    def test_every_fault_of_one_schedule_is_recovered_exactly(self):
        """One run with each badput source in turn (a compile stall,
        straggler steps, a commit, a trial window, exposed cross-slice
        waits, a wedge, a reset): every injected second lands in its own
        category and in no other."""
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=5.0)        # compile stall
        t, step = _steps(led, 5.0, 12, comm=0.1)           # comm median
        t, step = _steps(led, t, 4, comm=0.6, first=step)  # 0.5 s over it
        led.note_commit(2.0)                # eats the next two windows
        t, step = _steps(led, t, 2, comm=0.1, first=step)
        led.set_trial(True)
        t, step = _steps(led, t, 3, comm=0.1, first=step)
        led.set_trial(False)
        for _ in range(2):
            t += 1.0
            led.on_step_boundary(_rec(comm=0.1, cross=0.3), step=step, now=t)
            step += 1
        led.note_wedge(now=t)
        t += 2.0
        led.note_unwedged(now=t)
        t += 1.5                            # the window a reset loses,
        led.on_reset(now=t)
        t += 3.0                            # and the re-rendezvous
        led.on_step_boundary(None, step=step, now=t)
        t, step = _steps(led, t, 2, comm=0.1, first=step + 1)
        cats = led.assert_conservation(t, tol=1e-9)["categories"]
        assert {c: v for c, v in cats.items() if v} == pytest.approx({
            "init_compile": 5.0, "straggler_wait": 2.0,
            "checkpoint_commit": 2.0, "autopilot_trial": 3.0,
            "cross_wait_comm": 0.6, "wedge_idle": 2.0,
            "rendezvous_recovery": 4.5,
            PRODUCTIVE: 12.0 + 4 * 0.5 + 2 * 0.7 + 2.0})

    def test_snapshot_attributes_live_tail_virtually(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=2.0)
        t, _ = _steps(led, 2.0, 3)
        # Mid-window read: the open 0.4 s tail counts as (virtual)
        # productive so the categories still sum to the wall.
        snap = led.snapshot(t + 0.4)
        assert snap["categories"][PRODUCTIVE] == pytest.approx(3.4)
        assert snap["conservation_error"] <= 1e-9
        # ...and the read did not consume it: the closed window books the
        # full gap once.
        led.on_step_boundary(_rec(), step=4, now=t + 1.0)
        snap = led.assert_conservation(t + 1.0, tol=1e-9)
        assert snap["categories"][PRODUCTIVE] == pytest.approx(4.0)

    def test_assert_conservation_raises_on_violation(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=1.0)
        # An integration bug (double booking) breaks the invariant.
        led._acc[PRODUCTIVE] += 50.0
        with pytest.raises(AssertionError, match="conservation"):
            led.assert_conservation(2.0)

    def test_not_started_is_disabled(self):
        led = GoodputLedger()
        assert led.snapshot(1.0) == {"enabled": False}
        # Mutators before start() are no-ops, not crashes.
        led.on_step_boundary(_rec(), step=1, now=1.0)
        led.on_reset(2.0)
        assert led.snapshot(3.0) == {"enabled": False}


# ---------------------------------------------------------------------------
# Boundary semantics: the ledger must agree with the profile ledger's
# explicit-step / auto-mark rule or the two state machines drift.
# ---------------------------------------------------------------------------


class TestBoundaries:
    def test_automark_suppressed_after_explicit_step(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=1, now=2.0)       # explicit
        led.on_step_boundary(_rec(), step=2, now=3.0)
        # A stray auto mark (step=None) must NOT move the mark: the next
        # closed window still books its full measured gap.
        led.on_step_boundary(None, step=None, now=3.5)
        led.on_step_boundary(_rec(), step=3, now=4.0)
        snap = led.assert_conservation(4.0, tol=1e-9)
        assert snap["categories"][PRODUCTIVE] == pytest.approx(2.0)
        assert snap["steps"] == 2

    def test_automark_opens_first_window_before_explicit(self):
        led = GoodputLedger()
        led.start(0.0)
        # No explicit step seen yet: the auto mark is a real boundary.
        led.on_step_boundary(None, step=None, now=1.5)
        snap = led.snapshot(1.5)
        assert snap["categories"]["init_compile"] == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# Elastic resets: lost windows and the recovery gap.
# ---------------------------------------------------------------------------


class TestRecovery:
    def test_reset_books_lost_window_and_recovery_gap(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=1.0)
        t, _ = _steps(led, 1.0, 4)
        # Fail 0.7 s into an open training window: that partial step is
        # destroyed work — recovery badput, not productive time.
        led.on_reset(t + 0.7)
        # Re-rendezvous + restore until the first post-restore boundary.
        led.on_step_boundary(None, step=5, now=t + 3.0)
        t2, _ = _steps(led, t + 3.0, 2, first=6)
        snap = led.assert_conservation(t2, tol=1e-9)
        assert snap["categories"]["rendezvous_recovery"] == \
            pytest.approx(3.0)
        assert snap["categories"][PRODUCTIVE] == pytest.approx(6.0)
        assert snap["resets"] == 1

    def test_reset_during_init_books_init(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_reset(4.0)                 # died while still compiling
        led.on_step_boundary(None, step=1, now=6.0)
        snap = led.assert_conservation(6.0, tol=1e-9)
        assert snap["categories"]["init_compile"] == pytest.approx(4.0)
        assert snap["categories"]["rendezvous_recovery"] == \
            pytest.approx(2.0)

    def test_reset_clears_comm_baseline(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=1.0)
        t, nxt = _steps(led, 1.0, 10, comm=0.1)
        led.on_reset(t)
        led.on_step_boundary(None, step=nxt, now=t + 1.0)
        # Post-reset step times are not comparable to the old membership:
        # an elevated window right after must NOT book straggler_wait
        # (no baseline yet).
        led.on_step_boundary(_rec(comm=0.5), step=nxt + 1, now=t + 2.0)
        snap = led.assert_conservation(t + 2.0, tol=1e-9)
        assert snap["categories"]["straggler_wait"] == 0.0

    def test_observed_recovery_samples_are_kept(self):
        led = GoodputLedger()
        led.start(0.0)
        led.note_recovery("reset", 2.25)
        snap = led.snapshot(1.0)
        assert snap["recoveries_observed"] == \
            [{"cause": "reset", "seconds": 2.25}]


# ---------------------------------------------------------------------------
# The straggler excess rule.
# ---------------------------------------------------------------------------


class TestStraggler:
    def _baseline(self, led, comm=0.1):
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=1.0)
        return _steps(led, 1.0, 8, comm=comm)

    def test_excess_over_rolling_median(self):
        led = GoodputLedger()
        t, nxt = self._baseline(led)
        led.on_step_boundary(_rec(comm=0.5), step=nxt, now=t + 1.4)
        snap = led.assert_conservation(t + 1.4, tol=1e-9)
        assert snap["categories"]["straggler_wait"] == pytest.approx(0.4)
        assert snap["categories"][PRODUCTIVE] == pytest.approx(8.0 + 1.0)

    def test_jitter_below_floor_is_not_badput(self):
        led = GoodputLedger()
        t, nxt = self._baseline(led)
        led.on_step_boundary(_rec(comm=0.104), step=nxt, now=t + 1.0)
        assert led.snapshot(t + 1.0)["categories"]["straggler_wait"] == 0.0

    def test_no_baseline_no_excess(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=1.0)
        t, nxt = _steps(led, 1.0, 7, comm=0.1)   # 7 < 8: not enough
        led.on_step_boundary(_rec(comm=0.5), step=nxt, now=t + 1.0)
        assert led.snapshot(t + 1.0)["categories"]["straggler_wait"] == 0.0

    def test_permanent_elevation_adapts_into_the_median(self):
        """A delay that never ends becomes the rank's own baseline: the
        rolling median climbs and the per-step excess dries up — which is
        exactly why the chaos soak injects its straggler only AFTER a
        clean baseline window."""
        led = GoodputLedger()
        t, nxt = self._baseline(led)
        for i in range(40):
            t += 1.4
            led.on_step_boundary(_rec(comm=0.5), step=nxt + i, now=t)
        booked = led.snapshot(t)["categories"]["straggler_wait"]
        # The first ~median-flip steps book the full 0.4 excess, then the
        # adapted median swallows it: far less than 40 * 0.4 = 16.
        assert 0.4 <= booked <= 6.0
        led.assert_conservation(t, tol=1e-9)

    def test_custom_floor(self):
        led = GoodputLedger(straggler_floor_s=0.5)
        t, nxt = self._baseline(led)
        led.on_step_boundary(_rec(comm=0.5), step=nxt, now=t + 1.0)
        assert led.snapshot(t + 1.0)["categories"]["straggler_wait"] == 0.0

    def test_watchdog_naming_rides_the_snapshot(self):
        led = GoodputLedger()
        led.start(0.0)
        assert "straggler_named" not in led.snapshot(1.0)
        led.note_straggler(5)
        assert led.snapshot(2.0)["straggler_named"] == 5


# ---------------------------------------------------------------------------
# Checkpoint commits and clamping.
# ---------------------------------------------------------------------------


class TestCommitAndClamp:
    def test_commit_consumed_from_its_window(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=1.0)
        led.note_commit(0.3)
        led.on_step_boundary(_rec(), step=1, now=2.0)
        snap = led.assert_conservation(2.0, tol=1e-9)
        assert snap["categories"]["checkpoint_commit"] == \
            pytest.approx(0.3)
        assert snap["categories"][PRODUCTIVE] == pytest.approx(0.7)

    def test_commit_spans_windows(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=1.0)
        led.note_commit(2.5)
        t, _ = _steps(led, 1.0, 3)        # three 1.0 s windows
        snap = led.assert_conservation(t, tol=1e-9)
        assert snap["categories"]["checkpoint_commit"] == \
            pytest.approx(2.5)
        assert snap["categories"][PRODUCTIVE] == pytest.approx(0.5)

    def test_badput_scaled_to_the_window(self):
        """Reported badput can exceed the measured gap (mixed clocks,
        overlapping attributions): it is scaled down so the window books
        exactly its measured duration — conservation wins."""
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=1.0)
        led.on_step_boundary(_rec(cross=2.0), step=1, now=2.0)
        snap = led.assert_conservation(2.0, tol=1e-9)
        assert snap["categories"]["cross_wait_comm"] == pytest.approx(1.0)
        assert snap["categories"][PRODUCTIVE] == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# Autopilot trials and wedge verdicts.
# ---------------------------------------------------------------------------


class TestTrialAndWedge:
    def test_trial_windows_book_autopilot_trial(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=1.0)
        led.set_trial(True)
        t, nxt = _steps(led, 1.0, 2)
        led.set_trial(False)
        t, _ = _steps(led, t, 3, first=nxt)
        snap = led.assert_conservation(t, tol=1e-9)
        assert snap["categories"]["autopilot_trial"] == pytest.approx(2.0)
        assert snap["categories"][PRODUCTIVE] == pytest.approx(3.0)

    def test_wedge_requires_train_phase(self):
        led = GoodputLedger()
        led.start(0.0)
        led.note_wedge(1.0)               # still in init: no-op
        assert led.snapshot(1.5)["phase"] == "init"

    def test_wedge_then_unwedge_books_idle(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=1.0)
        t, nxt = _steps(led, 1.0, 2)
        led.note_wedge(t + 0.5)
        led.note_unwedged(t + 4.0)
        t2, _ = _steps(led, t + 4.0, 1, first=nxt)
        snap = led.assert_conservation(t2, tol=1e-9)
        # The whole stalled gap (last boundary -> unwedge) is idle.
        assert snap["categories"]["wedge_idle"] == pytest.approx(4.0)
        assert snap["categories"][PRODUCTIVE] == pytest.approx(3.0)

    def test_closed_window_overrides_wedge_verdict(self):
        led = GoodputLedger()
        led.start(0.0)
        led.on_step_boundary(None, step=0, now=1.0)
        led.note_wedge(1.5)
        # The step completed after all: the closed window is
        # authoritative and books through the normal decomposition.
        led.on_step_boundary(_rec(), step=1, now=2.0)
        snap = led.assert_conservation(2.0, tol=1e-9)
        assert snap["categories"]["wedge_idle"] == 0.0
        assert snap["categories"][PRODUCTIVE] == pytest.approx(1.0)
        assert snap["phase"] == "train"

    def test_wedge_from_health_rows(self, fresh_module):
        led = fresh_module.get_ledger()
        t0 = time.monotonic()
        led.start(t0)
        led.on_step_boundary(None, step=1, now=t0)
        fresh_module.wedge_from_rows(
            [{"rank": 3, "state": "stalled"},
             {"rank": 0, "state": "stalled"}], rank=0)
        assert led.snapshot(t0 + 1.0)["phase"] == "wedge"
        # Other ranks' verdicts never touch this rank's ledger.
        fresh_module.wedge_from_rows([{"rank": 3, "state": "healthy"}],
                                     rank=0)
        assert led.snapshot(t0 + 2.0)["phase"] == "wedge"
        fresh_module.wedge_from_rows([{"rank": 0, "state": "healthy"}],
                                     rank=0)
        assert led.snapshot(time.monotonic())["phase"] == "train"


# ---------------------------------------------------------------------------
# Serving-plane goodput: in-SLO token-seconds.
# ---------------------------------------------------------------------------


class TestServingGoodput:
    def test_in_slo_token_seconds(self):
        s = ServingGoodput()
        s.record_decode_step(0.5, 10, in_slo=True)    # 5 token-s, good
        s.record_decode_step(1.0, 10, in_slo=False)   # 10 token-s, bad
        snap = s.snapshot()
        assert snap["token_seconds"] == pytest.approx(15.0)
        assert snap["in_slo_token_seconds"] == pytest.approx(5.0)
        assert snap["goodput_ratio"] == pytest.approx(5.0 / 15.0)
        assert snap["tokens"] == 20 and snap["steps"] == 2

    def test_degenerate_steps_ignored(self):
        s = ServingGoodput()
        s.record_decode_step(-1.0, 10, in_slo=True)
        s.record_decode_step(0.5, 0, in_slo=True)
        assert s.snapshot()["steps"] == 0
        assert s.snapshot()["goodput_ratio"] == 1.0   # vacuously in-SLO


# ---------------------------------------------------------------------------
# Config knobs.
# ---------------------------------------------------------------------------


class TestConfigKnobs:
    def test_run_history_requires_goodput(self):
        with pytest.raises(ValueError, match="run_history_dir"):
            Config(goodput=False, run_history_dir="/tmp/x")

    def test_journal_cadence_must_be_positive(self):
        with pytest.raises(ValueError, match="goodput_journal_s"):
            Config(goodput_journal_s=0.0)

    def test_from_env_reads_the_knobs(self, monkeypatch, tmp_path):
        monkeypatch.setenv("HOROVOD_GOODPUT", "1")
        monkeypatch.setenv("HOROVOD_RUN_HISTORY_DIR", str(tmp_path))
        monkeypatch.setenv("HOROVOD_GOODPUT_JOURNAL_S", "2.5")
        monkeypatch.setenv("HOROVOD_RUN_ID", "abc123")
        c = Config.from_env()
        assert c.goodput and c.run_history_dir == str(tmp_path)
        assert c.goodput_journal_s == 2.5 and c.run_id == "abc123"

    def test_from_env_revalidates(self, monkeypatch, tmp_path):
        monkeypatch.setenv("HOROVOD_GOODPUT", "0")
        monkeypatch.setenv("HOROVOD_RUN_HISTORY_DIR", str(tmp_path))
        with pytest.raises(ValueError, match="run_history_dir"):
            Config.from_env()


# ---------------------------------------------------------------------------
# Durable run history: the journal and its readers.
# ---------------------------------------------------------------------------


def _write_run(root, rid, ratio, wall=100.0, ended=True, badput=None,
               cluster=None, named=None):
    """Seed one journaled run with a synthetic goodput summary."""
    cats = dict.fromkeys(CATEGORIES, 0.0)
    cats.update(badput or {})
    cats[PRODUCTIVE] = ratio * wall
    summary = {"enabled": True, "wall_s": wall, "phase": "train",
               "steps": 100, "resets": 0, "goodput_ratio": ratio,
               "categories": cats,
               "badput_s": round(wall - ratio * wall, 6),
               "conservation_error": 0.0}
    if named is not None:
        summary["straggler_named"] = named
    j = history.RunJournal(root, run_id=rid)
    j.append("run_start", fingerprint="fp", world=8, rank=0)
    j.append("goodput", summary=summary)
    if cluster is not None:
        j.append("cluster", view=cluster)
    if ended:
        j.append("run_end", goodput_ratio=ratio, wall_s=wall)
    return j.path


class TestRunHistory:
    def test_journal_roundtrip(self, tmp_path):
        path = _write_run(str(tmp_path), "r1", 0.9)
        recs = history.read_journal(path)
        assert [r["kind"] for r in recs] == \
            ["run_start", "goodput", "run_end"]
        assert all(r["run"] == "r1" for r in recs)

    def test_torn_final_line_tolerated(self, tmp_path):
        path = _write_run(str(tmp_path), "r1", 0.9, ended=False)
        with open(path, "a") as f:
            f.write('{"t": 1.0, "kind": "goodp')   # the SIGKILL artifact
        recs = history.read_journal(path)
        assert [r["kind"] for r in recs] == ["run_start", "goodput"]
        runs = history.read_runs(str(tmp_path))
        assert runs["r1"]["ended"] is False
        assert runs["r1"]["goodput"]["summary"]["goodput_ratio"] == 0.9

    def test_read_runs_summarizes(self, tmp_path):
        _write_run(str(tmp_path), "a", 0.8)
        _write_run(str(tmp_path), "b", 0.5, ended=False)
        runs = history.read_runs(str(tmp_path))
        assert set(runs) == {"a", "b"}
        assert runs["a"]["ended"] and not runs["b"]["ended"]
        assert runs["a"]["records"] == 3

    def test_journal_configure_is_rank0_only(self, tmp_path):
        cfg = SimpleNamespace(run_history_dir=str(tmp_path))
        try:
            assert history.journal_configure(cfg, rank=3, world=8) is None
            j = history.journal_configure(cfg, rank=0, world=8,
                                          run_id="only0")
            assert j is not None and history.get_journal() is j
            history.journal_append("goodput", summary={"goodput_ratio": 1})
            history.journal_finalize({"goodput_ratio": 1.0, "wall_s": 2.0})
            runs = history.read_runs(str(tmp_path))
            assert runs["only0"]["ended"]
            assert runs["only0"]["start"]["world"] == 8
        finally:
            history._journal = None

    def test_unarmed_appends_are_noops(self):
        history._journal = None
        history.journal_append("goodput", summary={})   # must not raise
        history.journal_finalize({})

    @pytest.mark.timeout(120)
    def test_sigkilled_run_leaves_parseable_journal(self, tmp_path):
        """The durability contract: a worker SIGKILLed mid-run leaves a
        journal whose last heartbeat is a parseable goodput summary and
        whose missing run_end marks it killed."""
        root = str(tmp_path)
        child = (
            "import time\n"
            "from horovod_tpu.goodput.ledger import GoodputLedger\n"
            "from horovod_tpu.goodput.history import RunJournal\n"
            f"j = RunJournal({root!r}, run_id='killme')\n"
            "j.append('run_start', fingerprint='fp', world=1, rank=0)\n"
            "led = GoodputLedger()\n"
            "led.start()\n"
            "step = 0\n"
            "while True:\n"
            "    time.sleep(0.02)\n"
            "    step += 1\n"
            "    led.on_step_boundary({'attribution': {}}, step=step)\n"
            "    j.append('goodput', summary=led.snapshot())\n"
        )
        proc = subprocess.Popen([sys.executable, "-c", child], cwd=_REPO,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        path = os.path.join(root, "run_killme.jsonl")
        try:
            deadline = time.time() + 90
            while time.time() < deadline:
                if len(history.read_journal(path)) >= 4:
                    break
                if proc.poll() is not None:
                    raise AssertionError("journal child died early")
                time.sleep(0.05)
        finally:
            if proc.poll() is None:
                os.kill(proc.pid, signal.SIGKILL)
            proc.wait()
        runs = history.read_runs(root)
        assert "killme" in runs, os.listdir(root)
        run = runs["killme"]
        assert run["ended"] is False          # killed, by definition
        summary = run["goodput"]["summary"]
        assert summary["enabled"] and summary["steps"] >= 1
        assert summary["conservation_error"] <= 0.01


# ---------------------------------------------------------------------------
# The report CLI: render, victim naming, cross-run regression gate.
# ---------------------------------------------------------------------------


class TestReport:
    def test_render_names_the_watchdog_victim(self, tmp_path, capsys):
        cluster = {"goodput": {"ranks": {
            "2": {"straggler_wait_s": 9.0},
            "5": {"straggler_wait_s": 11.0}}}}
        _write_run(str(tmp_path), "r1", 0.7,
                   badput={"straggler_wait": 30.0}, cluster=cluster,
                   named=2)
        assert report.main(["--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        # The comparative watchdog naming beats the (noisier) max
        # self-relative wait — rank 5's bigger number does not win.
        assert "victim: rank 2" in out
        assert "watchdog straggler naming" in out
        assert "straggler_wait" in out

    def test_find_victim_falls_back_to_max_wait(self):
        summary = {"goodput": {"summary": {"goodput_ratio": 0.5}},
                   "cluster": {"goodput": {"ranks": {
                       "1": {"straggler_wait_s": 2.0},
                       "4": {"straggler_wait_s": 7.0}}}}}
        rank, why = report.find_victim(summary)
        assert rank == "4" and "straggler_wait" in why

    def test_list_marks_killed_runs(self, tmp_path, capsys):
        _write_run(str(tmp_path), "a", 0.9)
        _write_run(str(tmp_path), "b", 0.4, ended=False)
        assert report.main(["--dir", str(tmp_path), "--list"]) == 0
        out = capsys.readouterr().out
        assert "[killed]" in out and "a " in out

    def test_diff_flags_seeded_regression(self, tmp_path, capsys):
        root = str(tmp_path)
        for i, ratio in enumerate((0.90, 0.91, 0.89, 0.90)):
            _write_run(root, f"h{i}", ratio)
        _write_run(root, "bad", 0.60,
                   badput={"straggler_wait": 40.0})
        # Healthy pair: exit 0.
        assert report.main(["--dir", root, "--diff", "h0", "h3"]) == 0
        # Seeded regression: absolute drop AND robust-z fire, exit 1.
        assert report.main(["--dir", root, "--diff", "h3", "bad"]) == 1
        out = capsys.readouterr().out
        assert "REGRESSION" in out
        assert "straggler_wait" in out

    def test_diff_unknown_run_exits_2(self, tmp_path, capsys):
        _write_run(str(tmp_path), "a", 0.9)
        assert report.main(["--dir", str(tmp_path),
                            "--diff", "a", "ghost"]) == 2

    def test_empty_dir_exits_2(self, tmp_path):
        assert report.main(["--dir", str(tmp_path / "nothing")]) == 2

    def test_json_output(self, tmp_path, capsys):
        _write_run(str(tmp_path), "a", 0.9)
        assert report.main(["--dir", str(tmp_path), "--json"]) == 0
        rec = json.loads(capsys.readouterr().out)
        assert rec["run"] == "a" and rec["ended"]


# ---------------------------------------------------------------------------
# Twin replay: the scale validation — a chaos plan replayed through the
# PR-19 digital twin, its virtual timeline booked through the SAME ledger
# class, must conserve exactly and name the injected faults.
# ---------------------------------------------------------------------------


class TestTwinReplay:
    ROUND_GAP = 30.0

    def _twin_report(self, seed=9):
        from horovod_tpu.sim import TwinJob
        plan = ChaosPlan([
            FaultSpec(site="negotiation.exchange", kind="crash", rank=37,
                      at=[2], max_fires=1),
            FaultSpec(site="negotiation.exchange", kind="delay", rank=5,
                      delay_ms=800, at=[14, 15, 16]),
        ], seed=seed)
        return TwinJob(128, 4, rounds=20, plan=plan, hysteresis=2,
                       round_gap_s=self.ROUND_GAP).run()

    def _replay(self, rep):
        """Coordinator-view replay on the virtual clock: each round is
        one step window whose comm attribution is the exchange duration;
        a round that removed members re-rendezvouses like the live
        elastic stack (reset -> recovery gap -> first explicit
        boundary)."""
        removal_rounds = {m["round"] for m in rep["membership"]}
        led = GoodputLedger()
        t = 0.0
        led.start(t)
        led.on_step_boundary(None, step=0, now=t)
        step = 0
        for rnd in rep["rounds"]:
            t_end = t + float(rnd["virtual_s"]) + self.ROUND_GAP
            step += 1
            if rnd["round"] in removal_rounds:
                led.on_reset(t_end)
                t = t_end + 5.0           # virtual re-rendezvous
                led.on_step_boundary(None, step=step, now=t)
            else:
                led.on_step_boundary(
                    _rec(comm=float(rnd["virtual_s"])), step=step,
                    now=t_end)
                t = t_end
        return led, t

    @pytest.mark.timeout(180)
    def test_virtual_badput_names_the_injected_faults(self):
        rep = self._twin_report()
        assert rep["final_world"] == 127   # the kill was remediated
        led, t = self._replay(rep)
        snap = led.assert_conservation(t, tol=1e-6)
        # The kill round replays as rendezvous_recovery badput...
        assert snap["categories"]["rendezvous_recovery"] > 0.0
        assert snap["resets"] >= 1
        # ...and the windowed 800 ms delays (injected only after a clean
        # baseline) book straggler_wait of the injected order.
        assert snap["categories"]["straggler_wait"] >= 0.4
        assert snap["categories"]["straggler_wait"] <= 3 * 0.8 + 1.0

    @pytest.mark.timeout(180)
    def test_replayed_decomposition_is_deterministic(self):
        snaps = []
        for _ in range(2):
            led, t = self._replay(self._twin_report())
            snaps.append(json.dumps(led.snapshot(t)["categories"],
                                    sort_keys=True))
        assert snaps[0] == snaps[1]
