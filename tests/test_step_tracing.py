"""What the program names from inside, for a profile to read: device scopes
in the compiled steps (``jax.named_scope``; found here in the compiled
HLO's ``op_name`` metadata), host spans through ``trace.span`` (store and
profiler annotation), the two ``fused_allreduce_tree`` gauges, and the
benchmark's reading of them (``benchmark/harness/scopes.py``) on
hand-written events. CPU only; nothing here is a time.
"""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _loss_fn(params, batch):
    h = jnp.tanh(batch["x"] @ params["w"])
    return jnp.mean((h @ params["v"]) ** 2)


def _params():
    return {"w": jnp.ones((8, 16), jnp.float32),
            "v": jnp.ones((16, 4), jnp.float32)}


@pytest.fixture(scope="module")
def mesh4():
    return Mesh(np.array(jax.devices()[:4]), ("hvd",))


def _hlo_of(kind, mesh):
    """Compiled HLO text of one step kind over ``mesh``."""
    from horovod_tpu.optim import DistributedOptimizer
    from horovod_tpu.parallel import (TrainState, ZeroTrainState,
                                      make_fsdp_train_step, make_train_step,
                                      make_zero_train_step)
    batch = {"x": jnp.ones((8, 8), jnp.float32)}
    rep = NamedSharding(mesh, P())
    if kind == "dp":
        opt = DistributedOptimizer(optax.adamw(1e-3))
        state = jax.device_put(TrainState.create(_params(), opt), rep)
        step = make_train_step(_loss_fn, opt, mesh, donate=False)
        return step.lower(state, batch).compile().as_text()
    if kind == "zero":
        tx = optax.adamw(1e-3)
        state = ZeroTrainState.create(_params(), tx, mesh)
        step = make_zero_train_step(_loss_fn, tx, mesh, donate=False)
        return step.lower(state, batch).compile().as_text()
    init, step = make_fsdp_train_step(_loss_fn, optax.adamw(1e-3), mesh,
                                      donate=False, min_size=16)
    params, opt_state = init(_params())
    return step.lower(params, opt_state, batch).compile().as_text()


def _op_names(hlo):
    return [m.group(1) for m in OP_NAME.finditer(hlo)]


class TestDeviceScopes:
    @pytest.mark.parametrize("kind,module", [
        ("dp", "jit_hvd_dp_step"), ("zero", "jit_hvd_zero_step"),
        ("fsdp", "jit_hvd_fsdp_step")])
    def test_step_module_and_phase_scopes(self, mesh4, kind, module):
        hlo = _hlo_of(kind, mesh4)
        assert hlo.startswith(f"HloModule {module},")
        names = _op_names(hlo)
        grad = [n for n in names if "/hvd.loss_and_grad/" in n]
        # forward and backward are told apart by JAX's own mark
        assert any("transpose(jvp(" in n for n in grad)
        assert any("transpose(" not in n for n in grad)
        assert any("/hvd.optimizer/" in n for n in names)

    def test_dp_exchange_scopes_and_wire_on_every_all_reduce(self, mesh4):
        hlo = _hlo_of("dp", mesh4)
        names = _op_names(hlo)
        for part in ("pack", "hvd.wire", "unpack"):
            assert any(f"/hvd.optimizer/hvd.grad_exchange/bucket0/{part}/"
                       in n for n in names), part
        reduces = [ln for ln in hlo.splitlines()
                   if re.search(r" all-reduce(-start)?\(", ln)]
        assert reduces
        for ln in reduces:
            m = OP_NAME.search(ln)
            assert m and "hvd.wire/" in m.group(1), ln[:200]
        # the division of Average stays outside the wire
        assert any(n.endswith("bucket0/div") for n in names)

    def test_scopes_leave_the_program_as_it_was(self, mesh4):
        """Scopes are metadata: the same step written without them lowers
        to the same operations."""
        from horovod_tpu.ops import in_jit
        from horovod_tpu.optim import DistributedOptimizer
        opt = DistributedOptimizer(optax.sgd(0.1))

        def bare(params, batch):
            def local(params, batch):
                params = in_jit.mark_varying(params, "hvd")
                loss, grads = jax.value_and_grad(_loss_fn)(params, batch)
                updates, _ = opt.update(grads, opt.init(params), params)
                return optax.apply_updates(params, updates), loss
            return jax.shard_map(local, mesh=mesh4,
                                 in_specs=(P(), P("hvd")),
                                 out_specs=(P(), P()),
                                 check_vma=False)(params, batch)

        def scoped(params, batch):
            def local(params, batch):
                params = in_jit.mark_varying(params, "hvd")
                with jax.named_scope("hvd.loss_and_grad"):
                    loss, grads = jax.value_and_grad(_loss_fn)(params, batch)
                with jax.named_scope("hvd.optimizer"):
                    updates, _ = opt.update(grads, opt.init(params), params)
                    params = optax.apply_updates(params, updates)
                return params, loss
            return jax.shard_map(local, mesh=mesh4,
                                 in_specs=(P(), P("hvd")),
                                 out_specs=(P(), P()),
                                 check_vma=False)(params, batch)

        def ops(fn):
            text = jax.jit(fn).lower(
                _params(), {"x": jnp.ones((8, 8))}).as_text()
            text = re.sub(r"loc\(.*\)$", "", text, flags=re.M)
            return [ln.strip() for ln in text.splitlines()
                    if "stablehlo." in ln]
        def unnamed(lines):      # the functions' names differ, no more
            return [re.sub(r"@\w+", "@f", ln) for ln in lines]
        assert unnamed(ops(bare)) == unnamed(ops(scoped))


class TestFusedAllreduceGauges:
    def test_buckets_and_bytes_of_a_known_tree(self, hvd, mesh4, monkeypatch):
        """Three float32 leaves of 1000, 24 and 3000 elements under a
        threshold of 4200 bytes: [1000 + 24] padded to 1024, then 3000
        padded to 3072; one int32 leaf reduced alone (Sum)."""
        from horovod_tpu import metrics
        from horovod_tpu.common import basics
        from horovod_tpu.optim import fused_allreduce_tree
        monkeypatch.setattr(basics.config(), "fusion_threshold", 4200)
        tree = {"a": jnp.ones((1000,), jnp.float32),
                "b": jnp.ones((24,), jnp.float32),
                "c": jnp.ones((3000,), jnp.float32),
                "d": jnp.ones((5,), jnp.int32)}
        traces = []

        def body(t):
            traces.append(1)
            return fused_allreduce_tree(t, op=hvd.Sum)
        f = jax.jit(jax.shard_map(body, mesh=mesh4, in_specs=P(),
                                  out_specs=P(), check_vma=False))
        lowered = f.lower(tree)

        def gauge(name):
            series = metrics.snapshot()[name]["series"]
            return {s["labels"]["axis_size"]: s["value"] for s in series}
        assert gauge("hvd_fused_allreduce_buckets")["4"] == 3
        assert gauge("hvd_fused_allreduce_bytes")["4"] \
            == (1024 + 3072) * 4 + 5 * 4
        names = _op_names(lowered.compile().as_text())
        assert any("hvd.grad_exchange/bucket1/pack/" in n for n in names)
        assert any(re.search(r"hvd.grad_exchange/leaf\d/hvd.wire/", n)
                   for n in names)
        # set while traced: running the compiled program traces nothing
        out = f(tree)
        assert float(out["a"][0]) == 4.0 and len(traces) == 1


class TestHostSpans:
    @pytest.fixture(autouse=True)
    def fresh_store(self):
        from horovod_tpu import trace
        trace.reset()
        yield
        trace.reset()

    @pytest.fixture()
    def annotations(self, monkeypatch):
        """Names (and keyword stats) of the profiler annotations entered."""
        from horovod_tpu import trace
        seen = []

        class Recorder:
            def __init__(self, name, **kw):
                seen.append((name, kw))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False
        monkeypatch.setattr(trace, "TraceAnnotation", Recorder)
        return seen

    def test_annotation_with_and_without_an_active_trace(self, annotations):
        from horovod_tpu import trace
        with trace.span("shard_batch"):
            pass
        assert annotations == [("hvd::shard_batch", {})]
        assert trace.snapshot()["traces"] == []      # nothing active
        tid = trace.step_trace(7)
        with trace.span("shard_batch", args={"rows": 8}):
            pass
        assert annotations[-1] == ("hvd::shard_batch", {"rows": 8})
        assert [s["name"] for s in trace.get(tid)["spans"]] \
            == ["shard_batch"]
        with trace.span("ALLREDUCE::g", store=False):
            pass
        assert annotations[-1][0] == "hvd::ALLREDUCE::g"
        assert len(trace.get(tid)["spans"]) == 1

    def test_real_annotation_costs_nothing_without_a_session(self):
        from horovod_tpu import trace
        with trace.span("shard_batch"):      # the real TraceAnnotation
            with trace.span("inner", tid=trace.run_tid()):
                pass
        run = trace.get(trace.run_tid())
        assert [s["name"] for s in run["spans"]] == ["inner"]

    def test_setup_spans_live_in_the_run_trace_with_parents(self):
        from horovod_tpu import trace
        run = trace.run_tid()
        assert run == trace.run_tid() and trace.get(run)["kind"] == "run"
        step = trace.step_trace(1)           # a step is active: run wins
        with trace.span("init", tid=run):
            with trace.span("init.recorders", tid=run):
                with trace.span("init.recorders.flight", tid=run):
                    pass
                with trace.span("init.recorders.goodput", tid=run):
                    pass
            with trace.span("init.topology", tid=run):
                pass
        with trace.span("broadcast_parameters", tid=run,
                        args={"leaves": 2, "bytes": 64}):
            pass
        assert trace.get(step)["spans"] == []
        tree = trace.tree(run)
        assert [c["name"] for c in tree["children"]] \
            == ["init", "broadcast_parameters"]
        init = tree["children"][0]
        assert [c["name"] for c in init["children"]] \
            == ["init.recorders", "init.topology"]
        assert [c["name"] for c in init["children"][0]["children"]] \
            == ["init.recorders.flight", "init.recorders.goodput"]
        assert tree["children"][1]["args"] == {"leaves": 2, "bytes": 64}
        assert init["dur"] >= init["children"][0]["dur"] >= 0.0

    def test_run_trace_is_one_and_bounded(self, monkeypatch):
        from horovod_tpu import trace
        monkeypatch.setattr(trace, "_MAX_SPANS", 4)
        run = trace.run_tid()
        for _ in range(9):
            with trace.span("opt_state_init", tid=run):
                pass
        rec = trace.get(run)
        assert len(rec["spans"]) == 4 and rec["dropped"] == 5
        kinds = [r["kind"] for r in trace.snapshot()["traces"]]
        assert kinds == ["run"]
        monkeypatch.setattr(trace, "armed", False)
        assert trace.run_tid() is None

    def test_init_and_setup_sites_write_their_spans(self, hvd, mesh4):
        """The spans of the real sites: a second init in this process is
        refused early and writes nothing, so read ``_init``'s children from
        a forced pass over the recorders, and drive the other sites."""
        from horovod_tpu import trace
        from horovod_tpu.common import basics
        from horovod_tpu.optim import DistributedOptimizer
        from horovod_tpu.parallel import TrainState, shard_batch
        hvd.init()                           # already up: no span
        assert trace.get(trace.run_tid())["spans"] == []
        with trace.run_span("init"):
            with trace.run_span("init.recorders"):
                basics._arm_recorders(basics.config(), basics.topology())
        params = hvd.broadcast_parameters(_params(), root_rank=0)
        TrainState.create(params, DistributedOptimizer(optax.sgd(0.1)))
        shard_batch({"x": np.ones((8, 8), np.float32)}, mesh4)
        names = [s["name"] for s in trace.get(trace.run_tid())["spans"]]
        for want in ("init", "init.recorders", "init.recorders.metrics",
                     "init.recorders.telemetry", "init.recorders.autopilot",
                     "broadcast_parameters", "opt_state_init"):
            assert want in names, (want, names)
        assert "shard_batch" not in names    # no step trace: profiler only
        spans = {s["name"]: s for s in
                 trace.get(trace.run_tid())["spans"]}
        assert spans["broadcast_parameters"]["args"] \
            == {"leaves": 2, "bytes": (8 * 16 + 16 * 4) * 4}
        tid = trace.step_trace(3)
        shard_batch({"x": np.ones((8, 8), np.float32)}, mesh4)
        assert [s["name"] for s in trace.get(tid)["spans"]] \
            == ["shard_batch"]

    def test_import_span_is_stored_at_import(self):
        code = ("import horovod_tpu as hvd\n"
                "from horovod_tpu import trace\n"
                "s = trace.get(trace.run_tid())['spans']\n"
                "assert [x['name'] for x in s] == ['import'], s\n"
                "assert 0.0 < s[0]['dur'] < 600.0\n"
                "print('ok')\n")
        env = dict(os.environ, PYTHONPATH=ROOT)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=240)
        assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr

    def test_one_annotation_site_in_the_package(self):
        hits = []
        for dirpath, _, files in os.walk(os.path.join(ROOT, "horovod_tpu")):
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(dirpath, f)
                    with open(path) as fh:
                        if "TraceAnnotation(" in fh.read():
                            hits.append(os.path.relpath(path, ROOT))
        assert hits == ["horovod_tpu/trace/__init__.py"]


# --- the benchmark's reading of scopes and spans -------------------------

class _Window:
    """What ``scopes`` takes of a ``trace_reduce.Chip``."""

    def __init__(self, index, steps, start_ns, end_ns):
        self.index, self.steps = index, steps
        self.start_ns, self.end_ns = start_ns, end_ns


class _Summary:
    def __init__(self, *chips):
        self.chips = list(chips)


STEP = "jit(hvd_dp_step)/shard_map/"
# (instruction's HLO line, op_name path or None, start ns, duration ns)
HAND_OPS = [
    ("%fusion.1 = bf16[8,1024]{1,0} fusion(%p.1), kind=kOutput",
     STEP + "hvd.loss_and_grad/jvp(GPT)/layer_0/mlp/dot_general", 1000, 400),
    ("%fusion.2 = bf16[8,1024]{1,0} fusion(%p.2), kind=kOutput",
     STEP + "hvd.loss_and_grad/transpose(jvp(GPT))/layer_0/mlp/dot_general",
     1400, 800),
    # a fusion across the scopes' border goes where its own path says
    ("%convert_bitcast_fusion = f32[4096]{0} fusion(%fusion.2)",
     STEP + "hvd.loss_and_grad/transpose(jvp(GPT))/layer_0/convert_element_"
     "type;hvd.optimizer/hvd.grad_exchange/bucket1/pack/reshape", 2200, 100),
    ("%dus_fusion.4 = f32[8192]{0} fusion(%convert_bitcast_fusion)",
     STEP + "hvd.optimizer/hvd.grad_exchange/bucket1/pack/concatenate",
     2300, 50),
    ("%psum.197 = f32[51511296]{0} all-reduce(f32[51511296]{0} %r.1), "
     "channel_id=1, to_apply=%region_1",
     STEP + "hvd.optimizer/hvd.grad_exchange/bucket1/hvd.wire/psum",
     2350, 3600),
    ("%all-reduce.7 = (f32[8192]{0}, f32[8192]{0}) all-reduce(%a, %b)",
     STEP + "hvd.optimizer/hvd.grad_exchange/bucket5/hvd.wire/psum",
     5950, 1500),
    ("%broadcast_multiply_fusion.4 = f32[8192]{0} fusion(%all-reduce.7)",
     STEP + "hvd.optimizer/hvd.grad_exchange/bucket5/div", 7450, 30),
    ("%slice.9 = f32[4096]{0} slice(%broadcast_multiply_fusion.4)",
     STEP + "hvd.optimizer/hvd.grad_exchange/bucket5/unpack/slice",
     7480, 20),
    ("%multiply_add_fusion = f32[1024,50304]{1,0} fusion(%p.9, %slice.9)",
     STEP + "hvd.optimizer/add", 7500, 2400),
    # the loss's own mean: a wire outside the exchange
    ("%all-reduce.1 = f32[] all-reduce(%loss)", STEP + "hvd.wire/psum",
     9900, 10),
    # made by the compiler, no metadata at all
    ("%copy-done.9 = f32[256,1024]{1,0} copy-done(%copy-start.9)", None,
     9910, 60),
    ("%fusion.3 = f32[] fusion(%x)", STEP + "div", 9970, 5),
]


def _hand_space(pb2, steps):
    space = pb2.XSpace()
    plane = space.planes.add(id=1, name="/device:TPU:0")
    plane.stat_metadata[1].id = 1
    plane.stat_metadata[1].name = "tf_op"
    plane.stat_metadata[2].id = 2
    plane.stat_metadata[2].name = "hlo_category"
    ops = plane.lines.add(id=1, name="XLA Ops", timestamp_ns=0)
    ids = {}
    for k in range(steps):
        for name, path, start, dur in HAND_OPS:
            if name not in ids:
                ids[name] = len(ids) + 1
                meta = plane.event_metadata[ids[name]]
                meta.id, meta.name = ids[name], name
                meta.stats.add(metadata_id=2, str_value="fusion")
                if path is not None:
                    meta.stats.add(metadata_id=1, str_value=path + ":")
            ops.events.add(metadata_id=ids[name],
                           offset_ps=(start + 10000 * k) * 1000,
                           duration_ps=dur * 1000)
    # one op of the next step, cut by the window's end: dropped
    ops.events.add(metadata_id=1, offset_ps=(10000 * steps + 990) * 1000,
                   duration_ps=400 * 1000)
    host = space.planes.add(id=2, name="/host:CPU")
    names = {1: "hvd::shard_batch", 2: "$fsdp.py:129 shard_batch",
             3: "hvd::ALLREDUCE::g"}
    for key, name in names.items():
        host.event_metadata[key].id = key
        host.event_metadata[key].name = name
    main = host.lines.add(id=7, name="python3", timestamp_ns=100)
    for k in range(steps):
        main.events.add(metadata_id=2, offset_ps=10000 * k * 1000,
                        duration_ps=900 * 1000)
        main.events.add(metadata_id=1, offset_ps=(10000 * k + 50) * 1000,
                        duration_ps=(800 + 100 * k) * 1000)
    main.events.add(metadata_id=3, offset_ps=5, duration_ps=7000)
    return space


class TestBenchmarkReading:
    def test_check_manifest_passes(self):
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--check-manifest"], capture_output=True, text=True,
            timeout=120, cwd=ROOT)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "0 problem(s)" in out.stdout

    @pytest.mark.parametrize("path,phase", [
        (STEP + "hvd.loss_and_grad/jvp(GPT)/layer_3/attention/split",
         "forward"),
        (STEP + "hvd.loss_and_grad/transpose(jvp(GPT))/layer_3/mlp/mul",
         "backward"),
        (STEP + "hvd.loss_and_grad/jvp(GPT)/transpose", "forward"),
        (STEP + "hvd.optimizer/add", "optimizer"),
        (STEP + "hvd.optimizer/hvd.grad_exchange/bucket3/hvd.wire/psum",
         "reduce"),
        (STEP + "hvd.optimizer/hvd.grad_exchange/leaf7/hvd.wire/all_gather",
         "reduce"),
        (STEP + "hvd.grad_exchange/bucket0/pack/concatenate", "bookkeeping"),
        (STEP + "hvd.optimizer/hvd.grad_exchange/bucket3/div", "bookkeeping"),
        (STEP + "hvd.optimizer/hvd.grad_exchange/unpack/convert_element_type",
         "bookkeeping"),
        (STEP + "hvd.wire/psum", "unscoped"),
        (STEP + "div", "unscoped"),
        ("", "unscoped"),
        (None, "unscoped"),
        (STEP + "hvd.loss_and_grad/transpose(jvp(GPT))/mul;hvd.optimizer/"
         "hvd.grad_exchange/bucket1/pack/reshape", "backward"),
    ])
    def test_phase_of_a_path(self, path, phase):
        from benchmark.harness import scopes
        assert scopes.phase_of(path) == phase

    def test_attribution_on_hand_written_events(self):
        from benchmark.harness import scopes
        steps = 2
        space = _hand_space(scopes.xplane_pb2(), steps)
        window = _Window(0, steps, 1000, 10000 * steps + 1000)
        trace = scopes.ScopedTrace(space, _Summary(window))
        chip, = trace.chips
        want_ns = {"forward": 400, "backward": 800 + 100,
                   "optimizer": 2400, "reduce": 3600 + 1500,
                   "bookkeeping": 50 + 30 + 20, "unscoped": 10 + 60 + 5}
        for phase, ns in want_ns.items():
            assert chip.seconds[phase] == pytest.approx(steps * ns * 1e-9)
            assert trace.phase_ms_per_step(phase) == pytest.approx(ns * 1e-6)
        # the all-reduce XLA left named psum.197 is a reduce like the rest
        assert chip.counts["reduce"] == 2 * steps
        assert sum(chip.counts.values()) == steps * len(HAND_OPS)
        assert sum(chip.seconds.values()) == pytest.approx(
            steps * sum(d for _, _, _, d in HAND_OPS) * 1e-9)
        assert dict(chip.unscoped) == pytest.approx(
            {"copy-done": steps * 60e-9, "all-reduce": steps * 10e-9,
             "fusion": steps * 5e-9})
        spans = trace.host_spans("shard_batch")
        assert [(s.start_ns, s.end_ns) for s in spans] \
            == [(150, 950), (10150, 11050)]
        assert len(trace.host_spans("ALLREDUCE::g")) == 1
        assert trace.host_spans("fsdp.py:129 shard_batch") == []

    def test_a_program_that_names_nothing_reads_as_none(self):
        from benchmark.harness import scopes
        space = _hand_space(scopes.xplane_pb2(), 1)
        for plane in space.planes:
            for meta in plane.event_metadata.values():
                del meta.stats[:]
                if meta.name.startswith("hvd::"):
                    meta.name = meta.name[5:]
        trace = scopes.ScopedTrace(space, _Summary(_Window(0, 1, 0, 20000)))
        for phase in scopes.PHASES[:-1]:
            assert trace.phase_ms_per_step(phase) is None
        assert trace.phase_ms_per_step("unscoped") > 0
        assert trace.host == []

    def test_span_and_gauge_readers_read_the_program(self, hvd, mesh4):
        """The two readers that ask the program itself, on what a run
        leaves behind; a name the program never wrote reads as None."""
        from benchmark.harness import metrics as bench_metrics
        from horovod_tpu import trace
        from horovod_tpu.optim import fused_allreduce_tree
        span_s = bench_metrics.load_reader(
            ROOT, "benchmark/metrics/readers/run_span_s.py")
        gauge = bench_metrics.load_reader(
            ROOT, "benchmark/metrics/readers/gauge_value.py")
        trace.reset()
        run = trace.run_tid()
        trace.add_span(run, "init.recorders", 10.0, 0.25)
        trace.add_span(run, "init.recorders", 12.0, 0.5)
        assert span_s({}, span="init.recorders") == 0.75
        assert span_s({}, span="import") is None
        trace.reset()
        jax.jit(jax.shard_map(
            lambda t: fused_allreduce_tree(t, op=hvd.Sum), mesh=mesh4,
            in_specs=P(), out_specs=P(), check_vma=False)).lower(
                {"a": jnp.ones((2048,), jnp.float32)})
        ctx = {"workload": {"chips": 4}}
        assert gauge(ctx, gauge="hvd_fused_allreduce_bytes", scale=1e-6) \
            == pytest.approx(2048 * 4 * 1e-6)
        assert gauge({"workload": {"chips": 3}},
                     gauge="hvd_fused_allreduce_bytes") is None
        assert gauge(ctx, gauge="no_such_gauge") is None

    def test_recorded_trace_reads_as_its_hand_numbers(self):
        """D11's least for the new reduction: two recorded steps of
        ``gpt2m_dp4`` (chips 0 and 1) against numbers worked out by the
        cutting tool's own code (``benchmark/tests/test_scopes.py`` holds
        the rest)."""
        import json
        from benchmark.harness import scopes, trace_reduce
        data = os.path.join(ROOT, "benchmark", "tests", "data")
        path = os.path.join(data, "gpt2m_dp4_2steps.scoped.pb.gz")
        with open(os.path.join(
                data, "gpt2m_dp4_2steps.scoped.expected.json")) as f:
            expected = json.load(f)
        summary = trace_reduce.TraceSummary(trace_reduce.load(path))
        trace = scopes.ScopedTrace(scopes.load_space(path), summary)
        for chip, window, want in zip(trace.chips, summary.chips,
                                      expected["chips"]):
            for phase in scopes.PHASES:
                assert chip.seconds[phase] == pytest.approx(
                    want["phase_ps"][phase] * 1e-12, rel=1e-4)
            assert chip.counts["reduce"] == 14 * expected["steps"]
            assert sum(chip.seconds.values()) == pytest.approx(
                window.busy_s(), rel=1e-4)
        assert len(trace.host_spans("shard_batch")) \
            == len(expected["host_spans"])


# --- the scope list over the four models (trace/scopes.py) ---------------

MODELS = ("gpt", "smallthinker", "nemotron_h", "afmoe")
SCOPE_LIKE = re.compile(r"^(lm|attn|ssm|moe|mlp|block|hvd)\.[a-z_]+$")
WRAPPED = re.compile(r"^(?:[A-Za-z_]+\()*([^()]*)\)*$")
LOC = re.compile(r'loc\("([^"]*)"')
# the scopes a model's step must carry, forward and backward, beside the
# three phases: the new ones of this list and the containers round them
MODEL_SCOPES = {
    "gpt": ("lm.model", "lm.embed", "lm.head", "block.norm", "attn.full",
            "attn.qkv", "attn.core", "attn.out", "mlp.dense"),
    "smallthinker": ("lm.model", "lm.embed", "lm.head", "block.norm",
                     "attn.full", "attn.window", "attn.qkv", "attn.rope",
                     "attn.core", "attn.out", "moe.route", "moe.experts"),
    "nemotron_h": ("lm.model", "lm.embed", "lm.head", "block.norm",
                   "attn.full", "attn.qkv", "attn.core", "attn.out",
                   "ssm.mixer", "ssm.in_proj", "ssm.conv", "ssm.scan",
                   "ssm.gate_norm", "ssm.out_proj", "moe.shared"),
    "afmoe": ("lm.model", "lm.embed", "lm.head", "block.norm",
              "block.post_norm", "mlp.dense", "attn.full", "attn.window",
              "attn.qkv", "attn.qk_norm", "attn.rope", "attn.core",
              "attn.gate", "attn.out", "moe.shared"),
}


def _tiny(which):
    """A tiny instance of one of the four models, flash kernels on (the
    Pallas interpreter on the CPU), as the cells build them."""
    if which == "gpt":
        from horovod_tpu.models.gpt import GPT, GPTConfig
        return GPT(GPTConfig.tiny(tp_axis=None, ep_axis=None,
                                  use_flash=True))
    if which == "smallthinker":
        from horovod_tpu.models.smallthinker import (SmallThinker,
                                                     SmallThinkerConfig)
        return SmallThinker(SmallThinkerConfig.tiny(use_flash=True))
    if which == "nemotron_h":
        from horovod_tpu.models.nemotron_h import NemotronH, NemotronHConfig
        return NemotronH(NemotronHConfig.tiny(use_flash=True))
    from horovod_tpu.models.afmoe import Afmoe, AfmoeConfig
    return Afmoe(AfmoeConfig.tiny(use_flash=True))


_lowerings = {}


def _lowered_step(which, scoped=True):
    """(StableHLO text without locations, the ``loc("...")`` paths) of the
    model's ``make_train_step`` lowered over one CPU device; with
    ``scoped=False`` ``jax.named_scope`` is a no-op meanwhile."""
    import contextlib
    from unittest import mock
    from horovod_tpu.optim import DistributedOptimizer
    from horovod_tpu.parallel import TrainState, make_train_step, moe
    if (which, scoped) in _lowerings:
        return _lowerings[which, scoped]
    model = _tiny(which)
    ids = jnp.zeros((2, 64), jnp.int32)

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["ids"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), batch["ids"][:, 1:]).mean()

    opt = DistributedOptimizer(optax.adamw(1e-3))
    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    patch = contextlib.nullcontext() if scoped else mock.patch.object(
        jax, "named_scope", lambda name: contextlib.nullcontext())
    # the two jitted conditionals of the expert layer keep their traces
    for fn in (moe._forward_where_they_fit, moe._backward_where_they_fit):
        fn.clear_cache()
    with patch:
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                ids[:1])["params"]
        state = jax.eval_shape(lambda p: TrainState.create(p, opt), params)
        low = make_train_step(loss_fn, opt, mesh, donate=False).lower(
            state, {"ids": ids})
        # op_name paths alone: a location may also name a source file
        out = low.as_text(), [
            p for p in LOC.findall(low.as_text(debug_info=True))
            if p.startswith("jit(hvd_dp_step)")]
    for fn in (moe._forward_where_they_fit, moe._backward_where_they_fit):
        fn.clear_cache()
    _lowerings[which, scoped] = out
    return out


def _parts(path):
    return [WRAPPED.sub(r"\1", p) for p in path.split("/")]


class TestScopeList:
    def test_the_list_is_sound(self):
        from horovod_tpu.trace import scopes
        names = [s.name for s in scopes.SCOPES]
        assert len(names) == len(set(names))
        for s in scopes.SCOPES:
            assert s.kind in ("leaf", "container", "rule"), s
            assert s.layer and s.holds
            assert (s.kind == "rule") == (s.under is not None), s
            if s.under is not None:
                assert scopes.KINDS[s.under] == "container"
        # a name outside the list, and a rule, cannot be entered
        for bad in ("attn.typo", "lm.loss"):
            with pytest.raises(ValueError, match="not a scope"):
                scopes.scope(bad)
        # none of the new names shadows a phase of harness/scopes.py
        from benchmark.harness import scopes as bench_scopes
        assert bench_scopes.phase_of(
            STEP + "hvd.loss_and_grad/jvp(GPT)/lm.model/layer_0/attn.full/"
            "attention/attn.qkv/qkv/dot_general") == "forward"

    @pytest.mark.parametrize("which", MODELS)
    def test_every_scope_is_on_an_op_forward_and_backward(self, which):
        _, paths = _lowered_step(which)
        for name in MODEL_SCOPES[which]:
            mine = [p for p in paths if name in _parts(p)]
            assert any("transpose(" not in p for p in mine), name
            assert any("transpose(" in p for p in mine), name
        # a scope round a custom_vjp site is on its backward rule's ops,
        # and one round an nn.remat site on the recomputed ones
        # (the flash kernels' backward rule, whose ops JAX marks
        # ``transpose(hvd.loss_and_grad)/jvp(<model>)/...``)
        bwd = [p for p in paths if "attention._attend" in _parts(p)
               and "transpose(hvd.loss_and_grad)" in p]
        assert bwd and all("attn.core" in _parts(p) for p in bwd)
        if which != "gpt":
            again = [p for p in paths if "checkpoint" in _parts(p)]
            assert again and all("lm.model" in _parts(p) for p in again)

    @pytest.mark.parametrize("which", MODELS)
    def test_every_scope_like_name_in_the_hlo_is_listed(self, which):
        from horovod_tpu.trace import scopes
        _, paths = _lowered_step(which)
        met = {p for path in paths for p in _parts(path)
               if SCOPE_LIKE.match(p)}
        assert met and met <= set(scopes.KINDS), met - set(scopes.KINDS)
        assert not any(scopes.KINDS[m] == "rule" for m in met)
        # what the rule calls the loss is there: under the phase, outside
        # the model
        assert any("hvd.loss_and_grad" in _parts(p)
                   and "lm.model" not in _parts(p) for p in paths)

    @pytest.mark.parametrize("which", MODELS)
    def test_scopes_leave_the_lowered_step_as_it_was(self, which):
        scoped, _ = _lowered_step(which)
        bare, paths = _lowered_step(which, scoped=False)
        assert not any(SCOPE_LIKE.match(p) for path in paths
                       for p in _parts(path))
        assert scoped == bare

    def test_no_literal_scope_outside_the_list_and_no_metric_beside_it(self):
        import json
        from horovod_tpu.trace import scopes
        literal = re.compile(r'named_scope\(\s*"')
        for dirpath, _, files in os.walk(os.path.join(ROOT, "horovod_tpu")):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(dirpath, f)) as fh:
                        assert not literal.search(fh.read()), f
        read = set()
        metrics_dir = os.path.join(ROOT, "benchmark", "metrics")
        for f in sorted(os.listdir(metrics_dir)):
            if not f.endswith(".json"):
                continue
            with open(os.path.join(metrics_dir, f)) as fh:
                args = json.load(fh).get("args", {})
            named = list(args.get("scopes", ()))
            if "scope_account" in f or args.get("part") in ("loss",):
                named.append("lm.loss")
            for name in named:
                assert name in scopes.KINDS, (f, name)
            read.update(named)
        # the leaves this PR's metrics are for
        assert {"lm.head", "lm.loss", "attn.qkv", "attn.out", "attn.rope",
                "attn.core", "ssm.in_proj", "ssm.gate_norm",
                "ssm.out_proj"} <= read


class TestCompileSpans:
    @pytest.fixture(autouse=True)
    def fresh_store(self):
        from horovod_tpu import trace
        trace.reset()
        yield
        trace.reset()

    @staticmethod
    def _compile_spans():
        from horovod_tpu import trace
        return [(s["name"], s["args"]["fun"])
                for s in trace.get(trace.run_tid())["spans"]
                if s["name"] in ("compile.trace", "compile.lower",
                                 "compile.backend")
                and s["args"]["fun"] == "hvd_dp_step"]

    def test_a_compiled_step_leaves_its_three_stages(self, hvd, mesh4):
        """``hvd.init`` installed the listener (tracing is armed in the
        tests): the step's first compile leaves one span a stage, a second
        call of the same shapes none, a new shape a second set."""
        from horovod_tpu.optim import DistributedOptimizer
        from horovod_tpu.parallel import TrainState, make_train_step
        opt = DistributedOptimizer(optax.adamw(1e-3))
        state = jax.device_put(TrainState.create(_params(), opt),
                               NamedSharding(mesh4, P()))
        step = make_train_step(_loss_fn, opt, mesh4, donate=False)
        batch = {"x": jnp.ones((8, 8), jnp.float32)}
        step(state, batch)
        stages = [("compile.trace", "hvd_dp_step"),
                  ("compile.lower", "hvd_dp_step"),
                  ("compile.backend", "hvd_dp_step")]
        assert self._compile_spans() == stages
        step(state, batch)
        assert self._compile_spans() == stages
        step(state, {"x": jnp.ones((16, 8), jnp.float32)})
        assert self._compile_spans() == stages * 2
        from horovod_tpu import trace
        spans = [s for s in trace.get(trace.run_tid())["spans"]
                 if s["name"].startswith("compile.")]
        assert all(s["dur"] >= 1e-3 for s in spans
                   if s["name"] != "compile.cache_load")
        assert len(spans) < 64           # the small ones were dropped

    def test_cache_load_is_the_child_of_its_backend_span(self):
        from horovod_tpu import trace
        from horovod_tpu.metrics import instruments
        instruments._on_compile_duration(instruments._CACHE_LOAD_EVENT, 0.25)
        instruments._on_compile_span(
            "/jax/core/compile/backend_compile_duration", 100.0, 100.75,
            fun_name="jit(hvd_dp_step)")
        instruments._on_compile_span(       # a fresh compile: no child
            "/jax/core/compile/backend_compile_duration", 101.0, 103.0,
            fun_name="other")
        instruments._on_compile_span(       # under a millisecond: dropped
            "/jax/core/compile/jaxpr_trace_duration", 104.0, 104.0005,
            fun_name="tiny")
        instruments._on_compile_span("/jax/core/some/other", 0.0, 9.0)
        tree = trace.tree(trace.run_tid())
        first, second = tree["children"]
        assert (first["name"], first["args"]) \
            == ("compile.backend", {"fun": "hvd_dp_step"})
        child, = first["children"]
        assert (child["name"], child["args"]) \
            == ("compile.cache_load", {"fun": "hvd_dp_step"})
        assert child["t0"] + child["dur"] == pytest.approx(100.75)
        assert child["dur"] == pytest.approx(0.25)
        assert second["args"] == {"fun": "other"} \
            and "children" not in second

    def test_disarmed_installs_nothing_and_leaves_no_span(self):
        code = ("import os\n"
                "import jax, jax.numpy as jnp\n"
                "from jax._src import monitoring\n"
                "import horovod_tpu as hvd\n"
                "from horovod_tpu import trace\n"
                "hvd.init()\n"
                "assert not trace.armed and trace.run_tid() is None\n"
                "assert monitoring.get_event_time_span_listeners() == []\n"
                "assert monitoring.get_event_duration_listeners() == []\n"
                "assert len(monitoring.get_event_listeners()) == 1\n"
                "jax.jit(lambda x: x * 2)(jnp.ones((4,)))\n"
                "assert trace.snapshot()['traces'] == []\n"
                "print('ok')\n")
        env = dict(os.environ, PYTHONPATH=ROOT, HOROVOD_TRACE="0",
                   JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=240)
        assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr

    def test_one_listener_site_in_the_package(self):
        hits = []
        for dirpath, _, files in os.walk(os.path.join(ROOT, "horovod_tpu")):
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(dirpath, f)
                    with open(path) as fh:
                        if re.search(r"register_event\w*_listener\(",
                                     fh.read()):
                            hits.append(os.path.relpath(path, ROOT))
        assert hits == ["horovod_tpu/metrics/instruments.py"]
