"""A run's profiler trace by what the program named from inside: device ops
by the ``hvd.*`` scope on their ``op_name`` (``jax.named_scope`` in
``parallel/dp.py``, ``optim/optimizer.py``, ``ops/in_jit.py``) and the
program's host spans (``horovod_tpu.trace.span`` enters a profiler
annotation ``hvd::<name>``). Code of the benchmark beside
``trace_reduce.py``, which reads the same file by instruction name.

Where an op's path lives (the look by hand: PERF.md section 3): not in the
event. On a TPU the name of an ``XLA Ops`` event is the HLO line without
its ``metadata={...}``, and the event's own stats are its device offset and
duration; the path is the stat ``tf_op`` of the event's *metadata* record
(``jit(hvd_dp_step)/shard_map/hvd.optimizer/hvd.grad_exchange/bucket3/
hvd.wire/psum:``, the HLO instruction's ``op_name`` and a colon).
``jax.profiler.ProfileData`` shows an event's own stats only, so this file
reads the protobuf itself, with the message classes tensorflow ships
(``tsl/profiler/protobuf/xplane_pb2.py``, loaded by path: tensorflow itself
is not imported). Host threads are the lines of the plane ``/host:CPU``;
an annotation is an event on the line of the thread that entered it (the
main thread: ``python3``), named as given, beside the Python tracer's own
``$file:line function`` events. Times of all planes are on one clock: an
event starts at its line's ``timestamp_ns`` plus its ``offset_ps``, which
is what ``ProfileData`` calls ``start_ns``.

An op belongs to the innermost ``hvd.*`` scope on its path:

    forward      under ``hvd.loss_and_grad``, not marked ``transpose(``
    backward     under ``hvd.loss_and_grad``, marked ``transpose(`` (JAX's
                 own mark on the ops of the backward pass)
    optimizer    innermost scope ``hvd.optimizer``
    reduce       ``hvd.wire`` inside ``hvd.grad_exchange``: the collective,
                 whatever XLA calls it (``all-reduce.7``, ``psum.197``)
    bookkeeping  under ``hvd.grad_exchange`` outside ``hvd.wire`` (pack,
                 unpack, the division of Average, compression)
    unscoped     no ``hvd.*`` scope, no path at all (ops the compiler made
                 and gave no metadata), or ``hvd.wire`` outside the
                 exchange (the mean of the loss)

A fusion that spans two scopes goes where its own ``op_name`` says; of a
path joined with ``;`` the first part counts. Each op is counted once, by
its own duration, inside the window ``trace_reduce`` fixed for its chip.
A program that names nothing (the parent of the PR that brought this file)
gives ``unscoped`` alone, and every reader built on this returns None.
"""

import collections
import gzip
import importlib.util
import os

from benchmark.harness import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PHASES = ("forward", "backward", "optimizer", "reduce", "bookkeeping",
          "unscoped")
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "hvd::"
PATH_STAT = "tf_op"

HostSpan = collections.namedtuple("HostSpan", "name start_ns end_ns")


def phase_of(path):
    """The phase of an op from its ``op_name`` path (None or "" where the
    op has none)."""
    path = (path or "").split(";")[0]
    scopes = [p for p in path.split("/") if p.startswith("hvd.")]
    if not scopes:
        return "unscoped"
    inner = scopes[-1]
    if inner == "hvd.loss_and_grad":
        marked = "transpose(" in path.split("hvd.loss_and_grad", 1)[1]
        return "backward" if marked else "forward"
    if inner == "hvd.optimizer":
        return "optimizer"
    if "hvd.grad_exchange" in scopes:
        return "reduce" if inner == "hvd.wire" else "bookkeeping"
    return "unscoped"


class ChipScopes:
    """One chip's device seconds by phase, inside its traced window.
    ``ops`` are (instruction name, path or None, start_ns, end_ns)."""

    def __init__(self, chip, ops):
        self.index, self.steps = chip.index, chip.steps
        self.seconds = dict.fromkeys(PHASES, 0.0)
        self.counts = dict.fromkeys(PHASES, 0)
        self.unscoped = collections.Counter()   # kind of op -> seconds
        for name, path, start_ns, end_ns in ops:
            if start_ns < chip.start_ns or end_ns > chip.end_ns:
                continue
            phase = phase_of(path)
            seconds = (end_ns - start_ns) * 1e-9
            self.seconds[phase] += seconds
            self.counts[phase] += 1
            if phase == "unscoped":
                self.unscoped[trace_reduce.group_name(name)] += seconds


class ScopedTrace:
    """The chips of ``summary`` (a ``trace_reduce.TraceSummary`` of the same
    file) with their ops by phase, and the ``hvd::`` events of the host
    plane. ``space`` is the trace's ``XSpace`` message."""

    def __init__(self, space, summary):
        windows = {c.index: c for c in summary.chips}
        self.chips, self.host = [], []
        for plane in space.planes:
            m = trace_reduce.DEVICE_PLANE.match(plane.name)
            if m and int(m.group(1)) in windows:
                for line in plane.lines:
                    if line.name == trace_reduce.OPS_LINE:
                        self.chips.append(ChipScopes(
                            windows[int(m.group(1))],
                            _device_ops(plane, line)))
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    self.host.extend(_host_spans(plane, line))
        self.chips.sort(key=lambda c: c.index)
        self.host.sort(key=lambda s: s.start_ns)

    def phase_ms_per_step(self, phase):
        """Mean over chips of the phase's device milliseconds a step; None
        where no op of any chip lies in it."""
        if not any(c.counts[phase] for c in self.chips):
            return None
        return 1e3 * sum(c.seconds[phase] / c.steps
                         for c in self.chips) / len(self.chips)

    def host_spans(self, name):
        return [s for s in self.host if s.name == name]


def _times_ns(line, event):
    """(start, end) in whole nanoseconds, as ``trace_reduce`` takes them
    from ``ProfileData``."""
    start = line.timestamp_ns + event.offset_ps / 1000
    return int(start), int(start + event.duration_ps / 1000)


def _paths(plane):
    """{event metadata id: op_name path} of a device plane."""
    wanted = {k for k, m in plane.stat_metadata.items()
              if m.name == PATH_STAT}
    out = {}
    for key, meta in plane.event_metadata.items():
        for stat in meta.stats:
            if stat.metadata_id in wanted:
                value = stat.str_value or \
                    plane.stat_metadata[stat.ref_value].name
                out[key] = value.rsplit(":", 1)[0]   # "<op_name>:<type>"
    return out


def _device_ops(plane, line):
    paths = _paths(plane)
    names = {k: trace_reduce.op_name(m.name)
             for k, m in plane.event_metadata.items()}
    for e in line.events:
        yield (names[e.metadata_id], paths.get(e.metadata_id),
               *_times_ns(line, e))


def _host_spans(plane, line):
    names = {k: m.name[len(HOST_PREFIX):]
             for k, m in plane.event_metadata.items()
             if m.name.startswith(HOST_PREFIX)}
    for e in line.events:
        if e.metadata_id in names:
            yield HostSpan(names[e.metadata_id], *_times_ns(line, e))


def xplane_pb2():
    """tensorflow's generated ``xplane_pb2``, loaded from its file so that
    tensorflow itself is not imported into a process that holds the chip
    (the module needs ``google.protobuf`` alone)."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.origin:
        raise ImportError("no tensorflow installation to take "
                          "xplane_pb2.py from")
    path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("bench_xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_space(path):
    """The ``XSpace`` of an ``.xplane.pb`` file (or a gzipped one)."""
    space = xplane_pb2().XSpace()
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


_loaded = {}


def of_run(ctx):
    """The ``ScopedTrace`` of the run ``ctx`` describes, read once from
    ``benchmark_out/trace/<cell>``; None for a run without a trace."""
    if ctx.get("trace") is None:
        return None
    trace_dir = os.path.join(ROOT, "benchmark_out", "trace",
                             ctx["workload"]["name"])
    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    if path not in _loaded:
        _loaded.clear()
        _loaded[path] = ScopedTrace(load_space(path), ctx["trace"])
    return _loaded[path]
