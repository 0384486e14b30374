"""From a profiler trace (``*.xplane.pb``) to what the per-layer metrics
read. Code of the benchmark: every PR computes the same number the same
way.

A TPU trace holds one plane per chip (``/device:TPU:<n>``). On it the line
``XLA Ops`` carries one event per executed HLO instruction (a Mosaic kernel
appears under the name given to its ``pallas_call``), and ``XLA Modules``
one event per run of a compiled program. Host threads are other planes and
are not read here (host spans are the ``tracing`` issue's).

The traced window of a chip runs from the start of the first run of the
step's module that the trace holds whole to the end of the last; ops
outside it (the reads of the loss, a step cut by the profiler's start) are
dropped.
"""

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast")
_SUFFIX = re.compile(r"([.\-_]\d+)+$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def op_name(event_name):
    """The instruction's name out of an event's name, which on a TPU is the
    whole HLO line: ``%fusion.19 = (f32[...`` -> ``fusion.19``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def opcode(event_name):
    """The instruction's opcode out of the same line: the first lower-case
    word that opens a bracket after the result's shape (``%psum.197 =
    f32[51511296]{0:T(1024)} all-reduce(f32[...`` -> ``all-reduce``); None
    where the event's name is not an HLO line."""
    line = event_name.split(" = ", 1)
    found = _OPCODE.search(" " + line[1]) if len(line) == 2 else None
    return found.group(1) if found else None


class Op(collections.namedtuple("Op", "name start_ns end_ns opcode",
                                defaults=(None,))):
    @property
    def seconds(self):
        return (self.end_ns - self.start_ns) * 1e-9

    @property
    def is_collective(self):
        """By the instruction's opcode or its name, whichever says so: XLA
        leaves an all-reduce that it does not combine under the JAX
        primitive's name (``psum.197``), and wraps some collectives in an
        ``async-start`` named after them."""
        return bool(COLLECTIVE.search(self.name)
                    or COLLECTIVE.search(self.opcode or ""))


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    """``ProfileData`` of an ``.xplane.pb`` file (or of a gzipped one, as the
    tests' small recorded trace is kept)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _union_seconds(intervals):
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total * 1e-9


def _subtract_seconds(intervals, others):
    """Length of ``intervals`` (their union) not covered by ``others``."""
    both = _union_seconds(list(intervals) + list(others))
    return both - _union_seconds(others)


def group_name(name):
    """``fusion.123`` -> ``fusion``: ops of one kind under one name."""
    return _SUFFIX.sub("", name) or name


class Chip:
    """One chip's ops inside its traced window."""

    def __init__(self, index, ops, modules, step_module):
        self.index = index
        runs = sorted(m for m in modules if m.name == step_module)
        if not runs:
            raise ValueError(f"chip {index}: no run of {step_module!r}")
        self.start_ns, self.end_ns = runs[0].start_ns, runs[-1].end_ns
        self.steps = len(runs)
        self.ops = [o for o in ops
                    if o.start_ns >= self.start_ns
                    and o.end_ns <= self.end_ns]

    @property
    def window_s(self):
        return (self.end_ns - self.start_ns) * 1e-9

    def busy_s(self):
        return _union_seconds((o.start_ns, o.end_ns) for o in self.ops)

    def seconds_of(self, pattern):
        rx = re.compile(pattern)
        return sum(o.seconds for o in self.ops if rx.search(o.name))

    def collective_exposed_s(self):
        coll, rest = [], []
        for o in self.ops:
            (coll if o.is_collective else rest).append(
                (o.start_ns, o.end_ns))
        return _subtract_seconds(coll, rest) if coll else 0.0

    def gaps(self):
        """Idle gaps (seconds, name of the op before the gap)."""
        out, end, last = [], self.start_ns, "window_start"
        for o in sorted(self.ops, key=lambda o: o.start_ns):
            if o.start_ns > end:
                out.append(((o.start_ns - end) * 1e-9, last, o.name))
            if o.end_ns > end:
                end, last = o.end_ns, o.name
        if self.end_ns > end:
            out.append(((self.end_ns - end) * 1e-9, last, "window_end"))
        return out


class TraceSummary:
    """The chips of one trace; means over chips are what metrics report."""

    def __init__(self, profile, step_module=None):
        self.chips = []
        for plane in profile.planes:
            m = DEVICE_PLANE.match(plane.name)
            if not m:
                continue
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines or MODULES_LINE not in lines:
                continue
            ops = [Op(op_name(e.name), int(e.start_ns),
                      int(e.start_ns + e.duration_ns), opcode(e.name))
                   for e in lines[OPS_LINE].events]
            modules = [Op(e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                       for e in lines[MODULES_LINE].events]
            if not ops:
                continue
            module = step_module or _longest_module(modules)
            self.chips.append(Chip(int(m.group(1)), ops, modules, module))
        if not self.chips:
            raise ValueError("the trace holds no TPU plane with ops")

    def _mean(self, f):
        return sum(f(c) for c in self.chips) / len(self.chips)

    @property
    def steps(self):
        return min(c.steps for c in self.chips)

    def window_s(self):
        return self._mean(lambda c: c.window_s)

    def busy_s(self):
        return self._mean(Chip.busy_s)

    def seconds_of(self, pattern):
        return self._mean(lambda c: c.seconds_of(pattern))

    def collective_exposed_s(self):
        return self._mean(Chip.collective_exposed_s)

    def breakdown(self, top=10):
        ops = collections.Counter()
        gaps = collections.Counter()
        for c in self.chips:
            for o in c.ops:
                # a collective under what it is: ``psum.197`` with the
                # ``all-reduce.N`` it is one of
                kind = o.opcode if o.is_collective and o.opcode else o.name
                ops[group_name(kind)] += o.seconds / len(self.chips)
            for seconds, before, after in c.gaps():
                gaps[f"after:{group_name(before)}"] += \
                    seconds / len(self.chips)
        return {"device_ops": [[n, s] for n, s in ops.most_common(top)],
                "idle_gaps": [[n, s] for n, s in gaps.most_common(top)]}


def _longest_module(modules):
    """The module that took most device time: the training step."""
    total = collections.Counter()
    for m in modules:
        total[m.name] += m.end_ns - m.start_ns
    if not total:
        raise ValueError("no module runs in the trace")
    return total.most_common(1)[0][0]


def describe(profile, limit=40):
    """Planes, lines and the commonest event names with their stats: what
    to look at by hand before trusting the reduction."""
    out = []
    for plane in profile.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            seen = collections.Counter()
            sample = {}
            for e in events:
                seen[e.name] += e.duration_ns
                sample.setdefault(e.name, e)
            for name, ns in seen.most_common(limit):
                stats = {k: (str(v)[:120]) for k, v in sample[name].stats}
                out.append(f"    {ns * 1e-6:10.3f} ms  {name[:100]!r}  "
                           f"{stats}")
    return "\n".join(out)
