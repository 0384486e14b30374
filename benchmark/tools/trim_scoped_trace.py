#!/usr/bin/env python3
"""Cut a recorded trace down to a small one that keeps what
``harness/scopes.py`` reads: scope paths and the program's host spans.

    python3 benchmark/tools/trim_scoped_trace.py <trace dir or .xplane.pb[.gz]> <out prefix> [steps] [chips]

``trim_trace.py`` cuts event names to the instruction's name and drops every
stat, so its traces cannot tell where the program put an op. This one keeps,
of the first ``chips`` (default 2) TPU planes, the lines ``XLA Modules`` and
``XLA Ops`` for the first ``steps`` (default 2) runs of the step's module
plus the ops of the next run that start within 2 us (so that the window's
cut is exercised), each op's name cut to the instruction's name and, of its
metadata's stats, ``tf_op`` alone (the ``op_name`` path); and of the host
plane the ``hvd::`` events (the program's spans), none of the Python
tracer's. Writes ``<out prefix>.scoped.pb.gz`` (not ``.xplane.pb.gz``: the
test of ``trace_reduce`` takes every such file of ``tests/data`` for one of
``trim_trace.py``'s) and ``<out prefix>.scoped.expected.json``: what
``scopes.py`` has to give, worked out here straight from the protobuf with
regular expressions, by other code than ``scopes.py``'s attribution (only its
loader of the protobuf is shared), in picoseconds.
"""

import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
# The phases as patterns over the whole path (scopes.py walks the path's
# parts instead): first match wins. A scope is a whole part of the path:
# the backward pass of a custom_vjp reads ``hvd.loss_and_grad/transpose(
# hvd.loss_and_grad)/jvp(GPT)/...``, where the second is JAX's mark, no scope.
INNERMOST = r"/(?!.*/hvd\.)"
RULES = [
    ("reduce", re.compile(r"/hvd\.grad_exchange/(.*/)?hvd\.wire/[^/]*$")),
    ("bookkeeping", re.compile(r"/hvd\.grad_exchange" + INNERMOST)),
    ("optimizer", re.compile(r"/hvd\.optimizer" + INNERMOST)),
    ("backward", re.compile(r"/hvd\.loss_and_grad" + INNERMOST
                            + r".*transpose\(")),
    ("forward", re.compile(r"/hvd\.loss_and_grad" + INNERMOST)),
]


def phase(path):
    path = path.split(";")[0]
    for name, rx in RULES:
        if rx.search(path):
            return name
    return "unscoped"


def at_ps(line, event):
    """An event's start on the trace's one clock, in picoseconds."""
    return line.timestamp_ns * 1000 + event.offset_ps


def main(argv):
    from benchmark.harness import scopes, trace_reduce
    path, prefix = argv[1], argv[2]
    steps = int(argv[3]) if len(argv) > 3 else 2
    chips = int(argv[4]) if len(argv) > 4 else 2
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    space = scopes.load_space(path)
    out, expected = scopes.xplane_pb2().XSpace(), {"steps": steps,
                                                   "chips": []}
    last_cut_ps = 0
    for plane in sorted(space.planes, key=lambda p: p.name):
        m = DEVICE.match(plane.name)
        if not m or int(m.group(1)) >= chips:
            continue
        lines = {line.name: line for line in plane.lines}
        tf_op = next(k for k, v in plane.stat_metadata.items()
                     if v.name == "tf_op")
        modules, ops = lines["XLA Modules"], lines["XLA Ops"]
        runs = sorted(modules.events, key=lambda e: e.offset_ps)
        longest = max(runs, key=lambda e: e.duration_ps).metadata_id
        runs = [e for e in runs if e.metadata_id == longest]
        cut = at_ps(modules, runs[steps])
        last_cut_ps = max(last_cut_ps, cut)
        new = out.planes.add(id=plane.id, name=plane.name)
        new.stat_metadata[tf_op].CopyFrom(plane.stat_metadata[tf_op])
        used = set()
        for line in (modules, ops):
            kept = new.lines.add(id=line.id, name=line.name,
                                 timestamp_ns=line.timestamp_ns)
            limit = cut + 2_000_000 if line is ops else cut
            for e in line.events:
                if at_ps(line, e) < limit and not (
                        line is modules
                        and at_ps(line, e) + e.duration_ps > cut):
                    kept.events.add(metadata_id=e.metadata_id,
                                    offset_ps=e.offset_ps,
                                    duration_ps=e.duration_ps)
                    used.add(e.metadata_id)
        paths = {}
        for k in used:
            meta = plane.event_metadata[k]
            new.event_metadata[k].id = meta.id
            new.event_metadata[k].name = meta.name.split(" = ")[0]
            for stat in meta.stats:
                if stat.metadata_id == tf_op:
                    new.event_metadata[k].stats.add().CopyFrom(stat)
                    paths[k] = (stat.str_value or plane.stat_metadata[
                        stat.ref_value].name)[:-1]    # "<op_name>:"
        start = at_ps(modules, runs[0])
        end = at_ps(modules, runs[steps - 1]) + runs[steps - 1].duration_ps
        chip = {"plane": plane.name, "ops_inside": 0,
                "run_starts_ps": [at_ps(modules, r) for r in runs[:steps]],
                "run_ends_ps": [at_ps(modules, r) + r.duration_ps
                                for r in runs[:steps]],
                "phase_ps": {}, "phase_ops": {}, "collectives": []}
        for e in ops.events:
            if at_ps(ops, e) < start or at_ps(ops, e) + e.duration_ps > end:
                continue
            p = phase(paths.get(e.metadata_id, ""))
            chip["ops_inside"] += 1
            chip["phase_ps"][p] = chip["phase_ps"].get(p, 0) + e.duration_ps
            chip["phase_ops"][p] = chip["phase_ops"].get(p, 0) + 1
            if p == "reduce":
                chip["collectives"].append(plane.event_metadata[
                    e.metadata_id].name.split(" = ")[0].lstrip("%"))
        expected["chips"].append(chip)
    host_spans = []
    for plane in space.planes:
        if plane.name != "/host:CPU":
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        mine = {k for k, v in plane.event_metadata.items()
                if v.name.startswith("hvd::")}
        for k in mine:
            new.event_metadata[k].id = k
            new.event_metadata[k].name = plane.event_metadata[k].name
        for line in plane.lines:
            events = [e for e in line.events if e.metadata_id in mine
                      and at_ps(line, e) < last_cut_ps]
            if not events:
                continue
            kept = new.lines.add(id=line.id, name=line.name,
                                 timestamp_ns=line.timestamp_ns)
            for e in events:
                kept.events.add(metadata_id=e.metadata_id,
                                offset_ps=e.offset_ps,
                                duration_ps=e.duration_ps)
                host_spans.append({
                    "name": plane.event_metadata[e.metadata_id].name,
                    "start_ps": at_ps(line, e),
                    "duration_ps": e.duration_ps})
    expected["host_spans"] = sorted(host_spans, key=lambda s: s["start_ps"])
    data = gzip.compress(out.SerializeToString(), 9)
    os.makedirs(os.path.dirname(os.path.abspath(prefix)), exist_ok=True)
    with open(prefix + ".scoped.pb.gz", "wb") as f:
        f.write(data)
    with open(prefix + ".scoped.expected.json", "w") as f:
        json.dump(expected, f, indent=1)
    print(f"{prefix}.scoped.pb.gz: {len(data)} bytes; "
          f"{json.dumps(expected)[:900]}")


if __name__ == "__main__":
    main(sys.argv)
