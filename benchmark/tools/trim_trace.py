#!/usr/bin/env python3
"""Cut a recorded trace down to the small one the tests keep.

    python3 benchmark/tools/trim_trace.py <trace dir or .xplane.pb> <out prefix> [steps]

Keeps, of every TPU plane, the lines the reduction reads (``Steps``,
``XLA Modules``, ``XLA Ops``, ``Async XLA Ops``) for the first ``steps``
(default 2) runs of the step's module plus the few ops of the next run that
start within 2 us, so that the window's cut is exercised; event names (on
a TPU the whole HLO line) are cut to ``<instruction> = <opcode>(``, the
result's shape and the operands left out. Writes ``<out prefix>.xplane.pb.gz``
and ``<out prefix>.expected.json``: the numbers the reduction has to give,
worked out here straight from the protobuf, by other code than the
reduction's: a collective is here what the profiler's own ``hlo_category``
of the instruction calls one (the reduction reads the opcode out of the
line). Needs tensorflow's ``xplane_pb2`` (a tool, not part of a run).
"""

import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

KEEP = ("Steps", "XLA Modules", "XLA Ops", "Async XLA Ops")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter"
                        r"|collective-permute|all-to-all")
CATEGORY_STAT = "hlo_category"


def cut_line(line):
    """``%psum.197 = f32[8]{0:T(8)} all-reduce(f32[8] %x), ...`` ->
    ``%psum.197 = all-reduce(``: the shape ends at the first space outside
    every bracket, the opcode at the bracket after it."""
    name, _, rest = line.partition(" = ")
    depth = 0
    for i, c in enumerate(rest):
        depth += (c in "([{") - (c in ")]}")
        if c == " " and depth == 0:
            return f"{name} = {rest[i + 1:].split('(', 1)[0]}("
    return name


def categories(plane):
    """{event metadata id: the profiler's ``hlo_category``}."""
    wanted = {k for k, m in plane.stat_metadata.items()
              if m.name == CATEGORY_STAT}
    out = {}
    for key, meta in plane.event_metadata.items():
        for stat in meta.stats:
            if stat.metadata_id in wanted:
                out[key] = stat.str_value or \
                    plane.stat_metadata[stat.ref_value].name
    return out


def union(intervals):
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total, end = total + b - a, b
        elif b > end:
            total, end = total + b - end, b
    return total


def main(argv):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    from benchmark.harness import trace_reduce
    path, prefix = argv[1], argv[2]
    steps = int(argv[3]) if len(argv) > 3 else 2
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out, expected = xplane_pb2.XSpace(), {"chips": []}
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        runs = sorted(lines["XLA Modules"].events, key=lambda e: e.offset_ps)
        longest = max(runs, key=lambda e: e.duration_ps).metadata_id
        runs = [e for e in runs if e.metadata_id == longest]
        cut = runs[steps].offset_ps
        new = out.planes.add(id=plane.id, name=plane.name)
        for k, v in plane.stat_metadata.items():
            new.stat_metadata[k].CopyFrom(v)
        used = set()
        for name in KEEP:
            if name not in lines:
                continue
            line = lines[name]
            kept = new.lines.add(id=line.id, name=line.name,
                                 timestamp_ns=line.timestamp_ns)
            for e in line.events:
                limit = cut + 2_000_000 if name.endswith("XLA Ops") else cut
                if e.offset_ps < limit and not (
                        name in ("Steps", "XLA Modules")
                        and e.offset_ps + e.duration_ps > cut):
                    kept.events.add().CopyFrom(e)
                    used.add(e.metadata_id)
        for k in used:
            meta = plane.event_metadata[k]
            new.event_metadata[k].id = meta.id
            new.event_metadata[k].name = cut_line(meta.name)
        start = runs[0].offset_ps
        end = runs[steps - 1].offset_ps + runs[steps - 1].duration_ps
        category = categories(plane)
        ops = [(e.offset_ps, e.offset_ps + e.duration_ps,
                plane.event_metadata[e.metadata_id].name.split(" = ")[0],
                bool(COLLECTIVE.search(category.get(e.metadata_id, ""))))
               for e in lines["XLA Ops"].events if e.offset_ps < cut
               + 2_000_000]
        inside = [o for o in ops if o[0] >= start and o[1] <= end]
        coll = [(a, b) for a, b, _, c in inside if c]
        rest = [(a, b) for a, b, _, c in inside if not c]
        expected["chips"].append({
            "plane": plane.name, "steps": steps, "window_ps": end - start,
            "busy_ps": union((a, b) for a, b, _, _ in inside),
            "flash_ps": sum(b - a for a, b, n, _ in inside
                            if "hvd_flash_" in n),
            "flash_calls": sum(1 for _, _, n, _ in inside
                               if "hvd_flash_" in n),
            "collectives": sorted(n for _, _, n, c in inside if c),
            "collective_ps": sum(b - a for a, b in coll),
            "collective_exposed_ps": union(coll + rest) - union(rest),
            "ops_total": len(ops), "ops_inside": len(inside)})
    data = gzip.compress(out.SerializeToString(), 9)
    os.makedirs(os.path.dirname(os.path.abspath(prefix)), exist_ok=True)
    with open(prefix + ".xplane.pb.gz", "wb") as f:
        f.write(data)
    with open(prefix + ".expected.json", "w") as f:
        json.dump(expected, f, indent=1)
    print(f"{prefix}.xplane.pb.gz: {len(data)} bytes; "
          f"{json.dumps(expected)[:600]}")


if __name__ == "__main__":
    main(sys.argv)
