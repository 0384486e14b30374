"""Per-layer metrics, found by name: ``benchmark/metrics/<name>.json`` names
a reader file and its arguments; the reader's ``read(ctx, **args)`` returns
the number, or None where it finds nothing to read (the metric is then left
out of the line)."""

import importlib.util
import json
import os


def load_spec(root, name):
    with open(os.path.join(root, "benchmark", "metrics",
                           f"{name}.json")) as f:
        return json.load(f)


def load_reader(root, path):
    full = os.path.join(root, path)
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + os.path.basename(path)[:-3], full)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_all(root, entries, workload, ctx):
    """{name: {"value", "unit"}} for the manifest's per-layer ``entries``
    that list ``workload`` (or list no cells at all)."""
    out = {}
    for entry in entries:
        if workload not in entry.get("workloads", [workload]):
            continue
        spec = load_spec(root, entry["name"])
        value = load_reader(root, spec["reader"])(ctx, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out
