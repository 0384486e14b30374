"""``BENCHMARK.json``: loading it, and every rule of its form that is known,
checked on the CPU before any chip time is spent
(``python benchmark/run.py --check-manifest``)."""

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_LEVEL = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head_",
               "n_embd", "n_inner", "expan", "per_tok", "top_k", "topk",
               "headdim", "d_head", "d_ssm", "d_conv", "d_model", "d_ff",
               "d_inner", "kv_channels")
# A key that counts layers is a depth, whatever words it carries
# (``num_hidden_layers``, ``n_layer``): the first cut of a configuration.
DEPTH = re.compile(r"(^|_)layers?$")
CHECK_ALLOWANCE_S = 43200


def load(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json; "
                   f"known: {[e['name'] for e in entries]}")


def _line(text, what, problems, limit=200):
    if not isinstance(text, str) or not 1 <= len(text) <= limit \
            or "\n" in text or "\t" in text:
        problems.append(f"{what}: must be 1 to {limit} characters on one "
                        f"line with no tab")


def _keys(obj, required, optional, what, problems):
    extra = set(obj) - set(required) - set(optional)
    missing = set(required) - set(obj)
    if extra:
        problems.append(f"{what}: keys not allowed: {sorted(extra)}")
    if missing:
        problems.append(f"{what}: keys missing: {sorted(missing)}")
    return not missing


def names_a_width(key):
    """Whether ``reduced`` may not list ``key``: a hidden, intermediate,
    latent, state or projection size, a head size, an expansion factor, the
    experts per token, a key that ends in ``_dim`` or ``_rank``. Depth, the
    number of heads or experts and the vocabulary may be cut."""
    if DEPTH.search(key):
        return False
    return key.endswith(("_dim", "_rank")) or any(
        w in key for w in WIDTH_WORDS)


def check(manifest, root):
    """Every breach of the manifest's rules found, as a list of lines (empty
    when the manifest is sound)."""
    p = []
    if set(manifest) != TOP_LEVEL:
        p.append(f"top level: keys must be exactly {sorted(TOP_LEVEL)}, got "
                 f"{sorted(manifest)}")
        return p
    size = len(json.dumps(manifest))
    if os.path.isfile(os.path.join(root, "BENCHMARK.json")):
        size = os.path.getsize(os.path.join(root, "BENCHMARK.json"))
    if size > 64 << 10:
        p.append("BENCHMARK.json is over 64 KiB")

    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16:
        p.append("paths: 1 to 16 directories")
    for d in paths:
        if not PATH.match(d) or d.startswith("/") or ".." in d.split("/"):
            p.append(f"paths: {d!r} is not a relative path inside the repo")
        elif not os.path.isdir(os.path.join(root, d)):
            p.append(f"paths: directory {d!r} does not exist")

    def under_paths(f):
        return any(f == d or f.startswith(d.rstrip("/") + "/")
                   for d in paths)

    cmd = manifest["command"]
    if not isinstance(cmd, list) or not 1 <= len(cmd) <= 32:
        p.append("command: a list of 1 to 32 strings")
    for word in cmd:
        _line(word, f"command word {word!r}", p)
        if word.startswith("/") or ".." in word.split("/"):
            p.append(f"command: {word!r} leads out of the repo")
        elif "/" in word and not under_paths(word):
            p.append(f"command: {word!r} is not under paths")

    secs = manifest["run_seconds"]
    if not isinstance(secs, int) or not 1 <= secs <= 51:
        p.append("run_seconds: a whole number from 1 to 51")
    else:
        cells = 24
        need = (2 + 14 * cells) * (secs + 60) + cells * 2 * 90 + 1200
        if need > CHECK_ALLOWANCE_S:
            p.append(f"run_seconds {secs}: a full check of 24 cells needs "
                     f"{need} s, over {CHECK_ALLOWANCE_S}")

    names = set()

    def name_ok(value, what):
        if not isinstance(value, str) or not NAME.match(value):
            p.append(f"{what}: {value!r} must be 1 to 64 characters from "
                     f"letters, digits, '_', '.' and '-', starting with a "
                     f"letter, digit or '_'")
            return False
        return True

    def unique(value, what):
        if value in names:
            p.append(f"{what}: name {value!r} is used twice")
        names.add(value)

    configs = manifest["configs"]
    if not 1 <= len(configs) <= 24:
        p.append("configs: 1 to 24")
    files = set()
    for c in configs:
        what = f"config {c.get('name')!r}"
        if not _keys(c, ("name", "source", "file", "reduced", "why"), (),
                     what, p):
            continue
        name_ok(c["name"], what)
        unique(("config", c["name"]), what)
        _line(c["source"], f"{what} source", p)
        _line(c["why"], f"{what} why", p)
        if not under_paths(c["file"]) or not PATH.match(c["file"]):
            p.append(f"{what}: file {c['file']!r} is not under paths")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            p.append(f"{what}: file {c['file']!r} does not exist")
        if c["file"] in files:
            p.append(f"{what}: file {c['file']!r} is another config's too")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            p.append(f"{what}: reduced has over 16 keys")
        for key in c["reduced"]:
            name_ok(key, f"{what} reduced key")
            if names_a_width(key):
                p.append(f"{what}: reduced names a width: {key!r}")

    cells = manifest["workloads"]
    if not 1 <= len(cells) <= 24:
        p.append("workloads: 1 to 24")
    pairs = set()
    for w in cells:
        what = f"workload {w.get('name')!r}"
        if not _keys(w, ("name", "config", "traffic", "chips", "why"), (),
                     what, p):
            continue
        name_ok(w["name"], what)
        name_ok(w["traffic"], f"{what} traffic")
        unique(("workload", w["name"]), what)
        _line(w["why"], f"{what} why", p)
        if w["chips"] not in (1, 4):
            p.append(f"{what}: chips must be 1 or 4")
        if w["config"] not in {c.get("name") for c in configs}:
            p.append(f"{what}: unknown config {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            p.append(f"{what}: the pair of config and traffic appears twice")
        pairs.add((w["config"], w["traffic"]))
        cell_file = os.path.join(root, "benchmark", "workloads",
                                 f"{w['name']}.json")
        if not os.path.isfile(cell_file):
            p.append(f"{what}: benchmark/workloads/{w['name']}.json "
                     f"does not exist")
        else:
            with open(cell_file) as f:
                data = json.load(f)
            for key in ("config", "chips"):
                if data.get(key) != w[key]:
                    p.append(f"{what}: its file says {key} "
                             f"{data.get(key)!r}, the manifest {w[key]!r}")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        p.append(f"workloads: {four} cells ask for 4 chips, at most "
                 f"{max(1, len(cells) // 4)} may")
    for c in configs:
        if c.get("name") not in {w.get("config") for w in cells}:
            p.append(f"config {c.get('name')!r} is used by no cell")

    cell_names = [w.get("name") for w in cells]
    e2e = manifest["end_to_end"]
    if not 1 <= len(e2e) <= 16:
        p.append("end_to_end: 1 to 16 metrics")
    reports = {n: set() for n in cell_names}   # cell -> e2e metrics
    for m in e2e:
        what = f"end_to_end metric {m.get('name')!r}"
        if not _keys(m, ("name", "unit", "better", "bound", "source"),
                     ("workloads",), what, p):
            continue
        name_ok(m["name"], what)
        unique(("metric", m["name"]), what)
        if not UNIT.match(str(m["unit"])):
            p.append(f"{what}: unit {m['unit']!r} must be 1 to 16 of "
                     f"letters, digits, '_', '/', '%', '.', '-'")
        if m["better"] not in ("lower", "higher"):
            p.append(f"{what}: better must be lower or higher")
        if m["source"] not in ("host_clock", "device_trace"):
            p.append(f"{what}: source must be host_clock or device_trace")
        if not isinstance(m["bound"], (int, float)) \
                or not 0.01 <= m["bound"] <= 0.1:
            p.append(f"{what}: bound must be from 0.01 to 0.1")
        for n in m.get("workloads", cell_names):
            if n not in reports:
                p.append(f"{what}: unknown workload {n!r}")
            else:
                reports[n].add(m["name"])
    if "setup_s" not in {m.get("name") for m in e2e}:
        p.append("end_to_end: setup_s is missing")

    per_layer = manifest["per_layer"]
    if not 1 <= len(per_layer) <= 128:
        p.append("per_layer: 1 to 128 metrics")
    has_layer = {n: False for n in cell_names}
    for m in per_layer:
        what = f"per_layer metric {m.get('name')!r}"
        if not _keys(m, ("name", "unit", "better", "source", "layer",
                         "moves"), ("workloads",), what, p):
            continue
        name_ok(m["name"], what)
        unique(("metric", m["name"]), what)
        if not UNIT.match(str(m["unit"])):
            p.append(f"{what}: unit {m['unit']!r} must be 1 to 16 of "
                     f"letters, digits, '_', '/', '%', '.', '-'")
        if m["better"] not in ("lower", "higher"):
            p.append(f"{what}: better must be lower or higher")
        if m["source"] not in SOURCES:
            p.append(f"{what}: source must be one of {SOURCES}")
        if not isinstance(m["layer"], str) or not NAME.match(m["layer"]):
            p.append(f"{what}: layer must be 1 to 64 characters from "
                     f"letters, digits, '_', '.' and '-', starting with a "
                     f"letter, digit or '_', not {m['layer']!r}")
        if "roofline" in m["name"] and (
                not m["name"].endswith("_roofline") or m["unit"] != "%"):
            p.append(f"{what}: a roofline share is named <kernel>_roofline "
                     f"with the unit %")
        for n in m.get("workloads", cell_names):
            if n not in reports:
                p.append(f"{what}: unknown workload {n!r}")
                continue
            has_layer[n] = True
            if m["moves"] not in reports[n]:
                p.append(f"{what}: moves {m['moves']!r}, which cell {n!r} "
                         f"does not report")
        spec_file = os.path.join(root, "benchmark", "metrics",
                                 f"{m['name']}.json")
        if not os.path.isfile(spec_file):
            p.append(f"{what}: benchmark/metrics/{m['name']}.json "
                     f"does not exist")
            continue
        with open(spec_file) as f:
            spec = json.load(f)
        for key in ("layer", "unit", "better", "source", "moves"):
            if spec.get(key) != m[key]:
                p.append(f"{what}: its file says {key} {spec.get(key)!r}, "
                         f"the manifest {m[key]!r}")
        if not os.path.isfile(os.path.join(root, spec.get("reader", ""))):
            p.append(f"{what}: reader {spec.get('reader')!r} does not exist")
    for n in cell_names:
        if len(reports.get(n, ())) < 2 or "setup_s" not in reports.get(n, ()):
            p.append(f"workload {n!r}: must report setup_s and one more "
                     f"end-to-end metric")
        if not has_layer.get(n):
            p.append(f"workload {n!r}: reports no per-layer metric")
    mfu = [m for m in per_layer
           if "mfu" in re.split(r"[._\-]", m.get("name", ""))]
    for m in per_layer:
        if m.get("name", "").endswith("_roofline") and not any(
                x.get("moves") == m.get("moves") for x in mfu):
            p.append(f"per_layer metric {m['name']!r}: no mfu metric moves "
                     f"{m.get('moves')!r} beside it")
    return p
