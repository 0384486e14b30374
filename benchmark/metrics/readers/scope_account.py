"""The step's device time as an account that closes: every op of the
traced window once, under the name the program gave it, and what belongs
to no name as numbers of their own. Milliseconds a step, mean over the
cell's chips.

The names are the program's list (``horovod_tpu/trace/scopes.py``
``SCOPES``: name and kind). An op belongs to the INNERMOST listed name on
its ``op_name``, the last one: under ``jax.checkpoint`` and a
``custom_vjp`` a path repeats itself (``.../ssm.mixer/hvd.loss_and_grad/
jvp(NemotronH)/layer_0/mixer/ssm.mixer/checkpoint/ssm.scan/...``), and JAX's
wrappers are taken off as ``scope_ms.py`` does (``transpose(jvp(x))`` ->
``x``; of a path joined with ``;`` the first part counts). Where that name
is a

    leaf       the op is the leaf's;
    container  the op has no leaf: ``unnamed``, unless a name of kind
               ``rule`` claims what is left directly ``under`` that
               container (``lm.loss``: under ``hvd.loss_and_grad``,
               outside ``lm.model``), and then it is that name's;
    nothing    (a path with no listed name on it) ``unnamed`` too;

and an op with no ``op_name`` at all (the compiler's copies, slices and
conditionals) is ``no_path``.

**An event that wholly encloses later events of its line is dropped**: the
profiler shows an HLO ``conditional`` (and a ``while`` or ``call``) as an
event round its branch's ops, and a sum over events would count the
branch twice. With those dropped the events of a chip's ``XLA Ops`` line
do not overlap, so the parts below sum to the chip's busy time.

``args.part`` picks what ``read`` returns: ``unnamed``, ``no_path``, or a
name of the list (a leaf or a rule: ``loss`` is short for ``lm.loss``).
The whole account, and the five largest ``unnamed`` groups by their
``op_name`` cut after the container (so that a traced run says what to
name next), go to stderr once a run. Nothing to read from a program
without the list (the parent of the PR that brought it), or where the part
holds no op.
"""

import collections
import os
import re
import sys

from benchmark.harness import scopes, trace_reduce

_WRAPPED = re.compile(r"^(?:[A-Za-z_]+\()*([^()]*)\)*$")
_LAYER = re.compile(r"(?<=[a-z]_)\d+")          # layer_3 -> layer_N
SHORT = {"loss": "lm.loss"}


def program_scopes():
    """{name: (kind, under)} of the program's list; None where the program
    has none."""
    try:
        from horovod_tpu.trace.scopes import SCOPES
    except ImportError:
        return None
    return {s.name: (s.kind, s.under) for s in SCOPES}


def without_enclosing(events):
    """``events`` ((name, path, start_ns, end_ns), any order) in time
    order, less every event that wholly encloses a later, shorter one of
    positive length (the first such event that starts inside it decides:
    the ops of one line do not overlap otherwise)."""
    events = sorted(events, key=lambda e: (e[2], -e[3]))
    kept = []
    for i, e in enumerate(events):
        encloses = False
        for j in range(i + 1, len(events)):
            nxt = events[j]
            if nxt[2] >= e[3]:
                break
            if nxt[3] > nxt[2]:
                encloses = nxt[3] <= e[3] and nxt[3] - nxt[2] < e[3] - e[2]
                break
        if not encloses:
            kept.append(e)
    return kept


def components(path):
    """The parts of an ``op_name`` path in order, each without JAX's
    wrappers."""
    parts = (path or "").split(";")[0].split("/")
    return [_WRAPPED.sub(r"\1", p) for p in parts]


def rules_of(listed):
    """{container: the rule's name} for the names given by rule."""
    return {under: name for name, (kind, under) in listed.items()
            if kind == "rule"}


def account_of(path, listed, rules):
    """(part, group): the part of the account an op with ``path`` belongs
    to, and for ``unnamed`` the path cut after the container (layer
    numbers as ``N``)."""
    if not path:
        return "no_path", None
    parts = components(path)
    at = [i for i, p in enumerate(parts) if p in listed]
    if not at:
        return "unnamed", _LAYER.sub("N", "/".join(parts))
    inner = parts[at[-1]]
    if listed[inner][0] == "leaf":
        return inner, None
    if inner in rules:
        return rules[inner], None
    return "unnamed", _LAYER.sub("N", "/".join(parts[at[-1]:]))


def account(events, listed):
    """({part: seconds}, {unnamed group: seconds}) of one chip's events
    inside its window."""
    parts = collections.Counter()
    groups = collections.Counter()
    rules = rules_of(listed)
    for _, path, start, end in without_enclosing(events):
        part, group = account_of(path, listed, rules)
        seconds = (end - start) * 1e-9
        parts[part] += seconds
        if group is not None:
            groups[group] += seconds
    return parts, groups


def _of_run(ctx):
    """({part: ms a step}, {unnamed group: ms a step}), mean over the
    run's chips; read and printed once a run."""
    if "_scope_account" in ctx:
        return ctx["_scope_account"]
    listed = program_scopes()
    if listed is None:
        ctx["_scope_account"] = None
        return None
    trace_dir = os.path.join(scopes.ROOT, "benchmark_out", "trace",
                             ctx["workload"]["name"])
    space = scopes.load_space(trace_reduce.find_xplane(trace_dir))
    windows = {c.index: c for c in ctx["trace"].chips}
    parts = collections.Counter()
    groups = collections.Counter()
    chips = 0
    for plane in space.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) not in windows:
            continue
        chip = windows[int(m.group(1))]
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            chips += 1
            inside = [e for e in scopes._device_ops(plane, line)
                      if e[2] >= chip.start_ns and e[3] <= chip.end_ns]
            chip_parts, chip_groups = account(inside, listed)
            for k, s in chip_parts.items():
                parts[k] += 1e3 * s / chip.steps
            for k, s in chip_groups.items():
                groups[k] += 1e3 * s / chip.steps
    if not chips:
        ctx["_scope_account"] = None
        return None
    parts = {k: v / chips for k, v in parts.items()}
    groups = {k: v / chips for k, v in groups.items()}
    _say(parts, groups)
    ctx["_scope_account"] = parts, groups
    return ctx["_scope_account"]


def _say(parts, groups):
    total = sum(parts.values())
    lines = [f"[scope_account] {total:.3f} ms a step in all (enclosing "
             f"events dropped)"]
    for name, ms in sorted(parts.items(), key=lambda kv: -kv[1]):
        lines.append(f"[scope_account]   {ms:9.3f}  {name}")
    for name, ms in sorted(groups.items(), key=lambda kv: -kv[1])[:5]:
        lines.append(f"[scope_account]   unnamed {ms:9.3f}  {name}")
    print("\n".join(lines), file=sys.stderr, flush=True)


def read(ctx, part):
    if ctx.get("trace") is None:
        return None
    try:
        found = _of_run(ctx)
    except FileNotFoundError:
        return None
    if found is None:
        return None
    part = SHORT.get(part, part)
    # an account that holds nothing unnamed reads 0, not nothing
    return found[0].get(part, 0.0 if part in ("unnamed", "no_path")
                        else None)
