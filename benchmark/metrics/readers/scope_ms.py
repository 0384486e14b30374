"""Device milliseconds a step of the ops the program put under named scopes
of its own, other than the ``hvd.*`` phases of ``harness/scopes.py``: an op
counts where a component of its ``op_name`` path is one of ``args.scopes``
(``moe.experts``; JAX wraps a component of the backward pass as
``transpose(jvp(moe.experts))``, which counts alike), or where its
instruction's name matches ``args.instructions``: the TPU compiler rewrites
``lax.ragged_dot`` into kernels of its own (``ragged-dot-none.3``,
``ragged-dot-metadata``) and gives them that name as their whole path, so
the scope they were traced under is lost and only the name finds them.
Each op counts once, inside the window ``trace_reduce`` fixed for its chip;
mean over the cell's chips.

With ``args.roofline_of`` (the name of a work count in the configuration's
``archs/<arch>.py``, ``{pass: {"flops", "bytes"}}`` a step) the number is
the share of that work's least time at the chip's peaks in the ops' time,
in percent. Nothing to read where no op is found: a program that names
nothing, or a cell that does not run the layer.
"""

import os
import re

from benchmark.harness import arch, flops, scopes, trace_reduce

_WRAPPED = re.compile(r"^(?:[A-Za-z_]+\()*([^()]*)\)*$")


def _ops_by_chip(ctx):
    """[(chip window, [(instruction, path components, seconds)])] of the
    run's trace, read once a run (``ctx`` is one run's)."""
    if "_scoped_ops" not in ctx:
        out = []
        trace_dir = os.path.join(scopes.ROOT, "benchmark_out", "trace",
                                 ctx["workload"]["name"])
        space = scopes.load_space(trace_reduce.find_xplane(trace_dir))
        windows = {c.index: c for c in ctx["trace"].chips}
        for plane in space.planes:
            m = trace_reduce.DEVICE_PLANE.match(plane.name)
            if not m or int(m.group(1)) not in windows:
                continue
            chip = windows[int(m.group(1))]
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                ops = [(name, _components(path), (end - start) * 1e-9)
                       for name, path, start, end
                       in scopes._device_ops(plane, line)
                       if start >= chip.start_ns and end <= chip.end_ns]
                out.append((chip, ops))
        ctx["_scoped_ops"] = out
    return ctx["_scoped_ops"]


def _components(path):
    """The scopes on an ``op_name`` path, each without JAX's wrappers
    (``transpose(jvp(x))`` -> ``x``); of a path joined with ``;`` the
    first part counts, as in ``harness/scopes.py``."""
    parts = (path or "").split(";")[0].split("/")
    return frozenset(_WRAPPED.sub(r"\1", p) for p in parts)


def read(ctx, scopes=(), instructions=None, roofline_of=None):
    if ctx.get("trace") is None:
        return None
    try:
        chips = _ops_by_chip(ctx)
    except FileNotFoundError:
        return None
    wanted = frozenset(scopes)
    named = re.compile(instructions) if instructions else None
    per_step, found = [], False
    for chip, ops in chips:
        mine = [s for name, parts, s in ops
                if parts & wanted or (named and named.search(name))]
        found = found or bool(mine)
        per_step.append(sum(mine) / chip.steps)
    if not found:
        return None
    seconds = sum(per_step) / len(per_step)
    if roofline_of is None:
        return 1e3 * seconds
    peaks, w = ctx["peaks"], ctx["window"]
    work = getattr(arch.of(ctx["cfg"]), roofline_of, None)
    if peaks is None or work is None:
        return None
    least = flops.least_seconds(
        work(ctx["cfg"], w["sequences_per_chip"], w["sequence_length"]),
        peaks)
    return 100.0 * least / seconds
