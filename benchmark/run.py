#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --check-manifest

Needs a TPU with at least the cell's chips; anything else exits non-zero
before a result line. ``--rehearse`` runs the same path on the CPU at a tiny
size to find faults of control flow: it is refused on a TPU, names the CPU
as its device and writes no time, rate or share under a metric's name.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``check``: each number compared beside its limit.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import shutil    # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import arch  # noqa: E402
from benchmark.harness import manifest as manifest_mod  # noqa: E402

GIB = 1 << 30
WARMUP_STEPS = 3
TRACE_STEPS = 10
OUT_DIR = os.path.join(ROOT, "benchmark_out")

# --rehearse: the cell every configuration runs (CPU, control flow only);
# the configuration's own tiny sizes are its architecture's (``REHEARSE`` in
# ``archs/<arch>.py``).
REHEARSE_CELL = {"sequences_per_chip": 2, "sequence_length": 64}

# Held for the whole run, on purpose, and used by nothing. Without a dict of
# this size built here, before jax is imported, the conversion of the step's
# jaxpr to MLIR takes 6.6 s and not 1.35 s on the chip's host, in every one of
# the twenty variants of this file that were tried (PERF.md, PR 28: it follows
# the interpreter's allocation layout and not the code; the cause is not
# found). The parent's module held such a dict, its table of rehearsal sizes.
_LAYOUT = {"a0": 64, "a1": 65, "a2": 66, "a3": 67, "a4": 68, "a5": 69, "a6": 70, "a7": 71, "a8": 72, "a9": 73, "a10": 74}


def say(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--check-manifest", action="store_true")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if not args.check_manifest and (args.workload is None
                                    or args.seconds is None):
        ap.error("--workload and --seconds are required")
    return args


def rehearse_cut(workload, cfg):
    """Both cut, in place, to the sizes of a rehearsal."""
    for key, tiny in arch.of(cfg).REHEARSE.items():
        cfg[key] = dict(cfg[key], **tiny) if isinstance(tiny, dict) else tiny
    cfg["assumed"] = dict(cfg["assumed"], vocab_rows=512)
    for spec in cfg["inputs"].values():
        spec["high"] = min(spec["high"], 500)
    workload.update(REHEARSE_CELL)
    return workload, cfg


def load_cell(manifest, name, rehearse):
    """(cell entry, cell file, configuration file) of workload ``name``."""
    cell = manifest_mod.entry(manifest["workloads"], name, "workload")
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           f"{name}.json")) as f:
        workload = json.load(f)
    conf = manifest_mod.entry(manifest["configs"], cell["config"], "config")
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    if rehearse:
        rehearse_cut(workload, cfg)
    return cell, workload, cfg


def device_record(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes(devices):
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    keeps no count, as the CPU)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def needed_bytes(devices, temporaries):
    """Bytes the fullest chip needs while a step runs. The runtime's
    ``peak_bytes_in_use`` does not count a compiled program's temporaries
    (PERF.md, PR 25: it read 6.06 GiB where the step holds 4.6 GiB live and
    9.7 GiB of temporaries), so the need is the live bytes at the window's
    close plus the step's temporaries from ``memory_analysis()``, or the
    counter's own peak (the set-up's) where that is higher."""
    def need(d):
        stats = d.memory_stats() or {}
        return max(stats.get("peak_bytes_in_use", 0),
                   stats.get("bytes_in_use", 0) + temporaries)
    return max(need(d) for d in devices)


def find_devices(jax, chips, rehearse):
    """The devices JAX sees and their record; exits where they are not what
    the run needs."""
    devices = jax.devices()
    device = device_record(devices)
    say(f"device {device}; the cell wants {chips} chip(s)")
    if rehearse:
        if device["platform"] == "tpu":
            raise SystemExit("--rehearse is refused on a TPU")
    elif device["platform"] != "tpu":
        raise SystemExit(f"no TPU: platform is {device['platform']!r} "
                         f"({device['kind']}); --rehearse runs the CPU")
    if len(devices) < chips:
        raise SystemExit(f"{len(devices)} chip(s) found, the cell asks "
                         f"for {chips}")
    return devices, device


def set_up(args, workload, cfg, used):
    """Everything before the window opens: ``hvd.init``, weights and state,
    the compiled step, its first three steps (read for ``correct``) and the
    warm-up. Returns what the window and the comparison need."""
    import jax
    from horovod_tpu.parallel import shard_batch
    from benchmark.harness import (check, program, reference, traffic,
                                   weights, window)
    spans = {}
    hvd, mesh = program.start(workload["chips"])
    spans["init"] = time.perf_counter() - _T0
    t0 = time.perf_counter()
    model, loss_fn = program.load_model_builder(cfg["model"])(cfg)
    batches = traffic.Batches(cfg, workload, args.seed)
    first = batches.next()
    shapes = reference.param_shapes(cfg)
    wanted = program.model_shapes(model, first)
    if weights.flatten(check.plain(wanted)) != weights.flatten(shapes):
        raise SystemExit("the program's parameter names or shapes are not "
                         "the reference's")
    params = weights.make_params(shapes, args.seed, cfg)
    step, state = program.build(hvd, mesh, cfg, loss_fn, params)
    del params
    jax.block_until_ready(state)
    spans["state"] = time.perf_counter() - t0

    compiled, spans["compile"] = program.compile_step(
        step, state, shard_batch(first, mesh))
    mem = compiled.memory_analysis()
    say("memory_analysis of the step: "
        f"arguments {mem.argument_size_in_bytes / GIB:.3f} GiB, outputs "
        f"{mem.output_size_in_bytes / GIB:.3f}, aliased "
        f"{mem.alias_size_in_bytes / GIB:.3f}, temporaries "
        f"{mem.temp_size_in_bytes / GIB:.3f}; peak_bytes_in_use before the "
        f"first step {peak_bytes(used) / GIB:.3f} GiB")

    def feed(state, host_batch):
        return program.feed(compiled, mesh, state, host_batch)

    norms = check.Norms(shapes, cfg, reference.fused_parts(cfg))
    readings = check.ProgramReadings(norms, args.seed, cfg)
    host_batch = first
    for k in range(1, check.CHECK_STEPS + 1):
        state, loss = feed(state, host_batch)
        readings.after_step(k, state, loss)
        host_batch = batches.next()
    warm = window.Window(feed, batches)
    state = warm.run(state, steps=WARMUP_STEPS)
    return {"hvd": hvd, "feed": feed, "state": state, "batches": batches,
            "shapes": shapes, "norms": norms, "readings": readings,
            "spans": spans, "temporaries": mem.temp_size_in_bytes,
            "step_s": warm.seconds / WARMUP_STEPS,
            "setup_s": time.perf_counter() - _T0}


def measure(args, run, used, trace_dir):
    """The window, the memory readings at its close and, with ``--trace 1``,
    the traced tail. Consumes ``run["state"]``."""
    import jax
    from benchmark.harness import trace_reduce, window
    step_s = run["step_s"]
    tail_s = min(TRACE_STEPS * step_s, args.seconds / 4) if args.trace else 0
    win = window.Window(run["feed"], run["batches"])
    state = win.run(run.pop("state"), seconds=args.seconds - tail_s)
    memory = {"peak": peak_bytes(used),
              "need": needed_bytes(used, run["temporaries"])}
    say(f"memory_stats of chip 0 at the window's close: "
        f"{dict(used[0].memory_stats() or {})}")
    tail, trace = window.Window(run["feed"], run["batches"]), None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            state = tail.run(state, steps=max(3, round(tail_s / step_s)))
        finally:
            jax.profiler.stop_trace()
        if not args.rehearse:
            trace = trace_reduce.TraceSummary(
                trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
    del state
    spans = run["spans"]
    say(f"window: {win.steps} steps in {win.seconds:.3f} s; set-up "
        f"{run['setup_s']:.2f} s (init {spans['init']:.2f}, state "
        f"{spans['state']:.2f}, compile {spans['compile']:.2f})")
    return win, tail, trace, memory


def verify(args, run, workload, cfg):
    """The plain reference's first three steps against the program's, once
    the program's state is freed. Returns (correct, rows)."""
    from benchmark.harness import check, reference, traffic
    t0 = time.perf_counter()
    batches = traffic.Batches(cfg, workload, args.seed)
    batches = [batches.next() for _ in range(check.CHECK_STEPS)]
    expected = check.reference_readings(
        reference.Reference(cfg, "float32"), run["norms"], run["shapes"],
        args.seed, cfg, batches)
    say(f"reference: {time.perf_counter() - t0:.1f} s")
    return check.compare(run["readings"].asdict(), expected,
                         workload["limits"])


def run_cell(args, manifest):
    cell, workload, cfg = load_cell(manifest, args.workload, args.rehearse)
    chips = workload["chips"]
    if args.rehearse:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={chips}")
    import jax
    from benchmark.harness import metrics, peaks, window

    devices, device = find_devices(jax, chips, args.rehearse)
    peak_row = None if args.rehearse else peaks.peaks_for(device["kind"])
    used = devices[:chips]
    run = set_up(args, workload, cfg, used)
    win, tail, trace, memory = measure(
        args, run, used, os.path.join(OUT_DIR, "trace", cell["name"]))
    ok, rows = verify(args, run, workload, cfg)
    run["hvd"].shutdown()

    failed = win.bad_losses + tail.bad_losses
    result_metrics = {}
    if args.rehearse:
        pass            # a CPU run writes nothing under a metric's name
    elif args.trace:
        ctx = {"spans": run["spans"], "cfg": cfg, "workload": workload,
               "peaks": peak_row, "trace": trace,
               "window": {
                   "steps": win.steps, "seconds": win.seconds,
                   "dispatch_s": win.dispatch_s, "chips": chips,
                   "sequences_per_chip": workload["sequences_per_chip"],
                   "sequence_length": workload["sequence_length"]}}
        result_metrics = metrics.read_all(ROOT, manifest["per_layer"],
                                          cell["name"], ctx)
    else:
        values = {
            "tokens_per_s_per_chip": (win.steps
                                      * run["batches"].tokens_per_step
                                      / win.seconds / chips),
            "step_ms_p95": window.percentile(win.intervals_ms(), 95),
            "peak_hbm_gib": memory["need"] / GIB,
            "setup_s": run["setup_s"]}
        for m in manifest["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                result_metrics[m["name"]] = {"value": values[m["name"]],
                                             "unit": m["unit"]}
    device["memory_peak_bytes"] = memory["peak"]
    result = {"correct": bool(ok and failed == 0),
              "attempted": win.steps + tail.steps, "failed": failed,
              "metrics": result_metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s()
        result["breakdown"] = trace.breakdown()
    if args.rehearse:
        result["rehearsal"] = {"steps": win.steps, "traced_steps": tail.steps}
    result["check"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                       for r in rows}
    for r in rows:
        say(f"check {r['name']}: {r['value']:.6g} (limit {r['limit']:.6g}) "
            f"{'ok' if r['ok'] else 'OVER'}"
            + (f" at {r['where']}" if r["where"] else ""))
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None):
    args = parse(argv)
    manifest = manifest_mod.load(ROOT)
    if args.check_manifest:
        problems = manifest_mod.check(manifest, ROOT)
        for line in problems:
            print(line)
        print(f"BENCHMARK.json: {len(problems)} problem(s)")
        return 1 if problems else 0
    return run_cell(args, manifest)


if __name__ == "__main__":
    sys.exit(main())
