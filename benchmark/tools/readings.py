#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip, in one process.

    python3 benchmark/tools/readings.py --workload <cell> --seeds 101,102,...
        [--controls 3] [--faults 3] [--out chiprun_out/readings_<cell>.json]

For every seed: the program's first three steps (through the window's own
compiled step and feed) against the float32 reference: the lower readings.
For the first ``--controls`` seeds: the reference with fp8 operands (the
control) and with bfloat16 operands (information) against the float32
reference: the upper readings. For the first ``--faults`` seeds: the
reference given half of the batch, and for a cell on several chips the
reference given chip 0's rows only (the exchange left out), against the
whole batch's reference. ``--reference-only`` leaves the program out (its
readings also come with every benchmark run's ``check``): control and faults
then need one chip, whatever the cell asks for. Not part of a benchmark run.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402
from benchmark.harness import manifest as manifest_mod  # noqa: E402


def gaps(got, want):
    """The numbers of ``check.compare`` with no limit applied."""
    from benchmark.harness import check
    _, rows = check.compare(got, want, dict.fromkeys(check.NUMBERS, 0.0))
    return {r["name"]: {"value": r["value"], "where": r["where"]}
            for r in rows}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--reference-only", action="store_true",
                    help="skip the program: control and faults only, which "
                         "need one chip whatever the cell asks for")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    manifest = manifest_mod.load(ROOT)
    cell, workload, cfg = bench.load_cell(manifest, args.workload,
                                          args.rehearse)
    chips = workload["chips"]
    if args.rehearse:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={chips}")
    import jax
    from horovod_tpu.parallel import shard_batch
    from benchmark.harness import (check, program, reference, traffic,
                                   weights)
    if (jax.devices()[0].platform == "tpu") == args.rehearse:
        raise SystemExit("needs a TPU, or --rehearse and none")

    if not args.reference_only:
        hvd, mesh = program.start(chips)
        model, loss_fn = program.load_model_builder(cfg["model"])(cfg)
    shapes = reference.param_shapes(cfg)
    norms = check.Norms(shapes, cfg, reference.fused_parts(cfg))
    refs = {p: reference.Reference(cfg, p)
            for p in ("float32", "bfloat16", "fp8")}
    compiled, out = None, []
    per_chip = workload["sequences_per_chip"]

    def first_batches(seed, rows=None):
        b = traffic.Batches(cfg, workload, seed)
        got = [b.next() for _ in range(check.CHECK_STEPS)]
        if rows is not None:
            got = [{k: v[:rows] for k, v in x.items()} for x in got]
        return got

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        batches = first_batches(seed)
        want = check.reference_readings(refs["float32"], norms, shapes, seed,
                                        cfg, batches)
        row = {"seed": seed, "losses_reference": want["losses"]}
        if not args.reference_only:
            step, state = program.build(
                hvd, mesh, cfg, loss_fn,
                weights.make_params(shapes, seed, cfg))
            if compiled is None:
                compiled, secs = program.compile_step(
                    step, state, shard_batch(batches[0], mesh))
                print(f"[readings] compiled in {secs:.1f} s", flush=True)
            readings = check.ProgramReadings(norms, seed, cfg)
            for k, host_batch in enumerate(batches, start=1):
                state, loss = program.feed(compiled, mesh, state, host_batch)
                readings.after_step(k, state, loss)
            del state, step
            row["losses_program"] = readings.losses
            row["program"] = gaps(readings.asdict(), want)
        if i < args.controls:
            for p in ("fp8", "bfloat16"):
                row[f"control_{p}"] = gaps(check.reference_readings(
                    refs[p], norms, shapes, seed, cfg, batches), want)
        if i < args.faults:
            half = first_batches(seed, rows=per_chip * chips // 2)
            row["fault_half_batch"] = gaps(check.reference_readings(
                refs["float32"], norms, shapes, seed, cfg, half), want)
            if chips > 1:
                one = first_batches(seed, rows=per_chip)
                row["fault_no_exchange"] = gaps(check.reference_readings(
                    refs["float32"], norms, shapes, seed, cfg, one), want)
        row["seconds"] = time.perf_counter() - t0
        out.append(row)
        print("[readings] " + json.dumps(row), flush=True)
    if not args.reference_only:
        hvd.shutdown()
    summary = {}
    for kind in ("program", "control_fp8", "control_bfloat16",
                 "fault_half_batch", "fault_no_exchange"):
        rows = [r[kind] for r in out if kind in r]
        if rows:
            summary[kind] = {
                n: {"min": min(r[n]["value"] for r in rows),
                    "max": max(r[n]["value"] for r in rows)}
                for n in rows[0]}
    print("[readings] summary " + json.dumps(summary, indent=1), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": out,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
