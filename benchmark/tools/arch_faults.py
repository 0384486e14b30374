#!/usr/bin/env python3
"""The faults of an architecture's own, planted in the plain reference, on
the chip: what each does to the numbers that decide ``correct``.

    python3 benchmark/tools/arch_faults.py --workload <cell> --seeds 301,302,303
        [--faults window_ignored,rope_left_out] [--out chiprun_out/faults_<cell>.json]

``tools/readings.py`` plants the faults every architecture has (half the
batch, the exchange left out). An architecture's file may state more of its
own as ``FAULTS`` and plant the one named by ``cfg["planted_fault"]`` in its
``Net``; this tool runs the float32 reference with each against the sound
float32 reference, the first three steps of every seed, and prints the gaps
with no limit applied. A cell's limits have to fail each on every seed. The
program is not run: one chip, whatever the cell asks for. Not part of a
benchmark run.
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
from benchmark.tools.readings import gaps  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", help="comma-separated; default: all the "
                                     "architecture states")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    _, workload, cfg = bench.load_cell(manifest_mod.load(ROOT), args.workload,
                                       args.rehearse)
    import jax
    from benchmark.harness import arch, check, reference, traffic
    if (jax.devices()[0].platform == "tpu") == args.rehearse:
        raise SystemExit("needs a TPU, or --rehearse and none")
    stated = getattr(arch.of(cfg), "FAULTS", ())
    faults = args.faults.split(",") if args.faults else list(stated)
    unknown = sorted(set(faults) - set(stated))
    if unknown or not faults:
        raise SystemExit(f"architecture {cfg['arch']!r} states the faults "
                         f"{list(stated)}, not {unknown}")
    shapes = reference.param_shapes(cfg)
    norms = check.Norms(shapes, cfg, reference.fused_parts(cfg))
    sound = reference.Reference(cfg, "float32")
    faulty = {f: reference.Reference(dict(cfg, planted_fault=f), "float32")
              for f in faults}
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        feed = traffic.Batches(cfg, workload, seed)
        batches = [feed.next() for _ in range(check.CHECK_STEPS)]
        want = check.reference_readings(sound, norms, shapes, seed, cfg,
                                        batches)
        row = {"seed": seed}
        for fault, ref in faulty.items():
            row[fault] = gaps(check.reference_readings(
                ref, norms, shapes, seed, cfg, batches), want)
        row["seconds"] = time.perf_counter() - t0
        out.append(row)
        print("[faults] " + json.dumps(row), flush=True)
    summary = {f: {n: {"min": min(r[f][n]["value"] for r in out),
                       "max": max(r[f][n]["value"] for r in out)}
                   for n in check.NUMBERS} for f in faults}
    print("[faults] summary " + json.dumps(summary, indent=1), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": out,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
