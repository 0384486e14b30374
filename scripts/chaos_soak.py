#!/usr/bin/env python
"""Chaos soak CLI: seeded multi-process elastic-recovery validation.

Runs the :mod:`horovod_tpu.chaos.soak` harness — a clean elastic run, a
chaos run under a seeded fault plan (worker kill + KV drop + straggler by
default, or ``--plan``), and a same-seed re-run — then prints ONE JSON
line with the verdict and evidence.
Partial progress streams to the ``HVD_BENCH_PROGRESS_FILE`` JSONL channel
(default ``bench_progress.jsonl``), so a wedged soak still leaves evidence.

Examples::

    python scripts/chaos_soak.py                      # 8 procs, default plan
    python scripts/chaos_soak.py --procs 4 --steps 6 --seed 7
    python scripts/chaos_soak.py --plan my_plan.yaml --no-rerun
"""

import argparse
import json
import os
import sys

# `python scripts/chaos_soak.py` puts scripts/ on sys.path, NOT the repo
# root — and the spawned workers re-import horovod_tpu too, so the repo
# must be on PYTHONPATH.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
os.environ["PYTHONPATH"] = _REPO + (
    os.pathsep + os.environ["PYTHONPATH"]
    if os.environ.get("PYTHONPATH") else "")

# The soak models hosts with loopback CPU processes; never grab a real TPU.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--procs", type=int, default=8,
                   help="Worker processes (loopback 'hosts'); default 8")
    p.add_argument("--steps", type=int, default=8,
                   help="Target training steps; default 8")
    p.add_argument("--seed", type=int, default=123,
                   help="Chaos seed (pins the whole injection schedule)")
    p.add_argument("--plan", help="YAML/JSON fault plan file "
                                  "(default: the built-in kill+drop+"
                                  "straggler acceptance plan)")
    p.add_argument("--workdir", help="Scratch dir (kept for inspection); "
                                     "default: a fresh tempdir")
    p.add_argument("--no-rerun", action="store_true",
                   help="Skip the same-seed determinism re-run")
    p.add_argument("--loss-tol", type=float, default=1e-5)
    p.add_argument("--leader-kill", action="store_true",
                   help="Run the TELEMETRY leader-kill soak instead: "
                        "kill a slice leader under HOROVOD_MESH_SLICES="
                        "--slices and assert re-election + the job view "
                        "naming the dead host (soak.run_leader_kill_soak)")
    p.add_argument("--slices", type=int, default=2,
                   help="Virtual slice count for --leader-kill; default 2")
    args = p.parse_args(argv)

    from horovod_tpu.chaos import soak

    if args.leader_kill:
        record = {"metric": "telemetry_leader_kill_soak",
                  "unit": "invariants", "procs": args.procs,
                  "slices": args.slices, "steps": args.steps,
                  "seed": args.seed}
        try:
            ev = soak.run_leader_kill_soak(
                procs=args.procs, slices=args.slices, steps=args.steps,
                seed=args.seed, workdir=args.workdir)
        except (AssertionError, RuntimeError, TimeoutError) as e:
            record.update({"value": 0.0, "ok": False,
                           "error": str(e)[:500]})
            print(json.dumps(record))
            return 1
        record.update({
            "value": 1.0, "ok": True, "victim": ev["victim"],
            "victim_host": ev["victim_host"],
            "healthy": ev["view"]["counts"]["healthy"],
            "slice_leaders": {s: m["leader"]
                              for s, m in ev["view"]["slices"].items()},
            "workdir": ev["workdir"],
        })
        print(json.dumps(record))
        return 0

    plan_dict = None
    if args.plan:
        import yaml
        with open(args.plan) as f:
            plan_dict = yaml.safe_load(f)

    record = {"metric": "chaos_soak", "unit": "invariants",
              "procs": args.procs, "steps": args.steps, "seed": args.seed}
    try:
        evidence = soak.run_soak(
            procs=args.procs, steps=args.steps, seed=args.seed,
            workdir=args.workdir, plan_dict=plan_dict,
            loss_tol=args.loss_tol, reruns=0 if args.no_rerun else 1)
    except (AssertionError, RuntimeError, TimeoutError) as e:
        record.update({"value": 0.0, "ok": False,
                       "error": str(e)[:500]})
        print(json.dumps(record))
        return 1
    record.update({
        "value": 1.0, "ok": True,
        "kill_budget": evidence["kill_budget"],
        "injections": len(evidence["ledger"]),
        "ledger_deterministic": evidence["ledger_deterministic"],
        "final_world": evidence["chaos_results"][0]["final_world"],
        "recovery_histogram_populated": all(
            r["recoveries"] >= 1 for r in evidence["chaos_results"]
            if r["resets"]),
        "workdir": evidence["workdir"],
    })
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
