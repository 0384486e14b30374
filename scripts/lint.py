#!/usr/bin/env python
"""Repo self-lint: hvdlint over the library, examples, scripts and tests.

Thin wrapper over ``python -m horovod_tpu.analysis.lint`` pinned to the
repo's default scope, so CI and humans run the identical check:

    python scripts/lint.py            # lint the default scope
    python scripts/lint.py --format json
    python scripts/lint.py path/...   # lint specific paths instead
    python scripts/lint.py --cost     # lint + the hvdcost CI gate
    python scripts/lint.py --race     # lint + the hvdrace concurrency gate
    python scripts/lint.py --cost --race --format json   # all three gates

Exit status 1 on any finding. ``--cost`` additionally runs
``python -m horovod_tpu.analysis.cost`` (the static per-link-tier cost
model + budget verdict, docs/static_analysis.md) after the lint, and
``--race`` runs ``python -m horovod_tpu.analysis.race`` (the lock-graph
concurrency analyzer) — so ONE command runs every static gate; arguments
after ``--cost-args`` / ``--race-args`` are forwarded to the respective
gate. With ``--format json`` each gate emits its own JSON document, so
stdout stays a parseable stream (jq -s / raw_decode), never JSON
followed by human text. The tier-1 gates
(tests/test_analysis.py::TestSelfLint / TestSelfRace) run these scopes
and assert they stay clean and under the 30 s budget; suppress
intentional violations inline with
``# hvdlint: disable=HVLxxx -- <reason>`` /
``# hvdrace: disable=HVRxxx -- <reason>`` (docs/static_analysis.md).
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_SCOPE = ("horovod_tpu", "examples", "scripts")
# hvdrace needs whole-package lock/call-graph resolution, so its scope is
# the package tree (analyzing unrelated scripts would only add pseudo
# locks without adding resolvable call edges).
RACE_SCOPE = ("horovod_tpu",)


def _extract_gate(argv, flag):
    """Pop ``--<gate>`` / ``--<gate>-args ...`` from argv; returns
    (enabled, forwarded_args)."""
    gate_argv = []
    enabled = False
    args_flag = flag + "-args"
    if args_flag in argv:
        i = argv.index(args_flag)
        gate_argv = argv[i + 1:]
        del argv[i:]
        enabled = True
    if flag in argv:
        argv.remove(flag)
        enabled = True
    return enabled, gate_argv


def main(argv=None):
    sys.path.insert(0, _REPO)
    from horovod_tpu.analysis.lint import main as lint_main

    argv = list(sys.argv[1:] if argv is None else argv)
    # --race-args must be extracted before --cost-args so a command line
    # like `--cost-args X --race-args Y` hands each gate its own tail.
    run_race, race_argv = _extract_gate(argv, "--race")
    run_cost, cost_argv = _extract_gate(argv, "--cost")
    value_flags = {"--rules", "--format", "--config"}
    has_paths = False
    skip_next = False
    for a in argv:
        if skip_next:
            skip_next = False
            continue
        if a in value_flags:
            skip_next = True
        elif not a.startswith("-"):
            has_paths = True
    if not has_paths:
        argv += [os.path.join(_REPO, p) for p in DEFAULT_SCOPE
                 if os.path.exists(os.path.join(_REPO, p))]
    json_mode = "--format" in argv and "json" in argv
    rc = lint_main(argv)
    if run_cost:
        from horovod_tpu.analysis.cost import main as cost_main
        # Machine-readable lint output stays machine-readable: a JSON
        # lint run forwards --json to the cost gate too, so stdout is a
        # stream of JSON documents (jq -s / raw_decode), never JSON
        # followed by human text.
        if json_mode and "--json" not in cost_argv:
            cost_argv = cost_argv + ["--json"]
        rc = max(rc, cost_main(cost_argv))
    if run_race:
        from horovod_tpu.analysis.race import main as race_main
        if not any(not a.startswith("-") for a in race_argv):
            race_argv = race_argv + [
                os.path.join(_REPO, p) for p in RACE_SCOPE
                if os.path.exists(os.path.join(_REPO, p))]
        if json_mode and "--format" not in race_argv:
            race_argv = race_argv + ["--format", "json"]
        rc = max(rc, race_main(race_argv))
    return rc


if __name__ == "__main__":
    sys.exit(main())
