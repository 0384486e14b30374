#!/usr/bin/env python3
"""Print what a recorded trace holds (planes, lines, commonest events with
their stats): the look by hand that comes before trusting the reduction.

    python3 benchmark/tools/trace_dump.py <trace dir or .xplane.pb> [out.txt]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import trace_reduce  # noqa: E402


def main(argv):
    path = argv[1]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    text = trace_reduce.describe(trace_reduce.load(path))
    if len(argv) > 2:
        with open(argv[2], "w") as f:
            f.write(text + "\n")
    else:
        print(text)


if __name__ == "__main__":
    main(sys.argv)
