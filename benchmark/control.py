"""The control: the plain reference put in the program's place, with one
of the configuration's guarantees broken, judged by the same check.

The configuration states the latency: every link delivers after one tick.
The control (``kinds/<kind>.py`` ``control``) breaks it for one share of
each pooled schedule, the last that travels at least one hop, which then
arrives one tick late at every node: the horizon cuts it one hop nearer
its origin. The check has to read it as not correct. The benchmark's own
runs never run this; ``python3 benchmark/control.py --workload <cell>
--seed <n>`` prints the numbers compared beside their limits, at the
cell's own size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def check(cell, seed: int) -> dict:
    """The harness's check with the control's counters in each call."""
    from benchmark import graphs, reference, spec

    indptr, indices, perm = graphs.overlay(cell.config, seed)
    graphs.check_csr(indptr, indices)
    kind = spec.module("kinds", cell.traffic["kind"])
    mix = kind.mix(cell.traffic, indptr.shape[0] - 1)
    ref = kind.reference(indptr, indices)
    mismatches, gap = 0, 0
    for origins, ticks in kind.schedules(mix, perm):
        want = kind.expected(ref, origins, ticks, mix)
        bad, g = reference.compare(kind.control(ref, origins, ticks, mix), want)
        mismatches, gap = mismatches + bad, max(gap, g)
    return {
        "counter_mismatches": {"value": mismatches, "at_most": 0},
        "max_counter_gap": {"value": gap, "at_most": 0},
        "correct": mismatches == 0,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="run the control of a cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmark import spec

    out = check(spec.load_cell(args.workload), args.seed)
    print(json.dumps({"workload": args.workload, "seed": args.seed, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
