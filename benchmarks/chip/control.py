#!/usr/bin/env python3
"""Readings behind the limits of ``correct``, on the chip, in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 [--jobs 2]

For each seed it sets the cell up, runs ``--jobs`` jobs of the program, and
compares them as a run does twice: the program against the reference (the
lower readings), and the control, the reference computed in the precision
below the configuration's, in the program's place (the upper readings).
One JSON line per seed; the benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--jobs", type=int, default=1)
    args = ap.parse_args(argv)
    try:
        plan = bench.set_up(args.workload)
    except bench.Refused as e:
        print(f"control: {e}", file=sys.stderr)
        return 2

    import traffic

    for seed in (int(s) for s in args.seeds.split(",")):
        job = traffic.make(plan.config, plan.mix, seed)
        kept = []
        for j in range(args.jobs):
            inp = job.inputs(j)
            kept.append((j, inp, job.run(inp)))
        program, limits = traffic.compare(job, kept)
        control, _ = traffic.compare(job, kept, control=True)
        print(json.dumps({"workload": args.workload, "seed": seed, "jobs": args.jobs,
                          "limits": limits, "program": program, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
