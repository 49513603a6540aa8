#!/usr/bin/env python3
"""The control of the check that decides ``correct``: the plain
reference computed in float32, the nearest precision below the int64
the census is exact in, put where the program's censuses go.  Prints,
per seed, the widest gap between the control and the int64 reference
over the censuses a run of the cell compares, and exits non-zero unless
every seed's gap is above the check's limit of 0, that is unless the
check fails the control.

    python3 benchmarks/chip/control.py --workload patents-batch \\
        --seeds 11,12,13

It runs on the host alone (the reference is numpy), at the cell's own
size.  A stream cell compares ``--windows`` windows per seed (the
driver's ``control_inputs``).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def control_gaps(cell: dict, seed: int, windows: int = 4) -> list[int]:
    """Gap of the float32 control against the int64 reference, per
    census the control stands in for."""
    import numpy as np

    from chip import reference
    jobs = cell["driver"].control_inputs(cell["config"], cell["traffic"],
                                         seed, windows)
    out = []
    for s, d, n in jobs:
        want = reference.census(s, d, n)
        got = reference.census(s, d, n, dtype=np.float32)
        out.append(reference.gap(got, want))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    ap.add_argument("--windows", type=int, default=4)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE.parent)]
    from chip import run
    cell = run.resolve(run.load_benchmark(), args.workload)
    worst = []
    for seed in (int(s) for s in args.seeds.split(",")):
        gaps = control_gaps(cell, seed, args.windows)
        worst.append(max(gaps))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_census_gap": max(gaps),
                          "per_census": gaps}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "control_census_gap_min": min(worst), "limit": 0,
                      "control_fails": min(worst) > 0}))
    return 0 if min(worst) > 0 else 1


if __name__ == "__main__":
    sys.exit(main())
