#!/usr/bin/env python3
"""Readings of the check, for setting its limits: sound runs and runs with
a fault planted under the timed path, several seeds in one process.

    python3 bench/control.py --workload wt-k4.backlog --seconds 51 \\
        --seeds 11,12,13 --fault none,stale

Prints one JSON line per run: the workload, seed, fault, ``correct`` and
every number the check compared. The benchmark's own runs never plant a
fault; this script and ``bench/tests`` do.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="none,stale")
    args = ap.parse_args(argv)

    import cell
    import faults

    for fault in args.fault.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            out = cell.run_cell(args.workload, seed, args.seconds, False,
                                t_process=time.perf_counter(),
                                hook=None if fault == "none" else faults.FAULTS[fault])
            res, rep = out["result"], out["report"]
            print(json.dumps({"workload": args.workload, "seed": seed, "fault": fault,
                              "correct": res["correct"], "batches": rep["batches"],
                              "reference_s": rep["reference_s"],
                              "end_to_end": rep["end_to_end"],
                              "limits": res["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
