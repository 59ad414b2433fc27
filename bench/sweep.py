#!/usr/bin/env python3
"""Find a configuration's knee: the highest offered rate at which the
journal backlog stays flat under open-loop arrivals.

    python3 bench/sweep.py --workload wt-k4.steady --rates 70,85,100,115 \\
        --seconds 30 --seed 5

One run per rate, in one process. Prints per rate the ops pending when
the last arrival came in, the batches and their mean size, and the
freshness median and 95th percentile. Below the knee the backlog at the
end stays within about one batch; above it, it grows with the window.
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import cell
    import stats

    bench = cell.load_benchmark()
    w, cfg_file, traffic_file = cell.find_cell(bench, args.workload)
    knee = cell.load_json(cfg_file)["knee_ops_s"]
    traffic = cell.load_json(traffic_file)
    for rate in (float(r) for r in args.rates.split(",")):
        out = cell.run_cell(args.workload, args.seed, args.seconds, False,
                            t_process=time.perf_counter(), bench=bench,
                            traffic=dict(traffic, load=rate / knee))
        run = out["run"]
        lat = run.op_commit - run.op_due
        print(json.dumps({
            "rate": rate, "correct": out["result"]["correct"],
            "backlog_at_last_arrival": run.generator["backlog_at_last_arrival"],
            "batches": len(run.batches),
            "mean_batch_ops": sum(b["ops"] for b in run.batches) / len(run.batches),
            "freshness_p50_s": stats.percentile(lat, 50),
            "freshness_p95_s": stats.percentile(lat, 95),
            "mean_batch_s": sum(b["end"] - b["start"] for b in run.batches) / len(run.batches),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
