#!/usr/bin/env python3
"""Benchmark of the sharded listing service on a TPU.

    python3 bench/run.py --workload wg-tri.backlog --seed 7 --seconds 51 --trace 0

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; then ``limits``, every number the check compared beside its
limit. Standard error ends with the same limits, one per line. Every
batch's times go to ``bench_artifacts/batches/<workload>.<seed>.<trace>.jsonl``.

Anything but a TPU, or fewer chips than the cell asks for, exits non-zero
without a result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)
# The TPU runtime logs under /tmp unless told otherwise; keep the run's
# files inside the checkout.
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "bench_artifacts", "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import cell

    try:
        out = cell.run_cell(args.workload, args.seed % 2**64, args.seconds,
                            bool(args.trace), t_process=T_PROCESS)
    except cell.CellError as e:
        cell.log(f"[bench] refused: {e}")
        return 2
    res, rep = out["result"], out["report"]
    cell.log(f"[bench] {args.workload} seed={args.seed} trace={args.trace} "
             f"device={json.dumps(res['device'])}")
    cell.log(f"[bench] setup_s={rep['setup_s']} breakdown={json.dumps(rep['setup'])}")
    cell.log(f"[bench] register={json.dumps(rep['register'])} "
             f"initial_counts={json.dumps(rep['initial_counts'])}")
    cell.log(f"[bench] window_s={rep['window_s']} batches={rep['batches']} "
             f"window_compiles={rep['window_compiles']} recoveries={rep['recoveries']} "
             f"reference_s={rep['reference_s']}")
    if rep["freshness_samples"]:
        cell.log(f"[bench] freshness samples={rep['freshness_samples']} "
                 f"generator={json.dumps(rep['generator'])}")
    cell.log(f"[bench] end_to_end={json.dumps(rep['end_to_end'])}")
    if "breakdown" in res:
        cell.log(f"[bench] breakdown={json.dumps(res['breakdown'])}")
    for name, v in res["limits"].items():
        cell.log(f"[limits] {name} = {v['value']} (limit {v['limit']})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
