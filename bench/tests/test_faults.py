"""Whole runs of the harness on the CPU at a tiny size, past its look for a
chip: a sound run comes out correct, and each fault planted under the
timed path makes ``correct`` false. The cells are added as files and
entries only, in a directory of their own, as a later PR adds its cells.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import json
import os
import shutil
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import cell  # noqa: E402
import faults  # noqa: E402

TINY = {"graph": {"log2_vertices": 6, "draws": 400, "graph_seed": 1,
                  "a": 0.57, "b": 0.19, "c": 0.19},
        "batch_ops": 8,
        "scheduler": {"min_ops": 1, "max_ops": 8, "target_cost": 1e18},
        "caps": {"v_cap": 128, "deg_cap": 64, "e_cap": 1024, "match_cap": 4096,
                 "group_cap": 1024, "set_cap": 64, "pair_cap": 128},
        "knee_ops_s": 40.0}
EXECUTORS = {"tiny-tri": {"q2_triangle": "tree"}, "tiny-k4": {"q4_clique4": "wcoj"},
             "tiny-sq": {"q1_square": "tree"}}
TRAFFIC = {"tiny-tri": ("backlog", "brisk"), "tiny-k4": ("backlog", "brisk"),
           "tiny-sq": ("drip",)}
EVEN = """
import numpy as np


def due_times(traffic, rate, seconds, rng):
    return np.arange(0.5, rate * seconds) / rate
"""


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench")
    for kind in ("metrics", "traffic", "arrivals", "graphs", "patterns"):
        shutil.copytree(os.path.join(BENCH, kind), d / kind)
    (d / "traffic" / "brisk.json").write_text(json.dumps(
        {"arrivals": "poisson", "load": 0.5, "delete_share": 0.5,
         "profile": [[0.5, 3.0], [0.5, 1.0]]}))
    # A new arrival law and op mix, as files alone.
    (d / "arrivals" / "even.py").write_text(EVEN)
    (d / "traffic" / "drip.json").write_text(json.dumps(
        {"arrivals": "even", "load": 0.5, "delete_share": 0.5,
         "insert_endpoints": "degree"}))
    (d / "metrics" / "ops_total.py").write_text(
        "def read(run):\n    return float(sum(b['ops'] for b in run.batches))\n")
    configs, cells = [], []
    for name, patterns in EXECUTORS.items():
        cfg = dict(TINY, patterns=patterns)
        if name == "tiny-sq":       # squares outnumber triangles: a sparser graph
            cfg["graph"] = dict(TINY["graph"], draws=120)
        (d / f"{name}.json").write_text(json.dumps(cfg))
        configs.append({"name": name, "file": str(d / f"{name}.json")})
        for traffic in TRAFFIC[name]:
            cells.append({"name": f"{name}.{traffic}", "config": name,
                          "traffic": traffic, "chips": 1})
    names = [c["name"] for c in cells]
    bench = {"configs": configs, "workloads": cells,
             "end_to_end": [{"name": "setup_s", "unit": "s"},
                            {"name": "register_s", "unit": "s"},
                            {"name": "ops_total", "unit": "ops", "workloads": names}],
             "per_layer": [{"name": "register_device_s", "unit": "s",
                            "moves": "register_s", "workloads": names}]}
    return str(d), bench


def _run(bench_dir, workload, hook=None):
    d, bench = bench_dir
    return cell.run_cell(workload, 3000000019, 2.0, False, t_process=time.perf_counter(),
                         bench=bench, bench_dir=d, require_tpu=False, hook=hook)


@pytest.mark.parametrize("workload", ["tiny-tri.backlog", "tiny-k4.backlog",
                                      "tiny-tri.brisk", "tiny-k4.brisk", "tiny-sq.drip"])
def test_a_sound_run_is_correct(bench_dir, workload):
    out = _run(bench_dir, workload)
    res = out["result"]
    assert res["correct"], res["limits"]
    assert res["metrics"]["ops_total"]["value"] > 0
    assert set(res["metrics"]) == {"setup_s", "register_s", "ops_total"}
    assert list(res)[-1] == "limits"
    assert out["report"]["window_compiles"] == 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("workload", ["tiny-tri.backlog", "tiny-k4.brisk", "tiny-sq.drip"])
def test_a_planted_fault_makes_the_run_incorrect(bench_dir, workload, fault):
    res = _run(bench_dir, workload, hook=faults.FAULTS[fault])["result"]
    assert not res["correct"]
