"""CPU checks of the benchmark's arithmetic, trace reduction, check and
generator; none of them builds the service or compiles a device step.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cell  # noqa: E402
import devtrace  # noqa: E402
import gen  # noqa: E402
import kernels  # noqa: E402
import reference  # noqa: E402
import stats  # noqa: E402

TINY_TRACE = os.path.join(BENCH, "testdata", "tiny_trace.xplane.pb")


# ------------------------------------------------------------------ arithmetic

def test_update_rate_ends_on_the_last_commit():
    commits = [(10.5, 128), (11.5, 128), (12.5, 64)]
    assert stats.update_rate(10.0, commits) == pytest.approx(320 / 2.5)
    with pytest.raises(ValueError):
        stats.update_rate(10.0, [])


@pytest.mark.parametrize("q,want", [(50, 2.5), (95, 3.85), (0, 1.0), (100, 4.0)])
def test_percentile_interpolates_between_ranks(q, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_spread_is_the_quartile_distance_over_the_median():
    v = [100.0, 101.0, 99.0, 100.5, 99.5, 100.0]
    q = [99.375, 100.0, 100.625]     # statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q[2] - q[0]) / 100.0)


# --------------------------------------------------------------------- traces

@pytest.fixture(scope="module")
def tiny():
    """A TPU v5e trace of three small jitted calls under ``bench.*`` spans."""
    return devtrace.reduce_trace(TINY_TRACE, window_span="bench.traced")


def test_trace_busy_and_idle(tiny):
    assert tiny["devices"] == 1
    assert tiny["window_s"] == pytest.approx(0.019658391)
    assert tiny["busy_s"] == pytest.approx(2.8838e-05)
    # every idle nanosecond of the window lies under some span or "other"
    assert sum(tiny["idle_by_span"].values()) == pytest.approx(
        tiny["window_s"] - tiny["busy_s"])
    assert tiny["idle_by_span"]["bench.advance"] == pytest.approx(0.00202534)


def test_trace_device_ops_and_breakdown(tiny):
    top = devtrace.top_ops(tiny["ops"])
    assert top[0][0] == "member_probe_pallas.1"
    assert top[0][1] == pytest.approx(2.5656e-05)
    bd = devtrace.breakdown(tiny)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["idle_gaps"][0][0] == "bench.generate"


def test_top_ops_leaves_out_control_flow():
    ops = {"%while.3 = (s32[]) while(s32[] %a)": [5.0, 1],
           "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %b)": [2.0, 4]}
    assert devtrace.top_ops(ops) == [["fusion.1", 2.0]]


def test_member_probe_roofline_reads_the_operation_bytes(tiny):
    run = cell.Run(workload="x", seed=0, seconds=1.0, arrivals="closed",
                   trace=tiny, device_kind="TPU v5 lite")
    read = cell.load_reader("member_probe_roofline.backlog")
    text = [t for t in tiny["ops"] if t.startswith("%member_probe")][0]
    secs, calls = tiny["ops"][text]
    least = kernels.member_probe_bytes(text) * calls / 819e9
    assert read(run) == pytest.approx(100 * least / secs)
    assert 0 < read(run) < 100


def test_member_probe_bytes_count_queries_table_and_result():
    text = ("%member_probe_pallas.2 = s32[8000,128]{1,0:T(8,128)S(1)} custom-call("
            "s32[8000,128]{1,0:T(8,128)} %r0, s32[8000,128]{1,0:T(8,128)} %r1, "
            "s32[1792,128]{1,0:T(8,128)S(1)} %p1, s32[1792,128]{1,0:T(8,128)S(1)} %p0), "
            "custom_call_target=\"tpu_custom_call\"")
    n_q, n_t = 8000 * 128, 1792 * 128
    assert kernels.member_probe_bytes(text) == 8 * n_q + 8 * n_t + n_q


def test_unknown_device_has_no_peaks():
    assert kernels.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        kernels.peaks("cpu")


# ---------------------------------------------------------------------- check

TRIANGLE = cell.load_patterns(["q2_triangle"])


def _triangle_run():
    """K4 on vertices 0..3 plus a pendant edge, and two batches of ops."""
    edges = np.array([[0, 1], [0, 2], [0, 3], [1, 2], [1, 3], [2, 3], [3, 4]])
    c = lambda u, v: (min(u, v) << 32) | max(u, v)  # noqa: E731
    journal = [np.array([-c(2, 3) - 1, c(2, 4)]), np.array([-c(0, 1) - 1])]
    commits = [{"hi": 2, "counts": {"q2_triangle": 2}},
               {"hi": 3, "counts": {"q2_triangle": 0}}]
    held = {c(0, 2), c(0, 3), c(1, 2), c(1, 3), c(3, 4), c(2, 4)}
    return edges, journal, commits, held


def test_compare_accepts_a_sound_run():
    edges, journal, commits, held = _triangle_run()
    assert cell.compare(5, edges, journal, {"q2_triangle": 4}, commits,
                        TRIANGLE, held) == (0, 0)


def test_compare_fails_on_a_wrong_count():
    edges, journal, commits, held = _triangle_run()
    commits[0]["counts"]["q2_triangle"] = 3
    assert cell.compare(5, edges, journal, {"q2_triangle": 4}, commits,
                        TRIANGLE, held)[0] == 1


def test_compare_fails_on_a_dropped_op():
    edges, journal, commits, held = _triangle_run()
    held.add((0 << 32) | 1)               # the deletion of (0, 1) never landed
    commits[1]["counts"]["q2_triangle"] = 2
    assert cell.compare(5, edges, journal, {"q2_triangle": 4}, commits,
                        TRIANGLE, held) == (1, 1)


def test_reference_counts_a_clique():
    k5 = np.array([[i, j] for i in range(5) for j in range(i + 1, 5)])
    shapes = cell.load_patterns(["q2_triangle", "q4_clique4"])
    assert reference.Counter(5, k5, shapes).counts == {"q2_triangle": 10, "q4_clique4": 5}


LIBRARY = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "patterns")))


def _subgraphs(n, edges, shape):
    """Distinct edge sets isomorphic to ``shape``, by networkx."""
    import networkx as nx

    g, p = nx.Graph(), nx.Graph(shape.edges)
    g.add_nodes_from(range(n))
    g.add_edges_from(edges.tolist())
    found = set()
    for m in nx.algorithms.isomorphism.GraphMatcher(g, p).subgraph_monomorphisms_iter():
        inv = {b: a for a, b in m.items()}
        found.add(frozenset((min(inv[a], inv[b]), max(inv[a], inv[b])) for a, b in p.edges()))
    return len(found)


@pytest.mark.parametrize("name", LIBRARY)
def test_reference_counts_every_pattern_op_by_op(name):
    """Counts of each pattern file match a brute-force count, from scratch
    and after inserts and deletes applied one at a time."""
    shape = cell.load_patterns([name])[name]
    n, edges = gen.config_graph({"graph": {"log2_vertices": 5, "draws": 140, "graph_seed": 2,
                                           "a": 0.45, "b": 0.22, "c": 0.22}})
    ctr = reference.Counter(n, edges, {name: shape})
    assert ctr.counts[name] == _subgraphs(n, edges, shape)
    st = gen.OpStream(n, edges, 12, np.random.default_rng(5))
    present = set(gen.codes_of(edges).tolist())
    for _ in range(2):
        kinds, codes = st.next_block()
        for k, c in zip(kinds.tolist(), codes.tolist()):
            (ctr.insert if k > 0 else ctr.delete)(c >> 32, c & 0xFFFFFFFF)
            (present.add if k > 0 else present.discard)(c)
        now = np.array([[c >> 32, c & 0xFFFFFFFF] for c in sorted(present)])
        assert ctr.counts[name] == _subgraphs(n, now, shape)


@pytest.mark.parametrize("name", LIBRARY)
def test_pattern_files_match_the_program_patterns(name):
    from repro.core.pattern import PATTERN_LIBRARY

    shape = cell.load_patterns([name])[name]
    assert reference.PatternShape(list(PATTERN_LIBRARY[name].edges)).key() == shape.key()


# ------------------------------------------------------------------ generator

def test_open_loop_schedule_is_fixed_by_the_seed():
    due_times = cell.load_arrivals("poisson")
    a = due_times({}, 80.0, 51.0, np.random.default_rng(7))
    b = due_times({}, 80.0, 51.0, np.random.default_rng(7))
    c = due_times({}, 80.0, 51.0, np.random.default_rng(8))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    # same arrivals in another order: same gaps, same count
    assert np.allclose(np.sort(np.diff(a)), np.sort(np.diff(c)), atol=1e-9) or \
        abs(a.size - c.size) <= 1
    assert abs(a.size - 80 * 51) <= 1 and np.all(np.diff(a) > 0) and a[-1] < 51.0


def test_a_rate_profile_shapes_the_arrivals():
    """Bursts as data: a profile of 3x and 1x in turn offers three times
    the ops in its busy halves, at the same mean rate."""
    due_times = cell.load_arrivals("poisson")
    traffic = {"profile": [[2.0, 3.0], [2.0, 1.0]]}
    a = due_times(traffic, 50.0, 40.0, np.random.default_rng(7))
    assert np.array_equal(a, due_times(traffic, 50.0, 40.0, np.random.default_rng(7)))
    busy = np.count_nonzero((a % 4.0) < 2.0)
    assert abs(a.size - 2000) <= 1 and abs(busy / (a.size - busy) - 3.0) < 0.05
    assert np.all(np.diff(a) >= 0) and a[-1] < 40.0


def test_degree_skewed_inserts_land_on_hubs():
    n, edges = gen.config_graph({"graph": {"log2_vertices": 8, "draws": 1500, "graph_seed": 1,
                                           "a": 0.57, "b": 0.19, "c": 0.19}})
    deg = np.bincount(edges.ravel(), minlength=n)
    hubs = set(np.argsort(deg)[-n // 16:].tolist())
    share = {}
    for law in ("uniform", "degree"):
        st = gen.OpStream(n, edges, 64, np.random.default_rng(3), 0.5, law)
        ends = []
        for _ in range(10):
            kinds, codes = st.next_block()
            a = codes[kinds > 0]
            ends += (a >> 32).tolist() + (a & 0xFFFFFFFF).tolist()
        share[law] = np.mean([e in hubs for e in ends])
    assert share["degree"] > 3 * share["uniform"]


def test_op_stream_is_fixed_by_the_seed_and_well_formed():
    cfg = {"graph": {"log2_vertices": 8, "draws": 1500, "graph_seed": 1,
                     "a": 0.57, "b": 0.19, "c": 0.19}}
    n, edges = gen.config_graph(cfg)
    blocks = {}
    for s in (3, 3, 4):
        st = gen.OpStream(n, edges, 16, np.random.default_rng(s))
        blocks.setdefault(s, []).append([st.next_block() for _ in range(20)])
    assert all(np.array_equal(x[1], y[1]) for x, y in zip(*blocks[3]))
    assert not all(np.array_equal(x[1], y[1]) for x, y in zip(blocks[3][0], blocks[4][0]))
    present = set(gen.codes_of(edges).tolist())
    for kinds, codes in blocks[3][0]:
        assert np.count_nonzero(kinds == gen.OP_DELETE) == 8
        d, a = set(codes[kinds < 0].tolist()), set(codes[kinds > 0].tolist())
        assert d <= present and not (a & present) and len(d) == 8 and len(a) == 8
        present = (present - d) | a


def test_configuration_graphs_keep_the_published_degree():
    for name in ("wg-tri", "wt-k4"):
        cfg = cell.load_json(os.path.join(BENCH, "configs", name + ".json"))
        n, edges = gen.config_graph(cfg)
        assert n == cfg["vertices"] and edges.shape == (cfg["edges"], 2)
        assert abs(2 * edges.shape[0] / n - cfg["published"]["average_degree"]) < 0.05
        assert np.all(edges[:, 0] < edges[:, 1])


@pytest.mark.parametrize("name", ["wg-tri", "wt-k4"])
def test_configuration_graphs_have_the_shape_they_state(name):
    """``stand_in`` is the graph as made; each statistic ``held`` lies
    within a tenth of the published figure, each other one does not."""
    cfg = cell.load_json(os.path.join(BENCH, "configs", name + ".json"))
    got = gen.graph_statistics(*gen.config_graph(cfg))
    assert got == pytest.approx(cfg["stand_in"], abs=5e-5)
    pub = cfg["published"]
    for key in ("average_degree", "average_clustering", "triangles_per_edge"):
        near = abs(got[key] - pub[key]) <= 0.1 * pub[key]
        assert near == (key in cfg["held"]), key


# -------------------------------------------------------------- the benchmark

def test_every_metric_cell_and_file_is_found_by_name():
    bench = cell.load_benchmark()
    for w in bench["workloads"]:
        _, cfg_file, traffic_file = cell.find_cell(bench, w["name"])
        assert os.path.exists(cfg_file) and os.path.exists(traffic_file)
        e2e = {m["name"] for m in cell.cell_metrics(bench, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cell.cell_metrics(bench, w["name"], True)
        assert layer and all(m["moves"] in e2e for m in layer)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] != "setup_s":
            assert callable(cell.load_reader(m["name"]))


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "wt-k4.backlog", "--seed", "3000000001",
                        "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not a TPU" in r.stderr


def test_data_driven_names_resolve_from_a_second_directory(tmp_path):
    """A later cell adds a configuration, a traffic mix and a metric as
    files and entries only; the harness finds them by name."""
    for kind in ("traffic", "metrics", "arrivals", "graphs", "patterns"):
        (tmp_path / kind).mkdir()
    (tmp_path / "arrivals" / "even.py").write_text(
        "import numpy as np\n\n\ndef due_times(traffic, rate, seconds, rng):\n"
        "    return np.arange(0.5, rate * seconds) / rate\n")
    (tmp_path / "graphs" / "ring.py").write_text(
        "import numpy as np\n\n\ndef graph(g):\n    n = g['vertices']\n"
        "    return n, np.stack([np.arange(n - 1), np.arange(1, n)], 1)\n")
    (tmp_path / "patterns" / "path3.json").write_text(json.dumps({"edges": [[0, 1], [1, 2]]}))
    (tmp_path / "traffic" / "trickle.json").write_text(json.dumps(
        {"arrivals": "poisson", "load": 0.1, "delete_share": 0.5}))
    (tmp_path / "metrics" / "ops_total.py").write_text(
        "def read(run):\n    return float(sum(b['ops'] for b in run.batches))\n")
    cfg = tmp_path / "dummy.json"
    cfg.write_text("{}")
    bench = {"configs": [{"name": "dummy", "file": str(cfg)}],
             "workloads": [{"name": "dummy.trickle", "config": "dummy",
                            "traffic": "trickle", "chips": 1}],
             "end_to_end": [{"name": "setup_s", "unit": "s"},
                            {"name": "ops_total", "unit": "ops"}],
             "per_layer": []}
    w, cfg_file, traffic_file = cell.find_cell(bench, "dummy.trickle", str(tmp_path))
    assert cfg_file == str(cfg) and traffic_file.endswith("trickle.json")
    assert cell.load_arrivals("even", str(tmp_path))({}, 2.0, 3.0, None).tolist() == \
        [0.25, 0.75, 1.25, 1.75, 2.25, 2.75]
    n, ring = gen.config_graph({"graph": {"generator": "ring", "vertices": 6}}, str(tmp_path))
    shapes = cell.load_patterns(["path3"], str(tmp_path))
    assert reference.Counter(n, ring, shapes).counts == {"path3": 4}
    read = cell.load_reader("ops_total", str(tmp_path))
    run = cell.Run(workload="dummy.trickle", seed=0, seconds=1.0, arrivals="poisson",
                   batches=[{"ops": 3}, {"ops": 4}])
    assert read(run) == 7.0
