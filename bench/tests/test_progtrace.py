"""CPU checks of the split of device time by named scope and of device
idle time by the service's spans (``bench/progtrace.py``).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import cell  # noqa: E402
import progtrace  # noqa: E402
from test_faults import _run, bench_dir  # noqa: E402,F401  (the tiny cells)

TINY_TRACE = os.path.join(BENCH, "testdata", "tiny_trace.xplane.pb")
PROGRAM = 3585353184268332993       # the tiny trace's one module
MS = 1_000_000


@pytest.mark.parametrize("stack,scope", [
    ("jit(body)/storage/patch/jit(member_probe_pallas)/pallas_call:", "storage/patch"),
    ("jit(body)/storage/candidates/sort:", "storage/candidates"),
    ("jit(body)/storage/membership/and:", "storage/membership"),
    ("jit(body)/maintain/delete_table/sort:", "maintain/delete_table"),
    ("jit(body)/maintain/seed_mask/psum:", "maintain/seed_mask"),
    ("jit(body)/maintain/tri/refresh/cond/branch_1_fun/add:", "maintain/tri/refresh"),
    ("jit(body)/maintain/q4_clique4/patch/while/body/gather:", "maintain/q4_clique4/patch"),
    ("jit(body)/maintain/tri/store", "maintain/tri/store"),
    ("jit(body)/maintain/tri/storey/add:", None),
    ("jit(<lambda>)/jit(member_probe_pallas)/reshape:", None),
    (None, None),
])
def test_scope_of_reads_the_named_scope_from_the_name_stack(stack, scope):
    assert progtrace.scope_of(stack) == scope


def test_scope_table_sums_device_time_and_leaves_out_control_flow():
    ops = [
        (0, 4 * MS, "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a)", "storage/patch"),
        (4 * MS, 5 * MS, "%fusion.2 = s32[8]{0} fusion(s32[8]{0} %a)", "storage/patch"),
        (5 * MS, 30 * MS, "%while.3 = (s32[]) while(s32[] %b)", "maintain/tri/patch"),
        (6 * MS, 8 * MS, "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %c)", "maintain/tri/patch"),
        (8 * MS, 9 * MS, "%copy.4 = s32[8]{0} copy(s32[8]{0} %d)", None),
        (40 * MS, 41 * MS, "%fusion.9 = s32[8]{0} fusion(s32[8]{0} %e)", "maintain/tri/store"),
    ]
    table = progtrace.scope_table(ops, 0, 20 * MS)
    assert table == {"storage/patch": [pytest.approx(0.005), 2],
                     "maintain/tri/patch": [pytest.approx(0.002), 1],
                     progtrace.UNSCOPED: [pytest.approx(0.001), 1]}


def test_idle_goes_to_the_innermost_service_span():
    # Host: bench.ingest [0, 10); bench.advance [10, 100) holding
    # service.batch [12, 98), which holds service.storage_update
    # [14, 40), service.maintain_mega [40, 80) (holding service.maintain
    # [70, 80)), service.mirror [80, 90) and service.graph_stats [90, 95).
    spans = [(0, 10 * MS, "bench.ingest"), (10 * MS, 100 * MS, "bench.advance"),
             (12 * MS, 98 * MS, "service.batch"),
             (14 * MS, 40 * MS, "service.storage_update"),
             (40 * MS, 80 * MS, "service.maintain_mega"),
             (70 * MS, 80 * MS, "service.maintain"),
             (80 * MS, 90 * MS, "service.mirror"),
             (90 * MS, 95 * MS, "service.graph_stats")]
    # Device busy [16, 38) and [42, 68): idle [0, 16), [38, 42), [68, 110).
    busy = [[(16 * MS, 38 * MS), (42 * MS, 68 * MS)]]
    idle = progtrace.idle_by_program_span(busy, spans, 0, 110 * MS)
    want = {"bench.ingest": 10, "bench.advance": 2 + 2,   # [10, 12) + [98, 100)
            "service.batch": 2 + 3,                        # [12, 14) + [95, 98)
            "service.storage_update": 2 + 2,               # [14, 16) + [38, 40)
            "service.maintain_mega": 2 + 2,                # [40, 42) + [68, 70)
            "service.maintain": 10, "service.mirror": 10,
            "service.graph_stats": 5, "other": 10}         # [100, 110)
    assert idle == {k: pytest.approx(v / 1e3) for k, v in want.items()}
    assert sum(idle.values()) == pytest.approx(0.110 - 0.022 - 0.026)


def test_idle_splits_per_device_and_clips_spans_to_the_window():
    spans = [(0, 50 * MS, "bench.advance"), (5 * MS, 50 * MS, "service.batch")]
    busy = [[(10 * MS, 20 * MS)], [(10 * MS, 30 * MS)]]
    idle = progtrace.idle_by_program_span(busy, spans, 8 * MS, 40 * MS)
    assert idle == {"service.batch": pytest.approx((2 + 20 + 2 + 10) / 1e3)}


def test_span_seconds_adds_the_named_spans_in_the_window():
    spans = [(0, 10 * MS, "service.mirror"), (20 * MS, 25 * MS, "service.graph_stats"),
             (30 * MS, 60 * MS, "service.mirror"), (0, 60 * MS, "service.batch")]
    assert progtrace.span_seconds(
        spans, ["service.mirror", "service.graph_stats"], 5 * MS, 40 * MS) == \
        pytest.approx((5 + 5 + 10) / 1e3)


def test_xla_op_names_reads_the_tf_op_stat_of_each_module():
    names = progtrace.xla_op_names(TINY_TRACE)
    kernel = [(p, t) for p, t in names if t.startswith("%member_probe_pallas.1 ")]
    assert kernel and kernel[0][0] == PROGRAM
    assert names[kernel[0]] == "jit(<lambda>)/jit(member_probe_pallas)/pallas_call:"
    assert {p for p, _ in names} == {PROGRAM}


def test_a_trace_without_scopes_is_all_unscoped():
    red = progtrace.reduce_program_trace(TINY_TRACE, window_span="bench.traced")
    assert set(red["scopes"]) == {progtrace.UNSCOPED}
    assert red["top_ops"][progtrace.UNSCOPED][0][0] == "member_probe_pallas.1"
    assert red["unscoped_by_step"] == {"other": pytest.approx(
        red["scopes"][progtrace.UNSCOPED][0])}
    # no service span in the trace: the idle time lies under bench spans
    assert red["span_s"] == {}
    assert red["idle_by_program_span"]["bench.advance"] == pytest.approx(0.00202534)


def test_scope_readers_report_nothing_for_a_program_without_scopes(tmp_path, monkeypatch):
    """A program whose steps carry no named scope (as before they had
    them) gives the scope metrics no reading, and no error."""
    trace_dir = tmp_path / "trace" / "x"
    profile = trace_dir / "plugins" / "profile" / "1"
    profile.mkdir(parents=True)
    shutil.copy(TINY_TRACE, profile / "t.xplane.pb")
    monkeypatch.setattr(cell, "ARTIFACTS", str(tmp_path))
    run = cell.Run(workload="x", seed=0, seconds=1.0, arrivals="closed",
                   trace={}, batches=[{}, {}])
    for name in ("maintain_refresh_ms.backlog", "maintain_patch_ms.backlog",
                 "maintain_store_ms.backlog", "storage_patch_ms.backlog"):
        assert cell.load_reader(name)(run) is None
    run.trace = None
    assert progtrace.for_run(run) is None


def test_scope_ms_per_batch_is_the_window_mean(monkeypatch):
    red = {"scopes": {"maintain/tri/patch": [0.3, 9], "maintain/sq/patch": [0.1, 9],
                      "maintain/tri/store": [0.5, 9], progtrace.UNSCOPED: [0.2, 4]}}
    monkeypatch.setattr(progtrace, "for_run", lambda run: red)
    run = cell.Run(workload="x", seed=0, seconds=1.0, arrivals="closed",
                   trace={}, batches=[{}, {}, {}, {}])
    assert progtrace.scope_ms_per_batch(run, "maintain/*/patch") == pytest.approx(100.0)
    assert cell.load_reader("maintain_store_ms.backlog")(run) == pytest.approx(125.0)
    assert cell.load_reader("maintain_refresh_ms.backlog")(run) is None
    run.arrivals = "poisson"
    assert progtrace.scope_ms_per_batch(run, "maintain/*/patch") is None


def test_summary_reports_the_stage_share_of_the_service_idle_time():
    red = {"scopes": {"storage/patch": [0.9, 3], progtrace.UNSCOPED: [0.1, 2]},
           "top_ops": {"storage/patch": [["fusion.7", 0.5]]},
           "unscoped_by_step": {"storage": 0.1},
           "span_s": {"service.mirror": 0.02},
           "idle_by_program_span": {"service.batch": 0.01, "service.mirror": 0.06,
                                    "bench.advance": 0.01, "bench.ingest": 0.5}}
    s = progtrace.summary(red, 2)
    assert s["unscoped_share"] == {"storage": pytest.approx(0.1)}
    assert s["top_ops_ms_per_batch"] == {"storage/patch": [["fusion.7", pytest.approx(250.0)]]}
    assert s["span_ms_per_batch"] == {"service.mirror": pytest.approx(10.0)}
    assert s["idle_in_stage_spans"] == pytest.approx(0.75)


def test_a_run_with_the_service_tracer_on_stays_correct(bench_dir):
    """The hook of the script's traced runs: a whole tiny run on the CPU
    with every service span opened, as in a traced run, is correct."""
    services = []

    def hook(svc):
        progtrace.tracer_on(svc)
        services.append(svc)

    res = _run(bench_dir, "tiny-tri.backlog", hook=hook)["result"]
    assert res["correct"], res["limits"]
    roots = services[0].obs.tracer.roots
    assert roots and all(r.name == "batch" for r in roots)
    names = {c.name for r in roots for c in r.children}
    assert {"storage_update", "maintain_mega", "mirror", "graph_stats"} <= names
