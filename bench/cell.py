"""One run of one benchmark cell: set-up, the measured window, the check.

A cell is a configuration (``bench/configs/<name>.json``: the graph, the
subscribed patterns and the engine's caps) under a traffic mix
(``bench/traffic/<name>.json``: closed-loop backlog, or open-loop arrivals
by the law it names). Graph generators, arrival laws, patterns and metric
readers are files found by name: ``bench/graphs/<generator>.py``,
``bench/arrivals/<law>.py``, ``bench/patterns/<name>.json`` and
``bench/metrics/<name>.py``. Everything runs through
``ListingService(backend="sharded")``: ``ingest()`` then ``advance()``,
which runs journal → scheduler → storage step → maintain megastep → sinks.

Nothing of the check runs inside the window: the counts every commit
reports are recorded, and compared with the host reference once the
window has closed and the backlog has drained.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
ARTIFACTS = os.path.join(ROOT, "bench_artifacts")
WARMUP_BATCHES = 2

if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import gen        # noqa: E402
import reference  # noqa: E402


class CellError(RuntimeError):
    """The cell cannot run here (no chip, unknown name, broken file)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding things by name
# ---------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, workload: str, bench_dir: str = BENCH):
    """``(cell, config_file, traffic_file)`` of ``workload`` in ``bench``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_file = os.path.join(ROOT, configs[cell["config"]]["file"])
    traffic_file = os.path.join(bench_dir, "traffic", cell["traffic"] + ".json")
    return cell, cfg_file, traffic_file


def cell_metrics(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced."""
    if not trace:
        return [m for m in bench["end_to_end"]
                if "workloads" not in m or workload in m["workloads"]]
    e2e = {m["name"] for m in cell_metrics(bench, workload, False)}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def load_reader(name: str, bench_dir: str = BENCH) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(run) -> float | None``."""
    try:
        return gen.load_named("metrics", name, bench_dir).read
    except LookupError as e:
        raise CellError(str(e)) from None


def load_arrivals(name: str, bench_dir: str = BENCH) -> Callable:
    """``bench/arrivals/<name>.py``'s ``due_times(traffic, rate, seconds, rng)``."""
    try:
        return gen.load_named("arrivals", name, bench_dir).due_times
    except LookupError as e:
        raise CellError(str(e)) from None


def load_patterns(names, bench_dir: str = BENCH) -> Dict[str, reference.PatternShape]:
    """Each pattern's shape from ``bench/patterns/<name>.json``."""
    shapes = {}
    for name in names:
        path = os.path.join(bench_dir, "patterns", name + ".json")
        if not os.path.exists(path):
            raise CellError(f"no pattern file for {name!r} at {path}")
        shapes[name] = reference.PatternShape(load_json(path)["edges"])
    return shapes


# ---------------------------------------------------------------------------
# The device
# ---------------------------------------------------------------------------

def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise CellError(f"JAX runs on {d.platform!r}, not a TPU: refusing to measure")
    if len(devs) < chips:
        raise CellError(f"the cell needs {chips} chip(s), JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class CompileCounter:
    """Counts executables JAX compiles or loads from its cache."""

    def __init__(self):
        import jax

        from jax._src import dispatch

        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == self.event:
            self.count += 1


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a run recorded; the metric readers read it."""

    workload: str
    seed: int
    seconds: float
    arrivals: str                       # "closed", or the open-loop law
    window_start: float = 0.0
    batches: List[dict] = dataclasses.field(default_factory=list)   # window batches
    op_due: Optional[np.ndarray] = None      # open loop: due time per window op
    op_start: Optional[np.ndarray] = None    # start of the op's batch
    op_commit: Optional[np.ndarray] = None   # its commit (sink event)
    register: Dict[str, float] = dataclasses.field(default_factory=dict)
    window_compiles: int = 0
    setup_s: float = 0.0
    trace: Optional[dict] = None
    device_kind: str = ""
    generator: Dict[str, float] = dataclasses.field(default_factory=dict)


class Recorder:
    """Sink callback: the time and counts of every commit."""

    def __init__(self):
        self.commits: Dict[int, dict] = {}     # batch_index → record

    def __call__(self, ev) -> None:
        rec = self.commits.get(ev.batch_index)
        if rec is None:
            rec = self.commits[ev.batch_index] = {
                "t": time.perf_counter(), "lo": ev.lo, "hi": ev.hi, "counts": {}}
        rec["counts"][ev.pattern] = ev.count_after


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def build_service(cfg: dict, n: int, edges: np.ndarray):
    """The configuration's sharded service over ``(n, edges)``."""
    import jax

    from repro.core.graph import Graph
    from repro.dist.jax_engine import EngineCaps
    from repro.stream import BatchScheduler, ListingService

    graph = Graph.from_edges(edges, n=n)
    cap = cfg["batch_ops"]
    svc = ListingService(
        graph, backend="sharded",
        caps=EngineCaps(**cfg["caps"]),
        max_add=cap, max_del=cap,
        scheduler=BatchScheduler(**cfg["scheduler"]))
    if svc.backend.m != jax.device_count():
        raise CellError(f"service spans {svc.backend.m} devices, JAX sees {jax.device_count()}")
    return svc


def device_edges(svc) -> set:
    """The edge set the device holds, read back from its partitions."""
    pt = svc.backend.pt
    hi = np.asarray(pt.edge_hi).reshape(-1).astype(np.int64)
    lo = np.asarray(pt.edge_lo).reshape(-1).astype(np.int64)
    keep = (hi >= 0) & (lo >= 0)
    return set(((hi[keep] << 32) | lo[keep]).tolist())


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, bench: Optional[dict] = None,
             bench_dir: str = BENCH, require_tpu: bool = True,
             hook: Optional[Callable] = None,
             traffic: Optional[dict] = None) -> dict:
    """One run: ``{"result": <the result line>, "report": <for stderr>,
    "run": Run}``.

    ``hook(svc)``, when given, is applied to the built service before the
    warm-up: the controls and fault tests plant their fault there.
    ``traffic`` replaces the cell's traffic file (the knee sweep's rates).
    """
    bench = bench if bench is not None else load_benchmark()
    cell, cfg_file, traffic_file = find_cell(bench, workload, bench_dir)
    cfg = load_json(cfg_file)
    traffic = traffic if traffic is not None else load_json(traffic_file)
    shapes = load_patterns(cfg["patterns"], bench_dir)
    metrics = cell_metrics(bench, workload, trace)
    e2e = cell_metrics(bench, workload, False)
    readers = {m["name"]: load_reader(m["name"], bench_dir) for m in metrics + e2e
               if m["name"] != "setup_s"}
    setup: Dict[str, float] = {}

    t = time.perf_counter()
    import jax

    import repro

    repro.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    info = device_info(cell["chips"], require_tpu)
    compiles = CompileCounter()
    setup["jax_init"] = time.perf_counter() - t

    # The configuration's graph, and the traffic from the seed.
    t = time.perf_counter()
    rng_ops, rng_due = (np.random.default_rng(s) for s in
                        np.random.SeedSequence(seed).spawn(2))
    n, edges = gen.config_graph(cfg, bench_dir)
    cap = cfg["batch_ops"]
    stream = gen.OpStream(n, edges, cap, rng_ops, traffic["delete_share"],
                          traffic.get("insert_endpoints", "uniform"))
    arrivals = traffic["arrivals"]
    if arrivals == "closed":
        due = None
        # Enough blocks for the window at four times the knee, plus the
        # warm-up and the lead; more are made if a faster program needs them.
        n_blocks = WARMUP_BATCHES + traffic["lead_batches"] + 1 + int(
            np.ceil(4 * cfg["knee_ops_s"] * seconds / cap))
    else:
        due = load_arrivals(arrivals, bench_dir)(
            traffic, traffic["load"] * cfg["knee_ops_s"], seconds, rng_due)
        n_blocks = WARMUP_BATCHES + int(np.ceil(due.size / cap)) + 1
    blocks = [stream.next_block() for _ in range(n_blocks)]
    setup["graph"] = time.perf_counter() - t

    # Service and subscriptions.
    t = time.perf_counter()
    from repro.core.pattern import PATTERN_LIBRARY
    from repro.stream import CallbackSink

    svc = build_service(cfg, n, edges)
    rec = Recorder()
    svc.subscribe(CallbackSink(rec))
    setup["service"] = time.perf_counter() - t

    prof = svc.obs.jaxprof
    t = time.perf_counter()
    c0 = sum(s.compile_seconds for s in prof.steps.values())
    x0 = sum(s.execute_seconds for s in prof.steps.values())
    initial: Dict[str, int] = {}
    for name, executor in cfg["patterns"].items():
        # The reference counts the pattern of the data file; the service
        # has to list the same one.
        if reference.PatternShape(list(PATTERN_LIBRARY[name].edges)).key() != shapes[name].key():
            raise CellError(f"pattern {name!r} of the program differs from "
                            f"bench/patterns/{name}.json")
        svc.backend.executor = executor      # read by the plan compiler at register
        initial[name] = svc.register(name, PATTERN_LIBRARY[name])
        if svc.backend.plan(name).executor != executor:
            raise CellError(f"{name} planned for {svc.backend.plan(name).executor}, "
                            f"not {executor}")
    reg_wall = time.perf_counter() - t
    reg_compile = sum(s.compile_seconds for s in prof.steps.values()) - c0
    reg_device = sum(s.execute_seconds for s in prof.steps.values()) - x0
    setup["register"] = reg_wall
    if hook is not None:
        hook(svc)

    # The journal as the benchmark fed it: (kind, code) in sequence order.
    journal: List[np.ndarray] = []
    feed = {"block": 0}

    def ingest(kinds: np.ndarray, codes: np.ndarray) -> None:
        d = codes[kinds == gen.OP_DELETE]
        a = codes[kinds == gen.OP_ADD]
        svc.ingest(delete=np.stack([d >> 32, d & 0xFFFFFFFF], 1),
                   add=np.stack([a >> 32, a & 0xFFFFFFFF], 1))
        # The journal appends an update's deletions first.
        journal.append(np.concatenate([-d - 1, a]))

    def next_block():
        if feed["block"] == len(blocks):
            blocks.append(stream.next_block())
        b = blocks[feed["block"]]
        feed["block"] += 1
        return b

    all_batches: List[dict] = []

    def one_batch(pending: int) -> None:
        k = svc.scheduler.next_batch_size(pending)
        t0 = time.perf_counter()
        with _annotate("bench.advance"):
            done = svc.advance(svc.committed_watermark + k)
        t1 = time.perf_counter()
        steps = prof.steps
        b = {"start": t0, "end": t1, "ops": sum(bm.n_ops for bm in done),
             "overflow": sum(bm.overflow + bm.storage_overflow for bm in done),
             "storage_s": steps["storage_update"].last_execute_s,
             "maintain_s": steps["maintain_mega"].last_execute_s,
             "hi": svc.committed_watermark}
        b["commit"] = rec.commits[done[-1].batch_index]["t"] if done else t1
        all_batches.append(b)

    # Warm-up: the cell's own shapes (full padded batches of the one
    # storage step and the one megastep) compile or load here.
    t = time.perf_counter()
    for _ in range(WARMUP_BATCHES):
        ingest(*next_block())
        one_batch(svc.journal.tail - svc.committed_watermark)
    setup["warmup"] = time.perf_counter() - t
    warm_batches = len(all_batches)

    trace_dir = None
    if trace:
        trace_dir = os.path.join(ARTIFACTS, "trace", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    compiles_before = compiles.count
    run = Run(workload=workload, seed=seed, seconds=seconds, arrivals=arrivals,
              device_kind=info["kind"])
    setup_s = time.perf_counter() - t_process

    # ---------------------------------------------------------------- window
    window = _annotate("bench.window")
    window.__enter__()
    w0 = time.perf_counter()
    run.window_start = w0
    if arrivals == "closed":
        lead = traffic["lead_batches"] * cap
        while time.perf_counter() - w0 < seconds:
            with _annotate("bench.ingest"):
                while svc.journal.tail - svc.committed_watermark < lead:
                    ingest(*next_block())
            one_batch(svc.journal.tail - svc.committed_watermark)
        w_end = time.perf_counter()
        window.__exit__(None, None, None)
        window_seq = (all_batches[warm_batches]["hi"] - all_batches[warm_batches]["ops"],
                      svc.committed_watermark)
        due_abs = None
    else:
        # Open loop: the ops due by now are appended, then one batch of
        # what is pending starts; with nothing pending the loop sleeps
        # until the next op is due. Each op is timed from its due time,
        # so a batch that runs long delays the ops due meanwhile.
        due_abs = w0 + due
        op_blocks = [next_block() for _ in range(int(np.ceil(due.size / cap)))]
        kinds = np.concatenate([b[0] for b in op_blocks])[:due.size]
        codes = np.concatenate([b[1] for b in op_blocks])[:due.size]
        first_seq = svc.journal.tail
        seq_of = np.zeros(due.size, np.int64)
        late_after_wait: List[float] = []
        i, woke, backlog_at_last = 0, False, -1
        while True:
            now = time.perf_counter()
            j = int(np.searchsorted(due_abs, now, side="right"))
            if j > i:
                with _annotate("bench.ingest"):
                    s = i
                    while s < j:              # one update per block part
                        e = min(j, (s // cap + 1) * cap)
                        sel = np.arange(s, e)
                        dmask = kinds[sel] == gen.OP_DELETE
                        # the journal gives an update's deletions the
                        # first sequence numbers
                        order = np.concatenate([sel[dmask], sel[~dmask]])
                        seq_of[order] = svc.journal.tail + 1 + np.arange(order.size)
                        ingest(kinds[sel], codes[sel])
                        s = e
                t_in = time.perf_counter()
                if woke:
                    late_after_wait.extend((t_in - due_abs[i:j]).tolist())
                i = j
                if i == due.size:
                    backlog_at_last = svc.journal.tail - svc.committed_watermark
            pending = svc.journal.tail - svc.committed_watermark
            if pending:
                one_batch(pending)
                woke = False
            elif i == due.size:
                break
            else:
                with _annotate("bench.wait"):
                    time.sleep(max(0.0, due_abs[i] - time.perf_counter()))
                woke = True
        w_end = time.perf_counter()
        window.__exit__(None, None, None)
        window_seq = (first_seq, svc.committed_watermark)
        starts = np.array([b["start"] for b in all_batches[warm_batches:]])
        commits = np.array([b["commit"] for b in all_batches[warm_batches:]])
        his = np.array([b["hi"] for b in all_batches[warm_batches:]])
        pos = np.searchsorted(his, seq_of, side="left")   # first batch with hi ≥ seq
        run.op_due, run.op_start, run.op_commit = due_abs, starts[pos], commits[pos]
        run.generator = {
            "late_after_wait_ms_p50": float(np.median(late_after_wait) * 1e3) if late_after_wait else 0.0,
            "late_after_wait_ms_max": float(np.max(late_after_wait) * 1e3) if late_after_wait else 0.0,
            "wakeups": len(late_after_wait),
            "backlog_at_last_arrival": backlog_at_last}
    run.window_compiles = compiles.count - compiles_before
    if trace:
        jax.profiler.stop_trace()
    run.batches = all_batches[warm_batches:]
    run.setup_s = setup_s
    run.register = {"wall_s": reg_wall, "compile_s": reg_compile, "device_s": reg_device}

    # ------------------------------------------------------- after the window
    # Drain what is pending, so every op ingested is committed and checked.
    while svc.journal.tail > svc.committed_watermark:
        one_batch(svc.journal.tail - svc.committed_watermark)
    peak = memory_peak_bytes()
    held = device_edges(svc)
    committed = svc.committed_watermark
    uncommitted = svc.journal.tail - committed
    overflow = sum(b["overflow"] for b in all_batches)
    fallbacks = svc.backend.cap_fallbacks + svc.backend.store_resizes
    del svc

    if trace:
        import devtrace

        t = time.perf_counter()
        run.trace = devtrace.reduce_trace(devtrace.find_xplane(trace_dir))
        log(f"[bench] trace reduced in {time.perf_counter() - t:.3f} s")

    # The check: the reference replays the journal the benchmark fed and
    # counts at every committed watermark.
    t = time.perf_counter()
    mismatches, edge_diff = compare(
        n, edges, journal, initial, list(rec.commits.values()), shapes, held)
    reference_s = time.perf_counter() - t
    limits = {
        "count_mismatches": {"value": int(mismatches), "limit": 0},
        "edge_set_diff": {"value": int(edge_diff), "limit": 0},
        "overflow": {"value": int(overflow), "limit": 0},
        "uncommitted_ops": {"value": int(uncommitted), "limit": 0},
    }
    correct = all(v["value"] <= v["limit"] for v in limits.values())

    # ---------------------------------------------------------------- report
    values: Dict[str, float] = {}
    for m in metrics:
        v = setup_s if m["name"] == "setup_s" else readers[m["name"]](run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(info, memory_peak_bytes=peak)
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    attempted = int(window_seq[1] - window_seq[0]) if due is None else int(due.size)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": int(uncommitted), "metrics": values, "device": device}
    if trace:
        import devtrace

        result["breakdown"] = devtrace.breakdown(run.trace)

    report = {
        "setup": setup, "setup_s": setup_s, "reference_s": reference_s,
        "window_s": w_end - w0, "batches": len(run.batches),
        "window_compiles": run.window_compiles, "recoveries": fallbacks,
        "register": run.register, "initial_counts": initial,
        "generator": run.generator,
        "end_to_end": {m["name"]: (setup_s if m["name"] == "setup_s"
                                   else readers[m["name"]](run)) for m in e2e},
        "freshness_samples": 0 if run.op_due is None else int(run.op_due.size),
    }
    write_batches(workload, seed, trace, report, all_batches, warm_batches, run)
    result["limits"] = limits
    return {"result": result, "report": report, "run": run}


def compare(n: int, edges: np.ndarray, journal: List[np.ndarray],
            initial: Dict[str, int], commits: List[dict],
            patterns: Dict[str, reference.PatternShape], held: set):
    """``(count_mismatches, edge_set_diff)`` of a run against the reference.

    ``journal`` holds the ops in sequence order (an edge code, or ``-code
    - 1`` for a deletion); ``commits`` the counts each commit reported
    (``{"hi": watermark, "counts": {pattern: count}}``); ``held`` the edge
    codes the device holds after the last op. A pattern a commit did not
    report counts as a mismatch.
    """
    ops = np.concatenate(journal) if journal else np.zeros(0, np.int64)
    ref = reference.Counter(n, edges, patterns)
    mismatches = sum(initial.get(p) != ref.counts[p] for p in patterns)
    seq = 0

    def replay(upto):
        for o in ops[seq:upto].tolist():
            code = -o - 1 if o < 0 else o
            (ref.delete if o < 0 else ref.insert)(code >> 32, code & 0xFFFFFFFF)

    for c in sorted(commits, key=lambda r: r["hi"]):
        replay(c["hi"])
        seq = c["hi"]
        mismatches += sum(c["counts"].get(p) != ref.counts[p] for p in patterns)
    replay(ops.size)
    present = {(u << 32) | v for u, nbrs in enumerate(ref.adj) for v in nbrs if u < v}
    return int(mismatches), len(held ^ present)


def write_batches(workload, seed, trace, report, all_batches, warm, run) -> str:
    """Every batch's wall, host and device-step times, to a per-run file."""
    os.makedirs(os.path.join(ARTIFACTS, "batches"), exist_ok=True)
    k = 0
    while True:     # a repeat of a seed gets a file of its own
        path = os.path.join(ARTIFACTS, "batches", f"{workload}.{seed}.{int(trace)}.{k}.jsonl")
        if not os.path.exists(path):
            break
        k += 1
    t0 = run.window_start
    with open(path, "w") as f:
        f.write(json.dumps({"run": report}) + "\n")
        for i, b in enumerate(all_batches):
            phase = "warmup" if i < warm else ("window" if i < warm + len(run.batches) else "drain")
            wall = b["end"] - b["start"]
            f.write(json.dumps({
                "i": i, "phase": phase, "t_s": b["start"] - t0, "ops": b["ops"],
                "wall_s": wall, "storage_s": b["storage_s"], "maintain_s": b["maintain_s"],
                "host_s": wall - b["storage_s"] - b["maintain_s"], "hi": b["hi"]}) + "\n")
        if run.trace is not None:
            f.write(json.dumps({"stalls": run.trace["stalls"],
                                "idle_by_span": run.trace["idle_by_span"]}) + "\n")
    return path
