#!/usr/bin/env python3
"""Device time by named scope, and device idle time by the service's spans.

Reads the same ``.xplane.pb`` as ``devtrace.py``, for two splits it does
not make:

- **Scopes.** The service's two device steps tag their phases with
  ``jax.named_scope``: ``storage/{candidates,membership,patch}`` in the
  storage step, ``maintain/{delete_table,seed_mask}`` and
  ``maintain/<pattern>/{refresh,patch,store}`` in the maintain megastep.
  On a TPU each operation's event metadata in the device plane carries
  the operation's name stack in its ``tf_op`` stat, next to the
  ``program_id`` of its module. ``jax.profiler.ProfileData`` does not
  expose event metadata, so ``xla_op_names`` reads it from the file's
  protobuf wire format itself. An operation is keyed by its module, found
  from the device's ``XLA Modules`` line, and its HLO text: fusion
  numbers repeat across modules.
- **Program spans.** With its tracer enabled the service opens a
  ``jax.profiler.TraceAnnotation`` named ``service.<span>`` for every
  span (``repro.obs.trace``). ``idle_by_program_span`` gives each device
  gap to the innermost ``service.*`` span open over it, else to the
  ``bench.*`` span, else to ``other``.

Run as a script, it runs one cell traced with the service's tracer on
and prints both splits::

    python3 bench/progtrace.py --workload wg-tri.backlog --seed 7 --seconds 51
"""

from __future__ import annotations

import collections
import fnmatch
import functools
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import devtrace

SCOPE_RE = re.compile(
    r"(?:^|/)(storage/(?:candidates|membership|patch)"
    r"|maintain/(?:delete_table|seed_mask)"
    r"|maintain/[^/:]+/(?:refresh|patch|store))(?=[/:]|$)")
UNSCOPED = "unscoped"
_MODULE_ID = re.compile(r"\((\d+)\)$")

Interval = Tuple[int, int]


# ---------------------------------------------------------------------------
# The xplane's event metadata (protobuf wire format)
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int = 0, end: Optional[int] = None):
    """``(field number, value)`` of one message: an int for a varint, a
    ``(start, end)`` slice for a length-delimited field."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} not expected in an xplane")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def xla_op_names(path: str) -> Dict[Tuple[int, str], str]:
    """``{(program_id, hlo_text): name stack}`` of every operation in the
    device planes' event metadata (the ``tf_op`` stat)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[Tuple[int, str], str] = {}
    for num, plane in _fields(buf):
        if num != 1:                                    # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pn, v in _fields(buf, *plane):
            if pn == 2:                                 # XPlane.name
                name = _text(buf, v)
            elif pn == 4:                               # event_metadata entry
                metas.append(v)
            elif pn == 5:                               # stat_metadata entry
                for en, ev in _fields(buf, *v):
                    if en == 2:
                        sid, sname = 0, ""
                        for sn, sv in _fields(buf, *ev):
                            if sn == 1:
                                sid = sv
                            elif sn == 2:
                                sname = _text(buf, sv)
                        stat_names[sid] = sname
        if not name.startswith("/device:") or "CUSTOM" in name:
            continue
        for entry in metas:
            for en, ev in _fields(buf, *entry):
                if en != 2:                             # the XEventMetadata
                    continue
                text, program, tf_op = "", None, None
                for mn, mv in _fields(buf, *ev):
                    if mn == 2:
                        text = _text(buf, mv)
                    elif mn == 5:                       # an XStat
                        sid, val = None, None
                        for xn, xv in _fields(buf, *mv):
                            if xn == 1:
                                sid = xv
                            elif xn in (3, 4):
                                val = xv
                            elif xn == 5:
                                val = _text(buf, xv)
                            elif xn == 7:               # interned string
                                val = stat_names.get(xv, "")
                        stat = stat_names.get(sid)
                        if stat == "program_id":
                            program = int(val)
                        elif stat == "tf_op":
                            tf_op = str(val)
                if tf_op is not None and program is not None:
                    out[(program, text)] = tf_op
    return out


def scope_of(name_stack: Optional[str]) -> Optional[str]:
    """``jit(body)/maintain/tri/patch/while/body/add:`` → ``maintain/tri/patch``."""
    m = SCOPE_RE.search(name_stack) if name_stack else None
    return m.group(1) if m else None


# ---------------------------------------------------------------------------
# The two reductions, on plain event lists
# ---------------------------------------------------------------------------

def _in_window(ops, lo: int, hi: int):
    """``(op, seconds inside [lo, hi))`` of each operation ``(start_ns,
    end_ns, hlo_text, ...)`` that ran there, leaving out control flow:
    its events span the operations of its bodies."""
    for op in ops:
        d = devtrace._overlap(op[0], op[1], lo, hi)
        if d and devtrace.op_name(op[2]).split(".")[0] not in devtrace._CONTAINERS:
            yield op, d / 1e9


def scope_table(ops: Iterable[Tuple[int, int, str, Optional[str]]],
                lo: int, hi: int) -> Dict[str, List[float]]:
    """``{scope: [device_seconds, calls]}`` of the operations
    ``(start_ns, end_ns, hlo_text, scope)`` inside ``[lo, hi)``; those
    with no scope under ``unscoped``."""
    table: Dict[str, List[float]] = {}
    for op, secs in _in_window(ops, lo, hi):
        rec = table.setdefault(op[3] or UNSCOPED, [0.0, 0])
        rec[0] += secs
        rec[1] += 1
    return table


_PRIORITY = {"service": 2, "bench": 1}


def _innermost(spans: Sequence[Tuple[int, int, str]]) -> List[Tuple[int, int, str]]:
    """Cut spans into disjoint pieces, each labelled by the innermost
    span open over it. The spans of one host thread nest; of two with
    the same bounds a ``service.*`` span counts as the inner."""
    pieces: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, int, str]] = []
    t = 0

    def close_until(x):
        nonlocal t
        while stack and stack[-1][1] <= x:
            _, e, name = stack.pop()
            if t < e:
                pieces.append((t, e, name))
                t = e

    for s, e, name in sorted(spans, key=lambda sp: (
            sp[0], -sp[1], _PRIORITY.get(sp[2].split(".")[0], 0))):
        close_until(s)
        if stack and t < s:
            pieces.append((t, s, stack[-1][2]))
        stack.append((s, e, name))
        t = s
    close_until(float("inf"))
    return pieces


def idle_by_program_span(busy: Sequence[Sequence[Interval]],
                         host_spans: Sequence[Tuple[int, int, str]],
                         lo: int, hi: int) -> Dict[str, float]:
    """``{span: idle_seconds}``: every gap between the operations of each
    device (``busy``, one interval list per device) inside ``[lo, hi)``,
    given to the innermost ``service.*`` span open over it, else to the
    ``bench.*`` span open over it, else to ``other``."""
    clipped = [(max(s, lo), min(e, hi), n) for s, e, n in host_spans
               if n.startswith(("service.", "bench.")) and e > lo and s < hi]
    labelled = _innermost([(lo, hi, "other")] + clipped)
    out: Dict[str, float] = collections.defaultdict(float)
    for intervals in busy:
        merged = devtrace._union(devtrace._clip(list(intervals), lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        j = 0
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            while j < len(labelled) and labelled[j][1] <= g0:
                j += 1
            k = j
            while k < len(labelled) and labelled[k][0] < g1:
                s, e, name = labelled[k]
                out[name] += devtrace._overlap(g0, g1, s, e) / 1e9
                k += 1
    return dict(out)


def span_seconds(host_spans: Sequence[Tuple[int, int, str]], names: Iterable[str],
                 lo: int, hi: int) -> float:
    """Host seconds inside ``[lo, hi)`` of the spans named ``names``."""
    names = set(names)
    return sum(devtrace._overlap(s, e, lo, hi) for s, e, n in host_spans
               if n in names) / 1e9


# ---------------------------------------------------------------------------
# A trace file
# ---------------------------------------------------------------------------

def _module_of(ops, modules) -> List[Optional[int]]:
    """The program id of the module each operation ran in (both lists
    sorted by start; a device runs one module at a time)."""
    out, j = [], 0
    for s, _, _ in ops:
        while j < len(modules) and modules[j][1] <= s:
            j += 1
        out.append(modules[j][2] if j < len(modules) and modules[j][0] <= s else None)
    return out


def reduce_program_trace(path: str, window_span: str = devtrace.WINDOW_SPAN) -> Dict:
    """Both splits of one trace file, inside the window span.

    Returns ``scopes`` (``{scope: [seconds, calls]}``, all devices),
    ``top_ops`` (each scope's three operations with the most device
    time, ``[[hlo_name, seconds], ...]``), ``unscoped_by_step``
    (unscoped seconds by the step whose module ran them: ``storage``,
    ``maintain``, or ``other`` for a module with no scope),
    ``idle_by_program_span`` and ``span_s`` (host seconds of each
    ``service.*`` span in the window). The last file's reduction is
    kept, since each scope metric of a run reads the same one.
    """
    return _reduce(path, os.path.getmtime(path), window_span)


@functools.lru_cache(maxsize=1)
def _reduce(path: str, mtime: float, window_span: str) -> Dict:
    from jax.profiler import ProfileData

    names = xla_op_names(path)
    by_text: Dict[str, set] = collections.defaultdict(set)
    for (_, text), stack in names.items():
        by_text[text].add(stack)
    devices, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            ops = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                         for e in lines["XLA Ops"].events)
            modules = []
            for e in lines["XLA Modules"].events if "XLA Modules" in lines else ():
                m = _MODULE_ID.search(e.name)
                modules.append((int(e.start_ns), int(e.start_ns + e.duration_ns),
                                int(m.group(1)) if m else None))
            modules.sort()
            devices.append((ops, _module_of(ops, modules)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                            for e in line.events)
    windows = [(s, e) for s, e, n in host if n == window_span]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for ops, _ in devices for s, _, _ in ops)
        hi = max(e for ops, _ in devices for _, e, _ in ops)

    def scope(text: str, program: Optional[int]) -> Optional[str]:
        stack = names.get((program, text))
        if stack is None and len(by_text.get(text, ())) == 1:
            stack = next(iter(by_text[text]))       # the module is unknown
        return scope_of(stack)

    scoped = [(s, e, text, scope(text, p), p)
              for ops, progs in devices for (s, e, text), p in zip(ops, progs)]
    scopes = scope_table([op[:4] for op in scoped], lo, hi)
    # Each module's step: the top level of the scopes that ran in it.
    step_time: Dict[Optional[int], Dict[str, int]] = collections.defaultdict(
        lambda: collections.defaultdict(int))
    for s, e, _, sc, p in scoped:
        if sc:
            step_time[p][sc.split("/")[0]] += e - s
    step = {p: max(t, key=t.get) for p, t in step_time.items()}
    unscoped_by_step: Dict[str, float] = collections.defaultdict(float)
    by_op: Dict[Tuple[str, str], float] = collections.defaultdict(float)
    for (_, _, text, sc, p), secs in _in_window(scoped, lo, hi):
        by_op[(sc or UNSCOPED, devtrace.op_name(text))] += secs
        if not sc:
            unscoped_by_step[step.get(p, "other")] += secs
    top_ops: Dict[str, List[list]] = collections.defaultdict(list)
    for (sc, name), secs in sorted(by_op.items(), key=lambda kv: -kv[1]):
        if len(top_ops[sc]) < 3:
            top_ops[sc].append([name, secs])
    spans = [(s, e, n) for s, e, n in host
             if (n.startswith("service.") or n.startswith("bench."))
             and n != window_span and devtrace._overlap(s, e, lo, hi)]
    return {
        "window_s": (hi - lo) / 1e9,
        "scopes": scopes,
        "top_ops": dict(top_ops),
        "unscoped_by_step": dict(unscoped_by_step),
        "idle_by_program_span": idle_by_program_span(
            [[(s, e) for s, e, _ in ops] for ops, _ in devices], spans, lo, hi),
        "span_s": {n: span_seconds(spans, [n], lo, hi)
                   for n in sorted({n for _, _, n in spans if n.startswith("service.")})},
    }


def trace_dir(workload: str) -> str:
    import cell

    return os.path.join(cell.ARTIFACTS, "trace", workload)


def for_run(run) -> Optional[Dict]:
    """``reduce_program_trace`` of a traced run's trace, else None."""
    if run.trace is None:
        return None
    try:
        return reduce_program_trace(devtrace.find_xplane(trace_dir(run.workload)))
    except FileNotFoundError:
        return None


def scope_ms_per_batch(run, pattern: str) -> Optional[float]:
    """Mean device milliseconds per window batch of the scopes matching
    ``pattern`` (``fnmatch``); None for an open-loop or untraced run, or
    a program whose steps carry no such scope."""
    if run.arrivals != "closed" or not run.batches:
        return None
    red = for_run(run)
    if red is None:
        return None
    hits = [secs for name, (secs, _) in red["scopes"].items()
            if fnmatch.fnmatchcase(name, pattern)]
    if not hits:
        return None
    return 1e3 * sum(hits) / len(run.batches)


def summary(red: Dict, n_batches: int) -> Dict:
    """The readings a person needs from one reduction: ms per batch of
    each scope and of each service span, the unscoped share of each
    step, and the share of the service's idle time that a stage span
    (a child of ``service.batch``) holds."""
    step_s: Dict[str, float] = collections.defaultdict(float)
    for name, (secs, _) in red["scopes"].items():
        if name != UNSCOPED:
            step_s[name.split("/")[0]] += secs
    idle = red["idle_by_program_span"]
    service_idle = {k: v for k, v in idle.items() if k.startswith("service.")}
    under_batch = sum(service_idle.values()) + idle.get("bench.advance", 0.0)
    staged = sum(v for k, v in service_idle.items() if k != "service.batch")
    return {
        "batches": n_batches,
        "scope_ms_per_batch": {k: 1e3 * v[0] / n_batches
                               for k, v in sorted(red["scopes"].items())},
        "top_ops_ms_per_batch": {k: [[n, 1e3 * v / n_batches] for n, v in ops]
                                 for k, ops in sorted(red["top_ops"].items())},
        "unscoped_share": {k: v / (v + step_s.get(k, 0.0))
                           for k, v in red["unscoped_by_step"].items()},
        "span_ms_per_batch": {k: 1e3 * v / n_batches for k, v in red["span_s"].items()},
        "idle_ms_per_batch": {k: 1e3 * v / n_batches for k, v in sorted(idle.items())},
        "idle_in_stage_spans": staged / under_batch if under_batch else None,
    }


def tracer_on(svc) -> None:
    """``run_cell``'s hook: the service's span tracer on, so its spans
    reach the profiler's trace."""
    from repro.obs import Tracer

    svc.obs.tracer = Tracer(enabled=True)


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)

    import time

    t_process = time.perf_counter()
    import cell

    try:
        out = cell.run_cell(args.workload, args.seed % 2**64, args.seconds, True,
                            t_process=t_process, hook=tracer_on)
    except cell.CellError as e:
        cell.log(f"[progtrace] refused: {e}")
        return 2
    run = out["run"]
    red = for_run(run)
    res = {"workload": args.workload, "seed": args.seed,
           "correct": out["result"]["correct"],
           "end_to_end": out["report"]["end_to_end"],
           "metrics": out["result"]["metrics"],
           "summary": summary(red, len(run.batches))}
    os.makedirs(os.path.join(cell.ARTIFACTS, "progtrace"), exist_ok=True)
    with open(os.path.join(cell.ARTIFACTS, "progtrace",
                           f"{args.workload}.{args.seed}.json"), "w") as f:
        json.dump(dict(res, reduction=red), f)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    BENCH = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(os.path.dirname(BENCH),
                                                      "bench_artifacts", "tpu_logs"))
    sys.exit(main())
