"""Reduction of a ``jax.profiler`` trace to the numbers the benchmark reports.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes.
Device planes are named ``/device:TPU:<i>``; each operation that ran on a
chip is an event of their ``XLA Ops`` line, named by its HLO instruction
(``%fusion.12 = s32[...] fusion(...)``). Host planes carry the spans the
benchmark opens with ``jax.profiler.TraceAnnotation`` (``bench.*``) on
the same clock, so an idle stretch of the device can be laid against
what the host was doing then.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
# A device gap at least this long is listed with the host events under it
# (the per-run record's stall evidence).
STALL_GAP_NS = 20_000_000

_NAME = re.compile(r"^%?([^\s=]+)")
# Control flow whose events span the operations of their bodies.
_CONTAINERS = ("while", "cond", "conditional", "call")


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = s32[8]{0} fusion(...)`` → ``fusion.12``."""
    m = _NAME.match(hlo_text)
    return m.group(1) if m else hlo_text


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _overlap(a0: int, a1: int, b0: int, b1: int) -> int:
    return max(0, min(a1, b1) - max(a0, b0))


def reduce_trace(path: str, window_span: str = WINDOW_SPAN) -> Dict:
    """Busy and idle time of the device inside the window, device time per
    operation, and idle time by the host span it fell under.

    Returns a dict with

    - ``window_s``: length of the ``window_span`` host span (the whole
      trace where the span is absent);
    - ``busy_s``: union of the device's operation intervals inside the
      window, averaged over the device planes;
    - ``ops``: ``{hlo_text: [device_seconds, calls]}`` inside the window;
    - ``idle_by_span``: ``{host_span: idle_seconds}``, every device gap
      inside the window split over the ``bench.*`` spans it overlaps, the
      rest under ``other``;
    - ``stalls``: gaps of at least 20 ms, each with the host events that
      overlap it most.
    """
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    devices, host_events = [], []
    for plane in prof.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops = [line for line in plane.lines if line.name == "XLA Ops"]
            if ops:
                devices.append([(e.start_ns, e.start_ns + e.duration_ns, e.name)
                                for e in ops[0].events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host_events.append((int(e.start_ns),
                                        int(e.start_ns + e.duration_ns),
                                        line.name, e.name))
    if not devices:
        raise ValueError(f"{path}: no device plane with XLA Ops")
    windows = [(s, e) for s, e, _, n in host_events if n == window_span]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for d in devices for s, _, _ in d)
        hi = max(e for d in devices for _, e, _ in d)
    # The benchmark's own spans do not nest (apart from the window), so
    # one sweep over gaps and spans, both sorted, splits every gap.
    spans = sorted((s, e, n) for s, e, _, n in host_events
                   if n.startswith("bench.") and n != window_span
                   and _overlap(s, e, lo, hi))
    ops: Dict[str, List[float]] = {}
    busy_ns = 0
    idle_by_span: Dict[str, float] = collections.defaultdict(float)
    stalls = []
    for events in devices:
        for s, e, text in events:
            d = _overlap(s, e, lo, hi)
            if d:
                rec = ops.setdefault(text, [0.0, 0])
                rec[0] += d / 1e9
                rec[1] += 1
        busy = _union(_clip([(s, e) for s, e, _ in events], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        first = 0
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            while first < len(spans) and spans[first][1] <= g0:
                first += 1
            covered = 0
            for s, e, n in spans[first:]:
                if s >= g1:
                    break
                ov = _overlap(g0, g1, s, e)
                idle_by_span[n] += ov / 1e9
                covered += ov
            if covered < g1 - g0:
                idle_by_span["other"] += (g1 - g0 - covered) / 1e9
            if g1 - g0 >= STALL_GAP_NS:
                under = sorted(((_overlap(g0, g1, s, e), ln, n)
                                for s, e, ln, n in host_events
                                if _overlap(g0, g1, s, e) and n != window_span),
                               reverse=True)[:12]
                stalls.append({"at_s": (g0 - lo) / 1e9, "gap_s": (g1 - g0) / 1e9,
                               "host": [[n, ln, ov / 1e9] for ov, ln, n in under]})
    n_dev = len(devices)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / n_dev,
        "devices": n_dev,
        "ops": ops,
        "idle_by_span": dict(idle_by_span),
        "stalls": stalls,
    }


def top_ops(ops: Dict[str, List[float]], n: int = 10) -> List[list]:
    """The ``n`` operations with the most device time, by HLO name, leaving
    out control flow (its time is that of the operations inside it)."""
    by_name: Dict[str, float] = collections.defaultdict(float)
    for text, (secs, _) in ops.items():
        name = op_name(text)
        if name.split(".")[0] not in _CONTAINERS:
            by_name[name] += secs
    return [[k, v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(idle_by_span: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(idle_by_span.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(reduced: Optional[Dict]) -> Optional[Dict]:
    if reduced is None:
        return None
    return {"device_ops": top_ops(reduced["ops"]),
            "idle_gaps": top_gaps(reduced["idle_by_span"])}
