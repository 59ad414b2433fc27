"""`repro.obs` — unified observability for the streaming service.

Three instruments behind one umbrella object:

- :mod:`repro.obs.metrics` — typed metrics registry (counters, gauges,
  fixed-bucket histograms) with Prometheus-text and JSON exposition;
- :mod:`repro.obs.trace` — hierarchical span tracer (batch →
  shared-delta → storage-update → maintain-mega → maintain →
  materialize → mirror → graph-stats → sinks) with JSONL and Chrome
  trace-event (Perfetto) export; an enabled tracer also opens one
  ``jax.profiler.TraceAnnotation`` (``service.<span>``) per span, so the
  spans share a ``jax.profiler`` trace's clock with the device's ops;
- :mod:`repro.obs.jaxprof` — device profiling: compile-vs-execute split
  per jitted SPMD step, XLA cost/memory analysis, optional
  ``jax.profiler`` windows, device→host transfer accounting. Time
  inside a step is split in the profiler trace by the steps' named
  scopes (``storage/*``, ``maintain/<pattern>/*``).

One :class:`Observability` per :class:`~repro.stream.service.ListingService`
(pass ``obs=``); the default is the *cheap* configuration — registry and
step profiling on, span tracing off — so two services in one process
never share counters and the hot path stays unperturbed.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from .jaxprof import JaxProfiler, ProfiledStep, StepProfile
from .metrics import (
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    ProbeView,
)
from .trace import NULL_SPAN, Span, Tracer

__all__ = [
    "Observability",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "ProbeView",
    "DEFAULT_LATENCY_BUCKETS",
    "Tracer",
    "Span",
    "NULL_SPAN",
    "JaxProfiler",
    "ProfiledStep",
    "StepProfile",
]


class Observability:
    """One service's metrics registry + span tracer + device profiler.

    ``Observability()``        — registry + device profiling on, tracing off
    ``Observability.full()``   — everything on (span tracing included)
    ``Observability.disabled()`` — every channel off (still safe to call)
    """

    def __init__(self, metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 jaxprof: Optional[JaxProfiler] = None):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        self.jaxprof = (jaxprof if jaxprof is not None
                        else JaxProfiler(self.metrics, enabled=True))
        #: per-pattern CompiledPlan dumps (latest wins across swaps);
        #: exported as `{prefix}_plans.json`
        self.plans: Dict[str, dict] = {}

    def record_plan(self, name: str, dump: dict) -> None:
        """Remember a pattern's latest :class:`~repro.planner.CompiledPlan`
        dump (``plan.to_json()``) for the export bundle. A plan swap
        re-records under the same name — the export shows the plan the
        service is *currently* executing."""
        self.plans[name] = dump

    @classmethod
    def full(cls) -> "Observability":
        obs = cls(tracer=Tracer(enabled=True))
        return obs

    @classmethod
    def disabled(cls) -> "Observability":
        obs = cls()
        obs.jaxprof.enabled = False
        return obs

    def export(self, dir_path: str, prefix: str = "obs") -> Dict[str, str]:
        """Write every artifact into ``dir_path``; returns name → path.

        Emits a metrics JSON snapshot + Prometheus text always, span
        exports (JSONL + Chrome trace-event JSON for Perfetto) when any
        spans were recorded, and the device-step profile when any step
        ran profiled.
        """
        os.makedirs(dir_path, exist_ok=True)
        out: Dict[str, str] = {}
        p = os.path.join(dir_path, f"{prefix}_metrics.json")
        self.metrics.save_json(p)
        out["metrics_json"] = p
        p = os.path.join(dir_path, f"{prefix}_metrics.prom")
        self.metrics.save_prometheus(p)
        out["metrics_prom"] = p
        if self.tracer.roots:
            p = os.path.join(dir_path, f"{prefix}_trace.jsonl")
            self.tracer.to_jsonl(p)
            out["trace_jsonl"] = p
            p = os.path.join(dir_path, f"{prefix}_trace_chrome.json")
            self.tracer.to_chrome_trace(p)
            out["trace_chrome"] = p
        if self.jaxprof.steps:
            p = os.path.join(dir_path, f"{prefix}_jaxprof.json")
            self.jaxprof.save_json(p)
            out["jaxprof_json"] = p
        if self.plans:
            import json

            p = os.path.join(dir_path, f"{prefix}_plans.json")
            with open(p, "w") as f:
                json.dump(self.plans, f, indent=2, sort_keys=True)
                f.write("\n")
            out["plans_json"] = p
        return out
