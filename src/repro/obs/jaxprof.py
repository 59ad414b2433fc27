"""Device-profiling hooks for the sharded streaming backend.

``BENCH_stream_service.json`` showed the device maintain path stuck at
~1.8 s/call with no way to say whether that is XLA compilation, steady
dispatch, padded-shape waste, or transfers. This module answers that by
wrapping every jitted SPMD step the backend drives
(:func:`~repro.dist.sharded.make_storage_update_step`, the per-pattern
maintain/list/init/refresh steps) in a :class:`ProfiledStep`:

- the **first** call of each wrapped step is compiled ahead-of-time via
  ``fn.lower(*args).compile()`` so compile time is measured *separately*
  from the first execution (the classic jit first-call conflation);
  recompiles — the backend's ``cap_fallbacks`` candidate-cap fallback
  and ``store_resizes`` rebuilds create a *new* wrapper under the same
  step name — accumulate into the same :class:`StepProfile`;
- each compiled step's ``cost_analysis()`` (flops / bytes accessed) and
  ``memory_analysis()`` (output / temp / argument bytes) are recorded
  once per compile;
- steady-state executions are timed with ``jax.block_until_ready`` so
  the numbers are wall-clock of actual device work, not dispatch;
- optionally, a ``jax.profiler`` trace can be armed for a chosen batch
  window (:meth:`JaxProfiler.arm_capture`) — the service calls
  :meth:`on_batch_start`/:meth:`on_batch_end` around every micro-batch;
- device→host transfer bytes flow through the backend's existing
  ``_pull`` seam into the ``host_transfer_bytes_total`` counter.

A failed compile or a failed execution raises: the profiler never
re-runs a step behind the caller's back (the maintain megastep donates
its inputs, so a re-run would read consumed buffers). Only a step with
nothing to lower — a plain Python callable — is timed by the first-call
heuristic (first call = compile + one execution, flagged
``heuristic``). Cost/memory analysis is optional metadata and stays
best-effort. A failed trace capture is reported in ``capture_errors``
and a warning; it does not stop the stream.
"""

from __future__ import annotations

import dataclasses
import json
import time
import warnings
from typing import Callable, Dict, List, Optional

__all__ = ["StepProfile", "ProfiledStep", "JaxProfiler"]


def _cost_dict(compiled) -> Optional[dict]:
    """Flatten ``Compiled.cost_analysis()`` into one {str: float} dict."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if ca is None:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for k, v in ca.items():
        try:
            out[str(k)] = float(v)
        except (TypeError, ValueError):
            continue
    return out


_MEM_FIELDS = (
    "generated_code_size_in_bytes",
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "alias_size_in_bytes",
    "temp_size_in_bytes",
)


def _memory_dict(compiled) -> Optional[dict]:
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    out = {}
    for f in _MEM_FIELDS:
        v = getattr(mem, f, None)
        if v is not None:
            try:
                out[f] = int(v)
            except (TypeError, ValueError):
                continue
    return out or None


@dataclasses.dataclass
class StepProfile:
    """Accumulated compile/execute accounting for one named step.

    One record per step *name* — recompiles of the same logical step
    (cap fallback, store resize) increment :attr:`compiles` and fold
    their compile time into :attr:`compile_seconds`.
    """

    name: str
    compiles: int = 0
    compile_seconds: float = 0.0
    calls: int = 0
    execute_seconds: float = 0.0
    last_execute_s: float = 0.0
    #: latest compile's XLA cost_analysis / memory_analysis (None when
    #: the runtime doesn't expose them)
    cost: Optional[dict] = None
    memory: Optional[dict] = None
    #: True when the step had nothing to lower (a plain callable) and the
    #: split used the first-call≈compile heuristic
    heuristic: bool = False

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class ProfiledStep:
    """Transparent callable wrapper around one jitted step.

    ``profiler_get`` is a zero-arg closure resolving to the current
    :class:`JaxProfiler` (or None) *at call time* — the backend compiles
    its steps before the service attaches observability, so the binding
    must be late.
    """

    __slots__ = ("name", "fn", "_profiler_get", "_compiled", "_warm")

    def __init__(self, name: str, fn: Callable,
                 profiler_get: Callable[[], Optional["JaxProfiler"]]):
        self.name = name
        self.fn = fn
        self._profiler_get = profiler_get
        self._compiled = None   # AOT executable once lowered
        self._warm = False      # first profiled call already accounted

    def __call__(self, *args):
        prof = self._profiler_get()
        if prof is None or not prof.enabled:
            return self.fn(*args)
        return prof._call(self, *args)


class JaxProfiler:
    """Per-service device profiler: step records + optional trace window.

    ``enabled=False`` turns every :class:`ProfiledStep` into a plain
    passthrough (zero accounting, no ``block_until_ready``).
    """

    def __init__(self, registry=None, enabled: bool = True,
                 aot: bool = True, collect_analysis: bool = True):
        self.registry = registry
        self.enabled = enabled
        self.aot = aot
        self.collect_analysis = collect_analysis
        self.steps: Dict[str, StepProfile] = {}
        # jax.profiler window capture state
        self._capture_logdir: Optional[str] = None
        self._capture_start = 0
        self._capture_len = 0
        self._capturing = False
        self.captured_dirs: List[str] = []
        self.capture_errors: List[str] = []

    # ----------------------------------------------------------- step timing
    def _record(self, name: str, kind: str, seconds: float) -> None:
        if self.registry is None:
            return
        self.registry.counter(
            f"jax_{kind}_seconds_total", f"total {kind} seconds per jitted step",
            labels=("step",)).labels(step=name).inc(seconds)
        self.registry.counter(
            f"jax_{kind}s_total" if kind == "compile" else "jax_execute_calls_total",
            f"{kind} count per jitted step",
            labels=("step",)).labels(step=name).inc()

    def _record_analysis(self, name: str, rec: StepProfile) -> None:
        if self.registry is None:
            return
        if rec.cost:
            flops = rec.cost.get("flops")
            if flops is not None:
                self.registry.gauge("jax_step_flops",
                                    "XLA cost_analysis flops of the latest compile",
                                    labels=("step",)).labels(step=name).set(flops)
        if rec.memory:
            for f in ("output_size_in_bytes", "temp_size_in_bytes"):
                v = rec.memory.get(f)
                if v is not None:
                    self.registry.gauge(
                        f"jax_step_{f}",
                        f"XLA memory_analysis {f} of the latest compile",
                        labels=("step",)).labels(step=name).set(v)

    def _compile(self, step: ProfiledStep, rec: StepProfile, args) -> None:
        """AOT-lower the step so compile time is isolated from execution."""
        t0 = time.perf_counter()
        compiled = step.fn.lower(*args).compile()
        dt = time.perf_counter() - t0
        step._compiled = compiled
        rec.compiles += 1
        rec.compile_seconds += dt
        self._record(step.name, "compile", dt)
        if self.collect_analysis:
            cost = _cost_dict(compiled)
            memory = _memory_dict(compiled)
            if cost is not None:
                rec.cost = cost
            if memory is not None:
                rec.memory = memory
            self._record_analysis(step.name, rec)

    def _call(self, step: ProfiledStep, *args):
        import jax

        rec = self.steps.get(step.name)
        if rec is None:
            rec = self.steps[step.name] = StepProfile(step.name)
        if not step._warm:
            step._warm = True
            if self.aot and hasattr(step.fn, "lower"):
                self._compile(step, rec, args)
            else:
                rec.heuristic = True
            if step._compiled is None:
                # Heuristic split: the first direct call pays compile +
                # one execution; attribute it wholly to compile (upper
                # bound, flagged via `heuristic`).
                t0 = time.perf_counter()
                out = jax.block_until_ready(step.fn(*args))
                dt = time.perf_counter() - t0
                rec.compiles += 1
                rec.compile_seconds += dt
                self._record(step.name, "compile", dt)
                return out
        fn = step._compiled if step._compiled is not None else step.fn
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        rec.calls += 1
        rec.execute_seconds += dt
        rec.last_execute_s = dt
        self._record(step.name, "execute", dt)
        return out

    # ------------------------------------------------------- capture windows
    def arm_capture(self, logdir: str, start_batch: int = 0,
                    n_batches: int = 1) -> None:
        """Capture a ``jax.profiler`` trace for batches
        ``[start_batch, start_batch + n_batches)`` of the next run."""
        self._capture_logdir = logdir
        self._capture_start = int(start_batch)
        self._capture_len = max(1, int(n_batches))

    def on_batch_start(self, batch_index: int) -> None:
        if (self._capture_logdir is None or self._capturing
                or batch_index != self._capture_start):
            return
        import jax

        try:
            jax.profiler.start_trace(self._capture_logdir)
            self._capturing = True
        except Exception as e:
            self._capture_failed("start", e)
            self._capture_logdir = None

    def on_batch_end(self, batch_index: int) -> None:
        if not self._capturing:
            return
        if batch_index >= self._capture_start + self._capture_len - 1:
            import jax

            try:
                jax.profiler.stop_trace()
                self.captured_dirs.append(self._capture_logdir)
            except Exception as e:
                self._capture_failed("stop", e)
            self._capturing = False
            self._capture_logdir = None

    def _capture_failed(self, phase: str, err: Exception) -> None:
        msg = f"jax.profiler {phase}_trace failed: {type(err).__name__}: {err}"
        self.capture_errors.append(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)
        if self.registry is not None:
            self.registry.counter(
                "jax_profiler_capture_errors_total",
                "failed jax.profiler trace starts/stops").inc()

    # --------------------------------------------------------------- exports
    def snapshot(self) -> dict:
        return {
            "steps": {name: rec.as_dict() for name, rec in sorted(self.steps.items())},
            "captured_dirs": list(self.captured_dirs),
            "capture_errors": list(self.capture_errors),
        }

    def save_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.snapshot(), f, indent=2, sort_keys=True)
