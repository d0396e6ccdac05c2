"""``Observability``: the one instrumentation surface an emitter holds.

Bundles the schema-strict :class:`~repro.obs.metrics.MetricsRegistry`, the
:class:`~repro.obs.events.EventLog`, and the rolling window of per-step
audit records. ``ShiftEngine`` and ``ServeSim`` both drive exactly this
object, which is what guarantees one metric schema across the live engine
and the simulator.

``record_step`` is the single source of truth for per-step bookkeeping:
each record carries the monotone step index and the duration *inside* the
record (so rolling-window trimming can never desynchronize a ``step_times``
list from a ``step_log`` list again), and the standard counters/histograms
(steps_total{config}, token totals, step_seconds, ...) are derived from the
record right there instead of being maintained in parallel at call sites.

``span`` marks a phase of the served step: a ``jax.profiler``
annotation, so the phase shows on the profiler's host plane on the same
clock as the device ops whenever a trace records, and its host seconds in
the step's ``host_s`` table. ``compile_count`` is the one process-wide
count of executables built, read as a delta around a step.

``NullObs`` is the disabled twin — same API, no recording — used for the
instrumented-vs-uninstrumented overhead A/B that CI gates
(``obs.overhead_ratio``).
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Dict, List, Optional

import jax

from . import schema
from .events import EventLog
from .metrics import MetricsRegistry

DEFAULT_STEP_WINDOW = 1024

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = 0
_listening = False


def _on_duration(event: str, duration: float, **_):
    global _compiles
    if event == COMPILE_EVENT:
        _compiles += 1


def compile_count() -> int:
    """Executables built in this process (compiled, or read from the
    persistent cache) since the first call. One listener serves every
    engine of the process."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return _compiles


class _Span:
    """See ``Observability.span``."""

    __slots__ = ("obs", "name", "step", "ann", "t0")

    def __init__(self, obs: "Observability", name: str,
                 step: Optional[int]):
        self.obs, self.name, self.step = obs, name, step

    def __enter__(self):
        if self.step is None:
            self.ann = jax.profiler.TraceAnnotation(self.name)
        else:
            self.obs.phases = {}
            self.ann = jax.profiler.TraceAnnotation(self.name,
                                                    step_num=self.step)
        self.ann.__enter__()
        self.t0 = self.obs.now()
        return self

    def __exit__(self, *exc):
        if self.step is None:
            key = self.name.rpartition(".")[2]
            ph = self.obs.phases
            ph[key] = ph.get(key, 0.0) + (self.obs.now() - self.t0)
        return self.ann.__exit__(*exc)


_NO_SPAN = contextlib.nullcontext()


class Observability:
    def __init__(self, source: str, window: int = DEFAULT_STEP_WINDOW,
                 now=time.monotonic, event_cap: int = 65536):
        self.source = source          # "engine" | "sim" (stamped in dumps)
        self.window = window
        self.now = now
        self.registry = MetricsRegistry()
        self.events = EventLog(cap=event_cap)
        self.step_records: List[dict] = []
        # host seconds per phase of the step in flight (see span)
        self.phases: Dict[str, float] = {}
        self.enabled = True
        # cluster replica id (None standalone). Set once by the Router via
        # the engine facade; stamped centrally on every step record and
        # event so no call site needs to thread it through.
        self.replica: Optional[int] = None

    # ------------------------------------------------------------- steps
    def record_step(self, rec: dict) -> dict:
        """Append one per-iteration audit record (schema-checked) and
        derive the standard step metrics from it."""
        if self.replica is not None:
            rec.setdefault("replica", self.replica)
        schema.check_step_record(rec)
        self.step_records.append(rec)
        if len(self.step_records) > self.window:
            del self.step_records[:len(self.step_records) - self.window]
        reg = self.registry
        cfgname = rec["config"]
        if cfgname is None:
            reg.counter("steps_idle_total").inc()
        else:
            reg.counter("steps_total", config=cfgname).inc()
        n_pre, n_dec = rec["prefill_tokens"], rec["decode_tokens"]
        if n_pre:
            reg.counter("tokens_prefill_total").inc(n_pre)
        if n_dec:
            reg.counter("tokens_decode_total").inc(n_dec)
        if rec["attn_ctx_tokens"]:
            reg.counter("attn_ctx_tokens_total").inc(rec["attn_ctx_tokens"])
        if rec["ready_decodes"] and not n_dec:
            reg.counter("decode_starved_steps_total").inc()
        reg.histogram("step_seconds").observe(rec["dur_s"])
        if n_pre or n_dec:
            reg.histogram("step_tokens").observe(n_pre + n_dec)
        return rec

    def span(self, name: str, step: Optional[int] = None):
        """Context manager around one phase of the served step. It opens a
        ``jax.profiler.TraceAnnotation(name)`` and adds its seconds on the
        obs clock to ``phases`` under the name's last dotted part
        (``engine.admit`` -> ``admit``). With ``step`` it is the step's own
        span instead: the annotation carries the index as its
        ``step_num`` stat (trace events join step records by index) and
        it starts an empty ``phases`` table."""
        return _Span(self, name, step)

    # ------------------------------------------------------------ events
    def emit(self, kind: str, *, step: int, ts: Optional[float] = None,
             rid: Optional[int] = None, **attrs) -> Optional[dict]:
        if self.replica is not None:
            attrs.setdefault("replica", self.replica)
        return self.events.emit(kind, step=step,
                                ts=self.now() if ts is None else ts,
                                rid=rid, **attrs)

    # ------------------------------------------------- metric call-throughs
    # Emitters record through these (not registry directly) so a disabled
    # NullObs is fully inert at every call site.
    def inc(self, name: str, amount: float = 1.0, **labels):
        self.registry.counter(name, **labels).inc(amount)

    def observe(self, name: str, value: float, **labels):
        self.registry.histogram(name, **labels).observe(value)

    def set_gauge(self, name: str, value: float, **labels):
        self.registry.gauge(name, **labels).set(value)

    def set_gauge_max(self, name: str, value: float, **labels):
        self.registry.gauge(name, **labels).set_max(value)

    # ----------------------------------------------------------- export
    def dump(self) -> dict:
        """The full observability state as one JSON-able dict — the input
        format of ``repro.obs.report``."""
        return {"schema_version": schema.SCHEMA_VERSION,
                "source": self.source,
                "metrics": self.registry.snapshot(),
                "events": [dict(e) for e in self.events.events],
                "events_dropped": self.events.dropped,
                "steps": [dict(r) for r in self.step_records]}

    def write_json(self, path: str):
        with open(path, "w") as f:
            json.dump(self.dump(), f, indent=1, sort_keys=True)

    def write_prometheus(self, path: str):
        with open(path, "w") as f:
            f.write(self.registry.to_prometheus())

    # ---------------------------------------------------------- snapshot
    def state_dict(self) -> dict:
        return {"source": self.source, "window": self.window,
                "registry": self.registry.state_dict(),
                "events": self.events.state_dict(),
                "steps": [dict(r) for r in self.step_records]}

    def load_state(self, state: dict):
        self.source = state["source"]
        self.window = state["window"]
        self.registry.load_state(state["registry"])
        self.events.load_state(state["events"])
        self.step_records = [dict(r) for r in state["steps"]]
        return self


class NullObs(Observability):
    """Disabled observability: same surface, records nothing. The engine
    behind it behaves identically (scheduling never reads obs state); the
    wall-time delta against the real thing is ``obs.overhead_ratio``."""

    def __init__(self, source: str = "null", now=time.monotonic):
        super().__init__(source, window=0, now=now, event_cap=1)
        self.enabled = False

    def record_step(self, rec: dict) -> dict:
        return rec

    def span(self, name: str, step: Optional[int] = None):
        return _NO_SPAN

    def emit(self, kind: str, *, step: int, ts: Optional[float] = None,
             rid: Optional[int] = None, **attrs) -> Optional[dict]:
        return None

    def inc(self, name: str, amount: float = 1.0, **labels):
        pass

    def observe(self, name: str, value: float, **labels):
        pass

    def set_gauge(self, name: str, value: float, **labels):
        pass

    def set_gauge_max(self, name: str, value: float, **labels):
        pass

    def state_dict(self):
        return None
