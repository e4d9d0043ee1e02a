"""repro.obs — process-local observability: metrics, spans, telemetry.

Zero-cost-when-disabled by construction: the module-level recorder is
``None`` until :func:`enable` is called, :func:`span` returns a shared
no-op context manager, and the solver's per-round telemetry rides in
loop state that is carried *unconditionally* (gated only by the static
``telemetry_rounds`` config knob) — so flipping obs on or off never
changes compiled executables, trace counts, or trees.  Tests assert
this bit-for-bit.

While tracing, every live :func:`span` is also a
``jax.profiler.TraceAnnotation`` of the same name (when jax is already
imported), so a profiler trace shows the program's spans on the device
trace's clock; and Python's garbage collections are recorded as ``gc``
spans (generation, objects collected).

Typical use::

    from repro import obs

    obs.enable(trace=True)
    ... run solves / serve traffic / graphstore builds ...
    obs.export_chrome_trace("trace.json")     # load in ui.perfetto.dev
    print(obs.prometheus_text())              # scrape-format metrics

The module is import-safe everywhere (stdlib + numpy only — it never
imports jax itself), so the graphstore CLI and serve engine instrument
themselves without touching the accelerator stack.
"""

from __future__ import annotations

import contextlib
import gc
import sys
import threading
import time
from typing import Dict, Optional

from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    parse_prometheus,
)
from .trace import Tracer, validate_chrome_trace

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Tracer",
    "add_counter",
    "add_span",
    "counter",
    "disable",
    "emit_round_telemetry",
    "enable",
    "enabled",
    "export_chrome_trace",
    "gauge",
    "histogram",
    "now",
    "parse_prometheus",
    "prometheus_text",
    "registry",
    "span",
    "tracer",
    "tracing",
    "validate_chrome_trace",
]

# Channel order of every per-round telemetry row, shared by all fixpoint
# loops (voronoi dense/bucket/frontier, pallas, mesh1d, mesh2d): int32
# counts.
ROUND_CHANNELS = ("frontier", "messages", "relaxations", "unreached")

_registry: Optional[MetricsRegistry] = None
_tracer: Optional[Tracer] = None
_enabled: bool = False


class _NoopSpan:
    """Shared do-nothing context manager handed out while obs is off."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()


def enable(trace: bool = True, metrics: bool = True) -> None:
    """Turns on recording; idempotent, keeps existing data on re-enable."""
    global _enabled, _registry, _tracer
    _enabled = True
    if metrics and _registry is None:
        _registry = MetricsRegistry()
    if trace and _tracer is None:
        _tracer = Tracer()
    if _tracer is not None and _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)


def disable() -> None:
    """Stops recording; accumulated data stays readable via registry()/tracer()."""
    global _enabled
    _enabled = False
    if _gc_span in gc.callbacks:
        gc.callbacks.remove(_gc_span)


def reset() -> None:
    """Drops all recorded data and returns to the disabled state (tests)."""
    global _registry, _tracer
    disable()
    _registry = None
    _tracer = None


def _annotation(name: str):
    """jax's profiler annotation ``name`` when jax is already loaded (the
    span then shows in a profiler trace), else None: obs never imports
    jax itself."""
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


@contextlib.contextmanager
def _annotated(ann, cm):
    with ann, cm as value:
        yield value


_gc_state = threading.local()


def _gc_span(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook while tracing: one ``gc`` span per collection."""
    if phase == "start":
        _gc_state.ann = _annotation("gc")
        if _gc_state.ann is not None:
            _gc_state.ann.__enter__()
        _gc_state.t0 = time.perf_counter()
        return
    t0 = getattr(_gc_state, "t0", None)
    if t0 is None:
        return
    _gc_state.t0 = None
    t1 = time.perf_counter()
    if _gc_state.ann is not None:
        _gc_state.ann.__exit__(None, None, None)
    if _tracer is not None:
        _tracer.add_span(
            "gc", t0, t1, generation=info.get("generation"),
            collected=info.get("collected"),
        )


def enabled() -> bool:
    return _enabled


def tracing() -> bool:
    """True when spans are actually being recorded (enabled + tracer)."""
    return _enabled and _tracer is not None


def registry() -> Optional[MetricsRegistry]:
    return _registry


def tracer() -> Optional[Tracer]:
    return _tracer


def now() -> float:
    """Timestamp for retroactive spans (:func:`add_span` /
    :func:`emit_round_telemetry`) — plain ``time.perf_counter()``."""
    return time.perf_counter()


def span(name: str, tid: int = 0, **args):
    """A live span on the global tracer (and the profiler's, when jax is
    loaded), or the shared no-op when off."""
    if _enabled and _tracer is not None:
        cm = _tracer.span(name, tid=tid, **args)
        ann = _annotation(name)
        return cm if ann is None else _annotated(ann, cm)
    return _NOOP_SPAN


def add_span(name: str, t_start: float, t_end: float, tid: int = 0, **args) -> None:
    """Retroactive span (no-op when disabled); stamps from time.perf_counter()."""
    if _enabled and _tracer is not None:
        _tracer.add_span(name, t_start, t_end, tid=tid, **args)


def add_counter(name: str, t: float, values: Dict[str, float], tid: int = 0) -> None:
    """A counter sample on the global tracer (no-op when disabled); ints
    stay exact."""
    if _enabled and _tracer is not None:
        _tracer.add_counter(name, t, values, tid=tid)


def counter(name: str, help: str = "", labels=None) -> Optional[Counter]:
    """The named counter on the global registry, or None when disabled."""
    if _enabled and _registry is not None:
        return _registry.counter(name, help, labels)
    return None


def gauge(name: str, help: str = "", labels=None) -> Optional[Gauge]:
    if _enabled and _registry is not None:
        return _registry.gauge(name, help, labels)
    return None


def histogram(name: str, help: str = "", labels=None) -> Optional[Histogram]:
    if _enabled and _registry is not None:
        return _registry.histogram(name, help, labels)
    return None


def prometheus_text() -> str:
    return _registry.prometheus_text() if _registry is not None else ""


def export_chrome_trace(path: str) -> bool:
    """Writes the accumulated trace; returns False if nothing was recorded."""
    if _tracer is None:
        return False
    _tracer.export_chrome(path)
    return True


def emit_round_telemetry(
    per_round,
    t_start: float,
    t_end: float,
    *,
    label: str,
    tid: int = 0,
    per_rank=None,
) -> None:
    """Renders per-round convergence telemetry into the trace.

    ``per_round`` is the (R, 4) host array of ROUND_CHANNELS rows carried
    out of a fixpoint loop.  The compiled loop has no host-visible clock,
    so the rounds' counter samples (``convergence[{label}]``, one track
    of the four channels) are placed at even steps across the real
    ``[t_start, t_end]`` solve interval: their order is real, their
    spacing is not (a profiler trace's device events give each round's
    real time).  ``per_rank`` — the (R, n_ranks, 4) flight-recorder
    buffer, when the solve ran with ``telemetry_per_rank=True`` —
    additionally renders one ``rank[{label}/{r}]`` counter track per mesh
    device, making load imbalance visible round by round.  No-op when
    tracing is off or the solve recorded zero rounds.
    """
    if not tracing() or per_round is None:
        return
    rounds = int(per_round.shape[0])
    if rounds == 0:
        return
    dt = (t_end - t_start) / rounds
    for r in range(rounds):
        values = {c: int(v) for c, v in zip(ROUND_CHANNELS, per_round[r])}
        _tracer.add_counter(f"convergence[{label}]", t_start + r * dt, values, tid=tid)
    if per_rank is not None:
        for r in range(min(rounds, int(per_rank.shape[0]))):
            t = t_start + r * dt
            for k in range(int(per_rank.shape[1])):
                vals = {c: int(v) for c, v in zip(ROUND_CHANNELS, per_rank[r, k])}
                _tracer.add_counter(f"rank[{label}/{k}]", t, vals, tid=tid)
