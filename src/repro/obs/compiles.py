"""repro.obs.compiles — JAX's own compile work as ``compile`` spans.

JAX reports each stage of turning a function into an executable as a
``jax.monitoring`` time span: tracing to a jaxpr, lowering to an MLIR
module, and the backend compile (which includes a read of the persistent
compilation cache, announced just before by a cache-hit event). While an
enabled ``Tracer`` is active, every such span becomes a finished child
span named ``compile`` of the innermost open program span — the same
rule by which ``Tracer.on_ledger`` charges bytes — with the attrs
``phase`` (``trace``/``lower``/``backend``), ``fn`` (the event's
``fun_name``) and, on ``backend`` spans, ``cache_hit``. The same events
feed the counters ``compile.<fn>`` (backend phase) and
``compile_s.<phase>`` (seconds).

The listeners are registered once per process, when the first enabled
``Tracer`` is built (``install``); a process with observability off
registers nothing. Import-safe without jax.
"""
from __future__ import annotations

from typing import Any, Optional

from repro.obs.timing import from_wall
from repro.obs.tracer import get_tracer

# jax._src.dispatch's event names -> the span's phase
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _Listener:
    """Turns JAX's compile events into spans on the active tracer. The
    cache-hit event fires inside the backend compile it belongs to, so
    it marks the next ``backend`` span."""

    def __init__(self) -> None:
        self.hit = False

    def on_event(self, event: str, **kwargs: Any) -> None:
        if event == CACHE_HIT:
            self.hit = True

    def on_span(self, event: str, start: float, end: float,
                **kwargs: Any) -> None:
        phase = PHASES.get(event)
        if phase is None:
            return
        hit = False
        if phase == "backend":
            hit, self.hit = self.hit, False
        tracer = get_tracer()
        if tracer.enabled:
            t1 = from_wall(end)
            tracer.record_compile(phase, str(kwargs.get("fun_name", "")),
                                  t1 - (end - start), t1, hit)


# JAX's listener lists are process-wide, so the registration is too
_LISTENER: Optional[_Listener] = None


def install() -> None:
    """Register the compile listeners with ``jax.monitoring`` (once per
    process; a no-op without jax)."""
    global _LISTENER
    if _LISTENER is not None:
        return
    try:
        from jax import monitoring
    except ImportError:  # pragma: no cover - analysis-only environment
        return
    _LISTENER = _Listener()
    monitoring.register_event_listener(_LISTENER.on_event)
    monitoring.register_event_time_span_listener(_LISTENER.on_span)
