"""``jax.compile`` spans: every XLA backend compile (or persistent-cache
load) recorded on a serving tracer, so a recompile shows as the interval
it held the host, not only as a count.

JAX reports each backend compile through ``jax.monitoring`` as a time
span on its own wall clock.  ``CompileSpans`` converts it to the
tracer's clock: the span ends at ``tracer.now()`` (the listener runs as
the compile returns) and starts the compile's duration earlier.

One listener per tracer, held weakly: a listener whose tracer has been
collected records nothing and is unregistered by the next
``record_compiles`` call (never from inside JAX's listener loop, which
a removal would disturb), and ``close()`` unregisters it at once.
"""
from __future__ import annotations

import weakref

import jax

__all__ = ["COMPILE_EVENT", "CompileSpans", "record_compiles"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACK = "jax"

_registered: list["CompileSpans"] = []


class CompileSpans:
    """A ``jax.monitoring`` event-time-span listener writing a
    ``jax.compile`` span (attribute ``fun_name``) on ``tracer``."""

    def __init__(self, tracer) -> None:
        self._tracer = weakref.ref(tracer)
        jax.monitoring.register_event_time_span_listener(self)
        _registered.append(self)

    @property
    def tracer(self):
        return self._tracer()

    def __call__(self, event: str, start_time: float, end_time: float,
                 **kwargs) -> None:
        tracer = self._tracer()
        if event != COMPILE_EVENT or tracer is None or not tracer.enabled:
            return
        t1 = tracer.now()
        tracer.complete("jax.compile", TRACK, t1 - (end_time - start_time), t1,
                        fun_name=kwargs.get("fun_name"))

    def close(self) -> None:
        if self in _registered:
            _registered.remove(self)
            jax.monitoring.unregister_event_time_span_listener(self)


def record_compiles(tracer) -> CompileSpans | None:
    """The listener recording ``tracer``'s compile spans, registered on
    first use; None for a disabled tracer (nothing is registered)."""
    for lst in list(_registered):
        if lst.tracer is None:
            lst.close()
    if not tracer.enabled:
        return None
    for lst in _registered:
        if lst.tracer is tracer:
            return lst
    return CompileSpans(tracer)
