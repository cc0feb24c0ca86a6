"""Host spans of the serve path, recorded by the JAX profiler.

    with span("engine.grow") as s:
        ...
        s.set_metadata(grown=3, cow=1)

A span is a ``jax.profiler.TraceAnnotation``: it lands in the same
``.xplane.pb`` as the device's programs and ops, on the profiler's clock,
whenever a profiler session records (``jax.profiler.trace`` /
``start_trace``, or a capture through ``jax.profiler.start_server``).
There is no switch and no other sink.  With nothing recording, ``span``
costs jaxlib's is-enabled check and returns one shared no-op object:
attrs are never formatted.  An attr that costs work to build goes in
``set_metadata`` behind ``if s:``, which is false when nothing records.

Names are dotted, ``<layer>.<work>`` (``magnus.predict``,
``engine.window``, ``radix.publish``); the metrics that read them are
listed in PERF.md section 3.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

recording = TraceAnnotation.is_enabled


class _Off:
    """The span while nothing records: enters, exits and drops attrs."""
    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def __bool__(self) -> bool:
        return False

    def set_metadata(self, **attrs) -> None:
        pass


_OFF = _Off()


def span(name: str, **attrs):
    """A profiler span named ``name`` carrying ``attrs`` (ints, bools or
    short strings), or the shared no-op where nothing records."""
    if not recording():
        return _OFF
    return TraceAnnotation(name, **attrs)
