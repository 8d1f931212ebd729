"""Host spans of the serving program, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``serve.<name>``.  Inside a profiler session it lands on the trace's host
plane with ``args`` as event stats, on the same clock as the device's
operations, so a device gap can be charged to what the host was doing in
it; the profiler keeps it in memory until ``stop_trace``.  Outside a
session entering and leaving one is a native check and nothing is
recorded, so there is no switch.  Use it as a context manager so an
exception closes it; an arg known only at the end goes in through the
span's ``set_metadata(**args)``::

    with span("admit") as sp:
        ...
        sp.set_metadata(admitted=n)

``docs/serving.md`` (Tracing) lists the spans.
"""

from __future__ import annotations

import jax

__all__ = ["span", "PREFIX"]

#: every program span's name starts with this
PREFIX = "serve."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """The host span ``serve.<name>`` with ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
