"""Host spans at the serving engine's layer boundaries.

``span(name, **attrs)`` is a ``jax.profiler.TraceAnnotation``: it writes a
host event into any profiler trace taken around serving, on the same clock
as the device's operations, and costs about a microsecond when no profiler
session is running. Every engine span is named ``fcvi.<layer>`` so that a
trace reader can tell the program's spans from its own. Attributes known
only inside the span are added with ``set_metadata(**attrs)`` on the object
the ``with`` statement binds.

Spans are opened on the host only, never inside jitted code.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "fcvi."


def span(name: str, **attrs) -> TraceAnnotation:
    """A host span ``name`` (which starts with ``fcvi.``) carrying
    ``attrs`` as its trace statistics."""
    assert name.startswith(PREFIX), name
    return TraceAnnotation(name, **attrs)
