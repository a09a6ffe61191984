"""Named spans in the profiler's own trace.

``span("ingest.prep")`` is a ``jax.profiler.TraceAnnotation`` named
``repro.ingest.prep``: it records into the profiler's host plane, on the
clock of the device planes, and only while a profiler session
(``jax.profiler.trace``) is active.  Keyword arguments become the
event's stats; pass values already at hand, never computed for the span.

Where jax has not been imported no profiler session can be running, so
the span is a shared no-op and the numpy-only core never imports jax.
"""
from __future__ import annotations

import contextlib
import sys

PREFIX = "repro."
_OFF = contextlib.nullcontext()


def span(name: str, **args):
    """A context manager that marks ``repro.<name>`` in a trace."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _OFF
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
