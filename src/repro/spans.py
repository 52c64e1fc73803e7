"""Host spans on the profiler's clock.

``span(name)`` is ``jax.profiler.TraceAnnotation(name)``: under an
active ``jax.profiler`` trace it lands on the host thread's line of that
trace, beside the device ops it dispatched; with no trace active it
costs about as much as ``contextlib.nullcontext``.  Where jax cannot be
imported it is ``nullcontext``.  docs/tracing.md names the spans.
"""

from __future__ import annotations

import contextlib
import functools


@functools.cache
def _annotation():
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return contextlib.nullcontext
    return TraceAnnotation


def span(name: str):
    """A context manager that marks ``name`` in the profiler's trace."""
    return _annotation()(name)
