"""Host spans in the profiler's own trace.

A span records only while a profiler session is open
(``jax.profiler.start_trace``); otherwise entering and leaving one costs
about a microsecond. The trace is the one store: the spans sit on the
device trace's clock, so each device idle gap can be put down to the host
step open over it. Every span is named ``repro.<name>``.
"""
from __future__ import annotations

import functools

import jax

PREFIX = "repro."


def span(name: str) -> jax.profiler.TraceAnnotation:
    """Context manager: one ``repro.<name>`` span around the block."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def spanned(name: str):
    """Decorator: one ``repro.<name>`` span around each call."""
    return functools.partial(jax.profiler.annotate_function,
                             name=PREFIX + name)
