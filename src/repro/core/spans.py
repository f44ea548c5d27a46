"""Host spans of the store on the profiler's clock (DESIGN.md §15).

``span(name)`` is a ``jax.profiler.TraceAnnotation`` named
``flashstore.<name>``: it lands in the same profiler trace, on the same
clock, as the device's operations, so a trace of a running store says
which host step of the program each idle stretch of the device fell
in. It records nothing unless a profiler trace is being taken, and
costs about a microsecond a span when none is.

Spans open at whole calls and steps, never per key and never inside a
jitted function (device steps carry ``jax.named_scope`` names instead).
A process that never imported JAX cannot be taking a trace, so the
simulator-only store stays JAX-free: there ``span`` is a null context.
"""
from __future__ import annotations

import contextlib
import functools
import sys

PREFIX = "flashstore."


def span(name: str):
    """Context manager timing one host step as ``flashstore.<name>``."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(PREFIX + name)


def traced(name: str):
    """Decorator: each call runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


@contextlib.contextmanager
def holding(lock, name: str):
    """Hold ``lock`` (any context manager) for the block, timing the wait
    to enter it as span ``name``."""
    with span(name):
        lock.__enter__()
    try:
        yield
    finally:
        lock.__exit__(None, None, None)
