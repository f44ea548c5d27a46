"""The one place that decides how a Pallas kernel runs.

A kernel lowered for the CPU backend (the test suite) runs in the Pallas
interpreter; lowered for a TPU it is compiled by Mosaic. The choice
follows the platform the enclosing program is lowered for — not an
option, not the process's default backend — so a program compiled ahead
of time for a described TPU gets the compiled kernel even in a CPU-only
process, and nothing on a TPU can quietly fall back to the interpreter.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kw):
    """``pl.pallas_call(kernel, **kw)`` with the interpret mode chosen by
    the lowering platform (interpreter on ``cpu``, compiled elsewhere)."""
    def call(*args):
        return jax.lax.platform_dependent(
            *args,
            cpu=lambda *a: pl.pallas_call(kernel, interpret=True, **kw)(*a),
            default=lambda *a: pl.pallas_call(kernel, **kw)(*a))
    return call
