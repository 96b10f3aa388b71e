"""Names for the program's own work in a profile.

``scope(name)`` names traced code: every operation traced inside it carries
``repro.<name>`` in its HLO ``op_name``, which survives compilation, so a
device operation can be put down to the program layer that issued it. It
is metadata only and costs nothing at run time. It must sit inside the
traced function: around an eager call to a jitted function, or around an
eager operation, it reaches nothing.

``span(name)`` marks host code on the profiler's clock (the clock the
benchmark's own ``bench.`` spans use). It is a no-op unless a profiler
session is on.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def scope(name: str):
    """``jax.named_scope("repro.<name>")``, for traced code."""
    return jax.named_scope(PREFIX + name)


def span(name: str):
    """``jax.profiler.TraceAnnotation("repro.<name>")``, for host code."""
    return jax.profiler.TraceAnnotation(PREFIX + name)
