"""The solver's own host spans and counters, on the profiler's clock.

``span(name)`` enters ``jax.profiler.TraceAnnotation(name)``, which costs
almost nothing unless a profiler trace is running and, when one is,
shares its clock with the device ops. The span also adds its host
seconds and one count to a process-wide table; ``count(name, k)`` adds
``k`` to the same table. ``snapshot()`` reads the table, ``reset()``
clears it.

Spans sit only at host boundaries: set-up's stages (once per bucket at
most) and, a few times per ``run_until`` call, its launch, wait and
harvest; never per pass or per diagonal. The device side is named with
``jax.named_scope`` in the traced code, which changes op metadata only.
"""

from __future__ import annotations

import contextlib
import time

import jax

__all__ = ["count", "reset", "snapshot", "span"]

#: name -> {"n": count, "s": host seconds, "first_s": the first span's}
_table: dict[str, dict] = {}


@contextlib.contextmanager
def span(name: str):
    """A host span named ``name`` in the profiler's trace and the table."""
    t = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        dt = time.perf_counter() - t
        e = _table.setdefault(name, {"n": 0, "s": 0.0, "first_s": dt})
        e["n"] += 1
        e["s"] += dt


def count(name: str, k: int = 1) -> None:
    """Add ``k`` to the counter ``name``."""
    e = _table.setdefault(name, {"n": 0, "s": 0.0, "first_s": 0.0})
    e["n"] += int(k)


def snapshot() -> dict[str, dict]:
    """A copy of the table: for each name its count ``n``, and for a span
    its host seconds ``s`` and the first span's ``first_s``."""
    return {k: dict(v) for k, v in _table.items()}


def reset() -> None:
    _table.clear()
