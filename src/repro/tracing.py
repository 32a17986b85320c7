"""Host spans of the program, in the JAX profiler's own trace.

Every span enters a ``jax.profiler.TraceAnnotation`` (``step`` a
``StepTraceAnnotation``), so a profiled run finds it on the same clock as
the device's ops.  Counts given to a span travel as the annotation's
metadata.  While :func:`recording` is active, spans also add their
``perf_counter`` durations, call counts and counts to in-memory totals
keyed by name: the record of the part of a run no profiler covers, such
as data preparation.

With no recorder active a span costs one annotation and one check of a
module global; it keeps nothing, and no span ever reads a device value.

    with tracing.span("fed.dispatch"):
        state, m = step(state, batch, key, **kwargs)

    with tracing.recording() as totals:
        run()
    totals["fed.batch"]  # {"calls": n, "seconds": s, "counts": {...}}
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator

import jax

Totals = Dict[str, Dict]

_totals = None   # the active recorder's totals, or None


class span:
    """Context manager: one host span named ``name``, with ``counts``."""

    __slots__ = ("name", "counts", "_ann", "_t0")

    def __init__(self, name: str, **counts: int):
        self.name, self.counts, self._t0 = name, counts, None
        self._ann = jax.profiler.TraceAnnotation(name, **counts)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        if _totals is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._t0 is not None and _totals is not None:
            dt = time.perf_counter() - self._t0
            tot = _totals.get(self.name)
            if tot is None:
                tot = _totals[self.name] = {"calls": 0, "seconds": 0.0,
                                            "counts": {}}
            tot["calls"] += 1
            tot["seconds"] += dt
            for k, v in self.counts.items():
                tot["counts"][k] = tot["counts"].get(k, 0) + v
        self._ann.__exit__(*exc)


class step(span):
    """A span that marks one step of a loop (``step_num``) for the
    profiler's per-step views; its totals are those of any span."""

    __slots__ = ()

    def __init__(self, name: str, step_num: int):
        self.name, self.counts, self._t0 = name, {}, None
        self._ann = jax.profiler.StepTraceAnnotation(name, step_num=step_num)


@contextlib.contextmanager
def recording() -> Iterator[Totals]:
    """Collect the totals of every span entered inside the block; an
    enclosing recorder resumes afterwards.  The totals take no lock: for
    spans of one thread at a time."""
    global _totals
    outer, _totals = _totals, {}
    try:
        yield _totals
    finally:
        _totals = outer
