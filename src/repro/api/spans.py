"""Host spans of the training loop.

``Span(name)`` is a context manager that does two things at once: it
enters ``jax.profiler.TraceAnnotation(name)``, so that a profiler trace
shows the span on the same clock as the device's operations, and it keeps
its own ``perf_counter`` duration in ``seconds``, so that the loop can
report the same interval without a profiler.  With no profiler running
the annotation costs about a microsecond.

    with Span("train.feed") as feed:
        batch = dataset.next_device_batch()
    history_entry["feed_s"] = feed.seconds
"""
from __future__ import annotations

import time

import jax


class Span:
    __slots__ = ("name", "seconds", "_start", "_annotation")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._start
        self._annotation.__exit__(*exc)
