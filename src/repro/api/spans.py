"""Host spans and counters of the training loop.

``Span(name)`` is a context manager that does two things at once: it
enters ``jax.profiler.TraceAnnotation(name)``, so that a profiler trace
shows the span on the same clock as the device's operations, and it keeps
its own ``perf_counter`` duration in ``seconds``, so that the loop can
report the same interval without a profiler.  With no profiler running
the annotation costs about a microsecond.

    with Span("train.feed") as feed:
        batch = dataset.next_device_batch()
    history_entry["feed_s"] = feed.seconds

``count(name, n)`` adds ``n`` to a process-wide counter and ``counters()``
returns a copy of them all: totals over every step the process ran, for
readers that take ratios (``Session.run`` adds each MoE step's expert rows).
"""
from __future__ import annotations

import time
from typing import Dict

import jax

_COUNTERS: Dict[str, int] = {}


def count(name: str, n: int) -> None:
    _COUNTERS[name] = _COUNTERS.get(name, 0) + int(n)


def counters() -> Dict[str, int]:
    return dict(_COUNTERS)


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
