"""The harness's own spans around its calls into the program.

Every span is stamped on the host clock (``time.perf_counter``).  In a
traced run it is also a ``jax.profiler.TraceAnnotation``, so the same span
sits on the device trace's clock and ``trace_reduce`` can say what the
host was doing during an idle gap.
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.rows: dict = {}        # name -> [(t0, t1)] host clock

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.rows.setdefault(name, []).append((t0, time.perf_counter()))

    def durations(self, name: str, lo: float = 0.0,
                  hi: float = float("inf")) -> list:
        return [b - a for a, b in self.rows.get(name, [])
                if a >= lo and b <= hi]
