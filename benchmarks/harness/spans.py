"""The harness's own spans: around its calls into the program, on the host's
monotonic clock, and (in a traced run) in the profiler's trace as well."""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(f"bench:{name}")
        t0 = time.perf_counter()
        with ctx:
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str, t_from: float = 0.0, t_to: float = float("inf")) -> float:
        return sum(
            min(b, t_to) - max(a, t_from)
            for n, a, b in self.records
            if n == name and b > t_from and a < t_to
        )
