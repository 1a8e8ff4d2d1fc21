"""Named timers for benchmarking.

Parity: Megatron-style `Timers` (reference: components/training/timers.py:
257-346 — barriered start/stop with min/max across ranks). Single-controller
JAX needs no cross-rank reduction: one process observes the whole step. The
device sync happens by blocking on a data transfer (`jax.device_get`): the
value cannot arrive before the step that produces it has run.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Optional

import jax

# retained raw entries per timer; aggregates (count/mean/min/max) stay exact
# for the whole run regardless — the cap only bounds host memory on
# million-step runs where the train loop times every step
_MAX_HISTORY = 4096


class Timer:
    def __init__(self, name: str):
        self.name = name
        self._start: Optional[float] = None
        self.elapsed_history: deque[float] = deque(maxlen=_MAX_HISTORY)
        self._pending: deque[float] = deque(maxlen=_MAX_HISTORY)
        self._count = 0
        self._sum = 0.0
        self._min = float("inf")
        self._max = 0.0

    def start(self, barrier_on: Any = None) -> None:
        if barrier_on is not None:
            jax.block_until_ready(barrier_on)
        self._start = time.perf_counter()

    def stop(self, barrier_on: Any = None) -> float:
        if barrier_on is not None:
            jax.block_until_ready(barrier_on)
        assert self._start is not None, f"timer {self.name} not started"
        dt = time.perf_counter() - self._start
        self.elapsed_history.append(dt)
        self._pending.append(dt)
        self._count += 1
        self._sum += dt
        self._min = dt if dt < self._min else self._min
        self._max = dt if dt > self._max else self._max
        self._start = None
        return dt

    @property
    def count(self) -> int:
        return self._count

    def mean(self, skip_first: int = 0) -> float:
        if skip_first:  # over the retained window only
            h = list(self.elapsed_history)[skip_first:]
            return sum(h) / max(len(h), 1)
        return self._sum / max(self._count, 1)

    def min(self, skip_first: int = 0) -> float:
        if skip_first:
            h = list(self.elapsed_history)[skip_first:]
            return min(h) if h else 0.0
        return self._min if self._count else 0.0

    def max(self, skip_first: int = 0) -> float:
        if skip_first:
            h = list(self.elapsed_history)[skip_first:]
            return max(h) if h else 0.0
        return self._max

    def drain(self) -> list[float]:
        """Entries recorded since the previous drain. Lets a periodic
        consumer (per-log-window step-time decomposition) report window
        means while `summary()` keeps the whole-run view."""
        new = list(self._pending)
        self._pending.clear()
        return new


class Timers:
    def __init__(self):
        self._timers: dict[str, Timer] = {}

    def __call__(self, name: str) -> Timer:
        if name not in self._timers:
            self._timers[name] = Timer(name)
        return self._timers[name]

    def drain_means(self) -> dict[str, float]:
        """Per-timer mean over the entries recorded since the last drain;
        timers with no new entries are omitted."""
        out: dict[str, float] = {}
        for n, t in self._timers.items():
            new = t.drain()
            if new:
                out[n] = sum(new) / len(new)
        return out

    def summary(self, skip_first: int = 0) -> dict[str, dict[str, float]]:
        return {
            n: {
                "mean_s": t.mean(skip_first),
                "min_s": t.min(skip_first),
                "max_s": t.max(skip_first),
                "count": t.count,
            }
            for n, t in self._timers.items()
        }
