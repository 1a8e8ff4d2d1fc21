"""Say where the host was when one call into the program took seconds.

A second thread looks, ten times a second, at how long the call the loop is
in has run; past ``threshold_s`` it keeps the main thread's innermost frames.
Beside it the loop records the main thread's own CPU seconds in every call,
and this file the seconds the garbage collector ran and the longest the
looking thread itself went unscheduled. Together they tell a collector pause
(gc seconds), Python or a C call that holds the interpreter (CPU seconds near
the wall seconds), a wait on the device or the runtime (no CPU, the looker
kept running, the frames name the call) and a frozen process or host (no
CPU, the looker stalled as well) apart.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
import traceback
from pathlib import Path


class StallWatch:
    def __init__(self, threshold_s: float = 1.0, poll_s: float = 0.1):
        self.threshold_s, self.poll_s = threshold_s, poll_s
        self.begin: float | None = None  # when the call the loop is in began
        self.seen: dict[float, list[str]] = {}  # call's begin -> frames, first look
        self.gc_s = 0.0
        self.gc_longest_s = 0.0
        self.looker_longest_gap_s = 0.0
        self._gc_t0 = 0.0
        self._main = threading.get_ident()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._look, name="bench-stall-watch", daemon=True)

    def start(self) -> "StallWatch":
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._gc_t0 = now
        else:
            self.gc_s += now - self._gc_t0
            self.gc_longest_s = max(self.gc_longest_s, now - self._gc_t0)

    def _look(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(self.poll_s):
            now = time.perf_counter()
            self.looker_longest_gap_s = max(self.looker_longest_gap_s, now - last)
            last = now
            begin = self.begin
            if begin is None or now - begin < self.threshold_s or begin in self.seen:
                continue
            frame = sys._current_frames().get(self._main)
            stack = traceback.extract_stack(frame)[-6:] if frame is not None else []
            self.seen[begin] = [f"{Path(f.filename).name}:{f.lineno}:{f.name}" for f in stack]
