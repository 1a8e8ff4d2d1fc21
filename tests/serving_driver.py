"""Open-loop driver for the serving engine's tests: each request is
submitted when its offset is due (wall clock) and the engine steps in
between, the way a front end drives `submit` / `step`."""

from __future__ import annotations

import time
from collections import deque


def drive(engine, arrivals, after_step=None) -> list[dict]:
    """``arrivals``: [(offset_s, prompt_ids, max_new_tokens|None)]. Runs
    until every request is terminal; ``after_step(engine)`` is called after
    each step. → the terminal records, in completion order."""
    pending = deque(sorted(arrivals, key=lambda a: a[0]))
    t0 = time.perf_counter()
    out: list[dict] = []
    while pending or not engine.idle():
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            _, prompt, max_new = pending.popleft()
            engine.submit(prompt, max_new_tokens=max_new)
        if engine.idle():
            time.sleep(min(0.001, max(pending[0][0] - now, 0.0)))
            continue
        out.extend(engine.step())
        if after_step is not None:
            after_step(engine)
    return out
