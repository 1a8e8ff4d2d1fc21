"""95th percentile, over every request due in the window, of the time from
when it was due to when the scheduler admitted it to a slot (the records'
``queue_s``; a request that never finished counts as the worst). Moves
tpot_p50_s (a queue means every slot is busy)."""

import math

from benchmarks.harness.traffic import percentile


def read(run: dict):
    a = run["artefacts"]
    if a["kind"] != "serve" or not a["queue_s"]:
        return None
    v = percentile(a["queue_s"], 0.95)
    return v if math.isfinite(v) else None
