"""Median, over every request due in the window, of first token minus the time
the request was DUE (an unfinished request counts as the worst). Recorded, not
bounded: between seeds it spreads by 3-5 % in a 51 s window, 12 % with a
stalled run among six. Moves tpot_p50_s (both are set by the iteration of one
decode plus one chunk a prefilling slot)."""

import math

from benchmarks.harness.traffic import percentile


def read(run: dict):
    a = run["artefacts"]
    if a["kind"] != "serve" or not a["ttft_s"]:
        return None
    v = percentile(a["ttft_s"], 0.5)
    return v if math.isfinite(v) else None
