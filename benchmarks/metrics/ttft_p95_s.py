"""95th percentile, over every request due in the window, of first token minus
the time the request was DUE (an unfinished request counts as the worst).
Recorded, not bounded: over 245 requests in 51 s it spreads by 15-19 %
between seeds (which long prompts meet decides the twelve worst), and one
stall of seconds moves it threefold. Moves tpot_p50_s."""

import math

from benchmarks.harness.traffic import percentile


def read(run: dict):
    a = run["artefacts"]
    if a["kind"] != "serve" or not a["ttft_s"]:
        return None
    v = percentile(a["ttft_s"], 0.95)
    return v if math.isfinite(v) else None
