"""95th percentile over requests of (last token - first token) / (tokens - 1).
Recorded, not bounded: between seeds it spreads by 6-15 % in a 51 s window,
and one stall of seconds moves it by half. Moves tpot_p50_s."""

import math

from benchmarks.harness.traffic import percentile


def read(run: dict):
    a = run["artefacts"]
    if a["kind"] != "serve" or not a["tpot_s"]:
        return None
    v = percentile(a["tpot_s"], 0.95)
    return v if math.isfinite(v) else None
