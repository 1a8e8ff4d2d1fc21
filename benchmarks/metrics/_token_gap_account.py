"""What the readers of the records' ``decode_account`` share.

Since PR 39 the terminal record of every request that decoded carries the
serve loop's own account over ``[first token, end]`` on the clock that timed
the request (``automodel_tpu/serving/loop_account.py``): seconds of the loop's
thread by phase (``s``; they sum to the request's ``decode_s``) and what the
loop did (``n``). A traced run's profiler slows the host, and its stop holds
the loop's thread for seconds, so the readers take the CLEAN requests only:
window requests (``w<i>``) that completed with the field and a token gap, and
finished before the profiler started (``t_done < trace_window[0]``; with no
trace window, all of them). Sums are over those requests, so a request weighs
by its token gaps as it does in ``tpot_p50_s``. Fewer than 20 of them, or a
program that does not write the field (an older commit): None.
"""

from __future__ import annotations

import re

from benchmarks.harness.traffic import percentile
from benchmarks.metrics._common import say

MIN_REQUESTS = 20
_WINDOW_ID = re.compile(r"^w\d+$")


def decode_s(rec: dict) -> float:
    """First token to end, as ``tpot_p50_s`` has it: ``(n - 1) / decode_tps``."""
    return (rec["n_generated"] - 1) / rec["decode_tps"]


def clean_requests(run: dict):
    a = run["artefacts"]
    if a.get("kind") != "serve":
        return None
    trace_t0 = (run.get("trace_window") or (None,))[0]
    clean = [
        r for r in a["records"]
        if _WINDOW_ID.match(str(r.get("request_id", "")))
        and r.get("completion_reason") in ("stop", "length")
        and r.get("decode_account") and r.get("n_generated", 0) > 1 and r.get("decode_tps")
        and (trace_t0 is None or r["t_done"] < trace_t0)
    ]
    return clean if len(clean) >= MIN_REQUESTS else None


def total(requests: list, part: str, *keys: str) -> float:
    return sum(r["decode_account"][part].get(k, 0) for r in requests for k in keys)


def gaps(requests: list) -> int:
    return sum(r["n_generated"] - 1 for r in requests)


def mean_gap_ms(requests: list) -> float:
    """The clean requests' token gap, a request weighing by its gaps."""
    return 1e3 * sum(map(decode_s, requests)) / gaps(requests)


def ms_per_token(requests: list) -> dict:
    """Each bucket's seconds over the token gaps, in ms a token."""
    buckets = sorted({k for r in requests for k in r["decode_account"]["s"]})
    return {k: 1e3 * total(requests, "s", k) / gaps(requests) for k in buckets}


def note(metric: str, requests: list, **own) -> None:
    """The notes line: the full split for all clean requests and for those
    between the 40th and 60th percentile of the token gap (the requests that
    ARE ``tpot_p50_s``), and how far a record's seconds are from its
    ``decode_s`` at worst."""
    gap = [decode_s(r) / (r["n_generated"] - 1) for r in requests]
    lo, hi = percentile(gap, 0.4), percentile(gap, 0.6)
    middle = [r for r, g in zip(requests, gap) if lo <= g <= hi]
    say(token_gap_account=metric, requests=len(requests), token_gaps=gaps(requests),
        mean_gap_ms=mean_gap_ms(requests), ms_per_token=ms_per_token(requests),
        requests_p40_to_p60=len(middle), ms_per_token_p40_to_p60=ms_per_token(middle),
        largest_residual=max(
            abs(sum(r["decode_account"]["s"].values()) - decode_s(r)) / decode_s(r)
            for r in requests),
        **own)
