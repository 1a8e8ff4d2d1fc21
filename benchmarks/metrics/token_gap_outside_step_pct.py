"""Share of a token gap spent between one ``engine.step()``'s return and the
next's entry (the front end: the harness's submit loop, a server's queue
handling): 100 x the sum of ``outside_step`` over the sum of ``decode_s``,
over the clean requests' ``decode_account`` (``_token_gap_account.py``).
Moves tpot_p50_s."""

from benchmarks.metrics import _token_gap_account as A


def read(run: dict):
    requests = A.clean_requests(run)
    if requests is None:
        return None
    outside = A.total(requests, "s", "outside_step")
    A.note("token_gap_outside_step_pct", requests, outside_step_s=outside)
    return 100.0 * outside / sum(map(A.decode_s, requests))
