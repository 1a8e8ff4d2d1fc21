"""Share of a token gap the serve loop's thread spent blocked on the device:
100 x the sum of ``decode_wait`` + ``first_token_wait`` over the sum of
``decode_s``, over the clean requests' ``decode_account``
(``_token_gap_account.py``: requests that finished before the profiler
started, on the clock that timed them). High: the programs set the gap, speed
them. Falling towards 0: the host sets the pace (pack the transfers, keep the
tables on the device). Moves tpot_p50_s."""

from benchmarks.metrics import _token_gap_account as A


def read(run: dict):
    requests = A.clean_requests(run)
    if requests is None:
        return None
    waited = A.total(requests, "s", "decode_wait", "first_token_wait")
    A.note("token_gap_device_wait_pct", requests, device_wait_s=waited)
    return 100.0 * waited / sum(map(A.decode_s, requests))
