"""Rows in the decode programs a request lived through: the sum of ``decoded``
over the sum of ``decode_launched``, over the clean requests'
``decode_account`` (``_token_gap_account.py``): occupancy as requests feel it
(a request lives where the server is full), free of the trace stop that
``decode_occupancy_pct`` averages in. None where no decode program was launched
(a speculative engine). Moves tpot_p50_s."""

from benchmarks.metrics import _token_gap_account as A


def read(run: dict):
    requests = A.clean_requests(run)
    if requests is None:
        return None
    rows, launched = A.total(requests, "n", "decoded"), A.total(requests, "n", "decode_launched")
    if not launched:
        return None
    A.note("decode_rows_per_token_gap", requests, decoded_rows=rows, decode_launched=launched,
           launched_ahead=A.total(requests, "n", "decode_launched_ahead"))
    return rows / launched
