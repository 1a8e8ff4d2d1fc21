"""Chunk-prefill programs of OTHER prompts a decoding request sits behind, a
token: the sum of ``chunks`` over the sum of ``n_generated - 1``, over the
clean requests' ``decode_account`` (``_token_gap_account.py``). With the
traced medians, ``decode_step_device_ms`` + this x ``prefill_chunk_device_ms``
is the device's part of a token gap: the notes line prints that sum beside
the clean requests' mean gap, and the difference (the host's exposed part).
Moves tpot_p50_s."""

from benchmarks.metrics import _token_gap_account as A
from benchmarks.metrics._common import DECODE_MODULE, PREFILL_MODULE, median_module_ms


def read(run: dict):
    requests = A.clean_requests(run)
    if requests is None:
        return None
    chunks = A.total(requests, "n", "chunks")
    value = chunks / A.gaps(requests)
    own = {"chunks": chunks}
    step_ms, chunk_ms = median_module_ms(run, DECODE_MODULE), median_module_ms(run, PREFILL_MODULE)
    if step_ms is not None and chunk_ms is not None:
        device_ms = step_ms + value * chunk_ms
        own.update(device_ms_per_token=device_ms,
                   host_exposed_ms_per_token=A.mean_gap_ms(requests) - device_ms)
    A.note("prefill_chunks_per_token_gap", requests, **own)
    return value
