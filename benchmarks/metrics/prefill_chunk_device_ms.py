"""Device time of one chunk-prefill program (``jit_chunk`` module events in
the device trace), median. Moves ttft_p95_s."""

from benchmarks.metrics._common import PREFILL_MODULE, median_module_ms


def read(run: dict):
    return median_module_ms(run, PREFILL_MODULE)
