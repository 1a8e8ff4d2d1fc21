"""Device time of one paged-decode program (``jit_step`` module events in the
device trace), median. Moves tpot_p95_s."""

from benchmarks.metrics._common import DECODE_MODULE, median_module_ms


def read(run: dict):
    return median_module_ms(run, DECODE_MODULE)
