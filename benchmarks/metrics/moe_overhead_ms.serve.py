"""Device self time of one paged-decode program under ``moe/router``,
``moe/dispatch`` and ``moe/combine``: everything of the sparse block but the
expert kernel (among it the split of the fused gate/up weight), median over
the traced ``jit_step`` programs, in milliseconds. Moves tpot_p50_s."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import DECODE_MODULE


def read(run: dict):
    return program_trace.median_ms(run, DECODE_MODULE, program_trace.moe_overhead)
