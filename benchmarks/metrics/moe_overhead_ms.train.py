"""Device self time a train step under ``moe/router``, ``moe/dispatch`` and
``moe/combine``: everything of the sparse block but its expert matmuls (sort,
permute, the gate/up weight split and casts, the weighted sum, the router),
median over the traced steps, in milliseconds. Moves
train_tokens_per_s_per_chip."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import TRAIN_MODULE


def read(run: dict):
    return program_trace.median_ms(run, TRAIN_MODULE, program_trace.moe_overhead)
