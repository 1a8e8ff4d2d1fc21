"""Device self time a train step under the ``layers`` scope and under no scope
inside it: what the ``lax.scan`` over the layer stack does itself (carried and
stacked buffers, the copies between the loop's body and its operands), median
over the traced steps, in milliseconds. Moves train_tokens_per_s_per_chip."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import TRAIN_MODULE


def read(run: dict):
    return program_trace.median_ms(run, TRAIN_MODULE, lambda scope, d: scope == "layers")
