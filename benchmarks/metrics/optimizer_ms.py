"""Device self time a train step under the ``optimizer`` and ``grad_clip``
scopes (the global norm, the clip, Adam's update, applying it), median over
the traced steps, in milliseconds. Moves train_tokens_per_s_per_chip."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import TRAIN_MODULE


def read(run: dict):
    return program_trace.median_ms(
        run, TRAIN_MODULE, lambda scope, d: scope in ("optimizer", "grad_clip"))
