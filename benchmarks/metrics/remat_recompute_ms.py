"""Device self time a train step of the ops JAX marks ``rematted_computation``
(the forward run again inside the backward: the layer under
``backend.remat`` and the loss's chunk body), whatever their scope, median
over the traced steps, in milliseconds. Needs no scope of the program's, so
an older commit reads too. Moves train_tokens_per_s_per_chip."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import TRAIN_MODULE


def read(run: dict):
    return program_trace.median_ms(
        run, TRAIN_MODULE, lambda scope, d: d == "remat", need_scopes=False)
