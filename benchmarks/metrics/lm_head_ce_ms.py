"""Device self time a train step under the ``lm_head_ce`` scope (the lm-head
projection, the loss and its chunk scan): forward, backward and the
backward's recompute of the logits, median over the traced steps, in
milliseconds. Moves train_tokens_per_s_per_chip."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import TRAIN_MODULE


def read(run: dict):
    return program_trace.median_ms(run, TRAIN_MODULE, lambda scope, d: scope == "lm_head_ce")
