"""Device time of one jitted train step: the median duration of the
``jit_step_fn`` module events in the device trace. Moves
train_tokens_per_s_per_chip."""

from benchmarks.metrics._common import TRAIN_MODULE, median_module_ms


def read(run: dict):
    return median_module_ms(run, TRAIN_MODULE)
