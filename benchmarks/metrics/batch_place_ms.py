"""Host time a train step spends making its batch: the program's
``train.collate`` (stacking a group of microbatches) plus ``train.place``
(putting it on the mesh), each the median over the traced steps, in
milliseconds. The device waits for it at every step boundary. Moves
train_tokens_per_s_per_chip."""

import statistics

from benchmarks.harness import program_trace
from benchmarks.metrics._common import say


def read(run: dict):
    spans = program_trace.spans(run, "train.")
    if spans is None:
        return None
    ms = lambda name: [1e3 * (s["end_s"] - s["start_s"]) for s in spans if s["name"] == name]
    collate, place = ms("train.collate"), ms("train.place")
    if not collate or not place:
        return None
    say(program_trace="input_path", collate_ms_median=statistics.median(collate),
        place_ms_median=statistics.median(place), groups=len(collate))
    return statistics.median(collate) + statistics.median(place)
