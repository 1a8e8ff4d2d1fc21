"""The largest ``|row sum - 1|`` of any hyper-connection ``Hres`` in a step
(every sublayer, every token; the Sinkhorn rounds normalise the columns last):
the program's ``train.counts`` counter ``mhc_res_row_err`` (written inside
``train.place`` with the PREVIOUS step's number once it is ready), median over
the window's traced steps. It says whether the configured rounds converged at
these weights; a change to the iteration moves it. A program without the
counter gives None. Moves train_tokens_per_s_per_chip."""

import statistics

from benchmarks.harness import program_trace
from benchmarks.metrics._common import say


def read(run: dict):
    spans = program_trace.spans(run, "train.counts")
    if spans is None:
        return None
    errs = [float(s["stats"]["mhc_res_row_err"]) for s in spans
            if s["name"] == "train.counts" and "mhc_res_row_err" in s["stats"]]
    if not errs:
        return None
    say(program_trace="mhc_res_row_err", steps=len(errs), largest=max(errs))
    return statistics.median(errs)
