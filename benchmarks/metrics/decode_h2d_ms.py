"""Host time of the transfers before a decode launch: the ``serve.decode_h2d``
span (the two table / token copies and six ``jnp.asarray`` transfers inside
``serve.decode_dispatch``), median over the traced iterations, in
milliseconds. The notes line gives the median and count of the three spans
the host's part of a decode dispatch is made of (``decode_plan``,
``decode_h2d``, ``decode_launch``). A program without the span (an older
commit) gives None. Moves tpot_p50_s."""

import statistics

from benchmarks.harness import program_trace
from benchmarks.metrics._common import say

PARTS = ("serve.decode_plan", "serve.decode_h2d", "serve.decode_launch")


def read(run: dict):
    spans = program_trace.spans(run, "serve.decode_h2d")
    if spans is None:
        return None
    ms = {name: [1e3 * (s["end_s"] - s["start_s"]) for s in spans if s["name"] == name]
          for name in PARTS}
    say(program_trace="decode_dispatch_parts",
        ms_median_and_count={n: [round(statistics.median(v), 4), len(v)]
                             for n, v in ms.items() if v})
    return statistics.median(ms["serve.decode_h2d"])
