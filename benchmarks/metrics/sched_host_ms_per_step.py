"""Host time of one scheduler iteration that is not waiting for the device:
``serve.step`` minus its ``*_wait`` children (the first-token wait after a
prompt's last chunk, the decode wait), median over the traced iterations, in
milliseconds. The notes line gives the median of every phase. Moves
tpot_p50_s."""

import statistics

from benchmarks.harness import program_trace
from benchmarks.metrics._common import say


def read(run: dict):
    its = program_trace.serve_iterations(run)
    if not its:
        return None
    host, by_phase = [], {}
    for step, kids in its:
        waits = sum(k["end_s"] - k["start_s"] for k in kids if k["name"].endswith("_wait"))
        host.append(1e3 * (step["end_s"] - step["start_s"] - waits))
        for k in kids:
            by_phase.setdefault(k["name"], []).append(1e3 * (k["end_s"] - k["start_s"]))
    say(program_trace="phases", iterations=len(its),
        step_ms_median=statistics.median(1e3 * (s["end_s"] - s["start_s"]) for s, _ in its),
        phase_ms_median_and_count={n: [round(statistics.median(v), 4), len(v)]
                                   for n, v in sorted(by_phase.items())})
    return statistics.median(host)
