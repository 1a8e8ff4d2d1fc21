"""Of the device's idle time that falls inside ``serve.step`` (each gap is cut
at the spans' edges and every piece goes to the innermost program span covering
it), the share that falls in a named child phase and not in the step's own remainder (expiry, drain, swap,
watchdog, the loop between the phases). The notes line gives the idle seconds
by phase, and what fell outside every program span (the harness's own loop).
Needs device idle gaps: no value off the chip. Moves tpot_p50_s."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import say


def read(run: dict):
    gaps = program_trace.device_gaps(run)
    spans = program_trace.spans(run, "serve.step")
    if not gaps or spans is None:
        return None
    by_phase: dict[str, float] = {}
    for seconds, inner, root in program_trace.gaps_by_span(gaps, spans):
        key = inner if root == "serve.step" else "outside_program_spans"
        by_phase[key] = by_phase.get(key, 0.0) + seconds
    outside = by_phase.pop("outside_program_spans", 0.0)
    in_step = sum(by_phase.values())
    say(program_trace="idle_by_phase",
        idle_seconds_by_phase={k: round(v, 6) for k, v in sorted(by_phase.items(),
                                                                key=lambda kv: -kv[1])},
        idle_seconds_outside_program_spans=round(outside, 6))
    if in_step <= 0:
        return None
    return 100.0 * (in_step - by_phase.get("serve.step", 0.0)) / in_step
