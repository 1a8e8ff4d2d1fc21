"""Share of the decode programs that were launched AHEAD: 100 x the sum of
``decode_launched_ahead`` over the sum of ``decode_launched`` of
``serve.counts`` over the traced iterations (a launch is ahead when the decode
step before it was still running once this one was queued behind it, by
``jax.Array.is_ready()`` of that step's tokens: the device went from one
program to the next without waiting for the host). Low means the serve loop
still serialises launch and read: the host's work of an iteration stands
between two decode programs instead of beside one. The notes line gives the
launches, those ahead, and ``discarded_rows`` (rows of a step launched for a
slot that had ended or changed hands by the time its tokens were read). A
program whose counter has no such stat, or that launched no decode program in
the trace, gives None. Moves tpot_p50_s."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import say


def read(run: dict):
    rows = program_trace.iteration_counts(run)
    if not rows or any("decode_launched" not in r for r in rows):
        return None
    launched = sum(r["decode_launched"] for r in rows)
    if not launched:
        return None
    ahead = sum(r["decode_launched_ahead"] for r in rows)
    say(program_trace="decode_launch", iterations=len(rows), launched=launched,
        launched_ahead=ahead, discarded_rows=sum(r["discarded_rows"] for r in rows))
    return 100.0 * ahead / launched
