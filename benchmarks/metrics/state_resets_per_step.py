"""Recurrent-state resets an iteration: the mean ``state_resets`` of
``serve.counts`` over the traced iterations (prompts whose FIRST chunk ran in
the iteration: the slot's conv state is read as zeros, whatever its previous
tenant left). Notes the mean ``state_slots`` beside it (slots holding live
state after the iteration). A program whose counter has no such stat gives
None. Moves tpot_p50_s."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import say


def read(run: dict):
    rows = program_trace.iteration_counts(run)
    if not rows or any("state_resets" not in r for r in rows):
        return None
    say(program_trace="state", iterations=len(rows),
        state_resets=sum(r["state_resets"] for r in rows),
        mean_state_slots=sum(r.get("state_slots", 0) for r in rows) / len(rows))
    return sum(r["state_resets"] for r in rows) / len(rows)
