"""Chunk-prefill programs an iteration ran beside its decode program: the mean
``chunks`` of ``serve.counts`` over the traced iterations whose decode
program ran (``decoded`` > 0). Every chunk delays that iteration's tokens by
one program. Moves tpot_p50_s."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import say


def read(run: dict):
    rows = program_trace.iteration_counts(run)
    if not rows:
        return None
    decoding = [r for r in rows if r["decoded"] > 0]
    say(program_trace="counts", iterations=len(rows), decoding_iterations=len(decoding),
        admitted=sum(r["admitted"] for r in rows), chunks=sum(r["chunks"] for r in rows),
        finished=sum(r["finished"] for r in rows),
        mean_decoded_slots=sum(r["decoded"] for r in decoding) / max(len(decoding), 1))
    if not decoding:
        return None
    return sum(r["chunks"] for r in decoding) / len(decoding)
