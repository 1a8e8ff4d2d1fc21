"""Context tokens the decode program's attention had to cover, as the program
counts them: the mean ``context_tokens`` of ``serve.counts`` (sum of the active
slots' lengths as the decode program read them) over the traced iterations
whose decode program ran. A count of work, not a goal.

The harness samples ``engine._lengths * engine._active`` AFTER each step; over
the same steps the two agree by
    after = context_tokens + decoded - freed,
the step having added one token an active slot and freed the slots it
finished (a request that ends at its decode holds prompt + generated - 1
tokens then). The notes line gives the largest residual of that relation over
the traced steps. Moves tpot_p50_s."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import say


def read(run: dict):
    rows = program_trace.iteration_counts(run)
    if not rows:
        return None
    decoding = [r for r in rows if r["decoded"] > 0]
    if not decoding:
        return None
    a = run["artefacts"]
    t0, t1 = run["trace_window"]
    note = {"harness_steps_in_trace": None}
    if a.get("kind") == "serve" and t0 is not None:
        steps = [s for s in a["steps"] if t0 <= s[0] and s[1] <= t1]
        note["harness_steps_in_trace"] = len(steps)
        if len(steps) == len(rows):
            freed_at: dict[float, int] = {}
            for rec in a["records"]:
                if rec.get("completion_reason") in ("stop", "length") and rec["n_generated"] >= 2:
                    freed_at[rec["t_done"]] = freed_at.get(rec["t_done"], 0) + (
                        rec["prompt_tokens"] + rec["n_generated"] - 1)
            residuals = [s[3] - (r["context_tokens"] + r["decoded"] - freed_at.get(s[1], 0))
                         for s, r in zip(steps, rows)]
            note.update(harness_after_step_mean=sum(s[3] for s in steps) / len(steps),
                        relation_largest_residual=max(abs(x) for x in residuals),
                        relation_steps_off=sum(1 for x in residuals if x))
    say(program_trace="context_tokens", program_iterations=len(rows), **note)
    return sum(r["context_tokens"] for r in decoding) / len(decoding)
