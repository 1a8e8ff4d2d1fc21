"""Share of a decode step's router picks that landed on the experts this chip
holds: 100 x the sum of ``held_expert_rows`` of ``serve.counts`` over slots x
experts a token x expert layers x the decode steps READ in the traced
iterations (those whose ``expert_grid_units`` is not 0; every slot's row is
routed, active or not). The balanced share is held / published experts (16 /
128 = 12.5 %): whether the seeded router deals this chip its share of rows,
which is what the held experts' weight stream scales with. A program without
the counter, or one whose decode does not run the fused expert kernel, gives
None. Moves tpot_p50_s."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import say, shapes


def read(run: dict):
    rows = program_trace.iteration_counts(run)
    if not rows or any("held_expert_rows" not in r for r in rows):
        return None
    read_steps = sum(1 for r in rows if r.get("expert_grid_units"))
    if not read_steps:
        return None
    c = shapes(run)
    held = sum(r["held_expert_rows"] for r in rows)
    picks = read_steps * run["artefacts"]["slots"] * c["top_k"] * c["expert_layers"]
    say(program_trace="held_experts", steps_read=read_steps, held_rows_per_step=held / read_steps,
        balanced_pct=100.0 * c["held_experts"] / c["published_experts"])
    return 100.0 * held / picks
