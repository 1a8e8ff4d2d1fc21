"""Share of the fused decode kernel's grid steps that hold a live page: 100 x
the sum of ``attn_live_steps`` over the sum of ``attn_grid_steps`` of
``serve.counts`` over the traced iterations that decoded (a K/V layer's grid
is slots x groups of table entries; a group is live when one of its pages
holds a position the slot attends, by the kernel's own arithmetic). What is
left of the kernel's time in dead steps: low means a grid bounded by the
longest live table would still pay. A program whose counter has no such stat,
or whose decode never ran the kernel, gives None. Moves tpot_p50_s."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import say


def read(run: dict):
    rows = program_trace.iteration_counts(run)
    if not rows or any("attn_grid_steps" not in r for r in rows):
        return None
    grid = sum(r["attn_grid_steps"] for r in rows)
    live = sum(r["attn_live_steps"] for r in rows)
    if not grid:
        return None
    decoding = sum(1 for r in rows if r["attn_grid_steps"])
    say(program_trace="attn_grid", iterations=decoding,
        grid_steps_per_layer=grid / decoding, live_steps_per_layer=live / decoding)
    return 100.0 * live / grid
