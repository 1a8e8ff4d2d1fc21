"""The absorbed latent decode attention's share of its roofline: the rows a
decode step had to read (``latent_context_rows`` of ``serve.counts``: every
slot's positions, all layers, as the program counted them before the launch)
under ``kernels/latent_paged_attn.py``'s law (the larger of rows x 1,152 B at
the HBM peak and rows x 64 x (576 + 512) x 2 FLOP at the bf16 peak) over the
device self time a ``jit_step`` spends under the program's ``mla_latent_attn``
scope, WHATEVER implements it there; both as means a step over the traced
window. Notes say which bound. A program without the counter or the scope (an
older commit) gives None. Moves tpot_p50_s."""

from benchmarks.harness import loader, program_trace
from benchmarks.metrics._common import DECODE_MODULE, kernel, say, shapes

_directions = loader.load_module("metrics", "_segment_directions")


def read(run: dict):
    if run["artefacts"]["kind"] != "serve" or not run["peaks"]:
        return None
    counts = program_trace.iteration_counts(run)
    if not counts or any("latent_context_rows" not in r for r in counts):
        return None
    launched = [r["latent_context_rows"] for r in counts if r["latent_context_rows"]]
    k = kernel("latent_paged_attn")
    runs = _directions.per_run(run, DECODE_MODULE, k.SCOPE)
    if not launched or not runs:
        return None
    c = shapes(run)
    rows = sum(launched) / len(launched)
    seconds = sum(sum(r.values()) for r in runs) / len(runs)
    if not seconds:
        return None
    least, which = k.bound(rows, c["q_heads"], c["latent_width"], c["latent_rank"], run["peaks"])
    say(roofline="latent_attn_roofline.serve", bound=which, rows_per_step=rows,
        needed_bytes_per_step=k.row_bytes(rows, c["latent_width"]),
        needed_flops_per_step=k.flops(rows, c["q_heads"], c["latent_width"], c["latent_rank"]),
        scope_seconds_per_step=seconds, traced_steps=len(runs), counted_launches=len(launched))
    return 100.0 * least / seconds
