"""Device self time a train step spends under the program's ``mhc`` scope
segment: the hyper-connection residual path of every sublayer (the coefficients
with their norm, product and Sinkhorn rounds; the pre-mix; the post-mix), all
blocks, the multi-token-prediction module's included, median over the traced
steps, in milliseconds; the notes split it by segment (``mhc_coeff``,
``mhc_pre``, ``mhc_post``) and by direction. A program without the scope (an
older commit) gives None. Moves train_tokens_per_s_per_chip."""

import statistics

from benchmarks.harness import loader
from benchmarks.metrics._common import TRAIN_MODULE, say

_directions = loader.load_module("metrics", "_segment_directions")


def read(run: dict):
    if run["artefacts"]["kind"] != "train":
        return None
    rows = _directions.per_run(run, TRAIN_MODULE, "mhc")
    if not rows:
        return None
    med = lambda rs, d: 1e3 * statistics.median(r[d] for r in rs)
    note = {f"mhc_{d}_ms": med(rows, d) for d in ("fwd", "bwd", "remat")}
    for segment in ("mhc_coeff", "mhc_pre", "mhc_post"):
        part = _directions.per_run(run, TRAIN_MODULE, segment)
        if part:
            note.update({f"{segment}_{d}_ms": med(part, d) for d in ("fwd", "bwd", "remat")})
    say(program_trace="mhc", traced_steps=len(rows), **note)
    return 1e3 * statistics.median(sum(r.values()) for r in rows)
