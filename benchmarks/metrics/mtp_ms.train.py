"""Device self time a train step spends under the program's ``mtp`` scope: the
multi-token-prediction module's next-token embedding, its two norms, its
projection and its block (attention, experts, both hyper-connection
sublayers), forward, backward and recompute, median over the traced steps, in
milliseconds; the notes split it by direction. Its pass through the head and
the loss is under ``lm_head_ce`` with the main one (``lm_head_ce_ms`` holds
both). A program without the scope gives None. Moves
train_tokens_per_s_per_chip."""

import statistics

from benchmarks.harness import loader
from benchmarks.metrics._common import TRAIN_MODULE, say

_directions = loader.load_module("metrics", "_segment_directions")


def read(run: dict):
    if run["artefacts"]["kind"] != "train":
        return None
    rows = _directions.per_run(run, TRAIN_MODULE, "mtp")
    if not rows:
        return None
    say(program_trace="mtp", traced_steps=len(rows),
        **{f"mtp_{d}_ms": 1e3 * statistics.median(r[d] for r in rows) for d in ("fwd", "bwd", "remat")})
    return 1e3 * statistics.median(sum(r.values()) for r in rows)
