"""The hyper-connection residual path's share of its roofline in the train
step: the bytes the stream MUST move (kernels/mhc.py: a sublayer's forward
reads X and y and writes X' and u, its backward reads X, dX', y, du and writes
dX, dy; bfloat16; every sublayer of every block) at the HBM peak over the
device self time under the program's ``mhc_pre`` and ``mhc_post`` scopes,
WHATEVER implements them, recomputation included in the time and not in the
need. Bound: bytes/s. A program without the scopes gives None. Moves
train_tokens_per_s_per_chip."""

from benchmarks.harness import loader
from benchmarks.metrics._common import TRAIN_MODULE, kernel, say, shapes

_directions = loader.load_module("metrics", "_segment_directions")


def read(run: dict):
    a = run["artefacts"]
    if a["kind"] != "train" or not run["peaks"]:
        return None
    k = kernel("mhc")
    per_scope = [_directions.per_run(run, TRAIN_MODULE, s) for s in k.SCOPES]
    if not all(per_scope):
        return None
    c = shapes(run)
    steps = len(per_scope[0])
    seconds = sum(sum(r.values()) for rows in per_scope for r in rows)
    if not seconds:
        return None
    tokens = a["tokens_per_step"] / run["device"]["count"]
    need = k.train_bytes(tokens, c["hc_streams"], c["hidden"]) * c["hc_sublayers"] * steps
    say(roofline="mhc_stream_roofline", bound="bytes", needed_bytes=need,
        scope_seconds=seconds, traced_steps=steps)
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / seconds
