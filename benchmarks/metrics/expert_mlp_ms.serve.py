"""Device time of the fused expert forward inside one paged-decode step:
the ``fused_expert_mlp_fwd`` ops that ran inside ``jit_step`` module events,
over the number of those events, in milliseconds. (Its roofline share needs
the count of experts a step touched, which only the program can give.)
Moves tpot_p95_s."""

from benchmarks.harness import trace
from benchmarks.metrics._common import DECODE_MODULE, kernel, module_count


def read(run: dict):
    red = run["reduction"]
    if run["artefacts"]["kind"] != "serve" or not red or not red["devices"]:
        return None
    steps = module_count(run, DECODE_MODULE)
    if not steps:
        return None
    seconds = trace.module_op_time(red, DECODE_MODULE, kernel("expert_mlp").FWD_PATTERN)
    return 1e3 * seconds / steps if seconds else None
