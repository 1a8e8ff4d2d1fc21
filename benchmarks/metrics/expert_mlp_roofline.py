"""The routed expert MLP's share of its roofline in the train step: the
FLOPs the algorithm needs for the traced steps (routed rows x top-k, no
padding, no recompute; kernels/expert_mlp.py) at the bf16 peak, over the
device time of the fused expert kernels and grouped matmuls. Bound: FLOP/s.
Moves train_tokens_per_s_per_chip."""

from benchmarks.harness import trace
from benchmarks.metrics._common import TRAIN_MODULE, hf, kernel, module_count, say


def read(run: dict):
    a, red = run["artefacts"], run["reduction"]
    if a["kind"] != "train" or not red or not red["devices"]:
        return None
    k = kernel("expert_mlp")
    c = hf(run)
    steps = module_count(run, TRAIN_MODULE)
    seconds, calls = trace.op_time(red, k.TRACE_PATTERN)
    if not steps or not seconds:
        return None
    tokens_per_chip = a["tokens_per_step"] / run["device"]["count"]
    width = int(c.get("moe_intermediate_size") or c["intermediate_size"])
    need = k.train_flops(tokens_per_chip, int(c["num_experts_per_tok"]),
                         int(c["hidden_size"]), width) * int(c["num_hidden_layers"]) * steps
    share = 100.0 * (need / run["peaks"]["bf16_flops_per_s"]) / seconds
    say(roofline="expert_mlp_roofline", bound="flops", needed_flops=need,
        kernel_seconds=seconds, kernel_calls=calls, traced_steps=steps)
    return share
