"""The splash attention kernels' share of their roofline in the train step:
causal FLOPs (forward + backward, the backward's recomputation not counted;
kernels/flash_attn.py) at the bf16 peak over the ``splash_mha_*`` device
time. Bound: FLOP/s. Moves train_tokens_per_s_per_chip."""

from benchmarks.harness import trace
from benchmarks.metrics._common import TRAIN_MODULE, hf, kernel, module_count, say


def read(run: dict):
    a, red = run["artefacts"], run["reduction"]
    if a["kind"] != "train" or not red or not red["devices"]:
        return None
    k = kernel("flash_attn")
    c = hf(run)
    steps = module_count(run, TRAIN_MODULE)
    seconds, calls = trace.op_time(red, k.TRACE_PATTERN)
    if not steps or not seconds:
        return None
    seq = a["seq_length"]
    batch_per_chip = a["tokens_per_step"] / seq / run["device"]["count"]
    need = k.train_flops(batch_per_chip, seq, int(c["num_attention_heads"]),
                         int(c["head_dim"])) * int(c["num_hidden_layers"]) * steps
    share = 100.0 * (need / run["peaks"]["bf16_flops_per_s"]) / seconds
    say(roofline="flash_attn_roofline", bound="flops", needed_flops=need,
        kernel_seconds=seconds, kernel_calls=calls, traced_steps=steps)
    return share
