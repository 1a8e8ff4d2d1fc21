"""The paged decode-attention kernel's share of its roofline: the KV bytes
the traced decode steps had to read (context tokens of the active slots, as
the harness sampled them after each step; kernels/paged_attn.py) at the HBM
peak, over the ``paged_attention`` device time. Bound: bytes/s. Moves
tpot_p95_s."""

from benchmarks.harness import trace
from benchmarks.metrics._common import hf, kernel, say


def read(run: dict):
    a, red = run["artefacts"], run["reduction"]
    if a["kind"] != "serve" or not red or not red["devices"] or run["trace_window"][0] is None:
        return None
    k = kernel("paged_attn")
    c = hf(run)
    t0, t1 = run["trace_window"]
    tokens = sum(s[3] for s in a["steps"] if t0 <= s[0] and s[1] <= t1)
    seconds, calls = trace.op_time(red, k.TRACE_PATTERN)
    if not tokens or not seconds:
        return None
    need = k.kv_bytes(tokens, int(c["num_hidden_layers"]), int(c["num_key_value_heads"]),
                      int(c["head_dim"]))
    share = 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / seconds
    say(roofline="paged_attn_roofline", bound="bytes", needed_bytes=need,
        kernel_seconds=seconds, kernel_calls=calls)
    return share
