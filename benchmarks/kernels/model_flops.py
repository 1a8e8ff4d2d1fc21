"""The per-token law of a sparse-expert decoder's training step: only the
experts a token is routed to count, attention counts the causal half, and
recomputation (remat, flash's second pass over the scores) does not count.
A copy of the arithmetic in the program's ``utils/flops_utils.py
moe_transformer_flops_per_token``, kept here so that the yardstick does not
move with the program."""


def forward_flops_per_token(hf: dict, seq_len: int) -> float:
    d = int(hf["hidden_size"])
    q_dim = int(hf["num_attention_heads"]) * int(hf["head_dim"])
    kv_dim = int(hf["num_key_value_heads"]) * int(hf["head_dim"])
    layers = int(hf["num_hidden_layers"])
    width = int(hf.get("moe_intermediate_size") or hf["intermediate_size"])
    top_k = int(hf["num_experts_per_tok"])
    attn_proj = 2 * (d * (q_dim + 2 * kv_dim) + q_dim * d)
    attn_scores = 2 * 2 * q_dim * (seq_len / 2)  # QK^T and PV over the causal half
    experts = 2 * 3 * d * width * top_k
    head = 2 * d * int(hf["vocab_size"])
    return layers * (attn_proj + attn_scores + experts) + head


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """Forward plus backward: three times the forward."""
    return 3.0 * forward_flops_per_token(hf, seq_len)


def parameter_count(hf: dict) -> int:
    d = int(hf["hidden_size"])
    q_dim = int(hf["num_attention_heads"]) * int(hf["head_dim"])
    kv_dim = int(hf["num_key_value_heads"]) * int(hf["head_dim"])
    width = int(hf.get("moe_intermediate_size") or hf["intermediate_size"])
    n_exp = int(hf.get("num_experts") or hf["num_local_experts"])
    layer = d * (q_dim + 2 * kv_dim) + q_dim * d + n_exp * 3 * d * width + d * n_exp
    return int(hf["num_hidden_layers"]) * layer + 2 * d * int(hf["vocab_size"])
