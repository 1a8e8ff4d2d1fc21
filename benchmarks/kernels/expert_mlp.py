"""The routed expert MLP (gate/up, SwiGLU, down): what the algorithm needs.

Found in the device trace by the names of the program's Pallas kernels:
``fused_expert_mlp_fwd`` and the three backward kernels ``_bwd_gu``,
``_bwd_dwd``, ``_bwd_dx``, and the grouped matmuls ``gmm`` / ``tgmm``.
Operations count routed rows only (tokens x experts per token): padding to a
tile, worst-case capacity and recomputation are the kernel's cost, not the
algorithm's need. The bound is FLOP/s (at 8192 tokens every expert sees
hundreds of rows, so the weights are read once for many rows)."""

TRACE_PATTERN = r"^(fused_expert_mlp_(fwd|bwd_gu|bwd_dwd|bwd_dx)|t?gmm)(\.\d+)?$"
FWD_PATTERN = r"^fused_expert_mlp_fwd(\.\d+)?$"


def forward_flops(routed_rows: int, hidden: int, width: int) -> float:
    """gate and up (2 x hidden x width each) and down (width x hidden), 2 ops a MAC."""
    return 2.0 * 3.0 * hidden * width * routed_rows


def train_flops(tokens: int, top_k: int, hidden: int, width: int) -> float:
    """Forward plus backward (input and weight gradients): three forwards."""
    return 3.0 * forward_flops(tokens * top_k, hidden, width)
