"""Causal self-attention over whole sequences (the splash kernels,
``splash_mha_*`` in the trace): QK^T and PV over the causal half, 2 ops a MAC;
backward needs twice the forward (dQ, dK, dV, dP). The kernel's second pass
over the scores in its backward is recomputation and is not counted. The
bound is FLOP/s."""

TRACE_PATTERN = r"^splash_mha"


def forward_flops(batch: int, seq: int, heads: int, head_dim: int) -> float:
    pairs = seq * (seq + 1) / 2
    return batch * heads * pairs * head_dim * 2 * 2


def train_flops(batch: int, seq: int, heads: int, head_dim: int) -> float:
    return 3.0 * forward_flops(batch, seq, heads, head_dim)
