"""FLOPs formulas and MFU computation.

Parity: the reference's per-arch FLOPs formulas and `calculate_mfu`
(components/utils/flops_utils.py:18-172). TPU-native addition: a peak-FLOPs
table keyed by `jax.Device.device_kind` instead of GPU SKUs.
"""

from __future__ import annotations

from typing import Any, Optional

import jax

# Peak dense BF16 TFLOPs per chip. Sources: public TPU spec sheets.
# device_kind strings as reported by the JAX runtime.
TPU_PEAK_BF16_TFLOPS: dict[str, float] = {
    "TPU v4": 275.0,
    "TPU v5": 459.0,  # v5p
    "TPU v5p": 459.0,
    "TPU v5 lite": 197.0,  # v5e
    "TPU v5e": 197.0,
    "TPU v6 lite": 918.0,  # v6e / Trillium
    "TPU v6e": 918.0,
    "TPU7x": 2307.0,  # ironwood
}
_H100_PEAK_TFLOPS = 989.0  # the reference's MFU basis (performance-summary.md:70)


def device_table_lookup(
    table: dict[str, float], what: str, device: Optional[jax.Device] = None
) -> float:
    """``table[device_kind]`` of `device` (default: first local device),
    exact or by prefix. A TPU whose kind is not in the table is an ERROR —
    a rate over a guessed peak is worse than none — while a host CPU, which
    has no entry by design, gets NaN so the CPU suite runs with its roofline
    class `unknown`."""
    d = device or jax.devices()[0]
    kind = getattr(d, "device_kind", "")
    if kind in table:
        return table[kind]
    for k, v in table.items():
        if kind.lower().startswith(k.lower()):
            return v
    if d.platform == "tpu":
        raise ValueError(
            f"no {what} on record for TPU device_kind {kind!r}; add it, with "
            "its source, to the table it belongs in (utils/flops_utils.py "
            "TPU_PEAK_BF16_TFLOPS, telemetry/profiling/cost.py TPU_HBM_GBPS)"
        )
    return float("nan")


def device_peak_tflops(device: Optional[jax.Device] = None) -> float:
    """Peak BF16 TFLOPs of `device` (see ``device_table_lookup``)."""
    return device_table_lookup(TPU_PEAK_BF16_TFLOPS, "peak bf16 TFLOP/s", device)


def dense_transformer_flops_per_token(
    hidden_size: int,
    num_layers: int,
    intermediate_size: int,
    vocab_size: int,
    seq_len: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    *,
    num_gated_linear: int = 3,
    causal: bool = True,
) -> float:
    """Training FLOPs per token (fwd+bwd = 3x fwd matmul FLOPs) for a dense
    llama-style decoder (reference: llama2/llama3 formulas,
    utils/flops_utils.py:60-100).
    """
    q_dim = num_heads * head_dim
    kv_dim = num_kv_heads * head_dim
    # per-token fwd matmul MACs ×2 = FLOPs
    attn_proj = 2 * (hidden_size * (q_dim + 2 * kv_dim) + q_dim * hidden_size)
    # attention scores+values: 2 matmuls of [S, H]x[H, S]; causal halves it
    attn_sdp = 2 * 2 * q_dim * seq_len * (0.5 if causal else 1.0)
    mlp = 2 * num_gated_linear * hidden_size * intermediate_size
    per_layer = attn_proj + attn_sdp + mlp
    lm_head = 2 * hidden_size * vocab_size
    fwd = num_layers * per_layer + lm_head
    return 3.0 * fwd  # fwd + bwd(2x)


def avg_attended_context(seq_len: int, window: Optional[int] = None) -> float:
    """Average number of attended positions per token under a causal mask,
    optionally with a sliding window (reference gpt-oss accounting,
    utils/flops_utils.py:606-617: w(w+1)/2 + (S-w)·w attended pairs)."""
    if window is not None and window < seq_len:
        pairs = window * (window + 1) / 2 + (seq_len - window) * window
        return pairs / seq_len
    return seq_len * 0.5


def moe_transformer_flops_per_token(
    hidden_size: int,
    num_layers: int,
    moe_intermediate_size: int,
    num_active_experts: int,
    shared_expert_intermediate: int,
    vocab_size: int,
    seq_len: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    dense_intermediate_size: int = 0,
    num_dense_layers: int = 0,
    causal: bool = True,
    layer_windows: Optional[list] = None,
) -> float:
    """Training FLOPs per token for a MoE decoder: only ACTIVE experts count
    (reference mixtral/qwen3 formulas, utils/flops_utils.py:120-172).

    ``layer_windows``: per-layer sliding window (None = full attention) —
    windowed layers attend to ~window positions, not seq/2, and counting
    them at full length would inflate MFU (reference gpt-oss accounting,
    utils/flops_utils.py:652-697)."""
    q_dim = num_heads * head_dim
    kv_dim = num_kv_heads * head_dim
    attn_proj = 2 * (hidden_size * (q_dim + 2 * kv_dim) + q_dim * hidden_size)
    if layer_windows is None:
        layer_windows = [None] * num_layers
    attn_sdp_total = sum(
        2 * 2 * q_dim * (avg_attended_context(seq_len, w) if causal else seq_len)
        for w in layer_windows
    )
    moe_mlp = 2 * 3 * hidden_size * (
        moe_intermediate_size * num_active_experts + shared_expert_intermediate
    )
    dense_mlp = 2 * 3 * hidden_size * dense_intermediate_size
    n_moe = num_layers - num_dense_layers
    fwd = (
        num_layers * attn_proj
        + attn_sdp_total
        + n_moe * moe_mlp
        + num_dense_layers * dense_mlp
        + 2 * hidden_size * vocab_size
    )
    return 3.0 * fwd


def flops_per_token_for_config(cfg: Any, seq_len: int) -> float:
    """Dispatch on a TransformerConfig-like object (dense or MoE)."""
    moe = getattr(cfg, "moe", None)
    if moe is not None:
        layer_types = getattr(cfg, "layer_types", None) or None
        windows = None
        if layer_types and getattr(cfg, "sliding_window", None):
            windows = [
                cfg.sliding_window if lt == "sliding_attention" else None
                for lt in layer_types
            ]
        return moe_transformer_flops_per_token(
            layer_windows=windows,
            hidden_size=cfg.hidden_size,
            num_layers=cfg.num_layers,
            moe_intermediate_size=moe.moe_intermediate_size,
            num_active_experts=moe.num_experts_per_tok,
            shared_expert_intermediate=moe.shared_expert_intermediate_size,
            vocab_size=cfg.vocab_size,
            seq_len=seq_len,
            num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim,
            dense_intermediate_size=cfg.intermediate_size,
            num_dense_layers=getattr(moe, "num_dense_layers", 0),
        )
    return dense_transformer_flops_per_token(
        hidden_size=cfg.hidden_size,
        num_layers=cfg.num_layers,
        intermediate_size=cfg.intermediate_size,
        vocab_size=cfg.vocab_size,
        seq_len=seq_len,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
    )


def gpipe_bubble_fraction(pp: int, n_microbatches: int) -> float:
    """The GPipe-wavefront bubble law (S−1)/(m+S−1): fraction of a step a
    rank spends idle under the AD-transposed schedule (parallel/pp.py;
    measured to ±5%, docs/history/PROFILE_PP_r04.md)."""
    if pp <= 1:
        return 0.0
    return (pp - 1) / (n_microbatches + pp - 1)


def zero_bubble_fraction(
    pp: int,
    n_microbatches: int,
    zb_queue: Optional[int] = None,
    w_deferred_fraction: float = 1.0,
) -> float:
    """Analytic bubble for the B/W-split schedule (parallel/zero_bubble.py).

    Cost model in forward-units F: fwd tick = 1; B tick = 2 (per-tick stage
    recompute + activation-grad matmuls, the remat-equivalent memory bound);
    deferred W chunk = 1. Full deferral runs (M+pp−1) fwd ticks + (M+pp−1)
    B ticks + M flat bubble-free W chunks against 4M units of per-rank work:

        bubble = 3(pp−1) / (4M + 3(pp−1))  <  (pp−1)/(M+pp−1)  for all M.

    A bounded queue (zb_queue = Q < M) puts a W contraction on EVERY B
    tick (the ring pop executes uniformly under the synchronous-tick SPMD
    program, popping zeros until the queue fills), so bounded B ticks cost
    3 — the combined-schedule cost — and Q chunks remain for the flat
    flush. The bound is therefore a MEMORY escape hatch, not a speedup:
    it lands at (or a flush-tail sliver above) the GPipe law while capping
    stash memory at Q chunks; only full deferral realizes the bubble win.

    ``w_deferred_fraction`` (d): the share of W work actually deferred —
    1.0 for dense stages (all seven projections tapped); the MoE pipeline
    defers only the ATTENTION projections (expert/router dW stays on the B
    tick), so its d is the attention share of per-layer weight-grad FLOPs
    and the B tick costs 2 + (1-d). d → 0 recovers the GPipe law exactly.
    """
    if pp <= 1:
        return 0.0
    m = n_microbatches
    d = min(max(float(w_deferred_fraction), 0.0), 1.0)
    q = m if zb_queue is None else max(1, min(int(zb_queue), m))
    work = 4.0 * m
    if q >= m:  # full deferral: B wave at (3-d)/tick + flat flush of d·M
        total = (4.0 - d) * (m + pp - 1) + d * m
    else:  # bounded ring: combined-cost ticks + flat flush of Q live slots
        total = 4.0 * (m + pp - 1) + q * d
    return max(0.0, 1.0 - work / total)


def pipeline_bubble_fraction(
    pp: int,
    n_microbatches: int,
    schedule: str = "gpipe",
    zb_queue: Optional[int] = None,
    w_deferred_fraction: float = 1.0,
) -> float:
    """Dispatch on MeshConfig.pp_schedule — used by the train step's
    pp_bubble_fraction metric and the benchmark recipe."""
    if schedule == "zero_bubble":
        return zero_bubble_fraction(
            pp, n_microbatches, zb_queue, w_deferred_fraction
        )
    return gpipe_bubble_fraction(pp, n_microbatches)


def calculate_mfu(
    tokens_per_second_per_chip: float,
    flops_per_token: float,
    peak_tflops: Optional[float] = None,
) -> float:
    """Model FLOPs utilization in [0, 1] (reference: calculate_mfu,
    utils/flops_utils.py:18)."""
    peak = peak_tflops if peak_tflops is not None else device_peak_tflops()
    return tokens_per_second_per_chip * flops_per_token / (peak * 1e12)
