"""Generic dense-transformer config + per-module kernel backend selection.

Parity: the reference's `BackendConfig` (components/models/common/utils.py:139)
selects per-module kernels (attn ∈ {te, sdpa, flex}, linear, rms_norm,
experts, dispatcher). TPU equivalents: attn ∈ {sdpa, flash, ring}, rms_norm ∈
{xla}, plus XLA-level knobs the reference expresses through torch.compile
(remat policy, scan over layers, dtypes).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp

from automodel_tpu.ops.rope import RopeConfig

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


def dtype_from_str(s: str | Any) -> Any:
    """Parity: shared/utils.py dtype_from_str."""
    if not isinstance(s, str):
        return s
    return _DTYPES[s.replace("torch.", "").replace("jnp.", "")]


@dataclasses.dataclass(frozen=True)
class BackendConfig:
    """Per-module kernel/backing choices (reference: common/utils.py:98-225)."""

    attn: str = "flash"  # any key of ops.attention.ATTENTION_BACKENDS
    rms_norm: str = "xla"
    # compute platform of the mesh the model runs on ('tpu'/'cpu'); resolved
    # by auto_model._as_backend from the MeshContext. Pallas kernel
    # eligibility keys off this — NOT the process default device, which may
    # point at a different backend than the mesh (e.g. CPU mesh + visible
    # TPU). None → fall back to the default-device heuristic.
    platform: Optional[str] = None
    # the MeshContext the model runs on, resolved beside ``platform``: a
    # Pallas kernel on a mesh of several devices must sit in a shard_map
    # (ops/platform_check.kernel_axes), so the attention call sites hand it
    # down. Not a user setting and not part of the config's identity.
    mesh_ctx: Any = dataclasses.field(default=None, compare=False, repr=False)
    experts: str = "gspmd"  # gspmd | ragged | ragged_fused | dense | a2a | a2a_fused
    fake_balanced_gate: bool = False  # deterministic routing for benchmarks
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # What a layer keeps for its backward (models/common/stacking.remat_wrap):
    # none: every residual. full: the layer's input; recomputes everything
    # XLA computes and never re-runs the attention kernel (splash's output
    # and logsumexp stay: S x N x (2 Dv + 4) bytes an attention layer and
    # sequence). full_save_dispatch: full, and the MoE sort permutations
    # (skips re-argsorting T*K picks per layer in the recompute pass; 2 to 4
    # int32 [T*K] leaves per layer). selective: full, and every product with no
    # batch dimension (the projections' and the MLP's outputs).
    remat: str = "none"
    scan_layers: bool = True
    # fp8 matmul recipe for dense projections (e4m3 fwd / e5m2 grads,
    # per-tensor dynamic scaling — see ops/fp8.py; reference:
    # quantization/fp8.py + BackendConfig.te_fp8)
    fp8: bool = False
    # fp8 for the EXPERT grouped matmuls: e4m3 with 128×128 blockwise weight
    # scales + per-tensor dynamic activation scales, straight-through grads
    # (reference GroupedExpertsFP8, components/moe/experts.py:478)
    fp8_experts: bool = False
    # ring attention with causally load-balanced zigzag seq layout —
    # requires the DATA permuted via parallel.cp.apply_zigzag
    cp_zigzag: bool = False
    pp_microbatches: int = 4  # pipeline microbatches when mesh pp > 1
    attn_block_q: int = 512
    attn_block_kv: int = 512

    def __post_init__(self):
        from automodel_tpu.ops.attention import ATTENTION_BACKENDS

        if self.attn not in ATTENTION_BACKENDS:
            raise ValueError(
                f"Unknown attn backend {self.attn!r}; available: {sorted(ATTENTION_BACKENDS)}"
            )
        if self.remat not in ("none", "full", "selective", "full_save_dispatch"):
            raise ValueError(f"Unknown remat policy {self.remat!r}")
        from automodel_tpu.moe.experts import EXPERT_BACKENDS

        if self.experts not in EXPERT_BACKENDS:
            raise ValueError(
                f"Unknown experts backend {self.experts!r}; available: {sorted(EXPERT_BACKENDS)}"
            )

    @property
    def param_jnp_dtype(self):
        return dtype_from_str(self.param_dtype)

    @property
    def compute_jnp_dtype(self):
        return dtype_from_str(self.compute_dtype)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Llama-family dense transformer hyperparameters, HF-ingestible."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope: RopeConfig = RopeConfig()
    rms_eps: float = 1e-6
    max_position_embeddings: int = 8192
    tie_embeddings: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    qk_norm: bool = False  # qwen3-style per-head RMSNorm on q/k
    # MiniMax-M2 style: RMSNorm over the FLATTENED q/k projection dims
    # (num_heads*head_dim) before the head reshape, instead of per-head
    qk_norm_flat: bool = False
    act: str = "silu"
    embed_scale: float = 1.0  # gemma multiplies embeddings by sqrt(hidden)
    logits_soft_cap: Optional[float] = None
    attn_soft_cap: Optional[float] = None
    sliding_window: Optional[int] = None
    # HF qwen2 convention: the first `max_window_layers` layers use FULL
    # attention; layers >= max_window_layers use the sliding window.
    max_window_layers: int = 0
    attn_scale: Optional[float] = None  # override 1/sqrt(head_dim)
    # GLM-4 / phi-style partial rotary: only the first
    # head_dim * partial_rotary_factor channels rotate
    partial_rotary_factor: float = 1.0
    # biencoder embedding models run the same stack bidirectionally
    # (reference: models/biencoder/llama_bidirectional_model.py)
    causal: bool = True

    @classmethod
    def from_hf(cls, hf_cfg: Any) -> "TransformerConfig":
        """Ingest an HF transformers config (LlamaConfig/Qwen2Config/...)."""
        get = lambda k, d=None: (
            hf_cfg.get(k, d) if isinstance(hf_cfg, dict) else getattr(hf_cfg, k, d)
        )
        heads = get("num_attention_heads")
        hidden = get("hidden_size")
        model_type = get("model_type", "llama")
        return cls(
            vocab_size=get("vocab_size"),
            hidden_size=hidden,
            intermediate_size=get("intermediate_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=heads,
            num_kv_heads=get("num_key_value_heads", heads),
            head_dim=get("head_dim") or hidden // heads,
            rope=RopeConfig.from_hf(hf_cfg),
            rms_eps=get("rms_norm_eps", 1e-6),
            max_position_embeddings=get("max_position_embeddings", 8192),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            attention_bias=bool(
                get("attention_bias", model_type in ("qwen2", "qwen2_moe"))
            ),
            mlp_bias=bool(get("mlp_bias", False)),
            qk_norm=model_type in ("qwen3", "qwen3_moe"),
            act=get("hidden_act", "silu"),
            # qwen2 gates the window behind use_sliding_window; mistral-style
            # configs apply sliding_window unconditionally when present.
            sliding_window=(
                get("sliding_window", None)
                # these families apply sliding_window unconditionally in HF
                if get("use_sliding_window", model_type in ("mistral", "mixtral", "phi3"))
                else None
            ),
            max_window_layers=get("max_window_layers", 0) or 0,
            partial_rotary_factor=get("partial_rotary_factor", 1.0) or 1.0,
        )

    @property
    def rope_dim(self) -> Optional[int]:
        """Rotary channel count when partial (None = full head_dim)."""
        if self.partial_rotary_factor and self.partial_rotary_factor < 1.0:
            return int(self.head_dim * self.partial_rotary_factor)
        return None

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim
