"""Rotary position embeddings.

Parity: reference models carry per-family rope_utils (e.g.
components/models/llama/rope_utils.py) supporting default / llama3 / yarn
scalings; TE provides fused RoPE on GPU. On TPU we precompute cos/sin tables
once per step (cheap) and let XLA fuse the elementwise application into the
surrounding matmuls — a fused kernel buys nothing here.

Convention: interleaved-half ("rotate_half") layout matching HF transformers,
so weights are interchangeable without permutation.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class RopeConfig:
    theta: float = 10000.0
    scaling: str | None = None  # None | "llama3" | "linear" | "yarn"
    factor: float = 1.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position: int = 8192
    # yarn
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    attention_factor: float | None = None  # HF yarn cos/sin multiplier

    SUPPORTED_SCALINGS = (None, "llama3", "linear", "yarn")

    def __post_init__(self):
        if self.scaling not in self.SUPPORTED_SCALINGS:
            raise ValueError(
                f"Unsupported rope_scaling type {self.scaling!r}; "
                f"supported: {self.SUPPORTED_SCALINGS}"
            )

    @staticmethod
    def from_hf(cfg) -> "RopeConfig":
        """Build from an HF config object / dict (rope_scaling conventions)."""
        get = lambda k, d=None: (cfg.get(k, d) if isinstance(cfg, dict) else getattr(cfg, k, d))
        rs = get("rope_scaling") or {}
        rtype = rs.get("rope_type", rs.get("type"))
        return RopeConfig(
            theta=get("rope_theta", 10000.0),
            scaling=None if rtype in (None, "default") else rtype,
            factor=rs.get("factor", 1.0),
            low_freq_factor=rs.get("low_freq_factor", 1.0),
            high_freq_factor=rs.get("high_freq_factor", 4.0),
            original_max_position=rs.get(
                "original_max_position_embeddings", get("max_position_embeddings", 8192)
            ),
            beta_fast=rs.get("beta_fast", 32.0),
            beta_slow=rs.get("beta_slow", 1.0),
            mscale=rs.get("mscale", 1.0),
            mscale_all_dim=rs.get("mscale_all_dim", 0.0),
            attention_factor=rs.get("attention_factor"),
        )


def _inv_freq(head_dim: int, cfg: RopeConfig) -> jnp.ndarray:
    inv = 1.0 / (cfg.theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if cfg.scaling == "linear":
        inv = inv / cfg.factor
    elif cfg.scaling == "llama3":
        # HF Llama-3 frequency-dependent scaling.
        low = cfg.original_max_position / cfg.low_freq_factor
        high = cfg.original_max_position / cfg.high_freq_factor
        wavelen = 2 * math.pi / inv
        smooth = (cfg.original_max_position / wavelen - cfg.low_freq_factor) / (
            cfg.high_freq_factor - cfg.low_freq_factor
        )
        scaled = jnp.where(
            wavelen < high,
            inv,
            jnp.where(wavelen > low, inv / cfg.factor, (1 - smooth) * inv / cfg.factor + smooth * inv),
        )
        inv = scaled
    elif cfg.scaling == "yarn":
        # DeepSeek/Qwen YaRN ramp (state-of-practice formulation).
        dim = head_dim

        def find_dim(n_rot: float) -> float:
            return (dim * math.log(cfg.original_max_position / (n_rot * 2 * math.pi))) / (
                2 * math.log(cfg.theta)
            )

        low = max(math.floor(find_dim(cfg.beta_fast)), 0)
        high = min(math.ceil(find_dim(cfg.beta_slow)), dim - 1)
        ramp = jnp.clip(
            (jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0, 1
        )
        inv = inv / cfg.factor * ramp + inv * (1 - ramp)
    elif cfg.scaling is not None:
        raise ValueError(f"Unsupported rope scaling {cfg.scaling!r}")
    return inv


def _attention_factor(cfg: RopeConfig) -> float:
    """HF yarn multiplies cos/sin by attention_factor (0.1·ln(factor)+1 when
    unset). Models that fold the correction into the softmax scale instead
    (DeepSeek MLA) use yarn_mscale() and a RopeConfig with factor<=1 here."""
    if cfg.scaling != "yarn":
        return 1.0
    if cfg.attention_factor is not None:
        return cfg.attention_factor
    if cfg.mscale and cfg.mscale_all_dim:
        # HF _compute_yarn_parameters: both given -> their ratio (1 for
        # DeepSeek-style configs, whose correction is in the softmax scale)
        return yarn_mscale(cfg)
    if cfg.factor > 1.0:
        return 0.1 * math.log(cfg.factor) + 1.0
    return 1.0


def yarn_mscale(cfg: RopeConfig) -> float:
    """Attention magnitude correction used by YaRN models (DeepSeek MLA)."""
    if cfg.scaling != "yarn" or cfg.factor <= 1.0:
        return 1.0

    def get(scale: float) -> float:
        return 0.1 * scale * math.log(cfg.factor) + 1.0 if scale > 0 else 1.0

    return get(cfg.mscale) / get(cfg.mscale_all_dim)


def rope_table(
    position_ids: jnp.ndarray, head_dim: int, cfg: RopeConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables [..., seq, head_dim] for given positions (fp32)."""
    inv = _inv_freq(head_dim, cfg)
    freqs = position_ids[..., None].astype(jnp.float32) * inv  # [..., S, hd/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    f = _attention_factor(cfg)
    return jnp.cos(emb) * f, jnp.sin(emb) * f


def mrope_table(
    position_ids: jnp.ndarray,  # [3, B, S] — t/h/w grid positions
    head_dim: int,
    cfg: RopeConfig,
    mrope_section: tuple[int, int, int],
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Interleaved multi-axis RoPE (Qwen3-VL: HF apply_interleaved_mrope,
    modeling_qwen3_vl_moe.py:830) — frequency slot i takes the H axis when
    i≡1 (mod 3) and i < 3·section_h, the W axis when i≡2 (mod 3) and
    i < 3·section_w, else the T axis. Returns cos/sin [B, S, head_dim]."""
    inv = _inv_freq(head_dim, cfg)
    freqs = position_ids[..., None].astype(jnp.float32) * inv  # [3, B, S, hd/2]
    i = jnp.arange(head_dim // 2)
    take_h = (i % 3 == 1) & (i < 3 * mrope_section[1])
    take_w = (i % 3 == 2) & (i < 3 * mrope_section[2])
    half = jnp.where(take_h, freqs[1], freqs[0])
    half = jnp.where(take_w, freqs[2], half)
    emb = jnp.concatenate([half, half], axis=-1)
    f = _attention_factor(cfg)
    return jnp.cos(emb) * f, jnp.sin(emb) * f


def apply_rope(
    q: jnp.ndarray,
    k: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    interleave: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Apply rotate-half RoPE. q/k: [B, S, N, H]; cos/sin: [B, S, H].

    ``interleave``: checkpoint stores pair-interleaved rope dims (DeepSeek
    MLA, HF `rope_interleave` / apply_rotary_pos_emb_interleave) — deinterleave
    [x0,y0,x1,y1,...] → [x0,x1,...,y0,y1,...] before the rotation.

    Partial rotary (GLM-4 / phi-style ``partial_rotary_factor``): when the
    table's last dim is smaller than the head dim, only the first
    ``rotary_dim`` channels rotate and the rest pass through (HF
    apply_rotary_pos_emb slices the same way).
    """

    def deint(x: jnp.ndarray) -> jnp.ndarray:
        *lead, d = x.shape
        return x.reshape(*lead, d // 2, 2).swapaxes(-1, -2).reshape(*lead, d)

    def rot(x: jnp.ndarray) -> jnp.ndarray:
        half = x.shape[-1] // 2
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([-x2, x1], axis=-1)

    rotary_dim = cos.shape[-1]
    q_pass = k_pass = None
    if rotary_dim < q.shape[-1]:
        q, q_pass = q[..., :rotary_dim], q[..., rotary_dim:]
        k, k_pass = k[..., :rotary_dim], k[..., rotary_dim:]
    if interleave:
        q, k = deint(q), deint(k)
    c = cos[..., None, :].astype(q.dtype)
    s = sin[..., None, :].astype(q.dtype)
    q, k = q * c + rot(q) * s, k * c + rot(k) * s
    if q_pass is not None:
        q = jnp.concatenate([q, q_pass], axis=-1)
        k = jnp.concatenate([k, k_pass], axis=-1)
    return q, k
