"""Gated DeltaNet linear attention (Qwen3-Next): the scalar-decay case of the
chunked delta rule.

Parity: HF modeling_qwen3_next.py ``torch_chunk_gated_delta_rule`` (the
reference consumes the fla/causal-conv1d CUDA kernels). The chunked
algorithm, its backward and its Pallas kernels are ops/delta_rule.py's (one
operator for this family's decay a head and Kimi's decay a channel, which
normalises q and k and scales q by ``dk^-0.5`` itself); what is this family's
own stays here: float32 operands like the reference kernel.
"""

from __future__ import annotations

import jax.numpy as jnp

from automodel_tpu.ops.delta_rule import chunked_delta_rule


def chunk_gated_delta_rule(
    query: jnp.ndarray,  # [B, S, H, dk] (post GQA repeat)
    key: jnp.ndarray,  # [B, S, H, dk]
    value: jnp.ndarray,  # [B, S, H, dv]
    g: jnp.ndarray,  # [B, S, H] log-decay
    beta: jnp.ndarray,  # [B, S, H] write strength
    chunk_size: int = 64,
    segment_ids: jnp.ndarray | None = None,  # [B, S] packed-doc boundaries
    platform: str | None = None,
    mesh_ctx=None,
) -> jnp.ndarray:
    """→ [B, S, H, dv]. Matches torch_chunk_gated_delta_rule with
    use_qk_l2norm_in_kernel=True (the operator normalises). Packed sequences:
    the state resets at a segment's first token (the reference THD path gets
    this from fla's varlen kernels)."""
    B, S, H, _ = query.shape
    flat = lambda x: x.reshape(B, S, -1).astype(jnp.float32)
    out = chunked_delta_rule(
        flat(query), flat(key), flat(value), g, beta,
        segment_ids=segment_ids, chunk_size=chunk_size, platform=platform, mesh_ctx=mesh_ctx,
    )
    return out.reshape(B, S, H, -1).astype(query.dtype)
