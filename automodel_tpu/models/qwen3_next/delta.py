"""Gated DeltaNet linear attention (Qwen3-Next), TPU-native.

Parity: HF modeling_qwen3_next.py ``torch_chunk_gated_delta_rule`` (the
reference consumes the fla/causal-conv1d CUDA kernels; models/qwen3_next/).
TPU formulation: the intra-chunk (I - A)^-1 forward substitution becomes a
unit-lower-triangular solve (one MXU-friendly triangular solve per chunk
instead of a 64-step python loop), and the inter-chunk recurrence is a
``lax.scan`` carrying the [dk, dv] state per head. All math in fp32 like
the reference kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def chunk_gated_delta_rule(
    query: jnp.ndarray,  # [B, S, H, dk] (post GQA repeat)
    key: jnp.ndarray,  # [B, S, H, dk]
    value: jnp.ndarray,  # [B, S, H, dv]
    g: jnp.ndarray,  # [B, S, H] log-decay
    beta: jnp.ndarray,  # [B, S, H] write strength
    chunk_size: int = 64,
    segment_ids: jnp.ndarray | None = None,  # [B, S] packed-doc boundaries
) -> jnp.ndarray:
    """→ [B, S, H, dv]. Matches torch_chunk_gated_delta_rule with
    use_qk_l2norm_in_kernel=True (l2 normalization applied here).

    Packed sequences: a segment START token gets an extra -50 on its
    log-decay. Within a segment the offsets cancel exactly in every
    g_cum[t] - g_cum[s] difference, while any cross-segment term carries
    exp(-50) ≈ 2e-22 — the recurrent state, the intra-chunk decay matrix,
    and the chunk-state handoff all reset at document boundaries with NO
    change to the chunked algorithm (the reference THD path gets this from
    fla's varlen kernels)."""
    in_dtype = query.dtype
    B, S, H, dk = query.shape
    dv = value.shape[-1]

    q = l2norm(query.astype(jnp.float32))
    k = l2norm(key.astype(jnp.float32))
    v = value.astype(jnp.float32)
    g = g.astype(jnp.float32)
    b = beta.astype(jnp.float32)
    if segment_ids is not None:
        prev = jnp.pad(segment_ids, ((0, 0), (1, 0)), constant_values=-1)[:, :S]
        starts = (segment_ids != prev).astype(jnp.float32)  # [B, S]
        g = g - 50.0 * starts[..., None]

    pad = (-S) % chunk_size
    if pad:
        zp = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, b = zp(q), zp(k), zp(v), zp(g), zp(b)
    Sp = S + pad
    n = Sp // chunk_size
    C = chunk_size

    # [B, H, n, C, d] chunk layout
    q = q.transpose(0, 2, 1, 3).reshape(B, H, n, C, dk) * (dk**-0.5)
    k = k.transpose(0, 2, 1, 3).reshape(B, H, n, C, dk)
    v = v.transpose(0, 2, 1, 3).reshape(B, H, n, C, dv)
    g = g.transpose(0, 2, 1).reshape(B, H, n, C)
    b = b.transpose(0, 2, 1).reshape(B, H, n, C)

    v_beta = v * b[..., None]
    k_beta = k * b[..., None]

    g_cum = jnp.cumsum(g, axis=-1)  # [B, H, n, C]
    tril = jnp.tril(jnp.ones((C, C), bool))
    tril_strict = jnp.tril(jnp.ones((C, C), bool), -1)
    decay = jnp.where(
        tril, jnp.exp(jnp.where(tril, g_cum[..., :, None] - g_cum[..., None, :], 0.0)), 0.0
    )

    # A strictly lower: -(k_beta k^T) ⊙ decay; T = (I - A)^-1 via unit-lower
    # triangular solve (the reference's 64-step forward substitution)
    A = jnp.where(
        tril_strict, -(jnp.einsum("bhncd,bhnmd->bhncm", k_beta, k)) * decay, 0.0
    )
    eye = jnp.eye(C, dtype=jnp.float32)
    T = jax.scipy.linalg.solve_triangular(
        eye - A, jnp.broadcast_to(eye, A.shape), lower=True, unit_diagonal=True
    )
    v_chunk = jnp.einsum("bhncm,bhnmd->bhncd", T, v_beta)
    k_cumdecay = jnp.einsum(
        "bhncm,bhnmd->bhncd", T, k_beta * jnp.exp(g_cum)[..., None]
    )

    def chunk_step(state, xs):
        q_i, k_i, v_i, kcd_i, gc_i = xs  # [B, H, C, .]
        # double-where: the upper triangle's g-difference is POSITIVE (decay
        # accumulates downward), so exp() there overflows — harmless for the
        # forward (masked) but it poisons the gradient with 0 * inf = NaN
        diff = jnp.where(tril, gc_i[..., :, None] - gc_i[..., None, :], 0.0)
        attn = jnp.where(
            tril, jnp.einsum("bhcd,bhmd->bhcm", q_i, k_i) * jnp.exp(diff), 0.0
        )
        v_prime = jnp.einsum("bhcd,bhdv->bhcv", kcd_i, state)
        v_new = v_i - v_prime
        out = (
            jnp.einsum("bhcd,bhdv->bhcv", q_i * jnp.exp(gc_i)[..., None], state)
            + jnp.einsum("bhcm,bhmv->bhcv", attn, v_new)
        )
        g_last = gc_i[..., -1]
        state = state * jnp.exp(g_last)[..., None, None] + jnp.einsum(
            "bhcd,bhcv->bhdv",
            k_i * jnp.exp(g_last[..., None] - gc_i)[..., None],
            v_new,
        )
        return state, out

    state0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    xs = tuple(
        jnp.moveaxis(x, 2, 0) for x in (q, k, v_chunk, k_cumdecay, g_cum)
    )
    _, outs = jax.lax.scan(chunk_step, state0, xs)  # [n, B, H, C, dv]
    out = jnp.moveaxis(outs, 0, 2).reshape(B, H, Sp, dv)[:, :, :S]
    return out.transpose(0, 2, 1, 3).astype(in_dtype)
