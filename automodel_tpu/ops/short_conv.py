"""Depthwise causal short convolution over the sequence dim.

Shared by every family with a short conv in front of (or as) its token mixer:
qwen3-next's gated DeltaNet, nemotron-v3's Mamba-2, lfm2-moe's gated short
conv. One function serves training (zeros before position 0, packed-document
boundaries honoured) and serving (the ``K - 1`` inputs before position 0
handed in as carried state).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def causal_conv1d(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    segment_ids: Optional[jnp.ndarray] = None,
    prev: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """x: [B, S, C]; weight: [C, K] (HF conv1d.weight squeezed; tap ``K - 1``
    multiplies the current position). No bias (every conv here is bias-free).

    ``segment_ids`` [B, S]: packed-sequence boundaries — taps that would mix
    a PREVIOUS document's tokens into this one are zeroed (each document
    sees the same left-zero-padding it would unpacked).

    ``prev`` [B, K - 1, C]: the inputs at the ``K - 1`` positions before
    position 0, oldest first (a serving slot's carried state) instead of
    zeros."""
    K = weight.shape[-1]
    S = x.shape[1]
    if prev is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
    out = x * weight[:, K - 1][None, None, :]
    for j in range(1, K):  # K is 3 or 4 — unrolled adds fuse into one kernel
        tap = xp[:, K - 1 - j : K - 1 - j + S, :]  # x shifted right by j
        if segment_ids is not None:
            sp = jnp.pad(segment_ids, ((0, 0), (j, 0)), constant_values=-1)
            same = (sp[:, :S] == segment_ids)[..., None]
            tap = tap * same.astype(tap.dtype)
        out = out + tap * weight[:, K - 1 - j][None, None, :]
    return out
