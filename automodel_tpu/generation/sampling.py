"""Token sampling: greedy / temperature / top-k / top-p.

All transforms are jit-traceable with a STATIC config (the frozen dataclass
hashes), so the decode while_loop compiles one program per sampling recipe.
The PRNG is threaded explicitly: callers derive a per-host base key via
``training.rng.sampling_key`` and fold the decode step index in per token —
multi-host generation never samples identical streams, and the same
(seed, host, step) always reproduces the same token.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """temperature <= 0 means greedy (HF convention do_sample=False);
    top_k/top_p restrict the support BEFORE renormalization (HF order:
    temperature → top-k → top-p)."""

    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None

    def __post_init__(self):
        if self.top_k is not None and self.top_k < 1:
            raise ValueError(f"top_k={self.top_k} must be >= 1")
        if self.top_p is not None and not (0.0 < self.top_p <= 1.0):
            raise ValueError(f"top_p={self.top_p} must be in (0, 1]")

    @property
    def greedy(self) -> bool:
        return self.temperature is None or self.temperature <= 0.0


_NEG_INF = jnp.float32(-1e30)


def _apply_top_k(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    k = min(k, logits.shape[-1])
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, _NEG_INF, logits)


def _apply_top_p(logits: jnp.ndarray, p: float) -> jnp.ndarray:
    """Nucleus filtering: keep the smallest prefix of descending-prob tokens
    whose cumulative probability reaches p (the top token always survives)."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    cum = jnp.cumsum(jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
    # a token is kept iff the cumulative mass BEFORE it is < p
    keep_sorted = jnp.concatenate(
        [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < p], axis=-1
    )
    # threshold logit = smallest kept logit; everything below it is cut
    kth = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits < kth, _NEG_INF, logits)


def _filter_logits(logits: jnp.ndarray, config: SamplingConfig) -> jnp.ndarray:
    """The shared transform pipeline (temperature → top-k → top-p, HF
    order) over ``[..., V]`` fp32 logits. ``sample`` draws from these;
    ``speculative_verify`` needs the SAME filtered distributions for both
    target and draft so the rejection rule reproduces exactly what a
    non-speculative sampler would draw."""
    logits = logits / jnp.float32(config.temperature)
    if config.top_k is not None:
        logits = _apply_top_k(logits, config.top_k)
    if config.top_p is not None and config.top_p < 1.0:
        logits = _apply_top_p(logits, config.top_p)
    return logits


def sample(
    logits: jnp.ndarray, key: jax.Array, config: SamplingConfig
) -> jnp.ndarray:
    """logits [B, V] → token ids [B] int32."""
    with jax.named_scope("sample"):
        logits = logits.astype(jnp.float32)
        if config.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, _filter_logits(logits, config), axis=-1
        ).astype(jnp.int32)


def sample_with_logprobs(
    logits: jnp.ndarray, key: jax.Array, config: SamplingConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """logits [B, V] → (token ids [B] int32, logprobs [B] float32).

    The logprob is ``log_softmax`` of the RAW (unfiltered, untempered)
    logits gathered at the sampled id — i.e. log π(a|s) under the model's
    full distribution, which is what importance ratios (GRPO/PPO) need and
    what a full-forward recompute reproduces exactly. Filtering/temperature
    shape WHICH token is drawn (identical stream to ``sample`` for the same
    key), not the reported probability."""
    with jax.named_scope("sample"):
        logits = logits.astype(jnp.float32)
        ids = sample(logits, key, config)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), ids[:, None].astype(jnp.int32), axis=-1
        )[:, 0]
        return ids, logp


def speculative_verify(
    target_logits: jnp.ndarray,
    draft_logits: Optional[jnp.ndarray],
    draft_tokens: jnp.ndarray,
    key: jax.Array,
    config: SamplingConfig,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The draft-and-verify acceptance rule (Leviathan et al., "Fast
    Inference from Transformers via Speculative Decoding", 2023).

    ``target_logits`` [B, S, V] are the ONE batched verify forward's
    outputs over the S = k+1 fed tokens ``[cur, d_1..d_k]`` (row i is the
    target's distribution for the token FOLLOWING fed token i);
    ``draft_tokens`` [B, k] are the draft's proposals, ``draft_logits``
    [B, k, V] the distributions it drew them from (ignored under greedy).

    → ``(tokens [B, S] int32, n_commit [B] int32 in 1..S)``: commit
    ``tokens[:, :n]`` — the accepted draft prefix plus one
    correction/bonus token. Greedy is the exact-match degenerate case:
    accept while ``d_i == argmax(target_i)``, corrections are the target
    argmax — committed tokens are bit-identical to the non-speculative
    greedy stream, which is the exactness guarantee the parity tests pin.
    Sampled mode implements the standard rejection rule (accept d_i w.p.
    ``min(1, p_i(d_i)/q_i(d_i))``, resample rejections from
    ``norm(max(p-q, 0))``, bonus from ``p_k``), which preserves the target
    distribution exactly in expectation."""
    B, S, V = target_logits.shape
    k = S - 1
    target_logits = target_logits.astype(jnp.float32)
    if config.greedy:
        t = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)  # [B, S]
        match = draft_tokens == t[:, :k]
        n_acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
        # accepted drafts ARE the target argmaxes, so the committed stream
        # is just the target row — prefix length n_acc + 1
        return t, (n_acc + 1).astype(jnp.int32)
    p = jax.nn.softmax(_filter_logits(target_logits, config), axis=-1)
    q = jax.nn.softmax(
        _filter_logits(draft_logits.astype(jnp.float32), config), axis=-1
    )
    p_d = jnp.take_along_axis(p[:, :k], draft_tokens[..., None], axis=-1)[..., 0]
    q_d = jnp.take_along_axis(q, draft_tokens[..., None], axis=-1)[..., 0]
    key_u, key_c = jax.random.split(key)
    u = jax.random.uniform(key_u, (B, k))
    accept = u < p_d / jnp.maximum(q_d, 1e-30)
    n_acc = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(axis=1)
    # correction distribution per draft position (residual), bonus at S-1
    resid = jnp.maximum(p[:, :k] - q, 0.0)
    rsum = resid.sum(axis=-1, keepdims=True)
    resid = jnp.where(rsum > 0, resid / jnp.maximum(rsum, 1e-30), p[:, :k])
    corr_probs = jnp.concatenate([resid, p[:, k:]], axis=1)  # [B, S, V]
    c = jax.random.categorical(
        key_c, jnp.log(jnp.maximum(corr_probs, 1e-30)), axis=-1
    ).astype(jnp.int32)
    drafts_pad = jnp.concatenate(
        [draft_tokens.astype(jnp.int32), jnp.zeros((B, 1), jnp.int32)], axis=1
    )
    idx = jnp.arange(S, dtype=jnp.int32)[None, :]
    tokens = jnp.where(idx < n_acc[:, None], drafts_pad, c)
    return tokens, (n_acc + 1).astype(jnp.int32)
