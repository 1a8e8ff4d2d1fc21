"""Attention backends.

Parity: the reference switches attn ∈ {te, sdpa, flex} per model
(components/attention/utils.py:25-65). TPU-native backends:

- ``"sdpa"``  — pure-XLA scaled dot-product attention (always available;
  reference-quality numerics; used on CPU tests).
- ``"flash"`` — Pallas TPU flash attention (jax.experimental.pallas.ops.tpu),
  the MXU-tiled kernel path. Falls back to sdpa off-TPU.
- ``"ring"``  — context-parallel ring attention over the ``cp`` mesh axis
  (automodel_tpu.parallel.cp), selected by the parallelism layer.

All backends take BSNH layout (batch, seq, heads, head_dim) and support GQA
via n_kv_heads < n_heads, causal masking, and optional segment ids for packed
(THD-equivalent) sequences — the reference handles packed sequences via TE THD
kernels (cp_utils.py:187-337); here segment ids express the same block-causal
structure.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from automodel_tpu.ops.platform_check import (
    is_tpu_platform,
    kernel_axes,
    kernel_shard_map,
    sharded_axes,
)

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[B, S, N_kv, H] → [B, S, N_kv*n_rep, H] (GQA expansion)."""
    if n_rep == 1:
        return x
    b, s, nkv, h = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, nkv, n_rep, h)).reshape(
        b, s, nkv * n_rep, h
    )


def sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    sinks: Optional[jnp.ndarray] = None,
    bidir_groups: Optional[jnp.ndarray] = None,
    attn_bias: Optional[jnp.ndarray] = None,
    kv_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """XLA scaled dot-product attention. q: [B,S,N,H], k/v: [B,S,Nkv,H].

    ``kv_mask``: [B, Sk] bool — per-key validity, ANDed onto the mask. The
    KV-cache decode path (generation/) expresses slot validity this way
    (position-tag masks subsume causality/window there, so decode calls pass
    causal=False and let the tags do the masking). A [B, Sq, Sk] mask gives
    PER-QUERY validity — the chunked-prefill path (serving/) uses it so each
    chunk token attends exactly its causal cache prefix.

    ``attn_bias``: additive fp32 bias [B, 1|N, Sq, Sk] applied after scaling
    (DeepSeek-V3.2 sparse top-k mask; TE core_attention_bias equivalent).

    ``sinks``: per-head learned sink logits [N] — an extra virtual key that
    absorbs probability mass (gpt-oss; modeling_gpt_oss.py:258: softmax over
    [logits, sink] then drop the sink column).

    ``bidir_groups``: [B, S] int group ids, -1 for ordinary causal tokens —
    tokens sharing a nonnegative group attend to each other BIDIRECTIONALLY
    (ORed onto the causal/window mask), the gemma-3 image-block rule
    (modeling_gemma3.py token_type_ids_mask_function).
    """
    b, sq, n, h = q.shape
    n_kv = k.shape[2]
    k = repeat_kv(k, n // n_kv)
    v = repeat_kv(v, n // n_kv)
    scale = scale if scale is not None else 1.0 / (h**0.5)
    logits = jnp.einsum("bqnh,bknh->bnqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if attn_bias is not None:
        logits = logits + attn_bias.astype(logits.dtype)
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    sk = k.shape[1]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
    if sliding_window is not None:
        pos_q = jnp.arange(sq)[:, None] + (sk - sq)
        pos_k = jnp.arange(sk)[None, :]
        mask = mask & (pos_q - pos_k < sliding_window)
    mask = mask[None, None]
    if bidir_groups is not None:
        gq = bidir_groups[:, None, :, None]
        gk = bidir_groups[:, None, None, :]
        mask = mask | ((gq >= 0) & (gq == gk))
    if segment_ids is not None:
        seg = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        mask = mask & seg
    if kv_mask is not None:
        mask = mask & (
            kv_mask[:, None, :, :] if kv_mask.ndim == 3
            else kv_mask[:, None, None, :]
        )
    logits = jnp.where(mask, logits, DEFAULT_MASK_VALUE)
    if sinks is not None:
        sink_col = jnp.broadcast_to(
            sinks.astype(jnp.float32)[None, :, None, None], (b, n, sq, 1)
        )
        combined = jnp.concatenate([logits, sink_col], axis=-1)
        probs = jax.nn.softmax(combined, axis=-1)[..., :-1].astype(q.dtype)
    else:
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknh->bqnh", probs, v)


def sdpa_decode(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    kv_mask: jnp.ndarray,
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    sinks: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Cache-attending attention for decode AND chunked prefill.

    q: [B, Sq, N, H] (Sq = 1 for single-token decode, the chunk length for
    serving/'s chunked prefill), k/v: [B, C, Nkv, H] (the cache), kv_mask:
    [B, C] (decode) or [B, Sq, C] (per-query, chunk) valid-slot mask
    (generation.kv_cache position tags — these already encode causality and
    any sliding window, so no causal mask is applied here). One fused XLA
    program: a [B, N, 1, C] decode logits block is VPU work, so decode never
    needs (or benefits from) splash — the MXU tile is 128 wide and a 1-row
    query can't fill it."""
    return sdpa(
        q, k, v,
        causal=False, scale=scale, logits_soft_cap=logits_soft_cap,
        sinks=sinks, kv_mask=kv_mask,
    )


def _pick_block(pref: int, s: int) -> int:
    """Largest TPU-friendly block (multiple of 128, splash requirement) that
    divides s. s is always a 128 multiple here, so 128 is a valid floor even
    when pref is smaller or not 128-aligned."""
    for b in (pref, 512, 256, 128):
        if b <= pref and b % 128 == 0 and s % b == 0:
            return b
    return 128


def _splash_blocks(head_dim: int, window: Optional[int]) -> tuple[int, int]:
    """(block_q, block_kv) for causal splash by problem shape. The static
    512/512 blocks are sized for head_dim 128. At head_dim 64 a block holds
    half the VMEM, so shorter q blocks keep more kv resident per pass; a
    128-token window under one 512 kv block wants kv blocks small enough
    for LocalMask's block pruning to skip the dead tiles (and their DMAs).
    The two head-64 rows are heuristics no chip run has timed (no cell of
    the benchmark runs splash at head_dim 64). A 192-wide q/k head (latent
    attention, V of 128) is timed (my chip run, PR 41; 16,384 tokens, 32
    heads, forward + backward ms): 512/512 117.8, 256/512 143.7, 1024/512
    110.2, 512/1024 108.2, 2048/512 110.6, 1024/1024 103.3; 1024/2048 does
    not fit the dkv kernel's VMEM."""
    if head_dim == 192 and not window:
        return 1024, 1024
    if head_dim == 64:
        if not window:
            return 256, 512
        if window == 128:
            return 256, 128
    return 512, 512


# The name splash's ``out`` and ``logsumexp`` carry inside its custom_vjp's
# forward. Every recomputing policy of models/common/stacking.remat_wrap
# keeps it, so a layer's backward reads the two arrays instead of running
# the forward kernel a second time.
SPLASH_RESIDUAL_NAME = "splash_residuals"


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "logits_soft_cap", "sliding_window", "block_q",
        "block_kv", "interpret",
    ),
)
def _splash_flash(
    q, k, v, segment_ids, sinks,
    *, causal, scale, logits_soft_cap, sliding_window, block_q, block_kv,
    interpret=False,
):
    """Splash attention (pallas TPU): native GQA (no repeat_kv materialize),
    sliding-window via LocalMask, logit soft cap, segment ids, and gpt-oss
    attention sinks — the TE-universality equivalent
    (reference components/attention/utils.py:25-65)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sak,
        splash_attention_mask as sam,
    )

    B, S, N, H = q.shape
    # pad seq to a 128 multiple instead of losing the fused kernel; padded q
    # rows are sliced off, padded kv is never attended (causal) / segmented out
    Sp = -(-S // 128) * 128
    pad = Sp - S
    if pad:
        zeros = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q, k, v = zeros(q), zeros(k), zeros(v)
        if segment_ids is None:
            segment_ids = jnp.concatenate(
                [
                    jnp.ones((B, S), jnp.int32),
                    jnp.zeros((B, pad), jnp.int32),
                ],
                axis=1,
            )
        else:
            segment_ids = jnp.pad(
                segment_ids, ((0, 0), (0, pad)), constant_values=-1
            )

    qt = (q * scale).transpose(0, 2, 1, 3)  # [B, N, S, H], pre-scaled
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    if sliding_window is not None:
        base = sam.LocalMask((Sp, Sp), window_size=(sliding_window - 1, 0), offset=0)
    elif causal:
        base = sam.CausalMask((Sp, Sp))
    else:
        base = sam.FullMask((Sp, Sp))
    mask = sam.MultiHeadMask([base] * N)
    bq = _pick_block(block_q, Sp)
    bkv = _pick_block(block_kv, Sp)
    kernel = sak.make_splash_mha(
        mask,
        block_sizes=sak.BlockSizes(
            block_q=bq, block_kv=bkv,
            block_q_dkv=bq, block_kv_dkv=bkv,
            block_q_dq=bq, block_kv_dq=bkv,
        ),
        head_shards=1,
        q_seq_shards=1,
        attn_logits_soft_cap=logits_soft_cap,
        residual_checkpoint_name=SPLASH_RESIDUAL_NAME,
        interpret=interpret,
    )
    seg = (
        sak.SegmentIds(q=segment_ids, kv=segment_ids)
        if segment_ids is not None
        else None
    )
    axes = (0, 0, 0, 0 if seg is not None else None, None)
    out = jax.vmap(kernel, in_axes=axes)(qt, kt, vt, seg, sinks)
    out = out.transpose(0, 2, 1, 3).astype(q.dtype)
    return out[:, :S] if pad else out


_warned_fallback: set = set()


def _fallback_loudly(reason: str):
    if reason not in _warned_fallback:
        _warned_fallback.add(reason)
        import logging

        logging.getLogger(__name__).warning(
            "flash attention falling back to XLA sdpa (%s) — O(S^2) "
            "materialized attention; expect a large perf cliff on TPU.", reason
        )


def flash(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    sinks: Optional[jnp.ndarray] = None,
    block_q: int = 512,
    block_kv: int = 512,
    platform: Optional[str] = None,
    mesh_ctx=None,
) -> jnp.ndarray:
    """Pallas TPU flash (splash) attention: causal/sliding-window/soft-cap/
    segments/sinks all stay on the fused kernel; sequences are padded to 128
    internally. Falls back to sdpa ONLY off-TPU or for ANY non-causal
    attention (splash's LocalMask enforces causality, so even non-causal
    windowed must not route there), and logs loudly when it does.

    ``mesh_ctx`` (parallel.mesh.MeshContext, from BackendConfig.mesh_ctx):
    on a mesh of several devices the kernel runs inside a ``shard_map`` —
    batch over the data axes, heads and KV heads over ``tp`` — because
    GSPMD cannot partition a Mosaic call."""
    h = q.shape[-1]
    if q.shape[1] == 1:
        # single-query decode: the splash MXU tiling pads the query to a
        # 128-row block — 127/128 of the kernel is wasted — while the XLA
        # sdpa lowers to one VPU-bound fused program. Not a fallback (no
        # warning): decode is DESIGNED to never require splash.
        return sdpa(
            q, k, v,
            causal=causal, scale=scale, segment_ids=segment_ids,
            logits_soft_cap=logits_soft_cap, sliding_window=sliding_window,
            sinks=sinks,
        )
    reason = None
    if not _flash_eligible(platform):
        reason = "not running on TPU"
    elif not causal:
        # splash LocalMask silently enforces causality, so non-causal windowed
        # attention must not route there; non-causal dense lacks a kernel win
        reason = "non-causal attention"
    if reason is not None:
        _fallback_loudly(reason)
        return sdpa(
            q, k, v,
            causal=causal, scale=scale, segment_ids=segment_ids,
            logits_soft_cap=logits_soft_cap, sliding_window=sliding_window,
            sinks=sinks,
        )
    scale = scale if scale is not None else 1.0 / (h**0.5)
    # an explicit attn_block_q/attn_block_kv in the backend config wins
    # outright; the shape rule only acts on the STATIC 512/512 defaults
    if (block_q, block_kv) == (512, 512):
        block_q, block_kv = _splash_blocks(h, sliding_window)
    interpret = _interpret_requested()

    def kernel(q, k, v, segment_ids, sinks):
        return _splash_flash(
            q, k, v, segment_ids, sinks,
            causal=causal, scale=scale, logits_soft_cap=logits_soft_cap,
            sliding_window=sliding_window, block_q=block_q, block_kv=block_kv,
            interpret=interpret,
        )

    if kernel_axes(mesh_ctx) is None:
        return kernel(q, k, v, segment_ids, sinks)
    return _flash_shard_map(kernel, mesh_ctx, q, k, v, segment_ids, sinks)


def flash_head_axes(mesh_ctx, num_heads: int, num_kv_heads: int):
    """The mesh axes the flash kernel's heads shard over, or a refusal of a
    mesh it cannot run on — asked when the model is built
    (auto_model._check_kernel_mesh) and again when the call is traced."""
    if mesh_ctx.cp_size > 1:
        raise ValueError(
            f"attn: flash on a mesh with cp={mesh_ctx.cp_size}: the flash "
            "kernel attends a whole sequence per device — use attn: ring "
            "for context parallelism"
        )
    return sharded_axes(
        mesh_ctx, "tensor", (num_heads, num_kv_heads),
        "attn: flash — num_attention_heads / num_key_value_heads",
    )


def _flash_shard_map(kernel, mesh_ctx, q, k, v, segment_ids, sinks):
    """Run ``kernel`` per device block: batch over the data axes, heads and
    KV heads over ``tp`` (GQA groups stay whole because both divide), the
    sequence whole — the layout the projections already produce, so no data
    moves at the region's edge."""
    batch = sharded_axes(mesh_ctx, "batch", (q.shape[0],), "attention batch")
    heads = flash_head_axes(mesh_ctx, q.shape[2], k.shape[2])
    qkv = P(batch, None, heads, None)
    args, specs = [q, k, v], [qkv, qkv, qkv]
    if segment_ids is not None:
        args.append(segment_ids)
        specs.append(P(batch, None))
    if sinks is not None:
        args.append(sinks)
        specs.append(P(heads))  # per-head logits follow the head shard

    def block(q, k, v, *rest):
        rest = list(rest)
        seg = rest.pop(0) if segment_ids is not None else None
        snk = rest.pop(0) if sinks is not None else None
        return kernel(q, k, v, seg, snk)

    return kernel_shard_map(mesh_ctx, block, tuple(specs), qkv)(*args)


def _ring_not_installed(*args, **kwargs):
    raise RuntimeError(
        "attention backend 'ring' needs a mesh: call "
        "automodel_tpu.parallel.cp.install_ring_backend(mesh_ctx) first "
        "(auto_model does this when backend.attn == 'ring')."
    )


ATTENTION_BACKENDS = {
    "sdpa": sdpa,
    "flash": flash,
    "ring": _ring_not_installed,
}


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    backend: str = "sdpa",
    platform: Optional[str] = None,
    mesh_ctx=None,
    **kwargs,
) -> jnp.ndarray:
    try:
        fn = ATTENTION_BACKENDS[backend]
    except KeyError:
        raise ValueError(
            f"Unknown attention backend {backend!r}; available: {sorted(ATTENTION_BACKENDS)}"
        )
    if backend == "flash":
        kwargs["platform"] = platform
        kwargs["mesh_ctx"] = mesh_ctx
    return fn(q, k, v, **kwargs)


def windowed_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    backend: str,
    is_sliding: jnp.ndarray,
    window: Optional[int],
    dynamic_window: jnp.ndarray,
    causal: bool = True,
    scale: Optional[float] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    logits_soft_cap: Optional[float] = None,
    sinks: Optional[jnp.ndarray] = None,
    bidir_groups: Optional[jnp.ndarray] = None,
    block_q: int = 512,
    block_kv: int = 512,
    platform: Optional[str] = None,
    mesh_ctx=None,
) -> jnp.ndarray:
    """Attention for scanned layer stacks that mix full and sliding-window
    layers (Gemma-2/3, GPT-OSS). The per-layer layer type rides the scan as
    the traced `is_sliding` flag; the flash path needs a STATIC window for
    its splash mask, so it branches with `lax.cond` between two static-mask
    kernels (both compile once; one executes per layer). The sdpa path takes
    the traced `dynamic_window` bound directly (window = S on full layers)."""
    if backend not in ATTENTION_BACKENDS:
        raise ValueError(
            f"Unknown attention backend {backend!r}; available: {sorted(ATTENTION_BACKENDS)}"
        )
    if bidir_groups is not None:
        # data-dependent OR-mask (gemma-3 image blocks): splash masks are
        # static, so this runs on sdpa until a custom dynamic-mask kernel
        if backend == "flash":
            _fallback_loudly("bidirectional image-block mask")
        return sdpa(
            q, k, v,
            causal=causal, scale=scale, segment_ids=segment_ids,
            logits_soft_cap=logits_soft_cap, sliding_window=dynamic_window,
            sinks=sinks, bidir_groups=bidir_groups,
        )
    if backend == "flash" and window is not None and _flash_eligible(platform):
        kw = dict(
            causal=causal, scale=scale, segment_ids=segment_ids,
            logits_soft_cap=logits_soft_cap, sinks=sinks,
            block_q=block_q, block_kv=block_kv, platform=platform,
            mesh_ctx=mesh_ctx,
        )
        if not isinstance(is_sliding, jax.core.Tracer):
            # static flag (unrolled layer loop): compile exactly one kernel
            return flash(q, k, v, sliding_window=window if bool(is_sliding) else None, **kw)
        return jax.lax.cond(
            is_sliding,
            lambda: flash(q, k, v, sliding_window=window, **kw),
            lambda: flash(q, k, v, sliding_window=None, **kw),
        )
    if backend == "flash" and window is None and _flash_eligible(platform):
        return flash(
            q, k, v,
            causal=causal, scale=scale, segment_ids=segment_ids,
            logits_soft_cap=logits_soft_cap, sinks=sinks,
            block_q=block_q, block_kv=block_kv, platform=platform,
            mesh_ctx=mesh_ctx,
        )
    if backend == "ring":
        return ATTENTION_BACKENDS["ring"](
            q, k, v,
            causal=causal, scale=scale, segment_ids=segment_ids,
            logits_soft_cap=logits_soft_cap, sliding_window=dynamic_window,
            sinks=sinks,
        )
    if backend == "flash":
        _fallback_loudly("not running on TPU")
    return sdpa(
        q, k, v,
        causal=causal, scale=scale, segment_ids=segment_ids,
        logits_soft_cap=logits_soft_cap, sliding_window=dynamic_window,
        sinks=sinks,
    )


def _interpret_requested() -> bool:
    """AUTOMODEL_FLASH_INTERPRET=1 runs the splash kernel through the pallas
    interpreter — the REAL kernel code path, executable on CPU (tests)."""
    import os

    return os.environ.get("AUTOMODEL_FLASH_INTERPRET", "0") == "1"


def _flash_eligible(platform: Optional[str] = None) -> bool:
    return _interpret_requested() or is_tpu_platform(platform)
