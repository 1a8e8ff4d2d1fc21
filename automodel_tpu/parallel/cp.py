"""Context parallelism: ring attention over the ``cp`` mesh axis.

Parity: the reference's CP paths (distributed/cp_utils.py:68-184 — torch
experimental `context_parallel` ring SDPA with allgather KV rotation; and the
TE `cp_comm_type="p2p"` ring, moe/parallelizer.py:279-297). TPU-native
design (SURVEY.md §7): `shard_map` over the cp axis with `lax.ppermute` KV
rotation and online-softmax (flash-style) merging of per-block partial
results, so each device only ever holds ``S/cp`` keys/values — the
long-context mechanism.

Two layers:

- :func:`ring_attention_shard` — per-device ring loop; runs INSIDE a
  shard_map region (or any context where ``axis_name`` is bound).
- :func:`make_ring_attention` — wraps it in `shard_map` with specs resolved
  from the MeshContext and registers it as the ``"ring"`` backend in
  `ops.attention.ATTENTION_BACKENDS` via :func:`install_ring_backend`.

Two seq layouts:

- CONTIGUOUS (default): rank r holds positions [r·S/cp, (r+1)·S/cp). Causal
  masking makes this load-imbalanced (later ranks do more real work).
- ZIGZAG (``zigzag=True``): the sequence splits into 2·cp chunks and rank r
  holds chunks (r, 2cp-1-r) — every rank sees the same causal work, the
  standard ring-attention balancing (the reference balances via THD
  round-robin partitioning, cp_utils.py:296-337). The DATA must be permuted
  into zigzag order first (:func:`zigzag_indices` / :func:`apply_zigzag` on
  input_ids/labels/position_ids/segment_ids); rope stays correct because
  position_ids carry true positions, and the loss is layout-invariant
  because labels were shifted before the permutation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from automodel_tpu.ops.attention import repeat_kv

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def zigzag_indices(seq_len: int, cp: int):
    """Permutation putting global positions into zigzag-layout order: chunk
    list (0, 2cp-1), (1, 2cp-2), ... concatenated rank-major."""
    import numpy as np

    if seq_len % (2 * cp):
        raise ValueError(f"seq_len {seq_len} must divide 2*cp={2 * cp}")
    half = seq_len // (2 * cp)
    chunks = np.arange(seq_len).reshape(2 * cp, half)
    order = []
    for r in range(cp):
        order.append(chunks[r])
        order.append(chunks[2 * cp - 1 - r])
    return np.concatenate(order)


def apply_zigzag(x, cp: int, axis: int = 1):
    """Reorder the seq axis into zigzag layout (host or device arrays)."""
    import numpy as np

    idx = zigzag_indices(x.shape[axis], cp)
    return jnp.take(x, idx, axis=axis) if isinstance(x, jnp.ndarray) else np.take(
        x, idx, axis=axis
    )


def undo_zigzag(x, cp: int, axis: int = 1):
    import numpy as np

    idx = zigzag_indices(x.shape[axis], cp)
    inv = np.empty_like(idx)
    inv[idx] = np.arange(len(idx))
    return jnp.take(x, inv, axis=axis) if isinstance(x, jnp.ndarray) else np.take(
        x, inv, axis=axis
    )


def _zigzag_positions(rank, s_loc: int, cp: int):
    """Global positions of a rank's local tokens in zigzag layout."""
    half = s_loc // 2
    a = jnp.arange(half)
    return jnp.concatenate(
        [rank * half + a, (2 * cp - 1 - rank) * half + a]
    )


def _ring_interpret_requested() -> bool:
    import os

    return os.environ.get("AUTOMODEL_RING_INTERPRET", "0") == "1"


def ring_attention_shard(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str = "cp",
    causal: bool = True,
    scale: Optional[float] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    sinks: Optional[jnp.ndarray] = None,
    zigzag: bool = False,
    platform: Optional[str] = None,
) -> jnp.ndarray:
    """Ring attention on per-device shards. q/k/v: [B, S_loc, N(,kv), H],
    segment_ids: [B, S_loc]. Requires `axis_name` bound (shard_map).

    On TPU (or under AUTOMODEL_RING_INTERPRET=1) each ring step runs the
    Pallas blockwise kernels from ops.ring_flash — O(S_loc·block) memory;
    otherwise (and for logits_soft_cap, which the kernel path doesn't carry)
    the XLA formulation below materializes per-step S_loc² logits.

    ``sinks`` (gpt-oss, [N] per-head logits): a sink is one extra virtual
    key with value 0, so it never needs to ride the ring — the merged
    (out, lse) pair absorbs it AFTER the last step: lse' = logaddexp(lse,
    sink) and out' = out·exp(lse − lse'). The saved lse' makes the existing
    blockwise backward exact (p = exp(s − lse') are the extended-softmax
    probabilities), with d_sink = −Σ p_sink·Δ falling out of the same
    flash identity the kernels use."""
    from automodel_tpu.ops.platform_check import is_tpu_platform

    interpret = _ring_interpret_requested()
    if logits_soft_cap is None and (interpret or is_tpu_platform(platform)):
        return _ring_flash_shard(
            q, k, v,
            axis_name=axis_name, causal=causal, scale=scale,
            segment_ids=segment_ids, sliding_window=sliding_window,
            sinks=sinks, zigzag=zigzag, interpret=interpret,
        )
    return _ring_attention_shard_xla(
        q, k, v,
        axis_name=axis_name, causal=causal, scale=scale,
        segment_ids=segment_ids, logits_soft_cap=logits_soft_cap,
        sliding_window=sliding_window, sinks=sinks, zigzag=zigzag,
    )


def _ring_flash_shard(
    q, k, v, *, axis_name, causal, scale, segment_ids, sliding_window,
    zigzag, interpret, sinks=None,
):
    from automodel_tpu.ops.ring_flash import (
        NEG_INF,
        flash_block_bwd,
        flash_block_fwd,
        merge_partials,
    )

    b, s_loc, n, h = q.shape
    scale = scale if scale is not None else 1.0 / (h**0.5)
    cp = jax.lax.psum(1, axis_name)  # python int inside shard_map
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def pos_of(rank):
        if zigzag:
            return _zigzag_positions(rank, s_loc, int(cp))
        return rank * s_loc + jnp.arange(s_loc)

    if segment_ids is None:
        seg0 = jnp.zeros((b, s_loc), jnp.int32)
    else:
        seg0 = segment_ids.astype(jnp.int32)

    def rotate(*xs):
        # one ppermute over the tuple → one fused collective on ICI
        return jax.lax.ppermute(xs, axis_name, perm)

    # NOTE: the custom_vjp fwd/bwd must not close over tracers (axis_index);
    # rank/positions are recomputed inside each impl.
    def _fwd_impl(q, k, v, seg, sk):
        my_rank = jax.lax.axis_index(axis_name)
        q_pos = pos_of(my_rank)
        out = jnp.zeros((b, s_loc, n, h), jnp.float32)
        lse = jnp.full((b, n, s_loc), NEG_INF, jnp.float32)

        # python loop: cp is a static int here, and unrolling lets the last
        # step skip its (result-discarding) kv rotation — ring attention is
        # ICI-bound, so a dead full-KV ppermute per layer is real wall-clock
        k_blk, v_blk, seg_blk = k, v, seg
        for step in range(cp):
            kv_pos = pos_of((my_rank - step) % cp)
            o_t, lse_t = flash_block_fwd(
                q, k_blk, v_blk, q_pos, kv_pos, seg, seg_blk,
                causal=causal, window=sliding_window, scale=scale,
                interpret=interpret,
            )
            out, lse = merge_partials(out, lse, o_t.astype(jnp.float32), lse_t)
            if step < cp - 1:
                k_blk, v_blk, seg_blk = rotate(k_blk, v_blk, seg_blk)
        if sk is not None:
            # fold the sink in post-merge: one zero-value virtual key
            s_b = sk.astype(jnp.float32)[None, :, None]  # [1, n, 1]
            lse_ext = jnp.logaddexp(lse, s_b)  # extended lse (dead rows → s)
            out = out * jnp.exp(lse - lse_ext).transpose(0, 2, 1)[..., None]
            lse = lse_ext
        return out.astype(q.dtype), lse

    @jax.custom_vjp
    def ring(q, k, v, seg, sk):
        return _fwd_impl(q, k, v, seg, sk)[0]

    def ring_fwd(q, k, v, seg, sk):
        out, lse = _fwd_impl(q, k, v, seg, sk)
        return out, (q, k, v, seg, sk, out, lse)

    def ring_bwd(res, dout):
        q, k, v, seg, sk, out, lse = res
        my_rank = jax.lax.axis_index(axis_name)
        q_pos = pos_of(my_rank)
        do32 = dout.astype(jnp.float32)
        # delta = rowsum(dO ∘ O) per (b, n, s) — the flash backward constant
        delta = (do32 * out.astype(jnp.float32)).sum(-1).transpose(0, 2, 1)

        dq = jnp.zeros(q.shape, jnp.float32)
        dk = jnp.zeros(k.shape, jnp.float32)
        dv = jnp.zeros(v.shape, jnp.float32)
        k_blk, v_blk, seg_blk = k, v, seg
        for step in range(cp):
            kv_pos = pos_of((my_rank - step) % cp)
            dq_t, dk_t, dv_t = flash_block_bwd(
                q, k_blk, v_blk, dout, lse, delta, q_pos, kv_pos, seg, seg_blk,
                causal=causal, window=sliding_window, scale=scale,
                interpret=interpret,
            )
            dq = dq + dq_t
            # dk/dv ride the ring WITH their kv block; after cp total
            # rotations they are back on the owning device with every
            # contribution (the k/v/seg blocks themselves stop one step
            # early — the last compute doesn't need the next block)
            dk, dv = dk + dk_t, dv + dv_t
            if step < cp - 1:
                k_blk, v_blk, seg_blk, dk, dv = rotate(
                    k_blk, v_blk, seg_blk, dk, dv
                )
            else:  # k/v/seg are done; dk/dv still need the final hop home
                dk, dv = rotate(dk, dv)
        import numpy as np

        ct_seg = np.zeros(seg.shape, jax.dtypes.float0)
        ct_sk = None
        if sk is not None:
            # sink column of the flash backward: dp_sink = dO·v_sink = 0, so
            # ds_sink = p_sink·(0 − Δ); summed over its (b, s) broadcast
            p_sink = jnp.exp(sk.astype(jnp.float32)[None, :, None] - lse)
            ct_sk = (-(p_sink * delta).sum(axis=(0, 2))).astype(sk.dtype)
        return (
            dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            ct_seg, ct_sk,
        )

    ring.defvjp(ring_fwd, ring_bwd)
    return ring(q, k, v, seg0, sinks)


def _ring_attention_shard_xla(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str = "cp",
    causal: bool = True,
    scale: Optional[float] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    sinks: Optional[jnp.ndarray] = None,
    zigzag: bool = False,
) -> jnp.ndarray:
    """Reference XLA ring (materializes per-step S_loc² logits)."""
    b, s_loc, n, h = q.shape
    n_kv = k.shape[2]
    scale = scale if scale is not None else 1.0 / (h**0.5)
    cp = jax.lax.psum(1, axis_name)
    my_rank = jax.lax.axis_index(axis_name)

    q32 = q.astype(jnp.float32)

    def pos_of(rank):  # global positions of rank's local tokens
        if zigzag:
            # cp is a traced axis size only under vmap-style tracing; in
            # shard_map it is a python int via psum(1) — static here
            return _zigzag_positions(rank, s_loc, int(cp))
        return rank * s_loc + jnp.arange(s_loc)

    q_pos = pos_of(my_rank)

    # online-softmax accumulators
    o = jnp.zeros((b, s_loc, n, h), jnp.float32)
    m = jnp.full((b, n, s_loc), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, n, s_loc), jnp.float32)

    if segment_ids is None:
        seg = jnp.zeros((b, s_loc), jnp.int32)
    else:
        seg = segment_ids.astype(jnp.int32)

    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def body(step, carry):
        o, m, l, k_blk, v_blk, seg_blk = carry
        src_rank = (my_rank - step) % cp
        kv_pos = pos_of(src_rank)

        k_exp = repeat_kv(k_blk, n // n_kv).astype(jnp.float32)
        v_exp = repeat_kv(v_blk, n // n_kv).astype(jnp.float32)
        logits = jnp.einsum("bqnh,bknh->bnqk", q32, k_exp) * scale
        if logits_soft_cap is not None:
            logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)

        mask = jnp.ones((s_loc, s_loc), bool)
        if causal:
            mask = q_pos[:, None] >= kv_pos[None, :]
        if sliding_window is not None:
            mask = mask & (q_pos[:, None] - kv_pos[None, :] < sliding_window)
        mask = mask[None, None]  # [1,1,sq,sk]
        if segment_ids is not None:
            mask = mask & (seg[:, None, :, None] == seg_blk[:, None, None, :])
        logits = jnp.where(mask, logits, _NEG_INF)

        m_new = jnp.maximum(m, logits.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bnqk,bknh->bqnh", p, v_exp
        )

        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        seg_nxt = jax.lax.ppermute(seg_blk, axis_name, perm)
        return o_new, m_new, l_new, k_nxt, v_nxt, seg_nxt

    o, m, l, *_ = jax.lax.fori_loop(0, cp, body, (o, m, l, k, v, seg))
    if sinks is not None:
        # the sink is one zero-value virtual key: it only grows the softmax
        # denominator, so fold it into l post-hoc (this path is plain
        # differentiable XLA — autodiff carries d_sinks)
        l = l + jnp.exp(sinks.astype(jnp.float32)[None, :, None] - m)
    l_t = l.transpose(0, 2, 1)[..., None]  # [b,s,n,1]
    out = jnp.where(l_t > 0, o / jnp.maximum(l_t, 1e-30), 0.0)
    return out.astype(q.dtype)


def make_ring_attention(mesh_ctx, zigzag: bool = False):
    """Drop-in attention over GLOBAL arrays: shard_map'd ring over cp, with
    batch sharded on the data axes and heads on tp (the GSPMD layout the rest
    of the model uses)."""
    mesh = mesh_ctx.mesh
    bspec = mesh_ctx.resolve(("batch",))  # P over batch axes
    batch_axes = bspec[0] if len(bspec) else None
    cp_ax = "cp" if mesh.shape["cp"] > 1 else None
    tp_ax = "tp" if mesh.shape["tp"] > 1 else None
    qkv_spec = P(batch_axes, cp_ax, tp_ax, None)
    seg_spec = P(batch_axes, cp_ax)

    def ring(
        q,
        k,
        v,
        *,
        causal: bool = True,
        scale: Optional[float] = None,
        segment_ids: Optional[jnp.ndarray] = None,
        logits_soft_cap: Optional[float] = None,
        sliding_window: Optional[int] = None,
        sinks: Optional[jnp.ndarray] = None,
        **_ignored,
    ):
        has_seg = segment_ids is not None
        has_sinks = sinks is not None
        in_specs = (qkv_spec, qkv_spec, qkv_spec)
        if has_seg:
            in_specs += (seg_spec,)
        if has_sinks:
            in_specs += (P(tp_ax),)  # per-head logits follow the head shard
        inner = functools.partial(
            ring_attention_shard,
            axis_name="cp",
            causal=causal,
            scale=scale,
            logits_soft_cap=logits_soft_cap,
            sliding_window=sliding_window,
            zigzag=zigzag and mesh.shape["cp"] > 1,
            platform=mesh_ctx.platform,
        )

        def fn(*args):
            q_, k_, v_, *rest = args
            rest = list(rest)
            kw = {}
            if has_seg:
                kw["segment_ids"] = rest.pop(0)
            if has_sinks:
                kw["sinks"] = rest.pop(0)
            return inner(q_, k_, v_, **kw)

        mapped = shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=qkv_spec, check_vma=False
        )
        args = (q, k, v)
        if has_seg:
            args += (segment_ids,)
        if has_sinks:
            args += (sinks,)
        return mapped(*args)

    return ring


def install_ring_backend(mesh_ctx, zigzag: bool = False) -> None:
    """Register ``"ring"`` in the attention-backend registry, bound to this
    mesh. One mesh at a time (module-global registry) — matches the
    one-mesh-per-process training model."""
    from automodel_tpu.ops.attention import ATTENTION_BACKENDS

    ATTENTION_BACKENDS["ring"] = make_ring_attention(mesh_ctx, zigzag=zigzag)
