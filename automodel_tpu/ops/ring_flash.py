"""Blockwise (flash) attention kernels for the ring/context-parallel path.

Parity: the reference runs TE fused attention inside its CP ring
(`cp_comm_type="p2p"`, components/moe/parallelizer.py:279-297) so ring steps
never materialize S² logits. Here: three Pallas kernels implementing the
standard flash decomposition — forward returning (normalized out, logsumexp),
and the dq / dkv backward passes that recompute probabilities from the saved
logsumexp. `parallel.cp` calls them once per ring step and merges the
per-step (out, lse) pairs with the online-softmax rule; the backward rides
dk/dv around the ring with their kv blocks.

Masking is positional: callers pass the GLOBAL position of every local row
(`q_pos`) / key (`kv_pos`), so one kernel serves the contiguous and zigzag
ring layouts, sliding windows, and non-causal attention; packed-sequence
segment ids compose on top. All accumulation is fp32.

Mosaic constraints shape the layouts: every in-kernel value is ≥2-D (1-D
bool/int reshapes don't lower), so q-aligned vectors ride as [.., S, 1]
blocks and kv-aligned ones as [.., 1, S], and size-1 block dims sit on
size-1 array dims (the tiling exemption).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _mask_tile(qp, kp, sq, sk, *, causal, window):
    """[bq, bkv] bool from [bq,1] q-side and [1,bkv] kv-side tiles."""
    m = sq == sk
    if causal:
        m = m & (qp >= kp)
    if window is not None:
        m = m & (qp - kp < window)
    return m


def _tile_alive(qp, kp, *, causal, window):
    """Scalar: does any (q, kv) pair in this tile pass the position mask?
    Position bounds only — segment masking rarely kills whole tiles. Lets
    @pl.when skip the matmuls on dead tiles (half of all tiles under
    causal; whole ring steps for not-yet-visible blocks)."""
    alive = jnp.bool_(True)
    if causal:
        alive = alive & (jnp.max(qp) >= jnp.min(kp))
    if window is not None:
        alive = alive & (jnp.min(qp) - jnp.max(kp) < window)
    return alive


def _fwd_kernel(qp_ref, kp_ref, sq_ref, sk_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, causal, window, scale, kv_steps):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_tile_alive(qp_ref[...], kp_ref[...], causal=causal, window=window))
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask_tile(qp_ref[...], kp_ref[...], sq_ref[0], sk_ref[0],
                          causal=causal, window=window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]  # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # masked→0, no overflow
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc = acc_scr[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc

    @pl.when(kv_i == kv_steps - 1)
    def _():
        l = l_scr[...]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0] = jnp.where(l > 0, acc_scr[...] / safe, 0.0).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l > 0, m_scr[...] + jnp.log(safe), NEG_INF)


def _dq_kernel(qp_ref, kp_ref, sq_ref, sk_ref, q_ref, k_ref, v_ref,
               do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
               *, causal, window, scale, kv_steps):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_tile_alive(qp_ref[...], kp_ref[...], causal=causal, window=window))
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask_tile(qp_ref[...], kp_ref[...], sq_ref[0], sk_ref[0],
                          causal=causal, window=window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
        dp = jax.lax.dot_general(
            do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        dq_scr[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kv_i == kv_steps - 1)
    def _():
        dq_ref[0] = dq_scr[...]


def _dkv_kernel(qp_ref, kp_ref, sq_ref, sk_ref, q_ref, k_ref, v_ref,
                do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                *, causal, window, scale, q_steps):
    q_i = pl.program_id(2)

    @pl.when(q_i == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_tile_alive(qp_ref[...], kp_ref[...], causal=causal, window=window))
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask_tile(qp_ref[...], kp_ref[...], sq_ref[0], sk_ref[0],
                          causal=causal, window=window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
        do = do_ref[0].astype(jnp.float32)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        dk_scr[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(q_i == q_steps - 1)
    def _():
        dk_ref[0] = dk_scr[...]
        dv_ref[0] = dv_scr[...]


def _pick_block(s: int, pref: int = 512) -> int:
    """Largest 128-multiple ≤ pref dividing s, preferring pref itself. Large
    tiles amortize per-grid-step overhead (at 256² tiles a 32k ring step is
    >100k grid steps and overhead dominates); bounded by VMEM via pref."""
    for b in [pref] + [c for c in (1024, 512, 256, 128) if c < pref]:
        if s % b == 0:
            return b
    return s  # small/odd seq: single tile (interpret/test sizes)


def _prep(q, k, v, q_pos, kv_pos, seg_q, seg_kv):
    """Flatten heads into the leading dim and lift vectors to 2-D:
    q-aligned → [.., Sq, 1], kv-aligned → [.., 1, Sk]."""
    B, Sq, N, H = q.shape
    Sk, Nkv = k.shape[1], k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(B * N, Sq, H)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Nkv, Sk, H)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Nkv, Sk, H)
    qp = q_pos.astype(jnp.int32)[:, None]            # [Sq, 1]
    kp = kv_pos.astype(jnp.int32)[None, :]           # [1, Sk]
    sq = seg_q.astype(jnp.int32)[:, :, None]         # [B, Sq, 1]
    sk = seg_kv.astype(jnp.int32)[:, None, :]        # [B, 1, Sk]
    return qf, kf, vf, qp, kp, sq, sk


def _specs(B, N, Nkv, H, bq, bkv, *, kv_major=False):
    """Block specs; grid is (bn, qt, kt) or with kv_major (bn, kt, qt)."""
    rep = N // Nkv

    def ix(fn):
        if kv_major:
            return lambda bn, kt, qt: fn(bn, qt, kt)
        return lambda bn, qt, kt: fn(bn, qt, kt)

    qpos = pl.BlockSpec((bq, 1), ix(lambda bn, qt, kt: (qt, 0)))
    kpos = pl.BlockSpec((1, bkv), ix(lambda bn, qt, kt: (0, kt)))
    segq = pl.BlockSpec((1, bq, 1), ix(lambda bn, qt, kt: (bn // N, qt, 0)))
    segk = pl.BlockSpec((1, 1, bkv), ix(lambda bn, qt, kt: (bn // N, 0, kt)))
    qspec = pl.BlockSpec((1, bq, H), ix(lambda bn, qt, kt: (bn, qt, 0)))
    kspec = pl.BlockSpec(
        (1, bkv, H),
        ix(lambda bn, qt, kt: ((bn // N) * Nkv + (bn % N) // rep, kt, 0)),
    )
    lspec = pl.BlockSpec((1, bq, 1), ix(lambda bn, qt, kt: (bn, qt, 0)))
    return qpos, kpos, segq, segk, qspec, kspec, lspec


def flash_block_fwd(q, k, v, q_pos, kv_pos, seg_q, seg_kv, *,
                    causal, window, scale, interpret=False,
                    block_q=None, block_kv=None):
    """q [B,Sq,N,H] × k/v [B,Sk,Nkv,H] → (out [B,Sq,N,H], lse [B,N,Sq]).
    ``block_q``/``block_kv`` override the static preferences."""
    B, Sq, N, H = q.shape
    Sk, Nkv = k.shape[1], k.shape[2]
    bq = _pick_block(Sq, block_q or 512)
    bkv = _pick_block(Sk, block_kv or 1024)
    qf, kf, vf, qp, kp, sq, sk = _prep(q, k, v, q_pos, kv_pos, seg_q, seg_kv)
    qpos, kpos, segq, segk, qspec, kspec, lspec = _specs(B, N, Nkv, H, bq, bkv)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, window=window,
                          scale=scale, kv_steps=Sk // bkv),
        grid=(B * N, Sq // bq, Sk // bkv),
        in_specs=[qpos, kpos, segq, segk, qspec, kspec, kspec],
        out_specs=[qspec, lspec],
        out_shape=[
            jax.ShapeDtypeStruct((B * N, Sq, H), q.dtype),
            jax.ShapeDtypeStruct((B * N, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="block_flash_fwd",
    )(qp, kp, sq, sk, qf, kf, vf)
    return (
        out.reshape(B, N, Sq, H).transpose(0, 2, 1, 3),
        lse.reshape(B, N, Sq),
    )


def flash_block_bwd(q, k, v, do, lse, delta, q_pos, kv_pos, seg_q, seg_kv, *,
                    causal, window, scale, interpret=False,
                    block_q=None, block_kv=None):
    """Backward for one kv block: → (dq [B,Sq,N,H] f32, dk, dv [B,Sk,Nkv,H]
    f32). `lse`/`delta` are [B,N,Sq] (global logsumexp / rowsum(do·out))."""
    B, Sq, N, H = q.shape
    Sk, Nkv = k.shape[1], k.shape[2]
    rep = N // Nkv
    bq = _pick_block(Sq, block_q or 512)
    bkv = _pick_block(Sk, block_kv or 1024)
    qf, kf, vf, qp, kp, sq, sk = _prep(q, k, v, q_pos, kv_pos, seg_q, seg_kv)
    dof = do.transpose(0, 2, 1, 3).reshape(B * N, Sq, H)
    lsef = lse.reshape(B * N, Sq, 1)
    deltaf = delta.reshape(B * N, Sq, 1)
    args = (qp, kp, sq, sk, qf, kf, vf, dof, lsef, deltaf)

    qpos, kpos, segq, segk, qspec, kspec, lspec = _specs(B, N, Nkv, H, bq, bkv)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, window=window,
                          scale=scale, kv_steps=Sk // bkv),
        grid=(B * N, Sq // bq, Sk // bkv),
        in_specs=[qpos, kpos, segq, segk, qspec, kspec, kspec, qspec, lspec, lspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B * N, Sq, H), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="block_flash_dq",
    )(*args)

    # dkv: kv tile outer, q tiles inner (accumulate over queries)
    qpos2, kpos2, segq2, segk2, qspec2, kspec2, lspec2 = _specs(
        B, N, Nkv, H, bq, bkv, kv_major=True
    )
    dkv_out = pl.BlockSpec((1, bkv, H), lambda bn, kt, qt: (bn, kt, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, window=window,
                          scale=scale, q_steps=Sq // bq),
        grid=(B * N, Sk // bkv, Sq // bq),
        in_specs=[qpos2, kpos2, segq2, segk2, qspec2, kspec2, kspec2,
                  qspec2, lspec2, lspec2],
        out_specs=[dkv_out, dkv_out],
        out_shape=[
            jax.ShapeDtypeStruct((B * N, Sk, H), jnp.float32),
            jax.ShapeDtypeStruct((B * N, Sk, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, H), jnp.float32),
            pltpu.VMEM((bkv, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="block_flash_dkv",
    )(*args)
    # GQA: per-q-head dk/dv reduce onto their kv head
    dk = dk.reshape(B, Nkv, rep, Sk, H).sum(axis=2).transpose(0, 2, 1, 3)
    dv = dv.reshape(B, Nkv, rep, Sk, H).sum(axis=2).transpose(0, 2, 1, 3)
    dq = dq.reshape(B, N, Sq, H).transpose(0, 2, 1, 3)
    return dq, dk, dv


def flash_attention(
    q, k, v, *,
    causal=True, scale=None, segment_ids=None, sliding_window=None,
    sinks=None, block_q=None, block_kv=None, interpret=False,
):
    """Non-ring single-chip entry over the SAME blockwise kernels the CP
    ring uses — one kv "ring step" covering the whole sequence. This is the
    in-tree alternative to the library splash kernel: positional masking
    with per-tile dead-tile skipping (a 128-token sliding window kills
    almost every kv tile), native GQA, packed-segment ids, gpt-oss sinks
    (folded post-merge exactly as parallel/cp.py does), and no head_dim
    divisibility constraint — head_dim 64 runs as-is. Nothing in the
    program calls it (tests/test_block_flash.py holds it to sdpa).

    q [B,S,N,H] × k/v [B,S,Nkv,H] → [B,S,N,H] in q.dtype; differentiable
    (custom_vjp on the flash identities, d_sinks included)."""
    B, S, N, H = q.shape
    scale = scale if scale is not None else 1.0 / (H**0.5)
    window = sliding_window
    Sp = -(-S // 128) * 128
    pad = Sp - S
    if pad:
        zeros = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q, k, v = zeros(q), zeros(k), zeros(v)
    if segment_ids is None:
        # padded tokens get segment -1 ≠ any real id → never attended; a
        # fully-padded q row comes out 0 via the all-masked guard and is
        # sliced off below
        seg0 = jnp.zeros((B, S), jnp.int32)
    else:
        seg0 = segment_ids.astype(jnp.int32)
    if pad:
        seg0 = jnp.pad(seg0, ((0, 0), (0, pad)), constant_values=-1)
    pos = jnp.arange(Sp, dtype=jnp.int32)
    kw = dict(causal=causal, window=window, scale=scale, interpret=interpret,
              block_q=block_q, block_kv=block_kv)

    def _fwd_impl(q, k, v, seg, sk):
        out, lse = flash_block_fwd(q, k, v, pos, pos, seg, seg, **kw)
        if sk is not None:
            # the sink is one zero-value virtual key: fold it post-merge —
            # lse' = logaddexp(lse, sink), out' = out·exp(lse − lse'). The
            # saved lse' makes the blockwise backward exact (p = exp(s −
            # lse') are the extended-softmax probabilities).
            s_b = sk.astype(jnp.float32)[None, :, None]  # [1, n, 1]
            lse_ext = jnp.logaddexp(lse, s_b)
            out = out.astype(jnp.float32) * jnp.exp(lse - lse_ext).transpose(
                0, 2, 1
            )[..., None]
            lse = lse_ext
        return out.astype(q.dtype), lse

    @jax.custom_vjp
    def attn(q, k, v, seg, sk):
        return _fwd_impl(q, k, v, seg, sk)[0]

    def attn_fwd(q, k, v, seg, sk):
        out, lse = _fwd_impl(q, k, v, seg, sk)
        return out, (q, k, v, seg, sk, out, lse)

    def attn_bwd(res, dout):
        q, k, v, seg, sk, out, lse = res
        do32 = dout.astype(jnp.float32)
        delta = (do32 * out.astype(jnp.float32)).sum(-1).transpose(0, 2, 1)
        dq, dk, dv = flash_block_bwd(
            q, k, v, dout, lse, delta, pos, pos, seg, seg, **kw
        )
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

    attn.defvjp(attn_fwd, attn_bwd)
    out = attn(q, k, v, seg0, sinks)
    return out[:, :S] if pad else out


def merge_partials(out_a, lse_a, out_t, lse_t):
    """Online-softmax merge of two independently-normalized partial
    attentions. out: [B,S,N,H] fp32, lse: [B,N,S] fp32."""
    m = jnp.maximum(lse_a, lse_t)
    # all-masked rows have lse == NEG_INF on both sides; keep them at 0/NEG_INF
    alive = m > NEG_INF / 2
    wa = jnp.where(alive, jnp.exp(lse_a - m), 0.0)
    wt = jnp.where(alive, jnp.exp(lse_t - m), 0.0)
    denom = wa + wt
    wa_n = (wa / jnp.maximum(denom, 1e-30)).transpose(0, 2, 1)[..., None]
    wt_n = (wt / jnp.maximum(denom, 1e-30)).transpose(0, 2, 1)[..., None]
    out = out_a * wa_n + out_t * wt_n
    lse = jnp.where(alive, m + jnp.log(jnp.maximum(denom, 1e-30)), NEG_INF)
    return out, lse
