"""Fused Pallas paged-attention decode kernel + int8 KV-block quantization.

The serving decode path (serving/paged.py) historically expressed paged
attention as XLA ops: per-slot block tables GATHER the block pool into a
contiguous ``[L, B, C_view, Nkv, H]`` view, the view feeds ``sdpa_decode``,
and the written token SCATTERS back — a full round trip of every resident
sequence's KV through HBM per decoded token. docs/serving.md named that
gather as the known limitation; this module is the fix: one kernel that
indexes the pool **in place** through the block tables (the vLLM
PagedAttention idea, Kwon et al. 2023, as a Mosaic kernel), dequantizing
int8 blocks on the fly, so per-token HBM traffic drops to the KV actually
attended.

Mechanics: grid ``(B, blocks_per_sequence)``; the per-slot block table and
lengths ride as **scalar-prefetch** operands so each grid step's BlockSpec
``index_map`` DMAs exactly the pool block ``tables[b, j]`` into VMEM —
no gather materialization, no copy of cold blocks past a sequence's
length (dead blocks are skipped via ``pl.when``). Online-softmax
accumulators live in VMEM scratch across the block dimension, GQA is
native (kv heads never repeat-materialize), and queries may be a chunk
(``Sq = k+1`` for the speculative verify forward) with per-query causal
masking against absolute positions.

Int8 KV blocks: values are stored per-(token row, kv head) — scale
``amax / 127`` alongside the pool as ``[*, NB, BS, Nkv]`` fp32 (the
row-granular refinement of the per-block scale layouts in ``ops/fp8.py``
/ ``checkpoint/quant_io.py``: incremental single-token writes can never
force a whole-block rescale). ``quantize_kv_rows`` is the write-side
transform (quantize-on-scatter), the kernel (and the gather fallback)
dequantize on read; quantize∘dequantize is exactly idempotent, so chunked
prefill's rewrite-the-view scatter does not drift.

The gather path stays in serving/paged.py as the fallback / A-B baseline
(``AUTOMODEL_PAGED_DECODE=gather``); ``tools/kernel_bench.py`` races the
two per (head_dim, block_size, kv dtype) into the autotune registry
(``autotune.paged_key``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from automodel_tpu.ops.platform_check import (
    kernel_axes,
    kernel_shard_map,
    sharded_axes,
)

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
INT8_MAX = 127.0

# per-grid-step VMEM budget for entry validation / sweep filtering — one
# block of k+v (+scales) plus the whole query/output/accumulator set must
# fit with double-buffering headroom
_VMEM_BUDGET = 12 * 1024 * 1024


# -- int8 KV-block quantization ----------------------------------------------


def quantize_kv_rows(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``[..., Nkv, H]`` → (int8 values, fp32 scales ``[..., Nkv]``).
    Symmetric per-(row, kv-head) absmax scaling: each written token row owns
    its scale, so single-token decode writes and whole-table prefill
    scatters use the same transform and never rescale neighbours."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / INT8_MAX
    q = jnp.clip(jnp.round(xf / scale[..., None]), -INT8_MAX, INT8_MAX)
    return q.astype(jnp.int8), scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of ``quantize_kv_rows`` (scale broadcast over H)."""
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)).astype(dtype)


# -- feasibility (shared with tools/kernel_bench.py sweep filtering) ---------


def _paged_budget_ok(
    block_size: int, nkv: int, head_dim: int, sq: int, rep: int,
    itemsize: int, quantized: bool = False,
) -> bool:
    kv = 2 * block_size * nkv * head_dim * itemsize
    if quantized:
        kv += 2 * block_size * nkv * 4
    rows = nkv * sq * rep
    qo = 2 * rows * head_dim * 4
    scratch = (2 * rows + rows * head_dim) * 4
    return 2 * kv + qo + scratch <= _VMEM_BUDGET


# -- kernel ------------------------------------------------------------------


def _paged_kernel(
    tables_ref, lengths_ref,  # scalar prefetch
    q_ref, k_ref, v_ref, ks_ref, vs_ref,  # ks/vs absent when not quantized
    o_ref, m_scr, l_scr, acc_scr,
    *, nkv, rep, sq, bs, nbseq, window, soft_cap, quantized,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    sr = sq * rep
    length = lengths_ref[b]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # dead-block skipping: query rows sit at absolute positions
    # length..length+sq-1 and attend pos <= their own position (the row at
    # `length` was scattered into the pool BEFORE this attend, decode_ctx
    # style), so blocks entirely past length+sq-1 — and, under a window,
    # entirely before length-window+1 — contribute nothing
    alive = j * bs <= length + sq - 1
    if window is not None:
        alive = alive & ((j + 1) * bs - 1 > length - window)

    @pl.when(alive)
    def _():
        k = k_ref[0].astype(jnp.float32)  # [BS, Nkv, H]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0][..., None]
            v = v * vs_ref[0][..., None]
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        # per-query absolute position: q rows are g-major then (qi, rep)
        qi = jax.lax.broadcasted_iota(jnp.int32, (sr, 1), 0) // rep
        q_abs = length + qi  # [SR, 1]
        mask = pos <= q_abs  # [SR, BS]
        if window is not None:
            mask = mask & (q_abs - pos < window)
        for g in range(nkv):
            qg = q_ref[0, g * sr : (g + 1) * sr, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                qg, k[:, g], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [SR, BS]
            if soft_cap is not None:
                s = soft_cap * jnp.tanh(s / soft_cap)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[g * sr : (g + 1) * sr]
            l_prev = l_scr[g * sr : (g + 1) * sr]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            m_scr[g * sr : (g + 1) * sr] = m_new
            l_scr[g * sr : (g + 1) * sr] = l_prev * corr + p.sum(
                axis=1, keepdims=True
            )
            acc_scr[g * sr : (g + 1) * sr] = acc_scr[
                g * sr : (g + 1) * sr
            ] * corr + jax.lax.dot_general(
                p, v[:, g], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(j == nbseq - 1)
    def _():
        l = l_scr[...]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0] = jnp.where(l > 0, acc_scr[...] / safe, 0.0).astype(o_ref.dtype)


def paged_attend(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    *,
    mesh_ctx=None,
    **kw,
) -> jnp.ndarray:
    """``_paged_attend`` on whatever mesh the pool lives on. On a mesh of
    several devices the kernel runs inside a ``shard_map`` over the axes
    the pool is sharded on — KV heads over ``tp`` (serving/paged.place_pool)
    and nothing else, since every sequence's table may point anywhere in
    the pool: each TP shard attends its own heads over its own pool slice
    and no cache collective runs. The data axes hold pool replicas, so the
    slots are computed whole on each."""
    if kernel_axes(mesh_ctx) is None:
        return _paged_attend(
            q, k_pool, v_pool, tables, lengths, k_scale, v_scale, **kw
        )
    # a stacked pool [L, NB, BS, Nkv, H] (``layer`` given) has one more leading axis
    lead = () if kw.get("layer") is None else (None,)
    heads = sharded_axes(
        mesh_ctx, "tensor", (q.shape[2], k_pool.shape[-2]),
        "paged-attention heads / KV heads",
    )
    qo = P(None, None, heads, None)
    pool = P(*lead, None, None, heads, None)
    args = [q, k_pool, v_pool, tables, lengths]
    specs = [qo, pool, pool, P(), P()]
    if k_scale is not None:
        args += [k_scale, v_scale]
        specs += [P(*lead, None, None, heads)] * 2
    return kernel_shard_map(
        mesh_ctx, functools.partial(_paged_attend, **kw), tuple(specs), qo
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "sliding_window", "logits_soft_cap", "interpret", "layer",
    ),
)
def _paged_attend(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    logits_soft_cap: Optional[float] = None,
    interpret: bool = False,
    layer: Optional[int] = None,
) -> jnp.ndarray:
    """Paged decode/verify attention, in place over the block pool.

    With ``layer`` (static) the pools (and scales) are the whole stacked
    arrays ``[L, NB, BS, Nkv, H]`` and the block specs index that layer of
    them: a stack that is not scanned never slices a layer's pool out.

    q ``[B, Sq, N, H]`` (Sq = 1 for decode, the verify chunk for
    speculative decoding); k_pool/v_pool ``[NB, BS, Nkv, H]`` (one layer's
    pool slice; int8 with ``k_scale``/``v_scale`` ``[NB, BS, Nkv]`` fp32);
    tables ``[B, NBseq]`` int32 block tables; lengths ``[B]`` int32 — the
    absolute position of query row 0 (rows ``length..length+Sq-1`` must
    already be scattered into the pool; row qi attends pos ≤ length+qi).
    → ``[B, Sq, N, H]`` in q.dtype. Equals ``sdpa_decode`` over the
    gathered (dequantized) view to fp32 accumulation order.
    """
    B, Sq, N, H = q.shape
    NB, BS, Nkv, Hp = k_pool.shape[-4:]
    if Hp != H:
        # a lane-packed pool (generation/kv_cache.packed_heads): ``pack`` KV
        # heads share one row of Hp = pack * H lanes. Each query is padded to
        # Hp with zeros outside its own KV head's lanes, so the kernel's QK^T
        # against the packed row is exactly the head's own dot product; PV
        # then comes out for all packed heads and the query's own lanes are
        # kept. The kernel sees Nkv packed heads of Hp, unchanged.
        pack = Hp // H
        own = jax.nn.one_hot((jnp.arange(N) // (N // (Nkv * pack))) % pack, pack, dtype=q.dtype)
        qp = (q[:, :, :, None, :] * own[None, None, :, :, None]).reshape(B, Sq, N, Hp)
        out = _paged_attend(
            qp, k_pool, v_pool, tables, lengths, k_scale, v_scale,
            scale=scale if scale is not None else 1.0 / (H**0.5),
            sliding_window=sliding_window, logits_soft_cap=logits_soft_cap,
            interpret=interpret, layer=layer,
        )
        return jnp.einsum("bsnph,np->bsnh", out.reshape(B, Sq, N, pack, H), own)
    NBseq = tables.shape[1]
    # a stacked pool's leading axis: squeezed out of the block, fixed at ``layer``
    lead_blk = () if layer is None else (None,)
    lead_ix = () if layer is None else (layer,)
    rep = N // Nkv
    SR = Sq * rep
    quantized = k_scale is not None
    scale = scale if scale is not None else 1.0 / (H**0.5)
    # g-major row layout: row g*SR + qi*rep + r holds (head g*rep+r, query qi)
    qf = (
        (q * jnp.asarray(scale, q.dtype))
        .reshape(B, Sq, Nkv, rep, H)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, Nkv * SR, H)
    )

    def ix_q(b, j, tbl, lens):
        return (b, 0, 0)

    def _live_j(b, j, lens):
        # dead-block DMA skip: blocks past the last attended position
        # (length + Sq - 1) re-fetch the LAST live block instead — Pallas
        # skips the copy when consecutive grid steps resolve to the same
        # block index, so per-token HBM traffic tracks the KV actually
        # attended, not the static table width. The kernel's pl.when
        # already skips their compute, and masking never reads them.
        return jnp.minimum(j, (lens[b] + (Sq - 1)) // BS)

    def ix_kv(b, j, tbl, lens):
        return (*lead_ix, tbl[b, _live_j(b, j, lens)], 0, 0, 0)

    def ix_scale(b, j, tbl, lens):
        return (*lead_ix, tbl[b, _live_j(b, j, lens)], 0, 0)

    in_specs = [
        pl.BlockSpec((1, Nkv * SR, H), ix_q),
        pl.BlockSpec((*lead_blk, 1, BS, Nkv, H), ix_kv),
        pl.BlockSpec((*lead_blk, 1, BS, Nkv, H), ix_kv),
    ]
    args = [qf, k_pool, v_pool]
    if quantized:
        in_specs += [
            pl.BlockSpec((*lead_blk, 1, BS, Nkv), ix_scale),
            pl.BlockSpec((*lead_blk, 1, BS, Nkv), ix_scale),
        ]
        args += [k_scale, v_scale]
    kernel = functools.partial(
        _paged_kernel,
        nkv=Nkv, rep=rep, sq=Sq, bs=BS, nbseq=NBseq,
        window=sliding_window, soft_cap=logits_soft_cap, quantized=quantized,
    )
    if not quantized:
        # keep one kernel body: bind the absent scale refs to None
        kernel = _without_scales(kernel)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NBseq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Nkv * SR, H), ix_q),
        scratch_shapes=[
            pltpu.VMEM((Nkv * SR, 1), jnp.float32),
            pltpu.VMEM((Nkv * SR, 1), jnp.float32),
            pltpu.VMEM((Nkv * SR, H), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Nkv * SR, H), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *args)
    return (
        out.reshape(B, Nkv, Sq, rep, H).transpose(0, 2, 1, 3, 4).reshape(B, Sq, N, H)
    )


def _without_scales(kernel):
    def wrapped(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr):
        return kernel(
            tables_ref, lengths_ref, q_ref, k_ref, v_ref, None, None,
            o_ref, m_scr, l_scr, acc_scr,
        )

    return wrapped
