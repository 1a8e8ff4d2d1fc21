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

Mechanics: grid ``(B, ceil(table width / P))`` — one slot's GROUP of ``P``
consecutive table entries a grid step (``pages_per_step``: 512 positions a
step, halved while the step's buffers overflow ``_VMEM_BUDGET``; 32 pages of
16 tokens). The per-slot block table and lengths ride as **scalar-prefetch**
operands; the K and V pools stay in HBM (``memory_space=pl.ANY``, the static
``layer`` of a stacked pool indexed in place) and the kernel copies the
slot's LIVE pages of the group, ``tables[b, g * P + p]``, into VMEM scratch
``[P, BS, Nkv, H]`` with its own ``make_async_copy`` each, started together
and then awaited in the same step (no copy is started a step ahead: at these
sizes the step count, not the copy, was what the trace showed). A page past
the slot's last attended position — or, under a window, before its first —
is not fetched and its positions are masked; a group with no live page does
nothing (``_live_pages``: one arithmetic for the kernel and for the host's
count of its steps, ``grid_steps``). The online softmax makes one update a
group over its ``P x BS`` positions; its accumulators live in VMEM scratch
across the group dimension, GQA is native (kv heads never
repeat-materialize), and queries may be a chunk (``Sq = k+1`` for the
speculative verify forward) with per-query causal masking against absolute
positions. Where the kernel cannot copy a page out of HBM itself — Mosaic
slices an HBM ref only in whole tiles of its minor two dims, so: an int8
pool (its scales ``[NB, BS, Nkv]`` have Nkv on the lanes), heads narrower
than 128 lanes left unpacked, an odd count of bfloat16 KV heads a TP shard —
``P`` is 1 and the same body takes the step's one page from a BlockSpec
``index_map`` through the tables, as every pool did before groups.

Int8 KV blocks: values are stored per-(token row, kv head) — scale
``amax / 127`` alongside the pool as ``[*, NB, BS, Nkv]`` fp32 (the
row-granular refinement of the per-block scale layouts in ``ops/fp8.py``
/ ``checkpoint/quant_io.py``: incremental single-token writes can never
force a whole-block rescale). ``quantize_kv_rows`` is the write-side
transform (quantize-on-scatter), the kernel (and the gather fallback)
dequantize on read; quantize∘dequantize is exactly idempotent, so chunked
prefill's rewrite-the-view scatter does not drift.

The gather path stays in serving/paged.py as the fallback where this
kernel cannot run (``serving.decode_kernel: gather``, or ``auto`` off the
TPU without interpret mode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
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

# per-grid-step VMEM budget for entry validation / sweep filtering — a
# group of pages of k+v (+scales) in scratch, their float32 working copies,
# and the whole query/output/accumulator set, with headroom under the
# compiler's own limit
_VMEM_BUDGET = 12 * 1024 * 1024
# positions a grid step aims to attend: pages a step = this over the block size
_STEP_POSITIONS = 512


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


# -- pages a grid step, feasibility (shared with the serving engine's grid
# -- counter) ------------------------------------------------------------------


def _up(n: int, to: int) -> int:
    return -(-n // to) * to


def _step_bytes(
    pages: int, block_size: int, nkv: int, head_dim: int, sq_rep: int,
    itemsize: int, quantized: bool,
) -> int:
    """VMEM one grid step holds at ``pages`` pages a step, as the chip tiles
    it: the minor two dims of every buffer round up to (sublanes, 128 lanes),
    32 bytes of sublanes a lane (8 float32 rows, 16 bfloat16, 32 int8)."""
    pos = pages * block_size
    lanes = _up(head_dim, 128)
    # the group's pages of k and v in the pool's dtype, and the float32
    # working copies the kernel body makes of them
    kv = 2 * pos * lanes * (_up(nkv, 32 // itemsize) * itemsize + _up(nkv, 8) * 4)
    if quantized:  # scales [pages, BS, Nkv] float32: Nkv on the lanes
        kv += 2 * 2 * pages * _up(block_size, 8) * _up(nkv, 128) * 4
    rows = _up(nkv * sq_rep, 8)
    qo = 2 * 2 * rows * lanes * 4  # query and output blocks, double-buffered
    acc = (2 * 128 + lanes) * rows * 4  # m, l (one lane used), acc
    scores = 4 * _up(sq_rep, 8) * _up(pos, 128) * 4  # s, p, mask, exp
    return kv + qo + acc + scores


def pages_per_step(
    block_size: int, nkv: int, head_dim: int, sq_rep: int, itemsize: int,
    quantized: bool = False,
) -> int:
    """Pool pages one grid step of the kernel fetches and attends, from what
    a call can see: ``_STEP_POSITIONS`` positions a step, halved while the
    step's buffers overflow ``_VMEM_BUDGET``. ``nkv`` / ``head_dim`` are the
    pool's own trailing dims (packed, and one TP shard's), ``sq_rep`` the
    query rows a KV head (``Sq * N / Nkv``).

    One page a step — the pipeline's own block fetch, no group — where the
    kernel cannot copy a page out of HBM itself: Mosaic slices an HBM ref
    only in whole tiles of its minor two dims (128 lanes x 4 bytes of
    sublanes), so a page ``[BS, Nkv, H]`` needs ``H`` a multiple of 128 and
    ``Nkv`` rows that fill 32-bit sublanes (any float32, even bfloat16, x4
    int8), and the int8 pool's scales ``[NB, BS, Nkv]`` (Nkv on the lanes)
    never qualify."""
    if quantized or head_dim % 128 or (nkv * itemsize) % 4:
        return 1
    pages = max(1, _STEP_POSITIONS // block_size)
    while pages > 1 and _step_bytes(
        pages, block_size, nkv, head_dim, sq_rep, itemsize, quantized
    ) > _VMEM_BUDGET:
        pages //= 2
    return pages


def _paged_budget_ok(
    block_size: int, nkv: int, head_dim: int, sq: int, rep: int,
    itemsize: int, quantized: bool = False,
) -> bool:
    pages = pages_per_step(block_size, nkv, head_dim, sq * rep, itemsize, quantized)
    return _step_bytes(
        pages, block_size, nkv, head_dim, sq * rep, itemsize, quantized
    ) <= _VMEM_BUDGET


def _live_pages(xp, length, j0, *, sq, bs, pages, nbseq, window):
    """Pages ``lo .. hi - 1`` of the group that starts at table column ``j0``
    which hold a position some query row attends (``lo >= hi``: none). Query
    rows sit at absolute positions length..length+sq-1 and attend pos <=
    their own position (the row at ``length`` was scattered into the pool
    BEFORE this attend, decode_ctx style), so pages entirely past
    length+sq-1 — and, under a window, entirely before length-window+1 —
    contribute nothing. One arithmetic for the kernel (``xp = jnp``, on
    scalars) and the host's count of its steps (``xp = np``, on arrays)."""
    last = xp.minimum((length + sq - 1) // bs, nbseq - 1)
    first = 0 if window is None else xp.maximum(length - window + 1, 0) // bs
    return xp.clip(first - j0, 0, pages), xp.clip(last - j0 + 1, 0, pages)


def grid_steps(
    lengths, table_width: int, *, pages: int, block_size: int, sq: int = 1,
    window: Optional[int] = None,
) -> tuple[int, int]:
    """(grid steps, steps that hold a live page) of one layer's call over
    slots of these ``lengths`` (host integers, every slot the call is handed:
    the kernel attends an inactive slot's row like any other)."""
    lengths = np.asarray(lengths, np.int64)
    j0 = np.arange(0, table_width, pages)
    lo, hi = _live_pages(
        np, lengths[:, None], j0[None, :], sq=sq, bs=block_size, pages=pages,
        nbseq=table_width, window=window,
    )
    return lengths.size * j0.size, int((lo < hi).sum())


# -- kernel ------------------------------------------------------------------


def _paged_kernel(
    tables_ref, lengths_ref,  # scalar prefetch
    q_ref, *refs,
    nkv, rep, sq, bs, pages, nbseq, layer, window, soft_cap, quantized,
):
    """One slot's group of ``pages`` table entries a grid step. ``pages >
    1``: ``refs`` = the K and V pools in HBM, the output, scratch for a
    group of pages of each and their DMA semaphores, then m / l / acc; the
    kernel copies the slot's live pages of the group itself. ``pages == 1``:
    ``refs`` = the step's K and V page (and int8 scales) as the pipeline
    fetched them, the output, m / l / acc."""
    grouped = pages > 1
    ks_buf = vs_buf = None
    if grouped:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr = refs
    elif quantized:
        k_buf, v_buf, ks_buf, vs_buf, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        k_buf, v_buf, o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    g = pl.program_id(1)
    sr = sq * rep
    npos = pages * bs
    length = lengths_ref[b]
    j0 = g * pages

    @pl.when(g == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if grouped:
            # pages a live group does not fetch keep what the scratch held;
            # their positions are masked (p = 0), and 0 x a stale finite
            # value is 0, but 0 x the NaN of a never-written buffer is not
            v_buf[...] = jnp.zeros_like(v_buf)

    # dead-page skipping: only the slot's live pages of this group are
    # fetched and attended, and a group with none does nothing
    lo, hi = _live_pages(
        jnp, length, j0, sq=sq, bs=bs, pages=pages, nbseq=nbseq, window=window
    )

    def fetch_live_pages():
        def copies(p):
            # table entry j0 + p of slot b -> page p of the scratch
            blk = tables_ref[b, j0 + p]
            at = (blk,) if layer is None else (layer, blk)
            return (
                pltpu.make_async_copy(k_hbm.at[at], k_buf.at[p], sems.at[0]),
                pltpu.make_async_copy(v_hbm.at[at], v_buf.at[p], sems.at[1]),
            )

        def each_live_page(do):
            def body(p, carry):
                for copy in copies(p):
                    do(copy)
                return carry

            jax.lax.fori_loop(lo, hi, body, 0)

        # all in flight together, then awaited: one round trip a step
        each_live_page(lambda copy: copy.start())
        each_live_page(lambda copy: copy.wait())

    @pl.when(lo < hi)
    def _():
        if grouped:
            fetch_live_pages()
        k = k_buf[...].astype(jnp.float32)  # [P, BS, Nkv, H]
        v = v_buf[...].astype(jnp.float32)
        if quantized:
            k = k * ks_buf[...][..., None]
            v = v * vs_buf[...][..., None]
        k = k.reshape(npos, nkv, k.shape[-1])
        v = v.reshape(npos, nkv, v.shape[-1])
        pos = j0 * bs + jax.lax.broadcasted_iota(jnp.int32, (1, npos), 1)
        # per-query absolute position: q rows are g-major then (qi, rep)
        qi = jax.lax.broadcasted_iota(jnp.int32, (sr, 1), 0) // rep
        q_abs = length + qi  # [SR, 1]
        mask = pos <= q_abs  # [SR, P*BS]
        if nbseq % pages:  # the last group's columns past the table's width
            mask = mask & (pos < nbseq * bs)
        if window is not None:
            mask = mask & (q_abs - pos < window)
        for h in range(nkv):
            qh = q_ref[0, h * sr : (h + 1) * sr, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                qh, k[:, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [SR, P*BS]
            if soft_cap is not None:
                s = soft_cap * jnp.tanh(s / soft_cap)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[h * sr : (h + 1) * sr]
            l_prev = l_scr[h * sr : (h + 1) * sr]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            m_scr[h * sr : (h + 1) * sr] = m_new
            l_scr[h * sr : (h + 1) * sr] = l_prev * corr + p.sum(
                axis=1, keepdims=True
            )
            acc_scr[h * sr : (h + 1) * sr] = acc_scr[
                h * sr : (h + 1) * sr
            ] * corr + jax.lax.dot_general(
                p, v[:, h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(g == pl.num_programs(1) - 1)
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
    rep = N // Nkv
    SR = Sq * rep
    quantized = k_scale is not None
    pages = pages_per_step(BS, Nkv, H, SR, k_pool.dtype.itemsize, quantized)
    scale = scale if scale is not None else 1.0 / (H**0.5)
    # g-major row layout: row g*SR + qi*rep + r holds (head g*rep+r, query qi)
    qf = (
        (q * jnp.asarray(scale, q.dtype))
        .reshape(B, Sq, Nkv, rep, H)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, Nkv * SR, H)
    )

    def ix_q(b, g, tbl, lens):
        return (b, 0, 0)

    in_specs = [pl.BlockSpec((1, Nkv * SR, H), ix_q)]
    args = [qf, k_pool, v_pool]
    if pages > 1:
        # the pools never enter the pipeline: they stay in HBM and the
        # kernel copies a slot's live pages of a group into its own scratch
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scratch = [
            pltpu.VMEM((pages, BS, Nkv, H), k_pool.dtype),
            pltpu.VMEM((pages, BS, Nkv, H), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    else:
        # a stacked pool's leading axis: squeezed out of the block, fixed at ``layer``
        lead_blk = () if layer is None else (None,)
        lead_ix = () if layer is None else (layer,)

        def _live_j(b, j, lens):
            # dead-page DMA skip: pages past the last attended position
            # (length + Sq - 1) re-fetch the LAST live page instead — the
            # pipeline skips the copy when consecutive grid steps resolve to
            # the same block index. The kernel already skips their compute,
            # and masking never reads them.
            return jnp.minimum(j, (lens[b] + (Sq - 1)) // BS)

        def ix_kv(b, j, tbl, lens):
            return (*lead_ix, tbl[b, _live_j(b, j, lens)], 0, 0, 0)

        def ix_scale(b, j, tbl, lens):
            return (*lead_ix, tbl[b, _live_j(b, j, lens)], 0, 0)

        in_specs += [pl.BlockSpec((*lead_blk, 1, BS, Nkv, H), ix_kv)] * 2
        if quantized:
            in_specs += [pl.BlockSpec((*lead_blk, 1, BS, Nkv), ix_scale)] * 2
            args += [k_scale, v_scale]
        scratch = []
    kernel = functools.partial(
        _paged_kernel,
        nkv=Nkv, rep=rep, sq=Sq, bs=BS, pages=pages, nbseq=NBseq, layer=layer,
        window=sliding_window, soft_cap=logits_soft_cap, quantized=quantized,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pl.cdiv(NBseq, pages)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Nkv * SR, H), ix_q),
        scratch_shapes=[
            *scratch,
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
