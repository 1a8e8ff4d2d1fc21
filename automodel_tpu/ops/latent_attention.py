"""Attention over a paged cache of LATENT rows (multi-head latent attention).

A latent layer keeps ONE row a token (``generation/kv_cache.latent_layer``):
``[c | k_rope]``, the normed compression (``rank`` wide, 512) beside the
rotated rotary key every head shares (64), in a pool ``[L, NB, BS, W]`` whose rows are
padded with zeros to whole lanes (576 -> 640: the chip tiles the minor dim to
128 lanes whatever the shape says, and the kernel may copy a page out of HBM
only in whole tiles; ``generation/kv_cache.lane_padded``). Per
head ``j`` the model's ``W_kvb`` expands ``c`` into ``[k_nope_j | v_j]``;
attention over the cache never needs the expansion stored:

- **decode** (``absorbed_attend``: one query a slot): ``W_kvb``'s key half is folded into the query, ``q~_j =
  [W_uk,j q_nope_j | q_rope_j]``, so every head scores against the SAME row,
  ``score = scale x q~_j . row``; the values are the compression itself, ``u_j
  = sum p c``, and ``W_kvb``'s value half expands the 512-wide result after
  the softmax. The Pallas kernel fetches a slot's live pages once for all the
  heads (grid ``(B, ceil(table width / P))``, the tables and lengths as
  scalar prefetch, the pool in HBM, ``P`` pages copied a step by the kernel's
  own DMAs, a dead group skipped: the arithmetic of ``ops/paged_attention``),
  feeds the rows to the MXU in the pool's own type and keeps the online
  softmax in float32. ``gather=True`` is the XLA path behind
  ``serving.decode_kernel: gather``: the tables' rows gathered into a view,
  the same absorbed sums in ``jnp``.
- **a prompt's chunk** (``chunk_attend``: hundreds of queries of ONE
  sequence against the prefix the pool already holds and the chunk's own
  rows, just written): the rows ``[0, start + S)`` are read back through the
  table a block of keys at a time and EXPANDED through ``W_kvb`` (512 ->
  heads x (128 + 128)), an online softmax over the blocks. Expanded, a key
  costs ``N (192 + 128)`` products a query and ``N x 256 x 512`` once a
  chunk; absorbed it costs ``N (576 + 512)`` a query: at 512 queries a chunk
  the expansion is under a third of the absorbed sums' extra work. The loop
  runs to the last live block (a traced trip count), so a chunk of 512 at
  position 0 costs two blocks of 256 keys and one at 7,680 thirty-two. On a
  v5e the block's float32 scores, written to HBM between XLA's fusions, bound
  both forms (not the MXU), and smaller blocks are faster: a layer's four
  chunks at 0 / 1,536 / 3,840 / 7,168 take 16.0 ms at 512 keys a block, 11.4
  at 256, 12.4 at 128 (PERF.md section 6, PR 47).

Scopes (under the model's ``attn/mla``): ``mla_q_absorb``,
``mla_latent_attn`` (the decode kernel), ``mla_v_expand``;
``mla_prefix_expand``, ``mla_chunk_attn``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ``grid_steps``: the host's count of this kernel's grid (its body shares
# ``_live_pages`` with the K/V kernel, so the arithmetic is one)
from automodel_tpu.ops.paged_attention import NEG_INF, _live_pages, _up, grid_steps  # noqa: F401

# per-grid-step VMEM budget (the kernel's own scratch and blocks, with headroom
# under the compiler's limit) and the positions a grid step aims to attend
_VMEM_BUDGET = 12 * 1024 * 1024
_STEP_POSITIONS = 1024
# keys a block of the chunk path's online softmax reads back and expands
# (timed on the chip at 128 / 256 / 512 / 1024 / 2048: the module docstring)
_CHUNK_KV_BLOCK = 256


def _step_bytes(pages: int, block_size: int, width: int, rank: int, heads: int,
                itemsize: int) -> int:
    """VMEM one grid step holds at ``pages`` pages a step, as the chip tiles
    it (minor dim up to 128 lanes, second-minor to 32 bytes of sublanes)."""
    pos = pages * block_size
    rows = _up(heads, 8)
    buf = pages * _up(block_size, 32 // itemsize) * _up(width, 128) * itemsize
    q = 2 * _up(heads, 32 // itemsize) * _up(width, 128) * itemsize  # double-buffered
    out = 2 * rows * _up(rank, 128) * 4
    acc = (2 * 128 + _up(rank, 128)) * rows * 4  # m, l (one lane used), acc
    scores = 4 * rows * _up(pos, 128) * 4  # s, p, mask, exp
    return buf + q + out + acc + scores


def pages_per_step(block_size: int, width: int, rank: int, heads: int,
                   itemsize: int) -> int:
    """Pool pages one grid step of the absorbed decode kernel fetches and
    attends: ``_STEP_POSITIONS`` positions a step, halved while the step's
    buffers overflow ``_VMEM_BUDGET``. ``heads``: the query heads, one query
    row each. A page is one whole ``[BS, W]`` tile group of the pool, so the
    kernel can always copy it out of HBM itself."""
    pages = max(1, _STEP_POSITIONS // block_size)
    while pages > 1 and _step_bytes(
        pages, block_size, width, rank, heads, itemsize
    ) > _VMEM_BUDGET:
        pages //= 2
    return pages


def context_rows(lengths) -> int:
    """Cached rows one layer's decode call has to read over slots of these
    ``lengths`` (host integers; every slot the call is handed): positions
    ``0 .. length`` each, the new token's own row included."""
    return int((np.asarray(lengths, np.int64) + 1).sum())


# -- the absorbed decode kernel -------------------------------------------------


def _absorbed_kernel(
    tables_ref, lengths_ref,  # scalar prefetch
    q_ref, pool_hbm, o_ref, buf, sem, m_scr, l_scr, acc_scr,
    *, bs, pages, nbseq, layer, rank,
):
    """One slot's group of ``pages`` table entries a grid step: copy its live
    pages into ``buf`` [P, BS, W], score every head's query row against the
    P x BS rows, accumulate ``p @ c`` [heads, rank]."""
    b = pl.program_id(0)
    g = pl.program_id(1)
    npos = pages * bs
    length = lengths_ref[b]
    j0 = g * pages

    @pl.when(g == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # pages a live group does not fetch keep what the scratch held: their
        # positions are masked (p = 0), and 0 x a stale finite value is 0, but
        # 0 x the NaN of a never-written buffer is not
        buf[...] = jnp.zeros_like(buf)

    lo, hi = _live_pages(
        jnp, length, j0, sq=1, bs=bs, pages=pages, nbseq=nbseq, window=None
    )

    @pl.when(lo < hi)
    def _():
        def copy(p):
            return pltpu.make_async_copy(
                pool_hbm.at[layer, tables_ref[b, j0 + p]], buf.at[p], sem.at[0]
            )

        def each_live_page(do):
            def body(p, carry):
                do(copy(p))
                return carry

            jax.lax.fori_loop(lo, hi, body, 0)

        # all in flight together, then awaited: one round trip a step
        each_live_page(lambda c: c.start())
        each_live_page(lambda c: c.wait())

        rows = buf[...].reshape(npos, buf.shape[-1])  # the pool's own type
        q = q_ref[0]  # [heads, W], scaled
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [N, P*BS]
        pos = j0 * bs + jax.lax.broadcasted_iota(jnp.int32, (1, npos), 1)
        mask = pos <= length
        if nbseq % pages:  # the last group's columns past the table's width
            mask = mask & (pos < nbseq * bs)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(g == pl.num_programs(1) - 1)
    def _():
        l = l_scr[...]
        o_ref[0] = jnp.where(l > 0, acc_scr[...] / jnp.maximum(l, 1e-30), 0.0).astype(
            o_ref.dtype
        )


@functools.partial(jax.jit, static_argnames=("layer", "rank", "interpret"))
def _absorbed_pallas(q, pool, tables, lengths, *, layer: int, rank: int,
                     interpret: bool = False):
    """q ``[B, N, W]`` (absorbed, scaled) against the stacked latent pool
    ``[L, NB, BS, W]`` through ``tables`` [B, NBseq]; slot ``b``'s query
    attends positions ``<= lengths[b]`` (already written). -> ``u`` [B, N,
    rank] = softmax(q . row) @ row[:rank]."""
    B, N, W = q.shape
    BS = pool.shape[2]
    NBseq = tables.shape[1]
    pages = pages_per_step(BS, W, rank, N, pool.dtype.itemsize)

    def ix_q(b, g, tbl, lens):
        return (b, 0, 0)

    kernel = functools.partial(
        _absorbed_kernel, bs=BS, pages=pages, nbseq=NBseq, layer=layer, rank=rank,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pl.cdiv(NBseq, pages)),
        in_specs=[
            pl.BlockSpec((1, N, W), ix_q),
            pl.BlockSpec(memory_space=pl.ANY),  # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, N, rank), ix_q),
        scratch_shapes=[
            pltpu.VMEM((pages, BS, W), pool.dtype),
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.VMEM((N, 1), jnp.float32),
            pltpu.VMEM((N, 1), jnp.float32),
            pltpu.VMEM((N, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="latent_paged_attention",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q.astype(pool.dtype), pool)


def _absorbed_gather(q, pool, tables, lengths, *, layer: int, rank: int):
    """The same sums through an XLA gather of every table's rows, in float32
    (the fallback where the kernel cannot run; the CPU has no bf16 x bf16 ->
    f32 product of these shapes)."""
    B, N, W = q.shape
    BS = pool.shape[2]
    rows = pool[layer, tables].reshape(B, tables.shape[1] * BS, W).astype(jnp.float32)
    s = jnp.einsum("bnw,bkw->bnk", q.astype(jnp.float32), rows)
    pos = jnp.arange(rows.shape[1], dtype=jnp.int32)
    mask = pos[None, :] <= lengths.astype(jnp.int32)[:, None]  # [B, K]
    p = jax.nn.softmax(jnp.where(mask[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bnk,bkr->bnr", p, rows[..., :rank]).astype(q.dtype)


def absorbed_attend(
    q_nope, q_rope, pool, w_kvb, tables, lengths, *, layer: int, scale: float,
    v_dim: int, interpret: bool = False, gather: bool = False,
):
    """Decode attention of one latent layer, absorbed: ONE query a slot.
    ``q_nope`` [B, 1, N, nope], ``q_rope`` [B, 1, N, r] (rotated); ``pool`` the
    stacked latent pool; ``w_kvb`` [rank, N x (nope + v)] (the model's
    ``kv_b_proj``). -> [B, 1, N, v_dim]."""
    B, _, N, nope = q_nope.shape
    rank = w_kvb.shape[0]
    w = w_kvb.astype(q_nope.dtype).reshape(rank, N, nope + v_dim)
    with jax.named_scope("mla_q_absorb"):
        q_lat = jnp.einsum("bnd,rnd->bnr", q_nope[:, 0], w[..., :nope])
        q = jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1) * jnp.asarray(scale, q_nope.dtype)
        # zeros against the pool rows' padding lanes
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pool.shape[-1] - q.shape[-1])))
    with jax.named_scope("mla_latent_attn"):
        if gather:
            u = _absorbed_gather(q, pool, tables, lengths, layer=layer, rank=rank)
        else:
            u = _absorbed_pallas(q, pool, tables, lengths, layer=layer, rank=rank,
                                 interpret=interpret)
    with jax.named_scope("mla_v_expand"):
        return jnp.einsum("bnr,rnv->bnv", u, w[..., nope:])[:, None]


# -- a prompt's chunk -------------------------------------------------------------


def chunk_attend(
    q_nope, q_rope, pool, w_kvb, tables, start, *, layer: int, scale: float,
    v_dim: int, kv_block: int = _CHUNK_KV_BLOCK,
):
    """A chunk's queries against the rows ``[0, start + S)`` its sequence
    holds in the pool (the chunk's own rows included: written before this
    call), read back a block of ``kv_block`` keys at a time and expanded
    through ``w_kvb``. ``q_nope`` [B, S, N, nope], ``q_rope`` [B, S, N, r];
    ``tables`` [B, NBseq]; ``start`` [B] the chunk's first position. Query
    ``s`` attends positions ``<= start + s``. -> [B, S, N, v_dim]."""
    B, S, N, nope = q_nope.shape
    rank = w_kvb.shape[0]
    BS = pool.shape[2]
    NBseq = tables.shape[1]
    nb = max(1, min(kv_block // BS, NBseq))  # table entries a block of keys
    width = -(-NBseq // nb) * nb
    tables = jnp.pad(tables.astype(jnp.int32), ((0, 0), (0, width - NBseq)))  # scratch block 0
    K = nb * BS
    dt = q_nope.dtype
    w = w_kvb.astype(dt)
    start = start.astype(jnp.int32)
    q_abs = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B, S]
    qn = q_nope * jnp.asarray(scale, dt)
    qr = q_rope * jnp.asarray(scale, dt)

    def block(j, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(tables, j * nb, nb, axis=1)  # [B, nb]
        with jax.named_scope("mla_prefix_expand"):
            # the layer indexed WITH the blocks: a `pool[layer]` of its own is a
            # copy of the layer's whole pool (420 MB at the cell's shapes) a call
            rows = pool[layer, ids].reshape(B, K, -1).astype(dt)
            kv = (rows[..., :rank] @ w).reshape(B, K, N, nope + v_dim)
        with jax.named_scope("mla_chunk_attn"):
            s = jnp.einsum("bsnd,bknd->bnsk", qn, kv[..., :nope],
                           preferred_element_type=jnp.float32)
            s = s + jnp.einsum("bsnr,bkr->bnsk", qr, rows[..., rank:rank + qr.shape[-1]],
                               preferred_element_type=jnp.float32)
            pos = j * K + jnp.arange(K, dtype=jnp.int32)
            mask = (pos[None, None, :] <= q_abs[:, :, None])[:, None]  # [B, 1, S, K]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1, keepdims=True)
            acc = acc * corr + jnp.einsum(
                "bnsk,bknv->bnsv", p.astype(dt), kv[..., nope:],
                preferred_element_type=jnp.float32,
            )
        return m_new, l, acc

    n_live = jnp.minimum((jnp.max(start) + S + K - 1) // K, width // nb)
    init = (
        jnp.full((B, N, S, 1), NEG_INF, jnp.float32),
        jnp.zeros((B, N, S, 1), jnp.float32),
        jnp.zeros((B, N, S, v_dim), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, n_live, block, init)
    out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(0, 2, 1, 3).astype(dt)
