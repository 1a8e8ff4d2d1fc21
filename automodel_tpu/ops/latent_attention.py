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
  table and EXPANDED through ``W_kvb`` (512 -> heads x (128 + 128)).
  Expanded, a key costs ``N (192 + 128)`` products a query and ``N x 256 x
  512`` once a chunk; absorbed it costs ``N (576 + 512)`` a query: at 512
  queries a chunk the expansion is under a third of the absorbed sums' extra
  work. Two executions of that one algorithm, with the same rounding points
  (operands in the compute type, scores and softmax in float32, ``p`` rounded
  to the compute type before the value product, a float32 accumulator, one
  division at the end):

  * **the kernel** (``latent_chunk_attention``; where ``chunk_blocks`` can
    tile the operands: ``nope`` and ``v`` whole 128-lane tiles, ``rope`` a
    multiple of 64, the queries whole sublanes). XLA expands the LIVE prefix
    once a layer (scope ``mla_prefix_expand``: a loop of ``_CHUNK_KEYS`` keys
    a trip to the last live block, a traced trip count; each trip gathers its
    table entries' rows, multiplies by ``W_kvb`` and writes the product
    straight into its place) into a buffer the kernel reads: flat, ``[B, L, N
    x (nope + v)]``, a head's 256 columns contiguous as the product leaves
    them (no ``[K, N, d]`` array ever: ``ops/delta_rule.py`` on why), beside
    the rotary key rows every head shares, ``[B, L, rope]``; ``L`` the table's
    positions in whole blocks (8,704 at the serve cell's table of 544
    entries: 285 MB a call, live inside one layer's attention only, never
    initialised: ``jax.lax.empty``, and a block past the last live one is
    neither written nor read). The kernel (scope ``mla_chunk_attn``): grid
    ``(B, N / _CHUNK_HEADS, L / _CHUNK_KEYS)``, key blocks innermost,
    ``start`` as scalar prefetch; a head group's queries ``[S, hb x 128]`` and
    ``[S, hb x 64]`` resident across the key blocks; per head ``m``, ``l``
    ``[S, 128]`` and ``acc`` ``[S, v]`` float32 in VMEM scratch; a grid step
    walks its heads (a rolled loop, two heads a trip so that the trip's rotary
    query columns start on a lane tile) and per head makes the two score
    products (``q_nope . k_nope`` + ``q_rope . k_rope``), the mask (only in a
    block that reaches past ``start``: the others hold no pair it removes), the
    exponent, ``p @ v``; the running max lies replicated over 128 lanes a query
    and the running sum in 128 partial sums that meet at the end, so a block
    costs one reduction across lanes a query. The float32 scores ``[S,
    keys]`` of a head never leave VMEM. A step past a sequence's last live
    block repeats that block in its ``index_map`` (nothing is fetched) and
    runs nothing
    (``_live_key_blocks``: ``_live_pages`` over the table as one group, the
    kernel's, its index maps' and the host's count ``chunk_grid_steps``, which
    the engine puts on the chunk's ``serve.prefill_dispatch`` span).
  * **the loop** (``gather=True``: ``serving.decode_kernel: gather``, and
    shapes the kernel cannot tile): ``lax.fori_loop`` over blocks of
    ``_CHUNK_KV_BLOCK`` keys, each read back, expanded and attended by XLA
    einsums. On a v5e a block's float32 scores ``[N, S, K]`` (33.5 MB at 256
    keys) are written to HBM between XLA's fusions and bound it (not the
    MXU), so SMALLER blocks are faster: a layer's four chunks at 0 / 1,536 /
    3,840 / 7,168 take 16.0 ms at 512 keys a block, 11.4 at 256, 12.4 at 128
    (PERF.md section 6, PR 47).

  Timed on a v5e at the serve cell's shapes (512 queries, 64 heads of 128 +
  64 / 128, bfloat16, a table over 8,192 positions; my chip runs, PR 48; ms a
  layer at ``start`` 0 / 1,536 / 3,840 / 7,168, the kernel alone from an
  expanded buffer, then ``chunk_attend`` whole with its expansion):
  **512 keys x 8 heads, the head loop rolled: 0.240 / 0.430 / 0.791 / 1.224,
  whole 0.266 / 0.656 / 1.317 / 2.108** (at 3,840: 91 GFLOP of the law's
  products in 0.79 ms, 58 % of the MXU's peak, where 12 passes of the array a
  head-block stand for the law's 10: the 64-wide rotary product costs a
  whole pass; a 512-key block 0.072 ms, 91 % of those passes' time); the loop
  unrolled 0.256 / 0.416 / 0.763 / 1.185 (3 s -> 6 s to compile an
  instance, compile-only: not taken); 1,024 x 8: 0.295 / 0.445 / 0.883 / 1.319, whole 0.409
  / 0.675 / 1.474 / 2.268 (a partial last block is attended whole); 1,024 x
  16: 0.297 / 0.433 / 0.877 / 1.314; 2,048 x 8: 0.491 / 0.492 / 1.091 / 1.392.
  The running max and sum are kept 128 lanes wide a query (``wide``): as
  ``[S, 1]`` columns, two reductions across lanes and their broadcasts a
  query and block, the same kernel read 512 x 8: 0.268 / 0.649 / 1.298 /
  2.067; 512 x 4: 0.289 / 0.676 / 1.320 / 2.096; 512 x 16: 0.259 / 0.642 /
  1.283 / 2.055; 256 x 8: 0.352 / 0.962 / 1.884 / 3.212; 1,024 x 8: 0.313 /
  0.480 / 0.976 / 1.468. The loop, whole, same call: 0.323 / 0.873 / 1.694 /
  2.883 at 256 keys a trip, 0.496 / 1.510 / 3.207 / 5.222 at 512.

Scopes (under the model's ``attn/mla``): ``mla_q_absorb``,
``mla_latent_attn`` (the decode kernel), ``mla_v_expand``;
``mla_prefix_expand``, ``mla_chunk_attn`` (the chunk kernel or the loop).
"""

from __future__ import annotations

import functools
import math

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
# the chunk kernel: keys a grid step attends, heads it walks (timed on the chip
# at 256 / 512 / 1024 / 2048 keys and 4 / 8 / 16 heads: the module docstring), and what
# it asks Mosaic for (a v5e core has 128 MiB; the default scoped limit is 16)
_CHUNK_KEYS = 512
_CHUNK_HEADS = 8
_VMEM_LIMIT = 64 * 1024 * 1024
# the LOOP path's only: keys a trip of its online softmax reads back, expands
# and attends (timed on the chip at 128 / 256 / 512 / 1024 / 2048: the module
# docstring)
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


def _live_key_blocks(xp, start, *, sq, keys, blocks):
    """Key blocks ``0 .. n - 1`` of ``keys`` positions that hold a position a
    chunk of ``sq`` queries at ``start`` attends: ``_live_pages`` with the whole
    table as one group of ``blocks`` pages of ``keys`` positions. One arithmetic
    for the kernel, its ``index_map`` (``xp = jnp``) and the host's count
    (``xp = np``)."""
    return _live_pages(xp, start, 0, sq=sq, bs=keys, pages=blocks, nbseq=blocks,
                       window=None)[1]


def chunk_blocks(
    sq: int, heads: int, nope: int, rope: int, v_dim: int, table_width: int,
    block_size: int, *, interpret: bool = False, kv_block: int = _CHUNK_KEYS,
):
    """(heads a grid step, keys a block, key blocks) of the chunk kernel over a
    table of ``table_width`` entries, or None where it cannot tile the operands
    (the chunk then takes the loop): a head's ``nope`` and ``v_dim`` columns
    are whole 128-lane tiles of the flat operands, ``rope`` half a tile or
    whole ones, a group's rotary query columns whole tiles, the queries whole
    sublanes, a block's keys whole lanes of the scores. Interpreted, any shape
    goes."""
    pages = max(1, min(kv_block // block_size, table_width))
    keys = pages * block_size
    if not interpret and (nope % 128 or v_dim % 128 or rope % 64 or sq % 8 or keys % 128):
        return None
    for hb in range(min(_CHUNK_HEADS, heads), 0, -1):
        if heads % hb == 0 and (interpret or not hb * rope % 128):
            return hb, keys, -(-table_width // pages)
    return None


def chunk_grid_steps(starts, sq: int, heads: int, blocks) -> tuple[int, int]:
    """(grid steps, steps that attend a live key block) of one layer's chunk
    kernel over sequences whose chunks of ``sq`` queries start at ``starts``
    (host integers); ``blocks`` = ``chunk_blocks(...)``."""
    hb, keys, nkb = blocks
    starts = np.asarray(starts, np.int64).reshape(-1)
    live = _live_key_blocks(np, starts, sq=sq, keys=keys, blocks=nkb)
    return starts.size * (heads // hb) * nkb, int(heads // hb * live.sum())


def _chunk_kernel(
    start_ref,  # scalar prefetch
    qn_ref, qr_ref, kv_ref, kr_ref, o_ref, m_scr, l_scr, acc_scr,
    *, heads, nope, rope, v_dim, keys, blocks,
):
    """One sequence's group of ``heads`` heads against one block of ``keys``
    expanded keys a grid step: per head the two score products, the online
    softmax in float32, ``p @ v``; the scores never leave VMEM."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    sq = qn_ref.shape[1]
    start = start_ref[b]
    n_live = _live_key_blocks(jnp, start, sq=sq, keys=keys, blocks=blocks)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    nt = (((1,), (1,)), ((), ()))
    # heads a trip of the head loop walks: the rotary columns of so many heads
    # fill whole lanes, so the trip's slice of them starts on a tile
    unit = max(1, 128 // rope)
    if heads % unit:  # interpreted shapes only: ``chunk_blocks`` groups whole tiles
        unit = 1

    def lanes(ref, first, width):
        """``ref[0, :, first : first + width]`` at a traced ``first``."""
        return ref[0, :, pl.ds(pl.multiple_of(first, 128) if width % 128 == 0 else first, width)]

    # the running max and sum a head as ``wide`` lanes a query: the max
    # replicated over them, the sum in ``wide`` partial sums that meet once, at
    # the end, so a block costs one reduction across lanes a query and not two
    wide = m_scr.shape[-1]

    def spread(x, n):
        """``[sq, wide]`` over ``n`` lanes."""
        return pltpu.repeat(x, n // wide, axis=1)

    def attend(masked: bool):
        kr = kr_ref[0]  # [keys, rope]: the rotary rows every head shares
        if masked:
            pos = j * keys + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
            mask = pos <= start + jax.lax.broadcasted_iota(jnp.int32, (sq, 1), 0)

        def trip(i, carry):
            qr = lanes(qr_ref, i * unit * rope, unit * rope)
            for u in range(unit):
                h = i * unit + u
                at = h * (nope + v_dim)
                s = jax.lax.dot_general(
                    lanes(qn_ref, h * nope, nope), lanes(kv_ref, at, nope), nt,
                    preferred_element_type=jnp.float32,
                ) + jax.lax.dot_general(
                    qr[:, u * rope:(u + 1) * rope], kr, nt, preferred_element_type=jnp.float32,
                )  # [sq, keys]
                if masked:
                    s = jnp.where(mask, s, NEG_INF)
                m_prev = m_scr[h]
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                # key 0 is in block 0 and every query attends it: ``m_new`` is a
                # real score from the first block on, so a masked pair's p is 0
                p = jnp.exp(s - spread(m_new, keys))
                corr = jnp.exp(m_prev - m_new)
                m_scr[h] = m_new
                l_scr[h] = l_scr[h] * corr + functools.reduce(
                    jnp.add, [p[:, c:c + wide] for c in range(0, keys, wide)])
                v = lanes(kv_ref, at + nope, v_dim)
                acc_scr[h] = acc_scr[h] * spread(corr, v_dim) + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            return carry

        jax.lax.fori_loop(0, heads // unit, trip, 0)

    # only a block that reaches past the chunk's first position holds a pair
    # the causal mask removes
    diagonal = (j + 1) * keys > start + 1

    @pl.when((j < n_live) & diagonal)
    def _():
        attend(True)

    @pl.when((j < n_live) & jnp.logical_not(diagonal))
    def _():
        attend(False)

    @pl.when(j == n_live - 1)
    def _():
        for h in range(heads):
            l = l_scr[h].sum(axis=1, keepdims=True)
            o_ref[0, :, h * v_dim:(h + 1) * v_dim] = (
                acc_scr[h] / jnp.maximum(l, 1e-30)
            ).astype(o_ref.dtype)


# jitted so that a program's layers share ONE trace of the kernel (its body is
# most of what tracing a chunk program costs: setup_s)
@functools.partial(
    jax.jit, static_argnames=("heads", "blocks", "nope", "rope", "v_dim", "interpret"))
def _chunk_pallas(qn, qr, kv, kr, start, *, heads, blocks, nope, rope, v_dim, interpret):
    """Flat queries ``[B, S, N x nope]`` / ``[B, S, N x rope]`` (scaled) against
    the expanded prefix ``kv`` [B, L, N x (nope + v)] and its rotary rows
    ``kr`` [B, L, rope]; query ``s`` of sequence ``b`` attends positions ``<=
    start[b] + s``. -> [B, S, N x v]."""
    B, S, _ = qn.shape
    hb, keys, nkb = blocks
    wide = math.gcd(128, keys, v_dim)  # lanes of the running max and sum: 128 on a chip

    def ix_q(b, g, j, start):
        return (b, 0, g)

    def live_block(b, j, start):
        # a dead step repeats the last live block: nothing is fetched for it
        return jnp.minimum(
            j, _live_key_blocks(jnp, start[b], sq=S, keys=keys, blocks=nkb) - 1)

    kernel = functools.partial(
        _chunk_kernel, heads=hb, nope=nope, rope=rope, v_dim=v_dim, keys=keys, blocks=nkb,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, heads // hb, nkb),
        in_specs=[
            pl.BlockSpec((1, S, hb * nope), ix_q),
            pl.BlockSpec((1, S, hb * rope), ix_q),
            pl.BlockSpec((1, keys, hb * (nope + v_dim)),
                         lambda b, g, j, start: (b, live_block(b, j, start), g)),
            pl.BlockSpec((1, keys, rope),
                         lambda b, g, j, start: (b, live_block(b, j, start), 0)),
        ],
        out_specs=pl.BlockSpec((1, S, hb * v_dim), ix_q),
        scratch_shapes=[
            pltpu.VMEM((hb, S, wide), jnp.float32),
            pltpu.VMEM((hb, S, wide), jnp.float32),
            pltpu.VMEM((hb, S, v_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, heads * v_dim), qn.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="latent_chunk_attention",
    )(start, qn, qr, kv, kr)


def _block_rows(pool, layer, tables, j, pages):
    """The rows of table entries ``[j * pages, (j + 1) * pages)`` of every
    sequence, ``[B, pages * BS, W]``."""
    ids = jax.lax.dynamic_slice_in_dim(tables, j * pages, pages, axis=1)
    # the layer indexed WITH the blocks: a `pool[layer]` of its own is a copy of
    # the layer's whole pool (420 MB at the cell's shapes) a call
    return pool[layer, ids].reshape(tables.shape[0], pages * pool.shape[2], -1)


def _chunk_kernel_attend(q_nope, q_rope, pool, w, tables, start, *, layer, scale, v_dim,
                         blocks, interpret):
    """``chunk_attend`` through the kernel: the live prefix expanded once into
    a flat buffer (XLA), then one ``pallas_call`` over it."""
    B, S, N, nope = q_nope.shape
    rope = q_rope.shape[-1]
    rank = w.shape[0]
    BS = pool.shape[2]
    hb, keys, nkb = blocks
    pages = keys // BS
    dt = q_nope.dtype
    tables = jnp.pad(tables, ((0, 0), (0, nkb * pages - tables.shape[1])))  # scratch block 0
    with jax.named_scope("mla_prefix_expand"):
        def expand(j, bufs):
            kv, kr = bufs
            rows = _block_rows(pool, layer, tables, j, pages).astype(dt)
            kv = jax.lax.dynamic_update_slice_in_dim(kv, rows[..., :rank] @ w, j * keys, axis=1)
            kr = jax.lax.dynamic_update_slice_in_dim(
                kr, rows[..., rank:rank + rope], j * keys, axis=1)
            return kv, kr

        # only the live blocks are written, and only they are read: the rest of
        # the buffer is never initialised (on a TPU; zeros elsewhere)
        n_live = jnp.max(_live_key_blocks(jnp, start, sq=S, keys=keys, blocks=nkb))
        kv, kr = jax.lax.fori_loop(0, n_live, expand, (
            jax.lax.empty((B, nkb * keys, N * (nope + v_dim)), dt),
            jax.lax.empty((B, nkb * keys, rope), dt),
        ))
    with jax.named_scope("mla_chunk_attn"):
        sc = jnp.asarray(scale, dt)
        out = _chunk_pallas(
            (q_nope * sc).reshape(B, S, N * nope), (q_rope * sc).reshape(B, S, N * rope),
            kv, kr, start, heads=N, blocks=blocks, nope=nope, rope=rope, v_dim=v_dim,
            interpret=interpret,
        )
    return out.reshape(B, S, N, v_dim)


def chunk_attend(
    q_nope, q_rope, pool, w_kvb, tables, start, *, layer: int, scale: float,
    v_dim: int, kv_block: int | None = None, interpret: bool = False, gather: bool = False,
):
    """A chunk's queries against the rows ``[0, start + S)`` its sequence
    holds in the pool (the chunk's own rows included: written before this
    call), read back through the table and expanded through ``w_kvb``.
    ``q_nope`` [B, S, N, nope], ``q_rope`` [B, S, N, r]; ``tables`` [B, NBseq];
    ``start`` [B] the chunk's first position. Query ``s`` attends positions
    ``<= start + s``. -> [B, S, N, v_dim]. The Pallas kernel over the expanded
    prefix where it can tile the operands (``chunk_blocks``), else and with
    ``gather`` (``serving.decode_kernel: gather``) the loop of XLA blocks;
    ``kv_block``: the keys a block of either (the tests' small tables)."""
    B, S, N, nope = q_nope.shape
    tables = tables.astype(jnp.int32)
    start = start.astype(jnp.int32)
    w = w_kvb.astype(q_nope.dtype)
    kw = dict(layer=layer, scale=scale, v_dim=v_dim)
    blocks = None if gather else chunk_blocks(
        S, N, nope, q_rope.shape[-1], v_dim, tables.shape[1], pool.shape[2],
        interpret=interpret, kv_block=kv_block or _CHUNK_KEYS,
    )
    if blocks is None:
        return _chunk_loop(q_nope, q_rope, pool, w, tables, start,
                           kv_block=kv_block or _CHUNK_KV_BLOCK, **kw)
    return _chunk_kernel_attend(q_nope, q_rope, pool, w, tables, start, blocks=blocks,
                                interpret=interpret, **kw)


def _chunk_loop(q_nope, q_rope, pool, w, tables, start, *, layer, scale, v_dim, kv_block):
    """``chunk_attend`` as a loop of XLA blocks: ``kv_block`` keys read back,
    expanded and attended a trip, each block's float32 scores through HBM."""
    B, S, N, nope = q_nope.shape
    rank = w.shape[0]
    BS = pool.shape[2]
    NBseq = tables.shape[1]
    nb = max(1, min(kv_block // BS, NBseq))  # table entries a block of keys
    width = -(-NBseq // nb) * nb
    tables = jnp.pad(tables, ((0, 0), (0, width - NBseq)))  # scratch block 0
    K = nb * BS
    dt = q_nope.dtype
    q_abs = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B, S]
    qn = q_nope * jnp.asarray(scale, dt)
    qr = q_rope * jnp.asarray(scale, dt)

    def block(j, carry):
        m, l, acc = carry
        with jax.named_scope("mla_prefix_expand"):
            rows = _block_rows(pool, layer, tables, j, nb).astype(dt)
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
