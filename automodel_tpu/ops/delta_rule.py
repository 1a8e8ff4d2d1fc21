"""Chunked gated delta rule: one operator for a scalar and a per-channel decay.

The recurrence, per head, with state ``S`` in R^{dk x dv}::

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

**The operator's boundary is where the projections (and convs) leave their
results.** ``q``, ``k``, ``v`` come as ``[B, S, H * d]`` in the compute type,
a head's channels contiguous, RAW; ``g`` as ``[B, S, H]`` (Qwen3-Next's gated
DeltaNet: one decay a head) or ``[B, S, H * dk]`` (Kimi's KDA: one a
channel), unclamped; ``beta`` as ``[B, S, H]``. A scalar decay is the
per-channel case with every channel alike, so there is ONE algorithm; both
families normalise ``q`` and ``k`` a head, so the operator always does. On a
TPU ``[S, H * d] <-> [S, H, d]`` is no free view (an (8, 128) tile holds
eight tokens of one head in the first and eight heads of one token in the
second), so nothing here ever has the second shape.

**Formed in the chunk body, from the ``[C, d]`` tile of a head it holds**
(``_chunk_body``'s prologue; in VMEM inside the kernels), with these
rounding points: ``qn = round((q / |q|) * dk^-0.5)`` and ``kn = round(k /
|k|)`` from float32 (``rsqrt`` of the lane sum of squares plus 1e-6), then
``kb = round(kn * beta)`` and ``vb = round(v * beta)`` from float32 again,
``g = clip(g, G_MIN, 0)`` in float32 and ``g = 0`` on a document's first
token; ``round`` is to the compute type (nothing for float32 operands). The
backward differentiates the same prologue, so the gradients come back for
the raw operands: ``dq``, ``dk``, ``dv`` in the compute type, ``dg`` float32
(zero where the clamp holds and on a first token) and ``dbeta``; ``beta k``,
``beta v``, the clamped ``g`` and their cotangents never reach HBM.

**The epilogue, where a caller asks for it** (``out_norm_eps``: a float). A
per-head RMS norm of ``o`` outside the operator needs ``[B, S, H, dv]``, the
shape nothing here has (on the chip: float32 relayouts forward, recomputed
and transposed, 22.5 ms a step of Kimi-Linear's four KDA layers at 16,384
tokens; PERF.md section 6, PR 42 and PR 49), while a head's ``[C, dv]`` tile
of ``o`` is in VMEM, in float32, when the body ends. So the body may end with
``o = o * rsqrt(mean(o * o, axis=-1) + eps)`` on that tile, BEFORE its one
rounding to the output type: a square, a lane sum over ``dv``, an ``rsqrt``
and a multiply, no product. The norm's scale (a ``[dv]`` row the heads share)
stays the caller's, as a flat ``[H * dv]`` multiply. The backward needs
nothing written for it: it recomputes the body, the epilogue included, takes
the cotangent of the NORMED output and differentiates both. A padded row is
``0 * rsqrt(eps)`` = 0. ``None`` (the default, Qwen3-Next's: its gated norm
multiplies by ``silu(z)`` in its model's own layout) leaves the body as it
is.

**The chunked form.** With ``G`` the cumulative sum of ``g`` inside a chunk
of ``C`` tokens and ``u_t = beta_t (v_t - (Diag(exp g_t) S_{t-1})^T k_t)``::

    (I + Diag(beta) A_kk) U = beta (V - (K * exp G) S_0)
    O   = (Q * exp G) S_0 + A_qk U
    S_C = Diag(exp G_C) S_0 + (K * exp(G_C - G))^T U
    A_kk[t, j] = sum_d k_t[d] k_j[d] exp(G_t[d] - G_j[d])   (j <  t)
    A_qk[t, j] = sum_d q_t[d] k_j[d] exp(G_t[d] - G_j[d])   (j <= t)

Everything but the unit-lower-triangular solve is a product on the MXU; the
solve is products too (``_unit_lower_inverse``: 8 x 8 diagonal blocks by
their Neumann product, then block merges; float32 products for float32
operands, three bfloat16 passes a product otherwise: its result feeds a
bfloat16 product), so a chunk's body is matmuls and elementwise
work only and runs as it stands inside a Pallas kernel.

**Per-channel decay and overflow.** ``exp(G_t - G_j)`` cannot be factored
as ``exp(G_t) * exp(-G_j)`` over a whole chunk: ``-G_j`` grows with j and
overflows. A chunk's rows are therefore taken ``SUB`` = 16 at a time, each
block against the reference point ``G_m`` at its own middle row:
``(q_t * exp(G_t - G_m)) . (k_j * exp(G_m - G_j))``. For j in an earlier
block both exponents are <= 0; inside the block one of them is positive and
at most the sum of |g| over 8 tokens. ``g`` is clamped to ``[G_MIN, 0]`` =
[-10, 0] on entry (a decay factor below exp(-10) = 4.5e-5 a token is held at
that; the published kernels clamp at -5), so that sum is <= 80 and
``exp(80)`` is finite in float32 and bfloat16. Within that range of ``g``
the factorisation is exact up to rounding, for any sequence.

**Packed documents.** A document's first token resets the state. That is a
mask, not a decay: ``seg`` counts the resets seen so far inside the chunk,
pairs (t, j) interact only when ``seg_t == seg_j``, the incoming state only
reaches rows with ``seg == 0``, and only the last segment's rows reach the
outgoing state. The start token's own ``g`` is set to 0 (everything it would
decay is masked), so no large number enters a cumulative sum.

**The backward** is a ``custom_vjp``. The forward keeps the state at every
chunk's boundary (``[B, H, S/C, dv, dk]`` float32) and no chunk's
intermediates; the backward walks the chunks in reverse, carries ``dS`` in
VMEM, and for each chunk recomputes the body from its boundary state and
differentiates THAT ONE BODY (``jax.vjp`` of ``_chunk_body`` inside the
kernel: the body is matmuls and elementwise ops, so its transpose is too)
EXCEPT the triangular inverse, which brings its own cotangent
(``_unit_lower_inverse``): with ``T = (I + N)^-1``, ``dN = -T^T dT T^T``, two
products in the forward's arithmetic on the ``T`` the body holds anyway, where
the transpose of the block-merge construction runs each of its twelve products
backwards (and rounds every cotangent to bfloat16 on the way). Nothing
differentiates through the scan over chunks.

**What a chunk-head costs** is its count of 128 x 128 x 128 products on the
MXU, 39 to 44 ns each (``dot_general`` in ``jax.make_jaxpr`` at C = dk = dv =
128, bfloat16; ``tests/test_delta_rule.py`` pins them): forward **52** (36 the
inverse's twelve in three passes, 13 the rule's, 3 the cumulative sum);
backward **87** = those 52 recomputed + 26 the rule's transposed + 3 the
cumulative sum's + 6 the identity's two. With ``jax.vjp`` through the inverse
it was 153 (72 for the twelve transposed). Float32 operands: 28 and 59 (81).
My chip run, PR 44, 16,384 tokens x 32 heads: ``delta_rule_fwd`` 8.85 ms a
call, the backward 24.77 -> 15.55 (14.2 with the identity's products left out).

On a TPU both passes are Pallas kernels (grid: batch x groups of
``_HEADS_PER_STEP`` heads x steps of ``_CHUNKS_PER_STEP`` chunks, the states
resident in a VMEM scratch across the steps of a sequence; the cumulative
sum of ``g`` is a product with a triangle of ones inside the body, so no
``[S, H * dk]`` float32 array is transposed for it outside); ``AUTOMODEL_DELTA_INTERPRET=1`` runs the same kernels
in interpret mode (the CPU tests); elsewhere the same body runs under a
``lax.scan`` with the same custom backward.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
G_MIN = -10.0  # per token and channel; see the module docstring
SUB = 16  # rows that share one reference point
# Timed at 16,384 tokens x 32 heads of 128 x 128, bfloat16, a per-channel decay,
# from the raw flat operands to ``o`` and the five gradients (forward / forward
# + backward ms a call; "h" operands on lanes, see ``_kernel_call``). My chip
# run, PR 44 (the inverse's backward by its identity, 87 products a chunk-head):
# chunks of 128, 2 chunks a step, 2 / 4 / 8 heads a step: 9.08 / 24.74,
# **8.86 / 24.40**, 8.74 / 24.90 (a step runs the forward twice under `remat:
# full`: 4 heads 33.3, 8 heads 33.7); 4 heads, 1 / 4 chunks a step: 8.85 /
# 24.43, 8.87 / 24.49; chunks of 64: 12.79 / 33.88. Before it (153 products; my
# chip run, PR 42, "h" on sublanes): 2 / 4 / 8 heads 9.50 / 34.70, 9.11 / 34.16,
# 8.95 / 33.88; 4 chunks a step 9.08 / 34.11; chunks of 64 13.13 / 47.86; the
# same body under `lax.scan` (XLA, chunks of 64): 14.36 / 56.35. PR 41's
# operator with its operands formed outside (reshape to heads, two l2 norms,
# `beta k`, `beta v`, the clamp, flatten) from the same arrays: 19.40 / 59.24 at
# chunks of 128, 23.41 / 73.18 at 64; the prologue costs the kernels 2.4 % and
# 4.0 % (8.74 -> 8.95 and 23.62 -> 24.56 ms a call in the cell's trace, PR 42).
_CHUNKS_PER_STEP = 2  # chunks a grid step walks
_HEADS_PER_STEP = 4  # heads a grid step walks: independent chains for the scheduler to interleave
_VMEM_LIMIT = 64 * 1024 * 1024  # a v5e core has 128 MiB; the default scoped limit is 16


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x = x.astype(F32)
    return x * lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def _interpret_requested() -> bool:
    return os.environ.get("AUTOMODEL_DELTA_INTERPRET", "0") == "1"


# -- one chunk ------------------------------------------------------------------


def _dot(a, b, dims, precision=None):
    return lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                           preferred_element_type=F32)


def _mm_split(a, b, dims=((1,), (0,))):
    """float32 ``a @ b`` from three bfloat16 passes on the MXU (hi/lo split:
    hi.hi + hi.lo + lo.hi, 2^-16 relative; the compiler's ``HIGHEST`` takes
    six). ``dims``: the contracting axes, for a transposed operand."""
    bf = jnp.bfloat16
    ah, bh = a.astype(bf), b.astype(bf)
    al, bl = (a - ah.astype(F32)).astype(bf), (b - bh.astype(F32)).astype(bf)
    return _dot(ah, bh, dims) + _dot(ah, bl, dims) + _dot(al, bh, dims)


def _inverse_mm(exact: bool):
    """The product of the triangular inverse and of its cotangent: float32
    (``HIGHEST``) for float32 operands, three bfloat16 passes otherwise."""
    if exact:
        return lambda a, b, dims=((1,), (0,)): _dot(a, b, dims, lax.Precision.HIGHEST)
    return _mm_split


_INVERSE_BASE = 8  # diagonal blocks inverted by their (short) Neumann product


def _block_merge_inverse(n: jnp.ndarray, exact: bool) -> jnp.ndarray:
    """(I + n)^-1 for a strictly lower triangular float32 ``n`` [C, C], from
    products only. The 8 x 8 diagonal blocks by ``(I - n)(I + n^2)(I + n^4)``
    (nilpotent: exact), then pairs of blocks merged level by level with
    ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``: every factor is
    an inverse of a diagonal block, so nothing larger than the answer is ever
    formed. (The Neumann product over the whole chunk needs ``n^(C/2)``, whose
    entries reach 1e37 at C = 128 for a run of equal keys written at full
    strength: NaN.) 4 + 2 log2(C / 8) products, as many as the plain product.

    ``exact``: float32 products (float32 operands); otherwise the result is
    rounded to bfloat16 by its one use, and three bfloat16 passes a product
    keep it well inside that (my chip run, PR 41, forward + backward ms at
    16,384 tokens x 32 heads, chunks of 64: six passes 74.1, three 62.9, one
    56.2 with 2^-9 compounding over the products)."""
    c = n.shape[0]
    mm = _inverse_mm(exact)
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same_block = lambda b: (row // b) == (col // b)
    base = min(_INVERSE_BASE, c)
    p = jnp.where(same_block(base), n, 0.0)
    x, power = (row == col).astype(F32) - p, 1
    while 2 * power < base:
        p = mm(p, p)
        x = x + mm(x, p)
        power *= 2
    b = base
    while b < c:  # x holds the inverses of the b x b diagonal blocks
        lower_left = jnp.where(same_block(2 * b) & ~same_block(b), n, 0.0)
        x = x - mm(mm(x, lower_left), x)
        b *= 2
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(n: jnp.ndarray, exact: bool) -> jnp.ndarray:
    """``T = (I + n)^-1`` by ``_block_merge_inverse`` (12 products at C = 128,
    36 ``dot_general`` in three passes), with the inverse's own cotangent in
    place of the construction's transpose: ``d(T) = -T d(n) T``, so

        dn = -T^T dT T^T

    two products in the forward's own arithmetic (``_inverse_mm``: 6
    ``dot_general`` in three passes, 2 at ``HIGHEST``) where ``jax.vjp`` of the
    construction transposes each of its twelve (72, every transposed pass with
    its cotangent rounded to bfloat16 on the way in). The residual is ``T``,
    which the caller holds anyway. Against float64 the identity reads 4.8e-6
    to 1.7e-5 of the largest entry where the transposed construction reads
    3.1e-3 to 3.1e-2 (``tests/test_delta_rule.py``; float32 operands: both at
    float32's rounding). The caller masks ``n`` to its strictly lower entries,
    and with it ``dn``."""
    return _block_merge_inverse(n, exact)


def _unit_lower_inverse_bwd(exact, t, dt):
    mm = _inverse_mm(exact)
    return (-mm(mm(t, dt, ((0,), (0,))), t, ((1,), (1,))),)


_unit_lower_inverse.defvjp(lambda n, exact: (_block_merge_inverse(n, exact),) * 2,
                           _unit_lower_inverse_bwd)


def _tri_sum(x: jnp.ndarray, reverse: bool) -> jnp.ndarray:
    """Inclusive cumulative sum of a float32 ``x`` [C, d] over its rows (from
    the last row up when ``reverse``) as a product with a triangle of ones on
    the MXU: ``x`` goes in as three bfloat16 terms (24 bits of it), the ones
    are exact, the sums accumulate in float32."""
    c = x.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    tri = ((col >= row) if reverse else (col <= row)).astype(jnp.bfloat16)
    out, rest = None, x
    for _ in range(3):
        term = rest.astype(jnp.bfloat16)
        rest = rest - term.astype(F32)
        part = _dot(tri, term, ((1,), (0,)))
        out = part if out is None else out + part
    return out


@jax.custom_vjp
def _chunk_cumsum(g):
    return _tri_sum(g, reverse=False)


_chunk_cumsum.defvjp(lambda g: (_tri_sum(g, reverse=False), None),
                     lambda _, d: (_tri_sum(d, reverse=True),))


def _chunk_body(st0, q, k, v, g, beta, segc=None, segr=None, out_norm_eps=None):
    """One chunk of one head, from the operands as the projections left them.
    ``st0`` [dv, dk] float32: the state BEFORE the chunk, transposed (the decay
    then scales its columns); ``q, k`` [C, dk], ``v`` [C, dv]: raw, in the
    compute type; ``g`` [C, dk] or [C, 1]: the log-decay, unclamped; ``beta``
    [C, 1]; ``segc`` [C, 1] int32: 2 x the resets seen so far + 1 on a
    document's first token, ``segr`` [1, C] int32: the resets seen so far; both
    None for one document. What only feeds the rule is formed here, in float32,
    and rounded to the compute type where the module docstring says.
    ``out_norm_eps``: a float divides each row of ``o`` by its root mean square
    (the epilogue; the state is the rule's own either way).
    -> (o [C, dv] float32, the state after the chunk [dv, dk] float32)."""
    cd, dk = q.dtype, q.shape[1]
    beta = beta.astype(F32)
    qn = (l2norm(q) * dk**-0.5).astype(cd)
    kn = l2norm(k).astype(cd)
    kb = (kn.astype(F32) * beta).astype(cd)
    vb = (v.astype(F32) * beta).astype(cd)
    g = jnp.clip(g.astype(F32), G_MIN, 0.0)
    if segc is not None:
        g = jnp.where((segc & 1) == 1, 0.0, g)
        segc = segc >> 1
    o, st1 = _chunk_rule(st0, qn, kn, kb, vb, jnp.broadcast_to(g, q.shape), segc, segr)
    if out_norm_eps is not None:
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + out_norm_eps)
    return o, st1


def _chunk_rule(st0, q, k, kb, vb, g, segc, segr):
    """The chunked form of the module docstring on formed operands: ``q, k``
    normalised, ``kb = beta k``, ``vb = beta v``, ``g`` [C, dk] float32 in
    [G_MIN, 0] and 0 on a document's first token; ``segc`` [C, 1] / ``segr``
    [1, C]: resets seen so far, or None."""
    c, dk = q.shape
    gc = _chunk_cumsum(g)  # inclusive, inside the chunk
    cd = q.dtype  # the matmuls' operand type; they accumulate in float32
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lower, strict = col <= row, col < row
    if segc is not None:
        same = segc == segr
        lower, strict = lower & same, strict & same
    qf, kf, kbf = q.astype(F32), k.astype(F32), kb.astype(F32)
    ridx = lax.broadcasted_iota(jnp.int32, (c, 1), 0)

    a_qk, a_kk = [], []
    for lo in range(0, c, SUB):
        hi, mid = lo + SUB, lo + SUB // 2
        ref = gc[mid:mid + 1]  # [1, dk]
        e_row = jnp.exp(gc[lo:hi] - ref)  # <= exp(80)
        e_col = jnp.where(ridx < hi, jnp.exp(jnp.minimum(ref - gc, -8 * G_MIN)), 0.0)
        lhs = jnp.concatenate([qf[lo:hi] * e_row, kbf[lo:hi] * e_row], axis=0).astype(cd)
        a = _dot(lhs, (kf * e_col).astype(cd), ((1,), (1,)))  # [2 SUB, C]
        a_qk.append(a[:SUB])
        a_kk.append(a[SUB:])
    a_qk = jnp.where(lower, jnp.concatenate(a_qk, axis=0), 0.0)
    t = _unit_lower_inverse(jnp.where(strict, jnp.concatenate(a_kk, axis=0), 0.0),
                            exact=cd == F32)

    gam = jnp.exp(gc)  # <= 1
    last = gc[c - 1:c]
    k_in, q_in = kbf * gam, qf * gam
    k_out = kf * jnp.exp(last - gc)  # <= 1
    carry = jnp.exp(last)  # [1, dk]
    if segc is not None:
        first = segc == 0
        k_in, q_in = jnp.where(first, k_in, 0.0), jnp.where(first, q_in, 0.0)
        seg_last = segr[:, c - 1:c]  # [1, 1]
        k_out = jnp.where(segc == seg_last, k_out, 0.0)
        carry = jnp.where(seg_last == 0, carry, 0.0)
    s0 = st0.astype(cd)
    r = vb.astype(F32) - _dot(k_in.astype(cd), s0, ((1,), (1,)))
    u = _dot(t.astype(cd), r.astype(cd), ((1,), (0,))).astype(cd)  # [C, dv]
    o = _dot(q_in.astype(cd), s0, ((1,), (1,))) + _dot(a_qk.astype(cd), u, ((1,), (0,)))
    st1 = st0 * carry + _dot(u, k_out.astype(cd), ((0,), (0,)))
    return o, st1


def _chunk_grads(st0, q, k, v, g, beta, segc, segr, do, dst1, out_norm_eps=None):
    """The body recomputed and transposed, its epilogue with it (``do`` is the
    cotangent of the output as the forward gave it): -> (dst0, dq, dk, dv, dg,
    dbeta), each in its operand's shape and type."""
    body = lambda *a: _chunk_body(*a, segc, segr, out_norm_eps)
    _, vjp = jax.vjp(body, st0, q, k, v, g, beta)
    return vjp((do, dst1))


# -- the Pallas kernels ------------------------------------------------------------


def _diagonal(ref, i, c):
    """Where lane ``l`` of a small operand's ``[hb, rows]`` block is row ``r`` of
    chunk ``i``: ``[C, rows]`` bool."""
    n = ref.shape[1]
    r = lax.broadcasted_iota(jnp.int32, (c, n), 0)
    l = lax.broadcasted_iota(jnp.int32, (c, n), 1)
    return l == r + i * c


def _head_column(ref, h, i, c):
    """Head ``h``'s ``[C, 1]`` column of chunk ``i`` out of a small operand's
    block (tokens on lanes): a select on the diagonal and a lane sum of one
    term each, so exact."""
    return jnp.sum(jnp.where(_diagonal(ref, i, c), ref[pl.ds(h, 1), :], 0.0), axis=1, keepdims=True)


def _add_head_row(ref, h, i, c, col):
    """The other way: a ``[C, 1]`` column into chunk ``i``'s lanes of head
    ``h``'s row (the rest of the row gets + 0)."""
    ref[pl.ds(h, 1), :] += jnp.sum(jnp.where(_diagonal(ref, i, c), col, 0.0), axis=0, keepdims=True)


def _fwd_kernel(*refs, c: int, n_sub: int, hb: int, dk: int, dv: int, g_small: bool, has_seg: bool,
                out_norm_eps: Optional[float]):
    q, k, v, g, beta, *seg, o, states, st = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        st[...] = jnp.zeros_like(st)

    def one(i, _):
        rows = pl.ds(pl.multiple_of(i * c, c), c)
        segs = (seg[0][rows, :], seg[1][i]) if has_seg else (None, None)
        for h in range(hb):  # independent chains: the scheduler interleaves them
            kc, vc = pl.ds(h * dk, dk), pl.ds(h * dv, dv)
            states[h, i] = st[h]
            out, st1 = _chunk_body(st[h], q[rows, kc], k[rows, kc], v[rows, vc],
                                   _head_column(g, h, i, c) if g_small else g[rows, kc],
                                   _head_column(beta, h, i, c), *segs, out_norm_eps=out_norm_eps)
            o[rows, vc] = out.astype(o.dtype)
            st[h] = st1
        return 0

    lax.fori_loop(0, n_sub, one, 0)


def _bwd_kernel(*refs, c: int, n_sub: int, hb: int, dk: int, dv: int, g_small: bool, has_seg: bool,
                out_norm_eps: Optional[float]):
    q, k, v, g, beta, *seg, do, states, dq, dk_, dv_, dg, dbeta, dst = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst[...] = jnp.zeros_like(dst)

    dbeta[...] = jnp.zeros_like(dbeta)  # a chunk adds its lanes to a head's row
    if g_small:
        dg[...] = jnp.zeros_like(dg)

    def one(j, _):
        i = n_sub - 1 - j
        rows = pl.ds(pl.multiple_of(i * c, c), c)
        segs = (seg[0][rows, :], seg[1][i]) if has_seg else (None, None)
        for h in range(hb):
            kc, vc = pl.ds(h * dk, dk), pl.ds(h * dv, dv)
            dst0, gq, gk, gv, gg, gb = _chunk_grads(
                states[h, i], q[rows, kc], k[rows, kc], v[rows, vc],
                _head_column(g, h, i, c) if g_small else g[rows, kc], _head_column(beta, h, i, c),
                *segs, do[rows, vc].astype(F32), dst[h], out_norm_eps=out_norm_eps)
            dq[rows, kc] = gq
            dk_[rows, kc] = gk
            dv_[rows, vc] = gv
            if g_small:
                _add_head_row(dg, h, i, c, gg)
            else:
                dg[rows, kc] = gg.astype(dg.dtype)
            _add_head_row(dbeta, h, i, c, gb)
            dst[h] = dst0
        return 0

    lax.fori_loop(0, n_sub, one, 0)


def _heads_per_step(heads: int) -> int:
    return next(n for n in range(min(_HEADS_PER_STEP, heads), 0, -1) if heads % n == 0)


def _by_group(x, hb):
    """A value a head and token, ``[B, Sp, H]`` -> ``[B, H / hb, hb, Sp]``: the
    layout the kernels read ``beta`` (and a scalar ``g``) in, see ``_kernel_call``."""
    B, Sp, H = x.shape
    return x.reshape(B, Sp, H // hb, hb).transpose(0, 2, 3, 1)


def _from_groups(x):
    B, n, hb, Sp = x.shape
    return x.transpose(0, 3, 1, 2).reshape(B, Sp, n * hb)


def _kernel_call(kernel, operands, outputs, *, heads, c, dk, dv, g_small, has_seg, out_norm_eps,
                 reverse, interpret, name):
    """Grid: batch x groups of ``hb`` heads x steps of ``_CHUNKS_PER_STEP``
    chunks (walked from the last when ``reverse``). An operand or output is
    "k" (``[B, Sp, H * dk]``), "v" (``[B, Sp, H * dv]``), "h" (a value a head
    and token: ``beta``, a scalar ``g`` and their cotangents), "segc", "segr"
    or "states" (``[B, H, Sp / C, dv, dk]``).

    "h" travels as ``[B, H / hb, hb, Sp]``, tokens on lanes, and the body takes
    a head's ``[C, 1]`` column out of its row by ``_head_column``. The other
    candidate, ``[B, H / hb, Sp, hb]`` (tokens on sublanes: a head's column is
    a plain lane slice of the block), pads ``hb`` = 4 lanes to 128 in HBM (64
    MB an array at 16,384 x 32 against 4) and read slower (my chip run, PR 42;
    the operator alone at 16,384 tokens x 32 heads of 128 x 128, bfloat16,
    the 1 MB transposes outside included, forward / forward + backward ms a
    call): sublanes 9.11 / 34.16, lanes **8.90 / 33.67**."""
    B, Sp, _ = operands[0][1].shape
    n_sub = _CHUNKS_PER_STEP
    hb = _heads_per_step(heads)
    n_steps = Sp // (n_sub * c)
    step = (lambda s: n_steps - 1 - s) if reverse else (lambda s: s)
    rows = n_sub * c
    specs = {
        "k": pl.BlockSpec((None, rows, hb * dk), lambda b, h, s: (b, step(s), h)),
        "v": pl.BlockSpec((None, rows, hb * dv), lambda b, h, s: (b, step(s), h)),
        "h": pl.BlockSpec((None, None, hb, rows), lambda b, h, s: (b, h, 0, step(s))),
        "segc": pl.BlockSpec((None, rows, 1), lambda b, h, s: (b, step(s), 0)),
        "segr": pl.BlockSpec((None, n_sub, 1, c), lambda b, h, s: (b, step(s), 0, 0)),
        "states": pl.BlockSpec((None, hb, n_sub, dv, dk), lambda b, h, s: (b, h, step(s), 0, 0)),
    }
    return pl.pallas_call(
        functools.partial(kernel, c=c, n_sub=n_sub, hb=hb, dk=dk, dv=dv, g_small=g_small,
                          has_seg=has_seg, out_norm_eps=out_norm_eps),
        grid=(B, heads // hb, n_steps),
        in_specs=[specs[kind] for kind, _ in operands],
        out_specs=[specs[kind] for kind, _ in outputs],
        out_shape=[sds for _, sds in outputs],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(*(a for _, a in operands))


def _kernel_operands(q, k, v, g, beta, segc, segr, heads):
    """-> (the kernels' operand list, their static arguments)."""
    hb = _heads_per_step(heads)
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    g_small = g.shape[-1] == heads
    seg = [("segc", segc), ("segr", segr)] if segc is not None else []
    ops = [("k", q), ("k", k), ("v", v), ("h", _by_group(g, hb)) if g_small else ("k", g),
           ("h", _by_group(beta, hb)), *seg]
    return ops, dict(heads=heads, dk=dk, dv=dv, g_small=g_small, has_seg=bool(seg))


def _kernel_fwd(q, k, v, g, beta, segc, segr, *, heads, c, out_norm_eps, interpret):
    """Flat operands ``[B, Sp, H * d]``, ``beta`` (and a scalar ``g``)
    ``[B, Sp, H]``, ``Sp`` a whole number of grid steps.
    -> (o [B, Sp, H * dv], boundary states [B, H, Sp / C, dv, dk])."""
    B, Sp, _ = q.shape
    ops, static = _kernel_operands(q, k, v, g, beta, segc, segr, heads)
    return _kernel_call(
        _fwd_kernel, ops,
        [("v", jax.ShapeDtypeStruct(v.shape, v.dtype)),
         ("states", jax.ShapeDtypeStruct((B, heads, Sp // c, static["dv"], static["dk"]), F32))],
        c=c, out_norm_eps=out_norm_eps, reverse=False, interpret=interpret, name="delta_rule_fwd",
        **static)


def _kernel_bwd(q, k, v, g, beta, segc, segr, do, states, *, heads, c, out_norm_eps, interpret):
    ops, static = _kernel_operands(q, k, v, g, beta, segc, segr, heads)
    like = lambda op, dt=None: (op[0], jax.ShapeDtypeStruct(op[1].shape, dt or op[1].dtype))
    dq, dk_, dv_, dg, dbeta = _kernel_call(
        _bwd_kernel, [*ops, ("v", do), ("states", states)],
        [like(ops[0]), like(ops[1]), like(ops[2]), like(ops[3], F32), like(ops[4], F32)],
        c=c, out_norm_eps=out_norm_eps, reverse=True, interpret=interpret, name="delta_rule_bwd",
        **static)
    return dq, dk_, dv_, _from_groups(dg) if static["g_small"] else dg, _from_groups(dbeta)


# -- the same body under a scan (off the TPU) ----------------------------------------


def _to_chunks(x, heads, c):
    """[B, Sp, H * d] (d = 1: a value a head) -> [Sp / C, B, H, C, d]."""
    B, Sp, hd = x.shape
    x = x.reshape(B, Sp // c, c, heads, hd // heads)
    return x.transpose(1, 0, 3, 2, 4)


def _from_chunks(x):
    n, B, H, c, d = x.shape
    return x.transpose(1, 0, 3, 2, 4).reshape(B, n * c, H * d)


def _seg_chunks(segc, segr, c):
    """-> per chunk ([B, C, 1], [B, 1, C]) stacked on a leading chunk axis."""
    B, Sp, _ = segc.shape
    return segc.reshape(B, Sp // c, c, 1).swapaxes(0, 1), segr.swapaxes(0, 1)


def _over_heads(fn, has_seg, n_tail=0):
    """``fn(st0, q, k, v, g, beta, segc, segr, *tail)`` of one head's chunk ->
    of [B, H, ...] operands; the segment counts are a batch row's, shared by
    its heads."""
    seg = 0 if has_seg else None
    per_head = jax.vmap(fn, in_axes=(0,) * 6 + (None, None) + (0,) * n_tail)
    return jax.vmap(per_head, in_axes=(0,) * 6 + (seg, seg) + (0,) * n_tail)


def _scan_fwd(q, k, v, g, beta, segc, segr, *, heads, c, out_norm_eps):
    B, Sp, _ = q.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    has_seg = segc is not None
    xs = tuple(_to_chunks(a, heads, c) for a in (q, k, v, g, beta))
    segs = _seg_chunks(segc, segr, c) if has_seg else None
    batched = _over_heads(functools.partial(_chunk_body, out_norm_eps=out_norm_eps), has_seg)

    def step(st, x):
        ops, seg = x
        sc, sr = seg if has_seg else (None, None)
        o, st1 = batched(st, *ops, sc, sr)
        return st1, (o, st)

    _, (o, states) = lax.scan(step, jnp.zeros((B, heads, dv, dk), F32), (xs, segs))
    return _from_chunks(o).astype(v.dtype), states.transpose(1, 2, 0, 3, 4)


def _scan_bwd(q, k, v, g, beta, segc, segr, do, states, *, heads, c, out_norm_eps):
    has_seg = segc is not None
    xs = tuple(_to_chunks(a, heads, c) for a in (q, k, v, g, beta))
    segs = _seg_chunks(segc, segr, c) if has_seg else None
    dos = _to_chunks(do.astype(F32), heads, c)
    batched = _over_heads(functools.partial(_chunk_grads, out_norm_eps=out_norm_eps), has_seg,
                          n_tail=2)

    def step(dst, x):
        ops, seg, d_o, st0 = x
        sc, sr = seg if has_seg else (None, None)
        dst0, *grads = batched(st0, *ops, sc, sr, d_o, dst)
        return dst0, grads

    states = states.transpose(2, 0, 1, 3, 4)  # a chunk's [B, H, dv, dk] a scan step
    _, grads = lax.scan(step, jnp.zeros_like(states[0]), (xs, segs, dos, states), reverse=True)
    return tuple(_from_chunks(x) for x in grads)


# -- the custom_vjp over flat, padded operands -----------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _chunked(q, k, v, g, beta, segc, segr, heads, c, mode, out_norm_eps):
    return _chunked_fwd(q, k, v, g, beta, segc, segr, heads, c, mode, out_norm_eps)[0]


def _chunked_fwd(q, k, v, g, beta, segc, segr, heads, c, mode, out_norm_eps):
    static = dict(heads=heads, c=c, out_norm_eps=out_norm_eps)
    if mode == "scan":
        o, states = _scan_fwd(q, k, v, g, beta, segc, segr, **static)
    else:
        o, states = _kernel_fwd(q, k, v, g, beta, segc, segr, **static,
                                interpret=mode == "interpret")
    return o, (q, k, v, g, beta, segc, segr, states)


def _chunked_bwd(heads, c, mode, out_norm_eps, res, do):
    *ops, segc, segr, states = res
    static = dict(heads=heads, c=c, out_norm_eps=out_norm_eps)
    if mode == "scan":
        grads = _scan_bwd(*ops, segc, segr, do, states, **static)
    else:
        grads = _kernel_bwd(*ops, segc, segr, do, states, **static,
                            interpret=mode == "interpret")
    return (*(d.astype(a.dtype) for d, a in zip(grads, ops)), None, None)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


# -- the operator -------------------------------------------------------------------------


def chunked_delta_rule(
    q: jnp.ndarray,  # [B, S, H * dk]: raw, as the projection (and conv) left it
    k: jnp.ndarray,  # [B, S, H * dk]
    v: jnp.ndarray,  # [B, S, H * dv]
    g: jnp.ndarray,  # [B, S, H] or [B, S, H * dk]: log-decay, unclamped
    beta: jnp.ndarray,  # [B, S, H]: write strength
    *,
    segment_ids: Optional[jnp.ndarray] = None,  # [B, S] packed-document ids
    out_norm_eps: Optional[float] = None,  # a float: each head's output row over its RMS
    chunk_size: int = 128,
    platform: Optional[str] = None,
    mesh_ctx=None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """-> ``o`` [B, S, H * dv] in ``v``'s type; ``H`` is ``beta``'s last axis,
    a head's channels are contiguous. The operator normalises ``q`` and ``k``
    a head (l2, ``eps`` 1e-6), scales ``q`` by ``dk ** -0.5``, forms ``beta k``
    and ``beta v`` and clamps ``g`` to [-10, 0] itself, a chunk's tile at a
    time (module docstring): no ``[B, S, H, d]`` array goes in or comes out,
    and the gradients come back for the raw operands. ``out_norm_eps``: a
    float makes ``o`` each head's output row divided by its root mean square,
    ``o * rsqrt(mean(o^2 over dv) + out_norm_eps)``, formed in float32 in the
    chunk body's epilogue and rounded ONCE to ``v``'s type (the RMS norm of
    ``o`` a head without its scale, which the caller multiplies in flat; the
    gradients are those of the normed output); ``None`` gives the rule's own
    ``o`` (Qwen3-Next's). It says which mathematics the caller wants, like
    ``segment_ids``; it is no tuning knob. The products take their
    operands in ``q``'s type (bfloat16 in training, float32 for Qwen3-Next and
    in the CPU tests) and accumulate in float32; the norms, the state, the
    cumulative decay and the triangular solve are float32 whatever the
    operands. Exact (to rounding) for ``g`` in [-10, 0]; a smaller ``g`` is
    held at -10 and its gradient is zero. The state starts at zero and resets
    where ``segment_ids`` changes."""
    from automodel_tpu.ops.platform_check import is_tpu_platform, kernel_axes

    if interpret is None:
        interpret = _interpret_requested()
    mode = "interpret" if interpret else ("kernel" if is_tpu_platform(platform) else "scan")
    if chunk_size % SUB or chunk_size & (chunk_size - 1):
        raise ValueError(f"chunk_size {chunk_size} is not a power of two >= {SUB}")

    def block(q, k, v, g, beta, segment_ids=None):
        return _delta_rule_block(q, k, v, g, beta, segment_ids, chunk_size, mode, out_norm_eps)

    if mode == "scan" or kernel_axes(mesh_ctx) is None:
        return block(q, k, v, g, beta, segment_ids)
    return _delta_shard_map(block, mesh_ctx, q, k, v, g, beta, segment_ids)


def _delta_shard_map(block, mesh_ctx, q, k, v, g, beta, segment_ids):
    """The kernels per device block: batch over the data axes, heads over
    ``tp`` (a shard of a flat operand is a contiguous block of heads), the
    sequence whole (GSPMD cannot partition a Mosaic call)."""
    from jax.sharding import PartitionSpec as P

    from automodel_tpu.ops.platform_check import kernel_shard_map, sharded_axes

    if mesh_ctx.cp_size > 1:
        raise ValueError("the chunked delta rule walks a whole sequence a device: cp must be 1")
    batch = sharded_axes(mesh_ctx, "batch", (q.shape[0],), "delta-rule batch")
    heads = sharded_axes(mesh_ctx, "tensor", (beta.shape[2],), "delta-rule heads")
    x = P(batch, None, heads)
    args, specs = [q, k, v, g, beta], [x] * 5
    if segment_ids is not None:
        args.append(segment_ids)
        specs.append(P(batch, None))
    return kernel_shard_map(mesh_ctx, block, tuple(specs), x)(*args)


def _delta_rule_block(q, k, v, g, beta, segment_ids, c, mode, out_norm_eps):
    B, S, H = beta.shape
    cd = q.dtype
    step = c * (_CHUNKS_PER_STEP if mode != "scan" else 1)
    pad = (-S) % step
    Sp = S + pad
    # padded rows: k = beta = 0 and g = 0, so they neither write nor decay
    padded = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    segc = segr = None
    if segment_ids is not None:
        prev = jnp.pad(segment_ids, ((0, 0), (1, 0)), constant_values=-1)[:, :S]
        # [B, S]; position 0 starts from zero anyway
        starts = jnp.pad((segment_ids != prev).astype(jnp.int32), ((0, 0), (0, pad)))
        seg = jnp.cumsum(starts.reshape(B, Sp // c, c), axis=2)  # resets so far, a chunk
        # the column also says which rows ARE a start (the body zeroes their g)
        segc = (2 * seg + starts.reshape(seg.shape)).reshape(B, Sp, 1)
        segr = seg.reshape(B, Sp // c, 1, c)
    o = _chunked(padded(q), padded(k.astype(cd)), padded(v.astype(cd)), padded(g), padded(beta),
                 segc, segr, H, c, mode, out_norm_eps)
    return o[:, :S].astype(v.dtype)
