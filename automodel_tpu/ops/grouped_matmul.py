"""Pallas grouped matmul (megablocks-style) — the MoE expert hot path.

Parity: reference `GroupedExperts` grouped GEMM (components/moe/experts.py:158
via torch `_grouped_mm`). On TPU the idiomatic lowering is `lax.ragged_dot`,
but this image's AOT compile helper crashes lowering ragged_dot at bench-scale
token counts, and XLA's lowering isn't tuned for the sorted-by-expert MoE
layout anyway — so this is a hand-scheduled Pallas kernel:

  out[m, n] = sum_k lhs[m, k] @ rhs[g(m), k, n]

with `lhs` rows sorted by group and `group_sizes[g]` rows per group.

Scheduling: the grid iterates over *work units* — (m-tile, group) pairs that
actually overlap — computed at trace time from `group_sizes` with jnp ops and
handed to the kernel via scalar prefetch (group/tile id + row window per
unit). A tile spanning a group boundary is visited once per group, with a row
mask selecting each group's rows; consecutive units on the same output tile
keep it resident in VMEM (TPU grids are sequential), so the read-modify-write
blend needs no atomics. Worst case `M/tm + G` units, i.e. O(1) overhead per
group boundary — dropless, no capacity factor, no padding per expert.

The backward needs two more kernels: dlhs is just gmm against `rhs`
transposed, and drhs is a transposed grouped matmul (`_tgmm`) accumulating
`lhs_g^T @ dout_g` per group over that group's row tiles in an fp32 VMEM
scratch, written once, in the weight's dtype, at the group's last unit. Its
plan gives an EMPTY group one unit too (`_plan(..., empty_units=True)`), so
the kernel writes that group's zero slab itself and no pass over
``[G, K, N]`` follows the call.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret_requested() -> bool:
    return os.environ.get("AUTOMODEL_GMM_INTERPRET", "0") == "1"


def _pallas_eligible(platform: str | None = None) -> bool:
    from automodel_tpu.ops.platform_check import is_tpu_platform

    return _interpret_requested() or is_tpu_platform(platform)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _out_sds(shape, dtype, *operands):
    """ShapeDtypeStruct carrying the union of the operands' vma — inside a
    check_vma shard_map region (the a2a/a2a_fused EP paths) a pallas_call
    must state how its output varies over the manual axes."""
    vmas = [jax.typeof(o).vma for o in operands]
    if any(vmas):
        vma = frozenset().union(*[v for v in vmas if v])
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _plan(group_sizes: jnp.ndarray, m_padded: int, tm: int, num_groups: int,
          empty_units: bool = False):
    """Work-unit schedule: for each of W = m_padded/tm + G grid steps, the
    (group, m-tile, row-window) it computes. All jnp — `group_sizes` is a
    traced value; the plan rides to the kernel as scalar prefetch.

    ``empty_units`` is the weight-gradient kernels' plan (`_tgmm`, the fused
    backward's `_bwd_gu` / `_bwd_dwd`): an empty group holds ONE unit with an
    empty row window, so the kernel that owns the group's output slab visits
    it and writes zeros there (W budgets a unit a group). Every kernel that
    produces ROWS leaves it off: an empty group then holds no unit, which is
    what the forward's dead-unit skip and `work_units` count on."""
    gs = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(gs)
    starts = ends - gs
    first = starts // tm
    last = jnp.maximum(ends - 1, starts) // tm
    ntiles = jnp.where(gs > 0, last - first + 1, 1 if empty_units else 0)
    wstart = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(ntiles)[:-1]])
    total = wstart[-1] + ntiles[-1]

    W = m_padded // tm + num_groups
    i = jnp.arange(W, dtype=jnp.int32)
    valid = i < total
    j = jnp.clip(i, 0, jnp.maximum(total - 1, 0))
    # last group whose first work unit is ≤ j; runs of equal wstart (empty
    # groups) resolve to the run's last member, which is the non-empty one
    g = (jnp.searchsorted(wstart, j, side="right") - 1).astype(jnp.int32)
    tile = first[g] + (j - wstart[g])
    if empty_units:
        # an empty group past the last row starts AT m_padded: its (empty)
        # window must still name a row tile the operands have
        tile = jnp.minimum(tile, m_padded // tm - 1)
    # row window; invalid (clamped) units get an empty window → masked no-op
    row_s = jnp.where(valid, starts[g], 0)
    row_e = jnp.where(valid, ends[g], 0)
    return g, tile.astype(jnp.int32), row_s, row_e


def _chunks(n: int, cap: int | None = None) -> list[int]:
    """The 128-multiples (≤ ``cap``) that divide the 128-padded ``n``,
    widest first. 128 always does."""
    n128 = _round_up(n, 128)
    return [
        n128 // j for j in range(1, n128 // 128 + 1)
        if n128 % j == 0 and (n128 // j) % 128 == 0
        and (cap is None or n128 // j <= cap)
    ]


def _gmm_tiles(K: int, N: int, dtype) -> tuple[int, int]:
    """(tm, tn) for _gmm: 256 rows and the widest column block whose
    double-buffered lhs / rhs / out blocks (all of K a block) fit ~12 MB of
    VMEM, 128 rows if none does. ``tn`` divides the 128-padded N: a column
    block that does not pads the weight up to a multiple of itself, a copy
    of ``[G, K, N]`` on every call and its padding as real matmul work.

    Timed on one v5e at the fused backward's two products (65,536 rows in
    128 groups of 455–570, bf16; PERF.md, PR 40), ms a call as (tm, tn):
    ``lhs @ Wgu`` (K 2048, N 1536) (512, 512) 4.41, (512, 768) 4.36,
    (256, 512) 3.78, **(256, 768) 3.66**, (128, 768) 3.69; ``dy @ Wd^T``
    (K 2048, N 768) (512, 512 on a weight padded to 1024) 4.74, (512, 384)
    2.27, (512, 768) 2.22, (256, 384) 1.99, **(256, 768) 1.86**,
    (128, 768) 1.90. A group there holds about 512 rows: a 512-row tile
    multiplies as many masked rows as real ones."""
    Kp, it = _round_up(K, 128), jnp.dtype(dtype).itemsize
    budget = 12 * 1024 * 1024
    for tm in (256, 128):
        for tn in _chunks(N):
            if 2 * it * (tm * Kp + Kp * tn + tm * tn) <= budget:
                return tm, tn
    return 128, 128


def _tgmm_tiles(K: int, N: int, dtype) -> tuple[int, int, int]:
    """(tm, tk, tn) for _tgmm: 256 rows like `_gmm_tiles`, and the widest
    divisors of the 128-padded K and N up to 512: its blocks (two inputs and
    the narrow out slab double-buffered, one fp32 scratch) stay under 4 MB."""
    return 256, _chunks(K, 512)[0], _chunks(N, 512)[0]


def _gmm_kernel(wg, wt, ws, we, lhs_ref, rhs_ref, out_ref, *, tm, tn,
                transpose_rhs=False):
    w = pl.program_id(1)
    t = wt[w]
    acc = jax.lax.dot_general(
        lhs_ref[...],
        rhs_ref[0],
        (((1,), (1,) if transpose_rhs else (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    rows = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
    mask = (rows >= ws[w]) & (rows < we[w])
    # same-tile successor: keep the previous visitor's rows; first visitor
    # zero-fills (uninitialized VMEM is only ever read through the select)
    same = jnp.logical_and(w > 0, wt[jnp.maximum(w - 1, 0)] == t)
    cur = out_ref[...]
    prev = jnp.where(same, cur, jnp.zeros_like(cur))
    out_ref[...] = jnp.where(mask, acc.astype(cur.dtype), prev)


def _gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray,
         interpret: bool = False, transpose_rhs: bool = False) -> jnp.ndarray:
    """lhs [M, K] (rows sorted by group) @ rhs [G, K, N] → [M, N].

    ``transpose_rhs``: rhs is [G, N, K] and contracts on its LAST dim —
    the backward's dlhs = dout @ W^T without materializing a transposed
    copy of the stacked weights (rhs.swapaxes(1, 2) costs a full relayout
    write per call)."""
    M, K = lhs.shape
    if transpose_rhs:
        G, N, _ = rhs.shape
    else:
        G, _, N = rhs.shape
    out_dtype = lhs.dtype
    tm, tn = _gmm_tiles(K, N, lhs.dtype)
    Mp, Kp, Np = _round_up(M, tm), _round_up(K, 128), _round_up(N, tn)
    if (Mp, Kp) != (M, K):
        lhs = jnp.pad(lhs, ((0, Mp - M), (0, Kp - K)))
    if transpose_rhs:
        if (Kp, Np) != (K, N):
            rhs = jnp.pad(rhs, ((0, 0), (0, Np - N), (0, Kp - K)))
    elif (Kp, Np) != (K, N):
        rhs = jnp.pad(rhs, ((0, 0), (0, Kp - K), (0, Np - N)))

    wg, wt, ws, we = _plan(group_sizes, Mp, tm, G)
    W = Mp // tm + G
    grid = (Np // tn, W)

    rhs_spec = (
        pl.BlockSpec((1, tn, Kp), lambda n, w, wg, wt, ws, we: (wg[w], n, 0))
        if transpose_rhs
        else pl.BlockSpec((1, Kp, tn), lambda n, w, wg, wt, ws, we: (wg[w], 0, n))
    )
    out = pl.pallas_call(
        functools.partial(
            _gmm_kernel, tm=tm, tn=tn, transpose_rhs=transpose_rhs
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, Kp), lambda n, w, wg, wt, ws, we: (wt[w], 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, w, wg, wt, ws, we: (wt[w], n)),
        ),
        out_shape=_out_sds((Mp, Np), out_dtype, lhs, rhs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="gmm",
    )(wg, wt, ws, we, lhs, rhs)
    return out[:M, :N]


def _group_edges(wg, w, W: int):
    """(first, last): whether unit ``w`` is the first / the last of its
    group's run in a plan with ``empty_units`` (every group holds a unit and
    the units past the plan's total stay on the last group). A kernel whose
    output block is the GROUP's slab zeroes its accumulator at ``first`` and
    writes the slab, once, at ``last``."""
    g = wg[w]
    first = jnp.logical_or(w == 0, wg[jnp.maximum(w - 1, 0)] != g)
    last = jnp.logical_or(w == W - 1, wg[jnp.minimum(w + 1, W - 1)] != g)
    return first, last


def _tgmm_kernel(wg, wt, ws, we, lhs_ref, dout_ref, out_ref, acc, *, tm, W):
    w = pl.program_id(2)
    first, last = _group_edges(wg, w, W)

    @pl.when(first)
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(we[w] > ws[w])
    def _():
        rows = wt[w] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mask = (rows >= ws[w]) & (rows < we[w])
        lhs_tile = lhs_ref[...]
        lhs = jnp.where(mask, lhs_tile, jnp.zeros_like(lhs_tile))
        acc[...] += jax.lax.dot_general(
            lhs,
            dout_ref[...],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(last)
    def _():
        out_ref[0] = acc[...].astype(out_ref.dtype)


def _tgmm(lhs: jnp.ndarray, dout: jnp.ndarray, group_sizes: jnp.ndarray,
          interpret: bool = False, out_dtype=jnp.float32) -> jnp.ndarray:
    """Per-group lhs_g^T @ dout_g: [M, K] × [M, N] → [G, K, N], summed in
    fp32 and rounded once to ``out_dtype``; an empty group's slab is zeros."""
    M, K = lhs.shape
    _, N = dout.shape
    G = group_sizes.shape[0]
    tm, tk, tn = _tgmm_tiles(K, N, lhs.dtype)
    Mp, Kp, Np = _round_up(M, tm), _round_up(K, tk), _round_up(N, tn)
    if (Mp, Kp) != (M, K):
        lhs = jnp.pad(lhs, ((0, Mp - M), (0, Kp - K)))
    if (Mp, Np) != (M, N):
        dout = jnp.pad(dout, ((0, Mp - M), (0, Np - N)))

    wg, wt, ws, we = _plan(group_sizes, Mp, tm, G, empty_units=True)
    W = Mp // tm + G
    grid = (Kp // tk, Np // tn, W)

    out = pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, W=W),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda k, n, w, wg, wt, ws, we: (wt[w], k)),
                pl.BlockSpec((tm, tn), lambda k, n, w, wg, wt, ws, we: (wt[w], n)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda k, n, w, wg, wt, ws, we: (wg[w], k, n)
            ),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=_out_sds((G, Kp, Np), out_dtype, lhs, dout),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="tgmm",
    )(wg, wt, ws, we, lhs, dout)
    return out[:, :K, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(lhs, rhs, group_sizes, interpret=False, transpose_rhs=False):
    return _gmm(lhs, rhs, group_sizes, interpret=interpret,
                transpose_rhs=transpose_rhs)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, interpret, transpose_rhs):
    return (
        _gmm(lhs, rhs, group_sizes, interpret=interpret,
             transpose_rhs=transpose_rhs),
        (lhs, rhs, group_sizes),
    )


def _match_vma(ct, primal):
    """Inside a check_vma shard_map region a custom-VJP cotangent must vary
    exactly as its primal does. A cotangent naturally varies over the UNION
    of the incoming gradient's and the other operand's axes; any axis the
    primal does not vary over means the primal was (conceptually) broadcast
    there — whose AD transpose is the psum this inserts (the replicated-
    weight gradient reduction shard_map's own transpose would have done)."""
    want = jax.typeof(primal).vma
    have = jax.typeof(ct).vma
    if have - want:
        ct = jax.lax.psum(ct, tuple(sorted(have - want)))
    return ct


def _grouped_matmul_bwd(interpret, transpose_rhs, res, dout):
    lhs, rhs, group_sizes = res
    # dlhs contracts rhs on the axis OPPOSITE the forward's — both cases run
    # straight off the stored layout (no rhs.swapaxes materialization)
    dlhs = _gmm(dout, rhs, group_sizes, interpret=interpret,
                transpose_rhs=not transpose_rhs)
    # y = lhs @ rhs^T → drhs[g, n, k] = Σ_m dout[m, n] · lhs[m, k]
    a, b = (dout, lhs) if transpose_rhs else (lhs, dout)
    drhs = _tgmm(a, b, group_sizes, interpret=interpret, out_dtype=rhs.dtype)
    return (
        _match_vma(dlhs.astype(lhs.dtype), lhs),
        _match_vma(drhs, rhs),
        None,
    )


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def ragged_dot(
    lhs: jnp.ndarray,
    rhs: jnp.ndarray,
    group_sizes: jnp.ndarray,
    *,
    interpret: bool | None = None,
    platform: str | None = None,
    transpose_rhs: bool = False,
) -> jnp.ndarray:
    """Drop-in for `jax.lax.ragged_dot`: Pallas gmm on TPU (or under
    AUTOMODEL_GMM_INTERPRET=1 anywhere), XLA's ragged_dot elsewhere.

    PRECONDITION (TPU path): rows at indices >= sum(group_sizes) are NOT
    covered by any work unit and return uninitialized memory — callers must
    either have sum(group_sizes) == lhs rows (the MoE dispatch paths do:
    group sizes are exact bincounts of the picks) or never read the tail
    (the a2a path's sentinel rows route to an explicit zero row instead).
    Zeroing the tail here would cost an [M, N] select per call on the
    hottest op in the MoE step."""
    if interpret is None:
        interpret = _interpret_requested()
    if not (interpret or _pallas_eligible(platform)):
        if transpose_rhs:
            rhs = rhs.swapaxes(1, 2)
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    return _grouped_matmul(lhs, rhs, group_sizes, interpret, transpose_rhs)
