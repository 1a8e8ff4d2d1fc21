"""Fused MoE expert MLP: gate_up matmul + gated activation + down matmul in
ONE Pallas kernel, with a purpose-tiled Pallas manual backward.

Forward motivation (a round-4 profile of the MoE step): the two-kernel expert path writes
the [T·K, 2I] gate_up output and the [T·K, I] activation to HBM and reads
them back (~600MB per layer at bench shape). Here both stay in VMEM: per
work unit (m-tile × group) the kernel loops I-chunks on the grid, computing
``acc += act(lhs @ Wgu[:, chunk]) @ Wd[chunk, :]`` with an fp32 accumulator
— the down-projection contraction is summable over I-chunks, so the
intermediate never materializes. Rows are lhs-masked (write-only outputs;
boundary tiles accumulate across consecutive work units like
ops/grouped_matmul._tgmm).

The grid is the static worst case ``Mp / tm + G`` work units, and only the
units that hold rows cost anything: `_plan` hands every unit past the
non-empty groups' tiles an empty row window, and such a unit runs no product
(``pl.when``) and names the weight blocks the step before it left resident
(`_weight_block`), so the pipeline copies nothing for it. A decode step
whose 512 rows touch 110 of 256 experts streams those 110 experts' weights,
not 258 x one expert's (PERF.md, PR 34). `work_units` counts both for the
serving engine's ``serve.counts``.

Backward motivation (docs/history/PROFILE_MOE_r05.md): a backward composed
of generic ``_tgmm``/transpose-GEMM calls gives the forward win back (34.40 ms
fused FWD+BWD vs 33.53 unfused; gmm2-class tiles ran 84.3 TFLOP/s vs
gmm1's 107.0). The backward here is three purpose-tiled kernels that fold
the dgate·dup activation-backward elementwise chain (and the sentinel-tail
``dout`` mask) in-kernel, so ``dg``/``du``/``mid`` never materialize in HBM
and ``lhs`` is read once for both weight grads:

- ``_bwd_gu``   — dWg, dWu (+ dgb, dub row sums) in one pass over lhs.
- ``_bwd_dwd``  — dWd (+ ddb) with the activation mid recomputed in-kernel.
- ``_bwd_dx``   — dlhs = dg·Wg^T + du·Wu^T fused over I-chunks.

Each result leaves its kernel once, in the form the caller wants: a weight
gradient is summed over its group's units in an fp32 VMEM scratch and
written at the group's last unit in the WEIGHT's dtype (no fp32 slab in
HBM, no cast pass), a fused [G, D, 2I] weight gets ONE [G, D, 2I] cotangent
from `_bwd_gu` (no concatenate), an empty group's zeros are written by the
one unit the backward's plan gives it (no select over [G, ·, ·]), and no
operand is padded at 128-aligned widths (PERF.md, PR 40).

Tile shapes come from the pickers beside each kernel (the shape and
`_VMEM_BUDGET`); the NaN-tail masking semantics from PR 5 are
preserved bit-for-bit — every row outside a work unit's window (boundary
rows of the neighbouring group AND the a2a sentinel tail) is zeroed on the
``dout`` side in-kernel, where 0·NaN can no longer survive.

Operand forms: gate and up as two [G, D, I] arrays, or ``up=None`` and
``gate`` the stored fused [G, D, 2I] weight. The second costs no copy: the
forward and all three backward kernels block both halves out of the one
array (and g/u out of one [M, 2I] product) by a column-block offset in the
up operand's index map (`_halves`, `_col_off`) — same blocks, same
arithmetic, bit-equal results. A per-call ``jnp.split`` of the weight was
43 % of every serve program at 256 experts x 3072 x 1536 (PERF.md, PR 26).

Same dropless semantics and work-unit plan as ops/grouped_matmul (reference
capability: the fused SwiGLU+GEMM epilogues TE/DeepEP provide on GPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from automodel_tpu.ops.grouped_matmul import (
    _chunks,
    _group_edges,
    _interpret_requested,
    _pallas_eligible,
    _plan,
    _round_up,
    ragged_dot,
)


def _kernel(wg, wt, ws, we, lhs_ref, wg_ref, wu_ref, wd_ref, *rest,
            tm, n_ic, act_kind, limit, W, has_bias):
    if has_bias:
        gb_ref, ub_ref, db_ref, out_ref, acc = rest
    else:
        out_ref, acc = rest
    w = pl.program_id(0)
    ic = pl.program_id(1)
    t = wt[w]
    first = jnp.logical_or(w == 0, wt[jnp.maximum(w - 1, 0)] != t)
    last = jnp.logical_or(w == W - 1, wt[jnp.minimum(w + 1, W - 1)] != t)

    @pl.when(jnp.logical_and(ic == 0, first))
    def _():
        acc[...] = jnp.zeros_like(acc)

    # a unit past the plan's total has an empty row window (`_plan`): it
    # multiplies nothing and, by `_weight_block`, fetches nothing. It shares
    # the last live unit's tile, so `first` is false for it and `last` true
    # only at W - 1, where the accumulator is still that unit's (zeros when
    # no group holds a row at all)
    @pl.when(we[w] > ws[w])
    def _():
        rows = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        lmask = (rows >= ws[w]) & (rows < we[w])
        lhs = jnp.where(lmask, lhs_ref[...], jnp.zeros_like(lhs_ref))

        # gate and up are SEPARATE [Dp, ic] blocks: of two [G, D, I] arrays,
        # or of the two halves of the ONE stored [G, D, 2I] array (`_fwd`
        # passes it twice and offsets the up block's column index). A
        # column-interleaved operand would need a host-side concat +
        # transpose whose AD transpose leaks a non-default layout onto the
        # weight grads, forcing full-size fp32 relayout copies in every
        # downstream elementwise consumer (optimizer, grad-norm)
        g = jax.lax.dot_general(
            lhs, wg_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [tm, ic_size]
        u = jax.lax.dot_general(
            lhs, wu_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if has_bias:
            g = g + gb_ref[0, 0, 0].astype(jnp.float32)
            u = u + ub_ref[0, 0, 0].astype(jnp.float32)
            # gpt-oss-style expert biases: once added, masked rows are no
            # longer zero (act(bias)·Wd ≠ 0) — re-mask mid before the down
            # contraction and gate the down bias on the same row window (each
            # work unit adds it exactly once, on its first I-chunk, to its
            # own rows only).
            @pl.when(ic == 0)
            def _():
                acc[...] += jnp.where(
                    lmask, db_ref[0, 0].astype(jnp.float32), 0.0
                )
        mid = _act_core(g, u, act_kind, limit)
        if has_bias:
            mid = jnp.where(lmask, mid, 0.0)
        acc[...] += jax.lax.dot_general(
            mid.astype(lhs_ref.dtype), wd_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jnp.logical_and(ic == n_ic - 1, last))
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


_IC_CANDS = (512, 384, 256, 128)


def _divisor_chunk(n128: int, cap: int = 512) -> int:
    """Largest 128-multiple ≤ cap dividing the 128-padded dim — a
    non-divisor pads up to a chunk multiple and burns the padding as real
    matmul work (I=768 with ic=512 pads to 1024: +33% expert FLOPs,
    measured 29.4% vs 31.5% MFU on the qwen-style bench fingerprint).
    128 divides any 128-multiple, so this always finds a divisor."""
    return next(c for c in _IC_CANDS if c <= cap and c <= n128 and n128 % c == 0)


def _halves(gate, up):
    """(gate operand, up operand, I) of the two forms every kernel here
    takes: two [.., I] arrays, or ``up=None`` and ``gate`` holding both
    halves side by side as [.., 2I] (gate columns first) — then the SAME
    array is handed to the pallas_call twice and the up BlockSpec adds a
    whole number of column blocks (`_col_off`), so nothing is copied."""
    if up is None:
        return gate, gate, gate.shape[-1] // 2
    return gate, up, gate.shape[-1]


def _col_off(fused: bool, I: int, block: int) -> int:
    """Column-BLOCK offset of the up half inside a fused [.., 2I] array."""
    if not fused:
        return 0
    assert I % block == 0, (I, block)  # in_place_ok + the divisor-chunk pickers
    return I // block


def _weight_block(w, i, wg, ws, we, n_ic: int):
    """(group, I-chunk) of the weight blocks grid step ``(w, i)`` names. A
    live unit walks its group's chunks; an empty one (row window ``0, 0``,
    its group clamped onto the last live unit's by `_plan`) stays on that
    unit's LAST chunk, so every block index equals the step's before and the
    pipeline issues no copy: an empty unit costs no weight traffic."""
    return wg[w], jnp.where(we[w] > ws[w], i, n_ic - 1)


def in_place_ok(D: int, I: int) -> bool:
    """Whether the halves of a fused [G, D, 2I] weight can be blocked in
    place: unaligned widths are padded by every kernel here, which is a
    copy already, so those keep the split-then-pad path."""
    return D % 128 == 0 and I % 128 == 0


_FWD_STACK = 14 * 1024 * 1024  # what the forward's blocks may fill of the ~16MB default scoped stack


def _fwd_vmem(tm: int, ic: int, Dp: int) -> int:
    """The forward's VMEM: double-buffered input blocks + output + fp32
    accumulator."""
    return (
        2 * (tm * Dp * 2)          # lhs
        + 2 * (Dp * 2 * ic * 2)    # wgu chunk
        + 2 * (ic * Dp * 2)        # wd chunk
        + 2 * (tm * Dp * 2)        # out
        + tm * Dp * 4              # acc scratch
    )


def _fwd_tiles(D: int, I: int) -> tuple[int, int]:
    """(tm, ic) of the forward: 512 rows and the largest divisor chunk of I,
    rows halved to 256 and then the chunk shrunk until the blocks fit the
    default scoped-vmem stack (Mosaic rejects the kernel at compile otherwise
    — hit at D=1536 with the 512/512 tiles). A hidden size so wide that no
    tile fits it (D=3584: 16.5MB at 256/128) is picked again under
    `_VMEM_BUDGET`, and the call then asks Mosaic for `_VMEM_LIMIT` as the
    backward kernels do (`_fwd_vmem_limit`). That pick, (256, 512) at 3584 x
    1024, was made to fit and timed afterwards (TPU v5 lite, PR 43's review,
    10,240 buffer rows over 8 experts, ms a forward at 512 live rows an
    expert / at a full buffer): (256, 512) 0.661 / 1.496; (256, 256) 0.679 /
    1.525; (256, 1024) 0.644 / 1.353; (512, 256) 0.563 / 1.514; (512, 512)
    0.555 / 1.500 (42 MB by `_fwd_vmem`, over the budget); (128, 512) 1.121 /
    2.631; (128, 1024) 0.722 / 1.437. 512 rows a tile are 16 % faster at the
    balanced share, a whole-width chunk 10 % at a full buffer: ≈ 0.1 ms a
    call either way, left to a `perf_opt` PR."""
    Dp = _round_up(D, 128)
    I128 = _round_up(I, 128)

    def pick(budget: int) -> tuple[int, int]:
        tm, ic = 512, _divisor_chunk(I128)
        while _fwd_vmem(tm, ic, Dp) > budget and tm > 256:
            tm //= 2
        while _fwd_vmem(tm, ic, Dp) > budget:
            smaller = [c for c in _IC_CANDS if c < ic and I128 % c == 0]
            if not smaller:
                break
            ic = smaller[0]
        return tm, ic

    tm, ic = pick(_FWD_STACK)
    if _fwd_vmem(tm, ic, Dp) > _FWD_STACK:
        tm, ic = pick(_VMEM_BUDGET)
    return tm, ic


def _fwd_vmem_limit(tm: int, ic: int, Dp: int) -> dict:
    """The compiler parameter a forward whose blocks overflow the default
    stack needs; nothing for every shape that fits it."""
    return {"vmem_limit_bytes": _VMEM_LIMIT} if _fwd_vmem(tm, ic, Dp) > _FWD_STACK else {}


def work_units(group_sizes, M: int, D: int, I: int):
    """(live, grid) work units of the forward over ``M`` rows sorted into
    groups of ``group_sizes`` [..., G] (traced or not; leading axes are
    calls, summed): the grid is `_fwd`'s static ``Mp / tm + G`` a call, by
    its own picker; live are the units `_plan` gives a row window (a
    non-empty group's tiles). Only those fetch weights and multiply."""
    tm, _ = _fwd_tiles(D, I)
    gs = jnp.asarray(group_sizes).astype(jnp.int32)
    ends = jnp.cumsum(gs, axis=-1)
    starts = ends - gs
    tiles = jnp.where(gs > 0, (ends - 1) // tm - starts // tm + 1, 0)
    calls = gs.size // gs.shape[-1]
    return tiles.sum(), calls * (_round_up(M, tm) // tm + gs.shape[-1])


def _fwd(lhs, gate, up, down, group_sizes, gb, ub, db, act_kind, limit,
         interpret):
    """lhs [M, D] sorted by group; gate/up [G, D, I] halves, or up=None and
    gate the fused [G, D, 2I] (`_halves`); down [G, I, D]; optional
    per-expert biases gb/ub [G, I], db [G, D] (gpt-oss) → [M, D]."""
    M, D = lhs.shape
    fused = up is None
    gate, up, I = _halves(gate, up)
    G = gate.shape[0]
    has_bias = gb is not None or ub is not None or db is not None
    Dp = _round_up(D, 128)
    I128 = _round_up(I, 128)
    tm, ic = _fwd_tiles(D, I)
    Mp, Ip = _round_up(M, tm), _round_up(I128, ic)
    if (Mp, Dp) != (M, D):
        lhs = jnp.pad(lhs, ((0, Mp - M), (0, Dp - D)))
    if (Dp, Ip) != (D, I):
        assert not fused, (D, I)  # fused_expert_mlp splits unaligned widths
        gate = jnp.pad(gate, ((0, 0), (0, Dp - D), (0, Ip - I)))
        up = jnp.pad(up, ((0, 0), (0, Dp - D), (0, Ip - I)))
        down = jnp.pad(down, ((0, 0), (0, Ip - I), (0, Dp - D)))
    # gate/up/down are blocked DIRECTLY from their stored [G, D, I] (or
    # fused [G, D, 2I]) / [G, I, D] layouts — no split, no concat, no
    # transpose: a transposed weight
    # operand's AD transpose emits the weight grads in a non-default layout,
    # and every fp32 elementwise consumer downstream (Adam, grad-norm) then
    # pays a full-size relayout copy (2.25GB per stacked expert tensor at
    # the MoE bench shape; the difference between fitting and OOM on 16GB)
    n_ic = Ip // ic
    off = _col_off(fused, I, ic)

    def wblock(at):
        """BlockSpec index map of a weight operand: ``at(group, chunk)``."""
        return lambda w, i, wg, wt, ws, we: at(
            *_weight_block(w, i, wg, ws, we, n_ic)
        )

    operands = [lhs, gate, up, down]
    in_specs = [
        pl.BlockSpec((tm, Dp), lambda w, i, wg, wt, ws, we: (wt[w], 0)),
        pl.BlockSpec((1, Dp, ic), wblock(lambda g, c: (g, 0, c))),
        pl.BlockSpec((1, Dp, ic), wblock(lambda g, c: (g, 0, c + off))),
        pl.BlockSpec((1, ic, Dp), wblock(lambda g, c: (g, c, 0))),
    ]
    if has_bias:
        zeros_i = jnp.zeros((G, I), lhs.dtype)
        gb = zeros_i if gb is None else gb
        ub = zeros_i if ub is None else ub
        db = jnp.zeros((G, D), lhs.dtype) if db is None else db
        # the unit axis before the lane dim keeps Mosaic's sublane tiling
        # rule satisfied (block dim == array dim == 1); without it a block
        # of 1 over the G (resp. n_ic) sublane axis fails lowering
        gb = jnp.pad(gb, ((0, 0), (0, Ip - I))).reshape(G, n_ic, 1, ic)
        ub = jnp.pad(ub, ((0, 0), (0, Ip - I))).reshape(G, n_ic, 1, ic)
        operands += [
            gb, ub, jnp.pad(db, ((0, 0), (0, Dp - D))).reshape(G, 1, Dp)
        ]
        in_specs += [
            pl.BlockSpec((1, 1, 1, ic), wblock(lambda g, c: (g, c, 0, 0))),
            pl.BlockSpec((1, 1, 1, ic), wblock(lambda g, c: (g, c, 0, 0))),
            pl.BlockSpec((1, 1, Dp), lambda w, i, wg, wt, ws, we: (wg[w], 0, 0)),
        ]

    wg, wt, ws, we = _plan(group_sizes, Mp, tm, G)
    W = Mp // tm + G

    # inside a check_vma shard_map region (the a2a_fused EP path) the
    # pallas_call output aval must carry the manual-axes vma explicitly
    from automodel_tpu.ops.grouped_matmul import _out_sds

    out_sds = _out_sds((Mp, Dp), lhs.dtype, lhs, gate, up, down)

    out = pl.pallas_call(
        functools.partial(
            _kernel, tm=tm, n_ic=n_ic, act_kind=act_kind, limit=limit, W=W,
            has_bias=has_bias,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(W, n_ic),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (tm, Dp), lambda w, i, wg, wt, ws, we: (wt[w], 0)
            ),
            scratch_shapes=[pltpu.VMEM((tm, Dp), jnp.float32)],
        ),
        out_shape=out_sds,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            **_fwd_vmem_limit(tm, ic, Dp),
        ),
        interpret=interpret,
        name="fused_expert_mlp_fwd",
    )(wg, wt, ws, we, *operands)
    return out[:M, :D]


def _reference(lhs, gate, up, down, group_sizes, gb, ub, db, act_kind, limit,
               platform):
    """The two-grouped-matmul composition — the backward path and the
    numerics reference. up=None: ``gate`` is the fused [G, D, 2I] weight;
    one grouped matmul, and the small [M, 2I] product is what is split."""
    if up is None:
        gu_g, gu_u = jnp.split(
            ragged_dot(lhs, gate, group_sizes, platform=platform), 2, axis=-1
        )
    else:
        gu_g = ragged_dot(lhs, gate, group_sizes, platform=platform)
        gu_u = ragged_dot(lhs, up, group_sizes, platform=platform)
    if gb is not None or ub is not None or db is not None:
        # row r belongs to group g iff cumsum[g-1] <= r < cumsum[g]
        bounds = jnp.cumsum(group_sizes.astype(jnp.int32))
        row_g = jnp.searchsorted(
            bounds, jnp.arange(lhs.shape[0], dtype=jnp.int32), side="right"
        )
    if gb is not None:
        gu_g = gu_g + gb.astype(gu_g.dtype)[row_g]
    if ub is not None:
        gu_u = gu_u + ub.astype(gu_u.dtype)[row_g]
    if act_kind == "swiglu_oai":
        g = jnp.minimum(gu_g, 7.0)
        u = jnp.clip(gu_u, -7.0, 7.0)
        mid = (u + 1.0) * (g * jax.nn.sigmoid(1.702 * g))
    else:
        mid = jax.nn.silu(gu_g)
        if limit is not None:
            mid = jnp.minimum(mid, limit)
            gu_u = jnp.clip(gu_u, -limit, limit)
        mid = mid * gu_u
    out = ragged_dot(mid.astype(lhs.dtype), down, group_sizes, platform=platform)
    if db is not None:
        out = out + db.astype(out.dtype)[row_g]
    return out


def fused_expert_mlp(lhs, gate, up, down, group_sizes,
                     gb=None, ub=None, db=None,
                     act_kind="swiglu", limit=None, platform=None,
                     interpret=None):
    """The fused expert MLP. ``gate``/``up`` are the [G, D, I] halves, or
    ``up=None`` and ``gate`` is the stored fused [G, D, 2I] weight (gate
    columns first, NOT gpt-oss's interleave): the kernels then block both
    halves out of it in place and its gradient comes back as one
    [G, D, 2I] array. Widths the kernels would pad anyway (`in_place_ok`)
    are split here instead — the pad is already a copy."""
    if up is None and not in_place_ok(lhs.shape[1], gate.shape[-1] // 2):
        gate, up = jnp.split(gate, 2, axis=-1)
    return _fused_expert_mlp(lhs, gate, up, down, group_sizes, gb, ub, db,
                             act_kind, limit, platform, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _fused_expert_mlp(lhs, gate, up, down, group_sizes, gb, ub, db,
                      act_kind, limit, platform, interpret):
    """Forward through the fused kernel; backward through the purpose-tiled
    manual kernels below (the bwd needs the g/u intermediates anyway — a
    remat-style re-run of the cheap gate_up GEMMs feeds them without ever
    materializing the activation chain)."""
    if interpret is None:
        interpret = _interpret_requested()
    if not (interpret or _pallas_eligible(platform)):
        return _reference(lhs, gate, up, down, group_sizes, gb, ub, db,
                          act_kind, limit, platform)
    return _fwd(lhs, gate, up, down, group_sizes, gb, ub, db, act_kind, limit,
                interpret)


def _vjp_fwd(lhs, gate, up, down, group_sizes, gb, ub, db,
             act_kind, limit, platform, interpret):
    y = _fused_expert_mlp(
        lhs, gate, up, down, group_sizes, gb, ub, db,
        act_kind, limit, platform, interpret
    )
    return y, (lhs, gate, up, down, group_sizes, gb, ub, db)


def _act_core(g32, u32, act_kind, limit):
    """The post-bias elementwise activation on fp32 values — ONE definition
    shared by the forward kernel, the backward kernels (which jax.vjp it
    tile-wise for exact clamp-aware derivatives), and `_act_fn`."""
    if act_kind == "swiglu_oai":
        gc = jnp.minimum(g32, 7.0)
        uc = jnp.clip(u32, -7.0, 7.0)
        return (uc + 1.0) * (gc * jax.nn.sigmoid(1.702 * gc))
    mid = jax.nn.silu(g32)
    if limit is not None:
        mid = jnp.minimum(mid, limit)
        u32 = jnp.clip(u32, -limit, limit)
    return mid * u32


def _act_fn(g, u, act_kind, limit):
    """The post-bias elementwise activation, in fp32 internally (matches the
    kernel); jax.vjp of THIS gives exact clamp-aware derivatives."""
    return _act_core(
        g.astype(jnp.float32), u.astype(jnp.float32), act_kind, limit
    ).astype(g.dtype)


def _act_grads(g, u, dmid, act_kind, limit):
    """(dg, du) fp32 of the elementwise chain — the exact jax.vjp of
    `_act_core`, evaluated tile-wise inside the backward kernels (all VPU
    work; the MXU contraction overlaps it)."""
    g32, u32 = g.astype(jnp.float32), u.astype(jnp.float32)
    _, vjp = jax.vjp(lambda a, b: _act_core(a, b, act_kind, limit), g32, u32)
    return vjp(dmid.astype(jnp.float32))


# -- purpose-tiled backward kernels -----------------------------------------
#
# All three share the grouped-matmul work-unit plan (scalar-prefetched
# (group, m-tile, row-window) tuples) and fold the activation backward and
# the row-window mask in-kernel. The row window doubles as the sentinel-tail
# mask: rows past sum(group_sizes) belong to no window, so their NaN/Inf
# garbage is zeroed on the dout side BEFORE any contraction — the PR 5
# semantics, now without the external [M, N] selects.
#
# Every result leaves its kernel once, in the form the caller wants. The two
# weight-gradient kernels sum a group's units in an fp32 VMEM scratch and
# write the group's slab at its LAST unit, rounded once to the weight's
# dtype; their plan gives an empty group one unit (`_plan(...,
# empty_units=True)`), which writes that group's zeros. `_bwd_gu`'s out block
# spans both halves, so a fused [G, D, 2I] weight gets ONE [G, D, 2I]
# cotangent straight from the kernel.

# The scoped limit the three backward kernels ask Mosaic for, and the part of
# it their pickers may fill with blocks (the rest is the kernels' own stack:
# the activation chain's fp32 tiles, a product before it is added). A v5e
# core has 128 MiB of VMEM; the 16 MiB scoped default cannot hold a weight
# slab's fp32 scratch beside its double-buffered out block at the widths
# that read each operand once (PERF.md, PR 40).
_VMEM_LIMIT = 64 * 1024 * 1024
_VMEM_BUDGET = 40 * 1024 * 1024


def _bwd_gu_budget_ok(tm, tk, tn, I, itemsize, out_itemsize):
    need = (
        2 * itemsize * tm * tk              # lhs block
        + 3 * 2 * itemsize * tm * tn        # g / u / dmid chunks
        + 4 * tk * 2 * I                    # fp32 scratch, both halves
        + 2 * out_itemsize * tk * 2 * I     # the [tk, 2I] out block(s)
    )
    return need <= _VMEM_BUDGET


def _bwd_dwd_budget_ok(tm, tk, tn, itemsize, out_itemsize):
    need = (
        2 * 2 * itemsize * tm * tk          # g / u blocks
        + 2 * itemsize * tm * tn            # dy block
        + 4 * tk * tn                       # fp32 scratch
        + 2 * out_itemsize * tk * tn        # dWd out block
    )
    return need <= _VMEM_BUDGET


def _bwd_dx_budget_ok(tm, tn, ic, itemsize):
    need = (
        3 * 2 * itemsize * tm * ic      # g / u / dmid chunks
        + 2 * 2 * itemsize * tn * ic    # gate / up chunks
        + 2 * itemsize * tm * tn        # out block
        + 4 * tm * tn                   # acc scratch
    )
    return need <= _VMEM_BUDGET


def _bwd_tiles(budget_ok, passes, a, b):
    """(tm, chunk of a, chunk of b): of the divisor chunks whose blocks fit
    `_VMEM_BUDGET`, the pair with the fewest ``passes(ca, cb)`` over the
    kernel's operands (ties: the wider blocks), at 256 rows and, if nothing
    fits, at 128. The chunks divide the 128-padded dims, so at `in_place_ok`
    widths (fused [.., 2I] operands read in place) nothing but rows is ever
    padded.

    Timed on one v5e at the train cell's shape (65,536 rows in 128 groups of
    455–570, D 2048, I 768, bf16; PERF.md, PR 40), ms a call as (tm, a, b):
    `_bwd_gu` (512, 512, 384) 7.22, (256, 512, 384) 6.48, (256, 512, 768)
    5.55, (256, 1024, 768) 4.56, (512, 2048, 768) 4.86, **(256, 2048, 768)
    4.14**, (128, 2048, 768) 4.10; `_bwd_dwd` (512, 384, 512) 5.15,
    (256, 768, 512) 3.75, (256, 768, 1024) 2.90, (512, 768, 2048) 2.75,
    **(256, 768, 2048) 2.40**, (128, 768, 2048) 2.27; `_bwd_dx`
    (512, 512, 384) 6.51, (256, 512, 768) 4.57, (256, 1024, 768) 4.07,
    (512, 2048, 768) 4.70, **(256, 2048, 768) 3.82**, (128, 2048, 768) 3.81.
    A group there holds about 512 rows, so a 512-row tile multiplies as many
    masked rows as real ones and a 256-row tile half as many; every wider
    chunk is one pass fewer over the row operands."""
    for tm in (256, 128):
        fits = [
            (passes(ca, cb), -ca * cb, ca, cb)
            for ca in _chunks(a) for cb in _chunks(b) if budget_ok(tm, ca, cb)
        ]
        if fits:
            return (tm, *min(fits)[2:])
    return 128, 128, 128


def _bwd_gu_tiles(D, I, dtype, out_dtype):
    """(tm, tk over D, tn over I): g / u / dmid are read once a tk pass (and
    their activation chain evaluated once a pass), then the widest I-chunk."""
    it, ito = jnp.dtype(dtype).itemsize, jnp.dtype(out_dtype).itemsize
    Dp, Ip = _round_up(D, 128), _round_up(I, 128)
    return _bwd_tiles(
        lambda tm, tk, tn: _bwd_gu_budget_ok(tm, tk, tn, Ip, it, ito),
        lambda tk, tn: (Dp // tk, Ip // tn), D, I,
    )


def _bwd_dwd_tiles(I, D, dtype, out_dtype):
    """(tm, tk over I, tn over D): g and u are read once a tn pass, dy once
    a tk pass."""
    it, ito = jnp.dtype(dtype).itemsize, jnp.dtype(out_dtype).itemsize
    Ip, Dp = _round_up(I, 128), _round_up(D, 128)
    return _bwd_tiles(
        lambda tm, tk, tn: _bwd_dwd_budget_ok(tm, tk, tn, it, ito),
        lambda tk, tn: 2 * Dp // tn + Ip // tk, I, D,
    )


def _bwd_dx_tiles(D, I, dtype):
    """(tm, tn over D, ic over I): g / u / dmid are read once a tn pass."""
    it = jnp.dtype(dtype).itemsize
    Dp, Ip = _round_up(D, 128), _round_up(I, 128)
    return _bwd_tiles(
        lambda tm, tn, ic: _bwd_dx_budget_ok(tm, tn, ic, it),
        lambda tn, ic: (Dp // tn, Ip // ic), D, I,
    )


def _bwd_gu_kernel(wg, wt, ws, we, lhs_ref, g_ref, u_ref, dmid_ref, *rest,
                   tm, tn, n_n, W, act_kind, limit, has_bias, fused):
    """Grid (k over D, w, n over I). ``acc[c]`` / ``acc[n_n + c]`` hold the
    group's [tk, tn] gate / up sums of I-chunk ``c``; the out block spans all
    of I (of 2I when ``fused``) and is written at the group's last step."""
    n_out = 1 if fused else 2
    outs, rest = rest[:n_out], rest[n_out:]
    if has_bias:
        dgb_ref, dub_ref, acc, bacc = rest
    else:
        (acc,) = rest
    w = pl.program_id(1)
    n = pl.program_id(2)
    first, last = _group_edges(wg, w, W)

    @pl.when(first)
    def _():
        acc[n] = jnp.zeros(acc.shape[1:], acc.dtype)
        acc[n_n + n] = jnp.zeros(acc.shape[1:], acc.dtype)
        if has_bias:
            bacc[n] = jnp.zeros(bacc.shape[1:], bacc.dtype)
            bacc[n_n + n] = jnp.zeros(bacc.shape[1:], bacc.dtype)

    @pl.when(we[w] > ws[w])
    def _():
        rows = wt[w] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mask = (rows >= ws[w]) & (rows < we[w])
        dg, du = _act_grads(
            g_ref[...], u_ref[...], dmid_ref[...], act_kind, limit
        )
        # dout mask folded in-kernel: rows outside this unit's window are the
        # neighbouring group's rows (boundary tile) or the a2a sentinel tail —
        # whose g/u/dmid can be NaN, which an lhs-only mask cannot neutralize
        dg = jnp.where(mask, dg, 0.0)
        du = jnp.where(mask, du, 0.0)
        lhs = jnp.where(mask, lhs_ref[...], jnp.zeros_like(lhs_ref))
        acc[n] += jax.lax.dot_general(
            lhs, dg.astype(lhs_ref.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc[n_n + n] += jax.lax.dot_general(
            lhs, du.astype(lhs_ref.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if has_bias:
            # bias grads are the dg/du row sums. Their block ignores the k
            # grid dim, so every k pass recomputes and rewrites the IDENTICAL
            # totals (same rows, same dg)
            bacc[n] += dg.sum(axis=0, keepdims=True)
            bacc[n_n + n] += du.sum(axis=0, keepdims=True)

    @pl.when(jnp.logical_and(last, n == n_n - 1))
    def _():
        up_ref, up_col = (outs[0], n_n * tn) if fused else (outs[1], 0)
        for c in range(n_n):
            outs[0][0, :, c * tn:(c + 1) * tn] = acc[c].astype(outs[0].dtype)
            up_ref[0, :, up_col + c * tn:up_col + (c + 1) * tn] = (
                acc[n_n + c].astype(up_ref.dtype)
            )
            if has_bias:
                dgb_ref[0, :, c * tn:(c + 1) * tn] = bacc[c]
                dub_ref[0, :, c * tn:(c + 1) * tn] = bacc[n_n + c]


def _bwd_gu(lhs, g, u, dmid, group_sizes, act_kind, limit, interpret,
            has_bias, out_dtype):
    """One pass over lhs → (dWg [G,D,I], dWu, dgb [G,I] f32 | None,
    dub | None), the weight gradients in ``out_dtype``. The dgate·dup chain
    runs in-kernel on the g/u/dmid tiles. u=None: ``g`` is the fused [M, 2I]
    product, read in place (`_halves`), and dWg is the ONE fused [G,D,2I]
    cotangent (gate columns first), dWu None."""
    from automodel_tpu.ops.grouped_matmul import _out_sds

    M, D = lhs.shape
    fused = u is None
    g, u, I = _halves(g, u)
    G = group_sizes.shape[0]
    tm, tk, tn = _bwd_gu_tiles(D, I, lhs.dtype, out_dtype)
    Mp, Kp, Np = _round_up(M, tm), _round_up(D, tk), _round_up(I, tn)
    assert not fused or (Kp, Np) == (D, I), (D, I, tk, tn)  # `in_place_ok`
    off = _col_off(fused, I, tn)
    if (Mp, Kp) != (M, D):
        lhs = jnp.pad(lhs, ((0, Mp - M), (0, Kp - D)))
    if (Mp, Np) != (M, I):
        pad = ((0, Mp - M), (0, Np - I))  # fused: rows only (exact tiles)
        g = jnp.pad(g, pad)
        u = g if fused else jnp.pad(u, pad)
        dmid = jnp.pad(dmid, pad)
    wg, wt, ws, we = _plan(group_sizes, Mp, tm, G, empty_units=True)
    W = Mp // tm + G
    n_n = Np // tn
    grid = (Kp // tk, W, n_n)
    in_specs = [
        pl.BlockSpec((tm, tk), lambda k, w, n, wg, wt, ws, we: (wt[w], k)),
        pl.BlockSpec((tm, tn), lambda k, w, n, wg, wt, ws, we: (wt[w], n)),
        pl.BlockSpec((tm, tn), lambda k, w, n, wg, wt, ws, we: (wt[w], n + off)),
        pl.BlockSpec((tm, tn), lambda k, w, n, wg, wt, ws, we: (wt[w], n)),
    ]
    width = 2 * Np if fused else Np
    slab = pl.BlockSpec(
        (1, tk, width), lambda k, w, n, wg, wt, ws, we: (wg[w], k, 0)
    )
    out_specs = [slab] if fused else [slab, slab]
    out_shapes = [
        _out_sds((G, Kp, width), out_dtype, lhs, g, u, dmid)
        for _ in out_specs
    ]
    scratch = [pltpu.VMEM((2 * n_n, tk, tn), jnp.float32)]
    if has_bias:
        brow = pl.BlockSpec(
            (1, 1, Np), lambda k, w, n, wg, wt, ws, we: (wg[w], 0, 0)
        )
        out_specs += [brow, brow]
        out_shapes += [
            _out_sds((G, 1, Np), jnp.float32, g, dmid),
            _out_sds((G, 1, Np), jnp.float32, u, dmid),
        ]
        scratch.append(pltpu.VMEM((2 * n_n, 1, tn), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(
            _bwd_gu_kernel, tm=tm, tn=tn, n_n=n_n, W=W, act_kind=act_kind,
            limit=limit, has_bias=has_bias, fused=fused,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="fused_expert_mlp_bwd_gu",
    )(wg, wt, ws, we, lhs, g, u, dmid)
    if fused:
        dwg, dwu, bias = outs[0], None, outs[1:]
    else:
        dwg, dwu, bias = outs[0][:, :D, :I], outs[1][:, :D, :I], outs[2:]
    if not has_bias:
        return dwg, dwu, None, None
    return dwg, dwu, bias[0][:, 0, :I], bias[1][:, 0, :I]


def _bwd_dwd_kernel(wg, wt, ws, we, g_ref, u_ref, dy_ref, dwd_ref, *rest,
                    tm, W, act_kind, limit, want_db):
    if want_db:
        ddb_ref, acc = rest
    else:
        (acc,) = rest
    w = pl.program_id(2)
    first, last = _group_edges(wg, w, W)

    @pl.when(first)
    def _():
        acc[...] = jnp.zeros_like(acc)
        if want_db:
            ddb_ref[...] = jnp.zeros_like(ddb_ref)

    @pl.when(we[w] > ws[w])
    def _():
        rows = wt[w] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mask = (rows >= ws[w]) & (rows < we[w])
        mid = _act_core(
            g_ref[...].astype(jnp.float32), u_ref[...].astype(jnp.float32),
            act_kind, limit,
        )
        mid = jnp.where(mask, mid, 0.0)
        # dy's sentinel tail is masked here, in-kernel: no [M, D] select
        # outside the kernel
        dy = jnp.where(mask, dy_ref[...], jnp.zeros_like(dy_ref))
        acc[...] += jax.lax.dot_general(
            mid.astype(dy_ref.dtype), dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if want_db:
            # the [1, tn] row stays resident over a group's units (its block
            # ignores k: every k pass rewrites the identical totals)
            ddb_ref[0] += dy.astype(jnp.float32).sum(axis=0, keepdims=True)

    @pl.when(last)
    def _():
        dwd_ref[0] = acc[...].astype(dwd_ref.dtype)


def _bwd_dwd(g, u, dy, group_sizes, act_kind, limit, interpret, want_db,
             out_dtype):
    """Down-proj transpose GEMM with the activation mid recomputed in-kernel
    → (dWd [G,I,D] in ``out_dtype``, ddb [G,D] f32 | None). u=None: ``g`` is
    the fused [M, 2I] product, read in place (`_halves`)."""
    from automodel_tpu.ops.grouped_matmul import _out_sds

    M = g.shape[0]
    fused = u is None
    g, u, I = _halves(g, u)
    _, D = dy.shape
    G = group_sizes.shape[0]
    tm, tk, tn = _bwd_dwd_tiles(I, D, g.dtype, out_dtype)
    Mp, Kp, Np = _round_up(M, tm), _round_up(I, tk), _round_up(D, tn)
    off = _col_off(fused, I, tk)
    if (Mp, Kp) != (M, I):
        pad = ((0, Mp - M), (0, Kp - I))  # fused: rows only (exact tiles)
        g = jnp.pad(g, pad)
        u = g if fused else jnp.pad(u, pad)
    if (Mp, Np) != (M, D):
        dy = jnp.pad(dy, ((0, Mp - M), (0, Np - D)))
    wg, wt, ws, we = _plan(group_sizes, Mp, tm, G, empty_units=True)
    W = Mp // tm + G
    grid = (Kp // tk, Np // tn, W)
    in_specs = [
        pl.BlockSpec((tm, tk), lambda k, n, w, wg, wt, ws, we: (wt[w], k)),
        pl.BlockSpec((tm, tk), lambda k, n, w, wg, wt, ws, we: (wt[w], k + off)),
        pl.BlockSpec((tm, tn), lambda k, n, w, wg, wt, ws, we: (wt[w], n)),
    ]
    out_specs = [
        pl.BlockSpec((1, tk, tn), lambda k, n, w, wg, wt, ws, we: (wg[w], k, n)),
    ]
    out_shapes = [_out_sds((G, Kp, Np), out_dtype, g, u, dy)]
    if want_db:
        out_specs.append(
            pl.BlockSpec((1, 1, tn), lambda k, n, w, wg, wt, ws, we: (wg[w], 0, n))
        )
        out_shapes.append(_out_sds((G, 1, Np), jnp.float32, dy))
    outs = pl.pallas_call(
        functools.partial(
            _bwd_dwd_kernel, tm=tm, W=W, act_kind=act_kind, limit=limit,
            want_db=want_db,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="fused_expert_mlp_bwd_dwd",
    )(wg, wt, ws, we, g, u, dy)
    return outs[0][:, :I, :D], outs[1][:, 0, :D] if want_db else None


def _bwd_dx_kernel(wg, wt, ws, we, g_ref, u_ref, dmid_ref, gate_ref, up_ref,
                   out_ref, acc, *, tm, n_ic, act_kind, limit, W):
    w = pl.program_id(1)
    i = pl.program_id(2)
    t = wt[w]
    first = jnp.logical_or(w == 0, wt[jnp.maximum(w - 1, 0)] != t)
    last = jnp.logical_or(w == W - 1, wt[jnp.minimum(w + 1, W - 1)] != t)

    @pl.when(jnp.logical_and(i == 0, first))
    def _():
        acc[...] = jnp.zeros_like(acc)

    rows = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mask = (rows >= ws[w]) & (rows < we[w])
    dg, du = _act_grads(g_ref[...], u_ref[...], dmid_ref[...], act_kind, limit)
    # boundary tiles: the other group's rows must not meet THIS group's
    # weights — mask before the contraction (accumulation across consecutive
    # work units blends the two groups' halves, exactly like the forward)
    dg = jnp.where(mask, dg, 0.0)
    du = jnp.where(mask, du, 0.0)
    cd = out_ref.dtype
    acc[...] += jax.lax.dot_general(
        dg.astype(cd), gate_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        du.astype(cd), up_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(jnp.logical_and(i == n_ic - 1, last))
    def _():
        out_ref[...] = acc[...].astype(cd)


def _bwd_dx(g, u, dmid, gate, up, group_sizes, interpret, act_kind, limit):
    """dlhs = dg·Wg^T + du·Wu^T in one kernel, I-chunked with an fp32
    accumulator (the forward's summable-contraction trick, transposed).
    Sentinel-tail rows come out zero or stay unwritten — the a2a consumer
    never reads them (ragged_dot precondition). u=None / up=None: ``g`` is
    the fused [M, 2I] product / ``gate`` the fused [G, D, 2I] weight, read
    in place (`_halves`)."""
    from automodel_tpu.ops.grouped_matmul import _out_sds

    M = g.shape[0]
    fused_m, fused_w = u is None, up is None
    g, u, I = _halves(g, u)
    gate, up, _ = _halves(gate, up)
    G, D, _ = gate.shape
    tm, tn, ic = _bwd_dx_tiles(D, I, g.dtype)
    Mp, Np, Ip = _round_up(M, tm), _round_up(D, tn), _round_up(I, ic)
    off_m, off_w = _col_off(fused_m, I, ic), _col_off(fused_w, I, ic)
    if (Mp, Ip) != (M, I):
        pad = ((0, Mp - M), (0, Ip - I))  # fused: rows only (exact tiles)
        g = jnp.pad(g, pad)
        u = g if fused_m else jnp.pad(u, pad)
        dmid = jnp.pad(dmid, pad)
    if (Np, Ip) != (D, I):
        assert not fused_w, (D, I, tn, ic)
        wpad = ((0, 0), (0, Np - D), (0, Ip - I))
        gate, up = jnp.pad(gate, wpad), jnp.pad(up, wpad)
    n_ic = Ip // ic
    wg, wt, ws, we = _plan(group_sizes, Mp, tm, G)
    W = Mp // tm + G
    grid = (Np // tn, W, n_ic)
    mrow = pl.BlockSpec((tm, ic), lambda n, w, i, wg, wt, ws, we: (wt[w], i))
    mrow_u = pl.BlockSpec(
        (tm, ic), lambda n, w, i, wg, wt, ws, we: (wt[w], i + off_m)
    )
    wslab = pl.BlockSpec((1, tn, ic), lambda n, w, i, wg, wt, ws, we: (wg[w], n, i))
    wslab_u = pl.BlockSpec(
        (1, tn, ic), lambda n, w, i, wg, wt, ws, we: (wg[w], n, i + off_w)
    )
    out = pl.pallas_call(
        functools.partial(
            _bwd_dx_kernel, tm=tm, n_ic=n_ic, act_kind=act_kind, limit=limit,
            W=W,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[mrow, mrow_u, mrow, wslab, wslab_u],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, w, i, wg, wt, ws, we: (wt[w], n)
            ),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=_out_sds((Mp, Np), g.dtype, g, u, dmid, gate, up),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="fused_expert_mlp_bwd_dx",
    )(wg, wt, ws, we, g, u, dmid, gate, up)
    return out[:M, :D]


def _vjp_bwd(act_kind, limit, platform, interpret, res, dy):
    from automodel_tpu.ops.grouped_matmul import (
        _match_vma,
        _pallas_eligible,
    )

    lhs, gate, up, down, group_sizes, gb, ub, db = res
    if interpret is None:
        interpret = _interpret_requested()
    mv = lambda ct, p: None if ct is None else _match_vma(ct, p)

    if not (interpret or _pallas_eligible(platform)):
        # non-pallas backends: AD through the XLA composition
        def f(args):
            lhs_, g_, u_, d_, gb_, ub_, db_ = args
            return _reference(lhs_, g_, u_, d_, group_sizes, gb_, ub_, db_,
                              act_kind, limit, platform)

        _, vjp = jax.vjp(f, (lhs, gate, up, down, gb, ub, db))
        (dl, dg_, du_, dd, dgb, dub, ddb), = vjp(dy)
        return (
            mv(dl, lhs), mv(dg_, gate), mv(du_, up), mv(dd, down), None,
            mv(dgb, gb), mv(dub, ub), mv(ddb, db),
        )

    # purpose-tiled manual backward: recompute the cheap gate_up GEMMs
    # (g, u) and the dmid transpose GEMM, then run the three fused kernels.
    # mid/dg/du (and masked copies of them) never materialize, lhs is read
    # once for both weight grads, and the sentinel-tail dout mask + the
    # bias-grad row sums are folded in-kernel: 6 grouped passes in all.
    # With the fused weight (up=None) g and u are ONE
    # [M, 2I] product of one pass over lhs, and the three kernels block
    # their g/u/gate/up operands out of the fused arrays in place.
    kw = dict(platform=platform, interpret=interpret)
    M = lhs.shape[0]
    G = gate.shape[0]
    g = ragged_dot(lhs, gate, group_sizes, **kw)
    u = None if up is None else ragged_dot(lhs, up, group_sizes, **kw)
    has_bias = gb is not None or ub is not None or db is not None
    if has_bias:
        bounds = jnp.cumsum(group_sizes.astype(jnp.int32))
        valid = (jnp.arange(M, dtype=jnp.int32) < bounds[-1])[:, None]
        row_g = jnp.searchsorted(
            bounds, jnp.arange(M, dtype=jnp.int32), side="right"
        )
        # tail rows land on row_g == G: clamp the gather index explicitly
        # and zero the gathered bias under the mask — never rely on XLA's
        # out-of-bounds clamp semantics for rows whose content is garbage
        # anyway
        row_gc = jnp.minimum(row_g, G - 1)
        bias_rows = lambda b: jnp.where(valid, b.astype(g.dtype)[row_gc], 0)
    if up is None and (gb is not None or ub is not None):
        # one [G, 2I] row of both biases for the one [M, 2I] product
        zeros_i = jnp.zeros((G, gate.shape[-1] // 2), g.dtype)
        g = g + bias_rows(jnp.concatenate(
            [zeros_i if b is None else b.astype(g.dtype) for b in (gb, ub)],
            axis=-1,
        ))
    else:
        if gb is not None:
            g = g + bias_rows(gb)
        if ub is not None:
            u = u + bias_rows(ub)

    dmid = ragged_dot(dy, down, group_sizes, transpose_rhs=True, **kw)
    # the weight gradients leave their kernels in the weight's dtype and
    # layout (up=None: ONE [G, D, 2I] array, dWu None)
    dWd, ddb = _bwd_dwd(
        g, u, dy, group_sizes, act_kind, limit, interpret, db is not None,
        down.dtype,
    )
    dWg, dWu, dgb, dub = _bwd_gu(
        lhs, g, u, dmid, group_sizes, act_kind, limit, interpret,
        gb is not None or ub is not None, gate.dtype,
    )
    # dlhs tail rows stay zero/uninitialized — they ARE the sentinel tail,
    # and the a2a consumer never reads them (ragged_dot precondition)
    dlhs = _bwd_dx(g, u, dmid, gate, up, group_sizes, interpret, act_kind,
                   limit)
    return (
        mv(dlhs.astype(lhs.dtype), lhs),
        mv(dWg, gate),
        mv(dWu, up),
        mv(dWd, down),
        None,
        mv(dgb.astype(gb.dtype), gb) if gb is not None else None,
        mv(dub.astype(ub.dtype), ub) if ub is not None else None,
        mv(ddb.astype(db.dtype), db) if db is not None else None,
    )


_fused_expert_mlp.defvjp(_vjp_fwd, _vjp_bwd)
