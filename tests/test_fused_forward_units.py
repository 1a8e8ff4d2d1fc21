"""The fused expert forward pays only for work units that hold rows.

`_fwd`'s grid is the static worst case ``Mp / tm + G``; `_plan` clamps the
units past the non-empty groups' tiles onto the last live one with an empty
row window. Such a unit must run no product (``pl.when``) and name the weight
blocks the step before it left resident (`_weight_block`), and the output must
stay BIT-equal to the kernel that multiplied zeros and fetched again: the
oracle below is that kernel's body and index rule, kept verbatim. Interpret
mode executes the real Pallas kernel on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from automodel_tpu.ops import fused_expert_mlp as fem
from automodel_tpu.ops.grouped_matmul import _plan, _round_up


def _old_kernel(wg, wt, ws, we, lhs_ref, wg_ref, wu_ref, wd_ref, *rest,
                tm, n_ic, act_kind, limit, W, has_bias):
    """`fused_expert_mlp._kernel` as it stood before empty units were
    skipped (PR 31's tree): every unit, live or not, runs all three products
    over an lhs masked to zeros."""
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

    rows = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    lmask = (rows >= ws[w]) & (rows < we[w])
    lhs = jnp.where(lmask, lhs_ref[...], jnp.zeros_like(lhs_ref))
    g = jax.lax.dot_general(
        lhs, wg_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    u = jax.lax.dot_general(
        lhs, wu_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if has_bias:
        g = g + gb_ref[0, 0, 0].astype(jnp.float32)
        u = u + ub_ref[0, 0, 0].astype(jnp.float32)

        @pl.when(ic == 0)
        def _():
            acc[...] += jnp.where(
                lmask, db_ref[0, 0].astype(jnp.float32), 0.0
            )
    mid = fem._act_core(g, u, act_kind, limit)
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


@pytest.fixture
def oracle(monkeypatch):
    """→ ``call(fn)``: run ``fn`` with the forward as it stood: the old body,
    and every unit walking its group's chunks ``i = 0 … n_ic - 1``."""

    def call(fn):
        with monkeypatch.context() as m:
            m.setattr(fem, "_kernel", _old_kernel)
            m.setattr(fem, "_weight_block", lambda w, i, wg, ws, we, n_ic: (wg[w], i))
            return fn()

    return call


def _sizes(G, at):
    sizes = np.zeros(G, np.int64)
    for g, n in at.items():
        sizes[g] = n
    return sizes


def _operands(seed, M, D, I, sizes, fused, biased, nan_tail, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    G = len(sizes)
    mk = lambda *s: rng.normal(size=s).astype(np.float32)
    lhs = mk(M, D)
    if nan_tail:
        lhs[int(sizes.sum()):] = np.nan
    gate_up = mk(G, D, 2 * I) * 0.3
    if fused:
        gate, up = jnp.asarray(gate_up, dtype), None
    else:
        gate, up = (jnp.asarray(h, dtype) for h in np.split(gate_up, 2, axis=-1))
    biases = [jnp.asarray(mk(G, n), dtype) if biased else None for n in (I, I, D)]
    return (jnp.asarray(lhs, dtype), gate, up, jnp.asarray(mk(G, I, D) * 0.3, dtype),
            jnp.asarray(sizes, jnp.int32), *biases)


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


# D 128 keeps the row tile at 512; I 256 is one chunk, I 640 five of 128
CASES = {
    # G = 64 experts, five of them routed: 59 empty units behind the live ones
    "five-of-64": dict(M=96, I=640, sizes=_sizes(64, {3: 20, 17: 1, 18: 40, 40: 7, 63: 28})),
    "five-of-64-fused-biased": dict(M=96, I=256, fused=True, biased=True,
                                    sizes=_sizes(64, {0: 9, 5: 30, 31: 2, 32: 50, 62: 5})),
    "only-first": dict(M=64, I=640, sizes=_sizes(16, {0: 64})),
    "only-first-fused": dict(M=64, I=256, fused=True, sizes=_sizes(16, {0: 33})),
    "only-last": dict(M=64, I=640, biased=True, sizes=_sizes(16, {15: 64})),
    "only-last-fused": dict(M=64, I=640, fused=True, sizes=_sizes(16, {15: 40})),
    # no group holds a row (an EP shard that received nothing): tile 0 zeros
    "all-empty": dict(M=64, I=640, sizes=_sizes(8, {}), nan_tail=True),
    "all-empty-fused-biased": dict(M=64, I=256, fused=True, biased=True, sizes=_sizes(8, {})),
    # group 2 straddles the edge of row tile 0 (rows 400 … 699), empties after
    "straddle": dict(M=1024, I=256, sizes=_sizes(12, {0: 400, 2: 300, 3: 324})),
    "straddle-then-empty": dict(M=1024, I=640, fused=True, biased=True,
                                sizes=_sizes(12, {0: 400, 2: 300})),
    # the a2a sentinel tail: NaN rows past the last group, in a live tile
    "nan-tail": dict(M=128, I=640, sizes=_sizes(64, {7: 30, 8: 11, 50: 29}), nan_tail=True),
    "nan-tail-fused": dict(M=128, I=256, fused=True, sizes=_sizes(64, {1: 60}), nan_tail=True),
    "nan-tail-biased-oai": dict(M=640, I=640, biased=True, act="swiglu_oai",
                                sizes=_sizes(8, {1: 500, 2: 30}), nan_tail=True),
    "bf16-fused": dict(M=96, I=640, fused=True, dtype=jnp.bfloat16,
                       sizes=_sizes(64, {3: 20, 17: 1, 18: 40, 40: 7, 63: 28})),
}


@pytest.mark.parametrize("name", list(CASES))
def test_forward_with_empty_units_is_bit_equal_to_the_old_kernel(name, oracle):
    case = dict(CASES[name])
    M, I, sizes = case.pop("M"), case.pop("I"), case.pop("sizes")
    act = case.pop("act", "swiglu")
    dtype = case.get("dtype", jnp.float32)
    D = 128
    ops = _operands(sum(map(ord, name)), M, D, I, sizes, case.get("fused", False),
                    case.get("biased", False), case.get("nan_tail", False), dtype)
    tm, ic = fem._fwd_tiles(D, I)
    assert (tm, _round_up(I, 128) // ic) == (512, 1 if I == 256 else 5)
    live, grid = fem.work_units(sizes, M, D, I)
    assert grid == _round_up(M, tm) // tm + len(sizes) and int(live) < grid

    run = lambda: fem._fwd(*ops, act, None, True)
    new, old = run(), oracle(run)
    n_real = int(sizes.sum())
    # rows of tiles the plan never visits are never written, by either kernel
    written = M if n_real else min(M, tm)
    np.testing.assert_array_equal(_bits(new)[:written], _bits(old)[:written])
    if not n_real:
        assert not np.asarray(new[:written], np.float32).any()
        return
    assert np.isfinite(np.asarray(new[:n_real], np.float32)).all()
    clean = (jnp.nan_to_num(ops[0]), *ops[1:])
    ref = fem._reference(*clean, act, None, None)
    want = np.asarray(ref[:n_real], np.float32)
    # bf16: the kernel rounds the activation once, the reference three times
    tol = 2e-4 if dtype == jnp.float32 else 2e-2 * np.abs(want).max()
    np.testing.assert_allclose(np.asarray(new[:n_real], np.float32), want, atol=tol, rtol=2e-4)


def test_grad_through_the_forward_with_empty_units(oracle):
    """The custom VJP's residuals are the operands, not anything the forward
    kernel made: every gradient bit-equal to the old forward's."""
    sizes = _sizes(64, {3: 20, 17: 1, 18: 40, 40: 7, 63: 28})
    lhs, gate, up, down, gs, gb, ub, db = _operands(5, 96, 128, 256, sizes, False, True, False)

    def loss(lhs, gate, up, down, gb, ub, db):
        y = fem.fused_expert_mlp(lhs, gate, up, down, gs, gb, ub, db,
                                 "swiglu", None, None, True)
        return (y * jnp.cos(jnp.arange(y.size, dtype=y.dtype).reshape(y.shape))).sum()

    # a new jit a call: the second is traced while the oracle stands in
    run = lambda: jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7))))(
        lhs, gate, up, down, gb, ub, db)
    (y_new, g_new), (y_old, g_old) = run(), oracle(run)
    assert _bits(y_new) == _bits(y_old)
    for a, b in zip(g_new, g_old):
        np.testing.assert_array_equal(_bits(a), _bits(b))
    touched = np.flatnonzero(sizes)
    assert np.asarray(g_new[1])[touched].any() and not np.delete(np.asarray(g_new[1]), touched, 0).any()


def _walk(sizes, M, D, I):
    """Every grid step's weight-block index, in the order the grid runs."""
    tm, ic = fem._fwd_tiles(D, I)
    n_ic = _round_up(I, 128) // ic
    Mp, G = _round_up(M, tm), len(sizes)
    wg, _, ws, we = (np.asarray(a) for a in _plan(jnp.asarray(sizes, jnp.int32), Mp, tm, G))
    W = Mp // tm + G
    w, i = np.divmod(np.arange(W * n_ic), n_ic)
    g, c = fem._weight_block(w, i, wg, ws, we, n_ic)
    return np.stack([np.asarray(g), np.asarray(c)], axis=1), int((we > ws).sum()), W, n_ic


@pytest.mark.parametrize(
    "M,D,I,G,routed,W",
    [
        (512, 3072, 1536, 256, 111, 258),  # serve-chat-minimax-m2's decode step
        (512, 2048, 1792, 32, 31, 34),     # serve-chat-lfm2-8b-a1b's, a layer
        (512, 3072, 1536, 256, 0, 258),    # nothing routed at all
        (96, 128, 640, 64, 5, 65),
    ],
)
def test_weight_blocks_change_only_in_live_units(M, D, I, G, routed, W):
    """Walking the whole grid by the kernel's own index rule: the weight block
    changes ``n_ic`` times a live unit (once to enter it, then a chunk a step)
    and never in an empty one; `work_units` counts the same live units."""
    rng = np.random.default_rng(G + routed)
    sizes = np.zeros(G, np.int64)
    if routed:
        picked = rng.choice(G, size=routed, replace=False)
        sizes[picked] = 1 + rng.multinomial(M - routed, np.ones(routed) / routed)
    blocks, live, grid, n_ic = _walk(sizes, M, D, I)
    assert grid == W and live >= routed  # a group over a tile edge is two units
    assert tuple(map(int, fem.work_units(sizes, M, D, I))) == (live, W)
    changes = int((blocks[1:] != blocks[:-1]).any(axis=1).sum())
    # the very first step's fetch is not a change from a step before it
    assert changes == max(live * n_ic - 1, 0)
    if live < W:
        assert (blocks[live * n_ic:] == blocks[max(live * n_ic - 1, 0)]).all()


# -- the backward's plan must not leak into the forward (PR 40) ---------------
#
# The weight-gradient kernels give an empty group one unit so that they can
# write its zero slab (`_plan(..., empty_units=True)`). The forward's
# dead-unit skip, and `expert_grid_live_pct` through `work_units`, rest on
# the opposite: an empty group holds NO unit.


@pytest.mark.parametrize(
    "sizes",
    [[300, 0, 0, 212], [0, 0, 512, 0], [0, 512], [512, 0], [0, 0, 0], [100, 200, 212]],
    ids=["run-inside", "all-but-one", "first", "last", "all", "none"],
)
def test_an_empty_group_holds_no_unit_in_the_forwards_plan(sizes):
    tm = fem._fwd_tiles(128, 128)[0]
    sizes = [s * tm // 256 for s in sizes]  # the ids' sizes are for tm 256
    G, Mp = len(sizes), 2 * tm
    gs = jnp.asarray(sizes, jnp.int32)
    W = Mp // tm + G
    wg, wt, ws, we = (np.asarray(a) for a in _plan(gs, Mp, tm, G))
    live = we > ws
    assert set(wg[live]) == {g for g, s in enumerate(sizes) if s > 0}
    n_live, grid = fem.work_units(gs, Mp, 128, 128)
    assert (int(n_live), int(grid)) == (int(live.sum()), W)
    # every row is in exactly one live unit's window
    covered = np.zeros(Mp, np.int32)
    for w in np.flatnonzero(live):
        lo, hi = max(ws[w], wt[w] * tm), min(we[w], (wt[w] + 1) * tm)
        covered[lo:hi] += 1
    assert (covered[: sum(sizes)] == 1).all() and (covered[sum(sizes):] == 0).all()

    # the weight-gradient plan: the same live units, and one empty-window
    # unit for each empty group, every group visited in order, tiles in range
    bg, bt, bs, be = (np.asarray(a) for a in _plan(gs, Mp, tm, G, empty_units=True))
    blive = be > bs
    assert int(blive.sum()) == int(live.sum())
    assert [tuple(r) for r in np.stack([bg, bt, bs, be], 1)[blive]] == [
        tuple(r) for r in np.stack([wg, wt, ws, we], 1)[live]
    ]
    assert sorted(set(bg)) == list(range(G))
    assert (np.diff(bg) >= 0).all() and (np.diff(bg) <= 1).all()
    assert bt.min() >= 0 and bt.max() < Mp // tm
    for g, s in enumerate(sizes):
        if s == 0:
            assert not blive[bg == g].any()
