"""Purpose-tiled fused expert-MLP backward (ops/fused_expert_mlp) parity.

Interpret mode executes the REAL Pallas kernel code on CPU — same scheme as
the splash/gmm tests. The manual backward (PR 10: `_bwd_gu`/`_bwd_dwd`/
`_bwd_dx`, activation-backward chain + sentinel-tail dout mask folded
in-kernel) must match jax.vjp through the `_reference` two-gmm composition
for every grad — dlhs, dWg, dWu, dWd, and the bias grads — including the
PR 5 planted-garbage-tail case and ragged group sizes with empty experts.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops.fused_expert_mlp import _reference, fused_expert_mlp

GRAD_NAMES = ("dlhs", "dWg", "dWu", "dWd", "dgb", "dub", "ddb")


def _case(rng, M, D, I, G, sizes, biased, dtype=jnp.float32):
    gs = jnp.asarray(sizes, jnp.int32)
    assert int(gs.sum()) <= M
    mk = lambda *s: jnp.asarray(rng.normal(size=s), dtype)
    lhs = mk(M, D)
    gate, up = mk(G, D, I) * 0.3, mk(G, D, I) * 0.3
    down = mk(G, I, D) * 0.3
    gb = mk(G, I) if biased else None
    ub = mk(G, I) if biased else None
    db = mk(G, D) if biased else None
    dy = mk(M, D)
    return lhs, gate, up, down, gs, gb, ub, db, dy


def _pulled(fn, args, dy):
    """(``fn``'s output, its cotangents for ``dy``) as ONE compiled program:
    called op by op, the glue around the kernels is a program a primitive."""
    def run(args, dy):
        y, vjp = jax.vjp(fn, *args)
        return y, vjp(dy)

    return jax.jit(run)(tuple(args), dy)


def _both(lhs, gate, up, down, gs, gb, ub, db, dy, act, limit):
    biased = gb is not None
    args = (lhs, gate, up, down) + ((gb, ub, db) if biased else ())

    def f_new(*a):
        b = a[4:] if biased else (None, None, None)
        return fused_expert_mlp(a[0], a[1], a[2], a[3], gs, *b,
                                act, limit, None, True)

    def f_ref(*a):
        b = a[4:] if biased else (None, None, None)
        return _reference(a[0], a[1], a[2], a[3], gs, *b, act, limit, None)

    y1, g1 = _pulled(f_new, args, dy)
    y2, g2 = _pulled(f_ref, args, dy)
    return y1, g1, y2, g2


@pytest.mark.parametrize(
    "act,limit,biased,sizes",
    [
        ("swiglu", None, False, [40, 0, 30, 58]),   # empty expert mid-list
        ("swiglu", 2.0, True, [1, 63, 0, 64]),      # clamp grads + boundary
        ("swiglu_oai", None, True, [0, 50, 50, 28]),  # empty FIRST expert
        ("swiglu_oai", None, False, [32, 32, 32, 32]),
    ],
)
def test_manual_backward_parity(act, limit, biased, sizes):
    rng = np.random.default_rng(0)
    lhs, gate, up, down, gs, gb, ub, db, dy = _case(
        rng, 128, 96, 80, 4, sizes, biased
    )
    y1, g1, y2, g2 = _both(lhs, gate, up, down, gs, gb, ub, db, dy, act, limit)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    names = GRAD_NAMES[: len(g1)]
    for n, a, b in zip(names, g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4,
            err_msg=f"{n} ({act}, limit={limit}, biased={biased})",
        )


def test_manual_backward_parity_garbage_tail():
    """Rows past sum(group_sizes) carry NaN in BOTH the inputs and the
    cotangents (the a2a sentinel-tail contract). Every weight/bias grad must
    stay finite AND match the reference computed on clean real rows; dlhs is
    only compared on real rows (tail rows are dont-care by contract)."""
    rng = np.random.default_rng(7)
    M, D, I, G, n_real = 96, 64, 48, 3, 70
    sizes = [30, 0, 40]
    lhs, gate, up, down, gs, gb, ub, db, dy = _case(
        rng, M, D, I, G, sizes, biased=True
    )
    lhs_n = np.array(lhs)  # copies — np.asarray of a jax array is read-only
    dy_n = np.array(dy)
    lhs_n[n_real:] = np.nan
    dy_n[n_real:] = np.nan
    lhs_dirty, dy_dirty = jnp.asarray(lhs_n), jnp.asarray(dy_n)

    # reference grads on a CLEAN tail (zeros) — what the masked kernels must
    # reproduce despite the garbage
    lhs_clean = jnp.asarray(np.where(np.isfinite(lhs_n), lhs_n, 0.0))
    dy_clean = jnp.asarray(np.where(np.isfinite(dy_n), dy_n, 0.0))

    def f_new(l, g_, u_, d_, gb_, ub_, db_):
        return fused_expert_mlp(l, g_, u_, d_, gs, gb_, ub_, db_,
                                "swiglu_oai", None, None, True)

    def f_ref(l, g_, u_, d_, gb_, ub_, db_):
        return _reference(l, g_, u_, d_, gs, gb_, ub_, db_,
                          "swiglu_oai", None, None)

    _, g1 = _pulled(f_new, (lhs_dirty, gate, up, down, gb, ub, db), dy_dirty)
    _, g2 = _pulled(f_ref, (lhs_clean, gate, up, down, gb, ub, db), dy_clean)
    for n, a, b in zip(GRAD_NAMES, g1, g2):
        a, b = np.asarray(a), np.asarray(b)
        if n == "dlhs":
            a, b = a[:n_real], b[:n_real]
        assert np.isfinite(a).all(), f"{n} poisoned by NaN tail"
        np.testing.assert_allclose(a, b, atol=5e-4, err_msg=n)
        assert np.abs(a).max() > 0.0, f"{n} all-zero"


def test_empty_expert_grads_zero():
    rng = np.random.default_rng(3)
    lhs, gate, up, down, gs, gb, ub, db, dy = _case(
        rng, 64, 32, 32, 4, [30, 0, 34, 0], biased=True
    )

    def f(g_, u_, d_, gb_, ub_, db_):
        return fused_expert_mlp(lhs, g_, u_, d_, gs, gb_, ub_, db_,
                                "swiglu", None, None, True)

    _, grads = _pulled(f, (gate, up, down, gb, ub, db), dy)
    for n, g in zip(GRAD_NAMES[1:], grads):
        g = np.asarray(g)
        assert np.abs(g[1]).max() == 0.0, f"{n}[empty expert 1] nonzero"
        assert np.abs(g[3]).max() == 0.0, f"{n}[empty expert 3] nonzero"
        assert np.abs(g[0]).max() > 0.0, f"{n}[expert 0] all-zero"


def test_manual_backward_bfloat16_smoke():
    """bf16 end-to-end through the new kernels (the bench dtype): finite and
    roughly matching the fp32 reference."""
    rng = np.random.default_rng(9)
    lhs, gate, up, down, gs, gb, ub, db, dy = _case(
        rng, 64, 32, 32, 2, [40, 24], biased=False, dtype=jnp.bfloat16
    )

    def f(l, g_, u_, d_):
        return fused_expert_mlp(l, g_, u_, d_, gs, None, None, None,
                                "swiglu", None, None, True)

    _, grads = _pulled(f, (lhs, gate, up, down), dy)
    _, ref32 = _pulled(
        lambda l, g_, u_, d_: _reference(
            l, g_, u_, d_, gs, None, None, None, "swiglu", None, None
        ),
        tuple(a.astype(jnp.float32) for a in (lhs, gate, up, down)),
        dy.astype(jnp.float32),
    )
    for n, a, b in zip(GRAD_NAMES, grads, ref32):
        a = np.asarray(a.astype(jnp.float32))
        assert np.isfinite(a).all(), n
        np.testing.assert_allclose(a, np.asarray(b), atol=0.15, rtol=0.1,
                                   err_msg=n)


# -- the fused [G, D, 2I] weight read in place (up=None) ---------------------
#
# The kernels block the gate and the up half out of ONE stored array by a
# column-block offset in the up BlockSpec's index map; same blocks, same
# arithmetic in the same order, so the result must be BIT-equal to handing
# them the two pre-split copies, and the [G, D, 2I] cotangent must equal the
# concatenate of the pre-split path's two.

IN_PLACE_CASES = [
    # id, D, I, dtype, act, biased, tail (rows past sum(group_sizes)), path
    ("i128-off1", 128, 128, jnp.float32, "swiglu", False, 0, "in_place"),
    ("i768-off2", 128, 768, jnp.float32, "swiglu", False, 0, "in_place"),
    ("i1536-off3", 128, 1536, jnp.float32, "swiglu", False, 0, "in_place"),
    ("i768-bf16", 256, 768, jnp.bfloat16, "swiglu", False, 0, "in_place"),
    ("i128-biased-oai", 128, 128, jnp.float32, "swiglu_oai", True, 0,
     "in_place"),
    ("i768-biased-limit", 128, 768, jnp.float32, "swiglu", True, 0,
     "in_place"),
    ("i1536-nan-tail", 128, 1536, jnp.float32, "swiglu", False, 26,
     "in_place"),
    ("i128-biased-nan-tail", 128, 128, jnp.float32, "swiglu", True, 26,
     "in_place"),
    # widths the kernels pad anyway: the op splits the weight itself
    ("i80-unaligned-falls-back", 128, 80, jnp.float32, "swiglu", False, 0,
     "op_splits"),
    ("d96-unaligned-falls-back", 96, 128, jnp.float32, "swiglu", True, 0,
     "op_splits"),
    # gpt-oss's column interleave: no block index expresses gu[..., ::2]
    ("interleaved-falls-back", 128, 128, jnp.float32, "swiglu_oai", True, 0,
     "caller_splits"),
]


@pytest.mark.parametrize(
    "D,I,dtype,act,biased,tail,path",
    [c[1:] for c in IN_PLACE_CASES], ids=[c[0] for c in IN_PLACE_CASES],
)
def test_fused_weight_read_in_place(D, I, dtype, act, biased, tail, path):
    from automodel_tpu.moe.config import MoEConfig
    from automodel_tpu.moe.experts import _fused_gate_up, _split_gate_up

    rng = np.random.default_rng(I + D + tail)
    G, sizes = 4, [40, 0, 33, 55]
    M = sum(sizes) + tail
    lhs, gate, up, down, gs, gb, ub, db, dy = _case(
        rng, M, D, I, G, sizes, biased, dtype
    )
    limit = 1.5 if (biased and act == "swiglu") else None
    interleaved = path == "caller_splits"
    cfg = MoEConfig(num_experts=G, num_experts_per_tok=1,
                    moe_intermediate_size=I, interleaved_gate_up=interleaved)
    if interleaved:
        gate_up = jnp.stack([gate, up], axis=-1).reshape(G, D, 2 * I)
    else:
        gate_up = jnp.concatenate([gate, up], axis=-1)
    if tail:  # the a2a sentinel tail: garbage in the inputs AND the cotangents
        n_real = M - tail
        lhs = lhs.at[n_real:].set(jnp.nan)
        dy = dy.at[n_real:].set(jnp.nan)
    bias = (gb, ub, db) if biased else ()

    def run(operands_of):
        def f(l, w, d, *b):
            g_, u_ = operands_of(w)
            return fused_expert_mlp(l, g_, u_, d, gs, *(b or (None,) * 3),
                                    act, limit, None, True)

        return f, *_pulled(f, (lhs, gate_up, down, *bias), dy)

    f_new, y_new, g_new = run(lambda w: _fused_gate_up(w, cfg))
    _, y_old, g_old = run(lambda w: _split_gate_up(w, interleaved))

    # which path ran: no [G, D, I] value in the program = nothing was copied
    half = (G, D, I)
    shapes = _all_shapes(jax.make_jaxpr(f_new)(lhs, gate_up, down, *bias).jaxpr)
    assert (half in shapes) == (path != "in_place"), path
    g_, u_ = _fused_gate_up(gate_up, cfg)
    assert (u_ is None) == (path != "caller_splits")

    real = slice(0, M - tail)
    assert np.array_equal(np.asarray(y_new[real]), np.asarray(y_old[real]))
    names = ("dlhs", "dW_gate_up", "dWd", "dgb", "dub", "ddb")
    for n, a, b in zip(names, g_new, g_old):
        a, b = np.asarray(a), np.asarray(b)
        if n == "dlhs":  # tail rows are dont-care by contract
            a, b = a[real], b[real]
        assert a.shape == b.shape and np.isfinite(a.astype(np.float32)).all(), n
        assert np.array_equal(a, b), (n, np.abs(a - b).max())
        assert np.abs(a.astype(np.float32)).max() > 0, n
    assert g_new[1].shape == gate_up.shape


def _all_shapes(jaxpr) -> set:
    """Shapes of every value a jaxpr (and its sub-jaxprs) defines."""
    out = set()
    for eqn in jaxpr.eqns:
        out.update(v.aval.shape for v in eqn.outvars if hasattr(v.aval, "shape"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out |= _all_shapes(sub)
    return out


def test_fused_weight_kernel_and_reference_backward_paths_agree():
    """The other route of the same custom VJP takes the fused operand too:
    the non-Pallas `_reference` composition (one grouped matmul, product
    split)."""
    rng = np.random.default_rng(13)
    D, I, G, sizes = 128, 128, 3, [30, 26, 40]
    lhs, gate, up, down, gs, gb, ub, db, dy = _case(
        rng, 96, D, I, G, sizes, biased=True
    )
    gate_up = jnp.concatenate([gate, up], axis=-1)

    def grads(interpret):
        def f(l, w, d, gb_, ub_, db_):
            return fused_expert_mlp(l, w, None, d, gs, gb_, ub_, db_,
                                    "swiglu", 1.5, None, interpret)

        return _pulled(f, (lhs, gate_up, down, gb, ub, db), dy)[1]

    fused = grads(True)
    reference = grads(False)  # CPU, no interpret: the XLA composition
    for a, c in zip(fused, reference):
        assert a.shape == c.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=5e-4)


# -- each result leaves its kernel once, in its final form (PR 40) ------------
#
# `_bwd_gu` / `_bwd_dwd` sum a group's units in an fp32 VMEM scratch and write
# the slab at the group's LAST unit in the weight's dtype; `_bwd_gu`'s out
# block spans both halves, so the fused weight's [G, D, 2I] cotangent is the
# kernel's own output; an empty group holds one unit of the backward's plan,
# which writes its zeros. Interpret mode fills an output nobody wrote with
# NaN (tests/test_grouped_matmul.py checks that premise), so a slab the
# kernels skipped would read NaN here.

# a row tile is 256 rows: group 0 is summed over the units of more than three
THREE_TILES = [1300, 0, 136, 100]


def _dense_weight_grads(lhs, g, u, dmid, dy, sizes, act, limit):
    """Per-group fp32 reference of the three weight gradients from the same
    bf16-or-f32 operands: (dWg, dWu, dWd), each [G, ., .] fp32."""
    from automodel_tpu.ops.fused_expert_mlp import _act_core, _act_grads

    dg, du = _act_grads(g, u, dmid, act, limit)
    mid = _act_core(g.astype(jnp.float32), u.astype(jnp.float32), act, limit)
    cast = lambda a: np.asarray(a.astype(lhs.dtype), np.float32)
    dg, du, mid = cast(dg), cast(du), cast(mid)
    l32, dy32 = np.asarray(lhs, np.float32), np.asarray(dy, np.float32)
    out = ([], [], [])
    for e, size in zip(np.cumsum(sizes), sizes):
        r = slice(e - size, e)
        out[0].append(l32[r].T @ dg[r])
        out[1].append(l32[r].T @ du[r])
        out[2].append(mid[r].T @ dy32[r])
    return tuple(np.stack(o) for o in out)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("fused", [False, True], ids=["two-array", "fused"])
def test_weight_gradient_kernels_round_the_f32_sum_once(fused, dtype):
    """bf16 and f32 weights: the kernel's narrow output IS its fp32 sum
    rounded once (the old path's ``f32 slab → astype``), a group spanning
    three row tiles is summed across its units, the fused form's one
    [G, D, 2I] array is the two-array form's pair side by side, and the
    NaN tail past the last group reaches nothing."""
    from automodel_tpu.ops.fused_expert_mlp import _bwd_dwd, _bwd_gu

    rng = np.random.default_rng(17)
    sizes, tail, D, I = THREE_TILES, 64, 128, 256
    G, M = len(sizes), sum(THREE_TILES) + 64
    mk = lambda *s: jnp.asarray(rng.normal(size=s), dtype)
    lhs, g, u, dmid, dy = mk(M, D), mk(M, I), mk(M, I), mk(M, I), mk(M, D)
    lhs, dy = (a.at[M - tail:].set(jnp.nan) for a in (lhs, dy))
    g, u, dmid = (a.at[M - tail:].set(jnp.nan) for a in (g, u, dmid))
    gs = jnp.asarray(sizes, jnp.int32)
    gu = (jnp.concatenate([g, u], axis=-1), None) if fused else (g, u)

    def run(out_dtype):
        dwg, dwu, _, _ = _bwd_gu(lhs, *gu, dmid, gs, "swiglu", 1.5, True,
                                 False, out_dtype)
        dwd, _ = _bwd_dwd(*gu, dy, gs, "swiglu", 1.5, True, False, out_dtype)
        return dwg, dwu, dwd

    narrow, wide = run(dtype), run(jnp.float32)
    if fused:
        assert narrow[1] is None and narrow[0].shape == (G, D, 2 * I)
        narrow = (*jnp.split(narrow[0], 2, axis=-1), narrow[2])
        wide = (*jnp.split(wide[0], 2, axis=-1), wide[2])
    ref = _dense_weight_grads(lhs[:M - tail], g[:M - tail], u[:M - tail],
                              dmid[:M - tail], dy[:M - tail], sizes,
                              "swiglu", 1.5)
    for n, a, w, r in zip(("dWg", "dWu", "dWd"), narrow, wide, ref):
        assert a.dtype == dtype and w.dtype == jnp.float32, n
        assert np.array_equal(np.asarray(a), np.asarray(w.astype(dtype))), n
        w = np.asarray(w)
        assert np.isfinite(w).all(), n
        assert np.abs(w[1]).max() == 0.0 and np.abs(w[0]).max() > 0.0, n
        np.testing.assert_allclose(w, r, atol=2e-3 * np.abs(r).max(), err_msg=n)


EMPTY_GROUPS = {
    "several-in-a-row": [50, 0, 0, 0, 78],
    "first": [0, 0, 60, 68],
    "last": [60, 68, 0, 0],
    "all-but-one": [0, 0, 0, 128],
    "every-group": [0, 0, 0],
}


@pytest.mark.parametrize("fused", [False, True], ids=["two-array", "fused"])
@pytest.mark.parametrize("groups", list(EMPTY_GROUPS))
def test_empty_groups_get_their_zeros_from_the_kernels(groups, fused):
    """Every weight and bias gradient of an empty group is exactly zero and
    nothing anywhere is NaN, with a NaN sentinel tail behind the real rows
    (under expert parallelism most groups of a shard can be empty)."""
    sizes = EMPTY_GROUPS[groups]
    rng = np.random.default_rng(len(sizes))
    M, D, I, G, n_real = 160, 128, 128, len(sizes), sum(sizes)
    lhs, gate, up, down, gs, gb, ub, db, dy = _case(
        rng, M, D, I, G, sizes, biased=True
    )
    lhs, dy = lhs.at[n_real:].set(jnp.nan), dy.at[n_real:].set(jnp.nan)
    w = (jnp.concatenate([gate, up], axis=-1), None) if fused else (gate, up)

    def f(wg_, wu_, d_, gb_, ub_, db_):
        return fused_expert_mlp(lhs, wg_, wu_, d_, gs, gb_, ub_, db_,
                                "swiglu_oai", None, None, True)

    grads = _pulled(f, (*w, down, gb, ub, db), dy)[1]
    if fused:
        assert grads[1] is None and grads[0].shape == (G, D, 2 * I)
        grads = (*jnp.split(grads[0], 2, axis=-1), *grads[2:])

    clean = lambda a: a.at[n_real:].set(0.0)
    ref = _pulled(
        lambda g_, u_, d_, gb_, ub_, db_: _reference(
            clean(lhs), g_, u_, d_, gs, gb_, ub_, db_, "swiglu_oai", None, None
        ),
        (gate, up, down, gb, ub, db), clean(dy),
    )[1]
    for n, a, b in zip(GRAD_NAMES[1:], grads, ref):
        a = np.asarray(a)
        assert np.isfinite(a).all(), n
        for grp, size in enumerate(sizes):
            assert (np.abs(a[grp]).max() > 0) == (size > 0), (n, grp, size)
        np.testing.assert_allclose(a, np.asarray(b), atol=5e-4, err_msg=n)
