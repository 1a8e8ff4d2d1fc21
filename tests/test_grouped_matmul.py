"""Pallas grouped matmul (ops/grouped_matmul.py) vs lax.ragged_dot.

Interpret mode executes the REAL kernel code path on CPU — same scheme as the
splash-attention tests (AUTOMODEL_FLASH_INTERPRET). Parity target:
reference grouped GEMM expert compute (components/moe/experts.py:158).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops import grouped_matmul as gm


def _random_case(rng, M, K, N, G, sizes=None):
    if sizes is None:
        cuts = np.sort(rng.integers(0, M + 1, size=G - 1))
        sizes = np.diff(np.concatenate([[0], cuts, [M]]))
    sizes = np.asarray(sizes, np.int32)
    lhs = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(G, K, N)), jnp.float32)
    return lhs, rhs, jnp.asarray(sizes)


@pytest.mark.parametrize(
    "M,K,N,G,sizes",
    [
        (64, 48, 40, 4, None),  # nothing divisible by tiles
        (256, 128, 128, 8, None),
        (128, 64, 96, 5, [0, 50, 0, 78, 0]),  # empty groups, incl. edges
        (96, 32, 32, 3, [96, 0, 0]),  # one group takes all rows
        (130, 128, 128, 2, [1, 129]),  # tile spans a group boundary
    ],
)
def test_gmm_forward_parity(M, K, N, G, sizes):
    rng = np.random.default_rng(0)
    lhs, rhs, gs = _random_case(rng, M, K, N, G, sizes)
    ref = jax.lax.ragged_dot(lhs, rhs, gs)
    got = gm._gmm(lhs, rhs, gs, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)


def test_gmm_grad_parity():
    rng = np.random.default_rng(1)
    lhs, rhs, gs = _random_case(rng, 192, 64, 80, 6)
    w = jnp.asarray(rng.normal(size=(192, 80)), jnp.float32)

    def loss_ref(l, r):
        return (jax.lax.ragged_dot(l, r, gs) * w).sum()

    def loss_got(l, r):
        return (gm._grouped_matmul(l, r, gs, True) * w).sum()

    gl_ref, gr_ref = jax.grad(loss_ref, (0, 1))(lhs, rhs)
    gl_got, gr_got = jax.grad(loss_got, (0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(gl_got), np.asarray(gl_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gr_got), np.asarray(gr_ref), atol=1e-4)


def test_gmm_grad_zero_for_empty_group():
    rng = np.random.default_rng(2)
    lhs, rhs, gs = _random_case(rng, 64, 32, 32, 4, [30, 0, 34, 0])
    grad = jax.grad(lambda r: gm._grouped_matmul(lhs, r, gs, True).sum())(rhs)
    assert float(jnp.abs(grad[1]).max()) == 0.0
    assert float(jnp.abs(grad[3]).max()) == 0.0
    assert float(jnp.abs(grad[0]).max()) > 0.0


def test_ragged_experts_through_real_kernel(monkeypatch):
    """The MoE ragged backend through the actual Pallas kernel (interpreted)
    must match the dense reference backend."""
    monkeypatch.setenv("AUTOMODEL_GMM_INTERPRET", "1")
    from automodel_tpu.moe.config import MoEConfig
    from automodel_tpu.moe.experts import dense_experts, ragged_experts
    from automodel_tpu.moe.gate import gate

    rng = np.random.default_rng(3)
    T, D, E, I, K = 48, 32, 8, 24, 2
    cfg = MoEConfig(
        num_experts=E, num_experts_per_tok=K, moe_intermediate_size=I,
        norm_topk_prob=True,
    )
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(D, E)), jnp.float32) * 0.1
    weights = {
        "gate_up": jnp.asarray(rng.normal(size=(E, D, 2 * I)), jnp.float32) * 0.1,
        "down": jnp.asarray(rng.normal(size=(E, I, D)), jnp.float32) * 0.1,
    }
    gout = gate(x, router, cfg)
    act2 = lambda g, u: jax.nn.silu(g) * u
    ref = dense_experts(x, gout, weights, cfg, act2)
    got = ragged_experts(x, gout, weights, cfg, act2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4)


def test_fused_expert_mlp_nan_tail_bias_grads_finite():
    """ADVICE r5 medium: rows past sum(group_sizes) (the a2a sentinel tail)
    carry uninitialized/garbage data — ragged_dot does not compute them and
    a2a buffers do not clear them. The manual backward's bias-grad seg_sum
    relied on a zero one-hot row to drop them, but 0·NaN = NaN: a NaN tail
    must not poison dgb/dub/ddb. Plants NaNs in both the tail inputs and
    the tail cotangents and asserts all bias grads stay finite."""
    from automodel_tpu.ops.fused_expert_mlp import fused_expert_mlp

    rng = np.random.default_rng(3)
    M, D, I, G = 16, 32, 24, 3
    n_real = 10  # sum(group_sizes) < M → 6 sentinel tail rows
    gs = jnp.asarray([4, 3, 3], jnp.int32)
    lhs = rng.normal(size=(M, D)).astype(np.float32)
    lhs[n_real:] = np.nan  # garbage tail, as the a2a path leaves it
    lhs = jnp.asarray(lhs)
    gate = jnp.asarray(rng.normal(size=(G, D, I)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(G, D, I)), jnp.float32)
    down = jnp.asarray(rng.normal(size=(G, I, D)), jnp.float32)
    gb = jnp.asarray(rng.normal(size=(G, I)), jnp.float32)
    ub = jnp.asarray(rng.normal(size=(G, I)), jnp.float32)
    db = jnp.asarray(rng.normal(size=(G, D)), jnp.float32)

    def f(gb_, ub_, db_):
        return fused_expert_mlp(
            lhs, gate, up, down, gs, gb_, ub_, db_, "swiglu", None, None, True
        )

    y, vjp = jax.vjp(f, gb, ub, db)
    dy = rng.normal(size=(M, D)).astype(np.float32)
    dy[n_real:] = np.nan  # tail cotangents are garbage too
    dgb, dub, ddb = vjp(jnp.asarray(dy))
    for name, g in (("dgb", dgb), ("dub", dub), ("ddb", ddb)):
        assert bool(jnp.isfinite(g).all()), f"{name} poisoned by NaN tail"
    # the real rows still produce real (nonzero) bias grads
    assert float(jnp.abs(ddb).max()) > 0.0


def test_fused_expert_mlp_nan_tail_weight_grads_finite():
    """The FULL manual backward under a garbage tail (the part the forward-
    focused PR 1 test never differentiated): dWg/dWu/dWd flow through
    `_tgmm`, whose in-kernel row mask zeroes only the LHS tile — a NaN tail
    in the dout operand still poisons the contraction (0·NaN = NaN), and
    the biased path additionally gathers `gb[row_g]` with the clamped
    sentinel index. Plants NaNs in the tail inputs and tail cotangents and
    asserts every weight and bias grad stays finite and nonzero."""
    from automodel_tpu.ops.fused_expert_mlp import fused_expert_mlp

    rng = np.random.default_rng(11)
    M, D, I, G = 16, 32, 24, 3
    n_real = 10  # sum(group_sizes) < M → 6 sentinel tail rows
    gs = jnp.asarray([4, 3, 3], jnp.int32)
    lhs = rng.normal(size=(M, D)).astype(np.float32)
    lhs[n_real:] = np.nan
    lhs = jnp.asarray(lhs)
    gate = jnp.asarray(rng.normal(size=(G, D, I)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(G, D, I)), jnp.float32)
    down = jnp.asarray(rng.normal(size=(G, I, D)), jnp.float32)
    gb = jnp.asarray(rng.normal(size=(G, I)), jnp.float32)
    ub = jnp.asarray(rng.normal(size=(G, I)), jnp.float32)
    db = jnp.asarray(rng.normal(size=(G, D)), jnp.float32)

    for biased in (True, False):  # the bias-less path masks the tail too
        def f(gate_, up_, down_, gb_, ub_, db_):
            return fused_expert_mlp(
                lhs, gate_, up_, down_, gs, gb_, ub_, db_,
                "swiglu", None, None, True,
            )

        args = (gate, up, down) + ((gb, ub, db) if biased else (None, None, None))
        y, vjp = jax.vjp(f, *args)
        dy = rng.normal(size=(M, D)).astype(np.float32)
        dy[n_real:] = np.nan
        grads = vjp(jnp.asarray(dy))
        names = ("dWg", "dWu", "dWd", "dgb", "dub", "ddb")
        for name, g in zip(names, grads):
            if g is None:
                continue
            assert bool(jnp.isfinite(g).all()), (
                f"{name} poisoned by NaN tail (biased={biased})"
            )
            assert float(jnp.abs(g).max()) > 0.0, f"{name} all-zero"


# -- `_tgmm` writes each group's slab once, in its final form (PR 40) ---------
#
# The kernel sums a group's units in an fp32 VMEM scratch and writes the slab
# at the group's last unit, rounded once to the weight's dtype; its plan
# (`_plan(..., empty_units=True)`) gives an empty group one unit, which writes
# that group's zeros. Interpret mode fills an output nobody wrote with NaN
# (`test_interpret_mode_poisons_unwritten_outputs`), so a slab the kernel
# skipped would read NaN here, as it would read garbage on the chip.


def _old_tgmm(lhs, dout, group_sizes):
    """`_tgmm` as it stood before PR 40, kept as the oracle: the fp32 slab
    accumulated in place in the OUTPUT block, empty groups left unwritten
    and zeroed by a select over [G, K, N] afterwards."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(wg, wt, ws, we, lhs_ref, dout_ref, out_ref, *, tm):
        w = pl.program_id(2)
        rows = wt[w] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mask = (rows >= ws[w]) & (rows < we[w])
        lhs_tile = lhs_ref[...]
        lhs_m = jnp.where(mask, lhs_tile, jnp.zeros_like(lhs_tile))
        acc = jax.lax.dot_general(
            lhs_m, dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        first = jnp.logical_or(w == 0, wg[jnp.maximum(w - 1, 0)] != wg[w])
        cur = out_ref[0]
        out_ref[0] = acc + jnp.where(first, jnp.zeros_like(cur), cur)

    M, K = lhs.shape
    N = dout.shape[1]
    G = group_sizes.shape[0]
    tm, tk, tn = gm._tgmm_tiles(K, N, lhs.dtype)
    assert (M % tm, K % tk, N % tn) == (0, 0, 0)  # the oracle pads nothing
    wg, wt, ws, we = gm._plan(group_sizes, M, tm, G)
    out = pl.pallas_call(
        functools.partial(kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(K // tk, N // tn, M // tm + G),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda k, n, w, wg, wt, ws, we: (wt[w], k)),
                pl.BlockSpec((tm, tn), lambda k, n, w, wg, wt, ws, we: (wt[w], n)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda k, n, w, wg, wt, ws, we: (wg[w], k, n)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((G, K, N), jnp.float32),
        interpret=True,
    )(wg, wt, ws, we, lhs, dout)
    return jnp.where((group_sizes > 0)[:, None, None], out, 0.0)


def test_interpret_mode_poisons_unwritten_outputs():
    from jax._src.pallas.primitives import uninitialized_value

    assert bool(jnp.isnan(uninitialized_value((2, 2), jnp.float32)).all())
    assert bool(jnp.isnan(uninitialized_value((2, 2), jnp.bfloat16)).all())


# a row tile is 256 rows (`_tgmm_tiles`): group 0 of "three-tiles" holds the
# rows of more than three tiles and is summed over as many units
TGMM_GROUPS = {
    "three-tiles": [1300, 100, 0, 136],
    "empty-run": [700, 0, 0, 0, 836],
    "empty-first": [0, 0, 1000, 536],
    "empty-last": [1000, 536, 0, 0],
    "all-but-one-empty": [0, 0, 1536, 0],
    "every-group-empty": [0, 0, 0],
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("groups", list(TGMM_GROUPS))
def test_tgmm_writes_each_slab_once_in_the_weights_dtype(groups, dtype):
    sizes = TGMM_GROUPS[groups]
    rng = np.random.default_rng(len(sizes) + sum(sizes))
    M, K, N = 1536, 128, 256
    lhs = jnp.asarray(rng.normal(size=(M, K)), dtype)
    dout = jnp.asarray(rng.normal(size=(M, N)), dtype)
    gs = jnp.asarray(sizes, jnp.int32)

    got = gm._tgmm(lhs, dout, gs, interpret=True, out_dtype=dtype)
    assert got.dtype == dtype and got.shape == (len(sizes), K, N)
    # the same fp32 sum as the old path's, rounded once
    old = _old_tgmm(lhs, dout, gs)
    assert np.array_equal(np.asarray(got), np.asarray(old.astype(dtype)))
    wide = gm._tgmm(lhs, dout, gs, interpret=True)
    assert wide.dtype == jnp.float32
    assert np.array_equal(np.asarray(wide), np.asarray(old))
    # an empty group's slab is zeros the kernel wrote; a live one's is not
    got = np.asarray(got.astype(jnp.float32))
    for g, size in enumerate(sizes):
        assert (np.abs(got[g]).max() > 0) == (size > 0), (g, size)
    # and the sums are the right ones
    ends = np.cumsum(sizes)
    l32, d32 = np.asarray(lhs, np.float32), np.asarray(dout, np.float32)
    for g, (e, size) in enumerate(zip(ends, sizes)):
        ref = l32[e - size:e].T @ d32[e - size:e]
        np.testing.assert_allclose(
            got[g], ref, atol=2e-2 * max(1.0, np.abs(ref).max()) if dtype == jnp.bfloat16 else 1e-3
        )


def test_grouped_matmul_weight_grad_comes_back_in_the_weights_dtype():
    """bf16 weight, transposed or not: the cotangent is the kernel's own
    output (no fp32 [G, K, N] value in the backward's program)."""
    rng = np.random.default_rng(4)
    lhs, rhs, gs = _random_case(rng, 256, 128, 128, 4, [100, 0, 56, 100])
    lhs, rhs = lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16)
    for transpose in (False, True):
        f = lambda r: gm._grouped_matmul(lhs, r, gs, True, transpose).astype(jnp.float32).sum()
        jaxpr = jax.make_jaxpr(jax.grad(f))(rhs)
        grad = jax.grad(f)(rhs)
        assert grad.dtype == jnp.bfloat16
        assert float(jnp.abs(grad[1].astype(jnp.float32)).max()) == 0.0
        f32_slabs = [
            v.aval for e in jaxpr.jaxpr.eqns for v in e.outvars
            if getattr(v.aval, "shape", None) == rhs.shape and v.aval.dtype == jnp.float32
        ]
        assert not f32_slabs, f32_slabs
