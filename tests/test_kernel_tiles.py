"""The tiles the expert kernels pick for the shapes the chip runs.

One case a (benchmark cell's expert shape, picker): the tiles are what the
cell's programs compile with today (pinned, so a picker edit shows here
before it shows in the ledger), they are lane-aligned, the kernel's blocks
fit the VMEM budget its picker models, and they tile the operands the way
the kernel's grid assumes: the cells store the fused [E, D, 2I] weight,
which the backward kernels block in place, so a chunk over D or I that left
a remainder would be a pad, which is a copy of the weight.
"""

import jax.numpy as jnp
import pytest

from automodel_tpu.ops import fused_expert_mlp as fem
from automodel_tpu.ops import grouped_matmul as gm

BF16 = jnp.bfloat16
IT = 2  # bytes an element
BUDGET = 12 * 1024 * 1024

# (D, I) of sdar-30b-a3b.train-l1, minimax-m2.serve-l1, lfm2-8b-a1b.serve-l13
SHAPES = [(2048, 768), (3072, 1536), (2048, 1792)]


def _aligned(*tiles):
    assert all(t > 0 and t % 128 == 0 for t in tiles), tiles


def _gmm(D, I, want):
    # gate/up product [M, D] @ [G, D, 2I], down product [M, I] @ [G, I, D]:
    # the lhs row block and the rhs column block hold ALL of K
    for (K, N), tiles in zip(((D, 2 * I), (I, D)), want):
        tm, tn = gm._gmm_tiles(K, N, BF16)
        assert (tm, tn) == tiles
        _aligned(tm, tn)
        assert 2 * IT * (tm * K + K * tn + tm * tn) <= BUDGET
        assert N % tn == 0  # no padded column block at these widths


def _tgmm(D, I, want):
    # dWgu = lhs^T @ dout: [M, D] x [M, 2I]; dWd: [M, I] x [M, D]
    for (K, N), tiles in zip(((D, 2 * I), (I, D)), want):
        tm, tk, tn = gm._tgmm_tiles(K, N, BF16)
        assert (tm, tk, tn) == tiles
        _aligned(tm, tk, tn)
        # two input blocks double-buffered + the fp32 [tk, tn] slab
        assert 2 * IT * (tm * tk + tm * tn) + 2 * 4 * tk * tn <= BUDGET
        assert N % tn == 0 and tk <= 512  # K pads up to tk (I = 768, 1792)


def _bwd_gu(D, I, want):
    tm, tk, tn = fem._bwd_gu_tiles(D, I, BF16)
    assert (tm, tk, tn) == want
    _aligned(tm, tk, tn)
    assert fem._bwd_gu_budget_ok(tm, tk, tn, IT)
    assert D % tk == 0 and I % tn == 0  # `_col_off(fused, I, tn)`


def _bwd_dwd(D, I, want):
    tm, tk, tn = fem._bwd_dwd_tiles(I, D, BF16)
    assert (tm, tk, tn) == want
    _aligned(tm, tk, tn)
    assert fem._bwd_dwd_budget_ok(tm, tk, tn, IT)
    assert I % tk == 0 and D % tn == 0  # `_col_off(fused, I, tk)`


def _bwd_dx(D, I, want):
    tm, tn, ic = fem._bwd_dx_tiles(D, I, BF16)
    assert (tm, tn, ic) == want
    _aligned(tm, tn, ic)
    assert fem._bwd_dx_budget_ok(tm, tn, ic, IT)
    assert D % tn == 0 and I % ic == 0  # `_col_off(fused, I, ic)`


WANT = {
    _gmm: [
        ((512, 512), (512, 512)),
        ((512, 256), (512, 512)),
        ((512, 512), (512, 512)),
    ],
    _tgmm: [
        ((512, 512, 512), (512, 512, 512)),
        ((512, 512, 256), (512, 512, 512)),
        ((512, 512, 512), (512, 512, 512)),
    ],
    _bwd_gu: [(512, 512, 384), (512, 512, 512), (512, 512, 256)],
    _bwd_dwd: [(512, 384, 512), (512, 512, 512), (512, 256, 512)],
    _bwd_dx: [(512, 512, 384), (512, 512, 512), (512, 512, 256)],
}


@pytest.mark.parametrize("shape", range(len(SHAPES)), ids=[f"{d}x{i}" for d, i in SHAPES])
@pytest.mark.parametrize("picker", list(WANT), ids=lambda f: f.__name__.lstrip("_"))
def test_tiles_of_the_cells_expert_shapes(picker, shape):
    picker(*SHAPES[shape], WANT[picker][shape])
