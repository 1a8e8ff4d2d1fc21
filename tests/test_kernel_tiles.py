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
F32 = jnp.float32
IT = 2  # bytes an element
GMM_BUDGET = 12 * 1024 * 1024  # under the 16 MiB scoped default
V5E_VMEM = 128 * 1024 * 1024

# (D, I) of sdar-30b-a3b.train-l1, minimax-m2.serve-l1, lfm2-8b-a1b.serve-l13
SHAPES = [(2048, 768), (3072, 1536), (2048, 1792)]


def _aligned(*tiles):
    assert all(t > 0 and t % 128 == 0 for t in tiles), tiles


def _gmm(D, I, want, products=None):
    # gate/up product [M, D] @ [G, D, 2I], down product [M, I] @ [G, I, D]:
    # the lhs row block and the rhs column block hold ALL of K
    for (K, N), tiles in zip(products or ((D, 2 * I), (I, D)), want):
        tm, tn = gm._gmm_tiles(K, N, BF16)
        assert (tm, tn) == tiles
        _aligned(tm, tn)
        assert 2 * IT * (tm * K + K * tn + tm * tn) <= GMM_BUDGET
        assert N % tn == 0  # no padded column block: the weight is read in place


def _gmm_transposed(D, I, want):
    # the fused backward's ``dmid = dy @ Wd^T`` (`transpose_rhs`: K = D,
    # N = I): at (2048, 768) a 512 column block padded ``down`` to
    # [G, 1024, 2048] on every step and multiplied the padding
    _gmm(D, I, [want], products=((D, I),))


def _tgmm(D, I, want):
    # dWgu = lhs^T @ dout: [M, D] x [M, 2I]; dWd: [M, I] x [M, D]
    for (K, N), tiles in zip(((D, 2 * I), (I, D)), want):
        tm, tk, tn = gm._tgmm_tiles(K, N, BF16)
        assert (tm, tk, tn) == tiles
        _aligned(tm, tk, tn)
        # two input blocks and the out slab double-buffered + the fp32 scratch
        # (an f32 weight's slab is the widest)
        assert (2 * IT * (tm * tk + tm * tn) + 4 * tk * tn
                + 2 * 4 * tk * tn) <= GMM_BUDGET
        assert K % tk == 0 and N % tn == 0  # neither operand nor slab padded


def _the_backward_asks_for_its_vmem():
    # the pickers fill `_VMEM_BUDGET` with blocks; Mosaic is asked for
    # `_VMEM_LIMIT`, the rest being the kernels' own stack; a v5e core has 128 MiB
    assert fem._VMEM_BUDGET < fem._VMEM_LIMIT <= V5E_VMEM // 2


def _bwd_gu(D, I, want):
    _the_backward_asks_for_its_vmem()
    tm, tk, tn = fem._bwd_gu_tiles(D, I, BF16, BF16)
    assert (tm, tk, tn) == want
    for out_dtype in (BF16, F32):  # an f32 master weight widens the out block
        tm, tk, tn = fem._bwd_gu_tiles(D, I, BF16, out_dtype)
        _aligned(tm, tk, tn)
        assert fem._bwd_gu_budget_ok(tm, tk, tn, I, IT, jnp.dtype(out_dtype).itemsize)
        assert D % tk == 0 and I % tn == 0  # `_col_off(fused, I, tn)`


def _bwd_dwd(D, I, want):
    _the_backward_asks_for_its_vmem()
    tm, tk, tn = fem._bwd_dwd_tiles(I, D, BF16, BF16)
    assert (tm, tk, tn) == want
    for out_dtype in (BF16, F32):
        tm, tk, tn = fem._bwd_dwd_tiles(I, D, BF16, out_dtype)
        _aligned(tm, tk, tn)
        assert fem._bwd_dwd_budget_ok(tm, tk, tn, IT, jnp.dtype(out_dtype).itemsize)
        assert I % tk == 0 and D % tn == 0  # `_col_off(fused, I, tk)`


def _bwd_dx(D, I, want):
    _the_backward_asks_for_its_vmem()
    tm, tn, ic = fem._bwd_dx_tiles(D, I, BF16)
    assert (tm, tn, ic) == want
    _aligned(tm, tn, ic)
    assert fem._bwd_dx_budget_ok(tm, tn, ic, IT)
    assert D % tn == 0 and I % ic == 0  # `_col_off(fused, I, ic)`


WANT = {
    _gmm: [
        ((256, 768), (256, 2048)),
        ((256, 512), (256, 1536)),
        ((256, 896), (256, 1024)),
    ],
    _gmm_transposed: [(256, 768), (256, 512), (256, 896)],
    _tgmm: [
        ((256, 512, 512), (256, 384, 512)),
        ((256, 512, 512), (256, 512, 512)),
        ((256, 512, 512), (256, 256, 512)),
    ],
    _bwd_gu: [(256, 2048, 768), (256, 1536, 768), (256, 1024, 1792)],
    _bwd_dwd: [(256, 768, 2048), (256, 768, 3072), (256, 1792, 2048)],
    _bwd_dx: [(256, 2048, 768), (256, 3072, 768), (256, 2048, 1792)],
}


@pytest.mark.parametrize("shape", range(len(SHAPES)), ids=[f"{d}x{i}" for d, i in SHAPES])
@pytest.mark.parametrize("picker", list(WANT), ids=lambda f: f.__name__.lstrip("_"))
def test_tiles_of_the_cells_expert_shapes(picker, shape):
    picker(*SHAPES[shape], WANT[picker][shape])


# (D, I) -> (tm, ic) of the fused forward, and whether it asks Mosaic for
# `_VMEM_LIMIT`: the three shapes above and kimi-linear's (2304, 1024) fit the
# default scoped stack at the tiles they have always had; xing4.0's hidden
# size of 3584 fits it at no tile (16.5 MB at 256 / 128), so its tiles are
# picked under `_VMEM_BUDGET` and the call asks for the limit, as the backward
# kernels do
FWD = [((2048, 768), (256, 256), False), ((3072, 1536), (256, 128), False),
       ((2048, 1792), (256, 256), False), ((2304, 1024), (256, 256), False),
       ((3584, 1024), (256, 512), True),
       # sarvam-105b's held experts on the serve path (4096 x 2048): over the
       # default stack at every tile too
       ((4096, 2048), (256, 512), True)]


@pytest.mark.parametrize("shape,want,asks", FWD, ids=[f"{d}x{i}" for (d, i), _, _ in FWD])
def test_forward_tiles_and_the_vmem_they_ask_for(shape, want, asks):
    D, I = shape
    tm, ic = fem._fwd_tiles(D, I)
    assert (tm, ic) == want
    _aligned(tm, ic)
    assert I % ic == 0  # `_col_off(fused, I, ic)`: the fused weight is blocked in place
    need = fem._fwd_vmem(tm, ic, D)
    assert need <= (fem._VMEM_BUDGET if asks else fem._FWD_STACK)
    assert fem._fwd_vmem_limit(tm, ic, D) == ({"vmem_limit_bytes": fem._VMEM_LIMIT} if asks else {})


def test_the_latent_decode_kernels_pages_at_the_sarvam_cells_shapes():
    """ops/latent_attention.pages_per_step beside its kernel: 64 heads against
    pages of 16 rows x 640 lanes (576 padded) in bfloat16. A decode step takes
    1,024 positions a grid step, under the VMEM budget. The host's count of
    the grid is the kernel's own arithmetic."""
    import numpy as np

    from automodel_tpu.ops import latent_attention as la

    assert la.pages_per_step(16, 640, 512, 64, 2) == 64
    assert la._step_bytes(64, 16, 640, 512, 64, 2) <= la._VMEM_BUDGET
    # the cell at its fullest: 32 slots of 7,935 cached rows, a table of 544 blocks
    lengths = np.full((32,), 7935)
    assert la.grid_steps(lengths, 544, pages=64, block_size=16) == (32 * 9, 32 * 8)
    assert la.context_rows(lengths) == 32 * 7936
    assert la.grid_steps(np.zeros((32,), int), 544, pages=64, block_size=16) == (288, 32)
