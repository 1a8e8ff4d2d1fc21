"""An expert layer that is told which experts it holds (MoEConfig.held_experts).

The guide's share test: the routed parts that all the shares of a layer give,
plus what every chip computes alike (the shared expert) counted ONCE, add up
to what the uncut layer gives. And the step's ``held_expert_rows`` against a
direct count of the picks."""

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.layer import init_moe_params, moe_block

D, E, K, I, SHARES = 32, 32, 4, 16, 8
WHOLE = MoEConfig(num_experts=E, num_experts_per_tok=K, moe_intermediate_size=I,
                  num_shared_experts=1, shared_expert_intermediate_size=I, score_func="sigmoid",
                  route_scale=2.446, norm_topk_prob=True, expert_bias=True)
ACT = jax.nn.silu


# the Xing4.0 cell's router: 64 experts, top-4, weights renormalised x 2, in 8 shares of 8
WHOLE_64 = dataclasses.replace(WHOLE, num_experts=64, route_scale=2.0)


def _setup(whole=WHOLE):
    params = init_moe_params(jax.random.key(0), whole, D, jnp.float32)
    params["router"]["bias"] = 0.05 * jax.random.normal(jax.random.key(1), (whole.num_experts,))
    x = jax.random.normal(jax.random.key(2), (2, 24, D))
    return params, x


def _share(params, lo, hi):
    """What the chip holding experts [lo, hi) has: the whole router and
    shared expert, its own experts' weights."""
    own = jax.tree.map(lambda a: a[lo:hi], params["experts"])
    return {**params, "experts": own}


@pytest.mark.parametrize("backend", ["ragged", "ragged_fused"])
@pytest.mark.parametrize("whole", [WHOLE, WHOLE_64], ids=["32-experts", "64-experts-top4-x2"])
def test_the_shares_add_up_to_the_uncut_layer(backend, whole, monkeypatch):
    monkeypatch.setenv("AUTOMODEL_GMM_INTERPRET", "1")
    params, x = _setup(whole)
    # one program a configuration: op by op a layer is a program a primitive
    block = lambda p, cfg, kind: jax.jit(
        lambda x, p: moe_block(x, p, cfg, ACT, experts_backend=kind))(x, p)
    out_whole, aux = block(params, whole, "dense")
    no_shared = {k: v for k, v in params.items() if k != "shared"}
    shared = out_whole - block(no_shared, whole, "dense")[0]
    n = whole.num_experts // SHARES
    total, rows = shared, 0
    for s in range(SHARES):
        cfg = dataclasses.replace(whole, held_experts=(s * n, (s + 1) * n))
        out, a = block(_share(no_shared, s * n, (s + 1) * n), cfg, backend)
        total = total + out
        # every share routes over all the experts and counts the same picks
        assert jnp.array_equal(a.expert_counts, aux.expert_counts)
        rows += int(a.expert_counts[s * n:(s + 1) * n].sum())
    # float32, the same products in another order: 1e-6 of the output's scale
    assert float(jnp.abs(total - out_whole).max() / jnp.abs(out_whole).max()) < 1e-5
    assert rows == x.shape[0] * x.shape[1] * K  # every pick lands on exactly one share


def test_a_share_holds_only_its_experts_and_the_whole_router():
    cfg = dataclasses.replace(WHOLE, held_experts=(8, 12))
    p = init_moe_params(jax.random.key(0), cfg, D, jnp.float32, n_layers=3)
    assert p["experts"]["gate_up"].shape == (3, 4, D, 2 * I)
    assert p["experts"]["down"].shape == (3, 4, I, D)
    assert p["router"]["weight"].shape == (3, D, E) and p["router"]["bias"].shape == (3, E)
    with pytest.raises(ValueError, match="held_experts"):
        dataclasses.replace(WHOLE, held_experts=(30, 34))


def test_a_bounded_buffer_drops_the_picks_over_it_and_nothing_else():
    params, x = _setup()
    cfg = dataclasses.replace(WHOLE, held_experts=(0, 4))
    full, aux = moe_block(x, _share(params, 0, 4), cfg, ACT, experts_backend="ragged")
    held = int(aux.expert_counts[:4].sum())
    # the balanced share is T*K*4/32 = 24 rows; a factor that covers the held picks changes nothing
    roomy = dataclasses.replace(cfg, held_capacity_factor=-(-held // 24) + 1.0)
    out, _ = moe_block(x, _share(params, 0, 4), roomy, ACT, experts_backend="ragged")
    assert jnp.array_equal(out, full)
    tight = dataclasses.replace(cfg, held_capacity_factor=8 / 24)  # 8 rows
    cut, _ = moe_block(x, _share(params, 0, 4), tight, ACT, experts_backend="ragged")
    assert held > 8 and not jnp.allclose(cut, full)


def test_held_expert_rows_in_the_step_metrics_is_the_direct_count():
    """Through a model's loss: MoEModelAux.held_expert_rows -> the loss's
    extras -> the step's metrics."""
    from automodel_tpu.training.train_step import build_train_step
    from automodel_tpu.optim.builders import build_optimizer
    from automodel_tpu.training.train_state import TrainState

    cfg = dataclasses.replace(WHOLE, held_experts=(4, 8))
    params = {"moe": _share(_setup()[0], 4, 8)}
    x = _setup()[1]

    def loss_fn(p, mb):
        out, aux = moe_block(mb["x"], p["moe"], cfg, ACT, experts_backend="ragged")
        extras = {"expert_counts": aux.expert_counts[None],
                  "held_expert_rows": aux.expert_counts[4:8].sum()}
        return jnp.sum(out * out), jnp.int32(out.shape[0] * out.shape[1]), extras

    opt = build_optimizer(lr=1e-3)
    step = build_train_step(loss_fn, opt, donate=False)
    _, metrics = step(TrainState.create(params, opt.init(params)), {"x": x[None]})
    _, aux = moe_block(x, params["moe"], cfg, ACT, experts_backend="ragged")
    assert int(metrics["held_expert_rows"]) == int(aux.expert_counts[4:8].sum()) > 0


def test_rows_past_the_held_picks_never_reach_the_result(monkeypatch):
    """The fused kernel leaves the buffer's rows past the held picks unwritten
    (on the chip: whatever was in memory). NaN there must reach neither the
    output nor any gradient."""
    import automodel_tpu.moe.experts as E

    params, x = _setup()
    cfg = dataclasses.replace(WHOLE, held_experts=(0, 4))
    share = _share(params, 0, 4)
    real = E._ragged_mlp

    def poisoned(xs, w_gu, w_dn, weights, group_sizes, *a):
        ys = real(xs, w_gu, w_dn, weights, group_sizes, *a)
        return jnp.where((jnp.arange(ys.shape[0]) < group_sizes.sum())[:, None], ys, jnp.nan)

    def loss(p, x):
        out, _ = moe_block(x, p, cfg, ACT, experts_backend="ragged")
        return jnp.sum(out * out)

    want = jax.grad(loss, argnums=(0, 1))(share, x)
    monkeypatch.setattr(E, "_ragged_mlp", poisoned)
    got = jax.grad(loss, argnums=(0, 1))(share, x)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(got))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6), got, want)
