"""MoE subsystem tests.

Mirrors the reference's unit-test strategy for components/moe (SURVEY.md §4):
gate semantics, backend equivalence against the dense reference, aux-free
bias balancing, and EP-sharded execution on the 8-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.moe import (
    MoEConfig,
    fake_balanced_gate,
    gate,
    init_moe_params,
    moe_block,
    update_gate_bias,
)
from automodel_tpu.moe.experts import (
    a2a_experts,
    dense_experts,
    gspmd_experts,
    ragged_experts,
)
from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
from automodel_tpu.parallel.plans import make_constrain


CFG = MoEConfig(
    num_experts=8,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
    norm_topk_prob=True,
    capacity_factor=8.0,  # no drops → exact match with dense
)


def _params(cfg=CFG, d=16, seed=0):
    return init_moe_params(jax.random.key(seed), cfg, d, jnp.float32)


def _x(t=24, d=16, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((t, d)), jnp.float32)


def test_gate_topk_and_norm():
    p, x = _params(), _x()
    out = gate(x, p["router"]["weight"], CFG)
    assert out.topk_idx.shape == (24, 2)
    # top-k ids unique per token, weights normalized
    assert all(len(set(row)) == 2 for row in np.asarray(out.topk_idx))
    np.testing.assert_allclose(np.asarray(out.topk_weights.sum(-1)), 1.0, rtol=1e-5)
    assert int(out.expert_counts.sum()) == 24 * 2


def test_gate_grouped_routing_limits_groups():
    cfg = MoEConfig(
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        n_group=4, topk_group=2,
    )
    p, x = _params(cfg), _x()
    out = gate(x, p["router"]["weight"], cfg)
    # every token's experts come from at most 2 distinct groups (of size 2)
    groups = np.asarray(out.topk_idx) // 2
    assert (np.array([len(set(g)) for g in groups]) <= 2).all()


def test_gate_sigmoid_bias_affects_selection_not_weights():
    cfg = MoEConfig(
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        score_func="sigmoid", expert_bias=True,
    )
    p, x = _params(cfg), _x()
    w = p["router"]["weight"]
    bias = jnp.zeros(8).at[3].set(1e3)  # force expert 3 into every selection
    out = gate(x, w, cfg, bias=bias)
    assert (np.asarray(out.topk_idx) == 3).any(axis=1).all()
    # combine weights are original sigmoid scores of the chosen experts
    scores = jax.nn.sigmoid(x @ w)
    picked = np.take_along_axis(np.asarray(scores), np.asarray(out.topk_idx), 1)
    np.testing.assert_allclose(np.asarray(out.topk_weights), picked, rtol=1e-5)


def test_fake_balanced_gate_is_balanced():
    out = fake_balanced_gate(_x(t=32), CFG)
    counts = np.asarray(out.expert_counts)
    assert counts.min() == counts.max() == 32 * 2 // 8


def test_update_gate_bias_pushes_toward_balance():
    bias = jnp.zeros(4)
    counts = jnp.asarray([10, 2, 4, 0])
    new = update_gate_bias(bias, counts, 0.1)
    assert new[0] < 0 and new[3] > 0  # overloaded down, starved up


def test_expert_backends_match_dense():
    p, x = _params(), _x()
    gout = gate(x, p["router"]["weight"], CFG)
    act2 = lambda g, u: jax.nn.silu(g) * u
    ref = dense_experts(x, gout, p["experts"], CFG, act2)
    rag = ragged_experts(x, gout, p["experts"], CFG, act2)
    np.testing.assert_allclose(np.asarray(rag), np.asarray(ref), rtol=1e-4, atol=1e-5)
    gsp = gspmd_experts(x.reshape(2, 12, 16), gout, p["experts"], CFG, act2)
    np.testing.assert_allclose(
        np.asarray(gsp).reshape(24, 16), np.asarray(ref), rtol=1e-4, atol=1e-5
    )


def test_gspmd_capacity_drops_lowest_priority():
    cfg = MoEConfig(
        num_experts=4, num_experts_per_tok=1, moe_intermediate_size=8,
        capacity_factor=0.25,  # cap = max(K, S*K/E*0.25) → heavy drops
    )
    p = _params(cfg, d=8)
    x = _x(t=16, d=8)
    gout = gate(x, p["router"]["weight"], cfg)
    out = gspmd_experts(
        x.reshape(1, 16, 8), gout, p["experts"], cfg,
        lambda g, u: jax.nn.silu(g) * u,
    )
    assert np.isfinite(np.asarray(out)).all()


def test_moe_block_shared_experts_and_aux():
    cfg = MoEConfig(
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        num_shared_experts=1, shared_expert_intermediate_size=32,
        aux_loss_coeff=0.01, bias_update_factor=0.001,
    )
    p = _params(cfg)
    x = _x(t=24).reshape(2, 12, 16)
    out, aux = moe_block(x, p, cfg, jax.nn.silu, experts_backend="dense")
    assert out.shape == x.shape
    assert float(aux.aux_loss) > 0
    assert int(aux.expert_counts.sum()) == 48


def test_moe_block_ep_sharded_matches_unsharded(devices8):
    """gspmd dispatch on an ep=4 mesh == single-device result."""
    cfg = MoEConfig(
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        capacity_factor=8.0,
    )
    p = _params(cfg)
    x = _x(t=64).reshape(4, 16, 16)
    ref, _ = moe_block(x, p, cfg, jax.nn.silu, experts_backend="gspmd")

    ctx = build_mesh(MeshConfig(dp_shard=4, ep=4), devices=devices8[:4])
    constrain = make_constrain(ctx)
    from automodel_tpu.parallel.plans import shard_params
    from automodel_tpu.moe.layer import MOE_SHARDING_RULES

    ps = shard_params(ctx, p, MOE_SHARDING_RULES)
    xs = jax.device_put(x, ctx.sharding("batch", None, None))

    @jax.jit
    def f(p_, x_):
        out, aux = moe_block(
            x_, p_, cfg, jax.nn.silu, experts_backend="gspmd", constrain=constrain
        )
        return out

    out = f(ps, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


# -- a2a token-exchange dispatcher (DeepEP equivalent) ------------------------


def _a2a_setup(devices8, cfg, t=64, d=16, tp=2, ep=4, seed=0):
    p = _params(cfg, d=d, seed=seed)
    x = _x(t=t, d=d).reshape(ep, t // ep, d)
    ctx = build_mesh(MeshConfig(dp_shard=ep, ep=ep, tp=tp), devices=devices8[: ep * tp])
    constrain = make_constrain(ctx)
    from automodel_tpu.moe.layer import MOE_SHARDING_RULES
    from automodel_tpu.parallel.plans import shard_params

    ps = shard_params(ctx, p, MOE_SHARDING_RULES)
    xs = jax.device_put(x, ctx.sharding("batch", None, None))
    return p, x, ps, xs, ctx, constrain


def test_a2a_matches_dense_on_ep_tp_mesh(devices8):
    """a2a dispatch on an ep=4 × tp=2 mesh == dense single-device result,
    with NO dropped tokens by construction (default strict capacity)."""
    p, x, ps, xs, ctx, constrain = _a2a_setup(devices8, CFG)
    gout = gate(x.reshape(-1, 16), p["router"]["weight"], CFG)
    act2 = lambda g, u: jax.nn.silu(g) * u
    ref = dense_experts(x.reshape(-1, 16), gout, p["experts"], CFG, act2)

    @jax.jit
    def f(p_, x_):
        out, _ = moe_block(
            x_, p_, CFG, jax.nn.silu, experts_backend="a2a", constrain=constrain
        )
        return out

    out = f(ps, xs)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, 16), np.asarray(ref), rtol=2e-4, atol=2e-5
    )


def test_a2a_dropless_under_extreme_imbalance(devices8):
    """Every token routed to ONE expert — worst-case skew; strict capacity
    still loses nothing (the gspmd capacity path would drop most picks)."""
    cfg = MoEConfig(
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        score_func="sigmoid", expert_bias=True,
    )
    p, x, ps, xs, ctx, constrain = _a2a_setup(devices8, cfg)
    # aux-free bias forces experts 3 and 5 into every selection
    bias = jnp.zeros(8).at[3].set(1e3).at[5].set(1e3)
    p["router"]["bias"] = bias
    ps["router"]["bias"] = jax.device_put(bias, ctx.replicated())

    gout = gate(x.reshape(-1, 16), p["router"]["weight"], cfg, bias=bias)
    assert set(np.asarray(gout.topk_idx).ravel()) == {3, 5}
    act2 = lambda g, u: jax.nn.silu(g) * u
    ref = dense_experts(x.reshape(-1, 16), gout, p["experts"], cfg, act2)

    @jax.jit
    def f(p_, x_):
        out, _ = moe_block(
            x_, p_, cfg, jax.nn.silu, experts_backend="a2a", constrain=constrain
        )
        return out

    out = f(ps, xs)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, 16), np.asarray(ref), rtol=2e-4, atol=2e-5
    )


def test_a2a_grad_parity_with_dense(devices8):
    """d(loss)/d(params) through the a2a dispatch (all_to_all transpose,
    ragged_dot grads, scatter combines) matches the dense backend."""
    p, x, ps, xs, ctx, constrain = _a2a_setup(devices8, CFG)

    def loss(p_, x_, backend, cons):
        out, _ = moe_block(
            x_, p_, CFG, jax.nn.silu, experts_backend=backend, constrain=cons
        )
        return (out.astype(jnp.float32) ** 2).mean()

    g_ref = jax.grad(lambda p_: loss(p_, x, "dense", lambda a, s: a))(p)
    g_a2a = jax.jit(jax.grad(lambda p_: loss(p_, xs, "a2a", constrain)))(ps)
    flat_ref = jax.tree_util.tree_leaves_with_path(g_ref)
    flat = dict(jax.tree_util.tree_leaves_with_path(g_a2a))
    for path, ref_leaf in flat_ref:
        np.testing.assert_allclose(
            np.asarray(flat[path]), np.asarray(ref_leaf),
            rtol=5e-4, atol=1e-5, err_msg=str(path),
        )


def test_a2a_nongated_relu2_matches_dense(devices8):
    """Non-gated (nemotron-v3 relu2) experts through the a2a dispatcher on
    an ep=4 × tp=2 mesh == dense single-device result — the DeepEP-equivalent
    backend is no longer gated-only (VERDICT r4 weak #4). Includes expert
    biases (the up-only [E, I] bias layout)."""
    from automodel_tpu.moe.layer import make_act2

    cfg = MoEConfig(
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        activation="relu2", expert_mlp_bias=True,
    )
    assert not cfg.gated
    p, x, ps, xs, ctx, constrain = _a2a_setup(devices8, cfg)
    # non-zero biases so the bias path is actually exercised
    rng = np.random.default_rng(3)
    for name, leaf in list(p["experts"].items()):
        if name.endswith("_bias"):
            b = jnp.asarray(rng.standard_normal(leaf.shape) * 0.1, leaf.dtype)
            p["experts"][name] = b
            ps["experts"][name] = jax.device_put(
                b, ps["experts"][name].sharding
            )

    gout = gate(x.reshape(-1, 16), p["router"]["weight"], cfg)
    act2 = make_act2(cfg, jax.nn.silu)
    ref = dense_experts(x.reshape(-1, 16), gout, p["experts"], cfg, act2)

    @jax.jit
    def f(p_, x_):
        out, _ = moe_block(
            x_, p_, cfg, jax.nn.silu, experts_backend="a2a", constrain=constrain
        )
        return out

    out = f(ps, xs)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, 16), np.asarray(ref), rtol=2e-4, atol=2e-5
    )


def test_a2a_backward_is_scatter_free(devices8):
    """The EP fwd+bwd HLO contains NO floating-point scatter (VERDICT r4
    weak #3): every permutation inside the manual region rides a gather-only
    custom VJP, and the send-buffer pack is itself a gather (picks are
    peer-contiguous after the sort). Only the int32 bincounts remain — [E]-
    wide bookkeeping, not the [T·K, D] data path the profile billed at ~4x
    gather cost."""
    p, x, ps, xs, ctx, constrain = _a2a_setup(devices8, CFG)
    gout = gate(x.reshape(-1, 16), p["router"]["weight"], CFG)
    act2 = lambda g, u: jax.nn.silu(g) * u

    def loss(p_, x_):
        out = a2a_experts(x_, gout, p_["experts"], CFG, act2, ctx)
        return (out.astype(jnp.float32) ** 2).mean()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(ps, xs).compile().as_text()
    float_scatters = [
        l.strip() for l in hlo.splitlines()
        if "scatter(" in l and (" f32[" in l or " bf16[" in l or " f16[" in l)
    ]
    assert not float_scatters, float_scatters[:4]


@pytest.mark.parametrize(
    "tp,d,inter,oai",
    [(2, 16, 32, True), (1, 128, 128, False), (1, 16, 32, False)],
    ids=["ep4-tp2-oai-interleaved", "ep4-tp1-in-place", "ep4-tp1-unaligned"],
)
def test_a2a_fused_matches_a2a(devices8, monkeypatch, tp, d, inter, oai):
    """experts='a2a_fused' (token exchange + one-kernel local expert MLP,
    interpret mode): numerics AND grads match the unfused a2a path. On the
    ep=4 × tp=2 mesh with gpt-oss-style biased interleaved swiglu_oai
    experts — the fused kernel's bias path inside the manual region, the
    halves pre-split so their tp shards align. With ONE tp shard the stored
    fused gate_up goes into the region whole: read in place at 128-multiple
    widths, split by the op itself at unaligned ones."""
    monkeypatch.setenv("AUTOMODEL_GMM_INTERPRET", "1")
    cfg = MoEConfig(
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=inter,
        **(dict(activation="swiglu_oai", interleaved_gate_up=True,
                expert_mlp_bias=True) if oai else {}),
    )
    p, x, ps, xs, ctx, constrain = _a2a_setup(devices8, cfg, d=d, tp=tp)
    from automodel_tpu.moe.experts import _a2a_weights

    assert ("uw" in _a2a_weights(p["experts"], cfg, whole=tp == 1)) == oai
    rng = np.random.default_rng(5)
    for name in ("gate_up_bias", "down_bias") if oai else ():
        b = jnp.asarray(
            rng.standard_normal(p["experts"][name].shape) * 0.1, jnp.float32
        )
        p["experts"][name] = b
        ps["experts"][name] = jax.device_put(b, ps["experts"][name].sharding)

    def loss(p_, x_, backend):
        out, _ = moe_block(
            x_, p_, cfg, jax.nn.silu, experts_backend=backend,
            constrain=constrain,
        )
        return (out.astype(jnp.float32) ** 2).mean(), out

    (l_ref, o_ref), g_ref = jax.jit(
        jax.value_and_grad(lambda p_: loss(p_, xs, "a2a"), has_aux=True)
    )(ps)
    (l_f, o_f), g_f = jax.jit(
        jax.value_and_grad(lambda p_: loss(p_, xs, "a2a_fused"), has_aux=True)
    )(ps)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-5)
    flat_ref = jax.tree_util.tree_leaves_with_path(g_ref)
    flat = dict(jax.tree_util.tree_leaves_with_path(g_f))
    for path, ref_leaf in flat_ref:
        np.testing.assert_allclose(
            np.asarray(flat[path]), np.asarray(ref_leaf),
            rtol=5e-4, atol=1e-5, err_msg=str(path),
        )

    # non-gated experts reject loudly (kernel envelope)
    cfg_ng = MoEConfig(num_experts=8, num_experts_per_tok=2,
                       moe_intermediate_size=32, activation="relu2")
    with pytest.raises(NotImplementedError, match="gated"):
        from automodel_tpu.moe.experts import _fused_act_of

        _fused_act_of(cfg_ng, "silu", False)


def test_a2a_bounded_capacity_drops_gracefully(devices8):
    """a2a_capacity_factor < worst case: over-capacity picks contribute zero
    (never NaN/garbage)."""
    cfg = MoEConfig(
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        score_func="sigmoid", expert_bias=True, a2a_capacity_factor=1.0,
    )
    p, x, ps, xs, ctx, constrain = _a2a_setup(devices8, cfg)
    bias = jnp.zeros(8).at[3].set(1e3).at[5].set(1e3)  # worst-case skew
    ps["router"]["bias"] = jax.device_put(bias, ctx.replicated())

    @jax.jit
    def f(p_, x_):
        out, _ = moe_block(
            x_, p_, cfg, jax.nn.silu, experts_backend="a2a", constrain=constrain
        )
        return out

    out = np.asarray(f(ps, xs))
    assert np.isfinite(out).all()


def test_a2a_single_slice_falls_back_to_ragged():
    """No mesh → the a2a backend is the ragged dropless path."""
    p, x = _params(), _x()
    gout = gate(x, p["router"]["weight"], CFG)
    act2 = lambda g, u: jax.nn.silu(g) * u
    ref = ragged_experts(x, gout, p["experts"], CFG, act2)
    out = a2a_experts(x.reshape(2, 12, 16), gout, p["experts"], CFG, act2, None)
    np.testing.assert_allclose(
        np.asarray(out).reshape(24, 16), np.asarray(ref), rtol=1e-5, atol=1e-6
    )


def test_ragged_fused_matches_ragged(monkeypatch):
    """experts='ragged_fused' (one-kernel expert MLP): numerics + grads
    match the two-gmm ragged path, incl. swiglu_oai and unbalanced groups
    with an empty expert (interpret mode). The swiglu_oai case carries
    gpt-oss-style per-expert gate_up/down biases (interleaved layout) so the
    fused kernel's in-kernel bias path is exercised, masked rows included."""
    monkeypatch.setenv("AUTOMODEL_GMM_INTERPRET", "1")
    import jax
    import jax.numpy as jnp

    from automodel_tpu.moe.config import MoEConfig
    from automodel_tpu.moe.experts import ragged_experts, ragged_fused_experts
    from automodel_tpu.moe.gate import GateOutput
    from automodel_tpu.moe.layer import make_act2

    rng = np.random.default_rng(0)
    T, D, I, E, K = 48, 16, 8, 4, 2
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    # unbalanced routing with expert 2 EMPTY
    idx_np = rng.choice([0, 1, 3], size=(T, K)).astype(np.int32)
    idx = jnp.asarray(idx_np)
    w = jnp.asarray(rng.random((T, K)).astype(np.float32))
    counts = jnp.bincount(idx.reshape(-1), length=E).astype(jnp.int32)
    gout = GateOutput(idx, w, counts, jnp.float32(0))

    for activation in ("swiglu", "swiglu_oai"):
        cfg = MoEConfig(num_experts=E, num_experts_per_tok=K,
                        moe_intermediate_size=I, activation=activation,
                        interleaved_gate_up=activation == "swiglu_oai")
        act2 = make_act2(cfg, jax.nn.silu)
        weights = {
            "gate_up": jnp.asarray(rng.normal(size=(E, D, 2 * I)) * 0.2,
                                   jnp.float32),
            "down": jnp.asarray(rng.normal(size=(E, I, D)) * 0.2, jnp.float32),
        }
        if activation == "swiglu_oai":  # gpt-oss fingerprint: biased experts
            weights["gate_up_bias"] = jnp.asarray(
                rng.normal(size=(E, 2 * I)) * 0.3, jnp.float32
            )
            weights["down_bias"] = jnp.asarray(
                rng.normal(size=(E, D)) * 0.3, jnp.float32
            )

        def f_ref(args):
            x_, wt = args
            y = ragged_experts(x_, gout, wt, cfg, act2)
            return jnp.sum(jnp.sin(y)), y

        def f_fused(args):
            x_, wt = args
            y = ragged_fused_experts(x_, gout, wt, cfg, act2)
            return jnp.sum(jnp.sin(y)), y

        (l1, y1), g1 = jax.value_and_grad(f_ref, has_aux=True)((x, weights))
        (l2, y2), g2 = jax.value_and_grad(f_fused, has_aux=True)((x, weights))
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y1),
                                   atol=1e-4, rtol=1e-4, err_msg=activation)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=1e-4, rtol=1e-4, err_msg=activation)


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


@pytest.mark.parametrize(
    "d,inter,interleaved,copies",
    [(128, 256, False, False), (128, 256, True, True), (96, 256, False, True),
     (128, 80, False, True)],
    ids=["aligned-in-place", "interleaved-copies", "d96-copies", "i80-copies"],
)
def test_ragged_fused_makes_no_copy_of_the_expert_weight(
    monkeypatch, d, inter, interleaved, copies
):
    """The guard that the per-call weight split does not creep back: at
    non-interleaved 128-multiple widths the forward AND backward programs of
    ragged_fused_experts define no [E, D, I] value (a half of gate_up) and
    apply no split/slice/gather to anything as large as the weight; the
    interleaved layout and widths off the 128 grid still copy."""
    monkeypatch.setenv("AUTOMODEL_GMM_INTERPRET", "1")
    from automodel_tpu.moe.experts import ragged_fused_experts
    from automodel_tpu.moe.layer import make_act2

    cfg = MoEConfig(num_experts=4, num_experts_per_tok=2,
                    moe_intermediate_size=inter,
                    interleaved_gate_up=interleaved)
    p, x = _params(cfg, d=d), _x(d=d)
    gout = gate(x, p["router"]["weight"], cfg)
    act2 = make_act2(cfg, jax.nn.silu)

    def loss(x_, w):
        y = ragged_fused_experts(x_, gout, w, cfg, act2)
        return (y.astype(jnp.float32) ** 2).sum()

    E, D, I = cfg.num_experts, d, inter

    def halves_and_cuts(fn):
        eqns = list(_all_eqns(jax.make_jaxpr(fn)(x, p["experts"]).jaxpr))
        halves = [
            e.primitive.name for e in eqns
            if any(getattr(v.aval, "shape", None) == (E, D, I) for v in e.outvars)
        ]
        cuts = [
            e.primitive.name for e in eqns
            if e.primitive.name in ("split", "slice", "dynamic_slice", "gather")
            and any(getattr(v.aval, "shape", ()) == (E, D, 2 * I)
                    for v in e.invars if hasattr(v, "aval"))
        ]
        return halves, cuts

    halves, cuts = halves_and_cuts(loss)
    assert bool(halves or cuts) == copies, (halves, cuts)
    # backward: the weight is not cut either, and its GRADIENT is the ONE
    # [E, D, 2I] array `_bwd_gu` writes (PR 40): no [E, D, I] half, nothing
    # to concatenate
    halves, cuts = halves_and_cuts(jax.grad(loss, argnums=(0, 1)))
    assert bool(cuts) == copies, cuts
    assert bool(halves) == copies, halves
