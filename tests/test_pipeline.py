"""Pipeline parallelism: forward/grad parity vs non-PP, and e2e training.

The reference validates PP via 3D (PP+FSDP+TP) composition tests (SURVEY.md
§2.10); here the 8-device mesh gives pp=2 × dp=2 × tp=2.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from automodel_tpu import auto_model
from automodel_tpu.parallel.mesh import MeshConfig, build_mesh

HF = {
    "architectures": ["LlamaForCausalLM"],
    "model_type": "llama",
    "vocab_size": 128,
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 4,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
}
FP32 = {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"}


@pytest.fixture(scope="module")
def pp_setup(devices8):
    ctx = build_mesh(MeshConfig(pp=2, dp_shard=2, tp=2), devices=devices8)
    auto_pp = auto_model.from_config(HF, ctx, {**FP32, "pp_microbatches": 4}, seed=0)
    auto_ref = auto_model.from_config(HF, None, FP32, seed=0)
    return ctx, auto_pp, auto_ref


def test_pp_forward_matches_unpipelined(pp_setup):
    ctx, auto_pp, auto_ref = pp_setup
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, size=(8, 16)), jnp.int32
    )
    out_pp = np.asarray(jax.jit(auto_pp.model.__call__)(auto_pp.params, ids))
    out_ref = np.asarray(auto_ref.model(auto_ref.params, ids))
    np.testing.assert_allclose(out_pp, out_ref, atol=2e-4, rtol=2e-3)


def test_pp_grads_match_unpipelined(pp_setup):
    ctx, auto_pp, auto_ref = pp_setup
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, 128, size=(8, 16)), jnp.int32
    )

    def loss(model):
        def f(p):
            return model(p, ids).astype(jnp.float32).sum()

        return f

    g_pp = jax.jit(jax.grad(loss(auto_pp.model)))(auto_pp.params)
    g_ref = jax.grad(loss(auto_ref.model))(auto_ref.params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3
        ),
        jax.device_get(g_pp),
        jax.device_get(g_ref),
    )


def test_pp_train_step_learns(pp_setup):
    from automodel_tpu.data.loader import place_batch
    from automodel_tpu.optim.builders import build_optimizer
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    ctx, auto_pp, _ = pp_setup
    opt = build_optimizer(name="adamw", lr=1e-3, grad_clip_norm=1.0)
    state = TrainState.create(auto_pp.params, jax.jit(opt.init)(auto_pp.params))
    loss_fn = make_causal_lm_loss(auto_pp.model, constrain=auto_pp.constrain)
    step = build_train_step(loss_fn, opt)
    ids = np.random.default_rng(0).integers(0, 128, size=(1, 8, 16)).astype(np.int32)
    batch = place_batch(ctx, {"input_ids": ids, "labels": ids})
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_pp_requires_divisible_layers(devices8):
    ctx = build_mesh(MeshConfig(pp=2, dp_shard=4), devices=devices8)
    bad = dict(HF, num_hidden_layers=3)
    with pytest.raises(ValueError, match="divide"):
        auto_model.from_config(bad, ctx, FP32, seed=0)

# ---- MoE + PP composition (VERDICT #105: was explicitly unsupported) --------

MOE_HF = {
    "architectures": ["Qwen3MoeForCausalLM"],
    "model_type": "qwen3_moe",
    "vocab_size": 128,
    "hidden_size": 64,
    "intermediate_size": 128,
    "moe_intermediate_size": 32,
    "num_hidden_layers": 4,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "num_experts": 4,
    "num_experts_per_tok": 2,
    "norm_topk_prob": True,
    # nonzero so the aux-loss parity assertion actually exercises the
    # validity-masked accumulation + /M averaging in spmd_pipeline
    "router_aux_loss_coef": 0.01,
}


@pytest.fixture(scope="module")
def moe_pp_setup(devices8):
    # pp=2 x ep=2 x tp=2: the 3-way composition the reference reaches via
    # per-stage parallelize_fn (moe/parallelizer.py:300)
    ctx = build_mesh(MeshConfig(pp=2, dp_shard=2, ep=2, tp=2), devices=devices8)
    auto_pp = auto_model.from_config(MOE_HF, ctx, {**FP32, "pp_microbatches": 4}, seed=0)
    auto_ref = auto_model.from_config(MOE_HF, None, FP32, seed=0)
    return ctx, auto_pp, auto_ref


def test_moe_pp_forward_and_aux_match(moe_pp_setup):
    ctx, auto_pp, auto_ref = moe_pp_setup
    ids = jnp.asarray(
        np.random.default_rng(2).integers(0, 128, size=(8, 16)), jnp.int32
    )
    out_pp, aux_pp = jax.jit(auto_pp.model.__call__)(auto_pp.params, ids)
    out_ref, aux_ref = auto_ref.model(auto_ref.params, ids)
    np.testing.assert_allclose(
        np.asarray(out_pp), np.asarray(out_ref), atol=2e-4, rtol=2e-3
    )
    # per-layer expert counts and summed aux loss survive the pipeline
    np.testing.assert_allclose(
        np.asarray(aux_pp.expert_counts),
        np.asarray(aux_ref.expert_counts),
        atol=1e-3,
    )
    np.testing.assert_allclose(
        float(aux_pp.aux_loss), float(aux_ref.aux_loss), rtol=1e-4, atol=1e-6
    )


def test_moe_pp_grads_match(moe_pp_setup):
    ctx, auto_pp, auto_ref = moe_pp_setup
    ids = jnp.asarray(
        np.random.default_rng(3).integers(0, 128, size=(8, 16)), jnp.int32
    )

    def loss(model):
        def f(p):
            logits, aux = model(p, ids)
            return logits.astype(jnp.float32).sum() + aux.aux_loss.astype(jnp.float32)

        return f

    g_pp = jax.jit(jax.grad(loss(auto_pp.model)))(auto_pp.params)
    g_ref = jax.grad(loss(auto_ref.model))(auto_ref.params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3
        ),
        jax.device_get(g_pp),
        jax.device_get(g_ref),
    )


def test_pp4_forward_matches(devices8):
    ctx = build_mesh(MeshConfig(pp=4, dp_shard=2), devices=devices8)
    auto_pp = auto_model.from_config(HF, ctx, {**FP32, "pp_microbatches": 8}, seed=0)
    auto_ref = auto_model.from_config(HF, None, FP32, seed=0)
    ids = jnp.asarray(
        np.random.default_rng(4).integers(0, 128, size=(8, 16)), jnp.int32
    )
    out_pp = np.asarray(jax.jit(auto_pp.model.__call__)(auto_pp.params, ids))
    out_ref = np.asarray(auto_ref.model(auto_ref.params, ids))
    np.testing.assert_allclose(out_pp, out_ref, atol=2e-4, rtol=2e-3)


def test_pp_no_full_activation_psum(pp_setup):
    """The pipeline output leaves the shard_map sharded on pp and is sliced —
    the compiled HLO must not contain an all-reduce over full [B,S,D]
    activations (VERDICT weak #4)."""
    ctx, auto_pp, _ = pp_setup
    ids = jnp.asarray(np.zeros((8, 16)), jnp.int32)
    compiled = jax.jit(auto_pp.model.__call__).lower(auto_pp.params, ids).compile()
    hlo = compiled.as_text()
    import re

    # the old psum was rank-4 [ticks, mb, S, D]; TP's legitimate per-layer
    # partial-sum all-reduces are rank-3 [mb, S, D] and stay
    bad = []
    for m in re.finditer(r"all-reduce[^=\n]*=\s*\(?(\S+?)[\s,)]", hlo):
        shape = m.group(1)
        dims = [int(d) for d in re.findall(r"(?<=[\[,])\d+(?=[\],])", shape)]
        if len(dims) >= 4 and np.prod(dims) >= 4 * 2 * 16 * 64:
            bad.append(m.group(0))
    assert not bad, bad


def test_moe_pp_a2a_manual_matches(devices8):
    """PP x EP with experts='a2a' runs the token-exchange body with ep
    MANUAL inside the pipeline region (VERDICT r2 #5) — no silent ragged
    downgrade — and matches the unpipelined forward."""
    import automodel_tpu.parallel.pp as ppm

    ctx = build_mesh(MeshConfig(pp=2, ep=2, dp_shard=4), devices=devices8)
    backend = {**FP32, "experts": "a2a", "pp_microbatches": 2}
    auto_pp = auto_model.from_config(MOE_HF, ctx, backend, seed=0)
    # reference must be DROPLESS too (a2a with no mesh → single-slice
    # ragged); the default gspmd backend drops late over-capacity picks
    auto_ref = auto_model.from_config(MOE_HF, None, {**FP32, "experts": "a2a"}, seed=0)
    ids = jnp.asarray(
        np.random.default_rng(7).integers(0, 128, size=(4, 32)), jnp.int32
    )
    ppm._logged_a2a_pp = False
    out_pp, aux_pp = jax.jit(lambda p, i: auto_pp.model(p, i))(auto_pp.params, ids)
    out_ref, aux_ref = auto_ref.model(auto_ref.params, ids)
    assert not ppm._logged_a2a_pp, "a2a silently downgraded to ragged under PP"
    np.testing.assert_allclose(
        np.asarray(out_pp), np.asarray(out_ref), atol=2e-4, rtol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(aux_pp.expert_counts), np.asarray(aux_ref.expert_counts)
    )

    # gradients flow through the manual exchange
    def loss_pp(p):
        out, aux = auto_pp.model(p, ids)
        return (out.astype(jnp.float32) ** 2).mean() + aux.aux_loss

    def loss_ref(p):
        out, aux = auto_ref.model(p, ids)
        return (out.astype(jnp.float32) ** 2).mean() + aux.aux_loss

    g_pp = jax.jit(jax.grad(loss_pp))(auto_pp.params)
    g_ref = jax.grad(loss_ref)(auto_ref.params)
    for path, a, b in zip(
        [p for p, _ in jax.tree_util.tree_flatten_with_path(g_ref)[0]],
        jax.tree.leaves(g_pp),
        jax.tree.leaves(g_ref),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-3,
            err_msg=str(path),
        )


def test_moe_pp_a2a_fused_matches_unfused(devices8, monkeypatch):
    """experts='a2a_fused' inside the pp x ep manual region (the fused
    local expert MLP on the token-exchange path) matches the unfused a2a
    pipeline forward — with the PALLAS KERNEL actually running (interpret
    mode), so vma/grid problems of a pallas_call nested in the pp-manual
    shard_map surface here, not on the first real-TPU PP run."""
    monkeypatch.setenv("AUTOMODEL_GMM_INTERPRET", "1")
    import automodel_tpu.parallel.pp as ppm

    ctx = build_mesh(MeshConfig(pp=2, ep=2, dp_shard=4), devices=devices8)
    ids = jnp.asarray(
        np.random.default_rng(9).integers(0, 128, size=(4, 32)), jnp.int32
    )
    outs = {}
    for exp in ("a2a", "a2a_fused"):
        ppm._logged_a2a_pp = False
        auto = auto_model.from_config(
            MOE_HF, ctx, {**FP32, "experts": exp, "pp_microbatches": 2}, seed=0
        )
        out, _ = jax.jit(lambda p, i: auto.model(p, i))(auto.params, ids)
        assert not ppm._logged_a2a_pp, f"{exp} silently downgraded under PP"
        outs[exp] = np.asarray(out)
    np.testing.assert_allclose(
        outs["a2a_fused"], outs["a2a"], atol=2e-5, rtol=1e-5
    )


# ---- zero-bubble schedule (B/W split, parallel/zero_bubble.py) --------------
# These meshes keep every non-pp axis at size 1: the zero-bubble region is
# manual over pp only.

ZB_TOL = dict(atol=2e-3, rtol=2e-3)  # fp32-accum reordering tolerance


def _grad_tree(model, params, ids):
    def f(p):
        out = model(p, ids)
        logits = out[0] if isinstance(out, tuple) else out
        loss = logits.astype(jnp.float32).sum()
        if isinstance(out, tuple):
            loss = loss + out[1].aux_loss.astype(jnp.float32)
        return loss

    return jax.device_get(jax.jit(jax.grad(f))(params))


def test_zero_bubble_matches_gpipe_dense(devices8):
    autos = {}
    for sched in ("gpipe", "zero_bubble"):
        ctx = build_mesh(
            MeshConfig(pp=2, dp_shard=1, pp_schedule=sched), devices=devices8[:2]
        )
        autos[sched] = auto_model.from_config(
            HF, ctx, {**FP32, "pp_microbatches": 4}, seed=0
        )
    assert autos["zero_bubble"].model.schedule == "zero_bubble"
    ids = jnp.asarray(
        np.random.default_rng(11).integers(0, 128, size=(8, 16)), jnp.int32
    )
    out = {
        s: np.asarray(jax.jit(a.model.__call__)(a.params, ids))
        for s, a in autos.items()
    }
    np.testing.assert_allclose(out["zero_bubble"], out["gpipe"], **ZB_TOL)
    g_g = _grad_tree(autos["gpipe"].model, autos["gpipe"].params, ids)
    g_z = _grad_tree(
        autos["zero_bubble"].model, autos["zero_bubble"].params, ids
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), **ZB_TOL
        ),
        g_z,
        g_g,
    )


def test_zero_bubble_law_below_gpipe():
    """Acceptance: analytic bubble fraction below the GPipe law
    (S−1)/(m+S−1) for m ∈ {4, 8, 16} at S ∈ {2, 4}."""
    from automodel_tpu.utils.flops_utils import (
        gpipe_bubble_fraction,
        zero_bubble_fraction,
    )

    for pp in (2, 4):
        for m in (4, 8, 16):
            zb = zero_bubble_fraction(pp, m)
            gp = gpipe_bubble_fraction(pp, m)
            assert zb < gp, (pp, m, zb, gp)
            # a bounded queue is the memory escape hatch, not a speedup:
            # every B tick then carries a W contraction (the combined-
            # schedule cost) plus a q-slot flush tail — at worst slightly
            # above the GPipe law, never better than full deferral
            for q in (1, 2):
                zq = zero_bubble_fraction(pp, m, zb_queue=q)
                assert zb <= zq <= gp + q / (4.0 * (m + pp - 1)), (pp, m, q, zq)
            # partial deferral (MoE attention-only taps) interpolates:
            # d=0 recovers the GPipe law exactly, d∈(0,1) sits between
            assert zero_bubble_fraction(
                pp, m, w_deferred_fraction=0.0
            ) == pytest.approx(gp)
            zhalf = zero_bubble_fraction(pp, m, w_deferred_fraction=0.5)
            assert zb < zhalf < gp
