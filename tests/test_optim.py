"""Optimizer numerics contracts.

The single-microbatch train-step fast path feeds BF16 grads straight into
the optimizer (training/train_step.py). optax's scale_by_adam inherits the
update dtype for its moments — bf16 nu's half-ulp exceeds the (1-b2)·g²
increment at b2=0.999 and the second moment freezes. These tests pin the
repo's adam to fp32 moments and the clip to fp32 norm accumulation
regardless of grad dtype (torch AdamW parity: fp32 exp_avg/exp_avg_sq).
"""

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.optim.builders import (
    build_optimizer,
    clip_by_global_norm_fp32,
    scale_by_adam_fp32_moments,
)


def test_adam_moments_stay_fp32_with_bf16_grads():
    params = {"w": jnp.ones((8, 8), jnp.bfloat16)}
    opt = scale_by_adam_fp32_moments(b1=0.9, b2=0.999)
    state = opt.init(params)
    assert state.mu["w"].dtype == jnp.float32
    assert state.nu["w"].dtype == jnp.float32
    g = {"w": jnp.full((8, 8), 1e-2, jnp.bfloat16)}
    nu_prev = None
    for _ in range(5):
        upd, state = opt.update(g, state)
        assert state.nu["w"].dtype == jnp.float32
        nu = float(state.nu["w"][0, 0])
        if nu_prev is not None:
            # the second moment must keep ACCUMULATING: with bf16 moments the
            # (1-b2)*g^2 increment rounds to a no-op after the first step
            assert nu > nu_prev, (nu, nu_prev)
        nu_prev = nu


def test_adam_fp32_moments_matches_optax_on_fp32_grads():
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}
    g = {"w": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}
    ours = scale_by_adam_fp32_moments(b1=0.9, b2=0.95, eps=1e-8)
    ref = __import__("optax").scale_by_adam(b1=0.9, b2=0.95, eps=1e-8)
    so, sr = ours.init(params), ref.init(params)
    for _ in range(3):
        uo, so = ours.update(g, so)
        ur, sr = ref.update(g, sr)
        np.testing.assert_allclose(uo["w"], ur["w"], rtol=1e-6)


def test_clip_fp32_does_not_saturate_on_bf16():
    # 1M bf16 elements of equal magnitude: bf16 partial sums saturate, the
    # fp32 clip must still compute the true norm (=10.0) and scale correctly
    g = {"w": jnp.full((1024, 1024), 10.0 / 1024.0, jnp.bfloat16)}
    clip = clip_by_global_norm_fp32(1.0)
    upd, _ = clip.update(g, clip.init(g))
    norm_after = float(
        jnp.sqrt(jnp.sum(jnp.square(upd["w"].astype(jnp.float32))))
    )
    np.testing.assert_allclose(norm_after, 1.0, rtol=2e-2)


def test_build_optimizer_end_to_end_bf16_loss_decreases():
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(64, 16))
    w_true = rng.normal(size=(16,))
    x = jnp.asarray(xs, jnp.bfloat16)
    y = jnp.asarray(xs @ w_true, jnp.bfloat16)  # fittable target
    params = {"w": jnp.zeros((16,), jnp.bfloat16)}
    opt = build_optimizer(name="adamw", lr=1e-2, weight_decay=0.01,
                          grad_clip_norm=1.0)
    state = opt.init(params)

    def loss_fn(p):
        pred = x @ p["w"]
        return jnp.mean(jnp.square(pred - y).astype(jnp.float32))

    losses = []
    for _ in range(50):
        l, g = jax.value_and_grad(loss_fn)(params)
        upd, state = opt.update(g, state, params)
        params = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32) + u.astype(jnp.float32)).astype(p.dtype),
            params, upd,
        )
        losses.append(float(l))
    assert losses[-1] < 0.5 * losses[0], (losses[0], losses[-1])


def test_train_state_starts_laid_out_as_the_step_returns_it(devices8):
    """Adam's moments are born on their params' shards (a bare
    ``jit(optimizer.init)`` puts all of them on device 0) and the step
    counter on the mesh — so the state going into step 1 is laid out like
    the state coming out, and the train step compiles exactly once."""
    from automodel_tpu import auto_model
    from automodel_tpu.data.loader import place_batch
    from automodel_tpu.optim.builders import init_opt_state
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import (
        build_train_step,
        make_causal_lm_loss,
    )

    hf = {
        "architectures": ["LlamaForCausalLM"], "model_type": "llama",
        "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
    }
    ctx = build_mesh(MeshConfig(dp_shard=4, tp=2), devices=devices8)
    auto = auto_model.from_config(hf, ctx, {"attn": "sdpa"}, seed=0)
    opt = build_optimizer(name="adamw", lr=1e-3, grad_clip_norm=1.0)
    state = TrainState.create(auto.params, init_opt_state(opt, auto.params, ctx))

    mu = state.opt_state[1].mu
    for p, m in zip(jax.tree.leaves(auto.params), jax.tree.leaves(mu)):
        assert m.sharding == p.sharding
    assert state.step.sharding == ctx.replicated()

    step = build_train_step(
        make_causal_lm_loss(auto.model, loss="masked_ce", constrain=auto.constrain),
        opt,
    )
    ids = np.random.default_rng(0).integers(0, 128, (1, 8, 32)).astype(np.int32)
    batch = place_batch(ctx, {"input_ids": ids, "labels": ids})
    for _ in range(2):
        state, _ = step(state, batch)
    assert step._cache_size() == 1
