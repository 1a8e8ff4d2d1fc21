"""FP8 training path (reference quantization/fp8.py + te_fp8 recipes):
e4m3-forward / e5m2-gradient matmuls with per-tensor dynamic scaling."""

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.ops.fp8 import fp8_dot


def test_fp8_dot_value_close():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    ref = np.asarray(x @ w)
    out = np.asarray(fp8_dot(x, w))
    # e4m3 ~ 3 mantissa bits after per-tensor scaling
    denom = np.abs(ref).max()
    assert np.abs(out - ref).max() / denom < 0.12


def test_fp8_dot_grads_flow():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((8, 6)), jnp.float32)

    def loss(x, w):
        return (fp8_dot(x, w) ** 2).sum()

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    rx, rw = jax.grad(lambda x, w: ((x @ w) ** 2).sum(), argnums=(0, 1))(x, w)
    for g, r in ((gx, rx), (gw, rw)):
        denom = np.abs(np.asarray(r)).max()
        assert np.abs(np.asarray(g) - np.asarray(r)).max() / denom < 0.25
        assert np.isfinite(np.asarray(g)).all()


def test_llama_trains_with_fp8(devices8):
    from automodel_tpu import auto_model
    from automodel_tpu.data.loader import place_batch
    from automodel_tpu.optim.builders import build_optimizer, init_opt_state
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    hf = {
        "architectures": ["LlamaForCausalLM"], "model_type": "llama",
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 16,
    }
    ctx = build_mesh(MeshConfig(dp_shard=8), devices=devices8)
    auto = auto_model.from_config(
        hf, ctx,
        {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32",
         "fp8": True},
        seed=0,
    )
    opt = build_optimizer(name="adamw", lr=5e-3, grad_clip_norm=1.0)
    state = TrainState.create(auto.params, init_opt_state(opt, auto.params, auto.mesh_ctx))
    step = build_train_step(
        make_causal_lm_loss(auto.model, constrain=auto.constrain), opt
    )
    ids = np.random.default_rng(0).integers(0, 64, size=(1, 8, 16)).astype(np.int32)
    batch = place_batch(ctx, {"input_ids": ids, "labels": ids})
    losses = []
    for _ in range(4):
        state, m = step(state, batch)
        losses.append(float(jax.device_get(m["loss"])))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_fp8_experts_qdq_blockwise():
    """Blockwise e4m3 QDQ: ≤256 distinct levels per 128x128 block, STE
    identity gradient, and error bounded by the block absmax/448 step."""
    import numpy as np
    from automodel_tpu.ops.fp8 import fp8_qdq_blockwise, fp8_qdq_tensor

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(4, 200, 300)), jnp.float32)  # non-divisible dims
    q = fp8_qdq_blockwise(w, block=128)
    assert q.shape == w.shape and q.dtype == w.dtype
    err = float(jnp.abs(q - w).max())
    assert 0 < err < 0.2 * float(jnp.abs(w).max())
    g = jax.grad(lambda w: fp8_qdq_blockwise(w).sum())(w)
    np.testing.assert_array_equal(np.asarray(g), np.ones_like(np.asarray(g)))
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    g = jax.grad(lambda x: fp8_qdq_tensor(x).sum())(x)
    np.testing.assert_array_equal(np.asarray(g), np.ones_like(np.asarray(g)))


def test_fp8_experts_path_close_to_bf16():
    """ragged experts with fp8=True stays close to the exact path and trains
    (reference GroupedExpertsFP8 tolerance-level parity)."""
    import numpy as np
    from automodel_tpu.moe.config import MoEConfig
    from automodel_tpu.moe.experts import ragged_experts
    from automodel_tpu.moe.gate import gate

    rng = np.random.default_rng(1)
    T, D, E, I, K = 48, 32, 4, 24, 2
    cfg = MoEConfig(num_experts=E, num_experts_per_tok=K,
                    moe_intermediate_size=I, norm_topk_prob=True)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(D, E)), jnp.float32) * 0.1
    weights = {
        "gate_up": jnp.asarray(rng.normal(size=(E, D, 2 * I)), jnp.float32) * 0.1,
        "down": jnp.asarray(rng.normal(size=(E, I, D)), jnp.float32) * 0.1,
    }
    gout = gate(x, router, cfg)
    act2 = lambda g, u: jax.nn.silu(g) * u
    exact = ragged_experts(x, gout, weights, cfg, act2)
    fp8 = ragged_experts(x, gout, weights, cfg, act2, fp8=True)
    rel = float(jnp.abs(fp8 - exact).max() / (jnp.abs(exact).max() + 1e-9))
    assert 0 < rel < 0.1, rel
    # gradients flow to weights through the QDQ (STE)
    gw = jax.grad(
        lambda w: ragged_experts(x, gout, w, cfg, act2, fp8=True).sum()
    )(weights)
    assert float(jnp.abs(gw["gate_up"]).max()) > 0
