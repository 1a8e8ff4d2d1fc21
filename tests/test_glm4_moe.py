"""GLM4-MoE: HF numerical parity through the shared MoE family
(sigmoid+bias router like DeepSeek-V3, shared expert, dense prefix,
partial rotary)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.qwen3_moe import (
    MoEForCausalLM,
    MoEStateDictAdapter,
    MoETransformerConfig,
)

# dropless experts for bit-parity: the tiny random model routes all tokens
# to the same experts, which the capacity-based gspmd backend would drop
FP32 = BackendConfig(
    attn="sdpa", param_dtype="float32", compute_dtype="float32", experts="dense"
)


def _hf_tiny():
    import torch

    torch.manual_seed(0)
    from transformers import Glm4MoeConfig, Glm4MoeForCausalLM

    cfg = Glm4MoeConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=3, num_attention_heads=2,
        num_key_value_heads=1, head_dim=16, n_routed_experts=4,
        n_shared_experts=1, num_experts_per_tok=2, first_k_dense_replace=1,
        partial_rotary_factor=0.5, use_qk_norm=True, norm_topk_prob=True,
        routed_scaling_factor=1.5, attn_implementation="eager",
    )
    m = Glm4MoeForCausalLM(cfg).eval()
    # nonzero correction bias so the selection-vs-weight split is exercised
    with torch.no_grad():
        for layer in m.model.layers[1:]:
            layer.mlp.gate.e_score_correction_bias.uniform_(-0.2, 0.2)
    return cfg, m


@pytest.fixture(scope="module")
def setup():
    hf_cfg, hf_model = _hf_tiny()
    cfg = MoETransformerConfig.from_hf(hf_cfg)
    adapter = MoEStateDictAdapter(cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    params = jax.tree.map(jnp.asarray, adapter.from_hf(lambda k: sd[k]))
    model = MoEForCausalLM(cfg, FP32)
    return hf_cfg, hf_model, cfg, adapter, sd, params, model


def test_config_ingest(setup):
    _, _, cfg, *_ = setup
    assert cfg.moe.score_func == "sigmoid"
    assert cfg.moe.expert_bias and cfg.moe.bias_update_factor > 0
    assert cfg.moe.num_shared_experts == 1
    assert cfg.moe.num_dense_layers == 1
    assert cfg.moe.route_scale == 1.5
    assert cfg.qk_norm
    assert cfg.rope_dim == 8  # head_dim 16 * 0.5


def test_logits_parity(setup):
    import torch

    _, hf_model, cfg, _, _, params, model = setup
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 96, size=(2, 12)).astype(np.int64)
    with torch.no_grad():
        hf_logits = hf_model(input_ids=torch.from_numpy(ids)).logits.numpy()
    logits, aux = jax.jit(lambda p, i: model(p, i))(params, jnp.asarray(ids))
    np.testing.assert_allclose(
        np.asarray(logits), hf_logits, atol=3e-4, rtol=2e-3
    )
    assert aux.expert_counts.shape == (2, 4)  # [L_moe, E]


def test_roundtrip(setup):
    _, _, cfg, adapter, sd, params, _ = setup
    out_sd = dict(adapter.to_hf(jax.device_get(params)))
    for k, v in sd.items():
        np.testing.assert_allclose(out_sd[k], v, atol=1e-6, err_msg=k)


def test_train_step_on_mesh(setup, devices8):
    from automodel_tpu import auto_model
    from automodel_tpu.data.loader import place_batch
    from automodel_tpu.optim.builders import build_optimizer, init_opt_state
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    hf = {
        "architectures": ["Glm4MoeForCausalLM"],
        "model_type": "glm4_moe",
        "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
        "moe_intermediate_size": 16, "num_hidden_layers": 3,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
        "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
        "first_k_dense_replace": 1, "partial_rotary_factor": 0.5,
        "use_qk_norm": True, "norm_topk_prob": True,
    }
    ctx = build_mesh(MeshConfig(dp_shard=4, ep=2, tp=2), devices=devices8)
    auto = auto_model.from_config(
        hf, ctx, {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32",
                  "experts": "a2a"},
        seed=0,
    )
    opt = build_optimizer(name="adamw", lr=2e-3, grad_clip_norm=1.0)
    state = TrainState.create(auto.params, init_opt_state(opt, auto.params, auto.mesh_ctx))
    step = build_train_step(
        make_causal_lm_loss(auto.model, constrain=auto.constrain), opt,
        post_step_fn=auto.model.post_step_fn,
    )
    ids = np.random.default_rng(0).integers(0, 96, size=(1, 8, 16)).astype(np.int32)
    batch = place_batch(ctx, {"input_ids": ids, "labels": ids})
    losses = []
    for _ in range(3):
        state, m = step(state, batch)
        losses.append(float(jax.device_get(m["loss"])))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
