"""Qwen3-MoE model: HF numerical parity + sharded training step.

Ground truth mirrors test_llama_parity.py: random tiny HF Qwen3MoeForCausalLM
→ adapter → logits match. Training: full train step with EP+FSDP sharding on
the 8-device mesh, aux loss and bias update active.
"""

import dataclasses

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


def _hf_tiny():
    import torch
    from transformers import Qwen3MoeConfig, Qwen3MoeForCausalLM

    torch.manual_seed(0)
    cfg = Qwen3MoeConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        moe_intermediate_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        num_experts=8,
        num_experts_per_tok=2,
        decoder_sparse_step=1,
        norm_topk_prob=True,
        mlp_only_layers=[],
        max_position_embeddings=256,
        tie_word_embeddings=False,
        router_aux_loss_coef=0.0,
    )
    return cfg, Qwen3MoeForCausalLM(cfg).eval()


FP32 = dict(param_dtype="float32", compute_dtype="float32")


@pytest.mark.parametrize("experts_backend", ["dense", "ragged", "gspmd"])
def test_logits_parity_with_hf(experts_backend):
    import torch

    hf_cfg, hf_model = _hf_tiny()
    cfg = MoETransformerConfig.from_hf(hf_cfg)
    assert cfg.moe.num_experts == 8 and cfg.qk_norm
    # gspmd path needs headroom to avoid drops in the parity check
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    model = MoEForCausalLM(cfg, BackendConfig(attn="sdpa", experts=experts_backend, **FP32))

    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    params = jax.tree.map(jnp.asarray, MoEStateDictAdapter(cfg).from_hf(lambda k: sd[k]))

    ids = np.random.default_rng(0).integers(0, 128, size=(2, 16))
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids)).logits.numpy()
    out, aux = jax.jit(lambda p, i: model(p, i))(params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(out), ref, atol=3e-4, rtol=3e-3)
    assert int(aux.expert_counts.sum()) == 2 * 2 * 16 * 2  # L*B*S*K


def test_hf_roundtrip():
    hf_cfg, hf_model = _hf_tiny()
    cfg = MoETransformerConfig.from_hf(hf_cfg)
    adapter = MoEStateDictAdapter(cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    params = adapter.from_hf(lambda k: sd[k])
    out_sd = dict(adapter.to_hf(params))
    for k, v in sd.items():
        np.testing.assert_array_equal(out_sd[k], v, err_msg=k)


def test_train_step_ep_sharded(devices8):
    """Full jitted train step with EP+FSDP+aux-free bias on the 8-dev mesh."""
    from automodel_tpu import auto_model
    from automodel_tpu.data.loader import place_batch
    from automodel_tpu.optim.builders import build_optimizer, init_opt_state
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    hf = {
        "architectures": ["Qwen3MoeForCausalLM"],
        "model_type": "qwen3_moe",
        "vocab_size": 128,
        "hidden_size": 64,
        "intermediate_size": 128,
        "moe_intermediate_size": 32,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 16,
        "num_experts": 8,
        "num_experts_per_tok": 2,
        "norm_topk_prob": True,
        "router_aux_loss_coef": 0.01,
        "topk_method": "noaux_tc",  # enables aux-free bias balancing
    }
    ctx = build_mesh(MeshConfig(dp_shard=4, ep=2, tp=2), devices=devices8)
    auto = auto_model.from_config(hf, ctx, {"attn": "sdpa", **FP32}, seed=0)
    opt = build_optimizer(name="adamw", lr=1e-3, grad_clip_norm=1.0)
    state = TrainState.create(auto.params, init_opt_state(opt, auto.params, auto.mesh_ctx))
    loss_fn = make_causal_lm_loss(auto.model, constrain=auto.constrain)
    step = build_train_step(
        loss_fn, opt, post_step_fn=auto.model.post_step_fn
    )
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, size=(2, 4, 16))
    batch = place_batch(
        ctx, {"input_ids": ids.astype(np.int32), "labels": ids.astype(np.int32)}
    )
    bias_before = np.asarray(
        state.params["moe_layers"]["moe"]["router"]["bias"]
    )
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]  # learns the repeated batch
    assert "moe_aux_loss" in metrics and "expert_load_imbalance" in metrics
    bias_after = np.asarray(state.params["moe_layers"]["moe"]["router"]["bias"])
    assert not np.array_equal(bias_before, bias_after)  # aux-free update ran


def test_full_save_dispatch_remat_matches_full():
    """remat='full_save_dispatch' (sort permutations saved across the remat
    boundary) must produce identical loss and grads to remat='full'."""
    import jax
    import jax.numpy as jnp

    from automodel_tpu import auto_model

    hf = {
        "architectures": ["Qwen3MoeForCausalLM"], "model_type": "qwen3_moe",
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "moe_intermediate_size": 16, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "num_experts": 4, "num_experts_per_tok": 2, "norm_topk_prob": True,
    }
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 16)))

    def run(remat):
        auto = auto_model.from_config(
            hf, None, {"attn": "sdpa", "param_dtype": "float32",
                       "compute_dtype": "float32", "experts": "ragged",
                       "remat": remat}, seed=0)

        def loss(p):
            logits, aux = auto.model(p, ids)
            return jnp.mean(logits.astype(jnp.float32) ** 2) + aux.aux_loss

        return jax.jit(jax.value_and_grad(loss))(auto.params)

    l_full, g_full = run("full")
    l_sd, g_sd = run("full_save_dispatch")
    np.testing.assert_allclose(float(l_sd), float(l_full), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_sd)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5,
                                   atol=1e-6)
