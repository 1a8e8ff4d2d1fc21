"""DeepSeek-V3 (MLA + sigmoid MoE): HF numerical parity.

Ground truth: tiny random HF DeepseekV3ForCausalLM → adapter → logits match,
covering MLA low-rank q/kv, decoupled interleaved RoPE, dense prefix layers,
shared experts, grouped sigmoid routing with e_score_correction_bias.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.deepseek_v3 import (
    DeepseekV3Config,
    DeepseekV3ForCausalLM,
    DeepseekV3StateDictAdapter,
)

FP32 = dict(param_dtype="float32", compute_dtype="float32")


def _hf_tiny(q_lora_rank=32):
    import torch
    from transformers import DeepseekV3Config as HFCfg
    from transformers import DeepseekV3ForCausalLM as HFModel

    torch.manual_seed(0)
    cfg = HFCfg(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        moe_intermediate_size=32,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=4,
        n_routed_experts=8,
        num_experts_per_tok=2,
        n_shared_experts=1,
        n_group=4,
        topk_group=2,
        first_k_dense_replace=1,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        q_lora_rank=q_lora_rank,
        kv_lora_rank=16,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        max_position_embeddings=256,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    return cfg, HFModel(cfg).eval()


@pytest.mark.parametrize("q_lora_rank", [32, None])
def test_logits_parity_with_hf(q_lora_rank):
    import torch

    hf_cfg, hf_model = _hf_tiny(q_lora_rank)
    cfg = DeepseekV3Config.from_hf(hf_cfg)
    assert cfg.moe.score_func == "sigmoid"
    assert cfg.moe.num_dense_layers == 1
    assert cfg.moe.expert_bias  # noaux_tc → correction bias present
    import dataclasses

    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
    )
    model = DeepseekV3ForCausalLM(cfg, BackendConfig(attn="sdpa", **FP32))
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    params = jax.tree.map(
        jnp.asarray, DeepseekV3StateDictAdapter(cfg).from_hf(lambda k: sd[k])
    )
    ids = np.random.default_rng(0).integers(0, 128, size=(2, 16))
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids)).logits.numpy()
    out, aux = jax.jit(lambda p, i: model(p, i))(params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(out), ref, atol=5e-4, rtol=3e-3)
    # 2 MoE layers × 2 batch × 16 seq × 2 topk
    assert int(aux.expert_counts.sum()) == 2 * 2 * 16 * 2


def test_hf_roundtrip():
    hf_cfg, hf_model = _hf_tiny()
    cfg = DeepseekV3Config.from_hf(hf_cfg)
    adapter = DeepseekV3StateDictAdapter(cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    params = adapter.from_hf(lambda k: sd[k])
    out_sd = dict(adapter.to_hf(params))
    missing = set(sd) - set(out_sd)
    assert not missing, f"to_hf missing keys: {sorted(missing)[:5]}"
    for k, v in sd.items():
        np.testing.assert_array_equal(out_sd[k], v, err_msg=k)


def test_sharded_train_step(devices8):
    from automodel_tpu import auto_model
    from automodel_tpu.data.loader import place_batch
    from automodel_tpu.optim.builders import build_optimizer, init_opt_state
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    hf = {
        "architectures": ["DeepseekV3ForCausalLM"],
        "model_type": "deepseek_v3",
        "vocab_size": 128,
        "hidden_size": 64,
        "intermediate_size": 128,
        "moe_intermediate_size": 32,
        "num_hidden_layers": 3,
        "num_attention_heads": 4,
        "n_routed_experts": 8,
        "num_experts_per_tok": 2,
        "n_shared_experts": 1,
        "n_group": 1,
        "topk_group": 1,
        "first_k_dense_replace": 1,
        "norm_topk_prob": True,
        "scoring_func": "sigmoid",
        "topk_method": "noaux_tc",
        "q_lora_rank": 32,
        "kv_lora_rank": 16,
        "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8,
        "v_head_dim": 16,
    }
    ctx = build_mesh(MeshConfig(dp_shard=4, ep=2, tp=2), devices=devices8)
    auto = auto_model.from_config(hf, ctx, {"attn": "sdpa", **FP32}, seed=0)
    opt = build_optimizer(name="adamw", lr=1e-3, grad_clip_norm=1.0)
    state = TrainState.create(auto.params, init_opt_state(opt, auto.params, auto.mesh_ctx))
    loss_fn = make_causal_lm_loss(auto.model, constrain=auto.constrain)
    step = build_train_step(loss_fn, opt, post_step_fn=auto.model.post_step_fn)
    ids = np.random.default_rng(0).integers(0, 128, size=(1, 4, 16)).astype(np.int32)
    batch = place_batch(ctx, {"input_ids": ids, "labels": ids})
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
