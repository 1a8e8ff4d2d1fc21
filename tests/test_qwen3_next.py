"""Qwen3-Next hybrid (gated DeltaNet linear attention + gated full attention
+ qwen2-moe-style MoE): HF numerical parity + delta-rule kernel parity +
e2e training on a mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.qwen3_next import (
    Qwen3NextConfig,
    Qwen3NextForCausalLM,
    Qwen3NextStateDictAdapter,
)

FP32 = BackendConfig(
    attn="sdpa", param_dtype="float32", compute_dtype="float32", experts="dense"
)


def _hf_tiny():
    import torch

    torch.manual_seed(0)
    from transformers import Qwen3NextConfig as HFCfg, Qwen3NextForCausalLM as HFModel

    cfg = HFCfg(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=2, num_key_value_heads=1,
        head_dim=16, linear_conv_kernel_dim=4, linear_key_head_dim=8,
        linear_value_head_dim=8, linear_num_key_heads=2, linear_num_value_heads=4,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, norm_topk_prob=True,
        partial_rotary_factor=0.25, rope_theta=10000.0,
        layer_types=["linear_attention", "linear_attention", "linear_attention", "full_attention"],
        attn_implementation="eager",
    )
    return cfg, HFModel(cfg).eval()


@pytest.fixture(scope="module")
def setup():
    hf_cfg, hf_model = _hf_tiny()
    cfg = Qwen3NextConfig.from_hf(hf_cfg)
    adapter = Qwen3NextStateDictAdapter(cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    params = jax.tree.map(jnp.asarray, adapter.from_hf(lambda k: sd[k]))
    model = Qwen3NextForCausalLM(cfg, FP32)
    return hf_cfg, hf_model, cfg, adapter, sd, params, model


def test_config_ingest(setup):
    _, _, cfg, *_ = setup
    assert cfg.layer_types == (
        "linear_attention", "linear_attention", "linear_attention", "full_attention"
    )
    assert cfg.n_linear == 3 and cfg.n_full == 1
    assert cfg.moe.softmax_before_topk and cfg.moe.shared_expert_gate
    assert cfg.moe.num_shared_experts == 1
    assert cfg.rope_dim == 4  # head_dim 16 * 0.25
    assert cfg.key_dim == 16 and cfg.value_dim == 32


def test_logits_parity(setup):
    import torch

    _, hf_model, cfg, _, _, params, model = setup
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 96, size=(2, 20)).astype(np.int64)
    with torch.no_grad():
        hf_logits = hf_model(input_ids=torch.from_numpy(ids)).logits.numpy()
    logits, aux = jax.jit(lambda p, i: model(p, i))(params, jnp.asarray(ids))
    np.testing.assert_allclose(
        np.asarray(logits), hf_logits, atol=5e-4, rtol=2e-3
    )
    assert aux.expert_counts.shape == (4, 4)


def test_roundtrip(setup):
    _, _, cfg, adapter, sd, params, _ = setup
    out_sd = dict(adapter.to_hf(jax.device_get(params)))
    assert set(out_sd) == set(sd)
    for k, v in sd.items():
        np.testing.assert_allclose(out_sd[k], v, atol=1e-6, err_msg=k)


def test_train_step_on_mesh(devices8):
    from automodel_tpu import auto_model
    from automodel_tpu.data.loader import place_batch
    from automodel_tpu.optim.builders import build_optimizer, init_opt_state
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    hf = {
        "architectures": ["Qwen3NextForCausalLM"],
        "model_type": "qwen3_next",
        "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 16,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 8,
        "linear_value_head_dim": 8, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "num_experts": 4,
        "num_experts_per_tok": 2, "moe_intermediate_size": 16,
        "shared_expert_intermediate_size": 16, "norm_topk_prob": True,
        "partial_rotary_factor": 0.25,
        "layer_types": ["linear_attention", "full_attention"],
    }
    ctx = build_mesh(MeshConfig(dp_shard=4, tp=2), devices=devices8)
    auto = auto_model.from_config(
        hf, ctx,
        {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32",
         "experts": "ragged"},
        seed=0,
    )
    opt = build_optimizer(name="adamw", lr=2e-3, grad_clip_norm=1.0)
    state = TrainState.create(auto.params, init_opt_state(opt, auto.params, auto.mesh_ctx))
    step = build_train_step(
        make_causal_lm_loss(auto.model, constrain=auto.constrain), opt
    )
    ids = np.random.default_rng(0).integers(0, 96, size=(1, 8, 64)).astype(np.int32)
    batch = place_batch(ctx, {"input_ids": ids, "labels": ids})
    losses = []
    for _ in range(3):
        state, m = step(state, batch)
        losses.append(float(jax.device_get(m["loss"])))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_packed_matches_unpacked(setup):
    """VERDICT r3 #8: segment resets in the conv window + chunked delta
    recurrence — a 2-doc packed row must reproduce each doc's unpacked
    logits (the reference trains hybrids packed via the THD path)."""
    _, _, cfg, _, _, params, model = setup
    rng = np.random.default_rng(7)
    la, lb = 40, 56  # spans several delta chunks? chunk=64; crosses chunk bdry
    doc_a = rng.integers(0, 96, (1, la))
    doc_b = rng.integers(0, 96, (1, lb))

    # one program a shape: op by op, each of the three calls is hundreds
    run = jax.jit(lambda p, i, **kw: model(p, i, **kw)[0])
    ref_a, ref_b = run(params, jnp.asarray(doc_a)), run(params, jnp.asarray(doc_b))

    packed = jnp.asarray(np.concatenate([doc_a, doc_b], axis=1))
    seg = jnp.asarray(
        np.concatenate([np.zeros((1, la)), np.ones((1, lb))], axis=1), jnp.int32
    )
    pos = jnp.asarray(
        np.concatenate([np.arange(la)[None], np.arange(lb)[None]], axis=1),
        jnp.int32,
    )
    got = run(params, packed, segment_ids=seg, position_ids=pos)
    np.testing.assert_allclose(
        np.asarray(got[:, :la]), np.asarray(ref_a), atol=2e-4, rtol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(got[:, la:]), np.asarray(ref_b), atol=2e-4, rtol=2e-3
    )
