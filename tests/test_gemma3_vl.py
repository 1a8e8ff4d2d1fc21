"""Gemma-3 VLM: HF numerical parity (SigLIP tower, projector avg-pool+norm,
image-feature scatter, bidirectional image-block attention) and e2e training
with a frozen tower. Reference parity target: recipes/vlm/finetune.py +
models VLM families."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.gemma3_vl import (
    Gemma3VLConfig,
    Gemma3VLForConditionalGeneration,
    Gemma3VLStateDictAdapter,
)

FP32 = BackendConfig(attn="sdpa", param_dtype="float32", compute_dtype="float32")

IMG_TOKEN = 120  # inside the tiny vocab
MM_TOKENS = 4  # 2x2 pooled tokens per image


def _hf_tiny():
    import torch

    torch.manual_seed(0)
    from transformers import Gemma3Config, Gemma3ForConditionalGeneration

    cfg = Gemma3Config(
        text_config=dict(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, max_position_embeddings=256, sliding_window=8,
            query_pre_attn_scalar=16, rope_theta=1_000_000.0,
            rope_local_base_freq=10_000.0, attn_implementation="eager",
        ),
        vision_config=dict(
            hidden_size=32, intermediate_size=64, num_hidden_layers=2,
            num_attention_heads=2, image_size=28, patch_size=7,
            attn_implementation="eager",
        ),
        mm_tokens_per_image=MM_TOKENS,
        image_token_index=IMG_TOKEN,
        boi_token_index=121,
        eoi_token_index=122,
        attn_implementation="eager",
    )
    return cfg, Gemma3ForConditionalGeneration(cfg).eval()


def _mk_inputs(rng, batch=2, seq=24, n_images=2):
    """input_ids with one image run (BOI + MM_TOKENS image tokens + EOI) per
    sample + random pixels."""
    ids = rng.integers(0, 100, size=(batch, seq)).astype(np.int64)
    for b in range(batch):
        start = 2 + b  # stagger runs across the batch
        ids[b, start] = 121
        ids[b, start + 1 : start + 1 + MM_TOKENS] = IMG_TOKEN
        ids[b, start + 1 + MM_TOKENS] = 122
    pixels = rng.standard_normal((n_images, 3, 28, 28)).astype(np.float32)
    tt = (ids == IMG_TOKEN).astype(np.int64)
    return ids, pixels, tt


@pytest.fixture(scope="module")
def parity_setup():
    hf_cfg, hf_model = _hf_tiny()
    cfg = Gemma3VLConfig.from_hf(hf_cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    adapter = Gemma3VLStateDictAdapter(cfg)
    params = jax.tree.map(jnp.asarray, adapter.from_hf(lambda k: sd[k]))
    model = Gemma3VLForConditionalGeneration(cfg, FP32)
    return hf_cfg, hf_model, cfg, adapter, sd, params, model


def test_config_ingest(parity_setup):
    _, _, cfg, *_ = parity_setup
    assert cfg.image_token_id == IMG_TOKEN
    assert cfg.mm_tokens_per_image == MM_TOKENS
    assert cfg.vision.num_patches == 16
    assert cfg.text.qk_norm


def test_vision_tower_parity(parity_setup):
    import torch

    hf_cfg, hf_model, cfg, _, _, params, model = parity_setup
    rng = np.random.default_rng(0)
    pixels = rng.standard_normal((2, 3, 28, 28)).astype(np.float32)
    with torch.no_grad():
        hf_out = hf_model.model.vision_tower(
            pixel_values=torch.from_numpy(pixels)
        ).last_hidden_state.numpy()
    from automodel_tpu.models.gemma3_vl.vision import vision_tower

    out = np.asarray(vision_tower(cfg.vision, FP32, params["vision"], pixels))
    np.testing.assert_allclose(out, hf_out, atol=2e-5, rtol=1e-4)


def test_vlm_logits_parity(parity_setup):
    import torch

    hf_cfg, hf_model, cfg, _, _, params, model = parity_setup
    rng = np.random.default_rng(1)
    ids, pixels, tt = _mk_inputs(rng)
    with torch.no_grad():
        hf_logits = hf_model(
            input_ids=torch.from_numpy(ids),
            pixel_values=torch.from_numpy(pixels),
            token_type_ids=torch.from_numpy(tt),
        ).logits.numpy()
    logits = np.asarray(model(params, jnp.asarray(ids), pixel_values=jnp.asarray(pixels)))
    np.testing.assert_allclose(logits, hf_logits, atol=3e-4, rtol=2e-3)


def test_text_only_matches_hf(parity_setup):
    import torch

    _, hf_model, cfg, _, _, params, model = parity_setup
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 100, size=(2, 16)).astype(np.int64)
    with torch.no_grad():
        hf_logits = hf_model(input_ids=torch.from_numpy(ids)).logits.numpy()
    logits = np.asarray(model(params, jnp.asarray(ids)))
    np.testing.assert_allclose(logits, hf_logits, atol=3e-4, rtol=2e-3)


def test_to_hf_roundtrip(parity_setup):
    _, _, cfg, adapter, sd, params, _ = parity_setup
    out_sd = dict(adapter.to_hf(jax.device_get(params)))
    # every key we own round-trips bit-exactly; the unused SigLIP pooling
    # head keys are intentionally not emitted
    for k, v in out_sd.items():
        np.testing.assert_array_equal(v, sd[k], err_msg=k)
    missing = set(sd) - set(out_sd)
    # allowed: unused SigLIP pooling head + the tied lm_head duplicate
    assert all(".head." in k or k == "lm_head.weight" for k in missing), missing


def test_image_group_ids():
    from automodel_tpu.models.gemma3_vl.model import image_group_ids

    ids = jnp.asarray([[1, 9, 9, 2, 9, 9, 3], [9, 1, 2, 3, 4, 5, 9]])
    g = np.asarray(image_group_ids(ids, 9))
    np.testing.assert_array_equal(g[0], [-1, 0, 0, -1, 1, 1, -1])
    np.testing.assert_array_equal(g[1], [0, -1, -1, -1, -1, -1, 1])


def test_vlm_train_step_frozen_tower(devices8):
    """e2e: VLM train step on an 8-device mesh with the vision tower frozen —
    the reference's freeze-config path (recipes/vlm/finetune.py:469)."""
    from automodel_tpu import auto_model
    from automodel_tpu.data.loader import place_batch
    from automodel_tpu.optim.builders import build_optimizer, init_opt_state
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss
    from automodel_tpu.training.freeze import freeze_mask, apply_freeze

    hf = {
        "architectures": ["Gemma3ForConditionalGeneration"],
        "model_type": "gemma3",
        "text_config": {
            "model_type": "gemma3_text",
            "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 2,
            "num_key_value_heads": 1, "head_dim": 32, "sliding_window": 8,
            "query_pre_attn_scalar": 32,
        },
        "vision_config": {
            "model_type": "siglip_vision_model",
            "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 1,
            "num_attention_heads": 2, "image_size": 28, "patch_size": 7,
        },
        "mm_tokens_per_image": MM_TOKENS,
        "image_token_index": IMG_TOKEN,
    }
    ctx = build_mesh(MeshConfig(dp_shard=4, tp=2), devices=devices8)
    auto = auto_model.from_config(
        hf, ctx, {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"},
        seed=0,
    )
    mask = freeze_mask(auto.params, ["vision/*"])
    opt = apply_freeze(build_optimizer(name="adamw", lr=2e-3, grad_clip_norm=1.0), mask)
    state = TrainState.create(auto.params, init_opt_state(opt, auto.params, auto.mesh_ctx))
    step = build_train_step(make_causal_lm_loss(auto.model, constrain=auto.constrain), opt)

    rng = np.random.default_rng(0)
    ids, pixels, _ = _mk_inputs(rng, batch=4, seq=16, n_images=4)
    labels = np.where(ids == IMG_TOKEN, -100, ids)
    batch = place_batch(
        ctx,
        {
            "input_ids": ids[None].astype(np.int32),
            "labels": labels[None].astype(np.int32),
            "pixel_values": pixels[None],
        },
    )
    # capture before stepping — the train step donates the state buffers
    v0 = jax.device_get(auto.params["vision"]["patch_embed"]["kernel"])
    t0 = jax.device_get(auto.params["text"]["embed"]["embedding"])
    losses = []
    for _ in range(3):
        state, m = step(state, batch)
        losses.append(float(jax.device_get(m["loss"])))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    # frozen tower params unchanged; text params moved
    v1 = jax.device_get(state.params["vision"]["patch_embed"]["kernel"])
    np.testing.assert_array_equal(v0, v1)
    t1 = jax.device_get(state.params["text"]["embed"]["embedding"])
    assert np.abs(t1 - t0).max() > 0


def test_vlm_recipe_e2e(tmp_path, devices8):
    """The full `finetune vlm` recipe path: YAML → FinetuneRecipeForVLM →
    frozen-tower training with metrics (reference recipes/vlm/finetune.py)."""
    from automodel_tpu.config.loader import ConfigNode
    from automodel_tpu.recipes.finetune_vlm import main

    cfg = ConfigNode(
        {
            "seed": 3,
            "model": {
                "hf_config": {
                    "architectures": ["Gemma3ForConditionalGeneration"],
                    "model_type": "gemma3",
                    "text_config": {
                        "model_type": "gemma3_text",
                        "vocab_size": 128, "hidden_size": 32,
                        "intermediate_size": 64, "num_hidden_layers": 2,
                        "num_attention_heads": 2, "num_key_value_heads": 1,
                        "head_dim": 16, "sliding_window": 8,
                        "query_pre_attn_scalar": 16,
                    },
                    "vision_config": {
                        "model_type": "siglip_vision_model",
                        "hidden_size": 32, "intermediate_size": 64,
                        "num_hidden_layers": 1, "num_attention_heads": 2,
                        "image_size": 28, "patch_size": 7,
                    },
                    "mm_tokens_per_image": MM_TOKENS,
                    "image_token_index": IMG_TOKEN,
                },
                "backend": {
                    "attn": "sdpa", "param_dtype": "float32",
                    "compute_dtype": "float32",
                },
            },
            "distributed": {"dp_shard": 8, "platform": "cpu"},
            "dataset": {
                "_target_": "automodel_tpu.data.vlm.MockVLMDataset",
                "vocab_size": 128,
                "seq_length": 32,
                "mm_tokens_per_image": MM_TOKENS,
                "image_token_id": IMG_TOKEN,
                "num_samples": 32,
            },
            "dataloader": {"global_batch_size": 8},
            "step_scheduler": {"num_epochs": 1, "max_steps": 4, "log_every_steps": 2},
            "optimizer": {"name": "adamw", "lr": 2e-3, "grad_clip_norm": 1.0},
            "loss_fn": {"name": "masked_ce"},
            "checkpoint": {"enabled": False},
            "logging": {"metrics_path": str(tmp_path / "vlm_metrics.jsonl")},
        }
    )
    last = main(cfg)
    assert np.isfinite(last["loss"])
    assert (tmp_path / "vlm_metrics.jsonl").exists()
