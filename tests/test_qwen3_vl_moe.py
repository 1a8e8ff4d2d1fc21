"""Qwen3-VL-MoE: HF numerical parity (vision tower with deepstack taps,
interleaved MRoPE, image-feature scatter, deepstack injection into early
decoder layers) and adapter round-trip. Reference parity target:
components/models/qwen3_vl_moe."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.qwen3_vl_moe import (
    Qwen3VLMoeConfig,
    Qwen3VLMoeForConditionalGeneration,
    Qwen3VLMoeStateDictAdapter,
    get_rope_index,
)

FP32 = BackendConfig(
    attn="sdpa", param_dtype="float32", compute_dtype="float32",
    experts="dense", scan_layers=False,
)

IMG_TOKEN = 120
VISION_START = 121
GRID = (1, 4, 4)  # one image: t=1, 4x4 patches → 2x2 merged tokens
N_MERGED = 4


def _hf_tiny():
    import torch

    torch.manual_seed(0)
    from transformers.models.qwen3_vl_moe.configuration_qwen3_vl_moe import (
        Qwen3VLMoeConfig as HFConfig,
    )
    from transformers.models.qwen3_vl_moe.modeling_qwen3_vl_moe import (
        Qwen3VLMoeForConditionalGeneration as HFModel,
    )

    cfg = HFConfig(
        text_config=dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            num_experts=4, num_experts_per_tok=2, decoder_sparse_step=1,
            max_position_embeddings=256, rope_theta=10_000.0,
            rope_scaling=dict(
                rope_type="default", mrope_section=[2, 1, 1],
                mrope_interleaved=True,
            ),
            attn_implementation="eager",
        ),
        vision_config=dict(
            depth=2, hidden_size=16, intermediate_size=32, num_heads=2,
            patch_size=4, temporal_patch_size=2, spatial_merge_size=2,
            out_hidden_size=32, num_position_embeddings=36,
            deepstack_visual_indexes=[0, 1],
        ),
        image_token_id=IMG_TOKEN,
        video_token_id=125,
        vision_start_token_id=VISION_START,
        attn_implementation="eager",
    )
    return cfg, HFModel(cfg).eval()


def _native_from_hf(hf_cfg, hf_model):
    cfg = Qwen3VLMoeConfig.from_hf(hf_cfg.to_dict())
    model = Qwen3VLMoeForConditionalGeneration(cfg, FP32)
    adapter = Qwen3VLMoeStateDictAdapter(cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    from automodel_tpu.checkpoint.hf_io import assemble_tree

    params = assemble_tree(adapter.iter_from_hf(lambda k: sd[k]))
    params = jax.tree.map(jnp.asarray, params)
    return cfg, model, params


def _mk_inputs(rng, hf_cfg, batch=2, seq=16):
    import torch

    t, h, w = GRID
    ids = rng.integers(0, 100, size=(batch, seq)).astype(np.int64)
    for b in range(batch):
        start = 1 + b
        ids[b, start] = VISION_START
        ids[b, start + 1 : start + 1 + N_MERGED] = IMG_TOKEN
    vc = hf_cfg.vision_config
    patch_dim = vc.in_channels * vc.temporal_patch_size * vc.patch_size**2
    pixels = rng.normal(size=(batch * t * h * w, patch_dim)).astype(np.float32)
    grid = np.tile(np.array([GRID]), (batch, 1))
    return (
        torch.tensor(ids),
        torch.tensor(pixels),
        torch.tensor(grid),
    )


@pytest.fixture(scope="module")
def parity_setup():
    hf_cfg, hf_model = _hf_tiny()
    cfg, model, params = _native_from_hf(hf_cfg, hf_model)
    return hf_cfg, hf_model, cfg, model, params


def test_logits_parity_with_images(parity_setup):
    import torch

    hf_cfg, hf_model, cfg, model, params = parity_setup
    rng = np.random.default_rng(0)
    ids_t, pix_t, grid_t = _mk_inputs(rng, hf_cfg)
    with torch.no_grad():
        out = hf_model(
            input_ids=ids_t, pixel_values=pix_t, image_grid_thw=grid_t
        ).logits.numpy()

    pos = get_rope_index(
        cfg, ids_t.numpy(), image_grid_thw=[tuple(g) for g in grid_t.numpy()]
    )
    # HF computes the same mrope positions — cross-check the host helper
    hf_pos, _ = hf_model.model.get_rope_index(
        ids_t, image_grid_thw=grid_t
    )
    np.testing.assert_array_equal(pos, hf_pos.numpy())

    grid = tuple(tuple(int(v) for v in g) for g in grid_t.numpy())
    logits, aux = jax.jit(
        lambda p, i, x, pos: model(p, i, pixel_values=x, image_grid_thw=grid, position_ids=pos)
    )(params, jnp.asarray(ids_t.numpy()), jnp.asarray(pix_t.numpy()), jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(logits), out, atol=2e-4, rtol=2e-3)


def test_logits_parity_text_only(parity_setup):
    import torch

    hf_cfg, hf_model, cfg, model, params = parity_setup
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 100, size=(2, 12)).astype(np.int64)
    with torch.no_grad():
        out = hf_model(input_ids=torch.tensor(ids)).logits.numpy()
    logits, _ = jax.jit(lambda p, i: model(p, i))(params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(logits), out, atol=2e-4, rtol=2e-3)


def test_adapter_round_trip(parity_setup):
    _, hf_model, cfg, _, params = parity_setup
    adapter = Qwen3VLMoeStateDictAdapter(cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    out = dict(adapter.to_hf(jax.tree.map(np.asarray, params)))
    missing = set(sd) - set(out)
    assert not missing, f"missing keys: {sorted(missing)[:8]}"
    for k in sd:
        np.testing.assert_allclose(out[k], sd[k], atol=1e-6, err_msg=k)


def test_trains_with_frozen_tower(parity_setup):
    """One jit train step over the VLM with the vision tower frozen."""
    from automodel_tpu.optim.builders import build_optimizer
    from automodel_tpu.training.freeze import freeze_mask
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    hf_cfg, _, cfg, model, params = parity_setup
    rng = np.random.default_rng(2)
    ids_t, pix_t, grid_t = _mk_inputs(rng, hf_cfg)
    ids = ids_t.numpy()
    pos = get_rope_index(cfg, ids, [tuple(g) for g in grid_t.numpy()])

    grid = tuple(tuple(int(v) for v in g) for g in grid_t.numpy())

    def loss_fn(p, mb):
        logits, aux = model(
            p, mb["input_ids"], pixel_values=mb["pixel_values"],
            image_grid_thw=grid, position_ids=mb["position_ids"],
        )
        logits = logits.astype(jnp.float32)
        labels = mb["labels"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        tok = lse - ll
        return tok.sum() + 0.0 * aux.aux_loss, jnp.asarray(tok.size)

    opt = build_optimizer(name="adamw", lr=5e-3)
    mask = freeze_mask(params, ["vision*"])
    state = TrainState.create(params, jax.jit(opt.init)(params))
    step = build_train_step(loss_fn, opt, grad_mask=mask)
    batch = {
        "input_ids": jnp.asarray(ids)[None],
        "labels": jnp.asarray(ids)[None],
        "pixel_values": jnp.asarray(pix_t.numpy())[None],
        "position_ids": jnp.asarray(pos)[None],
    }
    vis_before = jax.device_get(state.params["vision"])
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    assert losses[-1] < losses[0]
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        vis_before,
        jax.device_get(state.params["vision"]),
    )


def test_recipe_path_e2e():
    """The shipped finetune-vlm recipe drives Qwen3-VL-MoE end to end:
    MockQwen3VLDataset → vlm_collater (patch pixel layout + mrope stacking)
    → make_causal_lm_loss kw forwarding → frozen tower training."""
    from automodel_tpu.config.loader import ConfigNode
    from automodel_tpu.recipes.finetune_vlm import FinetuneRecipeForVLM

    grid = (1, 4, 4)
    cfg = ConfigNode({
        "seed": 0,
        "model": {
            "hf_config": {
                "architectures": ["Qwen3VLMoeForConditionalGeneration"],
                "text_config": {
                    "vocab_size": 256, "hidden_size": 32,
                    "intermediate_size": 64, "moe_intermediate_size": 16,
                    "num_hidden_layers": 2, "num_attention_heads": 4,
                    "num_key_value_heads": 2, "head_dim": 8,
                    "num_experts": 4, "num_experts_per_tok": 2,
                    "model_type": "qwen3_vl_moe_text",
                    "rope_theta": 10000.0,
                    "rope_scaling": {"rope_type": "default",
                                     "mrope_section": [2, 1, 1]},
                },
                "vision_config": {
                    "depth": 2, "hidden_size": 16, "intermediate_size": 32,
                    "num_heads": 2, "patch_size": 4, "temporal_patch_size": 2,
                    "spatial_merge_size": 2, "out_hidden_size": 32,
                    "num_position_embeddings": 36,
                    "deepstack_visual_indexes": [0, 1],
                },
                "image_token_id": 250,
                "vision_start_token_id": 251,
                "training_image_grid_thw": [list(grid)],
            },
            "backend": {"attn": "sdpa", "experts": "dense",
                        "param_dtype": "float32", "compute_dtype": "float32"},
        },
        "distributed": {"dp_shard": -1, "platform": "cpu"},
        "freeze": {"patterns": ["vision*"]},
        "dataset": {
            "_target_": "automodel_tpu.data.vlm.MockQwen3VLDataset",
            "vocab_size": 256, "seq_length": 32, "grid_thw": list(grid),
            "patch_size": 4, "temporal_patch_size": 2,
            "image_token_id": 250, "vision_start_token_id": 251,
            "num_samples": 32,
        },
        "dataloader": {"global_batch_size": 8},
        "step_scheduler": {"max_steps": 8, "num_epochs": 4, "log_every_steps": 4},
        "optimizer": {"name": "adamw", "lr": 0.01},
        "loss_fn": {"name": "masked_ce"},
        "checkpoint": {"enabled": False},
        "logging": {"metrics_path": "/tmp/qwen3vl_recipe_metrics.jsonl"},
    })
    recipe = FinetuneRecipeForVLM(cfg)
    recipe.setup()
    last = recipe.run_train_validation_loop()
    assert np.isfinite(float(last["loss"]))
