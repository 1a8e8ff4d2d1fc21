"""Original Kimi-VL (MoonViT + DeepSeek-V3): spatial patch-merger vs a naive
loop, adapter round-trip with the kimivl HF key layout (named linear_1/2
projector modules), registry dispatch, multimodal train smoke, and the
single-frame equivalence that justifies reusing the K2.5 tower. Reference
parity target: components/models/kimivl/model.py:1-874 (the reference
vendors this family too — no HF transformers module exists)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.kimi_vl import (
    KimiVLConfig,
    KimiVLForConditionalGeneration,
    KimiVLStateDictAdapter,
)
from automodel_tpu.models.kimi_k25_vl.vision import tpool_patch_merger

FP32 = BackendConfig(
    attn="sdpa", param_dtype="float32", compute_dtype="float32",
    experts="dense", scan_layers=False,
)

IMG_TOKEN = 120


def _hf_cfg():
    return {
        "architectures": ["KimiVLForConditionalGeneration"],
        "model_type": "kimi_vl",
        "vision_config": {
            "patch_size": 4,
            "init_pos_emb_height": 8,
            "init_pos_emb_width": 8,
            "num_attention_heads": 2,
            "num_hidden_layers": 2,
            "hidden_size": 16,
            "intermediate_size": 32,
            "merge_kernel_size": [2, 2],
        },
        "text_config": {
            "vocab_size": 256, "hidden_size": 32, "intermediate_size": 64,
            "moe_intermediate_size": 16, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "n_routed_experts": 4, "num_experts_per_tok": 2,
            "n_shared_experts": 1, "first_k_dense_replace": 1,
            "q_lora_rank": None, "kv_lora_rank": 16,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
            "topk_method": "noaux_tc", "scoring_func": "sigmoid",
            "norm_topk_prob": True, "rope_theta": 10_000.0,
        },
        "media_placeholder_token_id": IMG_TOKEN,
    }


def test_spatial_merger_matches_reference_loop():
    """At t=1 the shared t-pool merger IS the reference's 2-D patch_merger:
    per image, k×k spatial regroup to [new_h·new_w, kh·kw, d]."""
    rng = np.random.default_rng(1)
    grid_hws = ((4, 6), (2, 2))
    d = 8
    P = sum(h * w for h, w in grid_hws)
    x = rng.normal(size=(P, d)).astype(np.float32)
    grid_thw = tuple((1, h, w) for h, w in grid_hws)
    got = np.asarray(tpool_patch_merger(jnp.asarray(x), grid_thw, (2, 2)))

    # straight loop from the reference patch_merger formulation
    outs, off = [], 0
    for h, w in grid_hws:
        seq = x[off : off + h * w].reshape(h, w, d)
        off += h * w
        for bh in range(h // 2):
            for bw in range(w // 2):
                outs.append(
                    seq[2 * bh : 2 * bh + 2, 2 * bw : 2 * bw + 2, :].reshape(4, d)
                )
    np.testing.assert_allclose(got, np.stack(outs, 0), atol=1e-6)


@pytest.fixture(scope="module")
def built():
    hf = _hf_cfg()
    from automodel_tpu.models.registry import resolve_architecture

    model, adapter = resolve_architecture(hf)(hf, FP32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return model, adapter, params


def test_registry_and_config(built):
    model, adapter, _ = built
    assert isinstance(model, KimiVLForConditionalGeneration)
    assert isinstance(adapter, KimiVLStateDictAdapter)
    assert model.config.vision.init_pos_emb_time == 1  # single-frame tower


def test_adapter_round_trip(built):
    model, adapter, params = built
    params = jax.tree.map(np.asarray, params)
    hf = dict(adapter.to_hf(params))
    assert any(k.startswith("language_model.model.") for k in hf)
    assert any(k.startswith("vision_tower.encoder.blocks.") for k in hf)
    # the kimivl projector layout: named modules, not Sequential indices
    assert "multi_modal_projector.linear_1.weight" in hf
    assert "multi_modal_projector.pre_norm.weight" in hf
    assert not any(k.startswith("mm_projector.") for k in hf)
    back = adapter.from_hf(lambda k: hf[k])
    for p, v in jax.tree_util.tree_leaves_with_path(params):
        got = back
        for kk in p:
            got = got[kk.key]
        np.testing.assert_allclose(got, v, atol=1e-6, err_msg=str(p))


def test_multimodal_train_smoke(built):
    model, _, params = built
    cfg = model.config
    grid_hws = ((4, 4),)  # 16 patches → 4 merged tokens
    n_tok = 4
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 100, size=(1, 12)).astype(np.int64)
    ids[0, 2 : 2 + n_tok] = IMG_TOKEN
    pix = rng.normal(size=(16, cfg.vision.patch_dim)).astype(np.float32)

    def loss(p):
        logits, aux = model(
            p, jnp.asarray(ids), pixel_values=jnp.asarray(pix), grid_hws=grid_hws
        )
        return jnp.mean(logits.astype(jnp.float32) ** 2) + aux.aux_loss

    val, g = jax.jit(jax.value_and_grad(loss))(params)
    assert bool(jnp.isfinite(val))
    for part in ("vision", "projector", "text"):
        gn = jax.tree_util.tree_reduce(
            lambda a, x: a + jnp.sum(jnp.abs(x.astype(jnp.float32))), g[part], 0.0
        )
        assert float(gn) > 0, part


def test_count_mismatch_poisons(built):
    model, _, params = built
    cfg = model.config
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 100, size=(1, 12)).astype(np.int64)
    ids[0, 2:4] = IMG_TOKEN  # 2 placeholders but 4 features
    pix = rng.normal(size=(16, cfg.vision.patch_dim)).astype(np.float32)
    logits, _ = jax.jit(
        lambda p, i, x: model(p, i, pixel_values=x, grid_hws=((4, 4),))
    )(params, jnp.asarray(ids), jnp.asarray(pix))
    assert bool(jnp.isnan(logits).any())
