"""Kimi K2.5-VL: MoonViT3d tower invariants (2-D pairwise-complex rope vs a
numpy complex reference, sd2_tpool merger vs a naive loop), adapter
round-trip, registry + multimodal train smoke, NaN-poison guard. Reference
parity target: components/models/kimi_k25_vl (no HF transformers module
exists for this family — the reference vendors it too)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.kimi_k25_vl import (
    KimiK25VLConfig,
    KimiK25VLForConditionalGeneration,
    KimiK25VLStateDictAdapter,
    MoonViT3dConfig,
    tpool_patch_merger,
)
from automodel_tpu.models.kimi_k25_vl.vision import _rope_pairwise, _rope_tables

FP32 = BackendConfig(
    attn="sdpa", param_dtype="float32", compute_dtype="float32",
    experts="dense", scan_layers=False,
)

IMG_TOKEN = 120


def _hf_cfg():
    return {
        "architectures": ["KimiK25VLForConditionalGeneration"],
        "vision_config": {
            "patch_size": 4,
            "init_pos_emb_height": 8,
            "init_pos_emb_width": 8,
            "init_pos_emb_time": 2,
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


def test_rope_matches_complex_reference():
    cfg = MoonViT3dConfig(patch_size=4, num_heads=2, hidden_size=16)
    grid = ((1, 3, 5), (2, 2, 2))
    cos, sin = _rope_tables(cfg, grid)
    P = 3 * 5 + 2 * 2 * 2
    assert cos.shape == (P, cfg.head_dim // 2)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(P, cfg.num_heads, cfg.head_dim)).astype(np.float32)
    got = np.asarray(_rope_pairwise(jnp.asarray(x), cos, sin))

    # numpy complex reference, straight from the reference formulation:
    # freq j = theta^(-4j/hd); pair 2j rotates by x·f_j, pair 2j+1 by y·f_j
    hd = cfg.head_dim
    freqs = 1.0 / (10_000.0 ** (np.arange(0, hd, 4)[: hd // 4] / hd))
    angles = []
    for t, h, w in grid:
        yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
        xa = xx.reshape(-1, 1) * freqs
        ya = yy.reshape(-1, 1) * freqs
        a = np.stack([xa, ya], -1).reshape(h * w, -1)
        angles.append(np.tile(a, (t, 1)))
    ang = np.concatenate(angles, 0)
    cis = np.exp(1j * ang)[:, None, :]  # [P, 1, hd/2]
    xc = x.reshape(P, cfg.num_heads, hd // 2, 2)
    xc = xc[..., 0] + 1j * xc[..., 1]
    ref = xc * cis
    ref = np.stack([ref.real, ref.imag], -1).reshape(P, cfg.num_heads, hd)
    np.testing.assert_allclose(got, ref.astype(np.float32), atol=1e-5)
    # rotations preserve norms
    np.testing.assert_allclose(
        np.linalg.norm(got, axis=-1), np.linalg.norm(x, axis=-1), rtol=1e-5
    )


def test_tpool_merger_matches_naive():
    rng = np.random.default_rng(1)
    grid = ((2, 4, 6), (1, 2, 2))
    d = 8
    P = sum(t * h * w for t, h, w in grid)
    x = rng.normal(size=(P, d)).astype(np.float32)
    got = np.asarray(tpool_patch_merger(jnp.asarray(x), grid, (2, 2)))

    outs, off = [], 0
    for t, h, w in grid:
        seq = x[off : off + t * h * w].reshape(t, h, w, d)
        off += t * h * w
        for bh in range(h // 2):
            for bw in range(w // 2):
                block = seq[:, 2 * bh : 2 * bh + 2, 2 * bw : 2 * bw + 2, :]
                outs.append(block.mean(0).reshape(4, d))
    ref = np.stack(outs, 0)
    np.testing.assert_allclose(got, ref, atol=1e-5)


@pytest.fixture(scope="module")
def built():
    hf = _hf_cfg()
    from automodel_tpu.models.registry import resolve_architecture

    model, adapter = resolve_architecture(hf)(hf, FP32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return model, adapter, params


def test_adapter_round_trip(built):
    model, adapter, params = built
    assert isinstance(adapter, KimiK25VLStateDictAdapter)
    params = jax.tree.map(np.asarray, params)
    hf = dict(adapter.to_hf(params))
    assert set(hf) == set(adapter.vlm_keys(params))
    assert any(k.startswith("language_model.model.") for k in hf)
    assert any(k.startswith("vision_tower.") for k in hf)
    assert "mm_projector.proj.0.weight" in hf
    back = adapter.from_hf(lambda k: hf[k])
    for p, v in jax.tree_util.tree_leaves_with_path(params):
        got = back
        for kk in p:
            got = got[kk.key]
        np.testing.assert_allclose(got, v, atol=1e-6, err_msg=str(p))


def test_multimodal_train_smoke(built):
    model, _, params = built
    cfg = model.config
    grid = ((1, 4, 4),)  # 16 patches → 4 merged tokens
    n_tok = 4
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 100, size=(1, 12)).astype(np.int64)
    ids[0, 2 : 2 + n_tok] = IMG_TOKEN
    pix = rng.normal(size=(16, cfg.vision.patch_dim)).astype(np.float32)

    def loss(p):
        logits, aux = model(
            p, jnp.asarray(ids), pixel_values=jnp.asarray(pix), grid_thw=grid
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
    ids[0, 2:4] = IMG_TOKEN  # 2 tokens but 4 features
    pix = rng.normal(size=(16, cfg.vision.patch_dim)).astype(np.float32)
    logits, _ = model(
        params, jnp.asarray(ids), pixel_values=jnp.asarray(pix),
        grid_thw=((1, 4, 4),),
    )
    assert bool(jnp.isnan(logits).any())
