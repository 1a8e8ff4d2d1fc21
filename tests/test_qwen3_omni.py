"""Qwen3-Omni-MoE thinker: HF numerical parity of the text stack under
interleaved M-RoPE (1-D and 3-D positions), adapter round-trip with the
thinker prefix, registry train smoke. Reference parity target:
components/models/qwen3_omni_moe."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.qwen3_omni_moe import (
    Qwen3OmniMoeStateDictAdapter,
    Qwen3OmniMoeThinkerConfig,
    Qwen3OmniMoeThinkerForCausalLM,
)

FP32 = BackendConfig(
    attn="sdpa", param_dtype="float32", compute_dtype="float32",
    experts="dense", scan_layers=False,
)


def _hf_tiny():
    import torch

    torch.manual_seed(0)
    from transformers.models.qwen3_omni_moe.configuration_qwen3_omni_moe import (
        Qwen3OmniMoeTextConfig,
    )
    from transformers.models.qwen3_omni_moe.modeling_qwen3_omni_moe import (
        Qwen3OmniMoeThinkerTextModel,
    )

    cfg = Qwen3OmniMoeTextConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        moe_intermediate_size=16,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=8,
        num_experts=4,
        num_experts_per_tok=2,
        decoder_sparse_step=1,
        norm_topk_prob=True,
        max_position_embeddings=256,
        rope_theta=10_000.0,
        rope_scaling={"rope_type": "default", "mrope_section": [2, 1, 1]},
        tie_word_embeddings=True,
        attn_implementation="eager",
    )
    return cfg, Qwen3OmniMoeThinkerTextModel(cfg).eval()


@pytest.fixture(scope="module")
def parity_setup():
    hf_cfg, hf_model = _hf_tiny()
    cfg = Qwen3OmniMoeThinkerConfig.from_hf(hf_cfg.to_dict())
    model = Qwen3OmniMoeThinkerForCausalLM(cfg, FP32)
    adapter = Qwen3OmniMoeStateDictAdapter(cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}

    def get_tensor(k):  # thinker.model.X → the bare text-model key X
        assert k.startswith("thinker.model."), k
        return sd[k[len("thinker.model."):]]

    from automodel_tpu.checkpoint.hf_io import assemble_tree

    params = assemble_tree(adapter.iter_from_hf(get_tensor))
    params = jax.tree.map(jnp.asarray, params)
    return hf_cfg, hf_model, cfg, model, params


def test_hidden_parity_1d_positions(parity_setup):
    import torch

    _, hf_model, _, model, params = parity_setup
    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with torch.no_grad():
        ref = hf_model(input_ids=torch.tensor(ids)).last_hidden_state.numpy()
    got, _ = model.hidden(params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(got), ref, atol=2e-4, rtol=2e-3)


def test_hidden_parity_3d_positions(parity_setup):
    import torch

    _, hf_model, _, model, params = parity_setup
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 128, (2, 10))
    pos = rng.integers(0, 50, (3, 2, 10))  # distinct t/h/w streams
    with torch.no_grad():
        ref = hf_model(
            input_ids=torch.tensor(ids), position_ids=torch.tensor(pos)
        ).last_hidden_state.numpy()
    got, _ = model.hidden(
        params, jnp.asarray(ids), position_ids=jnp.asarray(pos)
    )
    np.testing.assert_allclose(np.asarray(got), ref, atol=2e-4, rtol=2e-3)


def test_adapter_round_trip(parity_setup):
    _, _, cfg, _, params = parity_setup
    adapter = Qwen3OmniMoeStateDictAdapter(cfg)
    host = jax.tree.map(np.asarray, params)
    out = dict(adapter.to_hf(host))
    assert all(k.startswith("thinker.") for k in out)
    back_tree_pairs = list(adapter.iter_from_hf(lambda k: out[k]))
    from automodel_tpu.checkpoint.hf_io import assemble_tree

    back = assemble_tree(iter(back_tree_pairs))
    for p, v in jax.tree_util.tree_leaves_with_path(host):
        got = back
        for kk in p:
            got = got[kk.key]
        np.testing.assert_allclose(got, v, atol=1e-6, err_msg=str(p))


def test_registry_train_smoke():
    from automodel_tpu.models.registry import resolve_architecture

    hf = {
        "architectures": ["Qwen3OmniMoeForConditionalGeneration"],
        "thinker_config": {
            "text_config": {
                "model_type": "qwen3_omni_moe_text",
                "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
                "moe_intermediate_size": 16, "num_hidden_layers": 2,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 8, "num_experts": 4, "num_experts_per_tok": 2,
                "norm_topk_prob": True,
                "rope_scaling": {"mrope_section": [2, 1, 1]},
            }
        },
    }
    model, adapter = resolve_architecture(hf)(hf, FP32)
    assert isinstance(model, Qwen3OmniMoeThinkerForCausalLM)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 128, (1, 12)))

    def loss(p):
        logits, aux = model(p, ids)
        return jnp.mean(logits.astype(jnp.float32) ** 2) + aux.aux_loss

    g = jax.jit(jax.grad(loss))(params)
    gn = jax.tree_util.tree_reduce(
        lambda a, x: a + jnp.sum(jnp.abs(x.astype(jnp.float32))), g, 0.0
    )
    assert bool(jnp.isfinite(gn)) and float(gn) > 0
