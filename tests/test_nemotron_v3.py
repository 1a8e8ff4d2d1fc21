"""Nemotron-V3: Mamba2 SSD chunked scan vs a naive sequential recurrence
(the numerics oracle — no HF module exists for this family; the reference
itself requires CUDA-only mamba_ssm), packed-segment reset, hybrid-block
train smoke across all four mixer types, adapter round-trip. Reference
parity target: components/models/nemotron_v3."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.nemotron_v3 import (
    NemotronV3Config,
    NemotronV3ForCausalLM,
    NemotronV3StateDictAdapter,
    mamba2_chunk_scan,
    mamba2_reference,
)

FP32 = BackendConfig(
    attn="sdpa", param_dtype="float32", compute_dtype="float32",
    experts="dense", scan_layers=False,
)


def _hf_cfg():
    return {
        "architectures": ["NemotronV3ForCausalLM"],
        "vocab_size": 128,
        "hidden_size": 32,
        "intermediate_size": 64,
        "num_hidden_layers": 4,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 8,
        "layers_block_type": ["mamba", "attention", "mlp", "moe"],
        "mamba_num_heads": 4,
        "mamba_head_dim": 8,
        "ssm_state_size": 16,
        "n_groups": 2,
        "conv_kernel": 4,
        "chunk_size": 8,
        "mlp_hidden_act": "relu2",
        "layer_norm_epsilon": 1e-5,
        "n_routed_experts": 4,
        "num_experts_per_tok": 2,
        "moe_intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 16,
        "routed_scaling_factor": 1.0,
        "norm_topk_prob": True,
        "tie_word_embeddings": False,
        "use_conv_bias": True,
    }


def test_ssd_matches_naive_recurrence():
    rng = np.random.default_rng(0)
    B, S, H, P, G, N = 2, 37, 4, 8, 2, 16  # S deliberately non-chunk-multiple
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (B, S, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 3.0, H), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, G, N)) * 0.3, jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, G, N)) * 0.3, jnp.float32)
    D = jnp.asarray(rng.normal(size=H), jnp.float32)
    got = mamba2_chunk_scan(x, dt, A, Bm, Cm, D, chunk_size=8)
    ref = mamba2_reference(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4, rtol=2e-3)


def test_ssd_segment_reset():
    """A 2-doc packed row must match each doc scanned separately."""
    rng = np.random.default_rng(1)
    B, H, P, G, N = 1, 4, 8, 2, 16
    la, lb = 11, 21
    S = la + lb

    def mk(s):
        return (
            jnp.asarray(rng.normal(size=(B, s, H, P)), jnp.float32),
            jnp.asarray(rng.uniform(0.01, 0.5, (B, s, H)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, s, G, N)) * 0.3, jnp.float32),
            jnp.asarray(rng.normal(size=(B, s, G, N)) * 0.3, jnp.float32),
        )

    xa, dta, Ba, Ca = mk(la)
    xb, dtb, Bb, Cb = mk(lb)
    A = jnp.asarray(-rng.uniform(0.5, 3.0, H), jnp.float32)
    D = jnp.asarray(rng.normal(size=H), jnp.float32)

    ya = mamba2_chunk_scan(xa, dta, A, Ba, Ca, D, chunk_size=8)
    yb = mamba2_chunk_scan(xb, dtb, A, Bb, Cb, D, chunk_size=8)

    cat = lambda a, b: jnp.concatenate([a, b], axis=1)
    seg = jnp.asarray(np.concatenate(
        [np.zeros((1, la)), np.ones((1, lb))], axis=1), jnp.int32)
    y = mamba2_chunk_scan(
        cat(xa, xb), cat(dta, dtb), A, cat(Ba, Bb), cat(Ca, Cb), D,
        chunk_size=8, segment_ids=seg,
    )
    np.testing.assert_allclose(np.asarray(y[:, :la]), np.asarray(ya), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y[:, la:]), np.asarray(yb), atol=1e-4)


@pytest.fixture(scope="module")
def built():
    from automodel_tpu.models.registry import resolve_architecture

    hf = _hf_cfg()
    model, adapter = resolve_architecture(hf)(hf, FP32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return model, adapter, params


def test_hybrid_train_smoke(built):
    model, _, params = built
    assert isinstance(model, NemotronV3ForCausalLM)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 128, (2, 24)))

    def loss(p):
        logits, aux = model(p, ids)
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    val, g = jax.jit(jax.value_and_grad(loss))(params)
    assert bool(jnp.isfinite(val))
    for part in ("mamba", "attn", "mlp", "moe", "embed"):
        gn = jax.tree_util.tree_reduce(
            lambda a, x: a + jnp.sum(jnp.abs(x.astype(jnp.float32))), g[part], 0.0
        )
        assert float(gn) > 0, part


def test_adapter_round_trip(built):
    model, adapter, params = built
    assert isinstance(adapter, NemotronV3StateDictAdapter)
    host = jax.tree.map(np.asarray, params)
    hf = dict(adapter.to_hf(host))
    assert "backbone.layers.0.mixer.A_log" in hf
    assert "backbone.layers.1.mixer.q_proj.weight" in hf
    assert "backbone.layers.2.mixer.up_proj.weight" in hf
    assert "backbone.layers.3.mixer.gate.e_score_correction_bias" in hf
    assert hf["backbone.layers.0.mixer.conv1d.weight"].ndim == 3
    back = adapter.from_hf(lambda k: hf[k])
    for p, v in jax.tree_util.tree_leaves_with_path(host):
        got = back
        for kk in p:
            got = got[kk.key]
        np.testing.assert_allclose(got, v, atol=1e-6, err_msg=str(p))


def test_packed_segments_forward(built):
    model, _, params = built
    rng = np.random.default_rng(3)
    la, lb = 10, 14
    doc_a = rng.integers(0, 128, (1, la))
    doc_b = rng.integers(0, 128, (1, lb))
    run = jax.jit(lambda p, i, **kw: model(p, i, **kw)[0])  # one program a shape
    ref_a, ref_b = run(params, jnp.asarray(doc_a)), run(params, jnp.asarray(doc_b))
    packed = jnp.asarray(np.concatenate([doc_a, doc_b], 1))
    seg = jnp.asarray(np.concatenate(
        [np.zeros((1, la)), np.ones((1, lb))], 1), jnp.int32)
    got = run(params, packed, segment_ids=seg)
    np.testing.assert_allclose(
        np.asarray(got[:, :la]), np.asarray(ref_a), atol=2e-4, rtol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(got[:, la:]), np.asarray(ref_b), atol=2e-4, rtol=2e-3
    )
