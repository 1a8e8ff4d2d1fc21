"""DeepSeek-V3.2 sparse indexer attention.

No HF implementation exists to diff against (transformers has no
deepseek_v32), so parity is established by: (a) an independent numpy
re-derivation of the indexer math from the official spec, (b) the exact
equivalence sparse→dense when index_topk ≥ seq_len (the V3.2 mask becomes
all-zeros and the model must reproduce V3 MLA numerics on the same
weights), and (c) adapter round-trip + training smoke. Reference:
components/models/deepseek_v32/layers.py:95,272,358."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu import auto_model
from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.deepseek_v32 import (
    DeepseekV32Config,
    DeepseekV32ForCausalLM,
    DeepseekV32StateDictAdapter,
)

FP32 = {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32",
        "experts": "dense", "scan_layers": False}

HF = {
    "architectures": ["DeepseekV32ForCausalLM"],
    "model_type": "deepseek_v32",
    "vocab_size": 128,
    "hidden_size": 48,
    "intermediate_size": 96,
    "moe_intermediate_size": 32,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "head_dim": 16,
    "q_lora_rank": 24,
    "kv_lora_rank": 16,
    "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8,
    "v_head_dim": 16,
    "n_routed_experts": 4,
    "num_experts_per_tok": 2,
    "n_shared_experts": 0,
    "first_k_dense_replace": 1,
    "topk_method": "noaux_tc",
    "norm_topk_prob": True,
    "index_n_heads": 2,
    "index_head_dim": 16,
    "index_topk": 6,
    "rope_interleave": True,
}


def _build(topk=None):
    hf = dict(HF)
    if topk is not None:
        hf["index_topk"] = topk
    return auto_model.from_config(hf, None, FP32, seed=0)


def test_sparse_equals_dense_when_topk_covers_seq():
    """index_topk ≥ S → the sparse mask is all-zeros over causal and V3.2
    must reproduce V3 MLA numerics on the SAME weights."""
    from automodel_tpu.models.deepseek_v3.model import DeepseekV3ForCausalLM

    auto = _build(topk=64)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, size=(2, 12)), jnp.int32
    )
    sparse_logits, _ = auto.model(auto.params, ids)
    v3 = DeepseekV3ForCausalLM(auto.model.config, auto.model.backend)
    dense_logits, _ = v3(auto.params, ids)  # ignores the indexer subtree
    np.testing.assert_allclose(
        np.asarray(sparse_logits), np.asarray(dense_logits), atol=2e-5
    )


def test_small_topk_changes_output():
    auto_dense = _build(topk=64)
    auto_sparse = _build(topk=2)
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, 128, size=(1, 12)), jnp.int32
    )
    a, _ = auto_dense.model(auto_dense.params, ids)
    b, _ = auto_sparse.model(auto_sparse.params, ids)
    assert not np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_indexer_mask_matches_numpy_rederivation():
    """Independent numpy implementation of the indexer math (official
    DeepSeek-V3.2-Exp formulas) must select the same top-k positions."""
    from automodel_tpu.models.deepseek_v32.model import (
        _hadamard_matrix,
        indexer_topk_mask,
    )
    from automodel_tpu.ops.rope import rope_table

    auto = _build(topk=3)
    cfg = auto.model.config
    rng = np.random.default_rng(2)
    B, S, D = 1, 8, cfg.hidden_size
    x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(B, S, cfg.q_lora_rank)), jnp.float32)
    pos = jnp.arange(S)[None]
    cos, sin = rope_table(pos, cfg.qk_rope_head_dim, cfg.rope)
    ip = jax.tree.map(lambda a: a[0], auto.params["moe_layers"]["indexer"])

    mask = np.asarray(indexer_topk_mask(cfg, ip, x, qr, cos, sin))[:, 0]

    # --- numpy re-derivation ---
    Hn, hd, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    nope = hd - rope
    xx, qq = np.asarray(x), np.asarray(qr)
    q = (qq @ np.asarray(ip["wq_b"]["kernel"])).reshape(B, S, Hn, hd)
    k = xx @ np.asarray(ip["wk"]["kernel"])
    mu = k.mean(-1, keepdims=True)
    k = (k - mu) / np.sqrt(((k - mu) ** 2).mean(-1, keepdims=True) + 1e-5)
    k = k * np.asarray(ip["k_norm"]["scale"]) + np.asarray(ip["k_norm"]["bias"])

    # rope reused from the library (it's covered by the v3 parity tests);
    # the independent check here is of the score/weight/topk pipeline
    from automodel_tpu.ops.rope import apply_rope as _ar

    q_pe, k_pe = _ar(
        jnp.asarray(q[..., nope:]), jnp.asarray(k[:, :, None, nope:]),
        cos, sin, interleave=True,
    )
    q = np.concatenate([q[..., :nope], np.asarray(q_pe)], axis=-1)
    k = np.concatenate([k[..., :nope], np.asarray(k_pe)[:, :, 0]], axis=-1)
    Hm = _hadamard_matrix(hd) * hd**-0.5
    q, k = q @ Hm, k @ Hm
    w = (xx @ np.asarray(ip["weights_proj"]["kernel"])) * Hn**-0.5 * hd**-0.5
    scores = np.einsum("bqhd,bkd->bhqk", q, k)
    scores = np.maximum(scores, 0.0) * w.transpose(0, 2, 1)[..., None]
    scores = scores.sum(axis=1)
    scores = np.where(np.tril(np.ones((S, S), bool))[None], scores, -1e30)
    topk_np = np.argsort(-scores, axis=-1)[..., :3]

    # tie-breaking differs between jax top_k and np argsort (ReLU makes exact
    # zero scores common), so compare the selected score VALUES, not indices;
    # rows below topk valid positions are skipped (-inf ties)
    for b in range(B):
        for s in range(3, S):
            sel = np.nonzero(mask[b, s] == 0)[0]
            got = np.sort(scores[b, s, sel])
            want = np.sort(scores[b, s, topk_np[b, s]])
            np.testing.assert_allclose(got, want, atol=1e-5, err_msg=str((b, s)))


def test_adapter_round_trip():
    auto = _build()
    adapter = auto.adapter
    assert isinstance(adapter, DeepseekV32StateDictAdapter)
    sd = dict(adapter.to_hf(jax.tree.map(np.asarray, auto.params)))
    assert any(".self_attn.indexer.wq_b.weight" in k for k in sd)
    from automodel_tpu.checkpoint.hf_io import assemble_tree

    params2 = assemble_tree(adapter.iter_from_hf(lambda k: sd[k]))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        ),
        jax.device_get(auto.params),
        params2,
    )


def test_train_step_learns():
    from automodel_tpu.optim.builders import build_optimizer, init_opt_state
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    auto = _build()
    loss_fn = make_causal_lm_loss(auto.model)
    opt = build_optimizer(name="adamw", lr=5e-3)
    state = TrainState.create(auto.params, init_opt_state(opt, auto.params, auto.mesh_ctx))
    step = build_train_step(loss_fn, opt)
    ids = np.random.default_rng(3).integers(0, 128, size=(1, 2, 12)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(ids)}
    # snapshot before stepping: the train step donates the state buffers
    i0 = jax.device_get(auto.params["moe_layers"]["indexer"]["wq_b"]["kernel"])
    a0 = jax.device_get(auto.params["moe_layers"]["attn"]["q_b_proj"]["kernel"])
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    assert losses[-1] < losses[0]
    # the MLA path trains; the indexer only emits DISCRETE top-k indices, so
    # (matching the reference, which likewise routes no LM-loss gradient into
    # it — DeepseekV32MLA.forward consumes indices only) it stays fixed
    # until an indexer-specific KL objective is wired in
    a1 = jax.device_get(state.params["moe_layers"]["attn"]["q_b_proj"]["kernel"])
    i1 = jax.device_get(state.params["moe_layers"]["indexer"]["wq_b"]["kernel"])
    assert not np.allclose(a0, a1)
    np.testing.assert_array_equal(i0, i1)
