"""LFM2-MoE (gated short-conv layers beside attention, models/lfm2_moe): the
family's forward, loss and gradients against the benchmark's plain reference
(benchmarks/reference/lfm2_moe.py: the published equations in float32, nothing
of the program imported), packed documents, the HF names, the published
parameter counts, and a training run through the recipe."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.lfm2_moe import (
    Lfm2MoeConfig,
    Lfm2MoeForCausalLM,
    Lfm2MoeStateDictAdapter,
)
from automodel_tpu.models.registry import resolve_architecture
from benchmarks.harness import loader

# two conv kinds of position (before and after an attention layer), a
# dense-leading layer, expert layers of both operator kinds
TINY = {
    "architectures": ["Lfm2MoeForCausalLM"], "model_type": "lfm2_moe",
    "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
    "moe_intermediate_size": 16, "num_hidden_layers": 5,
    "layer_types": ["conv", "full_attention", "conv", "conv", "full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_dense_layers": 1,
    "num_experts": 4, "num_experts_per_tok": 2, "norm_eps": 1e-5,
    "norm_topk_prob": True, "use_expert_bias": True, "routed_scaling_factor": 1,
    "rope_theta": 1000000, "conv_L_cache": 3, "conv_bias": False,
    "max_position_embeddings": 512,
}
FP32 = BackendConfig(attn="sdpa", param_dtype="float32", compute_dtype="float32",
                     experts="ragged")
# float32 program against a float32 reference: what is left is the order of
# the sums (sdpa's softmax, the experts' grouped matmul against tiles)
TOL = 2e-4
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def R():
    return loader.load_module("reference", "lfm2_moe")


@pytest.fixture(scope="module")
def setup():
    cfg = Lfm2MoeConfig.from_hf(TINY)
    model = Lfm2MoeForCausalLM(cfg, FP32)

    def weights(key, noise_key):
        # biases and norms off their init values, so that a skipped one shows
        leaves, treedef = jax.tree.flatten(model.init(key))
        keys = jax.random.split(noise_key, len(leaves))
        leaves = [a + 0.05 * jax.random.normal(k, a.shape, a.dtype) for a, k in zip(leaves, keys)]
        return jax.tree.unflatten(treedef, leaves)

    # every program of this file runs under one jit: called op by op, the model
    # is hundreds of programs of one primitive each
    return cfg, model, jax.jit(weights)(jax.random.key(0), jax.random.key(1))


def _ids(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, TINY["vocab_size"], shape), jnp.int32)


def _ref_logits(R, params, ids, precision="f32"):
    spec = R.spec(TINY, {})

    def logits(ref):
        h = R.hidden_states(ref, ids, spec, precision)
        return jnp.einsum("bsd,vd->bsv", h, ref["embed"].astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)

    return jax.jit(logits)(R.to_reference(params))


def test_registry_and_config():
    model, adapter = resolve_architecture(TINY)(TINY, FP32)
    assert isinstance(model, Lfm2MoeForCausalLM) and isinstance(adapter, Lfm2MoeStateDictAdapter)
    cfg = model.config
    assert cfg.layer_types == tuple(TINY["layer_types"]) and cfg.conv_taps == 3
    assert cfg.kv_layer_ids == (1, 4) and cfg.conv_layer_ids == (0, 2, 3)
    assert cfg.rms_eps == 1e-5 and cfg.qk_norm and not cfg.qk_norm_flat and cfg.tie_embeddings
    moe = cfg.moe
    assert (moe.score_func, moe.expert_bias, moe.norm_topk_prob) == ("sigmoid", True, True)
    assert moe.num_dense_layers == 1 and moe.num_shared_experts == 0
    kinds = [c.kind for c in model.cache_layout()]
    assert kinds == ["conv", "kv", "conv", "conv", "kv"]
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig.from_hf(dict(TINY, layer_types=["conv"]))


def test_forward_matches_the_reference(setup, R):
    _, model, params = setup
    ids = _ids((2, 40))
    logits, aux = jax.jit(lambda p, i: model(p, i))(params, ids)
    want = _ref_logits(R, params, ids)
    assert aux.expert_counts.shape == (4, 4)  # the four expert layers
    assert float(jnp.max(jnp.abs(logits - want))) < TOL


def test_a_lower_precision_fails_the_same_tolerance(setup, R):
    """The tolerance is tight enough to tell bf16 from the float32 the test
    configuration states: the reference with bf16 operands misses it."""
    _, _, params = setup
    ids = _ids((2, 40))
    gap = jnp.max(jnp.abs(_ref_logits(R, params, ids, "bf16") - _ref_logits(R, params, ids)))
    assert float(gap) > 10 * TOL


def test_loss_and_gradients_match_the_reference(setup, R):
    _, model, params = setup
    ids = _ids((2, 33), seed=3)
    inputs, labels = ids[:, :-1], ids[:, 1:]
    spec = R.spec(TINY, {})

    def program_loss(p):
        logits, _ = model(p, inputs)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))

    def reference_loss(p):
        total, n = R.loss_sum(R.to_reference(p), inputs, labels, spec)
        return total / n

    (lp, gp), (lr, gr) = jax.device_get(
        [jax.jit(jax.value_and_grad(f))(params) for f in (program_loss, reference_loss)]
    )
    assert abs(float(lp) - float(lr)) < 1e-5
    worst = max(
        float(np.max(np.abs(a - b))) / (float(np.max(np.abs(b))) + 1e-8)
        for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr))
    )
    assert worst < 2e-3  # relative to each leaf's largest gradient


def test_packed_documents_do_not_leak_through_the_conv(setup):
    """Two documents packed in one row (segment ids, positions restarting)
    give each document the logits it gets alone: the conv's taps stop at the
    boundary as attention does."""
    _, model, params = setup
    a, b = _ids((1, 11), seed=5), _ids((1, 13), seed=6)
    packed = jnp.concatenate([a, b], axis=1)
    seg = jnp.concatenate([jnp.zeros((1, 11), jnp.int32), jnp.ones((1, 13), jnp.int32)], axis=1)
    pos = jnp.concatenate([jnp.arange(11), jnp.arange(13)])[None, :].astype(jnp.int32)
    run = jax.jit(lambda p, i, **kw: model(p, i, **kw)[0])
    got = run(params, packed, segment_ids=seg, position_ids=pos)
    alone_a, alone_b = run(params, a), run(params, b)
    assert float(jnp.max(jnp.abs(got[:, :11] - alone_a))) < TOL
    assert float(jnp.max(jnp.abs(got[:, 11:] - alone_b))) < TOL
    # and without the boundary the second document does see the first
    leaky = run(params, packed, position_ids=pos)
    assert float(jnp.max(jnp.abs(leaky[:, 11:] - alone_b))) > 100 * TOL


def test_hf_names_round_trip(setup):
    cfg, _, params = setup
    adapter = Lfm2MoeStateDictAdapter(cfg)
    sd = dict(adapter.to_hf(params))
    assert sorted(sd) == sorted(adapter.hf_keys())
    for key in (
        "model.embed_tokens.weight", "model.embedding_norm.weight",
        "model.layers.0.operator_norm.weight", "model.layers.0.ffn_norm.weight",
        "model.layers.0.conv.in_proj.weight", "model.layers.0.conv.conv.weight",
        "model.layers.0.conv.out_proj.weight", "model.layers.0.feed_forward.w1.weight",
        "model.layers.0.feed_forward.w2.weight", "model.layers.0.feed_forward.w3.weight",
        "model.layers.1.self_attn.q_proj.weight", "model.layers.1.self_attn.k_proj.weight",
        "model.layers.1.self_attn.v_proj.weight", "model.layers.1.self_attn.out_proj.weight",
        "model.layers.1.self_attn.q_layernorm.weight",
        "model.layers.1.self_attn.k_layernorm.weight",
        "model.layers.1.feed_forward.gate.weight", "model.layers.1.feed_forward.expert_bias",
        "model.layers.1.feed_forward.experts.3.w1.weight",
        "model.layers.1.feed_forward.experts.3.w2.weight",
        "model.layers.1.feed_forward.experts.3.w3.weight",
    ):
        assert key in sd, key
    assert "lm_head.weight" not in sd  # tied
    assert sd["model.layers.0.conv.conv.weight"].shape == (32, 1, 3)
    assert sd["model.layers.0.conv.in_proj.weight"].shape == (96, 32)
    assert sd["model.layers.1.feed_forward.gate.weight"].shape == (4, 32)
    back = adapter.from_hf(lambda k: sd[k])
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _published() -> dict:
    row = next(json.loads(l) for l in open(CATALOG) if '"name": "LFM2-8B-A1B"' in l)
    return row["config"]


def test_the_full_published_config_builds_with_its_published_parameters():
    """All 24 layers, abstractly: 8.34 B parameters (published "8.3B")."""
    from automodel_tpu import auto_model
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh

    hf = dict(_published(), architectures=["Lfm2MoeForCausalLM"])
    mesh = build_mesh(MeshConfig.from_section({"dp_shard": 1}), devices=jax.devices()[:1])
    auto = auto_model.from_config(hf, mesh, {"attn": "sdpa", "experts": "ragged"}, abstract=True)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(auto.params))
    assert abs(n - 8.34e9) < 0.01 * 8.34e9
    assert len(auto.model.cache_layout()) == 24
    assert sum(c.kind == "kv" for c in auto.model.cache_layout()) == 6


def test_per_token_law_against_the_published_parameter_counts(R):
    """Beside benchmarks/tests/test_kernels.py's case for ``moe_decoder``: the
    module that decides ``correct`` also carries the law the utilisation
    metrics read, so the law is held to the source's published totals."""
    hf = _published()
    c = R.shapes(hf)
    total = c["parameter_count"]()
    assert abs(total - 8.34e9) < 0.01 * 8.34e9  # "8.3B", the head tied
    # the program's own tree has exactly as many
    assert (c["kv_layers"], c["conv_layers"], c["expert_layers"]) == (6, 18, 22)
    assert (c["kv_heads"], c["head_dim"], c["q_heads"]) == (8, 64, 32)
    assert (c["top_k"], c["hidden"], c["expert_width"], c["conv_taps"]) == (4, 2048, 1792, 3)
    assert c["vocab"] == 65536
    # forward: 2 x the published ACTIVE parameters (head in, embedding out:
    # the tied table is counted once, as the head) + attention's term
    seq = 4096
    active = 1.56e9
    attention = 6 * 4 * 32 * 64 * seq / 2
    law = c["forward_flops_per_token"](seq)
    assert abs(law - (2 * active + attention)) < 0.02 * law
    # the cut configuration the cell runs: 4.61 B parameters, 9.2 GB in bf16
    cell = json.loads((loader.BENCH_DIR / "configs" / "lfm2-8b-a1b.serve-l13.json").read_text())
    cut = R.shapes(loader.hf_config(cell))
    assert abs(cut["parameter_count"]() - 4.61e9) < 0.01 * 4.61e9
    assert (cut["kv_layers"], cut["conv_layers"], cut["expert_layers"]) == (3, 10, 12)


def test_the_cells_configuration_keeps_every_published_width():
    cell = json.loads((loader.BENCH_DIR / "configs" / "lfm2-8b-a1b.serve-l13.json").read_text())
    published = _published()
    for key, value in published.items():
        if key in cell["reduced"]:
            continue
        assert cell[key] == value, key
    assert sorted(cell["reduced"]) == ["layer_types", "num_dense_layers", "num_hidden_layers"]
    assert cell["layer_types"] == published["layer_types"][1:14]
    for key in ("source", "assumed", "departures", "deployment"):
        assert cell[key]


def test_trains_through_the_pretrain_recipe(tmp_path, devices8, monkeypatch):
    """`automodel pretrain llm` (recipes/train_ft): the family on a mesh, loss
    falling, the aux-free bias update reaching every expert layer."""
    from automodel_tpu.config.loader import ConfigNode
    from automodel_tpu.recipes.train_ft import main

    monkeypatch.setattr(jax, "devices", lambda *a: devices8)
    cfg = ConfigNode({
        "seed": 3,
        "model": {"hf_config": dict(TINY, vocab_size=128),
                  "backend": {"attn": "sdpa", "param_dtype": "float32",
                              "compute_dtype": "float32", "experts": "ragged"}},
        "distributed": {"dp_shard": 4, "tp": 2},
        "dataset": {"_target_": "automodel_tpu.data.sft.MockSFTDataset", "vocab_size": 128,
                    "seq_length": 32, "num_samples": 16},
        "dataloader": {"global_batch_size": 8},
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 8, "max_steps": 12},
        "optimizer": {"name": "adamw", "lr": 5e-3, "grad_clip_norm": 1.0},
        "loss_fn": {"name": "masked_ce"},
        "logging": {"metrics_path": str(tmp_path / "metrics.jsonl")},
    })
    last = main(cfg)
    assert last["step"] == 12 and np.isfinite(last["loss"])
    rows = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    losses = [r["loss"] for r in rows if "loss" in r]
    assert losses[-1] < losses[0]
