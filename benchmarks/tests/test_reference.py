"""The plain reference against the program's own models at the tiny CPU
presets of both families (same seeded weights, float32 on both sides), its
control one precision down, and a hand-checked routing case."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import loader
from benchmarks.harness import weights as W
from benchmarks.reference import moe_decoder as R

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
TINY = json.loads((Path(__file__).parent / "rehearse.json").read_text())["config"]


def tiny_config(name):
    config = json.loads((CONFIGS / f"{name}.json").read_text())
    config.update({k: v for k, v in TINY.items() if k in config})
    return config


def program_and_reference(name, seed=3):
    from automodel_tpu import auto_model

    config = tiny_config(name)
    backend = {"attn": "sdpa", "experts": "ragged", "param_dtype": "float32",
               "compute_dtype": "float32"}
    auto = auto_model.from_config(loader.program_hf_config(config), None, backend, abstract=True)
    params = W.make(auto.params, seed)
    ref_params = W.make(auto.params, seed, reference_layout=True)
    spec = R.DecoderSpec.from_config(loader.hf_config(config), config["reference"])
    return auto, params, ref_params, spec


@pytest.mark.parametrize("name", ["sdar-30b-a3b.train-l1", "minimax-m2.serve-l1"])
def test_reference_agrees_with_the_programs_forward(name):
    auto, params, ref_params, spec = program_and_reference(name)
    ids = np.random.default_rng(0).integers(3, spec.vocab_size, size=48).astype(np.int32)
    logits, _ = auto.model(params, jnp.asarray(ids)[None])
    ref = R.rows_logits(ref_params, jnp.asarray(ids), jnp.int32(0), spec, "f32", 48)
    # float32 on both sides: what is left is the order of sums
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_rows_window_and_end_padding_change_nothing():
    _, _, ref_params, spec = program_and_reference("minimax-m2.serve-l1")
    ids = np.random.default_rng(1).integers(3, spec.vocab_size, size=40).astype(np.int32)
    full = R.rows_logits(ref_params, jnp.asarray(ids), jnp.int32(0), spec, "f32", 40)
    padded = np.concatenate([ids, np.zeros(24, np.int32)])
    part = R.rows_logits(ref_params, jnp.asarray(padded), jnp.int32(30), spec, "f32", 8)
    np.testing.assert_allclose(np.asarray(full[30:38]), np.asarray(part), atol=1e-5, rtol=1e-5)


def test_reference_train_step_agrees_with_value_and_grad_of_the_program():
    auto, params, ref_params, spec = program_and_reference("sdar-30b-a3b.train-l1")
    from automodel_tpu.training.train_step import make_causal_lm_loss

    rng = np.random.default_rng(2)
    ids = rng.integers(3, spec.vocab_size, size=(2, 32)).astype(np.int32)
    labels = np.where(rng.random((2, 32)) < 0.25, -100, np.roll(ids, -1, axis=1)).astype(np.int32)
    loss_fn = make_causal_lm_loss(auto.model, loss="fused_linear_ce")

    def mean_loss(p):
        total, n, _ = loss_fn(p, {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(labels)})
        return total / n

    loss, grads = jax.value_and_grad(mean_loss)(params)
    opt = R.AdamSpec(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.0, clip_norm=None,
                     moments_dtype="float32")
    mu, nu = R.init_moments(ref_params, opt)
    _, _, _, ref_loss, norms, small = R.train_step(
        ref_params, mu, nu, jnp.int32(0), jnp.asarray(ids), jnp.asarray(labels), spec, opt
    )
    assert float(loss) == pytest.approx(float(ref_loss), abs=1e-4)
    got = {W.path_name(p): np.asarray(g) for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    want = W.by_program_name(jax.device_get(small), params, norms=False)
    for name, ref in want.items():
        np.testing.assert_allclose(got[name].reshape(ref.shape), ref, atol=2e-5, rtol=2e-3, err_msg=name)


def test_sigmoid_router_selects_by_biased_score_and_weighs_by_the_unbiased():
    spec = R.DecoderSpec(vocab_size=8, hidden_size=2, num_layers=1, num_heads=1, num_kv_heads=1,
                         head_dim=2, num_experts=3, top_k=1, expert_width=2, rms_eps=1e-6,
                         rope_theta=1e4, rotary_dim=2, qk_norm=None, router="sigmoid_bias")
    lp = {"router": jnp.asarray([[2.0, 0.0, -2.0], [0.0, 0.0, 0.0]]),
          "router_bias": jnp.asarray([0.0, 0.0, 1.0])}
    idx, w = R.route(jnp.asarray([[1.0, 0.0]]), lp, spec)
    # scores .88 .5 .12; with the bias .88 .5 1.12: expert 2 is chosen, its weight renormalises to 1
    assert idx.tolist() == [[2]] and float(w[0, 0]) == pytest.approx(1.0)
