"""QAT (STE fake-quant, delayed enablement) + QLoRA (NF4 base).

Reference parity targets: quantization/qat.py:46,125-146 (torchao fake-quant
quantizers with enable/disable hooks) and qlora.py:22 (bitsandbytes NF4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu import auto_model
from automodel_tpu.quantization import (
    QATConfig,
    QLoRAConfig,
    fake_quant_weight,
    make_qat_loss_fn,
    nf4_dequantize,
    nf4_dequantize_tree,
    nf4_quantize,
    nf4_quantize_tree,
)

HF = {
    "architectures": ["LlamaForCausalLM"],
    "model_type": "llama",
    "vocab_size": 128,
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
}
FP32 = {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"}


# ---- QAT -------------------------------------------------------------------
def test_fake_quant_levels_and_ste():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    q = fake_quant_weight(w, groupsize=32)
    # per group of 32 input rows, at most 16 distinct levels per output col
    qn = np.asarray(q)
    for col in range(4):
        grp = qn[:32, col]
        assert len(np.unique(np.round(grp / (np.abs(grp).max() / 7 + 1e-12)))) <= 16
    # straight-through: gradient of sum(fq(w)) is exactly ones
    g = jax.grad(lambda w: fake_quant_weight(w, 32).sum())(w)
    np.testing.assert_array_equal(np.asarray(g), np.ones_like(np.asarray(g)))
    # quantization changes values (it's not a no-op)
    assert float(jnp.abs(q - w).max()) > 0


def test_qat_delayed_enablement_and_training():
    from automodel_tpu.optim.builders import build_optimizer, init_opt_state
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    auto = auto_model.from_config(HF, None, FP32, seed=0)
    base_loss = make_causal_lm_loss(auto.model)
    qat_loss = make_qat_loss_fn(base_loss, QATConfig(
        quantizer_type="int4_weight_only", groupsize=32, start_step=2,
    ))
    assert qat_loss.needs_step

    ids = np.random.default_rng(1).integers(0, 128, size=(1, 12)).astype(np.int32)
    mb = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(ids)}
    # before start_step the transform is a no-op; after it, losses differ
    l_pre, _ = qat_loss(auto.params, mb, step=jnp.asarray(0))
    l_base, _ = base_loss(auto.params, mb)
    l_post, _ = qat_loss(auto.params, mb, step=jnp.asarray(5))
    np.testing.assert_allclose(float(l_pre), float(l_base), rtol=1e-6)
    assert abs(float(l_post) - float(l_base)) > 1e-6

    # end-to-end: train step consumes the step-threaded loss and learns
    opt = build_optimizer(name="adamw", lr=5e-3)
    state = TrainState.create(auto.params, init_opt_state(opt, auto.params, auto.mesh_ctx))
    step = build_train_step(qat_loss, opt)
    batch = {"input_ids": jnp.asarray(ids)[None], "labels": jnp.asarray(ids)[None]}
    losses = []
    for _ in range(6):
        state, metrics = step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    assert losses[-1] < losses[0]


def test_qat_config_validates():
    with pytest.raises(ValueError):
        QATConfig(quantizer_type="fp3")


# ---- QLoRA -----------------------------------------------------------------
def test_nf4_round_trip_error_bounded():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32))
    q = nf4_quantize(w, blocksize=64)
    assert q["codes"].dtype == jnp.uint8
    assert q["codes"].size == w.size // 2  # 4 bits/param packed
    back = nf4_dequantize(q)
    assert back.shape == w.shape and back.dtype == w.dtype
    err = float(jnp.abs(back - w).max() / jnp.abs(w).max())
    assert err < 0.2  # nf4 with absmax block scaling
    # deterministic round trip through quantize again
    q2 = nf4_quantize(back, blocksize=64)
    np.testing.assert_array_equal(np.asarray(q2["codes"]), np.asarray(q["codes"]))


def test_qlora_tree_and_training():
    from automodel_tpu.peft import PeftConfig, init_lora_params, make_lora_loss_fn
    from automodel_tpu.optim.builders import build_optimizer
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    auto = auto_model.from_config(HF, None, FP32, seed=0)
    qcfg = QLoRAConfig(min_size=1024, blocksize=64)
    qtree = nf4_quantize_tree(auto.params, qcfg)
    # big kernels are packed, embeddings/norms untouched
    assert "codes" in qtree["layers"]["attn"]["q_proj"]["kernel"]
    assert not isinstance(qtree["embed"]["embedding"], dict) or "codes" not in qtree[
        "embed"
    ]["embedding"]

    pcfg = PeftConfig(target_modules=("*attn/[qkvo]_proj*", "*mlp*"), dim=4, alpha=8)
    lora = init_lora_params(jax.random.key(0), auto.params, pcfg)
    base_loss = make_causal_lm_loss(auto.model)
    loss_fn = make_lora_loss_fn(
        base_loss, qtree, pcfg,
        graft_patterns=auto.model.lora_graft_patterns,
        base_transform=nf4_dequantize_tree,
    )
    ids = np.random.default_rng(3).integers(0, 128, size=(1, 2, 12)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(ids)}

    # loss at init is close to the full-precision base (nf4 error only) —
    # checked BEFORE training: the train step donates the lora buffers
    fp_loss = make_lora_loss_fn(
        base_loss, auto.params, pcfg,
        graft_patterns=auto.model.lora_graft_patterns,
    )
    mb = {k: v[0] for k, v in batch.items()}
    l_q = float(loss_fn(lora, mb, qtree)[0])
    l_f = float(fp_loss(lora, mb, auto.params)[0])
    assert abs(l_q - l_f) / abs(l_f) < 0.1

    opt = build_optimizer(name="adamw", lr=1e-2)
    state = TrainState.create(lora, jax.jit(opt.init)(lora))
    step = build_train_step(loss_fn, opt)
    losses = []
    for _ in range(6):
        state, metrics = step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    assert losses[-1] < losses[0]
