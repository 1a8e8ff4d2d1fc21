"""Zero-bubble pipeline schedule: the heavier parity legs.

Split out of test_pipeline.py on purpose: this file sorts LAST in the
suite, so the expensive multi-compile legs (bounded deferral queues, the
MoE gate-bias train-step parity) spend wall-clock only after every other
test has had its turn — the cheap dense parity + analytic-law acceptance
tests stay in test_pipeline.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu import auto_model
from automodel_tpu.parallel.mesh import MeshConfig, build_mesh

from tests.test_pipeline import HF, FP32, MOE_HF, ZB_TOL, _grad_tree


def test_zero_bubble_bounded_queue_matches(devices8):
    """pp_zb_queue < M consumes deferred W chunks on the B ticks instead of
    the flat flush — gradients must not change."""
    grads = {}
    for q in (None, 2, 1):
        ctx = build_mesh(
            MeshConfig(
                pp=2, dp_shard=1, pp_schedule="zero_bubble", pp_zb_queue=q
            ),
            devices=devices8[:2],
        )
        a = auto_model.from_config(HF, ctx, {**FP32, "pp_microbatches": 4}, seed=0)
        ids = jnp.asarray(
            np.random.default_rng(12).integers(0, 128, size=(8, 16)), jnp.int32
        )
        grads[q] = _grad_tree(a.model, a.params, ids)
    for q in (2, 1):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
            ),
            grads[q],
            grads[None],
        )


# qwen3_moe with the aux-free balancing path active (router bias +
# post-step update_gate_bias) — the hook the single-backward assumption
# in the gpipe path used to own
MOE_BIAS_HF = {
    **MOE_HF,
    "topk_method": "noaux_tc",  # → expert_bias + bias_update_factor=0.001
}


def test_zero_bubble_moe_parity_and_gate_bias_update(devices8):
    """MoE zero-bubble: forward/aux/grad parity with gpipe, and the aux-free
    gate-bias update (post_step_fn, driven by the forward-accumulated
    expert counts) produces the same bias trajectory under both schedules."""
    from automodel_tpu.data.loader import place_batch
    from automodel_tpu.optim.builders import build_optimizer, init_opt_state
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import (
        build_train_step,
        make_causal_lm_loss,
    )

    results = {}
    for sched in ("gpipe", "zero_bubble"):
        ctx = build_mesh(
            MeshConfig(pp=2, dp_shard=1, pp_schedule=sched), devices=devices8[:2]
        )
        auto = auto_model.from_config(
            MOE_BIAS_HF, ctx, {**FP32, "pp_microbatches": 4}, seed=0
        )
        assert auto.model.config.moe.bias_update_factor > 0
        ids = jnp.asarray(
            np.random.default_rng(13).integers(0, 128, size=(8, 16)), jnp.int32
        )
        out, aux = jax.jit(auto.model.__call__)(auto.params, ids)
        g = _grad_tree(auto.model, auto.params, ids)

        opt = build_optimizer(name="adamw", lr=1e-3, grad_clip_norm=1.0)
        state = TrainState.create(auto.params, init_opt_state(opt, auto.params, auto.mesh_ctx))
        loss_fn = make_causal_lm_loss(auto.model, constrain=auto.constrain)
        assert loss_fn.pipeline_info["schedule"] == sched
        step = build_train_step(loss_fn, opt, post_step_fn=auto.model.post_step_fn)
        batch = place_batch(
            ctx,
            {
                "input_ids": np.asarray(ids)[None],
                "labels": np.asarray(ids)[None],
            },
        )
        metrics = None
        for _ in range(2):
            state, metrics = step(state, batch)
        results[sched] = dict(
            out=np.asarray(out),
            counts=np.asarray(aux.expert_counts),
            aux_loss=float(aux.aux_loss),
            grads=g,
            loss=float(jax.device_get(metrics["loss"])),
            bias=np.asarray(
                jax.device_get(
                    state.params["moe_layers"]["moe"]["router"]["bias"]
                )
            ),
            bubble=float(jax.device_get(metrics["pp_bubble_fraction"])),
        )
    zb, gp = results["zero_bubble"], results["gpipe"]
    np.testing.assert_allclose(zb["out"], gp["out"], **ZB_TOL)
    np.testing.assert_allclose(zb["counts"], gp["counts"], atol=1e-3)
    np.testing.assert_allclose(zb["aux_loss"], gp["aux_loss"], rtol=1e-4, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), **ZB_TOL
        ),
        zb["grads"],
        gp["grads"],
    )
    np.testing.assert_allclose(zb["loss"], gp["loss"], rtol=1e-4)
    # the gate-bias update consumed identical expert counts → identical
    # post-step bias under both schedules (sign-of-error updates are exact)
    np.testing.assert_array_equal(zb["bias"], gp["bias"])
    assert zb["bias"].any(), "gate-bias update never fired"
    # the reported analytic bubble is below the GPipe law
    assert zb["bubble"] < gp["bubble"]




DEEPSEEK_HF = {
    "architectures": ["DeepseekV3ForCausalLM"],
    "model_type": "deepseek_v3",
    "vocab_size": 128,
    "hidden_size": 64,
    "intermediate_size": 128,
    "moe_intermediate_size": 32,
    "num_hidden_layers": 3,
    "num_attention_heads": 4,
    "n_routed_experts": 8,
    "num_experts_per_tok": 2,
    "n_shared_experts": 1,
    "n_group": 1,
    "topk_group": 1,
    "first_k_dense_replace": 1,
    "norm_topk_prob": True,
    "scoring_func": "sigmoid",
    "topk_method": "noaux_tc",
    "q_lora_rank": 32,
    "kv_lora_rank": 16,
    "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8,
    "v_head_dim": 16,
}


def test_zero_bubble_mla_falls_back_to_gpipe(devices8):
    """DeepSeek's MLA attention does raw kernel matmuls (no _proj / zb_tap
    hook): zero_bubble there would silently zero the deferred attention
    kernels' gradients, so maybe_pipeline must downgrade the schedule —
    visibly, in pipeline_info — rather than freeze weights."""
    ctx = build_mesh(
        MeshConfig(pp=2, dp_shard=1, pp_schedule="zero_bubble"),
        devices=devices8[:2],
    )
    auto = auto_model.from_config(
        DEEPSEEK_HF, ctx, {**FP32, "attn": "sdpa", "pp_microbatches": 4}, seed=0
    )
    assert auto.model.schedule == "gpipe"
    assert auto.model.pipeline_info["schedule"] == "gpipe"
