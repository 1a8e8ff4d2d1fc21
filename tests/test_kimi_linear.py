"""Kimi-Linear through the normal path, against its plain reference.

Tiny widths, float32, weights drawn by the benchmark's own rules
(``benchmarks/harness/weights.py`` with the configuration file's
``reference.init``), the delta-rule kernels in interpret mode. The program
holds experts [4, 8) of 16, as the cell's configuration holds 8 of 256, and
the reference is given the same share.
"""

import functools
import json
import os
import re
import sys
from pathlib import Path
from types import SimpleNamespace
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.registry import resolve_architecture
from benchmarks.harness import loader, program_trace
from benchmarks.harness import weights as W

HF = {
    "architectures": ["KimiLinearForCausalLM"], "model_type": "kimi_linear",
    "vocab_size": 96, "hidden_size": 48, "intermediate_size": 64, "moe_intermediate_size": 32,
    # one layer of each kind and nothing twice: delta rule and a dense MLP, delta
    # rule and experts, latent attention and experts
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 12,
    "kv_lora_rank": 24, "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "mla_use_nope": True, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "topk_group": 1, "num_experts": 16, "num_experts_per_token": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "rope_scaling": None, "tie_word_embeddings": False, "hidden_act": "silu",
    "linear_attn_config": {"full_attn_layers": [3], "kda_layers": [1, 2], "head_dim": 16,
                           "num_heads": 4, "short_conv_kernel_size": 4},
    "held_experts": [4, 8],
}
CONFIG_FILE = ROOT / "benchmarks" / "configs" / "kimi-linear-48b-a3b.train-ep32-l5.json"
F32 = BackendConfig(attn="sdpa", experts="ragged", param_dtype="float32",
                    compute_dtype="float32", remat="none")
# float32 against float32: what is left is the order of sums (the chunked
# rule against the token loop, the sorted experts against the dense ones):
# 1e-5 of the logits' scale, measured 3e-6. The same model in bfloat16 reads
# 2e-2 (the last test), three orders above the limit.
LOGITS_TOL = 5e-5
# gradients pass through the same sums twice and through a softmax and a
# sigmoid router; measured 2e-5 of the leaf's own norm at worst
GRAD_TOL = 2e-4


def _program_loss(model, params, ids, labels):
    logits, _ = model(params, ids)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    keep = labels >= 0
    return jnp.sum(jnp.where(keep, lse - picked, 0.0)) / jnp.sum(keep)


@pytest.fixture(scope="module")
def setup():
    """The model, its weights, one batch, and what the program and the
    reference make of that batch: each a whole program under ONE ``jax.jit``,
    compiled and run the first time a test reads it (called op by op, the
    model dispatched hundreds of programs of one primitive each, every test
    over again). The program's two are traced with the delta-rule kernels in
    interpret mode."""
    R = loader.load_module("reference", "kimi_linear")
    init = json.loads(CONFIG_FILE.read_text())["reference"]["init"]
    model, adapter = resolve_architecture(HF)(HF, F32)
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    params = W.make(abstract, 7, init=init)
    ref_hf = dict(HF, num_experts=4)  # the file's key counts the experts HELD
    spec = R.spec(ref_hf, {"published_experts": 16, "held_experts": [4, 8]})
    # key 0: the closest call of any top-4-of-16 pick on these ids is 5.4e-4 of
    # a score (test_no_routing_tie_...), the widest of keys 0-39 (key 9, which
    # the five-layer model of PR 43 used, reads 3.0e-4 on these three layers).
    # A margin some ten times the float32 noise between the chunked rule and
    # the token loop (1.2e-5, key 1, until PR 43) is near enough for a
    # differently ordered CPU reduction (six busy workers) to flip a pick now
    # and then, and one flipped pick moves the gradients by far more than
    # GRAD_TOL
    ids = jax.random.randint(jax.random.key(0), (2, 72), 0, HF["vocab_size"])
    labels = jnp.where(jax.random.uniform(jax.random.key(2), ids.shape) < 0.25, -100,
                       jnp.roll(ids, -1, axis=1))
    ref = R.to_reference(params)
    kernels = mock.patch.dict(os.environ, AUTOMODEL_DELTA_INTERPRET="1")

    @functools.cache
    def forward():
        """(logits, the model's counters)."""
        with kernels:
            return jax.jit(lambda p, i: model(p, i))(params, ids)

    @functools.cache
    def program():
        """(the loss, its gradient by every leaf)."""
        with kernels:
            return jax.jit(jax.value_and_grad(lambda p: _program_loss(model, p, ids, labels)))(params)

    @functools.cache
    def reference():
        """(the loss, its gradient by every leaf of the reference's tree)."""
        def loss(p):
            total, n = R.loss_sum(p, ids, labels, spec)
            return total / n

        return jax.jit(jax.value_and_grad(loss))(ref)

    @functools.cache
    def reference_logits():
        return jnp.stack([R.rows_logits(ref, row, 0, spec, "f32", ids.shape[1]) for row in ids])

    return SimpleNamespace(R=R, model=model, adapter=adapter, params=params, ref=ref, spec=spec,
                           ids=ids, labels=labels, forward=forward, program=program,
                           reference=reference, reference_logits=reference_logits)


def test_resolves_through_the_registry_with_the_published_keys():
    src = json.loads(CONFIG_FILE.read_text())
    hf = loader.program_hf_config(src)
    model, _ = resolve_architecture(hf)(hf, F32)
    c = model.config
    assert type(model).__name__ == "KimiLinearForCausalLM"
    assert c.layer_kinds == ("kda", "kda", "kda", "mla", "kda")
    assert (c.kda_num_heads, c.kda_head_dim, c.kda_conv_kernel) == (32, 128, 4)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank) == (128, 64, 128, 512)
    assert c.use_rope is False and c.q_lora_rank is None
    m = c.moe
    assert (m.num_experts, m.num_experts_per_tok, m.held_experts, m.num_held_experts) == (256, 8, (0, 8), 8)
    assert (m.score_func, m.norm_topk_prob, m.route_scale, m.num_shared_experts) == ("sigmoid", True, 2.446, 1)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert shapes["moe"]["experts"]["gate_up"].shape == (4, 8, 2304, 2048)
    assert shapes["moe"]["router"]["weight"].shape == (4, 2304, 256)
    assert shapes["lm_head"]["kernel"].shape == (2304, 20480)


def test_logits_and_loss_match_the_reference(setup):
    logits, aux = setup.forward()
    ref = setup.reference_logits()
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(logits - ref).max()) / scale < LOGITS_TOL
    assert abs(float(setup.program()[0]) - float(setup.reference()[0])) < 1e-5
    # the counter: picks that landed on the held experts, against a direct count
    assert aux.expert_counts.shape == (2, 16)
    assert int(aux.held_expert_rows) == int(aux.expert_counts[:, 4:8].sum())


def test_every_leaf_gradient_matches_the_reference(setup):
    # on the host: a norm of a difference is numpy's, not a program a leaf shape
    (_, got), (_, want) = jax.device_get((setup.program(), setup.reference()))
    names = jax.tree.leaves(setup.R.program_names(setup.ref), is_leaf=lambda x: isinstance(x, tuple))
    by_name = {W.path_name(p): g for p, g in jax.tree_util.tree_flatten_with_path(got)[0]}
    assert sorted(n for n, _ in names) == sorted(by_name)  # every leaf, once
    for (name, _), w in zip(names, jax.tree.leaves(want)):
        g = by_name[name]
        if name == "moe/router/bias":  # selects, never weighs: no gradient on either side
            assert not np.any(g) and not np.any(w)
            continue
        err = float(np.linalg.norm(g - w) / np.linalg.norm(w))
        assert err < GRAD_TOL, (name, err)


def test_no_routing_tie_sits_near_float32_noise(setup):
    """What the two tests above rest on: the router's top-k is the one
    discrete event between program and reference. A pick whose margin over the
    best unpicked score is within the float32 noise of two orders of summation
    flips, and a flip is no rounding. The margins on these ids are two orders
    above that noise (logits agree to 3e-6 of their scale)."""
    s, R = setup, setup.R
    margins = []
    real_route = R.route

    def spy_route(x, lp, sp):
        scores = jax.nn.sigmoid(x @ lp["router"]) + lp["router_bias"]
        top = jax.lax.top_k(scores, sp.top_k + 1)[0]
        jax.debug.callback(lambda m: margins.append(float(m)),
                           jnp.min(top[:, sp.top_k - 1] - top[:, sp.top_k]))
        return real_route(x, lp, sp)

    R.route = spy_route
    try:  # traced while the spy stands in
        jax.block_until_ready(jax.jit(lambda p: R.loss_sum(p, s.ids, s.labels, s.spec))(s.ref))
    finally:
        R.route = real_route
    jax.effects_barrier()
    assert len(margins) == 2 and min(margins) > 4e-4, margins  # two expert layers


def test_state_dict_round_trip(setup):
    adapter, params = setup.adapter, setup.params
    sd = dict(adapter.to_hf(params))
    assert sorted(sd) == sorted(adapter.hf_keys())
    # held experts keep their published numbers; the router keeps every column
    assert "model.layers.1.block_sparse_moe.experts.4.w1.weight" in sd
    assert "model.layers.1.block_sparse_moe.experts.0.w1.weight" not in sd
    assert sd["model.layers.1.block_sparse_moe.gate.weight"].shape == (16, 48)
    assert sd["model.layers.0.self_attn.A_log"].shape == (1, 1, 4, 1)
    assert sd["model.layers.0.self_attn.q_conv1d.weight"].shape == (64, 1, 4)
    back = adapter.from_hf(lambda k: sd[k])
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                 params, back)


def _kda_block_with_the_norm_outside(cfg, backend, h, lp, norm_scale):
    """``kda_block`` as it stood until the per-head norm became the delta-rule
    kernels' epilogue: the operator gives the raw ``o`` in the compute type,
    ``rms_norm`` takes it a head at a time as ``[B, S, H, dh]`` and the gate
    multiplies the flat result."""
    from automodel_tpu.ops.delta_rule import chunked_delta_rule
    from automodel_tpu.ops.norms import rms_norm
    from automodel_tpu.ops.short_conv import causal_conv1d

    B, S, _ = h.shape
    H, dh = cfg.kda_num_heads, cfg.kda_head_dim
    f32 = jnp.float32
    x = rms_norm(h, norm_scale, cfg.rms_eps)
    proj = lambda name: x @ lp[name]["kernel"].astype(x.dtype)
    conv = lambda a, name: jax.nn.silu(causal_conv1d(a, lp[name]["weight"].astype(a.dtype), None))
    q, k, v = (conv(proj(f"{n}_proj"), f"{n}_conv") for n in "qkv")
    f = (proj("f_a_proj") @ lp["f_b_proj"]["kernel"].astype(x.dtype)).astype(f32) + lp["dt_bias"].astype(f32)
    g = -jnp.repeat(jnp.exp(lp["A_log"].astype(f32)), dh) * jax.nn.softplus(f)
    beta = jax.nn.sigmoid(proj("b_proj").astype(f32))
    gate = proj("g_a_proj") @ lp["g_b_proj"]["kernel"].astype(x.dtype)
    o = chunked_delta_rule(q, k, v, g, beta, platform=backend.platform, mesh_ctx=backend.mesh_ctx)
    o = rms_norm(o.reshape(B, S, H, dh), lp["o_norm"]["scale"], cfg.rms_eps)
    o = (o.reshape(B, S, H * dh).astype(f32) * jax.nn.sigmoid(gate.astype(f32))).astype(x.dtype)
    return h + o @ lp["o_proj"]["kernel"].astype(x.dtype)


def test_the_kda_block_is_the_block_with_its_norm_outside(setup):
    """The mixer with ``out_norm_eps`` and a flat ``scale * sigmoid(gate)``
    pass against the formulation it replaces, float32, the kernels in
    interpret mode: the block's output and the gradient of every parameter
    of the layer (``o_norm.scale`` among them: its gradient is now a column
    sum of a flat array folded ``[H, dh] -> [dh]``) and of the input. The
    weights' norm scales are 1 + 0.1 N(0, 1), so a dropped or misplaced scale
    shows."""
    from automodel_tpu.models.kimi_linear.model import kda_block

    model, params = setup.model, setup.params
    cfg, backend = model.config, model.backend
    lp = jax.tree.map(lambda a: a[0], params["kda"])
    scale = params["layers"]["input_norm"]["scale"][0]
    assert float(jnp.abs(lp["o_norm"]["scale"] - 1.0).max()) > 0.05
    h = jax.random.normal(jax.random.key(3), (2, 72, cfg.hidden_size), jnp.float32)
    w = jax.random.normal(jax.random.key(4), h.shape, jnp.float32)

    def run(block):
        def weighed(h, lp, scale):
            out = block(h, lp, scale)
            return (out * w).sum(), out

        with mock.patch.dict(os.environ, AUTOMODEL_DELTA_INTERPRET="1"):
            return jax.device_get(jax.jit(jax.value_and_grad(weighed, argnums=(0, 1, 2), has_aux=True))(
                h, lp, scale))

    (_, got), got_grads = run(lambda h, lp, s: kda_block(cfg, backend, h, lp, s, None, lambda x, _: x))
    (_, want), want_grads = run(lambda h, lp, s: _kda_block_with_the_norm_outside(cfg, backend, h, lp, s))
    assert np.abs(got - want).max() / np.abs(want).max() < LOGITS_TOL
    flat = lambda t: {W.path_name(p): g for p, g in jax.tree_util.tree_flatten_with_path(t)[0]}
    got_grads, want_grads = flat(got_grads), flat(want_grads)
    assert sorted(got_grads) == sorted(want_grads) and any("o_norm" in n for n in got_grads)
    for name, w_ in want_grads.items():
        err = float(np.linalg.norm(got_grads[name] - w_) / np.linalg.norm(w_))
        assert err < GRAD_TOL, (name, err)


def test_the_train_step_writes_both_mixers_scopes_and_the_counter(setup):
    from automodel_tpu.optim.builders import build_optimizer
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss
    from automodel_tpu.utils.profiler import SCOPES

    model, params, ids, labels = setup.model, setup.params, setup.ids, setup.labels
    opt = build_optimizer(lr=1e-3, grad_clip_norm=1.0)
    step = build_train_step(make_causal_lm_loss(model, loss="fused_linear_ce", num_chunks=2),
                            opt, donate=False)
    state = TrainState.create(params, jax.jit(opt.init)(params))
    batch = {"input_ids": ids[None], "labels": labels[None]}
    lowered = step.lower(state, batch)  # traced once: its text is read, and it is run
    text = lowered.as_text(debug_info=True)
    segments = set()
    for name in re.findall(r'loc\("([^"/][^"]*)"', text):
        segs = program_trace.path_segments(name)
        segments |= {s for s in SCOPES if all(part in segs for part in s.split("/"))}
        # the frozen vocabulary files either mixer under `attn`
        if "kda" in segs or "mla" in segs:
            assert program_trace.scope_of(name) == "attn", name
    assert {"attn/kda", "attn/kda/kda_conv", "attn/kda/kda_gate", "attn/kda/kda_chunk",
            "attn/kda/kda_norm", "attn/mla"} <= segments
    _, metrics = lowered.compile()(state, batch)
    _, aux = setup.forward()
    assert int(metrics["held_expert_rows"]) == int(aux.expert_counts[:, 4:8].sum()) > 0


def test_serving_refuses_the_family(setup):
    from automodel_tpu.serving.engine import ServeConfig

    model = setup.model
    with pytest.raises(ValueError, match="delta-rule state is not served yet"):
        ServeConfig().check_layout(model.cache_layout(), type(model).__name__)


def test_bfloat16_is_far_outside_the_tolerances(setup):
    """What makes the limits above tight: the same weights through the same
    path with bfloat16 compute (delta rule, router input, every product) miss
    the float32 reference by orders more than ``LOGITS_TOL``."""
    bf16 = BackendConfig(attn="sdpa", experts="ragged", param_dtype="float32",
                         compute_dtype="bfloat16", remat="none")
    model, _ = resolve_architecture(HF)(HF, bf16)
    logits, _ = jax.jit(lambda p, i: model(p, i))(setup.params, setup.ids)
    ref = setup.reference_logits()
    gap = float(jnp.abs(logits.astype(jnp.float32) - ref).max() / jnp.abs(ref).max())
    assert gap > 20 * LOGITS_TOL, gap


def test_trains_from_a_yaml_through_the_normal_recipe(tmp_path, monkeypatch):
    """``automodel pretrain llm -c <yaml>``'s path: the YAML, ``from_config``
    by the registered architecture, recipes/train_ft.py; nothing else drives it."""
    from automodel_tpu.config.loader import load_yaml_config
    from automodel_tpu.recipes.train_ft import main

    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices(backend="cpu")[:1])
    cfg = load_yaml_config(ROOT / "examples" / "llm_pretrain" / "kimi_linear_tiny_cpu.yaml")
    cfg["output_dir"] = str(tmp_path / "run")
    cfg["logging"] = {"metrics_path": str(tmp_path / "metrics.jsonl")}
    last = main(cfg)
    assert last["step"] == 12 and np.isfinite(last["loss"])
    rows = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    losses = [r["loss"] for r in rows if "loss" in r]
    # random tokens: what can be learned is that no token is likelier than another
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses
    held = [r["held_expert_rows"] for r in rows if "held_expert_rows" in r]
    # 2 x 96 tokens x 4 picks x 4 expert layers, a quarter of 16 experts held
    assert held and all(0 < h < 2 * 96 * 4 * 4 for h in held)
