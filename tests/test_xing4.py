"""Xing4.0 through the normal path, against its plain reference.

Tiny widths, float32, weights drawn by the benchmark's own rules
(``benchmarks/harness/weights.py`` with the configuration file's
``reference.init``: ``alpha`` of order 1, ``b`` small and non-zero, so that no
map is uniform or the identity). The program holds experts [4, 8) of 16, as
the cell's configuration holds 8 of 64, and the reference is given the same
share.
"""

import functools
import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.registry import resolve_architecture
from automodel_tpu.training.train_step import make_causal_lm_loss, shift_labels
from benchmarks.harness import loader, program_trace
from benchmarks.harness import weights as W

HF = {
    "model_type": "xing4_0",  # no `architectures`: the registry knows the type
    "vocab_size": 96, "hidden_size": 48, "intermediate_size": 64, "moe_intermediate_size": 32,
    # one layer of each kind and nothing twice: a dense one, an expert one, and
    # the module's (one more of the expert kind)
    "num_hidden_layers": 2, "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 20, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 16, "num_experts_per_tok": 4,
    "n_shared_experts": 1, "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    # 15 rounds: the fewest at which every row of Hres sums to 1 within 1e-3 on
    # these ids (8.2e-4; 14 rounds leave 1.2e-3; the published count is 20).
    # How the operator converges is tests/test_hyper_connections.py's subject
    "moe_layer_freq": 1, "hc_mult": 4, "hc_sinkhorn_iters": 15, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "num_nextn_predict_layers": 1,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "max_position_embeddings": 4096,
    # 72 positions against an original window of 16: every branch of the ramp
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 16, "type": "yarn"},
    "tie_word_embeddings": False, "hidden_act": "silu",
    "held_experts": [4, 8], "mtp_loss_weight": 0.3,
}
CONFIG_FILE = ROOT / "benchmarks" / "configs" / "xing4.0-29b-a4b.train-ep8-l5.json"
F32 = BackendConfig(attn="sdpa", experts="ragged", param_dtype="float32",
                    compute_dtype="float32", remat="none")
# float32 against float32: what is left is the order of sums (the sorted
# experts against the dense ones, attention in blocks, the product with phi
# before or after the norm's scale): measured 5e-6 of a leaf's own norm at
# worst over the leaves. The same model with bfloat16 compute reads 1e-2 (the
# last test). No top-4-of-16 near-tie sits near that float32 noise on these
# ids: the smallest margin between a picked and an unpicked score is checked
# below, so that a flipped pick can neither hide in a tolerance nor come and go
# with the order in which a differently loaded CPU sums.
LOSS_TOL = 2e-5
GRAD_TOL = 2e-4


def _program_losses(model, params, batch):
    """(total, main, the module's): the step's loss function, normalised as
    the step normalises it."""
    loss_sum, n, extras = make_causal_lm_loss(model, loss="fused_linear_ce", num_chunks=2)(params, batch)
    mtp = extras["mtp_loss_sum"] / jnp.maximum(extras["mtp_tokens"], 1)
    total = loss_sum / n
    return total, total - model.config.mtp_loss_weight * mtp, mtp


@pytest.fixture(scope="module")
def setup():
    """The model, its weights, one batch, and what the program and the
    reference make of that batch: each a whole program under ONE ``jax.jit``,
    compiled and run the first time a test reads it. Called op by op, a
    three-layer model dispatched some 500 programs of one primitive each on
    the 8-device CPU platform, every test over again."""
    R = loader.load_module("reference", "xing4")
    init = json.loads(CONFIG_FILE.read_text())["reference"]["init"]
    model, adapter = resolve_architecture(HF)(HF, F32)
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    params = W.make(abstract, 7, init=init)
    ref_hf = dict(HF, n_routed_experts=4)  # the file's key counts the experts HELD
    spec = R.spec(ref_hf, {"published_experts": 16, "held_experts": [4, 8], "mtp_loss_weight": 0.3})
    # key 23: the closest call of any top-4-of-16 pick on these ids is 6.7e-4 of
    # a score (test_no_routing_tie_...), the widest of keys 0-39; at key 9, which
    # the three-layer model of PR 43 used, it is 4.8e-5
    ids = jax.random.randint(jax.random.key(23), (2, 72), 0, HF["vocab_size"])
    labels = jnp.where(jax.random.uniform(jax.random.key(2), ids.shape) < 0.25, -100,
                       jnp.roll(ids, -1, axis=1))
    ref = R.to_reference(params)
    batch = {"input_ids": ids, "labels": labels}

    @functools.cache
    def program():
        """((total, (main, the module's)), the gradient of total by every leaf)."""
        def loss(p):
            total, main, mtp = _program_losses(model, p, batch)
            return total, (main, mtp)

        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    @functools.cache
    def forward():
        """(the main head's logits, the model's counters)."""
        return jax.jit(lambda p, i: model(p, i))(params, ids)

    @functools.cache
    def reference():
        """(main, the module's, the gradient of main + 0.3 x the module's, the
        gradient of main alone): one forward, pulled back twice."""
        def run(p):
            (l_main, l_mtp), pull = jax.vjp(lambda q: R.losses(q, ids, labels, spec), p)
            one = jnp.ones((), jnp.float32)
            return l_main, l_mtp, pull((one, spec.mtp_weight * one))[0], pull((one, 0.0 * one))[0]

        return jax.jit(run)(ref)

    return SimpleNamespace(R=R, model=model, adapter=adapter, params=params, ref=ref, spec=spec,
                           ids=ids, labels=labels, program=program, forward=forward,
                           reference=reference)


def test_resolves_through_the_registry_with_the_published_keys():
    src = json.loads(CONFIG_FILE.read_text())
    hf = loader.program_hf_config(src)
    model, _ = resolve_architecture(hf)(hf, F32)
    c = model.config
    assert type(model).__name__ == "Xing4ForCausalLM"
    assert resolve_architecture({"model_type": "xing4_0"}) is resolve_architecture(hf)
    assert (c.hidden_size, c.num_heads, c.q_lora_rank, c.kv_lora_rank) == (3584, 32, 768, 512)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.intermediate_size) == (128, 64, 128, 9216)
    assert (c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps, c.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert (c.num_mtp_modules, c.mtp_loss_weight, c.num_layers) == (1, 0.3, 5)
    assert c.use_rope and c.rope_interleave and c.rope.scaling == "yarn" and c.rope.factor == 64
    # YaRN's correction sits in the softmax scale, and cos/sin carry none
    assert c.mla_attn_scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    from automodel_tpu.ops.rope import rope_table

    cos, _ = rope_table(jnp.zeros((1, 1), jnp.int32), 64, c.rope)
    np.testing.assert_allclose(cos, 1.0)
    m = c.moe
    assert (m.num_experts, m.num_experts_per_tok, m.held_experts, m.num_held_experts) == (64, 4, (0, 8), 8)
    assert (m.score_func, m.norm_topk_prob, m.route_scale, m.num_shared_experts) == ("sigmoid", True, 2, 1)
    assert (m.num_dense_layers, m.moe_intermediate_size, m.expert_bias, m.bias_update_factor) == (1, 1024, True, 0.0)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert shapes["moe"]["experts"]["gate_up"].shape == (4, 8, 3584, 2048)
    assert shapes["moe"]["router"]["weight"].shape == (4, 3584, 64)
    assert shapes["layers"]["attn_hc"]["phi"].shape == (5, 4 * 3584, 24)
    assert shapes["mtp"]["eh_proj"]["kernel"].shape == (1, 7168, 3584)
    assert shapes["mtp"]["moe"]["experts"]["gate_up"].shape == (1, 8, 3584, 2048)
    assert shapes["lm_head"]["kernel"].shape == (3584, 16384)
    # the reference's count of what is held here is the tree's
    R = loader.load_module("reference", "xing4")
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert R.shapes(loader.hf_config(src))["parameter_count"]() == held


def test_loss_and_mtp_loss_match_the_reference(setup):
    s = setup
    (total, (main, mtp)), _ = s.program()
    want_main, want_mtp, *_ = s.reference()
    assert abs(float(main) - float(want_main)) < LOSS_TOL
    assert abs(float(mtp) - float(want_mtp)) < LOSS_TOL
    assert abs(float(total) - float(want_main + 0.3 * want_mtp)) < LOSS_TOL
    # the two losses are different numbers over different targets
    assert abs(float(want_main) - float(want_mtp)) > 1e-3
    # logits of the main head, a row at a time
    logits, aux = s.forward()
    got = jnp.stack([s.R.rows_logits(s.ref, row, 0, s.spec, "f32", s.ids.shape[1]) for row in s.ids])
    assert float(jnp.abs(logits - got).max() / jnp.abs(got).max()) < 5e-5
    # the counters: picks on the held experts over the two expert layers
    # (the stack's, the module's), and rows that converged
    assert aux.expert_counts.shape == (2, 16)
    assert int(aux.held_expert_rows) == int(aux.expert_counts[:, 4:8].sum()) > 0
    assert 0 < float(aux.mhc_res_row_err) < 1e-3


def test_every_leaf_gradient_matches_the_reference(setup):
    s = setup
    # on the host: a norm of a difference is numpy's, not a program a leaf shape
    (_, got), (_, _, want, main_only) = jax.device_get((s.program(), s.reference()))
    names = jax.tree.leaves(s.R.program_names(s.ref), is_leaf=lambda x: isinstance(x, tuple))
    by_name = {W.path_name(p): g for p, g in jax.tree_util.tree_flatten_with_path(got)[0]}
    assert sorted(n for n, _ in names) == sorted(by_name)  # every leaf, once
    assert {"mtp/eh_proj/kernel", "mtp/layers/attn_hc/phi", "layers/mlp_hc/alpha"} <= set(by_name)
    for (name, _), w in zip(names, jax.tree.leaves(want)):
        g = by_name[name]
        if name.endswith("moe/router/bias"):  # selects, never weighs: no gradient on either side
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w))
            continue
        err = float(np.linalg.norm(g - w) / np.linalg.norm(w))
        assert err < GRAD_TOL, (name, err)
    # the embedding and the head are fed by BOTH losses: without the module's
    # their gradients are other numbers
    for leaf in ("embed", "head"):
        assert float(np.linalg.norm(want[leaf] - main_only[leaf]) / np.linalg.norm(want[leaf])) > 1e-2


# the smallest model `R.spec` takes: what the test below claims holds at any
# depth, so it runs at one dense layer, no module, two Sinkhorn rounds
ONE_LAYER = dict(HF, num_hidden_layers=1, num_nextn_predict_layers=0, hc_sinkhorn_iters=2)


@pytest.mark.parametrize("dtype,moments,tol", [(jnp.float32, "float32", 1e-5), (jnp.bfloat16, "param", 1e-2)],
                         ids=["float32", "bfloat16_one_rounding"])
def test_the_reference_step_keeps_its_moments_on_the_host_and_is_adam_step(setup, dtype, moments, tol):
    """The reference's step is two programs with the moments in numpy between
    them (beside float32 moments its gradient does not fit the chip):
    ``adam.step`` on the linear loss whose gradient is the gradient just
    computed is ``adam.step`` on the loss itself: to the order of a sum in
    float32; with bfloat16 parameters the gradient crosses between the
    programs in the parameters' type, where one program may keep XLA's excess
    precision: one bfloat16 rounding of a leaf at most."""
    from benchmarks.reference import adam

    R, ids, labels = setup.R, setup.ids, setup.labels
    model, _ = resolve_architecture(ONE_LAYER)(ONE_LAYER, F32)
    init = json.loads(CONFIG_FILE.read_text())["reference"]["init"]
    params = W.make(jax.eval_shape(model.init, jax.random.key(0)), 7, init=init)
    spec = R.spec(dict(ONE_LAYER, n_routed_experts=4), {"published_experts": 16, "held_experts": [4, 8]})
    opt = adam.AdamSpec(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.0, clip_norm=1.0,
                        moments_dtype=moments)
    ref = jax.tree.map(lambda a: a.astype(dtype), R.to_reference(params))

    def mean_loss(p):
        l_main, l_mtp = R.losses(p, ids, labels, spec)
        return l_main + spec.mtp_weight * l_mtp

    want = jax.jit(lambda p, m, v, t: adam.step(mean_loss, p, m, v, t, opt))
    w_state = (ref, *adam.init_moments(ref, opt))
    g_state = (jax.tree.map(jnp.copy, ref), *R.init_moments(ref, opt))
    for t in range(2):
        *w_state, w_loss, w_norms, w_small = want(*w_state, jnp.int32(t))
        *g_state, g_loss, g_norms, g_small = R.train_step(*g_state, jnp.int32(t), ids, labels, spec, opt)
        assert all(isinstance(a, np.ndarray) for a in jax.tree.leaves(g_state[1:]))
        assert abs(float(g_loss) - float(w_loss)) <= tol
        for g, w in zip(jax.tree.leaves((g_state, g_norms, g_small)), jax.tree.leaves((w_state, w_norms, w_small))):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            assert g.shape == w.shape and np.linalg.norm(g - w) <= tol * np.linalg.norm(w)


def test_no_routing_tie_sits_inside_float32_noise(setup):
    """What the gradient test's tolerance rests on (and what made its Kimi
    sibling flaky): a pick whose margin over the best unpicked score is within
    the float32 noise of two orders of summation flips between program and
    reference, and a flipped pick moves a gradient by far more than GRAD_TOL.
    The margins on these ids are two orders above that noise."""
    s, R = setup, setup.R
    margins = []

    def spy_route(x, lp, sp):
        scores = jax.nn.sigmoid(x @ lp["router"]) + lp["router_bias"]
        top = jax.lax.top_k(scores, sp.top_k + 1)[0]
        jax.debug.callback(lambda m: margins.append(float(m)),
                           jnp.min(top[:, sp.top_k - 1] - top[:, sp.top_k]))
        return real_route(x, lp, sp)

    real_route, R.route = R.route, spy_route
    try:  # traced while the spy stands in
        jax.block_until_ready(jax.jit(lambda p: R.losses(p, s.ids, jnp.roll(s.ids, -1, axis=1), s.spec))(s.ref))
    finally:
        R.route = real_route
    jax.effects_barrier()
    # two expert layers (the stack's, the module's) x two sequences
    assert len(margins) == 4 and min(margins) > 5e-4, margins


def test_packed_documents_end_the_mtp_shift(setup):
    model, params = setup.model, setup.params
    ids = setup.ids[:1]
    labels = jnp.roll(ids, -1, axis=1)
    seg = jnp.concatenate([jnp.ones((1, 40), jnp.int32), jnp.full((1, 32), 2, jnp.int32)], axis=1)
    pos = jnp.concatenate([jnp.arange(40), jnp.arange(32)])[None].astype(jnp.int32)
    shifted = shift_labels(labels, seg)
    # the last position of EACH document has no target, and nothing else is lost
    assert int(shifted[0, 39]) == -100 and int(shifted[0, 71]) == -100
    np.testing.assert_array_equal(np.delete(np.asarray(shifted[0]), [39, 71]),
                                  np.delete(np.asarray(labels[0, 1:]), [39]))
    assert int(shift_labels(labels)[0, 39]) == int(labels[0, 40])  # unpacked: one sequence
    # the module's loss over the packed row = its loss over the two documents apart
    packed = jax.jit(lambda p, b: _program_losses(model, p, b))(
        params, {"input_ids": ids, "labels": labels, "segment_ids": seg, "position_ids": pos})
    lf = jax.jit(make_causal_lm_loss(model, loss="fused_linear_ce", num_chunks=1))
    parts = [lf(params, {"input_ids": ids[:, a:b], "labels": labels[:, a:b]})[2]
             for a, b in ((0, 40), (40, 72))]
    apart = sum(p["mtp_loss_sum"] for p in parts) / sum(p["mtp_tokens"] for p in parts)
    assert sum(int(p["mtp_tokens"]) for p in parts) == 70
    assert abs(float(packed[2]) - float(apart)) < 5e-5


def test_state_dict_round_trip(setup):
    adapter, params = setup.adapter, setup.params
    sd = dict(adapter.to_hf(params))
    assert sorted(sd) == sorted(adapter.hf_keys())
    # held experts keep their published numbers; the router keeps every column
    assert "model.layers.1.mlp.experts.4.gate_proj.weight" in sd
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in sd
    assert sd["model.layers.1.mlp.gate.weight"].shape == (16, 48)
    assert sd["model.layers.0.mlp.gate_proj.weight"].shape == (64, 48)  # the leading dense layer
    assert sd["model.layers.1.attn_hc.phi"].shape == (4 * 48, 24)
    assert sd["model.layers.0.self_attn.q_a_proj.weight"].shape == (20, 48)
    # the module is layer num_hidden_layers, as DeepSeek-V3 stores it; the
    # embedding and the head are not written twice
    assert sd["model.layers.2.eh_proj.weight"].shape == (48, 96)
    assert "model.layers.2.shared_head.norm.weight" in sd and "model.layers.2.mlp_hc.alpha" in sd
    assert not any("layers.2.embed_tokens" in k or "shared_head.head" in k for k in sd)
    back = adapter.from_hf(lambda k: sd[k])
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                 params, back)


def test_the_train_step_writes_the_scopes_and_the_counters(setup):
    from automodel_tpu.optim.builders import build_optimizer
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step
    from automodel_tpu.utils.profiler import SCOPES

    model, params, ids, labels = setup.model, setup.params, setup.ids, setup.labels
    opt = build_optimizer(lr=1e-3, grad_clip_norm=1.0)
    step = build_train_step(make_causal_lm_loss(model, loss="fused_linear_ce", num_chunks=2),
                            opt, donate=False)
    state = TrainState.create(params, jax.jit(opt.init)(params))
    batch = {"input_ids": ids[None], "labels": labels[None]}
    lowered = step.lower(state, batch)  # traced once: its text is read, and it is run
    text = lowered.as_text(debug_info=True)
    segments, under_mtp = set(), set()
    for name in re.findall(r'loc\("([^"/][^"]*)"', text):
        segs = program_trace.path_segments(name)
        segments |= {s for s in SCOPES if all(part in segs for part in s.split("/"))}
        # the frozen vocabulary files the residual path under `norm`
        if "mhc" in segs:
            assert program_trace.scope_of(name) == "norm", name
        if "mtp" in segs:
            under_mtp.add(program_trace.scope_of(name))
    assert {"norm/mhc", "norm/mhc/mhc_coeff", "norm/mhc/mhc_pre", "norm/mhc/mhc_post",
            "mtp", "attn/mla"} <= segments
    # the module's ops keep a vocabulary name innermost (what is left without
    # one is the slicing of its stacked leaves, as in the stack itself)
    assert {"attn", "norm", "mlp", "embed", "final_norm", "moe/experts", "moe/router"} <= under_mtp
    _, metrics = lowered.compile()(state, batch)
    (_, (main, mtp)), _ = setup.program()
    assert float(metrics["mtp_loss"]) == pytest.approx(float(mtp), abs=1e-5)
    assert float(metrics["loss"]) == pytest.approx(float(main + 0.3 * mtp), abs=1e-5)
    assert 0 < float(metrics["mhc_res_row_err"]) < 1e-3
    _, aux = setup.forward()
    assert int(metrics["held_expert_rows"]) == int(aux.expert_counts[:, 4:8].sum()) > 0


def test_serving_refuses_the_family(setup):
    from automodel_tpu.auto_model import AutoModel
    from automodel_tpu.generation import kv_cache
    from automodel_tpu.generation.engine import GenerationUnsupported
    from automodel_tpu.serving.engine import ServingEngine

    model, params = setup.model, setup.params
    assert kv_cache.layout_of(model) is None
    with pytest.raises(GenerationUnsupported, match="states no cache layout"):
        ServingEngine(AutoModel(model=model, params=params, adapter=None, mesh_ctx=None))


def test_bfloat16_is_far_outside_the_tolerances(setup):
    """What makes the limits above tight: the same weights through the same
    path with bfloat16 compute miss the float32 reference by orders more."""
    bf16 = BackendConfig(attn="sdpa", experts="ragged", param_dtype="float32",
                         compute_dtype="bfloat16", remat="none")
    model, _ = resolve_architecture(HF)(HF, bf16)
    total, _, _ = jax.jit(lambda p, b: _program_losses(model, p, b))(
        setup.params, {"input_ids": setup.ids, "labels": setup.labels})
    l_main, l_mtp, *_ = setup.reference()
    assert abs(float(total) - float(l_main + 0.3 * l_mtp)) > 20 * LOSS_TOL


def test_trains_from_a_yaml_through_the_normal_recipe(tmp_path, monkeypatch):
    """``automodel pretrain llm -c <yaml>``'s path: the YAML, ``from_config``
    by the registered architecture, recipes/train_ft.py; nothing else drives it."""
    from automodel_tpu.config.loader import load_yaml_config
    from automodel_tpu.recipes.train_ft import main

    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices(backend="cpu")[:1])
    cfg = load_yaml_config(ROOT / "examples" / "llm_pretrain" / "xing4_tiny_cpu.yaml")
    # the file's third layer repeats its second's kind (experts): without it the
    # step (an unrolled stack under `remat: full`, 20 Sinkhorn rounds a
    # sublayer) compiles a quarter faster, and every assertion below still has
    # a dense layer, an expert layer and the module to read
    cfg["model"]["hf_config"]["num_hidden_layers"] = 2
    cfg["output_dir"] = str(tmp_path / "run")
    cfg["logging"] = {"metrics_path": str(tmp_path / "metrics.jsonl")}
    last = main(cfg)
    assert last["step"] == 12 and np.isfinite(last["loss"])
    rows = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    losses = [r["loss"] for r in rows if "loss" in r]
    # random tokens: what can be learned is that no token is likelier than another
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses
    mtp = [r["mtp_loss"] for r in rows if "mtp_loss" in r]
    # logged beside the loss, a number of its own: loss = main + 0.3 x the module's
    assert len(mtp) == len(losses) and all(0 < 0.3 * m < l for m, l in zip(mtp, losses))
    assert all(0 <= r["mhc_res_row_err"] < 1e-2 for r in rows if "mhc_res_row_err" in r)
    held = [r["held_expert_rows"] for r in rows if "held_expert_rows" in r]
    # 2 x 96 tokens x 4 picks x 2 expert layers (the module's among them), a quarter of 16 held
    assert held and all(0 < h < 2 * 96 * 4 * 2 for h in held)
