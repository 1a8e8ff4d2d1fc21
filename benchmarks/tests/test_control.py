"""The control of each kind of cell, at a size a test run can hold: the
reference put in the program's place one precision down (fp8 for bfloat16)
comes out NOT correct under the cell's own limits, and the sound program
comes out correct. And the harness, driven past its look for a chip with the
timed path broken underneath, reports ``correct: false``."""

import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import control, loader, serve, train
from benchmarks.harness import weights as W
from benchmarks.reference import moe_decoder as R

ROOT = Path(__file__).resolve().parents[2]


def rehearse_cell(workload):
    from benchmarks.run import rehearse_overrides

    return rehearse_overrides(loader.load_cell(loader.load_benchmark(), workload))


def test_train_control_fails_and_sound_passes(tmp_path):
    cell = rehearse_cell("train-30b-a3b")
    res = control.train_seed(cell, 5, tmp_path, "fp8")
    limits = cell["config"]["reference"]["limits"]
    key = next(k for k in res["control"] if k.startswith("small_grad_rel_diff"))
    assert max(v for k, v in res["control"].items() if k.startswith("small_grad_rel_diff")) > limits["small_grad_rel_diff"], key
    sound = {k.split("[")[0]: v for k, v in res["sound"].items()}
    assert sound["small_grad_rel_diff_worst_leaf"] < limits["small_grad_rel_diff"]
    assert sound["grad_norm_gap_worst_leaf"] < limits["grad_norm_gap"]
    assert sound["param_change_gap_worst_leaf"] < limits["param_change_gap"]


def test_serve_control_fails_and_bf16_passes_at_a_medium_size():
    spec = R.DecoderSpec(vocab_size=8192, hidden_size=256, num_layers=1, num_heads=8,
                         num_kv_heads=2, head_dim=32, num_experts=64, top_k=8, expert_width=128,
                         rms_eps=1e-6, rope_theta=5e6, rotary_dim=16, qk_norm="flat",
                         router="sigmoid_bias")
    bf = jnp.bfloat16
    s = lambda *shape, dt=bf: jax.ShapeDtypeStruct(shape, dt)
    D, E, I, V = 256, 64, 128, 8192
    tree = {"embed": {"embedding": s(V, D)}, "final_norm": {"scale": s(D)}, "lm_head": {"kernel": s(D, V)},
            "moe_layers": {"input_norm": {"scale": s(1, D)}, "post_attn_norm": {"scale": s(1, D)},
                           "attn": {"q_proj": {"kernel": s(1, D, 256)}, "k_proj": {"kernel": s(1, D, 64)},
                                    "v_proj": {"kernel": s(1, D, 64)}, "o_proj": {"kernel": s(1, 256, D)},
                                    "q_norm": {"scale": s(1, 256)}, "k_norm": {"scale": s(1, 64)}},
                           "moe": {"router": {"weight": s(1, D, E), "bias": s(1, E, dt=jnp.float32)},
                                   "experts": {"gate_up": s(1, E, D, 2 * I), "down": s(1, E, I, D)}}}}
    params = W.make(tree, 11, reference_layout=True)
    ids = jnp.asarray(np.random.default_rng(11).integers(3, V, size=256), jnp.int32)
    ref = R.rows_logits(params, ids, jnp.int32(0), spec, "f32", 256)
    limit = json.loads((ROOT / "benchmarks/configs/minimax-m2.serve-l1.json").read_text())[
        "reference"]["limits"]["served_token_gap_mean"]

    def mean_gap(precision):
        low = R.rows_logits(params, ids, jnp.int32(0), spec, precision, 256)
        tok = jnp.argmax(low, axis=-1)
        return float(jnp.mean(ref.max(-1) - jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]))

    assert mean_gap("bf16") < limit < mean_gap("fp8")


class _Args(types.SimpleNamespace):
    pass


def _ctx(cell, args, tmp_path):
    import benchmarks.run as run_module

    return run_module.Ctx(args, cell, tmp_path, {"platform": "cpu", "kind": "cpu", "count": 1})


def test_a_step_that_drops_part_of_the_batch_is_not_correct(tmp_path, monkeypatch):
    cell = rehearse_cell("train-30b-a3b")
    args = _Args(workload="train-30b-a3b", seed=7, seconds=0.5, trace=0)
    real = train.build_recipe

    def broken(cfg, seed):
        recipe = real(cfg, seed)
        place = recipe._place_group

        def drop_second_sequence(stacked):
            stacked = dict(stacked)
            labels = np.array(stacked["labels"])
            labels[:, 1:, :] = -100  # the timed path never learns from these rows
            stacked["labels"] = labels
            return place(stacked)

        recipe._place_group = drop_second_sequence
        return recipe

    monkeypatch.setattr(train, "build_recipe", broken)
    # the reference is fed what the dataloader produced, not what the broken step saw
    def step(self, keep_host_batch=False):
        r = self.recipe
        group = next(self.batches)
        stacked, n = r._prepare_group(group)
        host = {k: np.asarray(stacked[k]).copy() for k in ("input_ids", "labels")} if keep_host_batch else None
        batch = r._place_group(stacked)
        r.state, metrics = r.train_step(r.state, batch)
        return float(metrics["loss"]), n, host

    monkeypatch.setattr(train.Stepper, "step", step)
    result = train.run(cell, args, _ctx(cell, args, tmp_path))
    assert result["correct"] is False


@pytest.mark.parametrize("which", ["every_token", "one_token_of_one_request"])
def test_tokens_altered_where_they_are_produced_are_not_correct(tmp_path, monkeypatch, which):
    cell = rehearse_cell("serve-chat-minimax-m2")
    args = _Args(workload="serve-chat-minimax-m2", seed=7, seconds=3.0, trace=0)
    vocab = int(cell["config"]["vocab_size"])
    real = serve.build_engine

    def broken(cell_, seed):
        engine, abstract = real(cell_, seed)
        step = engine.step

        def altered():
            done = step()
            for rec in done:
                if which == "every_token":
                    rec["tokens"] = [(t + 1) % vocab for t in rec["tokens"]]
                elif rec["request_id"] == "w3":  # the mean forgives one token, the ceiling does not
                    rec["tokens"][1] = (rec["tokens"][1] + 1) % vocab
            return done

        engine.step = altered
        return engine, abstract

    monkeypatch.setattr(serve, "build_engine", broken)
    result = serve.run(cell, args, _ctx(cell, args, tmp_path))
    assert result["correct"] is False


def test_the_sound_harness_run_is_correct(tmp_path):
    cell = rehearse_cell("serve-chat-minimax-m2")
    args = _Args(workload="serve-chat-minimax-m2", seed=7, seconds=3.0, trace=0)
    assert serve.run(cell, args, _ctx(cell, args, tmp_path))["correct"] is True
