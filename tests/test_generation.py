"""Generation subsystem: KV-cache prefill/decode parity, sampling, engine,
CLI, benchmark-leg degradation, report schema. All CPU-fast, tier-1.

Parity is the ground truth: prefill + token-at-a-time cached decode must
reproduce the FULL no-cache forward — logits within fp32 tolerance at every
decode step, greedy tokens exactly — for the dense llama family, gpt2
(learned positions, no rope), qwen3_moe (the MoE decode path, including a
dense-prefix layer), and the sliding-window ring cache past wraparound.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.generation import kv_cache
from automodel_tpu.generation.engine import (
    GenerationConfig,
    GenerationEngine,
    GenerationUnsupported,
)
from automodel_tpu.generation.loop import build_decode_fn, build_prefill_fn
from automodel_tpu.generation.sampling import SamplingConfig, sample
from automodel_tpu.models.common.config import BackendConfig, TransformerConfig

FP32 = BackendConfig(attn="sdpa", param_dtype="float32", compute_dtype="float32")
GREEDY = SamplingConfig(temperature=0.0)


def test_generation_suite_runs_on_cpu():
    """Tier-1 contract: this whole module must run CPU-only (the conftest
    pins jax_platforms=cpu; nothing here may escape to an accelerator)."""
    assert jax.default_backend() == "cpu"
    assert all(d.platform == "cpu" for d in jax.devices())


# -- model zoo ----------------------------------------------------------------


def _tiny_llama(**over):
    kw = dict(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=3,
        num_heads=4, num_kv_heads=2, head_dim=8,
    )
    kw.update(over)
    cfg = TransformerConfig(**kw)
    from automodel_tpu.models.llama import LlamaForCausalLM

    model = LlamaForCausalLM(cfg, FP32)
    return model, model.init(jax.random.key(0))


def _tiny_gpt2():
    from automodel_tpu.models.gpt2.model import GPT2Config, GPT2ForCausalLM

    cfg = GPT2Config(vocab_size=96, n_positions=64, hidden_size=32, num_layers=2, num_heads=4)
    model = GPT2ForCausalLM(cfg, FP32)
    return model, model.init(jax.random.key(1))


def _tiny_moe():
    from automodel_tpu.models.qwen3_moe import MoEForCausalLM, MoETransformerConfig

    hf = {
        "architectures": ["Qwen3MoeForCausalLM"], "model_type": "qwen3_moe",
        "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 8, "num_experts_per_tok": 2,
        "max_position_embeddings": 256, "tie_word_embeddings": False,
        # one dense-prefix layer: the cache must split across both stacks
        "first_k_dense_replace": 1,
    }
    cfg = MoETransformerConfig.from_hf(hf)
    model = MoEForCausalLM(
        cfg,
        BackendConfig(
            attn="sdpa", experts="dense",
            param_dtype="float32", compute_dtype="float32",
        ),
    )
    return model, model.init(jax.random.key(2))


def _primary(out):
    return out[0] if isinstance(out, tuple) else out


def _full_logits(model, params, seq):
    # one program a length (op by op it is a program a primitive a length)
    logits = jax.jit(lambda p, i: _primary(model(p, i)))(params, jnp.asarray([seq]))
    return np.asarray(logits[0], np.float32)


def _cached_stepwise_logits(model, params, prompt, n_steps, capacity=None, window=None):
    """Drive the cache primitives directly: prefill the prompt, then greedy
    decode n_steps, capturing each step's logits. → (step_logits, tokens)."""
    mcfg = model.config
    S = len(prompt)
    capacity = capacity or (S + n_steps)
    cache = kv_cache.init_cache(
        mcfg.num_layers, 1, capacity, mcfg.num_kv_heads, mcfg.head_dim,
        dtype=jnp.float32, window=window,
    )
    lengths = jnp.asarray([S], jnp.int32)
    prefill = build_prefill_fn(lambda p, i, **kw: model(p, i, **kw))
    last, cache = prefill(params, jnp.asarray([prompt], jnp.int32), lengths, cache)
    step_logits = [np.asarray(last[0], np.float32)]
    tok = int(jnp.argmax(last[0]))
    tokens = [tok]
    @jax.jit
    def decode(params, tok, cache):
        kvc, ctx = kv_cache.decode_ctx(cache)
        primary, cache = model(params, tok, position_ids=ctx.q_pos[:, None], cache=(kvc, ctx))
        return _primary(primary), cache

    for _ in range(n_steps - 1):
        logits, cache = decode(params, jnp.asarray([[tok]], jnp.int32), cache)
        step_logits.append(np.asarray(logits[0, -1], np.float32))
        tok = int(jnp.argmax(logits[0, -1]))
        tokens.append(tok)
    return step_logits, tokens


def _assert_stepwise_parity(model, params, prompt, n_steps, window=None, capacity=None, atol=2e-4):
    got_logits, got_tokens = _cached_stepwise_logits(
        model, params, prompt, n_steps, capacity=capacity, window=window
    )
    seq = list(prompt)
    for i in range(n_steps):
        ref = _full_logits(model, params, seq)[-1]
        np.testing.assert_allclose(got_logits[i], ref, atol=atol, rtol=2e-3)
        ref_tok = int(np.argmax(ref))
        assert got_tokens[i] == ref_tok, f"step {i}: {got_tokens[i]} != {ref_tok}"
        seq.append(ref_tok)


# -- prefill/decode parity ----------------------------------------------------


def test_llama_prefill_decode_logits_parity():
    model, params = _tiny_llama()
    _assert_stepwise_parity(model, params, [1, 2, 3, 4, 5], n_steps=6)


def test_gpt2_prefill_decode_logits_parity():
    model, params = _tiny_gpt2()
    _assert_stepwise_parity(model, params, [3, 4, 5, 6], n_steps=5)


def test_qwen3_moe_prefill_decode_logits_parity():
    model, params = _tiny_moe()
    _assert_stepwise_parity(model, params, [7, 8, 9, 10], n_steps=5)


def test_sliding_window_ring_cache_wraparound():
    """Ring layout: capacity == window < prompt + new tokens, so prefill
    already wraps and decode overwrites expired slots; logits must still
    match the full windowed forward at every step."""
    model, params = _tiny_llama(sliding_window=4, num_layers=2)
    # prompt (6) > window (4): prefill wraps; 8 decode steps wrap again
    _assert_stepwise_parity(
        model, params, [1, 2, 3, 4, 5, 6], n_steps=8, window=4, capacity=4
    )


def test_ring_rejects_ragged_wrapping_batch():
    """A ragged batch whose padded prompt wraps the ring would silently
    lose short slots' in-window history — the engine must refuse it."""
    model, params = _tiny_llama(sliding_window=4, num_layers=2)
    from automodel_tpu.auto_model import AutoModel

    auto = AutoModel(model=model, params=params, adapter=None, mesh_ctx=None)
    eng = GenerationEngine(
        auto, GenerationConfig(max_new_tokens=4, greedy=True, pad_to_multiple=1)
    )
    with pytest.raises(ValueError, match="ring"):
        eng.generate_ids([[1, 2, 3, 4, 5, 6], [7, 8]])
    # equal-length wrapping batches and ragged window-fitting ones are fine
    assert eng.generate_ids([[1, 2, 3, 4, 5, 6], [9, 8, 7, 6, 5, 4]])["gen_tokens"] == 8
    assert eng.generate_ids([[1, 2, 3], [7, 8]])["gen_tokens"] == 8


def test_decode_loop_matches_full_forward_greedy_batched():
    """The jitted while_loop engine path on RAGGED slots (different prompt
    lengths in one batch) reproduces per-slot full-forward greedy decode."""
    model, params = _tiny_llama()
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]
    from automodel_tpu.auto_model import AutoModel

    auto = AutoModel(model=model, params=params, adapter=None, mesh_ctx=None)
    eng = GenerationEngine(
        auto, GenerationConfig(max_new_tokens=6, greedy=True, pad_to_multiple=1)
    )
    out = eng.generate_ids(prompts)
    for b, prompt in enumerate(prompts):
        seq = list(prompt)
        for _ in range(6):
            seq.append(int(np.argmax(_full_logits(model, params, seq)[-1])))
        assert out["tokens"][b] == seq[len(prompt):]
    assert out["gen_tokens"] == 12
    assert out["prefill_tokens"] == 8
    assert out["ttft_s"] > 0 and out["decode_tps"] > 0
    assert out["cache_bytes"] > 0


def test_stop_token_early_exit():
    model, params = _tiny_llama()
    # discover what greedy emits at step 2, then declare it the stop token
    _, toks = _cached_stepwise_logits(model, params, [1, 2, 3], n_steps=4)
    eos = toks[1]
    apply = lambda p, i, **kw: model(p, i, **kw)
    decode = build_decode_fn(apply, GREEDY, 16, eos_ids=(eos,), pad_id=0)
    prefill = build_prefill_fn(apply)
    cache = kv_cache.init_cache(3, 1, 32, 2, 8, jnp.float32)
    last, cache = prefill(
        params, jnp.asarray([[1, 2, 3]], jnp.int32), jnp.asarray([3], jnp.int32), cache
    )
    first = sample(last, jax.random.key(0), GREEDY)
    res, _ = decode(params, cache, first, jax.random.key(0))
    res = jax.device_get(res)
    # the eos is INCLUDED, everything after is pad, and the while_loop
    # exited early: exactly ONE body iteration ran (first token from
    # prefill, second token = eos), observable via the step counter
    assert res["n_generated"][0] == 2
    assert res["tokens"][0][1] == eos
    assert all(t == 0 for t in res["tokens"][0][2:])
    assert int(res["steps"]) == 1


# -- sampling -----------------------------------------------------------------


def test_sampling_greedy_is_argmax():
    logits = jnp.asarray([[0.1, 3.0, -1.0, 0.5], [2.0, 0.0, 5.0, 1.0]])
    out = sample(logits, jax.random.key(0), SamplingConfig(temperature=0.0))
    assert out.tolist() == [1, 2]


def test_sampling_top_k_restricts_support():
    logits = jnp.asarray([[5.0, 4.0, -10.0, -10.0]] * 64)
    cfg = SamplingConfig(temperature=1.0, top_k=2)
    out = sample(logits, jax.random.key(1), cfg)
    assert set(np.asarray(out).tolist()) <= {0, 1}


def test_sampling_top_p_restricts_support():
    # p(0)≈0.72, p(1)≈0.26: top_p=0.9 keeps {0,1}, cuts {2,3}
    logits = jnp.asarray([[3.0, 2.0, -1.0, -2.0]] * 128)
    cfg = SamplingConfig(temperature=1.0, top_p=0.9)
    out = sample(logits, jax.random.key(2), cfg)
    assert set(np.asarray(out).tolist()) <= {0, 1}


def test_sampling_deterministic_and_key_sensitive():
    logits = jax.random.normal(jax.random.key(3), (4, 32))
    cfg = SamplingConfig(temperature=0.8, top_k=8)
    a = sample(logits, jax.random.key(5), cfg)
    b = sample(logits, jax.random.key(5), cfg)
    c = sample(logits, jax.random.key(6), cfg)
    assert a.tolist() == b.tolist()
    assert a.tolist() != c.tolist()


def test_sampling_config_validation():
    with pytest.raises(ValueError):
        SamplingConfig(top_k=0)
    with pytest.raises(ValueError):
        SamplingConfig(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingConfig(top_p=1.5)


# -- per-host sampling RNG (training/rng.py) ----------------------------------


def test_sampling_key_per_host_streams():
    from automodel_tpu.training.rng import sampling_key

    k_h0 = sampling_key(42, host_index=0)
    k_h1 = sampling_key(42, host_index=1)
    # distinct hosts → distinct streams (multi-host generation must not
    # sample identical tokens on every host)
    assert not np.array_equal(
        jax.random.key_data(k_h0), jax.random.key_data(k_h1)
    )
    # deterministic per (seed, host)
    assert np.array_equal(
        jax.random.key_data(k_h0),
        jax.random.key_data(sampling_key(42, host_index=0)),
    )
    # decode-step fold-in changes the stream, deterministically
    s3 = sampling_key(42, step=3, host_index=0)
    assert not np.array_equal(jax.random.key_data(k_h0), jax.random.key_data(s3))
    assert np.array_equal(
        jax.random.key_data(s3),
        jax.random.key_data(sampling_key(42, step=3, host_index=0)),
    )
    # default host index = jax.process_index() (single-process: 0)
    assert np.array_equal(
        jax.random.key_data(sampling_key(42)), jax.random.key_data(k_h0)
    )
    # accepts an existing key and a traced step (fold_in inside jit)
    jitted = jax.jit(lambda k, i: sampling_key(k, step=i, host_index=0))
    jitted(k_h0, jnp.int32(1))


# -- engine / cache -----------------------------------------------------------


def test_engine_rejects_cacheless_model():
    class NoCacheModel:
        config = None

    class FakeAuto:
        model = NoCacheModel()
        params = None
        mesh_ctx = None
        constrain = staticmethod(lambda x, s: x)

    with pytest.raises(GenerationUnsupported):
        GenerationEngine(FakeAuto(), GenerationConfig())


def test_engine_context_limit():
    model, params = _tiny_llama(max_position_embeddings=16)
    from automodel_tpu.auto_model import AutoModel

    auto = AutoModel(model=model, params=params, adapter=None, mesh_ctx=None)
    eng = GenerationEngine(auto, GenerationConfig(max_new_tokens=20, greedy=True))
    with pytest.raises(ValueError, match="context limit"):
        eng.generate_ids([[1] * 8])


def test_cache_nbytes_and_census_visibility():
    """Cache arrays are ordinary live jax arrays, so the telemetry census
    (jax.live_arrays groups) sees them; nbytes reports the logical size."""
    from automodel_tpu.telemetry.memory import live_array_census

    cache = kv_cache.init_cache(2, 1, 16, 2, 8, jnp.float32)
    expect = 2 * (2 * 1 * 16 * 2 * 8 * 4)  # k+v fp32
    assert cache.nbytes >= expect
    census = live_array_census(top_k=64)
    shapes = {tuple(e["shape"]) for e in census["top"]}
    assert (2, 1, 16, 2, 8) in shapes


def test_engine_on_mesh(devices8):
    """Sharded path: engine over a from_config model on an 8-device CPU
    mesh; cache placement drops non-divisible axes instead of crashing."""
    from automodel_tpu import auto_model
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh

    ctx = build_mesh(MeshConfig(dp_shard=4, tp=2), devices=devices8)
    hf = {
        "architectures": ["LlamaForCausalLM"], "model_type": "llama",
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8,
        "max_position_embeddings": 128,
    }
    auto = auto_model.from_config(
        hf, ctx,
        {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"},
    )
    eng = GenerationEngine(auto, GenerationConfig(max_new_tokens=4, greedy=True))
    out = eng.generate_ids([[1, 2, 3, 4]] * 4)
    assert len(out["tokens"]) == 4
    assert all(len(t) == 4 for t in out["tokens"])
    # all slots identical prompts → identical greedy completions
    assert out["tokens"][0] == out["tokens"][1]


# -- CLI ----------------------------------------------------------------------


def _tiny_cli_cfg(**gen_over):
    from automodel_tpu.config.loader import ConfigNode

    return ConfigNode(
        {
            "seed": 0,
            "model": {
                "hf_config": {
                    "architectures": ["LlamaForCausalLM"],
                    "model_type": "llama",
                    "vocab_size": 64, "hidden_size": 32,
                    "intermediate_size": 64, "num_hidden_layers": 2,
                    "num_attention_heads": 4, "num_key_value_heads": 2,
                    "head_dim": 8, "max_position_embeddings": 128,
                },
                "backend": {
                    "attn": "sdpa",
                    "param_dtype": "float32",
                    "compute_dtype": "float32",
                },
            },
            "distributed": {"dp_shard": 1, "tp": 1},
            "generation": {"max_new_tokens": 5, "greedy": True, **gen_over},
        }
    )


def test_cli_generate_end_to_end(capsys, monkeypatch, cpu_devices):
    """`automodel_tpu generate` produces text end-to-end on CPU from a tiny
    from-config llama (token-id mode: no tokenizer configured)."""
    monkeypatch.setattr(jax, "devices", lambda *a: cpu_devices[:1])
    cfg = _tiny_cli_cfg()
    cfg.set_by_path("prompt", "1 2 3 4")
    from automodel_tpu.generation.engine import main

    rc = main(cfg)
    captured = capsys.readouterr().out
    assert rc == 0
    assert "completion:" in captured
    completion = [
        l.split("completion:", 1)[1].strip()
        for l in captured.splitlines()
        if l.startswith("completion:")
    ][0]
    assert len(completion.split()) == 5  # 5 greedy tokens as text
    stats = json.loads(
        [l for l in captured.splitlines() if l.startswith("{")][-1]
    )
    assert stats["event"] == "generation"
    assert stats["gen_tokens"] == 5 and stats["ttft_s"] > 0


def test_cli_generate_prompt_ids_and_missing_prompt(capsys, monkeypatch, cpu_devices):
    monkeypatch.setattr(jax, "devices", lambda *a: cpu_devices[:1])
    from automodel_tpu.generation.engine import main

    rc = main(_tiny_cli_cfg(prompt_ids=[[1, 2, 3], [4, 5, 6, 7]]))
    assert rc == 0
    assert capsys.readouterr().out.count("completion:") == 2
    rc = main(_tiny_cli_cfg())
    assert rc == 2  # no prompt anywhere → usage error, not a crash


def test_cli_app_routes_generate(tmp_path, monkeypatch, cpu_devices):
    import yaml

    monkeypatch.setattr(jax, "devices", lambda *a: cpu_devices[:1])
    cfg_path = tmp_path / "gen.yaml"
    cfg_path.write_text(yaml.safe_dump(_tiny_cli_cfg().to_dict()))
    from automodel_tpu.cli.app import main as app_main

    rc = app_main(["generate", "-c", str(cfg_path), "--prompt", "2 3 4"])
    assert rc == 0


# -- report schema -------------------------------------------------------------


def test_report_accepts_generation_keys(tmp_path):
    """ttft_s / decode_tps / gen_* ride the JSONL schema: numeric values
    lint clean, null-without-marker is still flagged."""
    from automodel_tpu.telemetry.report import lint_metrics_jsonl, summarize_metrics

    p = tmp_path / "m.jsonl"
    p.write_text(
        "\n".join(
            [
                json.dumps({"step": 1, "loss": 1.0, "ts": 1.0}),
                json.dumps(
                    {
                        "event": "generation", "step": 1, "ts": 2.0,
                        "ttft_s": 0.5, "decode_tps": 123.4,
                        "gen_tokens": 32, "gen_cache_bytes": 4096,
                        "gen_samples": [{"prompt": "1 2", "completion": "3"}],
                    }
                ),
            ]
        )
        + "\n"
    )
    records, problems = lint_metrics_jsonl(str(p))
    assert problems == []
    summary = summarize_metrics(records)
    assert summary["generation_records"] == 1
    assert summary["decode_tps_mean"] == pytest.approx(123.4)
    # null without marker is still a schema problem
    p.write_text(json.dumps({"step": 1, "ts": 1.0, "decode_tps": None}) + "\n")
    _, problems = lint_metrics_jsonl(str(p))
    assert any("decode_tps" in pr for pr in problems)


# -- train_ft in-training eval generation -------------------------------------


def test_train_ft_logs_generation_at_validation(tmp_path, devices8, monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: devices8)
    from automodel_tpu.config.loader import ConfigNode
    from automodel_tpu.recipes.train_ft import main

    cfg = ConfigNode(
        {
            "seed": 7,
            "model": {
                "hf_config": {
                    "architectures": ["LlamaForCausalLM"],
                    "model_type": "llama",
                    "vocab_size": 128, "hidden_size": 64,
                    "intermediate_size": 128, "num_hidden_layers": 2,
                    "num_attention_heads": 4, "num_key_value_heads": 2,
                    "max_position_embeddings": 128,
                },
                "backend": {
                    "attn": "sdpa",
                    "param_dtype": "float32",
                    "compute_dtype": "float32",
                },
            },
            "distributed": {"dp_shard": 4, "tp": 2},
            "dataset": {
                "_target_": "automodel_tpu.data.sft.MockSFTDataset",
                "vocab_size": 128, "seq_length": 32, "num_samples": 32,
            },
            "dataloader": {"global_batch_size": 8},
            "step_scheduler": {
                "grad_acc_steps": 1, "num_epochs": 1, "max_steps": 4,
                "val_every_steps": 2,
            },
            "optimizer": {"name": "adamw", "lr": 1e-3},
            "loss_fn": {"name": "masked_ce"},
            "logging": {"metrics_path": str(tmp_path / "metrics.jsonl")},
            "generation": {
                "max_new_tokens": 4,
                "greedy": True,
                "prompt_ids": [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12], [13, 14, 15, 16]],
            },
        }
    )
    main(cfg)
    lines = [
        json.loads(l)
        for l in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    gens = [l for l in lines if l.get("event") == "generation"]
    assert len(gens) >= 2  # val_every_steps=2, max_steps=4
    g = gens[0]
    assert len(g["gen_samples"]) == 4
    assert all(len(s["completion"].split()) == 4 for s in g["gen_samples"])
    assert g["ttft_s"] > 0 and g["decode_tps"] > 0 and g["gen_tokens"] == 16
    # the linter accepts the whole file
    from automodel_tpu.telemetry.report import lint_metrics_jsonl

    _, problems = lint_metrics_jsonl(str(tmp_path / "metrics.jsonl"))
    assert problems == []
