"""Benchmark recipe + timers + FLOPs utils."""

import json

import numpy as np
import pytest

from automodel_tpu.training.timers import Timers
from automodel_tpu.utils.flops_utils import (
    calculate_mfu,
    dense_transformer_flops_per_token,
)


def test_timers():
    t = Timers()
    t("a").start()
    dt = t("a").stop()
    assert dt >= 0 and t.summary()["a"]["count"] == 1


def test_dense_flops_sane():
    # ~6N per token rule of thumb for short seq: llama-8b-ish config
    fpt = dense_transformer_flops_per_token(
        hidden_size=4096, num_layers=32, intermediate_size=14336,
        vocab_size=128256, seq_len=1, num_heads=32, num_kv_heads=8, head_dim=128,
    )
    n_params = 8.0e9
    assert 0.8 * 6 * n_params < fpt < 1.3 * 6 * n_params
    assert 0 < calculate_mfu(10_000, fpt, peak_tflops=459.0) < 1.5


@pytest.mark.parametrize("with_serving", [False, True])
def test_benchmark_recipe_cli(tmp_path, with_serving):
    from automodel_tpu.cli.app import main as cli_main

    recipe = {
        "seed": 1,
        "model": {
            "hf_config": {
                "architectures": ["LlamaForCausalLM"],
                "model_type": "llama",
                "vocab_size": 128,
                "hidden_size": 64,
                "intermediate_size": 128,
                "num_hidden_layers": 2,
                "num_attention_heads": 4,
                "num_key_value_heads": 2,
                "head_dim": 16,
            },
            "backend": {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"},
        },
        "distributed": {"dp_shard": -1},
        "dataset": {
            "_target_": "automodel_tpu.data.sft.MockSFTDataset",
            "num_samples": 64,
            "seq_length": 16,
            "vocab_size": 128,
        },
        "dataloader": {"global_batch_size": 8},
        "step_scheduler": {"max_steps": 100},
        "optimizer": {"name": "adamw", "lr": 1e-3},
        "benchmark": {
            "warmup_steps": 1,
            "measure_steps": 2,
            "output_json": str(tmp_path / "bench.json"),
        },
    }
    if with_serving:
        # a recipe that still carries serving / generation sections runs the
        # TRAINING benchmark: the recipe has no other leg
        recipe["serving"] = {"slots": 2, "num_blocks": 32, "max_seq_len": 64}
        recipe["generation"] = {"max_new_tokens": 4, "greedy": True}
    import yaml

    cfg_path = tmp_path / "bench.yaml"
    cfg_path.write_text(yaml.safe_dump(recipe))
    rc = cli_main(["benchmark", "llm", "-c", str(cfg_path)])
    assert rc == 0
    result = json.loads((tmp_path / "bench.json").read_text())
    assert result["tokens_per_second"] > 0
    assert np.isfinite(result["loss"])
    assert result["timers"]["step"]["count"] == 2
    assert not [k for k in result if k.startswith(("serve_", "gen_"))]


def _run_from_repo_root(script: str):
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    return subprocess.run(
        [sys.executable, str(root / script)], cwd=root,
        capture_output=True, text=True, timeout=120,
    )


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """No chip is a fast non-zero exit that prints no result — never a CPU
    run written under a device metric's name (the suite pins
    JAX_PLATFORMS=cpu, which the child inherits)."""
    r = _run_from_repo_root("chip_smoke.py")
    assert r.returncode != 0
    assert "no TPU found" in r.stderr, r.stderr[-500:]
    assert r.stdout.strip() == "", r.stdout[-500:]


def test_compile_cache_is_placed_from_outside_when_the_env_says_so(monkeypatch):
    import jax

    from automodel_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before  # no path set in code
    # the in-checkout default is one fixed, git-ignored directory
    assert compile_cache.DEFAULT_DIR.name == ".jax_compile_cache"
    assert compile_cache.DEFAULT_DIR.parent.joinpath("chip_smoke.py").exists()
