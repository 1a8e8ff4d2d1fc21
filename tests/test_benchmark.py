"""Benchmark recipe + timers + FLOPs utils."""

import json

import numpy as np

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


def test_bench_classify_env_failure():
    """bench.py environment-failure detection: a libtpu client/terminal
    version mismatch in the probe's stderr is a NAMED environment failure;
    a dropped connection and a plain no-TPU host are not (an environment
    failure must report as such, never as 0.0-valued legs)."""
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "bench_module", Path(__file__).resolve().parent.parent / "bench.py"
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    mismatch = (
        "RuntimeError: Invalid argument: The libtpu version mismatch: "
        "client version 0.0.17 is incompatible with terminal version 0.0.21\n"
    )
    reason = bench.classify_env_failure(mismatch)
    assert reason is not None and "libtpu" in reason
    assert "0.0.17" in reason  # quotes the offending line

    assert bench.classify_env_failure(
        "TPU driver version skew detected\n"
    ) is not None
    assert bench.classify_env_failure(
        "PJRT API version 0.40 is older than the framework's\n"
    ) is not None

    # NOT environment failures: a dropped connection / garden-variety no-TPU
    assert bench.classify_env_failure("") is None
    assert bench.classify_env_failure("Connection reset by peer") is None
    assert bench.classify_env_failure(
        "RuntimeError: Backend 'tpu' is not in the list of known backends"
    ) is None


def test_bench_oom_dump_records_leg_and_first_oom(tmp_path, monkeypatch):
    """bench_oom_<leg>.json carries the leg name, a first_oom flag, and the
    live-buffer census (the first dump sees the pristine failure state;
    later dumps are cascade)."""
    import importlib.util
    import os
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "bench_module2", Path(__file__).resolve().parent.parent / "bench.py"
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.chdir(tmp_path)
    assert bench._first_oom_pending is True
    p1 = bench._oom_memory_dump("dense_8b")
    p2 = bench._oom_memory_dump("moe_ragged")
    d1 = json.loads(Path(p1).read_text())
    d2 = json.loads(Path(p2).read_text())
    assert d1["leg"] == "dense_8b" and d1["first_oom"] is True
    assert d2["leg"] == "moe_ragged" and d2["first_oom"] is False
    assert "census" in d1 and "devices" in d1  # live-buffer HBM census


def test_benchmark_recipe_cli(tmp_path):
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
    import yaml

    cfg_path = tmp_path / "bench.yaml"
    cfg_path.write_text(yaml.safe_dump(recipe))
    rc = cli_main(["benchmark", "llm", "-c", str(cfg_path)])
    assert rc == 0
    result = json.loads((tmp_path / "bench.json").read_text())
    assert result["tokens_per_second"] > 0
    assert np.isfinite(result["loss"])
    assert result["timers"]["step"]["count"] == 2


def _run_from_repo_root(script: str):
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    return subprocess.run(
        [sys.executable, str(root / script)], cwd=root,
        capture_output=True, text=True, timeout=120,
    )


def test_chip_smoke_and_bench_refuse_to_run_without_a_tpu():
    """No chip is a fast non-zero exit that prints no result — never a CPU
    run written under a device metric's name (the suite pins
    JAX_PLATFORMS=cpu, which the children inherit)."""
    for script in ("chip_smoke.py", "bench.py"):
        r = _run_from_repo_root(script)
        assert r.returncode != 0, script
        assert "no TPU found" in r.stderr, (script, r.stderr[-500:])
        assert r.stdout.strip() == "", (script, r.stdout[-500:])


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
