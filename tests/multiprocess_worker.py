"""Worker for the 2-process jax.distributed functional test
(test_multiprocess.py). Each process contributes its local CPU devices to a
GLOBAL mesh, runs the full stack — initialize_distributed → build_mesh →
auto_model.from_config → jitted train steps — and prints the loss sequence.

Reference equivalent: the 2-GPU torchrun functional tests
(tests/functional_tests/context_parallel/L2_CP_*.sh), which are the
reference's only real multi-process coverage."""

import json
import os
import sys

os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={os.environ.get('LOCAL_DEVICES', '2')}"
)
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

from automodel_tpu import auto_model
from automodel_tpu.data.loader import place_batch
from automodel_tpu.optim.builders import build_optimizer
from automodel_tpu.parallel.mesh import MeshConfig, build_mesh, initialize_distributed
from automodel_tpu.training.train_state import TrainState
from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss


def main() -> None:
    initialize_distributed()  # env-driven (JAX_COORDINATOR_ADDRESS/...)
    devices = [d for d in jax.devices("cpu")]
    ctx = build_mesh(
        MeshConfig(dp_shard=int(os.environ.get("DP", "4"))), devices=devices
    )
    hf = {
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "vocab_size": 128,
        "hidden_size": 32,
        "intermediate_size": 64,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 8,
        "tie_word_embeddings": False,
    }
    backend = {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"}
    auto = auto_model.from_config(hf, ctx, backend, seed=0)
    loss_fn = make_causal_lm_loss(auto.model, loss="masked_ce", constrain=auto.constrain)
    opt = build_optimizer(name="adamw", lr=3e-3)
    state = TrainState.create(auto.params, jax.jit(opt.init)(auto.params))
    step = build_train_step(loss_fn, opt)

    rng = np.random.default_rng(0)  # same data on every process
    ids = np.asarray(rng.integers(0, 128, (1, 4, 32)), np.int32)
    batch = place_batch(ctx, {"input_ids": ids, "labels": ids})
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    print("LOSSES " + json.dumps(losses), flush=True)


if __name__ == "__main__":
    main()
