"""Headline benchmarks on one chip: dense LoRA SFT MFU + MoE pretrain MFU.

Mirrors the reference benchmark conditions (docs/performance-summary.md:66-72;
BenchmarkingRecipeForNextTokenPrediction, recipes/llm/benchmark.py:34): mock
data, fake balanced gate for MoE, no grad clipping in the MoE leg, warmup
excluded, MFU = achieved model FLOPs / device peak.

Baselines (BASELINE.md): Llama3-8B LoRA SFT 402 TFLOPs/s on H100 (989 peak)
= 40.6% MFU; GPT-OSS-20B MoE pretrain 279 TFLOPs/s = 28.2% MFU. The dense
model tries the 8B shape first and steps down (6B, 3B, 0.9B) on OOM — the
bench chip may be a 16GB v5e; the metric reports which shape ran.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
Without a TPU it prints none and exits non-zero: a CPU run has no place
under a device metric's name.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

DENSE_BASELINE_MFU = 402.0 / 989.0  # reference Llama3-8B LoRA SFT, H100
MOE_BASELINE_MFU = 279.0 / 989.0  # reference GPT-OSS-20B pretrain, 8xH100

# (label, hidden, inter, layers, heads, kv_heads)
DENSE_SHAPES = [
    ("8b", 4096, 14336, 32, 32, 8),
    ("6b", 4096, 14336, 24, 32, 8),
    ("3b", 3072, 8192, 26, 24, 8),
    ("0.9b", 2048, 5632, 16, 16, 8),
]


def _dense_hf(shape) -> dict:
    _, h, i, l, n, kv = shape
    return {
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "vocab_size": 32768,
        "hidden_size": h,
        "intermediate_size": i,
        "num_hidden_layers": l,
        "num_attention_heads": n,
        "num_key_value_heads": kv,
        "head_dim": 128,
        "rms_norm_eps": 1e-5,
        "max_position_embeddings": 8192,
        "rope_theta": 500000.0,
        "tie_word_embeddings": False,
    }


def _moe_hf() -> dict:
    """GPT-OSS fingerprint scaled to a single ~16GB chip (~1.1B total):
    every structural feature of the 20B baseline model — 32 experts top-4,
    swiglu_oai with interleaved gate_up and expert biases, attention sinks,
    attention bias, alternating sliding(128)/full layers, head_dim 64 —
    with hidden/layers shrunk to fit. MFU-vs-MFU against the reference's
    GPT-OSS-20B number keeps the comparison like-for-like (VERDICT r3 #3);
    windowed layers are counted at window length in the FLOPs basis exactly
    as the reference's gpt_oss_flops does (utils/flops_utils.py:652-697).

    Scaling choice (r5): wide-and-shallow (D=I=1536, 4 layers) rather than
    narrow-and-deep (D=1024, 12 layers — r4's shape, which no longer fits
    next to fp32 Adam moments and, at D=1024, runs the grouped matmuls well
    below their wide-shape rates). The 20B model itself is wide (D=I=2880),
    so width is the more faithful axis to keep; depth only re-runs the same
    per-layer compute pattern. (A round-5 builder A/B on the chip had
    D=1536/L=4 at 23.4% vs D=1024/L=10 at 18.1%; the record is gone, so
    read that as history, not as a number to plan from.)"""
    return {
        "architectures": ["GptOssForCausalLM"],
        "model_type": "gpt_oss",
        "vocab_size": 65536,
        "hidden_size": 1536,
        "intermediate_size": 1536,  # per-expert I (gpt-oss layout, I=D)
        "num_hidden_layers": 4,
        "num_attention_heads": 16,
        "num_key_value_heads": 4,
        "head_dim": 64,
        "num_local_experts": 32,
        "num_experts_per_tok": 4,
        "sliding_window": 128,
        "attention_bias": True,
        "rms_norm_eps": 1e-5,
        "rope_theta": 150000.0,
        "tie_word_embeddings": False,
    }


def _moe_backend(experts: str) -> dict:
    """Backend for the MoE leg — ONE definition shared with
    tools/bench_moe_only.py so kernel iteration measures the same config the
    published bench runs. Remat choice measured on chip (r5): selective ≥
    full_save_dispatch ≥ full for the fused kernel now that bf16
    single-microbatch grads freed the activation headroom."""
    return {
        "attn": "flash",
        "param_dtype": "bfloat16",
        "compute_dtype": "bfloat16",
        "remat": "selective" if experts == "ragged_fused" else "full",
        "fake_balanced_gate": True,
        "experts": experts,
    }


# (the old in-process `_reset_between_legs` buffer-delete/cache-clear dance
# is gone: every leg now runs in its own subprocess — see the "subprocess
# leg isolation" section below — so a cold chip per leg holds by
# construction, not by cleanup)

_first_oom_pending = True


def _oom_memory_dump(leg: str, extra: dict | None = None) -> str | None:
    """Force-dump allocator stats + the live-array census when a leg dies,
    BEFORE anything frees the buffers — the census names what filled the
    chip (the diagnostic an all-zero round of legs once lacked). With
    subprocess leg isolation every leg dies in a pristine process, so the
    census always reflects cause, never cascade; the ``first_oom`` flag is
    kept (first OOM of THIS process) for artifact compatibility. ``extra``
    merges additional evidence into the record — the worker attaches the
    profiling subsystem's cost summary (what the step program WOULD have
    computed/moved) beside what actually filled the chip. → dump path, or
    None if even the dump failed."""
    global _first_oom_pending
    try:
        from automodel_tpu.telemetry.memory import memory_snapshot

        path = f"bench_oom_{leg}.json"
        with open(path, "w") as f:
            json.dump(
                {
                    "leg": leg,
                    "first_oom": _first_oom_pending,
                    **memory_snapshot(top_k=12),
                    **(extra or {}),
                },
                f, indent=2, default=str,
            )
        _first_oom_pending = False
        print(f"[bench] memory census for failed {leg} leg → {path}",
              file=sys.stderr, flush=True)
        return path
    except Exception:
        return None


def _abstract_step_cost(hf: dict, backend: dict, batch: int, seq: int) -> dict:
    """Cost summary of the leg's train step traced ABSTRACTLY (eval_shape
    params + ShapeDtypeStruct batch — zero device memory, so it works in
    the post-OOM wreckage): measured FLOPs/bytes of the program the chip
    was asked to run. Attached to the first-OOM record so an exhausted leg
    reports what it was trying to compute, not just a null."""
    import jax

    from automodel_tpu.models.common.config import BackendConfig
    from automodel_tpu.models.registry import resolve_architecture
    from automodel_tpu.optim.builders import build_optimizer
    from automodel_tpu.telemetry.profiling import trace_cost
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    bk = BackendConfig(**backend) if isinstance(backend, dict) else backend
    model, _ = resolve_architecture(hf)(hf, bk)
    params = jax.eval_shape(model.init, jax.random.key(0))
    loss_fn = make_causal_lm_loss(model, loss="fused_linear_ce")
    optimizer = build_optimizer(
        name="adamw", lr=1e-4, betas=(0.9, 0.95), moments_dtype="param"
    )
    opt_state = jax.eval_shape(optimizer.init, params)
    state = TrainState.create(params, opt_state)
    ids = jax.ShapeDtypeStruct((1, batch, seq), jax.numpy.int32)
    step = build_train_step(loss_fn, optimizer)
    cost = trace_cost(
        step, state, {"input_ids": ids, "labels": ids}, program="train_step"
    )
    return cost.to_dict()


def _is_oom(exc: Exception) -> bool:
    s = str(exc)
    return (
        "RESOURCE_EXHAUSTED" in s
        or "Out of memory" in s
        or "out of memory" in s
    )


def _run(hf, backend, batch, seq, steps, ctx, lora=False, qlora=False):
    """→ (tok/s/chip, flops/token). Builds everything fresh per workload."""
    from automodel_tpu import auto_model
    from automodel_tpu.data.loader import place_batch
    from automodel_tpu.optim.builders import build_optimizer
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss
    from automodel_tpu.utils.flops_utils import flops_per_token_for_config

    if qlora:
        # the full-precision base (15.3GB bf16 at 8B) must never touch the
        # 16GB chip: init on HOST, NF4-pack there, ship only packed codes.
        # numpy fills the eval_shape skeleton — jax threefry on CPU takes
        # >6 min for 8B params, numpy ~30s
        from automodel_tpu.models.registry import resolve_architecture
        from automodel_tpu.models.common.config import BackendConfig

        bk = BackendConfig(**backend) if isinstance(backend, dict) else backend
        model, adapter = resolve_architecture(hf)(hf, bk)
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        nprng = np.random.default_rng(0)

        def fill(path, a):
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            dt = jax.numpy.dtype(a.dtype)
            if name.endswith("/scale"):  # norm scales init at one
                return np.ones(a.shape, dt)
            if name.endswith("/bias"):
                return np.zeros(a.shape, dt)
            v = nprng.standard_normal(a.shape, dtype=np.float32)
            v *= 1.0 / np.sqrt(max(a.shape[-1], 1))
            return v.astype(dt)

        host_params = jax.tree_util.tree_map_with_path(fill, shapes)
        auto = auto_model.AutoModel(
            model=model, params=host_params, adapter=adapter, mesh_ctx=ctx,
            hf_config=hf,
        )
    else:
        auto = auto_model.from_config(hf, ctx, backend, seed=0)
    loss_fn = make_causal_lm_loss(
        auto.model, loss="fused_linear_ce", constrain=auto.constrain
    )
    if lora or qlora:
        from automodel_tpu.parallel.plans import shard_params
        from automodel_tpu.peft import (
            PeftConfig,
            init_lora_params,
            lora_sharding_rules,
            make_lora_loss_fn,
        )

        pcfg = PeftConfig(target_modules=["*attn/[qkvo]_proj*", "*mlp*"], dim=16, alpha=32)
        trainable = init_lora_params(jax.random.key(1), auto.params, pcfg)
        trainable = shard_params(
            ctx, trainable, lora_sharding_rules(auto.model.sharding_rules, trainable)
        )
        base_tree = auto.params
        if qlora:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from automodel_tpu.quantization import QLoRAConfig, nf4_quantize_tree

            base_tree = nf4_quantize_tree(auto.params, QLoRAConfig(), ctx=ctx)
            auto.params = None  # free the host fp tree
            # unquantized leaves (embed/norms/biases) are still host arrays
            # (numpy or cpu-jax) after the host init — ship them; leave the
            # already-placed packed codes alone
            rep = NamedSharding(ctx.mesh, P())

            def ship(x):
                if isinstance(x, jax.Array) and (
                    next(iter(x.devices())).platform != "cpu"
                ):
                    return x
                return jax.device_put(jax.numpy.asarray(x), rep)

            base_tree = jax.tree.map(ship, base_tree)
        loss_fn = make_lora_loss_fn(
            loss_fn, base_tree, pcfg,
            graft_patterns=getattr(auto.model, "lora_graft_patterns", ()),
        )
    else:
        trainable = auto.params

    # moments_dtype='param': bf16 Adam moments. A documented bench-only
    # capacity concession — fp32 moments for the ~1.1B MoE fingerprint are
    # 8.3GB of state, which plus params/grads/activations exceeds the 16GB
    # chip. The training DEFAULT stays fp32 (optim/builders.py).
    optimizer = build_optimizer(
        name="adamw", lr=1e-4, betas=(0.9, 0.95), moments_dtype="param"
    )
    state = TrainState.create(trainable, jax.jit(optimizer.init)(trainable))
    train_step = build_train_step(loss_fn, optimizer)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, hf["vocab_size"], size=(1, batch, seq))
    b = place_batch(
        ctx,
        {"input_ids": np.asarray(ids, np.int32), "labels": np.asarray(ids, np.int32)},
    )
    # warmup (compile); fetching the loss is the barrier
    for _ in range(2):
        state, metrics = train_step(state, b)
    jax.device_get(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = train_step(state, b)
    loss = float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    assert loss == loss, "non-finite bench loss"

    tokens = steps * batch * seq
    tps_chip = tokens / dt / len(jax.devices())
    return tps_chip, flops_per_token_for_config(auto.model.config, seq)


# -- input-pipeline A/B (host overlap) ----------------------------------------


def _input_pipeline_ab(spec: dict) -> dict:
    """Sync vs prefetched input pipeline on the SAME tiny model + dataset,
    with an injected per-batch collate delay (fault_injection.slow_collate_ms)
    standing in for expensive tokenization/disk work. The sync loop pays the
    delay serially every step; the prefetch pipeline (data/prefetch.py —
    background collate workers + N-deep device prefetch) hides it. Both runs
    must produce bit-identical loss trajectories — the overlap moves WHERE
    host work happens, never WHAT the optimizer sees."""
    import jax

    from automodel_tpu import auto_model
    from automodel_tpu.data.collators import stack_microbatches
    from automodel_tpu.data.loader import DataLoader, place_batch
    from automodel_tpu.data.prefetch import (
        PrefetchConfig,
        PrefetchingLoader,
        PreparedBatch,
    )
    from automodel_tpu.data.sft import MockSFTDataset
    from automodel_tpu.optim.builders import build_optimizer
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.resilience.fault_injection import activate
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    steps = int(spec.get("steps", 10))
    warmup = 2
    collate_ms = float(spec.get("collate_ms", 50.0))
    depth = int(spec.get("depth", 4))
    workers = int(spec.get("workers", 4))
    # sized so the device step is SMALL next to the injected collate delay:
    # the leg measures pipeline overlap, not model compute, and the speedup
    # ceiling is (collate + step) / max(step, collate / workers)
    batch, seq = 4, 64
    hf = _dense_hf(("ab", 64, 176, 2, 4, 2))
    hf.update(vocab_size=512, head_dim=16)
    backend = {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"}
    ctx = build_mesh(MeshConfig(dp_shard=-1))

    def run(prefetch: bool) -> tuple[float, list[float]]:
        # fresh model/optimizer per arm from the same seed: identical
        # initial state, so trajectory equality is a real determinism check
        auto = auto_model.from_config(hf, ctx, backend, seed=0)
        loss_fn = make_causal_lm_loss(
            auto.model, loss="masked_ce", constrain=auto.constrain
        )
        optimizer = build_optimizer(name="adamw", lr=1e-3)
        state = TrainState.create(auto.params, jax.jit(optimizer.init)(auto.params))
        train_step = build_train_step(loss_fn, optimizer)
        ds = MockSFTDataset(
            vocab_size=hf["vocab_size"], seq_length=seq,
            num_samples=batch * (steps + warmup + depth + 4), seed=0,
        )
        loader = DataLoader(ds, global_batch_size=batch, shuffle=True, seed=0)
        if prefetch:
            loader = PrefetchingLoader(
                loader,
                PrefetchConfig(depth=depth, collate_workers=workers),
                prepare=lambda group: (stack_microbatches(group), 0),
                place=lambda host: place_batch(ctx, host),
                group_size=1,
            )
        it = iter(loader)
        losses: list[float] = []

        def one():
            nonlocal state
            item = next(it)
            b = (
                item.device
                if isinstance(item, PreparedBatch)
                else place_batch(ctx, stack_microbatches([item]))
            )
            state, m = train_step(state, b)
            return m

        for _ in range(warmup):  # compile outside the timed window
            m = one()
        jax.device_get(m["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            # per-step loss fetch is the honest barrier: the device finishes
            # each step inside the window, so sync pays collate+compute
            # serially while prefetch overlaps them
            losses.append(float(jax.device_get(one()["loss"])))
        dt = time.perf_counter() - t0
        if prefetch:
            loader.close()
        return steps / dt, losses

    activate({"slow_collate_ms": collate_ms})
    try:
        sync_sps, sync_losses = run(False)
        prefetch_sps, prefetch_losses = run(True)
    finally:
        activate(None)
    return {
        "sync_steps_per_s": sync_sps,
        "prefetch_steps_per_s": prefetch_sps,
        "speedup": prefetch_sps / sync_sps,
        "losses_equal": sync_losses == prefetch_losses,
        "collate_ms": collate_ms,
        "losses": sync_losses,
    }


def _input_pipeline_leg() -> dict:
    """→ the bench-result keys for the input-pipeline A/B sub-leg. Always a
    CPU subprocess (host overlap is a host property — the device type only
    scales the compute being overlapped, and the leg must not contend for
    the TPU with the MFU legs). Degrades to null + a recorded reason."""
    collate_ms = float(os.environ.get("BENCH_COLLATE_MS", 50.0))
    nulls = {
        "input_pipeline_speedup": None,
        "input_pipeline_sync_steps_per_s": None,
        "input_pipeline_prefetch_steps_per_s": None,
        "input_pipeline_collate_ms": collate_ms,
    }
    res = _run_leg(
        "input_pipeline",
        {
            "input_pipeline": True, "force_cpu": True, "steps": 10,
            "collate_ms": collate_ms, "depth": 4, "workers": 4,
        },
        timeout_s=float(os.environ.get("BENCH_INPUT_TIMEOUT_S", 900)),
    )
    if not res.get("ok"):
        return {**nulls, "input_pipeline_failure": str(res.get("error"))}
    if not res.get("losses_equal"):
        return {
            **nulls,
            "input_pipeline_failure": (
                "loss trajectories diverged between the sync and prefetched "
                "runs — the overlap changed WHAT was trained, not just when"
            ),
        }
    print(
        f"[bench] input-pipeline A/B @ {collate_ms:.0f}ms collate: "
        f"sync {res['sync_steps_per_s']:.2f} steps/s, prefetch "
        f"{res['prefetch_steps_per_s']:.2f} steps/s ({res['speedup']:.2f}x)",
        file=sys.stderr, flush=True,
    )
    return {
        "input_pipeline_speedup": round(res["speedup"], 3),
        "input_pipeline_sync_steps_per_s": round(res["sync_steps_per_s"], 3),
        "input_pipeline_prefetch_steps_per_s": round(res["prefetch_steps_per_s"], 3),
        "input_pipeline_collate_ms": collate_ms,
        "input_pipeline_failure": None,
    }


# stderr signatures of a broken TPU ENVIRONMENT (as opposed to a genuinely
# TPU-less host): the libtpu client/terminal version mismatch class — the
# backend initializes, every op fails. (substring-pair, both must appear,
# case-insensitive)
_ENV_FAILURE_SIGNATURES: tuple[tuple[str, str], ...] = (
    ("libtpu", "version"),
    ("libtpu", "mismatch"),
    ("tpu driver", "version"),
    ("client version", ""),
    ("terminal version", ""),
    ("pjrt api version", ""),
    ("plugin", "incompatible"),
)


def classify_env_failure(stderr_text: str) -> str | None:
    """Match a failed TPU probe's stderr against the known environment-
    failure signatures (libtpu client/terminal version mismatch and kin).
    → a named reason quoting the offending line, or None (not an
    environment failure — a plain no-TPU host)."""
    if not stderr_text:
        return None
    low = stderr_text.lower()
    for a, b in _ENV_FAILURE_SIGNATURES:
        if a in low and (not b or b in low):
            line = next(
                (
                    ln.strip()
                    for ln in stderr_text.splitlines()
                    if a in ln.lower() and (not b or b in ln.lower())
                ),
                "",
            ) or next(
                (ln.strip() for ln in stderr_text.splitlines() if a in ln.lower()),
                a,
            )
            return f"libtpu/TPU runtime environment failure ({a}): {line[:300]}"
    return None


def _probe_tpu(timeout_s: float = 300) -> tuple[str, str]:
    """Check the TPU backend in a SUBPROCESS — the orchestrator must never
    hold the chip its workers need. The probe DISPATCHES one op, not just
    lists devices: a libtpu version mismatch initializes fine and fails
    every op, which would otherwise read as 0.0-valued legs. Returns
    (status, stderr): status 'tpu', or 'no-tpu' (the backend is not tpu,
    is unusable or did not answer — stderr says which)."""
    import subprocess

    probe_src = (
        "import jax, numpy, sys\n"
        "d = jax.devices()[0]\n"
        "if d.platform != 'tpu':\n"
        "    sys.exit('JAX reports platform %r, not tpu' % d.platform)\n"
        "jax.block_until_ready(jax.device_put(numpy.zeros((8, 8), numpy.float32), d) @ "
        "jax.device_put(numpy.zeros((8, 8), numpy.float32), d))\n"
    )
    try:
        r = subprocess.run(
            [sys.executable, "-c", probe_src],
            timeout=timeout_s, capture_output=True,
        )
        stderr = (r.stderr or b"").decode(errors="replace")
        return ("tpu" if r.returncode == 0 else "no-tpu"), stderr
    except subprocess.TimeoutExpired:
        return "no-tpu", f"TPU probe did not answer in {timeout_s:.0f}s"


def _dense_batches(label: str, env_batch: str | None) -> list[int]:
    """Batch attempts for one dense shape. Default batch measured on the
    16GB v5e with activation-side LoRA: 6b fits at batch 1 (67.9% MFU); 8b
    params alone (15.3G bf16) don't fit. Below the SMALLEST shape the
    ladder keeps shrinking (4 → 2 → 1) so a tight chip reports 0.9b@2 or
    @1 instead of a null round (ROADMAP item 3). An explicit BENCH_BATCH
    pins one attempt everywhere."""
    if env_batch is not None:
        return [int(env_batch)]
    default = 1 if label in ("8b", "6b") else 4
    if label == DENSE_SHAPES[-1][0]:
        return [b for b in (4, 2, 1) if b <= default] or [1]
    return [default]


# -- subprocess leg isolation --------------------------------------------------
#
# Every leg runs in its OWN process with a structured result file (ROADMAP
# item 3: in-process isolation via _reset_between_legs still left cascade
# effects — a leg that corrupted the XLA client state, or an OOM the
# allocator never fully recovered from, poisoned every later leg; r5 zeroed
# ALL legs that way). A subprocess gives each leg a cold chip by
# construction, and a worker that dies (OOM-killed, segfault) still yields
# a named failure instead of taking the whole bench down. The orchestrator
# never initializes the device backend at all — on TPU the runtime is
# process-exclusive, so holding it would starve every worker.


def _worker_main(spec: dict, result_path: str) -> int:
    """One leg, one process: run, write {ok, tps_chip, fpt, peak_tflops,
    n_devices, platform} or {ok: false, error, oom, census_path, cost}."""
    out: dict = {"ok": False, "leg": spec.get("leg", "?")}
    try:
        from automodel_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        if spec.get("force_cpu"):
            jax.config.update("jax_platforms", "cpu")
        if spec.get("input_pipeline"):
            out = {"ok": True, "leg": spec.get("leg", "?"), **_input_pipeline_ab(spec)}
            with open(result_path, "w") as f:
                json.dump(out, f, indent=2, default=str)
            return 0
        from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
        from automodel_tpu.utils.flops_utils import device_peak_tflops

        ctx = build_mesh(MeshConfig(dp_shard=-1))
        tps, fpt = _run(
            spec["hf"], spec["backend"], int(spec["batch"]), int(spec["seq"]),
            int(spec["steps"]), ctx,
            lora=bool(spec.get("lora")), qlora=bool(spec.get("qlora")),
        )
        from automodel_tpu.ops import autotune

        out = {
            "ok": True,
            "leg": spec.get("leg", "?"),
            "tps_chip": tps,
            "fpt": fpt,
            "peak_tflops": device_peak_tflops(),
            "n_devices": len(jax.devices()),
            "platform": jax.devices()[0].platform,
            # which kernel autotune table the leg's kernels resolved —
            # provenance for comparing rounds (tuned vs default tiles)
            "autotune": autotune.table_info(),
        }
    except Exception as exc:
        oom = _is_oom(exc)
        out.update(error=str(exc)[:2000], oom=oom)
        if oom:
            # the profiling subsystem's cost summary (abstract re-trace, no
            # device memory) beside the live-buffer census: what the step
            # wanted to compute/move vs what actually filled the chip
            cost: dict | None
            try:
                cost = _abstract_step_cost(
                    spec["hf"], spec["backend"], int(spec["batch"]), int(spec["seq"])
                )
                if spec.get("lora") or spec.get("qlora"):
                    # the abstract trace models the FULL-PARAMETER step;
                    # the leg's real program differs (frozen base, adapter-
                    # only moments, NF4 packing) — label it so the OOM
                    # post-mortem reads it as a bound, not an account
                    cost["note"] = (
                        "full-parameter dense-equivalent step: the leg ran "
                        "LoRA/QLoRA (frozen base, adapter-only optimizer "
                        "state, NF4-packed base for qlora) — treat FLOPs/"
                        "bytes as an upper bound, not a byte-accurate "
                        "account of what OOMed"
                    )
            except Exception as ce:
                cost = {"error": f"{type(ce).__name__}: {ce}"}
            out["cost"] = cost
            out["census_path"] = _oom_memory_dump(
                spec.get("leg", "leg"), extra={"cost": cost}
            )
    with open(result_path, "w") as f:
        json.dump(out, f, indent=2, default=str)
    return 0 if out.get("ok") else 1


def _run_leg(leg: str, spec: dict, timeout_s: float | None = None) -> dict:
    """Spawn `python bench.py --worker ...` → the worker's result dict.
    A worker that crashes without writing a result (OOM-killed, segfault)
    or times out still produces a structured failure."""
    import subprocess
    import tempfile

    if os.environ.get("BENCH_INPROC"):  # debugging escape hatch
        with tempfile.NamedTemporaryFile("r", suffix=".json", delete=False) as f:
            path = f.name
        _worker_main({**spec, "leg": leg}, path)
        with open(path) as f:
            return json.load(f)
    timeout_s = timeout_s or float(os.environ.get("BENCH_LEG_TIMEOUT_S", 5400))
    with tempfile.TemporaryDirectory(prefix="bench_leg_") as td:
        path = os.path.join(td, f"{leg}.json")
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--worker", json.dumps({**spec, "leg": leg}), "--result", path,
        ]
        try:
            r = subprocess.run(cmd, timeout=timeout_s)  # stderr streams through
        except subprocess.TimeoutExpired:
            return {
                "ok": False, "leg": leg, "oom": False,
                "error": f"leg timed out after {timeout_s:.0f}s (hung worker killed)",
            }
        if not os.path.exists(path):
            return {
                "ok": False, "leg": leg, "oom": False,
                "error": f"worker died (rc {r.returncode}) without writing a result "
                "— likely OOM-killed or segfaulted before the handler ran",
            }
        with open(path) as f:
            return json.load(f)


def main() -> None:
    status, probe_stderr = _probe_tpu(float(os.environ.get("BENCH_TPU_PROBE_S", 180)))
    if status != "tpu":
        # no chip is a failure, never a CPU run under a device metric's
        # name. A version-skewed libtpu is named as what it is.
        env_failure = classify_env_failure(probe_stderr)
        if env_failure is not None:
            print(
                json.dumps(
                    {
                        "metric": "environment_failure",
                        "value": None,
                        "environment_failure": env_failure,
                    }
                )
            )
            print(f"[bench] ENVIRONMENT FAILURE: {env_failure}", file=sys.stderr, flush=True)
            raise SystemExit(2)
        tail = probe_stderr.strip().splitlines()[-1:] or ["no detail"]
        print(
            f"[bench] no TPU found ({tail[0][:300]}); bench.py measures the "
            "chip and does not run anywhere else",
            file=sys.stderr, flush=True,
        )
        raise SystemExit(1)
    from automodel_tpu.utils.flops_utils import calculate_mfu

    seq = int(os.environ.get("BENCH_SEQ", 4096))
    steps = 8
    peak = float("nan")  # reported by the first successful worker
    kernel_autotune = None  # autotune provenance from the first ok worker

    # ---- dense LoRA (headline) — largest shape that fits, each attempt a
    # pristine subprocess; below the smallest shape the batch ladder
    # (4 → 2 → 1) keeps shrinking the footprint before giving up ----
    dense_mfu, dense_label, dense_tflops = float("nan"), "none", 0.0
    dense_done = False  # a leg RAN successfully (mfu may still be NaN when
    # the device kind is missing from the peak table — that must stop the
    # ladder and report TFLOPs + a named reason, not re-run every shape)
    dense_failures: list[str] = []
    dense_backend = {
        "attn": "flash",
        "param_dtype": "bfloat16",
        "compute_dtype": "bfloat16",
        "remat": os.environ.get("BENCH_REMAT", "full"),
    }
    for shape in DENSE_SHAPES:
        label = shape[0]
        batches = _dense_batches(label, os.environ.get("BENCH_BATCH"))
        for batch in batches:
            leg = f"dense_{label}_b{batch}"
            res = _run_leg(
                leg,
                {"hf": _dense_hf(shape), "backend": dense_backend,
                 "batch": batch, "seq": seq, "steps": steps, "lora": True},
            )
            if res.get("ok"):
                dense_done = True
                peak = float(res.get("peak_tflops", float("nan")))
                kernel_autotune = kernel_autotune or res.get("autotune")
                dense_mfu = calculate_mfu(res["tps_chip"], res["fpt"], peak)
                dense_tflops = res["tps_chip"] * res["fpt"] / 1e12
                dense_label = label if batch == batches[0] else f"{label}_b{batch}"
                print(
                    f"[bench] dense-{label} b{batch} LoRA tok/s/chip="
                    f"{res['tps_chip']:,.0f} TFLOPs/s={dense_tflops:.1f} "
                    f"MFU={dense_mfu:.3f}",
                    file=sys.stderr, flush=True,
                )
                break
            kind = "OOM" if res.get("oom") else f"error: {res.get('error')}"
            census = res.get("census_path")
            dense_failures.append(
                f"{label} b{batch}: {kind}" + (f" (census: {census})" if census else "")
            )
            if not res.get("oom"):
                # only running out of memory is a reason to try a smaller
                # shape; anything else would fail there too, and stepping
                # past it would report a smaller model's number as the
                # headline of a broken run
                print(f"[bench] dense-{label} b{batch} {kind}",
                      file=sys.stderr, flush=True)
                raise SystemExit(1)
            print(
                f"[bench] dense-{label} b{batch} OOM; trying smaller",
                file=sys.stderr, flush=True,
            )
        if dense_done:
            break

    # ---- true-8B QLoRA (VERDICT r3 #2): NF4 base ~4.5GB fits the chip ----
    qlora_mfu, qlora_tflops = float("nan"), 0.0
    qlora_failure = None
    res = _run_leg(
        "qlora_8b",
        {
            "hf": _dense_hf(DENSE_SHAPES[0]),
            "backend": {
                "attn": "flash", "param_dtype": "bfloat16",
                "compute_dtype": "bfloat16", "remat": "full",
            },
            "batch": int(os.environ.get("BENCH_QLORA_BATCH", 1)),
            "seq": seq, "steps": steps, "qlora": True,
        },
    )
    if res.get("ok"):
        peak = float(res.get("peak_tflops", peak))
        kernel_autotune = kernel_autotune or res.get("autotune")
        qlora_mfu = calculate_mfu(res["tps_chip"], res["fpt"], peak)
        qlora_tflops = res["tps_chip"] * res["fpt"] / 1e12
        if qlora_mfu != qlora_mfu:  # ran fine; device peak unknown
            qlora_failure = (
                f"measured {qlora_tflops:.1f} TFLOPs/s/chip but the device "
                "kind is missing from TPU_PEAK_BF16_TFLOPS — no MFU basis"
            )
        print(
            f"[bench] dense-8b QLoRA tok/s/chip={res['tps_chip']:,.0f} "
            f"TFLOPs/s={qlora_tflops:.1f} MFU={qlora_mfu:.3f}",
            file=sys.stderr, flush=True,
        )
    else:
        qlora_failure = ("OOM: " if res.get("oom") else "") + str(res.get("error"))
        if res.get("census_path"):
            qlora_failure += f" (census: {res['census_path']})"
        print(f"[bench] 8b QLoRA leg failed: {res.get('error')}", file=sys.stderr, flush=True)

    # ---- MoE pretrain (fake balanced gate, reference bench conditions) ----
    # single-chip backend choice (measured on the v5e): ragged via the Pallas
    # grouped matmul (ops/grouped_matmul.py) — 30.8% MFU vs dense 25.1% /
    # gspmd 23.3%. (XLA's own ragged_dot lowering crashes this image's AOT
    # compile helper at bench-scale token counts; the Pallas kernel is both
    # the fix and faster.) Multi-chip meshes use a2a (same kernel inside).
    # ragged_fused (one-kernel expert MLP + remat policy that saves the sort
    # permutations) is raced against ragged; BENCH_MOE_EXPERTS pins one.
    moe_mfu, moe_tflops, moe_backend = float("nan"), 0.0, "none"
    pinned = os.environ.get("BENCH_MOE_EXPERTS")
    candidates = [pinned] if pinned else ["ragged_fused", "ragged"]
    moe_tried = {}
    moe_failures: dict[str, str] = {}
    for experts in candidates:
        res = _run_leg(
            f"moe_{experts}",
            {
                "hf": _moe_hf(), "backend": _moe_backend(experts),
                "batch": int(os.environ.get("BENCH_MOE_BATCH", 6)),
                "seq": seq, "steps": steps,
            },
        )
        if res.get("ok"):
            peak = float(res.get("peak_tflops", peak))
            kernel_autotune = kernel_autotune or res.get("autotune")
            mfu = calculate_mfu(res["tps_chip"], res["fpt"], peak)
            if mfu != mfu:  # ran fine; device peak unknown — no MFU basis
                moe_failures[experts] = (
                    f"measured {res['tps_chip'] * res['fpt'] / 1e12:.1f} "
                    "TFLOPs/s/chip but the device kind is missing from "
                    "TPU_PEAK_BF16_TFLOPS — no MFU basis"
                )
                continue
            moe_tried[experts] = round(mfu * 100, 2)
            print(
                f"[bench] moe[{experts}] tok/s/chip={res['tps_chip']:,.0f} "
                f"TFLOPs/s={res['tps_chip'] * res['fpt'] / 1e12:.1f} MFU={mfu:.3f}",
                file=sys.stderr, flush=True,
            )
            if moe_mfu != moe_mfu or mfu > moe_mfu:
                moe_mfu = mfu
                moe_tflops = res["tps_chip"] * res["fpt"] / 1e12
                moe_backend = experts
        else:
            failure = ("OOM: " if res.get("oom") else "") + str(res.get("error"))
            if res.get("census_path"):
                failure += f" (census: {res['census_path']})"
            moe_failures[experts] = failure
            print(
                f"[bench] moe[{experts}] leg failed: {res.get('error')}",
                file=sys.stderr, flush=True,
            )

    # every dense shape OOMed → value null + reason, NOT 0.0: a 0.0 in the
    # emitted JSON must mean "measured and got zero", never "leg never ran"
    # (a round once shipped all-zero legs that read as measurements)
    dense_ok = dense_mfu == dense_mfu
    dense_failure = (
        None if dense_ok
        else (
            f"measured {dense_label} at {dense_tflops:.1f} TFLOPs/s/chip but "
            "the device kind is missing from TPU_PEAK_BF16_TFLOPS — no MFU "
            "basis (add the new chip to utils/flops_utils.py)"
        ) if dense_done
        else "every dense shape failed: " + "; ".join(dense_failures)
    )
    result = {
            "metric": f"llama_dense_lora_mfu_{dense_label}",
            "value": round(dense_mfu * 100, 2) if dense_ok else None,
            "unit": "%MFU",
            "vs_baseline": (
                round(dense_mfu / DENSE_BASELINE_MFU, 3) if dense_ok else None
            ),
            "dense_failure": dense_failure,
            "dense_tflops_per_chip": round(dense_tflops, 1) if dense_ok else None,
            "qlora_8b_mfu_pct": (
                round(qlora_mfu * 100, 2) if qlora_mfu == qlora_mfu else None
            ),
            "qlora_8b_vs_baseline": (
                round(qlora_mfu / DENSE_BASELINE_MFU, 3)
                if qlora_mfu == qlora_mfu else None
            ),
            "qlora_8b_tflops_per_chip": (
                round(qlora_tflops, 1) if qlora_mfu == qlora_mfu else None
            ),
            "qlora_8b_failure": qlora_failure,
            "moe_mfu_pct": round(moe_mfu * 100, 2) if moe_mfu == moe_mfu else None,
            "moe_vs_baseline": (
                round(moe_mfu / MOE_BASELINE_MFU, 3) if moe_mfu == moe_mfu else None
            ),
            "moe_tflops_per_chip": (
                round(moe_tflops, 1) if moe_mfu == moe_mfu else None
            ),
            "moe_experts_backend": moe_backend,
            "moe_mfu_pct_by_backend": moe_tried,
            "moe_failures": moe_failures or None,
            # kernel-autotune provenance (ops/autotune.py): which per-chip
            # table the workers' kernels resolved their tiles from, so a
            # BENCH artifact says whether it ran tuned or default shapes
            "kernel_autotune": kernel_autotune,
            # input-pipeline A/B sub-leg (host overlap, data/prefetch.py):
            # sync vs prefetched steps/s under an injected collate delay,
            # with a bit-identical-loss determinism check
            **_input_pipeline_leg(),
        }
    print(json.dumps(result))

    # the VERDICT-r5 guard: a 0.0/None-valued leg with no recorded reason is
    # a reporting bug, not a measurement — fail the bench loudly so it can
    # never again ship two rounds of silent zeros
    from automodel_tpu.telemetry.report import validate_bench_result

    problems = validate_bench_result(result)
    if problems:
        for p in problems:
            print(f"[bench] INVALID RESULT: {p}", file=sys.stderr, flush=True)
        raise SystemExit(1)


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _spec = json.loads(sys.argv[sys.argv.index("--worker") + 1])
        _result = sys.argv[sys.argv.index("--result") + 1]
        raise SystemExit(_worker_main(_spec, _result))
    main()
