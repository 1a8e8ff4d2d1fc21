"""Measure the SPMD pipeline bubble per SCHEDULE (gpipe vs zero_bubble).

Round 4 (PROFILE_PP_r04.md) established that the AD-transposed GPipe
wavefront sits on the (pp-1)/(M+pp-1) law to within 5% and recorded
zero-bubble B/W splitting as the remaining schedule-level headroom. This
round implements it (parallel/zero_bubble.py); this tool measures both
schedules over a microbatch sweep and writes PROFILE_PP_r06.md.

Method: pp stages over real XLA host devices (one per core so wall-clock
sees the schedule — with fewer cores than ranks the OS time-slices idle
ranks away and the bubble becomes invisible), fixed global batch, M swept.
T_work/overhead are fit from the gpipe leg exactly as in r04:

    t_gpipe(M) = T_work · (1 + (pp-1)/M) + c

and the measured bubble of EITHER schedule at M is then
1 − (T_work + c)/t(M)  (training/timers.measured_bubble_fraction), compared
against the analytic laws in utils/flops_utils (gpipe_bubble_fraction /
zero_bubble_fraction).

Run: JAX_PLATFORMS=cpu python tools/profile_pp.py
Knobs: PROFILE_PP_STAGES (default 2 = host cores), PROFILE_PP_REPS.
"""

from __future__ import annotations

import os
import sys
import time

PP = int(os.environ.get("PROFILE_PP_STAGES", 2))
os.environ.setdefault(
    "XLA_FLAGS", f"--xla_force_host_platform_device_count={PP}"
)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

from automodel_tpu import auto_model
from automodel_tpu.data.loader import place_batch
from automodel_tpu.optim.builders import build_optimizer
from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
from automodel_tpu.training.timers import measured_bubble_fraction
from automodel_tpu.training.train_state import TrainState
from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss
from automodel_tpu.utils.flops_utils import (
    gpipe_bubble_fraction,
    zero_bubble_fraction,
)

GLOBAL_BATCH = 16
SEQ = 128
REPS = int(os.environ.get("PROFILE_PP_REPS", 5))
MS = [4, 8, 16]


def step_time(M: int, schedule: str) -> float:
    ctx = build_mesh(
        MeshConfig(pp=PP, dp_shard=1, pp_schedule=schedule),
        devices=jax.devices("cpu")[:PP],
    )
    hf = {
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "vocab_size": 512,
        "hidden_size": 256,
        "intermediate_size": 1024,
        "num_hidden_layers": 8,
        "num_attention_heads": 8,
        "num_key_value_heads": 8,
        "head_dim": 32,
        "tie_word_embeddings": False,
    }
    backend = {
        "attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32",
        "remat": "full", "pp_microbatches": M,
    }
    auto = auto_model.from_config(hf, ctx, backend, seed=0)
    loss_fn = make_causal_lm_loss(auto.model, loss="masked_ce", constrain=auto.constrain)
    opt = build_optimizer(name="adamw", lr=1e-4)
    state = TrainState.create(auto.params, jax.jit(opt.init)(auto.params))
    step = build_train_step(loss_fn, opt)
    ids = np.random.default_rng(0).integers(0, 512, (1, GLOBAL_BATCH, SEQ)).astype(np.int32)
    b = place_batch(ctx, {"input_ids": ids, "labels": ids})
    state, m = step(state, b)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(REPS):
        state, m = step(state, b)
    jax.block_until_ready(m["loss"])
    return (time.perf_counter() - t0) / REPS


def main() -> None:
    from automodel_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    t = {s: [] for s in ("gpipe", "zero_bubble")}
    for schedule in t:
        for M in MS:
            dt = step_time(M, schedule)
            t[schedule].append(dt)
            print(f"{schedule:>12} M={M:>2}: {dt*1e3:8.1f} ms/step", flush=True)

    # T_work / c from the gpipe leg (the r04 fit); on a noisy/small host the
    # 2-param fit can come out non-physical — measured-bubble rows are only
    # emitted when it doesn't, the schedule RATIO rows below are always
    X = np.stack([1 + (PP - 1) / np.asarray(MS, float), np.ones(len(MS))], 1)
    coef, *_ = np.linalg.lstsq(X, np.asarray(t["gpipe"]), rcond=None)
    T_work, c = coef
    t_ideal = T_work + c
    rel_err = float(
        np.max(np.abs(X @ coef - t["gpipe"]) / np.asarray(t["gpipe"]))
    )
    fit_ok = T_work > 0 and t_ideal > 0

    rows = []
    for i, M in enumerate(MS):
        ratio = t["zero_bubble"][i] / t["gpipe"][i]
        # tick-model total-cost ratio (F=1, B=2, W=1 units)
        model_ratio = (3.0 * (M + PP - 1) + M) / (4.0 * (M + PP - 1))
        row = (
            f"M={M:>2}: gpipe {t['gpipe'][i]*1e3:7.1f} ms | zero_bubble "
            f"{t['zero_bubble'][i]*1e3:7.1f} ms | ratio {ratio:5.3f} "
            f"(tick model {model_ratio:5.3f})"
        )
        if fit_ok:
            row += (
                f" | bubble meas {measured_bubble_fraction(t['gpipe'][i], t_ideal):5.1%}"
                f"/{measured_bubble_fraction(t['zero_bubble'][i], t_ideal):5.1%}"
                f" vs law {gpipe_bubble_fraction(PP, M):5.1%}"
                f"/{zero_bubble_fraction(PP, M):5.1%}"
            )
        rows.append(row)
    analytic = []
    for M in MS:
        gbf, zbf = gpipe_bubble_fraction(PP, M), zero_bubble_fraction(PP, M)
        ratio = f"   (x{gbf / zbf:.2f} smaller)" if zbf > 0 else ""
        analytic.append(
            f"m={M:>2}:  GPipe law {gbf:6.2%}   zero-bubble {zbf:6.2%}{ratio}"
        )

    with open("PROFILE_PP_r06.md", "w") as f:
        f.write(f"""# Pipeline schedule profile (round 6): zero-bubble B/W split

Round 4 measured the GPipe wavefront on its (pp-1)/(M+pp-1) law within 5%
and named zero-bubble W-deferral the one schedule-level optimization left.
This round ships it (`parallel/zero_bubble.py`, `pp_schedule=zero_bubble`):
the stage backward splits into B (activation grads, on the ppermute
wavefront) and W (weight grads, exported as split_dot tap cotangents and
contracted as flat bubble-free work after the B wave drains).

## Analytic schedule model (tick costs: F=1, B=2 incl. recompute, W=1)

Per-rank idle is 3(pp-1) tick-equivalents under both schedules, but the
zero-bubble denominator grows by the flat W phase:

    GPipe:        bubble = (pp-1)/(M+pp-1)
    zero-bubble:  bubble = 3(pp-1)/(4M+3(pp-1))   < GPipe for every M

At pp={PP}, for the acceptance sweep m ∈ {{4, 8, 16}}:

```
""" + "\n".join(analytic) + f"""
```

Bounded deferral (`pp_zb_queue=Q<M`) is the memory escape hatch, not a
speedup: every B tick then carries a W contraction (combined-schedule
cost) and the bubble returns to ~the GPipe law while stash memory caps at
Q microbatches (utils/flops_utils.zero_bubble_fraction).

## Measured

pp={PP} over {PP} XLA host devices, one per core; 8-layer dense stack,
global batch {GLOBAL_BATCH}x{SEQ}, remat=full, {REPS}-rep means.

t_gpipe 2-param fit (r04 method): T_work = {T_work*1e3:.1f} ms,
overhead c = {c*1e3:.1f} ms, max deviation {rel_err:.1%}
({"physical — per-M measured bubble emitted" if fit_ok else
  "NON-physical on this host (per-tick overhead dominates the tiny "
  "per-tick compute at this scale) — only the schedule ratio rows below "
  "are meaningful"}).

```
""" + "\n".join(rows) + """
```

Honest read of the measured leg: this container exposes only as many cores
as stages at pp=2, where the tick-model gap between the schedules is just
1.5-3% of the step — below the host's noise floor — and the zero-bubble
implementation carries real per-tick constants the model ignores (per-layer
dynamic_slice of the closed-over kernels in the B pass, the stash-ring
dynamic updates, and the W-flush einsum hitting a different CPU kernel than
the scan matmuls). Wall-clock here does NOT resolve the law gap; the
recorded acceptance evidence is the analytic model above (whose GPipe half
r04 validated on-law within 5% at pp=4) plus the parity tests. Re-sweep on
a host with >= 4 cores at pp=4, where the law gap is 3x larger, before
quoting a measured speedup.

grads parity: `tests/test_pipeline.py` asserts zero_bubble loss/grads match
gpipe within fp32-accum tolerance on dense and MoE (incl. the aux-free
gate-bias update path), with full and bounded deferral queues.
""")
    print("wrote PROFILE_PP_r06.md", flush=True)


if __name__ == "__main__":
    main()
