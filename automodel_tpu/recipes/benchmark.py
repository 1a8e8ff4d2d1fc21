"""Benchmarking recipe.

Parity: BenchmarkingRecipeForNextTokenPrediction (recipes/llm/benchmark.py:
34-100) — reuses the finetune recipe's setup and step, adds warmup gating,
per-step timers, profiler windows, MFU via the FLOPs formulas, and a JSON
result. Reference benchmark conditions (docs/performance-summary.md:66-72):
mock data, fake balanced gate for MoE, no validation.

YAML additions over train_ft:
  benchmark: {warmup_steps: 3, measure_steps: 10, profile: {enabled, ...}}
"""

from __future__ import annotations

import json
import logging
import time

import jax
import numpy as np

from automodel_tpu.config.loader import ConfigNode
from automodel_tpu.data.collators import stack_microbatches
from automodel_tpu.data.loader import place_batch
from automodel_tpu.data.prefetch import PreparedBatch
from automodel_tpu.recipes.train_ft import TrainFinetuneRecipeForNextTokenPrediction
from automodel_tpu.telemetry import memory_snapshot
from automodel_tpu.utils.flops_utils import (
    calculate_mfu,
    device_peak_tflops,
    flops_per_token_for_config,
)
from automodel_tpu.utils.profiler import ProfilerConfig, StepProfiler

logger = logging.getLogger(__name__)


class BenchmarkingRecipeForNextTokenPrediction(TrainFinetuneRecipeForNextTokenPrediction):
    def run_benchmark(self) -> dict:
        # a benchmark hangs the same ways training does (wedged collective,
        # lost device): the watchdog turns a stuck run into stacks + a
        # flight-recorder dump instead of a silent stall. Pets ride the
        # measure loop below.
        self.guard.start()
        try:
            with self.telemetry.crash_guard():
                return self._run_benchmark_body()
        finally:
            self.guard.close()
            self._close_prefetch()
            if getattr(self, "_prom_server", None) is not None:
                self._prom_server.shutdown()

    def _run_benchmark_body(self) -> dict:
        bcfg = dict(self.cfg.get("benchmark", {}) or {})
        warmup = int(bcfg.get("warmup_steps", 3))
        measure = int(bcfg.get("measure_steps", 10))
        prof = StepProfiler(ProfilerConfig(**dict(bcfg.get("profile", {}) or {})))
        tel = self.telemetry
        timers = tel.timers

        it = iter(self.step_scheduler)
        group = next(it)
        if isinstance(group, PreparedBatch):
            # data.prefetch: the pipeline already stacked + placed the group
            stacked, batch = group.host, group.device
        else:
            stacked = stack_microbatches(group)
            batch = place_batch(self.mesh_ctx, stacked)
        tokens_per_step = int(np.prod(stacked["input_ids"].shape))

        state = self.state
        for i in range(warmup):
            state, metrics = self.train_step(state, batch)
        jax.device_get(metrics["loss"])  # barrier: the value needs the step
        # discard warmup compiles so any compile counted below is a RECOMPILE
        # inside the measure window (which pollutes step times)
        if tel.compile_bridge is not None:
            tel.compile_bridge.drain()

        # telemetry overhead = EVERY per-step telemetry op the loop adds
        # (profiler hook, all timer start/stops, ring append) — the
        # perf_counter brackets themselves are the same magnitude as one
        # timer call, so the estimate is conservative (over-counts slightly)
        tel_overhead_s = 0.0
        for i in range(measure):
            _t = time.perf_counter()
            prof.on_step(i)
            timers("step").start()
            timers("dispatch").start()
            tel_overhead_s += time.perf_counter() - _t
            state, metrics = self.train_step(state, batch)
            _t = time.perf_counter()
            timers("dispatch").stop()
            timers("device").start()
            tel_overhead_s += time.perf_counter() - _t
            jax.device_get(metrics["loss"])
            _t = time.perf_counter()
            timers("device").stop()
            dt = timers("step").stop()
            tel.record_step({"bench_step": i, "step_time_s": dt, "ts": time.time()})
            self.guard.on_step(i)  # heartbeat only (no consensus fold)
            tel_overhead_s += time.perf_counter() - _t
        prof.close()
        self.state = state

        n_chips = self.mesh_ctx.world_size
        mean_s = timers("step").mean()
        tps = tokens_per_step / mean_s
        seq_len = stacked["input_ids"].shape[-1]
        fpt = flops_per_token_for_config(self.model.config, seq_len)
        peak = device_peak_tflops()
        tflops_chip = tps / n_chips * fpt / 1e12
        result = {
            "tokens_per_second": tps,
            "tokens_per_second_per_chip": tps / n_chips,
            "tflops_per_second_per_chip": tflops_chip,
            "mfu": calculate_mfu(tps / n_chips, fpt, peak) if peak == peak else None,
            "step_time_mean_s": mean_s,
            "step_time_min_s": timers("step").min(),
            "step_time_max_s": timers("step").max(),
            "n_chips": n_chips,
            "tokens_per_step": tokens_per_step,
            "loss": float(jax.device_get(metrics["loss"])),
            "timers": timers.summary(),
            # step-time decomposition: host dispatch vs device execution
            # (device = the block after dispatch returns; data is pre-staged
            # here so there is no data-wait leg in the bench)
            "step_decomposition": {
                "dispatch_mean_s": timers("dispatch").mean(),
                "device_mean_s": timers("device").mean(),
            },
            # demonstrated overhead of the per-step telemetry bookkeeping
            # (acceptance bound: <1% of step time at default cadence)
            "telemetry_overhead_s_per_step": tel_overhead_s / max(measure, 1),
            "telemetry_overhead_fraction": (tel_overhead_s / max(measure, 1)) / max(mean_s, 1e-12),
            # what filled the chip at measurement end
            "memory": memory_snapshot(
                self.telemetry.config.census_top_k
            ),
        }
        if self.telemetry.compile_bridge is not None:
            d = self.telemetry.compile_bridge.drain()
            result["recompiles_during_measure"] = d["compiles"]
            if d["compiles"]:
                result["recompile_secs"] = round(d["compile_secs"], 4)
                logger.warning(
                    "benchmark: %d recompile(s) inside the measure window — "
                    "step times are polluted by %.2fs of compile",
                    d["compiles"], d["compile_secs"],
                )
        # cost attribution (telemetry/profiling/cost.py): measured FLOPs of
        # the ACTUAL step program beside the analytic law the `mfu` key is
        # built from — plus the roofline class for this leg. Drift between
        # `mfu` and `mfu_measured_pct` is the report's headline, not a bug
        # in either: it quantifies what the analytic law does not count
        # (remat recompute, dense-computed experts, fused heads).
        if self.profiling.enabled and self.profiling.cost_attribution:
            try:
                # NOT `as prof` — that would shadow the StepProfiler above
                from automodel_tpu.telemetry import profiling as profmod

                cost = profmod.program_cost(
                    self.train_step, self.state, batch, program="train_step"
                )
                basis = self.profiling.roofline_basis()
                result["cost"] = {**cost.to_dict(), **profmod.roofline(cost, basis)}
                m = profmod.mfu_measured_pct(cost.flops, mean_s, n_chips, basis)
                result["mfu_measured_pct"] = round(m, 3) if m is not None else None
            except Exception as e:
                result["cost_error"] = f"{type(e).__name__}: {e}"
        pinfo = getattr(self.model, "pipeline_info", None)
        if pinfo:
            from automodel_tpu.utils.flops_utils import pipeline_bubble_fraction

            # analytic bubble for the active schedule; the measured
            # counterpart needs a schedule-free work time (a microbatch
            # sweep, or a pp=1 run of the same shape)
            result["pipeline"] = {
                **pinfo,
                "bubble_fraction_analytic": pipeline_bubble_fraction(
                    pinfo["pp"], pinfo["n_microbatches"],
                    pinfo.get("schedule", "gpipe"), pinfo.get("zb_queue"),
                    pinfo.get("w_deferred_fraction", 1.0),
                ),
            }
        out_path = bcfg.get("output_json")
        if out_path:
            with open(out_path, "w") as f:
                json.dump(result, f, indent=2)
        logger.info(
            "benchmark: %s",
            json.dumps({k: v for k, v in result.items() if k not in ("timers", "memory")}),
        )
        print(json.dumps(result))
        return result


def main(cfg: ConfigNode) -> dict:
    recipe = BenchmarkingRecipeForNextTokenPrediction(cfg)
    recipe.setup()
    return recipe.run_benchmark()
