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
        # bench legs hang the same ways training does (wedged collective,
        # lost device): the watchdog turns a stuck leg into stacks + a
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
        # decode leg (generation subsystem): time-to-first-token + decode
        # tokens/sec through the jitted prefill/while-loop-decode programs.
        # Degrades to null-with-recorded-reason (validate_bench_result
        # semantics) when the `generation:` section or a cache-capable
        # model is absent — a leg that never ran must never read as 0.0.
        # the decode leg compiles fresh prefill/decode programs — minutes
        # at scale, with no pets in between: watchdog eval grace covers it
        with self.guard.phase("eval"):
            result.update(self._generation_leg())
        # serving leg (serving/): sustained throughput under Poisson request
        # arrivals through the continuous-batching engine — tokens/s, ttft
        # p50/p99, block-pool occupancy. Same degradation contract as the
        # decode leg: no `serving:` section / cache-less model / any failure
        # → null values WITH a recorded reason, never a silent 0.0.
        with self.guard.phase("eval"):
            result.update(self._serving_leg())
        # hierarchical-KV-cache A/B sub-leg (serving.kv_spill:): a prefill-
        # heavy shared-prefix schedule with a deliberately undersized pool,
        # replayed spill-on vs spill-off — the reload-vs-recompute crossover
        # measured on identical arrivals. Gated on serving.kv_spill.enabled;
        # degrades null-with-reason like every other leg.
        with self.guard.phase("eval"):
            result.update(self._spill_leg())
        # routed fleet sub-leg (serving/fleet/): the SAME Poisson arrivals
        # replayed through a router over >= 2 local replicas — the
        # routed-vs-single A/B that prices the fleet tier. Gated on a
        # `fleet:` section; degrades null-with-reason like every other leg.
        with self.guard.phase("eval"):
            result.update(
                self._fleet_leg(result.get("serve_tokens_per_s"))
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
            # counterpart needs a schedule-free work time (microbatch sweep
            # or pp=1 leg) — tools/profile_pp.py produces both
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


    def _generation_leg(self) -> dict:
        """→ {gen_ttft_s, gen_decode_tps, gen_failure[, gen_tokens,
        gen_cache_bytes]}. First call compiles (discarded), second call is
        the measurement. Mock prompts: random token ids, batch/length from
        `generation.bench_batch` / `generation.bench_prompt_len`."""
        if self._gen_engine is None:
            return {
                "gen_ttft_s": None,
                "gen_decode_tps": None,
                "gen_failure": self._gen_skip_reason
                or "no generation: section in config",
            }
        batch = int(self._gen_section.get("bench_batch", 4))
        prompt_len = int(self._gen_section.get("bench_prompt_len", 64))
        vocab = int(self.model.config.vocab_size)
        rng = np.random.default_rng(0)
        prompts = rng.integers(1, vocab, size=(batch, prompt_len)).tolist()
        try:
            self._gen_engine.generate_ids(prompts, params=self.state.params)
            out = self._gen_engine.generate_ids(prompts, params=self.state.params)
        except Exception as e:
            return {
                "gen_ttft_s": None,
                "gen_decode_tps": None,
                "gen_failure": f"{type(e).__name__}: {e}",
            }
        return {
            "gen_ttft_s": round(out["ttft_s"], 6),
            "gen_decode_tps": round(out["decode_tps"], 2),
            "gen_tokens": out["gen_tokens"],
            "gen_cache_bytes": out["cache_bytes"],
            "gen_failure": None,
        }

    def _poisson_arrivals(self, scfg) -> list:
        """The serving legs' shared workload: Poisson arrivals over mixed-
        length random prompts, deterministically derived from seed 0 — the
        single-replica leg and the routed fleet sub-leg replay EXACTLY the
        same (offset, prompt, budget) list, so their tokens/s compare."""
        vocab = int(self.model.config.vocab_size)
        rng = np.random.default_rng(0)
        lens = rng.integers(
            scfg.bench_prompt_len_min,
            scfg.bench_prompt_len_max + 1,
            size=scfg.bench_requests,
        )
        gaps = rng.exponential(
            1.0 / max(scfg.bench_rate, 1e-6), size=scfg.bench_requests
        )
        offsets = np.cumsum(gaps) - gaps[0]  # first arrives at t=0
        return [
            (
                float(offsets[i]),
                rng.integers(1, vocab, size=int(lens[i])).tolist(),
                scfg.bench_max_new_tokens,
            )
            for i in range(scfg.bench_requests)
        ]

    def _serving_leg(self) -> dict:
        """→ {serve_tokens_per_s, serve_ttft_p50_s, serve_ttft_p99_s,
        serve_block_occupancy_peak, serve_requests, serve_failure}.

        Poisson arrivals (`serving.bench_rate` req/s, exponential
        inter-arrival gaps) over `serving.bench_requests` mixed-length
        random prompts, driven in real time through the continuous-batching
        engine. A warm-up request is run first so the chunk-prefill/decode
        compiles don't pollute the measured ttfts."""
        nulls = {
            "serve_tokens_per_s": None,
            "serve_ttft_p50_s": None,
            "serve_ttft_p99_s": None,
            "serve_block_occupancy_peak": None,
            "serve_requests": None,
            "serve_accept_rate": None,
            "serve_draft_tps": None,
        }
        section = self.cfg.get("serving")
        if section is None:
            return {
                **nulls,
                "serve_failure": "no serving: section in config",
                "serve_spec_failure": "no serving: section in config",
            }
        if self.peft_config is not None:
            reason = "serving with peft adapters is not supported (merge first)"
            return {**nulls, "serve_failure": reason, "serve_spec_failure": reason}
        try:
            from automodel_tpu.serving.engine import ServeConfig, ServingEngine

            scfg = ServeConfig.from_dict(dict(section or {}))
            gcfg = getattr(self, "_gen_section", None)
            from automodel_tpu.generation.engine import GenerationConfig

            gen_cfg = GenerationConfig.from_dict(
                {
                    k: v
                    for k, v in dict(gcfg or {}).items()
                    if k not in ("prompts", "prompt_ids", "tokenizer", "enabled")
                }
            )
            # serve with the CURRENT weights, like the decode leg
            auto = self.auto
            params0 = auto.params
            auto.params = self.state.params
            engine = off_engine = None
            try:
                engine = ServingEngine(auto, scfg, gen_cfg)
                vocab = int(self.model.config.vocab_size)
                arrivals = self._poisson_arrivals(scfg)
                rng = np.random.default_rng(1)
                # warm-up: compile chunk prefill + decode outside the window
                engine.submit(
                    rng.integers(
                        1, vocab, size=len(arrivals[0][1])
                    ).tolist(),
                    max_new_tokens=2,
                )
                engine.run()
                _, stats = engine.run_workload(arrivals)
                decode_backend = engine.decode_backend
                # spec-on/spec-off A/B sub-leg: the same Poisson workload
                # through a second engine with the draft disabled, so the
                # speedup claim is measured on identical arrivals — the
                # speculative analogue of the fused-vs-composed backward A/B
                ab = None
                if scfg.speculative.enabled:
                    import dataclasses as _dc

                    # release the spec engine's pool HBM before the A/B
                    # engine allocates its own — num_blocks is sized to the
                    # chip, so two resident pools would OOM exactly the
                    # configs this sub-leg exists to measure
                    engine.release_pools()
                    off_cfg = _dc.replace(
                        scfg,
                        speculative=_dc.replace(
                            scfg.speculative, enabled=False, draft=None
                        ),
                    )
                    off_engine = ServingEngine(auto, off_cfg, gen_cfg)
                    off_engine.submit(
                        rng.integers(
                            1, vocab, size=len(arrivals[0][1])
                        ).tolist(),
                        max_new_tokens=2,
                    )
                    off_engine.run()
                    _, off_stats = off_engine.run_workload(arrivals)
                    on_tps = stats["sustained_tokens_per_s"]
                    off_tps = off_stats["sustained_tokens_per_s"]
                    ab = {
                        "spec_on_tokens_per_s": round(on_tps, 2),
                        "spec_off_tokens_per_s": round(off_tps, 2),
                        "speedup": (
                            round(on_tps / off_tps, 3) if off_tps > 0 else None
                        ),
                    }
            finally:
                auto.params = params0
                # free the leg's pool HBM before the next leg (the routed
                # fleet sub-leg builds N replica pools of its own)
                for obj in (engine, off_engine):
                    if obj is not None:
                        obj.release_pools()
        except Exception as e:
            reason = f"{type(e).__name__}: {e}"
            return {**nulls, "serve_failure": reason, "serve_spec_failure": reason}
        out = {
            "serve_tokens_per_s": round(stats["sustained_tokens_per_s"], 2),
            "serve_ttft_p50_s": round(stats["ttft_p50_s"], 6),
            "serve_ttft_p99_s": round(stats["ttft_p99_s"], 6),
            "serve_block_occupancy_peak": stats["block_occupancy_peak"],
            "serve_requests": stats["requests"],
            "serve_prefix_cache": stats["prefix_cache"],
            "serve_queue_depth_peak": stats["queue_depth_peak"],
            "serve_decode_backend": decode_backend,
            "serve_kv_cache_dtype": scfg.kv_cache_dtype,
            "serve_failure": None,
        }
        if scfg.speculative.enabled:
            out["serve_accept_rate"] = stats.get("accept_rate")
            out["serve_draft_tps"] = (
                round(stats["draft_tps"], 2)
                if isinstance(stats.get("draft_tps"), float) else None
            )
            out["serve_spec_ab"] = ab
            out["serve_spec_failure"] = (
                None if stats.get("accept_rate") is not None
                else "no speculative round ran inside the workload"
            )
        else:
            out["serve_accept_rate"] = None
            out["serve_draft_tps"] = None
            out["serve_spec_failure"] = "speculative decoding disabled"
        return out

    def _spill_arrivals(self, scfg, prefix_blocks: int, groups: int,
                        repeats: int) -> list:
        """The spill A/B's prefill-heavy workload: ``groups`` long shared
        prefixes (``prefix_blocks`` full blocks each), each re-arriving
        ``repeats`` times with a fresh one-block suffix, interleaved
        round-robin with Poisson gaps so every return to a group happens
        AFTER the other groups' prompts churned the pool. Derived from
        seed 2 — spill-on and spill-off replay exactly this list."""
        bs = scfg.block_size
        vocab = int(self.model.config.vocab_size)
        rng = np.random.default_rng(2)
        prefixes = [
            rng.integers(1, vocab, size=prefix_blocks * bs).tolist()
            for _ in range(groups)
        ]
        n = groups * repeats
        gaps = rng.exponential(1.0 / max(scfg.bench_rate, 1e-6), size=n)
        offsets = np.cumsum(gaps) - gaps[0]
        out = []
        for i in range(n):
            g = i % groups  # round-robin: maximal churn between repeats
            suffix = rng.integers(1, vocab, size=bs).tolist()
            out.append((float(offsets[i]), prefixes[g] + suffix, 4))
        return out

    def _spill_leg(self) -> dict:
        """→ {serve_spill_tokens_per_s, serve_spill_ttft_p50_s,
        serve_effective_hit_rate, serve_spill_reloads, serve_spill_ab,
        serve_spill_failure}. Both engines run non-speculative (spill and
        speculative are mutually exclusive) and share one undersized pool
        geometry, so the only difference between the legs is whether an
        evicted prefix reloads from host RAM or re-prefills."""
        nulls = {
            "serve_spill_tokens_per_s": None,
            "serve_spill_ttft_p50_s": None,
            "serve_effective_hit_rate": None,
            "serve_spill_reloads": None,
        }
        section = self.cfg.get("serving")
        if section is None:
            return {**nulls, "serve_spill_failure": "no serving: section in config"}
        if self.peft_config is not None:
            return {
                **nulls,
                "serve_spill_failure": (
                    "serving with peft adapters is not supported (merge first)"
                ),
            }
        import dataclasses as _dc

        on_engine = off_engine = None
        try:
            from automodel_tpu.generation.engine import GenerationConfig
            from automodel_tpu.serving.engine import ServeConfig, ServingEngine

            scfg = ServeConfig.from_dict(dict(section or {}))
            if not scfg.kv_spill.enabled:
                return {
                    **nulls,
                    "serve_spill_failure": "serving.kv_spill disabled",
                }
            gcfg = getattr(self, "_gen_section", None)
            gen_cfg = GenerationConfig.from_dict(
                {
                    k: v
                    for k, v in dict(gcfg or {}).items()
                    if k not in ("prompts", "prompt_ids", "tokenizer", "enabled")
                }
            )
            # pool sized to hold roughly ONE group's working set: returning
            # to any group after the round-robin forces the eviction the
            # hierarchy exists to absorb. serial slots keep the churn
            # deterministic-ish (one admission at a time).
            prefix_blocks, groups, repeats = 12, 3, 3
            per_req = prefix_blocks + 2  # suffix block + decode spill-over
            num_blocks = per_req + 4
            base = _dc.replace(
                scfg, slots=1, num_blocks=num_blocks,
                max_seq_len=max(
                    scfg.max_seq_len, num_blocks * scfg.block_size
                ),
                speculative=_dc.replace(
                    scfg.speculative, enabled=False, draft=None
                ),
            )
            arrivals = self._spill_arrivals(
                base, prefix_blocks, groups, repeats
            )
            auto = self.auto
            params0 = auto.params
            auto.params = self.state.params
            try:
                legs = {}
                for name, enabled in (("on", True), ("off", False)):
                    cfg_leg = _dc.replace(
                        base,
                        kv_spill=_dc.replace(scfg.kv_spill, enabled=enabled),
                    )
                    eng = ServingEngine(auto, cfg_leg, gen_cfg)
                    if name == "on":
                        on_engine = eng
                    else:
                        off_engine = eng
                    # warm: compile chunk prefill + decode outside the window
                    eng.submit(arrivals[0][1][: base.block_size], max_new_tokens=2)
                    eng.run()
                    if enabled:
                        # also warm the spill→reload cycle (bucketed
                        # extract + inject programs): park a prefix, churn
                        # it out of HBM, re-serve it — the A/B measures the
                        # hierarchy, not its one-time XLA compiles
                        warm = arrivals[0][1]
                        churn_len = min(
                            (num_blocks - 1) * base.block_size,
                            base.max_seq_len,
                        ) - 2
                        churn = (list(arrivals[1][1]) * 2)[:churn_len]
                        for p in (warm, churn, warm):
                            eng.submit(p, max_new_tokens=2)
                            eng.run()
                    eng.pool.clear_prefix_cache()
                    # warm-up traffic must not pollute the reported
                    # ledgers; zeroed TOGETHER (pool + tier) so the
                    # cross-tier invariants stay consistent
                    for d in [eng.pool.counters] + (
                        [eng.pool.spill.counters]
                        if eng.pool.spill is not None else []
                    ):
                        for key in d:
                            d[key] = 0
                    _, stats = eng.run_workload(arrivals)
                    eng.pool.check_invariants()
                    legs[name] = stats
                    eng.release_pools()
            finally:
                auto.params = params0
        except Exception as e:
            return {**nulls, "serve_spill_failure": f"{type(e).__name__}: {e}"}
        finally:
            for obj in (on_engine, off_engine):
                if obj is not None:
                    obj.release_pools()

        def _rates(stats):
            c = stats["prefix_cache"]
            hit, miss = c["prefix_hit_tokens"], c["prefix_miss_tokens"]
            rate = hit / (hit + miss) if hit + miss else None
            return rate, c

        on_rate, on_c = _rates(legs["on"])
        off_rate, _ = _rates(legs["off"])
        on_tps = legs["on"]["sustained_tokens_per_s"]
        off_tps = legs["off"]["sustained_tokens_per_s"]
        return {
            "serve_spill_tokens_per_s": round(on_tps, 2),
            "serve_spill_ttft_p50_s": round(legs["on"]["ttft_p50_s"], 6),
            "serve_effective_hit_rate": (
                round(on_rate, 4) if on_rate is not None else None
            ),
            "serve_spill_reloads": on_c["spill_reloads"],
            "serve_spill_ab": {
                "spill_on_tokens_per_s": round(on_tps, 2),
                "spill_off_tokens_per_s": round(off_tps, 2),
                "spill_on_ttft_p50_s": round(legs["on"]["ttft_p50_s"], 6),
                "spill_off_ttft_p50_s": round(legs["off"]["ttft_p50_s"], 6),
                "effective_hit_rate_on": (
                    round(on_rate, 4) if on_rate is not None else None
                ),
                "effective_hit_rate_off": (
                    round(off_rate, 4) if off_rate is not None else None
                ),
                "spilled_blocks": on_c["spilled_blocks"],
                "reloaded_blocks": on_c["spill_reloaded_blocks"],
                "speedup": (
                    round(on_tps / off_tps, 3) if off_tps > 0 else None
                ),
            },
            "serve_spill_failure": None,
        }

    def _fleet_leg(self, single_tps) -> dict:
        """→ {serve_fleet_tokens_per_s, serve_route_prefix_hit_rate,
        serve_fleet_retries, serve_fleet_ab, serve_fleet_failure}.

        The routed sub-leg: ``fleet.bench_replicas`` local replicas (each a
        real ServingEngine behind a real HTTP front, sharing the current
        weights), a Router probing and placing over them, and EXACTLY the
        same Poisson arrivals as the single-replica leg driven through
        POST /generate — the routed-vs-single A/B. Replica pools split the
        single leg's block budget (``fleet.bench_num_blocks`` overrides),
        so the comparison holds the pool HBM constant."""
        nulls = {
            "serve_fleet_tokens_per_s": None,
            "serve_route_prefix_hit_rate": None,
            "serve_fleet_retries": None,
        }
        fleet_section = self.cfg.get("fleet")
        if fleet_section is None:
            return {**nulls, "serve_fleet_failure": "no fleet: section in config"}
        if self.cfg.get("serving") is None:
            return {
                **nulls,
                "serve_fleet_failure": "no serving: section in config",
            }
        if self.peft_config is not None:
            return {
                **nulls,
                "serve_fleet_failure": (
                    "serving with peft adapters is not supported (merge first)"
                ),
            }
        import dataclasses as _dc
        import threading

        engines, servers, loops, router = [], [], [], None
        try:
            from automodel_tpu.generation.engine import GenerationConfig
            from automodel_tpu.serving.engine import ServeConfig, ServingEngine
            from automodel_tpu.serving.fleet.router import FleetConfig, Router
            from automodel_tpu.serving.server import serve_http

            fcfg = FleetConfig.from_dict(dict(fleet_section or {}))
            scfg = ServeConfig.from_dict(dict(self.cfg.get("serving") or {}))
            n = fcfg.bench_replicas
            per_blocks = fcfg.bench_num_blocks or max(
                scfg.num_blocks // n, scfg.slots * scfg.table_blocks + 2
            )
            # replicas run non-speculative: the fleet A/B prices ROUTING,
            # and N draft pools would multiply the leg's HBM footprint
            rcfg = _dc.replace(
                scfg, num_blocks=per_blocks,
                speculative=_dc.replace(
                    scfg.speculative, enabled=False, draft=None
                ),
            )
            gcfg = getattr(self, "_gen_section", None)
            gen_cfg = GenerationConfig.from_dict(
                {
                    k: v
                    for k, v in dict(gcfg or {}).items()
                    if k not in ("prompts", "prompt_ids", "tokenizer", "enabled")
                }
            )
            auto = self.auto
            params0 = auto.params
            auto.params = self.state.params
            try:
                vocab = int(self.model.config.vocab_size)
                arrivals = self._poisson_arrivals(scfg)
                rng = np.random.default_rng(1)
                for i in range(n):
                    e = ServingEngine(auto, rcfg, gen_cfg)
                    # warm: compile outside the window, flip /readyz true
                    e.submit(
                        rng.integers(
                            1, vocab, size=len(arrivals[0][1])
                        ).tolist(),
                        max_new_tokens=2,
                    )
                    e.run()
                    srv, loop = serve_http(e, None, port=0)
                    threading.Thread(
                        target=srv.serve_forever, daemon=True
                    ).start()
                    engines.append(e)
                    servers.append(srv)
                    loops.append(loop)
                router = Router(
                    FleetConfig.from_dict({
                        **{
                            k: v for k, v in dict(fleet_section or {}).items()
                            if k not in ("replicas", "dns", "port")
                        },
                        "replicas": [
                            {
                                "url": f"http://127.0.0.1:{s.server_address[1]}",
                                "name": f"bench-r{i}",
                            }
                            for i, s in enumerate(servers)
                        ],
                        "block_size": scfg.block_size,
                        "probe_interval_s": 0.25,
                    })
                ).start()
                _, fstats = router.run_workload(arrivals)
            finally:
                auto.params = params0
        except Exception as e:
            return {
                **nulls,
                "serve_fleet_failure": f"{type(e).__name__}: {e}",
            }
        finally:
            if router is not None:
                router.close()
            for srv in servers:
                srv.shutdown()
                srv.server_close()
            for loop in loops:
                loop.close()
            for e in engines:
                e.release_pools()
        if fstats["requests"] == 0:
            return {
                **nulls,
                "serve_fleet_failure": (
                    "no routed request completed: "
                    f"{fstats['failed_requests']} failed"
                ),
            }
        fleet_tps = fstats["fleet_tokens_per_s"]
        return {
            "serve_fleet_tokens_per_s": round(fleet_tps, 2),
            "serve_route_prefix_hit_rate": round(fstats["prefix_hit_rate"], 4),
            "serve_fleet_retries": fstats["retries"],
            "serve_fleet_replicas": n,
            "serve_fleet_requests": fstats["requests"],
            "serve_fleet_kv_handoffs": fstats["kv_handoffs"],
            "serve_fleet_ab": {
                "fleet_tokens_per_s": round(fleet_tps, 2),
                "single_tokens_per_s": single_tps,
                "speedup": (
                    round(fleet_tps / single_tps, 3)
                    if isinstance(single_tps, (int, float)) and single_tps > 0
                    else None
                ),
            },
            "serve_fleet_failure": None,
        }


def main(cfg: ConfigNode) -> dict:
    recipe = BenchmarkingRecipeForNextTokenPrediction(cfg)
    recipe.setup()
    return recipe.run_benchmark()
