"""LLM finetune / pretrain recipe.

Parity: TrainFinetuneRecipeForNextTokenPrediction
(recipes/llm/train_ft.py:803) — YAML-driven setup of mesh, model, data,
optimizer, step scheduler, checkpointing, metric logging, then the
train/validation loop. The torch version's hot loop
(_run_train_optim_step:1284) is here ONE jitted step: microbatch scan +
global token-count normalization + clip + optimizer update (see
training/train_step.py).

YAML sections (format-compatible in spirit with the reference recipes):
  model.pretrained_model_name_or_path | model.hf_config, model.backend
  distributed.{tp,cp,pp,ep,dp_shard,dp_replicate}
  dataset._target_ ..., validation_dataset (optional)
  dataloader.{global_batch_size, shuffle, ...}
  step_scheduler.{grad_acc_steps,num_epochs,max_steps,ckpt_every_steps,...}
  optimizer.{name,lr,...}   loss_fn.{name,...}
  checkpoint.{enabled,checkpoint_dir,...}   logging.{metrics_path}   seed
"""

from __future__ import annotations

import logging
import time
from typing import Any, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from automodel_tpu import auto_model
from automodel_tpu.checkpoint.checkpointer import Checkpointer, CheckpointingConfig
from automodel_tpu.config.loader import ConfigNode
from automodel_tpu.data.collators import stack_microbatches
from automodel_tpu.data.loader import DataLoader, place_batch
from automodel_tpu.data.prefetch import (
    PrefetchConfig,
    PrefetchingLoader,
    PreparedBatch,
)
from automodel_tpu.loggers.log_utils import setup_logging
from automodel_tpu.loggers.metric_logger import MetricLogger
from automodel_tpu.optim.builders import build_optimizer, init_opt_state
from automodel_tpu.optim.scheduler import build_lr_schedule
from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
from automodel_tpu.resilience import NonFiniteError, Resilience, TrainingPreempted
from automodel_tpu.resilience.manifest import step_dir_key
from automodel_tpu.training.rng import StatefulRNG
from automodel_tpu.training.step_scheduler import StepScheduler
from automodel_tpu.training.train_state import TrainState
from automodel_tpu.training.train_step import (
    build_eval_step,
    build_train_step,
    make_causal_lm_loss,
)

logger = logging.getLogger(__name__)


class _RollbackRequested(Exception):
    """Internal control flow: the non-finite policy asked for a restore of
    the last verified checkpoint (caught inside the crash guard, so it never
    reaches the flight recorder as a crash)."""

    def __init__(self, fail_step: int):
        super().__init__(f"rollback requested at step {fail_step}")
        self.fail_step = fail_step


# step metrics that ``train.counts`` reports beside the step's input tokens:
# the picks on held experts (``MoEConfig.held_experts``), a multi-token-
# prediction module's own loss and the largest error of a hyper-connection
# residual map's row sums (models/xing4)
COUNTED_METRICS = ("held_expert_rows", "mtp_loss", "mhc_res_row_err")


class _StepWithCounts:
    """The jitted train step (every attribute of it: ``lower``, ``trace``),
    which on a call also hands the recipe what ``train.counts`` reports."""

    def __init__(self, step, recipe):
        self._step, self._recipe = step, recipe

    def __call__(self, state, batch):
        out = self._step(state, batch)
        counts = {k: out[1][k] for k in COUNTED_METRICS if k in out[1]}
        if counts:
            self._recipe._counts_pending = (batch["input_ids"].size, counts)
        return out

    def __getattr__(self, name):
        return getattr(self._step, name)


class TrainFinetuneRecipeForNextTokenPrediction:
    def __init__(self, cfg: ConfigNode):
        self.cfg = cfg

    # -- setup --------------------------------------------------------------
    def setup(self) -> None:
        cfg = self.cfg
        # goodput ledger epoch: attempt wall clock starts HERE, so model
        # build / mesh / data setup land in the `startup` segment
        self._setup_t0 = time.time()
        setup_logging()
        self.rng = StatefulRNG(seed=cfg.get("seed", 42))

        dist = cfg.get("distributed", ConfigNode())
        # distributed.platform pins the device platform — e.g. `cpu` to run
        # SPMD recipes on virtual host devices (the reference's gloo-backend
        # CPU test path, init_utils.py:136-140)
        platform = dist.get("platform", None)
        devices = jax.devices(platform) if platform else None
        self.mesh_ctx = build_mesh(MeshConfig.from_section(dist), devices=devices)
        logger.info("mesh: %s", dict(self.mesh_ctx.mesh.shape))

        # model
        mcfg = cfg.model
        backend = dict(mcfg.get("backend", {}) or {})
        self.auto = self._build_auto(mcfg, backend)
        self.model = self.auto.model
        # zigzag CP: the ring masks tokens as if the DATA is in zigzag
        # order, so the loop must permute every seq-axis leaf to match
        self._zigzag_cp = (
            self.mesh_ctx.size("cp")
            if backend.get("cp_zigzag") and self.mesh_ctx.size("cp") > 1
            else 0
        )

        # peft (LoRA): trainable tree = adapters only; base closed over frozen
        pcfg = cfg.get("peft")
        self.peft_config = None
        if pcfg is not None:
            from automodel_tpu.peft import (
                PeftConfig,
                init_lora_params,
                lora_sharding_rules,
                num_trainable,
            )

            pkw = dict(pcfg or {})
            pkw.pop("_target_", None)
            # peft.qlora: {blocksize, ...} → NF4-quantize the frozen base
            self._qlora_cfg = pkw.pop("qlora", None)
            self.peft_config = PeftConfig(**pkw)
            lora = init_lora_params(
                jax.random.key(cfg.get("seed", 42) + 1), self.auto.params, self.peft_config
            )
            from automodel_tpu.parallel.plans import shard_params

            lora = shard_params(
                self.mesh_ctx,
                lora,
                lora_sharding_rules(self.model.sharding_rules, lora),
            )
            logger.info("LoRA: %d trainable params", num_trainable(lora))
            trainable = lora
        else:
            trainable = self.auto.params

        # optimizer + schedule
        ocfg = dict(cfg.get("optimizer", {}) or {"name": "adamw"})
        ocfg.pop("_target_", None)
        sched_cfg = dict(ocfg.get("lr_schedule") or {})
        self.lr_schedule = build_lr_schedule(lr=ocfg.get("lr", 1e-4), **sched_cfg)
        self.optimizer = self._wrap_optimizer(build_optimizer(**ocfg), trainable)
        opt_state = init_opt_state(self.optimizer, trainable, self.mesh_ctx)
        self.state = TrainState.create(trainable, opt_state)

        # loss + steps; a family may declare its own default loss (reference
        # nemotron_parse is the only family shipping one — its coordinate-
        # weighted CE) which explicit YAML settings override
        lcfg = dict(cfg.get("loss_fn", {}) or {})
        lcfg.pop("_target_", None)
        family_loss = getattr(self.model, "loss_name", None)
        loss_name = lcfg.pop("name", family_loss or "masked_ce")
        if family_loss is not None and loss_name == family_loss:
            lcfg = {**self.model.loss_kwargs(), **lcfg}
        self.loss_fn = make_causal_lm_loss(
            self.model, loss=loss_name, constrain=self.auto.constrain, **lcfg
        )
        qat_cfg = cfg.get("qat")
        if qat_cfg is not None:
            if self.peft_config is not None:
                raise ValueError(
                    "qat: and peft: are mutually exclusive — QAT fake-"
                    "quantizes the TRAINED weights; with LoRA the base is "
                    "frozen (use peft.qlora for a quantized base instead)"
                )
            from automodel_tpu.quantization import QATConfig, make_qat_loss_fn

            qd = dict(qat_cfg or {})
            qd.pop("_target_", None)
            self.loss_fn = make_qat_loss_fn(self.loss_fn, QATConfig(**qd))
        if self.peft_config is not None:
            from automodel_tpu.peft import make_lora_loss_fn

            base_tree = self.auto.params
            if self._qlora_cfg is not None:
                from automodel_tpu.quantization import QLoRAConfig, nf4_quantize_tree

                qc = QLoRAConfig(
                    **({} if self._qlora_cfg is True else dict(self._qlora_cfg))
                )
                base_tree = nf4_quantize_tree(self.auto.params, qc, ctx=self.mesh_ctx)
                # drop the full-precision base so HBM really holds the packed
                # codes only (the loss binds base_tree; adapters checkpoint
                # separately). Models that consume packed kernels natively
                # (llama _maybe_nf4) dequantize PER LAYER inside the scan and
                # need no base_transform — a whole-tree dequant at the loss
                # top materializes every layer at once (15.3GB for 8B).
                # Other families still dequantize at the loss top (correct,
                # memory-bounded by model size).
                self.auto.params = None
                logger.info("QLoRA: NF4-quantized base (blocksize=%d)", qc.blocksize)
            base_transform = None
            if self._qlora_cfg is not None and not getattr(
                self.model, "supports_packed_nf4", False
            ):
                from automodel_tpu.quantization import nf4_dequantize_tree

                base_transform = nf4_dequantize_tree
            # subclasses that REPLACE the loss (kd.py) re-wrap with the same
            # frozen base — after this point the full-precision tree may be
            # gone (QLoRA sets auto.params = None above)
            self._lora_base_tree = base_tree
            self._lora_base_transform = base_transform
            self.loss_fn = make_lora_loss_fn(
                self.loss_fn, base_tree, self.peft_config,
                graft_patterns=getattr(self.model, "lora_graft_patterns", ()),
                base_transform=base_transform,
                dropout_seed=cfg.get("seed", 42),
            )
        post_step = getattr(self.model, "post_step_fn", None) if self.peft_config is None else None
        # telemetry.{enabled,anomaly_flags} govern the in-jit anomaly
        # reductions (read here because the step compiles before the
        # Telemetry facade is built below)
        tcfg = dict(cfg.get("telemetry") or {})
        self._anomaly_flags = bool(tcfg.get("enabled", True)) and bool(
            tcfg.get("anomaly_flags", True)
        )
        # resilience: preemption handling + non-finite-step policy + fault
        # injection (resilience/). Built before the step because the `skip`
        # policy and the nan-grads injection live INSIDE the jit.
        self.resilience = Resilience.from_config(
            cfg.get("fault_tolerance"), cfg.get("fault_injection")
        )
        if (
            self.resilience.config.enabled
            and self.resilience.on_nonfinite == "raise"
            and not self._anomaly_flags
        ):
            # skip/rollback force the in-jit flag themselves; the default
            # raise policy respects the anomaly_flags opt-out — but that
            # leaves non-finite steps undetected, which deserves a shout
            logger.warning(
                "telemetry.anomaly_flags is disabled: fault_tolerance."
                "on_nonfinite=raise cannot detect non-finite steps — "
                "divergence will train through silently"
            )
        self.train_step = self._make_train_step(
            self.loss_fn, post_step_fn=post_step,
            grad_mask=getattr(self, "grad_mask", None),
        )
        # eval must not apply LoRA dropout — use the train=False variant
        self.eval_step = build_eval_step(
            getattr(self.loss_fn, "eval_loss_fn", self.loss_fn)
        )

        # data
        self.dataloader = self._build_dataloader(cfg.get("dataset"), cfg.get("dataloader", {}))
        self.val_dataloader = None
        if cfg.get("validation_dataset") is not None:
            self.val_dataloader = self._build_dataloader(
                cfg.get("validation_dataset"), cfg.get("validation_dataloader", cfg.get("dataloader", {}))
            )

        # host-overlap input pipeline (data.prefetch: — docs/performance.md,
        # "Host overlap"): background collate workers + N-deep device
        # prefetch. Wrapped BEFORE the scheduler so every recipe subclass
        # inherits it through the single loop. The facade's state_dict() is
        # the CONSUMPTION cursor (fetch run-ahead is never persisted), so
        # checkpoint resume and the rollback fast-forward stay bit-exact.
        scfg = dict(cfg.get("step_scheduler", {}) or {})
        self.prefetch_config = PrefetchConfig.from_data_section(cfg.get("data"))
        if self.prefetch_config.enabled:
            self.dataloader = PrefetchingLoader(
                self.dataloader,
                self.prefetch_config,
                prepare=self._prepare_group,
                place=self._place_group,
                group_size=int(scfg.get("grad_acc_steps", 1)),
            )
            if self.val_dataloader is not None:
                self.val_dataloader = PrefetchingLoader(
                    self.val_dataloader,
                    self.prefetch_config,
                    # parity with run_validation's sync branch, which stacks
                    # WITHOUT the zigzag-CP permutation — toggling prefetch
                    # must never change a val loss
                    prepare=self._prepare_val_group,
                    place=self._place_group,
                    group_size=1,
                )
            logger.info(
                "prefetch: depth=%d collate_workers=%d",
                self.prefetch_config.depth, self.prefetch_config.collate_workers,
            )

        # step scheduler + signal wiring: with resilience enabled (default),
        # SIGTERM means PREEMPTION — the handler flips the preempted flag and
        # asks the scheduler to stop at the next step boundary, after which
        # the loop saves an emergency checkpoint and exits with the requeue
        # code. With resilience disabled, the scheduler's own (chaining)
        # graceful-shutdown handler is installed as before.
        self.step_scheduler = StepScheduler(dataloader=self.dataloader, **scfg)
        if self.resilience.preemption is not None:
            self.resilience.preemption.on_preempt = self.step_scheduler.request_shutdown
            self.resilience.install()
        else:
            self.step_scheduler.install_signal_handler()

        # run-artifact routing: everything a run writes (metrics JSONL,
        # flight recorder, watchdog stacks, trace dirs, triggered captures)
        # lands under ONE per-run `output_dir` — never the CWD. An explicit
        # logging.metrics_path still wins (tests, operators pinning paths);
        # the default used to be ./train_metrics.jsonl, which littered the
        # repo root and mixed runs. The default is keyed on a CONFIG
        # fingerprint, not a timestamp: a preempted-and-requeued run (or
        # its multi-host peers) must land in the SAME dir so the metrics
        # JSONL and flight-recorder evidence stay one continuous record
        # across restarts (JSONL appends are flock-guarded, so sharing is
        # safe by construction).
        import zlib
        from pathlib import Path

        out_dir = cfg.get("output_dir")
        if out_dir is None:
            import json as _json

            fp = zlib.crc32(
                _json.dumps(cfg.to_dict(), sort_keys=True, default=str).encode()
            )
            out_dir = str(Path("runs") / f"run_{fp:08x}")
        self.output_dir = Path(out_dir)

        # metrics (JSONL + optional wandb/MLflow fan-out,
        # reference train_ft.py:844-853) — built BEFORE the checkpointer so
        # the startup auto-resume can stamp its resume marker
        log_cfg = cfg.get("logging", ConfigNode())
        wandb_run, sinks = None, []
        if log_cfg.get("wandb") is not None:
            from automodel_tpu.loggers.wandb_utils import setup_wandb

            wandb_run = setup_wandb(
                config=cfg.to_dict(), **dict(log_cfg.get("wandb") or {})
            )
        if log_cfg.get("mlflow") is not None:
            from automodel_tpu.loggers.mlflow_utils import MLflowLogger

            sinks.append(MLflowLogger(**dict(log_cfg.get("mlflow") or {})))
        metrics_path = Path(
            log_cfg.get("metrics_path", str(self.output_dir / "train_metrics.jsonl"))
        )

        # goodput run ledger (telemetry/goodput.py): the append-only
        # goodput.jsonl segment log beside the metrics JSONL (an explicit
        # logging.metrics_path wins, like the flight recorder), chained
        # across restart attempts — a new attempt record is written HERE
        # (closing a SIGKILL'd predecessor's tail), and its
        # attempt_id/restart_count envelope stamps every metrics record +
        # the flight-recorder fingerprint below
        from automodel_tpu.telemetry.goodput import GoodputLedger

        self.ledger = GoodputLedger(
            metrics_path.parent / "goodput.jsonl",
            t_start=self._setup_t0,
            enabled=bool(tcfg.get("enabled", True))
            and bool(tcfg.get("goodput", True)),
        )
        self.metric_logger = MetricLogger(
            str(metrics_path),
            wandb_run=wandb_run,
            sinks=sinks,
            # attempt identity on every record: `report`/`goodput` join and
            # order a requeued run's appended records deterministically
            envelope=self.ledger.envelope if self.ledger.enabled else None,
        )

        # telemetry: anomaly flags ride the jitted step (train_step.py);
        # this facade adds the step-time split, compile-event stamps, the
        # periodic memory census, and the crash flight recorder. On by
        # default — no `telemetry:` section required.
        from automodel_tpu.telemetry import (
            Telemetry,
            build_fingerprint,
            device_report,
        )

        # first record of the JSONL, once per run
        report = device_report(self.mesh_ctx, getattr(self.model, "backend", None))
        logger.info("device report: %s", report)
        self.metric_logger.log(report)

        fingerprint = build_fingerprint(cfg.to_dict(), self.mesh_ctx)
        if self.ledger.enabled:
            # the flight-recorder dump must name the attempt it belongs to
            fingerprint["attempt"] = dict(self.ledger.envelope)
        self.telemetry = Telemetry.from_config(
            cfg.get("telemetry"),
            fingerprint=fingerprint,
            default_recorder_path=str(
                self.metric_logger.path.parent / "flight_recorder.json"
            ),
            default_trace_dir=str(self.output_dir / "trace"),
        )

        # profiling pillar (telemetry/profiling/): cost-attributed MFU on
        # the log records (computed once at step 1, folded per window) and
        # the anomaly-armed triggered capture. On by default — a cheap host
        # trace at step 1, nothing on the hot path.
        from automodel_tpu.telemetry.profiling import ProfilingConfig

        self.profiling = ProfilingConfig.from_dict(dict(cfg.get("profiling") or {}))
        self._step_cost: Optional[dict] = None
        self._flops_per_token: Optional[float] = None
        self.telemetry.attach_profiling(
            self.profiling,
            capture_dir=str(self.output_dir / "captures"),
            event_hook=self._guard_event,
        )

        # metrics_server: a standalone Prometheus scrape port (the serving
        # server mounts /metrics on its own HTTP front). Section presence
        # opts in — a default port would collide across concurrent runs.
        self._prom = None
        self._prom_server = None
        if cfg.get("metrics_server") is not None:
            from automodel_tpu.telemetry.prometheus import (
                MetricsServerConfig,
                TrainMetricsExporter,
                start_metrics_server,
            )

            mscfg = MetricsServerConfig.from_dict(dict(cfg.get("metrics_server") or {}))
            if mscfg.enabled:
                try:
                    exporter = TrainMetricsExporter()
                    self._prom_server = start_metrics_server(
                        exporter.registry, mscfg.port, mscfg.host
                    )
                    self._prom = exporter
                    logger.info(
                        "metrics server listening on %s:%d",
                        mscfg.host, self._prom_server.server_address[1],
                    )
                except OSError as e:
                    # a busy scrape port (two runs on one host, a stale
                    # process) must never kill training — observability is
                    # best-effort everywhere else in this subsystem too
                    logger.warning(
                        "metrics server failed to bind %s:%d (%s) — "
                        "continuing WITHOUT a scrape port",
                        mscfg.host, mscfg.port, e,
                    )

        # distributed guard (resilience/guard.py): hang watchdog petted at
        # every step boundary, cross-host consensus at log/checkpoint/
        # shutdown boundaries, timed barriers at the multi-host sync
        # points. On by default; stacks/desync evidence lands next to the
        # metrics JSONL and in the flight recorder.
        from automodel_tpu.resilience.guard import DistributedGuard

        self.guard = DistributedGuard.from_config(
            cfg.get("distributed_guard"),
            fingerprint=self.telemetry.flight_recorder.fingerprint
            if self.telemetry.flight_recorder is not None
            else None,
            flight_recorder=self.telemetry.flight_recorder,
            metric_logger=self.metric_logger,
            default_stacks_path=str(
                self.metric_logger.path.parent / "watchdog_stacks.txt"
            ),
        )

        # in-training eval generation (generation: YAML section,
        # docs/generation.md): sample completions at validation boundaries
        # through the KV-cache inference engine and log them to the JSONL
        # (gen_samples + ttft_s/decode_tps). Never load-bearing: a skip is
        # logged with its reason.
        self._gen_engine = None
        self._gen_prompts = None
        self._gen_prompt_ids = None
        if cfg.get("generation") is not None:
            self._setup_eval_generation(dict(cfg.get("generation") or {}))

        # checkpointing — AFTER telemetry, so the event hook is live for the
        # startup auto-resume: a walk-back past a corrupt newest checkpoint
        # during _restore() must reach the flight recorder
        ccfg = dict(cfg.get("checkpoint", {}) or {})
        self.checkpointer = Checkpointer(CheckpointingConfig(**ccfg)) if ccfg.get(
            "enabled", False
        ) else None
        # best-val tracking: the newest validation metric at save time; a
        # save that improves on BEST.json gets the best marker (checkpoint
        # polish, reference base_recipe.py:768-850)
        self._last_val_metric: Optional[float] = None
        if self.checkpointer is not None:
            self.checkpointer.event_hook = self.telemetry.record_step
            # save/drain/restore wall time → goodput segments + the
            # ckpt_save_s/ckpt_drain_s/ckpt_restore_s stamps on the next
            # log record (+ /metrics histograms via the exporter)
            self.checkpointer.timing_hook = self.ledger.on_ckpt_timing
            # multi-host: at SIGTERM time drop a marker into the shared
            # checkpoint root so peer hosts dying of broken collectives
            # exit with the requeue code too (cli/app.py checks it)
            self.resilience.arm_peer_marker(self.checkpointer.root)
            # multi-host commit discipline: no host writes the manifest
            # until every host's save drained (timed — a dead peer turns
            # the commit into a diagnosed SyncTimeout, dir stays
            # uncommitted)
            self.checkpointer.commit_barrier = self.guard.barrier
        # the guard learns the runtime facts that exist only now: requeue
        # eligibility (a hang with nothing committed must exit 1, not loop
        # at zero progress), the shared root for the peer marker, where
        # desync events go, and the params tree for the jitted checksum
        self.guard.bind_runtime(
            requeue_eligible=(
                (lambda: self.checkpointer.latest_committed_dir() is not None)
                if self.checkpointer is not None
                else (lambda: False)
            ),
            peer_marker_root=(
                str(self.checkpointer.root) if self.checkpointer else None
            ),
            event_hook=self._guard_event,
            params_example=self.state.params,
        )
        if self.checkpointer and self.checkpointer.has_checkpoint():
            self._restore()
            # chain the ledger: the previous attempt's step time past the
            # step we actually resumed from is preemption-lost work
            self.ledger.on_resume(int(self.state.step))
        elif self.ledger.restart_count > 0:
            # a restarted attempt with NOTHING to resume from (killed
            # before any commit): the predecessor's entire stepped
            # progress is preemption-lost, not committed work
            self.ledger.on_resume(0)

    def _guard_event(self, rec: dict) -> None:
        """Anomaly evidence (desync, hang, trace_capture) goes to every
        sink: the flight recorder (post-mortem bundle), the metrics JSONL
        (for `report`), and the /metrics event counters when a scrape port
        is up."""
        self.telemetry.record_step(rec)
        try:
            self.metric_logger.log(dict(rec), step=rec.get("step"))
        except Exception:  # evidence is best-effort; the abort is not
            pass
        # a `skipped` trace_capture stamp is evidence of a capture that did
        # NOT happen — it must not advance the captures counter
        if self._prom is not None and rec.get("event") and not rec.get("skipped"):
            self._prom.event(str(rec["event"]))

    def _setup_eval_generation(self, gcfg: dict) -> None:
        from automodel_tpu.generation.engine import (
            GenerationConfig,
            GenerationEngine,
            GenerationUnsupported,
            resolve_tokenizer,
        )

        gcfg.pop("_target_", None)
        if gcfg.pop("enabled", True) is False:
            return
        prompts = gcfg.pop("prompts", None)
        prompt_ids = gcfg.pop("prompt_ids", None)
        tok_cfg = gcfg.pop("tokenizer", None)
        if self.peft_config is not None:
            # the trainable tree is the adapter, not decodable weights;
            # merged-adapter generation is a follow-up
            logger.warning(
                "generation: peft adapters are not supported (merge first)"
            )
            return
        # same resolution ladder as the generate CLI; the checkpoint
        # fallback only matters when text prompts are configured
        tokenizer = resolve_tokenizer(
            tok_cfg,
            self.cfg.model.get("pretrained_model_name_or_path")
            if prompts is not None
            else None,
        )
        try:
            self._gen_engine = GenerationEngine(
                self.auto, GenerationConfig.from_dict(gcfg), tokenizer=tokenizer
            )
        except GenerationUnsupported as e:
            logger.warning("generation: %s", e)
            return
        if prompts is not None and tokenizer is None:
            logger.warning(
                "generation.prompts given without generation.tokenizer — "
                "use generation.prompt_ids for tokenizer-less runs"
            )
            prompts = None
        self._gen_prompts = list(prompts) if prompts else None
        self._gen_prompt_ids = (
            [[int(t) for t in p] for p in prompt_ids] if prompt_ids else None
        )

    def _log_eval_generation(self) -> None:
        """Sample completions with the CURRENT weights and log them. A
        generation failure is logged and swallowed — eval sampling must
        never kill a training run."""
        eng = self._gen_engine
        if eng is None or (self._gen_prompts is None and self._gen_prompt_ids is None):
            return
        try:
            if self._gen_prompt_ids is not None:
                out = eng.generate_ids(self._gen_prompt_ids, params=self.state.params)
                shown = [" ".join(map(str, p)) for p in self._gen_prompt_ids]
                texts = [" ".join(map(str, t)) for t in out["tokens"]]
            else:
                out = eng.generate(self._gen_prompts, params=self.state.params)
                shown, texts = self._gen_prompts, out["texts"]
        except Exception as e:
            logger.warning("eval generation failed: %s", e)
            return
        for p, t in zip(shown, texts):
            logger.info("sample @%d | %s -> %s", self.step_scheduler.step, p, t)
        self.metric_logger.log(
            {
                "event": "generation",
                "gen_samples": [
                    {"prompt": p, "completion": t} for p, t in zip(shown, texts)
                ],
                "ttft_s": out["ttft_s"],
                "decode_tps": out["decode_tps"],
                "gen_tokens": out["gen_tokens"],
                "gen_cache_bytes": out["cache_bytes"],
            },
            step=self.step_scheduler.step,
        )

    def _compute_step_cost(self, batch) -> None:
        """One-time cost attribution of the jitted train step (profiling
        pillar, telemetry/profiling/cost.py): trip-count-aware measured
        FLOPs/bytes + category breakdown + roofline class, traced on host
        (abstract — no device memory). Runs once, inside the step-1 compile
        window; the static summary feeds mfu_measured_pct on every log
        record and is logged whole as a ``cost_attribution`` event."""
        from automodel_tpu.telemetry import profiling as prof

        if not hasattr(self.train_step, "trace"):
            # only a real jit program is attributable (tests wrap the step
            # in plain callables with side effects; tracing those would
            # invoke them an extra time)
            return
        cost = prof.program_cost(
            self.train_step, self.state, batch, program="train_step"
        )
        basis = self.profiling.roofline_basis()
        self._step_cost = {**cost.to_dict(), **prof.roofline(cost, basis)}
        # drop null fields (unknown roofline basis on CPU): the JSONL lint
        # treats a null numeric without a _nonfinite marker as corruption
        rec = {
            "event": "cost_attribution",
            "program": "train_step",
            **{k: v for k, v in self._step_cost.items() if v is not None},
        }
        self.telemetry.record_step({**rec, "ts": time.time()})
        logger.info(
            "train_step traced: pallas_kernels=%s mosaic_calls=%s",
            cost.pallas_kernels, cost.mosaic_calls,
        )
        try:
            self.metric_logger.log(rec)
        except Exception:
            pass

    def _fold_mfu(self, metrics: dict) -> dict:
        """Per-log-window MFU, both provenances (docs/performance.md):
        ``mfu_pct`` from the analytic flops_utils law × observed tokens/s;
        ``mfu_measured_pct`` from the measured step-program FLOPs × the
        amortized step time. Drift between them is signal (a law missing a
        term, a backend computing more than the law assumes, remat)."""
        from automodel_tpu.telemetry import profiling as prof

        basis = self.profiling.roofline_basis()
        peak, _ = basis.resolve()
        tpsd = metrics.get("tps_per_device")
        if (
            self._flops_per_token is not None
            and isinstance(tpsd, (int, float))
            and peak == peak
        ):
            metrics["mfu_pct"] = round(
                100.0 * tpsd * self._flops_per_token / (peak * 1e12), 3
            )
        if self._step_cost is not None and isinstance(
            metrics.get("step_time_s"), (int, float)
        ):
            m = prof.mfu_measured_pct(
                self._step_cost["flops"],
                metrics["step_time_s"],
                self.mesh_ctx.world_size,
                basis,
            )
            if m is not None:
                metrics["mfu_measured_pct"] = round(m, 3)
        return metrics

    def _make_train_step(self, loss_fn, post_step_fn=None, grad_mask=None):
        """Single construction point for the jitted step so every recipe
        subclass that swaps the loss (KD, biencoder, seq-cls) inherits the
        anomaly flags, the non-finite policy, and the fault-injection arm."""
        step = build_train_step(
            loss_fn, self.optimizer, self.lr_schedule, post_step_fn=post_step_fn,
            grad_mask=grad_mask, anomaly_flags=self._anomaly_flags,
            on_nonfinite=self.resilience.on_nonfinite,
            nan_grads_at_step=self.resilience.nan_grads_at_step,
        )
        return _StepWithCounts(step, self)

    def _build_auto(self, mcfg: Any, backend: dict):
        """Subclass hook (biencoder recipe wraps the model)."""
        if mcfg.get("pretrained_model_name_or_path"):
            ov = mcfg.get("hf_config_overrides")
            return auto_model.from_pretrained(
                mcfg.pretrained_model_name_or_path, self.mesh_ctx, backend,
                hf_config_overrides=(
                    ov.to_dict() if isinstance(ov, ConfigNode) else ov
                ),
            )
        hf_config = mcfg.get("hf_config")
        return auto_model.from_config(
            hf_config.to_dict() if isinstance(hf_config, ConfigNode) else hf_config,
            self.mesh_ctx,
            backend,
            seed=self.cfg.get("seed", 42),
        )

    def _wrap_optimizer(self, optimizer: Any, trainable: Any) -> Any:
        """Subclass hook (VLM recipe: freeze-pattern masking)."""
        return optimizer

    def _prepare_group(self, group: list) -> tuple[dict, int]:
        """One grad-acc group of collated microbatches → ([A]-stacked host
        batch with zigzag-CP permutation applied, token count). Shared by
        the sync loop body and the prefetch producer thread, so both paths
        build bit-identical batches. ``train.collate`` on the profiler's
        clock, on whichever thread runs it."""
        with TraceAnnotation("train.collate"):
            stacked = stack_microbatches(group)
            if self._zigzag_cp:
                from automodel_tpu.parallel.cp import apply_zigzag

                stacked = {
                    k: (
                        apply_zigzag(v, self._zigzag_cp, axis=2)
                        if k in ("input_ids", "labels", "position_ids", "segment_ids")
                        else v
                    )
                    for k, v in stacked.items()
                }
            # tps numerator: all *input_ids leaves (biencoder batches carry
            # query_/doc_input_ids instead of a single input_ids)
            n_tokens = int(
                sum(
                    np.prod(v.shape)
                    for k, v in stacked.items()
                    if k.endswith("input_ids") and isinstance(v, np.ndarray)
                )
            )
        return stacked, n_tokens

    def _prepare_val_group(self, group: list) -> tuple[dict, int]:
        """Validation variant of :meth:`_prepare_group`: stack only, no
        zigzag permutation — bit-parity with run_validation's sync branch
        (`place_batch(stack_microbatches([vb]))`)."""
        stacked = stack_microbatches(group)
        n_tokens = int(
            sum(
                np.prod(v.shape)
                for k, v in stacked.items()
                if k.endswith("input_ids") and isinstance(v, np.ndarray)
            )
        )
        return stacked, n_tokens

    def _place_group(self, stacked: dict) -> dict:
        with TraceAnnotation("train.place"):
            self._write_counts()
            return place_batch(self.mesh_ctx, stacked)

    def _write_counts(self) -> None:
        """``train.counts`` on the profiler's clock: the PREVIOUS step's input
        tokens and those of ``COUNTED_METRICS`` its model reports, once that
        step's metrics are ready. Never a barrier: a step still running keeps
        its counts for the next group. A model that reports none of them
        writes nothing."""
        pending = getattr(self, "_counts_pending", None)
        if pending is None or not all(v.is_ready() for v in pending[1].values()):
            return
        self._counts_pending = None
        counts = {k: int(v) if np.issubdtype(v.dtype, np.integer) else float(v)
                  for k, v in pending[1].items()}
        with TraceAnnotation("train.counts", tokens=int(pending[0]), **counts):
            pass

    def _close_prefetch(self) -> None:
        """Join the prefetch producers and drop their run-ahead (idempotent;
        the consumption cursor survives, so a state_dict() taken after the
        close — the emergency checkpoint — is still exact)."""
        for dl in (getattr(self, "dataloader", None), getattr(self, "val_dataloader", None)):
            if isinstance(dl, PrefetchingLoader):
                dl.close()

    def _build_dataloader(self, dataset_cfg: Any, dl_cfg: Any) -> DataLoader:
        if dataset_cfg is None:
            raise ValueError("A `dataset:` section is required")
        dataset = dataset_cfg.instantiate() if isinstance(dataset_cfg, ConfigNode) else dataset_cfg
        dl = dict(dl_cfg or {})
        dl.pop("_target_", None)
        return DataLoader(dataset, seed=self.cfg.get("seed", 42), **dl)

    # -- checkpoint ---------------------------------------------------------
    def save_checkpoint(self) -> None:
        if not self.checkpointer:
            return
        extra = {
            "dataloader": self.dataloader.state_dict(),
            "step_scheduler": self.step_scheduler.state_dict(),
            "rng": self.rng.state_dict(),
        }
        # with LoRA, state.params is the adapter tree: export HF-PEFT adapter
        # artifacts instead of a consolidated base model (reference: PeftAddon)
        hf_export = None if self.peft_config else (self.auto.adapter, self.state.params)
        out = self.checkpointer.save(
            self.state,
            epoch=self.step_scheduler.epoch,
            step=self.step_scheduler.step,
            extra_state=extra,
            hf_export=hf_export,
            config_snapshot=self.cfg.to_dict(),
            hf_meta={
                "hf_config": self.auto.hf_config,
                "source_dir": self.auto.source_dir,
            },
            layout_markers=getattr(self.model, "native_layout_markers", None),
        )
        if self.peft_config is not None:
            from automodel_tpu.peft import export_hf_peft

            export_hf_peft(
                jax.device_get(self.state.params),
                self.peft_config,
                self.auto.adapter,
                out / "hf_adapter",
            )
        # best-val marker: only SAVED checkpoints can be best (the marker
        # must always point at a restorable tree). BEST.json is re-read so a
        # resumed run never clobbers a better pre-preemption best.
        if self._last_val_metric is not None:
            best = self.checkpointer.best_info()
            if best is None or self._last_val_metric < float(best["value"]):
                self.checkpointer.mark_best(out, "val_loss", self._last_val_metric)
        logger.info("saved checkpoint at step %d", self.step_scheduler.step)

    def _restore(self, before_step: Optional[int] = None) -> None:
        # Abstract target WITH shardings so orbax restores every array —
        # params AND optimizer moments — directly onto its current-mesh shard
        # (adam state is 2x model size; restoring it replicated would OOM).
        # Param-path regexes match opt_state paths too (mu/nu mirror the param
        # tree as subtrees), so one rule set covers both.
        from automodel_tpu.parallel.plans import make_param_shardings

        abstract = jax.eval_shape(lambda: self.state)
        shardings = make_param_shardings(self.mesh_ctx, abstract, self.model.sharding_rules)
        abstract = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            abstract,
            shardings,
        )
        state, extra = self.checkpointer.load(
            abstract,
            expected_layout_markers=getattr(
                self.model, "native_layout_markers", None
            ),
            before_step=before_step,
        )
        self.state = state
        if "dataloader" in extra:
            self.dataloader.load_state_dict(extra["dataloader"])
        if "step_scheduler" in extra:
            self.step_scheduler.load_state_dict(extra["step_scheduler"])
        if "rng" in extra:
            self.rng.load_state_dict(extra["rng"])
        logger.info("restored checkpoint at step %d", int(self.state.step))
        # stamp the resume into the JSONL: step numbers may legitimately go
        # backwards after this (walk-back / rollback retraining), and the
        # report linter only excuses a rewind that follows such a marker
        if getattr(self, "metric_logger", None) is not None:
            self.metric_logger.log(
                {"event": "resume", "resumed_from_step": int(self.state.step)}
            )

    # -- train loop ---------------------------------------------------------
    def run_train_validation_loop(self) -> dict:
        """Timing semantics (docs/observability.md): non-log steps dispatch
        asynchronously, so per-step wall time is only observable at a log-
        step barrier — a naive per-step `dt` charges ALL queued device work
        to the log step (inflating step_time_s, deflating tps whenever
        log_every > 1). Each log record therefore reports the WINDOW since
        the last barrier, amortized: ``step_time_s`` = window seconds /
        ``steps_spanned``, ``tps`` = window tokens / window seconds. The
        alternative (blocking every step) would serialize host dispatch
        against device work; amortization keeps the numbers honest without
        touching the hot path. Step 1 blocks immediately and is reported as
        ``compile_time_s`` (XLA compile dominates it), excluded from every
        throughput window. Windows also restart after validation/checkpoint
        pauses so their wall time is never charged to training steps.

        Resilience semantics (docs/fault_tolerance.md): a preemption signal
        drains the loop at the next step boundary, then the end-of-loop save
        below becomes the EMERGENCY checkpoint — committed (manifest written,
        async save drained) before ``TrainingPreempted`` unwinds to the CLI,
        which exits with the requeue code. A non-finite step is detected one
        step late (the flag is fetched from the PREVIOUS step's metrics
        after dispatching the current one, so detection never stalls async
        dispatch) and handled per ``fault_tolerance.on_nonfinite``; rollback
        restores the last verified checkpoint and fast-forwards the
        dataloader past the offending window."""
        tel, res = self.telemetry, self.resilience
        self.guard.start()
        try:
            try:
                with tel.crash_guard():
                    last = self._train_loop_with_rollback(tel)
            finally:
                tel.close()
                # preemption drain discipline: join the prefetch workers
                # BEFORE the emergency save below — a producer mid-
                # device_put would contend with the save's device barrier,
                # and its run-ahead must be dropped (not persisted: the
                # consumption cursor already excludes it)
                self._close_prefetch()
            if self.checkpointer:
                if not res.preempted or res.config.emergency_checkpoint:
                    # drain + commit any in-flight cadence save FIRST, then
                    # skip the save when it already covers this optimizer
                    # step: save() begins by UNCOMMITTING the target dir, so
                    # re-saving would destroy the newest good checkpoint and
                    # restart a multi-GB upload inside the preemption grace
                    # window. Compare STEP numbers, not full dir paths —
                    # StepScheduler increments epoch before the loop exits,
                    # so a cadence save at epoch_E_step_S must still match
                    # when the scheduler now reads epoch E+1 (step is a
                    # global counter; same step == same param state).
                    with self.guard.phase("checkpoint"):
                        self.checkpointer.wait()
                        latest = self.checkpointer.latest_committed_dir()
                        if (
                            latest is None
                            or step_dir_key(latest)[1] != self.step_scheduler.step
                        ):
                            # the end-of-loop/emergency save is a commit
                            # point like any other: hosts must agree before
                            # the manifest lands
                            self.guard.pre_commit(
                                self.step_scheduler.step, self.state.params
                            )
                            self.save_checkpoint()
            # all hosts drain together (timed): a peer that died during its
            # final save surfaces as a diagnosed SyncTimeout, not a silent
            # per-host exit skew
            with self.guard.phase("shutdown"):
                self.guard.barrier("shutdown")
        finally:
            # ALWAYS drain + COMMIT any in-flight async save — even when the
            # loop died (e.g. NonFiniteError): a finished upload without its
            # manifest would be discarded as an uncommitted leftover on
            # restart. Signal handlers are restored only AFTER the emergency
            # save: a second SIGTERM during the save must keep hitting the
            # chaining handler, not the default terminate.
            try:
                if self.checkpointer:
                    with self.guard.phase("checkpoint"):
                        self.checkpointer.close()
            finally:
                # the end-of-loop/emergency save and final drain stamped
                # ckpt timings AFTER the last log record — flush them (and
                # any boundary time with no following record) as one
                # closing `goodput_tail` record so the JSONL totals and
                # /metrics histograms cover the whole run (BEFORE the
                # scrape server below shuts down, or the final save's
                # observations would never be scrapeable)
                tail = self.ledger.pop_pending()
                excluded = getattr(self, "_tail_excluded_s", 0.0)
                if excluded > 0:
                    tail["window_excluded_s"] = round(excluded, 6)
                    self._tail_excluded_s = 0.0
                if tail:
                    try:
                        self.metric_logger.log(
                            {"event": "goodput_tail", **tail},
                            step=self.step_scheduler.step,
                        )
                    except Exception:  # accounting is best-effort at exit
                        pass
                    if self._prom is not None:
                        self._prom.update(tail)
                # even when the final drain raises: a live watchdog thread
                # in an embedding process (tests, notebooks) would fire
                # minutes later and os._exit it
                self.guard.close()
                res.close()
                self.step_scheduler.restore_signal_handlers()
                if self._prom_server is not None:
                    self._prom_server.shutdown()
                # close the goodput attempt LAST — the final drain above is
                # the last accounted segment. A hard kill skips this close;
                # the next attempt (or the CLI) infers the tail instead.
                import sys as _sys

                self.ledger.close(
                    reason="preempted" if res.preempted
                    else ("crash" if _sys.exc_info()[0] is not None else "exit")
                )
        if res.preempted:
            # run-LOCAL committed dir only: latest_dir()'s restore_from
            # bootstrap fallback must not make a nothing-committed run look
            # requeue-eligible — that loops at zero net progress. Without a
            # checkpoint_dir, TrainingPreempted maps to a REAL failure exit.
            out = (
                self.checkpointer.latest_committed_dir()
                if self.checkpointer
                else None
            )
            raise TrainingPreempted(
                self.step_scheduler.step, str(out) if out else None
            )
        return last

    def _train_loop_with_rollback(self, tel) -> dict:
        while True:
            try:
                return self._train_loop_body(tel, restarted=self.resilience.rollbacks > 0)
            except _RollbackRequested as rb:
                self._rollback(rb.fail_step)

    def _rollback(self, fail_step: int) -> None:
        """on_nonfinite=rollback: restore the last VERIFIED checkpoint (the
        walk-back in Checkpointer.load) and fast-forward the dataloader past
        the offending window so the retrained steps see fresh data."""
        if not (self.checkpointer and self.checkpointer.has_checkpoint()):
            raise NonFiniteError(
                f"non-finite step {fail_step}: rollback requested but no "
                "checkpoint is available (enable checkpointing or use "
                "on_nonfinite: skip)"
            )
        self.telemetry.record_step(
            {"event": "rollback", "fail_step": fail_step, "ts": time.time()}
        )
        # quiesce any in-flight async save before reading the tree back
        self.checkpointer.wait()
        # strictly-before: a cadence save at the diverged step (saved in the
        # same iteration, before the lagged detection fired) holds the
        # poisoned params — never roll back INTO the blast radius
        self._restore(before_step=fail_step)
        ckpt_step = self.step_scheduler.step
        # goodput: the step time spent on (ckpt_step, fail_step] is about to
        # be re-done — reclassified as rollback_discard in the run ledger
        # (getattr: unit tests drive _rollback on a bare recipe object)
        led = getattr(self, "ledger", None)
        if led is not None:
            led.on_rollback(fail_step, ckpt_step)
        dl = self.dataloader
        ga = self.step_scheduler.grad_acc_steps
        nb = len(dl)
        # replay the scheduler's consumption, not steps*grad_acc: an epoch
        # whose length doesn't divide grad_acc discards its tail batches
        # (step_scheduler.__iter__ drops the partial group), so a window
        # spanning an epoch boundary consumes more batches than it yields
        # steps — undercounting would land the loader back INSIDE the
        # offending group and retrain the same bad batch every rollback
        epoch, pos = dl.epoch, dl.batch_in_epoch
        steps_left = max(fail_step - ckpt_step, 0)
        while steps_left and nb >= ga:
            in_epoch = (nb - pos) // ga
            if steps_left <= in_epoch:
                pos += steps_left * ga
                steps_left = 0
            else:
                steps_left -= in_epoch
                epoch += 1
                pos = 0
        # seek() on the prefetch facade flushes the run-ahead queue, joins
        # the producer, and restarts fetching at the rolled-back cursor —
        # a rollback across a prefetched window stays bit-exact. The plain
        # attribute assignment covers duck-typed loaders without seek().
        seek = getattr(dl, "seek", None)
        if seek is not None:
            seek(epoch, pos)
        else:
            dl.epoch, dl.batch_in_epoch = epoch, pos
        # keep the scheduler's epoch budget in sync: the skipped window may
        # contain epoch boundaries the scheduler will now never observe
        self.step_scheduler.epoch = epoch
        logger.warning(
            "rollback #%d: restored step %d, fast-forwarded dataloader to "
            "epoch %d batch %d, past the non-finite window ending at step %d",
            self.resilience.rollbacks, ckpt_step, epoch, pos, fail_step,
        )

    def _check_prev_nonfinite(self, res) -> None:
        """Fold the PREVIOUS step's non-finite flag into the policy. The
        flag is a scalar from an already-executed step, so fetching it does
        not block on the step just dispatched."""
        pending = self._pending_flag
        self._pending_flag = None
        if pending is None:
            return
        step_no, flag = pending
        if flag is None or not bool(jax.device_get(flag)):
            res.observe_step_flag(step_no, False)
            return
        action = res.observe_step_flag(step_no, True)
        self.telemetry.record_step(
            {
                "event": "nonfinite_step",
                "step": step_no,
                "policy": res.on_nonfinite,
                "action": action or "continue",
                "ts": time.time(),
            }
        )
        # anomaly-armed profiler: a non-finite step arms a capture of the
        # NEXT trace window + device memory profile (triggered.py)
        self.telemetry.trigger_capture(step_no, "nonfinite")
        if self._prom is not None:
            self._prom.event("nonfinite_step")
        if action == "raise":
            raise NonFiniteError(
                f"non-finite loss/gradients at step {step_no} "
                f"(policy: {res.on_nonfinite}) — see the flight recorder for "
                "the per-group grad norms of the offending step"
            )
        if action == "rollback":
            raise _RollbackRequested(step_no)

    def _train_loop_body(self, tel, restarted: bool = False) -> dict:
        last: dict = {}
        res = self.resilience
        # (step, device flag) of the step whose non-finite check is pending
        self._pending_flag: Optional[tuple] = None
        it = iter(self.step_scheduler)
        # after a rollback restart the step is already compiled — don't
        # re-report the first step as compile_time_s
        first_step = not restarted
        tokens_window = 0
        steps_window = 0
        # host time spent ACQUIRING the next device-ready batch (collate +
        # stack + H2D when sync; a queue pop when prefetched) — the per-log-
        # window decomposition key that makes the overlap visible
        input_wait_window = 0.0
        # wall time spent INSIDE val/ckpt boundaries since the last log
        # record: the windows restart after those pauses, so without this
        # stamp the boundary time vanishes from every record — surfaced as
        # `window_excluded_s` on the NEXT record so records sum to wall
        # clock (the invariant the goodput ledger needs)
        excluded_window = 0.0
        # boundary time accumulated after the LAST log record of the run
        # rides the end-of-run `goodput_tail` record instead of vanishing
        self._tail_excluded_s = 0.0
        # everything before the first batch was setup: close the ledger's
        # `startup` segment (idempotent across rollback restarts)
        self.ledger.loop_started()
        t_window = time.perf_counter()

        def flush_window_to_ledger(at_step: int) -> None:
            """Close a partial throughput window (log_every > 1, or the
            loop tail) into the ledger before a boundary reset discards
            it. Log barriers compute their own dt for the JSONL record
            and call ledger.window directly."""
            if steps_window:
                self.ledger.window(
                    time.perf_counter() - t_window, input_wait_window,
                    steps_window, at_step,
                )
        while True:
            t_input = time.perf_counter()
            tel.timers("data_wait").start()
            try:
                group = next(it)
            except StopIteration:
                # the scheduler consumed (and collated) one more batch
                # before noticing the epoch/max_steps budget — that tail
                # fetch is input wait like any other, not idle
                input_wait_window += time.perf_counter() - t_input
                break
            tel.timers("data_wait").stop()
            if isinstance(group, PreparedBatch):
                # prefetch pipeline: collate/stack/zigzag/device_put already
                # happened in the producer thread — this was a queue pop
                stacked, batch = group.host, group.device
                n_tokens_batch = group.n_tokens
            else:
                stacked, n_tokens_batch = self._prepare_group(group)
                batch = self._place_group(stacked)
            input_wait_window += time.perf_counter() - t_input
            step_no = self.step_scheduler.step
            tel.on_step(step_no)
            tel.timers("dispatch").start()
            self.state, metrics = self.train_step(self.state, batch)
            tel.timers("dispatch").stop()
            # step boundary: pet the hang watchdog (two attribute stores)
            # and fold the batch into the consensus data hash (crc32 over
            # host-side numpy, only when consensus is live) — nothing here
            # touches the jitted hot path
            self.guard.on_step(step_no, stacked)
            if res.injector is not None:
                res.injector.maybe_die(step_no)
                res.injector.maybe_straggle(step_no)
                res.injector.maybe_hang(step_no)
            if res.config.enabled and "nonfinite" in metrics:
                # check the PREVIOUS step's flag now that this one is in
                # flight (lagged detection, no dispatch stall), then queue
                # this step's flag
                self._check_prev_nonfinite(res)
                self._pending_flag = (step_no, metrics["nonfinite"])
            tokens_window += n_tokens_batch
            steps_window += 1
            host_rec = {"step": step_no, "tokens": n_tokens_batch, "ts": time.time()}
            if first_step:
                # cost attribution rides the compile window: the device is
                # busy compiling/executing step 1 while the host re-traces
                # the step abstractly. Never load-bearing.
                if (
                    self.profiling.enabled
                    and self.profiling.cost_attribution
                    and self._step_cost is None
                ):
                    try:
                        self._compute_step_cost(batch)
                    except Exception as e:
                        logger.warning("cost attribution failed: %s", e)
                if self._flops_per_token is None and "input_ids" in stacked:
                    try:
                        from automodel_tpu.utils.flops_utils import (
                            flops_per_token_for_config,
                        )

                        self._flops_per_token = flops_per_token_for_config(
                            self.model.config, int(stacked["input_ids"].shape[-1])
                        )
                    except Exception:
                        pass
                metrics = {k: v for k, v in jax.device_get(metrics).items()}
                metrics["compile_time_s"] = time.perf_counter() - t_window
                host_rec["compile_time_s"] = metrics["compile_time_s"]
                host_rec["loss"] = float(metrics["loss"])
                self.ledger.compile_window(
                    metrics["compile_time_s"], input_wait_window, step=step_no
                )
                # discard step 1's timer entries and compile events BEFORE
                # any enrich: the initial XLA compile is already reported as
                # compile_time_s, and must appear neither as this record's
                # `recompiles` nor in the first window's time/* means
                tel.timers.drain_means()
                if tel.compile_bridge is not None:
                    tel.compile_bridge.drain()
                if self.step_scheduler.is_log_step:
                    metrics.update(self.ledger.pop_pending())
                    metrics = tel.enrich(step_no, metrics)
                    metrics = self.guard.on_log(
                        step_no, metrics, params=self.state.params
                    )
                    self.metric_logger.log(metrics, step=int(metrics["step"]))
                    if self._prom is not None:
                        self._prom.update(metrics)
                        self._prom.update_goodput(self.ledger.snapshot())
                    last = metrics
                tel.record_step(host_rec)
                first_step = False
                tokens_window = steps_window = 0
                input_wait_window = 0.0
                t_window = time.perf_counter()
            elif self.step_scheduler.is_log_step:
                tel.timers("device_sync").start()
                metrics = {k: v for k, v in jax.device_get(metrics).items()}
                tel.timers("device_sync").stop()
                dt = time.perf_counter() - t_window
                metrics["steps_spanned"] = steps_window
                metrics["step_time_s"] = dt / max(steps_window, 1)
                metrics["tps"] = tokens_window / max(dt, 1e-9)
                metrics["tps_per_device"] = metrics["tps"] / self.mesh_ctx.world_size
                # input-pipeline decomposition beside step_time_s: amortized
                # host input wait per step, + the prefetch run-ahead gauge
                metrics["host_input_wait_s"] = input_wait_window / max(
                    steps_window, 1
                )
                if isinstance(self.dataloader, PrefetchingLoader):
                    metrics["prefetch_depth"] = self.dataloader.queue_depth
                if res.skipped_steps:
                    metrics["skipped_steps_total"] = res.skipped_steps
                if res.rollbacks:
                    metrics["rollbacks_total"] = res.rollbacks
                metrics = self._fold_mfu(metrics)
                # goodput: one closed window = a `step` + `input_wait`
                # segment pair summing to the window's wall clock
                self.ledger.window(dt, input_wait_window, steps_window, step_no)
                metrics.update(self.ledger.pop_pending())
                if excluded_window > 0:
                    metrics["window_excluded_s"] = round(excluded_window, 6)
                    excluded_window = 0.0
                metrics = tel.enrich(step_no, metrics)
                # the log step is already a device barrier: liveness +
                # cross-host consensus + straggler attribution ride it
                metrics = self.guard.on_log(
                    step_no, metrics, params=self.state.params
                )
                self.metric_logger.log(metrics, step=int(metrics["step"]))
                if self._prom is not None:
                    self._prom.update(metrics)
                    self._prom.update_goodput(self.ledger.snapshot())
                last = metrics
                host_rec.update(
                    {
                        k: metrics[k]
                        for k in ("loss", "grad_norm", "step_time_s", "tps", "nonfinite")
                        if k in metrics
                    }
                )
                tel.record_step(host_rec)
                tokens_window = steps_window = 0
                input_wait_window = 0.0
                t_window = time.perf_counter()
            else:
                tel.record_step(host_rec)
            gen_active = self._gen_engine is not None and (
                self._gen_prompts is not None or self._gen_prompt_ids is not None
            )
            if self.step_scheduler.is_val_step and (
                self.val_dataloader is not None or gen_active
            ):
                flush_window_to_ledger(step_no)
                t_boundary = time.perf_counter()
                # same early resolution as the ckpt block below: under
                # lag-1 detection a diverged step N would otherwise run a
                # full eval pass on NaN params and log a garbage val record
                # before the policy fires at N+1 (validation is a device
                # barrier anyway, so the early fetch costs nothing extra)
                if res.config.enabled:
                    self._check_prev_nonfinite(res)
                # eval/generation are legitimately slow (fresh compiles,
                # full passes): the watchdog's eval grace covers them
                with self.guard.phase("eval"):
                    if self.val_dataloader is not None:
                        with self.ledger.segment("eval", step=step_no):
                            val = self.run_validation()
                        # compile events during validation (eval_step's first
                        # compile) belong to the val record, not the next
                        # train window's `recompiles`
                        if tel.compile_bridge is not None:
                            d = tel.compile_bridge.drain()
                            if d["compiles"]:
                                val["eval_compiles"] = d["compiles"]
                                val["eval_compile_secs"] = round(d["compile_secs"], 4)
                        self.metric_logger.log(val, step=self.step_scheduler.step)
                    # sample completions with the current weights
                    # (generation: section); compiles + wall time land
                    # OUTSIDE the training windows (the reset below), like
                    # validation itself
                    if gen_active:
                        with self.ledger.segment("generation", step=step_no):
                            self._log_eval_generation()
                if tel.compile_bridge is not None:
                    tel.compile_bridge.drain()
                # val/generation wall time must not read as a slow step
                # (triggered profiler) any more than it reads as train
                # throughput (the window reset below)
                tel.skip_next_interval()
                excluded_window += time.perf_counter() - t_boundary
                tokens_window = steps_window = 0
                input_wait_window = 0.0
                t_window = time.perf_counter()
            if self.step_scheduler.is_ckpt_step:
                flush_window_to_ledger(step_no)
                t_boundary = time.perf_counter()
                # resolve THIS step's flag before persisting: a cadence save
                # at the diverged step would commit the poisoned params as
                # the newest checkpoint (integrity checks can't see NaN) and
                # crash-loop the restarted run. The save is a device barrier
                # anyway, so the early fetch costs nothing extra.
                if res.config.enabled:
                    self._check_prev_nonfinite(res)
                # same resolution point, cross-host edition: every host
                # must agree on (step, config, data order, params) before
                # this checkpoint may commit — a desynced checkpoint is as
                # poisonous as a NaN one and integrity checksums can't see
                # either
                self.guard.pre_commit(step_no, self.state.params)
                with self.guard.phase("checkpoint"):
                    self.save_checkpoint()
                tel.skip_next_interval()
                excluded_window += time.perf_counter() - t_boundary
                tokens_window = steps_window = 0
                input_wait_window = 0.0
                t_window = time.perf_counter()
        # the tail window (steps since the last log barrier) would vanish
        # from the ledger at loop exit — close it like any other window;
        # a stepless tail still carries the final StopIteration fetch
        if steps_window:
            flush_window_to_ledger(self.step_scheduler.step)
        elif input_wait_window > 0:
            self.ledger.add(
                "input_wait", input_wait_window, step=self.step_scheduler.step
            )
        # boundary time with no following log record: surfaced on the
        # end-of-run goodput_tail record (records must sum to wall clock)
        self._tail_excluded_s = excluded_window
        # a non-finite flag from the final step must still be enforced
        if res.config.enabled:
            self._check_prev_nonfinite(res)
        if res.skipped_steps:
            last["skipped_steps_total"] = res.skipped_steps
        if res.rollbacks:
            last["rollbacks_total"] = res.rollbacks
        return last

    def run_validation(self) -> dict:
        tot_loss, tot_n = 0.0, 0
        for vb in self.val_dataloader:
            # the prefetch facade yields device-ready batches (placed in its
            # producer thread); the sync path stacks + places inline
            batch = (
                vb.device
                if isinstance(vb, PreparedBatch)
                else place_batch(self.mesh_ctx, stack_microbatches([vb]))
            )
            out = jax.device_get(self.eval_step(self.state, batch))
            tot_loss += float(out["loss_sum"])
            tot_n += int(out["num_label_tokens"])
        val_loss = tot_loss / max(tot_n, 1)
        if isinstance(self.val_dataloader, PrefetchingLoader):
            # don't let the producer pre-stage the NEXT val epoch and pin
            # depth placed batches in device memory until the next val step
            self.val_dataloader.suspend()
        if val_loss == val_loss:  # a NaN eval must never look "best"
            self._last_val_metric = val_loss
        return {"val_loss": val_loss, "val_tokens": tot_n}


def main(cfg: ConfigNode) -> dict:
    recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg)
    recipe.setup()
    return recipe.run_train_validation_loop()
