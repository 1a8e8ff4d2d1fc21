"""CLI entry point.

Parity: the reference `automodel` CLI (_cli/app.py:202-245):
``automodel <command> <domain> -c cfg.yaml [--dotted.overrides]``. On TPU
there is no torchrun spawn — single-controller JAX runs the recipe in-process
(multi-host via `jax.distributed.initialize` when coordinator env vars are
present). Slurm/k8s submission lives in automodel_tpu.launcher.
"""

from __future__ import annotations

import sys

from automodel_tpu.config.arg_parser import parse_args_and_load_config

COMMANDS = ("finetune", "pretrain", "kd", "dpo", "grpo", "benchmark", "mine")
DOMAINS = ("llm", "vlm", "biencoder")


def _usage() -> str:
    return (
        "usage: automodel_tpu <finetune|pretrain|kd|dpo|grpo|benchmark|mine> <llm|vlm|biencoder> "
        "-c config.yaml [--dotted.key=value ...]\n"
        "       automodel_tpu dpo llm -c config.yaml   (preference optimization — DPO/ORPO over chosen/rejected pairs; posttrain: section)\n"
        "       automodel_tpu grpo llm -c config.yaml  (RL post-training — serving-engine rollouts, pluggable reward:, group-relative advantages, live weight hot-swap)\n"
        "       automodel_tpu generate -c config.yaml [--prompt '...'] [--dotted.key=value ...]\n"
        "       automodel_tpu serve -c config.yaml [--dotted.key=value ...]  (stdin-JSONL; serving.http.port for HTTP; GET /metrics /healthz /readyz; SIGTERM drains gracefully)\n"
        "       automodel_tpu route -c config.yaml [--dotted.key=value ...]  (fleet router over N serve replicas: fleet.replicas/fleet.dns; prefix-affinity + retry; same HTTP front contract; slo: section arms burn-rate alerting)\n"
        "       automodel_tpu fleet-status [-c config.yaml] [--router URL] [--watch] [--json]  (live per-replica health table: role/ready/queue/occupancy/hit-rate/accept-rate/firing SLOs, from the router's federated state or direct replica probes)\n"
        "       automodel_tpu profile -c config.yaml [--profiling.mode=train|generate] [--dotted.key=value ...]\n"
        "       automodel_tpu report <train_metrics.jsonl> [--strict]\n"
        "       automodel_tpu goodput <run-dir | goodput.jsonl> [--json]  (wall-clock decomposition of a training run across restart attempts; joins flight-recorder hang/desync evidence)\n"
        "       automodel_tpu trace <metrics.jsonl> [...] [--chrome out.json] [--md out.md] [--trace-id PREFIX]  (join multi-process span JSONLs into per-request waterfalls)\n"
        "       automodel_tpu verify-ckpt <ckpt_dir> [--no-checksums] [--json]"
    )


def _crash_is_preemption_collateral(cfg) -> bool:
    """Multi-host requeue wiring (resilience/preemption.py): when ONE host
    of a multi-host job is preempted it exits the requeue code, but its
    peers die of broken collectives with ordinary exceptions. The preempted
    host drops a marker into the shared checkpoint root at SIGTERM time; a
    crash here while that marker is FRESH is preemption collateral and must
    requeue too, or the launcher burns its backoff budget on spot churn."""
    from automodel_tpu.checkpoint.checkpointer import CheckpointingConfig
    from automodel_tpu.resilience import peer_preemption_fresh

    ccfg = dict(cfg.get("checkpoint", {}) or {})
    if not ccfg.get("enabled", False):
        return False
    # default from the dataclass, not a re-typed literal: the trainer writes
    # the marker into CheckpointingConfig.checkpoint_dir, and the two paths
    # must never drift apart
    return peer_preemption_fresh(
        ccfg.get("checkpoint_dir", CheckpointingConfig.checkpoint_dir)
    )


def _device_runtime() -> None:
    """What every command that can touch a device does first: point JAX at
    the one persistent compile cache (utils/compile_cache.py) and join the
    multi-host job when the launcher declared one."""
    from automodel_tpu.parallel.mesh import initialize_distributed
    from automodel_tpu.telemetry.compile_events import CompileEventBridge
    from automodel_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    CompileEventBridge()  # registers the process-wide compile listeners
    initialize_distributed()


def _compile_report() -> None:
    """One JSON line on stderr when a device command ends: what the process
    compiled and what the persistent cache saved it — how a second run
    shows that the cache was found again."""
    compile_events = sys.modules.get("automodel_tpu.telemetry.compile_events")
    totals = compile_events.compile_totals() if compile_events else None
    if totals is not None:
        import json

        import jax

        print(
            json.dumps({
                "event": "compile_report", **totals,
                "cache_dir": jax.config.jax_compilation_cache_dir,
            }),
            file=sys.stderr, flush=True,
        )


def main(argv: list[str] | None = None) -> int:
    try:
        return _run(argv)
    finally:
        _compile_report()


def _run(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # `report` takes a JSONL path, not a domain: validate + summarize a
    # metrics file (telemetry/report.py)
    if argv and argv[0] == "report":
        from automodel_tpu.telemetry.report import main as report_main

        return report_main(argv[1:])
    # `goodput` rolls a run dir's goodput.jsonl into a per-attempt +
    # whole-run wall-clock decomposition (telemetry/goodput.py) — no
    # config, no device runtime
    if argv and argv[0] == "goodput":
        from automodel_tpu.telemetry.goodput import main as goodput_main

        return goodput_main(argv[1:])
    # `trace` assembles span records from N per-process metrics JSONLs into
    # per-request waterfalls (markdown + Chrome-trace JSON) —
    # telemetry/tracing.py. No config, no device runtime.
    if argv and argv[0] == "trace":
        from automodel_tpu.telemetry.tracing import main as trace_main

        return trace_main(argv[1:])
    # `verify-ckpt` audits a checkpoint tree's manifests (integrity + layout
    # markers) without loading arrays — checkpoint/verify.py
    if argv and argv[0] == "verify-ckpt":
        from automodel_tpu.checkpoint.verify import main as verify_main

        return verify_main(argv[1:])
    # `generate` runs the inference engine (generation/engine.py): model +
    # mesh from the same YAML sections the recipes use, a `generation:`
    # section for sampling/lengths, `--prompt` via the dotted overrides
    if argv and argv[0] == "generate":
        from automodel_tpu.generation.engine import main as generate_main

        cfg = parse_args_and_load_config(argv[1:])
        _device_runtime()
        return generate_main(cfg)
    # `serve` runs the continuous-batching serving engine (serving/):
    # stdin-JSONL by default, a local HTTP front when serving.http.port is
    # set; model/mesh from the same YAML sections as `generate`
    if argv and argv[0] == "serve":
        from automodel_tpu.serving.server import main as serve_main

        cfg = parse_args_and_load_config(argv[1:])
        _device_runtime()
        return serve_main(cfg)
    # `route` runs the fleet router (serving/fleet/router.py): spreads
    # requests over N `serve` replicas with prefix-affinity placement,
    # disaggregated prefill/decode, and failure-aware retry. No model is
    # built and no device runtime initializes — a router needs no chip.
    if argv and argv[0] == "route":
        from automodel_tpu.serving.fleet.router import main as route_main

        cfg = parse_args_and_load_config(argv[1:])
        return route_main(cfg)
    # `fleet-status` renders the live per-replica health table (role,
    # readiness, queue depth, occupancy, hit/accept rates, firing SLOs)
    # from the router's federated /stats — or probes replicas directly
    # when no router runs. Plain argparse, no config machinery, no jax.
    if argv and argv[0] == "fleet-status":
        from automodel_tpu.serving.fleet.status import main as status_main

        return status_main(argv[1:])
    # `profile` opens a jax.profiler trace window around N steps of the
    # configured workload and GENERATES the PROFILE artifacts (structured
    # report.json + PROFILE.md) — telemetry/profiling/runner.py
    if argv and argv[0] == "profile":
        from automodel_tpu.telemetry.profiling.runner import main as profile_main

        cfg = parse_args_and_load_config(argv[1:])
        _device_runtime()
        return profile_main(cfg)
    if len(argv) < 2 or argv[0] in ("-h", "--help"):
        print(_usage())
        return 0 if argv and argv[0] in ("-h", "--help") else 2
    command, domain = argv[0], argv[1]
    if command not in COMMANDS:
        print(f"Unknown command {command!r}. {_usage()}")
        return 2
    if domain not in DOMAINS:
        print(f"Unknown domain {domain!r}. {_usage()}")
        return 2
    cfg = parse_args_and_load_config(argv[2:])

    # a `slurm:`/`k8s:` section outside the corresponding cluster submits
    # instead of running (reference: _cli/app.py:125-199 Slurm; its k8s path
    # is a stub at :333 — see launcher/k8s.py)
    import os

    def _launch_section(key: str, in_cluster_env: str, submit_fn):
        if cfg.get(key) is None or in_cluster_env in os.environ:
            return None
        section = dict(cfg.get(key) or {})
        section.pop("_target_", None)
        cfg_path = next(
            (argv[2:][i + 1] for i, a in enumerate(argv[2:]) if a in ("-c", "--config")),
            None,
        )
        return submit_fn(section, cfg_path)

    def _slurm(section, cfg_path):
        from automodel_tpu.launcher.slurm import SlurmConfig, submit

        return submit(SlurmConfig(**section), command, domain, cfg_path)

    def _k8s(section, cfg_path):
        from automodel_tpu.launcher.k8s import K8sConfig, submit

        apply = section.pop("apply", True)
        return submit(K8sConfig(**section), command, domain, cfg_path, apply=apply)

    for key, env, fn in (("slurm", "SLURM_JOB_ID", _slurm), ("k8s", "KUBERNETES_SERVICE_HOST", _k8s)):
        submitted = _launch_section(key, env, fn)
        if submitted is not None:
            print(f"submitted {submitted}")
            return 0

    _device_runtime()

    recipe_modules = {
        ("finetune", "llm"): "automodel_tpu.recipes.train_ft",
        ("pretrain", "llm"): "automodel_tpu.recipes.train_ft",
        ("benchmark", "llm"): "automodel_tpu.recipes.benchmark",
        ("kd", "llm"): "automodel_tpu.recipes.kd",
        ("dpo", "llm"): "automodel_tpu.posttrain.dpo",
        ("grpo", "llm"): "automodel_tpu.posttrain.grpo",
        ("finetune", "vlm"): "automodel_tpu.recipes.finetune_vlm",
        ("finetune", "biencoder"): "automodel_tpu.recipes.train_biencoder",
        ("mine", "biencoder"): "automodel_tpu.recipes.mine_hard_negatives",
    }
    module_name = recipe_modules.get((command, domain))
    if module_name is not None:
        import importlib

        try:
            module = importlib.import_module(module_name)
        except ModuleNotFoundError as e:
            if e.name != module_name:
                raise
            module = None
        if module is not None:
            from automodel_tpu.resilience import REQUEUE_EXIT_CODE, TrainingPreempted

            from automodel_tpu.resilience import DesyncError

            try:
                module.main(cfg)
            except DesyncError as e:
                # a desynced host is a REAL fault (bad code rev, data-order
                # bug, SDC) — never excused as preemption collateral, never
                # requeued into the same desync: fail loudly naming the host
                print(f"DESYNC: {e}", file=sys.stderr)
                return 1
            except TrainingPreempted as e:
                print(f"preempted: {e}", file=sys.stderr)
                if e.checkpoint_dir is None:
                    # nothing committed to resume from: requeueing would loop
                    # at zero progress forever — fail loudly instead so the
                    # launcher/operator sees a real failure
                    return 1
                # the emergency checkpoint is committed; exit with the
                # requeue code the launchers translate into a restart
                return REQUEUE_EXIT_CODE
            except Exception as e:
                if _crash_is_preemption_collateral(cfg):
                    print(
                        "crash while a peer host's preemption marker is "
                        f"fresh — requeueing as preemption collateral: {e!r}",
                        file=sys.stderr,
                    )
                    return REQUEUE_EXIT_CODE
                raise
            return 0
    print(f"{command} {domain} is not implemented yet")
    return 3


if __name__ == "__main__":
    raise SystemExit(main())
