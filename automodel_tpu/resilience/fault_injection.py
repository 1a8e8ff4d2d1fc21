"""Fault-injection harness: config/env-driven failures for resilience tests.

The subsystem this drives (preemption → emergency checkpoint, manifest
walk-back, retrying I/O, non-finite-step policy) is exactly the code that
only runs when something goes wrong — so it needs a way to MAKE things go
wrong, deterministically, on CPU, in tier-1. Four fault classes:

- ``die_at_step``           — kill the process at optimizer step k
  (``die_mode: hard`` = os._exit, simulating a SIGKILL'd host mid-async-save;
  ``exception`` = raise, exercising the crash-guard/flight-recorder path)
- ``nan_grads_at_step``     — poison the gradients at step k INSIDE the
  jitted step (keyed on the traced ``state.step`` so there is no recompile
  and no host sync; see train_step.py), firing the anomaly flags and
  whatever ``on_nonfinite`` policy is configured
- ``corrupt_ckpt_file``     — after a checkpoint commits, flip bytes in the
  first file matching this glob (relative to the checkpoint step dir), so
  the next load must detect the damage and walk back
- ``fail_io_attempts``/``fail_io_op`` — fail the first M attempts of any
  retry_io-wrapped op whose name contains ``fail_io_op``, proving the
  backoff absorbs transient storage errors (or exhausts loudly)
- ``hang_at_step``          — block the training loop at step k (a bounded
  ``time.sleep``, which releases the GIL exactly like a wedged collective
  would), driving the hang watchdog's detect → dump → requeue-exit path
- ``slow_collate_ms``       — sleep that long inside EVERY batch collate
  (``DataLoader.batch_for``), simulating an expensive host input pipeline
  (tokenization, disk reads) so the prefetch overlap (data/prefetch.py) is
  provable on CPU: a sync loop pays the delay per step, a prefetched loop
  hides it under device compute
- ``desync_batch_at_step``  — perturb THIS host's rolling data-batch hash
  at step k (on ``desync_on_host`` only), driving the cross-host consensus
  check's detect-and-name-the-culprit path
- ``straggle_host``/``straggle_ms`` — sleep ``straggle_ms`` per step on one
  host, driving the straggler-attribution metrics (``slowest_host``)
- ``slo_breach_stage``/``slo_breach_ms``/``slo_breach_from_step``/
  ``slo_breach_for_s`` — inflate the named serving stage by that many ms
  from scheduler step N for a bounded wall-clock window, so the fleet SLO
  engine's pending→firing→resolved lifecycle (telemetry/slo.py) is
  drivable end-to-end in tier-1
- ``weights_stream_abort_after`` — a serving peer answering a warm-start
  ``weights_fetch`` closes the connection after streaming that many leaves
  (the peer "dies" mid-stream), so the joiner's truncated-frame detection
  and cold-load fallback ladder are drivable in tier-1
- ``kv_push_drop_ack`` — a migration target accepting a scale-down
  ``kv_push`` closes the socket instead of acking (the survivor "dies"
  mid-ship), so the retiring replica's degrade-to-plain-drain path and its
  bounded exit deadline are drivable in tier-1
- ``hf_load_delay_ms`` — sleep that long inside the cold model load, a
  stand-in for the real HF checkpoint download/parse cost that is near
  zero on the tiny test models, so peer warm-start's time_to_ready_s win
  is measurable on CPU (the same role ``slow_collate_ms`` plays for the
  input-pipeline overlap proof)
- ``serve_tenant_flood_at_step`` — one tenant floods the serving admission
  queue with ``serve_tenant_flood_requests`` tiny requests at scheduler
  step k (the noisy-neighbor chaos knob): per-tenant quotas, tiered
  shedding and the anti-starvation aging bound (serving.qos) must keep
  every other tenant live

Activation: a ``fault_injection:`` YAML section (recipes call
``activate_from_config``) or the ``AUTOMODEL_FAULT_INJECTION`` env var
holding the same dict as JSON (for subprocess tests where no recipe code
runs before the fault must be armed). Inactive (the default) every hook is
a cheap None/False check — zero cost in production.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import json
import logging
import os
from pathlib import Path
from typing import Any, Optional

logger = logging.getLogger(__name__)

ENV_VAR = "AUTOMODEL_FAULT_INJECTION"
# distinctive code so tests can tell an injected hard-death from a real crash
HARD_DEATH_EXIT_CODE = 113


class InjectedFault(RuntimeError):
    """Raised by ``die_mode: exception`` and by injected I/O failures."""


@dataclasses.dataclass
class FaultInjectionConfig:
    die_at_step: Optional[int] = None
    die_mode: str = "hard"  # hard (os._exit, no cleanup) | exception
    nan_grads_at_step: Optional[int] = None
    corrupt_ckpt_file: Optional[str] = None  # glob under the step dir
    fail_io_attempts: int = 0
    fail_io_op: str = ""  # substring of the retry_io op name; "" = every op
    # distributed-guard faults (watchdog / consensus / straggler)
    hang_at_step: Optional[int] = None
    hang_seconds: float = 3600.0  # bounded — the watchdog exits long before
    # per-batch collate delay (data/loader.py batch_for) — the input-
    # pipeline overlap proof knob (tests/test_prefetch.py)
    slow_collate_ms: float = 0.0
    desync_batch_at_step: Optional[int] = None
    desync_on_host: int = 0  # process_index whose data hash is perturbed
    straggle_host: Optional[int] = None
    straggle_ms: float = 0.0  # per-step sleep on the straggling host
    # None → every step (straggler-attribution tests); an int → that ONE
    # step only, producing the step-time SPIKE the triggered-capture
    # profiler arms on (telemetry/profiling/triggered.py)
    straggle_at_step: Optional[int] = None
    # serving faults (serving/engine.py scheduler iterations, driven by the
    # chaos harness in tests/test_serving_chaos.py): a slow/hung decode
    # step (GIL-releasing sleep — the engine watchdog fires during it, the
    # engine fails the wave and rebuilds when it returns), a mid-request
    # engine exception, and allocator exhaustion (every available block
    # grabbed for hold_steps, so admissions queue and deadline/shed paths
    # fire)
    serve_hang_at_step: Optional[int] = None
    serve_hang_seconds: float = 2.0
    serve_exception_at_step: Optional[int] = None
    serve_exhaust_blocks_at_step: Optional[int] = None
    serve_exhaust_hold_steps: int = 50
    # request-tracing attribution proof (telemetry/tracing.py): sleep
    # trace_delay_ms inside EVERY execution of the named stage (span stage
    # names — prefill/decode/kv_inject/kv_send/kv_receive/placement/
    # forward), so the assembled waterfall and the /metrics per-stage
    # histogram must charge the delay to exactly that stage
    trace_delay_stage: Optional[str] = None
    trace_delay_ms: float = 0.0
    # SLO forced-breach knob (telemetry/slo.py e2e proof): inflate the
    # named serving stage (prefill -> ttft, decode -> decode_tps) by
    # slo_breach_ms per execution, starting at scheduler step
    # slo_breach_from_step and lasting slo_breach_for_s of wall clock from
    # the first inflated execution (None = forever). The bounded wall-clock
    # window is what makes alert FIRE **and** RESOLVE drivable in one
    # tier-1 process lifetime: steps race under load, wall time does not.
    slo_breach_stage: Optional[str] = None
    slo_breach_ms: float = 0.0
    slo_breach_from_step: int = 0
    slo_breach_for_s: Optional[float] = None
    # elastic-fleet chaos knobs (tests/test_fleet_elastic.py): a warm-start
    # weights stream truncated after N leaves, a migration push dropped
    # before its ack, and an injected cold-load cost so the warm-vs-cold
    # time_to_ready_s A/B has a real delta on tiny CPU models
    weights_stream_abort_after: Optional[int] = None
    kv_push_drop_ack: bool = False
    hf_load_delay_ms: float = 0.0
    # noisy-neighbor knob (multi-tenant QoS, tests/test_qos.py): at serving
    # scheduler step k, one tenant floods the admission queue with
    # serve_tenant_flood_requests tiny requests (tier defaults to the
    # flooding tenant's configured/default tier) — quotas, lowest-tier-first
    # shedding and the aging bound must keep every OTHER tenant live
    serve_tenant_flood_at_step: Optional[int] = None
    serve_tenant_flood_requests: int = 32
    serve_tenant_flood_tenant: str = "flood"
    serve_tenant_flood_tier: Optional[str] = None


def _process_index() -> int:
    try:
        import jax

        return jax.process_index()
    except Exception:
        return 0


class FaultInjector:
    def __init__(self, config: FaultInjectionConfig):
        self.config = config
        self._io_attempts: dict[str, int] = {}
        self._hung = False
        self._serve_hung = False
        self._flooded = False
        # slo_breach_for_s window bookkeeping (maybe_slo_breach)
        self._breach_started_t: Optional[float] = None
        self._breach_closed = False

    # -- step-loop hooks ----------------------------------------------------
    def maybe_die(self, step: int) -> None:
        c = self.config
        if c.die_at_step is None or step != c.die_at_step:
            return
        if c.die_mode == "exception":
            raise InjectedFault(f"injected crash at step {step}")
        logger.error("fault injection: hard death at step %d", step)
        os._exit(HARD_DEATH_EXIT_CODE)  # no atexit, no finally — like SIGKILL

    @property
    def nan_grads_at_step(self) -> Optional[int]:
        return self.config.nan_grads_at_step

    def maybe_hang(self, step: int) -> None:
        """Block the loop like a wedged collective would (sleep releases the
        GIL, so the watchdog thread stays runnable — same as jax's blocking
        calls). Fires once; the watchdog is expected to end the process."""
        c = self.config
        if c.hang_at_step is None or step != c.hang_at_step or self._hung:
            return
        self._hung = True
        logger.error(
            "fault injection: hanging at step %d for up to %.0fs",
            step, c.hang_seconds,
        )
        import time

        time.sleep(c.hang_seconds)

    def maybe_slow_collate(self) -> None:
        """Per-batch collate delay (called from ``DataLoader.batch_for``, so
        it fires on the sync path AND inside prefetch collate workers — the
        sleep releases the GIL exactly like tokenizer/disk work would)."""
        ms = self.config.slow_collate_ms
        if ms > 0:
            import time

            time.sleep(ms / 1000.0)

    def should_desync(self, step: int) -> bool:
        c = self.config
        if c.desync_batch_at_step is None or step != c.desync_batch_at_step:
            return False
        return _process_index() == c.desync_on_host

    # -- serving hooks ------------------------------------------------------
    def maybe_serve_hang(self, step: int) -> None:
        """Wedge one serving scheduler iteration (a bounded GIL-releasing
        sleep, exactly like a stuck device call): the engine watchdog is
        expected to fire mid-sleep and the engine to rebuild after."""
        c = self.config
        if c.serve_hang_at_step is None or step != c.serve_hang_at_step or self._serve_hung:
            return
        self._serve_hung = True
        logger.error(
            "fault injection: hanging serving step %d for %.1fs",
            step, c.serve_hang_seconds,
        )
        import time

        time.sleep(c.serve_hang_seconds)

    def maybe_tenant_flood(self, step: int) -> Optional[tuple]:
        """Noisy neighbor: at serving step k, → ``(tenant, n, tier)`` for
        the engine to submit as a burst of tiny requests from that tenant
        (tier None = the tenant's configured default). Fires once."""
        c = self.config
        if (
            c.serve_tenant_flood_at_step is None
            or step != c.serve_tenant_flood_at_step
            or self._flooded
        ):
            return None
        self._flooded = True
        logger.error(
            "fault injection: tenant %r flooding %d requests at serving "
            "step %d",
            c.serve_tenant_flood_tenant, c.serve_tenant_flood_requests, step,
        )
        return (
            c.serve_tenant_flood_tenant,
            max(int(c.serve_tenant_flood_requests), 0),
            c.serve_tenant_flood_tier,
        )

    def maybe_serve_exception(self, step: int) -> None:
        """Mid-request engine exception at serving step k (fires once: the
        step counter passes each value exactly once)."""
        c = self.config
        if c.serve_exception_at_step is not None and step == c.serve_exception_at_step:
            raise InjectedFault(f"injected serving engine crash at step {step}")

    def maybe_trace_delay(self, stage: str) -> None:
        """Sleep inside the named tracing stage's measured window (called
        at each stage's execution site in serving/engine.py, fleet/router.py
        and fleet/kv_transfer.py) — the delay must surface on that stage's
        span and /metrics histogram, nowhere else."""
        c = self.config
        if c.trace_delay_stage == stage and c.trace_delay_ms > 0:
            import time

            time.sleep(c.trace_delay_ms / 1000.0)

    def maybe_slo_breach(self, stage: str, step: int) -> None:
        """Inflate the named serving stage inside its breach window (called
        where the engine executes prefill/decode, beside
        ``maybe_trace_delay``). The delay is a GIL-releasing sleep — the
        inflated latency is REAL at the request level, so the /metrics
        histograms the SLO engine federates see it exactly like a slow
        model would produce it."""
        c = self.config
        if c.slo_breach_stage != stage or c.slo_breach_ms <= 0:
            return
        if step < c.slo_breach_from_step:
            return
        import time

        if c.slo_breach_for_s is not None:
            if self._breach_started_t is None:
                self._breach_started_t = time.monotonic()
                logger.error(
                    "fault injection: SLO breach window opened at serving "
                    "step %d (+%.0fms per %s for %.1fs)",
                    step, c.slo_breach_ms, stage, c.slo_breach_for_s,
                )
            elif time.monotonic() - self._breach_started_t >= c.slo_breach_for_s:
                if not self._breach_closed:
                    self._breach_closed = True
                    logger.error(
                        "fault injection: SLO breach window closed at "
                        "serving step %d", step,
                    )
                return
        time.sleep(c.slo_breach_ms / 1000.0)

    def should_abort_weights_stream(self, leaves_sent: int) -> bool:
        """True when the warm-start weights stream should die after
        ``leaves_sent`` leaves (checked between leaf writes in
        ``KVTransferServer._handle_weights``)."""
        c = self.config
        return (
            c.weights_stream_abort_after is not None
            and leaves_sent >= c.weights_stream_abort_after
        )

    def should_drop_kv_push(self) -> bool:
        """True when a migration target should close instead of acking an
        accepted ``kv_push`` (the survivor dies mid-ship)."""
        return self.config.kv_push_drop_ack

    def maybe_hf_load_delay(self) -> None:
        """Injected cold-load cost (called from the model-build path) —
        the stand-in for real HF download/parse time on tiny test models."""
        ms = self.config.hf_load_delay_ms
        if ms > 0:
            import time

            logger.warning(
                "fault injection: delaying cold model load by %.0fms", ms
            )
            time.sleep(ms / 1000.0)

    def maybe_straggle(self, step: int) -> None:
        c = self.config
        if c.straggle_host is None or c.straggle_ms <= 0:
            return
        if c.straggle_at_step is not None and step != c.straggle_at_step:
            return
        if _process_index() == c.straggle_host:
            import time

            time.sleep(c.straggle_ms / 1000.0)

    # -- checkpoint hook ----------------------------------------------------
    def after_checkpoint_save(self, step_dir: Path) -> None:
        """Corrupt the first file under ``step_dir`` matching the configured
        glob (called AFTER the manifest commits, so the damage is exactly
        what integrity verification exists to catch)."""
        pat = self.config.corrupt_ckpt_file
        if not pat:
            return
        for p in sorted(step_dir.rglob("*")):
            if p.is_file() and fnmatch.fnmatch(str(p.relative_to(step_dir)), pat):
                corrupt_file(p)
                logger.error("fault injection: corrupted %s", p)
                return

    # -- retry_io hook ------------------------------------------------------
    def check_io(self, op: str) -> None:
        c = self.config
        if c.fail_io_attempts <= 0 or c.fail_io_op not in op:
            return
        n = self._io_attempts.get(op, 0)
        if n < c.fail_io_attempts:
            self._io_attempts[op] = n + 1
            raise OSError(f"injected I/O failure {n + 1}/{c.fail_io_attempts} for {op}")


def corrupt_file(path: Path | str, offset_fraction: float = 0.5, n_bytes: int = 64) -> None:
    """Flip ``n_bytes`` in the middle of a file in place (bounded by size)."""
    path = Path(path)
    size = path.stat().st_size
    if size == 0:
        path.write_bytes(b"\xff")
        return
    off = int(size * offset_fraction) % size
    n = min(n_bytes, size - off)
    with open(path, "r+b") as f:
        f.seek(off)
        chunk = f.read(n)
        f.seek(off)
        f.write(bytes(b ^ 0xFF for b in chunk))


# -- process-global activation ----------------------------------------------
_ACTIVE: Optional[FaultInjector] = None
_ENV_CHECKED = False


def activate(config: FaultInjectionConfig | dict | None) -> Optional[FaultInjector]:
    """Install (or, with None, clear) the process-global injector."""
    global _ACTIVE, _ENV_CHECKED
    _ENV_CHECKED = True  # explicit activation wins over the env var
    if config is None:
        _ACTIVE = None
        return None
    if isinstance(config, dict):
        d = {k: v for k, v in config.items() if k != "_target_"}
        config = FaultInjectionConfig(**d)
    armed = (
        config.die_at_step is not None
        or config.nan_grads_at_step is not None
        or config.corrupt_ckpt_file
        or config.fail_io_attempts > 0
        or config.hang_at_step is not None
        or config.slow_collate_ms > 0
        or config.desync_batch_at_step is not None
        or config.straggle_host is not None
        or config.serve_hang_at_step is not None
        or config.serve_exception_at_step is not None
        or config.serve_exhaust_blocks_at_step is not None
        or (config.trace_delay_stage is not None and config.trace_delay_ms > 0)
        or (config.slo_breach_stage is not None and config.slo_breach_ms > 0)
        or config.weights_stream_abort_after is not None
        or config.kv_push_drop_ack
        or config.hf_load_delay_ms > 0
        or config.serve_tenant_flood_at_step is not None
    )
    if not armed:
        # an empty `fault_injection: {}` section (the docs' example form)
        # must not put a do-nothing injector — and its scary ACTIVE
        # warning — into a production run
        _ACTIVE = None
        return None
    _ACTIVE = FaultInjector(config)
    logger.warning("fault injection ACTIVE: %s", config)
    return _ACTIVE


def activate_from_config(section: Any) -> Optional[FaultInjector]:
    """From a YAML ``fault_injection:`` section (None → env var → inactive)."""
    if section is None:
        return active_injector()
    return activate(dict(section))


def active_injector() -> Optional[FaultInjector]:
    """The process-global injector, arming from ``AUTOMODEL_FAULT_INJECTION``
    (JSON) on first use so subprocess tests need no in-process setup."""
    global _ACTIVE, _ENV_CHECKED
    if not _ENV_CHECKED:
        _ENV_CHECKED = True
        raw = os.environ.get(ENV_VAR)
        if raw:
            try:
                _ACTIVE = FaultInjector(FaultInjectionConfig(**json.loads(raw)))
                logger.warning("fault injection ACTIVE from env: %s", raw)
            except (ValueError, TypeError) as e:
                raise ValueError(f"bad {ENV_VAR} value {raw!r}") from e
    return _ACTIVE
