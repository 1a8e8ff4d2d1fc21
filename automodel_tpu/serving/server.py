"""`automodel_tpu serve` — a thin front on the continuous-batching engine.

Two modes, one engine:

- **stdin-JSONL** (default): one request object per line —
  ``{"prompt": "..."} | {"prompt_ids": [...]}`` plus optional ``id`` /
  ``max_new_tokens`` / ``deadline_s`` / ``max_queue_wait_s`` — all
  submitted into the admission queue, completions printed as JSON lines AS
  THEY FINISH (continuous batching means short requests return before long
  ones that arrived earlier).
- **local HTTP** (``serving.http.port``): POST /generate with the same
  request object blocks until that request completes; GET /stats returns
  queue depth / occupancy / allocator counters; GET /metrics is the
  Prometheus exposition; GET /healthz (scheduler thread alive, last step
  age under the watchdog deadline) and GET /readyz (false while draining
  or before the first compiled decode) feed load balancers. A background
  thread runs the scheduler loop; handlers only enqueue and wait — stdlib
  ThreadingHTTPServer, no extra dependencies, explicitly a LOCAL/dev front
  (docs/serving.md covers what a production front needs on top).

Production hardening (docs/serving.md "Failure modes & operations"):

- **Graceful drain** — SIGTERM (chained through the PR 3
  ``PreemptionHandler``) flips both fronts to draining: new and queued
  requests are rejected retriable (HTTP 503 + ``Retry-After``, stdin-JSONL
  error/record lines), in-flight requests finish within
  ``serving.drain.grace_s`` (then are cancelled), the scheduler exits
  cleanly and the CLI exits 0 — or 75 (EX_TEMPFAIL, the launchers' requeue
  code) when running under slurm/k8s (``serving.drain.requeue_exit``).
- **Overload shedding** — a full admission queue is an explicit 503 +
  ``Retry-After`` (HTTP) / retriable error record (stdin), counted in
  ``requests_shed_total``; never a silent drop or unbounded ttft.
- **Engine stalls** — the scheduler-level ``EngineWatchdog``
  (``serving.watchdog:``) detects a wedged jitted step, dumps stacks + the
  flight recorder, and the engine fails only the affected wave and keeps
  serving.

Per-request telemetry (``ttft_s``, ``decode_tps``, ``queue_s``,
``queue_depth``, ``block_occupancy``, ``completion_reason``) rides the PR 2
metrics JSONL via ``logging.metrics_path`` and is accepted by
``automodel_tpu report --strict``.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from pathlib import Path
from typing import Any, Optional

logger = logging.getLogger(__name__)

# Retry-After advice on retriable rejections (503s): long enough for a
# drain to finish or a queue burst to clear, short enough to keep clients
# live. A load balancer should prefer another replica immediately. With
# QoS enabled the advice is SCALED BY TIER (interactive 1×, batch 2×,
# best_effort 3×): lower tiers back off longer, so the first capacity
# that frees up goes to the tier the operator ranked higher.
RETRY_AFTER_S = 5


def _tier_retry_after(tier: Any) -> int:
    """Tier-scaled Retry-After seconds (falls back to the flat advice on
    an unknown/absent tier — a rejection must never raise over advice)."""
    from automodel_tpu.serving.engine import tier_index

    try:
        return RETRY_AFTER_S * (tier_index(str(tier)) + 1)
    except (TypeError, ValueError):
        return RETRY_AFTER_S


def _encode_prompt(req: dict, tokenizer: Any) -> list[int]:
    if req.get("prompt_ids") is not None:
        return [int(t) for t in req["prompt_ids"]]
    prompt = req.get("prompt")
    if prompt is None:
        raise ValueError("request needs 'prompt' or 'prompt_ids'")
    if tokenizer is None:
        # token-id mode (tiny from-config models): same convention as the
        # generate CLI — whitespace/comma-separated ids
        toks = str(prompt).replace(",", " ").split()
        try:
            return [int(t) for t in toks]
        except ValueError:
            raise ValueError(
                "no tokenizer available: 'prompt' must be token ids "
                "(e.g. \"1 2 3\") or configure generation.tokenizer"
            )
    if callable(tokenizer):
        return tokenizer(str(prompt), add_special_tokens=True)["input_ids"]
    return tokenizer.encode(str(prompt))


def _decode_completion(tokens: list[int], tokenizer: Any) -> str:
    if tokenizer is None:
        return " ".join(map(str, tokens))
    return tokenizer.decode(tokens, skip_special_tokens=True)


def _drain_exit_code(drain_cfg: Any) -> int:
    """0 after a clean drain — or the launchers' requeue code (75) so a
    drained replica under slurm/k8s is restarted instead of counted as
    done. ``auto`` sniffs the launcher env the PR 3/5 requeue rules key on."""
    from automodel_tpu.resilience import REQUEUE_EXIT_CODE

    if drain_cfg.requeue_exit == "always":
        return REQUEUE_EXIT_CODE
    if drain_cfg.requeue_exit == "never":
        return 0
    under_launcher = (
        "SLURM_JOB_ID" in os.environ or "KUBERNETES_SERVICE_HOST" in os.environ
    )
    return REQUEUE_EXIT_CODE if under_launcher else 0


# /stats key → the /metrics family carrying the same fact. The drift guard
# (tests/test_fleet_health.py) walks this table both ways: every /stats key
# must appear here, and every serve-family metric must be reachable from it
# or listed in STATS_METRICS_ONLY. None marks info keys with no numeric
# metric; a tuple means the stats value is the SUM of those families;
# "allocator" fans out to automodel_serve_block_<counter-key> per entry.
STATS_METRIC_EQUIV = {
    "queue_depth": "automodel_serve_queue_depth",
    "busy_slots": (
        "automodel_serve_running_slots",
        "automodel_serve_prefilling_slots",
    ),
    "completed_total": "automodel_serve_requests_completed",
    "failed_total": "automodel_serve_requests_failed",
    "shed_total": "automodel_serve_requests_shed",
    "timeout_total": "automodel_serve_requests_timeout",
    "stall_total": "automodel_serve_engine_stalls",
    "error_total": "automodel_serve_engine_errors",
    "draining": "automodel_serve_draining",
    "drain_duration_s": "automodel_serve_drain_duration_seconds",
    "block_occupancy": "automodel_serve_block_occupancy",
    "blocks_in_use": "automodel_serve_blocks_in_use",
    "allocator": "automodel_serve_block_*",
    "decode_backend": None,
    "kv_cache_dtype": None,
    "spec_proposed_total": (
        "automodel_serve_spec_accepted",
        "automodel_serve_spec_rejected",
    ),
    "spec_accepted_total": "automodel_serve_spec_accepted",
    "spec_accept_rate": "automodel_serve_spec_accept_rate",
    "role": None,
    "block_size": None,
    "kv_transfer_port": None,
    "kv_injected_total": "automodel_serve_kv_injected",
    "hot_prefixes": None,
    "spill_bytes": "automodel_serve_spill_bytes",
    "spill_entries": "automodel_serve_spill_entries",
    # elastic fleet: boot provenance (time_to_ready_s is null until the
    # first readiness; boot_source is an info string)
    "time_to_ready_s": "automodel_serve_time_to_ready_seconds",
    "boot_source": None,
    # multi-tenant QoS: over-quota rejections, plus the per-tier/per-tenant
    # queue/served breakdown (an info dict — the numeric facts ride the
    # labeled automodel_serve_tier_*/tenant_* families below)
    "quota_total": "automodel_serve_requests_quota",
    "qos": None,
    # live hot-swap (engine.swap_weights): monotonic weights generation —
    # the router reads per-replica version skew off this during a rolling
    # update
    "weights_version": "automodel_serve_weights_version",
    # the scheduler thread's account (an info dict: its numbers ride the
    # labeled automodel_serve_loop_* families below)
    "loop_account": None,
}

# Families deliberately absent from /stats: per-request distributions have
# no single-number snapshot (histograms), and generated_tokens is observed
# per completion record rather than tracked on the engine.
STATS_METRICS_ONLY = (
    "automodel_serve_ttft_seconds",
    "automodel_serve_decode_tps",
    "automodel_serve_queue_seconds",
    "automodel_serve_stage_seconds",
    "automodel_serve_generated_tokens",
    # QoS labeled families: per-tier/per-tenant breakdowns whose /stats
    # shape is the "qos" info dict, not a single number
    "automodel_serve_tier_requests",
    "automodel_serve_tenant_requests",
    "automodel_serve_tier_ttft_seconds",
    # the loop account by phase / by count: /stats has it as "loop_account"
    "automodel_serve_loop_seconds",
    "automodel_serve_loop_events",
)


def stats_snapshot(engine: Any) -> dict:
    """The GET /stats body. Factored out of the handler so the drift guard
    can build it against a bare engine; call under the engine-loop lock
    when the scheduler is live."""
    return {
        "queue_depth": engine.queue_depth,
        "busy_slots": engine.busy_slots,
        "completed_total": engine.completed_total,
        "failed_total": engine.failed_total,
        "shed_total": engine.shed_total,
        "timeout_total": engine.timeout_total,
        "stall_total": engine.stall_total,
        "error_total": engine.error_total,
        "draining": engine.draining,
        "drain_duration_s": engine.drain_duration_s,
        "block_occupancy": engine.pool.occupancy(),
        "blocks_in_use": engine.pool.in_use(),
        "allocator": dict(engine.pool.counters),
        "decode_backend": engine.decode_backend,
        "kv_cache_dtype": engine.config.kv_cache_dtype,
        "spec_proposed_total": engine.spec_proposed_total,
        "spec_accepted_total": engine.spec_accepted_total,
        "spec_accept_rate": engine.spec_accept_rate,
        # fleet tier (serving/fleet/router.py probes these): role for pool
        # membership, block_size so the router can refuse affinity on a
        # geometry mismatch, hot_prefixes for prefix-affinity placement,
        # kv_transfer_port for the prefill→decode handoff
        "role": engine.config.role,
        "block_size": engine.config.block_size,
        "kv_transfer_port": engine.kv_transfer_port,
        "kv_injected_total": engine.kv_injected_total,
        "hot_prefixes": engine.hot_prefixes(),
        # hierarchical KV cache: host-tier occupancy (null when
        # serving.kv_spill is off; counters ride "allocator")
        "spill_bytes": (
            engine.pool.spill.bytes
            if engine.pool.spill is not None else None
        ),
        "spill_entries": (
            len(engine.pool.spill)
            if engine.pool.spill is not None else None
        ),
        # elastic fleet: which boot path this replica took and how long
        # startup→first-readiness took (the warm-vs-cold A/B number)
        "time_to_ready_s": engine.time_to_ready_s,
        "boot_source": engine.boot_source,
        # live hot-swap: which weights generation this replica serves
        "weights_version": engine.weights_version,
        # multi-tenant QoS: over-quota rejections + per-tier/per-tenant
        # queue and outcome breakdown (fleet-status renders these)
        "quota_total": engine.quota_total,
        "qos": engine.qos_snapshot(),
        # where the scheduler thread's time went since the engine was built
        # (seconds a step phase, `outside_step` = this front) and what it
        # did (docs/observability.md "Loop account")
        "loop_account": engine.loop_account(),
    }


_OK_REASONS = ("stop", "length")


def _reason_status(reason: str) -> int:
    """HTTP status for a terminal record that is not a completion."""
    if reason in _OK_REASONS:
        return 200
    if reason == "timeout":
        return 504  # the client's own budget expired — not retriable
    if reason == "quota":
        return 429  # over-quota: retriable AFTER Retry-After, not elsewhere
    return 503  # draining / cancelled / engine_stall / engine_error: retry


class _EngineLoop:
    """Background scheduler thread for the HTTP mode: handlers submit under
    the lock and wait on a per-request event; the loop steps the engine
    whenever there is work (and keeps stepping through a drain so in-flight
    requests finish and grace-expiry cancellations run)."""

    def __init__(self, engine: Any):
        self.engine = engine
        self.lock = threading.Lock()
        self._events: dict[str, threading.Event] = {}
        self._results: dict[str, dict] = {}
        self._abandoned: set[str] = set()  # timed-out waiters: drop on finish
        self.error: Optional[str] = None  # scheduler-thread death, terminal
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="serve-scheduler", daemon=True
        )

    def start(self) -> None:
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)

    def alive(self) -> bool:
        return self._thread.is_alive() and self.error is None

    def submit_blocking(
        self,
        prompt_ids: list[int],
        req: dict,
        timeout_s: float,
        submit: Optional[Any] = None,
        trace: Optional[Any] = None,
    ) -> dict:
        """``submit`` (optional, called under the lock) replaces the plain
        ``engine.submit`` — the /prefill and KV-handoff paths enqueue
        through their own entry points but share this wait machinery.
        ``trace`` is the propagated traceparent context (the engine mints
        its root span as a child of it)."""
        from automodel_tpu.serving.engine import QueueFull, QuotaExceeded

        ev = threading.Event()
        with self.lock:
            if self.error is not None:
                raise RuntimeError(f"serving engine is down: {self.error}")
            try:
                if submit is not None:
                    rid = submit()
                else:
                    kvp = req.get("kv_peer")  # router prefix-fetch hint
                    rid = self.engine.submit(
                        prompt_ids,
                        max_new_tokens=req.get("max_new_tokens"),
                        deadline_s=req.get("deadline_s"),
                        max_queue_wait_s=req.get("max_queue_wait_s"),
                        trace=trace,
                        kv_peer=kvp if isinstance(kvp, dict) else None,
                        return_logprobs=bool(req.get("return_logprobs")),
                        tenant=req.get("tenant"),
                        tier=req.get("tier"),
                    )
            except QueueFull:
                # the HTTP front sheds immediately — a blocked handler
                # thread per queued-out client is exactly the unbounded
                # latency shedding exists to prevent. ONE tier-labeled
                # record per give-up, never per retry (tests/test_qos.py
                # pins this seam).
                self.engine.record_shed(
                    prompt_ids=prompt_ids,
                    tenant=req.get("tenant"), tier=req.get("tier"),
                )
                raise
            except QuotaExceeded as e:
                # same seam doctrine as record_shed: submit raised without
                # a record, the answering front counts exactly one
                self.engine.record_quota(
                    prompt_ids=prompt_ids, tenant=e.tenant, tier=e.tier
                )
                raise
            self._events[rid] = ev
        if not ev.wait(timeout=timeout_s):
            with self.lock:
                self._events.pop(rid, None)
                # the request can't be cancelled mid-flight: remember the
                # abandonment so its eventual completion is discarded
                # instead of accumulating in _results forever
                self._abandoned.add(rid)
            raise TimeoutError(f"request {rid} timed out after {timeout_s}s")
        with self.lock:
            if self.error is not None and rid not in self._results:
                raise RuntimeError(f"serving engine died: {self.error}")
            return self._results.pop(rid)

    def _loop(self) -> None:
        while not self._stop.is_set():
            with self.lock:
                try:
                    idle = self.engine.idle()
                    done = [] if idle else self.engine.step()
                except Exception as e:  # scheduler death is TERMINAL, not silent
                    self.error = f"{type(e).__name__}: {e}"
                    logger.exception("serving scheduler thread died")
                    # wake every waiter so handlers return 503 immediately
                    # instead of blocking to their timeout
                    for ev in self._events.values():
                        ev.set()
                    self._events.clear()
                    return
                for rec in done:
                    rid = rec["request_id"]
                    ev = self._events.pop(rid, None)
                    if rid in self._abandoned:
                        self._abandoned.discard(rid)  # waiter gave up: drop
                        continue
                    self._results[rid] = rec
                    if ev is not None:
                        ev.set()
            if idle:
                # an idle server is healthy, not hung: keep the stall
                # watchdog's heartbeat fresh without counting a step
                self.engine.touch_watchdog()
                time.sleep(0.005)


def serve_http(
    engine: Any,
    tokenizer: Any,
    port: int,
    host: str = "127.0.0.1",
    kv_store: Any = None,
    on_retire: Any = None,
):
    """→ (ThreadingHTTPServer, _EngineLoop), both started. The caller calls
    ``server.serve_forever()`` (CLI) or drives requests itself (tests) and
    shuts both down. ``kv_store`` (a fleet ``HandoffStore``) arms the
    disaggregated paths: POST /generate with a ``handoff_id`` claims a
    transferred prefill payload from it. ``on_retire(migrate, deadline_s)``
    (optional, run on its own thread) arms POST /retire — the autoscaler's
    scale-down entry point: drain, optionally migrate hot prefix blocks to
    the survivor named in ``migrate``, then exit."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    loop = _EngineLoop(engine)
    loop.start()

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # route to logging, not stderr
            logger.debug("http: " + fmt, *args)

        def _json(
            self, code: int, obj: dict, retry_after: Any = False
        ) -> None:
            # retry_after: False = no header, True = flat advice, a
            # number = that many seconds (the tier-scaled QoS advice)
            body = (json.dumps(obj) + "\n").encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if retry_after:
                secs = (
                    RETRY_AFTER_S if retry_after is True else int(retry_after)
                )
                self.send_header("Retry-After", str(secs))
            self.end_headers()
            self.wfile.write(body)

        def _retry_advice(self, req: dict) -> int:
            """Tier-scaled Retry-After for this request: explicit tier,
            else the tenant's configured default, else the global one."""
            qos = engine.config.qos
            tier = req.get("tier")
            if tier is None:
                tenant = req.get("tenant")
                tier = (
                    qos.tier_for(str(tenant))
                    if tenant is not None else qos.default_tier
                )
            return _tier_retry_after(tier)

        def _stash_qos_headers(self, req: dict) -> None:
            """The router forwards tenant/tier as X-Tenant-Id / X-Tier
            headers (same vehicle as traceparent); body fields from
            bare-bones clients win so a direct caller stays authoritative
            over a middlebox."""
            if req.get("tenant") is None:
                h = self.headers.get("X-Tenant-Id")
                if h is not None:
                    req["tenant"] = h
            if req.get("tier") is None:
                h = self.headers.get("X-Tier")
                if h is not None:
                    req["tier"] = h

        def do_GET(self):
            if self.path == "/metrics":
                # Prometheus text exposition (telemetry/prometheus.py):
                # histograms were observed per completion; gauges + pool
                # counters sync here, under the engine lock, so a scrape is
                # one consistent snapshot
                from automodel_tpu.telemetry.prometheus import CONTENT_TYPE

                with loop.lock:
                    engine.metrics.sync(engine)
                    body = engine.metrics.registry.render().encode()
                self.send_response(200)
                self.send_header("Content-Type", CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if self.path == "/healthz":
                # liveness: the scheduler thread exists and its last step
                # boundary is inside the stall watchdog's deadline. An IDLE
                # engine is healthy by definition — no steps run, so age is
                # meaningless there. Deliberately LOCK-FREE: during the
                # exact wedged-step stall this endpoint exists to report,
                # the scheduler thread holds loop.lock inside engine.step()
                # — taking it here would hang the kubelet's probe instead
                # of answering 503. Everything read is a GIL-atomic
                # attribute (the same contract the watchdog thread relies
                # on), at worst one step stale.
                alive = loop.alive()
                idle = engine.idle()
                age = engine.last_step_age_s
                wd = engine.watchdog
                deadline = wd.deadline_s if wd is not None else None
                ok = alive and (
                    idle or wd is None or age is None or age <= deadline
                )
                return self._json(200 if ok else 503, {
                    "ok": ok,
                    "scheduler_alive": alive,
                    "idle": idle,
                    "last_step_age_s": age,
                    "stall_deadline_s": deadline,
                    "error": loop.error,
                })
            if self.path == "/readyz":
                # readiness: drop out of the load balancer while draining,
                # and never advertise before the first decode compiled (the
                # warm-up request flips this at startup). Lock-free for the
                # same reason as /healthz — a stalled scheduler must not
                # make the probe hang.
                ready = (
                    loop.alive()
                    and not engine.draining
                    and engine.first_decode_done
                )
                if ready:
                    # idempotent time_to_ready_s stamp: warmup-disabled
                    # servers reach readiness on their first true probe
                    engine.note_ready()
                return self._json(200 if ready else 503, {
                    "ready": ready,
                    "draining": engine.draining,
                    "first_decode_done": engine.first_decode_done,
                })
            if self.path != "/stats":
                return self._json(404, {"error": f"unknown path {self.path}"})
            with loop.lock:
                self._json(200, stats_snapshot(engine))

        def _read_req(self) -> dict:
            n = int(self.headers.get("Content-Length", 0))
            req = json.loads(self.rfile.read(n) or b"{}")
            if not isinstance(req, dict):
                raise ValueError("request body is not a JSON object")
            return req

        def _trace_ctx(self, req: dict):
            """Propagated trace context: the W3C ``traceparent`` HTTP
            header (the router sets it), with a body field fallback for
            bare-bones clients. None = this engine roots a new trace."""
            tracer = getattr(engine, "tracer", None)
            if tracer is None:
                return None
            return tracer.parse(
                self.headers.get("traceparent") or req.get("traceparent")
            )

        def _prefill(self):
            """Disaggregated fleet: run chunked prefill ONLY, then stream
            the finished KV block rows to the decode replica named in
            ``transfer: {host, port, handoff_id}``. Responds after the
            receiver acked — the router's follow-up /generate can never
            race the transfer."""
            from automodel_tpu.serving.engine import EngineDraining, QueueFull
            from automodel_tpu.serving.fleet.kv_transfer import (
                KVTransferError,
                send_kv,
            )

            try:
                req = self._read_req()
                transfer = dict(req.get("transfer") or {})
                if not transfer.get("handoff_id") or not transfer.get("host") \
                        or transfer.get("port") is None:
                    return self._json(400, {
                        "error": "prefill needs transfer.{host,port,handoff_id}"
                    })
                ids = _encode_prompt(req, tokenizer)
                ctx = self._trace_ctx(req)
                rec = loop.submit_blocking(
                    ids, req, timeout_s=float(req.get("timeout_s", 300.0)),
                    submit=lambda: engine.submit(
                        ids, prefill_only=True,
                        deadline_s=req.get("deadline_s"),
                        max_queue_wait_s=req.get("max_queue_wait_s"),
                        trace=ctx,
                    ),
                )
            except (ValueError, TypeError) as e:
                return self._json(400, {"error": str(e)})
            except QueueFull as e:
                # submit_blocking already recorded the shed — mirroring
                # /generate, no second record here
                return self._json(
                    503, {"error": str(e), "retriable": True, "reason": "shed"},
                    retry_after=True,
                )
            except EngineDraining as e:
                return self._json(
                    503,
                    {"error": str(e), "retriable": True, "reason": "draining"},
                    retry_after=True,
                )
            except TimeoutError as e:
                return self._json(504, {"error": str(e)})
            except RuntimeError as e:
                return self._json(503, {"error": str(e), "retriable": True})
            reason = rec.get("completion_reason")
            if reason != "prefilled":
                code = _reason_status(reason)
                return self._json(code, {
                    "error": f"prefill ended as {reason}",
                    "completion_reason": reason,
                    "retriable": bool(rec.get("retriable")),
                }, retry_after=code == 503)
            try:
                with loop.lock:
                    payload = engine.pop_prefill_payload(rec["request_id"])
            except KeyError as e:
                # the bounded stash evicted this payload before pickup
                # (kv_transfer.max_pending prefills completed in between) —
                # a transient capacity condition, not a dead replica: answer
                # 503 retriable instead of dying without a response (which
                # the router would read as replica death)
                return self._json(
                    503, {"error": str(e), "retriable": True},
                    retry_after=True,
                )
            meta = {
                "handoff_id": str(transfer["handoff_id"]),
                "request_id": rec["request_id"],
                "prompt_len": payload["prompt_len"],
                "first_token": payload["first_token"],
                "geometry": engine.kv_geometry(),
            }
            # tracing: the KV handoff is its own stage — kv_send here,
            # parented under this request's prefill-side root; the context
            # rides the AKV1 header so the receiver's kv_receive span joins
            # the same trace
            tracer = getattr(engine, "tracer", None)
            root = payload.get("trace")
            send_ctx = None
            if tracer is not None and tracer.active(root):
                from automodel_tpu.telemetry.tracing import to_traceparent

                send_ctx = tracer.start(parent=root)
                meta["traceparent"] = to_traceparent(send_ctx)
            t_send0 = time.perf_counter()
            try:
                send_kv(
                    (str(transfer["host"]), int(transfer["port"])),
                    meta, payload["kv"],
                )
            except KVTransferError as e:
                if send_ctx is not None:
                    tracer.record(
                        send_ctx, "kv_send", t_send0,
                        request_id=rec["request_id"], error=str(e)[:200],
                    )
                return self._json(
                    502, {"ok": False, "error": str(e), "retriable": True}
                )
            if send_ctx is not None:
                tracer.record(
                    send_ctx, "kv_send", t_send0,
                    request_id=rec["request_id"],
                    handoff_id=meta["handoff_id"],
                    prompt_tokens=payload["prompt_len"],
                )
            return self._json(200, {
                "ok": True,
                "handoff_id": meta["handoff_id"],
                "first_token": payload["first_token"],
                "prompt_tokens": payload["prompt_len"],
                "prefix_hit_tokens": rec.get("prefix_hit_tokens", 0),
                "ttft_s": rec.get("ttft_s"),
            })

        def _swap_weights(self):
            """Live weight hot-swap: ``{"peer": {"host", "port"},
            "timeout_s": s}``. Fetches the replacement tree over the AKV1
            ``weights_fetch`` op from the peer (the post-training trainer
            runs the listener), validates it against the param-tree
            signature under the engine lock, stages the swap, then waits
            for it to land — in-flight requests finish under the old
            weights first. A signature mismatch answers 409 with the old
            params untouched."""
            from automodel_tpu.serving.fleet.kv_transfer import (
                KVTransferError,
                fetch_weights,
            )

            try:
                req = self._read_req()
            except (ValueError, TypeError) as e:
                return self._json(400, {"error": str(e)})
            peer = req.get("peer")
            if not (
                isinstance(peer, dict)
                and peer.get("host")
                and peer.get("port") is not None
            ):
                return self._json(400, {
                    "error": "swap_weights needs peer.{host, port}"
                })
            timeout_s = float(req.get("timeout_s", 120.0))
            t0 = time.perf_counter()
            try:
                _, arrays = fetch_weights(
                    (str(peer["host"]), int(peer["port"])),
                    timeout_s=timeout_s,
                )
            except (KVTransferError, OSError) as e:
                return self._json(502, {"ok": False, "error": str(e)})
            # the flat {leaf-name: array} dict IS a valid pytree whose
            # signature matches the nested tree (dict keys are the joined
            # path names) — swap_weights rebinds leaves by name anyway
            try:
                with loop.lock:
                    target = engine.swap_weights(arrays)
            except ValueError as e:
                return self._json(409, {
                    "ok": False, "error": str(e),
                    "weights_version": engine.weights_version,
                })
            # the staged swap applies at the scheduler's next idle step
            # boundary; weights_version is a GIL-atomic int, so this poll
            # is deliberately lock-free (mirror of /healthz)
            deadline = t0 + timeout_s
            while (
                engine.weights_version < target
                and time.perf_counter() < deadline
                and loop.alive()
            ):
                time.sleep(0.01)
            if engine.weights_version < target:
                return self._json(504, {
                    "ok": False, "staged": True,
                    "error": (
                        f"swap staged but in-flight requests did not clear "
                        f"within {timeout_s}s"
                    ),
                    "weights_version": engine.weights_version,
                })
            return self._json(200, {
                "ok": True,
                "weights_version": engine.weights_version,
                "swap_s": round(time.perf_counter() - t0, 6),
            })

        def do_POST(self):
            if self.path == "/prefill":
                return self._prefill()
            if self.path == "/swap_weights":
                return self._swap_weights()
            if self.path == "/retire":
                # elastic fleet scale-down: ``{"migrate": {"host", "port"}
                # | null, "deadline_s": s}``. Responds 200 IMMEDIATELY and
                # runs drain → migrate → exit on a background thread — the
                # autoscaler must not block a probe sweep on a drain, and
                # the retiring process, not the caller, owns the deadline.
                if on_retire is None:
                    return self._json(400, {
                        "error": "this server has no retire hook "
                        "(the serve CLI front arms it)"
                    })
                try:
                    req = self._read_req()
                except (ValueError, TypeError) as e:
                    return self._json(400, {"error": str(e)})
                migrate = req.get("migrate")
                if migrate is not None and not (
                    isinstance(migrate, dict)
                    and migrate.get("host")
                    and migrate.get("port") is not None
                ):
                    return self._json(400, {
                        "error": "migrate must be null or {host, port}"
                    })
                deadline_s = float(req.get("deadline_s", 30.0))
                threading.Thread(
                    target=on_retire, args=(migrate, deadline_s),
                    name="serve-retire", daemon=True,
                ).start()
                return self._json(200, {
                    "ok": True,
                    "draining": True,
                    "migrate": migrate is not None,
                    "deadline_s": deadline_s,
                })
            if self.path != "/generate":
                return self._json(404, {"error": f"unknown path {self.path}"})
            from automodel_tpu.serving.engine import (
                EngineDraining,
                QueueFull,
                QuotaExceeded,
            )

            req = {}
            try:
                req = self._read_req()
                self._stash_qos_headers(req)
                ids = _encode_prompt(req, tokenizer)
                ctx = self._trace_ctx(req)
                submit = None
                if req.get("handoff_id") is not None:
                    # disaggregated decode: claim the transferred prefill
                    # payload and start the request directly in decode
                    if kv_store is None:
                        return self._json(400, {
                            "error": "this replica runs no KV-transfer "
                            "listener (serving.role: decode, or "
                            "serving.kv_transfer.enabled: true)"
                        })
                    try:
                        entry = kv_store.pop(str(req["handoff_id"]))
                    except KeyError as e:
                        # never arrived / expired: the router retries the
                        # whole prefill→decode flow elsewhere
                        return self._json(
                            409, {"error": str(e), "retriable": True}
                        )
                    submit = lambda: engine.submit_prefilled(  # noqa: E731
                        ids, entry["meta"]["first_token"], entry["kv"],
                        max_new_tokens=req.get("max_new_tokens"),
                        deadline_s=req.get("deadline_s"),
                        max_queue_wait_s=req.get("max_queue_wait_s"),
                        trace=ctx,
                    )
                rec = loop.submit_blocking(
                    ids, req, timeout_s=float(req.get("timeout_s", 300.0)),
                    submit=submit, trace=ctx,
                )
            except (ValueError, TypeError) as e:
                return self._json(400, {"error": str(e)})
            except QueueFull as e:
                # overload SHED: an explicit retriable signal the client
                # (or its load balancer) can act on — never a dropped
                # connection, never an unbounded queue
                return self._json(
                    503, {"error": str(e), "retriable": True, "reason": "shed"},
                    retry_after=self._retry_advice(req),
                )
            except QuotaExceeded as e:
                # over-quota: retriable after the (tier-scaled) Retry-After
                # on THIS replica — a 429, not a 503, so load balancers
                # don't burn retry budget hopping replicas that share the
                # same per-tenant policy
                return self._json(
                    429,
                    {"error": str(e), "retriable": True, "reason": "quota",
                     "tenant": e.tenant, "tier": e.tier},
                    retry_after=_tier_retry_after(e.tier),
                )
            except EngineDraining as e:
                return self._json(
                    503,
                    {"error": str(e), "retriable": True, "reason": "draining"},
                    retry_after=self._retry_advice(req),
                )
            except TimeoutError as e:
                return self._json(504, {"error": str(e)})
            except RuntimeError as e:  # scheduler thread died
                return self._json(503, {"error": str(e)})
            out = dict(rec)
            out["completion"] = _decode_completion(rec["tokens"], tokenizer)
            if req.get("id") is not None:
                out["id"] = req["id"]
            reason = rec.get("completion_reason", "length")
            code = _reason_status(reason)
            self._json(
                code, out,
                retry_after=self._retry_advice(req) if code == 503 else False,
            )

    server = ThreadingHTTPServer((host, port), Handler)
    server._engine_loop = loop  # for the caller's shutdown path
    return server, loop


def _install_drain_handler(engine: Any, on_term=None):
    """Chain SIGTERM → drain through the PR 3 PreemptionHandler (prior
    handlers — libtpu, cluster agents — still run). → the installed
    handler, or None when serving.drain.install_signal_handler is off or
    this is not the main thread (signal.signal would raise)."""
    drain_cfg = engine.config.drain
    if not drain_cfg.install_signal_handler:
        return None
    if threading.current_thread() is not threading.main_thread():
        return None
    from automodel_tpu.resilience.preemption import PreemptionHandler

    handler = PreemptionHandler(
        signals=("SIGTERM",),
        on_preempt=on_term,
        log_message=(
            "serving drain: rejecting new requests retriable, finishing "
            f"in-flight within serving.drain.grace_s={drain_cfg.grace_s}"
        ),
    )
    try:
        handler.install()
    except ValueError:  # non-main-thread despite the check (exotic embeds)
        return None
    return handler


def _warmup(engine: Any) -> None:
    """One tiny request through the engine before the front opens: absorbs
    the prefill/decode compiles (ttft of the FIRST real request) and flips
    ``first_decode_done`` so /readyz can go true. Best-effort."""
    try:
        vocab = int(getattr(engine.model.config, "vocab_size", 2))
        # max_new_tokens=2, not 1: a 1-token request completes at the
        # prefill tick and never runs (or compiles) the decode program —
        # readiness requires one real decode step
        engine.submit([min(1, max(vocab - 1, 0))], request_id="__warmup__",
                      max_new_tokens=2)
        engine.run()
    except Exception as e:
        logger.warning("serve warm-up request failed: %r", e)


def _tree_path_name(path) -> str:
    """The param-tree leaf naming rule — MUST match
    ``checkpoint.checkpointer.param_tree_signature`` exactly, so signature
    entries and wire-transferred leaves line up one-to-one."""
    return "/".join(
        str(getattr(k, "key", getattr(k, "idx", k))) for k in path
    )


def _warm_start_params(auto: Any, ws: Any) -> bool:
    """Peer warm-start (docs/serving.md "Elastic fleet"): stream the whole
    param tree from the serving peer named in ``serving.warm_start`` and
    swap it under this replica's structurally built tree. The peer's
    param-tree signature must digest-match this replica's own (the PR 6
    checkpoint guard) BEFORE any leaf is swapped — a mismatch means the
    architectures differ and cold load is the only correct path. → True
    when the swap landed; False (after logging) on ANY failure, leaving
    the cold-built params untouched."""
    import jax

    from automodel_tpu.checkpoint.checkpointer import param_tree_signature
    from automodel_tpu.serving.fleet.kv_transfer import (
        KVTransferError,
        fetch_weights,
    )

    addr = (str(ws.peer_host), int(ws.peer_port))
    t0 = time.perf_counter()
    try:
        expected = param_tree_signature(auto.params)
        sig, arrays = fetch_weights(addr, timeout_s=ws.timeout_s)
        if sig.get("digest") != expected["digest"]:
            raise KVTransferError(
                f"peer param-tree signature {sig.get('digest')!r} != this "
                f"replica's {expected['digest']!r} — the peer serves a "
                "different architecture/shape/dtype tree"
            )
        leaves, treedef = jax.tree_util.tree_flatten_with_path(auto.params)
        new_leaves = []
        for path, leaf in leaves:
            name = _tree_path_name(path)
            arr = arrays.get(name)
            if arr is None:
                # digest match makes this unreachable short of a hostile
                # peer — still a loud fallback, never a KeyError
                raise KVTransferError(f"peer stream is missing leaf {name}")
            new_leaves.append(jax.device_put(arr, leaf.sharding))
        auto.params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        logger.info(
            "peer warm-start from %s:%d landed %d leaves in %.3fs",
            addr[0], addr[1], len(new_leaves), time.perf_counter() - t0,
        )
        return True
    except Exception as e:
        # the fallback ladder: ANY failure — refused, died mid-stream,
        # signature mismatch — keeps the cold-built params
        logger.warning(
            "peer warm-start from %s:%d failed (%s: %s); cold load",
            addr[0], addr[1], type(e).__name__, e,
        )
        return False


def main(cfg: Any) -> int:
    """`automodel_tpu serve -c cfg.yaml` (stdin-JSONL, or HTTP when
    serving.http.port is set)."""
    from automodel_tpu.generation.engine import (
        GenerationConfig,
        build_auto_from_cfg,
        resolve_tokenizer,
    )
    from automodel_tpu.loggers.log_utils import setup_logging
    from automodel_tpu.serving.engine import ServeConfig, ServingEngine

    setup_logging()
    # time_to_ready_s starts here — BEFORE the model build, because load
    # time is exactly what peer warm-start exists to cut
    t_boot = time.perf_counter()
    serve_section = dict(cfg.get("serving", {}) or {})
    http_section = dict(serve_section.get("http") or {})
    serve_cfg = ServeConfig.from_dict(serve_section)
    gen_section = dict(cfg.get("generation", {}) or {})
    gen_cfg = GenerationConfig.from_dict(gen_section)
    tokenizer = resolve_tokenizer(
        gen_section.get("tokenizer"),
        cfg.model.get("pretrained_model_name_or_path"),
    )

    auto = build_auto_from_cfg(cfg)
    # elastic-fleet boot ladder: peer warm-start when configured, cold HF
    # otherwise (and as the fallback when any part of the fetch fails).
    # The injected hf_load_delay_ms cold-load cost (fault_injection.py)
    # applies ONLY on the cold path — it stands in for the real HF
    # download/parse time a warm start skips, so the time_to_ready_s A/B
    # is measurable on tiny CPU models.
    boot_source = "cold_hf"
    if serve_cfg.warm_start.enabled:
        if _warm_start_params(auto, serve_cfg.warm_start):
            boot_source = "peer_warm_start"
    if boot_source == "cold_hf":
        from automodel_tpu.resilience.fault_injection import active_injector

        inj = active_injector()
        if inj is not None:
            inj.maybe_hf_load_delay()
    on_record = None
    metrics_path = (cfg.get("logging") or {}).get("metrics_path") if cfg.get("logging") else None
    metric_logger = None
    if metrics_path:
        from automodel_tpu.loggers.metric_logger import MetricLogger

        metric_logger = MetricLogger(metrics_path)

        def on_record(rec: dict) -> None:
            rec = dict(rec)
            rec.pop("tokens", None)  # completions don't belong in metrics
            metric_logger.log(rec)

    # request tracing (telemetry/tracing.py): spans ride the same metrics
    # JSONL as serve_request records — no metrics_path means no span sink,
    # so tracing silently has nowhere to write (documented)
    from automodel_tpu.telemetry.tracing import Tracer, TracingConfig

    tracing_cfg = TracingConfig.from_dict(dict(cfg.get("tracing", {}) or {}))
    tracer = Tracer.from_config(
        tracing_cfg,
        process=f"serve-{serve_cfg.role}-{os.getpid()}",
        emit=on_record,
    )

    engine = ServingEngine(
        auto, serve_cfg, gen_cfg, on_record=on_record, tracer=tracer
    )
    engine.boot_t = t_boot
    engine.boot_source = boot_source

    # once per boot: the device report; each program's `cost_attribution`
    # record follows the first time it runs (profiling section, on by
    # default like the training recipe's)
    from automodel_tpu.telemetry import device_report
    from automodel_tpu.telemetry.profiling import ProfilingConfig

    prof = ProfilingConfig.from_dict(dict(cfg.get("profiling") or {}))
    engine.collect_program_costs = prof.enabled and prof.cost_attribution
    report = device_report(
        auto.mesh_ctx, auto.model.backend, decode_backend=engine.decode_backend
    )
    logger.info("device report: %s", report)
    if on_record is not None:
        on_record(report)

    # fleet KV listener: a decode-role replica listens for prefill→decode
    # handoffs, and a spill-enabled replica listens for peer /kv_fetch
    # (serving.kv_transfer.enabled: null = auto-on for either role); the
    # bound port is advertised to the router via /stats
    kv_server = None
    ktc = serve_cfg.kv_transfer
    kv_on = (
        ktc.enabled
        if ktc.enabled is not None
        else (serve_cfg.role == "decode" or serve_cfg.kv_spill.enabled)
    )
    if kv_on:
        from automodel_tpu.serving.fleet.kv_transfer import KVTransferServer

        kv_server = KVTransferServer(
            engine.kv_geometry(), host=ktc.host, port=ktc.port,
            max_pending=ktc.max_pending, ttl_s=ktc.ttl_s,
            max_frame_bytes=engine.kv_frame_bytes_bound(),
            tracer=engine.tracer,
        ).start()
        engine.kv_transfer_port = kv_server.port
        logger.info("KV-transfer listener on port %d", kv_server.port)

        # warm-start source for joining replicas: serve this replica's
        # param tree over ``op: weights_fetch``. A hot-swap can replace the
        # whole tree mid-serve, but never mutates leaves in place — one
        # GIL-atomic snapshot of the attribute up front keeps the streamed
        # signature and leaves from one consistent generation, so no
        # scheduler lock is needed.
        def _serve_weights():
            import jax

            from automodel_tpu.checkpoint.checkpointer import (
                param_tree_signature,
            )

            params = engine.auto.params
            sig = param_tree_signature(params)
            leaves = jax.tree_util.tree_flatten_with_path(params)[0]
            return sig, [
                (_tree_path_name(path), leaf) for path, leaf in leaves
            ]

        kv_server.weights_handler = _serve_weights

    # stall-watchdog evidence routing: stacks + flight recorder land next
    # to the metrics JSONL when one is configured (same layout the training
    # guard uses)
    flight_recorder = None
    stacks_path = None
    if metrics_path:
        try:
            from automodel_tpu.telemetry.flight_recorder import (
                FlightRecorder,
                build_fingerprint,
            )

            parent = Path(metrics_path).parent
            stacks_path = str(parent / "watchdog_stacks.txt")
            flight_recorder = FlightRecorder(
                path=str(parent / "flight_recorder.json"),
                fingerprint=build_fingerprint(
                    config=cfg.to_dict() if hasattr(cfg, "to_dict") else None,
                    mesh_ctx=auto.mesh_ctx,
                ),
            )
        except Exception as e:  # evidence plumbing must not block serving
            logger.warning("flight recorder unavailable: %r", e)
    engine.start_watchdog(
        flight_recorder=flight_recorder, metric_logger=metric_logger,
        stacks_path=stacks_path,
    )

    try:
        if http_section.get("port") is not None:
            return _serve_http_forever(
                engine, tokenizer, http_section, serve_cfg,
                kv_store=kv_server.store if kv_server is not None else None,
                kv_server=kv_server,
            )
        return _serve_stdin(engine, tokenizer, serve_cfg)
    finally:
        engine.stop_watchdog()
        if kv_server is not None:
            kv_server.close()
        if metric_logger is not None:
            metric_logger.close()


def retire_sequence(engine, loop, migrate, deadline_s: float) -> str:
    """Drain, then ship hot prefix blocks to the survivor — in that order,
    all inside ``deadline_s``. Runs on the serve-retire thread; the caller
    shuts the HTTP front down afterwards. Migration failure degrades to
    plain drain; NOTHING here may block retirement past the deadline.

    Returns the outcome record name (``migration_complete`` /
    ``migration_failed`` / ``migration_skipped``) so callers and tests can
    branch without re-parsing the JSONL.
    """
    t0 = time.monotonic()
    deadline = t0 + max(float(deadline_s), 0.0)
    engine.begin_drain()
    # in-flight requests finish under the scheduler as usual; stop
    # waiting at drain-completion, scheduler death, or the deadline
    # (whichever is first) so migration still gets its window
    while time.monotonic() < deadline:
        if engine.drain_complete() or not loop.alive():
            break
        time.sleep(0.05)
    migrated = 0
    available = 0
    error = None
    if migrate is not None and loop.alive():
        from automodel_tpu.serving.fleet.kv_transfer import (
            KVTransferError,
            push_kv,
        )

        try:
            with loop.lock:
                hashes, kv = engine.export_hot_blocks()
            available = len(hashes)
            if hashes:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise KVTransferError(
                        "retire deadline expired before the prefix push"
                    )
                migrated = push_kv(
                    (str(migrate["host"]), int(migrate["port"])),
                    hashes, kv, engine.kv_geometry(),
                    timeout_s=remaining,
                )
        except Exception as e:
            error = f"{type(e).__name__}: {str(e)[:200]}"
            logger.warning(
                "scale-down prefix migration to %s failed (%s); "
                "degrading to plain drain", migrate, error,
            )
    if migrate is None:
        outcome = "migration_skipped"
    elif error is not None:
        outcome = "migration_failed"
    else:
        outcome = "migration_complete"
    if engine.on_record is not None:
        engine.on_record({
            "event": outcome,
            "ts": engine._wall_ts(),
            "migrated_blocks": migrated,
            "hot_blocks": available,
            "retire_s": round(time.monotonic() - t0, 6),
            **({"error": error} if error else {}),
        })
    return outcome


def _serve_http_forever(
    engine, tokenizer, http_section, serve_cfg, kv_store=None, kv_server=None
) -> int:
    port = int(http_section["port"])
    host = str(http_section.get("host", "127.0.0.1"))
    drain_cfg = serve_cfg.drain
    if http_section.get("warmup", True):
        _warmup(engine)
        engine.note_ready()  # warmup flipped first_decode_done: stamp now
    state = {"rc": 0}

    def _retire(migrate, deadline_s: float):
        retire_sequence(engine, loop, migrate, deadline_s)
        state["rc"] = _drain_exit_code(drain_cfg)
        server.shutdown()

    server, loop = serve_http(
        engine, tokenizer, port, host=host, kv_store=kv_store,
        on_retire=_retire,
    )
    if kv_server is not None and serve_cfg.kv_spill.enabled:
        # peer /kv_fetch answers from the engine's pools, so the handler
        # must serialize with the scheduler: wired here — after the loop
        # (and its lock) exist — rather than at listener construction
        def _serve_fetch(chain_hashes):
            with loop.lock:
                return engine.fetch_prefix_blocks(chain_hashes)

        kv_server.fetch_handler = _serve_fetch

        # migration sink: a retiring peer's ``kv_push`` parks blocks in
        # this replica's spill tier (same lock discipline as /kv_fetch)
        def _serve_push(chain_hashes, kv):
            with loop.lock:
                return engine.receive_migrated_blocks(chain_hashes, kv)

        kv_server.push_handler = _serve_push

    def _drain_then_stop():
        # begin_drain only flips flags (GIL-atomic stores the scheduler
        # reads at its next iteration) — deliberately NOT taken under
        # loop.lock: if SIGTERM lands while a step is wedged (the stall
        # scenario), the scheduler holds the lock and the grace countdown
        # would never even start
        engine.begin_drain()
        # the scheduler thread keeps stepping: in-flight requests finish,
        # grace expiry cancels stragglers INSIDE engine.step — this thread
        # only watches for completion, with margin for a slow final step
        deadline = time.monotonic() + drain_cfg.grace_s + 10.0
        while time.monotonic() < deadline:
            if engine.drain_complete() or not loop.alive():
                break
            time.sleep(0.05)
        state["rc"] = _drain_exit_code(drain_cfg)
        server.shutdown()

    def _on_term():
        threading.Thread(
            target=_drain_then_stop, name="serve-drain", daemon=True
        ).start()

    handler = _install_drain_handler(engine, on_term=_on_term)
    print(
        json.dumps({
            "event": "serve_listening",
            "host": host, "port": server.server_address[1],
            "slots": serve_cfg.slots, "num_blocks": serve_cfg.num_blocks,
        }),
        flush=True,
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
        loop.close()
        if handler is not None:
            handler.restore()
    return state["rc"]


def _serve_stdin(engine, tokenizer, serve_cfg) -> int:
    """stdin-JSONL: submit every line, print terminal records as they
    happen. A bad line is THAT client's error — it gets an error JSON line
    and the batch continues; crashing here would destroy every other
    request's in-flight work. SIGTERM drains: remaining input is not read,
    queued requests are rejected retriable, in-flight requests finish
    within the grace."""
    import queue as queue_mod

    from automodel_tpu.serving.engine import (
        EngineDraining,
        QueueFull,
        QuotaExceeded,
    )

    drain_cfg = serve_cfg.drain
    handler = _install_drain_handler(engine)
    stdin = sys.stdin
    # a daemon reader thread feeds a queue: the scheduler loop never blocks
    # on stdin (completions stream out while input sits idle-open, SIGTERM
    # is observed between steps instead of inside a blocked read — PEP 475
    # would resume the read and swallow the drain), and select()'s
    # buffered-IO blind spot is avoided entirely
    lines_q: "queue_mod.Queue[str]" = queue_mod.Queue()

    def _reader():
        while True:
            line = stdin.readline()
            lines_q.put(line)  # "" = EOF sentinel
            if line == "":
                return

    threading.Thread(target=_reader, name="serve-stdin", daemon=True).start()

    counts = {"submitted": 0, "bad": 0}

    def handle_line(line: str, lineno: int) -> None:
        line = line.strip()
        if not line:
            return
        rid = None
        try:
            req = json.loads(line)
            if not isinstance(req, dict):
                raise ValueError("request line is not a JSON object")
            rid = req.get("id")
            ids = _encode_prompt(req, tokenizer)
            ctx = (
                engine.tracer.parse(req.get("traceparent"))
                if engine.tracer is not None else None
            )
            while True:
                try:
                    engine.submit(
                        ids,
                        request_id=str(rid) if rid is not None else None,
                        max_new_tokens=req.get("max_new_tokens"),
                        deadline_s=req.get("deadline_s"),
                        max_queue_wait_s=req.get("max_queue_wait_s"),
                        trace=ctx,
                        return_logprobs=bool(req.get("return_logprobs")),
                        tenant=req.get("tenant"),
                        tier=req.get("tier"),
                    )
                    break
                except QueueFull:
                    # bounded queue + unbounded stdin: absorb backpressure
                    # by draining a step — but if the step retired nothing
                    # and the queue is still full, SHED explicitly instead
                    # of spinning
                    before = engine.completed_total + engine.failed_total
                    for rec in engine.step():
                        _emit(rec, tokenizer)
                    if (
                        engine.completed_total + engine.failed_total == before
                        and engine.queue_depth >= engine.config.max_queue
                    ):
                        raise
        except QueueFull as e:
            # exactly ONE tier-labeled shed per given-up request, however
            # many backpressure retries the loop above absorbed
            engine.record_shed(
                request_id=str(rid) if rid is not None else None,
                tenant=req.get("tenant"), tier=req.get("tier"),
            )
            err = {
                "error": f"line {lineno}: {e}",
                "retriable": True, "reason": "shed",
            }
            if rid is not None:
                err["id"] = rid
            print(json.dumps(err), flush=True)
        except QuotaExceeded as e:
            engine.record_quota(
                request_id=str(rid) if rid is not None else None,
                tenant=e.tenant, tier=e.tier,
            )
            err = {
                "error": f"line {lineno}: {e}",
                "retriable": True, "reason": "quota",
                "tenant": e.tenant, "tier": e.tier,
            }
            if rid is not None:
                err["id"] = rid
            print(json.dumps(err), flush=True)
        except EngineDraining as e:
            err = {
                "error": f"line {lineno}: {e}",
                "retriable": True, "reason": "draining",
            }
            if rid is not None:
                err["id"] = rid
            print(json.dumps(err), flush=True)
        except (ValueError, TypeError) as e:
            counts["bad"] += 1
            err = {"error": f"line {lineno}: {e}"}
            if rid is not None:
                err["id"] = rid
            print(json.dumps(err), flush=True)
        else:
            counts["submitted"] += 1

    lineno = 0
    eof = False
    while not eof:
        if handler is not None and handler.preempted and not engine.draining:
            engine.begin_drain()
        if engine.draining:
            break
        got_line = False
        try:
            line = lines_q.get_nowait()
            if line == "":
                eof = True
            else:
                lineno += 1
                handle_line(line, lineno)
                got_line = True
        except queue_mod.Empty:
            pass
        # drain opportunistically so early completions stream out while
        # later lines are still being read (or while stdin sits idle-open)
        if not engine.idle():
            for rec in engine.step():
                _emit(rec, tokenizer)
        elif not got_line and not eof:
            engine.touch_watchdog()
            time.sleep(0.02)

    def _reject_buffered_lines() -> None:
        # lines the reader thread already pulled off the pipe are gone from
        # the client's side — dropping them silently on drain would break
        # the one-response-per-request contract, so each gets an explicit
        # retriable error line (they were never submitted, so there is no
        # engine record to emit)
        while True:
            try:
                line = lines_q.get_nowait()
            except queue_mod.Empty:
                return
            line = line.strip()
            if not line:
                continue
            err = {
                "error": "server is draining — retry against another replica",
                "retriable": True, "reason": "draining",
            }
            try:
                req = json.loads(line)
                if isinstance(req, dict) and req.get("id") is not None:
                    err["id"] = req["id"]
            except ValueError:
                pass
            print(json.dumps(err), flush=True)

    # EOF or drain: finish the remaining work. A SIGTERM landing in THIS
    # phase must still start the drain — the batch (pipe-then-close) case
    # spends almost its whole life here, after EOF. Iterations bounded by
    # the same analytic guard as ServingEngine.run.
    per_req = (
        -(-engine.config.max_seq_len // engine.config.prefill_chunk)
        + engine.config.max_seq_len
    )
    iter_bound = 64 + (engine.queue_depth + engine.busy_slots + 1) * (per_req + 2)
    drained_rc = None
    for _ in range(iter_bound):
        if handler is not None and handler.preempted and not engine.draining:
            engine.begin_drain()
        if engine.draining:
            _reject_buffered_lines()
            # engine.step rejects the queue retriable and cancels in-flight
            # requests once drain.grace_s expires
            if engine.drain_complete():
                drained_rc = _drain_exit_code(drain_cfg)
                break
        elif engine.idle():
            break
        for rec in engine.step():
            _emit(rec, tokenizer)
    else:
        raise RuntimeError(
            f"serving engine failed to drain within {iter_bound} iterations "
            f"(queue={engine.queue_depth}, busy={engine.busy_slots})"
        )
    if handler is not None:
        handler.restore()
    if drained_rc is not None:
        _reject_buffered_lines()  # lines that raced in during the drain
        return drained_rc
    if counts["submitted"] == 0:
        print(
            "no requests: pipe JSONL lines like "
            '{"prompt": "1 2 3", "max_new_tokens": 8} into stdin',
            file=sys.stderr,
        )
        return 2
    return 0 if counts["bad"] == 0 else 1


def _emit(rec: dict, tokenizer: Any) -> None:
    out = dict(rec)
    if out.get("event") == "serve_engine_event":
        # engine-level evidence (stall/rebuild) — pass through as-is
        print(json.dumps(out), flush=True)
        return
    out["completion"] = _decode_completion(out.pop("tokens", []), tokenizer)
    out.pop("event", None)
    print(json.dumps(out), flush=True)
