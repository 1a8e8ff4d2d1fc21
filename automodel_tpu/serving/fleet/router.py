"""`automodel_tpu route` — the fleet router above N serving replicas.

One ``ServingEngine`` is bounded by one chip's HBM; the router is the tier
that spreads heavy traffic over a fleet (docs/serving.md "Fleet"). It
keeps the SAME HTTP front contract as a single replica (POST /generate,
GET /stats /healthz /readyz /metrics), so a client — or a load balancer —
cannot tell a routed fleet from one engine.

Placement is **prefix affinity first, load second**:

1. The prompt's block chain is hashed with the SAME chain rule the
   replica's prefix cache keys its blocks under
   (:func:`automodel_tpu.serving.block_pool.prompt_chain` — deterministic
   across processes), and the replica whose advertised hot-prefix set
   (the ``hot_prefixes`` /stats field) contains the LONGEST match wins:
   its pool already holds the prompt's KV, so routing there turns a
   per-replica coin flip into a guaranteed hit.
2. No match → power-of-two-choices: two random ready replicas, the less
   loaded one (queue depth + busy slots from /stats) takes the request —
   near-best-of-N balancing at O(1) probe cost.

**Disaggregated prefill/decode** (Splitwise/DistServe): replicas declare a
role (``serving.role: prefill|decode|mixed``). When the fleet has prefill
replicas, a long prompt's math runs on one of them (POST /prefill), the
finished KV block rows stream to the chosen decode replica over the
:mod:`kv_transfer` socket transport, and the decode replica starts the
request directly in decode — long prompts never steal decode throughput.
A strong affinity hit (the decode replica already holds ≥ half the prompt)
bypasses the handoff entirely: recomputing the short tail is cheaper than
shipping it.

**Failure-aware retry**: every replica-side terminal record carries
``completion_reason`` + ``retriable`` (PR 9). The router resubmits
retriable failures (replica death, ``engine_stall``, shed, draining) to a
DIFFERENT replica within a bounded per-request ``retry_budget`` — a
replica killed mid-decode loses zero requests (tests/test_serving_chaos.py
pins it). Client-budget expiries (``timeout``) are never retried.

This module imports no jax: a router pod needs no accelerator.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import logging
import random
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
import uuid
import zlib
from typing import Any, Callable, Optional, Sequence

from automodel_tpu.serving.block_pool import prompt_chain

logger = logging.getLogger(__name__)

RETRY_AFTER_S = 5

# QoS tier order — MUST match serving/engine.py TIERS (not imported: the
# engine module pulls jax, and a router pod needs no accelerator). Unknown
# tiers rank as interactive here; the replica rejects them with a 400.
_TIER_ORDER = {"interactive": 0, "batch": 1, "best_effort": 2}


def _tier_label(tier: Any) -> str:
    """Bounded metrics label for a request's tier (arbitrary client
    strings must not mint label values)."""
    return tier if tier in _TIER_ORDER else "interactive"


def _tier_retry_after(tier: Any) -> int:
    """Tier-scaled Retry-After advice (mirror of serving/server.py):
    lower tiers back off longer, so freed capacity goes uphill first."""
    return RETRY_AFTER_S * (_TIER_ORDER.get(tier, 0) + 1)


def aggregate_qos(snapshots: Sequence[dict]) -> dict:
    """Fleet-wide QoS rollup: sum the per-replica /stats ``qos`` blocks
    (engine ``qos_snapshot``) into one queued/outcome view by tier and by
    tenant. Pure — fleet-status and its unit tests call it directly."""
    agg: dict = {
        "enabled": False,
        "queued_by_tier": {},
        "queued_by_tenant": {},
        "tiers": {},
        "tenants": {},
    }

    def _merge(dst: dict, src: dict) -> None:
        for k, v in (src or {}).items():
            if isinstance(v, dict):
                _merge(dst.setdefault(k, {}), v)
            elif v is not None:
                dst[k] = dst.get(k, 0) + v

    for snap in snapshots:
        if not isinstance(snap, dict):
            continue
        agg["enabled"] = agg["enabled"] or bool(snap.get("enabled"))
        for key in ("queued_by_tier", "queued_by_tenant", "tiers", "tenants"):
            _merge(agg[key], snap.get(key) or {})
    return agg


class ReplicaUnreachable(RuntimeError):
    """TCP-level failure talking to a replica (dead pod, reset socket):
    always retriable — the request never reached a scheduler."""


def _trace_headers(ctx) -> Optional[dict]:
    """traceparent header for a forward, or None when untraced. Lazy
    import: the tracing module is stdlib-only, but the router's import
    graph stays as small as it was."""
    if ctx is None:
        return None
    from automodel_tpu.telemetry.tracing import to_traceparent

    return {"traceparent": to_traceparent(ctx)}


@dataclasses.dataclass(frozen=True)
class ReplicaSpec:
    """One static ``fleet.replicas:`` entry."""

    url: str
    name: Optional[str] = None  # metrics label; default r0, r1, ...
    role: Optional[str] = None  # pin prefill|decode|mixed; None = from /stats

    def __post_init__(self):
        if self.role not in (None, "mixed", "prefill", "decode"):
            raise ValueError(
                f"fleet replica role={self.role!r} "
                "(want mixed|prefill|decode or omit)"
            )

    @classmethod
    def from_value(cls, v: Any, index: int) -> "ReplicaSpec":
        if isinstance(v, str):
            return cls(url=v, name=f"r{index}")
        d = dict(v)
        d.pop("_target_", None)
        unknown = set(d) - {"url", "name", "role"}
        if unknown:
            raise TypeError(f"unknown fleet replica keys: {sorted(unknown)}")
        if "url" not in d:
            raise TypeError("fleet replica needs a url")
        d.setdefault("name", f"r{index}")
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """The ``fleet:`` YAML section — the router's whole world."""

    replicas: tuple = ()  # static registry: urls or {url, name?, role?}
    dns: Optional[str] = None  # k8s headless service; re-resolved per probe
    dns_port: int = 8100  # replica HTTP port behind the DNS name
    port: Optional[int] = None  # router front port (`automodel_tpu route`)
    host: str = "127.0.0.1"
    block_size: int = 16  # MUST match the replicas' serving.block_size
    probe_interval_s: float = 2.0
    probe_timeout_s: float = 2.0
    # probe backoff: a replica that failed this many CONSECUTIVE probes
    # moves to an exponential schedule (doubling from probe_interval_s,
    # bounded by probe_backoff_max_s, deterministically jittered) instead
    # of costing a probe_timeout_s thread every sweep; first success snaps
    # it back to every sweep
    probe_backoff_after: int = 3
    probe_backoff_max_s: float = 30.0
    request_timeout_s: float = 300.0
    retry_budget: int = 2  # resubmissions per request (0 = never retry)
    affinity: bool = True  # prefix-affinity placement (else pure load)
    disaggregate: Optional[bool] = None  # null = auto (prefill replicas seen)
    drain_grace_s: float = 10.0  # SIGTERM → in-flight forward budget
    seed: int = 0  # power-of-two-choices rng

    def __post_init__(self):
        if self.retry_budget < 0:
            raise ValueError(f"fleet.retry_budget={self.retry_budget}")
        if self.block_size < 1:
            raise ValueError(f"fleet.block_size={self.block_size}")
        if self.probe_backoff_after < 1:
            raise ValueError(
                f"fleet.probe_backoff_after={self.probe_backoff_after}"
            )
        if self.probe_backoff_max_s < self.probe_interval_s:
            raise ValueError(
                f"fleet.probe_backoff_max_s={self.probe_backoff_max_s} must "
                f"be >= probe_interval_s={self.probe_interval_s} — a backoff "
                "shorter than the sweep cadence is no backoff at all"
            )

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "FleetConfig":
        d = dict(d or {})
        d.pop("_target_", None)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise TypeError(f"unknown fleet keys: {sorted(unknown)}")
        reps = d.get("replicas")
        if reps is not None:
            d["replicas"] = tuple(
                r if isinstance(r, ReplicaSpec) else ReplicaSpec.from_value(r, i)
                for i, r in enumerate(reps)
            )
            # the registry is keyed by name: a duplicate (copy-paste typo)
            # would silently collapse two replicas into one and halve the
            # fleet — refuse loudly instead
            names = [r.name or r.url for r in d["replicas"]]
            dupes = sorted({n for n in names if names.count(n) > 1})
            if dupes:
                raise ValueError(f"duplicate fleet replica names: {dupes}")
        return cls(**d)


@dataclasses.dataclass
class _Replica:
    """Runtime state the probe thread maintains per replica."""

    spec: ReplicaSpec
    alive: bool = False
    ready: bool = False
    role: str = "mixed"
    stats: dict = dataclasses.field(default_factory=dict)
    hot: frozenset = frozenset()  # advertised prefix-cache chain heads
    kv_port: Optional[int] = None
    block_size_ok: bool = True
    last_probe_t: Optional[float] = None
    # rolling weight update: traffic is shifted off an updating replica
    # (excluded from placement) while its weights swap — the drain
    # primitive that keeps in-flight requests alive through the update
    updating: bool = False
    # probe-backoff state: failures since the last success, and (once past
    # fleet.probe_backoff_after) the monotonic time the next probe is due
    consecutive_failures: int = 0
    next_probe_t: Optional[float] = None

    @property
    def name(self) -> str:
        return self.spec.name or self.spec.url

    @property
    def url(self) -> str:
        return self.spec.url.rstrip("/")

    @property
    def load(self) -> float:
        return float(
            (self.stats.get("queue_depth") or 0)
            + (self.stats.get("busy_slots") or 0)
        )

    def decode_capable(self) -> bool:
        return self.role in ("mixed", "decode")


def _http_json(
    url: str,
    obj: Optional[dict],
    timeout_s: float,
    headers: Optional[dict] = None,
) -> tuple[int, dict]:
    """One GET (obj None) or POST (obj) → (status, parsed body). HTTP error
    statuses return normally (the body carries the replica's structured
    rejection); TCP-level failures raise :class:`ReplicaUnreachable`.
    ``headers`` adds to the defaults (the tracing ``traceparent`` rides
    here)."""
    data = None if obj is None else json.dumps(obj).encode()
    hdrs = {} if data is None else {"Content-Type": "application/json"}
    if headers:
        hdrs.update(headers)
    req = urllib.request.Request(url, data=data, headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            body = json.loads(raw or b"{}")
        except ValueError:
            body = {"error": raw.decode(errors="replace")}
        return e.code, body
    except (OSError, urllib.error.URLError, ValueError) as e:
        raise ReplicaUnreachable(f"{url}: {e}") from e


def _prefix_hit_rate(stats: dict) -> Optional[float]:
    """Token-weighted prefix-hit rate from a replica's /stats allocator
    counters (None until the replica saw matchable prompt tokens)."""
    alloc = stats.get("allocator") or {}
    hit = alloc.get("prefix_hit_tokens") or 0
    miss = alloc.get("prefix_miss_tokens") or 0
    return hit / (hit + miss) if (hit + miss) > 0 else None


def _http_text(url: str, timeout_s: float) -> str:
    """One GET → decoded body (the replica /metrics scrape — Prometheus
    text, not JSON). TCP-level failures raise :class:`ReplicaUnreachable`;
    a non-200 answer does too (there is no structured body to salvage)."""
    req = urllib.request.Request(url)
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            if resp.status != 200:
                raise ReplicaUnreachable(f"{url}: HTTP {resp.status}")
            return resp.read().decode("utf-8", errors="replace")
    except (OSError, urllib.error.URLError, ValueError) as e:
        raise ReplicaUnreachable(f"{url}: {e}") from e


def probe_backoff_s(
    failures: int, after: int, base_s: float, max_s: float, salt: str = ""
) -> float:
    """Delay before the NEXT probe of a replica with ``failures``
    consecutive probe failures. 0.0 below the ``after`` threshold (keep
    probing every sweep — fast detection of a blip); past it, exponential
    doubling from ``base_s`` bounded by ``max_s``, with ±25% deterministic
    jitter (crc32 of salt+failures) so a rack of replicas that died
    together does not re-probe in lockstep. Pure — the unit tests walk the
    whole schedule without a fleet."""
    if failures < after:
        return 0.0
    exp = min(failures - after, 16)  # bound the shift itself, not just
    delay = min(base_s * (2.0**exp), max_s)  # the product: 2**1000 is inf
    frac = (zlib.crc32(f"{salt}:{failures}".encode()) % 1000) / 1000.0
    return min(delay * (0.75 + 0.5 * frac), max_s)


class RouterMetrics:
    """The router's /metrics surface (telemetry/prometheus.py registry):
    the ISSUE-named counters plus per-replica health gauges."""

    def __init__(self):
        from automodel_tpu.telemetry.prometheus import (
            LATENCY_BUCKETS,
            MetricsRegistry,
        )

        self.registry = MetricsRegistry()
        # outcome label (ok / retried / unroutable / the terminal
        # completion_reason, e.g. timeout or shed): retries and failure
        # classes are visible at scrape time, not just in the JSONL
        self.requests = self.registry.labeled_counter(
            "automodel_route_requests",
            "Requests routed to a terminal response, by replica and outcome "
            "(ok | retried | unroutable | terminal completion_reason)",
            ("replica", "outcome"),
        )
        self.prefix_hits = self.registry.counter(
            "automodel_route_prefix_hits",
            "Requests placed by prefix affinity (>= 1 matched chain block)",
        )
        self.retries = self.registry.counter(
            "automodel_route_retries",
            "Retriable replica failures resubmitted to a different replica",
        )
        self.unroutable = self.registry.counter(
            "automodel_route_unroutable",
            "Requests that exhausted the retry budget or found no replica",
        )
        self.handoffs = self.registry.counter(
            "automodel_route_kv_handoffs",
            "Disaggregated prefill->decode KV transfers orchestrated",
        )
        self.replica_up = self.registry.labeled_gauge(
            "automodel_route_replica_up",
            "1 when the replica answered its last /readyz probe, else 0",
            "replica",
        )
        self.replicas_ready = self.registry.gauge(
            "automodel_route_replicas_ready",
            "Ready replicas in the registry right now",
        )
        # elastic fleet (serving/fleet/autoscale.py): target vs actual is
        # the first thing to look at when a fleet feels the wrong size
        self.autoscale_target = self.registry.gauge(
            "automodel_route_autoscale_target_replicas",
            "Replica count the autoscaler currently wants (0 until the "
            "first tick; tracks actual between scale events)",
        )
        self.autoscale_events = self.registry.labeled_counter(
            "automodel_route_autoscale_events",
            "Scale events executed by the autoscaler, by direction "
            "(up | down)",
            "direction",
        )
        # multi-tenant QoS: terminal outcomes by tier — the router-front
        # mirror of the replicas' automodel_serve_tier_requests, so
        # per-tier burn is observable even for requests no replica ever
        # accepted (unroutable)
        self.tier_requests = self.registry.labeled_counter(
            "automodel_route_tier_requests",
            "Requests routed to a terminal response, by QoS tier and "
            "outcome (ok | retried | unroutable | terminal "
            "completion_reason)",
            ("tier", "outcome"),
        )
        self.latency = self.registry.labeled_histogram(
            "automodel_route_request_seconds",
            "Router-observed request latency (submit to terminal response), "
            "by outcome",
            "outcome",
            buckets=LATENCY_BUCKETS,
        )
        # per-stage latency from the router's trace spans (placement /
        # prefill_rpc / forward / probe_sweep) — the router-front mirror of
        # the replicas' automodel_serve_stage_seconds
        self.stage_seconds = self.registry.labeled_histogram(
            "automodel_route_stage_seconds",
            "Per-stage latency from router trace spans, by stage name",
            "stage",
            buckets=LATENCY_BUCKETS,
        )

    def observe_stage(self, stage: str, duration_s: float) -> None:
        """Tracer ``observe`` hook — every emitted router span lands in the
        per-stage histogram."""
        if duration_s < 0:
            return
        self.stage_seconds.observe(stage, duration_s)


class Router:
    """Replica registry + placement + retry. Thread-safe: HTTP handler
    threads call :meth:`handle_generate` concurrently while the probe
    thread refreshes replica state."""

    def __init__(
        self,
        config: FleetConfig,
        tokenizer: Any = None,
        on_record: Optional[Callable[[dict], None]] = None,
        tracer: Any = None,
        slo_config: Any = None,
        flight_recorder: Any = None,
        autoscale_config: Any = None,
        scale_backend: Any = None,
    ):
        self.config = config
        self.tokenizer = tokenizer
        self.on_record = on_record
        self.metrics = RouterMetrics()
        # request tracing: the router MINTS the trace for each request
        # (unless the client already sent a traceparent) and propagates it
        # on every forward — spans ride on_record like route_request records
        self.tracer = tracer
        if tracer is not None and tracer.observe is None:
            tracer.observe = self.metrics.observe_stage
        # one wall anchor per process (shared with the tracer when there is
        # one): record timestamps are monotonic-derived, never raw wall
        from automodel_tpu.telemetry.tracing import WallAnchor

        self._clock = tracer.clock if tracer is not None else WallAnchor()
        # fleet health plane (telemetry/federation.py + slo.py): every
        # probe sweep also scrapes each replica's /metrics, rolls the
        # snapshots into fleet-level series, and (when an `slo:` section is
        # configured) evaluates the burn-rate objectives against them
        from automodel_tpu.telemetry.federation import Federation

        retention = (
            slo_config.retention_s if slo_config is not None else 900.0
        )
        self.federation = Federation(retention_s=retention)
        self.slo = None
        if slo_config is not None and slo_config.objectives:
            from automodel_tpu.telemetry.slo import SLOEngine

            self.slo = SLOEngine(
                slo_config,
                self.federation,
                registry=self.metrics.registry,
                emit=on_record,
                flight_recorder=flight_recorder,
                wall=self._clock.wall,
            )
        self._lock = threading.Lock()
        self._replicas: dict[str, _Replica] = {}
        for spec in config.replicas:
            self._replicas[spec.name or spec.url] = _Replica(
                spec=spec, role=spec.role or "mixed"
            )
        if not self._replicas and not config.dns:
            raise ValueError(
                "fleet: needs replicas (static list) or dns (k8s headless "
                "service) — the router has nothing to route to"
            )
        self._rng = random.Random(config.seed)
        self._ids = itertools.count()
        self._stop = threading.Event()
        self._probe_thread: Optional[threading.Thread] = None
        self.draining = False
        # plain-int mirrors of the /metrics counters for /stats
        self.requests_total = 0
        self.completed_total = 0
        self.retries_total = 0
        self.prefix_hits_total = 0
        self.unroutable_total = 0
        self.handoffs_total = 0
        self.peer_hints_total = 0  # forwarded kv_peer prefix-fetch hints
        self._warned_block_size: set[str] = set()
        # rolling weight update progress, surfaced on /stats while (and
        # after) an update runs so version skew is observable fleet-wide
        self._rolling: Optional[dict] = None
        # elastic fleet: the hysteresis state machine rides the probe
        # sweep; the backend (local subprocesses or kubectl scale) is how
        # decisions become replicas (serving/fleet/autoscale.py)
        self.autoscaler = None
        self.scale_backend = scale_backend
        if autoscale_config is not None and autoscale_config.enabled:
            from automodel_tpu.serving.fleet.autoscale import Autoscaler

            self.autoscaler = Autoscaler(autoscale_config)

    # -- registry / probing ---------------------------------------------------
    def _resolve_dns(self) -> None:
        """k8s headless-service discovery: every A record behind
        ``fleet.dns`` is a replica pod. Re-resolved each probe cycle so
        scale-ups join and deleted pods leave without a router restart."""
        import socket as socket_mod

        try:
            infos = socket_mod.getaddrinfo(
                self.config.dns, self.config.dns_port,
                proto=socket_mod.IPPROTO_TCP,
            )
        except OSError as e:
            logger.warning("fleet.dns %s resolution failed: %s", self.config.dns, e)
            return
        ips = sorted({info[4][0] for info in infos})
        current = {f"dns-{ip.replace('.', '-').replace(':', '-')}": ip for ip in ips}
        with self._lock:
            for name in [
                n for n, r in self._replicas.items()
                if n.startswith("dns-") and n not in current
            ]:
                del self._replicas[name]
            for name, ip in current.items():
                if name not in self._replicas:
                    host = f"[{ip}]" if ":" in ip else ip
                    self._replicas[name] = _Replica(
                        spec=ReplicaSpec(
                            url=f"http://{host}:{self.config.dns_port}",
                            name=name,
                        )
                    )

    def probe_once(self) -> None:
        """One probe sweep: /readyz for health, /stats for load + roles +
        hot prefixes + the KV-transfer port. Replicas probe CONCURRENTLY:
        sequentially, every dead pod would cost a full probe_timeout_s and
        a large fleet's sweep (and the synchronous ``start()``) would take
        O(N × timeout) — instead the whole sweep is bounded at roughly one
        probe timeout."""
        t_probe0 = time.perf_counter()
        if self.config.dns:
            self._resolve_dns()
        now = time.monotonic()
        with self._lock:
            all_reps = list(self._replicas.values())
            # probe backoff: replicas deep in consecutive failure are only
            # due on their exponential schedule — the rest of the sweep
            # stops paying a probe_timeout_s thread for every corpse
            reps = [
                r for r in all_reps
                if r.next_probe_t is None or now >= r.next_probe_t
            ]
        threads = [
            threading.Thread(
                target=self._probe_replica, args=(rep,), daemon=True
            )
            for rep in reps
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ready = sum(1 for r in all_reps if r.ready)
        self.metrics.replicas_ready.set(ready)
        # health plane tick: fold this sweep's scrapes into the fleet
        # series, then judge the SLO objectives against them. Both are
        # bounded host-side work; a bug in either must not kill probing.
        try:
            self.federation.roll(time.monotonic())
            if self.slo is not None:
                self.slo.evaluate(time.monotonic())
        except Exception:
            logger.exception("fleet health-plane tick failed")
        # elastic fleet: the autoscaler evaluates once per sweep, right
        # after the federation rolled this sweep's scrapes in — its
        # signals are at most one sweep stale. Its bugs must not kill
        # probing either.
        try:
            self._autoscale_tick(time.monotonic())
        except Exception:
            logger.exception("autoscale tick failed")
        if self.tracer is not None:
            # probe sweeps are router-lifecycle work, not request work:
            # each sweep is its own single-span trace (sampled like any
            # root), so sweep latency shows up in the stage histogram and
            # the span JSONL without polluting request waterfalls
            self.tracer.record(
                self.tracer.start(), "probe_sweep", t_probe0,
                replicas=len(reps), ready=ready,
            )

    def _probe_replica(self, rep: "_Replica") -> None:
        alive, ready, stats = False, False, rep.stats
        try:
            code, _ = _http_json(
                rep.url + "/readyz", None, self.config.probe_timeout_s
            )
            alive = True
            _, stats = _http_json(
                rep.url + "/stats", None, self.config.probe_timeout_s
            )
            # ready only when BOTH legs answered: a replica that died
            # between /readyz and /stats must not be published as ready
            # with stale stats for a whole probe interval
            ready = code == 200
        except ReplicaUnreachable:
            alive, ready = False, False
        # fleet health plane: the /metrics scrape rides the same sweep — a
        # replica that answers probes but whose scrape fails (or fails to
        # parse) just drops out of this sweep's rollup; routing is
        # unaffected
        if alive:
            try:
                body = _http_text(
                    rep.url + "/metrics", self.config.probe_timeout_s
                )
                self.federation.ingest(rep.name, body, time.monotonic())
            except ReplicaUnreachable as e:
                logger.warning("replica %s /metrics scrape failed: %s", rep.name, e)
                self.federation.mark_down(rep.name)
            except ValueError as e:  # ExpositionParseError — counted inside
                logger.warning("replica %s /metrics unparseable: %s", rep.name, e)
        else:
            self.federation.mark_down(rep.name)
        with self._lock:
            rep.alive, rep.ready = alive, ready
            rep.last_probe_t = time.monotonic()
            if alive:
                # first success snaps a backed-off replica straight back
                # to every-sweep probing — recovery is never rate-limited
                rep.consecutive_failures = 0
                rep.next_probe_t = None
            else:
                rep.consecutive_failures += 1
                delay = probe_backoff_s(
                    rep.consecutive_failures,
                    self.config.probe_backoff_after,
                    self.config.probe_interval_s,
                    self.config.probe_backoff_max_s,
                    salt=rep.name,
                )
                rep.next_probe_t = (
                    time.monotonic() + delay if delay > 0 else None
                )
            if alive:
                rep.stats = stats
                rep.role = rep.spec.role or stats.get("role") or rep.role
                rep.kv_port = stats.get("kv_transfer_port")
                hot = stats.get("hot_prefixes")
                rep.hot = (
                    frozenset(int(h) for h in hot)
                    if isinstance(hot, list) else frozenset()
                )
                rbs = stats.get("block_size")
                rep.block_size_ok = (
                    rbs is None or int(rbs) == self.config.block_size
                )
                if (
                    not rep.block_size_ok
                    and rep.name not in self._warned_block_size
                ):
                    self._warned_block_size.add(rep.name)
                    logger.warning(
                        "replica %s serves block_size=%s but "
                        "fleet.block_size=%d — prefix affinity is OFF "
                        "for it (chain hashes cannot match)",
                        rep.name, rbs, self.config.block_size,
                    )
        self.metrics.replica_up.set(rep.name, 1.0 if ready else 0.0)

    def _probe_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.probe_once()
            except Exception:  # a probe bug must not kill routing
                logger.exception("replica probe sweep failed")
            self._stop.wait(self.config.probe_interval_s)

    def start(self) -> "Router":
        """Probe immediately (so the first request can route), then keep
        probing in the background."""
        self.probe_once()
        self._probe_thread = threading.Thread(
            target=self._probe_loop, name="fleet-probe", daemon=True
        )
        self._probe_thread.start()
        return self

    def close(self) -> None:
        self._stop.set()
        if self._probe_thread is not None:
            self._probe_thread.join(timeout=5)

    def _mark_down(self, rep: _Replica) -> None:
        with self._lock:
            rep.alive = False
            rep.ready = False
        self.metrics.replica_up.set(rep.name, 0.0)

    # -- elastic fleet (serving/fleet/autoscale.py, docs/serving.md) ----------
    def add_replica(
        self, name: str, url: str, role: Optional[str] = None
    ) -> None:
        """Join a replica at runtime (autoscaler spawn, tests). It becomes
        routable at its first successful probe, not here."""
        with self._lock:
            if name in self._replicas:
                raise ValueError(f"replica {name!r} already registered")
            self._replicas[name] = _Replica(
                spec=ReplicaSpec(url=url, name=name, role=role),
                role=role or "mixed",
            )

    def remove_replica(self, name: str) -> None:
        """Drop a replica from the registry (autoscaler retire). In-flight
        forwards to it finish or fail retriable on their own."""
        with self._lock:
            self._replicas.pop(name, None)
        self.metrics.replica_up.set(name, 0.0)
        self.federation.mark_down(name)

    def _kv_peer(self, exclude: frozenset = frozenset()) -> Optional[dict]:
        """Least-loaded ready replica with a KV-transfer listener, as the
        ``{"host", "port"}`` address warm-start and prefix migration both
        take — or None when the fleet has nothing to stream from."""
        with self._lock:
            peers = [
                r for r in self._replicas.values()
                if r.ready and r.kv_port and r.name not in exclude
            ]
        if not peers:
            return None
        rep = min(peers, key=lambda r: r.load)
        host = urllib.parse.urlsplit(rep.url).hostname or "127.0.0.1"
        return {"host": host, "port": int(rep.kv_port)}

    def _fleet_signals(self, now: float):
        """This sweep's federation rollup as :class:`FleetSignals`. Fleet
        gauges are SUMS across scraped replicas (federation.py), so the
        per-replica means divide by the scraped count; unknowns stay None
        and never trigger a scale."""
        from automodel_tpu.serving.fleet.autoscale import FleetSignals

        with self._lock:
            ready = sum(1 for r in self._replicas.values() if r.ready)
        fed = self.federation
        window_s = self.autoscaler.config.window_s
        n = int(fed.status().get("replicas_scraped") or 0) or max(ready, 1)
        qd = fed.latest("automodel_fleet_serve_queue_depth")
        occ = fed.latest("automodel_fleet_serve_block_occupancy")
        shed = fed.increase(
            "automodel_fleet_serve_requests_shed", window_s, now
        )
        return FleetSignals(
            ready_replicas=ready,
            queue_depth=None if qd is None else qd / n,
            shed_rate=None if shed is None else shed / window_s,
            occupancy=None if occ is None else occ / n,
            slos_firing=(
                len(self.slo.firing()) if self.slo is not None else 0
            ),
        )

    def _autoscale_tick(self, now: float) -> None:
        """One autoscaler evaluation, at the tail of each probe sweep."""
        if self.autoscaler is None or self.draining:
            return
        cfg = self.autoscaler.config
        # backfill the last scale-up's time_to_ready_s once the spawned
        # replica reports it (/stats) — fleet-status shows the number the
        # elastic fleet exists to improve
        last = self.autoscaler.last_event
        if (
            last is not None
            and last.get("direction") == "up"
            and "time_to_ready_s" not in last
        ):
            with self._lock:
                rep = self._replicas.get(last.get("replica"))
                ttr = (
                    rep.stats.get("time_to_ready_s")
                    if rep is not None and rep.ready else None
                )
                if ttr is not None:
                    last["time_to_ready_s"] = round(float(ttr), 6)
                    last["boot_source"] = rep.stats.get("boot_source")
        signals = self._fleet_signals(now)
        with self._lock:
            actual = len(self._replicas)
        direction, trigger = self.autoscaler.decide(signals, actual, now)
        if direction is None:
            self.metrics.autoscale_target.set(actual)
            return
        try:
            replica = (
                self._scale_up(cfg) if direction == "up"
                else self._scale_down(cfg)
            )
        except Exception as e:
            # backend failure: no note_scaled, so no cooldown — the streak
            # is still live and the next sweep retries
            logger.warning("autoscale %s failed: %s", direction, e)
            return
        if replica is None:
            return
        with self._lock:
            after = len(self._replicas)
        event = {
            "event": "scale_event",
            "ts": self._wall_ts(),
            "direction": direction,
            "trigger": trigger,
            "replica": replica,
            "replicas_before": actual,
            "replicas_after": after,
        }
        self.autoscaler.note_scaled(event, now)
        self.metrics.autoscale_events.inc(direction)
        self.metrics.autoscale_target.set(after)
        self._emit(event)
        logger.warning(
            "autoscale %s (trigger=%s): %d -> %d replicas (%s)",
            direction, trigger, actual, after, replica,
        )

    def _scale_up(self, cfg) -> Optional[str]:
        if self.scale_backend is None:
            logger.warning(
                "autoscale: scale up wanted but no backend is configured "
                "(k8s_fleet: section, or an injected LocalProcessBackend)"
            )
            return None
        warm = self._kv_peer() if cfg.warm_start else None
        name, url = self.scale_backend.spawn(warm)
        if name and getattr(self.scale_backend, "registry_managed", True):
            self.add_replica(name, url)
        return name or "(dns)"  # k8s: membership arrives via DNS discovery

    def _scale_down(self, cfg) -> Optional[str]:
        if self.scale_backend is None:
            logger.warning(
                "autoscale: scale down wanted but no backend is configured"
            )
            return None
        with self._lock:
            victims = [
                r for r in self._replicas.values()
                if r.ready and r.decode_capable()
            ]
        if len(victims) <= cfg.min_replicas:
            return None  # ready count sits at the floor even if the
            # registry is larger (dead entries don't make retiring safe)
        # least-loaded victim: fewest in-flight requests to drain, and the
        # load it sheds redistributes most easily
        victim = min(victims, key=lambda r: r.load)
        migrate = (
            self._kv_peer(exclude=frozenset({victim.name}))
            if cfg.migrate_on_scale_down else None
        )
        self.scale_backend.retire(
            victim.name, victim.url, migrate, cfg.retire_deadline_s
        )
        if getattr(self.scale_backend, "registry_managed", True):
            self.remove_replica(victim.name)
        return victim.name

    # -- placement ------------------------------------------------------------
    def _candidates(
        self, exclude: set, pool: str
    ) -> list[_Replica]:
        with self._lock:
            reps = list(self._replicas.values())
        if pool == "prefill":
            return [
                r for r in reps
                if r.ready and r.role == "prefill"
                and not r.updating and r.name not in exclude
            ]
        return [
            r for r in reps
            if r.ready and r.decode_capable()
            and not r.updating and r.name not in exclude
        ]

    def _match_blocks(self, rep: _Replica, chains: Sequence[int]) -> int:
        """Longest CONSECUTIVE chain prefix this replica's hot set covers —
        consecutive because ``match_prefix`` walks from block 1 and stops
        at the first miss; an orphaned deeper hash is unreachable there."""
        if not rep.block_size_ok:
            return 0
        n = 0
        for h in chains:
            if h not in rep.hot:
                break
            n += 1
        return n

    def place_decode(
        self,
        chains: Sequence[int],
        exclude: Optional[set] = None,
        tier_idx: int = 0,
    ) -> tuple[Optional[_Replica], int]:
        """→ (replica, matched chain blocks). Affinity first (longest
        advertised prefix match, ties to the least loaded), else
        power-of-two-choices on load — except for non-interactive tiers
        (``tier_idx > 0``), which take a FULL least-loaded scan: batch and
        best_effort work is latency-insensitive, so it can afford the O(N)
        probe to land on the true minimum and keep the lightly-loaded tail
        of the fleet absorbing it instead of contending with interactive
        traffic on a random pair."""
        cands = self._candidates(exclude or set(), "decode")
        if not cands:
            return None, 0
        if self.config.affinity and chains:
            matched = [(self._match_blocks(r, chains), r) for r in cands]
            best = max(m for m, _ in matched)
            if best > 0:
                tied = [r for m, r in matched if m == best]
                return min(tied, key=lambda r: r.load), best
        if tier_idx > 0 or len(cands) <= 2:
            return min(cands, key=lambda r: r.load), 0
        with self._lock:
            two = self._rng.sample(cands, 2)
        return min(two, key=lambda r: r.load), 0

    def place_prefill(self, exclude: Optional[set] = None) -> Optional[_Replica]:
        cands = self._candidates(exclude or set(), "prefill")
        return min(cands, key=lambda r: r.load) if cands else None

    def _peer_hint(
        self, chains: Sequence[int], rep: _Replica, match: int, exclude: set
    ) -> Optional[dict]:
        """Prefix-fetch hint for an affinity miss (docs/serving.md
        "Hierarchical KV cache"): when another ready replica advertises a
        DEEPER consecutive chain match than the chosen one AND runs a KV
        listener, the chosen replica can ``/kv_fetch`` the missing prefix
        blocks from it instead of re-prefilling. The hint is best-effort —
        the replica recomputes the chains itself (hashing is deterministic
        cross-process) and falls back to local prefill on any fetch
        failure. → ``{"host", "port"}`` or None.

        ``exclude`` should name only replicas whose KV listener is
        suspect (e.g. a failed transfer target) — NOT every replica a
        retry skipped: a shedding replica (503, queue full) refuses new
        decodes but its listener still serves prefix reads, and that
        shed-then-retry hop is exactly when the hint earns its keep
        (placement lands on a cold replica while the hot one stays the
        source of truth). Dead replicas drop out via ``ready``."""
        if not chains:
            return None
        best, peer = match, None
        for r in self._candidates(exclude | {rep.name}, "decode"):
            if not r.kv_port:
                continue
            m = self._match_blocks(r, chains)
            if m > best:
                best, peer = m, r
        if peer is None:
            return None
        host = urllib.parse.urlsplit(peer.url).hostname
        return {"host": host, "port": int(peer.kv_port)}

    def _disaggregate_active(self) -> bool:
        if self.config.disaggregate is False:
            return False
        return self.place_prefill() is not None

    # -- request path ---------------------------------------------------------
    def _encode(self, req: dict) -> Optional[list[int]]:
        """Token ids for chain hashing (and forwarded so every replica in a
        retry chain sees identical ids). None = unhashable here (text
        prompt, no router-side tokenizer): the request forwards verbatim
        and placement falls back to load-only."""
        if req.get("prompt_ids") is not None:
            return [int(t) for t in req["prompt_ids"]]
        prompt = req.get("prompt")
        if prompt is None:
            return None
        if self.tokenizer is not None:
            if callable(self.tokenizer):
                return self.tokenizer(str(prompt), add_special_tokens=True)[
                    "input_ids"
                ]
            return self.tokenizer.encode(str(prompt))
        try:  # token-id mode (tiny from-config fleets)
            return [int(t) for t in str(prompt).replace(",", " ").split()]
        except ValueError:
            return None

    def _wall_ts(self) -> float:
        return round(self._clock.wall(), 6)

    def _emit(self, rec: dict) -> None:
        if self.on_record is not None:
            try:
                self.on_record(dict(rec))
            except Exception:  # telemetry must never break routing
                pass

    def _count_retry(self) -> None:
        """One resubmission: the /metrics counter and its /stats mirror
        move together, always."""
        self.metrics.retries.inc()
        with self._lock:
            self.retries_total += 1

    def handle_generate(self, req: dict) -> tuple[int, dict]:
        """Route one request to a terminal response. → (HTTP status, body).
        The body is the winning replica's response verbatim (plus the
        router's ``route`` provenance block)."""
        t0 = time.perf_counter()
        rid = str(req.get("id")) if req.get("id") is not None else (
            f"route-{next(self._ids)}"
        )
        # trace root: continue the client's trace when a traceparent came in
        # (HTTP header, stashed into the body by the front), mint otherwise
        # — the router is where fleet traces are born
        tr = self.tracer
        client_tp = req.pop("traceparent", None)
        root = tr.start(parent=tr.parse(client_tp)) if tr is not None else None

        def _finish_span(outcome: str, **attrs) -> None:
            if tr is not None:
                tr.record(
                    root, "route", t0,
                    request_id=rid, outcome=outcome, **attrs,
                )

        if self.draining:
            _finish_span("draining")
            return 503, {
                "error": "router is draining — retry against another router",
                "retriable": True, "reason": "draining", "id": rid,
            }
        ids = self._encode(req)
        chains = (
            prompt_chain(ids, self.config.block_size)
            if ids and self.config.affinity else []
        )
        # multi-tenant QoS: tenant/tier ride the body (the HTTP front
        # stashes the X-Tenant-Id / X-Tier headers there, same vehicle as
        # traceparent) and forward to every replica in the retry chain
        tenant = str(req["tenant"]) if req.get("tenant") is not None else None
        tier = str(req["tier"]) if req.get("tier") is not None else None
        tier_label = _tier_label(tier)
        tier_idx = _TIER_ORDER.get(tier, 0)
        # tier-aware retry budget: best_effort work is exactly the traffic
        # the fleet sheds first under pressure — burning the full budget
        # re-offering it to replicas that just refused it steals forward
        # capacity from the tiers the operator ranked higher
        retry_budget = self.config.retry_budget
        if tier_idx >= _TIER_ORDER["best_effort"]:
            retry_budget = min(retry_budget, 1)
        with self._lock:
            self.requests_total += 1
        tried: set = set()
        tried_prefill: set = set()
        kv_suspect: set = set()  # replicas whose KV LISTENER failed us
        retries = 0
        last_error = "no ready decode-capable replica"
        rep = None
        match = 0
        # the forward timeout must EXCEED the replica-side budget (the
        # replica's submit_blocking answers 504 within the client's
        # timeout_s): if the two raced at the same value, a long-but-legal
        # decode would read as replica death — mark-down, resubmit, and the
        # same request terminalized on two replicas
        fwd_timeout = max(
            self.config.request_timeout_s,
            float(req.get("timeout_s") or 300.0) + 30.0,
        )
        from automodel_tpu.resilience.fault_injection import active_injector

        inj = active_injector()
        while retries <= retry_budget:
            t_place0 = time.perf_counter()
            if inj is not None:
                inj.maybe_trace_delay("placement")
            rep, match = self.place_decode(
                chains, exclude=tried, tier_idx=tier_idx
            )
            if tr is not None and rep is not None:
                # the placement decision, incl. WHY: affinity (and how deep
                # the match) vs pure load — one span per retry attempt
                tr.child(
                    root, "placement", t_place0,
                    request_id=rid, attempt=retries, replica=rep.name,
                    policy="affinity" if match > 0 else "load",
                    prefix_match_blocks=match,
                )
            if rep is None:
                break
            fwd = {k: v for k, v in req.items() if k != "prompt_ids"}
            if req.get("prompt_ids") is not None:
                fwd["prompt_ids"] = ids
            elif ids is not None and self.tokenizer is not None:
                # router-side tokenization: every replica in a retry chain
                # sees identical ids. WITHOUT a tokenizer a text prompt
                # forwards verbatim (docs/serving.md) — the token-id parse
                # is for affinity hashing only, and a numeric-looking text
                # prompt must not silently bypass the replica's tokenizer
                fwd.pop("prompt", None)
                fwd["prompt_ids"] = ids
            fwd["id"] = rid
            if self.config.affinity:
                hint = self._peer_hint(chains, rep, match, kv_suspect)
                if hint is not None:
                    fwd["kv_peer"] = hint
                    with self._lock:
                        self.peer_hints_total += 1
            used_prefill = None
            if (
                ids is not None
                # disaggregation needs ids BOTH sides agree on: client-sent
                # prompt_ids or a router-side tokenizer. Fallback-parsed
                # text must NOT disaggregate — /prefill would compute KV
                # for the parse while the decode replica re-encodes the
                # forwarded text with its own tokenizer
                and (
                    req.get("prompt_ids") is not None
                    or self.tokenizer is not None
                )
                and self._disaggregate_active()
                # strong affinity hit: the decode replica already holds at
                # least half the prompt — recomputing the tail beats a
                # whole-prompt KV transfer
                and match * self.config.block_size < max(len(ids) // 2, 1)
                and rep.kv_port
            ):
                pre = self.place_prefill(exclude=tried_prefill)
                if pre is not None:
                    handoff_id = uuid.uuid4().hex
                    host = urllib.parse.urlsplit(rep.url).hostname
                    pre_ctx = tr.start(parent=root) if tr is not None else None
                    t_pre0 = time.perf_counter()
                    try:
                        code, body = _http_json(
                            pre.url + "/prefill",
                            {
                                "prompt_ids": ids, "id": rid,
                                "transfer": {
                                    "host": host, "port": int(rep.kv_port),
                                    "handoff_id": handoff_id,
                                },
                            },
                            fwd_timeout,
                            headers=_trace_headers(pre_ctx),
                        )
                    except ReplicaUnreachable as e:
                        if tr is not None:
                            tr.record(
                                pre_ctx, "prefill_rpc", t_pre0,
                                request_id=rid, replica=pre.name,
                                attempt=retries, error="unreachable",
                            )
                        self._mark_down(pre)
                        tried_prefill.add(pre.name)
                        retries += 1
                        self._count_retry()
                        last_error = f"prefill replica unreachable: {e}"
                        continue
                    if tr is not None:
                        tr.record(
                            pre_ctx, "prefill_rpc", t_pre0,
                            request_id=rid, replica=pre.name,
                            attempt=retries, status=code,
                        )
                    if code != 200 or not body.get("ok"):
                        last_error = (
                            f"prefill on {pre.name} failed: "
                            f"{body.get('error', code)}"
                        )
                        if code == 502:
                            # 502 = the TRANSFER to the decode replica
                            # failed (server.py wraps KVTransferError as
                            # 502): the suspect is the decode target's
                            # listener (stale kv_port after a restart),
                            # not the prefill replica that ran the prompt
                            # — exclude the decode replica and keep the
                            # prefill pool intact
                            tried.add(rep.name)
                            kv_suspect.add(rep.name)
                            retries += 1
                            self._count_retry()
                            continue
                        if body.get("retriable", code == 503):
                            tried_prefill.add(pre.name)
                            retries += 1
                            self._count_retry()
                            continue
                        # terminal prefill failure (client budget expiry,
                        # bad request): one route_request record per
                        # terminal outcome — this path counts too
                        outcome = str(
                            body.get("completion_reason") or "prefill_failed"
                        )
                        self.metrics.requests.inc((pre.name, outcome))
                        self.metrics.tier_requests.inc((tier_label, outcome))
                        self.metrics.latency.observe(
                            outcome, time.perf_counter() - t0
                        )
                        _finish_span(
                            outcome, replica=pre.name, attempt=retries
                        )
                        self._emit({
                            "event": "route_request",
                            "request_id": rid,
                            "replica": pre.name,
                            "retries": retries,
                            "prefix_match_blocks": match,
                            "disaggregated": True,
                            "prefill_replica": pre.name,
                            "completion_reason": body.get(
                                "completion_reason", "prefill_failed"
                            ),
                            "tenant": tenant,
                            "tier": tier_label,
                            "status": code,
                            "route_s": round(time.perf_counter() - t0, 6),
                            "ts": self._wall_ts(),
                        })
                        return code, {**body, "id": rid}
                    fwd["handoff_id"] = handoff_id
                    used_prefill = pre.name
                    self.metrics.handoffs.inc()
                    with self._lock:
                        self.handoffs_total += 1
            fwd_ctx = tr.start(parent=root) if tr is not None else None
            t_fwd0 = time.perf_counter()
            if inj is not None:
                inj.maybe_trace_delay("forward")
            # tenant/tier forward as headers AND body fields: headers keep
            # the contract visible to middleboxes, the body survives
            # header-stripping fronts
            fwd_headers = dict(_trace_headers(fwd_ctx) or {})
            if tenant is not None:
                fwd_headers["X-Tenant-Id"] = tenant
            if tier is not None:
                fwd_headers["X-Tier"] = tier
            try:
                code, body = _http_json(
                    rep.url + "/generate", fwd, fwd_timeout,
                    headers=fwd_headers or None,
                )
            except ReplicaUnreachable as e:
                # TCP-level death: the replica never answered — always
                # retriable, and the registry marks it down until a probe
                # sees it healthy again
                if tr is not None:
                    tr.record(
                        fwd_ctx, "forward", t_fwd0,
                        request_id=rid, replica=rep.name,
                        attempt=retries, error="unreachable",
                    )
                self._mark_down(rep)
                tried.add(rep.name)
                retries += 1
                self._count_retry()
                last_error = f"replica {rep.name} unreachable: {e}"
                continue
            if tr is not None:
                # one forward span per retry attempt — the retry trail is
                # readable off the waterfall, not just the retries counter
                tr.record(
                    fwd_ctx, "forward", t_fwd0,
                    request_id=rid, replica=rep.name,
                    attempt=retries, status=code,
                )
            # 503 = shed/draining/engine down; 409 = the claimed handoff
            # never arrived or expired on that decode replica — both
            # resubmit elsewhere (the next round redoes prefill+transfer)
            if code in (503, 409) and body.get("retriable"):
                tried.add(rep.name)
                retries += 1
                self._count_retry()
                last_error = (
                    f"{rep.name} rejected retriable: "
                    f"{body.get('reason') or body.get('error')}"
                )
                continue
            # terminal — success (200), client-budget expiry (504), bad
            # request (400), or a non-retriable replica error
            if match > 0:
                self.metrics.prefix_hits.inc()
                with self._lock:
                    self.prefix_hits_total += 1
            if code == 200:
                outcome = "ok" if retries == 0 else "retried"
            else:
                outcome = str(
                    body.get("completion_reason")
                    or body.get("reason") or f"http_{code}"
                )
            self.metrics.requests.inc((rep.name, outcome))
            self.metrics.tier_requests.inc((tier_label, outcome))
            self.metrics.latency.observe(outcome, time.perf_counter() - t0)
            if code == 200:
                with self._lock:
                    self.completed_total += 1
            body = dict(body)
            body["id"] = rid
            body["route"] = {
                "replica": rep.name, "retries": retries,
                "prefix_match_blocks": match,
                "prefill_replica": used_prefill,
            }
            _finish_span(
                outcome, replica=rep.name, attempt=retries,
                completion_reason=body.get("completion_reason"),
            )
            self._emit({
                "event": "route_request",
                "request_id": rid,
                "replica": rep.name,
                "retries": retries,
                "prefix_match_blocks": match,
                "disaggregated": used_prefill is not None,
                "prefill_replica": used_prefill,
                "completion_reason": body.get("completion_reason"),
                "n_generated": body.get("n_generated"),
                "tenant": tenant,
                "tier": tier_label,
                "status": code,
                "route_s": round(time.perf_counter() - t0, 6),
                "ts": self._wall_ts(),
            })
            return code, body
        # exhausted: budget spent or nothing to route to — an explicit
        # retriable answer, never a silent drop
        self.metrics.unroutable.inc()
        self.metrics.requests.inc(
            (rep.name if rep is not None else "none", "unroutable")
        )
        self.metrics.tier_requests.inc((tier_label, "unroutable"))
        self.metrics.latency.observe("unroutable", time.perf_counter() - t0)
        with self._lock:
            self.unroutable_total += 1
        _finish_span(
            "unroutable",
            replica=rep.name if rep is not None else None, attempt=retries,
        )
        self._emit({
            "event": "route_request",
            "request_id": rid,
            "replica": rep.name if rep is not None else None,
            "retries": retries,
            "prefix_match_blocks": match,
            "completion_reason": "unroutable",
            "tenant": tenant,
            "tier": tier_label,
            "status": 503,
            "route_s": round(time.perf_counter() - t0, 6),
            "ts": self._wall_ts(),
        })
        return 503, {
            "error": (
                f"no replica could serve the request after {retries} "
                f"retr{'y' if retries == 1 else 'ies'}: {last_error}"
            ),
            "retriable": True, "reason": "unroutable", "id": rid,
            "tier": tier_label,
        }

    # -- fronts ---------------------------------------------------------------
    # -- rolling weight update (docs/posttrain.md) -----------------------------
    def rolling_update(
        self,
        peer: dict,
        timeout_s: float = 120.0,
        drain_timeout_s: float = 60.0,
    ) -> dict:
        """Fleet-wide weight hot-swap with zero dropped requests: one
        decode-capable replica at a time — shift traffic off it (the
        ``updating`` placement exclusion; retries re-place in-flight
        resubmissions onto siblings), wait for its slots and queue to
        empty, POST its /swap_weights at ``peer`` (the trainer's AKV1
        ``weights_fetch`` listener), confirm the version bump, re-admit.
        Progress lands in ``stats()["rolling_update"]`` and one
        ``rolling_update`` record per phase rides on_record, so the
        per-replica version skew window is observable while it closes.

        → summary dict {updated: [name], failed: [name], weights_version}.
        A replica that fails to drain or swap is re-admitted on its OLD
        weights and reported — a stalled update degrades loudly, never
        into dropped traffic."""
        with self._lock:
            targets = [
                r for r in self._replicas.values()
                if r.ready and r.decode_capable()
            ]
        self._rolling = {
            "active": True, "total": len(targets), "done": 0,
            "current": None, "updated": [], "failed": [],
        }
        self._emit({
            "event": "rolling_update", "phase": "start",
            "replicas": len(targets), "ts": self._wall_ts(),
        })
        probe_t = self.config.probe_timeout_s
        version: Optional[int] = None
        for rep in targets:
            t_rep0 = time.perf_counter()
            self._rolling["current"] = rep.name
            with self._lock:
                rep.updating = True
            err = None
            try:
                # traffic is off; wait for the replica to run dry (its own
                # queue keeps absorbing nothing new, in-flight finish)
                deadline = time.perf_counter() + drain_timeout_s
                while True:
                    _, st = _http_json(
                        rep.url + "/stats", None, timeout_s=probe_t
                    )
                    if (
                        not (st.get("busy_slots") or 0)
                        and not (st.get("queue_depth") or 0)
                    ):
                        break
                    if time.perf_counter() >= deadline:
                        raise TimeoutError(
                            f"{rep.name} still busy after {drain_timeout_s}s "
                            "traffic shift-off"
                        )
                    time.sleep(0.05)
                code, body = _http_json(
                    rep.url + "/swap_weights",
                    {"peer": dict(peer), "timeout_s": timeout_s},
                    timeout_s=timeout_s + probe_t,
                )
                if code != 200 or not body.get("ok"):
                    raise RuntimeError(
                        f"swap_weights on {rep.name} answered {code}: "
                        f"{body.get('error')}"
                    )
                version = int(body["weights_version"])
                _, st = _http_json(
                    rep.url + "/stats", None, timeout_s=probe_t
                )
                with self._lock:
                    rep.stats = st
            except (ReplicaUnreachable, RuntimeError, TimeoutError,
                    ValueError, KeyError) as e:
                err = f"{type(e).__name__}: {e}"
            finally:
                with self._lock:
                    rep.updating = False
            self._rolling["done"] += 1
            self._rolling["current"] = None
            if err is None:
                self._rolling["updated"].append(rep.name)
            else:
                self._rolling["failed"].append(rep.name)
                logger.error(
                    "rolling update: %s failed (%s) — re-admitted on its "
                    "old weights", rep.name, err,
                )
            rec = {
                "event": "rolling_update", "phase": "replica",
                "replica": rep.name, "ok": err is None,
                "duration_s": round(time.perf_counter() - t_rep0, 6),
                "ts": self._wall_ts(),
            }
            if err is None:
                rec["weights_version"] = version
            else:
                rec["detail"] = err
            self._emit(rec)
        self._rolling["active"] = False
        if version is not None:
            self._rolling["weights_version"] = version
        self._emit({
            "event": "rolling_update", "phase": "done",
            "updated": len(self._rolling["updated"]),
            "failed": len(self._rolling["failed"]),
            "weights_version": version, "ts": self._wall_ts(),
        })
        return {
            "updated": list(self._rolling["updated"]),
            "failed": list(self._rolling["failed"]),
            "weights_version": version,
        }

    def begin_drain(self) -> None:
        self.draining = True

    def ready(self) -> bool:
        """The router is ready while >= 1 decode-capable replica is — ONE
        replica down must not drop the whole fleet out of a load balancer."""
        return not self.draining and bool(self._candidates(set(), "decode"))

    def healthy(self) -> bool:
        return self._probe_thread is None or self._probe_thread.is_alive()

    def stats(self) -> dict:
        with self._lock:
            reps = {
                r.name: {
                    "url": r.url,
                    "role": r.role,
                    "alive": r.alive,
                    "ready": r.ready,
                    "queue_depth": r.stats.get("queue_depth"),
                    "busy_slots": r.stats.get("busy_slots"),
                    "block_occupancy": r.stats.get("block_occupancy"),
                    "shed_total": r.stats.get("shed_total"),
                    "quota_total": r.stats.get("quota_total"),
                    # multi-tenant QoS: this replica's qos_snapshot block
                    "qos": r.stats.get("qos"),
                    "hot_prefixes": len(r.hot),
                    "kv_transfer_port": r.kv_port,
                    # fleet-status columns (serving/fleet/status.py)
                    "spec_accept_rate": r.stats.get("spec_accept_rate"),
                    "prefix_hit_rate": _prefix_hit_rate(r.stats),
                    # rolling update: per-replica weights generation — the
                    # version skew window is these values disagreeing
                    "weights_version": r.stats.get("weights_version"),
                    "updating": r.updating,
                }
                for r in self._replicas.values()
            }
            out = {
                "replicas": reps,
                "replicas_ready": sum(1 for r in reps.values() if r["ready"]),
                "requests_total": self.requests_total,
                "completed_total": self.completed_total,
                "retries_total": self.retries_total,
                "prefix_hits_total": self.prefix_hits_total,
                "unroutable_total": self.unroutable_total,
                "kv_handoffs_total": self.handoffs_total,
                "kv_peer_hints_total": self.peer_hints_total,
                "disaggregated": self._disaggregate_active_unlocked(),
                "draining": self.draining,
            }
            if self._rolling is not None:
                out["rolling_update"] = dict(self._rolling)
        # fleet-wide QoS rollup: the per-replica qos blocks summed — the
        # numbers fleet-status's TIER/TENANT summary renders
        out["qos"] = aggregate_qos(
            [v.get("qos") for v in reps.values() if v.get("qos")]
        )
        out["federation"] = self.federation.status()
        if self.slo is not None:
            out["slo"] = self.slo.snapshot()
            out["alerts_firing"] = self.slo.firing()
        if self.autoscaler is not None:
            out["autoscale"] = self.autoscaler.status()
        return out

    def _disaggregate_active_unlocked(self) -> bool:
        if self.config.disaggregate is False:
            return False
        return any(
            r.ready and r.role == "prefill" for r in self._replicas.values()
        )

    # -- workload driver (the fleet's end-to-end tests) -----------------------
    def run_workload(
        self, arrivals: Sequence[tuple[float, Sequence[int], Optional[int]]]
    ) -> tuple[list[dict], dict]:
        """Drive a timed workload through the ROUTER: ``arrivals`` is
        [(offset_s, prompt_ids, max_new_tokens|None)]; one thread per
        request submits at its offset and blocks on the routed response.
        → (terminal bodies, aggregate stats)."""
        arrivals = sorted(arrivals, key=lambda a: a[0])
        results: list[Optional[tuple[int, dict]]] = [None] * len(arrivals)
        req0 = {
            "retries": self.retries_total,
            "hits": self.prefix_hits_total,
            "handoffs": self.handoffs_total,
        }
        t0 = time.perf_counter()

        durations: list[Optional[float]] = [None] * len(arrivals)

        def worker(i: int, offset: float, ids, max_new) -> None:
            delay = offset - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            body = {"prompt_ids": list(ids), "id": f"bench-{i}"}
            if max_new is not None:
                body["max_new_tokens"] = int(max_new)
            t_req = time.perf_counter()
            results[i] = self.handle_generate(body)
            durations[i] = time.perf_counter() - t_req

        threads = [
            threading.Thread(target=worker, args=(i, off, ids, mn), daemon=True)
            for i, (off, ids, mn) in enumerate(arrivals)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        done = [r for r in results if r is not None]
        out = [body for _, body in done]
        completions = [
            b for s, b in done
            if s == 200 and b.get("completion_reason") in ("stop", "length")
        ]
        gen = sum(int(b.get("n_generated") or 0) for b in completions)
        routed = len(completions)
        from automodel_tpu.telemetry.report import percentile

        route_durs = [d for d in durations if d is not None]
        # token-weighted hit rate: prompt tokens served from a replica's
        # cache hierarchy over all prompt tokens routed — the per-request
        # `prefix_hits` counter overstates 1-block matches
        hit_toks = sum(
            int(b.get("prefix_hit_tokens") or 0) for b in completions
        )
        prompt_toks = sum(
            int(b.get("prompt_tokens") or 0) for b in completions
        )
        stats = {
            "requests": routed,
            "gen_tokens": gen,
            "wall_s": wall,
            "fleet_tokens_per_s": gen / wall if wall > 0 else 0.0,
            "retries": self.retries_total - req0["retries"],
            "prefix_hits": self.prefix_hits_total - req0["hits"],
            "kv_handoffs": self.handoffs_total - req0["handoffs"],
            "prefix_hit_rate": (
                hit_toks / prompt_toks if prompt_toks else 0.0
            ),
            "prefix_hit_tokens": hit_toks,
            "prompt_tokens": prompt_toks,
            # shared linear-interpolation percentile (telemetry/report.py)
            # — the same rule every other p50/p99 in the tree uses
            "route_p50_s": percentile(route_durs, 0.50),
            "route_p99_s": percentile(route_durs, 0.99),
            "failed_requests": len(arrivals) - routed,
        }
        return out, stats


def serve_router_http(
    router: Router, port: int, host: str = "127.0.0.1"
):
    """→ started ThreadingHTTPServer exposing the router with the SAME
    front contract as a single replica (serving/server.py)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            logger.debug("router http: " + fmt, *args)

        def _json(self, code: int, obj: dict, retry_after: Any = False):
            # retry_after: False = no header, True = flat advice, a
            # number = that many seconds (tier-scaled QoS advice)
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

        def do_GET(self):
            if self.path == "/metrics":
                from automodel_tpu.telemetry.prometheus import CONTENT_TYPE

                # the router's own registry, then the federation block:
                # every replica sample re-exported with a `replica` label
                # plus the automodel_fleet_* aggregates (name sets are
                # disjoint, so the concatenation stays one valid exposition)
                body = (
                    router.metrics.registry.render()
                    + router.federation.render_federated()
                ).encode()
                self.send_response(200)
                self.send_header("Content-Type", CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if self.path == "/healthz":
                ok = router.healthy()
                return self._json(200 if ok else 503, {
                    "ok": ok, "probe_thread_alive": ok,
                })
            if self.path == "/readyz":
                ready = router.ready()
                return self._json(200 if ready else 503, {
                    "ready": ready,
                    "draining": router.draining,
                    "replicas_ready": len(router._candidates(set(), "decode")),
                })
            if self.path != "/stats":
                return self._json(404, {"error": f"unknown path {self.path}"})
            return self._json(200, router.stats())

        def do_POST(self):
            if self.path == "/rolling_update":
                # fleet-wide weight hot-swap: ``{"peer": {"host", "port"},
                # "timeout_s": s, "drain_timeout_s": s}``. Responds 200
                # IMMEDIATELY and runs the sequential update on a
                # background thread (mirror of a replica's /retire) — the
                # caller polls /stats rolling_update for progress.
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n) or b"{}")
                    if not isinstance(req, dict):
                        raise ValueError("request body is not a JSON object")
                except (ValueError, TypeError) as e:
                    return self._json(400, {"error": str(e)})
                peer = req.get("peer")
                if not (
                    isinstance(peer, dict)
                    and peer.get("host")
                    and peer.get("port") is not None
                ):
                    return self._json(400, {
                        "error": "rolling_update needs peer.{host, port}"
                    })
                if router._rolling is not None and router._rolling.get("active"):
                    return self._json(409, {
                        "error": "a rolling update is already in progress",
                        "rolling_update": dict(router._rolling),
                    })
                kw = {}
                if req.get("timeout_s") is not None:
                    kw["timeout_s"] = float(req["timeout_s"])
                if req.get("drain_timeout_s") is not None:
                    kw["drain_timeout_s"] = float(req["drain_timeout_s"])
                threading.Thread(
                    target=router.rolling_update, args=(peer,), kwargs=kw,
                    name="router-rolling-update", daemon=True,
                ).start()
                return self._json(200, {"ok": True, "started": True})
            if self.path != "/generate":
                return self._json(404, {"error": f"unknown path {self.path}"})
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                if not isinstance(req, dict):
                    raise ValueError("request body is not a JSON object")
            except (ValueError, TypeError) as e:
                return self._json(400, {"error": str(e)})
            # a client-sent traceparent continues the client's trace (the
            # body-field form also works for tests/curl without headers)
            tp = self.headers.get("traceparent")
            if tp is not None and "traceparent" not in req:
                req["traceparent"] = tp
            # tenant/tier headers stash into the body the same way (body
            # fields from bare-bones clients stay authoritative)
            for header, field in (("X-Tenant-Id", "tenant"), ("X-Tier", "tier")):
                hv = self.headers.get(header)
                if hv is not None and req.get(field) is None:
                    req[field] = hv
            code, body = router.handle_generate(req)
            self._json(
                code, body,
                retry_after=(
                    _tier_retry_after(req.get("tier"))
                    if code in (429, 503) else False
                ),
            )

    server = ThreadingHTTPServer((host, port), Handler)
    return server


def main(cfg: Any) -> int:
    """`automodel_tpu route -c cfg.yaml` — run the fleet router. The config
    needs a ``fleet:`` section (static ``replicas:`` or ``dns:``); the
    ``model:`` section is only consulted for an optional router-side
    tokenizer (text-prompt affinity hashing) and never built."""
    from automodel_tpu.loggers.log_utils import setup_logging

    setup_logging()
    fleet_section = dict(cfg.get("fleet", {}) or {})
    fcfg = FleetConfig.from_dict(fleet_section)
    if fcfg.port is None:
        print(
            "fleet.port is required for `automodel_tpu route` "
            "(the router's HTTP front)",
        )
        return 2
    tokenizer = None
    gen_section = dict(cfg.get("generation", {}) or {})
    if gen_section.get("tokenizer") is not None:
        # imports jax transitively — only paid when text-prompt affinity
        # hashing is actually configured
        from automodel_tpu.generation.engine import resolve_tokenizer

        tokenizer = resolve_tokenizer(gen_section.get("tokenizer"), None)
    on_record = None
    metric_logger = None
    logging_section = dict(cfg.get("logging", {}) or {})
    if logging_section.get("metrics_path"):
        from automodel_tpu.loggers.metric_logger import MetricLogger

        metric_logger = MetricLogger(logging_section["metrics_path"])
        on_record = metric_logger.log
    # request tracing: the router is where fleet traces are minted; spans
    # ride the same metrics JSONL as route_request records
    import os as os_mod

    from automodel_tpu.telemetry.tracing import Tracer, TracingConfig

    tracing_cfg = TracingConfig.from_dict(dict(cfg.get("tracing", {}) or {}))
    tracer = Tracer.from_config(
        tracing_cfg, process=f"router-{os_mod.getpid()}", emit=on_record
    )
    # fleet health plane: a strict `slo:` section arms burn-rate alerting
    # over the federated replica scrapes; alert transitions land in the
    # metrics JSONL and a flight-recorder ring next to it
    slo_cfg = None
    slo_section = dict(cfg.get("slo", {}) or {})
    if slo_section:
        from automodel_tpu.telemetry.slo import SLOConfig

        slo_cfg = SLOConfig.from_dict(slo_section)
    flight_recorder = None
    if slo_cfg is not None and logging_section.get("metrics_path"):
        from pathlib import Path as _Path

        from automodel_tpu.telemetry.flight_recorder import FlightRecorder

        flight_recorder = FlightRecorder(
            capacity=64,
            path=str(
                _Path(logging_section["metrics_path"]).parent
                / "router_flight_recorder.json"
            ),
        )
    # elastic fleet: a strict `autoscale:` section arms the closed-loop
    # controller; a `k8s_fleet:` section beside it gives it the kubectl
    # backend (otherwise decisions log but cannot act — tests inject a
    # LocalProcessBackend through the Router constructor instead)
    autoscale_cfg = None
    autoscale_section = dict(cfg.get("autoscale", {}) or {})
    if autoscale_section:
        from automodel_tpu.serving.fleet.autoscale import AutoscaleConfig

        autoscale_cfg = AutoscaleConfig.from_dict(autoscale_section)
    scale_backend = None
    if autoscale_cfg is not None and autoscale_cfg.enabled:
        k8s_section = dict(cfg.get("k8s_fleet", {}) or {})
        if k8s_section:
            from automodel_tpu.launcher.k8s import K8sFleetConfig
            from automodel_tpu.serving.fleet.autoscale import K8sFleetBackend

            k8s_section.pop("_target_", None)
            known = {f.name for f in dataclasses.fields(K8sFleetConfig)}
            unknown = set(k8s_section) - known
            if unknown:
                raise TypeError(f"unknown k8s_fleet keys: {sorted(unknown)}")
            kcfg = K8sFleetConfig(**k8s_section)
            role = "decode" if kcfg.mixed == 0 and kcfg.decode > 0 else "mixed"
            scale_backend = K8sFleetBackend(kcfg, role=role)
        else:
            logger.warning(
                "autoscale.enabled without a k8s_fleet: section — the "
                "controller will evaluate and log decisions but has no "
                "backend to act through"
            )
    router = Router(
        fcfg, tokenizer=tokenizer, on_record=on_record, tracer=tracer,
        slo_config=slo_cfg, flight_recorder=flight_recorder,
        autoscale_config=autoscale_cfg, scale_backend=scale_backend,
    )
    router.start()
    server = serve_router_http(router, fcfg.port, host=fcfg.host)

    def _drain_then_stop():
        router.begin_drain()
        deadline = time.monotonic() + fcfg.drain_grace_s
        while time.monotonic() < deadline:
            time.sleep(0.05)
        server.shutdown()

    def _on_term():
        threading.Thread(
            target=_drain_then_stop, name="route-drain", daemon=True
        ).start()

    handler = None
    if threading.current_thread() is threading.main_thread():
        from automodel_tpu.resilience.preemption import PreemptionHandler

        handler = PreemptionHandler(
            signals=("SIGTERM",), on_preempt=_on_term,
            log_message=(
                "router drain: rejecting new requests retriable, letting "
                f"in-flight forwards finish within {fcfg.drain_grace_s}s"
            ),
        )
        handler.install()
    print(
        json.dumps({
            "event": "route_listening",
            "host": fcfg.host, "port": server.server_address[1],
            "replicas": len(router._replicas), "dns": fcfg.dns,
        }),
        flush=True,
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
        router.close()
        if handler is not None:
            handler.restore()
        if metric_logger is not None:
            metric_logger.close()
    return 0
