"""Continuous-batching serving engine over the paged KV-cache pool.

The scheduler loop (one ``step()`` = one engine iteration):

1. **expire** — requests whose ``deadline_s``/``max_queue_wait_s`` elapsed
   are cancelled wherever they are (queued, prefilling, decoding): blocks
   freed, a ``timeout`` completion reason recorded. Nothing is ever
   silently dropped — every submitted request produces exactly one
   terminal record.
2. **admit** — pop queued requests into free decode slots. A request's
   WHOLE block budget (``ceil((prompt + max_new) / block_size)``) is
   allocated at admission (minus any prefix-cache hit), so a running
   sequence never needs a mid-flight allocation and the engine cannot
   deadlock on a full pool: if the pool can't cover the head-of-queue
   request it simply stays queued until completions free blocks. While
   **draining** nothing admits: the queue is flushed with retriable
   ``draining`` rejections and only in-flight requests keep running.
3. **prefill tick** — every mid-prefill slot advances ONE chunk
   (``prefill_chunk`` tokens) through the jitted chunked-prefill program.
   Bounding per-iteration prefill work is what keeps time-to-first-token of
   queued requests from stalling behind a single long prompt: the decode
   wave below still runs every iteration.
4. **decode tick** — one jitted paged decode step over all slots; active
   slots each advance one token. The tick LAUNCHES AHEAD: it dispatches
   step n + 1 first and only then reads and records step n's tokens, so
   the host's work of an iteration runs beside a decode program and the
   device always has the next one queued. Step n + 1 needs nothing of
   step n on the host: tables are fixed at admission, a slot with a row in
   flight is one token longer, a spent budget is arithmetic (a slot is
   never launched past ``max_new``), and ``cur`` is taken inside the
   program from step n's token array, which never left the device. Only a
   stop id needs the token: it is found one step late, and the row the
   slot already has in step n + 1 is DISCARDED when that step is read
   (never appended, no logprob; its K/V write lies inside the slot's own
   budget, behind every position anyone reads, and before any write of a
   later tenant in launch order). Every row of the step in flight carries
   the ``_Slot`` it was launched for, so a slot that ended, was cancelled
   or changed hands in between receives nothing. An iteration with nothing
   to launch still reads what is in flight (a request's last token arrives
   that way), and ``idle()`` is false until it has. The pipeline drains
   where the host must wait for the device anyway: the first-token wait
   after a prompt's last chunk. With ``serving.speculative:`` the tick
   keeps its synchronous order (one draft-propose + ONE batched verify
   forward advancing each slot by 1 to k+1 tokens, so the next lengths are
   not host-known; rollback of rejected drafts is a host-side length
   decrement; no copies). Slots whose token hits a stop id or whose
   budget is spent COMPLETE: their blocks decref back to the pool (prompt
   blocks stay matchable in the prefix cache) and the slot refills from the
   queue on the next iteration — mid-flight, without waiting for the rest
   of the wave.

Failure containment (the PR 3/5 doctrine ported to serving): a wedged
jitted step is detected by the :class:`EngineWatchdog` (adaptive EMA
deadline — resilience/watchdog.py) which dumps stacks + flight recorder
and flags the engine; when the blocked call returns (or an exception
escapes a tick) the engine fails ONLY the affected wave's requests with an
``engine_stall``/``engine_error`` reason, re-initializes the pool arrays
(the failed program may have left its donated buffers in an arbitrary
state), clears the prefix cache (contents no longer trusted), audits the
allocator invariants, and keeps serving the queue. Repeated back-to-back
rebuilds are a systemic fault and re-raise loudly instead of looping.

Greedy decode through this path is token-parity with the single-wave
``generation.GenerationEngine`` (tests/test_serving.py pins it, full and
ring-model layouts); sampled decode draws from the same per-host base key
but a GLOBAL step counter, so streams differ from the single-wave engine by
construction (documented in docs/serving.md).

Windowed (mistral-style) models run on the FULL paged layout with the
per-layer window masks narrowing attention — unlike the single-wave ring
layout there is no wraparound hazard, so ragged windowed batches are fine
here. HBM cost is bounded by ``max_seq_len``, not the window.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import logging
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.generation.engine import (
    GenerationConfig,
    GenerationUnsupported,
    _model_max_positions,
)
from automodel_tpu.generation import kv_cache
from automodel_tpu.generation.sampling import sample
from automodel_tpu.ops import latent_attention, paged_attention
from automodel_tpu.serving import loop_account, paged
from automodel_tpu.serving.block_pool import (
    BlockPool,
    HostSpillTier,
    blocks_needed,
    prompt_chain,
)
from automodel_tpu.telemetry.tracing import SpanContext, Tracer, WallAnchor
from automodel_tpu.training.rng import sampling_key

logger = logging.getLogger(__name__)

# terminal `completion_reason` values every request record carries exactly
# one of (docs/observability.md glossary):
#   stop         — hit a configured eos id
#   length       — spent its max_new_tokens budget
#   prefilled    — a prefill-only request (disaggregated fleet: the KV
#                  payload was extracted for transfer to a decode replica)
#                  finished its prompt; a completion, not a failure
#   timeout      — deadline_s / max_queue_wait_s expired (not retriable:
#                  the client's own budget ran out)
#   shed         — rejected at submit, admission queue full (retriable)
#   quota        — rejected at submit, the tenant's token-bucket quota is
#                  exhausted (retriable — after the Retry-After window the
#                  bucket has refilled)
#   draining     — rejected because the server is draining (retriable)
#   cancelled    — in flight when the drain grace expired (retriable)
#   engine_stall — failed by a watchdog-detected wedged step (retriable)
#   engine_error — failed by a scheduler/program exception (retriable)
COMPLETION_REASONS = (
    "stop", "length", "prefilled", "timeout", "shed", "quota", "draining",
    "cancelled", "engine_stall", "engine_error",
)
_COMPLETED_REASONS = frozenset({"stop", "length", "prefilled"})
_RETRIABLE_REASONS = frozenset(
    {"shed", "quota", "draining", "cancelled", "engine_stall", "engine_error"}
)

# QoS tiers, highest priority first (serving.qos / docs/serving.md
# "Multi-tenant QoS"): admission, shedding, and Retry-After scaling all key
# off the tier's INDEX in this tuple — interactive work is admitted first
# and shed last.
TIERS = ("interactive", "batch", "best_effort")
_TIER_INDEX = {t: i for i, t in enumerate(TIERS)}


def tier_index(tier: str) -> int:
    """Priority rank of a tier (0 = highest). Unknown tiers raise — a typo
    must never silently demote (or promote) a tenant."""
    try:
        return _TIER_INDEX[tier]
    except KeyError:
        raise ValueError(
            f"unknown QoS tier {tier!r} (want one of {'|'.join(TIERS)})"
        ) from None


class QueueFull(RuntimeError):
    """Admission queue at max_queue: overload is SHED back to the caller as
    an explicit retriable signal (HTTP 503 + Retry-After, stdin-JSONL error
    record) — the engine never silently drops or silently queues-forever."""


class EngineDraining(RuntimeError):
    """Submissions rejected while the server drains (SIGTERM received):
    retriable — the client should go to another replica. HTTP maps this to
    503 + Retry-After, stdin-JSONL to an error record."""


class QuotaExceeded(RuntimeError):
    """The tenant's token-bucket quota (requests/s or decode-tokens/s) is
    exhausted: retriable after the bucket refills. HTTP maps this to 429 +
    a tier-scaled Retry-After with ``reason: quota``. Carries ``tenant`` and
    ``tier`` so the front can label the rejection."""

    def __init__(self, message: str, tenant: str, tier: str):
        super().__init__(message)
        self.tenant = tenant
        self.tier = tier


def _cfg_dict(cls, d: Optional[dict], section: str):
    """Strict nested-section constructor shared by limits/drain/watchdog."""
    d = dict(d or {})
    d.pop("_target_", None)
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = set(d) - known
    if unknown:
        raise TypeError(f"unknown {section} keys: {sorted(unknown)}")
    return cls(**d)


@dataclasses.dataclass(frozen=True)
class LimitsConfig:
    """The ``serving.limits:`` section — per-request time budgets. 0/None
    disables a bound. Per-request ``deadline_s``/``max_queue_wait_s`` on
    submit (or the request JSON) override these defaults."""

    deadline_s: Optional[float] = None  # submit → completion wall cap
    max_queue_wait_s: Optional[float] = None  # submit → admission wall cap

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "LimitsConfig":
        return _cfg_dict(cls, d, "serving.limits")


@dataclasses.dataclass(frozen=True)
class DrainConfig:
    """The ``serving.drain:`` section — graceful-shutdown semantics.

    SIGTERM (chained through the PR 3 ``PreemptionHandler``) flips the
    server to draining: new and queued requests are rejected retriable,
    in-flight requests finish within ``grace_s``, then the scheduler exits
    cleanly. ``requeue_exit`` picks the exit code: ``auto`` exits 75
    (EX_TEMPFAIL — the launchers' requeue code) when running under slurm/
    k8s and 0 otherwise; ``always``/``never`` force it."""

    grace_s: float = 30.0
    install_signal_handler: bool = True
    requeue_exit: str = "auto"  # auto | always | never

    def __post_init__(self):
        if self.requeue_exit not in ("auto", "always", "never"):
            raise ValueError(
                f"serving.drain.requeue_exit={self.requeue_exit!r} "
                "(want auto|always|never)"
            )
        if self.grace_s < 0:
            raise ValueError(f"serving.drain.grace_s={self.grace_s}")

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "DrainConfig":
        return _cfg_dict(cls, d, "serving.drain")


@dataclasses.dataclass(frozen=True)
class StallConfig:
    """The ``serving.watchdog:`` section — scheduler-level stall detection
    (maps onto resilience.watchdog.EngineWatchdog). The watchdog thread is
    started by the serving fronts (``start_watchdog``), not by engine
    construction — batch ``run()`` drains own their own lifetime."""

    enabled: bool = True
    multiplier: float = 20.0
    min_deadline_s: float = 30.0
    max_deadline_s: float = 600.0
    ema_alpha: float = 0.2
    compile_grace_s: float = 1800.0  # first prefill/decode compile
    poll_interval_s: float = 0.25
    stacks_path: Optional[str] = None

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "StallConfig":
        return _cfg_dict(cls, d, "serving.watchdog")


@dataclasses.dataclass(frozen=True)
class KVTransferConfig:
    """The ``serving.kv_transfer:`` section — the prefill→decode KV handoff
    listener (serving/fleet/kv_transfer.py). A DECODE-role replica starts
    it by default (``enabled: null`` = auto); a mixed replica only when
    explicitly enabled. ``port: 0`` binds an ephemeral port, advertised to
    the router via the ``kv_transfer_port`` /stats field."""

    enabled: Optional[bool] = None  # null = auto (on when role == decode)
    host: str = "127.0.0.1"
    port: int = 0  # 0 = ephemeral, advertised via /stats
    max_pending: int = 32  # undelivered handoff payloads held host-side
    ttl_s: float = 120.0  # a payload never claimed by /generate expires

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "KVTransferConfig":
        return _cfg_dict(cls, d, "serving.kv_transfer")


@dataclasses.dataclass(frozen=True)
class KVSpillConfig:
    """The ``serving.kv_spill:`` section — the hierarchical KV cache
    (docs/serving.md "Hierarchical KV cache"). When enabled, prefix blocks
    evicted from the HBM pool's LRU spill device→host into a bounded
    host-RAM tier keyed by the same chain hashes the prefix cache uses;
    an admission whose prefix extends past resident blocks reloads the
    spilled rows through ``paged.inject_blocks`` instead of re-prefilling
    (greedy output bit-identical). ``peer_fetch`` extends the hierarchy
    fleet-wide: a router-hinted replica pulls missing prefix blocks from
    the peer that advertises them over AKV1 ``kv_fetch``, falling back to
    local recompute on any failure within the request's deadline."""

    enabled: bool = False
    max_host_mb: float = 256.0  # host tier budget (LRU beyond this)
    peer_fetch: bool = True  # honor router kv_peer hints via /kv_fetch
    fetch_timeout_s: float = 5.0  # per-fetch cap (also clamped to deadline)

    def __post_init__(self):
        if self.max_host_mb <= 0:
            raise ValueError(
                f"serving.kv_spill.max_host_mb={self.max_host_mb} (want > 0)"
            )
        if self.fetch_timeout_s <= 0:
            raise ValueError(
                f"serving.kv_spill.fetch_timeout_s={self.fetch_timeout_s} "
                "(want > 0)"
            )

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "KVSpillConfig":
        return _cfg_dict(cls, d, "serving.kv_spill")


@dataclasses.dataclass(frozen=True)
class WarmStartConfig:
    """The ``serving.warm_start:`` section — elastic-fleet peer warm-start
    (docs/serving.md "Elastic fleet"). When a peer is named, a starting
    replica builds its model STRUCTURALLY (shapes + sharding, seeded
    params) and then streams the actual weights from that peer's AKV1
    listener (``op: weights_fetch``) instead of paying the cold HF load,
    validating the peer's param-tree signature (the PR 6 checkpoint guard)
    against its own tree before swapping a single leaf. ANY failure —
    transport death, refusal, digest mismatch — falls back to the cold
    load path unchanged; warm-start is an optimization, never a
    correctness dependency. The boot source actually taken is recorded as
    ``boot_source`` (``cold_hf`` | ``peer_warm_start``) beside
    ``time_to_ready_s`` on /stats and the metrics JSONL."""

    peer_host: Optional[str] = None
    peer_port: Optional[int] = None  # the peer's kv_transfer listener port
    timeout_s: float = 60.0  # whole-tree stream budget

    def __post_init__(self):
        if (self.peer_host is None) != (self.peer_port is None):
            raise ValueError(
                "serving.warm_start needs BOTH peer_host and peer_port "
                f"(got host={self.peer_host!r}, port={self.peer_port!r})"
            )
        if self.timeout_s <= 0:
            raise ValueError(
                f"serving.warm_start.timeout_s={self.timeout_s} (want > 0)"
            )

    @property
    def enabled(self) -> bool:
        return self.peer_host is not None

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "WarmStartConfig":
        return _cfg_dict(cls, d, "serving.warm_start")


@dataclasses.dataclass(frozen=True)
class SpeculativeConfig:
    """The ``serving.speculative:`` section — draft-and-verify speculative
    decoding (Leviathan et al. 2023). A small draft model proposes ``k``
    tokens per slot per engine iteration; ONE batched verify forward
    through the paged path accepts a prefix + one correction/bonus token.
    Greedy output is bit-identical to non-speculative decoding (the
    exactness rule); sampled output preserves the target distribution.

    ``draft`` is a ``model:``-shaped section (``hf_config`` + ``backend``
    or ``pretrained_model_name_or_path``) built onto the target's mesh via
    the ``build_auto_from_model_section`` ladder. The draft must be
    cache-capable and share the target's vocabulary."""

    enabled: bool = False
    k: int = 4  # draft tokens proposed per slot per engine step
    draft: Optional[Any] = None  # model: section for the draft

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"serving.speculative.k={self.k} (want >= 1)")
        if self.enabled and not self.draft:
            raise ValueError(
                "serving.speculative.enabled needs a draft model section "
                "(serving.speculative.draft: {hf_config: ...} or "
                "{pretrained_model_name_or_path: ...})"
            )

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "SpeculativeConfig":
        return _cfg_dict(cls, d, "serving.speculative")


@dataclasses.dataclass(frozen=True)
class TenantConfig:
    """One ``serving.qos.tenants:`` entry — the tenant's default tier, its
    weighted-fair-queuing share, and its token-bucket quotas. A quota of
    None means unlimited (the bucket never rejects)."""

    tier: Optional[str] = None  # default tier; null = qos.default_tier
    weight: float = 1.0  # WFQ share within the tenant's tier
    requests_per_s: Optional[float] = None  # admission token bucket
    decode_tokens_per_s: Optional[float] = None  # decode-budget bucket
    burst_s: float = 2.0  # bucket depth, in seconds of the rate

    def __post_init__(self):
        if self.tier is not None:
            tier_index(self.tier)  # raises on a typo
        if self.weight <= 0:
            raise ValueError(f"qos tenant weight={self.weight} (want > 0)")
        for name in ("requests_per_s", "decode_tokens_per_s"):
            v = getattr(self, name)
            if v is not None and v <= 0:
                raise ValueError(f"qos tenant {name}={v} (want > 0 or null)")
        if self.burst_s <= 0:
            raise ValueError(f"qos tenant burst_s={self.burst_s} (want > 0)")

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "TenantConfig":
        return _cfg_dict(cls, d, "serving.qos.tenants entry")


@dataclasses.dataclass(frozen=True)
class QoSConfig:
    """The ``serving.qos:`` section — multi-tenant quality of service
    (docs/serving.md "Multi-tenant QoS"). When enabled, the admission queue
    becomes priority-tiered (``TIERS`` order) with EDF ordering inside each
    tier and weighted fair queuing across tenants; per-tenant token buckets
    reject over-quota submissions with the retriable ``quota`` reason; a
    full queue sheds strictly lowest-tier-first; and ``aging_s`` bounds
    starvation by promoting long-waiting low-tier work to the top tier.
    Disabled (the default), admission is exactly the FIFO it always was."""

    enabled: bool = False
    default_tier: str = "interactive"  # tier when request + tenant name none
    default_tenant: str = "anonymous"  # tenant when the request names none
    aging_s: float = 30.0  # queued longer than this → ordered as top tier
    tenants: Any = dataclasses.field(default_factory=dict)  # name → TenantConfig

    def __post_init__(self):
        tier_index(self.default_tier)
        if self.aging_s <= 0:
            raise ValueError(f"serving.qos.aging_s={self.aging_s} (want > 0)")
        from automodel_tpu.telemetry.prometheus import _LABEL_VALUE_OK

        for name in list(self.tenants) + [self.default_tenant]:
            # tenant names become /metrics label values — refuse anything
            # the exposition sanitizer would mangle, loudly and up front
            if not _LABEL_VALUE_OK.match(str(name)):
                raise ValueError(
                    f"qos tenant name {name!r} is not a valid metrics label "
                    "value (want [a-zA-Z0-9_.+-]+)"
                )

    def tenant(self, name: str) -> TenantConfig:
        return self.tenants.get(name) or TenantConfig()

    def tier_for(self, name: str) -> str:
        t = self.tenants.get(name)
        return t.tier if t is not None and t.tier else self.default_tier

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "QoSConfig":
        d = dict(d or {})
        tenants = d.get("tenants")
        if tenants is not None:
            d["tenants"] = {
                str(name): (
                    sub if isinstance(sub, TenantConfig)
                    else TenantConfig.from_dict(dict(sub or {}))
                )
                for name, sub in dict(tenants).items()
            }
        return _cfg_dict(cls, d, "serving.qos")


class _TokenBucket:
    """Per-tenant rate limiter: ``rate`` units/s refill into a bucket of
    ``rate * burst_s`` depth; ``take`` spends or refuses. rate None =
    unlimited. Timestamps are the caller's perf_counter values."""

    def __init__(self, rate: Optional[float], burst_s: float):
        self.rate = rate
        self.capacity = (rate or 0.0) * burst_s
        self.tokens = self.capacity
        self.t_last: Optional[float] = None

    def take(self, n: float, now: float) -> bool:
        if self.rate is None:
            return True
        if self.t_last is not None and now > self.t_last:
            self.tokens = min(
                self.capacity, self.tokens + (now - self.t_last) * self.rate
            )
        self.t_last = now
        if self.tokens >= n:
            self.tokens -= n
            return True
        return False


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """The `serving:` YAML section (scheduler/allocator knobs; sampling and
    stop tokens come from the `generation:` section)."""

    slots: int = 4  # decode batch width
    block_size: int = 16  # tokens per KV block
    num_blocks: int = 512  # pool size (block 0 is scratch)
    prefill_chunk: int = 64  # prompt tokens per engine iteration per slot
    # chunk programs one engine iteration runs at most, the oldest admissions
    # first; 0: one for EVERY prefilling slot, however many (a decoding slot's
    # next token then waits for all of them)
    max_prefill_chunks_per_step: int = 0
    max_seq_len: int = 1024  # per-request prompt + generated cap
    max_queue: int = 4096
    prefix_cache: bool = True
    # per-token math (docs/serving.md "Raw speed"): pool precision + which
    # decode backend runs the per-token attention
    kv_cache_dtype: str = "bf16"  # bf16 (model compute dtype) | int8
    decode_kernel: str = "auto"  # auto | fused (Pallas paged kernel) | gather
    # fleet tier (docs/serving.md "Fleet"): what this replica does in a
    # disaggregated pool and how much of its prefix cache it advertises
    role: str = "mixed"  # mixed | prefill | decode
    hot_prefix_advertise: int = 512  # cached chain heads exposed via /stats
    # production-hardening sections (docs/serving.md runbook)
    limits: LimitsConfig = dataclasses.field(default_factory=LimitsConfig)
    drain: DrainConfig = dataclasses.field(default_factory=DrainConfig)
    watchdog: StallConfig = dataclasses.field(default_factory=StallConfig)
    speculative: SpeculativeConfig = dataclasses.field(
        default_factory=SpeculativeConfig
    )
    kv_transfer: KVTransferConfig = dataclasses.field(
        default_factory=KVTransferConfig
    )
    kv_spill: KVSpillConfig = dataclasses.field(default_factory=KVSpillConfig)
    warm_start: WarmStartConfig = dataclasses.field(
        default_factory=WarmStartConfig
    )
    qos: QoSConfig = dataclasses.field(default_factory=QoSConfig)

    def __post_init__(self):
        if self.slots < 1 or self.block_size < 1 or self.prefill_chunk < 1:
            raise ValueError(
                f"serving: slots/block_size/prefill_chunk must be >= 1 "
                f"({self.slots}/{self.block_size}/{self.prefill_chunk})"
            )
        if self.max_seq_len < 2:
            raise ValueError(f"serving.max_seq_len={self.max_seq_len}")
        if self.max_prefill_chunks_per_step < 0:
            raise ValueError(
                "serving.max_prefill_chunks_per_step="
                f"{self.max_prefill_chunks_per_step} (want 0 = no bound, or >= 1)"
            )
        if self.kv_cache_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"serving.kv_cache_dtype={self.kv_cache_dtype!r} "
                "(want bf16|int8)"
            )
        if self.decode_kernel not in ("auto", "fused", "gather"):
            raise ValueError(
                f"serving.decode_kernel={self.decode_kernel!r} "
                "(want auto|fused|gather)"
            )
        if self.role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"serving.role={self.role!r} (want mixed|prefill|decode)"
            )
        if self.hot_prefix_advertise < 0:
            raise ValueError(
                f"serving.hot_prefix_advertise={self.hot_prefix_advertise}"
            )

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "ServeConfig":
        d = dict(d or {})
        d.pop("_target_", None)
        d.pop("http", None)  # server-level section (serving/server.py)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise TypeError(f"unknown serving keys: {sorted(unknown)}")
        for key, sub in (
            ("limits", LimitsConfig),
            ("drain", DrainConfig),
            ("watchdog", StallConfig),
            ("speculative", SpeculativeConfig),
            ("kv_transfer", KVTransferConfig),
            ("kv_spill", KVSpillConfig),
            ("warm_start", WarmStartConfig),
            ("qos", QoSConfig),
        ):
            v = d.get(key)
            if v is not None and not isinstance(v, sub):
                d[key] = sub.from_dict(dict(v))
        return cls(**d)

    def check_layout(self, layout, model_name: str) -> None:
        """Refuse, before anything is built, what the model's cache layout
        (``cache_layout()``, generation/kv_cache.py) cannot be served with:
        none of it is silently off. A layout that also keeps a fixed-size
        recurrent state a slot loses every feature that takes a sequence's
        state to be "blocks + a length"; a LATENT layout (one shared row a
        token, paged like K/V: prefix reuse works on its blocks as it stands)
        loses those that ship block rows or need a second pool."""
        kinds = kv_cache.recurrent_kinds(layout)
        unserved = [k for k in kinds if k != "conv"]
        if unserved:
            raise ValueError(
                f"{model_name} keeps {'/'.join(unserved)} state a layer (a delta-rule "
                "state [heads, dk, dv] a slot): serving/ holds per-head K/V, latent "
                "rows and conv windows, so a delta-rule state is not served yet; "
                "the family trains (recipes/train_ft.py)"
            )
        if kv_cache.layers_of(layout, "latent"):
            what = f"{model_name} keeps one latent row a token (a pool of one side, no V)"
            refused = [
                (self.kv_cache_dtype != "bf16", f"kv_cache_dtype: {self.kv_cache_dtype}",
                 "a latent row has no per-head scale to quantize under"),
                (self.kv_spill.enabled, "kv_spill.enabled",
                 "spilled and peer-fetched prefixes are (k, v) block rows; a "
                 "latent block's rows are not shipped yet"),
                (self.speculative.enabled, "speculative.enabled",
                 "the draft's parallel pool and the verify forward over a "
                 "latent pool are not built or tested"),
                (self.role != "mixed", f"role: {self.role}",
                 "the prefill->decode handoff ships (k, v) block rows; a latent "
                 "block's rows are not shipped yet"),
                (bool(self.kv_transfer.enabled), "kv_transfer.enabled",
                 "its listener receives (k, v) block rows; a latent block's "
                 "rows are not shipped yet"),
            ]
        elif kinds:
            what = f"{model_name} keeps {'/'.join(kinds)} state beside K/V"
            refused = [
                (self.prefix_cache, "prefix_cache: true",
                 "a prefix hit resumes at a block boundary and would need the "
                 "recurrent state AT that boundary, which is kept only for a "
                 "slot's newest position (set serving.prefix_cache: false)"),
                (self.kv_spill.enabled, "kv_spill.enabled",
                 "spilled and peer-fetched prefixes are K/V blocks by chain hash; "
                 "the recurrent state at their boundary is not among them"),
                (self.speculative.enabled, "speculative.enabled",
                 "rollback of rejected drafts is a length decrement, which does "
                 "not undo the inputs already shifted into a recurrent state"),
                (self.role != "mixed", f"role: {self.role}",
                 "the prefill->decode handoff ships K/V block rows only; the "
                 "slot's recurrent state would stay behind"),
            ]
        else:
            return
        for on, key, why in refused:
            if on:
                raise ValueError(f"serving.{key} is refused: {what}, and {why}")

    @property
    def spec_overhang(self) -> int:
        """Positions a speculative verify may WRITE past a sequence's final
        committed length (rejected-draft rows, rolled back by length
        decrement): the admission block budget and the table width both
        cover it so those writes always land in owned blocks."""
        return self.speculative.k if self.speculative.enabled else 0

    @property
    def table_blocks(self) -> int:
        """Static per-sequence block-table width. The extra headroom
        (prefill_chunk, or the speculative verify chunk when larger) keeps
        per-slot writes from ever clamping past the table (paged.py
        view-position invariant)."""
        headroom = max(self.prefill_chunk, self.spec_overhang + 1)
        return -(-(self.max_seq_len + headroom) // self.block_size)


@dataclasses.dataclass
class _Queued:
    rid: str
    prompt: list[int]
    max_new: int
    t_submit: float
    deadline_at: Optional[float] = None  # perf_counter absolute
    queue_deadline_at: Optional[float] = None
    # disaggregated fleet (docs/serving.md "Fleet"):
    prefill_only: bool = False  # prefill-role replica: extract KV, no decode
    payload: Optional[dict] = None  # decode-role replica: injected prompt KV
    # hierarchical KV cache: router hint naming the peer replica whose
    # prefix cache covers this prompt ({"host": ..., "port": ...}) — the
    # admission path /kv_fetch-es missing blocks from it, best-effort
    kv_peer: Optional[dict] = None
    # request tracing: this request's ROOT span context on this process
    # (child of the router's forward span when one propagated in)
    trace: Optional[SpanContext] = None
    # behavior-policy logprob capture (posttrain/grpo.py): record the
    # sampled sequence's per-token logprobs on the terminal record
    return_logprobs: bool = False
    # multi-tenant QoS (serving.qos): who submitted, at what priority —
    # stamped on the terminal record and on every tier/tenant metric label
    tenant: str = "anonymous"
    tier: str = "interactive"
    tier_idx: int = 0


@dataclasses.dataclass
class _Slot:
    request_id: str
    prompt: list[int]
    max_new: int
    blocks: list[int]  # every block this sequence holds a ref on
    hit_tokens: int  # prefix-cache reused tokens
    prefill_pos: int  # next absolute prompt position to compute
    t_submit: float
    t_admit: float
    deadline_at: Optional[float] = None
    decoding: bool = False
    generated: Optional[list[int]] = None
    t_first: Optional[float] = None
    # the loop's account at ``t_first`` (serving/loop_account.py): the
    # terminal record carries its difference to the account at the end
    account_first: Optional[loop_account.Snapshot] = None
    prefill_only: bool = False
    spec_proposed: int = 0  # draft tokens proposed for this request
    spec_accepted: int = 0  # draft tokens accepted by the verify rule
    trace: Optional[SpanContext] = None
    # parallel to ``generated`` when the request asked for logprobs: the
    # behavior policy's own log π(token) at each sampled position
    logprobs: Optional[list[float]] = None
    tenant: str = "anonymous"
    tier: str = "interactive"


@dataclasses.dataclass
class _DecodeInFlight:
    """A decode step that was launched and whose tokens are not read yet.
    ``slots[b]`` is the ``_Slot`` row ``b`` was launched for (None: the row
    was not active in this step); the arrays are still on the device."""

    tokens: Any  # [B] int32, the next step's ``prev_tokens``
    logps: Any  # [B] fp32
    units: Any  # [2] int32 (live, grid) expert work units
    slots: list  # [B] of Optional[_Slot]


def _tree_path_name(path) -> str:
    """The param-tree leaf naming rule — MUST match
    ``checkpoint.checkpointer.param_tree_signature`` exactly, so signature
    entries and hot-swapped/wire-transferred leaves line up one-to-one
    (server._warm_start_params applies the same rule)."""
    return "/".join(
        str(getattr(k, "key", getattr(k, "idx", k))) for k in path
    )


class ServingEngine:
    """Facade over (AutoModel, ServeConfig, GenerationConfig).

    ``submit`` enqueues token-id prompts; ``step`` runs one scheduler
    iteration and returns the requests that reached a terminal state in it
    (completed, timed out, rejected, failed — every record carries a
    ``completion_reason``); ``run`` drains everything. ``on_record``
    (optional) receives one telemetry dict per terminal request (the serve
    CLI points it at the metrics JSONL)."""

    # back-to-back rebuild budget: a fault that survives this many fresh
    # pools in a row is systemic (bad params, broken backend) — fail the
    # scheduler loudly instead of rebuild-looping forever
    MAX_CONSECUTIVE_REBUILDS = 8

    def __init__(
        self,
        auto: Any,
        config: Optional[ServeConfig] = None,
        gen_config: Optional[GenerationConfig] = None,
        on_record: Optional[Callable[[dict], None]] = None,
        tracer: Optional[Tracer] = None,
    ):
        self._layout = kv_cache.layout_of(auto.model)
        if self._layout is None:
            raise GenerationUnsupported(
                f"{type(auto.model).__name__} states no cache layout "
                "(cache_layout(): what each layer keeps for a sequence — `kv` "
                "heads x head_dim, `latent` one row a token, or `conv` channels "
                "x taps), so it has no decode path; families that state one: "
                "llama-generic (llama/qwen2/qwen3/mistral/phi3), gpt2, "
                "qwen3_moe (all `kv`), lfm2_moe (`kv` + `conv`), sarvam_mla "
                "(`latent`)"
            )
        self.auto = auto
        self.model = auto.model
        self.config = config or ServeConfig()
        self.config.check_layout(self._layout, type(auto.model).__name__)
        # a layout with a recurrent kind: the pool carries one state row a slot
        self._stateful = bool(kv_cache.recurrent_kinds(self._layout))
        # a latent layout: the pool has one side and its own decode kernel
        self._latent = bool(kv_cache.layers_of(self._layout, "latent"))
        self.gen_config = gen_config or GenerationConfig()
        self.on_record = on_record
        mcfg = self.model.config
        self._max_positions = _model_max_positions(mcfg)
        if self._max_positions and self.config.max_seq_len > self._max_positions:
            raise ValueError(
                f"serving.max_seq_len={self.config.max_seq_len} exceeds the "
                f"model context limit {self._max_positions}"
            )
        self.pool = BlockPool(
            self.config.num_blocks, self.config.block_size,
            prefix_cache=self.config.prefix_cache,
        )
        # per-token math levers (docs/serving.md "Raw speed"): pool
        # precision, decode backend, speculative draft
        self._quantized = self.config.kv_cache_dtype == "int8"
        self._compute_dtype = self.model.backend.compute_jnp_dtype
        from automodel_tpu.ops.attention import _interpret_requested

        self._interpret = _interpret_requested()
        self.decode_backend = self._resolve_decode_backend()
        if self._latent:
            from automodel_tpu.ops.platform_check import kernel_axes

            if kernel_axes(auto.mesh_ctx) is not None:
                raise ValueError(
                    f"{type(auto.model).__name__} keeps latent rows: the latent "
                    "pool and its decode kernel run on one device (no shard_map "
                    "over a mesh is built for them yet)"
                )
        elif self.decode_backend == "fused" and auto.mesh_ctx is not None:
            # the paged kernel runs per TP shard on whole heads
            # (ops/paged_attention.paged_attend); refuse here, not at the
            # first decode step — where step() would catch the error, fail
            # the wave, rebuild, and the front would still exit 0
            from automodel_tpu.ops.platform_check import sharded_axes

            sharded_axes(
                auto.mesh_ctx, "tensor",
                (int(mcfg.num_heads), int(mcfg.num_kv_heads)),
                "serving.decode_kernel: fused (or serve with decode_kernel: "
                "gather) — num_attention_heads / num_key_value_heads",
            )
        spec = self.config.speculative
        self._spec_enabled = bool(spec.enabled)
        sp = self.config.kv_spill
        if sp.enabled and self._spec_enabled:
            # same reason submit_prefilled refuses spec engines: a reloaded
            # prefix fills only the TARGET pool — the draft's parallel pool
            # would miss the prompt KV and proposals would attend garbage
            raise ValueError(
                "serving.kv_spill cannot be enabled together with "
                "serving.speculative: the draft pool has no spill tier, so "
                "a reloaded prefix would leave it without the prompt KV"
            )
        if sp.enabled:
            self.pool.spill = HostSpillTier(
                max(int(sp.max_host_mb * 1024 * 1024), 1)
            )
            self.pool.on_evict = self._spill_evicted
        self.draft_auto = None
        if self._spec_enabled:
            from automodel_tpu.generation.engine import (
                build_auto_from_model_section,
            )

            self.draft_auto = build_auto_from_model_section(
                spec.draft, auto.mesh_ctx, seed=self.gen_config.seed
            )
            draft_layout = kv_cache.layout_of(self.draft_auto.model)
            if draft_layout is None or kv_cache.recurrent_kinds(draft_layout, held=("kv",)):
                raise GenerationUnsupported(
                    "serving.speculative.draft model "
                    f"{type(self.draft_auto.model).__name__} must state a "
                    "cache layout of `kv` layers only (rollback is a length "
                    "decrement); it states "
                    f"{sorted({c.kind for c in draft_layout or ()}) or 'none'}"
                )
            self._draft_layout = draft_layout
            dv = int(self.draft_auto.model.config.vocab_size)
            tv = int(mcfg.vocab_size)
            if dv != tv:
                raise ValueError(
                    f"speculative draft vocab_size {dv} != target vocab_size "
                    f"{tv} — draft and target must share a vocabulary"
                )
            dmax = _model_max_positions(self.draft_auto.model.config)
            if dmax and self.config.max_seq_len + spec.k > dmax:
                # same loud refusal the target gets at line one of __init__:
                # a too-short draft context would silently extrapolate RoPE
                # past dmax and collapse the accept rate without ever erroring
                raise ValueError(
                    f"serving.max_seq_len={self.config.max_seq_len} + "
                    f"speculative.k={spec.k} exceeds the draft model's "
                    f"context limit {dmax}"
                )
        # launch-ahead (module docstring, step 4): a decode step's tokens stay
        # on the device for the next step to read; on a mesh they are pinned
        # replicated, where `_no_tokens` (_init_pool_arrays) is placed too
        self._token_sharding = (
            auto.mesh_ctx.replicated() if auto.mesh_ctx is not None else None
        )
        self._init_pool_arrays()
        constrain = auto.constrain

        def apply(params, ids, **kw):
            return self.model(params, ids, constrain=constrain, **kw)

        pk = dict(
            backend=self.decode_backend,
            block_size=self.config.block_size,
            compute_dtype=self._compute_dtype,
            interpret=self._interpret,
        )
        self._chunk = paged.build_chunk_prefill_fn(
            apply, self.config.prefill_chunk, self._compute_dtype,
            interpret=self._interpret, gather=self.decode_backend == "gather",
        )
        # the decode program always computes the sampled token's logprob
        # beside the token (one extra gather off logits already in hand);
        # whether it lands on the record is per-request (return_logprobs)
        self._decode = paged.build_paged_decode_fn(
            apply, self.gen_config.sampling,
            pad_id=self.gen_config.pad_token_id, with_logprobs=True,
            expert_units=self._expert_units_fn(),
            token_sharding=self._token_sharding, **pk,
        )
        if self._spec_enabled:
            d_model = self.draft_auto.model
            d_constrain = self.draft_auto.constrain

            def draft_apply(params, ids, **kw):
                return d_model(params, ids, constrain=d_constrain, **kw)

            d_pk = dict(pk, compute_dtype=d_model.backend.compute_jnp_dtype)
            self._draft_chunk = paged.build_chunk_prefill_fn(
                draft_apply, self.config.prefill_chunk,
                d_model.backend.compute_jnp_dtype,
            )
            self._propose = paged.build_draft_propose_fn(
                draft_apply, self.gen_config.sampling, spec.k,
                pad_id=self.gen_config.pad_token_id, **d_pk,
            )
            self._verify = paged.build_verify_fn(
                apply, self.gen_config.sampling, spec.k,
                pad_id=self.gen_config.pad_token_id, **pk,
            )
        self._base_key = sampling_key(self.gen_config.seed)
        self._eos = set(self.gen_config.eos_ids)
        # speculative accounting (accept-rate gauge, /stats)
        self.spec_proposed_total = 0
        self.spec_accepted_total = 0
        self.spec_rounds = 0

        B, NB = self.config.slots, self.config.table_blocks
        self._tables = np.zeros((B, NB), np.int32)
        self._lengths = np.zeros((B,), np.int32)
        self._cur = np.full((B,), self.gen_config.pad_token_id, np.int32)
        self._active = np.zeros((B,), bool)
        self._slots: list[Optional[_Slot]] = [None] * B
        # the decode step whose tokens the host has not read
        self._in_flight: Optional[_DecodeInFlight] = None
        self._queue: deque[_Queued] = deque()
        self._ids = itertools.count()
        self._step_counter = 0
        # step phases (docs/observability.md "Step phases and program
        # scopes", "Loop account"): each phase of step() is one
        # `with self._phase(...)`: a span on the profiler's clock, so a
        # device trace names what the host was doing in every idle gap, and
        # its perf_counter_ns duration in the loop's always-on account. The
        # iteration's integers accumulate there too and become the stats of
        # the `serve.counts` event that closes each iteration.
        self._account = loop_account.LoopAccount()
        self._phase = self._account.phase
        # live weight hot-swap (swap_weights): monotonic version tag
        # advertised on /stats + /metrics, and the validated replacement
        # tree staged until a step boundary with zero busy slots
        self.weights_version = 0
        self._pending_swap: Optional[Any] = None
        self.completed_total = 0  # stop/length completions
        self.failed_total = 0  # timeout/cancelled/stall/error terminations
        self.shed_total = 0
        self.quota_total = 0  # tenant token-bucket rejections
        self.timeout_total = 0
        # multi-tenant QoS (serving.qos): per-tenant token buckets (lazily
        # built from TenantConfig on first submission), per-(tier, tenant)
        # weighted-fair-queuing service accumulators (request token cost /
        # weight — reset never; relative order is all WFQ needs), and
        # cumulative per-tier / per-tenant terminal-outcome rollups for
        # /stats (the labeled /metrics families mirror them)
        self._req_buckets: dict[str, _TokenBucket] = {}
        self._decode_buckets: dict[str, _TokenBucket] = {}
        self._wfq_served: dict[tuple[str, str], float] = {}
        self.tier_counters: dict[str, dict[str, int]] = {
            t: {"completed": 0, "shed": 0, "timeout": 0, "quota": 0}
            for t in TIERS
        }
        self.tenant_counters: dict[str, dict[str, int]] = {}
        self.stall_total = 0  # watchdog-detected wedged steps
        self.error_total = 0  # recovered scheduler exceptions
        # drain state (begin_drain / drain_complete)
        self.draining = False
        self.drain_duration_s: Optional[float] = None
        self._drain_started: Optional[float] = None
        self._drain_deadline: Optional[float] = None
        # stall watchdog (start_watchdog): evidence handed over from the
        # watchdog thread, consumed at the next step boundary
        self._watchdog = None
        self._stall_evidence: Optional[dict] = None
        self._consecutive_rebuilds = 0
        self._exhaust_hold: Optional[tuple[list[int], int]] = None  # injection
        # disaggregated fleet: extracted prefill payloads awaiting pickup by
        # the /prefill handler (bounded — an abandoned payload must not pin
        # host memory forever), and the advertised KV-transfer listener port
        self._prefill_payloads: "OrderedDict[str, dict]" = OrderedDict()
        self.kv_transfer_port: Optional[int] = None  # set by the server front
        self.kv_injected_total = 0  # handoffs admitted into this pool
        self.first_decode_done = False  # readiness: first compiled decode
        self.last_step_t: Optional[float] = None  # monotonic, health age
        # elastic-fleet boot provenance: the server front stamps boot_t
        # (perf_counter at process start, BEFORE the model build — load
        # time is the whole point of the measurement) and boot_source;
        # note_ready() computes time_to_ready_s at first readiness
        self.boot_t: Optional[float] = None
        self.boot_source = "cold_hf"  # cold_hf | peer_warm_start
        self.time_to_ready_s: Optional[float] = None
        # /metrics exposition (telemetry/prometheus.py): histograms are
        # observed per completion (cheap, python dict ops); gauges + pool
        # counters sync at scrape time so the scheduler loop pays nothing
        from automodel_tpu.telemetry.prometheus import ServingMetrics

        self.metrics = ServingMetrics()
        # request tracing (telemetry/tracing.py): spans ride on_record like
        # every other telemetry record; every emitted span also observes
        # the /metrics per-stage histogram. All record timestamps derive
        # from ONE wall anchor + the monotonic clock — `ts` can never
        # disagree with the monotonic-difference durations it sits beside.
        self._clock = WallAnchor()
        self.tracer = tracer
        if tracer is not None:
            tracer.clock = self._clock  # one anchor per process, shared
            if tracer.observe is None:
                tracer.observe = self.metrics.observe_stage
        # cost attribution (telemetry/profiling/): when armed, the first
        # chunk-prefill/paged-decode call also records the program's
        # measured FLOPs/bytes (abstract host trace, one-time)
        self.collect_program_costs = False
        self.program_costs: dict = {}

    def _resolve_decode_backend(self) -> str:
        """fused (Pallas paged kernel) vs gather (XLA path):
        ``serving.decode_kernel`` when it names one, else the platform
        default (fused wherever the kernel can run — TPU or interpret
        mode — else gather)."""
        if self.config.decode_kernel in ("fused", "gather"):
            return self.config.decode_kernel
        from automodel_tpu.ops.platform_check import is_tpu_platform

        on_kernel_platform = self._interpret or is_tpu_platform(
            getattr(self.model.backend, "platform", None)
        )
        return "fused" if on_kernel_platform else "gather"

    def _init_pool_arrays(self) -> None:
        """(Re)create the HBM pool arrays — at construction, and on a
        rebuild after a stalled/failed program whose donated buffers can no
        longer be trusted (or were consumed by the failed call). With
        speculative decoding the draft model's parallel pool (same block
        geometry, its own layer/head dims) rebuilds in the same breath —
        a stall mid-verify must never leave half-trusted draft state."""
        # what the decode program is handed as "the previous step's tokens"
        # while no step is unread: placed as a step's own tokens come back, so
        # both are ONE compiled program
        no_tokens = np.full(
            (self.config.slots,), self.gen_config.pad_token_id, np.int32
        )
        self._no_tokens = (
            jnp.asarray(no_tokens) if self._token_sharding is None
            else jax.device_put(no_tokens, self._token_sharding)
        )
        self._pool = paged.place_pool(
            paged.layout_pool(
                self._layout, self.config.slots, self.config.num_blocks,
                self.config.block_size, dtype=self._compute_dtype,
                quantized=self._quantized, mesh_ctx=self.auto.mesh_ctx,
            ),
            self.auto.mesh_ctx,
        )
        if self._spec_enabled:
            self._draft_pool = paged.place_pool(
                paged.layout_pool(
                    self._draft_layout, self.config.slots,
                    self.config.num_blocks, self.config.block_size,
                    dtype=self.draft_auto.model.backend.compute_jnp_dtype,
                    quantized=self._quantized, mesh_ctx=self.auto.mesh_ctx,
                ),
                self.auto.mesh_ctx,
            )

    def release_pools(self) -> None:
        """Drop the engine's HBM pool arrays (target + draft). For callers
        that are DONE with this engine but keep the process alive (the
        benchmark harness frees the pool before its reference runs). The
        engine is unusable after."""
        self._pool = None
        self._in_flight = None
        if self._spec_enabled:
            self._draft_pool = None

    # -- stats ---------------------------------------------------------------
    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def busy_slots(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def state_slots(self) -> int:
        """Slots holding live recurrent state: a prompt's first chunk has
        run and the request has not left (0 for a K/V-only layout). The row
        dies with the slot: the next tenant's first chunk starts at position
        0, which reads zeros (serving/paged.py)."""
        if not self._stateful:
            return 0
        return sum(s is not None and s.prefill_pos > 0 for s in self._slots)

    @functools.cached_property
    def attn_pages_per_step(self) -> int:
        """Pool pages the fused decode kernel attends a grid step
        (ops/paged_attention.pages_per_step), from what the kernel itself
        sees: one TP shard's page of the pool as allocated (packed heads)
        and the query rows a KV head. A latent pool: its own kernel's picker
        (ops/latent_attention.pages_per_step), every head's queries against
        one page of rows."""
        if self._latent:
            block_size, width = self._pool.k.shape[-2:]
            return latent_attention.pages_per_step(
                block_size, width, self._layout[0].rank,
                int(self.model.config.num_heads), self._pool.k.dtype.itemsize,
            )
        k = self._pool.k
        values = k[0] if self._quantized else k
        block_size, nkv, width = values.shape[-3:]
        mesh_ctx = self.auto.mesh_ctx
        names = mesh_ctx and kv_cache.usable_axes(mesh_ctx, nkv, "tensor")
        shards = int(np.prod([mesh_ctx.mesh.shape[a] for a in names])) if names else 1
        return paged_attention.pages_per_step(
            block_size, nkv // shards, width,
            self._attn_query_rows * int(self.model.config.num_heads) // nkv,
            values.dtype.itemsize, self._quantized,
        )

    @functools.cached_property
    def _chunk_attn_blocks(self):
        """The blocks of a latent layer's chunk kernel
        (ops/latent_attention.chunk_blocks) from what the chunk program hands
        it, or None where a chunk's attention is not that kernel: a K/V
        layout, the gather backend, widths the kernel cannot tile."""
        if not self._latent or self.decode_backend != "fused":
            return None
        mcfg = self.model.config
        return latent_attention.chunk_blocks(
            self.config.prefill_chunk, int(mcfg.num_heads), int(mcfg.qk_nope_head_dim),
            int(mcfg.qk_rope_head_dim), int(mcfg.v_head_dim), self._tables.shape[1],
            self.config.block_size, interpret=self._interpret,
        )

    def _chunk_attn_steps(self, start: int) -> dict:
        """A latent layout's chunk at ``start``: its attention kernels' grid
        steps and those that attend a live key block, all layers, by the
        kernel's own arithmetic (0 and 0 where the chunk takes the loop)."""
        if not self._latent:
            return {}
        grid = live = 0
        if self._chunk_attn_blocks is not None:
            grid, live = latent_attention.chunk_grid_steps(
                [start], self.config.prefill_chunk, int(self.model.config.num_heads),
                self._chunk_attn_blocks,
            )
        layers = len(self._layout)
        return {"attn_grid_steps": layers * grid, "attn_live_steps": layers * live}

    def _expert_units_fn(self) -> Optional[Callable]:
        """``counts [L_moe, E]`` → the (live, grid) work units of the fused
        expert forward over a decode step's expert layers, or None where the
        step's experts do not run that kernel over the routed counts: a
        dense model, another experts backend, no Pallas here, or a mesh
        (each shard of the exchange plans its own rows)."""
        from automodel_tpu.ops import fused_expert_mlp
        from automodel_tpu.ops.grouped_matmul import _pallas_eligible
        from automodel_tpu.ops.platform_check import kernel_axes

        mcfg, backend, ctx = self.model.config, self.model.backend, self.auto.mesh_ctx
        moe = getattr(mcfg, "moe", None)
        if (
            moe is None
            or backend.experts not in ("ragged_fused", "a2a_fused")
            or kernel_axes(ctx) is not None
            or not _pallas_eligible(ctx.platform if ctx is not None else backend.platform)
        ):
            return None
        K = int(moe.num_experts_per_tok)
        D, I = int(mcfg.hidden_size), int(moe.moe_intermediate_size)
        if moe.held_experts is None:
            rows = self.config.slots * K
            return lambda counts: fused_expert_mlp.work_units(counts, rows, D, I)
        # a chip that holds a share of each layer's experts: the kernel's plan
        # is over the held experts' groups in the held picks' row buffer
        # (moe/experts.held_experts), and the picks that landed there are
        # counted beside its units
        lo, hi = moe.held_experts
        rows = -(-self.config.slots * min(K, hi - lo) // 8) * 8

        def held_units(counts):
            held = counts[..., lo:hi]
            return (*fused_expert_mlp.work_units(held, rows, D, I), held.sum())

        return held_units

    @property
    def _attn_query_rows(self) -> int:
        """Query rows a slot hands the decode kernel: the verify chunk of a
        speculative engine, else the one new token."""
        return self.config.speculative.k + 1 if self._spec_enabled else 1

    @property
    def pool_bytes(self) -> int:
        return self._pool.nbytes

    @property
    def draft_pool_bytes(self) -> int:
        return self._draft_pool.nbytes if self._spec_enabled else 0

    @property
    def spec_accept_rate(self) -> Optional[float]:
        """Engine-lifetime draft acceptance rate (None when speculative
        decoding is off or no round has run yet)."""
        if not self._spec_enabled or not self.spec_proposed_total:
            return None
        return self.spec_accepted_total / self.spec_proposed_total

    @property
    def watchdog(self):
        return self._watchdog

    @property
    def step_phase(self) -> Optional[str]:
        """The phase of ``step()`` in progress (None between iterations):
        the stall watchdog's evidence."""
        return self._account.step_phase

    def loop_account(self) -> dict:
        """Where the loop's thread has spent its time since the engine was
        built, and what it did: {"s": seconds a phase, "n": counts}
        (serving/loop_account.py; /stats and /metrics export it)."""
        return self._account.totals()

    @property
    def last_step_age_s(self) -> Optional[float]:
        return (
            time.monotonic() - self.last_step_t
            if self.last_step_t is not None else None
        )

    def idle(self) -> bool:
        return not self._queue and self._quiet

    @property
    def _quiet(self) -> bool:
        """No request holds a slot and no decode step is unread (a slot
        that ended by a stop id leaves one discarded row in flight)."""
        return self.busy_slots == 0 and self._in_flight is None

    def note_ready(self) -> None:
        """Stamp ``time_to_ready_s`` at this replica's FIRST readiness
        (idempotent; called after warmup and from the /readyz handler so
        warmup-disabled servers still stamp on their first true probe).
        Emits one ``replica_ready`` record — the elastic fleet's
        warm-vs-cold A/B number, labeled with the boot source taken."""
        if (
            self.time_to_ready_s is not None
            or not self.first_decode_done
            or self.boot_t is None
        ):
            return
        self.time_to_ready_s = time.perf_counter() - self.boot_t
        logger.info(
            "replica ready in %.3fs (boot source: %s)",
            self.time_to_ready_s, self.boot_source,
        )
        if self.on_record is not None:
            self.on_record({
                "event": "replica_ready",
                "ts": self._wall_ts(),
                "boot_source": self.boot_source,
                "time_to_ready_s": round(self.time_to_ready_s, 6),
            })

    # -- stall watchdog -------------------------------------------------------
    def start_watchdog(self, flight_recorder: Any = None,
                       metric_logger: Any = None,
                       stacks_path: Optional[str] = None):
        """Arm the scheduler-level stall watchdog (serving fronts call this;
        batch ``run()`` drains don't need a thread). → the EngineWatchdog,
        or None when serving.watchdog.enabled is false."""
        c = self.config.watchdog
        if not c.enabled or self._watchdog is not None:
            return self._watchdog
        from automodel_tpu.resilience.watchdog import EngineWatchdog, WatchdogConfig

        wcfg = WatchdogConfig(
            enabled=True, multiplier=c.multiplier,
            min_deadline_s=c.min_deadline_s, max_deadline_s=c.max_deadline_s,
            ema_alpha=c.ema_alpha, compile_grace_s=c.compile_grace_s,
            poll_interval_s=c.poll_interval_s,
            stacks_path=c.stacks_path or stacks_path,
            exit_on_hang=False,
        )
        self._watchdog = EngineWatchdog(
            wcfg, flight_recorder=flight_recorder, metric_logger=metric_logger,
            on_hang=self._note_stall,
            # read from the watchdog thread while the scheduler thread sits
            # in the wedged call: which phase of step() that call is
            evidence=lambda: {"step_phase": self.step_phase},
        )
        self._watchdog.start()
        return self._watchdog

    def stop_watchdog(self) -> None:
        wd, self._watchdog = self._watchdog, None
        if wd is not None:
            wd.stop()

    def touch_watchdog(self) -> None:
        """Idle heartbeat: the serving loop calls this when there is no work
        so an empty server never reads as a wedged one."""
        if self._watchdog is not None:
            self._watchdog.touch()

    def _note_stall(self, rec: dict) -> None:
        # called from the WATCHDOG thread while the scheduler thread is
        # blocked inside the wedged call; consumed at the next step boundary
        self._stall_evidence = dict(rec)

    # -- drain ----------------------------------------------------------------
    def begin_drain(self) -> None:
        """Flip to draining: new submissions raise ``EngineDraining``, the
        queue is flushed with retriable rejections at the next step, and
        in-flight requests get ``drain.grace_s`` to finish before they are
        cancelled. Idempotent."""
        if self.draining:
            return
        self.draining = True
        self._drain_started = time.perf_counter()
        self._drain_deadline = self._drain_started + max(
            self.config.drain.grace_s, 0.0
        )
        logger.warning(
            "serving drain started: %d queued rejected retriable, %d in "
            "flight, grace %.1fs",
            self.queue_depth, self.busy_slots, self.config.drain.grace_s,
        )

    def drain_complete(self) -> bool:
        """True once every in-flight request reached a terminal state after
        ``begin_drain``. Stamps ``drain_duration_s`` (and the /metrics
        gauge) on first observation."""
        done = self.draining and self.idle()
        if done and self.drain_duration_s is None:
            self.drain_duration_s = time.perf_counter() - self._drain_started
            logger.warning(
                "serving drain complete in %.3fs", self.drain_duration_s
            )
        return done

    # -- live weight hot-swap (docs/posttrain.md) -----------------------------
    def swap_weights(self, params: Any) -> int:
        """Stage a full replacement of the policy weights without a restart.

        The incoming tree is validated against the CURRENT tree's
        param-tree signature (path/shape/dtype set — the same guard
        warm-start and checkpoint restore use) before a single leaf is
        touched; a mismatch raises ``ValueError`` loudly with the old
        params bit-intact. A valid tree is device_put to the live leaves'
        shardings and staged; the scheduler applies it at a step boundary
        with ZERO busy slots and no decode step unread (``_quiet``), so
        every in-flight request finishes under the weights it started with,
        and new admissions hold (the queue keeps absorbing — nothing drops)
        until the swap lands. If no request is in flight the swap applies
        immediately. → the
        ``weights_version`` the engine advertises once the swap is live.

        Same shapes/dtypes means the already-compiled prefill/decode
        programs are reused as-is — a swap never recompiles."""
        from automodel_tpu.checkpoint.checkpointer import param_tree_signature

        cur_sig = param_tree_signature(self.auto.params)
        new_sig = param_tree_signature(params)
        if new_sig["digest"] != cur_sig["digest"]:
            cur_e, new_e = set(cur_sig["entries"]), set(new_sig["entries"])
            detail = (
                f"current digest {cur_sig['digest']} != incoming "
                f"{new_sig['digest']}; missing {sorted(cur_e - new_e)[:4]}, "
                f"unexpected {sorted(new_e - cur_e)[:4]}"
            )
            self._emit_event({
                "event": "weight_swap", "ok": False,
                "weights_version": self.weights_version,
                "detail": detail, "ts": self._wall_ts(),
            })
            raise ValueError(
                f"swap_weights refused: param tree signature mismatch "
                f"({detail}) — serving weights unchanged"
            )
        incoming = {
            _tree_path_name(path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
        }
        cur_leaves, treedef = jax.tree_util.tree_flatten_with_path(
            self.auto.params
        )
        staged = jax.tree_util.tree_unflatten(
            treedef,
            [
                jax.device_put(incoming[_tree_path_name(path)], leaf.sharding)
                for path, leaf in cur_leaves
            ],
        )
        self._pending_swap = staged
        target = self.weights_version + 1
        if self._quiet:
            self._apply_pending_swap()
        return target

    def _apply_pending_swap(self) -> None:
        """Flip the staged tree in (scheduler thread / caller under the
        serving lock): one attribute assignment — the next tick's fresh
        ``self.auto.params`` read picks it up."""
        if self._pending_swap is None:
            return
        self.auto.params = self._pending_swap
        self._pending_swap = None
        self.weights_version += 1
        # every cached prefix (and its host-spilled copies) holds K/V
        # computed under the OLD policy — serving it to a request running
        # the new weights would silently mix two policies in one sequence
        self.pool.clear_prefix_cache()
        logger.info(
            "weights hot-swapped: now serving weights_version=%d",
            self.weights_version,
        )
        self._emit_event({
            "event": "weight_swap", "ok": True,
            "weights_version": self.weights_version, "ts": self._wall_ts(),
        })

    def _emit_event(self, rec: dict) -> None:
        """on_record for non-request events (no completion_reason, so the
        per-request metrics observers are wrong for these)."""
        if self.on_record is not None:
            try:
                self.on_record(dict(rec))
            except Exception:  # telemetry must never break serving
                pass

    # -- submission -----------------------------------------------------------
    def submit(
        self,
        prompt_ids: Sequence[int],
        request_id: Optional[str] = None,
        max_new_tokens: Optional[int] = None,
        t_submit: Optional[float] = None,
        deadline_s: Optional[float] = None,
        max_queue_wait_s: Optional[float] = None,
        prefill_only: bool = False,
        trace: Optional[SpanContext] = None,
        kv_peer: Optional[dict] = None,
        return_logprobs: bool = False,
        tenant: Optional[str] = None,
        tier: Optional[str] = None,
        _payload: Optional[dict] = None,
    ) -> str:
        prompt = [int(t) for t in prompt_ids]
        if not prompt:
            raise ValueError("empty prompt (every request needs >= 1 token)")
        qos = self.config.qos
        tenant = str(tenant) if tenant is not None else qos.default_tenant
        tier = str(tier) if tier is not None else qos.tier_for(tenant)
        tier_idx = tier_index(tier)  # raises 400-ably on a typo
        if qos.enabled:
            from automodel_tpu.telemetry.prometheus import _LABEL_VALUE_OK

            if not _LABEL_VALUE_OK.match(tenant):
                raise ValueError(
                    f"tenant {tenant!r} is not a valid metrics label value "
                    "(want [a-zA-Z0-9_.+-]+)"
                )
        if return_logprobs and self._spec_enabled:
            # speculative commits draft+correction tokens whose per-token
            # behavior logprobs are not the target's sampling logprobs —
            # refuse rather than report numbers a ratio can't trust
            raise GenerationUnsupported(
                "return_logprobs is not supported on a speculative engine: "
                "committed tokens mix draft proposals and verify "
                "corrections, so no single behavior-policy logprob exists"
            )
        max_new = (
            self.gen_config.max_new_tokens
            if max_new_tokens is None
            else int(max_new_tokens)  # explicit 0 must hit the guard below
        )
        if max_new < 1:
            raise ValueError(f"max_new_tokens={max_new}")
        # a prefill-only request never decodes: its budget is the prompt
        # alone (positions 0..p-1), and its cap check ignores max_new
        total = len(prompt) if prefill_only else len(prompt) + max_new
        cap = min(
            self.config.max_seq_len,
            self._max_positions or self.config.max_seq_len,
        )
        if total > cap:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({0 if prefill_only else max_new}) = {total} exceeds the "
                f"serving limit {cap}"
            )
        need = blocks_needed(
            total, self.config.block_size,
            0 if prefill_only else self.config.spec_overhang,
        )
        if need > self.pool.usable_blocks:
            raise ValueError(
                f"request needs {need} blocks but the pool only has "
                f"{self.pool.usable_blocks} — raise serving.num_blocks"
            )
        now = time.perf_counter() if t_submit is None else t_submit
        rid = request_id if request_id is not None else f"req-{next(self._ids)}"
        lim = self.config.limits
        ddl = lim.deadline_s if deadline_s is None else float(deadline_s)
        qw = (
            lim.max_queue_wait_s
            if max_queue_wait_s is None else float(max_queue_wait_s)
        )
        # the engine's ROOT span for this request: child of the propagated
        # context (a router forward span) when one came in, a freshly
        # minted trace otherwise (the engine front IS the entry point for
        # direct requests). Unsampled contexts flow through but emit nothing.
        root = self.tracer.start(parent=trace) if self.tracer is not None else None
        q = _Queued(
            rid=rid, prompt=prompt, max_new=max_new, t_submit=now,
            deadline_at=now + ddl if ddl and ddl > 0 else None,
            queue_deadline_at=now + qw if qw and qw > 0 else None,
            prefill_only=prefill_only, payload=_payload, trace=root,
            kv_peer=kv_peer if kv_peer else None,
            return_logprobs=return_logprobs,
            tenant=tenant, tier=tier, tier_idx=tier_idx,
        )
        if self.draining:
            # no terminal record here (mirror of the shed seam): the
            # rejection is returned to the client directly, and a client
            # honoring Retry-After would otherwise inflate failed_total and
            # the JSONL with one synthetic record per retry attempt.
            # ACCEPTED-then-drained requests do get records (step's queue
            # flush) — that is the no-silent-drop contract's scope. The
            # draining check comes BEFORE any priority handling: no tier,
            # however high, jumps a drain (tests/test_qos.py pins it).
            raise EngineDraining(
                "server is draining — retry against another replica"
            )
        if qos.enabled:
            # token-bucket quotas, charged up front: one admission token and
            # the request's whole decode budget (max_new) — a worst-case
            # reservation, so a flooding tenant is bounded by what it COULD
            # decode, not by what its requests happen to generate
            tc = qos.tenant(tenant)
            rb = self._req_buckets.get(tenant)
            if rb is None:
                rb = self._req_buckets[tenant] = _TokenBucket(
                    tc.requests_per_s, tc.burst_s
                )
            db = self._decode_buckets.get(tenant)
            if db is None:
                db = self._decode_buckets[tenant] = _TokenBucket(
                    tc.decode_tokens_per_s, tc.burst_s
                )
            if not rb.take(1.0, now):
                raise QuotaExceeded(
                    f"tenant {tenant!r} over requests_per_s="
                    f"{tc.requests_per_s} quota",
                    tenant=tenant, tier=tier,
                )
            if not db.take(float(0 if prefill_only else max_new), now):
                raise QuotaExceeded(
                    f"tenant {tenant!r} over decode_tokens_per_s="
                    f"{tc.decode_tokens_per_s} quota",
                    tenant=tenant, tier=tier,
                )
        if len(self._queue) >= self.config.max_queue:
            if not qos.enabled:
                raise QueueFull(
                    "admission queue at serving.max_queue="
                    f"{self.config.max_queue}"
                )
            # overload sheds strictly lowest-tier-first: evict the worst
            # queued entry (lowest EFFECTIVE tier — aging promotion counts —
            # latest-submitted among those) when it ranks strictly below the
            # newcomer; otherwise the newcomer IS the lowest tier and is
            # refused. The evicted entry was accepted earlier, so the
            # no-silent-drop contract owes it a terminal `shed` record here.
            victim = self._shed_victim(tier_idx, now)
            if victim is None:
                raise QueueFull(
                    "admission queue at serving.max_queue="
                    f"{self.config.max_queue} (tier {tier!r} sheds first)"
                )
            self._queue.remove(victim)
            self.shed_total += 1
            self._rejection_record(victim, "shed")
        self._queue.append(q)
        return rid

    def _effective_tier(self, q: _Queued, now: float) -> int:
        """Tier rank used for ordering and shedding: the anti-starvation
        aging bound promotes work queued past ``qos.aging_s`` to the top
        tier, so a busy high tier can delay low-tier work but never starve
        it (and an aged entry is never the preferred shed victim)."""
        if now - q.t_submit >= self.config.qos.aging_s:
            return 0
        return q.tier_idx

    def _shed_victim(
        self, newcomer_tier_idx: int, now: float
    ) -> Optional[_Queued]:
        """The queued entry a full queue evicts to make room for a
        strictly-higher-tier newcomer: lowest effective tier, latest
        submission among ties (shedding the newest low-tier entry keeps
        the oldest closest to its aging promotion). None when nothing
        queued ranks strictly below the newcomer."""
        victim = None
        victim_key = None
        for q in self._queue:
            key = (self._effective_tier(q, now), q.t_submit)
            if victim_key is None or key > victim_key:
                victim, victim_key = q, key
        if victim is None or victim_key[0] <= newcomer_tier_idx:
            return None
        return victim

    # -- disaggregated prefill/decode (serving/fleet/) ------------------------
    def kv_geometry(self) -> dict:
        """The pool geometry a KV-transfer peer must match exactly — the
        handshake header both sides validate before any block row moves."""
        paged._refuse_latent(self._pool, "kv_geometry")
        L, _, BS, Nkv, H = self._pool.values_shape
        return {
            "layers": int(L),
            "block_size": int(BS),
            "num_kv_heads": int(Nkv),
            "head_dim": int(H),
            "kv_cache_dtype": self.config.kv_cache_dtype,
        }

    def kv_frame_bytes_bound(self) -> int:
        """Upper bound on a legitimate KV-transfer frame into this pool —
        the WHOLE pool's bytes (k + v, scales included). The transfer
        listener refuses anything larger before allocating."""
        total = 0
        for side in (self._pool.k, self._pool.v):
            arrs = side if isinstance(side, tuple) else (side,)
            for a in arrs:
                total += int(np.prod(a.shape)) * a.dtype.itemsize
        return total

    def hot_prefixes(self) -> list[int]:
        """Cached chain heads advertised via /stats for the fleet router's
        prefix-affinity placement."""
        return self.pool.cached_chain_hashes(self.config.hot_prefix_advertise)

    # -- hierarchical KV cache (docs/serving.md "Hierarchical KV cache") ------
    def _spill_evicted(self, evicted: list) -> None:
        """BlockPool eviction hook: copy the evicted prefix blocks' rows
        device→host into the spill tier, keyed by chain hash. Runs inside
        ``allocate()`` — strictly before the caller can overwrite the
        blocks it was handed. ONE gather + device sync per eviction event
        (the host round trip, not the bytes, dominates on small pools),
        padded to a power-of-two block count so the arbitrary batch sizes
        churn cost at most log2(pool) compiled programs."""
        tier = self.pool.spill
        if tier is None:
            return
        bids = [bid for _, bid in evicted]
        pad = paged.bucket_blocks(len(bids))
        k, v = paged.extract_blocks(
            self._pool, bids + [bids[-1]] * (pad - len(bids))
        )
        payloads = paged.split_kv_blocks({"k": k, "v": v})[: len(bids)]
        for (h, _), payload in zip(evicted, payloads):
            if tier.put(h, payload, paged.kv_nbytes(payload)):
                self.pool.counters["spilled_blocks"] += 1

    def fetch_prefix_blocks(self, chain_hashes: Sequence[int]):
        """Serve a peer replica's ``/kv_fetch``: the longest leading run of
        ``chain_hashes`` this replica can source — resident prefix-cache
        blocks extract device→host, spilled blocks come straight from the
        host tier. → ``(n, kv dict | None)``. Caller holds the scheduler
        lock (the server front wraps this in ``loop.lock``)."""
        tier = self.pool.spill
        pieces: list[dict] = []
        for h in chain_hashes:
            bid = self.pool.cached_block(int(h))
            if bid is not None:
                k, v = paged.extract_blocks(self._pool, [bid])
                pieces.append({"k": k, "v": v})
                continue
            p = tier.get(int(h)) if tier is not None else None
            if p is None:
                break
            pieces.append(p)
        if not pieces:
            return 0, None
        return len(pieces), paged.concat_kv_blocks(pieces)

    # -- elastic fleet (docs/serving.md "Elastic fleet") ----------------------
    def export_hot_blocks(self, limit: Optional[int] = None):
        """A retiring replica's migration export: up to ``limit`` hot
        prefix blocks in EVICTION-DISTANCE order (pinned, then parked LRU
        MRU-first, then spill-tier MRU-first — exactly the
        ``cached_chain_hashes`` advertisement order, so the blocks most
        worth keeping warm ship first if the deadline cuts the transfer
        short). → ``(chain_hashes, kv | None)``. Caller holds the
        scheduler lock."""
        hashes = self.pool.cached_chain_hashes(
            self.config.hot_prefix_advertise if limit is None else int(limit)
        )
        tier = self.pool.spill
        out: list[int] = []
        pieces: list[dict] = []
        for h in hashes:
            bid = self.pool.cached_block(int(h))
            if bid is not None:
                k, v = paged.extract_blocks(self._pool, [bid])
                pieces.append({"k": k, "v": v})
                out.append(int(h))
                continue
            p = tier.get(int(h)) if tier is not None else None
            if p is not None:
                pieces.append(p)
                out.append(int(h))
        if not pieces:
            return [], None
        return out, paged.concat_kv_blocks(pieces)

    def receive_migrated_blocks(self, chain_hashes: Sequence[int], kv: dict) -> int:
        """A survivor's migration sink (the AKV1 ``kv_push`` handler):
        park the shipped block rows in the HOST SPILL TIER keyed by their
        chain hashes — the next admission sharing the prefix reloads them
        through the normal hierarchy seam, and ``cached_chain_hashes``
        re-advertises them so router affinity follows the heat. Blocks
        this replica already holds (resident or spilled) are skipped.
        → the number of blocks accepted. Requires ``kv_spill.enabled``
        (no tier → 0 accepted, a loud refusal upstream). Caller holds the
        scheduler lock."""
        tier = self.pool.spill
        if tier is None:
            return 0
        payloads = paged.split_kv_blocks(kv)
        accepted = 0
        for h, payload in zip(chain_hashes, payloads):
            h = int(h)
            if self.pool.cached_block(h) is not None or tier.get(h) is not None:
                continue
            if tier.put(h, payload, paged.kv_nbytes(payload)):
                # the spill ledger counts tier entries however they arrived
                # (eviction or migration) — check_invariants pins
                # spilled_blocks == spill_puts
                self.pool.counters["spilled_blocks"] += 1
                accepted += 1
        return accepted

    def _resolve_hierarchy(
        self, q: _Queued, hits: list, hit_tokens: int, fresh: list
    ) -> int:
        """Admission-time resolution of a prefix match that ends short of
        the prompt's full chain: reload spilled blocks from the host tier,
        then (router-hinted) fetch the remainder from the peer that
        advertises it, and scatter everything into the leading ``fresh``
        blocks through ``inject_blocks`` — the exact seam disagg handoff
        uses, so greedy output is bit-identical to recompute.
        Every failure degrades to recompute; nothing here can fail the
        request short of the injection itself. → the updated hit_tokens
        (prefill resumes past everything served from any tier)."""
        sp = self.config.kv_spill
        tier = self.pool.spill
        if not sp.enabled or tier is None or not fresh:
            return hit_tokens
        bs = self.config.block_size
        chain = prompt_chain(q.prompt, bs)
        k = len(hits)
        if k >= len(chain):
            return hit_tokens
        t0 = time.perf_counter()
        pieces: list[dict] = []
        reloaded = 0
        for h in chain[k:]:
            if reloaded >= len(fresh):
                break
            p = tier.get(h)
            if p is None:
                break
            pieces.append(p)
            reloaded += 1
        fetched = 0
        want = chain[k + reloaded :]
        if (
            want
            and sp.peer_fetch
            and q.kv_peer is not None
            and k + reloaded + len(want) <= k + len(fresh)
        ):
            timeout = sp.fetch_timeout_s
            if q.deadline_at is not None:
                timeout = min(timeout, q.deadline_at - time.perf_counter())
            if timeout > 0:
                tf0 = time.perf_counter()
                try:
                    from automodel_tpu.serving.fleet.kv_transfer import fetch_kv

                    n, kv = fetch_kv(
                        (str(q.kv_peer["host"]), int(q.kv_peer["port"])),
                        want, self.kv_geometry(), timeout_s=timeout,
                    )
                    if n and kv is not None:
                        pieces.append(kv)
                        fetched = n
                        self.pool.counters["peer_fetch_blocks"] += n
                    self.pool.counters["peer_fetches"] += 1
                    self._child_span(
                        q.trace, "kv_fetch", tf0,
                        request_id=q.rid, blocks=fetched,
                    )
                except Exception as e:
                    # the fallback ladder's last rung: any fetch failure —
                    # refused, timed out, died mid-stream — recomputes
                    # locally within the request's original deadline
                    self.pool.counters["peer_fetch_failures"] += 1
                    logger.warning(
                        "peer KV fetch from %s failed (%s: %s); "
                        "recomputing locally",
                        q.kv_peer, type(e).__name__, e,
                    )
                    self._child_span(
                        q.trace, "kv_fetch", tf0,
                        request_id=q.rid, blocks=0, error=type(e).__name__,
                    )
        total = reloaded + fetched
        if not total:
            return hit_tokens
        from automodel_tpu.resilience.fault_injection import active_injector

        inj = active_injector()
        if inj is not None:
            inj.maybe_trace_delay("kv_inject")
        # ONE scatter, padded to a power-of-two block count aimed at the
        # scratch block: reload/fetch run lengths are arbitrary, and an
        # exact-length inject compiles per distinct length (the handoff
        # path's documented compile churn) — bucketing bounds a reload's
        # worst-case TTFT to log2(pool) one-time compiles
        pad = paged.bucket_blocks(total)
        table = list(fresh[:total]) + [0] * (pad - total)
        self._pool = paged.inject_blocks(
            self._pool, np.asarray(table, np.int32),
            paged.pad_kv_blocks(paged.concat_kv_blocks(pieces), pad),
        )
        if reloaded:
            self.pool.counters["spill_reloads"] += 1
            self.pool.counters["spill_reloaded_blocks"] += reloaded
        self._child_span(
            q.trace, "kv_reload", t0, request_id=q.rid,
            blocks=total, reloaded=reloaded, fetched=fetched,
        )
        return hit_tokens + total * bs

    def pop_prefill_payload(self, request_id: str) -> dict:
        """Claim the extracted KV payload of a completed prefill-only
        request (the /prefill handler ships it to the decode replica)."""
        try:
            return self._prefill_payloads.pop(request_id)
        except KeyError:
            raise KeyError(
                f"no prefill payload for {request_id!r} — the request did "
                "not complete as 'prefilled', or the payload was evicted"
            )

    def _stash_prefill_payload(self, rid: str, payload: dict) -> None:
        self._prefill_payloads[rid] = payload
        # bounded: an abandoned payload (router died between /prefill and
        # pickup) must not pin host copies of prompt KV forever
        while len(self._prefill_payloads) > max(
            int(self.config.kv_transfer.max_pending), 1
        ):
            dropped, _ = self._prefill_payloads.popitem(last=False)
            logger.warning("evicting unclaimed prefill payload %s", dropped)

    def submit_prefilled(
        self,
        prompt_ids: Sequence[int],
        first_token: int,
        kv: dict,
        request_id: Optional[str] = None,
        max_new_tokens: Optional[int] = None,
        deadline_s: Optional[float] = None,
        max_queue_wait_s: Optional[float] = None,
        trace: Optional[SpanContext] = None,
    ) -> str:
        """Enqueue a request whose prompt KV was computed on a PREFILL
        replica: admission allocates the normal whole budget, scatters the
        shipped block rows into this pool through the ``paged_write_targets``
        seam, and the slot starts directly in decode with ``first_token``
        (sampled by the prefill replica from the prompt's last logits)
        already committed. ``kv`` is ``{"k": rows, "v": rows}`` with each
        side ``[L, nb, BS, Nkv, H]`` (or ``(int8 values, fp32 scales)``
        pairs for int8 pools), ``nb = ceil(len(prompt)/block_size)``."""
        if self._spec_enabled:
            raise GenerationUnsupported(
                "disaggregated KV handoff into a speculative engine is not "
                "supported: the draft model's parallel pool would miss the "
                "prompt KV and proposals would attend garbage"
            )
        if self._stateful:
            raise GenerationUnsupported(
                "KV handoff into an engine whose layout keeps recurrent state "
                "is not supported: the shipped block rows are K/V only, the "
                "prompt's recurrent state would be missing"
            )
        prompt = [int(t) for t in prompt_ids]
        self._validate_kv_payload(prompt, kv)
        payload = {"first_token": int(first_token), "kv": kv}
        return self.submit(
            prompt, request_id=request_id, max_new_tokens=max_new_tokens,
            deadline_s=deadline_s, max_queue_wait_s=max_queue_wait_s,
            trace=trace, _payload=payload,
        )

    def _validate_kv_payload(self, prompt: list[int], kv: dict) -> None:
        geom = self.kv_geometry()
        nb = blocks_needed(len(prompt), self.config.block_size)
        want = (
            geom["layers"], nb, geom["block_size"], geom["num_kv_heads"],
            geom["head_dim"],
        )
        for side in ("k", "v"):
            rows = kv.get(side)
            if rows is None:
                raise ValueError(f"KV payload missing side {side!r}")
            quantized = isinstance(rows, tuple)
            if quantized != self._quantized:
                raise ValueError(
                    f"KV payload side {side!r} is "
                    f"{'int8' if quantized else 'raw'} but this pool is "
                    f"kv_cache_dtype={self.config.kv_cache_dtype}"
                )
            shape = tuple((rows[0] if quantized else rows).shape)
            if shape != want:
                raise ValueError(
                    f"KV payload side {side!r} shape {shape} != expected "
                    f"{want} (layers, ceil(prompt/block_size), block_size, "
                    "num_kv_heads, head_dim)"
                )
            if quantized and tuple(rows[1].shape) != want[:-1]:
                raise ValueError(
                    f"KV payload side {side!r} scales shape "
                    f"{tuple(rows[1].shape)} != expected {want[:-1]}"
                )

    def record_shed(
        self,
        request_id: Optional[str] = None,
        prompt_ids: Optional[Sequence[int]] = None,
        tenant: Optional[str] = None,
        tier: Optional[str] = None,
    ) -> dict:
        """Account an ACTUAL shed — the caller gave up on a ``QueueFull``
        and returned the overload signal to the client. Kept out of
        ``submit`` so a front that absorbs backpressure by retrying (the
        stdin batch mode) doesn't inflate ``requests_shed_total`` with
        retry attempts. ``tenant``/``tier`` label the record (and the
        per-tier /metrics families) with who was shed."""
        self.shed_total += 1
        qos = self.config.qos
        tenant = tenant if tenant is not None else qos.default_tenant
        tier = tier if tier is not None else qos.tier_for(tenant)
        q = _Queued(
            rid=request_id if request_id is not None else f"req-{next(self._ids)}",
            prompt=[int(t) for t in (prompt_ids or [])],
            max_new=0, t_submit=time.perf_counter(),
            tenant=tenant, tier=tier, tier_idx=tier_index(tier),
        )
        return self._rejection_record(q, "shed")

    def record_quota(
        self,
        request_id: Optional[str] = None,
        prompt_ids: Optional[Sequence[int]] = None,
        tenant: Optional[str] = None,
        tier: Optional[str] = None,
    ) -> dict:
        """Account a quota rejection the caller returned to the client —
        the ``record_shed`` seam's twin for ``QuotaExceeded``: ``submit``
        raises without a record so retrying fronts don't inflate the
        count; the front that actually answers the client calls this
        exactly once."""
        self.quota_total += 1
        qos = self.config.qos
        tenant = tenant if tenant is not None else qos.default_tenant
        tier = tier if tier is not None else qos.tier_for(tenant)
        q = _Queued(
            rid=request_id if request_id is not None else f"req-{next(self._ids)}",
            prompt=[int(t) for t in (prompt_ids or [])],
            max_new=0, t_submit=time.perf_counter(),
            tenant=tenant, tier=tier, tier_idx=tier_index(tier),
        )
        return self._rejection_record(q, "quota")

    # -- terminal records -----------------------------------------------------
    def _wall_ts(self) -> float:
        """Record timestamp: the process wall anchor + the monotonic clock.
        Never raw ``time.time()`` — a wall step mid-request would otherwise
        put a ``ts`` beside monotonic-difference durations it contradicts
        (the mixed-clock bug report --strict now lints for)."""
        return round(self._clock.wall(), 6)

    def _child_span(
        self,
        root: Optional[SpanContext],
        stage: str,
        t0: float,
        t1: Optional[float] = None,
        **attrs: Any,
    ) -> None:
        if self.tracer is not None and self.tracer.active(root):
            self.tracer.child(root, stage, t0, t1, **attrs)

    def _root_span(
        self,
        root: Optional[SpanContext],
        t0: float,
        t1: Optional[float] = None,
        **attrs: Any,
    ) -> None:
        if self.tracer is not None and self.tracer.active(root):
            self.tracer.record(root, "serve", t0, t1, **attrs)

    def _rejection_record(
        self, q: _Queued, reason: str, detail: Optional[str] = None
    ) -> dict:
        """Terminal record for a request that never reached a slot (shed /
        draining / queue timeout / admission failure)."""
        now = time.perf_counter()
        self.failed_total += 1
        if reason == "timeout":
            self.timeout_total += 1
        rec = {
            "event": "serve_request",
            "request_id": q.rid,
            "tokens": [],
            "n_generated": 0,
            "prompt_tokens": len(q.prompt),
            "completion_reason": reason,
            "retriable": reason in _RETRIABLE_REASONS,
            "tenant": q.tenant,
            "tier": q.tier,
            "queue_s": now - q.t_submit,
            "queue_depth": self.queue_depth,
            "ts": self._wall_ts(),
        }
        if detail:
            rec["detail"] = detail
        # drain/timeout/shed paths leave spans too: the whole life of this
        # request was the queue, and the root says why it ended
        self._child_span(
            q.trace, "queue", q.t_submit, now, request_id=q.rid
        )
        self._root_span(
            q.trace, q.t_submit, now,
            request_id=q.rid, completion_reason=reason,
        )
        self._emit(rec)
        return rec

    def _terminate(
        self, b: int, reason: str, detail: Optional[str] = None
    ) -> dict:
        """Free slot ``b`` and produce its one terminal record. ``reason``
        "stop"/"length" is a completion; anything else is a failure whose
        blocks must still come back (the leak-audit contract)."""
        slot = self._slots[b]
        end = self._account.snapshot()
        now = end.t
        gen = slot.generated or []
        self.pool.free(slot.blocks)
        self._slots[b] = None
        self._tables[b] = 0
        self._lengths[b] = 0
        self._active[b] = False
        self._cur[b] = self.gen_config.pad_token_id
        completed = reason in _COMPLETED_REASONS
        if completed:
            self.completed_total += 1
        else:
            self.failed_total += 1
            if reason == "timeout":
                self.timeout_total += 1
        rec = {
            "event": "serve_request",
            "request_id": slot.request_id,
            "tokens": list(gen),
            "n_generated": len(gen),
            "prompt_tokens": len(slot.prompt),
            "prefix_hit_tokens": slot.hit_tokens,
            "completion_reason": reason,
            "retriable": reason in _RETRIABLE_REASONS,
            "tenant": slot.tenant,
            "tier": slot.tier,
            "queue_s": slot.t_admit - slot.t_submit,
            "queue_depth": self.queue_depth,
            "block_occupancy": round(self.pool.occupancy(), 4),
            "ts": self._wall_ts(),
        }
        if slot.t_first is not None:
            decode_s = now - slot.t_first
            rec["ttft_s"] = slot.t_first - slot.t_submit
            # the first token is charged to ttft, like the single-wave engine
            rec["decode_tps"] = (
                (len(gen) - 1) / decode_s if decode_s > 0 and len(gen) > 1
                else 0.0
            )
        if slot.logprobs is not None:
            rec["logprobs"] = [round(lp, 6) for lp in slot.logprobs]
        if self._spec_enabled and slot.spec_proposed:
            rec["spec_proposed"] = slot.spec_proposed
            rec["spec_accepted"] = slot.spec_accepted
            rec["spec_accept_rate"] = round(
                slot.spec_accepted / slot.spec_proposed, 4
            )
        if detail:
            rec["detail"] = detail
        # tracing: the decode stage is the window from first token to
        # terminal (one span per request, attrs carry the volume); the root
        # span covers submit→terminal and names how it ended — including
        # the cancel/stall/drain paths, which land here like completions
        if slot.decoding and slot.t_first is not None:
            # what the loop did during this request's token gaps
            rec["decode_account"] = loop_account.between(slot.account_first, end)
            self._child_span(
                slot.trace, "decode", slot.t_first, now,
                request_id=slot.request_id, tokens=max(len(gen) - 1, 0),
            )
        self._root_span(
            slot.trace, slot.t_submit, now,
            request_id=slot.request_id, completion_reason=reason,
            n_generated=len(gen), prompt_tokens=len(slot.prompt),
        )
        self._emit(rec)
        return rec

    def _emit(self, rec: dict) -> None:
        try:
            if rec.get("completion_reason") in _COMPLETED_REASONS:
                self.metrics.observe_request(rec)
            else:
                self.metrics.observe_failure(rec.get("completion_reason", ""))
            self.metrics.observe_qos(rec)
            self._note_qos(rec)
        except Exception:  # telemetry must never break serving
            pass
        if self.on_record is not None:
            try:
                self.on_record(dict(rec))
            except Exception:  # telemetry must never break serving
                pass

    def _note_qos(self, rec: dict) -> None:
        """Fold one terminal record into the per-tier / per-tenant /stats
        rollups (the labeled /metrics families are observed beside this in
        ``ServingMetrics.observe_qos``)."""
        tier = rec.get("tier")
        tenant = rec.get("tenant")
        reason = rec.get("completion_reason")
        if tier is None or tenant is None or reason is None:
            return
        tc = self.tier_counters.get(tier)
        if tc is not None:
            if reason in _COMPLETED_REASONS:
                tc["completed"] += 1
            elif reason in tc:
                tc[reason] += 1
        nc = self.tenant_counters.setdefault(
            tenant,
            {"requests": 0, "completed": 0, "shed": 0, "quota": 0,
             "timeout": 0},
        )
        nc["requests"] += 1
        if reason in _COMPLETED_REASONS:
            nc["completed"] += 1
        elif reason in nc:
            nc[reason] += 1

    def qos_snapshot(self) -> dict:
        """The /stats ``qos`` block: live queue composition by tier and
        tenant plus the cumulative terminal rollups — the numbers
        fleet-status's TIER/TENANT summary and the noisy-neighbor tests
        read."""
        queued_by_tier: dict[str, int] = {t: 0 for t in TIERS}
        queued_by_tenant: dict[str, int] = {}
        for q in self._queue:
            queued_by_tier[q.tier] = queued_by_tier.get(q.tier, 0) + 1
            queued_by_tenant[q.tenant] = queued_by_tenant.get(q.tenant, 0) + 1
        return {
            "enabled": self.config.qos.enabled,
            "queued_by_tier": queued_by_tier,
            "queued_by_tenant": queued_by_tenant,
            "tiers": {t: dict(c) for t, c in self.tier_counters.items()},
            "tenants": {n: dict(c) for n, c in self.tenant_counters.items()},
        }

    def check_invariants(self) -> None:
        """Allocator + scheduler audit for the chaos suite: the pool's own
        invariants, queue entries unique by request id, and every queued
        entry carrying a valid tier. Raises on violation."""
        self.pool.check_invariants()
        rids = [q.rid for q in self._queue]
        if len(rids) != len(set(rids)):
            raise AssertionError(f"duplicate queued request ids: {rids}")
        for q in self._queue:
            tier_index(q.tier)
        for served in self._wfq_served.values():
            if served < 0:
                raise AssertionError(
                    f"negative WFQ service accumulator: {self._wfq_served}"
                )

    # -- scheduler ------------------------------------------------------------
    def _expire_tick(self) -> list[dict]:
        """Cancel every request whose deadline/queue-wait elapsed — queued,
        prefilling, or decoding — freeing its blocks."""
        now = time.perf_counter()
        done: list[dict] = []
        if self._queue and any(
            q.deadline_at is not None or q.queue_deadline_at is not None
            for q in self._queue
        ):
            keep: deque[_Queued] = deque()
            for q in self._queue:
                expired = (
                    (q.deadline_at is not None and now >= q.deadline_at)
                    or (
                        q.queue_deadline_at is not None
                        and now >= q.queue_deadline_at
                    )
                )
                if expired:
                    done.append(self._rejection_record(q, "timeout"))
                else:
                    keep.append(q)
            self._queue = keep
        for b, slot in enumerate(self._slots):
            if (
                slot is not None
                and slot.deadline_at is not None
                and now >= slot.deadline_at
            ):
                done.append(self._terminate(b, "timeout"))
        return done

    def _select_queued(self, now: float) -> int:
        """Index of the next queued request to admit. FIFO (index 0) when
        QoS is off — bit-identical to the engine before serving.qos
        existed. With QoS on the order is: effective tier (aging promotion
        counts) → weighted-fair service across tenants within the tier
        (least normalized service first) → EDF (earliest deadline) → FIFO.
        One O(queue) scan per free slot — max_queue bounds it."""
        if not self.config.qos.enabled or len(self._queue) <= 1:
            return 0
        best_i = 0
        best_key = None
        for i, q in enumerate(self._queue):
            key = (
                self._effective_tier(q, now),
                self._wfq_served.get((q.tier, q.tenant), 0.0)
                / self.config.qos.tenant(q.tenant).weight,
                q.deadline_at if q.deadline_at is not None else float("inf"),
                q.t_submit,
                i,
            )
            if best_key is None or key < best_key:
                best_key, best_i = key, i
        return best_i

    def _admit(self, done: list[dict]) -> None:
        with self._phase("admit"):
            self._admit_free_slots(done)

    def _admit_free_slots(self, done: list[dict]) -> None:
        for b in range(self.config.slots):
            if self._slots[b] is not None or not self._queue:
                continue
            idx = self._select_queued(time.perf_counter())
            q = self._queue[idx]
            t_adm0 = time.perf_counter()  # tracing: admission stage start
            if q.payload is not None:
                # KV handoff: the prompt's rows arrive pre-computed, so the
                # prefix cache is bypassed (shipped blocks are scattered
                # whole; the injected prefix registers below for FUTURE
                # requests to hit)
                hits, hit_tokens = [], 0
            else:
                hits, hit_tokens = self.pool.match_prefix(q.prompt)
            need = blocks_needed(
                len(q.prompt) if q.prefill_only else len(q.prompt) + q.max_new,
                self.config.block_size,
                0 if q.prefill_only else self.config.spec_overhang,
            )
            fresh = self.pool.allocate(need - len(hits))
            if fresh is None:
                # pool can't cover the selected head of the queue: undo the
                # hit refs and stop admitting this step (no overtaking past
                # the scheduling order's head — with QoS off that is plain
                # FIFO ttft fairness; with QoS on the head is the
                # tier/WFQ/EDF winner and overtaking it would invert the
                # priority order under exactly the pressure it exists for)
                if hits:
                    self.pool.free(hits)
                break
            del self._queue[idx]
            # WFQ accounting: charge the admitted request's whole token
            # budget to its (tier, tenant) lane — what "service" means here
            self._wfq_served[(q.tier, q.tenant)] = self._wfq_served.get(
                (q.tier, q.tenant), 0.0
            ) + float(len(q.prompt) + (0 if q.prefill_only else q.max_new))
            blocks = hits + fresh
            try:
                if q.payload is not None:
                    self._bind_injected_slot(b, q, blocks, done)
                else:
                    hit_tokens = self._resolve_hierarchy(
                        q, hits, hit_tokens, fresh
                    )
                    # token-weighted prefix accounting, stamped once per
                    # admission AFTER the hierarchy resolved: hit = matchable
                    # prompt tokens served from ANY tier, miss = matchable
                    # tokens about to recompute
                    bs = self.config.block_size
                    matchable = max(len(q.prompt) - 1, 0) // bs * bs
                    self.pool.note_prefix_tokens(
                        hit_tokens, max(matchable - hit_tokens, 0)
                    )
                    self._bind_slot(b, q, blocks, hit_tokens)
                self._account.n["admitted"] += 1
                # queue wait and admission (prefix match + whole-budget
                # block allocation + slot bind) as sibling stages under the
                # request root — the two ways a slow admission can hide
                self._child_span(
                    q.trace, "queue", q.t_submit, t_adm0, request_id=q.rid
                )
                self._child_span(
                    q.trace, "admission", t_adm0,
                    request_id=q.rid, blocks=len(blocks),
                    hit_tokens=hit_tokens if q.payload is None else 0,
                )
            except Exception as e:
                # leak audit: an exception between admit-time allocation and
                # slot binding must return EVERY block and fail only THIS
                # request — loudly — not the server
                self.pool.free(blocks)
                self.error_total += 1
                logger.exception("admission failed for %s", q.rid)
                done.append(
                    self._rejection_record(
                        q, "engine_error",
                        detail=f"admission: {type(e).__name__}: {e}",
                    )
                )

    def _bind_slot(
        self, b: int, q: _Queued, blocks: list[int], hit_tokens: int
    ) -> None:
        row = np.zeros((self.config.table_blocks,), np.int32)
        row[: len(blocks)] = blocks
        self._tables[b] = row
        self._lengths[b] = hit_tokens
        self._active[b] = False
        self._slots[b] = _Slot(
            request_id=q.rid, prompt=q.prompt, max_new=q.max_new,
            blocks=blocks, hit_tokens=hit_tokens,
            prefill_pos=hit_tokens, t_submit=q.t_submit,
            t_admit=time.perf_counter(), deadline_at=q.deadline_at,
            prefill_only=q.prefill_only, trace=q.trace,
            logprobs=[] if q.return_logprobs else None,
            tenant=q.tenant, tier=q.tier,
        )

    def _bind_injected_slot(
        self, b: int, q: _Queued, blocks: list[int], done: list[dict]
    ) -> None:
        """Admission for a KV-handoff request (``submit_prefilled``): the
        shipped prompt rows scatter into the allocated blocks and the slot
        starts directly in decode with the prefill replica's first token
        already committed — this replica never touches the prompt math."""
        p = len(q.prompt)
        nb = blocks_needed(p, self.config.block_size)
        row = np.zeros((self.config.table_blocks,), np.int32)
        row[: len(blocks)] = blocks
        t_inj0 = time.perf_counter()
        from automodel_tpu.resilience.fault_injection import active_injector

        inj = active_injector()
        if inj is not None:
            inj.maybe_trace_delay("kv_inject")
        self._pool = paged.inject_blocks(
            self._pool, np.asarray(blocks[:nb], np.int32), q.payload["kv"]
        )
        self._child_span(
            q.trace, "kv_inject", t_inj0,
            request_id=q.rid, blocks=nb, prompt_tokens=p,
        )
        first = int(q.payload["first_token"])
        account_first = self._account.snapshot()
        now = account_first.t
        self._tables[b] = row
        self._lengths[b] = p
        self._cur[b] = first
        self._active[b] = True
        self._slots[b] = _Slot(
            request_id=q.rid, prompt=q.prompt, max_new=q.max_new,
            blocks=blocks, hit_tokens=0, prefill_pos=p,
            t_submit=q.t_submit, t_admit=now, deadline_at=q.deadline_at,
            decoding=True, generated=[first], t_first=now,
            account_first=account_first, trace=q.trace,
            tenant=q.tenant, tier=q.tier,
        )
        # the injected prefix is as matchable as a locally-computed one —
        # future affinity-routed requests hit it without another transfer
        self.pool.register_prefix(q.prompt, blocks)
        self.kv_injected_total += 1
        if first in self._eos:
            done.append(self._terminate(b, "stop"))
        elif q.max_new <= 1:
            done.append(self._terminate(b, "length"))

    def _prefill_tick(self) -> list[dict]:
        from automodel_tpu.resilience.fault_injection import active_injector

        inj = active_injector()
        done: list[dict] = []
        chunk_len = self.config.prefill_chunk
        pad = self.gen_config.pad_token_id
        prefilling = [
            (b, s) for b, s in enumerate(self._slots) if s is not None and not s.decoding
        ]
        cap = self.config.max_prefill_chunks_per_step
        if cap and len(prefilling) > cap:
            # a bounded iteration: the rest keep their slots and wait a turn, so
            # the decode step that follows is at most ``cap`` chunks away
            prefilling = sorted(prefilling, key=lambda bs: bs[1].t_admit)[:cap]
        for b, slot in prefilling:
            p = len(slot.prompt)
            start = slot.prefill_pos
            real = min(chunk_len, p - start)
            with self._phase(
                "prefill_dispatch", slot=b, pos=start, tokens=real,
                **self._chunk_attn_steps(start),
            ) as dispatch:
                ids = np.full((chunk_len,), pad, np.int32)
                ids[:real] = slot.prompt[start : start + real]
                # a recurrent layout's chunk also names the slot's state row;
                # a chunk at position 0 resets it inside the program
                state_row = (jnp.int32(b),) if self._stateful else ()
                if self._stateful and start == 0:
                    self._account.n["state_resets"] += 1
                if inj is not None:
                    inj.maybe_trace_delay("prefill")
                    inj.maybe_slo_breach("prefill", self._step_counter)
                if self.collect_program_costs and "chunk_prefill" not in self.program_costs:
                    self._record_cost(
                        "chunk_prefill", self._chunk,
                        self.auto.params, self._pool,
                        jnp.asarray(self._tables[b]), jnp.asarray(ids),
                        jnp.int32(start), jnp.int32(real), *state_row,
                    )
                last, self._pool = self._chunk(
                    self.auto.params, self._pool,
                    jnp.asarray(self._tables[b]), jnp.asarray(ids),
                    jnp.int32(start), jnp.int32(real), *state_row,
                )
                if self._spec_enabled:
                    # the draft model prefills the same chunk into its
                    # parallel pool (same tables/offsets) so its proposals
                    # see the whole prompt; its last-token logits are unused
                    # — the first sampled token always comes from the TARGET
                    _, self._draft_pool = self._draft_chunk(
                        self.draft_auto.params, self._draft_pool,
                        jnp.asarray(self._tables[b]), jnp.asarray(ids),
                        jnp.int32(start), jnp.int32(real),
                    )
            self._account.n["chunks"] += 1
            # one span per chunk: a single long prompt's prefill shows as a
            # chunk train, and a stall inside one chunk names its offset
            self._child_span(
                slot.trace, "prefill", dispatch.start_s, dispatch.end_s,
                request_id=slot.request_id, pos=start, tokens=real,
            )
            slot.prefill_pos = start + real
            self._lengths[b] = slot.prefill_pos
            if slot.prefill_pos < p:
                continue
            # prompt fully in: sample the first token (charged to ttft) —
            # the host blocks here until the chunk program has run
            self._account.n["first_token_waits"] += 1
            with self._phase("first_token_wait", slot=b):
                first = int(
                    sample(
                        last[None, :],
                        jax.random.fold_in(self._base_key, self._step_counter),
                        self.gen_config.sampling,
                    )[0]
                )
            # publish the prompt blocks to the prefix cache, flip to decode
            with self._phase("record"):
                if slot.logprobs is not None:
                    # same raw-logits rule as the decode program (the chunk
                    # already handed `last` to the host, so this is free)
                    slot.logprobs.append(
                        float(jax.nn.log_softmax(last.astype(jnp.float32))[first])
                    )
                self.pool.register_prefix(slot.prompt, slot.blocks)
                slot.account_first = self._account.snapshot()
                slot.t_first = slot.account_first.t
                slot.generated = [first]
                if slot.prefill_only:
                    # disaggregated fleet: the prompt's block rows leave for
                    # a decode replica — extract BEFORE _terminate decrefs
                    # the blocks (contents survive until reuse, but
                    # extraction from owned blocks is the contract the
                    # transfer relies on)
                    k, v = paged.extract_blocks(self._pool, slot.blocks)
                    self._stash_prefill_payload(slot.request_id, {
                        "first_token": first,
                        "prompt_len": p,
                        "kv": {"k": k, "v": v},
                        # host-side only: the /prefill handler parents its
                        # kv_send span under this request's root
                        "trace": slot.trace,
                    })
                    done.append(self._terminate(b, "prefilled"))
                    continue
                slot.decoding = True
                self._cur[b] = first
                self._active[b] = True
                self._lengths[b] = p
                if first in self._eos:
                    done.append(self._terminate(b, "stop"))
                elif slot.max_new <= 1:
                    done.append(self._terminate(b, "length"))
        return done

    def _decode_tick(self) -> list[dict]:
        if self._spec_enabled:
            # a verify step commits 1 to k + 1 tokens a slot: the next
            # step's lengths are not host-known, so nothing launches ahead
            if not self._active.any():
                return []
            self._inject_decode_faults()
            return self._spec_decode_tick()
        read = self._in_flight
        with self._phase("decode_plan"):
            launch, ahead, slots = self._launch_rows(read)
            lengths = self._lengths + ahead
            if launch.any():
                self._note_decode_wave(lengths, launch)
        if read is None and not launch.any():
            return []
        self._inject_decode_faults()
        self._in_flight = (
            self._launch_decode(launch, ahead, lengths, slots, read)
            if launch.any() else None
        )
        return self._read_decode(read) if read is not None else []

    def _inject_decode_faults(self) -> None:
        from automodel_tpu.resilience.fault_injection import active_injector

        inj = active_injector()
        if inj is not None:
            # lands inside every traced request's decode window (t_first →
            # terminal), so the delay attributes to the decode stage
            inj.maybe_trace_delay("decode")
            inj.maybe_slo_breach("decode", self._step_counter)

    def _launch_rows(self, read: Optional[_DecodeInFlight]):
        """→ ``(launch [B] bool, ahead [B] bool, slots [B])``: the rows the
        next decode step advances, those of them whose newest token is still
        in ``read`` (the unread step: the row is one token longer than the
        host has recorded and takes ``cur`` from that step's token array),
        and the ``_Slot`` each launched row belongs to. A slot whose budget
        its row in flight spends is not launched again."""
        B = self.config.slots
        launch, ahead = np.zeros((B,), bool), np.zeros((B,), bool)
        slots: list[Optional[_Slot]] = [None] * B
        for b in np.flatnonzero(self._active):
            slot = self._slots[b]
            in_flight = read is not None and read.slots[b] is slot
            if len(slot.generated) + in_flight < slot.max_new:
                launch[b], ahead[b], slots[b] = True, in_flight, slot
        return launch, ahead, slots

    def _launch_decode(
        self, launch: np.ndarray, ahead: np.ndarray, lengths: np.ndarray,
        slots: list, read: Optional[_DecodeInFlight],
    ) -> _DecodeInFlight:
        """Dispatch one decode step over the ``launch`` rows (``lengths`` as
        the program reads them) and leave its tokens on the device. Every
        host array handed over is this call's own: the scheduler writes
        ``_tables``/``_cur`` while the program may still be reading what it
        was given."""
        n = self._account.n
        with self._phase("decode_dispatch"):
            with self._phase("decode_h2d"):
                args = (
                    self.auto.params, self._pool,
                    jnp.asarray(self._tables.copy()), jnp.asarray(lengths),
                    jnp.asarray(self._cur.copy()), jnp.asarray(launch),
                    self._base_key, jnp.int32(self._step_counter),
                    read.tokens if read is not None else self._no_tokens,
                    jnp.asarray(ahead),
                )
            if self.collect_program_costs and "paged_decode" not in self.program_costs:
                self._record_cost("paged_decode", self._decode, *args)
            with self._phase("decode_launch"):
                tokens, logps, units, self._pool = self._decode(*args)
                n["decode_launched"] += 1
                # the step before is still running with this one queued
                # behind it: the device goes from one to the other without
                # the host
                n["decode_launched_ahead"] += int(
                    read is not None and not read.tokens.is_ready()
                )
        return _DecodeInFlight(tokens, logps, units, slots)

    def _read_decode(self, step: _DecodeInFlight) -> list[dict]:
        """Bring a launched step's tokens to the host and record them. A row
        whose slot ended (a stop id found in the step before, a timeout, a
        cancel) or changed hands since the launch is discarded."""
        n = self._account.n
        with self._phase("decode_wait"):
            tokens, logps, units = jax.device_get(
                (step.tokens, step.logps, step.units)
            )
        n["expert_live_units"] += int(units[0])
        n["expert_grid_units"] += int(units[1])
        if len(units) > 2:  # a held share's picks (_expert_units_fn)
            n["held_expert_rows"] += int(units[2])
        self.first_decode_done = True
        done: list[dict] = []
        with self._phase("record"):
            for b, slot in enumerate(step.slots):
                if slot is None:
                    continue
                if self._slots[b] is not slot:
                    n["discarded_rows"] += 1
                    continue
                tok = int(tokens[b])
                slot.generated.append(tok)
                if slot.logprobs is not None:
                    slot.logprobs.append(float(logps[b]))
                self._lengths[b] += 1
                self._cur[b] = tok
                if tok in self._eos:
                    done.append(self._terminate(b, "stop"))
                elif len(slot.generated) >= slot.max_new:
                    done.append(self._terminate(b, "length"))
        return done

    def _note_decode_wave(self, lengths: np.ndarray, active: np.ndarray) -> None:
        """What the decode program is about to read, for `serve.counts`:
        the active slots and the context tokens their attention covers."""
        n = self._account.n
        n["decoded"] += int(active.sum())
        n["context_tokens"] += int(lengths[active].sum())
        if self._latent:
            # rows the decode attention has to read, every layer's: each slot
            # the call is handed, active or not (the kernel attends them all)
            n["latent_context_rows"] += len(self._layout) * latent_attention.context_rows(
                lengths
            )
        if self.decode_backend == "fused":
            # every slot's row, active or not: the kernel attends them all
            steps = latent_attention.grid_steps if self._latent else paged_attention.grid_steps
            grid, live = steps(
                lengths, self._tables.shape[1],
                pages=self.attn_pages_per_step,
                block_size=self.config.block_size, sq=self._attn_query_rows,
            )
            n["attn_grid_steps"] += grid
            n["attn_live_steps"] += live

    def _spec_decode_tick(self) -> list[dict]:
        """One speculative round for the whole decode wave: the draft
        proposes ``spec_k`` tokens per slot (its own pool, shared tables),
        ONE batched verify forward through the target commits the accepted
        prefix + a correction/bonus token. Rollback of rejected drafts is
        pure bookkeeping — the host simply advances ``lengths`` by the
        committed count, leaving rejected K/V rows past the length where
        no future attend can see them and the next round overwrites."""
        k = self.config.speculative.k
        tables = jnp.asarray(self._tables)
        lengths = jnp.asarray(self._lengths)
        cur = jnp.asarray(self._cur)
        active = jnp.asarray(self._active)
        step = jnp.int32(self._step_counter)
        with self._phase("decode_plan"):
            self._note_decode_wave(self._lengths, self._active)
        with self._phase("spec_propose") as propose:
            drafts, draft_logits, self._draft_pool = self._propose(
                self.draft_auto.params, self._draft_pool,
                tables, lengths, cur, active, self._base_key, step,
            )
        with self._phase("spec_verify") as verify:
            if self.collect_program_costs and "spec_verify" not in self.program_costs:
                self._record_cost(
                    "spec_verify", self._verify,
                    self.auto.params, self._pool, tables, lengths, cur,
                    drafts, draft_logits, active, self._base_key, step,
                )
            tokens, n_commit, self._pool = self._verify(
                self.auto.params, self._pool, tables, lengths, cur,
                drafts, draft_logits, active, self._base_key, step,
            )
            tokens = np.asarray(jax.device_get(tokens))
            n_commit = np.asarray(jax.device_get(n_commit))
        self.first_decode_done = True
        self.spec_rounds += 1  # one propose+verify round per WAVE, not per slot
        done: list[dict] = []
        with self._phase("record"):
            self._record_spec_wave(done, tokens, n_commit, k, propose, verify)
        return done

    def _record_spec_wave(self, done, tokens, n_commit, k, propose, verify) -> None:
        for b, slot in enumerate(self._slots):
            if slot is None or not self._active[b]:
                continue
            # per-wave propose/verify spans on every traced slot the wave
            # served: the whole wave's wall time IS where this request's
            # time went (the calls are batched over the wave)
            self._child_span(
                slot.trace, "spec_propose", propose.start_s, propose.end_s,
                request_id=slot.request_id, k=k,
            )
            self._child_span(
                slot.trace, "spec_verify", verify.start_s, verify.end_s,
                request_id=slot.request_id,
                accepted=int(n_commit[b]) - 1,
            )
            n = int(n_commit[b])
            accepted = n - 1
            slot.spec_proposed += k
            slot.spec_accepted += accepted
            self.spec_proposed_total += k
            self.spec_accepted_total += accepted
            reason = None
            used = 0
            for tok in (int(t) for t in tokens[b, :n]):
                slot.generated.append(tok)
                used += 1
                if tok in self._eos:
                    reason = "stop"
                    break
                if len(slot.generated) >= slot.max_new:
                    reason = "length"
                    break
            # committed length only ever moves FORWARD by what was kept:
            # the rejected tail needs no cache surgery (paged.py rollback
            # contract); a truncated commit only happens when terminating
            self._lengths[b] += used
            self._cur[b] = slot.generated[-1]
            if reason is not None:
                done.append(self._terminate(b, reason))

    def _rebuild(self, reason: str, detail: Optional[str] = None) -> list[dict]:
        """Recover from a stalled or failed program: fail the affected
        wave's requests, re-initialize the pool arrays (the donated buffers
        of a failed call are gone or garbage), clear the prefix cache
        (contents no longer trusted), audit the allocator, and keep the
        queue. Queued requests have no device state and ride through."""
        done: list[dict] = []
        affected = 0
        for b, slot in enumerate(self._slots):
            if slot is not None:
                done.append(self._terminate(b, reason, detail=detail))
                affected += 1
        self.pool.clear_prefix_cache()
        self.pool.check_invariants()
        self._init_pool_arrays()
        self._in_flight = None  # the step in flight goes with the wave it fails
        self._tables[:] = 0
        self._lengths[:] = 0
        self._active[:] = False
        self._cur[:] = self.gen_config.pad_token_id
        if reason == "engine_stall":
            self.stall_total += 1
        else:
            self.error_total += 1
        try:
            self.metrics.observe_engine_event(reason)
        except Exception:
            pass
        logger.error(
            "serving engine %s at step %d: failed %d in-flight request(s), "
            "pool rebuilt, queue (%d) kept — %s",
            reason, self._step_counter, affected, self.queue_depth,
            detail or "",
        )
        rec = {
            "event": "serve_engine_event",
            "reason": reason,
            "step": self._step_counter,
            "requests_failed": affected,
            "ts": self._wall_ts(),
        }
        if detail:
            rec["detail"] = detail
        if self.on_record is not None:
            try:
                self.on_record(rec)
            except Exception:
                pass
        return done

    def _injection_tick(self, inj: Any) -> None:
        """Serving fault hooks (resilience/fault_injection.py): allocator
        exhaustion, a slow/hung step, a mid-request engine exception, a
        noisy-neighbor tenant flood. Each is a cheap None-check when
        unarmed."""
        c = inj.config
        step = self._step_counter
        flood = inj.maybe_tenant_flood(step)
        if flood is not None:
            # noisy neighbor: one tenant slams the admission path with a
            # burst of real submissions — quotas, tiering, and shedding are
            # expected to contain it (tests/test_qos.py proves isolation).
            # Rejections are accounted through the same seams a front uses.
            tenant, n, tier = flood
            for i in range(n):
                rid = f"flood-{tenant}-{step}-{i}"
                try:
                    self.submit(
                        [1, 2, 3], request_id=rid, max_new_tokens=4,
                        tenant=tenant, tier=tier,
                    )
                except QuotaExceeded as e:
                    self.record_quota(
                        request_id=rid, tenant=e.tenant, tier=e.tier
                    )
                except QueueFull:
                    self.record_shed(request_id=rid, tenant=tenant, tier=tier)
                except EngineDraining:
                    break
        if self._exhaust_hold is not None and step >= self._exhaust_hold[1]:
            self.pool.free(self._exhaust_hold[0])
            self._exhaust_hold = None
            logger.error("fault injection: released the exhausted pool")
        if (
            c.serve_exhaust_blocks_at_step is not None
            and step == c.serve_exhaust_blocks_at_step
            and self._exhaust_hold is None
        ):
            grabbed = self.pool.allocate(self.pool.available()) or []
            self._exhaust_hold = (
                grabbed, step + max(int(c.serve_exhaust_hold_steps), 1)
            )
            logger.error(
                "fault injection: exhausted the block pool (%d blocks) "
                "until step %d", len(grabbed), self._exhaust_hold[1],
            )
        inj.maybe_serve_hang(step)
        inj.maybe_serve_exception(step)

    def step(self) -> list[dict]:
        """One scheduler iteration → the requests that reached a terminal
        state in it (every record carries a ``completion_reason``).

        On the profiler's clock the iteration is one ``serve.step`` span;
        its phases (admit, each chunk's dispatch, the first-token wait, the
        plan and the dispatch of the NEXT decode step, the decode wait and
        the records of the step launched an iteration ago) are its children,
        and ``serve.counts`` closes it with the iteration's integers. The
        same phases are the buckets of the loop's always-on account
        (serving/loop_account.py)."""
        with self._phase(
            "step", step=self._step_counter,
            queued=len(self._queue), busy=self.busy_slots,
        ):
            self._account.begin_iteration()
            done = self._iterate()
            self._account.close_iteration(
                finished=len(done), state_slots=self.state_slots
            )
        return done

    def _iterate(self) -> list[dict]:
        if self._watchdog is not None:
            self._watchdog.pet(self._step_counter)
            if not self.first_decode_done:
                # the training watchdog's second-pet rule ends the compile
                # grace too early here: serving compiles TWO programs at
                # different steps (chunk prefill on the first prefill tick,
                # paged decode a few steps later) — hold the grace until
                # the decode program has actually run once
                self._watchdog.set_phase("compile")
        done: list[dict] = []
        try:
            from automodel_tpu.resilience.fault_injection import active_injector

            inj = active_injector()
            if inj is not None:
                self._injection_tick(inj)
            done += self._expire_tick()
            if self.draining:
                while self._queue:
                    done.append(
                        self._rejection_record(self._queue.popleft(), "draining")
                    )
                if (
                    self._drain_deadline is not None
                    and time.perf_counter() >= self._drain_deadline
                ):
                    for b, slot in enumerate(self._slots):
                        if slot is not None:
                            done.append(
                                self._terminate(
                                    b, "cancelled",
                                    detail="drain grace "
                                    f"{self.config.drain.grace_s}s expired",
                                )
                            )
            else:
                if self._pending_swap is None:
                    # a staged weight swap holds admissions (the queue keeps
                    # absorbing) so no request starts under weights that are
                    # about to be replaced mid-generation
                    self._admit(done)
            done += self._prefill_tick()
            done += self._decode_tick()
            rebuilt = False
        except Exception as e:
            rebuilt = True
            self._consecutive_rebuilds += 1
            if self._consecutive_rebuilds > self.MAX_CONSECUTIVE_REBUILDS:
                raise  # systemic — the serving front reports scheduler death
            done += self._rebuild(
                "engine_error", detail=f"{type(e).__name__}: {e}"
            )
        ev, self._stall_evidence = self._stall_evidence, None
        if ev is not None:
            # the wedged call returned after the watchdog fired: its wave is
            # suspect — fail it, rebuild, keep serving. Stall rebuilds draw
            # on the SAME consecutive budget as exception rebuilds: a step
            # that stalls every single time is just as systemic as one that
            # raises every time, and must not rebuild-loop forever.
            rebuilt = True
            self._consecutive_rebuilds += 1
            if self._consecutive_rebuilds > self.MAX_CONSECUTIVE_REBUILDS:
                raise RuntimeError(
                    f"serving engine stalled {self._consecutive_rebuilds} "
                    "consecutive scheduler iterations — systemic fault, "
                    "refusing to rebuild-loop"
                )
            done += self._rebuild(
                "engine_stall",
                detail=(
                    f"no step-boundary heartbeat for {ev.get('heartbeat_age_s')}s "
                    f"(deadline {ev.get('deadline_s')}s) in phase "
                    f"{ev.get('step_phase')}"
                ),
            )
        if not rebuilt:
            self._consecutive_rebuilds = 0
        if self._pending_swap is not None and self._quiet:
            # the step that terminated the last in-flight request is the
            # swap boundary: everything before this line ran (and finished)
            # under the old weights, everything admitted after runs under
            # the new — in-flight outputs are bit-untouched by the swap
            self._apply_pending_swap()
        self._step_counter += 1
        self.last_step_t = time.monotonic()
        if self.draining:
            self.drain_complete()  # stamps drain_duration_s when reached
        return done

    def run(self, max_iterations: Optional[int] = None) -> list[dict]:
        """Drain the queue and every running slot. ``max_iterations`` guards
        against scheduler bugs (default: a generous analytic bound)."""
        if max_iterations is None:
            n_req = len(self._queue) + self.busy_slots
            per_req = (
                -(-self.config.max_seq_len // self.config.prefill_chunk)
                + self.config.max_seq_len
            )
            max_iterations = 64 + (n_req + 1) * (per_req + 2)
        out: list[dict] = []
        for _ in range(max_iterations):
            if self.idle():
                return out
            out.extend(self.step())
        raise RuntimeError(
            f"serving engine failed to drain within {max_iterations} "
            f"iterations (queue={self.queue_depth}, busy={self.busy_slots})"
        )

    def _record_cost(self, name: str, jit_fn, *args) -> None:
        from automodel_tpu.telemetry.profiling import record_program_cost

        record_program_cost(self.program_costs, name, jit_fn, *args)
        cost = self.program_costs[name]
        logger.info(
            "%s traced: pallas_kernels=%s mosaic_calls=%s",
            name, cost.get("pallas_kernels"), cost.get("mosaic_calls"),
        )
        if self.on_record is not None:
            self.on_record(
                {"event": "cost_attribution", "program": name, **cost}
            )
