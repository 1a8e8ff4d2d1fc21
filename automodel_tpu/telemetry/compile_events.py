"""Bridge jax.monitoring compile events into step metrics.

A mid-run recompile (shape drift from an unpadded last batch, a donated
buffer falling back, a new code path) spends seconds on the host and — with
async dispatch — masquerades as one mysteriously slow step in the JSONL.
JAX already announces every compile via `jax.monitoring` duration events
(`/jax/core/compile/backend_compile_duration` et al.); this module
accumulates them process-wide and lets each consumer drain the delta since
its last look, so the MetricLogger can stamp `recompiles`/`recompile_secs`
onto exactly the log window the compile happened in.

jax.monitoring has no targeted unregister (only `clear_event_listeners`,
which would nuke other listeners), so registration is a process-global
singleton and per-consumer state is just a cursor into the global totals —
building many CompileEventBridge instances (every recipe in a test session)
never stacks listeners.
"""

from __future__ import annotations

import threading

# the backend-compile event is the expensive one; trace/lowering events are
# folded into the same counters as "compile work" seen by the host
_EVENT_SUFFIXES = (
    "backend_compile_duration",
    "jaxpr_to_mlir_module_duration",
)

# a jit call that had to trace again (new shapes, a new closure) announces it
# here even when the persistent cache then spares the backend compile: counted
# apart, so a retrace that hits the cache is visible and `compile_secs` keeps
# meaning what it meant
_TRACE_EVENT_SUFFIX = "jaxpr_trace_duration"

_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

_lock = threading.Lock()
_totals = {"count": 0, "secs": 0.0, "cache_hits": 0, "cache_misses": 0,
           "traces": 0, "trace_secs": 0.0}
_registered = False


def _listener(event: str, duration_secs: float, **kwargs) -> None:
    if event.endswith(_TRACE_EVENT_SUFFIX):
        with _lock:
            _totals["traces"] += 1
            _totals["trace_secs"] += float(duration_secs)
        return
    if not event.endswith(_EVENT_SUFFIXES):
        return
    with _lock:
        # count whole compiles, not sub-phases: only the backend event bumps
        # the counter; every phase adds to the seconds
        if event.endswith("backend_compile_duration"):
            _totals["count"] += 1
        _totals["secs"] += float(duration_secs)


def _cache_listener(event: str, **kwargs) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        with _lock:
            _totals[key] += 1


def _ensure_registered() -> None:
    global _registered
    with _lock:
        if _registered:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_listener)
        jax.monitoring.register_event_listener(_cache_listener)
        _registered = True


def compile_totals() -> dict[str, float] | None:
    """Process totals since the listeners were registered — compiles, the
    seconds they took (a persistent-cache hit costs its retrieval only), how
    many were hits or misses of that cache, and how many times a function
    was traced to a jaxpr (every compile starts with one; so does a retrace
    whose executable then comes from the cache). None when nothing in this
    process registered them (no device command ran)."""
    with _lock:
        if not _registered:
            return None
        return {
            "compiles": _totals["count"],
            "compile_secs": round(_totals["secs"], 3),
            "cache_hits": _totals["cache_hits"],
            "cache_misses": _totals["cache_misses"],
            "traces": _totals["traces"],
            "trace_secs": round(_totals["trace_secs"], 3),
        }


class CompileEventBridge:
    """Per-consumer cursor over the process-global compile counters."""

    def __init__(self):
        _ensure_registered()
        with _lock:
            self._seen_count = _totals["count"]
            self._seen_secs = _totals["secs"]

    def drain(self) -> dict[str, float]:
        """→ {"compiles": n, "compile_secs": s} since the previous drain."""
        with _lock:
            count, secs = _totals["count"], _totals["secs"]
        out = {
            "compiles": count - self._seen_count,
            "compile_secs": secs - self._seen_secs,
        }
        self._seen_count, self._seen_secs = count, secs
        return out
