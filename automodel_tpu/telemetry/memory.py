"""Device-memory telemetry: per-device allocator stats + a live-array census.

Parity motive: the reference leans on `torch.cuda.memory_summary()` and
nsys memory tracks to explain OOMs; JAX's equivalents are
`Device.memory_stats()` (TPU/GPU allocator counters — returns None on the
CPU backend) and `jax.live_arrays()` (every array the client still holds a
reference to). Grouping live arrays by (dtype, shape) gives a top-K census
that names *what* filled the chip — stacked expert grads vs optimizer
moments vs activations read very differently — which is exactly the
information a leg that dies of RESOURCE_EXHAUSTED otherwise lacks.

Everything here is host-side and allocation-free on device; callers control
the cadence (TelemetryConfig.memory_every_steps) and the forced dump on
RESOURCE_EXHAUSTED (flight_recorder.py).
"""

from __future__ import annotations

import time
from typing import Any

import jax

# allocator counters worth forwarding (subset of the backend's dict; CPU
# returns None, some backends omit keys)
_STAT_KEYS = (
    "bytes_in_use",
    "peak_bytes_in_use",
    "largest_alloc_size",
    "bytes_limit",
    "num_allocs",
)


def device_memory_stats() -> dict[str, dict[str, int]]:
    """Per-device allocator counters keyed by device id (as a string, so the
    dict JSON-serializes). Devices whose backend exposes no stats (CPU) get
    an empty dict — callers fall back to the live-array census totals."""
    out: dict[str, dict[str, int]] = {}
    for d in jax.devices():
        stats: Any = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        out[str(d.id)] = (
            {k: int(stats[k]) for k in _STAT_KEYS if k in stats} if stats else {}
        )
    return out


def live_array_census(top_k: int = 8) -> dict[str, Any]:
    """Group `jax.live_arrays()` by (dtype, shape): the top-K groups by total
    bytes plus an `other_bytes` remainder. `bytes` counts the GLOBAL logical
    size of sharded arrays (``Array.nbytes`` semantics), so a census taken on
    one host of a multi-host run over-reports per-chip residency by the
    sharding factor — it ranks culprits, it is not an allocator audit."""
    groups: dict[tuple[str, tuple], dict[str, int]] = {}
    n_arrays = 0
    total = 0
    for a in jax.live_arrays():
        try:
            key = (str(a.dtype), tuple(int(s) for s in a.shape))
            nbytes = int(a.nbytes)
        except Exception:
            continue  # deleted/donated between enumeration and inspection
        n_arrays += 1
        total += nbytes
        g = groups.setdefault(key, {"count": 0, "bytes": 0})
        g["count"] += 1
        g["bytes"] += nbytes
    ranked = sorted(groups.items(), key=lambda kv: kv[1]["bytes"], reverse=True)
    top = [
        {"dtype": k[0], "shape": list(k[1]), "count": g["count"], "bytes": g["bytes"]}
        for k, g in ranked[:top_k]
    ]
    return {
        "n_arrays": n_arrays,
        "total_bytes": total,
        "top": top,
        "other_bytes": total - sum(e["bytes"] for e in top),
    }


def memory_snapshot(top_k: int = 8) -> dict[str, Any]:
    """One self-contained snapshot: allocator counters + census + timestamp.
    Safe to call at any point, including from an exception handler after a
    RESOURCE_EXHAUSTED (the failed leg's buffers are still live then, which
    is precisely what makes the census diagnostic)."""
    return {
        "ts": time.time(),
        "devices": device_memory_stats(),
        "census": live_array_census(top_k),
    }


def max_bytes_in_use() -> tuple[int, int]:
    """(max bytes_in_use, max peak_bytes_in_use) across devices — the two
    scalars cheap enough to fold into per-step metrics. Falls back to the
    live-array total when the backend has no allocator stats (CPU)."""
    stats = device_memory_stats()
    in_use = [s["bytes_in_use"] for s in stats.values() if "bytes_in_use" in s]
    peak = [s["peak_bytes_in_use"] for s in stats.values() if "peak_bytes_in_use" in s]
    if not in_use:
        total = live_array_census(top_k=0)["total_bytes"]
        return total, total
    return max(in_use), max(peak) if peak else max(in_use)
