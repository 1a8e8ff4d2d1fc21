"""Crash flight recorder: a ring of the last-N step records + a run
fingerprint, dumped to JSON when the training loop dies.

Parity motive: the reference's per-rank crash logs (exception + last
iteration metrics per rank). Single-controller JAX gets one process, so one
ring buffer suffices; what it must capture is the TPU-specific failure
shape — a RESOURCE_EXHAUSTED at an async dispatch boundary, where the
traceback alone says nothing about which buffers filled the chip. The dump
therefore bundles (a) the last N host-side step records, (b) a
config/mesh/env fingerprint so the leg is reproducible, and (c) a forced
memory snapshot taken AT dump time — after an OOM the culprit buffers are
still live, so the census names them.

Used as a context manager around the train/bench loop::

    with telemetry.crash_guard():      # → FlightRecorder.__enter__
        ... loop ...                   # exception → dump + re-raise
"""

from __future__ import annotations

import json
import os
import re
import sys
import time
import traceback
from collections import deque
from pathlib import Path
from typing import Any, Optional

from automodel_tpu.telemetry import memory as mem_telemetry

# env vars worth fingerprinting: platform pinning, XLA tuning, where the
# compile cache lives
_ENV_KEYS = (
    "JAX_PLATFORMS",
    "XLA_FLAGS",
    "LIBTPU_INIT_ARGS",
    "JAX_COMPILATION_CACHE_DIR",
    "TPU_CHIPS_PER_HOST_BOUNDS",
)

# the dump is an artifact people attach to bug reports: mask config values
# whose key looks credential-shaped (wandb api keys, dataset auth tokens, …)
_SECRET_KEY_RE = re.compile(
    r"(?i)(token|secret|password|passwd|credential|api_?key|access_key|auth)"
)


def _redact(obj: Any) -> Any:
    if isinstance(obj, dict):
        return {
            k: (
                "<redacted>"
                if isinstance(k, str) and _SECRET_KEY_RE.search(k)
                else _redact(v)
            )
            for k, v in obj.items()
        }
    if isinstance(obj, (list, tuple)):
        return [_redact(x) for x in obj]
    return obj


def device_info() -> dict[str, Any]:
    """The device as JAX reports it — what every result names so a number
    can never be read apart from the hardware it came from."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "count": len(devs),
    }


def device_report(mesh_ctx: Any, backend: Any, **extra: Any) -> dict[str, Any]:
    """The once-per-run ``device_report`` record of the train and serve
    paths: the hardware, the mesh axes in use and the kernel backends the
    model was built with. What each program really traced follows in its
    ``cost_attribution`` record (pallas_kernels, mosaic_calls)."""
    return {
        "event": "device_report",
        **device_info(),
        "mesh": (
            {k: v for k, v in mesh_ctx.mesh.shape.items() if v > 1}
            if mesh_ctx is not None else {}
        ),
        "attn": getattr(backend, "attn", None),
        "experts": getattr(backend, "experts", None),
        **extra,
    }


def build_fingerprint(
    config: Optional[dict] = None, mesh_ctx: Any = None
) -> dict[str, Any]:
    """Config/mesh/env fingerprint stamped into every dump (and usable on
    its own for run provenance)."""
    import jax

    try:
        device = {
            **device_info(),
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
        }
    except Exception as e:  # backend init can itself be the failure
        device = {"error": repr(e)}
    return {
        "jax_version": jax.__version__,
        "python": sys.version.split()[0],
        "device": device,
        "mesh": dict(mesh_ctx.mesh.shape) if mesh_ctx is not None else None,
        "env": {k: os.environ[k] for k in _ENV_KEYS if k in os.environ},
        "config": _redact(config) if config is not None else None,
    }


def _jsonable(v: Any) -> Any:
    if hasattr(v, "item"):
        try:
            return v.item()
        except Exception:
            pass
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, (str, int, bool)) or v is None:
        return v
    if isinstance(v, float):
        return v if v == v and v not in (float("inf"), float("-inf")) else None
    return str(v)


class FlightRecorder:
    """Bounded ring of step records; dumps on exception (context manager)
    or on demand (`dump`). Recording is a deque append of already-host-side
    values — it must never force a device sync, so callers only pass
    host-known fields (step number, wall times, fetched metrics)."""

    def __init__(
        self,
        capacity: int = 16,
        path: str = "flight_recorder.json",
        fingerprint: Optional[dict] = None,
        census_top_k: int = 8,
    ):
        self.capacity = capacity
        self.path = Path(path)
        self.fingerprint = fingerprint or {}
        self.census_top_k = census_top_k
        self._ring: deque = deque(maxlen=max(capacity, 1))

    def record(self, rec: dict[str, Any]) -> None:
        self._ring.append(_jsonable(rec))

    @property
    def records(self) -> list[dict]:
        return list(self._ring)

    def dump(self, reason: str = "exception", exc: Optional[BaseException] = None) -> Path:
        try:
            snapshot = mem_telemetry.memory_snapshot(self.census_top_k)
        except Exception as e:  # never let the dump re-crash the crash path
            snapshot = {"error": repr(e)}
        payload = {
            "reason": reason,
            "ts": time.time(),
            "exception": (
                {
                    "type": type(exc).__name__,
                    "message": str(exc),
                    "traceback": "".join(
                        traceback.format_exception(type(exc), exc, exc.__traceback__)
                    ),
                }
                if exc is not None
                else None
            ),
            "fingerprint": _jsonable(self.fingerprint),
            "records": self.records,
            "memory": _jsonable(snapshot),
        }
        self.path.parent.mkdir(parents=True, exist_ok=True)
        with open(self.path, "w") as f:
            json.dump(payload, f, indent=2, default=str)
        return self.path

    # -- context manager: dump on any exception, then re-raise --------------
    def __enter__(self) -> "FlightRecorder":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None:
            try:
                path = self.dump(reason=exc_type.__name__, exc=exc)
                print(
                    f"[telemetry] flight recorder dumped to {path} "
                    f"({len(self._ring)} step records + memory census)",
                    file=sys.stderr,
                    flush=True,
                )
            except Exception:
                pass
        return False  # never swallow
