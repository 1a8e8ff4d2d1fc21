"""Persistent per-chip-kind kernel autotune registry.

The hand-scheduled Pallas kernels (grouped matmuls, the fused expert MLP and
its manual backward, splash/block flash attention) each have tile/block
shapes that decide whether the MXU runs full or half-empty — the classic
block-shape-tuning problem FlashAttention-2 and the megablocks grouped-GEMM
line solved by matching tiles to the *problem* shape instead of one static
default. PROFILE_MOE_r05.md is the local evidence: gmm2 runs 84.3 TFLOP/s
vs gmm1's 107.0 on the same chip purely from tile choice, and splash at
head_dim 64 runs at 30% of peak with blocks sized for head_dim 128.

This module is the measured-once, persisted table those kernels consult:

- ``autotune_defaults.json`` (committed, next to this file) holds per
  chip-kind entries — the v5e defaults ship in-tree so a fresh checkout
  gets tuned shapes without a sweep.
- ``AUTOMODEL_AUTOTUNE_TABLE=<path.json>`` layers a runtime table (same
  schema) over the defaults — the file ``tools/kernel_bench.py`` writes
  under a run's ``output_dir``. Runtime entries win.
- ``tools/kernel_bench.py --write-defaults`` merges a sweep's winners back
  into the committed defaults for the measured chip kind.

Entries are plain dicts; the consuming kernel validates them (VMEM budget,
alignment) and falls back to its built-in heuristic on anything infeasible —
a stale or hand-edited table can cost performance, never correctness.

Table schema::

    {"format_version": 1,
     "chips": {"<device_kind>": {"<entry key>": {..., "source": "..."}}}}

Entry keys are built by the ``*_key`` helpers below so the sweep driver and
the kernels can never disagree on the spelling.
"""

from __future__ import annotations

import json
import os
import threading
from pathlib import Path
from typing import Any, Optional

DEFAULTS_PATH = Path(__file__).with_name("autotune_defaults.json")
ENV_TABLE = "AUTOMODEL_AUTOTUNE_TABLE"
FORMAT_VERSION = 1

_lock = threading.Lock()
# (path, mtime) -> parsed chips dict; invalidated explicitly (tests, sweeps)
_cache: dict[str, tuple[float, dict]] = {}


# -- entry keys (one spelling, shared with tools/kernel_bench.py) -----------


def tgmm_key(k: int, n: int, dtype: Any) -> str:
    """Transposed grouped matmul [M,K]x[M,N] -> [G,K,N]."""
    return f"tgmm:k{k}:n{n}:{_dt(dtype)}"


def gmm_key(k: int, n: int, dtype: Any, transpose_rhs: bool) -> str:
    """Grouped matmul [M,K]@[G,K,N] (or [G,N,K] transposed)."""
    return f"gmm:k{k}:n{n}:{_dt(dtype)}:{'t' if transpose_rhs else 'n'}"


def moe_bwd_gu_key(d: int, i: int, dtype: Any) -> str:
    """Fused activation-backward + dual tgmm (dWg/dWu/dgb/dub)."""
    return f"moe_bwd_gu:d{d}:i{i}:{_dt(dtype)}"


def moe_bwd_dwd_key(i: int, d: int, dtype: Any) -> str:
    """Fused mid-recompute + down-proj transpose GEMM (dWd/ddb)."""
    return f"moe_bwd_dwd:i{i}:d{d}:{_dt(dtype)}"


def moe_bwd_dx_key(d: int, i: int, dtype: Any) -> str:
    """Fused activation-backward + dual weight-transpose GEMM (dx)."""
    return f"moe_bwd_dx:d{d}:i{i}:{_dt(dtype)}"


def attn_key(head_dim: int, window: Optional[int], causal: bool) -> str:
    """Flash-attention backend + block selection per problem shape."""
    return f"attn:h{head_dim}:w{window or 0}:{'c' if causal else 'nc'}"


def paged_key(head_dim: int, block_size: int, kv_dtype: str) -> str:
    """Paged-attention decode backend per (head_dim, KV block size, pool
    dtype): ``backend`` ∈ {"fused" (ops/paged_attention.py Pallas kernel),
    "gather" (XLA gather → sdpa_decode → scatter baseline)} — raced by
    tools/kernel_bench.py, consulted by serving/engine.py when
    ``serving.decode_kernel: auto``."""
    return f"paged:h{head_dim}:bs{block_size}:{kv_dtype}"


def _dt(dtype: Any) -> str:
    import jax.numpy as jnp

    return jnp.dtype(dtype).name


# -- chip identity ----------------------------------------------------------


def chip_key() -> str:
    """``jax.Device.device_kind`` of the first device ("TPU v5 lite", "cpu",
    ...). A backend that cannot start raises here as it would one line
    later in the kernel: there is no device to pick tiles for. Matching
    against the table is exact-then-prefix, same scheme as
    utils.flops_utils."""
    import jax

    return jax.devices()[0].device_kind


def _match_chip(chips: dict, chip: str) -> Optional[dict]:
    if chip in chips:
        return chips[chip]
    low = chip.lower()
    for k, v in chips.items():
        if low.startswith(k.lower()) or k.lower().startswith(low):
            return v
    return None


# -- table loading / lookup -------------------------------------------------


def _load(path: Path) -> dict:
    """chips dict of one table file, mtime-cached; unreadable/garbage files
    read as empty (a broken table must cost tuning, not training)."""
    key = str(path)
    try:
        mtime = path.stat().st_mtime
    except OSError:
        return {}
    with _lock:
        hit = _cache.get(key)
        if hit is not None and hit[0] == mtime:
            return hit[1]
    try:
        raw = json.loads(path.read_text())
        chips = raw.get("chips", {}) if isinstance(raw, dict) else {}
        if not isinstance(chips, dict):
            chips = {}
    except Exception:
        chips = {}
    with _lock:
        _cache[key] = (mtime, chips)
    return chips


def clear_cache() -> None:
    with _lock:
        _cache.clear()


def _tables() -> list[dict]:
    """Chips dicts in *ascending* precedence (later wins)."""
    out = [_load(DEFAULTS_PATH)]
    env = os.environ.get(ENV_TABLE)
    if env:
        out.append(_load(Path(env)))
    return out


def lookup(key: str, chip: Optional[str] = None) -> Optional[dict]:
    """The entry for ``key`` on ``chip`` (default: the running chip kind), or
    None — the caller then uses its built-in heuristic. Runtime table
    (``AUTOMODEL_AUTOTUNE_TABLE``) entries shadow committed defaults."""
    chip = chip if chip is not None else chip_key()
    entry: Optional[dict] = None
    for chips in _tables():
        per_chip = _match_chip(chips, chip)
        if per_chip and key in per_chip and isinstance(per_chip[key], dict):
            entry = per_chip[key]
    return entry


def table_info(chip: Optional[str] = None) -> dict:
    """Provenance stamp for bench/profile artifacts: which chip key resolved,
    how many DISTINCT entries apply (runtime-shadowed defaults counted
    once), and which files supplied them."""
    chip = chip if chip is not None else chip_key()
    sources = []
    keys: set[str] = set()
    paths = [DEFAULTS_PATH] + (
        [Path(os.environ[ENV_TABLE])] if os.environ.get(ENV_TABLE) else []
    )
    for p in paths:
        per_chip = _match_chip(_load(p), chip)
        if per_chip:
            sources.append(str(p))
            keys.update(per_chip)
    return {"chip": chip, "entries": len(keys), "sources": sources}


# -- recording (tools/kernel_bench.py) --------------------------------------


def save_table(path: str | Path, entries: dict, chip: Optional[str] = None) -> Path:
    """Write (or merge into) a table file at ``path`` with ``entries`` for
    ``chip``. Existing entries for other chips/keys in the file survive."""
    path = Path(path)
    chip = chip if chip is not None else chip_key()
    existing: dict = {}
    if path.exists():
        try:
            existing = json.loads(path.read_text())
        except Exception:
            existing = {}
    if not isinstance(existing, dict):
        existing = {}
    chips = existing.get("chips")
    if not isinstance(chips, dict):
        chips = {}
    per_chip = dict(chips.get(chip) or {})
    per_chip.update(entries)
    chips[chip] = per_chip
    out = {"format_version": FORMAT_VERSION, "chips": chips}
    if "comment" in existing:
        out["comment"] = existing["comment"]
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(path.suffix + ".tmp")
    tmp.write_text(json.dumps(out, indent=2, sort_keys=True) + "\n")
    tmp.replace(path)
    clear_cache()
    return path


# -- entry validation helpers ----------------------------------------------


def valid_tiles(
    entry: Optional[dict],
    names: tuple[str, ...],
    budget_fn,
    *,
    multiple: int = 128,
) -> Optional[tuple[int, ...]]:
    """Extract ``names`` (e.g. ("tm", "tk", "tn")) from an entry, enforcing
    positive ints, ``multiple``-alignment, and the caller's feasibility
    check: ``budget_fn(*tiles) -> bool`` (typically a VMEM-budget model;
    pass None to skip). A falsy result or an exception reads as infeasible.
    → tiles tuple, or None — the caller falls back to its heuristic."""
    if not entry:
        return None
    tiles = []
    for n in names:
        v = entry.get(n)
        if not isinstance(v, int) or v <= 0 or v % multiple:
            return None
        tiles.append(v)
    try:
        if budget_fn is not None and not budget_fn(*tiles):
            return None
    except Exception:
        return None
    return tuple(tiles)
