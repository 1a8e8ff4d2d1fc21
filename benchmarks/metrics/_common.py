"""Shared by the metric readers: nothing metric-specific lives here."""

from __future__ import annotations

import json
import statistics

from benchmarks.harness import loader, trace


TRAIN_MODULE = r"^jit_step_fn$"
PREFILL_MODULE = r"^jit_chunk$"
DECODE_MODULE = r"^jit_step$"


def hf(run: dict) -> dict:
    return loader.hf_config(run["cell"]["config"])


def kernel(name: str):
    return loader.load_module("kernels", name)


def median_module_ms(run: dict, pattern: str):
    red = run["reduction"]
    if not red or not red["devices"]:
        return None
    durs = trace.module_durations(red, pattern)
    return statistics.median(durs) * 1e3 if durs else None


def module_count(run: dict, pattern: str) -> int:
    red = run["reduction"]
    return len(trace.module_durations(red, pattern)) if red and red["devices"] else 0


def idle_pct(run: dict):
    """1 - busy / window, per device, the worst device."""
    red = run["reduction"]
    if not red or not red["devices"]:
        return None
    return max(100.0 * (1.0 - d["busy_s"] / d["window_s"]) for d in red["devices"].values())


def say(**kv) -> None:
    print(json.dumps(kv), flush=True)
