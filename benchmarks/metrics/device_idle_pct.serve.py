"""Idle share of the device in the traced part of the serving window:
1 - union of device-op intervals / window, per device, the worst device.
Moves tpot_p50_s."""

from benchmarks.metrics._common import idle_pct


def read(run: dict):
    return idle_pct(run) if run["artefacts"]["kind"] == "serve" else None
