"""Idle share of the device in the traced part of the training window:
1 - union of device-op intervals / window, per device, the worst device.
Moves train_tokens_per_s_per_chip."""

from benchmarks.metrics._common import idle_pct


def read(run: dict):
    return idle_pct(run) if run["artefacts"]["kind"] == "train" else None
