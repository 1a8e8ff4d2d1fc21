#!/usr/bin/env python
"""Validate + summarize a train_metrics.jsonl.

Thin CLI wrapper over automodel_tpu/telemetry/report.py (which
`automodel_tpu report` also uses): strict-JSON schema lint (bare NaN/Infinity
tokens, null-without-marker, step monotonicity, request-tracing span schema
and negative durations) plus a tps/step-time/loss summary table with
per-stage span p50/p99 rollups. To JOIN span records across multiple
processes' files into per-request waterfalls, use `automodel_tpu trace`.

    python tools/metrics_report.py train_metrics.jsonl [--strict]

Exit code 1 when --strict and any schema problem was found (or when the
file yielded no parseable records at all).
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from automodel_tpu.telemetry.report import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
