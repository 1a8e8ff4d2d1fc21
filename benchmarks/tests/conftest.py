"""The harness's own tests: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
They are not part of the repository's tier-1 suite."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import pytest


@pytest.fixture(autouse=True)
def _compile_counters_registered():
    """As ``benchmarks/run.py`` does before it hands a cell to its driver."""
    from automodel_tpu.telemetry import compile_events

    compile_events._ensure_registered()
