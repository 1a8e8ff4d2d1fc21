"""Tier-1 runs the tests of what the LFM2 cell adds to the benchmark
(benchmarks/tests/test_lfm2_cell.py: the readers of
the scopes and the counter stats this family's programs write), from the file
they live in, as tests/test_benchmark_readers.py does for the trace reader."""

import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

_spec = importlib.util.spec_from_file_location(
    "benchmarks_tests_lfm2_cell", ROOT / "benchmarks" / "tests" / "test_lfm2_cell.py")
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
globals().update({k: v for k, v in vars(_module).items() if k.startswith("test_")})
