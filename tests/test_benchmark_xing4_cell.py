"""Tier-1 runs the tests of what the Xing4.0 cell adds to the benchmark
(benchmarks/tests/test_xing4_cell.py: the cell as the loader sees it, the
configuration against the source's keys, the reference's shapes and the
residual path's law against the published counts, the readers of the scopes
and the counter this family's programs write, the rehearsal), from the file
they live in, as tests/test_benchmark_kimi_linear_cell.py does."""

import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

_spec = importlib.util.spec_from_file_location(
    "benchmarks_tests_xing4_cell", ROOT / "benchmarks" / "tests" / "test_xing4_cell.py")
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
globals().update({k: v for k, v in vars(_module).items() if k.startswith("test_")})
