"""Tier-1 runs the tests of what the Xing4.0 cell adds to the benchmark
(benchmarks/tests/test_xing4_cell.py: the cell as the loader sees it, the
configuration against the source's keys, the reference's shapes and the
residual path's law against the published counts, the readers of the scopes
and the counter this family's programs write, the rehearsal), from the file
they live in, as tests/test_benchmark_kimi_linear_cell.py does."""

import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

_spec = importlib.util.spec_from_file_location(
    "benchmarks_tests_xing4_cell", ROOT / "benchmarks" / "tests" / "test_xing4_cell.py")
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
globals().update({k: v for k, v in vars(_module).items() if k.startswith("test_")})

# The rehearsal is one subprocess that compiles the harness's program and its
# float32 reference into a cold cache (a checkout's `.jax_compile_cache` is
# git-ignored) and then runs a 2 s window: 213.6 s at the driver's six workers
# (412 s on a builder's machine a third slower, PR 46), most of it two whole-
# program compiles (the unrolled layer loops, ROADMAP Design 8).
# tests/conftest.py's 180 s is for cases in this process; this one states its
# own and stays in tier-1, where it guards the cell's harness path.
test_the_rehearsal_runs_to_a_result_line = pytest.mark.budget(600)(test_the_rehearsal_runs_to_a_result_line)  # noqa: F821
