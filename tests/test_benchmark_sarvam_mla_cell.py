"""Tier-1 runs the tests of what the sarvam-105b cell adds to the benchmark
(benchmarks/tests/test_sarvam_mla_cell.py: the cell as the loader sees it, the
configuration against the source's keys, the traffic mix in which the seed
cannot change the work, the reference's shapes and the two laws against the
published counts, the readers of the scopes and counters this family's
programs write, the rehearsal), from the file they live in, as
tests/test_benchmark_xing4_cell.py does."""

import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

_spec = importlib.util.spec_from_file_location(
    "benchmarks_tests_sarvam_mla_cell", ROOT / "benchmarks" / "tests" / "test_sarvam_mla_cell.py")
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
globals().update({k: v for k, v in vars(_module).items() if k.startswith("test_")})

# The rehearsal is one subprocess that compiles the serving programs and the
# float32 reference at tiny sizes into a cold cache and runs a 2 s window: ~20 s
# on a builder's machine (PR 47). It states its own budget all the same: a
# busy machine's cold compile is what tests/conftest.py's 180 s is not for.
test_the_rehearsal_runs_to_a_result_line = pytest.mark.budget(600)(test_the_rehearsal_runs_to_a_result_line)  # noqa: F821
