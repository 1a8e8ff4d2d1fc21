"""Tier-1 runs the benchmark's program-trace and counter reader tests too.

``benchmarks/tests`` is the harness's own suite and is not collected by
``pytest tests/``; the reader there is the other half of the spans and scopes
this repository's program writes (tests/test_step_spans.py,
tests/test_step_scopes.py), so its tests are run from here as well, from the
file they live in.
"""

import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

FIXTURES = ("train_xplane", "train_ops", "serve_xplane")
# the trace reader, and the readers of the counters the engine writes into
# ``serve.counts``: ``attn_grid_steps`` / ``attn_live_steps`` (PR 29),
# ``expert_grid_units`` / ``expert_live_units`` (PR 34) and ``decode_launched``
# / ``decode_launched_ahead`` / ``discarded_rows`` (PR 38); of the records'
# ``decode_account`` and the ``serve.decode_h2d`` span (PR 39)
for _name in ("program_trace", "paged_grid_live_pct", "expert_grid_live_pct",
              "decode_launch_ahead_pct", "token_gap_account", "decode_h2d_ms"):
    # ``benchmarks_tests_program_trace`` is the name that file looks for to
    # bring the architecture-dispatch cases along
    _spec = importlib.util.spec_from_file_location(
        f"benchmarks_tests_{_name}", ROOT / "benchmarks" / "tests" / f"test_{_name}.py")
    _module = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_module)
    # the tests and the fixtures they ask for, under the names pytest looks for
    globals().update({k: v for k, v in vars(_module).items()
                      if k.startswith("test_") or k in FIXTURES})
