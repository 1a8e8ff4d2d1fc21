"""docs/performance.md must quote the committed BENCH_chip.json VERBATIM.

ROADMAP item 3's drift guard: round 5 shipped a doc whose MoE headline
(27.1) disagreed with the committed artifact (25.51). The doc's contract —
"every number in this table is quoted VERBATIM from the committed artifact"
— is now enforced: every numeric value in BENCH_chip.json (recursively,
incl. the per-backend MoE map) must appear as the same decimal string in
docs/performance.md, so prose and artifact can never drift again. If
BENCH_chip.json is ever regenerated, this test fails until the doc table
is updated from the artifact.
"""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _numeric_leaves(obj, prefix=""):
    if isinstance(obj, bool) or obj is None:
        return
    if isinstance(obj, (int, float)):
        yield prefix, obj
    elif isinstance(obj, dict):
        for k, v in obj.items():
            yield from _numeric_leaves(v, f"{prefix}.{k}" if prefix else k)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            yield from _numeric_leaves(v, f"{prefix}[{i}]")


def test_performance_doc_quotes_bench_artifact_verbatim():
    artifact = json.loads(
        open(os.path.join(REPO, "BENCH_chip.json")).read().splitlines()[0]
    )
    doc = open(os.path.join(REPO, "docs", "performance.md")).read()
    missing = []
    for path, value in _numeric_leaves(artifact):
        text = json.dumps(value)  # the artifact's own decimal spelling
        if text not in doc:
            missing.append(f"{path} = {text}")
    assert not missing, (
        "docs/performance.md does not quote these BENCH_chip.json values "
        f"verbatim (update the doc table from the artifact): {missing}"
    )


def test_bench_artifact_is_valid_per_report_contract():
    """The committed artifact itself must satisfy the validate_bench_result
    invariant (no silent-zero / reasonless-null legs)."""
    from automodel_tpu.telemetry.report import validate_bench_result

    artifact = json.loads(
        open(os.path.join(REPO, "BENCH_chip.json")).read().splitlines()[0]
    )
    assert validate_bench_result(artifact) == []
