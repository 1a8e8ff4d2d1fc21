"""The documents point at the one benchmark the repo has.

`PERF.md` must describe what `BENCHMARK.json` declares (every cell and
configuration in §4, every end-to-end metric in §2), and neither README.md
nor docs/*.md (docs/history/ is the archive) may send a reader to a
harness, a tuning table, an environment switch or a config key that PR 30
removed: a document that names a way to measure which no longer exists is
how the pre-ledger numbers outlived their harnesses.
"""

import glob
import json
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# files, environment variables and config keys PR 30 deleted
REMOVED = (
    "bench.py",
    "bench_moe_only.py",
    "kernel_bench.py",
    "profile_moe.py",
    "profile_pp.py",
    "ops/autotune.py",
    "autotune_defaults.json",
    "AUTOMODEL_PAGED_DECODE",
    "AUTOMODEL_AUTOTUNE_TABLE",
    "validate_bench_result",
    "bench_requests",
    "bench_rate",
    "bench_prompt_len_min",
    "bench_prompt_len_max",
    "bench_max_new_tokens",
    "bench_replicas",
    "bench_num_blocks",
    "BENCH_chip.json",
)


def _read(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return f.read()


def _section(doc: str, number: int) -> str:
    """The text of PERF.md's `## <number>. ...` section."""
    m = re.search(rf"^## {number}\. .*?(?=^## \d+\. |\Z)", doc, re.M | re.S)
    assert m, f"PERF.md has no section {number}"
    return m.group(0)


def test_perf_md_describes_what_the_benchmark_declares():
    bench = json.loads(_read("BENCHMARK.json"))
    doc = _read("PERF.md")
    cells = _section(doc, 4)
    names = [w["name"] for w in bench["workloads"]] + [c["name"] for c in bench["configs"]]
    missing = [n for n in names if f"`{n}`" not in cells]
    assert not missing, f"PERF.md §4 names no {missing}"
    metrics = _section(doc, 2)
    missing = [m["name"] for m in bench["end_to_end"] if f"`{m['name']}`" not in metrics]
    assert not missing, f"PERF.md §2 names no {missing}"


def test_documents_name_nothing_pr30_removed():
    paths = [os.path.join(REPO, "README.md")] + sorted(
        glob.glob(os.path.join(REPO, "docs", "*.md"))
    )
    assert len(paths) > 3
    found = []
    for path in paths:
        text = open(path).read()
        for name in REMOVED:
            # `bench.py` must not match `llama_dense_bench.yaml` or `kernel_bench.py`
            if re.search(rf"(?<![A-Za-z0-9_]){re.escape(name)}(?![A-Za-z0-9_])", text):
                found.append(f"{os.path.relpath(path, REPO)}: {name}")
    assert not found, found
