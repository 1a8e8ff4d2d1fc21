"""Find a cell's files by the names BENCHMARK.json gives, and refuse bad ones.

Nothing about a cell is coded here: a workload names its ``config`` and
``traffic``; ``configs`` gives each configuration's file; the traffic mix is
``benchmarks/traffic/<mix>.json``; a per-layer metric is
``benchmarks/metrics/<name>.py`` and a kernel law ``benchmarks/kernels/<name>.py``,
both loaded by file path (metric names may hold dots).
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class BenchmarkError(Exception):
    """A name, unit or file the benchmark cannot accept."""


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise BenchmarkError(f"{what} {name!r} is not a valid name")
    return name


def check_metric(m: dict, end_to_end: bool) -> None:
    check_name(m.get("name"), "metric")
    if not UNIT_RE.match(str(m.get("unit", ""))):
        raise BenchmarkError(f"metric {m['name']}: unit {m.get('unit')!r}")
    if m.get("better") not in ("lower", "higher"):
        raise BenchmarkError(f"metric {m['name']}: better {m.get('better')!r}")
    allowed = ("host_clock", "device_trace") if end_to_end else SOURCES
    if m.get("source") not in allowed:
        raise BenchmarkError(f"metric {m['name']}: source {m.get('source')!r}")


def load_benchmark(root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"]:
        check_metric(m, end_to_end=True)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        check_metric(m, end_to_end=False)
        if m.get("moves") not in e2e:
            raise BenchmarkError(f"metric {m['name']} moves {m.get('moves')!r}")
    for c in bench["configs"]:
        check_name(c["name"], "config")
    for w in bench["workloads"]:
        check_name(w["name"], "workload")
        check_name(w["traffic"], "traffic")
        if w["chips"] not in (1, 4):
            raise BenchmarkError(f"workload {w['name']}: chips {w['chips']!r}")
    return bench


def load_cell(bench: dict, workload: str, root: Path = ROOT) -> dict:
    """-> {"workload", "config" (the file's dict), "config_name", "traffic"
    (the mix's dict), "chips", "end_to_end", "per_layer"} for one cell."""
    check_name(workload, "workload")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise BenchmarkError(f"workload {workload}: no config {cell['config']!r}")
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic_file = BENCH_DIR / "traffic" / f"{cell['traffic']}.json"
    if not traffic_file.exists():
        raise BenchmarkError(f"no traffic mix {traffic_file}")
    traffic = json.loads(traffic_file.read_text())

    def applies(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return {
        "workload": workload,
        "config_name": cell["config"],
        "config": config,
        "traffic_name": cell["traffic"],
        "traffic": traffic,
        "chips": int(cell["chips"]),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_module(kind: str, name: str) -> ModuleType:
    """``benchmarks/<kind>/<name>.py`` by file path."""
    check_name(name, kind)
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.exists():
        raise BenchmarkError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def hf_config(config: dict) -> dict:
    """The model's own keys: the file's top level without the benchmark's."""
    own = {"source", "reduced", "assumed", "departures", "deployment", "chips",
           "program", "reference"}
    return {k: v for k, v in config.items() if k not in own}


def program_hf_config(config: dict) -> dict:
    """What ``model.hf_config`` of the program's YAML holds: the source's keys
    plus the mapped keys the program reads them under (``program.hf_map``)."""
    hf = hf_config(config)
    hf.update(config["program"].get("hf_map", {}))
    return hf
