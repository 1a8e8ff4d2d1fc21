"""BENCHMARK.json and the files it names: found by name, bad ones refused."""

import json
from pathlib import Path

import pytest

from benchmarks.harness import loader

ROOT = Path(__file__).resolve().parents[2]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_every_cell_metric_kernel_and_traffic_file_is_found_by_name():
    bench = loader.load_benchmark()
    for w in bench["workloads"]:
        cell = loader.load_cell(bench, w["name"])
        assert cell["traffic"]["kind"] in ("train", "serve")
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in bench["per_layer"]:
        assert callable(loader.load_module("metrics", m["name"]).read)


@pytest.mark.parametrize("bad", ["has space", "a/b", "", "x" * 65, "név"])
def test_bad_names_are_refused(bad):
    with pytest.raises(loader.BenchmarkError):
        loader.check_name(bad, "workload")
    with pytest.raises(loader.BenchmarkError):
        loader.load_module("metrics", bad)


@pytest.mark.parametrize("field,value", [("unit", "tokens per second"), ("unit", ""),
                                          ("better", "faster"), ("source", "guess")])
def test_bad_metric_fields_are_refused(field, value):
    m = {"name": "x", "unit": "s", "better": "lower", "source": "host_clock"}
    loader.check_metric(m, end_to_end=True)
    with pytest.raises(loader.BenchmarkError):
        loader.check_metric(dict(m, **{field: value}), end_to_end=True)


def test_an_end_to_end_metric_takes_no_program_source():
    m = {"name": "x", "unit": "s", "better": "lower", "source": "program_counter"}
    loader.check_metric(m, end_to_end=False)
    with pytest.raises(loader.BenchmarkError):
        loader.check_metric(m, end_to_end=True)


def test_unknown_workload_is_refused():
    with pytest.raises(loader.BenchmarkError):
        loader.load_cell(loader.load_benchmark(), "no-such-cell")


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog is not on this machine")
def test_every_number_of_the_source_is_in_the_file_or_under_reduced():
    catalog = {}
    for line in CATALOG.read_text().splitlines():
        row = json.loads(line)
        catalog[row["source_url"]] = row["config"]
    bench = loader.load_benchmark()
    for c in bench["configs"]:
        ours = json.loads((ROOT / c["file"]).read_text())
        for key, value in catalog[c["source"]].items():
            if key in c["reduced"]:
                continue
            assert ours[key] == value, (c["name"], key)
