"""The reader of ``paged_grid_live_pct``: the counter's two stats as a share,
nothing from a program that does not write them."""

import json

import pytest

from benchmarks.harness import loader, program_trace


def _rows():
    base = {"admitted": 0, "chunks": 1, "decoded": 3, "context_tokens": 9, "finished": 0}
    return [dict(base, attn_grid_steps=4352, attn_live_steps=230),
            dict(base, decoded=0, attn_grid_steps=0, attn_live_steps=0),  # chunks only
            dict(base, attn_grid_steps=4352, attn_live_steps=250)]


def test_reader_gives_live_over_grid_of_the_decoding_iterations(monkeypatch, capsys):
    reader = loader.load_module("metrics", "paged_grid_live_pct")
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: _rows())
    assert reader.read({}) == pytest.approx(100.0 * 480 / 8704)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["iterations"] == 2 and note["grid_steps_per_layer"] == 4352
    assert note["live_steps_per_layer"] == 240


def test_reader_leaves_out_a_program_without_the_counter(monkeypatch):
    reader = loader.load_module("metrics", "paged_grid_live_pct")
    old = [{k: v for k, v in r.items() if not k.startswith("attn_")} for r in _rows()]
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: old)
    assert reader.read({}) is None  # the parent commit: no such stat
    idle = [dict(r, attn_grid_steps=0, attn_live_steps=0) for r in _rows()]
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: idle)
    assert reader.read({}) is None  # the gather backend: the kernel never ran
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: None)
    assert reader.read({}) is None  # no trace


def test_the_metric_is_declared_for_both_serve_cells():
    bench = loader.load_benchmark()
    for cell in ("serve-chat-minimax-m2", "serve-chat-lfm2-8b-a1b"):
        entry = next(m for m in loader.load_cell(bench, cell)["per_layer"]
                     if m["name"] == "paged_grid_live_pct")
        assert entry["moves"] == "tpot_p50_s" and entry["source"] == "program_counter"
        assert entry["layer"] == "kernels"
    names = {m["name"] for m in loader.load_cell(bench, "train-30b-a3b")["per_layer"]}
    assert "paged_grid_live_pct" not in names
