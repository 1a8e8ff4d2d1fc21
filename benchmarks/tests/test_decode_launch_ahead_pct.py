"""The reader of ``decode_launch_ahead_pct``: the counter's launches as a
share, nothing from a program that does not write them."""

import json

import pytest

from benchmarks.harness import loader, program_trace


def _rows():
    base = {"admitted": 0, "chunks": 0, "decoded": 3, "context_tokens": 9, "finished": 0}
    return [dict(base, decode_launched=1, decode_launched_ahead=0, discarded_rows=0),  # the first
            dict(base, decode_launched=1, decode_launched_ahead=1, discarded_rows=0),
            dict(base, chunks=1, decode_launched=1, decode_launched_ahead=1, discarded_rows=2),
            dict(base, decoded=0, decode_launched=0, decode_launched_ahead=0,
                 discarded_rows=1)]  # nothing to launch: the step in flight is read


def test_launch_ahead_reader_gives_ahead_over_launched(monkeypatch, capsys):
    reader = loader.load_module("metrics", "decode_launch_ahead_pct")
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: _rows())
    assert reader.read({}) == pytest.approx(100.0 * 2 / 3)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert (note["iterations"], note["launched"], note["launched_ahead"]) == (4, 3, 2)
    assert note["discarded_rows"] == 3


def test_launch_ahead_reader_leaves_out_a_program_without_the_counter(monkeypatch):
    reader = loader.load_module("metrics", "decode_launch_ahead_pct")
    old = [{k: v for k, v in r.items() if not k.startswith(("decode_", "discarded_"))}
           for r in _rows()]
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: old)
    assert reader.read({}) is None  # the parent commit: no such stat
    quiet = [dict(r, decode_launched=0, decode_launched_ahead=0) for r in _rows()]
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: quiet)
    assert reader.read({}) is None  # nothing decoded in the trace
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: None)
    assert reader.read({}) is None  # no trace


def test_the_launch_ahead_metric_is_declared_for_both_serve_cells():
    bench = loader.load_benchmark()
    for cell in ("serve-chat-minimax-m2", "serve-chat-lfm2-8b-a1b"):
        entry = next(m for m in loader.load_cell(bench, cell)["per_layer"]
                     if m["name"] == "decode_launch_ahead_pct")
        assert entry["moves"] == "tpot_p50_s" and entry["source"] == "program_counter"
        assert entry["layer"] == "serve loop" and entry["unit"] == "%"
        assert entry["better"] == "higher"
    names = {m["name"] for m in loader.load_cell(bench, "train-30b-a3b")["per_layer"]}
    assert "decode_launch_ahead_pct" not in names
