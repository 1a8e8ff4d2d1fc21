"""The reader of ``decode_h2d_ms``: the median of the span, nothing from a
program that does not write it."""

import json

import pytest

from benchmarks.harness import loader, program_trace


def _span(name: str, start_ms: float, ms: float) -> dict:
    return {"name": name, "start_s": 1e-3 * start_ms, "end_s": 1e-3 * (start_ms + ms),
            "stats": {}, "thread": "main#0", "parent": None}


def test_decode_h2d_reader_gives_the_span_s_median(monkeypatch, capsys):
    reader = loader.load_module("metrics", "decode_h2d_ms")
    spans = [_span("serve.step", 0, 30)]
    for i, h2d in enumerate((0.9, 1.1, 1.0, 5.0)):  # the median ignores the one stall
        spans += [_span("serve.decode_plan", 10 * i, 0.2),
                  _span("serve.decode_dispatch", 10 * i + 1, h2d + 1.6),
                  _span("serve.decode_h2d", 10 * i + 1, h2d),
                  _span("serve.decode_launch", 10 * i + 1 + h2d, 1.5)]
    monkeypatch.setattr(
        program_trace, "spans",
        lambda run, prefix: spans if any(s["name"].startswith(prefix) for s in spans) else None)
    assert reader.read({}) == pytest.approx(1.05)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["ms_median_and_count"] == {
        "serve.decode_plan": [0.2, 4], "serve.decode_h2d": [1.05, 4],
        "serve.decode_launch": [1.5, 4]}
    # the parent commit: a dispatch with no such child
    spans[:] = [s for s in spans if s["name"] in ("serve.step", "serve.decode_dispatch")]
    assert reader.read({}) is None
    monkeypatch.setattr(program_trace, "spans", lambda run, prefix: None)  # no trace
    assert reader.read({}) is None


def test_decode_h2d_ms_is_declared_for_both_serve_cells():
    bench = loader.load_benchmark()
    for cell in ("serve-chat-minimax-m2", "serve-chat-lfm2-8b-a1b"):
        entry = next(m for m in loader.load_cell(bench, cell)["per_layer"]
                     if m["name"] == "decode_h2d_ms")
        assert (entry["layer"], entry["moves"], entry["source"], entry["unit"]) == (
            "serve loop", "tpot_p50_s", "program_span", "ms")
