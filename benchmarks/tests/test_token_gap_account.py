"""The readers of the records' ``decode_account``: sums over the requests that
finished before the profiler started, nothing from a program that does not
write the field."""

import json

import pytest

from benchmarks.harness import loader

SERVE_CELLS = ("serve-chat-minimax-m2", "serve-chat-lfm2-8b-a1b")


def _record(i: int, t_done: float, **over) -> dict:
    """Request ``w<i>``: 11 tokens, a gap of 10 ms (every fifth one 20 ms)."""
    gap = 0.02 if i % 5 == 0 else 0.01
    seconds = {"decode_wait": 4 * gap, "first_token_wait": gap, "decode_launch": 2 * gap,
               "outside_step": 2 * gap, "step": gap}
    return dict(
        request_id=f"w{i}", completion_reason="length", n_generated=11, t_done=t_done,
        decode_tps=10 / sum(seconds.values()),
        decode_account={"s": seconds, "n": {
            "iterations": 12, "chunks": 3, "first_token_waits": 1, "decode_launched": 10,
            "decode_launched_ahead": 9, "decoded": 40 + i % 2, "context_tokens": 4000,
            "expert_live_units": 300, "expert_grid_units": 2580, "discarded_rows": 0}},
        **over)


def _run(records: list, trace_t0=100.0) -> dict:
    return {"artefacts": {"kind": "serve", "records": records}, "reduction": None,
            "trace_window": (trace_t0, None if trace_t0 is None else trace_t0 + 4.0)}


def _records() -> list:
    clean = [_record(i, t_done=50.0 + i) for i in range(24)]
    return clean + [
        _record(30, t_done=100.5),  # finished while the profiler ran
        _record(31, t_done=140.0),  # and after it: behind the trace stop
        dict(_record(32, t_done=60.0), request_id="r3"),  # the ramp's, not the window's
        dict(_record(33, t_done=60.0), request_id="warm0"),
        dict(_record(34, t_done=60.0), completion_reason="engine_stall"),
        dict(_record(35, t_done=60.0), n_generated=1, decode_tps=0.0),  # no token gap
        {k: v for k, v in _record(36, t_done=60.0).items() if k != "decode_account"},
    ]


@pytest.mark.parametrize("name,value", [
    ("token_gap_device_wait_pct", 50.0),  # (4 + 1) of the 10 parts of every gap
    ("token_gap_outside_step_pct", 20.0),
    ("prefill_chunks_per_token_gap", 0.3),  # 3 chunks over 10 gaps
    ("decode_rows_per_token_gap", 4.05),  # 40 or 41 rows over 10 launches
])
def test_record_readers_sum_over_the_clean_requests(name, value, capsys):
    reader = loader.load_module("metrics", name)
    assert reader.read(_run(_records())) == pytest.approx(value)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["token_gap_account"] == name
    assert (note["requests"], note["token_gaps"]) == (24, 240)
    # 5 of the 24 have the 20 ms gap: the mean is theirs too, the middle is not
    assert note["mean_gap_ms"] == pytest.approx((19 * 10 + 5 * 20) / 24)
    assert sum(note["ms_per_token"].values()) == pytest.approx(note["mean_gap_ms"])
    assert note["ms_per_token"]["decode_wait"] == pytest.approx(0.4 * note["mean_gap_ms"])
    assert note["requests_p40_to_p60"] == 19
    assert sum(note["ms_per_token_p40_to_p60"].values()) == pytest.approx(10.0)
    assert note["largest_residual"] < 1e-12
    # a record whose seconds do not sum to its decode_s shows in the residual
    off = _records()
    off[3]["decode_account"]["s"]["record"] = 0.01 * 0.1
    reader.read(_run(off))
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["largest_residual"] == pytest.approx(0.01)
    # no trace window (an untraced run): every finished window request is clean
    reader.read(_run(_records(), trace_t0=None))
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["requests"] == 26
    # under 20 clean requests, a program that writes no such field (the parent
    # commit), a train cell: nothing, and no error
    assert reader.read(_run(_records()[5:])) is None  # 19 of them
    bare = [{k: v for k, v in r.items() if k != "decode_account"} for r in _records()]
    assert reader.read(_run(bare)) is None
    assert reader.read({"artefacts": {"kind": "train"}, "reduction": None}) is None
    # declared for both serve cells, on the serve loop's layer
    bench = loader.load_benchmark()
    for cell in SERVE_CELLS:
        entry = next(m for m in loader.load_cell(bench, cell)["per_layer"] if m["name"] == name)
        assert (entry["layer"], entry["moves"], entry["source"]) == (
            "serve loop", "tpot_p50_s", "program_counter")
    assert name not in {m["name"] for m in loader.load_cell(bench, "train-30b-a3b")["per_layer"]}


def test_rows_reader_needs_a_decode_launch():
    reader = loader.load_module("metrics", "decode_rows_per_token_gap")
    records = _records()
    for r in records:
        if "decode_account" in r:
            r["decode_account"]["n"]["decode_launched"] = 0  # a speculative engine
    assert reader.read(_run(records)) is None


def test_chunks_reader_prices_the_device_s_part_of_a_gap(monkeypatch, capsys):
    """With the traced medians of the two programs the notes line says what of
    a token gap the device accounts for, and what is left to the host."""
    reader = loader.load_module("metrics", "prefill_chunks_per_token_gap")
    monkeypatch.setattr(reader, "median_module_ms",
                        lambda run, pattern: {"^jit_step$": 4.0, "^jit_chunk$": 16.0}[pattern])
    assert reader.read(_run(_records())) == pytest.approx(0.3)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["device_ms_per_token"] == pytest.approx(4.0 + 0.3 * 16.0)
    assert note["host_exposed_ms_per_token"] == pytest.approx(
        note["mean_gap_ms"] - note["device_ms_per_token"])
