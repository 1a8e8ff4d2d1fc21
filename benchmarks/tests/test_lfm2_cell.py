"""What the LFM2 cell adds to the benchmark: the reader of scopes the frozen vocabulary lacks (on synthetic
device ops), the state counter's reader, and the cell as ``load_cell`` sees it."""

import json

import pytest

from benchmarks.harness import loader, program_trace

CELL = "serve-chat-lfm2-8b-a1b"


def _ops():
    """Two ``jit_step`` runs and one ``jit_chunk`` on one device, picoseconds:
    (start, duration, name, tf_op, source)."""
    step = "jit(step)/layers/conv/dot_general"
    return {0: {
        "modules": [(0, 1000, "jit_step(1)"), (2000, 1000, "jit_step(1)"), (4000, 1000, "jit_chunk(2)")],
        "ops": [
            (0, 300, "fusion.1", step, ""),
            (100, 100, "fusion.2", "jit(step)/layers/conv/vmap()/gather", ""),  # nested: self time
            (400, 200, "fusion.3", "jit(step)/kv_write/state_write/concatenate", ""),
            (600, 100, "fusion.4", "jit(step)/layers/attn/dot_general", ""),
            (2000, 500, "fusion.1", step, ""),
            (4000, 700, "fusion.9", "jit(chunk)/layers/conv/dot_general", ""),
        ],
    }}


def test_scope_segments_reader_on_synthetic_ops(monkeypatch, tmp_path):
    reader = loader.load_module("metrics", "_scope_segments")
    monkeypatch.setattr(program_trace, "read_device_ops", lambda path: _ops())
    reader._per_run_seconds.cache_clear()
    conv = reader._per_run_seconds(str(tmp_path), r"^jit_step$", ("conv",))
    assert conv == pytest.approx((300e-12, 500e-12))  # the nested op counted once
    state = reader._per_run_seconds(str(tmp_path), r"^jit_step$", ("state_write",))
    assert state == pytest.approx((200e-12, 0.0))
    both = reader._per_run_seconds(str(tmp_path), r"^jit_step$", ("conv", "state_write"))
    assert both == pytest.approx((500e-12, 500e-12))  # what conv_mixer_ms.serve reads
    assert reader._per_run_seconds(str(tmp_path), r"^jit_chunk$", ("conv",)) == pytest.approx((700e-12,))
    # a program that never writes the scope (an older commit): nothing, not zeros
    assert reader._per_run_seconds(str(tmp_path), r"^jit_step$", ("no_such_scope",)) == ()
    reader._per_run_seconds.cache_clear()


def test_state_counter_reader_leaves_an_older_program_out(monkeypatch):
    reader = loader.load_module("metrics", "state_resets_per_step")
    rows = [{"admitted": 1, "chunks": 1, "decoded": 3, "context_tokens": 9, "finished": 0,
             "state_resets": 1, "state_slots": 2},
            {"admitted": 0, "chunks": 1, "decoded": 3, "context_tokens": 12, "finished": 1,
             "state_resets": 0, "state_slots": 3}]
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: rows)
    assert reader.read({}) == 0.5
    old = [{k: v for k, v in r.items() if not k.startswith("state_")} for r in rows]
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: old)
    assert reader.read({}) is None
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: None)
    assert reader.read({}) is None


def test_the_cell_as_the_loader_sees_it():
    bench = loader.load_benchmark()
    cell = loader.load_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "open_loop"
    assert cell["reference"].__name__.endswith("lfm2_moe")
    names = {m["name"] for m in cell["per_layer"]}
    assert {"conv_mixer_ms.serve", "state_resets_per_step", "paged_attn_roofline",
            "expert_mlp_ms.serve"} <= names
    assert {m["name"] for m in cell["end_to_end"]} == {"tpot_p50_s", "setup_s"}
    # every serve metric of the other serve cell is reported here too
    other = {m["name"] for m in loader.load_cell(bench, "serve-chat-minimax-m2")["per_layer"]}
    assert other <= names
    # the mix is the other serve cell's but for its rate
    mine, theirs = (json.loads((loader.BENCH_DIR / "traffic" / f"{n}.json").read_text())
                    for n in ("chat-open-loop-lfm2", "chat-open-loop"))
    for key in ("prompt_tokens", "output_tokens", "ramp_s", "drain_s", "check_requests",
                "same_work_every_seed", "generator"):
        assert mine[key] == theirs[key], key
    serving = cell["config"]["program"]["serving"]
    assert serving["prefix_cache"] is False and serving["decode_kernel"] == "fused"
    # every leaf the program's tree has gets a rule (the conv's taps over the tap axis)
    init = cell["config"]["reference"]["init"]
    assert init["conv/weight"] == {"rule": "fan_in", "axis": -1}
