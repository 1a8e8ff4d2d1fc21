"""The trace reduction, on a small recorded trace of the chip (eight steps of
the train cell, ``--trace 1``, TPU v5 lite) and on hand-made events."""

import gzip
from pathlib import Path

import pytest

from benchmarks.harness import loader, trace

DATA = Path(__file__).parent / "data" / "train_step.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduction(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "train_step.xplane.pb"
    path.write_bytes(gzip.decompress(DATA.read_bytes()))
    return trace.reduce_file(path)


def test_recorded_trace_busy_idle_and_modules(reduction):
    dev = reduction["devices"][0]
    assert list(reduction["devices"]) == [0]
    assert 2.0 < dev["window_s"] < 2.3
    assert dev["busy_s"] <= dev["window_s"]
    idle = 1 - dev["busy_s"] / dev["window_s"]
    assert 0.005 < idle < 0.05  # the chip read 1.9 %
    steps = trace.module_durations(reduction, r"^jit_step_fn$")
    assert len(steps) == 8 and all(0.25 < s < 0.29 for s in steps)
    assert dev["collective_s"] == 0  # one chip


def test_recorded_trace_kernel_sums(reduction):
    expert = loader.load_module("kernels", "expert_mlp")
    flash = loader.load_module("kernels", "flash_attn")
    s, n = trace.op_time(reduction, expert.TRACE_PATTERN)
    assert n == 64 and 0.28 < s < 0.33  # 8 steps x (2 fwd + 3 bwd + 3 gmm)
    s, n = trace.op_time(reduction, flash.TRACE_PATTERN)
    assert n == 32 and 0.10 < s < 0.14  # 8 steps x (2 fwd + dq + dkv)
    inside = trace.module_op_time(reduction, r"^jit_step_fn$", expert.FWD_PATTERN)
    assert 0.08 < inside < 0.11


def test_recorded_trace_gaps_are_named_by_the_harness_span(reduction):
    names = [n for n, _ in reduction["idle_gaps"]]
    assert names and names[0] == "input"  # the device waits while the host fetches a batch
    assert {n for _, _, n in reduction["host_spans"]} == {"input", "train_step"}


def _op(s, e, name):
    return (s, e, name)


def test_two_devices_are_reduced_apart_and_exposed_collective_is_counted():
    events = {
        "devices": {
            0: {"ops": [_op(0.0, 1.0, "fusion.1"), _op(0.5, 2.0, "all-to-all.3"),
                        _op(3.0, 4.0, "fusion.2")],
                "modules": [(0.0, 4.0, "jit_step_fn(123)")]},
            1: {"ops": [_op(0.0, 4.0, "fusion.1")], "modules": [(0.0, 4.0, "jit_step_fn(123)")]},
        },
        "host": [(0.0, 10.0, "train_step"), (2.1, 2.9, "input")],
    }
    red = trace.reduce_events(events)
    d0, d1 = red["devices"][0], red["devices"][1]
    assert d0["busy_s"] == pytest.approx(3.0) and d0["window_s"] == pytest.approx(4.0)
    assert d1["busy_s"] == pytest.approx(4.0)  # never merged into one union
    assert d0["collective_s"] == pytest.approx(1.5)
    assert d0["collective_exposed_s"] == pytest.approx(1.0)  # 1.0-2.0: no compute beside it
    assert red["idle_gaps"][0] == ("input", pytest.approx(1.0))
    assert d0["module_ops"]["jit_step_fn"]["fusion.1"] == pytest.approx(1.0)


def test_short_name_keeps_the_kernels_own_name():
    text = "%fused_expert_mlp_fwd.12 = bf16[65536,2048]{1,0} custom-call(s32[384]{0} %c)"
    assert trace.short_name(text) == "fused_expert_mlp_fwd.12"
