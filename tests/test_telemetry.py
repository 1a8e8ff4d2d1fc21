"""Telemetry subsystem: timers/profiler/logger round-trips, planted-NaN
anomaly flags, flight-recorder crash dumps, memory census, compile-event
bridge, cadence/overhead bounds, and the amortized log-window timing."""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from automodel_tpu.loggers.metric_logger import MetricLogger
from automodel_tpu.telemetry import Telemetry, TelemetryConfig, build_fingerprint
from automodel_tpu.telemetry.compile_events import CompileEventBridge
from automodel_tpu.telemetry.flight_recorder import FlightRecorder
from automodel_tpu.telemetry.memory import live_array_census, memory_snapshot
from automodel_tpu.telemetry.report import (
    lint_metrics_jsonl,
    summarize_metrics,
)
from automodel_tpu.training.timers import Timers
from automodel_tpu.training.train_state import TrainState
from automodel_tpu.training.train_step import build_train_step
from automodel_tpu.utils.profiler import ProfilerConfig, StepProfiler


# -- timers ------------------------------------------------------------------

def test_timer_drain_windows():
    t = Timers()
    for _ in range(3):
        t("a").start()
        t("a").stop()
    first = t.drain_means()
    assert "a" in first and first["a"] >= 0
    assert t.drain_means() == {}  # nothing new since last drain
    t("a").start()
    t("a").stop()
    assert "a" in t.drain_means()
    assert t.summary()["a"]["count"] == 4  # summary still sees everything


def test_timer_history_bounded_aggregates_exact():
    from automodel_tpu.training.timers import _MAX_HISTORY, Timer

    t = Timer("x")
    n = _MAX_HISTORY + 500
    for _ in range(n):
        t.start()
        t.stop()
    # raw history is capped; whole-run aggregates stay exact
    assert len(t.elapsed_history) == _MAX_HISTORY
    assert t.count == n
    s = {"mean": t.mean(), "min": t.min(), "max": t.max()}
    assert 0 <= s["min"] <= s["mean"] <= s["max"]
    # an undrained pending buffer must not grow unboundedly either
    assert len(t.drain()) <= _MAX_HISTORY


# -- profiler window containment (satellite 1) -------------------------------

class _FakeProfiler:
    def __init__(self):
        self.started = 0
        self.stopped = 0

    def start_trace(self, d):
        self.started += 1

    def stop_trace(self):
        self.stopped += 1


def test_step_profiler_opens_mid_window_on_resume(monkeypatch):
    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    prof = StepProfiler(ProfilerConfig(enabled=True, start_step=3, end_step=6))
    # resumed-from-checkpoint run first sees step 4 (> start_step)
    prof.on_step(4)
    assert fake.started == 1, "trace must open inside the window, not only at =="
    prof.on_step(5)
    prof.on_step(6)
    assert fake.stopped == 1
    # past the window: never reopens
    prof.on_step(7)
    assert fake.started == 1


# -- metric logger strict JSON (satellite 2) ---------------------------------

class _CaptureSink:
    def __init__(self):
        self.records = []

    def log(self, rec, step=None):
        self.records.append(rec)


def test_metric_logger_nonfinite_and_ts(tmp_path):
    sink = _CaptureSink()
    ml = MetricLogger(str(tmp_path / "m.jsonl"), sinks=[sink])
    ml.log(
        {
            "loss": float("nan"),
            "grad_norm": float("inf"),
            "tps": 123.0,
            "per_layer": [1.0, float("nan")],
        },
        step=3,
    )
    ml.close()
    line = (tmp_path / "m.jsonl").read_text().splitlines()[0]
    # strict parse: no bare NaN/Infinity tokens
    rec = json.loads(line, parse_constant=lambda t: pytest.fail(f"bare {t} token"))
    assert rec["loss"] is None and rec["loss_nonfinite"] is True
    assert rec["grad_norm"] is None and rec["grad_norm_nonfinite"] is True
    assert rec["per_layer"] == [1.0, None] and rec["per_layer_nonfinite"] is True
    assert rec["tps"] == 123.0 and "tps_nonfinite" not in rec
    assert rec["step"] == 3 and "ts" in rec
    # sinks see the caller's record — NaN preserved, injected ts absent
    (srec,) = sink.records
    assert "ts" not in srec
    assert math.isnan(srec["loss"])


def test_metric_logger_lints_clean(tmp_path):
    ml = MetricLogger(str(tmp_path / "m.jsonl"))
    ml.log({"loss": 1.5, "tps": 10.0}, step=1)
    ml.log({"loss": float("nan")}, step=2)
    ml.close()
    records, problems = lint_metrics_jsonl(str(tmp_path / "m.jsonl"))
    assert len(records) == 2 and problems == []
    s = summarize_metrics(records)
    assert s["train_steps_logged"] == 2 and s["first_loss"] == 1.5


# -- in-step anomaly flags (tentpole pillar 2) -------------------------------

def _toy_step(anomaly_flags=True):
    def loss_fn(params, mb):
        loss_sum = jnp.sum(params["w"]["a"] * mb["x"]) + jnp.sum(params["v"] * mb["x"][:2])
        return loss_sum, jnp.int32(mb["x"].shape[0])

    opt = optax.sgd(1e-2)
    params = {"w": {"a": jnp.ones((4,))}, "v": jnp.ones((2,))}
    state = TrainState.create(params, opt.init(params))
    step = build_train_step(loss_fn, opt, donate=False, anomaly_flags=anomaly_flags)
    return state, step


def test_planted_nan_flags_that_step(tmp_path):
    state, step = _toy_step()
    clean = {"x": jnp.ones((1, 4))}
    # NaN planted at index 2: group 'w' (sees all 4) blows up, group 'v'
    # (sees only x[:2]) stays finite — the norms localize the group
    nan_batch = {"x": jnp.array([[1.0, 1.0, jnp.nan, 1.0]])}

    state, m0 = step(state, clean)
    m0 = jax.device_get(m0)
    assert not bool(m0["nonfinite"])
    assert int(m0["grad_nonfinite_count"]) == 0

    state, m1 = step(state, nan_batch)
    m1 = jax.device_get(m1)
    assert bool(m1["nonfinite"]), "NaN microbatch must flag the step it occurs in"
    assert int(m1["grad_nonfinite_count"]) > 0
    # per-group norms localize the blowup: group 'w' touched the NaN input,
    # group 'v' saw only the first two (finite) elements
    assert not np.isfinite(m1["grad_norm/w"])
    assert np.isfinite(m1["grad_norm/v"])

    # and the flag survives the logger round-trip as strict JSON
    ml = MetricLogger(str(tmp_path / "m.jsonl"))
    ml.log(m1, step=int(m1["step"]))
    ml.close()
    rec = json.loads((tmp_path / "m.jsonl").read_text().splitlines()[0])
    assert rec["nonfinite"] is True
    assert rec["loss"] is None and rec["loss_nonfinite"] is True


def test_anomaly_flags_can_be_disabled():
    state, step = _toy_step(anomaly_flags=False)
    _, m = step(state, {"x": jnp.ones((1, 4))})
    assert "nonfinite" not in m


# -- memory census (tentpole pillar 1) ---------------------------------------

def test_live_array_census_ranks_by_bytes():
    big = jnp.ones((256, 256), jnp.float32)  # 256KB group
    small = jnp.ones((8,), jnp.float32)
    census = live_array_census(top_k=4)
    assert census["n_arrays"] >= 2
    assert census["total_bytes"] >= big.nbytes
    assert census["top"], "top-K must be non-empty with live arrays around"
    sizes = [e["bytes"] for e in census["top"]]
    assert sizes == sorted(sizes, reverse=True)
    snap = memory_snapshot(top_k=2)
    assert "devices" in snap and "census" in snap and len(snap["census"]["top"]) <= 2
    del big, small


# -- compile-event bridge (tentpole pillar 3) --------------------------------

def test_compile_bridge_counts_recompiles():
    bridge = CompileEventBridge()
    bridge.drain()  # discard whatever this process compiled so far

    @jax.jit
    def f(x):
        return x * 2 + 1

    f(jnp.ones((7,)))  # fresh shape → compile
    d = bridge.drain()
    assert d["compiles"] >= 1 and d["compile_secs"] > 0
    f(jnp.ones((7,)))  # cache hit → no compile
    assert bridge.drain()["compiles"] == 0
    # a second consumer has its own cursor and sees nothing new
    assert CompileEventBridge().drain()["compiles"] == 0


def test_compile_totals_count_traces_apart_from_compiles():
    """A function traced again is counted under ``traces`` whether or not a
    backend compile follows, and its seconds stay out of ``compile_secs``."""
    from automodel_tpu.telemetry import compile_events

    compile_events._ensure_registered()
    before = compile_events.compile_totals()

    @jax.jit
    def g(x):
        return x * 3 - 1

    g(jnp.ones((5,)))
    mid = compile_events.compile_totals()
    assert mid["traces"] > before["traces"] and mid["trace_secs"] >= before["trace_secs"]
    assert mid["compiles"] > before["compiles"]
    g(jnp.ones((5,)))  # cached: neither traced nor compiled again
    assert compile_events.compile_totals() == mid
    # the listener itself: a trace event moves only the two new keys
    compile_events._listener("/jax/core/compile/jaxpr_trace_duration", 0.25)
    after = compile_events.compile_totals()
    assert after["traces"] == mid["traces"] + 1
    assert after["trace_secs"] == pytest.approx(mid["trace_secs"] + 0.25, abs=2e-3)
    assert {k: after[k] for k in ("compiles", "compile_secs", "cache_hits", "cache_misses")} == {
        k: mid[k] for k in ("compiles", "compile_secs", "cache_hits", "cache_misses")}


# -- flight recorder (tentpole pillar 4) -------------------------------------

def test_flight_recorder_crash_dump(tmp_path):
    path = tmp_path / "fr.json"
    fp = build_fingerprint({"seed": 1}, mesh_ctx=None)
    rec = FlightRecorder(capacity=4, path=str(path), fingerprint=fp)
    with pytest.raises(RuntimeError, match="induced"):
        with rec:
            for i in range(10):
                rec.record({"step": i, "loss": float(i)})
            raise RuntimeError("induced failure")
    dump = json.loads(path.read_text())
    assert dump["reason"] == "RuntimeError"
    assert "induced failure" in dump["exception"]["message"]
    assert "RuntimeError" in dump["exception"]["traceback"]
    # ring keeps exactly the LAST capacity records
    assert [r["step"] for r in dump["records"]] == [6, 7, 8, 9]
    # fingerprint + forced memory snapshot present
    assert dump["fingerprint"]["jax_version"] == jax.__version__
    assert dump["fingerprint"]["config"] == {"seed": 1}
    assert "census" in dump["memory"] and "devices" in dump["memory"]


def test_fingerprint_redacts_credentials(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    fp = build_fingerprint(
        {
            "logging": {"wandb": {"api_key": "sk-live-hunter2", "project": "ok"}},
            "dataset": {"auth_token": "tok123", "path": "gs://bucket"},
        }
    )
    assert fp["config"]["logging"]["wandb"]["api_key"] == "<redacted>"
    assert fp["config"]["dataset"]["auth_token"] == "<redacted>"
    assert fp["config"]["logging"]["wandb"]["project"] == "ok"
    assert fp["config"]["dataset"]["path"] == "gs://bucket"
    # the environment that shapes a run rides along with its values
    assert fp["env"]["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/cache"


def test_metric_logger_cleans_nested_dicts(tmp_path):
    ml = MetricLogger(str(tmp_path / "m.jsonl"))
    ml.log({"nested": {"a": float("nan"), "b": 2.0}}, step=1)  # must not raise
    ml.close()
    rec = json.loads((tmp_path / "m.jsonl").read_text().splitlines()[0])
    assert rec["nested"] == {"a": None, "b": 2.0}
    assert rec["nested_nonfinite"] is True


def test_flight_recorder_jsonable_records(tmp_path):
    rec = FlightRecorder(capacity=2, path=str(tmp_path / "fr.json"))
    rec.record({"step": 1, "loss": np.float32(2.5), "nonfinite": np.bool_(True),
                "weird": object()})
    p = rec.dump(reason="manual")
    dump = json.loads(p.read_text())
    r = dump["records"][0]
    assert r["loss"] == 2.5 and r["nonfinite"] is True and isinstance(r["weird"], str)


# -- telemetry facade: cadence + overhead bounds -----------------------------

def test_memory_census_cadence(monkeypatch, tmp_path):
    calls = {"n": 0}
    import automodel_tpu.telemetry as tel_mod

    real = tel_mod.memory_telemetry.memory_snapshot
    monkeypatch.setattr(
        tel_mod.memory_telemetry, "memory_snapshot",
        lambda k: calls.__setitem__("n", calls["n"] + 1) or real(k),
    )
    tel = Telemetry(
        TelemetryConfig(
            memory_every_steps=10,
            flight_recorder_path=str(tmp_path / "fr.json"),
        )
    )
    logged = []
    for step in range(1, 103):
        tel.on_step(step)  # sampling rides the PER-STEP hook...
        if step % 3 == 0:  # ...independent of a coprime log cadence
            logged.append(tel.enrich(step, {"loss": 1.0, "step": step}))
    assert calls["n"] == 10, "census must run on its cadence only (10/102 steps)"
    assert tel.memory_samples == 10
    # the sampled scalars ride the NEXT log record even though the log
    # cadence (3) never coincides with the memory cadence (10)
    with_mem = [m for m in logged if "mem_bytes_in_use" in m]
    assert len(with_mem) == 10


def test_telemetry_per_step_overhead_bounded(tmp_path):
    """<1% of step time at default cadence: the per-step host work is two
    timer pairs + a ring append. Bound it at 50µs/step (0.5% of even a fast
    10ms step); best-of-5 trials so a CPU-contended CI box can't flake the
    assert — contention inflates the mean, not the min."""
    import time as _time

    tel = Telemetry(
        TelemetryConfig(
            memory_every_steps=0,  # isolate the per-step path
            flight_recorder_path=str(tmp_path / "fr.json"),
        )
    )
    step = 0
    best = float("inf")
    for _trial in range(5):
        t0 = _time.perf_counter()
        for _ in range(200):
            step += 1
            tel.timers("data_wait").start()
            tel.timers("data_wait").stop()
            tel.timers("dispatch").start()
            tel.timers("dispatch").stop()
            tel.on_step(step)
            tel.record_step({"step": step, "tokens": 1024, "ts": 0.0})
        best = min(best, _time.perf_counter() - t0)
    per_step = best / 200
    assert per_step < 50e-6, f"per-step telemetry overhead too high: {per_step*1e6:.1f}µs"
    # ring stayed bounded
    assert len(tel.flight_recorder.records) == tel.config.flight_recorder_steps


def test_telemetry_disabled_is_inert(tmp_path):
    tel = Telemetry(TelemetryConfig(enabled=False))
    assert tel.flight_recorder is None and tel.compile_bridge is None
    m = tel.enrich(50, {"loss": 1.0})
    assert m == {"loss": 1.0}
    with tel.crash_guard():
        pass  # nullcontext


def test_lint_flags_bare_nan_tokens(tmp_path):
    p = tmp_path / "legacy.jsonl"
    p.write_text('{"step": 1, "loss": NaN, "ts": 1.0}\n{"step": 2, "loss": 2.0, "ts": 2.0}\n')
    records, problems = lint_metrics_jsonl(str(p))
    assert len(records) == 1  # bad line skipped, good line parsed
    assert any("NaN" in p_ for p_ in problems)


# -- e2e: recipe wiring ------------------------------------------------------

def _recipe_cfg(tmp_path, **extra):
    from automodel_tpu.config.loader import ConfigNode

    cfg = {
        "seed": 7,
        "model": {
            "hf_config": {
                "architectures": ["LlamaForCausalLM"],
                "model_type": "llama",
                "vocab_size": 64,
                "hidden_size": 32,
                "intermediate_size": 64,
                "num_hidden_layers": 1,
                "num_attention_heads": 4,
                "num_key_value_heads": 2,
                "max_position_embeddings": 64,
            },
            "backend": {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"},
        },
        "distributed": {"dp_shard": 4, "tp": 2},
        "dataset": {
            "_target_": "automodel_tpu.data.sft.MockSFTDataset",
            "vocab_size": 64,
            "seq_length": 16,
            "num_samples": 48,
        },
        "dataloader": {"global_batch_size": 8},
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 1, "max_steps": 6,
                           "log_every_steps": 2},
        "optimizer": {"name": "adamw", "lr": 1e-3},
        "loss_fn": {"name": "masked_ce"},
        "logging": {"metrics_path": str(tmp_path / "metrics.jsonl")},
        "telemetry": {
            "memory_every_steps": 2,
            "flight_recorder_steps": 6,
            "flight_recorder_path": str(tmp_path / "fr.json"),
        },
    }
    cfg.update(extra)
    return ConfigNode(cfg)


def test_e2e_amortized_windows_and_telemetry_keys(tmp_path, devices8, monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: devices8)
    from automodel_tpu.recipes.train_ft import main

    last = main(_recipe_cfg(tmp_path))
    assert int(last["step"]) == 6
    lines = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    # event records (cost_attribution from the profiling pillar) interleave
    # with the step log records; this test is about the latter
    lines = [l for l in lines if l.get("event") is None]
    # log_every=2, max_steps=6 → logs at 2, 4, 6 (step 1 is not a log step)
    steps = [l["step"] for l in lines]
    assert steps == [2, 4, 6]
    # first window after step-1 compile barrier spans exactly 1 step (step 2);
    # later windows span the full log_every=2
    assert lines[0]["steps_spanned"] == 1
    assert lines[1]["steps_spanned"] == 2 and lines[2]["steps_spanned"] == 2
    for rec in lines:
        assert rec["tps"] > 0 and rec["step_time_s"] > 0
        assert rec["nonfinite"] is False
        assert "time/data_wait_s" in rec and "time/dispatch_s" in rec
        assert any(k.startswith("grad_norm/") for k in rec)
    # step 1's compile-scale dispatch entry is drained, not averaged into
    # the first window's decomposition. Relative bound (CPU dispatch is
    # ~synchronous, so dispatch ≈ step time): a leaked step-1 entry would
    # make the mean many times the window's own step_time_s.
    assert lines[0]["time/dispatch_s"] <= lines[0]["step_time_s"] * 1.5
    # memory cadence (every 2 steps) stamped allocator scalars on log records
    assert any("mem_bytes_in_use" in rec for rec in lines)


def test_e2e_induced_crash_dumps_flight_recorder(tmp_path, devices8, monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: devices8)
    from automodel_tpu.recipes.train_ft import TrainFinetuneRecipeForNextTokenPrediction

    r = TrainFinetuneRecipeForNextTokenPrediction(_recipe_cfg(tmp_path))
    r.setup()
    real_step = r.train_step
    calls = {"n": 0}

    def dying_step(state, batch):
        calls["n"] += 1
        if calls["n"] == 4:
            raise RuntimeError("induced mid-run failure")
        return real_step(state, batch)

    r.train_step = dying_step
    with pytest.raises(RuntimeError, match="induced mid-run"):
        r.run_train_validation_loop()
    dump = json.loads((tmp_path / "fr.json").read_text())
    assert dump["reason"] == "RuntimeError"
    # last-N step records present (steps 1..3 dispatched before the death);
    # the memory cadence (every 2 steps) interleaves a census record
    step_recs = [
        rec for rec in dump["records"]
        if "memory" not in rec and rec.get("event") is None
    ]
    assert [rec["step"] for rec in step_recs] == [1, 2, 3]
    assert any("memory" in rec for rec in dump["records"])
    assert "census" in dump["memory"]
    mesh = dump["fingerprint"]["mesh"]
    assert mesh["dp_shard"] == 4 and mesh["tp"] == 2
