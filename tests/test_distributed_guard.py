"""Distributed guard (resilience/watchdog.py, consensus.py, timed_sync.py,
guard.py): hang watchdog with adaptive deadline + stacks/flight-recorder
evidence + requeue exit, cross-host desync detection naming the offending
host and blocking the checkpoint commit, timed collectives, straggler
attribution, and the fault-injection knobs that drive all of it on CPU."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from automodel_tpu.resilience import fault_injection as fi
from automodel_tpu.resilience.consensus import (
    COLUMNS,
    ConsensusConfig,
    ConsensusGuard,
    DesyncError,
    config_crc,
    find_divergent,
    fold_array_crc,
)
from automodel_tpu.resilience.preemption import REQUEUE_EXIT_CODE
from automodel_tpu.resilience.timed_sync import (
    SyncTimeout,
    barrier_with_timeout,
    slowest_host,
    timed_call,
)
from automodel_tpu.resilience.watchdog import Watchdog, WatchdogConfig

_WORKER = os.path.join(os.path.dirname(__file__), "resilience_worker.py")

_DATA_COL = COLUMNS.index("data")
_TIME_COL = COLUMNS.index("step_time")


@pytest.fixture(autouse=True)
def _reset_fault_injection():
    yield
    fi.activate(None)


# ---------------------------------------------------------------------------
# timed_sync.py
# ---------------------------------------------------------------------------


def test_timed_call_passes_results_and_exceptions_through():
    assert timed_call(lambda: 42, name="ok", timeout_s=5.0) == 42
    with pytest.raises(ValueError, match="boom"):
        timed_call(lambda: (_ for _ in ()).throw(ValueError("boom")),
                   name="err", timeout_s=5.0)


def test_timed_call_timeout_names_the_sync_point():
    t0 = time.monotonic()
    with pytest.raises(SyncTimeout, match="checkpoint_commit"):
        timed_call(lambda: time.sleep(30), name="checkpoint_commit",
                   timeout_s=0.2)
    assert time.monotonic() - t0 < 5.0  # main thread got control back


def test_barrier_single_process_is_free():
    # no gather_fn, one process: returns immediately without a thread
    assert barrier_with_timeout("shutdown", timeout_s=0.001) == 1


def test_barrier_timeout_on_dead_peer():
    with pytest.raises(SyncTimeout, match="init"):
        barrier_with_timeout(
            "init", timeout_s=0.2, gather_fn=lambda v: time.sleep(30)
        )


def test_slowest_host_attribution():
    worst, ratio = slowest_host([0.10, 0.11, 0.42, 0.10])
    assert worst == 2
    assert ratio == pytest.approx(0.42 / 0.105)
    assert slowest_host([]) == (0, 1.0)


# ---------------------------------------------------------------------------
# watchdog.py
# ---------------------------------------------------------------------------


def _wd(tmp_path, **kw):
    kw.setdefault("min_deadline_s", 0.3)
    kw.setdefault("poll_interval_s", 0.05)
    kw.setdefault("compile_grace_s", 0.5)
    kw.setdefault("ema_alpha", 0.5)
    kw.setdefault("stacks_path", str(tmp_path / "stacks.txt"))
    return WatchdogConfig(**kw)


def test_watchdog_adaptive_deadline_tracks_ema(tmp_path):
    wd = Watchdog(_wd(tmp_path, multiplier=10.0, min_deadline_s=0.01,
                      max_deadline_s=2.0, enabled=False))
    wd.pet(1)
    time.sleep(0.05)
    wd.pet(2)
    time.sleep(0.05)
    wd.pet(3)
    assert wd.ema_step_time_s == pytest.approx(0.05, rel=0.6)
    # deadline = ema * multiplier, clamped
    assert 0.2 <= wd.deadline_s <= 2.0
    wd._ema_s = 100.0
    assert wd.deadline_s == 2.0  # max clamp
    wd._ema_s = 1e-6
    assert wd.deadline_s == 0.01  # min clamp


def test_watchdog_phase_grace_and_compile_grace(tmp_path):
    wd = Watchdog(_wd(tmp_path, min_deadline_s=0.1, checkpoint_grace_s=5.0,
                      compile_grace_s=7.0, enabled=False))
    wd._phase = "compile"
    assert wd.deadline_s == 7.0  # compile grace ...
    wd.pet(1)
    assert wd._phase == "compile"  # ... survives the first pet (the first
    # real execution blocks at the first barrier AFTER it) ...
    wd.pet(2)
    assert wd._phase is None  # ... and ends at the second
    assert wd.deadline_s == 0.1
    with wd.phase("checkpoint"):
        assert wd.deadline_s == 5.0
    assert wd.deadline_s == 0.1
    with pytest.raises(ValueError):
        with wd.phase("nonsense"):
            pass


def test_watchdog_phase_time_never_pollutes_ema(tmp_path):
    wd = Watchdog(_wd(tmp_path, enabled=False))
    wd.pet(1)
    time.sleep(0.02)
    wd.pet(2)
    ema_before = wd.ema_step_time_s
    with wd.phase("eval"):
        time.sleep(0.3)  # a slow eval pass
    wd.pet(3)  # first pet after the phase: dt skipped
    assert wd.ema_step_time_s == ema_before


def test_watchdog_fires_with_stacks_and_flight_recorder(tmp_path):
    from automodel_tpu.telemetry.flight_recorder import FlightRecorder

    rec = FlightRecorder(capacity=4, path=str(tmp_path / "fr.json"))
    rec.record({"step": 7, "loss": 1.0})
    fired = []
    wd = Watchdog(
        _wd(tmp_path, min_deadline_s=0.2),
        flight_recorder=rec,
        on_hang=fired.append,
    )
    wd.start()
    try:
        wd.pet(7)
        deadline = time.monotonic() + 10
        while not fired and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        wd.stop()
    assert fired, "watchdog did not fire within the deadline"
    hang = fired[0]
    assert hang["event"] == "hang" and hang["step"] == 7
    assert hang["heartbeat_age_s"] > 0.2
    # evidence bundle: all-thread stacks + flight-recorder dump with the
    # hang event stamped into the ring
    stacks = (tmp_path / "stacks.txt").read_text()
    assert "hang at step 7" in stacks and "Thread" in stacks
    dump = json.loads((tmp_path / "fr.json").read_text())
    assert dump["reason"] == "hang"
    assert any(r.get("event") == "hang" for r in dump["records"])


def test_watchdog_petting_keeps_it_quiet(tmp_path):
    fired = []
    wd = Watchdog(_wd(tmp_path, min_deadline_s=0.3), on_hang=fired.append)
    wd.start()
    try:
        for i in range(12):  # 0.6s total, pets every 0.05s
            wd.pet(i)
            time.sleep(0.05)
        assert not fired
    finally:
        wd.stop()
    assert not fired


def test_watchdog_disabled_never_starts_a_thread(tmp_path):
    wd = Watchdog(_wd(tmp_path, enabled=False))
    assert wd.start()._thread is None


# ---------------------------------------------------------------------------
# consensus.py
# ---------------------------------------------------------------------------


def test_find_divergent_majority_names_the_minority():
    base = np.array([3.0, 111.0, 222.0, 0.5, 0.1])
    m = np.stack([base, base, base])
    assert find_divergent(m) == []
    m[1, _DATA_COL] = 999.0
    f = find_divergent(m)
    assert len(f) == 1 and f[0]["host"] == 1 and f[0]["component"] == "data"
    assert f[0]["majority"] == 222.0


def test_find_divergent_no_majority_reports_everyone():
    m = np.zeros((3, len(COLUMNS)))
    m[:, _DATA_COL] = [1.0, 2.0, 3.0]  # shattered: no majority value
    hosts = {f["host"] for f in find_divergent(m)}
    assert hosts == {0, 1, 2}


def test_find_divergent_plurality_attributes_both_divergers():
    """Two hosts diverging DIFFERENTLY from an agreeing pair: the plurality
    (not strict-majority) rule must blame exactly the two divergers, never
    smear the healthy pair."""
    m = np.ones((4, len(COLUMNS)))
    m[:, _DATA_COL] = [7.0, 7.0, 8.0, 9.0]
    f = find_divergent(m)
    assert {x["host"] for x in f} == {2, 3}
    assert all(x["majority"] == 7.0 for x in f)
    # a 2-host split has no plurality: report both (cannot attribute)
    m2 = np.ones((2, len(COLUMNS)))
    m2[:, _DATA_COL] = [1.0, 2.0]
    assert {x["host"] for x in find_divergent(m2)} == {0, 1}


def test_desync_error_renders_crc_values_exactly():
    """Two near-identical 32-bit CRCs must not round to the same printed
    value — the abort message is the operator's primary evidence."""
    f = [{"host": 1, "component": "data",
          "value": 4294901234.0, "majority": 4294907777.0}]
    msg = str(DesyncError(5, "checkpoint", f))
    assert "4294901234" in msg and "4294907777" in msg


def test_find_divergent_ignores_step_time_column():
    base = np.ones((4, len(COLUMNS)))
    base[:, _TIME_COL] = [0.1, 0.2, 0.9, 0.1]  # hosts legitimately differ
    assert find_divergent(base) == []


def test_config_crc_is_order_stable():
    a = config_crc({"x": 1, "y": {"b": 2, "a": 3}})
    b = config_crc({"y": {"a": 3, "b": 2}, "x": 1})
    assert a == b
    assert a != config_crc({"x": 2, "y": {"b": 2, "a": 3}})


def test_rolling_hash_tracks_batch_bytes():
    b1 = np.arange(32, dtype=np.int32).reshape(4, 8)
    h1 = fold_array_crc(0, b1)
    assert fold_array_crc(0, b1) == h1  # deterministic
    b2 = b1.copy()
    b2[2, 3] += 1  # one token different → different order/data
    assert fold_array_crc(0, b2) != h1
    assert fold_array_crc(h1, b2) != fold_array_crc(h1, b1)  # rolling


def _guard(gather=None, **cfg):
    return ConsensusGuard(
        ConsensusConfig(**cfg), fingerprint={"cfg": 1}, gather_fn=gather
    )


def test_consensus_agreement_yields_straggler_metrics():
    def gather(vec):
        rows = np.stack([vec, vec, vec])
        rows[:, _TIME_COL] = [0.1, 0.5, 0.1]
        return rows

    g = _guard(gather)
    g.fold_batch(1, {"input_ids": np.arange(8, dtype=np.int32)})
    out = g.check(1, step_time_s=0.1)
    assert out["slowest_host"] == 1
    assert out["host_step_time_max_s"] == pytest.approx(0.5)
    assert out["straggler_ratio"] == pytest.approx(5.0)


def test_consensus_desync_raises_naming_the_host():
    def gather(vec):
        rows = np.stack([vec, vec, vec])
        rows[2, _DATA_COL] += 17.0  # host 2 saw different data
        return rows

    events = []
    g = _guard(gather)
    g.event_hook = events.append
    g.fold_batch(3, {"input_ids": np.arange(8, dtype=np.int32)})
    with pytest.raises(DesyncError, match="host 2") as ei:
        g.check(3, where="checkpoint")
    assert ei.value.hosts == [2]
    assert ei.value.where == "checkpoint"
    assert events and events[0]["event"] == "desync"
    assert events[0]["desync_hosts"] == [2]


def test_consensus_single_process_without_injection_is_inert():
    g = _guard()
    assert not g.active() or jax.process_count() > 1
    assert g.check(5) == {}
    assert g.checks == 0  # nothing gathered, nothing compared


def test_consensus_injected_desync_single_process():
    """`desync_batch_at_step` drives the full detect-and-attribute path on
    one process: the injector perturbs the reported hash, the guard
    simulates two healthy peers holding the clean shadow, and the majority
    rule localizes the desynced host."""
    fi.activate({"desync_batch_at_step": 2})
    g = _guard()
    assert g.active()
    ids = np.arange(16, dtype=np.int32)
    g.fold_batch(1, {"input_ids": ids})
    assert g._data_hash == g._clean_hash
    g.check(1)  # agreement while unperturbed
    g.fold_batch(2, {"input_ids": ids})
    assert g._data_hash != g._clean_hash
    with pytest.raises(DesyncError, match="data"):
        g.check(2, where="checkpoint")


# ---------------------------------------------------------------------------
# fault-injection knobs
# ---------------------------------------------------------------------------


def test_injector_straggle_sleeps_only_on_the_straggling_host():
    inj = fi.FaultInjector(fi.FaultInjectionConfig(
        straggle_host=0, straggle_ms=80.0
    ))
    t0 = time.perf_counter()
    inj.maybe_straggle(1)  # process_index 0 matches
    assert time.perf_counter() - t0 >= 0.08
    inj2 = fi.FaultInjector(fi.FaultInjectionConfig(
        straggle_host=3, straggle_ms=500.0
    ))
    t0 = time.perf_counter()
    inj2.maybe_straggle(1)  # not our host: no sleep
    assert time.perf_counter() - t0 < 0.1


def test_injector_hang_fires_once_and_is_bounded():
    inj = fi.FaultInjector(fi.FaultInjectionConfig(
        hang_at_step=2, hang_seconds=0.2
    ))
    t0 = time.perf_counter()
    inj.maybe_hang(1)
    assert time.perf_counter() - t0 < 0.1  # wrong step: no hang
    inj.maybe_hang(2)
    assert time.perf_counter() - t0 >= 0.2
    t1 = time.perf_counter()
    inj.maybe_hang(2)  # fires once — a resumed loop must not re-hang
    assert time.perf_counter() - t1 < 0.1


def test_guard_knobs_arm_the_injector():
    assert fi.activate({"hang_at_step": 3}) is not None
    assert fi.activate({"desync_batch_at_step": 1}) is not None
    assert fi.activate({"straggle_host": 0, "straggle_ms": 5}) is not None
    assert fi.activate({}) is None


# ---------------------------------------------------------------------------
# launcher wiring
# ---------------------------------------------------------------------------


def test_slurm_time_limit_grace_signal():
    from automodel_tpu.launcher.slurm import SlurmConfig, render_sbatch

    s = render_sbatch(SlurmConfig(), "finetune", "llm", "c.yaml")
    # SIGTERM ahead of the time limit: hitting the wall clock becomes a
    # normal preemption (emergency checkpoint → 75 → requeue). No `B:`
    # prefix — that would signal only the batch shell, which has no trap
    # forwarding to the srun tasks where the PreemptionHandler lives.
    assert "#SBATCH --signal=TERM@90" in s
    assert "--signal=B:" not in s
    off = render_sbatch(
        SlurmConfig(term_grace_s=0), "finetune", "llm", "c.yaml"
    )
    assert "--signal=TERM" not in off


def test_k8s_termination_grace_period():
    from automodel_tpu.launcher.k8s import K8sConfig, render_manifest

    m = render_manifest(K8sConfig(), "finetune", "llm", "c.yaml")
    assert "terminationGracePeriodSeconds: 90" in m
    m2 = render_manifest(
        K8sConfig(termination_grace_s=300), "finetune", "llm", "c.yaml"
    )
    assert "terminationGracePeriodSeconds: 300" in m2


# ---------------------------------------------------------------------------
# report.py: guard keys are first-class schema citizens
# ---------------------------------------------------------------------------


def test_report_accepts_guard_event_keys(tmp_path):
    from automodel_tpu.telemetry.report import lint_metrics_jsonl, summarize_metrics

    p = tmp_path / "m.jsonl"
    p.write_text(
        '{"step": 1, "loss": 1.0, "ts": 1, "heartbeat_age_s": 0.01, '
        '"slowest_host": 2, "straggler_ratio": 1.7}\n'
        '{"event": "desync", "step": 2, "ts": 2, "desync_hosts": [1], '
        '"findings": [{"host": 1, "component": "data"}]}\n'
        '{"event": "hang", "step": 3, "ts": 3, "heartbeat_age_s": 12.5, '
        '"deadline_s": 4.0}\n'
    )
    recs, problems = lint_metrics_jsonl(str(p))
    assert not problems, problems
    s = summarize_metrics(recs)
    assert s["hang_events"] == [{"step": 3, "heartbeat_age_s": 12.5}]
    assert s["desync_events"] == [{"step": 2, "hosts": [1]}]
    assert s["straggler_ratio_max"] == 1.7


# ---------------------------------------------------------------------------
# recipe e2e (8-device CPU mesh, single process)
# ---------------------------------------------------------------------------


def _recipe_cfg(tmp_path, extra=None):
    from automodel_tpu.config.loader import ConfigNode

    cfg = {
        "seed": 7,
        "model": {
            "hf_config": {
                "architectures": ["LlamaForCausalLM"],
                "model_type": "llama",
                "vocab_size": 128,
                "hidden_size": 64,
                "intermediate_size": 128,
                "num_hidden_layers": 2,
                "num_attention_heads": 4,
                "num_key_value_heads": 2,
                "max_position_embeddings": 128,
            },
            "backend": {"attn": "sdpa", "param_dtype": "float32",
                        "compute_dtype": "float32"},
        },
        "distributed": {"dp_shard": 4, "tp": 2},
        "dataset": {
            "_target_": "automodel_tpu.data.sft.MockSFTDataset",
            "vocab_size": 128,
            "seq_length": 32,
            "num_samples": 64,
        },
        "dataloader": {"global_batch_size": 8},
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 2, "max_steps": 4},
        "optimizer": {"name": "adamw", "lr": 1e-3},
        "checkpoint": {"enabled": True, "checkpoint_dir": str(tmp_path / "ckpt")},
        "logging": {"metrics_path": str(tmp_path / "metrics.jsonl")},
        "telemetry": {"memory_every_steps": 0},
    }
    for k, v in (extra or {}).items():
        cfg[k] = v
    return ConfigNode(cfg)


def _run_recipe(cfg, monkeypatch, devices8):
    monkeypatch.setattr(jax, "devices", lambda *a: devices8)
    from automodel_tpu.recipes.train_ft import TrainFinetuneRecipeForNextTokenPrediction

    r = TrainFinetuneRecipeForNextTokenPrediction(cfg)
    r.setup()
    return r


def test_e2e_desynced_checkpoint_never_commits(tmp_path, devices8, monkeypatch):
    """Acceptance: batch desync is detected at the next boundary with the
    offending host named, and the desynced checkpoint never commits —
    DesyncError fires at the PRE-COMMIT resolution point, before save()."""
    cfg = _recipe_cfg(tmp_path, {
        # no log boundary before the ckpt one: the pre-commit check at
        # step 2 must be the detection point
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 2,
                           "max_steps": 4, "ckpt_every_steps": 2,
                           "log_every_steps": 5},
    })
    r = _run_recipe(cfg, monkeypatch, devices8)

    def divergent_gather(vec):
        rows = np.stack([vec, vec, vec])
        rows[1, _DATA_COL] += 1.0  # host 1 iterated different data
        return rows

    r.guard.consensus._gather = divergent_gather
    with pytest.raises(DesyncError, match="host 1") as ei:
        r.run_train_validation_loop()
    assert ei.value.where == "checkpoint" and ei.value.step == 2
    # the step-2 checkpoint must NOT have committed
    committed = {p.parent.name for p in (tmp_path / "ckpt").glob("*/MANIFEST.json")}
    assert not any(d.endswith("_step_2") for d in committed), committed
    # evidence: desync event in the metrics JSONL and the flight recorder
    recs = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    ev = next(r_ for r_ in recs if r_.get("event") == "desync")
    assert ev["desync_hosts"] == [1]
    dump = json.loads((tmp_path / "flight_recorder.json").read_text())
    assert dump["reason"] == "DesyncError"
    assert any(rec.get("event") == "desync" for rec in dump["records"])


def test_e2e_straggler_metrics_ride_the_log_record(tmp_path, devices8, monkeypatch):
    cfg = _recipe_cfg(tmp_path)
    r = _run_recipe(cfg, monkeypatch, devices8)

    def balanced_but_slow_host_2(vec):
        rows = np.stack([vec, vec, vec])
        rows[:, _TIME_COL] = [0.1, 0.1, 0.4]
        return rows

    r.guard.consensus._gather = balanced_but_slow_host_2
    last = r.run_train_validation_loop()
    assert last["slowest_host"] == 2
    assert last["straggler_ratio"] == pytest.approx(4.0)
    assert "heartbeat_age_s" in last
    # the JSONL passes the strict linter with the new keys present
    from automodel_tpu.telemetry.report import lint_metrics_jsonl

    _, problems = lint_metrics_jsonl(str(tmp_path / "metrics.jsonl"))
    assert not problems, problems


def test_e2e_injected_desync_detected_at_next_boundary(
    tmp_path, devices8, monkeypatch
):
    """The YAML-only path: fault_injection.desync_batch_at_step, no test
    seams — detection at the first boundary after the poisoned step."""
    cfg = _recipe_cfg(tmp_path, {
        "fault_injection": {"desync_batch_at_step": 2},
    })
    r = _run_recipe(cfg, monkeypatch, devices8)
    with pytest.raises(DesyncError) as ei:
        r.run_train_validation_loop()
    assert ei.value.step == 2  # log boundary of the poisoned step
    assert ei.value.findings[0]["component"] == "data"


def test_e2e_watchdog_catches_injected_hang(tmp_path, devices8, monkeypatch):
    """In-process leg of acceptance (a): hang_at_step blocks the loop, the
    watchdog fires within the adaptive deadline and produces the full
    evidence bundle (the subprocess leg asserts the requeue exit code)."""
    cfg = _recipe_cfg(tmp_path, {
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 2,
                           "max_steps": 4, "ckpt_every_steps": 0},
        "fault_injection": {"hang_at_step": 3, "hang_seconds": 25.0},
        # CPU steps here are seconds, not milliseconds: keep the multiplier
        # small so deadline = EMA x 2 stays far below the injected 25s hang,
        # and the floor above the real step time — detection unambiguous
        "distributed_guard": {
            "watchdog": {"min_deadline_s": 3.0, "poll_interval_s": 0.1,
                         "multiplier": 2.0, "compile_grace_s": 600.0},
        },
    })
    r = _run_recipe(cfg, monkeypatch, devices8)
    fired = []
    r.guard.watchdog.on_hang = fired.append  # observe instead of exiting
    t0 = time.monotonic()
    r.run_train_validation_loop()  # completes after the bounded hang
    assert fired, "watchdog did not fire during the injected hang"
    hang = fired[0]
    assert hang["event"] == "hang" and hang["step"] == 3
    assert hang["heartbeat_age_s"] >= 3.0
    assert time.monotonic() - t0 < 180
    stacks = (tmp_path / "watchdog_stacks.txt").read_text()
    assert "hang at step 3" in stacks
    dump = json.loads((tmp_path / "flight_recorder.json").read_text())
    assert dump["reason"] == "hang"


# ---------------------------------------------------------------------------
# subprocess e2e: injected hang → stacks + dump + requeue exit (acceptance a)
# ---------------------------------------------------------------------------


def _clean_env(tmp_path):
    # a compile cache of the test's own, as in tests/test_goodput.py
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    for k in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_COORDINATOR_ADDRESS",
              "JAX_NUM_PROCESSES", "JAX_PROCESS_ID", fi.ENV_VAR):
        env.pop(k, None)
    return env


def test_hang_subprocess_requeue_exit_with_evidence(tmp_path):
    ckpt_dir = tmp_path / "ckpt"
    metrics = tmp_path / "metrics.jsonl"
    cfg = {
        "seed": 3,
        "model": {
            "hf_config": {
                "architectures": ["LlamaForCausalLM"],
                "model_type": "llama",
                "vocab_size": 64,
                "hidden_size": 32,
                "intermediate_size": 64,
                "num_hidden_layers": 2,
                "num_attention_heads": 2,
                "num_key_value_heads": 1,
                "max_position_embeddings": 64,
            },
            "backend": {"attn": "sdpa", "param_dtype": "float32",
                        "compute_dtype": "float32"},
        },
        "distributed": {"dp_shard": 2},
        "dataset": {
            "_target_": "automodel_tpu.data.sft.MockSFTDataset",
            "vocab_size": 64, "seq_length": 16, "num_samples": 64,
        },
        "dataloader": {"global_batch_size": 4},
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 1000,
                           "max_steps": 100000, "ckpt_every_steps": 1},
        "optimizer": {"name": "adamw", "lr": 1e-3},
        "checkpoint": {"enabled": True, "checkpoint_dir": str(ckpt_dir)},
        "logging": {"metrics_path": str(metrics)},
        "telemetry": {"memory_every_steps": 0},
        # hang AFTER the step-1 checkpoint committed → requeue-eligible
        "fault_injection": {"hang_at_step": 3, "hang_seconds": 3600},
        "distributed_guard": {
            "watchdog": {"min_deadline_s": 4.0, "poll_interval_s": 0.2,
                         "multiplier": 10.0, "compile_grace_s": 600.0},
        },
    }
    cfg_path = tmp_path / "cfg.yaml"
    cfg_path.write_text(json.dumps(cfg))  # JSON is valid YAML

    out = subprocess.run(
        [sys.executable, _WORKER, "finetune", "llm", "-c", str(cfg_path)],
        env=_clean_env(tmp_path), capture_output=True, text=True, timeout=500,
    )
    # detected within the adaptive deadline → hard exit with the requeue
    # code (a committed checkpoint exists to resume from)
    assert out.returncode == REQUEUE_EXIT_CODE, (
        out.stdout[-2000:], out.stderr[-2000:]
    )
    assert "[watchdog] HANG" in out.stderr
    # evidence bundle on disk: all-thread stacks + flight recorder with the
    # hang event + the hang record in the metrics JSONL
    stacks = (tmp_path / "watchdog_stacks.txt").read_text()
    assert "hang at step 3" in stacks and "Thread" in stacks
    dump = json.loads((tmp_path / "flight_recorder.json").read_text())
    assert dump["reason"] == "hang"
    hang_recs = [r for r in dump["records"] if r.get("event") == "hang"]
    assert hang_recs and hang_recs[0]["step"] == 3
    recs = [json.loads(l) for l in metrics.read_text().splitlines()]
    assert any(r.get("event") == "hang" for r in recs)
    # the peer-preemption marker was stamped into the shared checkpoint
    # root, so peers dying of the abandoned collectives requeue too
    from automodel_tpu.resilience.preemption import PEER_PREEMPTION_MARKER

    assert (ckpt_dir / PEER_PREEMPTION_MARKER).exists()
