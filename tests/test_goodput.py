"""Run-ledger goodput accounting (telemetry/goodput.py).

Three layers, mirroring the resilience test split:

- ledger/rollup units: attempt chaining, inferred tail close, reclassified
  preemption-lost / rollback-discard math, unattributed residual + the
  hang-event join.
- in-process recipe e2e on the 8-device CPU mesh: each fault-injection
  knob moves exactly its own segment (`slow_collate_ms` → input_wait,
  `nan_grads_at_step` + rollback → rollback_discard, `die_at_step` →
  preemption_lost across a chained restart), the ckpt-timing +
  window_excluded_s stamps, the attempt envelope, and the report lint.
- subprocess e2e: SIGTERM mid-epoch → exit 75 → restart resumes →
  `automodel_tpu goodput` shows two chained attempts with a
  preemption-lost segment equal to steps-since-last-commit and segments
  summing to measured wall clock within 5%; an injected hang → watchdog
  `os._exit` → the dead attempt's unattributed idle joins the
  flight-recorder hang event.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

import jax

from automodel_tpu.resilience import REQUEUE_EXIT_CODE
from automodel_tpu.resilience import fault_injection as fi
from automodel_tpu.telemetry.goodput import (
    GoodputLedger,
    SEGMENT_KINDS,
    main as goodput_main,
    rollup,
    _read_records,
)

_WORKER = os.path.join(os.path.dirname(__file__), "resilience_worker.py")


# ---------------------------------------------------------------------------
# ledger + rollup units
# ---------------------------------------------------------------------------


def test_segment_taxonomy_is_closed():
    from automodel_tpu.telemetry.goodput import CKPT_PENDING_KEYS, RECLASSIFIED_KINDS

    assert set(RECLASSIFIED_KINDS) <= set(SEGMENT_KINDS)
    assert set(CKPT_PENDING_KEYS) <= set(SEGMENT_KINDS)


def test_ledger_writes_attempt_and_segments(tmp_path):
    path = tmp_path / "goodput.jsonl"
    led = GoodputLedger(path, t_start=time.time() - 1.0)
    assert led.restart_count == 0
    led.loop_started()
    led.window(2.0, 0.5, steps=2, step_to=2)
    led.on_ckpt_timing("ckpt_save", 0.25, step=2)
    assert led.pop_pending() == {"ckpt_save_s": 0.25}
    assert led.pop_pending() == {}
    led.close(reason="exit")
    recs = _read_records(path)
    kinds = [r.get("kind") for r in recs if r.get("event") == "segment"]
    assert kinds == ["startup", "step", "input_wait", "ckpt_save"]
    step_seg = next(r for r in recs if r.get("kind") == "step")
    assert step_seg["duration_s"] == pytest.approx(1.5)
    assert (step_seg["step_from"], step_seg["step_to"]) == (1, 2)
    assert recs[-1]["event"] == "attempt_end" and recs[-1]["reason"] == "exit"
    roll = rollup(recs)
    a = roll["attempts"][0]
    assert a["segments"]["step"] == pytest.approx(1.5)
    assert a["segments"]["input_wait"] == pytest.approx(0.5)
    # startup + segments cover everything but the 0-length tail
    assert a["accounted_fraction"] > 0.9


def test_ledger_chains_and_infers_a_killed_tail(tmp_path):
    path = tmp_path / "goodput.jsonl"
    led1 = GoodputLedger(path, t_start=time.time() - 10.0)
    led1.loop_started()
    led1.window(4.0, 0.0, steps=4, step_to=4)  # steps 1..4, 1s each
    # no close: simulates SIGKILL mid-run
    led2 = GoodputLedger(path, t_start=time.time())
    assert led2.restart_count == 1
    recs = _read_records(path)
    inferred = [r for r in recs if r.get("event") == "attempt_end"]
    assert len(inferred) == 1 and inferred[0]["inferred"] is True
    assert inferred[0]["attempt_id"] == led1.attempt_id
    # resumed from the step-2 checkpoint: steps 3,4 were never committed
    led2.on_resume(2)
    led2.on_resume(2)  # idempotent: one chain, one reclassification
    recs = _read_records(path)
    lost = [r for r in recs if r.get("kind") == "preemption_lost"]
    assert len(lost) == 1
    assert lost[0]["attempt_id"] == led1.attempt_id  # the DEAD attempt lost it
    assert lost[0]["steps_lost"] == 2
    assert lost[0]["duration_s"] == pytest.approx(2.0)  # pro-rata 1s/step
    roll = rollup(recs)
    a1 = roll["attempts"][0]
    # reclassification moves seconds between buckets, never adds wall clock
    assert a1["segments"]["preemption_lost"] == pytest.approx(2.0)
    assert a1["segments"]["step"] == pytest.approx(2.0)
    assert a1["steps_lost"] == 2
    assert roll["run"]["n_attempts"] == 2


def test_resume_from_scratch_loses_everything(tmp_path):
    """A predecessor killed before ANY commit: the restart resumes from
    step 0 and the dead attempt's entire stepped progress reclassifies."""
    path = tmp_path / "goodput.jsonl"
    led1 = GoodputLedger(path, t_start=time.time() - 10.0)
    led1.loop_started()
    led1.window(3.0, 0.0, steps=3, step_to=3)
    led2 = GoodputLedger(path, t_start=time.time())
    led2.on_resume(0)
    roll = rollup(_read_records(path))
    a1 = roll["attempts"][0]
    assert a1["steps_lost"] == 3
    assert a1["segments"]["preemption_lost"] == pytest.approx(3.0)
    assert a1["segments"].get("step", 0.0) == pytest.approx(0.0)
    assert a1["steps_committed"] == 0


def test_rollback_reclassifies_own_step_time(tmp_path):
    led = GoodputLedger(tmp_path / "goodput.jsonl", t_start=time.time() - 5.0)
    led.loop_started()
    led.window(3.0, 0.0, steps=3, step_to=3)  # steps 1..3
    led.on_rollback(fail_step=3, restored_step=1)  # discard steps 2,3
    roll = rollup(_read_records(led.path))
    a = roll["attempts"][0]
    assert a["segments"]["rollback_discard"] == pytest.approx(2.0)
    assert a["segments"]["step"] == pytest.approx(1.0)
    assert a["steps_discarded"] == 2
    # the in-memory snapshot nets the same way (the /metrics view)
    snap = led.snapshot()
    assert snap["segments"]["rollback_discard"] == pytest.approx(2.0)
    assert snap["segments"]["step"] == pytest.approx(1.0)


def test_rollup_unattributed_joins_hang_events(tmp_path):
    t0 = time.time() - 100.0
    recs = [
        {"event": "attempt", "attempt_id": "a1", "restart_count": 0,
         "start_ts": t0, "ts": t0},
        {"event": "segment", "attempt_id": "a1", "kind": "step",
         "duration_s": 10.0, "step_from": 1, "step_to": 10, "ts": t0 + 10},
        # no attempt_end: the watchdog os._exit'd mid-hang
    ]
    hang_ts = t0 + 40.0
    events = [{"event": "hang", "step": 10, "ts": hang_ts}]
    roll = rollup(recs, events)
    a = roll["attempts"][0]
    # wall extends to the hang evidence; the silent 30s reads unattributed
    assert a["wall_s"] == pytest.approx(40.0)
    assert a["unattributed_s"] == pytest.approx(30.0)
    assert a["anomalies"] == [{"event": "hang", "step": 10, "ts": hang_ts}]
    # without the event, the attempt would end at its last record
    roll2 = rollup(recs)
    assert roll2["attempts"][0]["wall_s"] == pytest.approx(10.0)
    # a SURVIVED anomaly must never truncate the wall clock: segments
    # recorded after an early desync still extend the attempt's end
    recs3 = recs + [
        {"event": "segment", "attempt_id": "a1", "kind": "step",
         "duration_s": 50.0, "step_from": 11, "step_to": 60, "ts": t0 + 200},
    ]
    early = [{"event": "desync", "step": 2, "ts": t0 + 5}]
    a3 = rollup(recs3, early)["attempts"][0]
    assert a3["wall_s"] == pytest.approx(200.0)
    assert a3["anomalies"][0]["event"] == "desync"


def test_ledger_disabled_is_a_no_op(tmp_path):
    led = GoodputLedger(tmp_path / "goodput.jsonl", enabled=False)
    led.loop_started()
    led.window(1.0, 0.0, steps=1, step_to=1)
    led.on_ckpt_timing("ckpt_save", 0.5)
    led.on_resume(0)
    led.on_rollback(1, 0)
    led.close()
    assert not (tmp_path / "goodput.jsonl").exists()
    assert led.pop_pending() == {}


# ---------------------------------------------------------------------------
# in-process recipe e2e (tiny llama on the 8-device CPU mesh)
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
        "optimizer": {"name": "adamw", "lr": 1e-3, "grad_clip_norm": 1.0},
        "loss_fn": {"name": "masked_ce"},
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


def _goodput(tmp_path) -> dict:
    return rollup(
        _read_records(tmp_path / "goodput.jsonl"),
        [],
    )


@pytest.fixture(scope="module")
def clean_run(tmp_path_factory, devices8):
    """ONE clean 4-step recipe run (with a scrape port and a cadence save)
    shared by the clean-accounting, CLI, /metrics, and slow-collate-
    baseline tests — a tiny-llama build per test is the dominant cost of
    this module."""
    import urllib.request

    tmp = tmp_path_factory.mktemp("clean_run")
    mp = pytest.MonkeyPatch()
    scraped = {}
    try:
        mp.setattr(jax, "devices", lambda *a: devices8)
        from automodel_tpu.recipes.train_ft import (
            TrainFinetuneRecipeForNextTokenPrediction,
        )

        r = TrainFinetuneRecipeForNextTokenPrediction(_recipe_cfg(tmp, {
            "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 2,
                               "max_steps": 4, "ckpt_every_steps": 2},
            "metrics_server": {"port": 0},
        }))
        r.setup()
        orig_update_goodput = r._prom.update_goodput

        def capture_and_scrape(snapshot):
            orig_update_goodput(snapshot)
            port = r._prom_server.server_address[1]
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics"
            ) as resp:
                scraped["body"] = resp.read().decode()

        mp.setattr(r._prom, "update_goodput", capture_and_scrape)
        last = r.run_train_validation_loop()
    finally:
        mp.undo()
    return tmp, r, last, scraped


def test_e2e_ledger_accounts_a_clean_run(clean_run):
    tmp_path, r, last, _ = clean_run
    assert last["step"] == 4
    roll = _goodput(tmp_path)
    a = roll["attempts"][0]
    assert a["end_reason"] == "exit" and not a["inferred_end"]
    for kind in ("startup", "compile", "step"):
        assert a["segments"].get(kind, 0) > 0, (kind, a["segments"])
    assert a["steps_attempted"] == 4 and a["steps_committed"] == 4
    # the instrumented seams leave almost nothing unattributed on a run
    # with no faults (the acceptance e2e pins 5% on the subprocess run)
    assert a["accounted_fraction"] > 0.9
    # envelope on every metrics record
    recs = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert recs and all(
        rec.get("attempt_id") == a["attempt_id"] and rec.get("restart_count") == 0
        for rec in recs
    )
    # flight-recorder fingerprint carries the attempt identity
    fp = r.telemetry.flight_recorder.fingerprint
    assert fp["attempt"] == {"attempt_id": a["attempt_id"], "restart_count": 0}


def test_e2e_slow_collate_moves_only_input_wait(
    clean_run, tmp_path, devices8, monkeypatch
):
    """slow_collate_ms must surface as `input_wait` seconds, not inflate
    the productive `step` bucket (the window split subtracts it). The
    shared clean run is the uninjected baseline."""
    base_roll = _goodput(clean_run[0])
    slow = _run_recipe(
        _recipe_cfg(tmp_path / "slow", {"fault_injection": {"slow_collate_ms": 60}}),
        monkeypatch, devices8,
    )
    slow.run_train_validation_loop()
    fi.activate(None)  # don't leak the injector into other tests
    slow_roll = _goodput(tmp_path / "slow")
    b, s = base_roll["attempts"][0]["segments"], slow_roll["attempts"][0]["segments"]
    # 4 steps x 60ms of injected collate: the delta lands in input_wait...
    assert s["input_wait"] - b.get("input_wait", 0.0) > 0.15
    # ...and ONLY there: no lost/discard segments, and the productive step
    # bucket did not absorb the delay (generous bound — CPU timing noise)
    assert "rollback_discard" not in s and "preemption_lost" not in s
    assert s["step"] <= 3 * b["step"] + 0.3


def test_e2e_rollback_moves_only_rollback_discard(tmp_path, devices8, monkeypatch):
    """A transient NaN under on_nonfinite=rollback reclassifies exactly the
    re-done steps' time as rollback_discard."""
    import numpy as np
    import jax.numpy as jnp

    cfg = _recipe_cfg(tmp_path, {
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 2, "max_steps": 4,
                           "ckpt_every_steps": 1},
        "fault_tolerance": {"on_nonfinite": "rollback"},
    })
    r = _run_recipe(cfg, monkeypatch, devices8)
    orig_step, fired = r.train_step, []

    def flaky_step(state, batch):
        state, m = orig_step(state, batch)
        if int(jax.device_get(m["step"])) == 3 and not fired:
            fired.append(1)
            m = dict(m)
            m["nonfinite"] = jnp.bool_(True)
        return state, m

    r.train_step = flaky_step
    last = r.run_train_validation_loop()
    assert last["rollbacks_total"] == 1
    roll = _goodput(tmp_path)
    a = roll["attempts"][0]
    assert a["steps_discarded"] == 1  # fail 3, restored 2
    assert a["segments"].get("rollback_discard", 0) > 0
    assert "preemption_lost" not in a["segments"]
    # a rollback also restores a checkpoint: restore time is its own bucket
    assert a["segments"].get("ckpt_restore", 0) > 0
    recs = _read_records(tmp_path / "goodput.jsonl")
    rb = next(r_ for r_ in recs if r_.get("kind") == "rollback_discard")
    assert (rb["fail_step"], rb["restored_step"]) == (3, 2)
    assert np.isfinite(last["loss"])


def test_e2e_die_then_restart_chains_preemption_lost(tmp_path, devices8, monkeypatch):
    """die_at_step (crash mode) at step 5 with commits at 3: the restarted
    attempt resumes from 3 and reclassifies the dead attempt's step-4..5
    time as preemption_lost — the `die_at_step` attribution leg."""
    cfg = _recipe_cfg(tmp_path, {
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 4, "max_steps": 8,
                           "ckpt_every_steps": 3},
        "fault_injection": {"die_at_step": 5, "die_mode": "exception"},
    })
    r = _run_recipe(cfg, monkeypatch, devices8)
    with pytest.raises(fi.InjectedFault):
        r.run_train_validation_loop()
    fi.activate(None)
    roll1 = _goodput(tmp_path)
    assert roll1["attempts"][0]["end_reason"] == "crash"
    # restart (empty fault_injection section clears the injector)
    cfg2 = _recipe_cfg(tmp_path, {
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 4, "max_steps": 6,
                           "ckpt_every_steps": 3},
        "fault_injection": {},
    })
    r2 = _run_recipe(cfg2, monkeypatch, devices8)
    assert int(r2.state.step) == 3  # resumed from the step-3 commit
    r2.run_train_validation_loop()
    roll = _goodput(tmp_path)
    assert roll["run"]["n_attempts"] == 2
    a1, a2 = roll["attempts"]
    # the injected death fires before step 5's window closes: the dead
    # attempt accounted steps 1..4, resumed at 3 → exactly step 4 was lost
    assert a1["steps_lost"] == 1
    assert a1["segments"].get("preemption_lost", 0) > 0
    assert a2["resumed_from_step"] == 3
    assert a2["segments"].get("ckpt_restore", 0) > 0
    assert "preemption_lost" not in a2["segments"]
    # metrics file: restart_count 0-records then 1-records, strict-clean
    from automodel_tpu.telemetry.report import lint_metrics_jsonl

    records, problems = lint_metrics_jsonl(str(tmp_path / "metrics.jsonl"))
    assert problems == []
    rcs = [rec["restart_count"] for rec in records if "restart_count" in rec]
    assert rcs == sorted(rcs) and set(rcs) == {0, 1}
    # the startup restore stamps ckpt_restore_s on the restarted attempt's
    # first log record
    post = [rec for rec in records if rec.get("restart_count") == 1 and "loss" in rec]
    assert post and post[0].get("ckpt_restore_s", 0) > 0


def test_e2e_ckpt_stamps_and_window_excluded(tmp_path, devices8, monkeypatch):
    cfg = _recipe_cfg(tmp_path, {
        "validation_dataset": {
            "_target_": "automodel_tpu.data.sft.MockSFTDataset",
            "vocab_size": 128, "seq_length": 32, "num_samples": 16,
        },
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 2, "max_steps": 4,
                           "ckpt_every_steps": 2, "val_every_steps": 2},
    })
    r = _run_recipe(cfg, monkeypatch, devices8)
    r.run_train_validation_loop()
    recs = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    # the save at the step-2 boundary stamps the NEXT record (step 3)
    rec3 = next(rec for rec in recs if rec.get("step") == 3 and "loss" in rec)
    assert rec3.get("ckpt_save_s", 0) > 0
    # ...which also carries the boundary wall time the window excluded
    assert rec3.get("window_excluded_s", 0) > 0
    # eval + ckpt_save segments in the ledger
    segs = _goodput(tmp_path)["attempts"][0]["segments"]
    assert segs.get("eval", 0) > 0 and segs.get("ckpt_save", 0) > 0
    # records sum to loop wall clock: compile + step windows + excluded
    # boundary time cover what the ledger accounted for those buckets
    from automodel_tpu.telemetry.report import summarize_metrics

    summary = summarize_metrics(recs)
    assert summary["attempts"] == 1
    assert summary["ckpt_save_s_total"] > 0
    assert summary["window_excluded_s_total"] > 0
    # the step-4 boundary (val + ckpt) has no following log record: its
    # time + the final save's stamps ride the closing goodput_tail record
    tail = [rec for rec in recs if rec.get("event") == "goodput_tail"]
    assert tail and (
        tail[-1].get("window_excluded_s", 0) > 0
        or tail[-1].get("ckpt_save_s", 0) > 0
    )
    # (the restart-side ckpt_restore_s stamp is pinned by the die-chain
    # test above, which already pays for a second recipe build)


def test_goodput_cli_renders_and_json(clean_run, tmp_path, capsys):
    run_dir = clean_run[0]
    assert goodput_main([str(run_dir)]) == 0
    out = capsys.readouterr().out
    assert "goodput_fraction" in out and "whole run" in out
    assert "startup" in out and "compile" in out
    assert goodput_main([str(run_dir), "--json"]) == 0
    roll = json.loads(capsys.readouterr().out)
    assert roll["run"]["n_attempts"] == 1
    assert goodput_main([str(tmp_path / "nope")]) == 2


def test_e2e_metrics_port_exports_goodput(clean_run):
    body = clean_run[3]["body"]
    assert "automodel_train_goodput_fraction" in body
    assert 'automodel_train_goodput_seconds{segment="step"}' in body
    assert "automodel_train_ckpt_save_seconds_bucket" in body


def test_report_flags_restart_count_regression(tmp_path):
    from automodel_tpu.telemetry.report import lint_metrics_jsonl

    p = tmp_path / "m.jsonl"
    p.write_text(
        json.dumps({"step": 1, "restart_count": 1, "ts": 1.0}) + "\n"
        + json.dumps({"step": 2, "restart_count": 0, "ts": 2.0}) + "\n"
    )
    _, problems = lint_metrics_jsonl(str(p))
    assert any("restart_count went backwards" in pr for pr in problems)


# ---------------------------------------------------------------------------
# subprocess e2e (acceptance): SIGTERM → 75 → restart → joined ledger;
# hang → watchdog exit → unattributed idle joined to the hang evidence
# ---------------------------------------------------------------------------


def _clean_env(tmp_path):
    # a compile cache of the test's own: the checkout's `.jax_compile_cache`
    # may hold XLA:CPU results another machine type compiled, which abort
    # this host's child (rc -6)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    for k in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_COORDINATOR_ADDRESS",
              "JAX_NUM_PROCESSES", "JAX_PROCESS_ID", fi.ENV_VAR):
        env.pop(k, None)
    return env


def _subprocess_cfg(tmp_path, **extra):
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
                           "max_steps": 100000, "ckpt_every_steps": 3},
        "optimizer": {"name": "adamw", "lr": 1e-3},
        "checkpoint": {"enabled": True, "checkpoint_dir": str(tmp_path / "ckpt")},
        "logging": {"metrics_path": str(tmp_path / "metrics.jsonl")},
        "telemetry": {"memory_every_steps": 0},
    }
    cfg.update(extra)
    return cfg


def test_sigterm_requeue_resume_yields_one_joined_ledger(tmp_path):
    """The acceptance e2e: cadence saves, SIGTERM mid-epoch (emergency
    checkpoint disabled so the kill strands work past the last commit) →
    exit 75 → restart resumes → ONE goodput ledger with two chained
    attempts, a preemption-lost segment equal to steps-since-last-commit,
    and per-attempt segments summing to wall clock within 5%."""
    ckpt_dir = tmp_path / "ckpt"
    metrics = tmp_path / "metrics.jsonl"
    cfg = _subprocess_cfg(
        tmp_path,
        fault_tolerance={"emergency_checkpoint": False},
        # ~300ms/step so the SIGTERM lands a deterministic 2+ steps past
        # the last commit (fast CPU steps would race the cadence and kill
        # at a freshly-committed step — zero lost work to measure)
        fault_injection={"slow_collate_ms": 300},
    )
    cfg["step_scheduler"]["ckpt_every_steps"] = 5
    cfg_path = tmp_path / "cfg.yaml"
    cfg_path.write_text(json.dumps(cfg))  # JSON is valid YAML

    argv = [sys.executable, _WORKER, "finetune", "llm", "-c", str(cfg_path)]
    proc = subprocess.Popen(
        argv, env=_clean_env(tmp_path), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    deadline = time.time() + 300

    def _logged_steps():
        try:
            return [
                json.loads(l).get("step")
                for l in metrics.read_text().splitlines()
                if l.strip()
            ]
        except (OSError, ValueError):
            return []

    try:
        # wait for the step-5 commit AND ≥ 2 more steps past it, so the
        # kill is guaranteed to strand committed-but-unsaved work (the next
        # commit is 3 slow steps away at step 10)
        while True:
            steps = [s for s in _logged_steps() if isinstance(s, int)]
            if (
                list(ckpt_dir.glob("epoch_*_step_5/MANIFEST.json"))
                and steps and max(steps) >= 7
            ):
                break
            if proc.poll() is not None:
                pytest.fail(f"worker died early: {proc.communicate()[1][-2000:]}")
            if time.time() > deadline:
                pytest.fail("worker never reached step 7 with a step-5 commit")
            time.sleep(0.1)
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == REQUEUE_EXIT_CODE, (out[-2000:], err[-2000:])

    committed = sorted(
        (p.parent for p in ckpt_dir.glob("epoch_*_step_*/MANIFEST.json")),
        key=lambda p: int(p.name.rsplit("_", 1)[1]),
    )
    last_commit = int(committed[-1].name.rsplit("_", 1)[1])

    # requeue: resume and run a couple more steps to a clean exit
    out2 = subprocess.run(
        argv + [f"--step_scheduler.max_steps={last_commit + 2}"],
        env=_clean_env(tmp_path), capture_output=True, text=True, timeout=300,
    )
    assert out2.returncode == 0, out2.stderr[-2000:]

    records = _read_records(tmp_path / "goodput.jsonl")
    roll = rollup(records)
    assert roll["run"]["n_attempts"] == 2
    a1, a2 = roll["attempts"]
    # the restarted attempt resumed from the newest commit: everything the
    # killed attempt stepped past it is preemption-lost — exactly
    # steps-since-last-commit (closed windows; the in-flight step at kill
    # time never closed a window, so it was never accounted anywhere)
    attempt1_steps = max(
        r.get("step_to", 0) for r in records
        if r.get("attempt_id") == a1["attempt_id"] and r.get("kind") == "step"
    )
    assert a2["resumed_from_step"] == last_commit
    assert a1["steps_lost"] == attempt1_steps - last_commit >= 1
    assert a1["segments"].get("preemption_lost", 0) > 0
    assert a1["end_reason"] == "preempted"  # graceful drain closed the tail
    # the headline invariant: per-attempt segments sum to measured wall
    # clock within 5% (unattributed is the residual)
    for a in (a1, a2):
        assert a["wall_s"] > 0
        assert a["unattributed_s"] <= 0.05 * a["wall_s"], a
    # and the CLI renders the joined ledger
    out3 = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "from automodel_tpu.telemetry.goodput import main; "
         "sys.exit(main(sys.argv[1:]))" % os.path.dirname(os.path.dirname(_WORKER)),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60,
    )
    assert out3.returncode == 0, out3.stderr[-2000:]
    assert "preemption_lost" in out3.stdout
    assert "whole run — 2 attempt(s)" in out3.stdout


def test_hang_watchdog_exit_reads_as_unattributed_idle(tmp_path):
    """hang_at_step wedges the loop mid-step; the watchdog os._exit(75)
    skips every finally, so the attempt never closes — the rollup must
    infer the tail from the flight-recorder hang evidence and charge the
    silence to `unattributed`, not to any productive segment."""
    cfg = _subprocess_cfg(
        tmp_path,
        fault_injection={"hang_at_step": 3, "hang_seconds": 3600},
        distributed_guard={
            "watchdog": {"min_deadline_s": 4.0, "poll_interval_s": 0.2,
                         "multiplier": 10.0, "compile_grace_s": 600.0},
        },
    )
    cfg["step_scheduler"]["ckpt_every_steps"] = 1
    cfg_path = tmp_path / "cfg.yaml"
    cfg_path.write_text(json.dumps(cfg))
    out = subprocess.run(
        [sys.executable, _WORKER, "finetune", "llm", "-c", str(cfg_path)],
        env=_clean_env(tmp_path), capture_output=True, text=True, timeout=500,
    )
    assert out.returncode == REQUEUE_EXIT_CODE, (
        out.stdout[-2000:], out.stderr[-2000:]
    )
    from automodel_tpu.telemetry.goodput import _collect_events

    records = _read_records(tmp_path / "goodput.jsonl")
    events = _collect_events(tmp_path)
    assert any(e.get("event") == "hang" for e in events)
    roll = rollup(records, events)
    a = roll["attempts"][0]
    # no attempt_end was ever written (os._exit) — the rollup inferred it
    assert a["end_reason"] is None and not a["inferred_end"]
    # the hang silence (≥ the 4s watchdog deadline) is unattributed idle,
    # joined to the hang event naming step 3
    assert a["unattributed_s"] >= 3.5
    # the hang lands in BOTH the flight recorder and the metrics JSONL —
    # the event join must dedupe it to one anomaly
    assert len(a["anomalies"]) == 1 and a["anomalies"][0]["event"] == "hang"
    assert a["anomalies"][0]["step"] == 3
    # the step segments stayed honest: nothing charged the hang to `step`
    assert a["segments"].get("step", 0) < a["unattributed_s"]
    # and only its own segment moved: no lost/discard reclassification
    assert "preemption_lost" not in a["segments"]
    assert "rollback_discard" not in a["segments"]
