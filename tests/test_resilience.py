"""Resilience subsystem (automodel_tpu/resilience/): retrying I/O, manifest
commit + integrity walk-back, (epoch, step) checkpoint ordering/pruning,
preemption → emergency checkpoint → requeue exit code, non-finite-step
policies (raise | skip | rollback), and the fault-injection harness that
drives all of it end-to-end on CPU."""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.resilience import (
    REQUEUE_EXIT_CODE,
    NonFiniteError,
    PreemptionHandler,
    RetriesExhausted,
    TrainingPreempted,
    corrupt_file,
    verify_manifest,
    write_manifest,
)
from automodel_tpu.resilience import fault_injection as fi
from automodel_tpu.resilience.retry import backoff_delays, retry_io

_WORKER = os.path.join(os.path.dirname(__file__), "resilience_worker.py")


@pytest.fixture(autouse=True)
def _reset_fault_injection():
    yield
    fi.activate(None)  # never leak an armed injector into other tests


# ---------------------------------------------------------------------------
# retry.py
# ---------------------------------------------------------------------------


def test_retry_succeeds_after_transient_failures():
    sleeps, calls = [], []

    @retry_io(op="t", max_attempts=4, base_delay_s=0.1, max_delay_s=10.0,
              jitter=0.0, sleep=sleeps.append)
    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert flaky() == "ok"
    assert len(calls) == 3
    assert sleeps == [pytest.approx(0.1), pytest.approx(0.2)]  # exponential


def test_retry_exhaustion_chains_last_error():
    sleeps = []

    @retry_io(op="t", max_attempts=3, base_delay_s=0.01, jitter=0.0,
              sleep=sleeps.append)
    def dead():
        raise OSError("gone")

    with pytest.raises(RetriesExhausted) as ei:
        dead()
    assert ei.value.attempts == 3
    assert isinstance(ei.value.__cause__, OSError)
    assert len(sleeps) == 2  # no sleep after the final attempt


def test_retry_typed_filter_propagates_immediately():
    calls = []

    @retry_io(op="t", max_attempts=5, sleep=lambda d: None)
    def buggy():
        calls.append(1)
        raise ValueError("a bug, not weather")

    with pytest.raises(ValueError):
        buggy()
    assert len(calls) == 1  # not retried


def test_backoff_delays_cap_and_jitter_bounds():
    ds = list(backoff_delays(6, base_delay_s=1.0, max_delay_s=4.0, jitter=0.0))
    assert ds == [1.0, 2.0, 4.0, 4.0, 4.0]
    for d, base in zip(
        backoff_delays(4, 1.0, 100.0, jitter=0.25), [1.0, 2.0, 4.0]
    ):
        assert 0.75 * base <= d <= 1.25 * base


def test_fault_injection_fails_first_m_io_attempts():
    fi.activate({"fail_io_attempts": 2, "fail_io_op": "flaky_op"})
    calls = []

    @retry_io(op="flaky_op", max_attempts=4, sleep=lambda d: None)
    def fn():
        calls.append(1)
        return "made it"

    # two injected failures absorbed by the backoff, third attempt runs
    assert fn() == "made it"
    assert len(calls) == 1

    @retry_io(op="flaky_op_2", max_attempts=2, sleep=lambda d: None)
    def fn2():
        return "never"

    fi.activate({"fail_io_attempts": 5, "fail_io_op": "flaky_op_2"})
    with pytest.raises(RetriesExhausted):
        fn2()  # more injected failures than attempts → exhausts loudly


def test_fault_injection_empty_section_stays_inactive():
    """`fault_injection: {}` (the docs' example form) must not arm a
    do-nothing injector — or its scary ACTIVE warning — in a real run."""
    assert fi.activate({}) is None and fi.active_injector() is None
    assert fi.activate({"die_mode": "exception"}) is None  # nothing armed
    assert fi.activate({"die_at_step": 3}) is not None


# ---------------------------------------------------------------------------
# manifest.py
# ---------------------------------------------------------------------------


def test_manifest_roundtrip_and_corruption_detection(tmp_path):
    d = tmp_path / "epoch_0_step_3"
    (d / "state").mkdir(parents=True)
    (d / "state" / "arrays.bin").write_bytes(os.urandom(4096))
    (d / "extra_state.json").write_text("{}")
    write_manifest(d, epoch=0, step=3, layout_markers={"k": "v1"})
    ok, problems = verify_manifest(d)
    assert ok and not problems
    m = json.loads((d / "MANIFEST.json").read_text())
    assert m["step"] == 3 and m["fingerprint"]["layout_markers"] == {"k": "v1"}
    assert set(m["files"]) == {"state/arrays.bin", "extra_state.json"}

    # flipped bytes → named in problems; size-only pass stays green
    corrupt_file(d / "state" / "arrays.bin")
    ok, problems = verify_manifest(d)
    assert not ok and any("arrays.bin" in p and "checksum" in p for p in problems)
    ok_sz, _ = verify_manifest(d, check_checksums=False)
    assert ok_sz

    # truncation → caught by the cheap size pass too
    with open(d / "extra_state.json", "w") as f:
        f.write("")
    ok_sz, problems = verify_manifest(d, check_checksums=False)
    assert not ok_sz and any("size" in p for p in problems)


def test_manifest_skips_stale_orbax_tmp_dirs(tmp_path):
    """Garbage from a killed async save (`state.orbax-checkpoint-tmp-*`)
    next to a re-saved step must not be checksummed into the manifest:
    listing it retains dead bytes forever and makes its later cleanup look
    like corruption (good dir quarantined, pointless walk-back)."""
    d = tmp_path / "epoch_0_step_3"
    (d / "state").mkdir(parents=True)
    (d / "state" / "arrays.bin").write_bytes(os.urandom(256))
    stale = d / "state.orbax-checkpoint-tmp-12345"
    stale.mkdir()
    (stale / "array.bin").write_bytes(b"\0" * 64)
    write_manifest(d, epoch=0, step=3)
    m = json.loads((d / "MANIFEST.json").read_text())
    assert set(m["files"]) == {"state/arrays.bin"}
    shutil.rmtree(stale)  # operator tidy / orbax GC
    ok, problems = verify_manifest(d)
    assert ok, problems  # cleanup is NOT corruption
    # the checkpointer reclaims the leftover on the next save of the step
    ck = _mk_checkpointer(tmp_path)
    out = ck.save(_state(1.0), epoch=0, step=1)
    stale2 = out / "state.orbax-checkpoint-tmp-99"
    stale2.mkdir()
    ck.save(_state(2.0), epoch=0, step=1)
    assert not stale2.exists()

    # missing manifest = uncommitted
    (d / "MANIFEST.json").unlink()
    ok, problems = verify_manifest(d)
    assert not ok and "missing" in problems[0]


# ---------------------------------------------------------------------------
# checkpointer: commit marker, ordering, prune, walk-back
# ---------------------------------------------------------------------------


def _mk_checkpointer(tmp_path, **kw):
    from automodel_tpu.checkpoint.checkpointer import Checkpointer, CheckpointingConfig

    return Checkpointer(CheckpointingConfig(checkpoint_dir=str(tmp_path / "run"), **kw))


def _state(v: float):
    return {"w": jnp.full((4,), v, jnp.float32)}


def _abstract():
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), _state(0.0))


def test_save_commits_manifest_and_orders_by_epoch_then_step(tmp_path):
    ck = _mk_checkpointer(tmp_path)
    d1 = ck.save(_state(1.0), epoch=0, step=100)
    d2 = ck.save(_state(2.0), epoch=1, step=50)
    assert (d1 / "MANIFEST.json").exists() and (d2 / "MANIFEST.json").exists()
    # step alone would pick epoch_0_step_100; (epoch, step) must win
    assert ck.latest_dir().name == "epoch_1_step_50"
    restored, _ = ck.load(_abstract())
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 2.0))


def test_kill_during_async_save_falls_back_to_committed(tmp_path):
    """A dir left by a killed async save — even one whose orbax rename
    landed — has no manifest and must not count as a checkpoint."""
    ck = _mk_checkpointer(tmp_path)
    ck.save(_state(1.0), epoch=0, step=1)
    # simulate the kill: completed-looking state dir, no manifest
    dead = ck.root / "epoch_0_step_2"
    (dead / "state").mkdir(parents=True)
    (dead / "state" / "junk.bin").write_bytes(b"\0" * 128)
    assert ck.latest_dir().name == "epoch_0_step_1"
    restored, _ = ck.load(_abstract())
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 1.0))


def test_async_save_commits_on_close(tmp_path):
    ck = _mk_checkpointer(tmp_path, is_async=True)
    out = ck.save(_state(3.0), epoch=0, step=2)
    ck.close()  # drains the upload, then writes the manifest
    assert (out / "MANIFEST.json").exists()
    ok, problems = verify_manifest(out)
    assert ok, problems


def test_async_drain_failure_costs_one_checkpoint_not_the_run(tmp_path, monkeypatch):
    """A transient storage error surfacing at the async drain must leave
    the dir uncommitted (resume skips it) WITHOUT propagating — the run
    keeps training and the next cadence save commits normally."""
    ck = _mk_checkpointer(tmp_path, is_async=True)
    events = []
    ck.event_hook = events.append
    d1 = ck.save(_state(1.0), epoch=0, step=1)
    monkeypatch.setattr(
        ck._async, "wait_until_finished",
        lambda: (_ for _ in ()).throw(OSError("remote store flaked")),
    )
    ck.wait()  # swallows: checkpoint lost, run survives
    assert not (d1 / "MANIFEST.json").exists()
    assert any(e.get("event") == "async_save_failed" for e in events)
    monkeypatch.undo()
    d2 = ck.save(_state(2.0), epoch=0, step=2)
    ck.close()
    assert (d2 / "MANIFEST.json").exists()
    restored, _ = ck.load(_abstract())
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 2.0))


def test_legacy_tree_without_manifests_still_resumes(tmp_path):
    ck = _mk_checkpointer(tmp_path)
    for step, v in ((1, 1.0), (2, 2.0)):
        out = ck.save(_state(v), epoch=0, step=step)
        (out / "MANIFEST.json").unlink()  # pre-manifest era save
    assert ck.latest_dir().name == "epoch_0_step_2"
    restored, _ = ck.load(_abstract())
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 2.0))


def test_load_walks_back_past_corrupt_newest(tmp_path):
    ck = _mk_checkpointer(tmp_path)
    events = []
    ck.event_hook = events.append
    ck.save(_state(1.0), epoch=0, step=1)
    d2 = ck.save(_state(2.0), epoch=0, step=2)
    victim = next(p for p in (d2 / "state").rglob("*") if p.is_file() and p.stat().st_size > 0)
    corrupt_file(victim)
    restored, _ = ck.load(_abstract())  # newest fails checksums → step 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 1.0))
    assert any(e.get("event") == "checkpoint_fallback" for e in events)
    # the corrupt dir is quarantined out of the epoch_*_step_* namespace:
    # it must not occupy a keep_last_k slot (pruning would otherwise delete
    # newer GOOD post-resume saves while keeping the corrupt one forever)
    assert not d2.exists()
    assert (ck.root / "epoch_0_step_2.corrupt").exists()
    assert ck.latest_dir().name == "epoch_0_step_1"

    # corrupt the survivor too → bounded walk-back exhausts loudly
    from automodel_tpu.checkpoint.checkpointer import CheckpointIntegrityError

    d1 = ck.root / "epoch_0_step_1"
    victim1 = next(p for p in (d1 / "state").rglob("*") if p.is_file() and p.stat().st_size > 0)
    corrupt_file(victim1)
    with pytest.raises(CheckpointIntegrityError):
        ck.load(_abstract())


def test_walk_back_reaches_legacy_dirs_as_last_resort(tmp_path):
    """A manifest-era tree still holding valid pre-manifest checkpoints:
    strict commit semantics ignore them for latest/prune, but the restore
    walk-back must prefer them over crashing when every manifest-era dir
    fails verification."""
    ck = _mk_checkpointer(tmp_path)
    legacy = ck.save(_state(5.0), epoch=0, step=5)
    (legacy / "MANIFEST.json").unlink()  # pre-manifest era save
    d9 = ck.save(_state(9.0), epoch=0, step=9)  # manifest era begins
    assert ck.latest_dir().name == "epoch_0_step_9"
    assert ck.latest_committed_dir().name == "epoch_0_step_9"
    victim = next(
        p for p in (d9 / "state").rglob("*") if p.is_file() and p.stat().st_size > 0
    )
    corrupt_file(victim)
    restored, _ = ck.load(_abstract())  # quarantines 9 → legacy last resort
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 5.0))


def test_append_attempt_idempotent_after_durable_write(tmp_path):
    """A retry whose previous attempt wrote the FULL line durably (flush
    raised a deferred error afterwards) must not append the record twice —
    the per-append offset makes the second attempt truncate first."""
    from automodel_tpu.loggers.metric_logger import _append_attempt

    p = tmp_path / "m.jsonl"
    p.write_text('{"step": 1}\n')
    state: dict = {}
    _append_attempt(p, b'{"step": 2}\n', state)  # attempt 1: lands durably
    _append_attempt(p, b'{"step": 2}\n', state)  # retry after failed flush
    assert p.read_text().splitlines() == ['{"step": 1}', '{"step": 2}']


def test_append_attempt_never_truncates_another_writers_record(tmp_path):
    """Shared-FS multi-host logging: bytes another writer appended between
    our attempts are NOT a prefix of our record, so the retry must move its
    offset forward (worst case: our record duplicated) instead of
    truncating the other host's committed record away."""
    from automodel_tpu.loggers.metric_logger import _append_attempt

    p = tmp_path / "m.jsonl"
    p.write_text('{"host": "a", "step": 1}\n')
    ours = b'{"host": "a", "step": 2}\n'
    state: dict = {}
    _append_attempt(p, ours, state)  # lands durably, flush "fails"
    with open(p, "ab") as f:  # host B appends between our attempts
        f.write(b'{"host": "b", "step": 2}\n')
    _append_attempt(p, ours, state)  # retry
    lines = p.read_text().splitlines()
    assert '{"host": "b", "step": 2}' in lines  # B's record survived
    assert lines[0] == '{"host": "a", "step": 1}'
    assert all(l.startswith("{") and l.endswith("}") for l in lines)


def test_append_attempt_lockfree_seals_partial_tail(tmp_path, monkeypatch):
    """Filesystems where flock is unavailable can't prove a dangling tail
    is dead, so it can't be truncated — but appending straight onto it
    would merge it into OUR record and destroy both. The fallback seals
    the fragment with a newline: it becomes its own lint-flagged line and
    the new record stays parseable."""
    from automodel_tpu.loggers import metric_logger as ml

    monkeypatch.setattr(ml, "fcntl", None)
    p = tmp_path / "m.jsonl"
    p.write_bytes(b'{"step": 1}\n{"step": 2, "lo')  # crashed mid-record
    ml._append_attempt(p, b'{"step": 3}\n', {})
    lines = p.read_text().splitlines()
    assert lines[0] == '{"step": 1}'
    assert lines[1] == '{"step": 2, "lo'  # sealed, not merged/truncated
    assert json.loads(lines[2]) == {"step": 3}


def test_explicit_restore_from_never_silently_substitutes(tmp_path):
    from automodel_tpu.checkpoint.checkpointer import CheckpointIntegrityError

    ck = _mk_checkpointer(tmp_path)
    ck.save(_state(1.0), epoch=0, step=1)
    d2 = ck.save(_state(2.0), epoch=0, step=2)
    victim = next(p for p in (d2 / "state").rglob("*") if p.is_file() and p.stat().st_size > 0)
    corrupt_file(victim)
    with pytest.raises(CheckpointIntegrityError):
        ck.load(_abstract(), path=d2)  # asked for THIS dir; no walk-back


def test_prune_counts_committed_only_and_protects_restore_from(tmp_path):
    ck = _mk_checkpointer(tmp_path, keep_last_k=2)
    d1 = ck.save(_state(1.0), epoch=0, step=1)
    ck.save(_state(2.0), epoch=0, step=2)
    ck.save(_state(3.0), epoch=0, step=3)
    assert not d1.exists()  # beyond k, unprotected → pruned
    # uncommitted crash leftovers: one NEWER than any committed dir (could
    # be the in-flight save — untouchable) and one strictly OLDER (garbage
    # a killed save left behind — collected)
    newer = ck.root / "epoch_0_step_9"
    (newer / "state").mkdir(parents=True)
    # a kill mid-upload leaves only the orbax tmp dir, never state/
    stale = ck.root / "epoch_0_step_0"
    (stale / "state.orbax-checkpoint-tmp-42").mkdir(parents=True)
    # a legacy (pre-manifest) checkpoint HAS state/ — must never be swept
    legacy = ck.root / "epoch_0_step_1"
    (legacy / "state").mkdir(parents=True)
    ck.config.restore_from = str(ck.root / "epoch_0_step_2")
    ck.save(_state(4.0), epoch=0, step=4)
    ck.save(_state(5.0), epoch=0, step=5)
    names = {p.name for p in ck.root.iterdir()}
    assert "epoch_0_step_2" in names  # restore_from survives beyond k
    assert "epoch_0_step_3" not in names  # normal victim pruned
    assert {"epoch_0_step_4", "epoch_0_step_5"} <= names
    assert "epoch_0_step_9" in names  # newer uncommitted: untouched, uncounted
    assert "epoch_0_step_0" not in names  # stale tmp-only leftover: collected
    assert "epoch_0_step_1" in names  # legacy-looking dir with state/: kept


def test_restore_from_is_bootstrap_not_a_pin(tmp_path):
    """restore_from seeds the FIRST resume only; once the run commits its
    own checkpoints (e.g. the emergency save of a preempted run), those
    win — otherwise a requeued job would loop on the base checkpoint
    forever. Walk-back (before_step) must also prefer run-local dirs."""
    base = _mk_checkpointer(tmp_path / "base")
    base_dir = base.save(_state(7.0), epoch=0, step=99)

    ck = _mk_checkpointer(tmp_path, restore_from=str(base_dir))
    # empty run tree → bootstrap from restore_from; but the RUN-LOCAL view
    # (what decides preemption requeue-eligibility) stays empty
    assert ck.latest_dir() == base_dir
    assert ck.latest_committed_dir() is None
    restored, _ = ck.load(_abstract())
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 7.0))
    # run-local commits take precedence from then on
    ck.save(_state(1.0), epoch=0, step=1)
    ck.save(_state(2.0), epoch=0, step=2)
    assert ck.latest_dir().name == "epoch_0_step_2"
    assert ck.latest_committed_dir().name == "epoch_0_step_2"
    restored, _ = ck.load(_abstract())
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 2.0))
    # rollback's strictly-before constraint: run-local step 1 wins; with no
    # run-local dir before the fail step, the bootstrap is the fallback
    restored, _ = ck.load(_abstract(), before_step=2)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 1.0))
    restored, _ = ck.load(_abstract(), before_step=1)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full((4,), 7.0))


def test_size_only_manifests(tmp_path):
    """checkpoint.manifest_checksums=false: commit + truncation detection
    without the commit-time checksum read-back."""
    ck = _mk_checkpointer(tmp_path, manifest_checksums=False)
    out = ck.save(_state(1.0), epoch=0, step=1)
    m = json.loads((out / "MANIFEST.json").read_text())
    assert m["algorithm"] == "size-only"
    assert all("crc32" not in e for e in m["files"].values())
    ok, problems = verify_manifest(out)  # full verify: nothing to checksum
    assert ok, problems
    victim = next(p for p in (out / "state").rglob("*") if p.is_file() and p.stat().st_size > 64)
    with open(victim, "r+b") as f:  # truncation IS still caught
        f.truncate(10)
    ok, problems = verify_manifest(out)
    assert not ok and any("size" in p for p in problems)


def test_metric_logger_seals_partial_trailing_line(tmp_path):
    """A crash (or failed retry attempt) mid-append leaves a partial record
    with no trailing newline. The next append SEALS it with a newline
    instead of truncating it: a dangling tail is indistinguishable from
    another live writer's in-flight record (NFS flock can be a per-host
    no-op), so unowned bytes are never deleted — the fragment becomes its
    own lint-flagged line and every real record stays parseable."""
    from automodel_tpu.loggers.metric_logger import MetricLogger

    ml = MetricLogger(str(tmp_path / "m.jsonl"))
    ml.log({"step": 1, "loss": 1.0})
    with open(ml.path, "ab") as f:  # crash mid-append: partial record
        f.write(b'{"step": 2, "los')
    ml.log({"step": 3, "loss": 3.0})
    lines = ml.path.read_text().splitlines()
    assert lines[1] == '{"step": 2, "los'  # sealed, not merged/truncated
    recs = []
    for l in lines:
        try:
            recs.append(json.loads(l))
        except ValueError:
            pass  # the sealed fragment — report.py lints past it the same way
    assert [r["step"] for r in recs] == [1, 3]
    # unlink mid-run (log rotation): the logger recreates and keeps going
    ml.path.unlink()
    ml.log({"step": 6, "loss": 6.0})
    assert json.loads(ml.path.read_text())["step"] == 6


def test_report_lint_gates_backwards_steps_on_resume_marker(tmp_path):
    from automodel_tpu.telemetry.report import lint_metrics_jsonl, summarize_metrics

    p = tmp_path / "m.jsonl"
    # a rewind with NO marker is still corruption
    p.write_text(
        '{"step": 5, "loss": 1.0, "ts": 1}\n{"step": 2, "loss": 1.0, "ts": 2}\n'
    )
    _, problems = lint_metrics_jsonl(str(p))
    assert any("backwards" in x for x in problems)
    # a rewind AFTER a resume marker (stamped by every checkpoint restore)
    # is a legitimate retrain, surfaced as a resume point
    p.write_text(
        '{"step": 5, "loss": 1.0, "ts": 1}\n'
        '{"event": "resume", "resumed_from_step": 1, "ts": 2}\n'
        '{"step": 2, "loss": 1.0, "ts": 3}\n'
    )
    recs, problems = lint_metrics_jsonl(str(p))
    assert not problems
    assert summarize_metrics(recs).get("resume_points") == [2]


def test_slurm_requeue_template():
    from automodel_tpu.launcher.slurm import SlurmConfig, render_sbatch

    s = render_sbatch(SlurmConfig(), "finetune", "llm", "c.yaml")
    assert "#SBATCH --requeue" in s
    assert "scontrol requeue $SLURM_JOB_ID" in s
    # multi-node: srun reports the HIGHEST task rc (SIGKILLed peers → 137
    # masks the 75), so the per-task marker must gate the requeue too
    assert 'touch ".preempted_$SLURM_JOB_ID"' in s
    assert '[ -f ".preempted_$SLURM_JOB_ID" ]' in s
    off = render_sbatch(
        SlurmConfig(requeue_on_preemption=False), "finetune", "llm", "c.yaml"
    )
    assert "scontrol requeue" not in off and "--requeue" not in off


def test_k8s_pod_failure_policy_ignores_disruption_kills():
    """A spot preemption whose emergency save outlives the grace window
    ends in SIGKILL (137, not 75) — the DisruptionTarget Ignore rule must
    match FIRST so that kill requeues instead of tripping the catch-all
    FailJob with backoffLimit 0."""
    from automodel_tpu.launcher.k8s import K8sConfig, render_manifest
    from automodel_tpu.resilience.preemption import REQUEUE_EXIT_CODE

    m = render_manifest(K8sConfig(), "finetune", "llm", "c.yaml")
    assert "podFailurePolicy" in m and f"values: [{REQUEUE_EXIT_CODE}]" in m
    assert m.index("DisruptionTarget") < m.index("onExitCodes")
    assert "FailJob" in m and "backoffLimit: 0" in m  # single host: fail fast
    # multi-host: a preempted host's PEERS die with ordinary exit codes
    # (broken collectives) — no FailJob catch-all; a bounded backoffLimit
    # absorbs the collateral instead
    mh = render_manifest(K8sConfig(num_hosts=4), "finetune", "llm", "c.yaml")
    assert "FailJob" not in mh and "DisruptionTarget" in mh
    assert "backoffLimit: 16" in mh
    off = render_manifest(
        K8sConfig(requeue_on_preemption=False), "finetune", "llm", "c.yaml"
    )
    assert "podFailurePolicy" not in off and "backoffLimit: 0" in off


def test_verify_ckpt_cli(tmp_path):
    from automodel_tpu.checkpoint.verify import main as verify_main

    ck = _mk_checkpointer(tmp_path)
    ck.save(_state(1.0), epoch=0, step=1)
    d2 = ck.save(_state(2.0), epoch=0, step=2)
    assert verify_main([str(ck.root)]) == 0
    victim = next(p for p in (d2 / "state").rglob("*") if p.is_file() and p.stat().st_size > 0)
    corrupt_file(victim)
    assert verify_main([str(ck.root)]) == 1  # corrupt dir flagged
    assert verify_main([str(ck.root), "--no-checksums"]) == 0  # sizes intact
    assert verify_main([str(tmp_path / "nope")]) == 2


def test_verify_ckpt_tolerates_uncommitted_leftover(tmp_path):
    """An uncommitted kill-mid-save leftover next to verified checkpoints
    is a state the Checkpointer itself tolerates (resume skips it, _prune
    GCs it) — the audit must report it but still exit 0; a tree with
    NOTHING committed is a real failure."""
    from automodel_tpu.checkpoint.verify import main as verify_main

    ck = _mk_checkpointer(tmp_path)
    ck.save(_state(1.0), epoch=0, step=1)
    leftover = ck.root / "epoch_0_step_2" / "state"
    leftover.mkdir(parents=True)
    (leftover / "data.bin").write_bytes(b"x" * 16)  # no MANIFEST.json
    assert verify_main([str(ck.root)]) == 0
    # no manifests anywhere + completed state/ dirs = legacy pre-manifest
    # tree, which the Checkpointer's fallback resumes → audit says so too
    legacy = tmp_path / "legacy_tree"
    (legacy / "epoch_0_step_1" / "state").mkdir(parents=True)
    assert verify_main([str(legacy)]) == 0
    # nothing resumable at all (only a mid-upload tmp, never a state/)
    only_bad = tmp_path / "only_uncommitted"
    (only_bad / "epoch_0_step_1" / "state.orbax-checkpoint-tmp-1").mkdir(parents=True)
    assert verify_main([str(only_bad)]) == 1


# ---------------------------------------------------------------------------
# step scheduler: chaining handlers, epoch-tail shutdown
# ---------------------------------------------------------------------------


def test_scheduler_chains_and_restores_prior_handler():
    from automodel_tpu.training.step_scheduler import StepScheduler

    prior_calls = []
    prior = lambda s, f: prior_calls.append(s)  # noqa: E731
    old = signal.signal(signal.SIGUSR1, prior)
    try:
        sched = StepScheduler(dataloader=[{"x": 1}, {"x": 2}], num_epochs=1)
        sched.install_signal_handler((signal.SIGUSR1,))
        os.kill(os.getpid(), signal.SIGUSR1)
        assert sched.shutdown_requested
        assert prior_calls == [signal.SIGUSR1]  # chained, not clobbered
        list(sched)  # drain
        # restoration is the CALLER's job (the recipe runs it after the
        # end-of-run save, so a second signal during that save still hits
        # the chaining handler) — until then our handler stays installed
        assert signal.getsignal(signal.SIGUSR1) is not prior
        sched.restore_signal_handlers()
        assert signal.getsignal(signal.SIGUSR1) is prior
    finally:
        signal.signal(signal.SIGUSR1, old)


def test_scheduler_epoch_tail_shutdown_stops_before_next_epoch():
    from automodel_tpu.training.step_scheduler import StepScheduler

    sched = StepScheduler(grad_acc_steps=2, num_epochs=3)

    class TailSignaler:
        """3 batches/epoch: batch 3 is the tail (never fills a group);
        the shutdown lands while producing it — mid-group, end of epoch."""

        def __iter__(self):
            for i in range(3):
                if i == 2 and sched.epoch == 0:
                    sched.request_shutdown()
                yield {"i": i}

    sched.dataloader = TailSignaler()
    groups = list(sched)
    assert len(groups) == 1  # epoch 0's one full group; NOT one from epoch 1
    assert sched.epoch == 1


def test_preemption_handler_chain_flag_restore():
    fired = []
    prior_calls = []
    old = signal.signal(signal.SIGUSR2, lambda s, f: prior_calls.append(s))
    try:
        h = PreemptionHandler(signals=("SIGUSR2",), on_preempt=lambda: fired.append(1))
        with h:
            assert not h.preempted
            os.kill(os.getpid(), signal.SIGUSR2)
            assert h.preempted
            assert fired == [1] and len(prior_calls) == 1
            os.kill(os.getpid(), signal.SIGUSR2)
            assert fired == [1]  # on_preempt fires once
        assert signal.getsignal(signal.SIGUSR2) not in (h._handle,)  # restored
    finally:
        signal.signal(signal.SIGUSR2, old)


def test_peer_preemption_marker_fresh_and_stale(tmp_path):
    from automodel_tpu.resilience.preemption import (
        PEER_PREEMPTION_MARKER,
        peer_preemption_fresh,
        write_peer_preemption_marker,
    )

    root = tmp_path / "ckpts"
    assert not peer_preemption_fresh(root)  # nothing there
    write_peer_preemption_marker(root)
    assert peer_preemption_fresh(root)
    # age it past the freshness window: a crash hours after the last
    # preemption is a real crash, never excused by a stale marker
    marker = root / PEER_PREEMPTION_MARKER
    old = time.time() - 7200
    os.utime(marker, (old, old))
    assert not peer_preemption_fresh(root)
    write_peer_preemption_marker(root)  # touch refreshes
    assert peer_preemption_fresh(root)


def test_arm_peer_marker_chains_prior_on_preempt(tmp_path):
    from automodel_tpu.resilience import (
        FaultToleranceConfig,
        Resilience,
        peer_preemption_fresh,
    )

    res = Resilience(FaultToleranceConfig())
    prior_calls = []
    # the recipe installs request_shutdown here BEFORE arming the marker;
    # arming must chain it, not clobber it
    res.preemption.on_preempt = lambda: prior_calls.append(1)
    res.arm_peer_marker(tmp_path / "ckpts")
    res.preemption.on_preempt()
    assert prior_calls == [1]
    assert peer_preemption_fresh(tmp_path / "ckpts")


def test_cli_classifies_crash_as_preemption_collateral(tmp_path):
    from automodel_tpu.cli.app import _crash_is_preemption_collateral
    from automodel_tpu.resilience.preemption import (
        PEER_PREEMPTION_MARKER,
        write_peer_preemption_marker,
    )

    root = tmp_path / "ckpts"
    cfg_on = {"checkpoint": {"enabled": True, "checkpoint_dir": str(root)}}
    assert not _crash_is_preemption_collateral(cfg_on)  # no marker: real crash
    write_peer_preemption_marker(root)
    assert _crash_is_preemption_collateral(cfg_on)
    # checkpointing off → no shared root to trust, marker or not
    assert not _crash_is_preemption_collateral({"checkpoint": {"enabled": False}})
    assert not _crash_is_preemption_collateral({})
    # stale marker → real crash again
    old = time.time() - 7200
    os.utime(root / PEER_PREEMPTION_MARKER, (old, old))
    assert not _crash_is_preemption_collateral(cfg_on)


# ---------------------------------------------------------------------------
# in-jit skip policy (unit) — bit-identical carry-through
# ---------------------------------------------------------------------------


def test_train_step_skip_discards_update_bit_identically():
    import optax

    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step

    def loss_fn(params, mb):
        pred = params["w"] * mb["x"]
        return jnp.sum((pred - 1.0) ** 2), jnp.int32(mb["x"].size)

    opt = optax.adam(1e-2)
    params = {"w": jnp.arange(1.0, 5.0, dtype=jnp.float32)}
    state = TrainState.create(params, opt.init(params))
    step = build_train_step(
        loss_fn, opt, donate=False, anomaly_flags=True,
        on_nonfinite="skip", nan_grads_at_step=2,
    )
    batch = {"x": jnp.ones((1, 4), jnp.float32)}

    state, m1 = step(state, batch)
    assert not bool(jax.device_get(m1["skipped"]))
    p1 = jax.device_get(state.params)
    o1 = jax.device_get(state.opt_state)

    state, m2 = step(state, batch)  # poisoned step
    m2 = jax.device_get(m2)
    assert bool(m2["skipped"]) and bool(m2["nonfinite"])
    p2 = jax.device_get(state.params)
    o2 = jax.device_get(state.opt_state)
    # params AND optimizer moments carried through bit-identical
    jax.tree.map(np.testing.assert_array_equal, p1, p2)
    jax.tree.map(np.testing.assert_array_equal, o1, o2)
    assert int(jax.device_get(state.step)) == 2  # step still advances

    state, m3 = step(state, batch)  # recovery
    assert not bool(jax.device_get(m3["skipped"]))
    p3 = jax.device_get(state.params)
    assert not np.array_equal(p3["w"], p2["w"])  # training resumed


# ---------------------------------------------------------------------------
# recipe-level policies (tiny llama on the 8-device CPU mesh)
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
            "backend": {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"},
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


def test_e2e_skip_policy_counts_and_finishes(tmp_path, devices8, monkeypatch):
    """Acceptance (c): a planted-NaN step with on_nonfinite=skip leaves the
    run alive; the skip is counted in the metrics and the JSONL flags the
    exact step."""
    cfg = _recipe_cfg(tmp_path, {
        "fault_tolerance": {"on_nonfinite": "skip"},
        "fault_injection": {"nan_grads_at_step": 2},
    })
    r = _run_recipe(cfg, monkeypatch, devices8)
    last = r.run_train_validation_loop()
    assert last["step"] == 4
    assert np.isfinite(last["loss"])
    assert last["skipped_steps_total"] == 1
    lines = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    rec2 = next(l for l in lines if l.get("step") == 2 and "skipped" in l)
    assert rec2["skipped"] is True and rec2["nonfinite"] is True
    # grads (not the loss) were poisoned: grad_norm serialized as strict-
    # JSON null with the sidecar marker
    assert rec2.get("grad_norm") is None and rec2.get("grad_norm_nonfinite") is True
    # params stayed finite through the poisoned step
    flat = jax.device_get(jax.tree.leaves(r.state.params))
    assert all(np.isfinite(x).all() for x in flat)


def test_e2e_raise_policy_dumps_flight_recorder(tmp_path, devices8, monkeypatch):
    cfg = _recipe_cfg(tmp_path, {
        "fault_injection": {"nan_grads_at_step": 2},  # default policy: raise
    })
    r = _run_recipe(cfg, monkeypatch, devices8)
    with pytest.raises(NonFiniteError, match="step 2"):
        r.run_train_validation_loop()
    dump = json.loads((tmp_path / "flight_recorder.json").read_text())
    assert dump["reason"] == "NonFiniteError"
    assert any(rec.get("event") == "nonfinite_step" for rec in dump["records"])


def test_raise_policy_never_commits_poisoned_cadence_checkpoint(
    tmp_path, devices8, monkeypatch
):
    """Checkpoint cadence hits the diverged step: the pending flag must be
    resolved BEFORE the save (integrity checksums can't see NaN), so the
    newest committed checkpoint stays the healthy pre-divergence one and a
    restarted run does not crash-loop on poisoned params."""
    cfg = _recipe_cfg(tmp_path, {
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 2, "max_steps": 4,
                           "ckpt_every_steps": 1},
        "fault_injection": {"nan_grads_at_step": 2},  # default policy: raise
    })
    r = _run_recipe(cfg, monkeypatch, devices8)
    with pytest.raises(NonFiniteError, match="step 2"):
        r.run_train_validation_loop()
    committed = {p.parent.name for p in (tmp_path / "ckpt").glob("*/MANIFEST.json")}
    assert committed == {"epoch_0_step_1"}  # step 2 was never persisted


def test_e2e_rollback_restores_and_completes(tmp_path, devices8, monkeypatch):
    """One transient NaN at step 3 → restore the step-2 checkpoint,
    fast-forward the data past the bad window, finish all 4 steps."""
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
            m["nonfinite"] = jnp.bool_(True)  # transient divergence
        return state, m

    r.train_step = flaky_step
    last = r.run_train_validation_loop()
    assert last["step"] == 4
    assert last["rollbacks_total"] == 1
    assert np.isfinite(last["loss"])
    # the offending window's batch was skipped: restore to step 2 (2
    # consumed) + 1 fast-forwarded + replay of steps 3,4 + the scheduler's
    # one look-ahead batch before noticing max_steps → 6 (a run without the
    # rollback ends at 5)
    assert r.dataloader.state_dict()["batch_in_epoch"] == 6


def test_e2e_rollback_budget_exhausts_to_raise(tmp_path, devices8, monkeypatch):
    """A DETERMINISTIC NaN (injected by step number, so it recurs after the
    restore) must burn the rollback budget and then raise — not loop."""
    cfg = _recipe_cfg(tmp_path, {
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 2, "max_steps": 4,
                           "ckpt_every_steps": 1},
        "fault_tolerance": {"on_nonfinite": "rollback", "max_rollbacks": 1},
        "fault_injection": {"nan_grads_at_step": 2},
    })
    r = _run_recipe(cfg, monkeypatch, devices8)
    with pytest.raises(NonFiniteError):
        r.run_train_validation_loop()
    assert r.resilience.rollbacks == 1  # budget consumed before raising


def test_rollback_fast_forward_accounts_for_epoch_tail():
    """The fast-forward must replay the scheduler's consumption, not
    steps*grad_acc: with len(dl)=10 and grad_acc=3, each epoch discards one
    tail batch, so skipping steps 3..5 from a step-2 checkpoint lands at
    epoch 1 batch 6 — the naive 3*3=9-batch skip would land at epoch 1
    batch 5, INSIDE the offending group, and retrain the bad batch."""
    from types import SimpleNamespace

    from automodel_tpu.recipes.train_ft import (
        TrainFinetuneRecipeForNextTokenPrediction as _R,
    )

    class _DL:
        epoch, batch_in_epoch = 0, 6  # as restored by the step-2 checkpoint

        def __len__(self):
            return 10

    r = object.__new__(_R)
    r.dataloader = _DL()
    r.step_scheduler = SimpleNamespace(step=2, epoch=0, grad_acc_steps=3)
    r.checkpointer = SimpleNamespace(has_checkpoint=lambda: True, wait=lambda: None)
    r.telemetry = SimpleNamespace(record_step=lambda rec: None)
    r.resilience = SimpleNamespace(rollbacks=1)
    r._restore = lambda before_step: None  # state already at step 2
    r._rollback(fail_step=5)
    assert (r.dataloader.epoch, r.dataloader.batch_in_epoch) == (1, 6)
    assert r.step_scheduler.epoch == 1  # epoch budget follows the skip


def test_e2e_preemption_emergency_checkpoint_in_process(tmp_path, devices8, monkeypatch):
    """SIGTERM mid-run → loop drains at the step boundary, the end-of-loop
    save becomes the committed emergency checkpoint (manifest present even
    though ckpt_every_steps would never have fired), TrainingPreempted
    unwinds."""
    cfg = _recipe_cfg(tmp_path, {
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 2, "max_steps": 50,
                           "ckpt_every_steps": 0},
    })
    r = _run_recipe(cfg, monkeypatch, devices8)
    orig_step = r.train_step

    def step_then_sigterm(state, batch):
        out = orig_step(state, batch)
        if int(jax.device_get(out[1]["step"])) == 2:
            os.kill(os.getpid(), signal.SIGTERM)
        return out

    r.train_step = step_then_sigterm
    with pytest.raises(TrainingPreempted) as ei:
        r.run_train_validation_loop()
    assert ei.value.step == 2
    # requeue-eligible: the committed emergency dir rides the exception
    # (the CLI maps checkpoint_dir=None to a REAL failure exit, not 75)
    assert ei.value.checkpoint_dir and "epoch_0_step_2" in ei.value.checkpoint_dir
    manifests = list((tmp_path / "ckpt").glob("epoch_*_step_*/MANIFEST.json"))
    assert manifests, "emergency checkpoint must be committed"
    ok, problems = verify_manifest(manifests[0].parent)
    assert ok, problems
    # a fresh recipe auto-resumes from it
    r2 = _run_recipe(_recipe_cfg(tmp_path), monkeypatch, devices8)
    assert int(r2.state.step) == 2
    r2.resilience.close()  # don't leak the SIGTERM handler into other tests


# ---------------------------------------------------------------------------
# subprocess e2e: real SIGTERM → exit 75 → restart resumes (acceptance a)
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


def test_sigterm_subprocess_requeue_exit_and_resume(tmp_path):
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
                           "max_steps": 100000, "ckpt_every_steps": 3},
        "optimizer": {"name": "adamw", "lr": 1e-3},
        "checkpoint": {"enabled": True, "checkpoint_dir": str(ckpt_dir)},
        "logging": {"metrics_path": str(metrics)},
        "telemetry": {"memory_every_steps": 0},
    }
    cfg_path = tmp_path / "cfg.yaml"
    cfg_path.write_text(json.dumps(cfg))  # JSON is valid YAML

    argv = [sys.executable, _WORKER, "finetune", "llm", "-c", str(cfg_path)]
    proc = subprocess.Popen(
        argv, env=_clean_env(tmp_path), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    deadline = time.time() + 300
    try:
        while not list(ckpt_dir.glob("epoch_*_step_*/MANIFEST.json")):
            if proc.poll() is not None:
                pytest.fail(f"worker died early: {proc.communicate()[1][-2000:]}")
            if time.time() > deadline:
                pytest.fail("no committed checkpoint appeared in time")
            time.sleep(0.25)
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
    assert committed
    last_step = int(committed[-1].name.rsplit("_", 1)[1])
    n_lines_before = len(metrics.read_text().splitlines())

    # restart with a finite horizon: must RESUME from the emergency
    # checkpoint, not from scratch
    out2 = subprocess.run(
        argv + [f"--step_scheduler.max_steps={last_step + 2}"],
        env=_clean_env(tmp_path), capture_output=True, text=True, timeout=300,
    )
    assert out2.returncode == 0, out2.stderr[-2000:]
    new = [
        json.loads(l)
        for l in metrics.read_text().splitlines()[n_lines_before:]
    ]
    steps = [rec["step"] for rec in new if "loss" in rec]
    assert steps and steps[0] == last_step + 1  # resumed, not restarted
    assert steps[-1] == last_step + 2
