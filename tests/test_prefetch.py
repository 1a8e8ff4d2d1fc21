"""Host-overlap input pipeline (data/prefetch.py): stream parity with the
sync path, consumption-cursor resume semantics (prefetched-but-unconsumed
batches replay exactly once), rollback across a prefetched window, the
overlap itself (injected collate delay hidden behind consumer work), and the
e2e determinism contract — loss trajectory bit-identical sync vs prefetch
vs resume-after-kill."""

import json
import os
import signal
import time
from pathlib import Path

import numpy as np
import pytest

import jax

from automodel_tpu.config.loader import ConfigNode
from automodel_tpu.data.collators import stack_microbatches
from automodel_tpu.data.loader import DataLoader
from automodel_tpu.data.prefetch import (
    PrefetchConfig,
    PrefetchingLoader,
    PreparedBatch,
)
from automodel_tpu.data.sft import MockSFTDataset


def _sync_groups(ds, gbs, group_size, seed=0, epochs=1):
    """The sync reference stream: stacked grad-acc groups, tail discarded
    (exactly what StepScheduler's grouping feeds the train loop)."""
    out = []
    loader = DataLoader(ds, global_batch_size=gbs, shuffle=True, seed=seed)
    for _ in range(epochs):
        group = []
        for b in loader:
            group.append(b)
            if len(group) == group_size:
                out.append(stack_microbatches(group))
                group = []
    return out


def _facade(ds, gbs, group_size, depth=3, workers=2, seed=0):
    return PrefetchingLoader(
        DataLoader(ds, global_batch_size=gbs, shuffle=True, seed=seed),
        PrefetchConfig(depth=depth, collate_workers=workers),
        group_size=group_size,
    )


def _assert_batches_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def test_prefetch_stream_parity_and_tail_discard():
    """40 samples / gbs 4 / grad_acc 3 → 10 batches, 3 full groups per
    epoch (tail discarded) — bit-identical to the sync grouping, across an
    epoch boundary."""
    ds = MockSFTDataset(vocab_size=64, seq_length=8, num_samples=40, seed=0)
    ref = _sync_groups(ds, 4, 3, epochs=2)
    assert len(ref) == 6
    pf = _facade(ds, 4, 3)
    got = []
    for _ in range(2):  # one __iter__ call per epoch, like the scheduler
        got.extend(item.host for item in pf)
    pf.close()
    assert len(got) == len(ref)
    for a, b in zip(got, ref):
        _assert_batches_equal(a, b)


def test_consumption_cursor_not_fetch_cursor():
    """With depth 3 the producer runs well ahead; state_dict() must track
    only what the consumer popped. A fresh pipeline restored from the
    snapshot replays the unconsumed remainder exactly once — no gap (a
    fetch-cursor state would skip the prefetched window), no repeat."""
    ds = MockSFTDataset(vocab_size=64, seq_length=8, num_samples=48, seed=1)
    ref = _sync_groups(ds, 4, 2, seed=1)
    assert len(ref) == 6
    pf = _facade(ds, 4, 2, depth=3, seed=1)
    it = iter(pf)
    consumed = [next(it).host, next(it).host]
    # let the producer run ahead of the consumer before snapshotting
    deadline = time.monotonic() + 5
    while pf.queue_depth < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert pf.queue_depth >= 1
    snap = pf.state_dict()
    assert snap["batch_in_epoch"] == 4  # 2 groups x 2 batches CONSUMED
    pf.close()  # simulated kill: run-ahead dropped

    pf2 = _facade(ds, 4, 2, depth=3, seed=999)  # seed restored from snap
    pf2.load_state_dict(snap)
    replayed = [item.host for item in pf2]
    pf2.close()
    seen = consumed + replayed
    assert len(seen) == len(ref)
    for a, b in zip(seen, ref):
        _assert_batches_equal(a, b)


def test_seek_flushes_run_ahead_and_replays_exactly():
    """seek() (the rollback fast-forward entry point) joins the producer,
    drops everything fetched ahead, and restarts at the exact cursor."""
    ds = MockSFTDataset(vocab_size=64, seq_length=8, num_samples=40, seed=2)
    ref = _sync_groups(ds, 4, 1, seed=2, epochs=2)
    pf = _facade(ds, 4, 1, depth=4, seed=2)
    it = iter(pf)
    for _ in range(6):
        next(it)
    # roll back INTO the already-consumed region, then fast-forward past an
    # epoch boundary — both directions must land bit-exactly
    pf.seek(0, 3)
    assert pf.state_dict()["batch_in_epoch"] == 3
    tail = [item.host for item in pf]  # rest of epoch 0
    tail += [item.host for item in pf]  # epoch 1
    pf.close()
    for a, b in zip(tail, ref[3:]):
        _assert_batches_equal(a, b)
    assert len(tail) == len(ref) - 3


def test_seed_change_invalidates_cached_epoch_order():
    """load_state_dict may carry a different seed than the warm loader's;
    a stale cached shuffle order would silently replay the old stream."""
    ds = MockSFTDataset(vocab_size=64, seq_length=8, num_samples=24, seed=0)
    warm = DataLoader(ds, global_batch_size=4, shuffle=True, seed=1)
    next(iter(warm))  # epoch-0 order now cached under seed 1
    warm.load_state_dict({"epoch": 0, "batch_in_epoch": 0, "seed": 2})
    fresh = DataLoader(ds, global_batch_size=4, shuffle=True, seed=2)
    _assert_batches_equal(warm.batch_for(0, 0), fresh.batch_for(0, 0))


def test_producer_exception_surfaces_at_pop():
    class Boom:
        def __len__(self):
            return 12

        def __getitem__(self, i):
            if i >= 6:
                raise RuntimeError("shard went away")
            return {"input_ids": [1, 2, 3]}

    pf = _facade(Boom(), 2, 1, depth=2, workers=1)
    it = iter(pf)
    with pytest.raises(RuntimeError, match="shard went away"):
        for _ in range(10):
            next(it)
    pf.close()


def test_overlap_hides_injected_collate_delay():
    """The headline property, loader-level so it is robust to CI load: with
    a 40ms injected collate delay and ~25ms of consumer work per step, the
    prefetched pipeline must run >= 1.5x the sync loop (the theoretical
    ratio here is ~2.4x: 65ms serial vs max(25, 40/4)ms overlapped)."""
    from automodel_tpu.resilience.fault_injection import activate

    ds = MockSFTDataset(vocab_size=64, seq_length=8, num_samples=160, seed=3)
    steps, work_s = 12, 0.025
    activate({"slow_collate_ms": 40.0})
    try:
        sync = DataLoader(ds, global_batch_size=4, shuffle=True, seed=3)
        it = iter(sync)
        t0 = time.perf_counter()
        for _ in range(steps):
            next(it)
            time.sleep(work_s)  # stands in for device compute
        t_sync = time.perf_counter() - t0

        pf = _facade(ds, 4, 1, depth=4, workers=4, seed=3)
        it = iter(pf)
        next(it)  # warm the pipeline (the train loop's compile step)
        time.sleep(0.3)
        t0 = time.perf_counter()
        for _ in range(steps):
            next(it)
            time.sleep(work_s)
        t_pf = time.perf_counter() - t0
        pf.close()
    finally:
        activate(None)
    speedup = t_sync / t_pf
    assert speedup >= 1.5, (
        f"prefetch only {speedup:.2f}x over sync "
        f"(sync {t_sync:.3f}s, prefetched {t_pf:.3f}s for {steps} steps)"
    )


def test_report_strict_and_metrics_gauges(tmp_path):
    """`report --strict` accepts the new keys (numeric or null+marker) and
    the /metrics exporter publishes them as gauges under its lock."""
    from automodel_tpu.telemetry.prometheus import TrainMetricsExporter
    from automodel_tpu.telemetry.report import (
        lint_metrics_jsonl,
        summarize_metrics,
    )

    p = tmp_path / "m.jsonl"
    p.write_text(
        json.dumps(
            {"step": 1, "ts": 1.0, "loss": 2.0, "host_input_wait_s": 0.012,
             "prefetch_depth": 3}
        )
        + "\n"
        + json.dumps({"step": 2, "ts": 2.0, "loss": 1.9, "host_input_wait_s": "slow"})
        + "\n"
    )
    records, problems = lint_metrics_jsonl(str(p))
    assert len(records) == 2
    assert any("host_input_wait_s is not numeric" in x for x in problems)
    assert summarize_metrics(records)["host_input_wait_s_mean"] == pytest.approx(0.012)

    ex = TrainMetricsExporter()
    ex.update({"step": 1, "host_input_wait_s": 0.034, "prefetch_depth": 2})
    body = ex.registry.render()
    assert "automodel_train_host_input_wait_seconds 0.034" in body
    assert "automodel_train_prefetch_queue_depth 2" in body


# -- e2e: recipe-level determinism + exactly-once replay ----------------------


def _recipe_cfg(tmp_path: Path, tag: str, extra: dict | None = None) -> ConfigNode:
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
        "step_scheduler": {
            "grad_acc_steps": 1, "num_epochs": 2, "max_steps": 6,
            "ckpt_every_steps": 1, "log_every_steps": 1,
        },
        "optimizer": {"name": "adamw", "lr": 1e-3, "grad_clip_norm": 1.0},
        "loss_fn": {"name": "masked_ce"},
        "checkpoint": {"enabled": True, "checkpoint_dir": str(tmp_path / f"ckpt_{tag}")},
        "logging": {"metrics_path": str(tmp_path / f"metrics_{tag}.jsonl")},
    }
    for k, v in (extra or {}).items():
        cfg[k] = v
    return ConfigNode(cfg)


def _losses_by_step(path: Path) -> dict[int, float]:
    out: dict[int, float] = {}
    for line in path.read_text().splitlines():
        rec = json.loads(line)
        if "loss" in rec and isinstance(rec.get("step"), int):
            out[rec["step"]] = rec["loss"]  # last occurrence wins (replays)
    return out


PREFETCH = {"data": {"prefetch": {"depth": 3, "collate_workers": 2}}}


@pytest.fixture(scope="module")
def sync_reference(tmp_path_factory, devices8, monkeypatch_module):
    """One uninterrupted SYNC run — the trajectory every prefetch variant
    must reproduce bit-exactly."""
    tmp = tmp_path_factory.mktemp("prefetch_ref")
    from automodel_tpu.recipes.train_ft import main

    last = main(_recipe_cfg(tmp, "sync"))
    assert int(last["step"]) == 6
    return _losses_by_step(tmp / "metrics_sync.jsonl")


@pytest.fixture(scope="module")
def monkeypatch_module(devices8):
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "devices", lambda *a: devices8)
    yield mp
    mp.undo()


def test_e2e_prefetch_loss_trajectory_bit_identical(
    tmp_path, devices8, monkeypatch_module, sync_reference
):
    from automodel_tpu.recipes.train_ft import main

    last = main(_recipe_cfg(tmp_path, "pf", PREFETCH))
    assert int(last["step"]) == 6
    got = _losses_by_step(tmp_path / "metrics_pf.jsonl")
    assert got == sync_reference  # bit-identical, every step


def test_e2e_kill_mid_prefetch_replays_exactly_once(
    tmp_path, devices8, monkeypatch_module, sync_reference
):
    """Kill at step 4 with the producer running ahead (slow collate keeps
    the queue mid-flight), restart, finish. The merged per-step trajectory
    must equal the uninterrupted sync run's — a batch trained twice or
    skipped would shift every subsequent loss."""
    from automodel_tpu.recipes.train_ft import TrainFinetuneRecipeForNextTokenPrediction
    from automodel_tpu.resilience import InjectedFault

    cfg = _recipe_cfg(
        tmp_path, "kill",
        {
            **PREFETCH,
            "fault_injection": {
                "die_at_step": 4, "die_mode": "exception", "slow_collate_ms": 20,
            },
        },
    )
    r1 = TrainFinetuneRecipeForNextTokenPrediction(cfg)
    r1.setup()
    with pytest.raises(InjectedFault):
        r1.run_train_validation_loop()

    # restart WITHOUT the fault (transient kill); auto-resumes the newest
    # committed checkpoint and replays the unconsumed window exactly once
    cfg2 = _recipe_cfg(tmp_path, "kill", {**PREFETCH, "fault_injection": {}})
    r2 = TrainFinetuneRecipeForNextTokenPrediction(cfg2)
    r2.setup()
    assert int(r2.state.step) < 4  # resumed strictly before the kill
    last = r2.run_train_validation_loop()
    assert int(last["step"]) == 6
    got = _losses_by_step(tmp_path / "metrics_kill.jsonl")
    assert got == sync_reference


def test_e2e_rollback_across_prefetched_window(
    tmp_path, devices8, monkeypatch_module
):
    """on_nonfinite=rollback with the pipeline running ahead: the restore +
    fast-forward must flush the run-ahead and re-seek (a stale prefetched
    batch would retrain the offending window). Sync and prefetched arms of
    the SAME transient divergence must converge to identical final losses."""
    import jax.numpy as jnp

    from automodel_tpu.recipes.train_ft import TrainFinetuneRecipeForNextTokenPrediction

    def run(tag, extra):
        cfg = _recipe_cfg(
            tmp_path, tag,
            {**extra, "fault_tolerance": {"on_nonfinite": "rollback"}},
        )
        r = TrainFinetuneRecipeForNextTokenPrediction(cfg)
        r.setup()
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
        assert int(last["step"]) == 6
        assert last["rollbacks_total"] == 1
        return r, _losses_by_step(tmp_path / f"metrics_{tag}.jsonl")

    r_sync, sync_losses = run("rb_sync", {})
    r_pf, pf_losses = run("rb_pf", PREFETCH)
    assert pf_losses == sync_losses
    # both arms resumed their loaders at the same consumption cursor
    s1, s2 = r_sync.dataloader.state_dict(), r_pf.dataloader.state_dict()
    assert (s1["epoch"], s1["batch_in_epoch"]) == (s2["epoch"], s2["batch_in_epoch"])


def test_preemption_drain_joins_prefetch_worker(tmp_path, devices8, monkeypatch_module):
    """SIGTERM-style drain: the loop stops at the step boundary, the
    prefetch producer is JOINED before the emergency save, and the saved
    cursor (consumption, not fetch) resumes the next run exactly."""
    from automodel_tpu.recipes.train_ft import TrainFinetuneRecipeForNextTokenPrediction
    from automodel_tpu.resilience import TrainingPreempted

    cfg = _recipe_cfg(
        tmp_path, "drain",
        {
            **PREFETCH,
            "step_scheduler": {
                "grad_acc_steps": 1, "num_epochs": 2, "max_steps": 50,
                "ckpt_every_steps": 0, "log_every_steps": 1,
            },
        },
    )
    r = TrainFinetuneRecipeForNextTokenPrediction(cfg)
    r.setup()
    orig_step, n = r.train_step, []

    def step_then_preempt(state, batch):
        out = orig_step(state, batch)
        n.append(1)
        if len(n) == 3:
            os.kill(os.getpid(), signal.SIGTERM)
        return out

    r.train_step = step_then_preempt
    with pytest.raises(TrainingPreempted):
        r.run_train_validation_loop()
    # producer joined (no thread left behind), run-ahead dropped
    assert r.dataloader._thread is None
    assert r.dataloader.queue_depth == 0
    # the emergency checkpoint's cursor is the consumption cursor: 3 steps
    # x 1 batch consumed, regardless of how far the producer had fetched
    assert r.dataloader.state_dict()["batch_in_epoch"] == 3
