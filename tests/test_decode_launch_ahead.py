"""The decode tick launches ahead (serving/engine.py, scheduler step 4): step
n + 1 is dispatched before step n's tokens are read, ``cur`` stays on the
device, a stop id is found one step late and costs one discarded row, and
every row of the step in flight carries the ``_Slot`` it was launched for.

Ground truth is the single-wave ``GenerationEngine`` (greedy tokens, token for
token) and the engine's own ``serve.counts``; nothing here depends on how long
anything took (how often a launch finds the step before still RUNNING is the
chip's to say: ``decode_launch_ahead_pct``)."""

from collections import Counter

import numpy as np
import pytest

import jax

from automodel_tpu.auto_model import AutoModel
from automodel_tpu.generation.engine import GenerationConfig, GenerationEngine
from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
from automodel_tpu.serving import loop_account
from automodel_tpu.serving.engine import ServeConfig, ServingEngine, SpeculativeConfig

FP32 = BackendConfig(attn="sdpa", param_dtype="float32", compute_dtype="float32")
# greedy tokens of the tiny llama below (seed 0, no eos, 10 a prompt): token 53
# comes 8th, 2nd, 3rd, 4th and 4th for five of these prompts and never for the
# other five, so as a stop id it ends some requests mid-stream and not others
PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 11], [12, 13, 14], [15], [7, 8, 9],
           [20, 21, 22, 23, 24], [30, 31], [40, 41, 42, 43, 44, 45, 46]]
STOP = 53


@pytest.fixture(scope="module")
def auto():
    from automodel_tpu.models.llama import LlamaForCausalLM

    model = LlamaForCausalLM(
        TransformerConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=3,
                          num_heads=4, num_kv_heads=2, head_dim=8),
        FP32,
    )
    return AutoModel(model=model, params=model.init(jax.random.key(0)), adapter=None,
                     mesh_ctx=None)


@pytest.fixture(scope="module")
def free_running(auto):
    """The single-wave engine's greedy tokens with no stop id, 10 a prompt
    (greedy is prefix-stable: a shorter budget or a stop id cuts these)."""
    ref = GenerationEngine(
        auto, GenerationConfig(max_new_tokens=10, greedy=True, pad_to_multiple=1))
    return ref.generate_ids([list(p) for p in PROMPTS])["tokens"]


@pytest.fixture
def counts(monkeypatch):
    """Every ``serve.counts`` the engines of a test write, in order."""
    events = []

    class Recorder:
        def __init__(self, name, **stats):
            if name == "serve.counts":
                events.append(stats)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(loop_account, "TraceAnnotation", Recorder)
    return events


def _engine(auto, *, stop=None, max_new=10, **serve):
    kw = dict(slots=3, block_size=4, num_blocks=64, prefill_chunk=4, max_seq_len=48)
    kw.update(serve)
    return ServingEngine(
        auto, ServeConfig(**kw),
        GenerationConfig(max_new_tokens=max_new, greedy=True, eos_token_id=stop),
    )


def _cut(tokens, budget, stop):
    """What a request is owed: the free-running tokens up to its budget or its
    first stop id (inclusive), and why it ended."""
    out = list(tokens[:budget])
    if stop in out:
        return out[: out.index(stop) + 1], "stop"
    return out, "length"


def test_mixed_stop_and_length_batch_matches_the_single_wave_engine(auto, free_running, counts):
    """(a) A batch in which some requests end by a stop id mid-stream, one by a
    stop id on its budget's last token, the rest by length: tokens,
    ``n_generated`` and ``completion_reason`` are the single-wave engine's, and
    exactly the stop endings that still had budget left a row in flight."""
    budgets = [8, 10, 10, 3, 10, 6, 10, 10, 10, 2]
    # the single-wave engine itself under the stop id, for the full budgets
    single = GenerationEngine(auto, GenerationConfig(
        max_new_tokens=10, greedy=True, eos_token_id=STOP, pad_to_multiple=1))
    stopped = single.generate_ids([list(p) for p in PROMPTS])["tokens"]
    eng = _engine(auto, stop=STOP)
    ids = [eng.submit(p, max_new_tokens=n) for p, n in zip(PROMPTS, budgets)]
    done = {r["request_id"]: r for r in eng.run()}
    in_flight_at_stop = 0
    for i, (rid, budget) in enumerate(zip(ids, budgets)):
        want, why = _cut(free_running[i], budget, STOP)
        rec = done[rid]
        assert (rec["tokens"], rec["n_generated"], rec["completion_reason"]) == (
            want, len(want), why), (i, rec)
        if budget == 10:
            assert rec["tokens"] == stopped[i]
        # found by a decode read (not at the prompt's flip) with budget left:
        # the slot already had a row in the step after
        in_flight_at_stop += why == "stop" and 2 <= len(want) < budget
    reasons = Counter(r["completion_reason"] for r in done.values())
    assert reasons == {"stop": 4, "length": 6} and in_flight_at_stop == 3
    assert done[ids[0]]["n_generated"] == 8  # a stop id on the budget's last token
    assert sum(e["discarded_rows"] for e in counts) == in_flight_at_stop
    # every launch was read, an iteration later; the first found nothing in flight
    launches = [e["decode_launched"] for e in counts]
    assert sum(launches) > 0 and launches[-1] == 0
    assert all(e["decode_launched_ahead"] <= before for e, before in zip(counts, [0] + launches))
    assert eng.idle() and eng._in_flight is None
    eng.check_invariants()
    assert eng.pool.in_use() == 0
    # the placeholder for "nothing in flight" is placed as a step's own tokens
    # come back: one compiled decode program, not two
    assert eng._decode._cache_size() == 1


def test_one_decode_program_on_a_mesh(devices8):
    """A step's tokens come back committed to the mesh; what stands in for them
    while nothing is in flight must be placed alike, or every engine compiles
    its decode program twice. (On tp = 2 the pool's sharding comes back from
    the first program spelled differently, which is a second cache entry of its
    own and older than launch-ahead.)"""
    from automodel_tpu import auto_model
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh

    hf = {
        "architectures": ["LlamaForCausalLM"], "model_type": "llama",
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 8, "max_position_embeddings": 128,
    }
    backend = {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"}
    tokens = []
    for cfg, devices in ((MeshConfig(dp_shard=1), devices8[:1]),
                         (MeshConfig(dp_shard=4, tp=2), devices8)):
        eng = _engine(auto_model.from_config(hf, build_mesh(cfg, devices=devices), backend),
                      max_new=5, slots=2)
        ids = [eng.submit([1, 2, 3, 4]), eng.submit([9, 8, 7])]
        while eng._in_flight is None:
            eng.step()
        assert eng._in_flight.tokens.sharding == eng._no_tokens.sharding
        done = {r["request_id"]: r for r in eng.run()}
        tokens.append([done[i]["tokens"] for i in ids])
        assert eng._decode._cache_size() == (1 if len(devices) == 1 else 2)
    assert tokens[0] == tokens[1] and all(len(t) == 5 for t in tokens[0])


def test_slot_freed_by_a_stop_id_serves_its_next_tenant_alone(auto, counts):
    """(b) One slot, prefix cache on: A ends by a stop id with a row in flight,
    B (same first block) takes the slot in the next iteration while that row
    is still unread. B's tokens are those it gets served alone."""
    a, b = [6, 7, 8, 9, 20, 21], [6, 7, 8, 9, 3]
    second = GenerationEngine(
        auto, GenerationConfig(max_new_tokens=2, greedy=True, pad_to_multiple=1)
    ).generate_ids([a])["tokens"][0][1]
    alone = _engine(auto, stop=second, slots=1)
    rid = alone.submit(b)
    want = alone.run()[0]
    assert want["request_id"] == rid and want["n_generated"] >= 3
    counts.clear()

    eng = _engine(auto, stop=second, slots=1)
    ida, idb = eng.submit(a), eng.submit(b)
    done, bound_with_a_row_unread = {}, False
    while not eng.idle():
        before = eng._slots[0]
        for r in eng.step():
            done[r["request_id"]] = r
        now = eng._slots[0]
        if now is not None and now is not before and now.request_id == idb:
            # B was bound in this iteration: the step read in it was launched for A
            bound_with_a_row_unread = counts[-1]["discarded_rows"] == 1
    assert done[ida]["completion_reason"] == "stop" and done[ida]["n_generated"] == 2
    assert bound_with_a_row_unread
    assert done[idb]["prefix_hit_tokens"] == 4
    assert done[idb]["tokens"] == want["tokens"]
    assert done[idb]["completion_reason"] == want["completion_reason"]
    assert sum(e["discarded_rows"] for e in counts) == 1 + (
        want["completion_reason"] == "stop" and want["n_generated"] < 10)


def test_recurrent_slot_freed_by_a_stop_id_serves_its_next_tenant_alone(counts):
    """(c) The same on a layout with conv state beside K/V (no prefix cache
    there): the discarded row shifted one input into the freed slot's state,
    and the next tenant's first chunk resets it."""
    from test_serving_recurrent_state import _auto as lfm2_auto

    _, auto = lfm2_auto()
    a, b = [6, 7, 8, 9, 11], [21, 22, 23, 24, 25, 26, 27, 28, 29, 30]

    def engine(stop):
        return ServingEngine(
            auto,
            ServeConfig(slots=1, block_size=4, num_blocks=64, prefill_chunk=8, max_seq_len=64,
                        prefix_cache=False),
            GenerationConfig(max_new_tokens=8, greedy=True, eos_token_id=stop),
        )

    free = engine(None)
    free.submit(a)
    second = free.run()[0]["tokens"][1]
    alone = engine(second)
    alone.submit(b, return_logprobs=True)
    want = alone.run()[0]
    assert want["n_generated"] >= 3
    counts.clear()

    eng = engine(second)
    ida, idb = eng.submit(a), eng.submit(b, return_logprobs=True)
    done = {r["request_id"]: r for r in eng.run()}
    assert done[ida]["completion_reason"] == "stop" and done[ida]["n_generated"] == 2
    assert sum(e["discarded_rows"] for e in counts) >= 1
    assert done[idb]["tokens"] == want["tokens"]
    np.testing.assert_allclose(done[idb]["logprobs"], want["logprobs"], atol=1e-5)


def test_idle_is_false_while_a_step_is_unread_and_run_drains(auto, free_running, counts):
    """(d) A stop id leaves no busy slot and an empty queue but one step in
    flight: the engine is not idle until it has read (and discarded) it.
    ``run()`` drains a queue deeper than the slots, one terminal record each."""
    eng = _engine(auto, stop=STOP, slots=1)
    rid = eng.submit(PROMPTS[2])  # 53 comes second
    done = []
    while not done:
        done = eng.step()
    assert done[0]["request_id"] == rid and done[0]["completion_reason"] == "stop"
    assert eng.busy_slots == 0 and eng.queue_depth == 0
    assert eng._in_flight is not None and not eng.idle()
    assert eng.step() == [] and counts[-1]["discarded_rows"] == 1
    assert counts[-1]["decode_launched"] == 0  # nothing to launch: a read only
    assert eng._in_flight is None and eng.idle()

    eng = _engine(auto, stop=STOP, slots=2)
    budgets = [8, 10, 10, 3, 10, 6, 10, 4, 10, 2]
    ids = [eng.submit(p, max_new_tokens=n) for p, n in zip(PROMPTS, budgets)]
    records = eng.run()
    assert Counter(r["request_id"] for r in records) == Counter(ids)
    assert eng.idle() and eng._in_flight is None and eng.pool.in_use() == 0
    for i, (rid, budget) in enumerate(zip(ids, budgets)):
        rec = next(r for r in records if r["request_id"] == rid)
        assert (rec["tokens"], rec["completion_reason"]) == _cut(free_running[i], budget, STOP)


def _step_until_in_flight_for(eng, rid):
    """Step until the step in flight holds a row for ``rid`` → its slot."""
    for _ in range(64):
        eng.step()
        step = eng._in_flight
        slot = step and next((s for s in step.slots if s and s.request_id == rid), None)
        if slot is not None and len(slot.generated) >= 2:
            return slot
    raise AssertionError(f"{rid} never had a row in flight")


def test_a_request_expired_with_a_row_in_flight_gets_nothing_of_it(auto, free_running, counts):
    """(e) ``_expire_tick`` cancels A between the launch and the read of a
    step, and the slot goes to B in the same iteration: the row launched for A
    is discarded, A's record holds what had been read, B and the bystander C
    are served as if alone."""
    eng = _engine(auto, slots=2)
    ida = eng.submit(PROMPTS[0], deadline_s=3600.0)
    idc = eng.submit(PROMPTS[4])
    idb = eng.submit(PROMPTS[7])  # queued: both slots are taken
    slot = _step_until_in_flight_for(eng, ida)
    read_so_far = list(slot.generated)
    slot.deadline_at = 0.0  # long past
    discarded_before = sum(e["discarded_rows"] for e in counts)
    done = {r["request_id"]: r for r in eng.step()}
    assert done[ida]["completion_reason"] == "timeout"
    assert done[ida]["tokens"] == read_so_far == free_running[0][: len(read_so_far)]
    assert sum(e["discarded_rows"] for e in counts) == discarded_before + 1
    done.update((r["request_id"], r) for r in eng.run())
    assert done[idb]["tokens"] == free_running[7] and done[idc]["tokens"] == free_running[4]
    assert set(done) == {ida, idb, idc}
    eng.check_invariants()
    assert eng.pool.in_use() == 0


def test_a_rebuild_drops_the_step_in_flight_with_its_wave(auto, free_running):
    """(e) A decode launch that raises while the step before is unread: the
    wave fails with what had been read, the step in flight is dropped (no
    token of it reaches a record), and the queue is served from a fresh pool."""
    eng = _engine(auto, slots=2)
    ida, idc = eng.submit(PROMPTS[0]), eng.submit(PROMPTS[4])
    slot = _step_until_in_flight_for(eng, ida)
    read_so_far = {s.request_id: list(s.generated) for s in eng._slots if s is not None}
    real = eng._decode

    def broken(*args):
        raise RuntimeError("boom")

    eng._decode = broken
    idb = eng.submit(PROMPTS[7])
    failed = {r["request_id"]: r for r in eng.step()}
    eng._decode = real
    assert eng._in_flight is None
    assert {r["completion_reason"] for r in failed.values()} == {"engine_error"}
    assert {rid: r["tokens"] for rid, r in failed.items()} == read_so_far
    assert len(slot.generated) == len(read_so_far[ida])
    done = {r["request_id"]: r for r in eng.run()}
    assert set(done) == {idb} and done[idb]["tokens"] == free_running[7]
    assert eng.idle() and eng.pool.in_use() == 0


def test_speculative_decoding_keeps_its_synchronous_tick(auto, free_running, counts):
    """(f) A verify step commits 1 to k + 1 tokens a slot, so the next step's
    lengths are not host-known: nothing is launched ahead, nothing is ever in
    flight between iterations, and greedy tokens are unchanged."""
    draft = {
        "hf_config": dict(
            architectures=["LlamaForCausalLM"], model_type="llama", vocab_size=64,
            hidden_size=16, intermediate_size=32, num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=1, head_dim=8, max_position_embeddings=128),
        "backend": {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"},
    }
    eng = _engine(auto, slots=2, max_new=6,
                  speculative=SpeculativeConfig(enabled=True, k=2, draft=draft))
    ids = [eng.submit(p) for p in PROMPTS[:3]]
    done = {}
    while not eng.idle():
        done.update((r["request_id"], r) for r in eng.step())
        assert eng._in_flight is None
    assert [done[i]["tokens"] for i in ids] == [t[:6] for t in free_running[:3]]
    assert any(e["decoded"] for e in counts)
    assert all(e["decode_launched"] == e["decode_launched_ahead"] == e["discarded_rows"] == 0
               for e in counts)
