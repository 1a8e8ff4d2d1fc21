"""The phase spans inside ``ServingEngine.step`` and the train input path, read
back from a REAL ``jax.profiler`` trace (one trace for the whole file; nothing
here depends on how long anything took).

Ground truth is the engine's own state: every iteration is driven through a
recorder that notes what ``step()`` returned, the arrays the decode program was
handed, and ``_lengths``/``_active`` afterwards; ``serve.counts`` must say the
same. The decode tick launches ahead: an iteration dispatches the NEXT decode
step and then reads the one launched an iteration ago, so a launch's tokens
(and a request's end) show one iteration after it. The reader is the benchmark's (``benchmarks/harness/program_trace.py``),
so the pair is tested together.

The same primitive keeps the loop's always-on account
(``serving/loop_account.py``): its totals and every record's ``decode_account``
are held against the spans and the ``serve.counts`` rows of that trace.
"""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from automodel_tpu.auto_model import AutoModel
from automodel_tpu.generation.engine import GenerationConfig
from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
from automodel_tpu.serving import loop_account
from automodel_tpu.serving.engine import ServeConfig, ServingEngine, StallConfig
from benchmarks.harness import program_trace, trace

FP32 = BackendConfig(attn="sdpa", param_dtype="float32", compute_dtype="float32")
PHASES = ("serve.admit", "serve.prefill_dispatch", "serve.first_token_wait", "serve.decode_plan",
          "serve.decode_dispatch", "serve.decode_wait", "serve.record", "serve.counts")
# where the host's part of a decode dispatch is spent: children of the dispatch
DISPATCH_PARTS = ("serve.decode_h2d", "serve.decode_launch")


def _tiny_auto():
    from automodel_tpu.models.llama import LlamaForCausalLM

    model = LlamaForCausalLM(
        TransformerConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
                          num_heads=4, num_kv_heads=2, head_dim=8),
        FP32,
    )
    return AutoModel(model=model, params=model.init(jax.random.key(0)), adapter=None,
                     mesh_ctx=None)


def _engine(**serve_over):
    return ServingEngine(
        _tiny_auto(),
        ServeConfig(slots=3, block_size=4, num_blocks=64, prefill_chunk=4, max_seq_len=48,
                    **serve_over),
        GenerationConfig(max_new_tokens=6, greedy=True),
    )


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Five requests (prompts of 3 to 11 tokens: one to three chunks each; one
    asks for a single token and ends at its prefill) through a three-slot
    engine, and two input groups through the train recipe's collate/place,
    under one trace."""
    from automodel_tpu.recipes.train_ft import TrainFinetuneRecipeForNextTokenPrediction

    eng = _engine()
    eng.submit([5, 6, 7], request_id="warm", max_new_tokens=3)
    eng.run()  # both programs compiled before the trace

    recipe = TrainFinetuneRecipeForNextTokenPrediction.__new__(
        TrainFinetuneRecipeForNextTokenPrediction)
    recipe._zigzag_cp = 0
    recipe.mesh_ctx = None
    group = [{"input_ids": np.ones((2, 8), np.int32), "labels": np.ones((2, 8), np.int32)}]

    rows = []
    real_decode = eng._decode

    def spy(params, pool, tables, lengths, cur, active, *rest):
        rows[-1]["read"] = int(np.asarray(lengths)[np.asarray(active)].sum())
        rows[-1]["wave"] = int(np.asarray(active).sum())
        return real_decode(params, pool, tables, lengths, cur, active, *rest)

    eng._decode = spy
    trace_dir = tmp_path_factory.mktemp("trace")
    rng = np.random.default_rng(0)
    first_token = {}  # request id -> (index of the iteration it came in, slot)
    before = eng._account.snapshot()
    jax.profiler.start_trace(str(trace_dir))
    try:
        for _ in range(2):
            stacked, _ = recipe._prepare_group(group)
            recipe._place_group(stacked)
        for i, (n_prompt, n_new) in enumerate([(3, 6), (9, 4), (11, 1), (5, 6), (7, 3)]):
            eng.submit(rng.integers(1, 64, size=n_prompt).tolist(), request_id=f"r{i}",
                       max_new_tokens=n_new)
        while not eng.idle():
            rows.append({"step": eng._step_counter, "queued": eng.queue_depth,
                         "busy": eng.busy_slots, "read": 0, "wave": 0})
            done = eng.step()
            rows[-1].update(done=done, after=int((eng._lengths * eng._active).sum()))
            for b, slot in enumerate(eng._slots):
                if slot is not None and slot.t_first is not None:
                    first_token.setdefault(slot.request_id, (len(rows) - 1, b))
    finally:
        jax.profiler.stop_trace()
    spans = program_trace.read_spans(trace.find_xplane(trace_dir))
    return {"rows": rows, "spans": spans, "first_token": first_token,
            "account": (before, eng._account.snapshot())}


def _steps(spans):
    return [(i, s) for i, s in enumerate(spans) if s["name"] == "serve.step"]


def test_every_phase_is_a_child_of_its_step(traced):
    spans = traced["spans"]
    steps = _steps(spans)
    assert len(steps) == len(traced["rows"])
    seen = set()
    for sp in spans:
        if sp["name"].startswith("serve.") and sp["name"] != "serve.step":
            parent = spans[sp["parent"]]
            assert parent["name"] == (
                "serve.decode_dispatch" if sp["name"] in DISPATCH_PARTS else "serve.step"), sp
            assert parent["start_s"] <= sp["start_s"] and sp["end_s"] <= parent["end_s"]
            seen.add(sp["name"])
    assert seen == set(PHASES + DISPATCH_PARTS)
    for i, sp in enumerate(spans):
        if sp["name"] == "serve.decode_dispatch":  # the transfers, then the launch
            assert [k["name"] for k in program_trace.children_of(spans, i)] == list(DISPATCH_PARTS)
    # one thread, in order, and counts is the last child of every step
    for i, step in steps:
        kids = program_trace.children_of(spans, i)
        assert kids[-1]["name"] == "serve.counts"
        assert all(a["end_s"] <= b["start_s"] for a, b in zip(kids, kids[1:]))


def test_step_stats_are_the_engine_s_state_at_entry(traced):
    for (_, sp), row in zip(_steps(traced["spans"]), traced["rows"]):
        assert {k: int(sp["stats"][k]) for k in ("step", "queued", "busy")} == {
            k: row[k] for k in ("step", "queued", "busy")}


def test_counts_agree_with_the_records_and_the_arrays(traced):
    spans = traced["spans"]
    admitted = launched_before = read_before = wave_before = 0
    for (i, _), row in zip(_steps(spans), traced["rows"]):
        kids = program_trace.children_of(spans, i)
        counts = {k: int(v) for k, v in kids[-1]["stats"].items()}
        chunks = [k for k in kids if k["name"] == "serve.prefill_dispatch"]
        assert counts["chunks"] == len(chunks)
        assert all(0 < int(c["stats"]["tokens"]) <= 4 for c in chunks)
        assert counts["finished"] == len(row["done"])
        # what the decode program was handed
        assert counts["decoded"] == row["wave"]
        assert counts["context_tokens"] == row["read"]
        names = [k["name"] for k in kids]
        # launch-ahead: an iteration dispatches a step when a slot has budget
        # left and reads the step launched the iteration before
        assert counts["decode_launched"] == (counts["decoded"] > 0) == (
            "serve.decode_dispatch" in names)
        assert ("serve.decode_wait" in names) == bool(launched_before)
        if "serve.decode_wait" in names and counts["decode_launched"]:
            assert names.index("serve.decode_dispatch") < names.index("serve.decode_wait")
        # ahead only of a step that was in flight (how often is the chip's to say)
        assert counts["decode_launched_ahead"] <= min(counts["decode_launched"], launched_before)
        assert counts["discarded_rows"] == 0  # no stop id, nothing cancelled
        # the relation to an after-step sample of _lengths * _active (the
        # harness's): the tokens of the step just launched are not recorded
        # yet, and a slot whose budget the step in flight spent ended at this
        # iteration's read, so what is left is what that step is reading
        assert row["after"] == counts["context_tokens"]
        # and the relation PR 25 pinned, moved by the iteration the read now
        # lags: the step launched an iteration ago added one token a row, this
        # iteration's read freed the slots that row finished (a request that
        # ends at its decode held prompt + n_generated - 1 tokens then), and
        # the prompts whose last chunk ran in this iteration started decoding
        freed = sum(r["prompt_tokens"] + r["n_generated"] - 1 for r in row["done"]
                    if r["n_generated"] >= 2)
        started = sum(
            next(int(c["stats"]["pos"]) + int(c["stats"]["tokens"]) for c in chunks
                 if c["stats"]["slot"] == w["stats"]["slot"])
            for w in kids if w["name"] == "serve.first_token_wait"
        ) - sum(r["prompt_tokens"] for r in row["done"] if r["n_generated"] == 1)
        assert row["after"] == read_before + wave_before - freed + started
        launched_before = counts["decode_launched"]
        read_before, wave_before = row["read"], row["wave"]
        admitted += counts["admitted"]
    assert admitted == 5
    first_waits = [s for s in spans if s["name"] == "serve.first_token_wait"]
    assert len(first_waits) == 5  # one a prompt, after its last chunk


def _iteration_kids(traced):
    spans = traced["spans"]
    return [program_trace.children_of(spans, i) for i, _ in _steps(spans)]


def test_every_record_carries_its_slice_of_the_loop_s_account(traced):
    """A request's ``decode_account`` is the account's difference between its
    first token and its end: the seconds sum to its ``decode_s`` and the counts
    are those of the ``serve.counts`` rows in between. The first token comes in
    a prefill tick, BEFORE that iteration's decode tick and the chunks of the
    slots after its own; the end comes at a decode read, AFTER that iteration's
    chunks and launch."""
    kids = _iteration_kids(traced)
    counts = [{k: int(v) for k, v in ks[-1]["stats"].items()} for ks in kids]
    decoded = 0
    for j, row in enumerate(traced["rows"]):
        for rec in row["done"]:
            acct, n = rec["decode_account"], rec["n_generated"]
            assert set(acct["n"]) == set(loop_account.REQUEST_COUNTS)
            assert set(acct["s"]) <= set(loop_account.BUCKETS)
            assert all(v > 0 for v in acct["s"].values())
            if n == 1:  # ended where its first token was recorded
                assert set(acct["s"]) == {"record"} and not any(acct["n"].values())
                continue
            decoded += 1
            assert sum(acct["s"].values()) == pytest.approx(
                (n - 1) / rec["decode_tps"], abs=1e-6)
            i, b = traced["first_token"][rec["request_id"]]
            own = lambda name: sum(k["name"] == name and int(k["stats"]["slot"]) > b
                                   for k in kids[i])
            want = {k: sum(c[k] for c in counts[i:j + 1])
                    for k in ("decode_launched", "decode_launched_ahead", "decoded",
                              "context_tokens", "expert_live_units", "expert_grid_units")}
            want["chunks"] = own("serve.prefill_dispatch") + sum(
                c["chunks"] for c in counts[i + 1:j + 1])
            want["first_token_waits"] = own("serve.first_token_wait") + sum(
                k["name"] == "serve.first_token_wait" for ks in kids[i + 1:j + 1] for k in ks)
            want["iterations"] = j - i
            assert {k: acct["n"][k] for k in want} == want, rec["request_id"]
            assert acct["n"]["decode_launched"] >= n - 1  # one launch a token of its own
            assert {"decode_wait", "decode_dispatch", "decode_launch", "record", "step",
                    "outside_step"} <= set(acct["s"])
    assert decoded == 4


def test_account_totals_are_the_counts_rows_and_the_spans(traced):
    before, after = traced["account"]
    spans, kids = traced["spans"], _iteration_kids(traced)
    counts = [{k: int(v) for k, v in ks[-1]["stats"].items()} for ks in kids]
    n = {k: after.n[k] - before.n[k] for k in after.n}
    assert {k: n[k] for k in loop_account.ITERATION_COUNTS} == {
        k: sum(c[k] for c in counts) for k in loop_account.ITERATION_COUNTS}
    named = lambda name: sum(s["name"] == name for s in spans)
    assert n["iterations"] == named("serve.step") == len(traced["rows"])
    assert n["first_token_waits"] == named("serve.first_token_wait")
    # every nanosecond of the thread is in exactly one bucket
    ns = {k: after.ns[k] - before.ns[k] for k in after.ns}
    assert sum(ns.values()) == after.t_ns - before.t_ns
    # a bucket got time exactly where the trace has a span of its name (the
    # train spans and the trace's own start and stop fell in `outside_step`)
    assert {k for k, v in ns.items() if v} == {"outside_step"} | {
        s["name"].removeprefix("serve.") for s in spans
        if s["name"].startswith("serve.") and s["name"] != "serve.counts"}


def _spec_engine():
    from automodel_tpu.serving.engine import SpeculativeConfig

    draft = {
        "hf_config": dict(
            architectures=["LlamaForCausalLM"], model_type="llama", vocab_size=64,
            hidden_size=16, intermediate_size=32, num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=1, head_dim=8, max_position_embeddings=128),
        "backend": {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"},
    }
    return _engine(speculative=SpeculativeConfig(enabled=True, k=2, draft=draft))


def _recurrent_engine():
    from tests.test_serving_recurrent_state import _auto
    from tests.test_serving_recurrent_state import _engine as recurrent_engine

    return recurrent_engine(_auto()[1])


@pytest.mark.parametrize("build,waits", [
    (_spec_engine, {"spec_propose", "spec_verify"}),
    (_recurrent_engine, {"decode_wait"}),
])
def test_other_ticks_and_layouts_produce_the_slice(build, waits):
    """The speculative tick's phases are buckets like any other, and a layout
    with recurrent state goes through the same primitive."""
    eng = build()
    for prompt in ([5, 6, 7, 8, 9, 10, 11, 12, 13], [9, 8, 7]):
        eng.submit(prompt, max_new_tokens=5)
    recs = eng.run()
    assert len(recs) == 2
    for rec in recs:
        acct = rec["decode_account"]
        assert sum(acct["s"].values()) == pytest.approx(
            (rec["n_generated"] - 1) / rec["decode_tps"], abs=1e-6)
        assert waits <= set(acct["s"]) and acct["n"]["decoded"] > 0
    totals = eng.loop_account()
    assert set(totals["s"]) == set(loop_account.BUCKETS)
    assert set(totals["n"]) == set(loop_account.COUNTS)
    assert totals["n"]["chunks"] >= 3 and totals["n"]["first_token_waits"] == 2
    assert (totals["n"]["decode_launched"] == 0) == ("spec_verify" in waits)


def test_stats_and_metrics_show_the_loop_s_account():
    """/stats and /metrics of ``automodel_tpu serve``: the totals, as the
    operator's sink (``stats_snapshot`` and ``ServingMetrics.sync`` are what
    the two handlers call under the engine lock)."""
    from automodel_tpu.serving.server import stats_snapshot
    from automodel_tpu.telemetry.federation import parse_exposition

    eng = _engine()
    for prompt in ([5, 6, 7, 8, 9], [9, 8, 7]):
        eng.submit(prompt, max_new_tokens=4)
    eng.run()
    stats = stats_snapshot(eng)["loop_account"]
    assert stats["n"]["iterations"] == eng._step_counter
    assert stats["n"]["chunks"] == 3 and stats["n"]["decode_launched"] >= 3
    eng.metrics.sync(eng)
    fams = parse_exposition(eng.metrics.registry.render())
    seconds = {k[0][1]: v for k, v in fams["automodel_serve_loop_seconds"].samples.items()}
    events = {k[0][1]: v for k, v in fams["automodel_serve_loop_events"].samples.items()}
    assert set(seconds) == set(loop_account.BUCKETS) and events == stats["n"]
    # the same thread, a little later: every counter at or past /stats'
    assert all(seconds[k] >= v for k, v in stats["s"].items())
    assert seconds["decode_wait"] == stats["s"]["decode_wait"] > 0
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.run()
    eng.metrics.sync(eng)  # a counter never goes back
    later = parse_exposition(eng.metrics.registry.render())["automodel_serve_loop_seconds"]
    assert all(later.samples[k] >= v for k, v in
               fams["automodel_serve_loop_seconds"].samples.items())


def test_train_input_path_spans(traced):
    names = [s["name"] for s in traced["spans"] if s["name"].startswith("train.")]
    assert names.count("train.collate") == 2 and names.count("train.place") == 2


def test_gap_goes_to_the_innermost_span_covering_it(traced):
    spans = traced["spans"]
    wait = next(s for s in spans if s["name"] == "serve.decode_wait")
    step = spans[wait["parent"]]
    mid = 0.5 * (wait["start_s"] + wait["end_s"])
    rows = program_trace.gaps_by_span(
        [(mid - 1e-7, mid + 1e-7), (step["start_s"] - 2.0, step["start_s"] - 1.0)], spans)
    assert rows[0][1:] == ("serve.decode_wait", "serve.step")
    assert rows[1][1:] == (None, None)


def test_stall_evidence_names_the_phase(tmp_path):
    """A decode program that does not come back: the watchdog's record and the
    engine's event both say the iteration sat in ``decode_dispatch``."""
    records = []
    eng = _engine(watchdog=StallConfig(
        min_deadline_s=0.2, max_deadline_s=0.5, multiplier=4.0, poll_interval_s=0.02,
        compile_grace_s=60.0, stacks_path=str(tmp_path / "stacks.txt")))
    eng.on_record = records.append
    eng.submit([5, 6, 7], max_new_tokens=3)
    eng.run()
    assert eng.step_phase is None  # between iterations
    wd = eng.start_watchdog()
    try:
        for _ in range(4):  # seed the deadline's average with real iterations
            eng.submit([5, 6, 7], max_new_tokens=3)
            eng.run()
        real = eng._decode

        def hung(*a):
            time.sleep(1.2)
            return real(*a)

        eng._decode = hung
        eng.submit([9, 8, 7], max_new_tokens=3)
        done = []
        while not eng.idle():
            done += eng.step()
            eng._decode = real
        assert wd.fired is not None and wd.fired["step_phase"] == "decode_dispatch"
        assert [r["completion_reason"] for r in done] == ["engine_stall"]
        event = next(r for r in records if r.get("event") == "serve_engine_event")
        assert "in phase decode_dispatch" in event["detail"]
    finally:
        eng.stop_watchdog()


def test_counts_carry_the_fused_kernel_s_grid(monkeypatch):
    """``attn_grid_steps`` / ``attn_live_steps`` of ``serve.counts`` are the
    fused decode kernel's own grouping of the lengths it is handed: heads of
    128 in blocks of 16 are pages it reads 32 a grid step, a table 69 wide is
    three groups a slot, and a group is live up to the one holding position
    ``length`` (the row written just before the attend)."""
    from automodel_tpu.models.llama import LlamaForCausalLM
    from automodel_tpu.ops import paged_attention

    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    model = LlamaForCausalLM(
        TransformerConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=1,
                          num_heads=2, num_kv_heads=2, head_dim=128),
        FP32,
    )
    auto = AutoModel(model=model, params=model.init(jax.random.key(0)), adapter=None,
                     mesh_ctx=None)
    eng = ServingEngine(
        auto,
        ServeConfig(slots=4, block_size=16, num_blocks=96, prefill_chunk=16,
                    max_seq_len=16 * 69, decode_kernel="fused"),
        GenerationConfig(max_new_tokens=3, greedy=True),
    )
    assert eng.decode_backend == "fused" and eng.attn_pages_per_step == 32
    assert eng.attn_pages_per_step == paged_attention.pages_per_step(16, 2, 128, 1, 4)

    # by hand: an empty slot still attends position 0 of its scratch page; 511
    # ends the first group, 512 opens the second, an inactive slot counts too
    eng._lengths[:] = [0, 511, 512, 600]
    eng._active[:] = [False, True, True, False]
    eng._note_decode_wave(eng._lengths, eng._active)
    counts = eng.loop_account()["n"]
    assert (counts["attn_grid_steps"], counts["attn_live_steps"]) == (4 * 3, 1 + 1 + 2 + 2)
    eng._lengths[:] = 0
    eng._active[:] = False

    # through step(): the event carries them, from the lengths the program read
    events, handed = [], []

    class Recorder:
        def __init__(self, name, **stats):
            if name == "serve.counts":
                events.append(stats)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    real_decode = eng._decode

    def spy(params, pool, tables, lengths, *rest):
        handed.append(np.asarray(lengths).copy())
        return real_decode(params, pool, tables, lengths, *rest)

    eng._decode = spy
    monkeypatch.setattr(loop_account, "TraceAnnotation", Recorder)
    eng.submit(list(range(1, 20)), request_id="a")
    eng.submit([3, 4, 5], request_id="b")
    eng.run()
    decoding = [e for e in events if e["decoded"]]
    assert len(decoding) == len(handed) > 0
    for event, lengths in zip(decoding, handed):
        assert (event["attn_grid_steps"], event["attn_live_steps"]) == (
            paged_attention.grid_steps(lengths, 69, pages=32, block_size=16))
        assert event["attn_grid_steps"] == 12 and event["attn_live_steps"] == 4
    assert all(e["attn_grid_steps"] == 0 for e in events if not e["decoded"])


def test_counts_carry_the_fused_expert_forward_s_units(monkeypatch):
    """``expert_live_units`` / ``expert_grid_units`` of ``serve.counts`` are the
    fused expert forward's own plan over the rows a decode step routed: every
    slot's row (free ones too) x top-k, sorted into the experts of each expert
    layer; the grid is fixed by the shapes, a unit is live when its expert got
    rows. A dense model's counts say 0 / 0."""
    from automodel_tpu.models.qwen3_moe import MoEForCausalLM, MoETransformerConfig
    from automodel_tpu.ops import fused_expert_mlp

    monkeypatch.setenv("AUTOMODEL_GMM_INTERPRET", "1")
    hf = {
        "architectures": ["Qwen3MoeForCausalLM"], "model_type": "qwen3_moe",
        "vocab_size": 128, "hidden_size": 128, "intermediate_size": 128,
        "moe_intermediate_size": 128, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 16, "num_experts_per_tok": 2,
        "max_position_embeddings": 256, "tie_word_embeddings": False,
        "first_k_dense_replace": 1,
    }
    model = MoEForCausalLM(
        MoETransformerConfig.from_hf(hf),
        BackendConfig(attn="sdpa", experts="ragged_fused", param_dtype="float32",
                      compute_dtype="float32"),
    )
    auto = AutoModel(model=model, params=model.init(jax.random.key(2)), adapter=None,
                     mesh_ctx=None)
    eng = ServingEngine(
        auto,
        ServeConfig(slots=3, block_size=4, num_blocks=64, prefill_chunk=4, max_seq_len=48),
        GenerationConfig(max_new_tokens=6, greedy=True),
    )
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
    eng.submit(list(range(1, 11)), request_id="a")
    eng.submit([3, 4, 5], request_id="b")
    eng.run()
    # the units come to the host with the step's tokens: in the iteration
    # AFTER the one that launched it (the decode tick launches ahead)
    read = [e for e, before in zip(events[1:], events) if before["decode_launched"]]
    assert read and sum(e["decode_launched"] for e in events) == len(read)
    # two expert layers (the first of three is dense), 3 slots x top-2 rows
    grid = 2 * fused_expert_mlp.work_units(np.zeros(16, int), 6, 128, 128)[1]
    for e in read:
        assert e["expert_grid_units"] == grid == 2 * (1 + 16)
        # 6 rows a layer in one row tile: a live unit is an expert touched,
        # at least top-k of them a layer and at most one a row
        assert 2 * 2 <= e["expert_live_units"] <= 2 * 6
    assert all(e["expert_grid_units"] == e["expert_live_units"] == 0
               for e, before in zip(events, [{"decode_launched": 0}] + events)
               if not before["decode_launched"])

    dense = _engine()
    events.clear()
    dense.submit([5, 6, 7], max_new_tokens=3)
    dense.run()
    assert any(e["decoded"] for e in events)
    assert all(e["expert_grid_units"] == e["expert_live_units"] == 0 for e in events)
