"""Serving subsystem: paged allocator properties, continuous-batching
scheduler semantics, greedy parity vs the single-wave engine (full + ring
model layouts, ragged prompts, stop-token mid-wave refill), prefix caching,
CLI + HTTP front, bench-leg degradation, report schema. All CPU-fast,
tier-1.

Parity ground truth: the paged/continuous path must reproduce the PR 4
single-wave ``GenerationEngine``'s greedy tokens exactly, per prompt — the
allocator/scheduler may change WHERE K/V lives and WHEN prompts prefill,
never what gets decoded."""

import json
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.auto_model import AutoModel
from automodel_tpu.generation.engine import GenerationConfig, GenerationEngine
from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
from automodel_tpu.serving.block_pool import BlockPool, BlockPoolError
from automodel_tpu.serving.engine import QueueFull, ServeConfig, ServingEngine

FP32 = BackendConfig(attn="sdpa", param_dtype="float32", compute_dtype="float32")


def _tiny_llama(**over):
    kw = dict(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=3,
        num_heads=4, num_kv_heads=2, head_dim=8,
    )
    kw.update(over)
    from automodel_tpu.models.llama import LlamaForCausalLM

    model = LlamaForCausalLM(TransformerConfig(**kw), FP32)
    return model, model.init(jax.random.key(0))


def _auto(model, params, mesh_ctx=None):
    return AutoModel(model=model, params=params, adapter=None, mesh_ctx=mesh_ctx)


def _single_wave_greedy(auto, prompt, max_new):
    """Reference: the PR 4 engine, one prompt at a time."""
    eng = GenerationEngine(
        auto, GenerationConfig(max_new_tokens=max_new, greedy=True, pad_to_multiple=1)
    )
    return eng.generate_ids([list(prompt)])["tokens"][0]


def _single_wave_greedy_batch(auto, prompts, max_new):
    """One batched reference call (ONE compile set — greedy tokens are
    per-slot identical to per-prompt calls)."""
    eng = GenerationEngine(
        auto, GenerationConfig(max_new_tokens=max_new, greedy=True, pad_to_multiple=1)
    )
    return eng.generate_ids([list(p) for p in prompts])["tokens"]


# -- allocator ----------------------------------------------------------------


def test_block_pool_basics():
    pool = BlockPool(num_blocks=8, block_size=4)
    assert pool.usable_blocks == 7 and pool.available() == 7
    a = pool.allocate(3)
    assert len(a) == 3 and 0 not in a
    assert pool.in_use() == 3 and 0 < pool.occupancy() < 1
    pool.free(a)
    assert pool.available() == 7
    with pytest.raises(BlockPoolError, match="double free"):
        pool.free([a[0]])
    with pytest.raises(BlockPoolError, match="scratch"):
        pool.free([0])
    assert pool.allocate(8) is None  # more than usable
    assert pool.counters["failed_allocs"] == 1


def test_block_pool_prefix_cache_reuse_and_eviction():
    pool = BlockPool(num_blocks=6, block_size=2)  # 5 usable
    tokens = [1, 2, 3, 4, 5]  # 2 full blocks (last token never cached)
    blocks = pool.allocate(3)
    pool.register_prefix(tokens, blocks)
    pool.free(blocks)  # cached blocks park in the LRU, still matchable
    assert pool.available() == 5
    hits, n = pool.match_prefix(tokens)
    assert n == 4 and hits == blocks[:2]
    assert pool.counters["prefix_hits"] == 1
    assert pool.counters["prefix_tokens_reused"] == 4
    pool.free(hits)
    # a full-pool allocation evicts the cached blocks (cache never causes
    # an allocation failure)
    big = pool.allocate(5)
    assert big is not None and pool.counters["evictions"] >= 1
    assert pool.match_prefix(tokens) == ([], 0)  # evicted → miss
    pool.free(big)
    pool.check_invariants()


def test_block_pool_property_randomized_schedule():
    """No block leaked or double-freed across a randomized admit/finish
    schedule with prefix caching on: invariants hold after every operation
    and the drained pool returns to fully available."""
    rng = random.Random(0)
    pool = BlockPool(num_blocks=24, block_size=4)
    live: list[tuple[list[int], list[int]]] = []  # (all blocks, tokens)
    for step in range(400):
        if live and (rng.random() < 0.45 or pool.available() < 4):
            blocks, _ = live.pop(rng.randrange(len(live)))
            pool.free(blocks)
        else:
            # a few recurring prompts so prefix hits actually occur
            tokens = [rng.randrange(4) for _ in range(rng.choice([3, 7, 9, 13]))]
            hits, n_hit = pool.match_prefix(tokens)
            need = -(-(len(tokens) + 3) // 4) - len(hits)
            fresh = pool.allocate(need)
            if fresh is None:
                if hits:
                    pool.free(hits)
            else:
                pool.register_prefix(tokens, hits + fresh)
                live.append((hits + fresh, tokens))
        pool.check_invariants()
    for blocks, _ in live:
        pool.free(blocks)
    pool.check_invariants()
    assert pool.available() == pool.usable_blocks
    assert pool.counters["allocated"] > 0 and pool.counters["prefix_hits"] > 0


# -- greedy parity ------------------------------------------------------------


def test_paged_greedy_parity_ragged_prompts_full_layout():
    """Ragged prompts through chunked prefill + paged decode == per-prompt
    single-wave greedy, token for token."""
    model, params = _tiny_llama()
    auto = _auto(model, params)
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17], [3, 1]]
    refs = _single_wave_greedy_batch(auto, prompts, 6)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=2, block_size=4, num_blocks=32, prefill_chunk=4, max_seq_len=32),
        GenerationConfig(max_new_tokens=6, greedy=True),
    )
    ids = [srv.submit(p) for p in prompts]
    done = {r["request_id"]: r for r in srv.run()}
    for rid, ref in zip(ids, refs):
        assert done[rid]["tokens"] == ref
    srv.pool.check_invariants()
    assert srv.pool.available() == srv.pool.usable_blocks  # all freed


def test_paged_greedy_parity_gpt2():
    """gpt2 (learned positions, its own decoder) rides the same
    chunk/decode path."""
    from automodel_tpu.models.gpt2.model import GPT2Config, GPT2ForCausalLM

    gpt2 = GPT2ForCausalLM(
        GPT2Config(vocab_size=96, n_positions=64, hidden_size=32, num_layers=2, num_heads=4),
        FP32,
    )
    _assert_family_parity(gpt2, gpt2.init(jax.random.key(1)), [[3, 4, 5, 6], [10, 11]])


@pytest.mark.slow
def test_paged_greedy_parity_qwen3_moe():
    """qwen3_moe (MoE decode incl. a dense-prefix layer) — the heaviest
    family build, beyond the tier-1 acceptance list."""
    from automodel_tpu.models.qwen3_moe import MoEForCausalLM, MoETransformerConfig

    hf = {
        "architectures": ["Qwen3MoeForCausalLM"], "model_type": "qwen3_moe",
        "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 8, "num_experts_per_tok": 2,
        "max_position_embeddings": 256, "tie_word_embeddings": False,
        "first_k_dense_replace": 1,
    }
    moe = MoEForCausalLM(
        MoETransformerConfig.from_hf(hf),
        BackendConfig(
            attn="sdpa", experts="dense",
            param_dtype="float32", compute_dtype="float32",
        ),
    )
    _assert_family_parity(moe, moe.init(jax.random.key(2)), [[7, 8, 9, 10], [20, 21, 22]])


def _assert_family_parity(model, params, prompts):
    auto = _auto(model, params)
    refs = _single_wave_greedy_batch(auto, prompts, 5)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=2, block_size=4, num_blocks=32, prefill_chunk=4, max_seq_len=48),
        GenerationConfig(max_new_tokens=5, greedy=True),
    )
    ids = [srv.submit(p) for p in prompts]
    done = {r["request_id"]: r for r in srv.run()}
    for rid, ref in zip(ids, refs):
        assert done[rid]["tokens"] == ref


def test_paged_greedy_parity_sliding_window_ring_model():
    """A homogeneous sliding-window model: the single-wave engine uses the
    RING layout (and rejects ragged wrapping batches); serving uses the full
    paged layout with per-layer window masks — same greedy tokens, and the
    ragged batch the ring engine refuses is served fine."""
    model, params = _tiny_llama(sliding_window=4, num_layers=2)
    auto = _auto(model, params)
    prompts = [[1, 2, 3, 4, 5, 6], [7, 8]]  # ragged + wraps the ring window
    ring_eng = GenerationEngine(
        auto, GenerationConfig(max_new_tokens=8, greedy=True, pad_to_multiple=1)
    )
    with pytest.raises(ValueError, match="ring"):
        ring_eng.generate_ids(prompts)
    # per-prompt ring decode is exact — that is the parity reference
    refs = [ring_eng.generate_ids([p])["tokens"][0] for p in prompts]
    srv = ServingEngine(
        auto,
        ServeConfig(slots=2, block_size=4, num_blocks=32, prefill_chunk=4, max_seq_len=32),
        GenerationConfig(max_new_tokens=8, greedy=True),
    )
    ids = [srv.submit(p) for p in prompts]
    done = {r["request_id"]: r for r in srv.run()}
    for rid, ref in zip(ids, refs):
        assert done[rid]["tokens"] == ref


# -- continuous batching ------------------------------------------------------


def test_slot_refill_mid_flight_exceeds_slot_count():
    """The acceptance observable: with 2 slots and 6 requests of mixed
    budget, completed-request count exceeds slot count within ONE engine
    run, the queue drains, and nothing is dropped."""
    model, params = _tiny_llama()
    auto = _auto(model, params)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=2, block_size=4, num_blocks=48, prefill_chunk=4, max_seq_len=32),
        GenerationConfig(max_new_tokens=8, greedy=True),
    )
    reqs = [
        ([1, 2, 3], 2), ([4, 5], 8), ([6, 7, 8, 9], 3),
        ([10, 11], 2), ([12, 13, 14], 5), ([15], 4),
    ]
    ids = [srv.submit(p, max_new_tokens=n) for p, n in reqs]
    done = srv.run()
    assert len(done) == 6 > srv.config.slots
    assert {r["request_id"] for r in done} == set(ids)  # no drops
    assert srv.queue_depth == 0 and srv.busy_slots == 0
    by_id = {r["request_id"]: r for r in done}
    for rid, (p, n) in zip(ids, reqs):
        assert by_id[rid]["n_generated"] == n  # no eos in vocab → exact budget
        assert by_id[rid]["ttft_s"] > 0
    # parity holds for every request even with mid-flight refills: greedy
    # is prefix-stable, so one budget-8 batched reference covers every
    # shorter per-request budget (ONE compile set, no eos configured)
    refs8 = _single_wave_greedy_batch(auto, [p for p, _ in reqs], 8)
    for rid, ref, (p, n) in zip(ids, refs8, reqs):
        assert by_id[rid]["tokens"] == ref[:n]


def test_stop_token_mid_wave_refill():
    """A slot whose sequence hits the stop token frees mid-wave and the
    queue refills it while the other slot keeps decoding."""
    model, params = _tiny_llama()
    auto = _auto(model, params)
    # discover what greedy emits second for this prompt, declare it eos
    ref = _single_wave_greedy(auto, [1, 2, 3], 4)
    eos = ref[1]
    gen = GenerationConfig(max_new_tokens=12, greedy=True, eos_token_id=eos)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=1, block_size=4, num_blocks=32, prefill_chunk=4, max_seq_len=32),
        gen,
    )
    # request A stops at eos after 2 tokens; B (queued behind the single
    # slot) must still complete — the refill is the continuous-batching move
    a = srv.submit([1, 2, 3])
    b = srv.submit([7, 8, 9])
    done = {r["request_id"]: r for r in srv.run()}
    assert done[a]["tokens"][-1] == eos and done[a]["n_generated"] == 2
    assert len(done[b]["tokens"]) >= 1
    # single-wave reference with the same eos config
    eng = GenerationEngine(auto, GenerationConfig(
        max_new_tokens=12, greedy=True, eos_token_id=eos, pad_to_multiple=1
    ))
    assert done[b]["tokens"] == eng.generate_ids([[7, 8, 9]])["tokens"][0]


def test_chunked_prefill_interleaves_with_decode():
    """A short request admitted alongside a LONG prompt completes before
    the long prompt's prefill finishes — chunked prefill never stalls the
    decode wave (the ttft contract)."""
    model, params = _tiny_llama(max_position_embeddings=256)
    auto = _auto(model, params)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=2, block_size=4, num_blocks=64, prefill_chunk=2, max_seq_len=64),
        GenerationConfig(max_new_tokens=2, greedy=True),
    )
    long_prompt = list(range(1, 41))  # 40 tokens / chunk 2 → 20 iterations
    short = srv.submit([1, 2], max_new_tokens=2)
    long = srv.submit(long_prompt)
    order = []
    for _ in range(200):
        for rec in srv.step():
            order.append(rec["request_id"])
        if srv.idle():
            break
    assert order[0] == short and order[-1] == long
    # and the long prompt still decodes correctly after 20 chunks
    assert {r for r in order} == {short, long}


def test_prefix_cache_hit_reuses_blocks_with_unchanged_output():
    """Second request with the same prompt: allocator counters prove block
    reuse; greedy output is unchanged."""
    model, params = _tiny_llama()
    auto = _auto(model, params)
    prompt = list(range(1, 18))  # 17 tokens, bs 4 → 4 full blocks
    gen = GenerationConfig(max_new_tokens=4, greedy=True)
    scfg = ServeConfig(slots=1, block_size=4, num_blocks=32, prefill_chunk=8, max_seq_len=40)
    srv = ServingEngine(auto, scfg, gen)
    a = srv.submit(prompt)
    out_a = {r["request_id"]: r for r in srv.run()}[a]
    assert srv.pool.counters["prefix_hits"] == 0
    b = srv.submit(prompt)
    out_b = {r["request_id"]: r for r in srv.run()}[b]
    assert out_b["tokens"] == out_a["tokens"] == _single_wave_greedy(auto, prompt, 4)
    assert srv.pool.counters["prefix_hits"] == 1
    assert srv.pool.counters["prefix_blocks_reused"] == 4
    assert out_b["prefix_hit_tokens"] == 16
    # fully-aligned prompt: the LAST block is never served from cache (its
    # logits seed the first token) — an 8-token prompt reuses only 1 block
    srv2 = ServingEngine(auto, scfg, gen)
    p8 = list(range(1, 9))
    srv2.submit(p8)
    srv2.run()
    c = srv2.submit(p8)
    out_c = {r["request_id"]: r for r in srv2.run()}[c]
    assert out_c["prefix_hit_tokens"] == 4
    assert out_c["tokens"] == _single_wave_greedy(auto, p8, 4)
    # disabling the cache changes nothing but the counters
    srv3 = ServingEngine(
        auto,
        ServeConfig(slots=1, block_size=4, num_blocks=32, prefill_chunk=8,
                    max_seq_len=40, prefix_cache=False),
        gen,
    )
    srv3.submit(prompt)
    srv3.submit(prompt)
    outs = srv3.run()
    assert all(r["tokens"] == out_a["tokens"] for r in outs)
    assert srv3.pool.counters["prefix_hits"] == 0


def test_admission_backpressure_and_limits():
    model, params = _tiny_llama()
    auto = _auto(model, params)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=1, block_size=4, num_blocks=16, prefill_chunk=4,
                    max_seq_len=16, max_queue=2),
        GenerationConfig(max_new_tokens=4, greedy=True),
    )
    with pytest.raises(ValueError, match="empty prompt"):
        srv.submit([])
    with pytest.raises(ValueError, match="serving limit"):
        srv.submit(list(range(1, 15)), max_new_tokens=8)  # 14 + 8 > 16
    with pytest.raises(ValueError, match="max_new_tokens"):
        srv.submit([1, 2], max_new_tokens=0)  # explicit 0 is an error, not
        # a fall-through to the generation default
    srv.submit([1, 2])
    srv.submit([3, 4])
    with pytest.raises(QueueFull):
        srv.submit([5, 6])
    srv.run()


def test_pool_exhaustion_queues_until_blocks_free():
    """Requests beyond the pool stay QUEUED (never dropped, never
    deadlocked) and complete once earlier completions free blocks."""
    model, params = _tiny_llama()
    auto = _auto(model, params)
    # pool of 7 usable blocks, each request needs 3 → only 2 fit at once
    srv = ServingEngine(
        auto,
        ServeConfig(slots=4, block_size=4, num_blocks=8, prefill_chunk=4,
                    max_seq_len=12, prefix_cache=False),
        GenerationConfig(max_new_tokens=4, greedy=True),
    )
    ids = [srv.submit([i + 1, i + 2, i + 3]) for i in range(5)]
    done = srv.run()
    assert {r["request_id"] for r in done} == set(ids)
    assert srv.pool.counters["failed_allocs"] > 0  # backpressure happened
    srv.pool.check_invariants()


def test_sustained_poisson_workload():
    """Poisson arrivals of mixed-length prompts, submitted as they come
    due while the engine steps: the queue drains and every record is
    coherent."""
    from serving_driver import drive

    model, params = _tiny_llama()
    auto = _auto(model, params)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=2, block_size=4, num_blocks=48, prefill_chunk=8, max_seq_len=32),
        GenerationConfig(max_new_tokens=3, greedy=True),
    )
    rng = np.random.default_rng(0)
    arrivals = []
    t = 0.0
    for i in range(8):
        t += float(rng.exponential(0.002))
        n = int(rng.integers(2, 10))
        arrivals.append((t, rng.integers(1, 64, size=n).tolist(), 3))
    occupancy = []
    done = drive(srv, arrivals, lambda e: occupancy.append(e.pool.occupancy()))
    assert len(done) == 8
    assert {r["completion_reason"] for r in done} == {"length"}
    assert sum(r["n_generated"] for r in done) == 24
    assert all(r["ttft_s"] > 0 for r in done)
    assert 0 < max(occupancy) <= 1
    assert srv.idle()


def test_engine_on_mesh(devices8):
    """Sharded pool: serving over a from_config model on an 8-device CPU
    mesh (tp=2 shards the pool's KV heads)."""
    from automodel_tpu import auto_model
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh

    ctx = build_mesh(MeshConfig(dp_shard=4, tp=2), devices=devices8)
    hf = {
        "architectures": ["LlamaForCausalLM"], "model_type": "llama",
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8,
        "max_position_embeddings": 128,
    }
    auto = auto_model.from_config(
        hf, ctx,
        {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"},
    )
    srv = ServingEngine(
        auto,
        ServeConfig(slots=2, block_size=8, num_blocks=16, prefill_chunk=8, max_seq_len=64),
        GenerationConfig(max_new_tokens=4, greedy=True),
    )
    a = srv.submit([1, 2, 3, 4])
    b = srv.submit([1, 2, 3, 4])
    done = {r["request_id"]: r for r in srv.run()}
    assert done[a]["tokens"] == done[b]["tokens"]  # identical prompts
    assert len(done[a]["tokens"]) == 4


@pytest.mark.parametrize("tp,tail,spec", [(2, (4, 128), "tp"), (8, (8, 64), "tp")])
def test_narrow_heads_pack_only_where_the_pool_shards_the_same(
    monkeypatch, devices8, tp, tail, spec
):
    """8 KV heads of 64 pack into 4 rows of 128 lanes (kv_cache.packed_heads).
    On tp=2 the 4 packed rows still shard over tp, so the pool is packed; on
    tp=8 they would not, so the pool stays [8, 64] and shards as the heads
    did. Either way the engine decodes the one-device engine's tokens, through
    the paged kernel (interpreted) inside its shard_map."""
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    from automodel_tpu import auto_model
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh

    hf = {
        "architectures": ["LlamaForCausalLM"], "model_type": "llama",
        "vocab_size": 64, "hidden_size": 64, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 8,
        "num_key_value_heads": 8, "head_dim": 64,
        "max_position_embeddings": 128,
    }
    backend = {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"}

    def tokens(ctx):
        auto = auto_model.from_config(hf, ctx, backend)
        srv = ServingEngine(
            auto,
            ServeConfig(slots=2, block_size=8, num_blocks=16, prefill_chunk=8, max_seq_len=64,
                        decode_kernel="fused"),
            GenerationConfig(max_new_tokens=4, greedy=True),
        )
        ids = [srv.submit([1, 2, 3, 4, 5]), srv.submit([9, 8, 7])]
        done = {r["request_id"]: r for r in srv.run()}
        return srv, [done[i]["tokens"] for i in ids]

    srv, got = tokens(build_mesh(MeshConfig(dp_shard=8 // tp, tp=tp), devices=devices8))
    assert srv._pool.values_shape[3:] == tail
    assert srv._pool.k.sharding.spec[3] in (spec, (spec,))
    one, want = tokens(build_mesh(MeshConfig(dp_shard=1), devices=devices8[:1]))
    assert one._pool.values_shape[3:] == (4, 128)
    assert got == want


# -- serve CLI / HTTP ---------------------------------------------------------


def _tiny_serve_cfg(tmp_path=None, **serving_over):
    from automodel_tpu.config.loader import ConfigNode

    cfg = {
        "seed": 0,
        "model": {
            "hf_config": {
                "architectures": ["LlamaForCausalLM"],
                "model_type": "llama",
                "vocab_size": 64, "hidden_size": 32,
                "intermediate_size": 64, "num_hidden_layers": 2,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 8, "max_position_embeddings": 128,
            },
            "backend": {
                "attn": "sdpa",
                "param_dtype": "float32",
                "compute_dtype": "float32",
            },
        },
        "distributed": {"dp_shard": 1},
        "generation": {"max_new_tokens": 4, "greedy": True},
        "serving": {
            "slots": 2, "block_size": 4, "num_blocks": 32,
            "prefill_chunk": 4, "max_seq_len": 32, **serving_over,
        },
    }
    if tmp_path is not None:
        cfg["logging"] = {"metrics_path": str(tmp_path / "serve_metrics.jsonl")}
    return ConfigNode(cfg)


def test_serve_cli_stdin_jsonl(tmp_path, capsys, monkeypatch, cpu_devices):
    import io

    monkeypatch.setattr(jax, "devices", lambda *a: cpu_devices[:1])
    monkeypatch.setattr(
        "sys.stdin",
        io.StringIO(
            json.dumps({"id": "a", "prompt": "1 2 3"}) + "\n"
            + json.dumps({"id": "b", "prompt_ids": [7, 8], "max_new_tokens": 2}) + "\n"
        ),
    )
    from automodel_tpu.serving.server import main

    rc = main(_tiny_serve_cfg(tmp_path))
    assert rc == 0
    out_lines = [
        json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")
    ]
    by_id = {r["request_id"]: r for r in out_lines}
    assert set(by_id) == {"a", "b"}
    assert len(by_id["a"]["completion"].split()) == 4
    assert by_id["b"]["n_generated"] == 2
    assert by_id["a"]["ttft_s"] > 0
    # per-request telemetry landed on the metrics JSONL and lints clean
    from automodel_tpu.telemetry.report import lint_metrics_jsonl, summarize_metrics

    records, problems = lint_metrics_jsonl(str(tmp_path / "serve_metrics.jsonl"))
    assert problems == []
    serves = [r for r in records if r.get("event") == "serve_request"]
    assert len(serves) == 2
    assert all("tokens" not in r for r in serves)  # completions stay out
    summary = summarize_metrics(records)
    assert summary["serve_requests"] == 2
    assert summary["serve_ttft_p50_s"] > 0


def test_serve_cli_stdin_bad_line_does_not_kill_the_batch(
    tmp_path, capsys, monkeypatch, cpu_devices
):
    """One malformed request line gets an error JSON line; every other
    request still completes (rc 1 signals the partial failure)."""
    import io

    monkeypatch.setattr(jax, "devices", lambda *a: cpu_devices[:1])
    monkeypatch.setattr(
        "sys.stdin",
        io.StringIO(
            json.dumps({"id": "good", "prompt": "1 2 3"}) + "\n"
            + "{not json\n"
            + json.dumps({"id": "oversize", "prompt": "1 2", "max_new_tokens": 999}) + "\n"
            + json.dumps({"id": "good2", "prompt_ids": [5, 6], "max_new_tokens": 2}) + "\n"
        ),
    )
    from automodel_tpu.serving.server import main

    rc = main(_tiny_serve_cfg())
    assert rc == 1  # completions delivered, bad lines reported
    out = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    errs = [r for r in out if "error" in r]
    done = {r["request_id"]: r for r in out if "request_id" in r}
    assert len(errs) == 2
    assert any(r.get("id") == "oversize" for r in errs)
    assert set(done) == {"good", "good2"}
    assert done["good2"]["n_generated"] == 2


def test_serve_cli_app_routing_and_empty_stdin(monkeypatch, cpu_devices, tmp_path):
    import io

    import yaml

    monkeypatch.setattr(jax, "devices", lambda *a: cpu_devices[:1])
    monkeypatch.setattr("sys.stdin", io.StringIO(""))
    cfg_path = tmp_path / "serve.yaml"
    cfg_path.write_text(yaml.safe_dump(_tiny_serve_cfg().to_dict()))
    from automodel_tpu.cli.app import main as app_main

    assert app_main(["serve", "-c", str(cfg_path)]) == 2  # no requests → usage


def test_serve_http_end_to_end(monkeypatch, cpu_devices):
    import urllib.request

    monkeypatch.setattr(jax, "devices", lambda *a: cpu_devices[:1])
    from automodel_tpu.generation.engine import build_auto_from_cfg
    from automodel_tpu.serving.server import serve_http

    cfg = _tiny_serve_cfg()
    auto = build_auto_from_cfg(cfg)
    engine = ServingEngine(
        auto,
        ServeConfig.from_dict(dict(cfg.get("serving"))),
        GenerationConfig.from_dict(dict(cfg.get("generation"))),
    )
    server, loop = serve_http(engine, None, port=0)
    import threading

    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        port = server.server_address[1]
        body = json.dumps({"prompt": "1 2 3", "max_new_tokens": 3}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = json.loads(resp.read())
        assert len(out["completion"].split()) == 3
        assert out["n_generated"] == 3 and out["ttft_s"] > 0
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stats", timeout=30
        ) as resp:
            stats = json.loads(resp.read())
        assert stats["completed_total"] == 1
        # a bad request is a 400, not a hung connection
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=b"{}",
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=30)
        assert ei.value.code == 400
    finally:
        server.shutdown()
        loop.close()


# -- robustness: deadlines / drain / shed / leak audit (PR 9) -----------------


def test_serve_config_nested_sections_parse_and_reject_unknown_keys():
    from automodel_tpu.serving.engine import DrainConfig, LimitsConfig, StallConfig

    cfg = ServeConfig.from_dict({
        "slots": 2,
        "limits": {"deadline_s": 30.0, "max_queue_wait_s": 5.0},
        "drain": {"grace_s": 10.0, "requeue_exit": "never"},
        "watchdog": {"enabled": False, "min_deadline_s": 1.0},
    })
    assert cfg.limits.deadline_s == 30.0 and cfg.limits.max_queue_wait_s == 5.0
    assert cfg.drain.grace_s == 10.0 and cfg.drain.requeue_exit == "never"
    assert cfg.watchdog.enabled is False
    with pytest.raises(TypeError, match="serving.limits"):
        ServeConfig.from_dict({"limits": {"deadline_ss": 1}})
    with pytest.raises(TypeError, match="serving.drain"):
        ServeConfig.from_dict({"drain": {"grace": 1}})
    with pytest.raises(TypeError, match="serving.watchdog"):
        ServeConfig.from_dict({"watchdog": {"multiplierr": 2}})
    with pytest.raises(ValueError, match="requeue_exit"):
        ServeConfig.from_dict({"drain": {"requeue_exit": "sometimes"}})
    assert LimitsConfig.from_dict(None).deadline_s is None
    assert DrainConfig.from_dict(None).grace_s == 30.0
    assert StallConfig.from_dict(None).enabled is True


@pytest.mark.parametrize(
    "section,key",
    [
        ("serving", "bench_requests"),
        ("serving", "bench_rate"),
        ("serving", "bench_prompt_len_min"),
        ("serving", "bench_prompt_len_max"),
        ("serving", "bench_max_new_tokens"),
        ("fleet", "bench_replicas"),
        ("fleet", "bench_num_blocks"),
    ],
)
def test_a_benchmark_s_traffic_is_no_key_of_the_server(section, key):
    """The traffic a harness sends is the harness's (benchmarks/traffic/):
    a YAML that still sets one of the old knobs is refused by name, not
    silently ignored."""
    from automodel_tpu.serving.fleet.router import FleetConfig

    cls = {"serving": ServeConfig, "fleet": FleetConfig}[section]
    with pytest.raises(TypeError, match=f"unknown {section} keys.*{key}"):
        cls.from_dict({key: 8})


def test_completion_reason_on_normal_completions():
    """Every terminal record carries exactly one completion_reason: length
    for a spent budget, stop for an eos hit."""
    model, params = _tiny_llama()
    auto = _auto(model, params)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=1, block_size=4, num_blocks=32, prefill_chunk=4, max_seq_len=32),
        GenerationConfig(max_new_tokens=3, greedy=True),
    )
    srv.submit([1, 2, 3])
    recs = srv.run()
    assert [r["completion_reason"] for r in recs] == ["length"]
    assert recs[0]["retriable"] is False
    # eos → stop
    ref = _single_wave_greedy(auto, [1, 2, 3], 4)
    srv2 = ServingEngine(
        auto,
        ServeConfig(slots=1, block_size=4, num_blocks=32, prefill_chunk=4, max_seq_len=32),
        GenerationConfig(max_new_tokens=12, greedy=True, eos_token_id=ref[1]),
    )
    srv2.submit([1, 2, 3])
    recs2 = srv2.run()
    assert recs2[0]["completion_reason"] == "stop"


def test_deadline_cancels_mid_decode_and_frees_blocks():
    import time as _time

    model, params = _tiny_llama()
    auto = _auto(model, params)
    recs = []
    srv = ServingEngine(
        auto,
        ServeConfig(slots=1, block_size=4, num_blocks=32, prefill_chunk=4, max_seq_len=64),
        GenerationConfig(max_new_tokens=40, greedy=True),
        on_record=recs.append,
    )
    srv.submit([1, 2, 3], deadline_s=0.05)
    out = srv.run()
    assert len(out) == 1 and out[0]["completion_reason"] == "timeout"
    # it was cancelled MID-decode: some tokens were produced, fewer than
    # the budget, and every block came back
    assert 0 < out[0]["n_generated"] < 40
    assert out[0]["retriable"] is False
    srv.pool.check_invariants()
    assert srv.pool.available() == srv.pool.usable_blocks
    assert srv.timeout_total == 1
    # the record rode the telemetry hook and the /metrics counter moved
    assert recs and recs[-1]["completion_reason"] == "timeout"
    rendered = srv.metrics.registry.render()
    assert "automodel_serve_requests_timeout_total 1" in rendered
    assert "automodel_serve_requests_failed_total 1" in rendered


def test_queue_wait_timeout_expires_queued_request():
    import time as _time

    model, params = _tiny_llama()
    auto = _auto(model, params)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=1, block_size=4, num_blocks=32, prefill_chunk=4, max_seq_len=32),
        GenerationConfig(max_new_tokens=4, greedy=True),
    )
    a = srv.submit([1, 2, 3])
    b = srv.submit([4, 5, 6], max_queue_wait_s=0.001)
    _time.sleep(0.01)
    done = {r["request_id"]: r for r in srv.run()}
    assert done[a]["completion_reason"] == "length"
    assert done[b]["completion_reason"] == "timeout"
    assert done[b]["n_generated"] == 0 and "ttft_s" not in done[b]
    srv.pool.check_invariants()


def test_limits_config_defaults_apply_to_every_request():
    """serving.limits.max_queue_wait_s applies without per-request args."""
    import time as _time

    from automodel_tpu.serving.engine import LimitsConfig

    model, params = _tiny_llama()
    auto = _auto(model, params)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=1, block_size=4, num_blocks=8, prefill_chunk=4,
                    max_seq_len=12, prefix_cache=False,
                    limits=LimitsConfig(max_queue_wait_s=0.001)),
        GenerationConfig(max_new_tokens=4, greedy=True),
    )
    # the pool only fits one request; the second must expire in queue
    a = srv.submit([1, 2, 3])
    out = srv.step()  # a admitted before its queue-wait bound elapses
    b = srv.submit([4, 5, 6])
    _time.sleep(0.01)
    done = {r["request_id"]: r for r in out + srv.run()}
    assert done[b]["completion_reason"] == "timeout"
    assert done[a]["completion_reason"] == "length"


def test_drain_rejects_queue_finishes_inflight_and_stamps_duration():
    from automodel_tpu.serving.engine import DrainConfig, EngineDraining

    model, params = _tiny_llama()
    auto = _auto(model, params)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=2, block_size=4, num_blocks=32, prefill_chunk=4,
                    max_seq_len=32, drain=DrainConfig(grace_s=30.0)),
        GenerationConfig(max_new_tokens=4, greedy=True),
    )
    a = srv.submit([1, 2, 3])
    b = srv.submit([4, 5])
    srv.step()  # a, b admitted
    c = srv.submit([6, 7])  # queued behind full slots
    srv.begin_drain()
    with pytest.raises(EngineDraining):
        srv.submit([9, 9])
    out = []
    for _ in range(200):
        out.extend(srv.step())
        if srv.drain_complete():
            break
    by = {r["request_id"]: r for r in out}
    assert by[c]["completion_reason"] == "draining" and by[c]["retriable"] is True
    assert by[a]["completion_reason"] == "length"
    assert by[b]["completion_reason"] == "length"
    assert srv.drain_duration_s is not None and srv.drain_duration_s >= 0
    srv.pool.check_invariants()
    assert srv.pool.available() == srv.pool.usable_blocks
    rendered = srv.metrics.registry.render()
    srv.metrics.sync(srv)
    rendered = srv.metrics.registry.render()
    assert "automodel_serve_draining 1" in rendered
    assert "automodel_serve_drain_duration_seconds" in rendered


def test_drain_grace_expiry_cancels_inflight():
    from automodel_tpu.serving.engine import DrainConfig

    model, params = _tiny_llama()
    auto = _auto(model, params)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=1, block_size=4, num_blocks=64, prefill_chunk=4,
                    max_seq_len=64, drain=DrainConfig(grace_s=0.0)),
        GenerationConfig(max_new_tokens=40, greedy=True),
    )
    a = srv.submit([1, 2, 3])
    srv.step()  # admitted, prefilling
    srv.begin_drain()
    out = []
    for _ in range(50):
        out.extend(srv.step())
        if srv.drain_complete():
            break
    assert [r["completion_reason"] for r in out] == ["cancelled"]
    assert out[0]["retriable"] is True
    srv.pool.check_invariants()
    assert srv.pool.available() == srv.pool.usable_blocks


def test_shed_accounting_record_and_counter():
    model, params = _tiny_llama()
    auto = _auto(model, params)
    recs = []
    srv = ServingEngine(
        auto,
        ServeConfig(slots=1, block_size=4, num_blocks=16, prefill_chunk=4,
                    max_seq_len=16, max_queue=1),
        GenerationConfig(max_new_tokens=4, greedy=True),
        on_record=recs.append,
    )
    srv.submit([1, 2])
    with pytest.raises(QueueFull):
        srv.submit([3, 4])
    # submit itself never records a shed (backpressure retries must not
    # inflate the counter) — the front calls record_shed when it gives up
    assert srv.shed_total == 0 and not recs
    rec = srv.record_shed(request_id="client-1", prompt_ids=[3, 4])
    assert rec["completion_reason"] == "shed" and rec["retriable"] is True
    assert srv.shed_total == 1
    assert recs[-1]["request_id"] == "client-1"
    assert "automodel_serve_requests_shed_total 1" in srv.metrics.registry.render()
    srv.run()


def test_block_leak_regression_exception_between_alloc_and_bind(monkeypatch):
    """Satellite: a planted exception between admit-time allocation and
    slot binding must free every block (invariants + free count restored)
    and fail only that request — loudly, with an engine_error record."""
    model, params = _tiny_llama()
    auto = _auto(model, params)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=2, block_size=4, num_blocks=32, prefill_chunk=4, max_seq_len=32),
        GenerationConfig(max_new_tokens=4, greedy=True),
    )
    free_before = srv.pool.available()
    monkeypatch.setattr(
        ServingEngine, "_bind_slot",
        lambda self, *a, **k: (_ for _ in ()).throw(RuntimeError("planted")),
    )
    bad = srv.submit([1, 2, 3])
    out = srv.step()
    monkeypatch.undo()
    assert [r["request_id"] for r in out] == [bad]
    assert out[0]["completion_reason"] == "engine_error"
    assert out[0]["retriable"] is True
    srv.pool.check_invariants()
    assert srv.pool.available() == free_before  # zero leaked blocks
    assert srv.error_total == 1
    # the engine still serves after the fault
    ok = srv.submit([4, 5, 6])
    done = {r["request_id"]: r for r in srv.run()}
    assert done[ok]["completion_reason"] == "length"


def test_block_pool_clear_prefix_cache():
    pool = BlockPool(num_blocks=8, block_size=2)
    tokens = [1, 2, 3, 4, 5]
    blocks = pool.allocate(3)
    pool.register_prefix(tokens, blocks)
    pool.free(blocks)  # parked in the LRU
    pool.clear_prefix_cache()
    pool.check_invariants()
    assert pool.available() == pool.usable_blocks
    assert pool.match_prefix(tokens) == ([], 0)
    # clearing while a registered block is still referenced: it loses the
    # hash mapping and frees normally later
    blocks2 = pool.allocate(3)
    pool.register_prefix(tokens, blocks2)
    pool.clear_prefix_cache()
    pool.check_invariants()
    pool.free(blocks2)
    pool.check_invariants()
    assert pool.available() == pool.usable_blocks


def test_drain_exit_code_policy(monkeypatch):
    from automodel_tpu.resilience import REQUEUE_EXIT_CODE
    from automodel_tpu.serving.engine import DrainConfig
    from automodel_tpu.serving.server import _drain_exit_code

    for k in ("SLURM_JOB_ID", "KUBERNETES_SERVICE_HOST"):
        monkeypatch.delenv(k, raising=False)
    assert _drain_exit_code(DrainConfig(requeue_exit="auto")) == 0
    assert _drain_exit_code(DrainConfig(requeue_exit="always")) == REQUEUE_EXIT_CODE
    monkeypatch.setenv("SLURM_JOB_ID", "1234")
    assert _drain_exit_code(DrainConfig(requeue_exit="auto")) == REQUEUE_EXIT_CODE
    assert _drain_exit_code(DrainConfig(requeue_exit="never")) == 0
    monkeypatch.delenv("SLURM_JOB_ID")
    monkeypatch.setenv("KUBERNETES_SERVICE_HOST", "10.0.0.1")
    assert _drain_exit_code(DrainConfig(requeue_exit="auto")) == REQUEUE_EXIT_CODE


def test_http_healthz_readyz_and_drain_503(monkeypatch, cpu_devices):
    """Satellite: /readyz false before the first compiled decode and while
    draining; /healthz reports scheduler liveness; draining POSTs get 503 +
    Retry-After."""
    import urllib.error
    import urllib.request

    monkeypatch.setattr(jax, "devices", lambda *a: cpu_devices[:1])
    from automodel_tpu.generation.engine import build_auto_from_cfg
    from automodel_tpu.serving.server import serve_http

    cfg = _tiny_serve_cfg()
    auto = build_auto_from_cfg(cfg)
    engine = ServingEngine(
        auto,
        ServeConfig.from_dict(dict(cfg.get("serving"))),
        GenerationConfig.from_dict(dict(cfg.get("generation"))),
    )
    server, loop = serve_http(engine, None, port=0)
    import threading

    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        port = server.server_address[1]

        def get(path):
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=30
                ) as resp:
                    return resp.status, json.loads(resp.read()), dict(resp.headers)
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read()), dict(e.headers)

        code, body, _ = get("/readyz")
        assert code == 503 and body["ready"] is False
        assert body["first_decode_done"] is False
        code, body, _ = get("/healthz")
        assert code == 200 and body["ok"] is True  # idle engine is healthy
        # one request compiles the decode → ready
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"prompt": "1 2 3", "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = json.loads(resp.read())
        assert out["completion_reason"] == "length"
        code, body, _ = get("/readyz")
        assert code == 200 and body["ready"] is True
        # drain: readyz flips false, new POSTs are 503 + Retry-After
        with loop.lock:
            engine.begin_drain()
        code, body, _ = get("/readyz")
        assert code == 503 and body["draining"] is True
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") is not None
        assert json.loads(ei.value.read())["reason"] == "draining"
        # stats surface the new counters
        code, stats, _ = get("/stats")
        assert stats["draining"] is True and "shed_total" in stats
    finally:
        server.shutdown()
        loop.close()


def test_report_summarizes_completion_reasons_and_engine_events(tmp_path):
    """Satellite: report --strict accepts the new serve keys and surfaces
    shed/timeout/stall counts in the summary."""
    from automodel_tpu.telemetry.report import lint_metrics_jsonl, summarize_metrics

    path = tmp_path / "m.jsonl"
    recs = [
        {"event": "serve_request", "request_id": "a", "n_generated": 4,
         "prompt_tokens": 3, "completion_reason": "length", "retriable": False,
         "ttft_s": 0.01, "decode_tps": 50.0, "queue_s": 0.001,
         "queue_depth": 0, "block_occupancy": 0.1, "ts": 1.0},
        {"event": "serve_request", "request_id": "b", "n_generated": 0,
         "prompt_tokens": 2, "completion_reason": "timeout", "retriable": False,
         "queue_s": 0.3, "queue_depth": 1, "ts": 2.0},
        {"event": "serve_request", "request_id": "c", "n_generated": 0,
         "prompt_tokens": 2, "completion_reason": "shed", "retriable": True,
         "queue_s": 0.0, "queue_depth": 9, "ts": 3.0},
        {"event": "serve_request", "request_id": "d", "n_generated": 2,
         "prompt_tokens": 2, "completion_reason": "engine_stall",
         "retriable": True, "queue_s": 0.0, "queue_depth": 0, "ts": 4.0},
        {"event": "serve_engine_event", "reason": "engine_stall", "step": 7,
         "requests_failed": 1, "ts": 4.0},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    records, problems = lint_metrics_jsonl(str(path))
    assert problems == []
    summary = summarize_metrics(records)
    assert summary["serve_requests"] == 4
    assert summary["serve_completion_reasons"] == {
        "engine_stall": 1, "length": 1, "shed": 1, "timeout": 1,
    }
    assert summary["serve_shed"] == 1
    assert summary["serve_timeouts"] == 1
    assert summary["serve_stalls"] == 1
    assert summary["serve_engine_events"][0]["reason"] == "engine_stall"


# -- hierarchical KV cache: host spill tier (ISSUE 16) ------------------------


def test_host_spill_tier_lru_budget_and_counters():
    """The tier's byte ledger: LRU eviction to fit the budget, oversize
    rejection, overwrite accounting, MRU-first advertisement — invariants
    audited after every mutation."""
    from automodel_tpu.serving.block_pool import HostSpillTier

    tier = HostSpillTier(max_bytes=256)
    assert tier.put(1, b"a" * 64, 64) and tier.put(2, b"b" * 64, 64)
    assert tier.bytes == 128 and len(tier) == 2
    tier.check_invariants()
    # a get refreshes recency: hash 1 moves to the MRU end
    assert tier.get(1) == b"a" * 64
    assert tier.chain_hashes() == [1, 2]  # MRU first
    # filling past the budget evicts the LRU entry (hash 2, not 1)
    assert tier.put(3, b"c" * 128, 128) and tier.put(4, b"d" * 64, 64)
    tier.check_invariants()
    assert 2 not in tier and 1 in tier
    assert tier.counters["spill_evicted"] == 1
    assert tier.get(2) is None  # miss: no counter, no error
    # oversize payload: rejected, counted, nothing else disturbed
    assert not tier.put(5, b"x" * 512, 512)
    assert tier.counters["spill_rejected"] == 1 and 5 not in tier
    tier.check_invariants()
    # overwrite replaces the old bytes in the ledger
    before = tier.bytes
    assert tier.put(1, b"A" * 32, 32)
    assert tier.bytes == before - 64 + 32
    tier.check_invariants()
    tier.clear()
    assert len(tier) == 0 and tier.bytes == 0
    tier.check_invariants()
    with pytest.raises(ValueError):
        HostSpillTier(max_bytes=0)


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_spill_reload_bit_identity_vs_recompute(dtype):
    """Tentpole acceptance: a prefix evicted to the host tier and reloaded
    at the next admission produces greedy output bit-identical to full
    recompute (spill-off engine), for raw and quantized pools, with the
    whole spill/reload flow visible in the counters."""
    from automodel_tpu.serving.engine import KVSpillConfig

    model, params = _tiny_llama()
    auto = _auto(model, params)

    def _mk(spill_on):
        return ServingEngine(
            auto,
            ServeConfig(
                slots=1, block_size=4, num_blocks=12, prefill_chunk=4,
                max_seq_len=64, kv_cache_dtype=dtype,
                kv_spill=KVSpillConfig(enabled=spill_on, max_host_mb=4.0),
            ),
            GenerationConfig(max_new_tokens=6, greedy=True),
        )

    prompt = list(range(1, 14))    # 3-block chain, parks 3 cached blocks
    big = list(range(20, 60))      # disjoint 40-token prompt: forces eviction

    eng = _mk(True)
    r1 = eng.submit(prompt, max_new_tokens=6)
    rec1 = {r["request_id"]: r for r in eng.run()}[r1]
    # churn: the big prompt needs every block — the parked prefix evicts
    # THROUGH the spill hook (rows copied host-side before overwrite)
    rb = eng.submit(big, max_new_tokens=2)
    assert {r["request_id"]: r for r in eng.run()}[rb][
        "completion_reason"
    ] in ("stop", "length")
    c = eng.pool.counters
    assert c["evictions"] > 0
    assert c["spilled_blocks"] == eng.pool.spill.counters["spill_puts"] > 0
    # re-serve: the prefix is gone from HBM but reloads from the host tier
    r2 = eng.submit(prompt, max_new_tokens=6)
    rec2 = {r["request_id"]: r for r in eng.run()}[r2]
    assert rec2["tokens"] == rec1["tokens"]
    assert c["spill_reloads"] == 1
    assert c["spill_reloaded_blocks"] == 3
    assert rec2["prefix_hit_tokens"] == 12  # reloads count as hit tokens
    eng.pool.check_invariants()
    assert eng.pool.available() == eng.pool.usable_blocks
    # ground truth: a spill-off engine recomputes everything
    off = _mk(False)
    ro = off.submit(prompt, max_new_tokens=6)
    reco = {r["request_id"]: r for r in off.run()}[ro]
    assert rec2["tokens"] == reco["tokens"]
    assert off.pool.spill is None
    assert off.pool.counters["spilled_blocks"] == 0


def test_spill_churn_randomized_invariants():
    """Randomized admit/finish/evict/reload schedule at the pool level
    with a live host tier: check_invariants() (pool + tier + cross-tier
    counter ledgers) passes after EVERY operation, and the drained pool
    returns to fully available. The reload bookkeeping mirrors the
    engine's contract: spilled_blocks bumps only on an accepted put,
    spill_reloads once per admission that moved >= 1 block."""
    from automodel_tpu.serving.block_pool import HostSpillTier, prompt_chain

    rng = random.Random(16)
    pool = BlockPool(num_blocks=16, block_size=4)
    pool.spill = HostSpillTier(max_bytes=40 * 64)

    def on_evict(evicted):
        for h, bid in evicted:
            if pool.spill.put(h, ("payload", h), 64):
                pool.counters["spilled_blocks"] += 1

    pool.on_evict = on_evict
    live: list[list[int]] = []
    reload_hits = 0
    for step in range(600):
        if live and (rng.random() < 0.45 or pool.available() < 5):
            pool.free(live.pop(rng.randrange(len(live))))
        else:
            # few distinct token streams -> recurring chains that cycle
            # resident -> evicted(spilled) -> reloaded
            tokens = [rng.randrange(3) for _ in range(rng.choice([5, 9, 13, 17]))]
            hits, hit_tokens = pool.match_prefix(tokens)
            chain = prompt_chain(tokens, 4)
            reloaded = 0
            for h in chain[len(hits):]:
                if pool.spill.get(h) is None:
                    break
                reloaded += 1
            need = -(-(len(tokens) + 1) // 4) - len(hits)
            fresh = pool.allocate(need)
            if fresh is None:
                if hits:
                    pool.free(hits)
            else:
                if reloaded:
                    reload_hits += reloaded
                    pool.counters["spill_reloads"] += 1
                    pool.counters["spill_reloaded_blocks"] += reloaded
                hit_tokens += reloaded * 4
                matchable = max(len(tokens) - 1, 0) // 4 * 4
                pool.note_prefix_tokens(
                    hit_tokens, max(matchable - hit_tokens, 0)
                )
                pool.register_prefix(tokens, hits + fresh)
                live.append(hits + fresh)
        pool.check_invariants()
    for blocks in live:
        pool.free(blocks)
    pool.check_invariants()
    assert pool.available() == pool.usable_blocks
    # the schedule actually exercised the hierarchy end to end
    assert pool.counters["evictions"] > 0
    assert pool.counters["spilled_blocks"] > 0
    assert reload_hits > 0 and pool.counters["spill_reloads"] > 0
    assert pool.counters["prefix_hit_tokens"] > 0
    assert pool.counters["prefix_miss_tokens"] > 0


def test_kv_spill_config_parse_validation_and_spec_exclusion():
    from automodel_tpu.serving.engine import KVSpillConfig, SpeculativeConfig

    cfg = ServeConfig.from_dict({
        "kv_spill": {"enabled": True, "max_host_mb": 64.0,
                     "peer_fetch": False, "fetch_timeout_s": 2.0},
    })
    assert cfg.kv_spill.enabled and cfg.kv_spill.max_host_mb == 64.0
    assert cfg.kv_spill.peer_fetch is False
    assert KVSpillConfig.from_dict(None) == KVSpillConfig()
    assert KVSpillConfig.from_dict(None).enabled is False
    with pytest.raises(TypeError, match="serving.kv_spill"):
        ServeConfig.from_dict({"kv_spill": {"max_host_mbb": 1}})
    with pytest.raises(ValueError, match="max_host_mb"):
        ServeConfig.from_dict({"kv_spill": {"max_host_mb": 0}})
    with pytest.raises(ValueError, match="fetch_timeout_s"):
        ServeConfig.from_dict({"kv_spill": {"fetch_timeout_s": -1}})
    # spill + speculative decoding are mutually exclusive at engine build
    # (the draft pool holds no prompt KV a reload could ever be bit-
    # identical to)
    model, params = _tiny_llama()
    draft = {
        "hf_config": {
            "architectures": ["LlamaForCausalLM"], "model_type": "llama",
            "vocab_size": 64, "hidden_size": 16, "intermediate_size": 32,
            "num_hidden_layers": 1, "num_attention_heads": 2,
            "num_key_value_heads": 1, "head_dim": 8,
            "max_position_embeddings": 128,
        },
        "backend": {"attn": "sdpa", "param_dtype": "float32",
                    "compute_dtype": "float32"},
    }
    with pytest.raises(ValueError, match="kv_spill"):
        ServingEngine(
            _auto(model, params),
            ServeConfig(
                slots=1, block_size=4, num_blocks=16, prefill_chunk=4,
                max_seq_len=32,
                kv_spill=KVSpillConfig(enabled=True),
                speculative=SpeculativeConfig(enabled=True, k=2, draft=draft),
            ),
            GenerationConfig(max_new_tokens=4, greedy=True),
        )
