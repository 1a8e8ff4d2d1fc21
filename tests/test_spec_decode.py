"""Serving raw-speed levers: speculative decoding exactness, the fused
Pallas paged-attention kernel (interpret-mode parity vs the gather path,
incl. int8 blocks), and int8 KV-cache pools.

The exactness contracts pinned here:

- **greedy spec parity** — a speculative engine (any draft, any accept
  rate) produces BIT-IDENTICAL greedy tokens to the non-speculative
  engine, for ragged batches across the cache-capable families;
- **fused == gather** — the paged kernel indexing the pool in place
  equals the gather → ``sdpa_decode`` view path, bf16/fp32 and int8;
- **int8 within its rounding of fp32** — along the full-precision greedy
  path the quantized pool's log-probabilities stay within a stated
  tolerance, and its greedy token is the full-precision one wherever the
  top two candidates are further apart than that;
- **rollback is leak-free** — ``BlockPool.check_invariants()`` holds
  after every engine step of a randomized accept/reject schedule,
  including rollbacks across a block boundary (``spec_k > block_size``).

All CPU-fast tier-1 except the qwen3_moe family build (slow-marked, like
its non-speculative parity sibling)."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.auto_model import AutoModel
from automodel_tpu.generation.engine import GenerationConfig, GenerationEngine
from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
from automodel_tpu.serving.engine import (
    ServeConfig,
    ServingEngine,
    SpeculativeConfig,
)

FP32 = BackendConfig(attn="sdpa", param_dtype="float32", compute_dtype="float32")


def _tiny_llama(seed=0, **over):
    from automodel_tpu.models.llama import LlamaForCausalLM

    kw = dict(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=3,
        num_heads=4, num_kv_heads=2, head_dim=8,
    )
    kw.update(over)
    model = LlamaForCausalLM(TransformerConfig(**kw), FP32)
    return model, model.init(jax.random.key(seed))


def _auto(model, params):
    return AutoModel(model=model, params=params, adapter=None, mesh_ctx=None)


def _draft_section(**over):
    """A model:-shaped draft section (smaller than the target, same vocab)."""
    hf = dict(
        architectures=["LlamaForCausalLM"], model_type="llama",
        vocab_size=64, hidden_size=16, intermediate_size=32,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
        head_dim=8, max_position_embeddings=128,
    )
    hf.update(over)
    return {
        "hf_config": hf,
        "backend": {
            "attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32",
        },
    }


def _serve(auto, *, max_new=6, spec_k=None, draft=None, **over):
    spec = (
        SpeculativeConfig(enabled=True, k=spec_k, draft=draft or _draft_section())
        if spec_k is not None
        else SpeculativeConfig()
    )
    return ServingEngine(
        auto,
        ServeConfig(
            slots=2, block_size=4, num_blocks=48, prefill_chunk=4,
            max_seq_len=48, speculative=spec, **over,
        ),
        GenerationConfig(max_new_tokens=max_new, greedy=True),
    )


def _greedy_refs(auto, prompts, max_new):
    eng = GenerationEngine(
        auto, GenerationConfig(max_new_tokens=max_new, greedy=True, pad_to_multiple=1)
    )
    return eng.generate_ids([list(p) for p in prompts])["tokens"]


def _run(srv, prompts):
    ids = [srv.submit(p) for p in prompts]
    done = {r["request_id"]: r for r in srv.run()}
    return [done[i] for i in ids]


# -- fused kernel parity (interpret mode) -------------------------------------


def _kernel_case(seed=0, B=3, N=4, Nkv=2, H=16, NB=12, BS=4, NBseq=5):
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.normal(size=(NB, BS, Nkv, H)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, BS, Nkv, H)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, NB, size=(B, NBseq)), jnp.int32)
    lengths = jnp.asarray([7, 13, 0], jnp.int32)
    return kp, vp, tables, lengths


# pages of [16, 2, 128] float32 are ones the kernel copies itself, 32 a grid
# step (512 positions): a table 69 wide is three groups, the last one of 5
_GROUP_BS, _GROUP_NBSEQ, _GROUP_POSITIONS = 16, 69, 512


def _grouped_case(seed, sq, Nkv=2, H=128, NB=48):
    """Slots whose lengths sit at the edges of the kernel's groups: one
    position short of a group, exactly one, one over; an empty slot whose
    table points at scratch block 0; query rows straddling the edge
    (508 + Sq - 1 >= 512); a context in the second group; the table full."""
    from automodel_tpu.ops import paged_attention as pa

    BS, NBseq = _GROUP_BS, _GROUP_NBSEQ
    per = pa.pages_per_step(BS, Nkv, H, sq * 2, 4) * BS
    assert per == _GROUP_POSITIONS and NBseq * BS % per  # groups, and a ragged last one
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.normal(size=(NB, BS, Nkv, H)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, BS, Nkv, H)), jnp.float32)
    lengths = [per - 1, per, per + 1, 0, per - 4, per + 200, NBseq * BS - sq]
    tables = rng.integers(1, NB, size=(len(lengths), NBseq))
    tables[3] = 0
    return kp, vp, jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32)


def _gather_ref(q, kp, vp, tables, lengths, window=None, cap=None):
    from automodel_tpu.ops.attention import sdpa_decode

    B, Sq = q.shape[:2]
    NB, BS, Nkv, H = kp.shape
    NBseq = tables.shape[1]
    Cv = NBseq * BS
    view_k = kp[tables].reshape(B, Cv, Nkv, H)
    view_v = vp[tables].reshape(B, Cv, Nkv, H)
    j = jnp.arange(Cv)
    q_abs = lengths[:, None] + jnp.arange(Sq)[None]
    mask = j[None, None, :] <= q_abs[:, :, None]
    if window is not None:
        mask = mask & (q_abs[:, :, None] - j[None, None, :] < window)
    return sdpa_decode(q, view_k, view_v, kv_mask=mask, logits_soft_cap=cap)


@pytest.mark.parametrize(
    "geometry,sq,window,cap",
    [("h16", sq, w, c) for sq in (1, 4) for w, c in [(None, None), (6, None), (None, 5.0)]]
    + [
        # the kernel's own groups of pages (``_grouped_case``): lengths at a
        # group's edges, a table the group does not divide, verify rows
        # across an edge, a window that opens in one group and closes in
        # the next (length 712: positions 413..712, the edge at 512), a cap
        ("groups", 1, None, None),
        ("groups", 5, None, None),
        ("groups", 1, 300, None),
        ("groups", 5, 300, 5.0),
        # heads of 64 packed two a lane row in a stacked pool, layer 1
        ("packed-layer1", 1, None, None),
        ("packed-layer1", 5, 300, None),
    ],
)
def test_paged_attend_kernel_parity_vs_gather(geometry, sq, window, cap):
    """The fused kernel == the gathered-view sdpa_decode path: decode
    (Sq=1) and verify-chunk (Sq>1) queries, causal per-query masks,
    sliding window, logit soft cap; one page a step (heads of 16) and
    groups of 32 pages the kernel copies itself (heads of 128)."""
    from automodel_tpu.generation import kv_cache
    from automodel_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(7)
    kw = dict(sliding_window=window, logits_soft_cap=cap, interpret=True)
    if geometry == "h16":
        kp, vp, tables, lengths = _kernel_case()
        q = jnp.asarray(rng.normal(size=(3, sq, 4, 16)), jnp.float32)
        out = pa.paged_attend(q, kp, vp, tables, lengths, **kw)
    elif geometry == "groups":
        kp, vp, tables, lengths = _grouped_case(1, sq)
        q = jnp.asarray(rng.normal(size=(len(lengths), sq, 4, 128)), jnp.float32)
        out = pa.paged_attend(q, kp, vp, tables, lengths, **kw)
    else:
        # 4 KV heads of 64 live as [.., 2, 128] rows in layer 1 of a stacked
        # pool; the reference attends the same pages unpacked
        kp, vp, tables, lengths = _grouped_case(2, sq)
        assert kv_cache.packed_heads(4, 64) == kp.shape[-2:]
        q = jnp.asarray(rng.normal(size=(len(lengths), sq, 8, 64)), jnp.float32)
        other = jnp.full_like(kp, jnp.nan)  # layer 0 must never be read
        out = pa.paged_attend(
            q, jnp.stack([other, kp]), jnp.stack([other, vp]), tables, lengths,
            layer=1, **kw,
        )
        kp, vp = kv_cache.unpack_heads(kp, 64), kv_cache.unpack_heads(vp, 64)
    ref = _gather_ref(q, kp, vp, tables, lengths, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("geometry", ["h16", "h128-group-edges"])
def test_paged_attend_kernel_parity_int8_blocks(geometry):
    """Int8 pool blocks: the kernel's in-kernel dequant == dequantize the
    whole pool then run the gather reference; quantize∘dequantize is
    idempotent (the chunk-prefill rewrite-the-view scatter must not
    drift). Its scales ``[NB, BS, Nkv]`` are not pages the kernel can copy
    out of HBM itself, so an int8 pool runs one page a step at any width:
    the same lengths as the grouped cases must come out the same."""
    from automodel_tpu.ops import paged_attention as pa

    if geometry == "h16":
        kp, vp, tables, lengths = _kernel_case(seed=3)
        shape = (3, 2, 4, 16)
    else:
        kp, vp, tables, lengths = _grouped_case(3, 2)
        shape = (len(lengths), 2, 4, 128)
        assert pa.pages_per_step(_GROUP_BS, 2, 128, 4, 1, quantized=True) == 1
    kq, ks = pa.quantize_kv_rows(kp)
    vq, vs = pa.quantize_kv_rows(vp)
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=shape), jnp.float32)
    out = pa.paged_attend(q, kq, vq, tables, lengths, ks, vs, interpret=True)
    kd = pa.dequantize_kv(kq, ks, jnp.float32)
    vd = pa.dequantize_kv(vq, vs, jnp.float32)
    ref = _gather_ref(q, kd, vd, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    kq2, ks2 = pa.quantize_kv_rows(pa.dequantize_kv(kq, ks, jnp.float32))
    assert bool((kq2 == kq).all()) and np.allclose(np.asarray(ks2), np.asarray(ks))


@pytest.mark.parametrize(
    "shape,pages",
    [
        # (block size, kv heads, head dim, Sq x rep, element bytes, int8 pool)
        ((16, 8, 128, 6, 2, False), 32),  # MiniMax-M2's pool, one layer
        ((16, 4, 128, 8, 2, False), 32),  # LFM2's, heads of 64 packed in twos
        ((16, 8, 128, 5 * 6, 2, False), 32),  # a verify chunk of 5
        ((32, 8, 128, 6, 2, False), 16),  # the same positions a step
        ((16, 16, 128, 4, 2, False), 16),  # halved until the step fits VMEM
        ((16, 8, 128, 6, 1, True), 1),  # int8: scales are no page to copy
        ((16, 8, 64, 6, 2, False), 1),  # heads of 64 unpacked: half a lane row
        ((16, 1, 128, 8, 2, False), 1),  # one bfloat16 head: half a sublane
        ((16, 64, 128, 1, 4, False), 4),  # and halved again
    ],
)
def test_pages_per_step_from_shapes(shape, pages):
    """The pages a grid step is derived from what a call can see, and the
    step it gives fits the budget the sweep filter holds it to."""
    from automodel_tpu.ops import paged_attention as pa

    bs, nkv, h, sr, itemsize, quantized = shape
    assert pa.pages_per_step(bs, nkv, h, sr, itemsize, quantized) == pages
    assert pa._paged_budget_ok(bs, nkv, h, 1, sr, itemsize, quantized)
    assert pa._step_bytes(pages, bs, nkv, h, sr, itemsize, quantized) <= pa._VMEM_BUDGET


def test_grid_steps_match_the_kernels_live_pages():
    """The host's count of live grid steps is the kernel's own arithmetic:
    a group is live iff one of its pages holds a position that the
    slot's query rows attend."""
    from automodel_tpu.ops import paged_attention as pa

    BS, NBseq, P = 16, 37, 16
    for sq, window in [(1, None), (5, None), (1, 200), (5, 40)]:
        lengths = np.array([0, 1, 15, 16, 255, 256, 257, 252, 400, 592 - sq])  # 256 a group
        grid, live = pa.grid_steps(
            lengths, NBseq, pages=P, block_size=BS, sq=sq, window=window
        )
        assert grid == len(lengths) * 3
        want = 0
        for n in lengths:
            # positions some query row attends: row qi sits at n + qi
            seen = np.zeros(NBseq * BS, bool)
            for qi in range(sq):
                a = 0 if window is None else max(0, n + qi - window + 1)
                seen[a : n + qi + 1] = True
            want += sum(seen[g * P * BS : (g + 1) * P * BS].any() for g in range(3))
        assert live == want, (sq, window, live, want)


def test_fused_engine_greedy_parity(monkeypatch):
    """End-to-end: the serving engine on the fused kernel (interpret mode)
    decodes the same greedy tokens as the gather engine and the
    single-wave reference."""
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    model, params = _tiny_llama()
    auto = _auto(model, params)
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17]]
    refs = _greedy_refs(auto, prompts, 6)
    srv = _serve(auto, decode_kernel="fused")
    assert srv.decode_backend == "fused"
    recs = _run(srv, prompts)
    assert [r["tokens"] for r in recs] == refs
    srv.pool.check_invariants()
    assert srv.pool.available() == srv.pool.usable_blocks


def test_fused_engine_greedy_parity_sliding_window(monkeypatch):
    """Windowed model on the fused kernel: the kernel's in-kernel window
    mask == the per-layer tag-mask gather path."""
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    model, params = _tiny_llama(sliding_window=4, num_layers=2)
    auto = _auto(model, params)
    prompts = [[1, 2, 3, 4, 5, 6], [7, 8]]
    gather = _run(_serve(auto, max_new=8, decode_kernel="gather"), prompts)
    fused = _run(_serve(auto, max_new=8, decode_kernel="fused"), prompts)
    assert [r["tokens"] for r in fused] == [r["tokens"] for r in gather]


# -- int8 KV-cache pool -------------------------------------------------------


# What an int8 K/V row can promise: each value within 1/254 of its row's
# largest, which three layers of attention turn into a log-probability within
# 0.016 of the full-precision one on this model (read over the 13 positions
# compared below; the full-precision pool reads 0.000000 on all 18).
INT8_LOGPROB_TOL = 0.03


def test_int8_pool_greedy_tokens_match_fp32():
    """Along the full-precision greedy path the quantized pool's
    log-probability of its pick is within ``INT8_LOGPROB_TOL`` of the full
    forward's, and its pick IS the full-precision token wherever that one
    leads the runner-up by more than twice the tolerance. Token equality
    everywhere, which this test asked until PR 46, is not a claim a quantized
    pool can make: two of these three requests meet a position whose top two
    are 0.013 and 0.011 apart, inside int8's rounding, and from there on the
    two engines continue different sequences."""
    model, params = _tiny_llama(seed=1)
    auto = _auto(model, params)
    prompts = [[1, 2, 3, 4, 5], [9, 10, 11], [20, 21, 22, 23, 24, 25]]
    refs = _greedy_refs(auto, prompts, 6)
    srv = _serve(auto, kv_cache_dtype="int8", decode_kernel="gather")
    ids = [srv.submit(p, return_logprobs=True) for p in prompts]
    done = {r["request_id"]: r for r in srv.run()}
    forward = jax.jit(lambda ids: model(params, ids))
    compared = decided = 0
    for prompt, want, i in zip(prompts, refs, ids):
        got, got_logp = done[i]["tokens"], done[i]["logprobs"]
        # full precision's log-probabilities at every position of ITS path
        logits = forward(jnp.asarray([list(prompt) + list(want)]))
        logits = logits[0] if isinstance(logits, tuple) else logits
        logp = np.asarray(jax.nn.log_softmax(logits[0], axis=-1))[len(prompt) - 1:-1]
        for t, row in enumerate(logp):
            # the two engines have read the same tokens so far
            assert abs(got_logp[t] - row[got[t]]) < INT8_LOGPROB_TOL, (prompt, t)
            compared += 1
            first, second = np.sort(row)[::-1][:2]
            if first - second > 2 * INT8_LOGPROB_TOL:
                decided += 1
                assert got[t] == want[t], (prompt, t, first - second)
            if got[t] != want[t]:
                break  # a near-tie went the other way: other sequences from here
    # the claim is no empty one: most positions are compared, and more than
    # half of all 18 are decided by a margin the tolerance cannot cross
    assert compared >= 12 and 2 * decided > sum(len(r) for r in refs), (compared, decided)


def test_int8_pool_fused_matches_gather(monkeypatch):
    """int8 × fused: quantize-on-write in the paged scatter + in-kernel
    dequant == the dequantized-gather path, token for token."""
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    model, params = _tiny_llama(seed=2)
    auto = _auto(model, params)
    prompts = [[5, 6, 7, 8], [30, 31]]
    gather = _run(_serve(auto, kv_cache_dtype="int8", decode_kernel="gather"), prompts)
    fused = _run(_serve(auto, kv_cache_dtype="int8", decode_kernel="fused"), prompts)
    assert [r["tokens"] for r in fused] == [r["tokens"] for r in gather]


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_int8_pool_of_narrow_heads_keeps_a_scale_a_head(monkeypatch, kernel):
    """Heads of 64 are lane-packed two a row in a full-precision pool
    (kv_cache.packed_heads); an int8 pool is left unpacked, so its scales
    stay one a (token row, kv head), and decodes the full-precision pool's
    greedy tokens through either backend."""
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    model, params = _tiny_llama(seed=3, hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=64)
    auto = _auto(model, params)
    prompts = [[5, 6, 7, 8, 9], [30, 31]]
    full = _serve(auto, decode_kernel=kernel)
    int8 = _serve(auto, kv_cache_dtype="int8", decode_kernel=kernel)
    assert full._pool.values_shape[3:] == (1, 128)
    assert int8._pool.values_shape[3:] == (2, 64)
    assert int8._pool.k[1].shape == int8._pool.values_shape[:4]
    assert [r["tokens"] for r in _run(int8, prompts)] == [
        r["tokens"] for r in _run(full, prompts)
    ] == _greedy_refs(auto, prompts, 6)


def test_int8_pool_halves_kv_bytes():
    """The capacity claim behind kv_cache_dtype: the int8 pool's value
    arrays are half the bf16-equivalent bytes (scale overhead is 1/(2H)
    here), so the same HBM budget holds ~2x the blocks."""
    model, params = _tiny_llama()
    bf16 = _serve(_auto(model, params))
    int8 = _serve(_auto(model, params), kv_cache_dtype="int8")
    # fp32 backend here: values shrink 4x; the general claim is
    # values_bytes(int8) == values_bytes(dtype)/itemsize
    assert int8.pool_bytes < bf16.pool_bytes / 2
    assert int8._pool.quantized and not bf16._pool.quantized


# -- speculative decoding -----------------------------------------------------


def test_spec_greedy_parity_llama_ragged():
    """Greedy spec parity, ragged llama batch, an uncorrelated random
    draft (low accept rate): committed tokens are bit-identical to the
    non-speculative engine — the rejection rule's exactness guarantee."""
    model, params = _tiny_llama()
    auto = _auto(model, params)
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17], [3, 1]]
    refs = _greedy_refs(auto, prompts, 6)
    srv = _serve(auto, spec_k=3)
    recs = _run(srv, prompts)
    assert [r["tokens"] for r in recs] == refs
    assert srv.spec_proposed_total > 0
    srv.pool.check_invariants()
    assert srv.pool.available() == srv.pool.usable_blocks


def test_spec_greedy_parity_gpt2():
    from automodel_tpu.models.gpt2.model import GPT2Config, GPT2ForCausalLM

    gpt2 = GPT2ForCausalLM(
        GPT2Config(vocab_size=96, n_positions=64, hidden_size=32, num_layers=2, num_heads=4),
        FP32,
    )
    auto = _auto(gpt2, gpt2.init(jax.random.key(1)))
    prompts = [[3, 4, 5, 6], [10, 11]]
    refs = _greedy_refs(auto, prompts, 5)
    draft = _draft_section()
    draft["hf_config"]["vocab_size"] = 96
    recs = _run(_serve(auto, max_new=5, spec_k=3, draft=draft), prompts)
    assert [r["tokens"] for r in recs] == refs


def test_qwen3_moe_mixed_stack_int8_fused_spec(monkeypatch):
    """The mixed dense/MoE stack slices its cache sides by LAYER RANGES
    (dense prefix scan + MoE scan + concat) — with an int8 pool those
    sides are (values, scales) tuples, which raw tuple slicing would
    mis-split. Pin the tiniest qwen3_moe through all three levers at once
    against its own fp32 non-speculative output."""
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    from automodel_tpu.models.qwen3_moe import MoEForCausalLM, MoETransformerConfig

    hf = {
        "architectures": ["Qwen3MoeForCausalLM"], "model_type": "qwen3_moe",
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
        "moe_intermediate_size": 16, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "num_experts": 4, "num_experts_per_tok": 2,
        "max_position_embeddings": 128, "tie_word_embeddings": False,
        "first_k_dense_replace": 1,  # 1 dense + 1 MoE: both scan ranges live
    }
    moe = MoEForCausalLM(
        MoETransformerConfig.from_hf(hf),
        BackendConfig(
            attn="sdpa", experts="dense",
            param_dtype="float32", compute_dtype="float32",
        ),
    )
    auto = _auto(moe, moe.init(jax.random.key(2)))
    prompts = [[7, 8, 9, 10], [20, 21]]
    base = _run(_serve(auto, max_new=4), prompts)
    spec = _run(
        _serve(
            auto, max_new=4, spec_k=3,
            kv_cache_dtype="int8", decode_kernel="fused",
        ),
        prompts,
    )
    assert [r["tokens"] for r in spec] == [r["tokens"] for r in base]


@pytest.mark.slow
def test_spec_greedy_parity_qwen3_moe():
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
    auto = _auto(moe, moe.init(jax.random.key(2)))
    prompts = [[7, 8, 9, 10], [20, 21, 22]]
    refs = _greedy_refs(auto, prompts, 5)
    draft = _draft_section()
    draft["hf_config"]["vocab_size"] = 128
    recs = _run(_serve(auto, max_new=5, spec_k=3, draft=draft), prompts)
    assert [r["tokens"] for r in recs] == refs


def test_spec_parity_fused_int8_compound(monkeypatch):
    """All three levers at once — speculative decoding over an int8 pool
    through the fused kernel — still bit-identical greedy tokens."""
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    model, params = _tiny_llama()
    auto = _auto(model, params)
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]
    # reference: the same int8 pool WITHOUT speculation (quantization
    # shifts logits slightly, so the exactness contract is spec-vs-nonspec
    # at equal pool precision; int8-vs-fp32 equality is pinned separately)
    base = _run(
        _serve(auto, kv_cache_dtype="int8", decode_kernel="fused"), prompts
    )
    spec = _run(
        _serve(auto, spec_k=3, kv_cache_dtype="int8", decode_kernel="fused"),
        prompts,
    )
    assert [r["tokens"] for r in spec] == [r["tokens"] for r in base]


def test_spec_self_draft_accepts_everything_and_stamps_records():
    """A draft with the TARGET's own weights agrees everywhere: accept
    rate 1.0, per-request records carry spec_accepted/spec_accept_rate,
    /metrics exposes the counters + gauge."""
    from serving_driver import drive

    model, params = _tiny_llama(num_layers=2)
    auto = _auto(model, params)
    draft = _draft_section(
        hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    )
    srv = _serve(auto, max_new=9, spec_k=3, draft=draft)
    srv.draft_auto.params = params  # self-draft: identical proposals
    arrivals = [(0.0, [1, 2, 3, 4, 5], 9), (0.0, [7, 8, 9], 9)]
    done = drive(srv, arrivals)
    assert srv.spec_accept_rate == 1.0
    assert srv.spec_proposed_total == srv.spec_accepted_total > 0
    # rounds count propose+verify WAVES, not slot-rounds: with two slots
    # decoding concurrently, rounds must sit strictly below proposed / k
    assert 0 < srv.spec_rounds < srv.spec_proposed_total // 3
    for rec in done:
        assert rec["spec_accept_rate"] == 1.0
        assert rec["spec_accepted"] == rec["spec_proposed"]
    srv.metrics.sync(srv)
    rendered = srv.metrics.registry.render()
    assert "automodel_serve_spec_accepted_total" in rendered
    assert "automodel_serve_spec_rejected_total 0" in rendered
    assert "automodel_serve_spec_accept_rate 1\n" in rendered


def test_spec_eos_inside_accepted_block_terminates_exactly():
    """A stop token committed mid-round (inside the accepted prefix)
    truncates the completion exactly where the non-speculative engine
    stops — never decodes past eos."""
    model, params = _tiny_llama()
    auto = _auto(model, params)
    prompts = [[1, 2, 3, 4, 5]]
    ref = _greedy_refs(auto, prompts, 8)[0]
    eos = ref[2]  # force a stop mid-stream
    gen = GenerationConfig(max_new_tokens=8, greedy=True, eos_token_id=int(eos))
    draft = _draft_section(
        hidden_size=32, intermediate_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    )
    spec = SpeculativeConfig(enabled=True, k=4, draft=draft)
    srv = ServingEngine(
        auto,
        ServeConfig(slots=2, block_size=4, num_blocks=48, prefill_chunk=4,
                    max_seq_len=48, speculative=spec),
        gen,
    )
    srv.draft_auto.params = params  # all-accept → eos lands inside a block
    rec = _run(srv, prompts)[0]
    assert rec["completion_reason"] == "stop"
    assert rec["tokens"] == ref[: ref.index(eos) + 1]
    srv.pool.check_invariants()
    assert srv.pool.available() == srv.pool.usable_blocks


def test_spec_rollback_invariants_randomized_schedule():
    """A noisy-copy draft produces a genuinely mixed accept/reject
    schedule; with ``spec_k > block_size`` every rejection rolls back
    across a block boundary. BlockPool invariants audited after EVERY
    engine step, parity still exact, pool drains to fully available."""
    model, params = _tiny_llama(num_layers=2)
    auto = _auto(model, params)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, 64, size=int(n)).tolist()
        for n in rng.integers(2, 9, size=6)
    ]
    refs = _greedy_refs(auto, prompts, 7)
    draft = _draft_section(
        hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    )
    srv = _serve(auto, max_new=7, spec_k=6, draft=draft)  # k=6 > block_size=4
    noisy = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.key(9), x.shape, x.dtype),
        params,
    )
    srv.draft_auto.params = noisy  # agrees often, not always
    ids = [srv.submit(p) for p in prompts]
    done = {}
    for _ in range(10_000):
        if srv.idle():
            break
        for rec in srv.step():
            done[rec["request_id"]] = rec
        srv.pool.check_invariants()  # after every rollback
    assert [done[i]["tokens"] for i in ids] == refs
    accepted, proposed = srv.spec_accepted_total, srv.spec_proposed_total
    assert 0 < accepted < proposed, (
        f"schedule not mixed: {accepted}/{proposed} — tune the noise"
    )
    assert srv.pool.available() == srv.pool.usable_blocks


def test_spec_config_validation_draft_mismatch():
    """Loud refusals: missing draft, vocab mismatch, cache-less draft."""
    model, params = _tiny_llama()
    auto = _auto(model, params)
    with pytest.raises(ValueError, match="draft"):
        SpeculativeConfig(enabled=True)
    bad_vocab = _draft_section(vocab_size=32)
    with pytest.raises(ValueError, match="vocab"):
        _serve(auto, spec_k=2, draft=bad_vocab)


@pytest.mark.parametrize(
    "decode_kernel,interpret,env,want",
    [
        ("fused", False, None, "fused"),
        ("gather", True, None, "gather"),
        ("auto", False, None, "gather"),  # the CPU's only decode path
        ("auto", True, None, "fused"),  # the kernel can run
        ("auto", True, "gather", "fused"),  # no environment switch
    ],
)
def test_decode_backend_resolution(monkeypatch, decode_kernel, interpret, env, want):
    """serving.decode_kernel when it names a backend, else the platform's
    default; nothing in the environment chooses."""
    model, params = _tiny_llama()
    auto = _auto(model, params)
    monkeypatch.delenv("AUTOMODEL_FLASH_INTERPRET", raising=False)
    monkeypatch.delenv("AUTOMODEL_PAGED_DECODE", raising=False)
    if interpret:
        monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    if env:
        monkeypatch.setenv("AUTOMODEL_PAGED_DECODE", env)
    assert _serve(auto, decode_kernel=decode_kernel).decode_backend == want


def test_fused_decode_refuses_kv_heads_the_mesh_does_not_divide(devices8):
    """The paged kernel runs per TP shard on whole KV heads. A mesh that
    does not divide them is refused when the engine is BUILT: at the first
    decode step the error would be caught by step(), the wave failed, the
    pool rebuilt — and the stdin front would still exit 0."""
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh

    model, params = _tiny_llama()  # 2 KV heads
    ctx = build_mesh(MeshConfig(dp_shard=1, tp=4), devices=devices8[:4])
    auto = AutoModel(model=model, params=params, adapter=None, mesh_ctx=ctx)
    with pytest.raises(ValueError, match="decode_kernel: gather"):
        _serve(auto, decode_kernel="fused")
    assert _serve(auto, decode_kernel="gather").decode_backend == "gather"


# -- CLI wiring ---------------------------------------------------------------


def test_serve_cli_spec_example_yaml_e2e(tmp_path, capsys, monkeypatch, cpu_devices):
    """The committed serve_tiny_cpu_spec.yaml drives the stdin CLI end to
    end: speculative engine, int8 pool, per-request spec keys on the
    metrics JSONL, report --strict clean."""
    import io
    from pathlib import Path

    monkeypatch.setattr(jax, "devices", lambda *a: cpu_devices[:1])
    from automodel_tpu.config.loader import load_yaml_config

    yaml_path = (
        Path(__file__).resolve().parent.parent
        / "examples" / "generation" / "serve_tiny_cpu_spec.yaml"
    )
    cfg = load_yaml_config(yaml_path)
    cfg = type(cfg)(
        {**cfg.to_dict(), "logging": {"metrics_path": str(tmp_path / "m.jsonl")}}
    )
    monkeypatch.setattr(
        "sys.stdin",
        io.StringIO(
            json.dumps({"id": "a", "prompt": "1 2 3"}) + "\n"
            + json.dumps({"id": "b", "prompt_ids": [7, 8], "max_new_tokens": 4}) + "\n"
        ),
    )
    from automodel_tpu.serving.server import main

    rc = main(cfg)
    assert rc == 0
    out_lines = [
        json.loads(l) for l in capsys.readouterr().out.splitlines()
        if l.startswith("{")
    ]
    by_id = {r["request_id"]: r for r in out_lines}
    assert set(by_id) == {"a", "b"}
    assert by_id["b"]["n_generated"] == 4
    assert "spec_accept_rate" in by_id["a"]
    from automodel_tpu.telemetry.report import lint_metrics_jsonl, summarize_metrics

    records, problems = lint_metrics_jsonl(str(tmp_path / "m.jsonl"))
    assert problems == []
    summary = summarize_metrics(records)
    assert summary["serve_requests"] == 2
    assert "serve_accept_rate" in summary
