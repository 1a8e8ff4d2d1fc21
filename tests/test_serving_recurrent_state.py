"""Two kinds of per-sequence state in one serving engine: paged K/V for the
attention layers, a slot-indexed conv state for the short-conv layers
(models/lfm2_moe through serving/paged.py and serving/engine.py).

Everything is held against the benchmark's plain reference
(benchmarks/reference/lfm2_moe.py): ONE causal float32 forward over a prompt
with its served tokens, no cache and no state. Logits are compared, not
tokens: with random weights the largest logit changes on rounding."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.auto_model import AutoModel
from automodel_tpu.generation import kv_cache
from automodel_tpu.generation.engine import (
    GenerationConfig,
    GenerationEngine,
    GenerationUnsupported,
)
from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeForCausalLM
from automodel_tpu.serving import paged
from automodel_tpu.serving.engine import ServeConfig, ServingEngine
from benchmarks.harness import loader, program_trace

ROOT = Path(__file__).resolve().parents[1]
VOCAB, CHUNK, BS = 96, 8, 4
# float32 on both sides: what is left is the order of the sums
TOL = 3e-4


def _hf(head_dim: int) -> dict:
    return {
        "architectures": ["Lfm2MoeForCausalLM"], "model_type": "lfm2_moe",
        "vocab_size": VOCAB, "hidden_size": 32, "intermediate_size": 64,
        "moe_intermediate_size": 16, "num_hidden_layers": 5,
        "layer_types": ["conv", "full_attention", "conv", "conv", "full_attention"],
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": head_dim,
        "num_dense_layers": 1, "num_experts": 4, "num_experts_per_tok": 2,
        "norm_eps": 1e-5, "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1, "rope_theta": 1000000, "conv_L_cache": 3,
        "conv_bias": False, "max_position_embeddings": 512,
    }


@pytest.fixture(scope="module")
def R():
    return loader.load_module("reference", "lfm2_moe")


def _auto(head_dim: int = 8) -> tuple:
    hf = _hf(head_dim)
    model = Lfm2MoeForCausalLM(
        Lfm2MoeConfig.from_hf(hf),
        BackendConfig(attn="sdpa", experts="ragged", param_dtype="float32",
                      compute_dtype="float32"),
    )
    params = model.init(jax.random.key(0))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    params = jax.tree.unflatten(
        treedef, [a + 0.05 * jax.random.normal(k, a.shape, a.dtype) for a, k in zip(leaves, keys)])
    return hf, AutoModel(model=model, params=params, adapter=None, mesh_ctx=None)


def _reference_rows(R, hf, params, seq, start):
    """Reference logits of rows ``start`` .. of ONE causal forward over ``seq``."""
    ids = jnp.asarray(seq, jnp.int32)
    return np.asarray(R.rows_logits(R.to_reference(params), ids, jnp.int32(start),
                                    R.spec(hf, {}), "f32", len(seq) - start))


def _prompt(n: int, seed: int) -> list:
    return np.random.default_rng(seed).integers(3, VOCAB, size=n).tolist()


def _serve(**kw) -> ServeConfig:
    base = dict(slots=3, block_size=BS, num_blocks=96, prefill_chunk=CHUNK, max_seq_len=64,
                prefix_cache=False, decode_kernel="gather")
    return ServeConfig.from_dict({**base, **kw})


def _engine(auto, **kw) -> ServingEngine:
    return ServingEngine(auto, _serve(**kw), GenerationConfig(max_new_tokens=8, greedy=True))


# -- the two programs, by hand: logits of every step ------------------------------
@pytest.mark.parametrize("backend,head_dim", [("gather", 8), ("fused", 8), ("fused", 64)])
def test_chunks_then_decode_match_the_full_forward(R, backend, head_dim):
    """Sequence B (2.5 chunks, the last one padded) prefills chunk by chunk
    in slot 1 while sequence A, prefilled before it, decodes in slot 0: a
    decode step runs between B's chunks with slot 1 INACTIVE. Then both
    decode. Every logit row of both equals the reference's full forward, so
    the conv state was carried across chunks, cut at the chunk's last REAL
    position, and left alone by the decode steps in between. head_dim 64 is
    the lane-packed pool (two KV heads a 128-lane row)."""
    hf, auto = _auto(head_dim)
    model, params = auto.model, auto.params
    apply = lambda p, ids, **kw: model(p, ids, **kw)
    chunk = paged.build_chunk_prefill_fn(apply, CHUNK, jnp.float32)
    forward = jax.jit(paged._make_forward(apply, backend, BS, jnp.float32, interpret=True))
    slots, nbseq = 3, 16
    pool = paged.layout_pool(model.cache_layout(), slots, 64, BS, dtype=jnp.float32)
    assert pool.values_shape[0] == 2 and pool.state.shape == (3, slots, 2, 32)
    assert pool.values_shape[3:] == ((1, 128) if head_dim == 64 else (2, head_dim))
    tables = np.zeros((slots, nbseq), np.int32)
    tables[0, :8], tables[1, :12] = np.arange(1, 9), np.arange(9, 21)
    lengths = np.zeros((slots,), np.int32)
    cur = np.zeros((slots,), np.int32)
    active = np.zeros((slots,), bool)
    seqs = {0: _prompt(5, 11), 1: _prompt(20, 12)}
    rows = {0: [], 1: []}  # logits of the positions each sequence's tokens came from

    def prefill_chunk(slot, start):
        nonlocal pool
        prompt = seqs[slot]
        real = min(CHUNK, len(prompt) - start)
        ids = np.zeros((CHUNK,), np.int32)
        ids[:real] = prompt[start:start + real]
        last, pool = chunk(params, pool, jnp.asarray(tables[slot]), jnp.asarray(ids),
                           jnp.int32(start), jnp.int32(real), jnp.int32(slot))
        lengths[slot] = start + real
        if start + real == len(prompt):
            rows[slot].append(np.asarray(last))
            cur[slot], active[slot] = int(np.argmax(last)), True
            seqs[slot] = prompt + [int(cur[slot])]

    def decode_step():
        nonlocal pool
        logits, pool, _ = forward(params, pool, jnp.asarray(tables), jnp.asarray(lengths),
                                  jnp.asarray(cur)[:, None], jnp.asarray(active))
        for b in np.flatnonzero(active):
            rows[b].append(np.asarray(logits[b, 0]))
            lengths[b] += 1
            cur[b] = int(np.argmax(logits[b, 0]))
            seqs[b] = seqs[b] + [int(cur[b])]

    prefill_chunk(0, 0)
    for start in (0, 8, 16):  # B's chunks, a decode step of A after each
        prefill_chunk(1, start)
        before = np.asarray(pool.state[:, 2])  # the free slot's rows
        mid = np.asarray(pool.state[:, 1])
        decode_step()
        if start < 16:  # B is mid-prefill: inactive in that decode step
            np.testing.assert_array_equal(np.asarray(pool.state[:, 1]), mid)
        np.testing.assert_array_equal(np.asarray(pool.state[:, 2]), before)
    for _ in range(7):
        decode_step()
    for b, prompt_len in ((0, 5), (1, 20)):
        served = seqs[b][:-1]  # the last token was never fed
        want = _reference_rows(R, hf, params, served, prompt_len - 1)
        got = np.stack(rows[b])
        assert got.shape == want.shape and len(got) >= 9
        assert float(np.max(np.abs(got - want))) < TOL, b


# -- through the engine ------------------------------------------------------------
def _check_record(R, hf, params, prompt, rec):
    """The record's tokens are the reference's greedy continuation and each
    token's log-probability the reference's, position for position."""
    toks = rec["tokens"]
    want = _reference_rows(R, hf, params, prompt + toks[:-1], len(prompt) - 1)
    logp = np.asarray(jax.nn.log_softmax(jnp.asarray(want), axis=-1))
    assert np.argmax(want, axis=-1).tolist() == toks
    got = np.asarray(rec["logprobs"], np.float64)
    assert float(np.max(np.abs(got - logp[np.arange(len(toks)), toks]))) < TOL


def test_two_prompts_prefill_in_turn_while_a_third_decodes(R):
    hf, auto = _auto()
    eng = _engine(auto)
    prompts = {"a": _prompt(5, 1), "b": _prompt(20, 2), "c": _prompt(19, 3)}
    done = {}
    eng.submit(prompts["a"], request_id="a", max_new_tokens=14, return_logprobs=True)
    while eng.busy_slots == 0 or not eng._active.any():
        for rec in eng.step():
            done[rec["request_id"]] = rec
    for rid in ("b", "c"):
        eng.submit(prompts[rid], request_id=rid, max_new_tokens=8, return_logprobs=True)
    saw_both_prefilling_beside_a_decode = False
    while not eng.idle():
        for rec in eng.step():
            done[rec["request_id"]] = rec
        prefilling = [s for s in eng._slots if s is not None and not s.decoding]
        saw_both_prefilling_beside_a_decode |= len(prefilling) == 2 and bool(eng._active.any())
        assert eng.state_slots == sum(s is not None and s.prefill_pos > 0 for s in eng._slots)
    assert saw_both_prefilling_beside_a_decode
    assert sorted(done) == ["a", "b", "c"]
    for rid, rec in done.items():
        assert rec["completion_reason"] == "length"
        _check_record(R, hf, auto.params, prompts[rid], rec)
    assert eng.state_slots == 0  # the state went with the slots


def test_a_reused_slot_holds_nothing_of_its_previous_tenant(R):
    hf, auto = _auto()
    eng = _engine(auto, slots=1)
    first, second = _prompt(21, 7), _prompt(13, 8)
    eng.submit(first, request_id="x", max_new_tokens=6, return_logprobs=True)
    (x,) = eng.run()
    state_after_x = np.asarray(eng._pool.state)
    assert np.abs(state_after_x[:, 0]).max() > 0  # the row is dirty when Y arrives
    resets_before = eng.loop_account()["n"]["state_resets"]
    eng.submit(second, request_id="y", max_new_tokens=6, return_logprobs=True)
    recs = []
    while not eng.idle():
        recs += eng.step()
    # Y's first chunk, and only that one
    assert eng.loop_account()["n"]["state_resets"] - resets_before == 1
    _check_record(R, hf, auto.params, first, x)
    _check_record(R, hf, auto.params, second, recs[0])


def test_kv_is_allocated_for_the_kv_layers_only():
    _, auto = _auto()
    eng = _engine(auto)
    layout = auto.model.cache_layout()
    assert [c.kind for c in layout] == ["conv", "kv", "conv", "conv", "kv"]
    assert eng._pool.values_shape == (2, 96, BS, 2, 8)  # 2 K/V layers, not 5
    assert eng._pool.state.shape == (3, 3, 2, 32)  # 3 conv layers x 3 slots x (taps - 1) x D
    assert eng.pool_bytes == 2 * 2 * 96 * BS * 2 * 8 * 4 + 3 * 3 * 2 * 32 * 4
    assert kv_cache.recurrent_kinds(layout) == ["conv"]


def test_a_kv_only_family_states_its_layout_and_gets_no_state():
    from automodel_tpu.models.llama import LlamaForCausalLM
    from automodel_tpu.models.common.config import TransformerConfig

    cfg = TransformerConfig.from_hf({
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "max_position_embeddings": 128})
    model = LlamaForCausalLM(cfg, BackendConfig(attn="sdpa", param_dtype="float32",
                                                compute_dtype="float32"))
    layout = model.cache_layout()
    assert layout == (kv_cache.kv_layer(2, 8),) * 2 and not kv_cache.recurrent_kinds(layout)
    eng = ServingEngine(AutoModel(model=model, params=model.init(jax.random.key(0)),
                                  adapter=None, mesh_ctx=None), _serve(prefix_cache=True))
    assert eng._pool.state is None and eng.state_slots == 0


# -- what assumes "state = blocks + a length" is refused, by name -------------------
DRAFT = {"hf_config": {"architectures": ["LlamaForCausalLM"], "vocab_size": VOCAB,
                       "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 1,
                       "num_attention_heads": 4, "num_key_value_heads": 2}}


@pytest.mark.parametrize("section,message", [
    ({"prefix_cache": True}, r"serving\.prefix_cache: true is refused.*conv state.*at that boundary"),
    ({"kv_spill": {"enabled": True}}, r"serving\.kv_spill\.enabled is refused.*conv state"),
    ({"speculative": {"enabled": True, "k": 2, "draft": DRAFT}},
     r"serving\.speculative\.enabled is refused.*length decrement"),
    ({"role": "prefill"}, r"serving\.role: prefill is refused.*K/V block rows only"),
])
def test_what_takes_state_for_blocks_and_a_length_is_refused(section, message):
    _, auto = _auto()
    with pytest.raises(ValueError, match=re.compile(message, re.S | re.I)):
        _engine(auto, **section)


def test_the_contiguous_engine_and_the_kv_handoff_refuse_the_layout():
    _, auto = _auto()
    with pytest.raises(GenerationUnsupported, match="conv state beside K/V.*contiguous"):
        GenerationEngine(auto, GenerationConfig(max_new_tokens=4, greedy=True))
    eng = _engine(auto)
    with pytest.raises(GenerationUnsupported, match="recurrent state"):
        eng.submit_prefilled([1, 2, 3], 4, {"k": None, "v": None})


# -- tracing ------------------------------------------------------------------------
def test_the_new_scopes_are_in_the_programs_op_names():
    """`conv` in the train step and both serve programs, `state_write` in the
    serve programs, as segments of the op's name path (what the new readers
    match; the harness's frozen vocabulary files them under `layers` and
    `kv_write`)."""
    from automodel_tpu.training.train_step import make_causal_lm_loss

    _, auto = _auto()
    eng = _engine(auto)
    params = auto.params
    loss = make_causal_lm_loss(auto.model)
    batch = {k: jnp.ones((2, 16), jnp.int32) for k in ("input_ids", "labels")}
    lowered = {
        "train": jax.jit(jax.grad(lambda p: loss(p, batch)[0])).lower(params),
        "chunk": eng._chunk.lower(
            params, eng._pool, jnp.asarray(eng._tables[0]), jnp.zeros((CHUNK,), jnp.int32),
            jnp.int32(0), jnp.int32(5), jnp.int32(0)),
        "decode": eng._decode.lower(
            params, eng._pool, jnp.asarray(eng._tables), jnp.asarray(eng._lengths),
            jnp.asarray(eng._cur), jnp.asarray(eng._active), eng._base_key, jnp.int32(0),
            eng._no_tokens, jnp.asarray(eng._active)),
    }
    for name, low in lowered.items():
        segments, scopes = set(), set()
        for loc in re.findall(r'loc\("([^"/][^"]*)"', low.as_text(debug_info=True)):
            segments.update(program_trace.path_segments(loc))
            scopes.add(program_trace.scope_of(loc))
        assert "conv" in segments, name
        assert ("state_write" in segments) == (name != "train"), name
        assert {"layers", "attn", "moe/experts"} <= scopes, name


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    """`benchmarks/run.py --rehearse` of the new cell, sized by the
    configuration's own `rehearse` block: correct, nothing failed, and the
    counter metric this family adds is read from the program's trace."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "serve-chat-lfm2-8b-a1b",
         "--seed", "2147485003", "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"]["state_resets_per_step"]["value"] > 0
    # no device metric is ever read from a CPU trace
    assert "conv_mixer_ms.serve" not in line["metrics"]
