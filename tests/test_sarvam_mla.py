"""sarvam_mla served through the latent pool, against its plain reference.

Tiny widths, float32, weights drawn by the benchmark's own rules
(``benchmarks/harness/weights.py`` with the configuration file's
``reference.init``). The program holds experts [0, 2) of 16, as the cell's
configuration holds 16 of 128, and the reference is given the same share.
Every program is jitted once in a module-scoped fixture; the tests read what
it returned.
"""

import dataclasses
import functools
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from automodel_tpu.generation import kv_cache
from automodel_tpu.generation.engine import GenerationConfig
from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.registry import resolve_architecture
from automodel_tpu.moe.layer import moe_block
from automodel_tpu.ops import latent_attention
from automodel_tpu.serving import paged
from automodel_tpu.serving.engine import ServeConfig, ServingEngine
from benchmarks.harness import loader
from benchmarks.harness import weights as W

HF = {
    "model_type": "sarvam_mla",  # no `architectures`: the registry knows the type
    "vocab_size": 96, "hidden_size": 48, "intermediate_size": 64, "moe_intermediate_size": 32,
    # one layer of each kind: the leading dense one and an expert one
    "num_hidden_layers": 2, "first_k_dense_replace": 1, "num_attention_heads": 4,
    "head_dim": 32, "q_head_dim": 24, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 16, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "moe_router_enable_expert_bias": True, "use_qk_norm": True,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "max_position_embeddings": 4096,
    # 75 positions against an original window of 16: every branch of the ramp
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 16,
                     "type": "deepseek_yarn"},
    "tie_word_embeddings": False, "hidden_act": "silu", "held_experts": [0, 2],
}
CONFIG_FILE = ROOT / "benchmarks" / "configs" / "sarvam-105b.serve-ep8-l6.json"
F32 = BackendConfig(attn="sdpa", experts="ragged", param_dtype="float32",
                    compute_dtype="float32", remat="none")
# float32 against float32 at `highest`: what is left is the order of sums
# (chunks against a cached prefix and absorbed decode steps against one causal
# pass over expanded keys; the sorted held picks against dense experts). A
# bfloat16 program rounds every product's operands to 2^-8 relative, 4e-3 on
# logits of order 1: it fails a float32 configuration by an order and more
LOGIT_TOL = 2e-4
BLOCK, CHUNK, PROMPT, NEW = 8, 32, 70, 5


def _reference():
    config = json.loads(CONFIG_FILE.read_text())
    R = loader.load_module("reference", config["reference"]["module"])
    hf = {k: v for k, v in HF.items() if k != "held_experts"}
    hf["num_experts"] = 2  # the file's key counts the experts held here
    spec = R.spec(hf, {"published_experts": 16, "held_experts": [0, 2]})
    return R, spec, config["reference"].get("init")


def _build(backend: BackendConfig):
    builder = resolve_architecture(HF)
    model, _ = builder(HF, backend)
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    return model, abstract


@pytest.fixture(scope="module")
def setup():
    """The model, its weights and the reference's, and ONE run of each
    program: a prompt of 70 tokens in chunks of 32 (the last one padded) then
    5 decode steps through the latent pool (the XLA gather path), the same
    through the Pallas kernel interpreted, and the reference's one causal
    pass over the whole sequence."""
    R, spec, init = _reference()
    model, abstract = _build(F32)
    params = W.make(abstract, 7, init=init)
    ref_params = W.make(abstract, 7, to_reference=R.to_reference, init=init)
    layout = model.cache_layout()
    rng = np.random.default_rng(3)
    ids = rng.integers(3, HF["vocab_size"], size=PROMPT + NEW).astype(np.int32)
    n_table = -(-(PROMPT + NEW + CHUNK) // BLOCK)
    table = jnp.arange(1, n_table + 1, dtype=jnp.int32)  # block 0 is scratch

    def apply(p, tokens, **kw):
        return model(p, tokens, **kw)

    def serve(interpret: bool, gather: bool):
        pool = paged.layout_pool(layout, 1, n_table + 1, BLOCK, dtype=jnp.float32)
        forward = jax.jit(
            lambda p, pool, toks, start: paged._fused_forward(
                apply, p, pool, table[None], start[None], toks[None],
                jnp.ones((1,), bool), block_size=BLOCK, interpret=interpret, gather=gather,
            )[:2],
            donate_argnums=(1,),
        )
        rows = []
        for start in range(0, PROMPT, CHUNK):
            real = min(CHUNK, PROMPT - start)
            toks = np.zeros((CHUNK,), np.int32)
            toks[:real] = ids[start:start + real]
            logits, pool = forward(params, pool, jnp.asarray(toks), jnp.int32(start))
            rows.append(np.asarray(logits[0, :real]))
        for t in range(PROMPT, PROMPT + NEW):
            logits, pool = forward(params, pool, jnp.asarray(ids[t:t + 1]), jnp.int32(t))
            rows.append(np.asarray(logits[0]))
        return np.concatenate(rows), np.asarray(pool.k)

    gather_logits, gather_pool = serve(interpret=False, gather=True)
    kernel_logits, kernel_pool = serve(interpret=True, gather=False)
    want = np.asarray(R.rows_logits(ref_params, jnp.asarray(ids), jnp.int32(0), spec, "f32",
                                    PROMPT + NEW))
    return SimpleNamespace(R=R, spec=spec, model=model, params=params, ref_params=ref_params,
                           layout=layout, ids=ids, gather_logits=gather_logits,
                           kernel_logits=kernel_logits, gather_pool=gather_pool,
                           kernel_pool=kernel_pool, want=want)


def test_the_family_and_its_layout():
    model, _ = _build(F32)
    cfg = model.config
    assert type(model).__name__ == "SarvamMlaForCausalLM"
    # the source's names: deepseek_yarn is ops/rope.py's yarn, head_dim 576 is the row
    assert cfg.rope.scaling == "yarn" and cfg.rope.factor == 40 and cfg.q_lora_rank is None
    assert (cfg.moe.num_experts, cfg.moe.held_experts, cfg.moe.num_shared_experts) == (16, (0, 2), 1)
    assert cfg.moe.score_func == "sigmoid" and cfg.moe.expert_bias and cfg.moe.norm_topk_prob
    assert cfg.moe.route_scale == 2.5 and cfg.moe.bias_update_factor == 0.0
    assert cfg.mla_attn_scale == pytest.approx(24 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    assert model.cache_layout() == (kv_cache.latent_layer(24 + 8, 24),) * 2
    assert kv_cache.recurrent_kinds(model.cache_layout()) == []
    pool = paged.layout_pool(model.cache_layout(), 4, 16, 8, dtype=jnp.float32)
    # one side, rows padded to whole lanes
    assert pool.latent and pool.v is None and pool.k.shape == (2, 16, 8, 128)


def test_chunks_then_decode_through_the_latent_pool_are_the_references_forward(setup):
    err = np.abs(setup.gather_logits - setup.want).max()
    assert err < LOGIT_TOL, err
    assert (setup.gather_logits.argmax(-1) == setup.want.argmax(-1)).all()
    # the held share is the reference's: without it the expert layer's output moves the logits
    assert np.abs(setup.want).max() > 0.1


def test_the_kernel_interpreted_is_the_gather_path(setup):
    np.testing.assert_allclose(setup.kernel_logits, setup.gather_logits, atol=2e-5)
    # both wrote the same rows; what lies past a row's 32 numbers is zeros
    np.testing.assert_allclose(setup.kernel_pool, setup.gather_pool, atol=1e-5)
    assert not setup.gather_pool[..., 32:].any() and setup.gather_pool[:, 1:9, :, :32].any()


def test_absorbed_is_expanded_on_the_same_rows(setup):
    """The two attends of one layer on the rows the run above left in the pool:
    four queries at successive positions, expanded as one chunk (the prefix
    read back through kv_b) and absorbed one a slot (kv_b folded into query
    and output), the four slots sharing the sequence's table."""
    cfg = setup.model.config
    N, nope, r, v = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k = jax.random.split(jax.random.key(5), 2)
    qn = jax.random.normal(k[0], (1, 4, N, nope), jnp.float32)
    qr = jax.random.normal(k[1], (1, 4, N, r), jnp.float32)
    pool = jnp.asarray(setup.gather_pool)
    w = setup.params["layers"]["01"]["attn"]["kv_b_proj"]["kernel"]
    tables = jnp.arange(1, pool.shape[1], dtype=jnp.int32)[None]
    start = jnp.asarray([PROMPT - 3], jnp.int32)
    kw = dict(layer=1, scale=cfg.mla_attn_scale, v_dim=v)
    absorbed = jax.jit(lambda *a: latent_attention.absorbed_attend(*a, gather=True, **kw))(
        qn[0][:, None], qr[0][:, None], pool, w, jnp.tile(tables, (4, 1)), start + jnp.arange(4))
    for path in ({"gather": True}, {"interpret": True}):  # the loop, the kernel
        expanded = jax.jit(lambda *a: latent_attention.chunk_attend(*a, kv_block=16, **path, **kw))(
            qn, qr, pool, w, tables, start)
        np.testing.assert_allclose(np.asarray(absorbed)[:, 0], np.asarray(expanded)[0], atol=2e-5)
    assert np.abs(np.asarray(absorbed)).max() > 0.05


# -- the chunk kernel against the loop --------------------------------------------

KEYS = 16  # keys a block of either path in the cases below: two table entries of 8


@functools.lru_cache(maxsize=None)
def _chunk_case(B: int, N: int, nope: int, rope: int, v: int, width: int = 12, keys: int = KEYS):
    """Random rows in a pool of 40 blocks, ``B`` sequences over tables of
    ``width`` entries (12: 6 key blocks of 16), 8 queries; the kernel
    interpreted and the loop, each jitted once with ``start`` (and the tables)
    as arguments. Pool block 39 is NaN: no clean table names it."""
    S, rank = 8, 24
    k = jax.random.split(jax.random.key(11), 4)
    rows = jax.random.normal(k[0], (2, 40, BLOCK, rank + rope), jnp.float32)
    pool = jnp.zeros((2, 40, BLOCK, 128), jnp.float32).at[..., :rank + rope].set(rows)
    pool = pool.at[:, 39].set(jnp.nan)
    w = 0.2 * jax.random.normal(k[1], (rank, N * (nope + v)), jnp.float32)
    qn = jax.random.normal(k[2], (B, S, N, nope), jnp.float32)
    qr = jax.random.normal(k[3], (B, S, N, rope), jnp.float32)
    tables = 1 + jnp.arange(B * width, dtype=jnp.int32).reshape(B, width)
    kw = dict(layer=1, scale=0.3, v_dim=v, kv_block=keys)
    kernel = jax.jit(lambda t, st: latent_attention.chunk_attend(
        qn, qr, pool, w, t, st, interpret=True, **kw))
    loop = jax.jit(lambda t, st: latent_attention.chunk_attend(
        qn, qr, pool, w, t, st, gather=True, **kw))
    return SimpleNamespace(kernel=kernel, loop=loop, tables=tables, S=S, width=width)


@pytest.mark.parametrize("shape, starts", [
    ((1, 4, 16, 8, 16), [0]),  # the first chunk: one live block, the causal mask in it
    ((1, 4, 16, 8, 16), [5]),  # mid-block: the chunk straddles blocks 0 and 1
    ((1, 4, 16, 8, 16), [16]),  # a block's edge: block 0 needs no mask, block 1 all of it
    ((1, 4, 16, 8, 16), [88]),  # the table's last block
    ((2, 4, 16, 8, 16), [3, 70]),  # two sequences, one live block and five
    # whole lanes, two blocks of 128 keys: two heads a trip share a tile of rotary
    # columns, the running max and sum 128 lanes wide
    ((1, 2, 128, 64, 128, 32, 128), [150]),
], ids=["start0", "mid-block", "block-edge", "last-block", "two-unequal", "whole-lanes"])
def test_the_chunk_kernel_is_the_loop_on_the_same_rows(shape, starts):
    case = _chunk_case(*shape)
    start = jnp.asarray(starts, jnp.int32)
    got, want = np.asarray(case.kernel(case.tables, start)), np.asarray(case.loop(case.tables, start))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.isfinite(got).all() and np.abs(want).max() > 0.05


def test_the_chunk_kernel_never_reads_a_dead_block():
    """Two sequences, one live key block and five: the expansion runs five
    trips for both, so the first one's buffer holds whatever its table's dead
    entries name. NaN rows there (and past the second one's live blocks) change
    nothing: a dead step neither fetches nor attends."""
    case = _chunk_case(2, 4, 16, 8, 16)
    starts = np.asarray([3, 70])
    start = jnp.asarray(starts, jnp.int32)
    live = -(-(starts + case.S) // KEYS)  # key blocks that hold an attended position
    entries = np.arange(case.width)[None, :] // (KEYS // BLOCK)
    garbage = jnp.where(entries < live[:, None], case.tables, 39)
    assert (np.asarray(garbage) == 39).sum() == (6 - 1 + 6 - 5) * 2
    clean = np.asarray(case.kernel(case.tables, start))
    np.testing.assert_array_equal(np.asarray(case.kernel(garbage, start)), clean)
    assert np.isfinite(clean).all()
    # the loop attends every block it reads: the same tables poison it
    assert np.isnan(np.asarray(case.loop(garbage, start))).any()
    # and the host's count of the kernel's steps is these live blocks
    blocks = latent_attention.chunk_blocks(case.S, 4, 16, 8, 16, case.width, BLOCK,
                                           interpret=True, kv_block=KEYS)
    assert blocks == (4, KEYS, 6)
    assert latent_attention.chunk_grid_steps(starts, case.S, 4, blocks) == (12, 6)


def _walk(jaxpr, inside_kernel=False):
    """(equation, whether a pallas_call holds it) over a jaxpr and what it nests."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_kernel
        held = inside_kernel or eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, held)


def test_the_chunk_program_holds_one_kernel_a_layer_and_no_score_array(setup):
    """The chunk program with the kernel: one ``pallas_call`` a latent layer,
    and outside them no float32 array with a (heads, queries, keys) extent;
    the loop's program is the control that has them."""
    N, keys = setup.model.config.num_heads, 14 * BLOCK  # the table's 14 entries are one block
    pool = jax.eval_shape(lambda: paged.layout_pool(setup.layout, 1, 16, BLOCK, dtype=jnp.float32))

    def program(**path):
        return jax.make_jaxpr(lambda p, pool, t, toks: paged._fused_forward(
            lambda pp, ids, **kw: setup.model(pp, ids, **kw), p, pool, t,
            jnp.zeros((1,), jnp.int32), toks, jnp.ones((1,), bool), block_size=BLOCK,
            **path)[0])(setup.params, pool, jax.ShapeDtypeStruct((1, 14), jnp.int32),
                        jax.ShapeDtypeStruct((1, CHUNK), jnp.int32)).jaxpr

    def scores(jaxpr):
        return [v.aval.shape for eqn, held in _walk(jaxpr) if not held for v in eqn.outvars
                if v.aval.dtype == jnp.float32 and v.aval.ndim >= 3
                and all(d in v.aval.shape for d in (N, CHUNK, keys))]

    kernel = program(interpret=True, gather=False)
    calls = [eqn for eqn, _ in _walk(kernel) if eqn.primitive.name == "pallas_call"]
    assert len(calls) == len(setup.layout) == 2
    assert all(eqn.params["name"] == "latent_chunk_attention" for eqn in calls)
    assert scores(kernel) == []
    assert scores(program(interpret=False, gather=True))  # the loop writes them


def test_eight_ranks_held_parts_and_one_shared_expert_are_the_uncut_layer(setup):
    """The share ties to the model: the program's expert layer on each of
    eight ranks (2 of 16 experts each) summed, the shared expert counted
    once, is the reference's uncut layer on the same weights."""
    R, model = setup.R, setup.model
    cfg = model.config
    E, Eh, D, I = 16, 2, cfg.hidden_size, cfg.moe.moe_intermediate_size
    whole = dataclasses.replace(cfg.moe, held_experts=None)
    from automodel_tpu.moe.layer import init_moe_params

    abstract = jax.eval_shape(lambda: init_moe_params(jax.random.key(0), whole, D, jnp.float32))
    mp = W.make(abstract, 11, init={"router/bias": {"rule": "small_bias"}})
    x = jax.random.normal(jax.random.key(2), (1, 24, D), jnp.float32)

    @jax.jit
    def ranks(mp, x):
        total = 0.0
        for rank in range(E // Eh):
            lo = rank * Eh
            mine = dict(mp, experts={k: w[lo:lo + Eh] for k, w in mp["experts"].items()})
            part, _ = moe_block(x, mine, dataclasses.replace(cfg.moe, held_experts=(lo, lo + Eh)),
                                jax.nn.silu, experts_backend="ragged")
            total = total + part
        # every rank added the shared expert: count it once
        only_shared, _ = moe_block(
            x, dict(mp, experts={k: w[:Eh] * 0 for k, w in mp["experts"].items()}),
            dataclasses.replace(cfg.moe, held_experts=(0, Eh)), jax.nn.silu,
            experts_backend="ragged")
        return total - (E // Eh - 1) * only_shared

    uncut = dataclasses.replace(setup.spec, held=(0, E))
    lp = {"mlp_norm": 1.0 + 0.1 * jax.random.normal(jax.random.key(4), (D,)), "router": mp["router"]["weight"],
          "router_bias": mp["router"]["bias"], "gate_up": mp["experts"]["gate_up"],
          "down": mp["experts"]["down"],
          **{f"shared_{n}": mp["shared"][f"{n}_proj"]["kernel"] for n in ("gate", "up", "down")}}
    # the reference norms its input itself; the program's block is handed the normed rows
    h = x[0]
    normed = R.rms_norm(h, lp["mlp_norm"], uncut.rms_eps)
    want = R.mlp(h, lp, uncut, "f32")
    got = ranks(mp, normed[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # one rank alone is NOT the layer: the other seven hold most of the picks
    one, _ = moe_block(normed[None], dict(mp, experts={k: w[:Eh] for k, w in mp["experts"].items()}),
                       dataclasses.replace(cfg.moe, held_experts=(0, Eh)), jax.nn.silu,
                       experts_backend="ragged")
    assert np.abs(np.asarray(one[0]) - np.asarray(want)).max() > 0.05


def test_the_programs_write_the_latent_scopes(setup):
    """The path segments the new readers match, in the lowered chunk and
    decode programs' op names (utils/profiler.SCOPES lists them)."""
    import re

    from automodel_tpu.utils.profiler import SCOPES

    pool = jax.eval_shape(lambda: paged.layout_pool(setup.layout, 1, 16, BLOCK, dtype=jnp.float32))
    table = jax.ShapeDtypeStruct((1, 14), jnp.int32)
    found = {}
    for name, S in (("chunk", CHUNK), ("decode", 1)):
        low = jax.jit(lambda p, pool, t, toks: paged._fused_forward(
            lambda pp, ids, **kw: setup.model(pp, ids, **kw), p, pool, t,
            jnp.zeros((1,), jnp.int32), toks, jnp.ones((1,), bool), block_size=BLOCK,
            interpret=False, gather=True)[0]).lower(
                setup.params, pool, table, jax.ShapeDtypeStruct((1, S), jnp.int32))
        names = re.findall(r"mla_\w+|latent_write", low.as_text(debug_info=True))
        found[name] = set(names) - {"mla_branch"}  # the function's name, in source locations
    assert found["chunk"] == {"mla_prefix_expand", "mla_chunk_attn", "latent_write"}
    assert found["decode"] == {"mla_q_absorb", "mla_latent_attn", "mla_v_expand", "latent_write"}
    mine = {s.rsplit("/", 1)[-1] for s in SCOPES if "mla_" in s or s.endswith("latent_write")}
    assert mine == found["chunk"] | found["decode"]


def _engine(model, params, **serving):
    auto = SimpleNamespace(model=model, params=params, mesh_ctx=None,
                           constrain=lambda a, s: a)
    cfg = dict(slots=2, block_size=BLOCK, num_blocks=48, prefill_chunk=CHUNK, max_seq_len=128,
               decode_kernel="gather", prefix_cache=False)
    cfg.update(serving)
    return ServingEngine(auto, ServeConfig.from_dict(cfg),
                         GenerationConfig.from_dict({"max_new_tokens": NEW, "greedy": True, "seed": 0}))


def test_the_engine_serves_it_and_reuses_a_prefix_over_latent_blocks(setup):
    prompt = setup.ids[:PROMPT].tolist()
    eng = _engine(setup.model, setup.params, prefix_cache=True)
    eng.submit(prompt, request_id="a", max_new_tokens=NEW)
    (first,) = eng.run()
    assert first["completion_reason"] == "length" and len(first["tokens"]) == NEW
    # the first token is the reference's greedy one after the prompt (the rest
    # follow the engine's own tokens, which the reference was not fed)
    assert first["tokens"][0] == int(setup.want[PROMPT - 1].argmax()) and first["prefix_hit_tokens"] == 0
    # the same prompt again: its whole blocks are found, the rest is prefilled
    eng.submit(prompt, request_id="b", max_new_tokens=NEW)
    (again,) = eng.run()
    assert again["tokens"] == first["tokens"]
    assert again["prefix_hit_tokens"] == (PROMPT - 1) // BLOCK * BLOCK
    n = eng._account.n
    assert n["latent_context_rows"] > 0 and n["attn_grid_steps"] == 0  # the gather path has no grid


def test_a_chunks_span_carries_the_kernels_grid_and_live_steps(setup, monkeypatch):
    """``serve.prefill_dispatch`` of a latent engine: the chunk kernels' grid
    steps and live steps of that chunk, every layer's, by the kernel's own host
    arithmetic; 0 and 0 where the chunk takes the loop. The chunk program is a
    stub: the counts are the host's, from the chunk's position alone."""
    from automodel_tpu.serving import loop_account

    spans = []

    class Spy:
        def __init__(self, name, **stats):
            spans.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(loop_account, "TraceAnnotation", Spy)
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    prompt = (3 + np.arange(1100) % 90).tolist()
    seen = {}
    for backend in ("fused", "gather"):
        eng = _engine(setup.model, setup.params, decode_kernel=backend, max_seq_len=2048,
                      num_blocks=300, slots=1)
        vocab = setup.model.config.vocab_size
        eng._chunk = lambda params, pool, *rest: (jnp.zeros((vocab,), jnp.float32), pool)
        del spans[:]
        eng.submit(prompt, request_id=backend, max_new_tokens=1)
        (done,) = eng.run()
        assert done["completion_reason"] == "length"
        seen[backend] = [st for name, st in spans if name == "serve.prefill_dispatch"]
        width = eng._tables.shape[1]
    assert [st["pos"] for st in seen["fused"]] == list(range(0, 1100, CHUNK))
    # a table over 2,048 positions and a chunk more = 5 key blocks of 512 (the last
    # one part), the 4 heads one group, 2 layers
    blocks = latent_attention.chunk_blocks(CHUNK, 4, 16, 8, 16, width, BLOCK, interpret=True)
    assert blocks == (4, 512, 5)
    for st in seen["fused"]:
        grid, live = latent_attention.chunk_grid_steps([st["pos"]], CHUNK, 4, blocks)
        assert (st["attn_grid_steps"], st["attn_live_steps"]) == (2 * grid, 2 * live)
        assert st["attn_live_steps"] == 2 * (1 + (st["pos"] + CHUNK - 1) // 512)
    assert {st["attn_grid_steps"] for st in seen["fused"]} == {10}
    assert {st["attn_live_steps"] for st in seen["fused"]} == {2, 4, 6}
    assert all(st["attn_grid_steps"] == st["attn_live_steps"] == 0 for st in seen["gather"])
    assert len(seen["gather"]) == len(seen["fused"])


def test_a_bounded_iteration_runs_the_oldest_admissions_chunks_first(setup):
    """``serving.max_prefill_chunks_per_step``: two prompts of three chunks
    each, admitted together. Unbounded, an iteration runs a chunk for each;
    bounded to one, the older admission's chunks go first and the other
    slot waits its turn. The tokens are the same either way."""
    prompts = [setup.ids[:PROMPT].tolist(), setup.ids[3:PROMPT + 3].tolist()]

    def serve(**serving):
        eng = _engine(setup.model, setup.params, **serving)
        for i, prompt in enumerate(prompts):
            eng.submit(prompt, request_id=str(i), max_new_tokens=NEW)
        chunks, firsts, done = [], [], []
        while not eng.idle():
            before = eng._account.n["chunks"]
            done += eng.step()
            chunks.append(eng._account.n["chunks"] - before)
            firsts.append([s is not None and s.t_first is not None for s in eng._slots])
        return chunks, firsts, {r["request_id"]: r["tokens"] for r in done}

    free, _, want = serve()
    bounded, firsts, got = serve(max_prefill_chunks_per_step=1)
    assert free[:3] == [2, 2, 2] and sum(free) == 6
    assert bounded[:6] == [1] * 6 and sum(bounded) == 6
    assert firsts[2] == [True, False] and firsts[5][1]  # slot 0's prompt whole, then slot 1's
    assert got == want and sorted(got) == ["0", "1"]
    with pytest.raises(ValueError, match="max_prefill_chunks_per_step=-1"):
        ServeConfig.from_dict({"max_prefill_chunks_per_step": -1})


@pytest.mark.parametrize("serving, what", [
    ({"kv_spill": {"enabled": True}}, "kv_spill.enabled"),
    ({"speculative": {"enabled": True, "k": 2, "draft": {"hf_config": {"model_type": "llama"}}}},
     "speculative.enabled"),
    ({"role": "prefill"}, "role: prefill"),
    ({"role": "decode"}, "role: decode"),
    ({"kv_transfer": {"enabled": True}}, "kv_transfer.enabled"),
    ({"kv_cache_dtype": "int8"}, "kv_cache_dtype: int8"),
])
def test_what_a_latent_layout_cannot_be_served_with_is_refused_by_name(serving, what):
    model, _ = _build(F32)
    with pytest.raises(ValueError, match=f"serving.{what} is refused: SarvamMlaForCausalLM keeps "
                                         "one latent row a token"):
        ServeConfig.from_dict(serving).check_layout(model.cache_layout(), "SarvamMlaForCausalLM")


def test_check_layout_accepts_latent_and_still_refuses_delta():
    latent = (kv_cache.latent_layer(576, 512),) * 2
    ServeConfig().check_layout(latent, "M")  # prefix_cache on by default: fine over latent blocks
    delta = (kv_cache.LayerCache("delta", 32, 128), kv_cache.latent_layer(576, 512))
    with pytest.raises(ValueError, match="keeps delta state a layer"):
        ServeConfig().check_layout(delta, "KimiLinearForCausalLM")
    # a latent pool's block rows are not shipped: by name, not by a shape error
    pool = paged.layout_pool(latent, 2, 4, 8)
    with pytest.raises(NotImplementedError, match="extract_blocks: block rows of a latent pool"):
        paged.extract_blocks(pool, [1])
    with pytest.raises(NotImplementedError, match="inject_blocks"):
        paged.inject_blocks(pool, [1], {"k": None, "v": None})
    with pytest.raises(NotImplementedError, match="latent rows alone"):
        paged.layout_pool((kv_cache.kv_layer(2, 64), kv_cache.latent_layer(576, 512)), 2, 4, 8)


def test_the_state_dict_adapter_round_trips(setup):
    from automodel_tpu.models.sarvam_mla import SarvamMlaStateDictAdapter

    adapter = SarvamMlaStateDictAdapter(setup.model.config)
    hf = {k: np.asarray(v) for k, v in adapter.to_hf(jax.device_get(setup.params))}
    assert sorted(hf) == sorted(adapter.hf_keys())
    # only the held experts are named; the router keeps its published width
    assert "model.layers.1.mlp.experts.1.up_proj.weight" in hf
    assert "model.layers.1.mlp.experts.2.up_proj.weight" not in hf
    assert hf["model.layers.1.mlp.gate.weight"].shape == (16, 48)
    assert hf["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"].shape == (32, 48)
    back = adapter.from_hf(lambda key: hf[key])
    for (pa, a), (pb, b) in zip(jax.tree_util.tree_leaves_with_path(back),
                                jax.tree_util.tree_leaves_with_path(jax.device_get(setup.params))):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
