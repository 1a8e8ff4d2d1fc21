"""The named scopes over the jitted train and serve programs
(``automodel_tpu/utils/profiler.SCOPES``): every name lands in the programs'
op names, most ops carry one, and they are metadata only.

The programs are the real ones at a tiny size: the train step over a
qwen3-moe with a dense first layer (two microbatches, fused linear CE, Adam
with clipping) and the serving engine's chunk-prefill and paged-decode
programs over the same model. Op names are read where a device trace reads
them: the ``op_name`` of the compiled module's instructions.
"""

import contextlib
import re
import sys
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from automodel_tpu.auto_model import AutoModel
from automodel_tpu.generation.engine import GenerationConfig
from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.optim.builders import build_optimizer
from automodel_tpu.serving.engine import ServeConfig, ServingEngine
from automodel_tpu.training.train_state import TrainState
from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss
from automodel_tpu.utils.profiler import SCOPES
from benchmarks.harness import program_trace

# data movement and plumbing the compiler mostly folds away
TRIVIAL = {"parameter", "constant", "tuple", "get-tuple-element", "reshape", "broadcast",
           "iota", "convert", "bitcast", "bitcast-convert", "copy", "call", "while",
           "conditional"}


def _model():
    from automodel_tpu.models.qwen3_moe import MoEForCausalLM, MoETransformerConfig

    hf = {"architectures": ["Qwen3MoeForCausalLM"], "model_type": "qwen3_moe",
          "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
          "moe_intermediate_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
          "num_experts_per_tok": 2, "max_position_embeddings": 256,
          "tie_word_embeddings": False, "first_k_dense_replace": 1}
    model = MoEForCausalLM(
        MoETransformerConfig.from_hf(hf),
        BackendConfig(attn="sdpa", experts="ragged", param_dtype="float32",
                      compute_dtype="float32", remat="full"),
    )
    return model, model.init(jax.random.key(0))


def _lowered_programs() -> dict:
    """{"train" | "chunk" | "decode": jax Lowered}, every jit built anew."""
    model, params = _model()
    opt = build_optimizer(lr=1e-3, grad_clip_norm=1.0)
    step = build_train_step(
        make_causal_lm_loss(model, loss="fused_linear_ce", num_chunks=2), opt, donate=False)
    state = TrainState.create(params, opt.init(params))
    batch = {k: jnp.ones((2, 2, 16), jnp.int32) for k in ("input_ids", "labels")}
    eng = ServingEngine(
        AutoModel(model=model, params=params, adapter=None, mesh_ctx=None),
        ServeConfig(slots=2, block_size=4, num_blocks=32, prefill_chunk=8, max_seq_len=32),
        GenerationConfig(max_new_tokens=4, greedy=True),
    )
    return {
        "train": step.lower(state, batch),
        "chunk": eng._chunk.lower(
            params, eng._pool, jnp.asarray(eng._tables[0]), jnp.zeros((8,), jnp.int32),
            jnp.int32(0), jnp.int32(5)),
        "decode": eng._decode.lower(
            params, eng._pool, jnp.asarray(eng._tables), jnp.asarray(eng._lengths),
            jnp.asarray(eng._cur), jnp.asarray(eng._active), eng._base_key, jnp.int32(0),
            eng._no_tokens, jnp.asarray(eng._active)),
    }


@pytest.fixture(scope="module")
def programs():
    return {name: (low, low.compile().as_text()) for name, low in _lowered_programs().items()}


def _instructions(hlo: str):
    """(opcode, op_name or "") of every instruction of a compiled module's text."""
    for line in hlo.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%?[\w.\-]+ = (?:\([^=]*\)|\S+) ([\w\-]+)\(", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            yield m.group(1), name.group(1) if name else ""


def test_reader_holds_the_same_vocabulary():
    # every name the harness's (frozen) vocabulary knows is a program scope,
    # in the program's order; the program may name more (a newer family's
    # scopes, read by their own metrics through the op's path segments)
    assert tuple(s for s in SCOPES if s in program_trace.VOCABULARY) == tuple(
        program_trace.VOCABULARY)
    # a leaf names one scope only, or the reader could not tell them apart
    leaves = [s.rsplit("/", 1)[-1] for s in SCOPES]
    assert len(set(leaves)) == len(leaves)


def test_every_scope_is_in_the_programs_op_names(programs):
    """As the program wrote them (the lowered module, before the compiler
    merges what computes the same thing, as it does the two global norms)."""
    found = set()
    for low, _ in programs.values():
        for name in re.findall(r'loc\("([^"/][^"]*)"', low.as_text(debug_info=True)):
            found.add(program_trace.scope_of(name))  # file names start with "/"
    # this model writes every scope but a recurrent family's own
    # (tests/test_serving_recurrent_state.py finds those in its programs)
    assert found - {program_trace.UNSCOPED} == set(program_trace.VOCABULARY)
    # ... and kimi_linear's two mixers (tests/test_kimi_linear.py finds those)
    # ... and xing4's residual path and its multi-token-prediction module
    # (tests/test_xing4.py finds those)
    assert set(SCOPES) - set(program_trace.VOCABULARY) == {
        "conv", "kv_write/state_write", "attn/kda", "attn/kda/kda_conv", "attn/kda/kda_gate",
        "attn/kda/kda_chunk", "attn/kda/kda_norm", "attn/mla",
        "norm/mhc", "norm/mhc/mhc_coeff", "norm/mhc/mhc_pre", "norm/mhc/mhc_post", "mtp",
        # ... and the latent pool's segments (tests/test_sarvam_mla.py finds those)
        "attn/mla/mla_q_absorb", "attn/mla/mla_latent_attn", "attn/mla/mla_v_expand",
        "attn/mla/mla_prefix_expand", "attn/mla/mla_chunk_attn", "kv_write/latent_write"}


@pytest.mark.parametrize("program,must_have", [
    ("train", {"embed", "layers", "norm", "attn", "moe/router", "moe/dispatch", "moe/experts",
               "moe/combine", "mlp", "final_norm", "lm_head_ce", "grad_accum", "anomaly",
               "optimizer"}),
    ("chunk", {"embed", "layers", "attn", "kv_write", "moe/experts", "lm_head"}),
    ("decode", {"embed", "layers", "attn", "kv_write", "moe/experts", "lm_head", "sample"}),
])
def test_most_ops_of_a_compiled_program_carry_a_scope(programs, program, must_have):
    # an instruction without any op_name is the compiler's own (the CPU
    # backend's expansions of sort and cumsum): the program cannot name it
    names = [name for op, name in _instructions(programs[program][1])
             if op not in TRIVIAL and name]
    scopes = [program_trace.scope_of(name) for name in names]
    assert must_have <= set(scopes)
    share = 1.0 - scopes.count(program_trace.UNSCOPED) / len(scopes)
    assert share >= 0.9, (program, share)


def test_backward_and_recompute_are_told_apart(programs):
    names = [n for _, n in _instructions(programs["train"][1]) if n]
    seen = {(program_trace.scope_of(n), program_trace.direction_of(n)) for n in names}
    for scope in ("attn", "moe/experts"):
        assert {(scope, "fwd"), (scope, "bwd"), (scope, "remat")} <= seen, scope
    assert ("optimizer", "bwd") not in seen and ("optimizer", "remat") not in seen
    # the fused loss carries its own rule (ops/losses._fused_ce_fwd): its three
    # products run in the forward chunk loop, its backward is the multiply by
    # the loss's cotangent, and NOTHING of it is recomputed. At this size the
    # compiler folds that multiply into its consumers, so the backward is read
    # where the program wrote it: the lowered module's locations
    wrote = {(program_trace.scope_of(n), program_trace.direction_of(n)) for n in re.findall(
        r'loc\("([^"/][^"]*)"', programs["train"][0].as_text(debug_info=True))}
    assert ("lm_head_ce", "fwd") in seen and ("lm_head_ce", "bwd") in wrote
    assert ("lm_head_ce", "remat") not in seen | wrote


def test_path_segments_unwrap_transforms():
    assert program_trace.path_segments(
        "jit(step_fn)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
        "rematted_computation/moe/dispatch/sort:") == [
            "jit(step_fn)", "layers", "while", "body", "closed_call", "checkpoint",
            "rematted_computation", "moe", "dispatch", "sort"]
    assert program_trace.scope_of("jit(step_fn)/jvp(layers)/while/body/moe/experts/x") == "moe/experts"
    assert program_trace.scope_of("jit(step_fn)/jvp(layers)/while/body/experts/x") == "layers"
    assert program_trace.scope_of("jit(step_fn)/transpose(jvp())/while/body/add") == "unscoped"
    assert program_trace.scope_of("jit(sample)/argmax") == "unscoped"  # a jit's name is no scope


def test_scopes_are_metadata_only(programs, monkeypatch):
    """The compiled modules, metadata stripped, are the same with the scopes
    and with every vocabulary name kept off the name stack."""
    import jax._src.api as jax_api

    words = {w for s in SCOPES for w in s.split("/")}
    real = jax_api.source_info_util

    class WithoutScopes:
        def __getattr__(self, attr):
            return getattr(real, attr)

        @staticmethod
        def extend_name_stack(name):
            return contextlib.nullcontext() if name in words else real.extend_name_stack(name)

    def stripped(hlo: str) -> str:
        hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
        # the header line and the stack-frame tables the metadata pointed into
        hlo = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*",
                     "", hlo, flags=re.M)
        hlo = re.sub(r"^HloModule .*$", "", hlo, flags=re.M)
        # an instruction's own name ends in a number the compiler draws from
        # the op name: number the values by first appearance instead
        order: dict[str, str] = {}
        return re.sub(r"%[\w.\-]+", lambda m: order.setdefault(m.group(0), f"%v{len(order)}"),
                      hlo)

    # the persistent compile cache keys a module WITHOUT its metadata, so with it
    # on (another test of this process may have enabled it) the second compile
    # is handed the first one's executable, names and all: off for this compile
    from jax._src import compilation_cache

    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with monkeypatch.context() as m:
            m.setattr(jax_api, "source_info_util", WithoutScopes())
            bare = {name: low.compile().as_text()
                    for name, low in _lowered_programs().items()}
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()
    for name, (_, with_scopes) in programs.items():
        assert not any(program_trace.scope_of(n) != program_trace.UNSCOPED
                       for _, n in _instructions(bare[name]) if n), name
        assert stripped(bare[name]) == stripped(with_scopes), name
