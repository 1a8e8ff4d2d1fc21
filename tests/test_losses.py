"""CE loss parity: chunked / fused-linear vs the plain masked formulation,
values AND gradients.

`chunked_cross_entropy` carries `jax.checkpoint` on its scan body — without
it, scan's AD stacks every chunk's fp32 softmax residuals into a
[chunks, chunk_t, V] buffer (4GB at the MoE bench shape; the round-5 on-chip
OOM). `fused_linear_cross_entropy` carries its own differentiation rule
instead (`ops/losses._fused_ce_fwd`): the forward chunk loop forms dlogits
while a chunk's logits are live and from them dH and dW, so nothing of size
T x V is saved and no logits product is recomputed; the backward is a
multiply by the scalar cotangent. These tests pin that rule against autodiff
of the unchunked path: every chunking, the soft cap, both dtypes, an input
that takes no gradient, and the undifferentiated primal.

Reference surface: components/loss/{masked_ce.py,chunked_ce.py,linear_ce.py}.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops import losses
from automodel_tpu.ops.losses import (
    IGNORE_INDEX,
    chunked_cross_entropy,
    fused_linear_cross_entropy,
    masked_cross_entropy,
)

T, D, V = 96, 32, 257  # deliberately awkward vocab; T divisible by 8 chunks


def _data(t=T, dtype=jnp.float32):
    rng = np.random.default_rng(0)
    hidden = jnp.asarray(rng.normal(size=(t, D)), dtype)
    kernel = jnp.asarray(rng.normal(size=(D, V)) * 0.1, dtype)
    labels = rng.integers(0, V, size=(t,))
    labels[::7] = IGNORE_INDEX  # sprinkle padding
    return hidden, kernel, jnp.asarray(labels, jnp.int32)


@pytest.fixture(scope="module")
def data():
    return _data()


def _masked(labels, cap=None, scale=1.0):
    def f(h, k):
        lg = (h @ k).astype(jnp.float32)
        if cap is not None:
            lg = cap * jnp.tanh(lg / cap)
        s, n = masked_cross_entropy(lg, labels)
        return scale * s / n

    return f


def _fused(labels, cap=None, scale=1.0, **kw):
    def f(h, k):
        s, n = fused_linear_cross_entropy(h, k, labels, logits_soft_cap=cap, **kw)
        return scale * s / n

    return f


def _close(got, want, dtype):
    """f32: the tolerances this file has always held. bf16: the reference
    rounds its own products to bf16 (eps 2^-8), so a few eps of the largest
    entry."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    else:
        np.testing.assert_allclose(got, want, rtol=2**-6, atol=2**-6 * np.abs(want).max())


def test_chunked_matches_masked(data):
    hidden, kernel, labels = data
    logits = hidden @ kernel

    def f_masked(lg):
        s, n = masked_cross_entropy(lg, labels)
        return s / n

    def f_chunked(lg):
        s, n = chunked_cross_entropy(lg, labels, num_chunks=8)
        return s / n

    v0, g0 = jax.value_and_grad(f_masked)(logits)
    v1, g1 = jax.value_and_grad(f_chunked)(logits)
    np.testing.assert_allclose(v0, v1, rtol=1e-6)
    np.testing.assert_allclose(g0, g1, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("cap", [None, 30.0], ids=["nocap", "cap30"])
@pytest.mark.parametrize("chunking", ["default", "chunks8", "prime_t"])
def test_fused_linear_matches_masked(chunking, cap, dtype, monkeypatch):
    """Value and both gradients against autodiff of the unchunked loss."""
    kw = {}
    t = T
    if chunking == "default":
        # the shape's own chunk width, shrunk so that T = 96 takes several
        monkeypatch.setattr(losses, "_CHUNK_TOKENS_PER_ACC_BYTE", 6)
        itemsize = jnp.dtype(dtype).itemsize
        assert losses._chunk_count(T, V, dtype, dtype) == T // (6 * itemsize) > 1
    elif chunking == "chunks8":
        kw["num_chunks"] = 8
    else:
        t, kw["num_chunks"] = 97, 8  # no chunk count divides a prime: one chunk
    hidden, kernel, labels = _data(t, dtype)
    v0, g0 = jax.value_and_grad(_masked(labels, cap), argnums=(0, 1))(hidden, kernel)
    v1, g1 = jax.value_and_grad(_fused(labels, cap, **kw), argnums=(0, 1))(hidden, kernel)
    np.testing.assert_allclose(v0, v1, rtol=1e-6 if dtype == jnp.float32 else 2**-7)
    for a, b in zip(g1, g0):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape
        _close(a, b, dtype)


def test_fused_linear_upstream_cotangent(data):
    """The backward rule is a multiply by the loss sum's cotangent: one that
    is not 1 / n must come through."""
    hidden, kernel, labels = data
    g0 = jax.grad(_masked(labels, scale=3.7), argnums=(0, 1))(hidden, kernel)
    g1 = jax.grad(_fused(labels, scale=3.7, num_chunks=8), argnums=(0, 1))(hidden, kernel)
    for a, b in zip(g1, g0):
        _close(a, b, jnp.float32)


def _vocab_products(fn, *args) -> int:
    """dot_generals with a V-sized dimension in fn's jaxpr, scan bodies included."""
    def walk(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                n += any(V in v.aval.shape for v in (*eqn.invars, *eqn.outvars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += walk(sub)
        return n

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("argnum,products", [(0, 2), (1, 2), ((0, 1), 3)],
                         ids=["hidden_only", "kernel_only", "both"])
def test_fused_linear_forms_only_the_gradients_asked_for(data, argnum, products):
    """A frozen head (LoRA) or a frozen trunk costs nothing: the rule sees
    which inputs are perturbed and leaves the other's product out."""
    hidden, kernel, labels = data
    g0 = jax.grad(_masked(labels), argnums=argnum)(hidden, kernel)
    g1 = jax.grad(_fused(labels, num_chunks=8), argnums=argnum)(hidden, kernel)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        _close(a, b, jnp.float32)
    assert _vocab_products(
        jax.grad(_fused(labels, num_chunks=8), argnums=argnum), hidden, kernel) == products


def test_fused_linear_all_ignored_chunk(data):
    """A chunk of nothing but IGNORE_INDEX adds no loss, no count, no gradient."""
    hidden, kernel, labels = data
    labels = labels.at[: T // 8 * 2].set(IGNORE_INDEX)  # the first two of 8 chunks
    v0, g0 = jax.value_and_grad(_masked(labels), argnums=(0, 1))(hidden, kernel)
    v1, g1 = jax.value_and_grad(_fused(labels, num_chunks=8), argnums=(0, 1))(hidden, kernel)
    np.testing.assert_allclose(v0, v1, rtol=1e-6)
    for a, b in zip(g1, g0):
        _close(a, b, jnp.float32)
    assert not np.asarray(g1[0][: T // 8 * 2]).any()


@pytest.mark.parametrize("cap", [None, 30.0], ids=["nocap", "cap30"])
def test_fused_linear_primal(data, cap):
    """Undifferentiated (evaluation): same sum and count, ONE product a chunk."""
    hidden, kernel, labels = data
    s0, n0 = masked_cross_entropy(
        hidden @ kernel if cap is None else cap * jnp.tanh(hidden @ kernel / cap), labels)
    s1, n1 = fused_linear_cross_entropy(hidden, kernel, labels, num_chunks=8,
                                        logits_soft_cap=cap)
    np.testing.assert_allclose(s0, s1, rtol=1e-6)
    assert int(n0) == int(n1) == int((labels != IGNORE_INDEX).sum())
    assert _vocab_products(
        lambda h, k: fused_linear_cross_entropy(h, k, labels, num_chunks=8), hidden, kernel) == 1


@pytest.mark.parametrize("t,v,dtype,chunks", [
    (8192, 151936, jnp.bfloat16, 8),   # the train cell: 1024 tokens, 0.93 GB live
    (8192, 200064, jnp.bfloat16, 8),
    (8192, 50257, jnp.float32, 4),     # f32 accumulator: twice the tokens
    (8192, 262144, jnp.float32, 8),    # 2048 f32 tokens would hold 4.3 GB: 1024
    (12288, 151936, jnp.bfloat16, 12),
    (1000, 151936, jnp.bfloat16, 1),   # fewer tokens than a chunk
    (8191, 151936, jnp.bfloat16, 8),   # a prime: left to _usable_chunks (one chunk)
], ids=["cell", "minimax_vocab", "gpt2_f32", "gemma_f32", "three_docs", "short", "prime"])
def test_chunk_width_follows_the_shape(t, v, dtype, chunks):
    assert losses._chunk_count(t, v, dtype, dtype) == chunks
    if t % chunks == 0:
        itemsize = jnp.dtype(dtype).itemsize
        assert (4 + itemsize) * (t // chunks) * v <= losses._CHUNK_LIVE_BYTES


def test_fused_linear_no_stacked_logits_residual(data):
    """The compiled backward must not hold a [chunks, chunk_t, V] residual:
    the rule keeps peak temps near ONE chunk's logits, not all of them.
    Asserted on the CPU executable's temp-buffer budget (fp32 logits for all
    chunks = chunks x chunk_t x V x 4 bytes)."""
    hidden, kernel, labels = data

    def f(h, k):
        s, n = fused_linear_cross_entropy(h, k, labels, num_chunks=8)
        return s / n

    g = jax.jit(jax.grad(f, argnums=(0, 1)))
    mem = g.lower(hidden, kernel).compile().memory_analysis()
    if mem is None or not hasattr(mem, "temp_size_in_bytes"):
        pytest.skip("memory analysis unavailable on this backend")
    stacked = 8 * (T // 8) * V * 4
    assert mem.temp_size_in_bytes < stacked, (
        f"temps {mem.temp_size_in_bytes} >= stacked-residual size {stacked}"
    )


def test_fused_linear_trains_like_masked_under_fsdp(devices8):
    """A train step on a 4-device FSDP mesh (parameters and the batch sharded
    over dp_shard): GSPMD partitions the rule's scan, carried dW accumulator
    included, and one step moves every parameter as the unfused loss does."""
    from automodel_tpu import auto_model
    from automodel_tpu.data.loader import place_batch
    from automodel_tpu.optim.builders import build_optimizer
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import build_train_step, make_causal_lm_loss

    ctx = build_mesh(MeshConfig(dp_shard=4), devices=devices8[:4])
    hf = {
        "architectures": ["LlamaForCausalLM"], "model_type": "llama",
        "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 16,
    }
    auto = auto_model.from_config(
        hf, ctx, {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"},
        seed=0,
    )
    opt = build_optimizer(name="adamw", lr=2e-3, grad_clip_norm=1.0)
    ids = np.random.default_rng(1).integers(0, 96, size=(1, 8, 16)).astype(np.int32)
    labels = ids.copy()
    labels[0, :, :3] = IGNORE_INDEX
    batch = place_batch(ctx, {"input_ids": ids, "labels": labels})
    after = {}
    for name, kw in (("masked_ce", {}), ("fused_linear_ce", {"num_chunks": 4})):
        step = build_train_step(
            make_causal_lm_loss(auto.model, loss=name, constrain=auto.constrain, **kw),
            opt, donate=False)
        state, m = step(TrainState.create(auto.params, jax.jit(opt.init)(auto.params)), batch)
        after[name] = (float(m["loss"]), float(m["grad_norm"]), state.params)
    (l0, n0, p0), (l1, n1, p1) = after["masked_ce"], after["fused_linear_ce"]
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    np.testing.assert_allclose(n1, n0, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
