"""Every Pallas family lowers for the TPU — on one device and on a 2x2 mesh.

No chip: a compile-only TPU client (utils/compile_only.py) runs the real
XLA:TPU + Mosaic compile on ShapeDtypeStructs sharded over the devices of a
``v5e:2x2`` topology. GSPMD refuses a Mosaic call it is asked to partition,
so on a mesh each kernel must sit in the shard_map its wrapper builds
(ops/platform_check.kernel_axes); the 8-device CPU tests cannot see a miss
because off-TPU ``flash`` routes to ``sdpa`` and the grouped matmul to
``lax.ragged_dot``. The second half runs the same wrappers with the kernels
interpreted on the 8 CPU devices and compares with sdpa / a gathered view /
``lax.ragged_dot``.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.layer import init_moe_params, moe_block
from automodel_tpu.ops.attention import flash, sdpa, sdpa_decode
from automodel_tpu.ops.paged_attention import paged_attend, quantize_kv_rows
from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
from automodel_tpu.parallel.plans import make_constrain
from automodel_tpu.utils.compile_only import mosaic_calls, topology_devices


@functools.lru_cache(maxsize=None)
def _tpu_ctx(n: int, **degrees):
    return build_mesh(MeshConfig(**degrees), devices=topology_devices()[:n])


def _sds(ctx, shape, dtype, *logical):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=ctx.sharding(*logical))


def _compile(fn, *args):
    # conftest pins matmul precision to "highest" for CPU parity tests; on
    # the chip nothing sets it, and Mosaic refuses a bf16 matmul asked for
    # fp32 precision
    with jax.default_matmul_precision("default"):
        return mosaic_calls(jax.jit(fn).lower(*args).compile())


# -- lowering: one device and the 2x2 mesh ------------------------------------


@pytest.mark.parametrize(
    "n,degrees", [(1, {}), (4, {"dp_shard": 2, "tp": 2})], ids=["1chip", "dp2xtp2"]
)
def test_flash_fwd_bwd_lowers(n, degrees):
    ctx = _tpu_ctx(n, **degrees)
    q = _sds(ctx, (2, 256, 4, 128), jnp.bfloat16, "batch", None, "tensor", None)
    kv = _sds(ctx, (2, 256, 2, 128), jnp.bfloat16, "batch", None, "tensor", None)

    def loss(q, k, v):
        out = flash(q, k, v, platform="tpu", mesh_ctx=ctx)
        return out.astype(jnp.float32).sum()

    # splash forward + its dq and dkv kernels
    assert _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) == 3


@pytest.mark.parametrize(
    "n,degrees", [(1, {}), (4, {"dp_shard": 2, "tp": 2})], ids=["1chip", "dp2xtp2"]
)
def test_latent_attention_lowers_with_v_narrower_than_qk(n, degrees):
    """Kimi-Linear's / DeepSeek-V3's latent block: q/k heads of 192, v of
    128, handed to splash as they are (no V pad)."""
    ctx = _tpu_ctx(n, **degrees)
    qk = _sds(ctx, (2, 512, 4, 192), jnp.bfloat16, "batch", None, "tensor", None)
    v = _sds(ctx, (2, 512, 4, 128), jnp.bfloat16, "batch", None, "tensor", None)

    def loss(q, k, v):
        out = flash(q, k, v, scale=192**-0.5, platform="tpu", mesh_ctx=ctx)
        assert out.shape[-1] == 128
        return out.astype(jnp.float32).sum()

    assert _compile(jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v) == 3


@pytest.mark.parametrize("packed", [False, True], ids=["one_document", "packed"])
@pytest.mark.parametrize(
    "n,degrees,per_channel",
    [(1, {}, True), (1, {}, False), (4, {"dp_shard": 2, "tp": 2}, True)],
    ids=["1chip-kda", "1chip-scalar", "dp2xtp2-kda"],
)
def test_delta_rule_kernels_fwd_bwd_lower(n, degrees, per_channel, packed):
    """ops/delta_rule.py at the cell's head shapes (32 heads of 128 x 128, a
    per-channel decay) and Qwen3-Next's scalar decay, flat operands as the
    projections leave them: one forward and one backward Mosaic call, on a
    mesh inside a shard_map (a ``tp`` shard is a contiguous block of heads)."""
    from automodel_tpu.ops.delta_rule import chunked_delta_rule

    ctx = _tpu_ctx(n, **degrees)
    B, S, H, d = 2, 1024, 32, 128
    qkv = _sds(ctx, (B, S, H * d), jnp.bfloat16, "batch", None, "tensor")
    g = _sds(ctx, (B, S, H * d if per_channel else H), jnp.float32, "batch", None, "tensor")
    beta = _sds(ctx, (B, S, H), jnp.float32, "batch", None, "tensor")
    seg = _sds(ctx, (B, S), jnp.int32, "batch", None)

    def loss(q, k, v, g, beta, seg):
        out = chunked_delta_rule(q, k, v, g, beta, segment_ids=seg if packed else None,
                                 platform="tpu", mesh_ctx=ctx)
        return out.astype(jnp.float32).sum()

    assert _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), qkv, qkv, qkv, g, beta, seg) == 2


def _eqns(jaxpr, stop=lambda eqn: False):
    """Every equation of a jaxpr and of the jaxprs in its parameters (not
    those of an equation ``stop`` names)."""
    from jax._src import core

    for eqn in jaxpr.eqns:
        yield eqn
        if not stop(eqn):
            for sub in core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub, stop)


def _delta_rule_boundary(fn, args, per_head):
    """-> (ranks of the array operands and results of the operator's
    ``custom_vjp`` call in ``fn``'s jaxpr, equations of ``fn``'s
    value-and-grad whose result has the shape ``per_head`` = [B, S, H, d])."""
    is_call = lambda e: e.primitive.name.startswith("custom_vjp_call")
    calls = [e for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr, is_call) if is_call(e)]
    assert len(calls) == 1  # the kernels' bodies hold one of their own (the cumulative sum)
    ranks = {len(v.aval.shape) for v in (*calls[0].invars, *calls[0].outvars)}
    grad = jax.make_jaxpr(jax.value_and_grad(fn, argnums=tuple(range(len(args)))))(*args)
    shaped = [e for e in _eqns(grad.jaxpr)
              if any(getattr(v.aval, "shape", None) == per_head for v in e.outvars)]
    return ranks, shaped


def test_kda_block_hands_the_delta_rule_what_the_convs_wrote():
    """``kda_block`` at the cell's widths ([1, 512, 2304], 32 heads of 128,
    bfloat16): every array the operator's ``custom_vjp`` takes or gives is
    rank 3, and NO equation of the value-and-grad gives a ``[B, S, H, dh]``
    array: q, k, v, the decay, the output gate and ``o`` stay ``[B, S, H *
    dh]`` from the projections to ``o_proj``, forward and transposed. The
    count's history: 137 before the kernels formed their own operands (the
    reshapes of q, k, v and both gates, the softplus on the reshaped ``f``,
    two ``l2norm``, ``beta k``, ``beta v``, the clamp, and the transposes of
    each); 18 while ``kda_norm`` held the per-head RMS norm of ``o`` (6
    forward from the reshape of ``o`` on, 12 backward: float32 relayouts on
    the chip); 0 since the norm's division is the kernels' epilogue
    (``out_norm_eps``) and its scale a ``[H * dh]`` row. The block compiles
    for the chip with one forward and one backward Mosaic call."""
    import types

    from automodel_tpu.models.common.config import BackendConfig
    from automodel_tpu.models.kimi_linear.model import init_kda_layer, kda_block

    ctx = _tpu_ctx(1)
    B, S, D, H, dh = 1, 512, 2304, 32, 128
    cfg = types.SimpleNamespace(hidden_size=D, kda_num_heads=H, kda_head_dim=dh, kda_dim=H * dh,
                                kda_conv_kernel=4, rms_eps=1e-5)
    backend = BackendConfig(param_dtype="bfloat16", compute_dtype="bfloat16", platform="tpu",
                            mesh_ctx=ctx)
    lp = jax.tree.map(lambda a: _sds(ctx, a.shape[1:], a.dtype),
                      jax.eval_shape(lambda: init_kda_layer(cfg, backend, jax.random.key(0), 1)))
    h, scale = _sds(ctx, (B, S, D), jnp.bfloat16), _sds(ctx, (D,), jnp.bfloat16)

    def loss(h, lp, scale):
        out = kda_block(cfg, backend, h, lp, scale, None, lambda x, _: x)
        return out.astype(jnp.float32).sum()

    ranks, shaped = _delta_rule_boundary(loss, (h, lp, scale), (B, S, H, dh))
    assert ranks == {3}
    assert not shaped, [(e.primitive.name, str(e.source_info.name_stack)) for e in shaped]
    assert _compile(jax.grad(loss, argnums=(0, 1)), h, lp, scale) == 2


def test_gated_delta_net_hands_the_delta_rule_flat_operands():
    """Qwen3-Next's wrapper (``models/qwen3_next/delta.py``) takes and gives
    ``[B, S, H, d]`` (its model's layout); between the two reshapes nothing
    has that shape: FIVE equations of the value-and-grad (``o`` reshaped out,
    its cotangent made, ``dq``, ``dk``, ``dv`` reshaped back), 35 before."""
    from automodel_tpu.models.qwen3_next.delta import chunk_gated_delta_rule

    B, S, H, d = 1, 256, 4, 128
    x = jax.ShapeDtypeStruct((B, S, H, d), jnp.float32)
    small = jax.ShapeDtypeStruct((B, S, H), jnp.float32)

    def loss(q, k, v, g, beta):
        return chunk_gated_delta_rule(q, k, v, g, beta, platform="tpu").sum()

    ranks, shaped = _delta_rule_boundary(loss, (x, x, x, small, small), (B, S, H, d))
    assert ranks == {3}
    assert len(shaped) == 5, [e.primitive.name for e in shaped]


def test_flash_refuses_heads_the_mesh_does_not_divide():
    ctx = _tpu_ctx(4, dp_shard=1, tp=4)
    q = _sds(ctx, (2, 256, 4, 128), jnp.bfloat16)
    kv = _sds(ctx, (2, 256, 2, 128), jnp.bfloat16)  # 2 KV heads over tp=4
    with pytest.raises(ValueError, match="not divisible by mesh axes"):
        jax.jit(
            lambda q, k, v: flash(q, k, v, platform="tpu", mesh_ctx=ctx)
        ).lower(q, kv, kv)


# B, Sq, N, H of the queries; the pool's shape; the table's width; layer
_SMALL_PAGED = (4, None, 8, 128), (64, 16, 4, 128), 8, None
_PAGED_CASES = {
    "1chip-bf16-decode": (1, {}, 1, False, *_SMALL_PAGED),
    "tp4-int8-verify": (4, {"dp_shard": 1, "tp": 4}, 5, True, *_SMALL_PAGED),
    # the two serve cells at their published widths, so that pages a grid
    # step which overflow VMEM, or a page the kernel cannot copy out of HBM,
    # fail here and not on the chip. LFM2-8B-A1B: 128 slots, 8 KV heads of 64
    # packed two a lane row, layer 1 of the stacked pool of its 3 K/V layers
    "1chip-lfm2-cell": (1, {}, 1, False, (128, None, 32, 64), (3, 16384, 16, 4, 128), 544, 1),
    # MiniMax-M2: 64 slots, 48 Q / 8 KV heads of 128, a one-layer pool
    "1chip-minimax-cell": (1, {}, 1, False, (64, None, 48, 128), (20480, 16, 8, 128), 544, None),
    # and its verify chunk of 5: 30 query rows a KV head in the same step
    "1chip-minimax-verify": (1, {}, 5, False, (64, None, 48, 128), (20480, 16, 8, 128), 544, None),
}


@pytest.mark.parametrize(
    "n,degrees,sq,int8,q_shape,pool_shape,nbseq,layer",
    list(_PAGED_CASES.values()), ids=list(_PAGED_CASES),
)
def test_paged_decode_lowers(n, degrees, sq, int8, q_shape, pool_shape, nbseq, layer):
    ctx = _tpu_ctx(n, **degrees)
    B, _, N, H = q_shape
    lead = (None,) * (len(pool_shape) - 4)
    q = _sds(ctx, (B, sq, N, H), jnp.bfloat16, None, None, "tensor", None)
    pool = _sds(
        ctx, pool_shape, jnp.int8 if int8 else jnp.bfloat16,
        *lead, None, None, "tensor", None,
    )
    args = [q, pool, pool, _sds(ctx, (B, nbseq), jnp.int32), _sds(ctx, (B,), jnp.int32)]
    if int8:
        scale = _sds(ctx, pool_shape[:-1], jnp.float32, *lead, None, None, "tensor")
        args += [scale, scale]
    kw = {} if layer is None else {"layer": layer}
    assert _compile(functools.partial(paged_attend, mesh_ctx=ctx, **kw), *args) == 1


def test_latent_chunk_attention_lowers_at_the_serve_cells_widths():
    """sarvam-105b's chunk of 512 queries, 64 heads of 128 + 64 / 128 over a
    latent pool of 640-lane rows and a table of 544 entries (8,192 positions and
    a chunk): the expansion's buffer and ONE Mosaic call; heads of 16 lanes, which
    the kernel cannot tile, take the loop (no call)."""
    from automodel_tpu.ops import latent_attention

    ctx = _tpu_ctx(1)
    bf = jnp.bfloat16

    def calls(N, nope, rope, v, rank):
        args = [_sds(ctx, (1, 512, N, nope), bf), _sds(ctx, (1, 512, N, rope), bf),
                _sds(ctx, (6, 20480, 16, 640), bf), _sds(ctx, (rank, N * (nope + v)), bf),
                _sds(ctx, (1, 544), jnp.int32), _sds(ctx, (1,), jnp.int32)]
        return _compile(functools.partial(
            latent_attention.chunk_attend, layer=3, scale=0.135, v_dim=v), *args)

    assert calls(64, 128, 64, 128, 512) == 1
    assert latent_attention.chunk_blocks(512, 64, 128, 64, 128, 544, 16) == (8, 512, 17)
    assert calls(4, 16, 8, 16, 24) == 0
    assert latent_attention.chunk_blocks(512, 4, 16, 8, 16, 544, 16) is None


def test_fused_linear_ce_is_three_vocabulary_products():
    """The train cell's loss at its widths (D 2048, V 151,936, bf16) over two
    1024-token chunks: differentiated, the chunk loop holds the logits, dH and
    dW products and no fourth (autodiff of a checkpointed scan recomputed the
    logits: four)."""
    from automodel_tpu.ops.losses import fused_linear_cross_entropy

    ctx = _tpu_ctx(1)
    t, d, v = 2048, 2048, 151936
    h = _sds(ctx, (1, t, d), jnp.bfloat16)
    w = _sds(ctx, (d, v), jnp.bfloat16)
    labels = _sds(ctx, (1, t), jnp.int32)

    def loss(h, w, labels):
        s, n = fused_linear_cross_entropy(h, w, labels)
        return s / n

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(h, w, labels).compile()
    hlo = compiled.as_text()
    assert hlo.count(" convolution(") == 3
    # one body of the chunk loop, as the compiler counts it
    chunk_product = 2 * 1024 * d * v
    assert 3.0 <= compiled.cost_analysis()["flops"] / chunk_product < 3.1
    # a chunk's f32 logits and bf16 dlogits, not the stacked [T, V]
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * 1024 * v * 1.05


@pytest.mark.parametrize(
    "n_dev,degrees,B,T",
    [(1, {}, 1, 8192), (4, {"dp_shard": 4}, 4, 2048), (4, {"dp_shard": 2, "cp": 2}, 2, 4104)],
    ids=["one_chip_cell_shape", "dp4", "dp2_cp2_rows_padded"],
)
def test_hyper_connection_residual_path_lowers_unpadded(n_dev, degrees, B, T):
    """train-8k-xing4.0-29b-a4b's residual path at its shape, [8192, 4 x 3584]
    bfloat16, forward and backward through XLA:TPU; and the same kernels on
    the 2x2 mesh under their shard_map (batch over the data axes, the sequence
    over cp; 2052 rows a device are padded to a tile): the TPU has one path.
    The stream is carried flat (ops/hyper_connections.py): as [T, 4, C] its 4
    rows would be padded to a tile of 16 and every copy of the stream would
    be four times its size."""
    from automodel_tpu.ops import hyper_connections as hc

    ctx = _tpu_ctx(n_dev, **degrees)
    n, D = 4, 3584
    x = _sds(ctx, (B, T, n * D), jnp.bfloat16, "batch", "seq", None)
    y = _sds(ctx, (B, T, D), jnp.bfloat16, "batch", "seq", None)
    phi = _sds(ctx, (n * D, hc.n_coefficients(n)), jnp.bfloat16, None, None)
    b = _sds(ctx, (hc.n_coefficients(n),), jnp.float32, None)
    alpha = _sds(ctx, (3,), jnp.float32, None)

    def loss(x, y, phi, b, alpha):
        co = hc.coefficients(x, phi, b, alpha, n=n, norm_eps=1e-6, sinkhorn_iters=20,
                             sinkhorn_eps=1e-6, clamp=(-30.0, 30.0))
        u = hc.pre_mix(x, co.pre)
        out = hc.post_mix(x, y + u, co.post, co.res, platform="tpu", mesh_ctx=ctx)
        return out.astype(jnp.float32).sum() + hc.res_row_error(co.res)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(x, y, phi, b, alpha).compile()
    # the post-mix's two kernels (its forward is dead here: the loss needs only
    # the backward's outputs, which read x, y and the coefficients)
    assert 1 <= mosaic_calls(compiled) <= 2
    stream = B * T * n * D * 2 // n_dev
    m = compiled.memory_analysis()
    # out: the stream's gradient (+ the small ones); temporaries: a few
    # float32 copies of the stream at most. A padded layout alone is 4 x.
    assert m.output_size_in_bytes < 1.3 * stream
    assert m.temp_size_in_bytes < 6 * stream, m.temp_size_in_bytes / stream


# experts, top-k, expert width, hidden, x [B, S]
_SMALL_MOE = 8, 2, 256, 256, (4, 128)


@pytest.mark.parametrize(
    "n,degrees,backend,shape,grad",
    [
        (1, {}, "ragged", _SMALL_MOE, True),
        (4, {"dp_shard": 4}, "ragged", _SMALL_MOE, True),
        (4, {"dp_shard": 4, "ep": 4}, "a2a_fused", _SMALL_MOE, True),
        # the one-device branch both benchmark cells take: the kernels block
        # both halves out of the stored fused gate_up (up index offset)
        (1, {}, "ragged_fused", _SMALL_MOE, True),
        # serve-chat-minimax-m2's decode step at its published widths (64
        # slots x top-8 = 512 rows over 256 experts, a grid of 258 units that
        # are mostly empty): the forward's `pl.when` on the plan's row window
        # and the `where` in its weight index maps, through Mosaic
        (1, {}, "ragged_fused", (256, 8, 1536, 3072, (64, 1)), False),
        # train-30b-a3b's expert layer at its published widths (2 x 4096
        # tokens x top-8 = 65,536 rows over 128 experts): the backward's wide
        # tiles (a [2048, 1536] fp32 scratch beside its double-buffered out
        # block) need the scoped VMEM the kernels ask for, and Mosaic alone
        # says whether they got it
        (1, {}, "ragged_fused", (128, 8, 768, 2048, (2, 4096)), True),
        # train-8k-xing4.0-29b-a4b's held-expert layer at its published widths
        # (8 of 64 experts, top-4, a buffer of 8,192 rows): at a hidden size
        # of 3584 no tile of the forward fits the default scoped stack, and
        # Mosaic alone says whether the limit it asks for instead is granted
        (1, {}, "a2a_fused", (64, 4, 1024, 3584, (1, 8192),
                              {"held_experts": (0, 8), "held_capacity_factor": 2.0}), True),
    ],
    ids=["1chip-ragged", "dp4-ragged", "ep4-a2a_fused", "1chip-ragged_fused",
         "1chip-ragged_fused-minimax-decode", "1chip-ragged_fused-30b-a3b-train",
         "1chip-a2a_fused-xing4-held-train"],
)
def test_expert_kernels_fwd_bwd_lower(n, degrees, backend, shape, grad):
    ctx = _tpu_ctx(n, **degrees)
    E, K, I, D, tokens, *extra = shape
    cfg = MoEConfig(num_experts=E, num_experts_per_tok=K, moe_intermediate_size=I,
                    **(extra[0] if extra else {}))
    mp = jax.eval_shape(
        lambda: init_moe_params(jax.random.key(0), cfg, D, jnp.bfloat16)
    )
    spec = {
        "router": {"weight": (None, None)},
        "experts": {
            "gate_up": ("expert", "expert_fsdp", "tensor"),
            "down": ("expert", "tensor", "expert_fsdp"),
        },
    }
    mp = jax.tree.map(
        lambda a, s: _sds(ctx, a.shape, a.dtype, *s), mp, spec,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    x = _sds(ctx, (*tokens, D), jnp.bfloat16, "batch", None, None)
    constrain = make_constrain(ctx)

    def loss(x, mp):
        out, _ = moe_block(
            x, mp, cfg, jax.nn.silu, experts_backend=backend,
            constrain=constrain, platform="tpu",
        )
        return out.astype(jnp.float32).sum()

    if not grad:  # a serving program: the forward kernel alone
        assert _compile(loss, x, mp) == 1
        return
    # ragged: 2 gmm forward, then gmm (dlhs) and tgmm (drhs) backward;
    # fused: one forward kernel and the three purpose-tiled backward ones.
    # How many survive depends on what XLA finds dead, so: forward AND
    # backward kernels present, not an exact count
    assert _compile(jax.grad(loss, argnums=1), x, mp) >= 4


# -- numerics: the wrappers with interpreted kernels on the 8 CPU devices -----


def test_flash_wrapper_matches_sdpa(devices8, monkeypatch):
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    ctx = build_mesh(MeshConfig(dp_shard=2, tp=4), devices=devices8)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 128, 8, 128)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 128, 4, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 128, 4, 128)), jnp.float32)
    seg = jnp.asarray(np.repeat([[0, 1], [0, 0]], 64, axis=1), jnp.int32)
    out = jax.jit(
        lambda q, k, v, s: flash(
            q, k, v, segment_ids=s, platform="cpu", mesh_ctx=ctx
        )
    )(q, k, v, seg)
    assert out.sharding.spec == P("dp_shard", None, "tp")
    # fp32 online softmax vs one-shot softmax: accumulation order only
    np.testing.assert_allclose(
        out, sdpa(q, k, v, segment_ids=seg), atol=2e-5, rtol=2e-5
    )


def test_paged_wrapper_matches_gathered_view(devices8):
    ctx = build_mesh(MeshConfig(dp_shard=2, tp=4), devices=devices8)
    rng = np.random.default_rng(0)
    B, Sq, N, Nkv, H, NB, BS, NBseq = 4, 3, 8, 4, 128, 32, 16, 4
    q = jnp.asarray(rng.standard_normal((B, Sq, N, H)), jnp.float32)
    kq, ks = quantize_kv_rows(
        jnp.asarray(rng.standard_normal((NB, BS, Nkv, H)), jnp.float32)
    )
    vq, vs = quantize_kv_rows(
        jnp.asarray(rng.standard_normal((NB, BS, Nkv, H)), jnp.float32)
    )
    tables = jnp.asarray(rng.integers(1, NB, (B, NBseq)), jnp.int32)
    lengths = jnp.asarray([0, 17, 40, 61], jnp.int32)
    out = jax.jit(
        functools.partial(paged_attend, interpret=True, mesh_ctx=ctx)
    )(q, kq, vq, tables, lengths, ks, vs)
    assert out.sharding.spec == P(None, None, "tp")
    # reference: gather the (dequantized) blocks into a contiguous view and
    # attend it under the per-query causal mask
    deq = lambda x, s: (x.astype(jnp.float32) * s[..., None])[tables].reshape(
        B, NBseq * BS, Nkv, H
    )
    pos = jnp.arange(NBseq * BS)[None, None, :]
    mask = pos <= (lengths[:, None] + jnp.arange(Sq)[None, :])[:, :, None]
    ref = sdpa_decode(q, deq(kq, ks), deq(vq, vs), kv_mask=mask)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("backend", ["ragged", "ragged_fused"])
def test_expert_wrapper_matches_lax_ragged_dot(devices8, monkeypatch, backend):
    cfg = MoEConfig(num_experts=8, num_experts_per_tok=2, moe_intermediate_size=128)
    D = 128
    mp = init_moe_params(jax.random.key(0), cfg, D, jnp.float32)
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((4, 32, D)), jnp.float32
    )

    def loss(x, mp, **kw):
        out, _ = moe_block(x, mp, cfg, jax.nn.silu, platform="cpu", **kw)
        return (out * out).sum()

    # reference first, kernels off: sort + lax.ragged_dot on one device
    ref = jax.grad(loss, argnums=(0, 1))(x, mp, experts_backend="ragged")
    monkeypatch.setenv("AUTOMODEL_GMM_INTERPRET", "1")
    ctx = build_mesh(MeshConfig(dp_shard=4, tp=2), devices=devices8)
    got = jax.jit(
        jax.grad(
            functools.partial(
                loss, experts_backend=backend, constrain=make_constrain(ctx)
            ),
            argnums=(0, 1),
        )
    )(x, mp)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


# -- refused at setup, never a lowering error at the first step ---------------

_TINY_MOE = {
    "architectures": ["Qwen3MoeForCausalLM"], "model_type": "qwen3_moe",
    "vocab_size": 256, "hidden_size": 128, "intermediate_size": 256,
    "moe_intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "num_experts": 8, "num_experts_per_tok": 2,
}


@pytest.mark.parametrize(
    "degrees,backend,match",
    [
        ({"dp_shard": 1, "tp": 4}, {"attn": "flash"}, "not divisible by mesh axes"),
        ({"dp_shard": 2, "cp": 2}, {"attn": "flash"}, "use attn: ring"),
        ({"dp_shard": 2, "pp": 2}, {"attn": "sdpa", "experts": "ragged"},
         "use experts: gspmd with pp"),
    ],
    ids=["kv-heads-vs-tp", "flash-under-cp", "expert-kernels-under-pp"],
)
def test_unsupported_kernel_mesh_is_refused_when_the_model_is_built(
    degrees, backend, match
):
    from automodel_tpu import auto_model

    ctx = _tpu_ctx(4, **degrees)
    with pytest.raises(ValueError, match=match):
        auto_model.from_config(_TINY_MOE, ctx, backend, abstract=True)


def test_flash_inside_a_pipeline_stage_lowers():
    """A stage is manual over pp only; the flash wrapper nests a shard_map
    over the axes still auto (GSPMD refuses the Mosaic call otherwise, even
    when those axes have size 1)."""
    from automodel_tpu import auto_model

    hf = dict(_TINY_MOE, architectures=["LlamaForCausalLM"], model_type="llama")
    ctx = _tpu_ctx(4, pp=2, dp_shard=2)
    auto = auto_model.from_config(
        hf, ctx,
        {"attn": "flash", "param_dtype": "bfloat16", "pp_microbatches": 2},
        abstract=True,
    )
    ids = _sds(ctx, (4, 256), jnp.int32, "batch", None)
    assert _compile(lambda p, i: auto(p, i), auto.params, ids) >= 1
