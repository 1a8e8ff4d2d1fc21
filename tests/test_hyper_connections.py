"""The hyper-connection residual path (ops/hyper_connections.py) against a
loop-per-token float32 version: the three functions and every gradient, the
Sinkhorn rounds' convergence, the clamp and the single-stream limit. (Its
lowering for the TPU at the benchmark cell's shape is with the other
compile-only tests, tests/test_tpu_lowering.py.)"""

import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.ops import hyper_connections as hc

N, C, T = 4, 24, 10
ITERS, EPS_H, EPS_N, CLAMP = 20, 1e-6, 1e-6, (-30.0, 30.0)


def _weights(seed=0, scale=1.0):
    k = jax.random.split(jax.random.key(seed), 5)
    return {
        "x": jax.random.normal(k[0], (T, N * C), jnp.float32),
        "y": jax.random.normal(k[1], (T, C), jnp.float32),
        "phi": scale * jax.random.normal(k[2], (N * C, hc.n_coefficients(N)), jnp.float32) / math.sqrt(N * C),
        "b": 0.05 * jax.random.normal(k[3], (hc.n_coefficients(N),), jnp.float32),
        "alpha": 1.0 + 0.1 * jax.random.normal(k[4], (3,), jnp.float32),
    }


def _operator(w, iters=ITERS, clamp=CLAMP):
    co = hc.coefficients(w["x"], w["phi"], w["b"], w["alpha"], n=N, norm_eps=EPS_N,
                         sinkhorn_iters=iters, sinkhorn_eps=EPS_H, clamp=clamp)
    return co, hc.pre_mix(w["x"], co.pre), hc.post_mix(w["x"], w["y"], co.post, co.res)


def _token_loop(w, iters=ITERS, clamp=CLAMP):
    """The equations of the module's docstring, a token at a time, float32."""
    us, outs, maps = [], [], []
    for t in range(T):
        X = w["x"][t].reshape(N, C)
        x = X.reshape(-1)
        m = (x / jnp.sqrt(jnp.mean(x * x) + EPS_N)) @ w["phi"]
        pre = jax.nn.sigmoid(w["alpha"][0] * m[:N] + w["b"][:N])
        post = 2 * jax.nn.sigmoid(w["alpha"][1] * m[N:2 * N] + w["b"][N:2 * N])
        M = jnp.exp(jnp.clip(w["alpha"][2] * m[2 * N:] + w["b"][2 * N:], *clamp).reshape(N, N))
        for _ in range(iters):
            M = M / (M.sum(-1, keepdims=True) + EPS_H)
            M = M / (M.sum(-2, keepdims=True) + EPS_H)
        us.append(pre @ X)
        outs.append((M @ X + post[:, None] * w["y"][t][None, :]).reshape(-1))
        maps.append(M)
    return jnp.stack(us), jnp.stack(outs), jnp.stack(maps)


def test_forward_matches_the_token_loop():
    w = _weights()
    # each side one program: op by op, the loop is 10 tokens x 20 rounds of
    # single primitives
    co, u, out = jax.jit(_operator)(w)
    u_ref, out_ref, maps = jax.jit(_token_loop)(w)
    np.testing.assert_allclose(u, u_ref, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(out, out_ref, rtol=2e-5, atol=2e-6)
    # tokens on the minor axis: [n, T] and [n, n, T], float32
    assert co.pre.shape == co.post.shape == (N, T) and co.res.shape == (N, N, T)
    assert co.res.dtype == jnp.float32
    np.testing.assert_allclose(jnp.moveaxis(co.res, -1, 0), maps, rtol=2e-5, atol=1e-7)


@functools.cache
def _gradients():
    """(the operator's, the token loop's) gradient by every leaf: two compiled
    programs, run once; the five cases below read a leaf each."""
    w = _weights(1)
    mix = jax.random.normal(jax.random.key(9), (2, T, N * C))

    def scalar(u, out):  # both outputs, weighted so that nothing cancels
        return jnp.sum(u * mix[0, :, :C]) + jnp.sum(out * mix[1])

    got = jax.jit(jax.grad(lambda v: scalar(*_operator(v)[1:])))(w)
    want = jax.jit(jax.grad(lambda v: scalar(*_token_loop(v)[:2])))(w)
    return jax.device_get((got, want))


@pytest.mark.parametrize("leaf", ["x", "y", "phi", "b", "alpha"])
def test_every_gradient_matches_the_token_loop(leaf):
    got, want = (g[leaf] for g in _gradients())
    assert float(np.linalg.norm(got - want) / np.linalg.norm(want)) < 2e-5


def test_rows_and_columns_sum_to_one_after_20_rounds_and_not_after_2():
    # logits of unit scale, as the benchmark cell draws them (alpha of order 1,
    # phi of fan-in scale): 20 rounds read 1e-6, 2 rounds 1e-1. (Wider logits
    # converge more slowly: at three times the scale 20 rounds read 3e-2.)
    w = _weights(2)
    for iters, converged in ((20, True), (2, False)):
        res = _operator(w, iters=iters)[0].res
        cols, rows = res.sum(axis=0), res.sum(axis=1)
        np.testing.assert_allclose(cols, 1.0, atol=1e-5)  # normalised last
        err = float(hc.res_row_error(res))
        assert err == pytest.approx(float(jnp.abs(rows - 1).max()))
        assert (err < 1e-4) == converged, (iters, err)


def test_the_clamp_acts_at_30_and_is_inert_inside():
    w = _weights(3)
    # push one entry of R far out through its bias: exp(200) would overflow
    big = {**w, "b": w["b"].at[2 * N].set(200.0)}
    res = _operator(big)[0].res
    assert bool(jnp.isfinite(res).all())
    at_clamp = {**w, "b": w["b"].at[2 * N].set(30.0 - float(w["alpha"][2]) * 0.0)}
    # at or beyond +30 the value is the clamp's: the same map whatever the excess
    phi0 = {"phi": jnp.zeros_like(w["phi"])}
    np.testing.assert_allclose(_operator({**big, **phi0})[0].res,
                               _operator({**at_clamp, **phi0})[0].res, rtol=1e-6)
    # inside (-30, 30) a clamp ten times as wide changes nothing
    np.testing.assert_array_equal(_operator(w)[0].res, _operator(w, clamp=(-300.0, 300.0))[0].res)
    # and the clamp's branch passes no gradient to what it cut
    g = jax.grad(lambda b: _operator({**big, **phi0, "b": b})[2].sum())(big["b"])
    assert float(g[2 * N]) == 0.0


def test_equal_streams_through_a_uniform_map_are_the_single_stream_block():
    """phi = 0, Hpre = 1/n, Hpost = 1: u = x and every stream becomes x + F(x)
    whatever doubly stochastic Hres the biases give."""
    w = _weights(4)
    x1 = jax.random.normal(jax.random.key(5), (T, C))
    b = jnp.concatenate([jnp.full((N,), -math.log(N - 1.0)), jnp.zeros((N,)), w["b"][2 * N:]])
    F = lambda u: jnp.tanh(u) * 3.0
    w = {**w, "x": jnp.tile(x1, (1, N)), "phi": jnp.zeros_like(w["phi"]), "b": b}
    co = hc.coefficients(w["x"], w["phi"], w["b"], w["alpha"], n=N, norm_eps=EPS_N,
                         sinkhorn_iters=ITERS, sinkhorn_eps=EPS_H, clamp=CLAMP)
    np.testing.assert_allclose(co.pre, 1.0 / N, rtol=1e-6)
    np.testing.assert_allclose(co.post, 1.0, rtol=1e-6)
    u = hc.pre_mix(w["x"], co.pre)
    np.testing.assert_allclose(u, x1, rtol=1e-5, atol=1e-6)
    out = hc.post_mix(w["x"], F(u), co.post, co.res).reshape(T, N, C)
    for i in range(N):
        np.testing.assert_allclose(out[:, i], x1 + F(x1), rtol=1e-4, atol=1e-5)


def test_bfloat16_stream_keeps_float32_coefficients():
    w = _weights(6)
    x16 = w["x"].astype(jnp.bfloat16)
    co = hc.coefficients(x16, w["phi"].astype(jnp.bfloat16), w["b"], w["alpha"], n=N,
                         norm_eps=EPS_N, sinkhorn_iters=ITERS, sinkhorn_eps=EPS_H, clamp=CLAMP)
    assert co.pre.dtype == co.res.dtype == jnp.float32
    u = hc.pre_mix(x16, co.pre)
    out = hc.post_mix(x16, w["y"].astype(jnp.bfloat16), co.post, co.res)
    assert u.dtype == out.dtype == jnp.bfloat16 and out.shape == x16.shape


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 1e-2)], ids=["f32", "bf16"])
def test_the_post_mix_kernels_match_the_jnp_sums(dtype, tol, monkeypatch):
    """The TPU's forward and backward kernels, interpreted: the next stream and
    all four gradients against the same sums in jax.numpy (float32: the order of
    a row's 5 terms; bfloat16: one rounding of each result)."""
    n, C, T_ = 4, 640, 96  # two lane chunks (512 + 128), three token tiles of 32
    k = jax.random.split(jax.random.key(11), 5)
    x = jax.random.normal(k[0], (2, T_ // 2, n * C), jnp.float32).astype(dtype)
    y = jax.random.normal(k[1], (2, T_ // 2, C), jnp.float32).astype(dtype)
    post = 2 * jax.nn.sigmoid(jax.random.normal(k[2], (n, T_)))
    res = hc.sinkhorn(jnp.exp(jax.random.normal(k[3], (n, n, T_))), 20, 1e-6)
    w = jax.random.normal(k[4], x.shape)
    loss = lambda f: (lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w))
    both = lambda f, w: jax.jit(lambda *a: (f(*a), jax.grad(w(f), argnums=(0, 1, 2, 3))(*a)))
    want, want_g = both(hc._post_mix_jnp, loss)(x, y, post, res)
    monkeypatch.setenv("AUTOMODEL_MHC_INTERPRET", "1")
    got, got_g = both(hc.post_mix, loss)(x, y, post, res)
    assert got.dtype == dtype and got.shape == x.shape
    rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))
                             / jnp.linalg.norm(b.astype(jnp.float32)))
    assert rel(got, want) < tol
    for g, wg in zip(got_g, want_g):
        assert g.shape == wg.shape and g.dtype == wg.dtype
        assert rel(g, wg) < tol
    # rows no token tile divides are padded to one: the same kernels
    x5, y5, post5, res5 = x[:, :5], y[:, :5], post[:, :10], res[:, :, :10]
    total = lambda f: (lambda *a: f(*a).astype(jnp.float32).sum())
    got5, g5 = both(hc.post_mix, total)(x5, y5, post5, res5)
    want5, w5 = both(hc._post_mix_jnp, total)(x5, y5, post5, res5)
    assert rel(got5, want5) < tol
    for g, wg in zip(g5, w5):
        assert g.shape == wg.shape and rel(g, wg) < tol

def test_the_post_mix_kernels_run_per_device_block_on_a_mesh(monkeypatch):
    """On a mesh of several devices the kernels sit in a shard_map (batch over
    the data axes, the sequence over cp; 9 rows a device here, padded to a
    tile): the same stream and gradients as the jnp sums, no second path."""
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh

    ctx = build_mesh(MeshConfig(dp_shard=2, cp=2), devices=jax.devices()[:4])
    n, C, B, S = 4, 128, 2, 18
    k = jax.random.split(jax.random.key(5), 5)
    x = jax.random.normal(k[0], (B, S, n * C), jnp.float32)
    y = jax.random.normal(k[1], (B, S, C), jnp.float32)
    post = 2 * jax.nn.sigmoid(jax.random.normal(k[2], (n, B * S)))
    res = hc.sinkhorn(jnp.exp(jax.random.normal(k[3], (n, n, B * S))), 20, 1e-6)
    w = jax.random.normal(k[4], x.shape)
    want = jax.value_and_grad(lambda *a: jnp.sum(hc._post_mix_jnp(*a) * w), argnums=(0, 1, 2, 3))(x, y, post, res)
    monkeypatch.setenv("AUTOMODEL_MHC_INTERPRET", "1")
    sharded = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(hc.post_mix(*a, mesh_ctx=ctx) * w), argnums=(0, 1, 2, 3)))
    rows = ctx.sharding("batch", "seq", None)
    got = sharded(jax.device_put(x, rows), jax.device_put(y, rows), post, res)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, wg in zip(got[1], want[1]):
        np.testing.assert_allclose(g, wg, rtol=2e-5, atol=2e-5)


def test_the_tpu_refuses_a_width_its_kernels_cannot_take():
    x, y = jnp.zeros((16, 4 * 24)), jnp.zeros((16, 24))
    post, res = jnp.ones((4, 16)), jnp.ones((4, 4, 16))
    with pytest.raises(ValueError, match="multiple of 128"):
        hc.post_mix(x, y, post, res, platform="tpu")


def test_equal_streams_leave_the_pre_map_without_a_gradient():
    """Where a sublayer's input streams are equal (the embedding repeated, the
    module's ``h'`` repeated) ``u = (sum_i Hpre[i]) x``, and a pre-norm branch
    rescales ``u``: the loss cannot see ``Hpre``, and the 4 ``pre`` columns of
    ``phi`` and entries of ``b`` have no gradient, while ``post`` has one. (In
    bfloat16 those columns carry rounding noise instead, which Adam steps on
    at full size: the one leaf the benchmark cell's ``param_change_gap``
    reads, PERF.md section 6.)"""
    w = _weights(seed=3)
    x = jnp.tile(w["x"][:, :C], (1, N))
    proj = jax.random.normal(jax.random.key(4), (C, C)) / math.sqrt(C)

    def loss(phi, b):
        co = hc.coefficients(x, phi, b, w["alpha"], n=N, norm_eps=EPS_N, sinkhorn_iters=ITERS,
                             sinkhorn_eps=EPS_H, clamp=CLAMP)
        u = hc.pre_mix(x, co.pre)
        y = (u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True) + 1e-12)) @ proj
        return jnp.sum(hc.post_mix(x, y, co.post, co.res) ** 2)

    g_phi, g_b = jax.grad(loss, argnums=(0, 1))(w["phi"], w["b"])
    pre, post = jnp.linalg.norm(g_phi[:, :N]), jnp.linalg.norm(g_phi[:, N:2 * N])
    assert post > 0 and pre < 1e-5 * post
    assert jnp.max(jnp.abs(g_b[:N])) < 1e-5 * jnp.max(jnp.abs(g_b[N:2 * N]))
