"""Manifold-constrained hyper-connections: the residual path as an operator.

Parity: mHC (arXiv:2512.24880) on hyper-connections (arXiv:2409.19606). The
residual stream is ``n`` streams wide; a sublayer ``F`` reads a learned,
input-dependent mix of them and its output is written back through two more
maps, the stream-to-stream one projected onto the doubly stochastic matrices
by Sinkhorn-Knopp. A token's ``X [n, C]``, the sublayer's own ``phi [nC, n + n
+ n^2]``, ``b [n + n + n^2]`` and three scalars ``alpha``:

    x     = vec(X);  xhat = x / sqrt(mean(x^2) + norm_eps)     (no learned scale)
    m     = xhat @ phi
    Hpre  = sigmoid(alpha[0] * m[:n] + b[:n])
    Hpost = 2 * sigmoid(alpha[1] * m[n:2n] + b[n:2n])
    R     = clip(alpha[2] * m[2n:] + b[2n:], clamp).reshape(n, n)
    M     = exp(R);  iters times:  M /= M.sum(-1) + eps;  M /= M.sum(-2) + eps
    u     = sum_i Hpre[i] X[i]                                 (``pre_mix``)
    X'[i] = sum_j M[i, j] X[j] + Hpost[i] F(u)                 (``post_mix``)

Three pure functions a block calls around its sublayer. The coefficients and
the pre-mix are ``jax.numpy`` and their gradients JAX's own; the post-mix,
where XLA reads the stream once an OUTPUT stream, is a pair of Pallas kernels
on the TPU, whatever the mesh and the row count (``post_mix``).

Layout. The stream is carried FLAT, ``[..., n * C]`` with stream ``i`` the
channels ``[i C, (i + 1) C)``: that is ``vec(X)`` as the norm and ``phi`` read
it, each stream is a lane-aligned slice, and no array has ``n`` = 4 rows on
the tiled minor-two dimensions (a bf16 ``[T, 4, C]`` pads each token's 4 rows
to a tile of 16). The coefficients are float32 with TOKENS ON THE MINOR AXIS,
``[n, T]`` and ``[n, n, T]``: the Sinkhorn rounds are then elementwise over
full vector registers (a ``[T, n, n]`` array would use 4 of a register's 128
lanes), and the row and column sums are adds of ``n`` slices, which fuse.
The two mixes multiply the stream in float32 and write it back in its type.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.ops.platform_check import is_tpu_platform, kernel_axes

F32 = jnp.float32


class Coefficients(NamedTuple):
    pre: jnp.ndarray  # [n, T] float32
    post: jnp.ndarray  # [n, T]
    res: jnp.ndarray  # [n, n, T]: res[i, j] takes stream j into stream i


def n_coefficients(n: int) -> int:
    return n + n + n * n


def sinkhorn(m: jnp.ndarray, iters: int, eps: float) -> jnp.ndarray:
    """``m`` [n, n, T] positive -> rows (axis 1) then columns (axis 0)
    normalised, ``iters`` times. The sums are adds of the ``n`` slices."""
    n = m.shape[0]
    for _ in range(iters):
        m = m / (sum(m[:, j] for j in range(n))[:, None] + eps)
        m = m / (sum(m[i] for i in range(n))[None] + eps)
    return m


def coefficients(
    x: jnp.ndarray,  # [..., n * C]
    phi: jnp.ndarray,  # [n * C, n + n + n^2]
    b: jnp.ndarray,  # [n + n + n^2]
    alpha: jnp.ndarray,  # [3]: pre, post, res
    *,
    n: int,
    norm_eps: float,
    sinkhorn_iters: int,
    sinkhorn_eps: float,
    clamp: tuple[float, float],
) -> Coefficients:
    xt = x.reshape(-1, x.shape[-1])
    # xhat @ phi = (x @ phi) / rms(x): the product takes the stream as it is
    # stored and accumulates in float32, the norm scales 24 numbers a token
    raw = jax.lax.dot_general(
        phi.astype(x.dtype), xt, (((0,), (1,)), ((), ())), preferred_element_type=F32
    )  # [n + n + n^2, T]
    x32 = xt.astype(F32)
    inv_rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1) + norm_eps)  # [T]
    m = raw * inv_rms[None, :]
    a, b = alpha.astype(F32), b.astype(F32)[:, None]
    pre = jax.nn.sigmoid(a[0] * m[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[n : 2 * n] + b[n : 2 * n])
    r = jnp.clip(a[2] * m[2 * n :] + b[2 * n :], clamp[0], clamp[1])
    res = sinkhorn(jnp.exp(r).reshape(n, n, -1), sinkhorn_iters, sinkhorn_eps)
    return Coefficients(pre, post, res)


def streams(x: jnp.ndarray, n: int) -> list[jnp.ndarray]:
    """The ``n`` streams of a flat ``[..., n * C]`` stream, each in float32."""
    c = x.shape[-1] // n
    return [x[..., i * c : (i + 1) * c].astype(F32) for i in range(n)]


def _per_token(h: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """A coefficient row [T] against ``like`` [..., C]."""
    return h.reshape(*like.shape[:-1], 1)


@jax.custom_vjp
def _materialised(u):
    return jax.lax.optimization_barrier(u)


_materialised.defvjp(lambda u: (jax.lax.optimization_barrier(u), None), lambda _, g: (g,))


def pre_mix(x: jnp.ndarray, pre: jnp.ndarray) -> jnp.ndarray:
    """``x`` [..., n * C], ``pre`` [n, T] -> the sublayer's input [..., C].
    The result leaves through an optimisation barrier (forward only; the
    cotangent passes untouched): without it XLA fuses the four-stream read
    into the sublayer's first op, the norm of ``u``, and the pass over the
    stream runs under the sublayer's name, out of sight of whatever reads
    the residual path's time (the caller's ``mhc_pre`` scope). ``u`` is
    written once either way; the norm reads it back."""
    xs = streams(x, pre.shape[0])
    u = sum(_per_token(pre[i], xs[i]) * xs[i] for i in range(len(xs)))
    return _materialised(u.astype(x.dtype))


def _post_mix_jnp(x, y, post, res):
    n = post.shape[0]
    xs, y32 = streams(x, n), y.astype(F32)
    out = [
        sum(_per_token(res[i, j], y32) * xs[j] for j in range(n)) + _per_token(post[i], y32) * y32
        for i in range(n)
    ]
    return jnp.concatenate(out, axis=-1).astype(x.dtype)


# -- the post-mix as a kernel --------------------------------------------------
# XLA computes the n output streams in n fusions, each reading all n input
# streams (measured on the chip at [8192, 4 x 3584]: 1.76 ms forward, 5.0
# forward + backward, against 0.65 / 1.7 of HBM time for what must move). The
# kernels hold a tile of tokens' whole rows: every stream is read once.

_TOKEN_TILES = (64, 32, 16)  # rows a grid step; a [64, 4 x 3584] bf16 block is 1.8 MB
_LANE_CHUNK = 512  # channels taken together inside a step (keeps the live float32 small)
_COEF_LANES = 128  # the per-token coefficients, padded to a lane row: [post (n), res (n x n), 0...]
# the backward holds x, the cotangent and dx double-buffered (11 MB at 64 rows
# of 4 x 3584) beside its float32 chunks: over the 16 MiB default scoped
# stack once XLA also places a small operand in VMEM (refused on the chip at
# 16.8 MB), so both calls ask for their own limit (a v5e core has 128 MiB)
_VMEM_LIMIT = 64 * 1024 * 1024


def _interpret_requested() -> bool:
    return os.environ.get("AUTOMODEL_MHC_INTERPRET", "0") == "1"


def _chunks(C: int):
    return [(c0, min(_LANE_CHUNK, C - c0)) for c0 in range(0, C, _LANE_CHUNK)]


def _post_fwd_kernel(x_ref, y_ref, c_ref, o_ref, *, n: int, C: int):
    c = c_ref[...]
    for c0, w in _chunks(C):
        y = y_ref[:, c0:c0 + w].astype(F32)
        xs = [x_ref[:, j * C + c0:j * C + c0 + w].astype(F32) for j in range(n)]
        for i in range(n):
            acc = c[:, i:i + 1] * y
            for j in range(n):
                k = n + i * n + j
                acc = acc + c[:, k:k + 1] * xs[j]
            o_ref[:, i * C + c0:i * C + c0 + w] = acc.astype(o_ref.dtype)


def _post_bwd_kernel(x_ref, y_ref, c_ref, g_ref, dx_ref, dy_ref, dc_ref, *, n: int, C: int):
    c = c_ref[...]
    sums = [jnp.zeros((c.shape[0], 1), F32) for _ in range(n + n * n)]
    for c0, w in _chunks(C):
        y = y_ref[:, c0:c0 + w].astype(F32)
        xs = [x_ref[:, j * C + c0:j * C + c0 + w].astype(F32) for j in range(n)]
        gs = [g_ref[:, i * C + c0:i * C + c0 + w].astype(F32) for i in range(n)]
        dy = c[:, 0:1] * gs[0]
        for i in range(1, n):
            dy = dy + c[:, i:i + 1] * gs[i]
        dy_ref[:, c0:c0 + w] = dy.astype(dy_ref.dtype)
        for j in range(n):
            dx = c[:, n + j:n + j + 1] * gs[0]
            for i in range(1, n):
                k = n + i * n + j
                dx = dx + c[:, k:k + 1] * gs[i]
            dx_ref[:, j * C + c0:j * C + c0 + w] = dx.astype(dx_ref.dtype)
        for i in range(n):
            sums[i] = sums[i] + jnp.sum(gs[i] * y, axis=-1, keepdims=True)
            for j in range(n):
                k = n + i * n + j
                sums[k] = sums[k] + jnp.sum(gs[i] * xs[j], axis=-1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, dc_ref.shape, 1)
    dc = jnp.zeros(dc_ref.shape, F32)
    for k, v in enumerate(sums):
        dc = jnp.where(lane == k, v, dc)
    dc_ref[...] = dc


def _rows(T: int) -> int:
    """Rows a grid step, for ``T`` rows that are a multiple of the smallest tile."""
    return next(t for t in _TOKEN_TILES if T % t == 0)


def _coef_rows(post, res):
    """[n, T], [n, n, T] -> [T, 128]: a token's coefficients on one lane row."""
    n, T = post.shape
    c = jnp.concatenate([post, res.reshape(n * n, T)], axis=0).T
    return jnp.pad(c, ((0, 0), (0, _COEF_LANES - c.shape[1])))


def _post_call(kernel, name, ins, outs, n, C, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = ins[0].shape[0]
    tm = _rows(T)
    spec = lambda a: pl.BlockSpec((tm, a.shape[1]), lambda t: (t, 0))
    return pl.pallas_call(
        functools.partial(kernel, n=n, C=C),
        grid=(T // tm,),
        in_specs=[spec(a) for a in ins],
        out_specs=[spec(a) for a in outs],
        out_shape=outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _post_rows(x, y, c, n, interpret):
    """Rows ``x`` [T, n * C], ``y`` [T, C], ``c`` [T, 128] -> [T, n * C]."""
    return _post_rows_fwd(x, y, c, n, interpret)[0]


def _post_rows_fwd(x, y, c, n, interpret):
    (out,) = _post_call(_post_fwd_kernel, "mhc_post_fwd", [x, y, c],
                        [jax.ShapeDtypeStruct(x.shape, x.dtype)], n, y.shape[1], interpret)
    return out, (x, y, c)


def _post_rows_bwd(n, interpret, saved, g):
    x, y, c = saved
    dx, dy, dc = _post_call(
        _post_bwd_kernel, "mhc_post_bwd", [x, y, c, g],
        [jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(y.shape, y.dtype),
         jax.ShapeDtypeStruct(c.shape, F32)], n, y.shape[1], interpret)
    # lanes past n + n^2 hold no coefficient: the kernel leaves their gradient zero
    return dx, dy, dc


_post_rows.defvjp(_post_rows_fwd, _post_rows_bwd)


def _post_mix_kernels(x, y, post, res, interpret):
    """The kernels on one device's tokens: rows padded to a tile (a padded
    row's stream, output and coefficients are zero), the pad sliced off."""
    n, C = post.shape[0], y.shape[-1]
    x2, y2, c = x.reshape(-1, n * C), y.reshape(-1, C), _coef_rows(post, res)
    T = x2.shape[0]
    pad = (-T) % _TOKEN_TILES[-1]
    if pad:
        x2, y2, c = (jnp.pad(a, ((0, pad), (0, 0))) for a in (x2, y2, c))
    return _post_rows(x2, y2, c, n, interpret)[:T].reshape(x.shape)


def _post_shard_map(mesh_ctx, x, y, post, res, interpret):
    """The kernels per device block (GSPMD cannot partition a Mosaic call):
    a token's mix is its own, so batch over the data axes and the sequence
    over ``cp``, as the stream is already laid out; channels whole."""
    from jax.sharding import PartitionSpec as P

    from automodel_tpu.ops.platform_check import kernel_shard_map, sharded_axes

    B, S = x.shape[:2]
    batch = sharded_axes(mesh_ctx, "batch", (B,), "hyper-connection batch")
    seq = sharded_axes(mesh_ctx, "seq", (S,), "hyper-connection sequence")
    rows, coef = P(batch, seq, None), P(None, batch, seq)

    def block(x, y, post, res):
        n = post.shape[0]
        return _post_mix_kernels(x, y, post.reshape(n, -1), res.reshape(n, n, -1), interpret)

    n = post.shape[0]
    return kernel_shard_map(mesh_ctx, block, (rows, rows, coef, P(None, None, batch, seq)), rows)(
        x, y, post.reshape(n, B, S), res.reshape(n, n, B, S))


def post_mix(x: jnp.ndarray, y: jnp.ndarray, post: jnp.ndarray, res: jnp.ndarray, *,
             platform: Optional[str] = None, mesh_ctx=None) -> jnp.ndarray:
    """``x`` [..., n * C], the sublayer's output ``y`` [..., C] -> the next
    stream [..., n * C]. On the TPU two Pallas kernels (forward; the
    backward's ``dx``, ``dy`` and the 20 coefficient gradients from one read of
    ``x``, ``y`` and the cotangent), under a ``shard_map`` on a mesh of several
    devices (the stream is then ``[B, S, n * C]``); ``AUTOMODEL_MHC_INTERPRET=1``
    runs the same kernels interpreted (the CPU tests). Off the TPU the same
    sums in ``jax.numpy``. The TPU has no second path: a width the kernels
    cannot take is refused."""
    n, C = post.shape[0], y.shape[-1]
    interpret = _interpret_requested()
    if not (interpret or is_tpu_platform(platform)):
        return _post_mix_jnp(x, y, post, res)
    if C % 128 or n + n * n > _COEF_LANES:
        raise ValueError(
            f"hyper-connection post-mix kernels: hidden size {C} must be a multiple of 128 "
            f"and n + n^2 = {n + n * n} at most {_COEF_LANES}")
    if kernel_axes(mesh_ctx) is None:
        return _post_mix_kernels(x, y, post, res, interpret)
    return _post_shard_map(mesh_ctx, x, y, post, res, interpret)


def res_row_error(res: jnp.ndarray) -> jnp.ndarray:
    """The largest ``|row sum - 1|`` of any token's ``Hres`` (the columns were
    normalised last): whether the rounds converged at these weights."""
    rows = sum(res[:, j] for j in range(res.shape[1]))
    return jnp.max(jnp.abs(jax.lax.stop_gradient(rows) - 1.0))
