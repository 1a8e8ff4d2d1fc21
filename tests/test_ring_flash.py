"""Pallas ring-flash kernels (ops/ring_flash.py) vs global sdpa.

AUTOMODEL_RING_INTERPRET=1 runs the REAL kernel code through the pallas
interpreter on the CPU mesh — same scheme as the splash/gmm tests. Parity
target: the reference's fused-attention-inside-CP-ring
(components/moe/parallelizer.py:279-297, cp_comm_type="p2p").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from automodel_tpu.ops.attention import sdpa
from automodel_tpu.parallel import cp as cpm


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("AUTOMODEL_RING_INTERPRET", "1")


def _run_ring(mesh, q, k, v, seg, *, window, zigzag):
    inner = functools.partial(
        cpm.ring_attention_shard, axis_name="cp", causal=True,
        sliding_window=window, zigzag=zigzag, platform="cpu",
    )
    spec = P(None, "cp", None, None)
    if seg is not None:
        mapped = shard_map(
            lambda a, b, c, s: inner(a, b, c, segment_ids=s),
            mesh=mesh, in_specs=(spec, spec, spec, P(None, "cp")),
            out_specs=spec, check_vma=False,
        )
        return mapped, (q, k, v, seg)
    mapped = shard_map(
        lambda a, b, c: inner(a, b, c),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )
    return mapped, (q, k, v)


@pytest.mark.parametrize("zigzag", [False, True])
@pytest.mark.parametrize("window", [None, 96])
@pytest.mark.parametrize("use_seg", [False, True])
def test_ring_flash_parity(devices8, zigzag, window, use_seg):
    cp = 4
    mesh = Mesh(np.array(devices8[:cp]), ("cp",))
    rng = np.random.default_rng(0)
    B, S, N, NKV, H = 2, 256, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, N, H)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, NKV, H)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, NKV, H)), jnp.float32)
    seg = None
    if use_seg:
        half = jnp.asarray(
            rng.integers(0, 3, size=(B, 1)).repeat(S // 2, 1), jnp.int32
        )
        seg = jnp.concatenate([half, half + 1], axis=1)

    ref = sdpa(q, k, v, causal=True, segment_ids=seg, sliding_window=window)
    dref = jax.grad(
        lambda q, k, v: (
            sdpa(q, k, v, causal=True, segment_ids=seg, sliding_window=window) ** 2
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)

    qq, kk, vv, ss = q, k, v, seg
    if zigzag:
        qq = cpm.apply_zigzag(q, cp, axis=1)
        kk = cpm.apply_zigzag(k, cp, axis=1)
        vv = cpm.apply_zigzag(v, cp, axis=1)
        ss = cpm.apply_zigzag(seg, cp, axis=1) if use_seg else None
    mapped, args = _run_ring(mesh, qq, kk, vv, ss, window=window, zigzag=zigzag)
    out = jax.jit(mapped)(*args)
    grads = jax.jit(
        jax.grad(lambda *a: (mapped(*a) ** 2).sum(), argnums=(0, 1, 2))
    )(*args)
    if zigzag:
        out = cpm.undo_zigzag(out, cp, axis=1)
        grads = tuple(cpm.undo_zigzag(g, cp, axis=1) for g in grads)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    for g, r in zip(grads, dref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-3)


def test_ring_flash_fully_masked_rows(devices8):
    """First tokens of a fresh segment boundary on a far rank must come out
    zero, not NaN (all-masked guard in the kernel + merge)."""
    cp = 2
    mesh = Mesh(np.array(devices8[:cp]), ("cp",))
    rng = np.random.default_rng(1)
    B, S, N, H = 1, 128, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, N, H)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, N, H)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, N, H)), jnp.float32)
    # every token its own segment → each token only attends to itself
    seg = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    mapped, args = _run_ring(mesh, q, k, v, seg, window=None, zigzag=False)
    out = jax.jit(mapped)(*args)
    assert bool(jnp.isfinite(out).all())
    ref = sdpa(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
