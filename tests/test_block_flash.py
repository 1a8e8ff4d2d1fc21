"""Non-ring single-chip entry over the blockwise flash kernels
(ops/ring_flash.flash_attention) vs sdpa, and the per-shape splash blocks
of ops/attention.flash.

Interpret mode executes the REAL kernel code on CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops.attention import sdpa
from automodel_tpu.ops.ring_flash import flash_attention


def _qkv(rng, B, S, N, NKV, H, dtype=jnp.float32):
    mk = lambda n: jnp.asarray(rng.normal(size=(B, S, n, H)), dtype)
    return mk(N), mk(NKV), mk(NKV)


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("use_sinks", [False, True])
def test_block_flash_parity(head_dim, window, use_sinks):
    """Causal / sliding-window / sinks at head_dim ∈ {64, 128}: forward and
    all grads (incl. d_sinks) vs the sdpa reference."""
    rng = np.random.default_rng(0)
    B, S, N, NKV = 2, 256, 4, 2
    q, k, v = _qkv(rng, B, S, N, NKV, head_dim)
    sinks = (
        jnp.asarray(rng.normal(size=(N,)), jnp.float32) if use_sinks else None
    )

    def f_new(q, k, v, s):
        return flash_attention(
            q, k, v, causal=True, sliding_window=window, sinks=s,
            interpret=True,
        )

    def f_ref(q, k, v, s):
        return sdpa(q, k, v, causal=True, sliding_window=window, sinks=s)

    np.testing.assert_allclose(
        np.asarray(f_new(q, k, v, sinks)), np.asarray(f_ref(q, k, v, sinks)),
        atol=2e-4,
    )
    argnums = (0, 1, 2, 3) if use_sinks else (0, 1, 2)
    args = (q, k, v) + ((sinks,) if use_sinks else ())
    g1 = jax.grad(
        lambda *a: (f_new(*(a + (() if use_sinks else (None,)))) ** 2).sum(),
        argnums=argnums,
    )(*args)
    g2 = jax.grad(
        lambda *a: (f_ref(*(a + (() if use_sinks else (None,)))) ** 2).sum(),
        argnums=argnums,
    )(*args)
    for name, a, b in zip(("dq", "dk", "dv", "dsinks"), g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-3, err_msg=name
        )


def test_block_flash_segment_ids_parity():
    rng = np.random.default_rng(1)
    B, S, N, NKV, H = 2, 256, 4, 2, 64
    q, k, v = _qkv(rng, B, S, N, NKV, H)
    half = jnp.asarray(
        rng.integers(0, 3, size=(B, 1)).repeat(S // 2, 1), jnp.int32
    )
    seg = jnp.concatenate([half, half + 1], axis=1)
    out = flash_attention(q, k, v, causal=True, segment_ids=seg, interpret=True)
    ref = sdpa(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_block_flash_unpadded_seq():
    """A non-128-multiple sequence pads internally; padded keys must never
    be attended and the output slice must match sdpa exactly."""
    rng = np.random.default_rng(2)
    B, S, N, NKV, H = 1, 200, 2, 1, 64
    q, k, v = _qkv(rng, B, S, N, NKV, H)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = sdpa(q, k, v, causal=True)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    g1 = jax.grad(lambda a: (flash_attention(
        a, k, v, causal=True, interpret=True) ** 2).sum())(q)
    g2 = jax.grad(lambda a: (sdpa(a, k, v, causal=True) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=5e-3)


@pytest.mark.parametrize(
    "head_dim,window,given,want",
    [
        (128, None, {}, (512, 512)),
        (64, None, {}, (256, 512)),
        (64, 128, {}, (256, 128)),
        (64, None, {"block_q": 128}, (128, 512)),  # an explicit block wins
    ],
)
def test_flash_picks_splash_blocks_by_shape(
    monkeypatch, head_dim, window, given, want
):
    """ops/attention.flash hands splash the blocks of its shape rule
    (head_dim, window), and an explicit attn_block_q / attn_block_kv keeps
    the caller's blocks."""
    from automodel_tpu.ops import attention

    seen = {}

    def splash(q, k, v, segment_ids, sinks, *, block_q, block_kv, **kw):
        seen["blocks"] = (block_q, block_kv)
        return q

    monkeypatch.setattr(attention, "_splash_flash", splash)
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    q, k, v = _qkv(np.random.default_rng(3), 1, 256, 2, 1, head_dim)
    attention.flash(q, k, v, causal=True, sliding_window=window, **given)
    assert seen["blocks"] == want
