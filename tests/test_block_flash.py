"""Non-ring single-chip entry over the blockwise flash kernels
(ops/ring_flash.flash_attention) vs sdpa, and the per-shape autotune
routing in ops/attention.flash.

Interpret mode executes the REAL kernel code on CPU.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops import autotune
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


def test_flash_routes_block_backend_from_autotune_table(
    tmp_path, monkeypatch
):
    """A per-chip table entry with backend=block routes ops/attention.flash
    (the model-facing entry point) onto the in-tree kernels — at head_dim 64
    + window 128 this is the shape the library splash kernel on this build
    cannot even run, so parity here proves the race wiring end-to-end."""
    from automodel_tpu.ops.attention import flash

    table = {
        "format_version": 1,
        "chips": {
            autotune.chip_key(): {
                autotune.attn_key(64, 128, True): {
                    "backend": "block", "block_q": 128, "block_kv": 128,
                }
            }
        },
    }
    path = tmp_path / "table.json"
    path.write_text(json.dumps(table))
    monkeypatch.setenv(autotune.ENV_TABLE, str(path))
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")
    autotune.clear_cache()
    try:
        rng = np.random.default_rng(3)
        q, k, v = _qkv(rng, 1, 256, 2, 1, 64)
        out = flash(q, k, v, causal=True, sliding_window=128)
        ref = sdpa(q, k, v, causal=True, sliding_window=128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    finally:
        autotune.clear_cache()


def test_flash_without_table_entry_unchanged(monkeypatch):
    """No table entry for the shape → flash keeps its pre-table behavior
    (splash path / sdpa fallback off-TPU) — the committed defaults carry
    only TPU chip kinds, so CPU flows are untouched."""
    from automodel_tpu.ops.attention import _autotune_entry

    autotune.clear_cache()
    monkeypatch.delenv(autotune.ENV_TABLE, raising=False)
    assert _autotune_entry(31337, None, True) is None
    # committed defaults must never carry entries for the CPU chip kind
    assert autotune.lookup(autotune.attn_key(64, 128, True), chip="cpu") is None
