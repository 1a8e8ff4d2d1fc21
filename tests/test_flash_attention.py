"""Flash (splash) attention backend: the kernel path must be TAKEN — not
silently fall back to sdpa — for every shape the model zoo produces
(reference universality: components/attention/utils.py:25-65 routes ALL
models through TE fused attention).

Runs the real splash kernel through the pallas interpreter on CPU
(AUTOMODEL_FLASH_INTERPRET=1); numerics are compared against the sdpa
reference. TPU-hardware parity (incl. grads and bf16) is exercised by the
benchmark recipe on the real chip.
"""

import numpy as np
import pytest


import jax
import jax.numpy as jnp

import automodel_tpu.ops.attention as attn_mod
from automodel_tpu.ops.attention import sdpa, windowed_attention


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")


@pytest.fixture
def no_fallback(monkeypatch):
    """Make any sdpa fallback inside flash() an ERROR."""

    def boom(*a, **k):
        raise AssertionError("flash fell back to sdpa — kernel path not taken")

    monkeypatch.setattr(attn_mod, "sdpa", boom)


def _mk(b=1, s=256, n=2, nkv=1, h=64, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, s, n, h)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, nkv, h)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, nkv, h)), jnp.float32)
    return q, k, v


def _close(a, b, tol=2e-2):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    rel = np.abs(a - b).max() / max(1e-9, np.abs(b).max())
    assert rel < tol, f"rel err {rel}"


def test_flash_kernel_taken_causal_gqa(no_fallback):
    q, k, v = _mk()
    out = attn_mod.flash(q, k, v)
    _close(out, sdpa(q, k, v))


def test_flash_kernel_taken_gemma2_shape(no_fallback):
    """Sliding window + logit soft cap + non-1/sqrt(h) scale — the exact
    combination that previously forced O(S^2) sdpa on TPU."""
    q, k, v = _mk(h=64)
    out = attn_mod.flash(
        q, k, v, sliding_window=64, logits_soft_cap=50.0, scale=0.0884
    )
    _close(out, sdpa(q, k, v, sliding_window=64, logits_soft_cap=50.0, scale=0.0884))


def test_flash_kernel_taken_gpt_oss_sinks(no_fallback):
    """Sliding window + attention sinks (gpt-oss)."""
    q, k, v = _mk(n=2, nkv=1, h=64)
    sinks = jnp.asarray(np.random.default_rng(1).standard_normal(2), jnp.float32)
    out = attn_mod.flash(q, k, v, sliding_window=64, sinks=sinks)
    _close(out, sdpa(q, k, v, sliding_window=64, sinks=sinks))


def test_flash_kernel_taken_unaligned_seq(no_fallback):
    """S not a multiple of 128 pads inside the wrapper instead of falling
    back (a 4097-token sequence must not lose the fused kernel)."""
    q, k, v = _mk(s=200)
    out = attn_mod.flash(q, k, v)
    assert out.shape == q.shape
    _close(out, sdpa(q, k, v))


def test_flash_kernel_taken_segments_padded(no_fallback):
    """Packed segments + internal padding compose."""
    q, k, v = _mk(s=200)
    seg = jnp.asarray(np.repeat([0, 1], 100)[None, :], jnp.int32)
    out = attn_mod.flash(q, k, v, segment_ids=seg)
    _close(out, sdpa(q, k, v, segment_ids=seg))


def test_windowed_attention_cond_branches(no_fallback):
    """The scanned mixed-layer helper picks the right static mask per branch
    while staying on the kernel."""
    q, k, v = _mk()
    # static flags (unrolled layer loop): branch picked at trace time
    sliding = windowed_attention(
        q, k, v, backend="flash", is_sliding=np.bool_(True),
        window=64, dynamic_window=np.int32(64),
    )
    full = windowed_attention(
        q, k, v, backend="flash", is_sliding=np.bool_(False),
        window=64, dynamic_window=np.int32(256),
    )
    _close(sliding, sdpa(q, k, v, sliding_window=64))
    _close(full, sdpa(q, k, v))
    assert np.abs(np.asarray(sliding) - np.asarray(full)).max() > 1e-3

    # TRACED flag (scanned layer stack): the lax.cond path must route the
    # same way when the predicate is a Tracer, as in gemma/gpt-oss scans
    jitted = jax.jit(
        lambda flag: windowed_attention(
            q, k, v, backend="flash", is_sliding=flag,
            window=64, dynamic_window=jnp.where(flag, 64, 256),
        )
    )
    _close(jitted(jnp.asarray(True)), sdpa(q, k, v, sliding_window=64))
    _close(jitted(jnp.asarray(False)), sdpa(q, k, v))


def test_flash_grads_match_sdpa():
    q, k, v = _mk()
    ct = jnp.asarray(np.random.default_rng(2).standard_normal(q.shape), jnp.float32)

    def loss(fn):
        return jax.grad(
            lambda q, k, v: (fn(q, k, v, sliding_window=64) * ct).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    for a, b in zip(loss(attn_mod.flash), loss(sdpa)):
        _close(a, b, tol=3e-2)


def test_flash_off_tpu_falls_back_loudly(monkeypatch, caplog):
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "0")
    attn_mod._warned_fallback.clear()
    q, k, v = _mk(s=64)
    import logging

    with caplog.at_level(logging.WARNING, logger="automodel_tpu.ops.attention"):
        out = attn_mod.flash(q, k, v)
    assert any("falling back" in r.message for r in caplog.records)
    _close(out, sdpa(q, k, v))
