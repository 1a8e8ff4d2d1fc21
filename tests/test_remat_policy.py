"""What a checkpoint policy keeps of the attention kernel (models/common/
stacking.remat_wrap, ops/attention.SPLASH_RESIDUAL_NAME): splash's ``out``
and ``logsumexp`` carry a name that every recomputing policy keeps, so a
layer's backward holds the dkv and dq kernels and NOT a second forward
kernel. The mechanism is static (every attention layer under a policy, or
none), so the jaxpr of the gradient pins it.

Runs the real splash kernel through the pallas interpreter on the CPU
(AUTOMODEL_FLASH_INTERPRET=1) at tiny shapes.
"""

from pathlib import Path

import numpy as np
import pytest
import yaml

import jax
import jax.numpy as jnp
from jax._src import core

import automodel_tpu.ops.attention as attn_mod
from automodel_tpu.models.common import stacking
from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.registry import resolve_architecture
from automodel_tpu.training.train_step import make_causal_lm_loss

ROOT = Path(__file__).resolve().parent.parent
RECOMPUTING = ("full", "full_save_dispatch", "selective")


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("AUTOMODEL_FLASH_INTERPRET", "1")


def _drop_the_name(monkeypatch):
    """The stack as it was before the name: no policy keeps splash's
    residuals (a name no policy keeps is an identity)."""
    monkeypatch.setattr(stacking, "_KEPT_NAMES", {
        remat: tuple(n for n in names if n != attn_mod.SPLASH_RESIDUAL_NAME)
        for remat, names in stacking._KEPT_NAMES.items()
    })


def splash_calls(jaxpr, kind: str) -> int:
    """``pallas_call``s named ``splash_mha_<kind>*``, every nested jaxpr
    included; a scan's body counts once."""
    return sum(
        (eqn.primitive.name == "pallas_call"
         and eqn.params["name"].startswith(f"splash_mha_{kind}"))
        + sum(splash_calls(sub, kind) for sub in core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns
    )


def _stack(*, dqk=16, dv=16, heads=2, kv_heads=2, dtype=jnp.float32):
    """(params, x, layer_fn): two blocks of projections, attention and an
    output product around a residual, in run_layer_stack's form."""
    rng = np.random.default_rng(0)
    layers, seq, hidden = 2, 128, 32

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2]), dtype)

    params = {
        "wq": w(layers, hidden, heads * dqk), "wk": w(layers, hidden, kv_heads * dqk),
        "wv": w(layers, hidden, kv_heads * dv), "wo": w(layers, heads * dv, hidden),
    }
    x = jnp.asarray(rng.standard_normal((1, seq, hidden)), dtype)

    def layer_fn(h, xs):
        lp, _ = xs
        b, s, _ = h.shape
        q = (h @ lp["wq"]).reshape(b, s, heads, dqk)
        k = (h @ lp["wk"]).reshape(b, s, kv_heads, dqk)
        v = (h @ lp["wv"]).reshape(b, s, kv_heads, dv)
        o = attn_mod.flash(q, k, v).reshape(b, s, heads * dv)
        return h + jnp.tanh(o @ lp["wo"]), None

    return params, x, layer_fn


def _loss(layer_fn, remat, scan_layers):
    def loss(params, x):
        h, _ = stacking.run_layer_stack(
            layer_fn, x, params, None,
            scan_layers=scan_layers, remat=remat, num_layers=2,
        )
        return jnp.mean(jnp.square(h.astype(jnp.float32)))

    return loss


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
@pytest.mark.parametrize("remat", ("none",) + RECOMPUTING)
def test_one_forward_kernel_an_attention_layer(remat, scan_layers):
    params, x, layer_fn = _stack()
    jaxpr = jax.make_jaxpr(jax.grad(_loss(layer_fn, remat, scan_layers)))(params, x).jaxpr
    bodies = 1 if scan_layers else 2  # a scan's body is traced once
    assert splash_calls(jaxpr, "fwd") == bodies
    assert splash_calls(jaxpr, "dkv") == bodies
    assert splash_calls(jaxpr, "dq") == bodies


@pytest.mark.parametrize("remat", RECOMPUTING)
def test_without_the_name_the_forward_kernel_runs_twice(remat, monkeypatch):
    """What this file's first test would read if the name were lost: the
    recompute holds the forward kernel again."""
    _drop_the_name(monkeypatch)
    params, x, layer_fn = _stack()
    jaxpr = jax.make_jaxpr(jax.grad(_loss(layer_fn, remat, True)))(params, x).jaxpr
    assert splash_calls(jaxpr, "fwd") == 2
    assert splash_calls(jaxpr, "dkv") == 1


def _value_and_grads(loss, *args):
    value, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(*args)
    return [np.asarray(a) for a in jax.tree.leaves((value, grads))]


@pytest.mark.parametrize(
    "dqk,dv,heads,kv_heads", [(192, 128, 2, 2), (128, 128, 8, 4)],
    ids=["qk192-v128", "128-gqa-4kv"],
)
@pytest.mark.parametrize("remat", ["full", "selective"])
def test_loss_and_gradients_bit_equal_with_and_without_the_name(
    remat, dqk, dv, heads, kv_heads, monkeypatch
):
    params, x, layer_fn = _stack(dqk=dqk, dv=dv, heads=heads, kv_heads=kv_heads,
                                 dtype=jnp.bfloat16)
    kept = _value_and_grads(_loss(layer_fn, remat, True), params, x)
    _drop_the_name(monkeypatch)
    recomputed = _value_and_grads(_loss(layer_fn, remat, True), params, x)
    assert len(kept) == len(recomputed) == 6  # loss, four weights, the input
    for a, b in zip(kept, recomputed):
        assert np.isfinite(a.astype(np.float32)).all()
        np.testing.assert_array_equal(a, b)


# examples/llm_pretrain/<recipe>.yaml's model at its widths, cut in depth to
# one block of each kind so that the case costs seconds: (keys changed,
# attention blocks left)
TINY = {
    "kimi_linear_tiny_cpu": (
        {"num_hidden_layers": 2,  # KDA + dense MLP, latent attention + experts
         "linear_attn_config": {"kda_layers": [1], "full_attn_layers": [2]}}, 1),
    # the layer's block and the MTP module's; 2 Sinkhorn rounds of the 20 (the
    # residual path's, not attention's: 8 s of compile a gradient)
    "xing4_tiny_cpu": ({"num_hidden_layers": 1, "hc_sinkhorn_iters": 2}, 2),
}


@pytest.mark.parametrize("recipe", sorted(TINY))
def test_the_tiny_latent_attention_models_take_a_gradient_under_full(recipe, monkeypatch):
    """With ``attn: flash`` the latent block's q/k are wider than its v (24 /
    16 here, 192 / 128 as published); the gradient under ``full`` is finite
    and is the gradient with nothing kept."""
    cfg = yaml.safe_load((ROOT / "examples" / "llm_pretrain" / f"{recipe}.yaml").read_text())
    cut, attention_blocks = TINY[recipe]
    hf = cfg["model"]["hf_config"]
    for key, value in cut.items():
        hf[key] = dict(hf[key], **value) if isinstance(value, dict) else value
    backend = BackendConfig(**dict(cfg["model"]["backend"], attn="flash"))
    assert backend.remat == "full"
    model, _ = resolve_architecture(hf)(hf, backend)
    params = jax.jit(model.init)(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (1, cfg["dataset"]["seq_length"]), 0, hf["vocab_size"])

    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
    step_loss = make_causal_lm_loss(model, loss="fused_linear_ce", num_chunks=2)

    def loss(params):  # the step's own, the MTP module's pass included
        loss_sum, n, *_ = step_loss(params, batch)
        return loss_sum / n

    def gradient():
        traced = jax.jit(jax.grad(loss)).trace(params)  # traced once for both
        return splash_calls(traced.jaxpr.jaxpr, "fwd"), traced.lower().compile()(params)

    forward_kernels, kept = gradient()
    assert forward_kernels == attention_blocks
    _drop_the_name(monkeypatch)
    forward_kernels, recomputed = gradient()
    assert forward_kernels == 2 * attention_blocks
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(recomputed)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", ["cond-sliding", "cond-full", "sink"])
def test_the_cond_path_and_a_sink_differentiate_under_full(case):
    """``windowed_attention`` under a checkpoint sees a traced ``is_sliding``
    and branches with ``lax.cond`` between two static-mask kernels; a sink
    adds a per-head logit. Both reach the same ``_splash_flash``: the
    gradient under ``full`` is the unwrapped function's."""
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 256, 2, 16)), jnp.float32) for _ in range(3))
    sinks = jnp.asarray(rng.standard_normal(2), jnp.float32) if case == "sink" else None
    is_sliding = jnp.asarray(case == "cond-sliding")

    def f(q, k, v, is_sliding):
        out = attn_mod.windowed_attention(
            q, k, v, backend="flash", is_sliding=is_sliding, window=128,
            dynamic_window=jnp.where(is_sliding, 128, 256), sinks=sinks,
        )
        return jnp.sum(jnp.square(out))

    grad = lambda fn: jax.jit(jax.grad(fn, argnums=(0, 1, 2)))(q, k, v, is_sliding)
    under_full = grad(stacking.remat_wrap(f, "full"))
    plain = grad(f)
    for a, b in zip(under_full, plain):
        assert np.isfinite(np.asarray(a)).all() and float(jnp.abs(a).max()) > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    # found out, not required (ISSUE 45): the name survives the cond. Each
    # branch's forward kernel is traced once; the cond's partial evaluation
    # hands the named residuals out as the branches' outputs, where the
    # policy sees them. Without the name the recompute traces both again (4).
    jaxpr = jax.make_jaxpr(jax.grad(stacking.remat_wrap(f, "full")))(q, k, v, is_sliding).jaxpr
    assert splash_calls(jaxpr, "fwd") == splash_calls(jaxpr, "dkv") == 2
