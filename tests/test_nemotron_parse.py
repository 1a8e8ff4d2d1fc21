"""Nemotron-Parse: mBART decoder parity vs HF transformers (the decoder is
stock MBartDecoderLayer in the reference, so torch is a real oracle here),
neck conv↔linear equivalence vs torch convs, the coordinate-weighted loss
vs a direct formulation, shift_tokens_right semantics, adapter round-trip,
and an end-to-end train smoke. Reference:
components/models/nemotron_parse/{model.py,nemotron_parse_loss.py}.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.nemotron_parse import (
    NemotronParseConfig,
    NemotronParseForConditionalGeneration,
    NemotronParseStateDictAdapter,
    RadioBackboneConfig,
    shift_tokens_right,
)

FP32 = BackendConfig(attn="sdpa", param_dtype="float32", compute_dtype="float32")


def _tiny_cfg():
    return NemotronParseConfig(
        vision=RadioBackboneConfig(
            patch_size=4, hidden_size=24, summary_width=72, num_layers=2,
            num_heads=2, max_grid=16,
        ),
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        intermediate_size=64, max_positions=64,
        class_token_start_idx=100,
    )


@pytest.fixture(scope="module")
def built():
    model = NemotronParseForConditionalGeneration(_tiny_cfg(), FP32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return model, params


def test_decoder_parity_with_hf_mbart():
    """Load HF MBartDecoder weights through the adapter's decoder plans and
    require identical hidden states (self-attn + cross-attn + gelu FFN +
    the +2 position offset + layernorm_embedding/final layer_norm)."""
    import torch
    from transformers.models.mbart.configuration_mbart import MBartConfig
    from transformers.models.mbart.modeling_mbart import MBartDecoder

    torch.manual_seed(0)
    hf_cfg = MBartConfig(
        vocab_size=128, d_model=32, decoder_layers=2, decoder_attention_heads=4,
        decoder_ffn_dim=64, max_position_embeddings=64, activation_function="gelu",
        dropout=0.0, attention_dropout=0.0, activation_dropout=0.0,
        scale_embedding=False,
    )
    dec = MBartDecoder(hf_cfg).eval()

    cfg = _tiny_cfg()
    model = NemotronParseForConditionalGeneration(cfg, FP32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1))

    # map HF weights into the native decoder subtree via the adapter plans
    sd = {("decoder." + k): v.detach().numpy() for k, v in dec.state_dict().items()}
    adapter = NemotronParseStateDictAdapter(cfg)
    from automodel_tpu.checkpoint.hf_io import assemble_tree

    def plans_subset():
        for path, key, tr, _ in adapter._decoder_flat_plans():
            if path[0] == "decoder":
                yield path, (tr(sd[key]) if tr else sd[key])
        from automodel_tpu.checkpoint.hf_io import LazyStacked

        for sub, hf_sub, tr in adapter._layer_plans():
            vals = [sd[f"decoder.layers.{i}.{hf_sub}"] for i in range(cfg.num_layers)]
            yield ("decoder", "layers", *sub), np.stack(
                [np.ascontiguousarray(v.T) if tr else v for v in vals]
            )

    loaded = assemble_tree(plans_subset())
    params["decoder"] = jax.tree.map(jnp.asarray, loaded["decoder"])

    rng = np.random.default_rng(0)
    ids = rng.integers(4, 128, size=(2, 9))
    enc = rng.normal(size=(2, 5, 32)).astype(np.float32)
    with torch.no_grad():
        ref = dec(
            input_ids=torch.tensor(ids),
            encoder_hidden_states=torch.tensor(enc),
        ).last_hidden_state.numpy()

    from automodel_tpu.models.nemotron_parse.model import decoder_forward

    got = np.asarray(
        decoder_forward(cfg, FP32, params["decoder"], jnp.asarray(ids), jnp.asarray(enc))
    )
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-3)


def test_neck_matches_torch_convs():
    """The neck's linear formulation == the reference's Conv1d/Conv2d."""
    import torch

    torch.manual_seed(1)
    cfg = RadioBackboneConfig(hidden_size=24, summary_width=72)
    h, w = 2, 8
    B, N = 2, h * w
    conv1 = torch.nn.Conv1d(24, 1024, 1)
    conv2 = torch.nn.Conv2d(1024, 1024, (1, 4), stride=(1, 4), bias=False)
    ln = lambda: torch.nn.LayerNorm(1024, eps=1e-6)
    ln1, ln2, ln3 = ln(), ln(), ln()
    sum_proj = torch.nn.Linear(72, 1024)

    feats = torch.randn(B, N, 24)
    summary = torch.randn(B, 72)
    with torch.no_grad():
        out = conv1(feats.permute(0, 2, 1)).permute(0, 2, 1)
        out = ln1(out)
        out = out.permute(0, 2, 1).reshape(B, 1024, h, w)
        out = conv2(out)
        out = out.reshape(B, 1024, -1).permute(0, 2, 1)
        out = ln2(out)
        s = ln3(sum_proj(summary))
        ref = torch.cat([out, s[:, None, :]], dim=1).numpy()

    from automodel_tpu.models.nemotron_parse.state_dict_adapter import _conv1, _conv2
    from automodel_tpu.models.nemotron_parse.vision import neck_forward

    np_params = {
        "conv1": {"kernel": _conv1(conv1.weight.detach().numpy()),
                  "bias": conv1.bias.detach().numpy()},
        "layer_norm1": {"scale": ln1.weight.detach().numpy(), "bias": ln1.bias.detach().numpy()},
        "conv2": {"kernel": _conv2(conv2.weight.detach().numpy())},
        "layer_norm2": {"scale": ln2.weight.detach().numpy(), "bias": ln2.bias.detach().numpy()},
        "sum_proj": {"kernel": sum_proj.weight.detach().numpy().T,
                     "bias": sum_proj.bias.detach().numpy()},
        "layer_norm3": {"scale": ln3.weight.detach().numpy(), "bias": ln3.bias.detach().numpy()},
    }
    got = np.asarray(neck_forward(
        cfg, jax.tree.map(jnp.asarray, np_params),
        jnp.asarray(feats.numpy()), jnp.asarray(summary.numpy()), (h, w),
    ))
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-3)


def test_coordinate_weighted_loss():
    from automodel_tpu.ops.losses import build_loss

    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(2, 6, 128)), jnp.float32)
    labels = np.full((2, 6), -100, np.int32)
    labels[0, :3] = [5, 110, 7]   # one coordinate token (>=100)
    labels[1, :2] = [120, 3]      # one coordinate token
    labels = jnp.asarray(labels)
    loss_fn = build_loss("nemotron_parse", coordinate_weight=10.0,
                         class_token_start_idx=100)
    s, n = loss_fn(logits, labels)
    assert int(n) == 5

    # direct formulation
    lp = jax.nn.log_softmax(logits, axis=-1)
    ref = 0.0
    for b in range(2):
        for t in range(6):
            lb = int(labels[b, t])
            if lb == -100:
                continue
            w = 10.0 if lb >= 100 else 1.0
            ref += -float(lp[b, t, lb]) * w
    np.testing.assert_allclose(float(s), ref, rtol=1e-5)


def test_shift_tokens_right():
    labels = jnp.asarray([[5, 6, 7, -100], [8, -100, -100, -100]], jnp.int32)
    got = np.asarray(shift_tokens_right(labels, pad_token_id=1,
                                        decoder_start_token_id=2))
    np.testing.assert_array_equal(got, [[2, 5, 6, 7], [2, 8, 1, 1]])


def test_adapter_round_trip(built):
    model, params = built
    adapter = NemotronParseStateDictAdapter(model.config)
    params = jax.tree.map(np.asarray, params)
    hf = dict(adapter.to_hf(params))
    w = model.config.hidden_size  # == neck width
    assert "encoder.conv2.weight" in hf
    assert hf["encoder.conv2.weight"].shape == (w, w, 1, 4)
    assert "decoder.layers.1.encoder_attn.out_proj.weight" in hf
    back = adapter.from_hf(lambda k: hf[k], backbone_init=params["vision"]["backbone"])
    for p, v in jax.tree_util.tree_leaves_with_path(params):
        got = back
        for kk in p:
            got = got[kk.key]
        np.testing.assert_allclose(got, v, atol=1e-6, err_msg=str(p))


def test_train_smoke_with_family_loss(built):
    """End-to-end: pixels → backbone → neck → decoder (teacher-forced from
    labels) → logits → the family loss; grads reach every part."""
    model, params = built
    cfg = model.config
    from automodel_tpu.ops.losses import build_loss

    loss_fn = build_loss(model.loss_name, **model.loss_kwargs())
    rng = np.random.default_rng(3)
    h, w = 4, 8
    pix = jnp.asarray(
        rng.normal(size=(2, h * w, cfg.vision.patch_dim)), jnp.float32
    )
    labels = rng.integers(4, 128, size=(2, 10)).astype(np.int32)
    labels[:, -2:] = -100
    labels[0, 1] = 110  # a coordinate token
    labels = jnp.asarray(labels)

    def loss(p):
        logits = model(p, labels=labels, pixel_patches=pix, grid_hw=(h, w))
        s, n = loss_fn(logits, labels)
        return s / jnp.maximum(n, 1)

    val, g = jax.jit(jax.value_and_grad(loss))(params)
    assert bool(jnp.isfinite(val))
    for part in ("vision", "decoder", "lm_head"):
        gn = jax.tree_util.tree_reduce(
            lambda a, x: a + jnp.sum(jnp.abs(x.astype(jnp.float32))), g[part], 0.0
        )
        assert float(gn) > 0, part


def test_registry_dispatch():
    from automodel_tpu.models.registry import resolve_architecture

    hf = {
        "architectures": ["NemotronParseForConditionalGeneration"],
        "model_type": "nemotron_parse",
        "decoder": {"vocab_size": 128, "d_model": 32, "decoder_layers": 2,
                    "decoder_attention_heads": 4, "decoder_ffn_dim": 64},
        "encoder": {"patch_size": 4},
        "max_sequence_length": 64,
    }
    model, adapter = resolve_architecture(hf)(hf, FP32)
    assert isinstance(model, NemotronParseForConditionalGeneration)
    assert model.config.hidden_size == 32
    assert model.loss_name == "nemotron_parse"
