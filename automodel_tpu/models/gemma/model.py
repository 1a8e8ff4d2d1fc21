"""Gemma family (Gemma 2 / Gemma 3 text), TPU-native.

The Gemma architecture differs from llama in ways that need their own layer
function (the reason Gemma2 was *removed* from the generic llama builder):

- zero-centered RMSNorm: `x̂ · (1 + w)`, computed in fp32 then cast
  (modeling_gemma3.py Gemma3RMSNorm);
- sandwich norms: post-attention and post-FFN norms apply to the residual
  BRANCH OUTPUT (llama norms only pre-normalize inputs);
- embeddings scaled by sqrt(hidden_size);
- attention-score and final-logit soft caps (Gemma 2);
- alternating local/global attention (`layer_types`), with PER-TYPE rope
  theta in Gemma 3 (local 10k, global 1M) — expressed as two precomputed
  rope tables and per-layer scanned flags, so the whole stack still runs as
  ONE lax.scan (windows become dynamic mask bounds instead of static mask
  structure);
- query scaled by query_pre_attn_scalar^-0.5 (not head_dim).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
from automodel_tpu.models.common.stacking import run_layer_stack
from automodel_tpu.models.llama.model import (
    ACT_FNS,
    Constrain,
    _dense_init,
    _noop_constrain,
    _proj,
)
from automodel_tpu.ops.attention import windowed_attention
from automodel_tpu.ops.rope import RopeConfig, apply_rope, rope_table


def gemma_rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (normed * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class GemmaConfig(TransformerConfig):
    layer_types: tuple = ()  # "sliding_attention" | "full_attention" per layer
    rope_local_theta: float = 10000.0
    query_pre_attn_scalar: float = 256.0

    @classmethod
    def from_hf(cls, hf_cfg: Any) -> "GemmaConfig":
        get = lambda k, d=None: (
            hf_cfg.get(k, d) if isinstance(hf_cfg, dict) else getattr(hf_cfg, k, d)
        )
        if get("text_config") is not None:  # multimodal wrapper config
            hf_cfg = get("text_config")
            get = lambda k, d=None: (
                hf_cfg.get(k, d) if isinstance(hf_cfg, dict) else getattr(hf_cfg, k, d)
            )
        model_type = get("model_type", "gemma2")
        base = TransformerConfig.from_hf(hf_cfg)
        L = base.num_layers
        lt = get("layer_types")
        if lt is None:
            if model_type == "gemma2":
                # gemma2: even layers sliding, odd full
                lt = [
                    "sliding_attention" if i % 2 == 0 else "full_attention"
                    for i in range(L)
                ]
            else:  # gemma3: 5 local : 1 global
                lt = [
                    "full_attention" if (i + 1) % 6 == 0 else "sliding_attention"
                    for i in range(L)
                ]
        fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
        fields.update(
            layer_types=tuple(lt),
            rope_local_theta=get("rope_local_base_freq", 10000.0) or 10000.0,
            query_pre_attn_scalar=get("query_pre_attn_scalar", base.head_dim),
            embed_scale=float(get("hidden_size")) ** 0.5,
            logits_soft_cap=get("final_logit_softcapping"),
            attn_soft_cap=get("attn_logit_softcapping"),
            sliding_window=get("sliding_window", 4096),
            qk_norm=model_type in ("gemma3", "gemma3_text"),
            tie_embeddings=bool(get("tie_word_embeddings", True)),
            # legacy gemma-1 configs say hidden_act="gelu" but HF deliberately
            # runs the tanh approximation regardless (the gemma activation
            # fix); ACT_FNS["gelu"] is now exact-erf, so remap here. NB:
            # transformers GemmaConfig carries an EXPLICIT hidden_activation
            # of None — `or` (not a get default) must do the fallthrough.
            act=(
                "gelu_pytorch_tanh"
                if (get("hidden_activation") or get("hidden_act") or "gelu_pytorch_tanh")
                in ("gelu", "gelu_pytorch_tanh")
                else (get("hidden_activation") or get("hidden_act"))
            ),
        )
        return cls(**fields)


def init_params(cfg: GemmaConfig, backend: BackendConfig, key: jax.Array) -> dict:
    pd = backend.param_jnp_dtype
    L, D, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    keys = jax.random.split(key, 9)

    def stack(k, shape, in_axis=0):
        return _dense_init(k, (L, *shape), pd, in_axis=in_axis + 1)

    layers = {
        "attn": {
            "q_proj": {"kernel": stack(keys[0], (D, cfg.q_dim))},
            "k_proj": {"kernel": stack(keys[1], (D, cfg.kv_dim))},
            "v_proj": {"kernel": stack(keys[2], (D, cfg.kv_dim))},
            "o_proj": {"kernel": stack(keys[3], (cfg.q_dim, D))},
        },
        "mlp": {
            "gate_proj": {"kernel": stack(keys[4], (D, I))},
            "up_proj": {"kernel": stack(keys[5], (D, I))},
            "down_proj": {"kernel": stack(keys[6], (I, D))},
        },
        # zero-centered norms init at 0 (= identity scale)
        "input_norm": {"scale": jnp.zeros((L, D), pd)},
        "post_attn_norm": {"scale": jnp.zeros((L, D), pd)},
        "pre_ffn_norm": {"scale": jnp.zeros((L, D), pd)},
        "post_ffn_norm": {"scale": jnp.zeros((L, D), pd)},
    }
    if cfg.qk_norm:
        layers["attn"]["q_norm"] = {"scale": jnp.zeros((L, cfg.head_dim), pd)}
        layers["attn"]["k_norm"] = {"scale": jnp.zeros((L, cfg.head_dim), pd)}
    params = {
        "embed": {"embedding": jax.random.normal(keys[7], (cfg.vocab_size, D)).astype(pd) * 0.02},
        "layers": layers,
        "final_norm": {"scale": jnp.zeros((D,), pd)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(keys[8], (D, cfg.vocab_size), pd)}
    return params


def _layer(
    cfg: GemmaConfig,
    backend: BackendConfig,
    h: jnp.ndarray,
    lp: dict,
    flags: dict,  # per-layer scanned: {"window": i32, "use_local_rope": bool}
    ropes: dict,  # {"local": (cos,sin), "global": (cos,sin)}
    segment_ids: Optional[jnp.ndarray],
    constrain: Constrain,
    bidir_groups: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    B, S, D = h.shape
    x = gemma_rms_norm(h, lp["input_norm"]["scale"], cfg.rms_eps)
    q = _proj(x, lp["attn"]["q_proj"], backend.fp8).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = _proj(x, lp["attn"]["k_proj"], backend.fp8).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = _proj(x, lp["attn"]["v_proj"], backend.fp8).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = gemma_rms_norm(q, lp["attn"]["q_norm"]["scale"], cfg.rms_eps)
        k = gemma_rms_norm(k, lp["attn"]["k_norm"]["scale"], cfg.rms_eps)
    use_local = flags["use_local_rope"]
    cos = jnp.where(use_local, ropes["local"][0], ropes["global"][0])
    sin = jnp.where(use_local, ropes["local"][1], ropes["global"][1])
    q, k = apply_rope(q, k, cos, sin)
    attn_out = windowed_attention(
        q,
        k,
        v,
        backend=backend.attn,
        platform=backend.platform,
        mesh_ctx=backend.mesh_ctx,
        is_sliding=flags["is_sliding"],
        window=cfg.sliding_window,
        dynamic_window=flags["window"],  # dynamic bound; S for full layers
        causal=True,
        scale=cfg.query_pre_attn_scalar**-0.5,
        segment_ids=segment_ids,
        logits_soft_cap=cfg.attn_soft_cap,
        bidir_groups=bidir_groups,
        block_q=backend.attn_block_q,
        block_kv=backend.attn_block_kv,
    )
    attn_out = _proj(attn_out.reshape(B, S, cfg.q_dim), lp["attn"]["o_proj"], backend.fp8)
    h = h + gemma_rms_norm(attn_out, lp["post_attn_norm"]["scale"], cfg.rms_eps)
    h = constrain(h, ("batch", "seq", None))
    y = gemma_rms_norm(h, lp["pre_ffn_norm"]["scale"], cfg.rms_eps)
    act = ACT_FNS[cfg.act]
    mlp = _proj(
        act(_proj(y, lp["mlp"]["gate_proj"], backend.fp8))
        * _proj(y, lp["mlp"]["up_proj"], backend.fp8),
        lp["mlp"]["down_proj"], backend.fp8,
    )
    h = h + gemma_rms_norm(mlp, lp["post_ffn_norm"]["scale"], cfg.rms_eps)
    return constrain(h, ("batch", "seq", None))


def forward_hidden(
    cfg: GemmaConfig,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    position_ids: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    constrain: Constrain = _noop_constrain,
    inputs_embeds: Optional[jnp.ndarray] = None,
    bidir_groups: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    cd = backend.compute_jnp_dtype
    B, S = input_ids.shape
    if position_ids is None:
        position_ids = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)
        )
    if inputs_embeds is not None:
        # VLM path: caller already embedded + scaled text tokens and
        # scattered projected image features in (gemma3_vl/model.py)
        h = inputs_embeds.astype(cd)
    else:
        h = constrain(params["embed"]["embedding"], (None, None)).astype(cd)[input_ids]
        h = h * jnp.asarray(cfg.embed_scale, cd)
    h = constrain(h, ("batch", "seq", None))

    ropes = {
        "global": rope_table(position_ids, cfg.head_dim, cfg.rope),
        "local": rope_table(
            position_ids,
            cfg.head_dim,
            dataclasses.replace(cfg.rope, theta=cfg.rope_local_theta, scaling=None),
        ),
    }
    sw = cfg.sliding_window or S
    # numpy (not jnp) so the unrolled path indexes out STATIC per-layer flags
    # (one attention kernel compiled per layer); lax.scan slices them as
    # traced leaves in the scanned path
    import numpy as _np

    windows = _np.asarray(
        [sw if t == "sliding_attention" else S for t in cfg.layer_types], _np.int32
    )
    use_local = _np.asarray(
        [t == "sliding_attention" for t in cfg.layer_types], bool
    )

    def layer_fn(carry, xs):
        lp, flags = xs
        out = _layer(
            cfg, backend, carry, lp, flags, ropes, segment_ids, constrain,
            bidir_groups=bidir_groups,
        )
        return out, None

    flags = {"window": windows, "use_local_rope": use_local, "is_sliding": use_local}
    h, _ = run_layer_stack(
        layer_fn, h, params["layers"], flags,
        scan_layers=backend.scan_layers, remat=backend.remat,
        num_layers=cfg.num_layers,
    )
    return gemma_rms_norm(h, params["final_norm"]["scale"], cfg.rms_eps)


SHARDING_RULES = [
    (r"layers/.*norm/scale$", (None, None)),
    (r"final_norm/scale$", (None,)),
    # projection rules shared with llama
    (r"embed/embedding$", ("tensor", "fsdp")),
    (r"layers/attn/[qkv]_proj/kernel$", (None, "fsdp", "tensor")),
    (r"layers/attn/o_proj/kernel$", (None, "tensor", "fsdp")),
    (r"layers/mlp/(gate|up)_proj/kernel$", (None, "fsdp", "tensor")),
    (r"layers/mlp/down_proj/kernel$", (None, "tensor", "fsdp")),
    (r"lm_head/kernel$", ("fsdp", "tensor")),
]


@dataclasses.dataclass
class GemmaForCausalLM:
    config: GemmaConfig
    backend: BackendConfig = BackendConfig()

    # see llama.model._proj: these paths apply grafted LoRA activation-side
    lora_graft_patterns = ("*/attn/[qkvo]_proj/kernel", "*/mlp/*_proj/kernel")

    def init(self, key: jax.Array) -> dict:
        return init_params(self.config, self.backend, key)

    def hidden(self, params: dict, input_ids: jnp.ndarray, **kw: Any) -> jnp.ndarray:
        return forward_hidden(self.config, self.backend, params, input_ids, **kw)

    def lm_head(self, params: dict) -> jnp.ndarray:
        if self.config.tie_embeddings:
            return params["embed"]["embedding"].T
        return params["lm_head"]["kernel"]

    def __call__(self, params: dict, input_ids: jnp.ndarray, **kw: Any) -> jnp.ndarray:
        h = self.hidden(params, input_ids, **kw)
        logits = h @ self.lm_head(params).astype(h.dtype)
        if self.config.logits_soft_cap is not None:
            logits = self.config.logits_soft_cap * jnp.tanh(
                logits / self.config.logits_soft_cap
            )
        return logits

    @property
    def sharding_rules(self):
        return SHARDING_RULES
