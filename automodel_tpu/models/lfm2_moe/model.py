"""LFM2-MoE (LiquidAI LFM2-8B-A1B): gated short-conv layers beside attention.

Parity: HF ``modeling_lfm2_moe.py`` (``Lfm2MoeForCausalLM``). Per layer
``h = x + Op(RMS(x; operator_norm))``, ``y = h + FF(RMS(h; ffn_norm))``; the
final norm is the one HF calls ``embedding_norm``; the head is the tied
embedding. ``Op`` by the published ``layer_types``:

- ``conv``: ``[B, C, z] = split3(x W_in)``, ``u = B * z``, a depthwise causal
  conv of ``conv_L_cache`` taps over ``u``, ``Op = (C * conv(u)) W_out``. No
  bias, no activation. What a sequence carries between tokens is the last
  ``taps - 1`` rows of ``u``: a fixed-size state, not K/V;
- ``full_attention``: the llama attention block with a per-head RMS on q and
  k before rotary (``q_layernorm``/``k_layernorm``), GQA, rotary over the
  whole head.

``FF`` is a dense SwiGLU on the first ``num_dense_layers`` layers and the
shared routed-expert block on the rest, its gate configured as MiniMax-M2's
(sigmoid scores, a selection-only ``expert_bias``, top-k weights
renormalised: by their sum clamped at 1e-20 as in every family, where the
published code adds 1e-6 to it, under 1e-6 relative on four sigmoid scores).

TPU structure: the published ``layer_types`` is not periodic at its tail, so
the stack takes the list as given: layers are NOT stacked (``layers/00`` ..
``layers/NN``, each leaf its own array) and the loop is unrolled with static
per-layer routing. An unstacked tree is what keeps a kernel's operand from
being a slice of a stacked weight (a copy of every expert's weights a layer
a step). Decode threads the cache through the same loop: K/V for the
attention layers only (the stacked pool is written and read in place, by
layer index), and the conv layers' state one row a slot
(generation/kv_cache.py ``LayerCache``, ``CacheContext.conv_prev``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.generation import kv_cache as kv_cache_mod
from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.llama.model import (
    ACT_FNS,
    _dense_init,
    _noop_constrain,
    attention_block,
)
from automodel_tpu.models.qwen3_moe.model import MoEModelAux, MoETransformerConfig
from automodel_tpu.moe.gate import update_gate_bias
from automodel_tpu.moe.layer import MOE_SHARDING_RULES, init_moe_params, moe_block
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import rope_table
from automodel_tpu.ops.short_conv import causal_conv1d

LAYER_KINDS = ("conv", "full_attention")


def layer_name(i: int) -> str:
    """The key of layer ``i`` under ``params["layers"]`` (sorts in order)."""
    return f"{i:02d}"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(MoETransformerConfig):
    layer_types: tuple = ()
    conv_taps: int = 3  # HF conv_L_cache

    @classmethod
    def from_hf(cls, hf_cfg: Any) -> "Lfm2MoeConfig":
        get = lambda k, d=None: (
            hf_cfg.get(k, d) if isinstance(hf_cfg, dict) else getattr(hf_cfg, k, d)
        )
        base = MoETransformerConfig.from_hf(hf_cfg)
        if get("conv_bias", False):
            raise NotImplementedError("lfm2_moe: conv_bias (published: false)")
        L = base.num_layers
        layer_types = tuple(get("layer_types") or ())
        if len(layer_types) != L or set(layer_types) - set(LAYER_KINDS):
            raise ValueError(
                f"lfm2_moe: layer_types must name {L} layers, each one of "
                f"{LAYER_KINDS}; got {layer_types}"
            )
        moe = dataclasses.replace(
            base.moe,
            score_func="sigmoid",
            softmax_before_topk=False,
            expert_bias=bool(get("use_expert_bias", True)),
            norm_topk_prob=bool(get("norm_topk_prob", True)),
            route_scale=float(get("routed_scaling_factor", 1.0) or 1.0),
            num_dense_layers=int(get("num_dense_layers", 0) or 0),
            num_shared_experts=0,
            # the bias is a buffer the published training nudges; here it is
            # served as loaded and trained by the aux-free rule
            bias_update_factor=0.001 if get("use_expert_bias", True) else 0.0,
        )
        fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
        fields.update(
            moe=moe,
            layer_types=layer_types,
            conv_taps=int(get("conv_L_cache", 3)),
            rms_eps=float(get("norm_eps", 1e-5)),
            qk_norm=True,
            qk_norm_flat=False,
            # the family ties its head to the embedding unless told otherwise
            tie_embeddings=bool(get("tie_word_embeddings", get("tie_embedding", True))),
        )
        return cls(**fields)

    @property
    def kv_layer_ids(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == "full_attention")

    @property
    def conv_layer_ids(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == "conv")


def init_params(cfg: Lfm2MoeConfig, backend: BackendConfig, key: jax.Array) -> dict:
    pd = backend.param_jnp_dtype
    D, I = cfg.hidden_size, cfg.intermediate_size
    keys = jax.random.split(key, cfg.num_layers + 2)
    layers = {}
    for i, kind in enumerate(cfg.layer_types):
        k = jax.random.split(keys[i], 8)
        lp: dict = {
            "operator_norm": {"scale": jnp.ones((D,), pd)},
            "ffn_norm": {"scale": jnp.ones((D,), pd)},
        }
        if kind == "conv":
            lp["conv"] = {
                "in_proj": {"kernel": _dense_init(k[0], (D, 3 * D), pd)},
                "weight": (
                    jax.random.normal(k[1], (D, cfg.conv_taps)) / cfg.conv_taps**0.5
                ).astype(pd),
                "out_proj": {"kernel": _dense_init(k[2], (D, D), pd)},
            }
        else:
            lp["attn"] = {
                "q_proj": {"kernel": _dense_init(k[0], (D, cfg.q_dim), pd)},
                "k_proj": {"kernel": _dense_init(k[1], (D, cfg.kv_dim), pd)},
                "v_proj": {"kernel": _dense_init(k[2], (D, cfg.kv_dim), pd)},
                "o_proj": {"kernel": _dense_init(k[3], (cfg.q_dim, D), pd)},
                "q_norm": {"scale": jnp.ones((cfg.head_dim,), pd)},
                "k_norm": {"scale": jnp.ones((cfg.head_dim,), pd)},
            }
        if i < cfg.moe.num_dense_layers:
            lp["mlp"] = {
                "gate_proj": {"kernel": _dense_init(k[4], (D, I), pd)},
                "up_proj": {"kernel": _dense_init(k[5], (D, I), pd)},
                "down_proj": {"kernel": _dense_init(k[6], (I, D), pd)},
            }
        else:
            lp["moe"] = init_moe_params(k[7], cfg.moe, D, pd)
        layers[layer_name(i)] = lp
    params: dict = {
        "embed": {
            "embedding": jax.random.normal(keys[-1], (cfg.vocab_size, D)).astype(pd) * 0.02
        },
        "layers": layers,
        "final_norm": {"scale": jnp.ones((D,), pd)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(keys[-2], (D, cfg.vocab_size), pd)}
    return params


def short_conv_block(cfg, h, lp, segment_ids, constrain, prev=None):
    """Pre-norm gated short conv + residual. ``prev`` [B, taps - 1, D]: the
    conv's inputs before this call's first position (serving: the slot's
    state). -> (h, u): ``u`` [B, S, D] is what the NEXT call's ``prev`` is
    cut from."""
    D = cfg.hidden_size
    with jax.named_scope("norm"):
        x = rms_norm(h, lp["operator_norm"]["scale"], cfg.rms_eps)
    with jax.named_scope("conv"):
        cp = lp["conv"]
        bcz = x @ cp["in_proj"]["kernel"].astype(x.dtype)
        b, c, z = bcz[..., :D], bcz[..., D : 2 * D], bcz[..., 2 * D :]
        u = b * z
        v = causal_conv1d(u, cp["weight"].astype(x.dtype), segment_ids, prev)
        out = (c * v) @ cp["out_proj"]["kernel"].astype(x.dtype)
    return constrain(h + out, ("batch", "seq", None)), u


def forward_hidden(
    cfg: Lfm2MoeConfig,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    position_ids: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    constrain=_noop_constrain,
    cache: Optional[tuple] = None,
):
    """``cache``: the serving hook — ``(KVCache, CacheContext)`` whose k/v
    cover the ``full_attention`` layers only, in order, and whose ``state``
    ``[n_conv, rows, taps - 1, D]`` covers the ``conv`` layers; the return
    becomes ``((h, aux), new_cache)``."""
    cd = backend.compute_jnp_dtype
    moe = cfg.moe
    kvc = ctx = None
    if cache is not None:
        kvc, ctx = cache
    if position_ids is None:
        position_ids = jnp.broadcast_to(
            jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None, :], input_ids.shape
        )
    with jax.named_scope("embed"):
        h = constrain(params["embed"]["embedding"], (None, None)).astype(cd)[input_ids]
    h = constrain(h, ("batch", "seq", None))
    with jax.named_scope("attn"):  # the rope table every attention layer reads
        cos, sin = rope_table(position_ids, cfg.rope_dim or cfg.head_dim, cfg.rope)

    def maybe_remat(fn):
        from automodel_tpu.models.common.stacking import remat_wrap

        return fn if cache is not None else remat_wrap(fn, backend.remat)

    ck, cv = (kvc.k, kvc.v) if cache is not None else (None, None)
    new_states: list = []
    counts_l, aux_l = [], []
    i_kv = i_conv = 0
    with jax.named_scope("layers"):
        for i, kind in enumerate(cfg.layer_types):
            lp = params["layers"][layer_name(i)]

            if kind == "conv":
                prev = None
                if cache is not None:
                    with jax.named_scope("conv"):  # the slot's state, read
                        prev = ctx.conv_prev(kvc.state[i_conv])

                def operator(h, lp=lp, prev=prev):
                    return short_conv_block(cfg, h, lp, segment_ids, constrain, prev)

            else:
                layer_cache = None if cache is None else (ck, cv)
                layer_ctx = None if cache is None else ctx.at_layer(i_kv)

                def operator(h, lp=lp, layer_cache=layer_cache, layer_ctx=layer_ctx):
                    alp = {"input_norm": lp["operator_norm"], "attn": lp["attn"]}
                    with jax.named_scope("attn"):
                        out = attention_block(
                            cfg, backend, h, alp, cos, sin, segment_ids, constrain,
                            cache=layer_cache, cache_ctx=layer_ctx,
                        )
                    return out if layer_cache is not None else (out, None)

            def feed_forward(h, lp=lp):
                with jax.named_scope("norm"):
                    x = rms_norm(h, lp["ffn_norm"]["scale"], cfg.rms_eps)
                if "mlp" in lp:
                    act = ACT_FNS[cfg.act]
                    with jax.named_scope("mlp"):
                        out = (
                            act(x @ lp["mlp"]["gate_proj"]["kernel"].astype(x.dtype))
                            * (x @ lp["mlp"]["up_proj"]["kernel"].astype(x.dtype))
                        ) @ lp["mlp"]["down_proj"]["kernel"].astype(x.dtype)
                    return out, None
                return moe_block(
                    x, lp["moe"], moe, ACT_FNS[cfg.act],
                    experts_backend=backend.experts,
                    fake_gate=backend.fake_balanced_gate,
                    constrain=constrain, platform=backend.platform,
                    fp8=backend.fp8_experts, act_name=cfg.act,
                )

            def layer(h, operator=operator, feed_forward=feed_forward):
                h, kept = operator(h)
                out, aux = feed_forward(h)
                return constrain(h + out, ("batch", "seq", None)), kept, aux

            h, kept, aux = maybe_remat(layer)(h)
            if kind == "conv":
                if cache is not None:
                    with jax.named_scope("conv"):  # ... and cut anew
                        new_states.append(ctx.conv_next(prev, kept))
                i_conv += 1
            else:
                if cache is not None:
                    ck, cv = kept
                i_kv += 1
            if aux is not None:
                counts_l.append(aux.expert_counts)
                aux_l.append(aux.aux_loss)

    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"]["scale"], cfg.rms_eps)
    if counts_l:
        aux_out = MoEModelAux(jnp.stack(counts_l), jnp.stack(aux_l).sum())
    else:
        aux_out = MoEModelAux(
            jnp.zeros((0, moe.num_experts), jnp.int32), jnp.float32(0.0)
        )
    if cache is None:
        return h, aux_out
    state = kvc.state
    if new_states:
        with jax.named_scope("kv_write"):  # the cache write, of the other kind
            state = ctx.write_state(state, new_states)
    return (h, aux_out), kvc.replace(k=ck, v=cv, state=state)


def forward(cfg, backend, params, input_ids, cache: Optional[tuple] = None, **kw):
    out = forward_hidden(cfg, backend, params, input_ids, cache=cache, **kw)
    (h, aux), new_cache = out if cache is not None else (out, None)
    kernel = (
        params["embed"]["embedding"].T if cfg.tie_embeddings
        else params["lm_head"]["kernel"]
    )
    with jax.named_scope("lm_head"):
        logits = h @ kernel.astype(h.dtype)
    return (logits, aux) if cache is None else ((logits, aux), new_cache)


# layers are unstacked: no leading layer dim on any rule
SHARDING_RULES: list[tuple[str, tuple]] = [
    *[(r"moe/" + pat, spec) for pat, spec in MOE_SHARDING_RULES],
    (r"conv/in_proj/kernel$", ("fsdp", None)),
    (r"conv/out_proj/kernel$", (None, "fsdp")),
    (r"conv/weight$", (None, None)),
    (r"attn/[qkv]_proj/kernel$", ("fsdp", "tensor")),
    (r"attn/o_proj/kernel$", ("tensor", "fsdp")),
    (r"attn/[qk]_norm/scale$", (None,)),
    (r"mlp/(gate|up)_proj/kernel$", ("fsdp", "tensor")),
    (r"mlp/down_proj/kernel$", ("tensor", "fsdp")),
    (r"layers/.*norm/scale$", (None,)),
    (r"embed/embedding$", ("tensor", "fsdp")),
    (r"final_norm/scale$", (None,)),
    (r"lm_head/kernel$", ("fsdp", "tensor")),
]


@dataclasses.dataclass
class Lfm2MoeForCausalLM:
    config: Lfm2MoeConfig
    backend: BackendConfig = BackendConfig()

    lora_graft_patterns = ("*/attn/[qkvo]_proj/kernel",)

    def cache_layout(self) -> tuple:
        """Per layer, what a sequence keeps: K/V on the attention layers, the
        conv's last ``taps - 1`` inputs on the others."""
        c = self.config
        return tuple(
            kv_cache_mod.kv_layer(c.num_kv_heads, c.head_dim)
            if t == "full_attention"
            else kv_cache_mod.conv_layer(c.hidden_size, c.conv_taps)
            for t in c.layer_types
        )

    def init(self, key: jax.Array) -> dict:
        return init_params(self.config, self.backend, key)

    def __call__(self, params: dict, input_ids: jnp.ndarray, **kw: Any):
        return forward(self.config, self.backend, params, input_ids, **kw)

    def hidden(self, params: dict, input_ids: jnp.ndarray, **kw: Any):
        return forward_hidden(self.config, self.backend, params, input_ids, **kw)

    def lm_head(self, params: dict) -> jnp.ndarray:
        if self.config.tie_embeddings:
            return params["embed"]["embedding"].T
        return params["lm_head"]["kernel"]

    @property
    def sharding_rules(self) -> list[tuple[str, tuple]]:
        return SHARDING_RULES

    def post_step_fn(self, params: dict, extras: dict) -> dict:
        """Aux-free balancing: nudge each expert layer's selection bias by its
        counts (``extras["expert_counts"]`` [L_moe, E], in layer order)."""
        u = self.config.moe.bias_update_factor
        if u <= 0 or "expert_counts" not in extras:
            return params
        counts = extras["expert_counts"]
        row = 0
        for i in range(self.config.num_layers):
            router = params["layers"][layer_name(i)].get("moe", {}).get("router")
            if router is None:
                continue
            if "bias" in router:
                router["bias"] = update_gate_bias(router["bias"], counts[row], u)
            row += 1
        return params
