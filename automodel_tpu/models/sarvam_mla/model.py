"""Sarvam-105B (sarvamai/sarvam-105b, ``model_type: sarvam_mla``): DeepSeek-V3's
latent attention WITHOUT q compression over a sigmoid-routed expert stack with
a selection bias and one shared expert, served through a paged LATENT cache.

The block is models/deepseek_v3's (``mla_branch``; ``kv_a_norm`` is the one
latent norm that exists without ``q_lora_rank``), the expert layer moe/layer.py
(``MoEConfig.held_experts`` for a chip that holds a share of a layer's
experts). What this family adds:

- the source's key names (``num_shared_experts``,
  ``moe_router_enable_expert_bias``, ``rope_scaling.type: deepseek_yarn``, a
  ``head_dim`` of 576 that is the CACHED ROW's width, not a head's);
- ``cache_layout()``: every layer keeps one latent row a token
  (``generation/kv_cache.latent_layer``: ``kv_lora_rank + qk_rope_head_dim``
  wide), so ``ServingEngine`` serves it from a latent pool;
- a layer stack that is NOT stacked (``params["layers"]["00"] ...``, as
  models/lfm2_moe): a serving program hands every weight whole to its kernel,
  and each layer writes its rows into the stacked pool in place and attends
  through ``CacheContext.at_layer`` (no per-layer slice of the pool is copied).

Assumed where the config.json is silent (the benchmark configuration's
``assumed`` says why): ``use_qk_norm: true`` is DeepSeek's latent norms, of
which only ``kv_a_norm`` exists here (a per-head norm on an expanded key
would make the 576-wide cache the config declares impossible; a per-head norm
on q is the other reading and is NOT applied); sigmoid scores, top-k of score
+ bias, weights renormalised x ``routed_scaling_factor``; one expert group;
interleaved rotary; the shared expert's width is ``moe_intermediate_size`` x
``num_shared_experts``; no attention bias.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.generation import kv_cache as kv_cache_mod
from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.deepseek_v3.model import (
    DeepseekV3Config,
    init_mla_layer,
    mla_branch,
)
from automodel_tpu.models.llama.model import ACT_FNS, _dense_init, _noop_constrain
from automodel_tpu.models.qwen3_moe.model import MoEModelAux
from automodel_tpu.moe.gate import update_gate_bias
from automodel_tpu.moe.layer import MOE_SHARDING_RULES, init_moe_params, moe_block
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import rope_table


def layer_name(i: int) -> str:
    """The key of layer ``i`` under ``params["layers"]`` (sorts in order)."""
    return f"{i:02d}"


@dataclasses.dataclass(frozen=True)
class SarvamMlaConfig(DeepseekV3Config):
    @classmethod
    def from_hf(cls, hf_cfg: Any) -> "SarvamMlaConfig":
        hf = dict(hf_cfg) if isinstance(hf_cfg, dict) else dict(vars(hf_cfg))
        if hf.get("q_lora_rank"):
            raise NotImplementedError("sarvam_mla: q_lora_rank (published: none)")
        if hf.get("tie_word_embeddings"):
            raise NotImplementedError("sarvam_mla: a tied head (published: untied)")
        rs = dict(hf.get("rope_scaling") or {})
        if rs.get("type", rs.get("rope_type")) == "deepseek_yarn":
            rs.pop("rope_type", None)
            rs["type"] = "yarn"  # ops/rope.py's name for the same ramp
        held = hf.get("held_experts")
        as_v3 = {
            **hf,
            "rope_scaling": rs or None,
            # the source's head_dim (576) is the cached row, not a head
            "head_dim": int(hf.get("q_head_dim") or 0)
            or int(hf["qk_nope_head_dim"]) + int(hf["qk_rope_head_dim"]),
            "n_shared_experts": hf.get("num_shared_experts", 0),
            "scoring_func": "sigmoid",
            "norm_topk_prob": hf.get("norm_topk_prob", True),
        }
        base = DeepseekV3Config.from_hf(as_v3)
        fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
        fields["moe"] = dataclasses.replace(
            base.moe,
            expert_bias=bool(hf.get("moe_router_enable_expert_bias", True)),
            # the source states no update rate for the selection bias
            bias_update_factor=float(hf.get("router_bias_update_factor", 0.0) or 0.0),
            held_experts=tuple(held) if held else None,
            held_capacity_factor=hf.get("held_capacity_factor"),
        )
        return cls(**fields)

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim


def init_params(cfg: SarvamMlaConfig, backend: BackendConfig, key: jax.Array) -> dict:
    pd = backend.param_jnp_dtype
    D, I = cfg.hidden_size, cfg.intermediate_size
    keys = jax.random.split(key, cfg.num_layers + 2)
    layers = {}
    for i in range(cfg.num_layers):
        k = jax.random.split(keys[i], 5)
        # one layer of the family's stacked initialiser, its leading axis dropped
        lp = jax.tree.map(lambda x: x[0], init_mla_layer(cfg, backend, k[0], 1))
        if i < cfg.moe.num_dense_layers:
            lp["mlp"] = {
                "gate_proj": {"kernel": _dense_init(k[1], (D, I), pd)},
                "up_proj": {"kernel": _dense_init(k[2], (D, I), pd)},
                "down_proj": {"kernel": _dense_init(k[3], (I, D), pd)},
            }
        else:
            lp["moe"] = init_moe_params(k[4], cfg.moe, D, pd)
        layers[layer_name(i)] = lp
    return {
        "embed": {
            "embedding": jax.random.normal(keys[-1], (cfg.vocab_size, D)).astype(pd) * 0.02
        },
        "layers": layers,
        "final_norm": {"scale": jnp.ones((D,), pd)},
        "lm_head": {"kernel": _dense_init(keys[-2], (D, cfg.vocab_size), pd)},
    }


def forward_hidden(
    cfg: SarvamMlaConfig,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    position_ids: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    constrain=_noop_constrain,
    cache: Optional[tuple] = None,
):
    """``cache``: the serving hook, ``(KVCache, CacheContext)`` whose ``k`` is
    the stacked latent pool ``[L, NB, BS, W]`` (``v`` None) and whose context
    is the paged plan; the return becomes ``((h, aux), new_cache)``."""
    cd = backend.compute_jnp_dtype
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
    with jax.named_scope("attn"):  # the rope table every layer's attention reads
        cos, sin = rope_table(position_ids, cfg.qk_rope_head_dim, cfg.rope)

    def maybe_remat(fn):
        from automodel_tpu.models.common.stacking import remat_wrap

        return fn if cache is not None else remat_wrap(fn, backend.remat)

    pool = kvc.k if cache is not None else None
    act = ACT_FNS[cfg.act]
    counts_l, aux_l, held_l = [], [], []
    with jax.named_scope("layers"):
        for i in range(cfg.num_layers):
            lp = params["layers"][layer_name(i)]
            layer_ctx = None if cache is None else ctx.at_layer(i)

            def layer(h, pool, lp=lp, layer_ctx=layer_ctx):
                with jax.named_scope("attn"), jax.named_scope("mla"):
                    x = rms_norm(h, lp["input_norm"]["scale"], cfg.rms_eps)
                    out = mla_branch(
                        cfg, backend, x, lp["attn"], cos, sin, segment_ids,
                        cache=pool, cache_ctx=layer_ctx,
                    )
                    if layer_ctx is not None:
                        out, pool = out
                    h = constrain(h + out, ("batch", "seq", None))
                with jax.named_scope("norm"):
                    x = rms_norm(h, lp["post_attn_norm"]["scale"], cfg.rms_eps)
                if "mlp" in lp:
                    with jax.named_scope("mlp"):
                        out = (
                            act(x @ lp["mlp"]["gate_proj"]["kernel"].astype(x.dtype))
                            * (x @ lp["mlp"]["up_proj"]["kernel"].astype(x.dtype))
                        ) @ lp["mlp"]["down_proj"]["kernel"].astype(x.dtype)
                    aux = None
                else:
                    out, aux = moe_block(
                        x, lp["moe"], cfg.moe, act,
                        experts_backend=backend.experts,
                        fake_gate=backend.fake_balanced_gate,
                        constrain=constrain, platform=backend.platform,
                        fp8=backend.fp8_experts, act_name=cfg.act,
                    )
                return constrain(h + out, ("batch", "seq", None)), pool, aux

            h, pool, aux = maybe_remat(layer)(h, pool)
            if aux is not None:
                counts_l.append(aux.expert_counts)
                aux_l.append(aux.aux_loss)
                if cfg.moe.held_experts is not None:
                    lo, hi = cfg.moe.held_experts
                    held_l.append(aux.expert_counts[lo:hi].sum())

    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"]["scale"], cfg.rms_eps)
    if counts_l:
        aux_out = MoEModelAux(
            jnp.stack(counts_l), jnp.stack(aux_l).sum(),
            held_expert_rows=jnp.stack(held_l).sum() if held_l else None,
        )
    else:
        aux_out = MoEModelAux(
            jnp.zeros((0, cfg.moe.num_experts), jnp.int32), jnp.float32(0.0)
        )
    if cache is None:
        return h, aux_out
    return (h, aux_out), kvc.replace(k=pool)


def forward(cfg, backend, params, input_ids, cache: Optional[tuple] = None, **kw):
    out = forward_hidden(cfg, backend, params, input_ids, cache=cache, **kw)
    (h, aux), new_cache = out if cache is not None else (out, None)
    with jax.named_scope("lm_head"):
        logits = h @ params["lm_head"]["kernel"].astype(h.dtype)
    return (logits, aux) if cache is None else ((logits, aux), new_cache)


# layers are unstacked: no leading layer dim on any rule
SHARDING_RULES: list[tuple[str, tuple]] = [
    *[(r"moe/" + pat, spec) for pat, spec in MOE_SHARDING_RULES],
    (r"attn/q_proj/kernel$", ("fsdp", "tensor")),
    (r"attn/kv_a_proj/kernel$", ("fsdp", None)),
    (r"attn/kv_a_norm/scale$", (None,)),
    (r"attn/kv_b_proj/kernel$", ("fsdp", "tensor")),
    (r"attn/o_proj/kernel$", ("tensor", "fsdp")),
    (r"mlp/(gate|up)_proj/kernel$", ("fsdp", "tensor")),
    (r"mlp/down_proj/kernel$", ("tensor", "fsdp")),
    (r"layers/.*norm/scale$", (None,)),
    (r"embed/embedding$", ("tensor", "fsdp")),
    (r"final_norm/scale$", (None,)),
    (r"lm_head/kernel$", ("fsdp", "tensor")),
]


@dataclasses.dataclass
class SarvamMlaForCausalLM:
    config: SarvamMlaConfig
    backend: BackendConfig = BackendConfig()

    def cache_layout(self) -> tuple:
        """Every layer keeps one latent row a token: the normed compression
        beside the rotated shared key (512 + 64)."""
        c = self.config
        return (kv_cache_mod.latent_layer(c.latent_width, c.kv_lora_rank),) * int(c.num_layers)

    def init(self, key: jax.Array) -> dict:
        return init_params(self.config, self.backend, key)

    def __call__(self, params: dict, input_ids: jnp.ndarray, **kw: Any):
        return forward(self.config, self.backend, params, input_ids, **kw)

    def hidden(self, params: dict, input_ids: jnp.ndarray, **kw: Any):
        return forward_hidden(self.config, self.backend, params, input_ids, **kw)

    def lm_head(self, params: dict) -> jnp.ndarray:
        return params["lm_head"]["kernel"]

    @property
    def sharding_rules(self) -> list[tuple[str, tuple]]:
        return SHARDING_RULES

    def post_step_fn(self, params: dict, extras: dict) -> dict:
        """Aux-free balancing, off unless the config names an update rate:
        nudge each expert layer's selection bias by its counts
        (``extras["expert_counts"]`` [L_moe, E], in layer order)."""
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
