"""Kimi-Linear: KDA delta-rule layers beside NoPE latent attention, sparse experts.

Parity: HF ``modeling_kimi.py`` (moonshotai/Kimi-Linear-48B-A3B). Per layer,
by the published ``linear_attn_config`` (layer numbers there count from 1):

- ``kda_layers``: Kimi Delta Attention. ``q, k, v = silu(conv4(x W))`` (three
  depthwise causal convs, ops/short_conv.py), q and k l2-normalised per head,
  a PER-CHANNEL log-decay ``g = -exp(A_log) * softplus(W_fb(W_fa x) + dt_bias)``
  and a write strength ``beta = sigmoid(x W_b)`` feed the chunked delta rule
  (ops/delta_rule.py); the output goes through a per-head RMS norm gated by
  ``sigmoid(W_gb(W_ga x))`` and ``W_o``.
- ``full_attn_layers``: the latent block of models/deepseek_v3 (``q_lora_rank``
  null) with ``mla_use_nope``: no rotary anywhere. Rotary is the config's
  ``use_rope``; the block is the same code.
- the MLP: the first ``first_k_dense_replace`` layers a dense SwiGLU, the
  others routed experts (sigmoid scores, selection by score + bias, weights
  renormalised and scaled) plus a shared expert (moe/layer.py).

TPU structure: the two mixers have different parameter shapes, so they are
stacked by kind (``kda`` / ``mla``), the MLPs likewise (``dense_mlp`` /
``moe``), the norms over all layers; the layer loop is unrolled with static
routing, as models/qwen3_next does.

Not served: a delta-rule state has no slot in serving/'s cache layouts yet
(``cache_layout`` says so, ``ServeConfig.check_layout`` refuses the family for
its ``delta`` layers alone; its latent layers' rows would live in the latent
pool that models/sarvam_mla is served from).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.deepseek_v3.model import (
    DeepseekV3Config,
    init_mla_layer,
    mla_block,
)
from automodel_tpu.models.llama.model import ACT_FNS, _dense_init, _noop_constrain
from automodel_tpu.models.qwen3_moe.model import MoEModelAux
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.gate import update_gate_bias
from automodel_tpu.moe.layer import init_moe_params, moe_block
from automodel_tpu.ops.delta_rule import chunked_delta_rule
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import rope_table
from automodel_tpu.ops.short_conv import causal_conv1d


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(DeepseekV3Config):
    layer_kinds: tuple = ()  # "kda" | "mla" a layer
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    kda_conv_kernel: int = 4

    @classmethod
    def from_hf(cls, hf_cfg: Any) -> "KimiLinearConfig":
        get = lambda k, d=None: (
            hf_cfg.get(k, d) if isinstance(hf_cfg, dict) else getattr(hf_cfg, k, d)
        )
        base = DeepseekV3Config.from_hf(hf_cfg)
        L = base.num_layers
        lin = get("linear_attn_config") or {}
        lget = lambda k, d=None: (lin.get(k, d) if isinstance(lin, dict) else getattr(lin, k, d))
        kda = set(lget("kda_layers") or ())
        full = set(lget("full_attn_layers") or ())
        kinds = []
        for n in range(1, L + 1):  # the published lists count layers from 1
            if (n in kda) == (n in full):
                raise ValueError(
                    f"linear_attn_config: layer {n} of {L} must be in exactly one of "
                    "kda_layers / full_attn_layers"
                )
            kinds.append("kda" if n in kda else "mla")
        held = get("held_experts")
        moe = MoEConfig(
            num_experts=get("num_experts"),
            num_experts_per_tok=get("num_experts_per_token"),
            moe_intermediate_size=get("moe_intermediate_size"),
            num_shared_experts=get("num_shared_experts", 0) or 0,
            shared_expert_intermediate_size=get("moe_intermediate_size"),
            score_func=get("moe_router_activation_func", "sigmoid"),
            route_scale=get("routed_scaling_factor", 1.0) or 1.0,
            norm_topk_prob=bool(get("moe_renormalize", True)),
            n_group=get("num_expert_group", 1) or 1,
            topk_group=get("topk_group", 1) or 1,
            num_dense_layers=get("first_k_dense_replace", 0) or 0,
            # the published router carries e_score_correction_bias; the
            # source states no update rate, so the bias is held unless one is given
            expert_bias=True,
            bias_update_factor=get("router_bias_update_factor", 0.0) or 0.0,
            held_experts=tuple(held) if held else None,
            held_capacity_factor=get("held_capacity_factor"),
        )
        if (get("moe_layer_freq", 1) or 1) != 1:
            raise NotImplementedError("moe_layer_freq != 1")
        fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
        fields.update(
            moe=moe,
            use_rope=not bool(get("mla_use_nope", False)),
            layer_kinds=tuple(kinds),
            kda_num_heads=lget("num_heads", base.num_heads),
            kda_head_dim=lget("head_dim", 128),
            kda_conv_kernel=lget("short_conv_kernel_size", 4),
        )
        return cls(**fields)

    @property
    def kda_dim(self) -> int:
        return self.kda_num_heads * self.kda_head_dim

    @property
    def n_kda(self) -> int:
        return sum(k == "kda" for k in self.layer_kinds)

    @property
    def n_mla(self) -> int:
        return sum(k == "mla" for k in self.layer_kinds)


def init_kda_layer(cfg: KimiLinearConfig, backend: BackendConfig, key, L: int) -> dict:
    pd = backend.param_jnp_dtype
    D, H, dh, P = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_dim
    keys = jax.random.split(key, 14)

    def stack(k, shape):
        return _dense_init(k, (L, *shape), pd, in_axis=1)

    def conv(k):
        return (jax.random.normal(k, (L, P, cfg.kda_conv_kernel))
                / cfg.kda_conv_kernel**0.5).astype(pd)

    # the family's convention: A in [1, 16], the time step in [1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(keys[12], (L, P), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    return {
        "q_proj": {"kernel": stack(keys[0], (D, P))},
        "k_proj": {"kernel": stack(keys[1], (D, P))},
        "v_proj": {"kernel": stack(keys[2], (D, P))},
        "q_conv": {"weight": conv(keys[3])},
        "k_conv": {"weight": conv(keys[4])},
        "v_conv": {"weight": conv(keys[5])},
        "A_log": jnp.log(jax.random.uniform(keys[11], (L, H), minval=1.0, maxval=16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "f_a_proj": {"kernel": stack(keys[6], (D, dh))},
        "f_b_proj": {"kernel": stack(keys[7], (dh, P))},
        "b_proj": {"kernel": stack(keys[8], (D, H))},
        "g_a_proj": {"kernel": stack(keys[9], (D, dh))},
        "g_b_proj": {"kernel": stack(keys[10], (dh, P))},
        "o_norm": {"scale": jnp.ones((L, dh), pd)},
        "o_proj": {"kernel": stack(keys[13], (P, D))},
    }


def init_params(cfg: KimiLinearConfig, backend: BackendConfig, key: jax.Array) -> dict:
    pd = backend.param_jnp_dtype
    D, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    nd = cfg.moe.num_dense_layers
    keys = jax.random.split(key, 8)
    params: dict = {
        "embed": {"embedding": (jax.random.normal(keys[0], (cfg.vocab_size, D)) * 0.02).astype(pd)},
        "final_norm": {"scale": jnp.ones((D,), pd)},
        "layers": {
            "input_norm": {"scale": jnp.ones((L, D), pd)},
            "post_attn_norm": {"scale": jnp.ones((L, D), pd)},
        },
    }
    if cfg.n_kda:
        params["kda"] = init_kda_layer(cfg, backend, keys[1], cfg.n_kda)
    if cfg.n_mla:
        params["mla"] = init_mla_layer(cfg, backend, keys[2], cfg.n_mla)["attn"]
    if nd:
        dk = jax.random.split(keys[3], 3)
        params["dense_mlp"] = {
            "gate_proj": {"kernel": _dense_init(dk[0], (nd, D, I), pd, in_axis=1)},
            "up_proj": {"kernel": _dense_init(dk[1], (nd, D, I), pd, in_axis=1)},
            "down_proj": {"kernel": _dense_init(dk[2], (nd, I, D), pd, in_axis=1)},
        }
    if L > nd:
        params["moe"] = init_moe_params(keys[4], cfg.moe, D, pd, n_layers=L - nd)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(keys[5], (D, cfg.vocab_size), pd)}
    return params


def kda_block(cfg, backend, h, lp, norm_scale, segment_ids, constrain):
    """One KDA layer's mixer with its pre-norm and residual. Scope ``kda``
    (inside the caller's ``attn``) with the segments ``kda_conv``,
    ``kda_gate``, ``kda_chunk`` and ``kda_norm``; the projections are the
    scope's own. Every array is ``[B, S, H * dh]`` (or ``[B, S, H]``), a
    head's channels contiguous: the per-head RMS norm of ``o`` divides inside
    the delta-rule kernels (``out_norm_eps``: the float32 tile, before the
    one rounding), and ``kda_norm`` holds what is left of the gated norm, one
    flat float32 pass ``o * scale * sigmoid(gate)`` rounded once. ``o`` is
    rounded twice on its way to ``o_proj`` (the kernel's write, this pass)."""
    H, dh = cfg.kda_num_heads, cfg.kda_head_dim
    f32 = jnp.float32
    with jax.named_scope("kda"):
        x = rms_norm(h, norm_scale, cfg.rms_eps)
        proj = lambda name: x @ lp[name]["kernel"].astype(x.dtype)
        q, k, v = proj("q_proj"), proj("k_proj"), proj("v_proj")
        with jax.named_scope("kda_conv"):
            conv = lambda a, name: jax.nn.silu(
                causal_conv1d(a, lp[name]["weight"].astype(a.dtype), segment_ids))
            q, k, v = conv(q, "q_conv"), conv(k, "k_conv"), conv(v, "v_conv")
        with jax.named_scope("kda_gate"):
            f = proj("f_a_proj") @ lp["f_b_proj"]["kernel"].astype(x.dtype)
            f = f.astype(f32) + lp["dt_bias"].astype(f32)
            g = -jnp.repeat(jnp.exp(lp["A_log"].astype(f32)), dh) * jax.nn.softplus(f)
            beta = jax.nn.sigmoid(proj("b_proj").astype(f32))  # [B, S, H]
            gate = proj("g_a_proj") @ lp["g_b_proj"]["kernel"].astype(x.dtype)
        with jax.named_scope("kda_chunk"):
            # q, k, v, g stay [B, S, H * dh] as the convs and the gate left
            # them: the operator normalises q and k, forms beta k, beta v and
            # the clamp, and divides a head's row of o by its root mean
            # square, a tile at a time (ops/delta_rule.py)
            o = chunked_delta_rule(
                q, k, v, g, beta, segment_ids=segment_ids, out_norm_eps=cfg.rms_eps,
                platform=backend.platform, mesh_ctx=backend.mesh_ctx,
            )
        with jax.named_scope("kda_norm"):
            # what is left of the gated norm: one flat pass. The scale is a
            # [H * dh] row, so its gradient is a column sum of a flat array
            scale = jnp.tile(lp["o_norm"]["scale"].astype(f32), H)
            o = (o.astype(f32) * scale * jax.nn.sigmoid(gate.astype(f32))).astype(x.dtype)
        h = h + o @ lp["o_proj"]["kernel"].astype(x.dtype)
    return constrain(h, ("batch", "seq", None))


def forward_hidden(
    cfg: KimiLinearConfig,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    position_ids: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    constrain=_noop_constrain,
) -> tuple[jnp.ndarray, MoEModelAux]:
    from automodel_tpu.models.common.stacking import remat_wrap

    cd = backend.compute_jnp_dtype
    B, S = input_ids.shape
    with jax.named_scope("embed"):
        h = constrain(params["embed"]["embedding"], (None, None)).astype(cd)[input_ids]
    h = constrain(h, ("batch", "seq", None))
    cos = sin = None
    if cfg.use_rope:
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
        with jax.named_scope("attn"):
            cos, sin = rope_table(position_ids, cfg.qk_rope_head_dim, cfg.rope)
    nd = cfg.moe.num_dense_layers
    act = ACT_FNS[cfg.act]
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)

    counts_l, aux_l = [], []
    i_kda = i_mla = 0
    for i, kind in enumerate(cfg.layer_kinds):
        norms = at(params["layers"], i)
        if kind == "kda":
            mixer_p, i_kda = at(params["kda"], i_kda), i_kda + 1
        else:
            mixer_p, i_mla = at(params["mla"], i_mla), i_mla + 1
        mlp_p = at(params["dense_mlp"], i) if i < nd else at(params["moe"], i - nd)

        def layer(h, kind=kind, norms=norms, mixer_p=mixer_p, mlp_p=mlp_p, dense=i < nd):
            with jax.named_scope("attn"):
                if kind == "kda":
                    h = kda_block(cfg, backend, h, mixer_p, norms["input_norm"]["scale"],
                                  segment_ids, constrain)
                else:
                    with jax.named_scope("mla"):
                        h = mla_block(cfg, backend, h,
                                      {"attn": mixer_p, "input_norm": norms["input_norm"]},
                                      cos, sin, segment_ids, constrain)
            with jax.named_scope("norm"):
                x = rms_norm(h, norms["post_attn_norm"]["scale"], cfg.rms_eps)
            if dense:
                with jax.named_scope("mlp"):
                    out = (
                        act(x @ mlp_p["gate_proj"]["kernel"].astype(x.dtype))
                        * (x @ mlp_p["up_proj"]["kernel"].astype(x.dtype))
                    ) @ mlp_p["down_proj"]["kernel"].astype(x.dtype)
                aux = None
            else:
                out, aux = moe_block(
                    x, mlp_p, cfg.moe, act,
                    experts_backend=backend.experts,
                    fake_gate=backend.fake_balanced_gate,
                    constrain=constrain, platform=backend.platform,
                    fp8=backend.fp8_experts, act_name=cfg.act,
                )
            return constrain(h + out, ("batch", "seq", None)), aux

        h, aux = remat_wrap(layer, backend.remat)(h)
        if aux is not None:
            counts_l.append(aux.expert_counts)
            aux_l.append(aux.aux_loss)

    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"]["scale"], cfg.rms_eps)
    E = cfg.moe.num_experts
    counts = jnp.stack(counts_l) if counts_l else jnp.zeros((0, E), jnp.int32)
    aux_loss = jnp.stack(aux_l).sum() if aux_l else jnp.float32(0.0)
    held = None
    if cfg.moe.held_experts is not None:
        lo, hi = cfg.moe.held_experts
        held = counts[:, lo:hi].sum()  # picks that landed on held experts, all layers
    return h, MoEModelAux(counts, aux_loss, held)


_L = (None,)  # the stacked-layers axis
SHARDING_RULES: list[tuple[str, tuple]] = [
    (r"layers/.*norm/scale$", (None, None)),
    (r"kda/[qkv]_proj/kernel$", (*_L, "fsdp", "tensor")),
    (r"kda/[qkv]_conv/weight$", (*_L, "tensor", None)),
    (r"kda/A_log$", (*_L, "tensor")),
    (r"kda/dt_bias$", (*_L, "tensor")),
    (r"kda/[fg]_a_proj/kernel$", (*_L, "fsdp", None)),
    (r"kda/[fg]_b_proj/kernel$", (*_L, None, "tensor")),
    (r"kda/b_proj/kernel$", (*_L, "fsdp", "tensor")),
    (r"kda/o_norm/scale$", (None, None)),
    (r"kda/o_proj/kernel$", (*_L, "tensor", "fsdp")),
    (r"mla/q_proj/kernel$", (*_L, "fsdp", "tensor")),
    (r"mla/kv_a_proj/kernel$", (*_L, "fsdp", None)),
    (r"mla/kv_a_norm/scale$", (None, None)),
    (r"mla/kv_b_proj/kernel$", (*_L, "fsdp", "tensor")),
    (r"mla/o_proj/kernel$", (*_L, "tensor", "fsdp")),
    (r"dense_mlp/(gate|up)_proj/kernel$", (*_L, "fsdp", "tensor")),
    (r"dense_mlp/down_proj/kernel$", (*_L, "tensor", "fsdp")),
    (r"moe/router/weight$", (None, None, None)),
    (r"moe/router/(bias|linear_bias)$", (None, None)),
    (r"moe/experts/gate_up$", (*_L, "expert", "expert_fsdp", "tensor")),
    (r"moe/experts/down$", (*_L, "expert", "tensor", "expert_fsdp")),
    (r"moe/shared/(gate|up)_proj/kernel$", (*_L, "fsdp", "tensor")),
    (r"moe/shared/down_proj/kernel$", (*_L, "tensor", "fsdp")),
    (r"embed/embedding$", ("tensor", "fsdp")),
    (r"final_norm/scale$", (None,)),
    (r"lm_head/kernel$", ("fsdp", "tensor")),
]


@dataclasses.dataclass
class KimiLinearForCausalLM:
    config: KimiLinearConfig
    backend: BackendConfig = BackendConfig()

    def init(self, key: jax.Array) -> dict:
        return init_params(self.config, self.backend, key)

    def hidden(self, params, input_ids, **kw):
        return forward_hidden(self.config, self.backend, params, input_ids, **kw)

    def lm_head(self, params: dict) -> jnp.ndarray:
        if self.config.tie_embeddings:
            return params["embed"]["embedding"].T
        return params["lm_head"]["kernel"]

    def __call__(self, params, input_ids, **kw):
        h, aux = self.hidden(params, input_ids, **kw)
        return h @ self.lm_head(params).astype(h.dtype), aux

    def cache_layout(self) -> tuple:
        """What a serving cache keeps a layer: a delta-rule state ``[heads, dk,
        dv]`` (and three conv windows) for a KDA layer, one latent row a token
        for an MLA layer. serving/ holds latent rows (the latent pool,
        serving/paged.py) and no delta-rule state yet:
        ``ServeConfig.check_layout`` refuses the family for its ``delta``
        layers alone."""
        from automodel_tpu.generation import kv_cache

        c = self.config
        return tuple(
            kv_cache.LayerCache("delta", c.kda_num_heads, c.kda_head_dim) if k == "kda"
            else kv_cache.latent_layer(c.kv_lora_rank + c.qk_rope_head_dim, c.kv_lora_rank)
            for k in c.layer_kinds
        )

    @property
    def sharding_rules(self) -> list[tuple[str, tuple]]:
        return SHARDING_RULES

    def post_step_fn(self, params: dict, extras: dict) -> dict:
        u = self.config.moe.bias_update_factor
        if u <= 0 or "expert_counts" not in extras or "moe" not in params:
            return params
        params["moe"]["router"]["bias"] = jax.vmap(
            lambda b, c: update_gate_bias(b, c, u)
        )(params["moe"]["router"]["bias"], extras["expert_counts"])
        return params
