"""Qwen3-Next: hybrid linear-attention (gated DeltaNet) + full-attention MoE.

Parity: reference models/qwen3_next/ (~700 LoC on fla/causal-conv1d CUDA
kernels) / HF modeling_qwen3_next.py. Architecture per layer_types entry:

- ``linear_attention``: depthwise causal conv over concat(q,k,v) → silu →
  chunked gated delta rule (delta.py) → gated RMSNorm (silu(z) gate) →
  out_proj;
- ``full_attention``: llama-style attention with an output gate carved from
  a double-width q_proj (out * sigmoid(gate)), zero-centered q/k norms,
  partial rotary (0.25);
- every layer: qwen2-moe-style MoE (softmax-before-topk router, shared
  expert with sigmoid gate), zero-centered input/post norms.

TPU structure: the two attention kinds have different param shapes, so the
stack splits into two stacked subtrees (full_attn / linear_attn) plus one
all-layers stack for norms+MoE; the layer loop is unrolled with static
per-layer routing (layer_types is config, not data).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.gemma.model import gemma_rms_norm
from automodel_tpu.models.llama.model import ACT_FNS, _dense_init, _noop_constrain
from automodel_tpu.models.qwen3_moe.model import (
    MoEModelAux,
    MoETransformerConfig,
)
from automodel_tpu.models.qwen3_next.delta import chunk_gated_delta_rule
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.layer import init_moe_params, moe_block
from automodel_tpu.ops.attention import attention
from automodel_tpu.ops.rope import apply_rope, rope_table
from automodel_tpu.ops.short_conv import causal_conv1d


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig(MoETransformerConfig):
    layer_types: tuple = ()
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4

    @classmethod
    def from_hf(cls, hf_cfg: Any) -> "Qwen3NextConfig":
        get = lambda k, d=None: (
            hf_cfg.get(k, d) if isinstance(hf_cfg, dict) else getattr(hf_cfg, k, d)
        )
        base = MoETransformerConfig.from_hf(hf_cfg)
        L = base.num_layers
        lt = get("layer_types") or [
            "full_attention" if (i + 1) % 4 == 0 else "linear_attention"
            for i in range(L)
        ]
        moe = dataclasses.replace(
            base.moe,
            softmax_before_topk=True,
            # qwen3-next always has ONE shared expert with a sigmoid gate
            # (qwen2-moe style); its HF config has no n_shared_experts key
            num_shared_experts=1,
            shared_expert_gate=True,
            shared_expert_intermediate_size=get("shared_expert_intermediate_size")
            or base.moe.moe_intermediate_size,
        )
        fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
        fields.update(
            moe=moe,
            layer_types=tuple(lt),
            qk_norm=True,
            linear_num_key_heads=get("linear_num_key_heads", 16),
            linear_num_value_heads=get("linear_num_value_heads", 32),
            linear_key_head_dim=get("linear_key_head_dim", 128),
            linear_value_head_dim=get("linear_value_head_dim", 128),
            linear_conv_kernel_dim=get("linear_conv_kernel_dim", 4),
        )
        return cls(**fields)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def n_full(self) -> int:
        return sum(t == "full_attention" for t in self.layer_types)

    @property
    def n_linear(self) -> int:
        return sum(t == "linear_attention" for t in self.layer_types)


def init_params(cfg: Qwen3NextConfig, backend: BackendConfig, key: jax.Array) -> dict:
    pd = backend.param_jnp_dtype
    D = cfg.hidden_size
    L, Lf, Ll = cfg.num_layers, cfg.n_full, cfg.n_linear
    keys = jax.random.split(key, 12)

    def stack(k, n, shape, in_axis=0):
        return _dense_init(k, (n, *shape), pd, in_axis=in_axis + 1)

    conv_dim = 2 * cfg.key_dim + cfg.value_dim
    params: dict = {
        "embed": {
            "embedding": jax.random.normal(keys[0], (cfg.vocab_size, D)).astype(pd)
            * 0.02
        },
        "layers": {
            "input_norm": {"scale": jnp.zeros((L, D), pd)},
            "post_attn_norm": {"scale": jnp.zeros((L, D), pd)},
            "moe": init_moe_params(keys[1], cfg.moe, D, pd, n_layers=L),
        },
        "full_attn": {
            "q_proj": {"kernel": stack(keys[2], Lf, (D, 2 * cfg.q_dim))},
            "k_proj": {"kernel": stack(keys[3], Lf, (D, cfg.kv_dim))},
            "v_proj": {"kernel": stack(keys[4], Lf, (D, cfg.kv_dim))},
            "o_proj": {"kernel": stack(keys[5], Lf, (cfg.q_dim, D))},
            "q_norm": {"scale": jnp.zeros((Lf, cfg.head_dim), pd)},
            "k_norm": {"scale": jnp.zeros((Lf, cfg.head_dim), pd)},
        },
        "linear_attn": {
            "in_qkvz": {"kernel": stack(keys[6], Ll, (D, 2 * cfg.key_dim + 2 * cfg.value_dim))},
            "in_ba": {"kernel": stack(keys[7], Ll, (D, 2 * cfg.linear_num_value_heads))},
            "conv": {"weight": jax.random.normal(keys[8], (Ll, conv_dim, cfg.linear_conv_kernel_dim)).astype(pd) * 0.02},
            "dt_bias": jnp.ones((Ll, cfg.linear_num_value_heads), pd),
            "A_log": jnp.zeros((Ll, cfg.linear_num_value_heads), pd),
            "norm": {"scale": jnp.ones((Ll, cfg.linear_value_head_dim), pd)},
            "out_proj": {"kernel": stack(keys[9], Ll, (cfg.value_dim, D))},
        },
        "final_norm": {"scale": jnp.zeros((D,), pd)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(keys[10], (D, cfg.vocab_size), pd)}
    return params


def _full_attn_layer(cfg, backend, x, ap, cos, sin, segment_ids):
    """Gated full attention (HF Qwen3NextAttention): q_proj emits
    [q | gate] per head; output is attn * sigmoid(gate)."""
    B, S, D = x.shape
    qg = x @ ap["q_proj"]["kernel"].astype(x.dtype)
    qg = qg.reshape(B, S, cfg.num_heads, 2 * cfg.head_dim)
    q, gate_ = qg[..., : cfg.head_dim], qg[..., cfg.head_dim :]
    gate_ = gate_.reshape(B, S, cfg.q_dim)
    k = (x @ ap["k_proj"]["kernel"].astype(x.dtype)).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim
    )
    v = (x @ ap["v_proj"]["kernel"].astype(x.dtype)).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim
    )
    q = gemma_rms_norm(q, ap["q_norm"]["scale"], cfg.rms_eps)
    k = gemma_rms_norm(k, ap["k_norm"]["scale"], cfg.rms_eps)
    q, k = apply_rope(q, k, cos, sin)
    out = attention(
        q, k, v,
        backend=backend.attn, platform=backend.platform,
        mesh_ctx=backend.mesh_ctx,
        causal=True, segment_ids=segment_ids,
        **(
            {"block_q": backend.attn_block_q, "block_kv": backend.attn_block_kv}
            if backend.attn == "flash"
            else {}
        ),
    )
    out = out.reshape(B, S, cfg.q_dim) * jax.nn.sigmoid(gate_.astype(jnp.float32)).astype(x.dtype)
    return out @ ap["o_proj"]["kernel"].astype(x.dtype)


def _linear_attn_layer(cfg, x, lp, segment_ids=None):
    """Gated DeltaNet (HF Qwen3NextGatedDeltaNet). ``segment_ids`` reset the
    conv window and the delta-rule state at packed-document boundaries."""
    B, S, D = x.shape
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    hk, hv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    ratio = nv // nk

    if "in_qkvz" in lp:
        qkvz = x @ lp["in_qkvz"]["kernel"].astype(x.dtype)
        ba = x @ lp["in_ba"]["kernel"].astype(x.dtype)
        # HF fix_query_key_value_ordering: grouped per k-head
        qkvz = qkvz.reshape(B, S, nk, 2 * hk + 2 * ratio * hv)
        q = qkvz[..., :hk]
        k = qkvz[..., hk : 2 * hk]
        vz = qkvz[..., 2 * hk :].reshape(B, S, nk, 2, ratio * hv)
        v = vz[..., 0, :].reshape(B, S, nv, hv)
        z = vz[..., 1, :].reshape(B, S, nv, hv)
        ba = ba.reshape(B, S, nk, 2 * ratio)
        b = ba[..., :ratio].reshape(B, S, nv)
        a = ba[..., ratio:].reshape(B, S, nv)
    else:
        # Qwen3.5-MoE native GatedDeltaNet: SEPARATE in_proj_qkv/z/b/a
        # (reference models/qwen3_5_moe/model.py:75-82); qkv keeps the same
        # per-k-head grouping [q | k | v·ratio], z/b/a are flat per v-head
        qkv = x @ lp["in_qkv"]["kernel"].astype(x.dtype)
        qkv = qkv.reshape(B, S, nk, 2 * hk + ratio * hv)
        q = qkv[..., :hk]
        k = qkv[..., hk : 2 * hk]
        v = qkv[..., 2 * hk :].reshape(B, S, nv, hv)
        z = (x @ lp["in_z"]["kernel"].astype(x.dtype)).reshape(B, S, nv, hv)
        b = x @ lp["in_b"]["kernel"].astype(x.dtype)  # [B, S, nv]
        a = x @ lp["in_a"]["kernel"].astype(x.dtype)

    # conv over concat(q,k,v) flat channels, then silu
    mixed = jnp.concatenate(
        [q.reshape(B, S, -1), k.reshape(B, S, -1), v.reshape(B, S, -1)], axis=-1
    )
    mixed = jax.nn.silu(
        causal_conv1d(mixed, lp["conv"]["weight"].astype(x.dtype), segment_ids)
    )
    q = mixed[..., : cfg.key_dim].reshape(B, S, nk, hk)
    k = mixed[..., cfg.key_dim : 2 * cfg.key_dim].reshape(B, S, nk, hk)
    v = mixed[..., 2 * cfg.key_dim :].reshape(B, S, nv, hv)

    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32)
    )
    q = jnp.repeat(q, ratio, axis=2)
    k = jnp.repeat(k, ratio, axis=2)

    core = chunk_gated_delta_rule(
        q, k, v, g, beta, segment_ids=segment_ids
    )  # [B, S, nv, hv]

    # gated RMSNorm (standard weight, silu(z) gate) in fp32
    cf = core.astype(jnp.float32)
    normed = cf * jax.lax.rsqrt((cf * cf).mean(-1, keepdims=True) + cfg.rms_eps)
    normed = lp["norm"]["scale"].astype(jnp.float32) * normed
    out = (normed * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return out.reshape(B, S, cfg.value_dim) @ lp["out_proj"]["kernel"].astype(x.dtype)


def forward_hidden(
    cfg: Qwen3NextConfig,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    position_ids: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    constrain=_noop_constrain,
) -> tuple[jnp.ndarray, MoEModelAux]:
    cd = backend.compute_jnp_dtype
    B, S = input_ids.shape
    if position_ids is None:
        position_ids = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)
        )
    h = constrain(params["embed"]["embedding"], (None, None)).astype(cd)[input_ids]
    h = constrain(h, ("batch", "seq", None))
    cos, sin = rope_table(position_ids, cfg.rope_dim or cfg.head_dim, cfg.rope)

    def maybe_remat(fn):
        from automodel_tpu.models.common.stacking import remat_wrap

        return remat_wrap(fn, backend.remat)

    counts_l, aux_l = [], []
    i_full = i_lin = 0
    for i, lt in enumerate(cfg.layer_types):
        norm_p = jax.tree.map(lambda x: x[i], params["layers"])

        if lt == "full_attention":
            ap = jax.tree.map(lambda x: x[i_full], params["full_attn"])
            i_full += 1
            mixer = lambda x, ap=ap: _full_attn_layer(
                cfg, backend, x, ap, cos, sin, segment_ids
            )
        else:
            lp = jax.tree.map(lambda x: x[i_lin], params["linear_attn"])
            i_lin += 1
            mixer = lambda x, lp=lp: _linear_attn_layer(
                cfg, x, lp, segment_ids=segment_ids
            )

        def layer(h, norm_p=norm_p, mixer=mixer):
            x = gemma_rms_norm(h, norm_p["input_norm"]["scale"], cfg.rms_eps)
            h = h + mixer(x)
            h = constrain(h, ("batch", "seq", None))
            x = gemma_rms_norm(h, norm_p["post_attn_norm"]["scale"], cfg.rms_eps)
            out, aux = moe_block(
                x,
                norm_p["moe"],
                cfg.moe,
                ACT_FNS[cfg.act],
                experts_backend=backend.experts,
                fake_gate=backend.fake_balanced_gate,
                constrain=constrain,
                platform=backend.platform,
                fp8=backend.fp8_experts,
                act_name=cfg.act,
            )
            return constrain(h + out, ("batch", "seq", None)), aux

        h, aux = maybe_remat(layer)(h)
        counts_l.append(aux.expert_counts)
        aux_l.append(aux.aux_loss)

    h = gemma_rms_norm(h, params["final_norm"]["scale"], cfg.rms_eps)
    return h, MoEModelAux(jnp.stack(counts_l), jnp.stack(aux_l).sum())


SHARDING_RULES: list[tuple[str, tuple]] = [
    (r"layers/.*norm/scale$", (None, None)),
    (r"layers/moe/router/weight$", (None, None, None)),
    (r"layers/moe/router/(bias|linear_bias)$", (None, None)),
    (r"layers/moe/experts/gate_up$", (None, "expert", "expert_fsdp", "tensor")),
    (r"layers/moe/experts/down$", (None, "expert", "tensor", "expert_fsdp")),
    (r"layers/moe/shared/(gate|up)_proj/kernel$", (None, "fsdp", "tensor")),
    (r"layers/moe/shared/down_proj/kernel$", (None, "tensor", "fsdp")),
    (r"layers/moe/shared_gate/kernel$", (None, None, None)),
    (r"full_attn/[qkv]_proj/kernel$", (None, "fsdp", "tensor")),
    (r"full_attn/o_proj/kernel$", (None, "tensor", "fsdp")),
    (r"full_attn/[qk]_norm/scale$", (None, None)),
    (r"linear_attn/in_qkvz/kernel$", (None, "fsdp", "tensor")),
    (r"linear_attn/in_ba/kernel$", (None, "fsdp", None)),
    (r"linear_attn/out_proj/kernel$", (None, "tensor", "fsdp")),
    (r"linear_attn/(conv/weight|dt_bias|A_log|norm/scale)$", ()),
    (r"embed/embedding$", ("tensor", "fsdp")),
    (r"final_norm/scale$", (None,)),
    (r"lm_head/kernel$", ("fsdp", "tensor")),
]


@dataclasses.dataclass
class Qwen3NextForCausalLM:
    config: Qwen3NextConfig
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

    @property
    def sharding_rules(self) -> list[tuple[str, tuple]]:
        return SHARDING_RULES

    def post_step_fn(self, params: dict, extras: dict) -> dict:
        return params  # softmax router — no aux-free bias to update
