"""DeepSeek-V3 family: MLA attention + sigmoid-gated MoE, TPU-native.

Parity: reference models/deepseek_v3 (model.py:346, layers.py:37-220 — MLA
multi-head latent attention with q/kv low-rank compression + decoupled RoPE;
sigmoid gate with grouped routing + aux-free bias, model.py:121-136).

Reuses the MoE decoder scaffolding (models/qwen3_moe/model.py) with the
attention block swapped for MLA; the MoE stack, shared experts, dense prefix,
aux plumbing, and EP sharding rules are identical.

MLA layout (names follow the HF checkpoint):
  q: x → q_a_proj [D,qr] → rmsnorm → q_b_proj [qr, N*(nope+rope)]
     (or a single q_proj when q_lora_rank is null)
  kv: x → kv_a_proj_with_mqa [D, kvr+rope]; split; rmsnorm(kv part)
      → kv_b_proj [kvr, N*(nope+v)]; rope part is a single shared head
  attention over concat(nope, rope) dims; v_head_dim may differ from qk dim.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.llama.model import Constrain, _dense_init
from automodel_tpu.models.qwen3_moe.model import (
    MoEModelAux,
    MoETransformerConfig,
    SHARDING_RULES as MOE_RULES,
    forward_hidden as moe_forward_hidden,
    init_params as moe_init_params,
)
from automodel_tpu.moe.gate import update_gate_bias
from automodel_tpu.ops.attention import attention
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import apply_rope, yarn_mscale


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config(MoETransformerConfig):
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_interleave: bool = True
    # False: no rotary anywhere in the latent block (Kimi-Linear's
    # `mla_use_nope`); the `qk_rope_head_dim` channels then pass as they are
    use_rope: bool = True

    @classmethod
    def from_hf(cls, hf_cfg: Any) -> "DeepseekV3Config":
        base = MoETransformerConfig.from_hf(hf_cfg)
        get = lambda k, d=None: (
            hf_cfg.get(k, d) if isinstance(hf_cfg, dict) else getattr(hf_cfg, k, d)
        )
        fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
        fields.update(
            q_lora_rank=get("q_lora_rank"),
            kv_lora_rank=get("kv_lora_rank", 512),
            qk_nope_head_dim=get("qk_nope_head_dim", 128),
            qk_rope_head_dim=get("qk_rope_head_dim", 64),
            v_head_dim=get("v_head_dim", 128),
            rope_interleave=bool(get("rope_interleave", True)),
            qk_norm=False,
            # V3's router always carries e_score_correction_bias (zero-init
            # buffer) and balances aux-free (modeling_deepseek_v3.py:121)
            moe=dataclasses.replace(
                fields["moe"],
                # sigmoid scoring is hardcoded in V3 (modeling_deepseek_v3.py:
                # forward: router_logits.sigmoid()), not a config field
                score_func=get("scoring_func", None) or "sigmoid",
                expert_bias=True,
                bias_update_factor=fields["moe"].bias_update_factor or 1e-3,
            ),
        )
        return cls(**fields)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def mla_attn_scale(self) -> float:
        # HF DeepseekV3Attention: qk_head_dim^-0.5 × yarn mscale² folded into
        # the softmax scale (mscale_all_dim variant)
        import math

        scale = self.qk_head_dim**-0.5
        r = self.rope
        if r.scaling == "yarn" and r.factor > 1.0 and r.mscale_all_dim:
            m = 0.1 * r.mscale_all_dim * math.log(r.factor) + 1.0
            scale = scale * m * m
        return scale


def init_mla_layer(cfg: DeepseekV3Config, backend: BackendConfig, key, L: int) -> dict:
    pd = backend.param_jnp_dtype
    D, N = cfg.hidden_size, cfg.num_heads
    qk, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    keys = jax.random.split(key, 6)

    def stack(k, shape, in_axis=0):
        return _dense_init(k, (L, *shape), pd, in_axis=in_axis + 1)

    attn: dict = {
        "kv_a_proj": {"kernel": stack(keys[2], (D, cfg.kv_lora_rank + rope))},
        "kv_a_norm": {"scale": jnp.ones((L, cfg.kv_lora_rank), pd)},
        "kv_b_proj": {"kernel": stack(keys[3], (cfg.kv_lora_rank, N * (qk + v)))},
        "o_proj": {"kernel": stack(keys[4], (N * v, D))},
    }
    if cfg.q_lora_rank:
        attn["q_a_proj"] = {"kernel": stack(keys[0], (D, cfg.q_lora_rank))}
        attn["q_a_norm"] = {"scale": jnp.ones((L, cfg.q_lora_rank), pd)}
        attn["q_b_proj"] = {"kernel": stack(keys[1], (cfg.q_lora_rank, N * (qk + rope)))}
    else:
        attn["q_proj"] = {"kernel": stack(keys[0], (D, N * (qk + rope)))}
    return {
        "attn": attn,
        "input_norm": {"scale": jnp.ones((L, D), pd)},
        "post_attn_norm": {"scale": jnp.ones((L, D), pd)},
    }


def mla_branch(
    cfg: DeepseekV3Config,
    backend: BackendConfig,
    x: jnp.ndarray,
    ap: dict,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    segment_ids: Optional[jnp.ndarray],
    cache: Optional[jnp.ndarray] = None,
    cache_ctx: Any = None,
):
    """The latent-attention branch: the NORMED input ``x`` [B, S, D] in, the
    output projection's result out, no residual. ``mla_block`` adds it to the
    single-stream residual; models/xing4 writes it into a multi-stream one.

    ``cache`` / ``cache_ctx`` (serving/): the stacked LATENT pool ``[L, NB, BS,
    W]`` and the paged plan with this layer named (``CacheContext.at_layer``).
    What is cached a token is ``[c | rotated k_rot]`` (``kv_lora_rank`` +
    ``qk_rope_head_dim`` numbers, every head's keys and values in one row);
    the return becomes ``(out, pool)``. A decode step's one query a sequence
    attends ABSORBED, ``kv_b_proj`` folded into the query and the output; a
    prompt's chunk reads its prefix back and expands it through ``kv_b_proj``
    (ops/latent_attention.py)."""
    B, S, D = x.shape
    N = cfg.num_heads
    nope, rope, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    if cfg.q_lora_rank:
        qa = x @ ap["q_a_proj"]["kernel"].astype(x.dtype)
        qa = rms_norm(qa, ap["q_a_norm"]["scale"], cfg.rms_eps)
        q = qa @ ap["q_b_proj"]["kernel"].astype(x.dtype)
    else:
        q = x @ ap["q_proj"]["kernel"].astype(x.dtype)
    if cache_ctx is not None:
        # a serving program's few rows: without the barrier the compiler moves the
        # split of the 192-wide heads below into the WEIGHT's layout and copies
        # the whole q projection (100 MB at 4096 x 64 x 192) a layer a call
        q = jax.lax.optimization_barrier(q)
    q = q.reshape(B, S, N, nope + rope)
    q_pass, q_rot = q[..., :nope], q[..., nope:]

    ckv = x @ ap["kv_a_proj"]["kernel"].astype(x.dtype)  # [B,S,kvr+rope]
    k_pass_c, k_rot = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank :]
    k_pass_c = rms_norm(k_pass_c, ap["kv_a_norm"]["scale"], cfg.rms_eps)
    if cache_ctx is None:
        kv = (k_pass_c @ ap["kv_b_proj"]["kernel"].astype(x.dtype)).reshape(
            B, S, N, nope + vdim
        )
        k_pass, v = kv[..., :nope], kv[..., nope:]

    k_rot = k_rot[:, :, None, :]  # single shared rope head [B,S,1,rope]
    if cfg.use_rope:
        q_rot, k_rot = apply_rope(q_rot, k_rot, cos, sin, interleave=cfg.rope_interleave)

    if cache_ctx is not None:
        from automodel_tpu.ops import latent_attention

        pool = cache_ctx.write_latent(
            cache, jnp.concatenate([k_pass_c, k_rot[:, :, 0, :]], axis=-1)
        )
        kw = dict(layer=cache_ctx.layer, scale=cfg.mla_attn_scale, v_dim=vdim)
        w_kvb = ap["kv_b_proj"]["kernel"]
        if S == 1:
            out = latent_attention.absorbed_attend(
                q_pass, q_rot, pool, w_kvb, cache_ctx.tables, cache_ctx.q_pos,
                interpret=cache_ctx.paged_interpret, gather=cache_ctx.paged_gather,
                **kw,
            )
        else:
            out = latent_attention.chunk_attend(
                q_pass, q_rot, pool, w_kvb, cache_ctx.tables, cache_ctx.q_pos,
                interpret=cache_ctx.paged_interpret, gather=cache_ctx.paged_gather,
                **kw,
            )
        out = out.reshape(B, S, N * vdim) @ ap["o_proj"]["kernel"].astype(x.dtype)
        return out, pool

    k_rot = jnp.broadcast_to(k_rot, (B, S, N, rope))

    qh = jnp.concatenate([q_pass, q_rot], axis=-1)
    kh = jnp.concatenate([k_pass, k_rot], axis=-1)

    # splash takes a V narrower than q/k as it is (its out block is head_dim_v)
    out = attention(
        qh,
        kh,
        v,
        backend=backend.attn,
        platform=backend.platform,
        mesh_ctx=backend.mesh_ctx,
        causal=True,
        scale=cfg.mla_attn_scale,
        segment_ids=segment_ids,
        **(
            {"block_q": backend.attn_block_q, "block_kv": backend.attn_block_kv}
            if backend.attn == "flash"
            else {}
        ),
    )
    return out.reshape(B, S, N * vdim) @ ap["o_proj"]["kernel"].astype(x.dtype)


def mla_block(
    cfg: DeepseekV3Config,
    backend: BackendConfig,
    h: jnp.ndarray,
    lp: dict,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    segment_ids: Optional[jnp.ndarray],
    constrain: Constrain,
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    x = rms_norm(h, lp["input_norm"]["scale"], cfg.rms_eps)
    h = h + mla_branch(cfg, backend, x, lp["attn"], cos, sin, segment_ids)
    return constrain(h, ("batch", "seq", None))


def init_params(cfg: DeepseekV3Config, backend: BackendConfig, key: jax.Array) -> dict:
    params = moe_init_params(cfg, backend, key)
    # replace llama attention params with MLA in both stacks
    k1, k2 = jax.random.split(jax.random.fold_in(key, 7))
    nd = cfg.moe.num_dense_layers
    nm = cfg.num_layers - nd
    if nd > 0:
        mla = init_mla_layer(cfg, backend, k1, nd)
        params["dense_layers"]["attn"] = mla["attn"]
    params["moe_layers"]["attn"] = init_mla_layer(cfg, backend, k2, nm)["attn"]
    return params


SHARDING_RULES: list[tuple[str, tuple]] = [
    (r"attn/q_a_proj/kernel$", (None, "fsdp", None)),
    (r"attn/q_a_norm/scale$", (None, None)),
    (r"attn/q_b_proj/kernel$", (None, "fsdp", "tensor")),
    (r"attn/q_proj/kernel$", (None, "fsdp", "tensor")),
    (r"attn/kv_a_proj/kernel$", (None, "fsdp", None)),
    (r"attn/kv_a_norm/scale$", (None, None)),
    (r"attn/kv_b_proj/kernel$", (None, "fsdp", "tensor")),
    (r"attn/o_proj/kernel$", (None, "tensor", "fsdp")),
    *MOE_RULES,
]


@dataclasses.dataclass
class DeepseekV3ForCausalLM:
    config: DeepseekV3Config
    backend: BackendConfig = BackendConfig()

    def init(self, key: jax.Array) -> dict:
        return init_params(self.config, self.backend, key)

    def _fwd_hidden(self, params, input_ids, **kw):
        return moe_forward_hidden(
            self.config,
            self.backend,
            params,
            input_ids,
            attn_block=mla_block,
            rope_dim=self.config.qk_rope_head_dim,
            **kw,
        )

    def __call__(self, params: dict, input_ids: jnp.ndarray, **kw: Any):
        h, aux = self._fwd_hidden(params, input_ids, **kw)
        logits = h @ self.lm_head(params).astype(h.dtype)
        return logits, aux

    def hidden(self, params: dict, input_ids: jnp.ndarray, **kw: Any):
        return self._fwd_hidden(params, input_ids, **kw)

    def lm_head(self, params: dict) -> jnp.ndarray:
        if self.config.tie_embeddings:
            return params["embed"]["embedding"].T
        return params["lm_head"]["kernel"]

    # hooks for parallel/pp.py (MLA block + decoupled-rope dim)
    @property
    def pp_attn_block(self):
        return mla_block

    @property
    def pp_rope_dim(self):
        return self.config.qk_rope_head_dim

    @property
    def sharding_rules(self) -> list[tuple[str, tuple]]:
        return SHARDING_RULES

    def post_step_fn(self, params: dict, extras: dict) -> dict:
        u = self.config.moe.bias_update_factor
        if u <= 0 or "expert_counts" not in extras:
            return params
        bias = params["moe_layers"]["moe"]["router"].get("bias")
        if bias is None:
            return params
        counts = extras["expert_counts"]
        params["moe_layers"]["moe"]["router"]["bias"] = jax.vmap(
            lambda b, c: update_gate_bias(b, c, u)
        )(bias, counts)
        return params
