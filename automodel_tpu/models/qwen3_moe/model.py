"""MoE causal LM, TPU-native — the Qwen3-MoE-shaped family.

Covers the reference's qwen3_moe (components/models/qwen3_moe/, ~500 LoC) and
generalizes to any "dense-attention + per-layer routed-FFN" decoder: optional
dense prefix layers (DeepSeek's first_k_dense_replace), shared experts, and
every Gate feature in automodel_tpu.moe.

Structure follows the dense family (stacked layer leaves under `lax.scan`);
the attention block is literally the llama one. A layer's params are
{attn, input_norm, post_attn_norm, moe} with the dense prefix (if any) kept
as a separate stacked tree so each stack scans homogeneously.

Forward returns (logits, MoEModelAux) — aux carries per-layer expert counts
and the summed aux loss for the load-balance metrics and aux-free bias
updates (reference: moe/load_balance_metrics.py, train_ft.py:1341).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.generation import kv_cache as kv_cache_mod
from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
from automodel_tpu.models.llama.model import (
    ACT_FNS,
    SHARDING_RULES as DENSE_RULES,
    Constrain,
    _dense_init,
    _noop_constrain,
    attention_block,
)
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.gate import update_gate_bias
from automodel_tpu.moe.layer import init_moe_params, moe_block
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import rope_table


@dataclasses.dataclass(frozen=True)
class MoETransformerConfig(TransformerConfig):
    moe: Optional[MoEConfig] = None

    @classmethod
    def from_hf(cls, hf_cfg: Any) -> "MoETransformerConfig":
        base = TransformerConfig.from_hf(hf_cfg)
        get = lambda k, d=None: (
            hf_cfg.get(k, d) if isinstance(hf_cfg, dict) else getattr(hf_cfg, k, d)
        )
        model_type = get("model_type", "")
        # GLM4-MoE routes like DeepSeek-V3 (sigmoid scores + always-present
        # e_score_correction_bias, grouped top-k) but has no scoring_func /
        # topk_method keys in its HF config (modeling_glm4_moe.py
        # Glm4MoeTopkRouter)
        is_glm4 = model_type == "glm4_moe"
        # mixtral's expert MLP width is `intermediate_size` and its count
        # `num_local_experts` (handled by the get-chains below); qwen2-moe
        # always has one sigmoid-gated shared expert
        is_qwen2_moe = model_type == "qwen2_moe"
        aux_free = get("topk_method", None) == "noaux_tc" or is_glm4
        moe = MoEConfig(
            num_experts=get("num_experts", None)
            or get("n_routed_experts", None)
            or get("num_local_experts"),
            num_experts_per_tok=get("num_experts_per_tok", 8),
            moe_intermediate_size=get("moe_intermediate_size", None)
            or get("intermediate_size"),
            num_shared_experts=(
                1 if is_qwen2_moe else get("n_shared_experts", 0) or 0
            ),
            shared_expert_intermediate_size=get("shared_expert_intermediate_size", 0)
            or get("moe_intermediate_size", 0)
            or 0,
            shared_expert_gate=is_qwen2_moe,
            score_func=get("scoring_func", None) or ("sigmoid" if is_glm4 else "softmax"),
            # every softmax-scoring family ingested here (qwen3-moe, mixtral,
            # qwen2-moe) softmaxes the FULL router logits before top-k;
            # gpt-oss (softmax over the picked logits) sets its own config
            softmax_before_topk=True,
            route_scale=get("routed_scaling_factor", 1.0) or 1.0,
            norm_topk_prob=bool(get("norm_topk_prob", True)),
            n_group=get("n_group", 1) or 1,
            topk_group=get("topk_group", 1) or 1,
            aux_loss_coeff=get("router_aux_loss_coef", 0.0) or 0.0,
            num_dense_layers=get("first_k_dense_replace", 0) or 0,
            expert_bias=aux_free,
            bias_update_factor=0.001 if aux_free else 0.0,
        )
        fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
        fields["moe"] = moe
        # qwen3_moe uses qk per-head norms like qwen3; glm4_moe gates them
        if model_type in ("qwen3_moe", "qwen3moe", "qwen3_vl_moe_text"):
            fields["qk_norm"] = True
        elif is_glm4:
            fields["qk_norm"] = bool(get("use_qk_norm", False))
        return cls(**fields)


class MoEModelAux(NamedTuple):
    expert_counts: jnp.ndarray  # [L_moe, E]
    aux_loss: jnp.ndarray  # scalar
    # picks that landed on the experts this device holds, over all layers
    # (MoEConfig.held_experts); None where every expert is here
    held_expert_rows: Optional[jnp.ndarray] = None
    # a multi-token-prediction module's final-normed hidden [B, S, D]: the
    # loss runs the head on it against the labels shifted once more
    # (models/xing4); None where a family has no such module
    mtp_hidden: Optional[jnp.ndarray] = None
    # the largest |row sum - 1| of any hyper-connection Hres in the forward
    # (ops/hyper_connections.res_row_error); None without such a residual path
    mhc_res_row_err: Optional[jnp.ndarray] = None


def _init_attn_layer(cfg: TransformerConfig, backend: BackendConfig, key, L: int) -> dict:
    """Stacked attention + norm params for L layers (llama layout)."""
    pd = backend.param_jnp_dtype
    D = cfg.hidden_size
    keys = jax.random.split(key, 4)

    def stack(k, shape, in_axis=0):
        return _dense_init(k, (L, *shape), pd, in_axis=in_axis + 1)

    attn = {
        "q_proj": {"kernel": stack(keys[0], (D, cfg.q_dim))},
        "k_proj": {"kernel": stack(keys[1], (D, cfg.kv_dim))},
        "v_proj": {"kernel": stack(keys[2], (D, cfg.kv_dim))},
        "o_proj": {"kernel": stack(keys[3], (cfg.q_dim, D))},
    }
    if cfg.attention_bias:
        attn["q_proj"]["bias"] = jnp.zeros((L, cfg.q_dim), pd)
        attn["k_proj"]["bias"] = jnp.zeros((L, cfg.kv_dim), pd)
        attn["v_proj"]["bias"] = jnp.zeros((L, cfg.kv_dim), pd)
    if cfg.qk_norm:
        # minimax-m2 norms the FLATTENED projection dims (qk_norm_flat)
        qd = cfg.q_dim if cfg.qk_norm_flat else cfg.head_dim
        kd = cfg.kv_dim if cfg.qk_norm_flat else cfg.head_dim
        attn["q_norm"] = {"scale": jnp.ones((L, qd), pd)}
        attn["k_norm"] = {"scale": jnp.ones((L, kd), pd)}
    return {
        "attn": attn,
        "input_norm": {"scale": jnp.ones((L, D), pd)},
        "post_attn_norm": {"scale": jnp.ones((L, D), pd)},
    }


def init_params(cfg: MoETransformerConfig, backend: BackendConfig, key: jax.Array) -> dict:
    pd = backend.param_jnp_dtype
    D, I = cfg.hidden_size, cfg.intermediate_size
    moe = cfg.moe
    nd = moe.num_dense_layers
    nm = cfg.num_layers - nd
    keys = jax.random.split(key, 8)

    params: dict = {
        "embed": {
            "embedding": jax.random.normal(keys[0], (cfg.vocab_size, D)).astype(pd)
            * 0.02
        },
        "final_norm": {"scale": jnp.ones((D,), pd)},
    }
    if nd > 0:
        dense = _init_attn_layer(cfg, backend, keys[1], nd)
        dk = jax.random.split(keys[2], 3)
        dense["mlp"] = {
            "gate_proj": {"kernel": _dense_init(dk[0], (nd, D, I), pd, in_axis=1)},
            "up_proj": {"kernel": _dense_init(dk[1], (nd, D, I), pd, in_axis=1)},
            "down_proj": {"kernel": _dense_init(dk[2], (nd, I, D), pd, in_axis=1)},
        }
        params["dense_layers"] = dense
    moe_layers = _init_attn_layer(cfg, backend, keys[3], nm)
    moe_layers["moe"] = init_moe_params(keys[4], moe, D, pd, n_layers=nm)
    params["moe_layers"] = moe_layers
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(keys[5], (D, cfg.vocab_size), pd)}
    return params


def forward_hidden(
    cfg: MoETransformerConfig,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    position_ids: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    constrain: Constrain = _noop_constrain,
    attn_block: Any = attention_block,
    rope_dim: Optional[int] = None,
    inputs_embeds: Optional[jnp.ndarray] = None,
    rope_cos_sin: Optional[tuple] = None,
    deepstack: Optional[tuple] = None,
    cache: Optional[tuple] = None,
):
    """``inputs_embeds``/``rope_cos_sin``/``deepstack`` are the VLM hooks
    (qwen3_vl_moe): precomputed embeddings with image features scattered in,
    an mrope cos/sin table, and ``(visual_mask [B,S,1], ds [n_deep,B,S,D])``
    visual embeds added to the hidden states after each of the first n_deep
    layers (HF Qwen3VLMoeTextModel._deepstack_process).

    ``cache``: generation hook — ``(KVCache, CacheContext)``; the cache's
    layer axis covers dense-prefix + MoE layers in order, sliced statically
    per stack. Return becomes ``((h, aux), new_cache)``. Only the default
    llama attention block supports it (the VLM attn_block overrides don't
    decode)."""
    cd = backend.compute_jnp_dtype
    moe = cfg.moe
    kvc = ctx = None
    if cache is not None:
        if deepstack is not None:
            raise NotImplementedError("KV-cache decode with deepstack (VLM)")
        if attn_block is not attention_block:
            raise NotImplementedError(
                "KV-cache decode requires the default attention block"
            )
        kvc, ctx = cache
    if position_ids is None:
        position_ids = jnp.arange(input_ids.shape[1])[None, :].astype(jnp.int32)
        position_ids = jnp.broadcast_to(position_ids, input_ids.shape)
    if inputs_embeds is None:
        # explicit planned reshard before the gather: the table's fsdp dim
        # (dp_shard, ep, cp) doesn't match the batch-sharded gather output
        # and XLA otherwise emits an "involuntary full rematerialization"
        # (VERDICT r2 weak #6) — same data movement, chosen deliberately
        with jax.named_scope("embed"):
            h = constrain(params["embed"]["embedding"], (None, None)).astype(cd)[input_ids]
    else:
        h = inputs_embeds.astype(cd)
    h = constrain(h, ("batch", "seq", None))
    with jax.named_scope("attn"):  # the rope table every layer's attention reads
        cos, sin = rope_cos_sin if rope_cos_sin is not None else rope_table(
            position_ids, rope_dim or cfg.rope_dim or cfg.head_dim, cfg.rope
        )

    def maybe_remat(fn):
        from automodel_tpu.models.common.stacking import remat_wrap

        return remat_wrap(fn, backend.remat)

    nd = moe.num_dense_layers
    new_k_parts: list = []
    new_v_parts: list = []

    def attn_and_kv(carry, lp, layer_kv):
        with jax.named_scope("attn"):
            if layer_kv is None:
                return attn_block(
                    cfg, backend, carry, lp, cos, sin, segment_ids, constrain
                ), None
            return attn_block(
                cfg, backend, carry, lp, cos, sin, segment_ids, constrain,
                cache=layer_kv, cache_ctx=ctx,
            )

    if "dense_layers" in params:
        def dense_fn(carry, xs):
            lp, layer_kv = xs if cache is not None else (xs, None)
            hh, new_kv = attn_and_kv(carry, lp, layer_kv)
            with jax.named_scope("norm"):
                x = rms_norm(hh, lp["post_attn_norm"]["scale"], cfg.rms_eps)
            act = ACT_FNS[cfg.act]
            with jax.named_scope("mlp"):
                mlp = (
                    act(x @ lp["mlp"]["gate_proj"]["kernel"].astype(x.dtype))
                    * (x @ lp["mlp"]["up_proj"]["kernel"].astype(x.dtype))
                ) @ lp["mlp"]["down_proj"]["kernel"].astype(x.dtype)
                out = constrain(hh + mlp, ("batch", "seq", None))
            return out, (None if cache is None else new_kv)

        with jax.named_scope("attn"):  # this stack's slice of the cache
            dxs = (
                params["dense_layers"]
                if cache is None
                else (
                    params["dense_layers"],
                    (
                        kv_cache_mod.layer_range(kvc.k, 0, nd),
                        kv_cache_mod.layer_range(kvc.v, 0, nd),
                    ),
                )
            )
        with jax.named_scope("layers"):
            h, dys = jax.lax.scan(
                dense_fn if cache is not None else maybe_remat(dense_fn), h, dxs
            )
        if cache is not None:
            new_k_parts.append(dys[0])
            new_v_parts.append(dys[1])

    def moe_fn(carry, xs):
        lp, layer_kv = xs if cache is not None else (xs, None)
        hh, new_kv = attn_and_kv(carry, lp, layer_kv)
        with jax.named_scope("norm"):
            x = rms_norm(hh, lp["post_attn_norm"]["scale"], cfg.rms_eps)
        out, aux = moe_block(
            x,
            lp["moe"],
            moe,
            ACT_FNS[cfg.act],
            experts_backend=backend.experts,
            fake_gate=backend.fake_balanced_gate,
            constrain=constrain,
            platform=backend.platform,
            fp8=backend.fp8_experts,
            act_name=cfg.act,
        )
        hh = hh + out
        hh = constrain(hh, ("batch", "seq", None))
        return hh, (aux if cache is None else (aux, new_kv))

    nm = cfg.num_layers - nd
    if deepstack is not None:
        # run the first n_deep layers unstacked, adding the deepstack visual
        # embeds at image positions after each, then scan the homogeneous rest
        if moe.num_dense_layers:
            # HF injects after the first n_deep DECODER layers overall; with
            # first_k_dense_replace > 0 this loop (over MoE layers only)
            # would shift the injection points — no shipped deepstack model
            # has dense lead layers, so fail loudly rather than drift
            raise NotImplementedError(
                "deepstack injection with first_k_dense_replace "
                f"(num_dense_layers={moe.num_dense_layers}) is not supported"
            )
        vis_mask, ds = deepstack  # [B,S,1], [n_deep,B,S,D]
        nd = ds.shape[0]
        counts_l, aux_l = [], []
        for i in range(nd):
            lp = jax.tree.map(lambda x: x[i], params["moe_layers"])
            h, aux = maybe_remat(moe_fn)(h, lp)
            h = h + jnp.where(vis_mask, ds[i].astype(h.dtype), 0)
            counts_l.append(aux.expert_counts)
            aux_l.append(aux.aux_loss)
        rest = jax.tree.map(lambda x: x[nd:], params["moe_layers"])
        with jax.named_scope("layers"):
            h, auxs = jax.lax.scan(maybe_remat(moe_fn), h, rest)
        counts = jnp.concatenate([jnp.stack(counts_l), auxs.expert_counts])
        aux_losses = jnp.concatenate([jnp.stack(aux_l), auxs.aux_loss])
    elif backend.scan_layers:
        with jax.named_scope("attn"):  # this stack's slice of the cache
            mxs = (
                params["moe_layers"]
                if cache is None
                else (
                    params["moe_layers"],
                    (
                        kv_cache_mod.layer_range(kvc.k, nd),
                        kv_cache_mod.layer_range(kvc.v, nd),
                    ),
                )
            )
        # the scan's own carried and stacked buffers show as `layers` with
        # no inner scope (the benchmark's layer_scan_overhead_ms)
        with jax.named_scope("layers"):
            h, ys = jax.lax.scan(
                moe_fn if cache is not None else maybe_remat(moe_fn), h, mxs
            )
        if cache is not None:
            auxs, (mk, mv) = ys
            new_k_parts.append(mk)
            new_v_parts.append(mv)
        else:
            auxs = ys
        counts, aux_losses = auxs.expert_counts, auxs.aux_loss
    else:
        counts_l, aux_l, mk_l, mv_l = [], [], [], []
        for i in range(nm):
            lp = jax.tree.map(lambda x: x[i], params["moe_layers"])
            xs = (
                lp
                if cache is None
                else (
                    lp,
                    (
                        kv_cache_mod.layer_slice(kvc.k, nd + i),
                        kv_cache_mod.layer_slice(kvc.v, nd + i),
                    ),
                )
            )
            h, ys = moe_fn(h, xs)
            aux = ys if cache is None else ys[0]
            if cache is not None:
                mk_l.append(ys[1][0])
                mv_l.append(ys[1][1])
            counts_l.append(aux.expert_counts)
            aux_l.append(aux.aux_loss)
        counts = jnp.stack(counts_l)
        aux_losses = jnp.stack(aux_l)
        if cache is not None:
            new_k_parts.append(kv_cache_mod.stack_layer_sides(mk_l))
            new_v_parts.append(kv_cache_mod.stack_layer_sides(mv_l))

    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"]["scale"], cfg.rms_eps)
    out = (h, MoEModelAux(counts, aux_losses.sum()))
    if cache is None:
        return out
    with jax.named_scope("kv_write"):
        new_cache = kvc.replace(
            k=kv_cache_mod.concat_layer_sides(new_k_parts),
            v=kv_cache_mod.concat_layer_sides(new_v_parts),
        )
    return out, new_cache


def forward(
    cfg: MoETransformerConfig,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    attn_block: Any = attention_block,
    rope_dim: Optional[int] = None,
    cache: Optional[tuple] = None,
    **kw: Any,
):
    out = forward_hidden(
        cfg, backend, params, input_ids, attn_block=attn_block,
        rope_dim=rope_dim, cache=cache, **kw
    )
    (h, aux), new_cache = out if cache is not None else (out, None)
    kernel = (
        params["embed"]["embedding"].T
        if cfg.tie_embeddings
        else params["lm_head"]["kernel"]
    )
    with jax.named_scope("lm_head"):
        logits = h @ kernel.astype(h.dtype)
        if cfg.logits_soft_cap is not None:
            logits = cfg.logits_soft_cap * jnp.tanh(logits / cfg.logits_soft_cap)
    return (logits, aux) if cache is None else ((logits, aux), new_cache)


# dense rules match here too ("layers/attn/..." regexes find
# "moe_layers/attn/..." and "dense_layers/mlp/..." via re.search); MoE leaves
# get explicit stacked rules (leading layer dim unsharded).
SHARDING_RULES: list[tuple[str, tuple]] = [
    (r"moe/router/weight$", (None, None, None)),
    (r"moe/router/(bias|linear_bias)$", (None, None)),
    (r"moe/experts/gate_up$", (None, "expert", "expert_fsdp", "tensor")),
    (r"moe/experts/down$", (None, "expert", "tensor", "expert_fsdp")),
    (r"moe/experts/gate_up_bias$", (None, "expert", "tensor")),
    (r"moe/experts/down_bias$", (None, "expert", None)),
    (r"moe/shared/(gate|up)_proj/kernel$", (None, "fsdp", "tensor")),
    (r"moe/shared/down_proj/kernel$", (None, "tensor", "fsdp")),
    (r"moe/shared_gate/kernel$", (None, None, None)),
    *DENSE_RULES,
]


@dataclasses.dataclass
class MoEForCausalLM:
    """Bundled config + backend with the functional API underneath."""

    config: MoETransformerConfig
    backend: BackendConfig = BackendConfig()

    # attention rides llama's attention_block/_proj, which applies grafted
    # LoRA activation-side; mlp/expert weights do raw kernel matmuls and
    # stay on the merged fallback (see peft.lora.graft_lora)
    lora_graft_patterns = ("*/attn/[qkvo]_proj/kernel",)

    def cache_layout(self) -> tuple:
        """What each layer keeps between a sequence's tokens (the engines
        read this, generation/kv_cache.py): per-head K/V on every layer."""
        return kv_cache_mod.uniform_kv_layout(self.config)

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

    # hooks for parallel/pp.py: the per-layer attention block and rope dim
    # the pipelined forward must reuse
    @property
    def pp_attn_block(self):
        return attention_block

    pp_rope_dim = None

    @property
    def sharding_rules(self) -> list[tuple[str, tuple]]:
        return SHARDING_RULES

    # -- aux-free balancing hook (post-optimizer-step) -----------------------
    def post_step_fn(self, params: dict, extras: dict) -> dict:
        u = self.config.moe.bias_update_factor
        if u <= 0 or "expert_counts" not in extras:
            return params
        bias = params["moe_layers"]["moe"]["router"].get("bias")
        if bias is None:
            return params
        counts = extras["expert_counts"]  # [L, E] summed over microbatches
        new_bias = jax.vmap(lambda b, c: update_gate_bias(b, c, u))(bias, counts)
        params["moe_layers"]["moe"]["router"]["bias"] = new_bias
        return params
