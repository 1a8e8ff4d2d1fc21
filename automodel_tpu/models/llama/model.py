"""Dense Llama-family causal LM, TPU-native.

Covers the reference's dense families llama/qwen2/qwen3
(components/models/llama/model.py:526, qwen2, qwen3 — config flags select
attention bias / qk-norm / tied embeddings) as ONE functional implementation:

- params are a plain pytree; every per-layer leaf is stacked on a leading
  layer axis so the whole decoder runs under `lax.scan` (one XLA While op —
  constant compile time in depth, PP-splittable by slicing the layer axis);
- compute follows BackendConfig (attention backend, remat policy, dtypes);
- parallelism is applied from outside via sharding rules on param paths and
  an activation-constraint callback — the model stays pure (the reference
  enforces the same split: model code pure torch, parallelism in config,
  README.md:59-66).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.generation import kv_cache
from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
from automodel_tpu.ops.attention import attention
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import apply_rope, rope_table

Constrain = Callable[[jnp.ndarray, tuple], jnp.ndarray]
_noop_constrain: Constrain = lambda x, spec: x

ACT_FNS = {
    "silu": jax.nn.silu,
    # HF ACT2FN["gelu"] is the exact erf form; jax defaults to tanh-approx
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_pytorch_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def _dense_init(key, shape, dtype, in_axis: int = 0):
    fan_in = shape[in_axis]
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype) / jnp.sqrt(
        jnp.asarray(fan_in, jnp.float32)
    ).astype(dtype)


def init_params(cfg: TransformerConfig, backend: BackendConfig, key: jax.Array) -> dict:
    """Random init (pretraining); layer leaves stacked [L, ...]."""
    pd = backend.param_jnp_dtype
    L, D, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    keys = jax.random.split(key, 10)

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
        "input_norm": {"scale": jnp.ones((L, D), pd)},
        "post_attn_norm": {"scale": jnp.ones((L, D), pd)},
    }
    if cfg.attention_bias:
        layers["attn"]["q_proj"]["bias"] = jnp.zeros((L, cfg.q_dim), pd)
        layers["attn"]["k_proj"]["bias"] = jnp.zeros((L, cfg.kv_dim), pd)
        layers["attn"]["v_proj"]["bias"] = jnp.zeros((L, cfg.kv_dim), pd)
    if cfg.mlp_bias:
        layers["mlp"]["gate_proj"]["bias"] = jnp.zeros((L, I), pd)
        layers["mlp"]["up_proj"]["bias"] = jnp.zeros((L, I), pd)
        layers["mlp"]["down_proj"]["bias"] = jnp.zeros((L, D), pd)
    if cfg.qk_norm:
        qd = cfg.q_dim if cfg.qk_norm_flat else cfg.head_dim
        kd = cfg.kv_dim if cfg.qk_norm_flat else cfg.head_dim
        layers["attn"]["q_norm"] = {"scale": jnp.ones((L, qd), pd)}
        layers["attn"]["k_norm"] = {"scale": jnp.ones((L, kd), pd)}
    params = {
        "embed": {"embedding": jax.random.normal(keys[7], (cfg.vocab_size, D)).astype(pd) * 0.02},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((D,), pd)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(keys[8], (D, cfg.vocab_size), pd)}
    return params


def _maybe_nf4(kernel):
    """NF4-packed kernels (QLoRA bound base) dequantize HERE — inside the
    layer scan body — so only ONE layer's bf16 weights exist at a time; a
    dequant at the loss top would materialize the whole stack (15.3GB for
    8B). quantization/qlora.py packs stacked leaves per layer for this."""
    if isinstance(kernel, dict) and "codes" in kernel:
        from automodel_tpu.quantization.qlora import nf4_dequantize

        return nf4_dequantize(kernel)
    return kernel


def _proj(x: jnp.ndarray, p: dict, fp8: bool = False) -> jnp.ndarray:
    from automodel_tpu.ops import fp8 as _fp8

    if "zb_tap" in p:
        # zero-bubble pipeline B-pass (parallel/zero_bubble.py): the grafted
        # tap pair routes this projection through the B/W-split matmul —
        # backward computes dx only and exports (x, dy) for the deferred
        # weight-grad contraction. Grafting is gated off fp8/NF4/LoRA sites.
        from automodel_tpu.parallel.zero_bubble import split_dot

        xtap, ytap = p["zb_tap"]
        y = split_dot(xtap.ndim == x.ndim, x, p["kernel"], xtap, ytap)
    else:
        y = _fp8.maybe_fp8_dot(x, _maybe_nf4(p["kernel"]), fp8)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    if "lora_A" in p:
        # activation-side LoRA (grafted by peft.graft_lora; scale folded into
        # A). The merged form W+s·A@B forces the layer-scan backward to carry
        # a full-rank [L,in,out] dW accumulator — at 3B+ that alone OOMs a
        # 16GB chip; the two rank-r matmuls here never materialize it.
        xa = x
        if "lora_drop_seed" in p:
            # input-side adapter dropout (reference LinearLoRA placement);
            # seeds are per-step/site/layer, grafted by make_lora_loss_fn
            key = jax.random.wrap_key_data(p["lora_drop_seed"])
            keep = 1.0 - p["lora_drop_rate"]
            mask = jax.random.bernoulli(key, keep, x.shape)
            xa = x * mask.astype(x.dtype) / keep.astype(x.dtype)
        y = y + (xa @ p["lora_A"].astype(x.dtype)) @ p["lora_B"].astype(x.dtype)
    return y


def _layer_sliding_window(cfg: TransformerConfig, layer_idx: int) -> Optional[int]:
    """HF qwen2 semantics: layers < max_window_layers attend fully."""
    if cfg.sliding_window is None:
        return None
    if cfg.max_window_layers and layer_idx < cfg.max_window_layers:
        return None
    return cfg.sliding_window


def attention_block(
    cfg: TransformerConfig,
    backend: BackendConfig,
    h: jnp.ndarray,
    lp: dict,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    segment_ids: Optional[jnp.ndarray],
    constrain: Constrain,
    sliding_window: Optional[int] = None,
    cache: Optional[tuple] = None,
    cache_ctx: Any = None,
):
    """Pre-norm attention + residual; shared across dense and MoE families.

    ``cache``/``cache_ctx`` (generation subsystem): ``cache`` is this
    layer's KV slice ``(k [B,C,Nkv,H], v [B,C,Nkv,H])`` riding the layer
    scan; ``cache_ctx`` is the shared per-forward write/attend plan
    (generation.kv_cache.CacheContext). Post-RoPE k/v are written into the
    cache; prefill then attends normally over the incoming block (the
    packed segment-ids path), decode attends the single query over the
    cache under the position-tag mask. With a cache the return value is
    ``(h, (new_k, new_v))`` instead of ``h``."""
    B, S, D = h.shape
    with jax.named_scope("norm"):
        x = rms_norm(h, lp["input_norm"]["scale"], cfg.rms_eps)
    q = _proj(x, lp["attn"]["q_proj"], backend.fp8)
    k = _proj(x, lp["attn"]["k_proj"], backend.fp8)
    v = _proj(x, lp["attn"]["v_proj"], backend.fp8).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm and cfg.qk_norm_flat:
        # MiniMax-M2: RMSNorm over flattened projection dims pre-reshape
        q = rms_norm(q, lp["attn"]["q_norm"]["scale"], cfg.rms_eps)
        k = rms_norm(k, lp["attn"]["k_norm"]["scale"], cfg.rms_eps)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm and not cfg.qk_norm_flat:
        q = rms_norm(q, lp["attn"]["q_norm"]["scale"], cfg.rms_eps)
        k = rms_norm(k, lp["attn"]["k_norm"]["scale"], cfg.rms_eps)
    q, k = apply_rope(q, k, cos, sin)
    new_layer_kv = None
    if cache is not None:
        ck, cv = cache
        with jax.named_scope("kv_write"):
            new_layer_kv = cache_ctx.write(ck, cv, k, v)
        if cache_ctx.attends_cache:
            # decode (single query) and chunked prefill (serving/): attend
            # over the cache — sdpa_decode under the position-tag mask, or
            # the fused paged kernel indexing the block pool in place; the
            # ctx owns the dispatch (generation.kv_cache.CacheContext.attend)
            attn_out = cache_ctx.attend(
                q, new_layer_kv,
                sliding_window=sliding_window,
                scale=cfg.attn_scale,
                logits_soft_cap=cfg.attn_soft_cap,
                mesh_ctx=backend.mesh_ctx,
            )
            h = h + _proj(
                attn_out.reshape(B, S, cfg.q_dim), lp["attn"]["o_proj"], backend.fp8
            )
            return constrain(h, ("batch", "seq", None)), new_layer_kv
    attn_out = attention(
        q,
        k,
        v,
        backend=backend.attn,
        platform=backend.platform,
        mesh_ctx=backend.mesh_ctx,
        causal=cfg.causal,
        scale=cfg.attn_scale,
        segment_ids=segment_ids,
        logits_soft_cap=cfg.attn_soft_cap,
        sliding_window=sliding_window,
        **(
            {"block_q": backend.attn_block_q, "block_kv": backend.attn_block_kv}
            if backend.attn == "flash"
            else {}
        ),
    )
    h = h + _proj(attn_out.reshape(B, S, cfg.q_dim), lp["attn"]["o_proj"], backend.fp8)
    h = constrain(h, ("batch", "seq", None))
    return h if cache is None else (h, new_layer_kv)


def decoder_layer(
    cfg: TransformerConfig,
    backend: BackendConfig,
    h: jnp.ndarray,
    lp: dict,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    segment_ids: Optional[jnp.ndarray],
    constrain: Constrain,
    sliding_window: Optional[int] = None,
    cache: Optional[tuple] = None,
    cache_ctx: Any = None,
):
    out = attention_block(
        cfg, backend, h, lp, cos, sin, segment_ids, constrain, sliding_window,
        cache=cache, cache_ctx=cache_ctx,
    )
    h, new_layer_kv = out if cache is not None else (out, None)
    x = rms_norm(h, lp["post_attn_norm"]["scale"], cfg.rms_eps)
    act = ACT_FNS[cfg.act]
    mlp = _proj(
        act(_proj(x, lp["mlp"]["gate_proj"], backend.fp8))
        * _proj(x, lp["mlp"]["up_proj"], backend.fp8),
        lp["mlp"]["down_proj"], backend.fp8,
    )
    h = h + mlp
    h = constrain(h, ("batch", "seq", None))
    return h if cache is None else (h, new_layer_kv)


def forward_hidden(
    cfg: TransformerConfig,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    position_ids: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    constrain: Constrain = _noop_constrain,
    inputs_embeds: Optional[jnp.ndarray] = None,
    cache: Optional[tuple] = None,
):
    """Embed + decoder stack → final-norm hidden states [B, S, D].

    ``inputs_embeds``: VLM hook (same contract as gemma/qwen3_moe) — caller
    already embedded text tokens and scattered projected image features.

    ``cache``: generation hook — ``(KVCache, CacheContext)`` from
    generation.kv_cache.prefill_ctx/decode_ctx. The per-layer KV slices
    ride the layer scan as xs/ys; the return value becomes
    ``(hidden, new_KVCache)``."""
    cd = backend.compute_jnp_dtype
    if position_ids is None:
        position_ids = jnp.arange(input_ids.shape[1])[None, :].astype(jnp.int32)
        position_ids = jnp.broadcast_to(position_ids, input_ids.shape)
    if inputs_embeds is not None:
        h = inputs_embeds.astype(cd)
    else:
        h = constrain(params["embed"]["embedding"], (None, None)).astype(cd)[input_ids]
        if cfg.embed_scale != 1.0:
            h = h * jnp.asarray(cfg.embed_scale, cd)
    h = constrain(h, ("batch", "seq", None))
    cos, sin = rope_table(position_ids, cfg.rope_dim or cfg.head_dim, cfg.rope)

    kvc = ctx = None
    if cache is not None:
        kvc, ctx = cache

    def make_layer_fn(sliding_window):
        def layer_fn(carry, xs):
            lp, layer_kv = (xs, None) if cache is None else xs
            out = decoder_layer(
                cfg, backend, carry, lp, cos, sin, segment_ids, constrain,
                sliding_window=sliding_window, cache=layer_kv, cache_ctx=ctx,
            )
            return out if cache is not None else (out, None)

        if cache is not None:
            # inference: no backward pass, remat would only re-run compute
            return layer_fn
        from automodel_tpu.models.common.stacking import remat_wrap

        return remat_wrap(layer_fn, backend.remat)

    L = cfg.num_layers
    # mixed full/windowed layers force per-layer calls; the homogeneous case
    # (every layer same window) keeps the single lax.scan over stacked params.
    homogeneous = cfg.sliding_window is None or cfg.max_window_layers in (0, None)
    new_cache = None
    if backend.scan_layers and homogeneous:
        xs = (
            params["layers"]
            if cache is None
            else (params["layers"], (kvc.k, kvc.v))
        )
        h, ys = jax.lax.scan(make_layer_fn(_layer_sliding_window(cfg, 0)), h, xs)
        if cache is not None:
            new_cache = kvc.replace(k=ys[0], v=ys[1])
    else:
        new_k, new_v = [], []
        for i in range(L):
            lp = jax.tree.map(lambda x: x[i], params["layers"])
            xs = (
                lp
                if cache is None
                else (lp, (kv_cache.layer_slice(kvc.k, i), kv_cache.layer_slice(kvc.v, i)))
            )
            h, lkv = make_layer_fn(_layer_sliding_window(cfg, i))(h, xs)
            if cache is not None:
                new_k.append(lkv[0])
                new_v.append(lkv[1])
        if cache is not None:
            new_cache = kvc.replace(
                k=kv_cache.stack_layer_sides(new_k),
                v=kv_cache.stack_layer_sides(new_v),
            )
    h = rms_norm(h, params["final_norm"]["scale"], cfg.rms_eps)
    return h if cache is None else (h, new_cache)


def lm_head_kernel(cfg: TransformerConfig, params: dict) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return params["embed"]["embedding"].T
    return _maybe_nf4(params["lm_head"]["kernel"])


def forward(
    cfg: TransformerConfig,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    position_ids: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    constrain: Constrain = _noop_constrain,
    cache: Optional[tuple] = None,
):
    """Full forward → logits [B, S, V] (compute dtype); with ``cache``
    (generation) → ``(logits, new_KVCache)``."""
    out = forward_hidden(
        cfg, backend, params, input_ids, position_ids, segment_ids, constrain,
        cache=cache,
    )
    h, new_cache = out if cache is not None else (out, None)
    logits = h @ lm_head_kernel(cfg, params).astype(h.dtype)
    if cfg.logits_soft_cap is not None:
        logits = cfg.logits_soft_cap * jnp.tanh(logits / cfg.logits_soft_cap)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits if cache is None else (logits, new_cache)


# -- sharding rules ---------------------------------------------------------
# Logical dim specs per param-path regex; resolved against the MeshContext by
# automodel_tpu.parallel.plans. This is the reference's "TP plan" concept
# (distributed/optimized_tp_plans.py) as pure annotation.
SHARDING_RULES: list[tuple[str, tuple]] = [
    (r"embed/embedding$", ("tensor", "fsdp")),
    (r"layers/attn/[qkv]_proj/kernel$", (None, "fsdp", "tensor")),
    (r"layers/attn/[qkv]_proj/bias$", (None, "tensor")),
    (r"layers/attn/o_proj/kernel$", (None, "tensor", "fsdp")),
    (r"layers/attn/[qk]_norm/scale$", (None, None)),
    (r"layers/mlp/(gate|up)_proj/kernel$", (None, "fsdp", "tensor")),
    (r"layers/mlp/(gate|up)_proj/bias$", (None, "tensor")),
    (r"layers/mlp/down_proj/kernel$", (None, "tensor", "fsdp")),
    (r"layers/mlp/down_proj/bias$", (None, None)),
    (r"layers/.*norm/scale$", (None, "fsdp")),
    (r"final_norm/scale$", ("fsdp",)),
    (r"lm_head/kernel$", ("fsdp", "tensor")),
]


@dataclasses.dataclass
class LlamaForCausalLM:
    """Bundled config + backend with the functional API underneath.

    supports_packed_nf4: every kernel this family consumes flows through
    _proj/lm_head_kernel, which dequantize NF4-packed dicts per layer inside
    the scan (QLoRA without materializing the full-precision stack)."""

    supports_packed_nf4 = True

    config: TransformerConfig
    backend: BackendConfig = BackendConfig()

    # adapter paths `_proj` consumes activation-side when grafted into the
    # param tree (peft.make_lora_loss_fn grafts these; others stay merged)
    lora_graft_patterns = ("*/attn/[qkvo]_proj/kernel", "*/mlp/*_proj/kernel")

    def cache_layout(self) -> tuple:
        """What each layer keeps between a sequence's tokens (the engines
        read this, generation/kv_cache.py): per-head K/V on every layer."""
        return kv_cache.uniform_kv_layout(self.config)

    def init(self, key: jax.Array) -> dict:
        return init_params(self.config, self.backend, key)

    def __call__(self, params: dict, input_ids: jnp.ndarray, **kw: Any) -> jnp.ndarray:
        return forward(self.config, self.backend, params, input_ids, **kw)

    def hidden(self, params: dict, input_ids: jnp.ndarray, **kw: Any) -> jnp.ndarray:
        return forward_hidden(self.config, self.backend, params, input_ids, **kw)

    def lm_head(self, params: dict) -> jnp.ndarray:
        return lm_head_kernel(self.config, params)

    @property
    def sharding_rules(self) -> list[tuple[str, tuple]]:
        return SHARDING_RULES
