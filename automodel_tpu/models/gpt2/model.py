"""GPT-2 causal LM, TPU-native.

Parity: reference ``components/models/gpt2.py:1-239`` — a self-contained
GPT-2 (learned absolute position embeddings, pre-LN blocks with full
LayerNorm + bias, fused-QKV attention, non-gated GELU MLP, tied lm_head).
Differences here are TPU-native by design:

- per-layer leaves stacked on a leading layer axis → one ``lax.scan``
  (the reference loops an nn.ModuleList);
- q/k/v kernels stored separately so tensor-parallel sharding splits heads
  cleanly (the HF checkpoint's fused Conv1D ``c_attn`` is split by the
  state-dict adapter);
- attention rides the shared backend switch (splash/flash/sdpa) instead of
  torch SDPA.

The reference trains with dropout 0.1; like the rest of the framework the
TPU model is deterministic (dropout is a no-op at 0, and the reference's
bench conditions run eval/grad-accum paths where it is disabled).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.generation import kv_cache
from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.llama.model import ACT_FNS, _proj as _llama_proj
from automodel_tpu.ops.attention import attention
from automodel_tpu.ops.norms import layer_norm

Constrain = Callable[[jnp.ndarray, tuple], jnp.ndarray]
_noop_constrain: Constrain = lambda x, spec: x


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 2048
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    layer_norm_eps: float = 1e-5
    tie_embeddings: bool = True
    n_inner: Optional[int] = None  # HF n_inner; None → 4·hidden
    act: str = "gelu_pytorch_tanh"  # HF gelu_new ≡ tanh approximation

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def logits_soft_cap(self):
        return None

    @property
    def num_kv_heads(self) -> int:
        return self.num_heads  # no GQA in GPT-2

    @property
    def moe(self):
        return None

    @property
    def intermediate_size(self) -> int:
        return self.n_inner or 4 * self.hidden_size

    @classmethod
    def from_hf(cls, hf: Any) -> "GPT2Config":
        get = lambda k, d=None: (
            hf.get(k, d) if isinstance(hf, dict) else getattr(hf, k, d)
        )
        n_pos = get("n_positions", None) or get("n_ctx", None) or 2048
        hf_act = get("activation_function", "gelu_new")
        act = {
            "gelu_new": "gelu_pytorch_tanh",
            "gelu_pytorch_tanh": "gelu_pytorch_tanh",
            "gelu": "gelu",
        }.get(hf_act)
        if act is None:
            raise ValueError(f"unsupported gpt2 activation_function {hf_act!r}")
        return cls(
            vocab_size=get("vocab_size", 50257),
            n_positions=n_pos,
            hidden_size=get("n_embd", None) or get("hidden_size", 768),
            num_layers=get("n_layer", None) or get("num_hidden_layers", 12),
            num_heads=get("n_head", None) or get("num_attention_heads", 12),
            layer_norm_eps=get("layer_norm_epsilon", 1e-5),
            tie_embeddings=bool(get("tie_word_embeddings", True)),
            n_inner=get("n_inner", None),
            act=act,
        )


def init_params(cfg: GPT2Config, backend: BackendConfig, key: jax.Array) -> dict:
    """GPT-2 init scheme (reference _init_weights: normal(0, 0.02) weights,
    zero biases, both embeddings normal(0, 0.02))."""
    pd = backend.param_jnp_dtype
    L, D, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    keys = jax.random.split(key, 8)

    def w(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(pd)

    layers = {
        "ln_1": {"scale": jnp.ones((L, D), pd), "bias": jnp.zeros((L, D), pd)},
        "attn": {
            "q_proj": {"kernel": w(keys[0], L, D, D), "bias": jnp.zeros((L, D), pd)},
            "k_proj": {"kernel": w(keys[1], L, D, D), "bias": jnp.zeros((L, D), pd)},
            "v_proj": {"kernel": w(keys[2], L, D, D), "bias": jnp.zeros((L, D), pd)},
            "o_proj": {"kernel": w(keys[3], L, D, D), "bias": jnp.zeros((L, D), pd)},
        },
        "ln_2": {"scale": jnp.ones((L, D), pd), "bias": jnp.zeros((L, D), pd)},
        "mlp": {
            "fc": {"kernel": w(keys[4], L, D, I), "bias": jnp.zeros((L, I), pd)},
            "proj": {"kernel": w(keys[5], L, I, D), "bias": jnp.zeros((L, D), pd)},
        },
    }
    params = {
        "embed": {"embedding": w(keys[6], cfg.vocab_size, D)},
        "pos_embed": {"embedding": w(keys[7], cfg.n_positions, D)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((D,), pd), "bias": jnp.zeros((D,), pd)},
    }
    if not cfg.tie_embeddings:  # HF gpt2 always ties; kept for from_config use
        params["lm_head"] = {"kernel": w(jax.random.split(keys[6])[1], D, cfg.vocab_size)}
    return params


def _proj(x: jnp.ndarray, p: dict) -> jnp.ndarray:
    # the shared llama projection: bias + activation-side LoRA incl. the
    # grafted adapter DROPOUT seeds and NF4-packed kernels — reimplementing
    # it here silently dropped LoRA dropout
    return _llama_proj(x, p)


def decoder_layer(
    cfg: GPT2Config,
    backend: BackendConfig,
    h: jnp.ndarray,
    lp: dict,
    segment_ids: Optional[jnp.ndarray],
    constrain: Constrain,
    cache: Optional[tuple] = None,
    cache_ctx: Any = None,
):
    """``cache``/``cache_ctx``: generation hook, same contract as the llama
    attention_block — this layer's (k, v) cache slices plus the shared
    write/attend plan; returns ``(h, (new_k, new_v))`` when caching."""
    B, S, D = h.shape
    x = layer_norm(h, lp["ln_1"]["scale"], lp["ln_1"]["bias"], cfg.layer_norm_eps)
    q = _proj(x, lp["attn"]["q_proj"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = _proj(x, lp["attn"]["k_proj"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    v = _proj(x, lp["attn"]["v_proj"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    new_layer_kv = None
    if cache is not None:
        new_layer_kv = cache_ctx.write(cache[0], cache[1], k, v)
    if cache is not None and cache_ctx.attends_cache:
        # ctx-dispatched cache attend: sdpa_decode over the (gathered)
        # cache, or the fused paged kernel over the block pool (serving/)
        attn_out = cache_ctx.attend(
            q, new_layer_kv, mesh_ctx=backend.mesh_ctx
        )
    else:
        attn_out = attention(
            q, k, v,
            backend=backend.attn,
            platform=backend.platform,
            mesh_ctx=backend.mesh_ctx,
            causal=True,
            segment_ids=segment_ids,
        )
    h = h + _proj(attn_out.reshape(B, S, D), lp["attn"]["o_proj"])
    h = constrain(h, ("batch", "seq", None))
    x = layer_norm(h, lp["ln_2"]["scale"], lp["ln_2"]["bias"], cfg.layer_norm_eps)
    mlp = _proj(ACT_FNS[cfg.act](_proj(x, lp["mlp"]["fc"])), lp["mlp"]["proj"])
    h = h + mlp
    h = constrain(h, ("batch", "seq", None))
    return h if cache is None else (h, new_layer_kv)


def forward_hidden(
    cfg: GPT2Config,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    position_ids: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    constrain: Constrain = _noop_constrain,
    cache: Optional[tuple] = None,
):
    cd = backend.compute_jnp_dtype
    if input_ids.shape[1] > cfg.n_positions:
        # learned wpe has no extrapolation; an OOB gather would silently
        # clamp to the last row (reference gpt2.py raises the same way)
        raise ValueError(
            f"sequence length {input_ids.shape[1]} exceeds maximum context "
            f"size {cfg.n_positions}"
        )
    if position_ids is None:
        position_ids = jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None, :]
        position_ids = jnp.broadcast_to(position_ids, input_ids.shape)
    h = params["embed"]["embedding"].astype(cd)[input_ids]
    h = h + params["pos_embed"]["embedding"].astype(cd)[position_ids]
    h = constrain(h, ("batch", "seq", None))

    kvc = ctx = None
    if cache is not None:
        kvc, ctx = cache

        def layer_fn(carry, xs):
            lp, layer_kv = xs
            return decoder_layer(
                cfg, backend, carry, lp, segment_ids, constrain,
                cache=layer_kv, cache_ctx=ctx,
            )

    else:

        def layer_fn(carry, lp):
            return decoder_layer(cfg, backend, carry, lp, segment_ids, constrain), None

        from automodel_tpu.models.common.stacking import remat_wrap

        layer_fn = remat_wrap(layer_fn, backend.remat)
    new_cache = None
    if backend.scan_layers:
        xs = params["layers"] if cache is None else (params["layers"], (kvc.k, kvc.v))
        h, ys = jax.lax.scan(layer_fn, h, xs)
        if cache is not None:
            new_cache = kvc.replace(k=ys[0], v=ys[1])
    else:
        new_k, new_v = [], []
        for i in range(cfg.num_layers):
            lp = jax.tree.map(lambda x: x[i], params["layers"])
            xs = (
                lp
                if cache is None
                else (lp, (kv_cache.layer_slice(kvc.k, i), kv_cache.layer_slice(kvc.v, i)))
            )
            h, lkv = layer_fn(h, xs)
            if cache is not None:
                new_k.append(lkv[0])
                new_v.append(lkv[1])
        if cache is not None:
            new_cache = kvc.replace(
                k=kv_cache.stack_layer_sides(new_k),
                v=kv_cache.stack_layer_sides(new_v),
            )
    h = layer_norm(
        h, params["final_norm"]["scale"], params["final_norm"]["bias"],
        cfg.layer_norm_eps,
    )
    return h if cache is None else (h, new_cache)


def lm_head_kernel(cfg: GPT2Config, params: dict) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return params["embed"]["embedding"].T
    return params["lm_head"]["kernel"]


def forward(
    cfg: GPT2Config,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    position_ids: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    constrain: Constrain = _noop_constrain,
    cache: Optional[tuple] = None,
):
    out = forward_hidden(
        cfg, backend, params, input_ids, position_ids, segment_ids, constrain,
        cache=cache,
    )
    h, new_cache = out if cache is not None else (out, None)
    logits = h @ lm_head_kernel(cfg, params).astype(h.dtype)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits if cache is None else (logits, new_cache)


SHARDING_RULES: list[tuple[str, tuple]] = [
    (r"embed/embedding$", ("tensor", "fsdp")),
    (r"pos_embed/embedding$", (None, "fsdp")),
    (r"layers/attn/[qkv]_proj/kernel$", (None, "fsdp", "tensor")),
    (r"layers/attn/[qkv]_proj/bias$", (None, "tensor")),
    (r"layers/attn/o_proj/kernel$", (None, "tensor", "fsdp")),
    (r"layers/attn/o_proj/bias$", (None, None)),
    (r"layers/mlp/fc/kernel$", (None, "fsdp", "tensor")),
    (r"layers/mlp/fc/bias$", (None, "tensor")),
    (r"layers/mlp/proj/kernel$", (None, "tensor", "fsdp")),
    (r"layers/mlp/proj/bias$", (None, None)),
    (r"layers/ln_[12]/(scale|bias)$", (None, "fsdp")),
    (r"final_norm/(scale|bias)$", ("fsdp",)),
    (r"lm_head/kernel$", ("fsdp", "tensor")),
]


def build_gpt2_model(
    vocab_size: int = 50257,
    n_positions: int = 2048,
    n_ctx: Optional[int] = None,
    n_embd: int = 768,
    n_layer: int = 12,
    n_head: int = 12,
    backend: Optional[BackendConfig] = None,
    **extra: Any,
) -> "GPT2ForCausalLM":
    """Single-level YAML builder (reference build_gpt2_model,
    components/models/gpt2.py:199-239): exposes the common GPT-2 sizes as
    flat kwargs for ``_target_``-driven configs; unknown extras are ignored
    with a warning, and legacy ``n_ctx`` maps to ``n_positions``."""
    if n_ctx is not None and n_ctx != n_positions:
        n_positions = n_ctx
    if extra:
        import logging

        logging.getLogger(__name__).warning(
            "build_gpt2_model: ignoring unsupported kwargs: %s",
            ", ".join(extra),
        )
    cfg = GPT2Config(
        vocab_size=vocab_size, n_positions=n_positions, hidden_size=n_embd,
        num_layers=n_layer, num_heads=n_head,
    )
    return GPT2ForCausalLM(cfg, backend or BackendConfig())


@dataclasses.dataclass
class GPT2ForCausalLM:
    config: GPT2Config
    backend: BackendConfig = BackendConfig()

    lora_graft_patterns = ("*/attn/[qkvo]_proj/kernel", "*/mlp/*/kernel")

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
