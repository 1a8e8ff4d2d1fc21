"""Nemotron-V3 (Nano-v3 hybrid Mamba2/attention/MLP/MoE), TPU-native.

Parity: reference components/models/nemotron_v3/{model,layers}.py — single-
mixer pre-norm blocks (norm → mixer → residual) whose mixer is, per
``layers_block_type``:

- ``mamba``: Mamba2 — in_proj → [z | x | B | C | dt], depthwise causal conv
  over [x|B|C] + silu, softplus(dt + dt_bias) clamped to time_step_limit,
  SSD chunked scan (ssd.py), gated group-RMSNorm norm(x·silu(z)), out_proj;
- ``attention``: NoPE GQA attention (no rotary — layers.py:65-120), optional
  biases, per-head q/k norms NOT present (plain sdpa);
- ``mlp``: non-gated ReLU² MLP;
- ``moe``: sigmoid-routed grouped top-k with a constant e_score correction
  bias, ReLU² non-gated experts, one ungated ReLU² shared expert, no aux
  loss (model.py:57-79).

TPU structure: like qwen3_next, heterogeneous mixers split into per-type
stacked subtrees; the layer loop is unrolled with static types.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
from automodel_tpu.models.llama.model import ACT_FNS, _dense_init, _noop_constrain
from automodel_tpu.models.nemotron_v3.ssd import mamba2_chunk_scan
from automodel_tpu.ops.short_conv import causal_conv1d
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.layer import init_moe_params, moe_block
from automodel_tpu.ops.attention import attention
from automodel_tpu.ops.norms import rms_norm


@dataclasses.dataclass(frozen=True)
class NemotronV3Config(TransformerConfig):
    moe: Optional[MoEConfig] = None
    layers_block_type: tuple = ()
    mamba_num_heads: int = 8
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 64
    use_bias: bool = False
    use_conv_bias: bool = True
    time_step_limit: tuple = (0.0, float("inf"))

    @classmethod
    def from_hf(cls, hf_cfg: Any) -> "NemotronV3Config":
        get = lambda k, d=None: (
            hf_cfg.get(k, d) if isinstance(hf_cfg, dict) else getattr(hf_cfg, k, d)
        )
        base = TransformerConfig.from_hf(hf_cfg)
        L = base.num_layers
        lbt = get("layers_block_type") or None
        if lbt is None:
            # 'M' → mamba, '*' → attention, '-' → mlp, else moe
            pat = get("hybrid_override_pattern") or "M" * L
            m = {"M": "mamba", "*": "attention", "-": "mlp"}
            lbt = [m.get(ch, "moe") for ch in pat]
        moe = None
        if "moe" in lbt:
            moe = MoEConfig(
                num_experts=get("n_routed_experts"),
                num_experts_per_tok=get("num_experts_per_tok", 8),
                moe_intermediate_size=get("moe_intermediate_size"),
                num_shared_experts=1,
                shared_expert_intermediate_size=(
                    get("moe_shared_expert_intermediate_size")
                    or get("moe_intermediate_size")
                ),
                shared_expert_gate=False,
                score_func="sigmoid",
                softmax_before_topk=False,
                route_scale=get("routed_scaling_factor", 1.0) or 1.0,
                norm_topk_prob=bool(get("norm_topk_prob", True)),
                n_group=get("n_group", 1) or 1,
                topk_group=get("topk_group", 1) or 1,
                aux_loss_coeff=0.0,
                expert_bias=True,  # constant e_score_correction_bias buffer
                bias_update_factor=0.0,  # present but NOT updated (train_gate=False)
                activation="relu2",
                expert_mlp_bias=bool(get("mlp_bias", False)),
            )
        fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
        fields.update(
            moe=moe,
            layers_block_type=tuple(lbt),
            act=get("mlp_hidden_act", "relu2"),
            rms_eps=get("layer_norm_epsilon", None) or base.rms_eps,
            mamba_num_heads=get("mamba_num_heads", 8),
            mamba_head_dim=get("mamba_head_dim", 64),
            ssm_state_size=get("ssm_state_size", 128),
            n_groups=get("n_groups", 8),
            conv_kernel=get("conv_kernel", 4),
            chunk_size=get("chunk_size", 64),
            use_bias=bool(get("use_bias", False)),
            use_conv_bias=bool(get("use_conv_bias", True)),
            time_step_limit=tuple(get("time_step_limit", (0.0, float("inf")))),
        )
        return cls(**fields)

    @property
    def mamba_intermediate(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_intermediate + 2 * self.n_groups * self.ssm_state_size

    @property
    def mamba_proj_size(self) -> int:
        # [z | x | B | C | dt]
        return self.mamba_intermediate + self.conv_dim + self.mamba_num_heads

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layers_block_type)


def init_params(cfg: NemotronV3Config, backend: BackendConfig, key: jax.Array) -> dict:
    pd = backend.param_jnp_dtype
    D = cfg.hidden_size
    L = cfg.num_layers
    Lm, La, Lp, Lo = (cfg.count(k) for k in ("mamba", "attention", "mlp", "moe"))
    keys = jax.random.split(key, 16)

    def stack(k, n, shape):
        return _dense_init(k, (n, *shape), pd, in_axis=1)

    params: dict = {
        "embed": {
            "embedding": jax.random.normal(keys[0], (cfg.vocab_size, D)).astype(pd)
            * 0.02
        },
        "layers": {"norm": {"scale": jnp.ones((L, D), pd)}},
        "final_norm": {"scale": jnp.ones((D,), pd)},
    }
    if Lm:
        H, inter, cd_ = cfg.mamba_num_heads, cfg.mamba_intermediate, cfg.conv_dim
        mam = {
            "in_proj": {"kernel": stack(keys[1], Lm, (D, cfg.mamba_proj_size))},
            "conv": {"weight": jax.random.normal(
                keys[2], (Lm, cd_, cfg.conv_kernel)).astype(pd) * 0.02},
            "dt_bias": jnp.ones((Lm, H), pd),
            # A = -exp(A_log); reference inits A_log = log(arange(1, H+1))
            "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32))[None]
            .repeat(Lm, 0).astype(pd),
            "D": jnp.ones((Lm, H), pd),
            "norm": {"scale": jnp.ones((Lm, inter), pd)},
            "out_proj": {"kernel": stack(keys[3], Lm, (inter, D))},
        }
        if cfg.use_conv_bias:
            mam["conv"]["bias"] = jnp.zeros((Lm, cd_), pd)
        if cfg.use_bias:
            mam["in_proj"]["bias"] = jnp.zeros((Lm, cfg.mamba_proj_size), pd)
            mam["out_proj"]["bias"] = jnp.zeros((Lm, D), pd)
        params["mamba"] = mam
    if La:
        attn = {
            "q_proj": {"kernel": stack(keys[4], La, (D, cfg.q_dim))},
            "k_proj": {"kernel": stack(keys[5], La, (D, cfg.kv_dim))},
            "v_proj": {"kernel": stack(keys[6], La, (D, cfg.kv_dim))},
            "o_proj": {"kernel": stack(keys[7], La, (cfg.q_dim, D))},
        }
        if cfg.attention_bias:
            for p, dim in (("q_proj", cfg.q_dim), ("k_proj", cfg.kv_dim),
                           ("v_proj", cfg.kv_dim), ("o_proj", D)):
                attn[p]["bias"] = jnp.zeros((La, dim), pd)
        params["attn"] = attn
    if Lp:
        I = cfg.intermediate_size
        params["mlp"] = {
            "up_proj": {"kernel": stack(keys[8], Lp, (D, I))},
            "down_proj": {"kernel": stack(keys[9], Lp, (I, D))},
        }
        if cfg.mlp_bias:
            params["mlp"]["up_proj"]["bias"] = jnp.zeros((Lp, I), pd)
            params["mlp"]["down_proj"]["bias"] = jnp.zeros((Lp, D), pd)
    if Lo:
        params["moe"] = init_moe_params(keys[10], cfg.moe, D, pd, n_layers=Lo)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(keys[11], (D, cfg.vocab_size), pd)}
    return params


def _mamba_mixer(cfg: NemotronV3Config, x, mp, segment_ids=None):
    """Mamba2 mixer (reference NemotronV3Mamba2Mixer ≡
    mamba_split_conv1d_scan_combined semantics)."""
    B, S, D = x.shape
    H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N = cfg.n_groups, cfg.ssm_state_size
    inter = cfg.mamba_intermediate

    proj = x @ mp["in_proj"]["kernel"].astype(x.dtype)
    if "bias" in mp["in_proj"]:
        proj = proj + mp["in_proj"]["bias"].astype(x.dtype)
    z = proj[..., :inter]
    xbc = proj[..., inter : inter + cfg.conv_dim]
    dt_raw = proj[..., inter + cfg.conv_dim :]  # [B, S, H]

    xbc = causal_conv1d(xbc, mp["conv"]["weight"].astype(x.dtype), segment_ids)
    if "bias" in mp["conv"]:
        xbc = xbc + mp["conv"]["bias"].astype(x.dtype)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :inter].reshape(B, S, H, P)
    Bm = xbc[..., inter : inter + G * N].reshape(B, S, G, N)
    Cm = xbc[..., inter + G * N :].reshape(B, S, G, N)

    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + mp["dt_bias"].astype(jnp.float32)
    )
    lo, hi = cfg.time_step_limit
    if (lo, hi) != (0.0, float("inf")):
        dt = jnp.clip(dt, lo, hi)
    A = -jnp.exp(mp["A_log"].astype(jnp.float32))

    y = mamba2_chunk_scan(
        xs, dt, A, Bm, Cm, mp["D"].astype(jnp.float32),
        chunk_size=cfg.chunk_size, segment_ids=segment_ids,
    )  # [B, S, H, P]

    # gated group RMSNorm: norm(y · silu(z)), rms within n_groups groups
    y = y.reshape(B, S, inter).astype(jnp.float32) * jax.nn.silu(
        z.astype(jnp.float32)
    )
    yg = y.reshape(B, S, G, inter // G)
    yg = yg * jax.lax.rsqrt((yg * yg).mean(-1, keepdims=True) + cfg.rms_eps)
    y = (yg.reshape(B, S, inter) * mp["norm"]["scale"].astype(jnp.float32)).astype(
        x.dtype
    )
    out = y @ mp["out_proj"]["kernel"].astype(x.dtype)
    if "bias" in mp["out_proj"]:
        out = out + mp["out_proj"]["bias"].astype(x.dtype)
    return out


def _attn_mixer(cfg, backend, x, ap, segment_ids):
    """NoPE GQA attention (reference NemotronV3Attention — no rotary)."""
    B, S, D = x.shape

    def proj(name, nh):
        y = x @ ap[name]["kernel"].astype(x.dtype)
        if "bias" in ap[name]:
            y = y + ap[name]["bias"].astype(x.dtype)
        return y.reshape(B, S, nh, cfg.head_dim)

    q = proj("q_proj", cfg.num_heads)
    k = proj("k_proj", cfg.num_kv_heads)
    v = proj("v_proj", cfg.num_kv_heads)
    out = attention(
        q, k, v, backend=backend.attn, platform=backend.platform,
        mesh_ctx=backend.mesh_ctx,
        causal=True, segment_ids=segment_ids,
        **(
            {"block_q": backend.attn_block_q, "block_kv": backend.attn_block_kv}
            if backend.attn == "flash"
            else {}
        ),
    )
    out = out.reshape(B, S, cfg.q_dim) @ ap["o_proj"]["kernel"].astype(x.dtype)
    if "bias" in ap["o_proj"]:
        out = out + ap["o_proj"]["bias"].astype(x.dtype)
    return out


def forward_hidden(
    cfg: NemotronV3Config,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    position_ids=None,  # unused: NoPE attention + Mamba positions
    segment_ids=None,
    constrain=_noop_constrain,
):
    from automodel_tpu.models.qwen3_moe.model import MoEModelAux

    cd = backend.compute_jnp_dtype
    h = constrain(params["embed"]["embedding"], (None, None)).astype(cd)[input_ids]
    h = constrain(h, ("batch", "seq", None))

    def maybe_remat(fn):
        from automodel_tpu.models.common.stacking import remat_wrap

        return remat_wrap(fn, backend.remat)

    idx = {"mamba": 0, "attention": 0, "mlp": 0, "moe": 0}
    counts_l, aux_l = [], []
    for i, bt in enumerate(cfg.layers_block_type):
        nscale = params["layers"]["norm"]["scale"][i]
        j = idx[bt]
        idx[bt] += 1

        if bt == "mamba":
            mp = jax.tree.map(lambda a: a[j], params["mamba"])
            mixer = lambda y, mp=mp: _mamba_mixer(cfg, y, mp, segment_ids)
        elif bt == "attention":
            ap = jax.tree.map(lambda a: a[j], params["attn"])
            mixer = lambda y, ap=ap: _attn_mixer(cfg, backend, y, ap, segment_ids)
        elif bt == "mlp":
            pp = jax.tree.map(lambda a: a[j], params["mlp"])
            act = ACT_FNS[cfg.act]

            def mixer(y, pp=pp, act=act):
                u = y @ pp["up_proj"]["kernel"].astype(y.dtype)
                if "bias" in pp["up_proj"]:
                    u = u + pp["up_proj"]["bias"].astype(y.dtype)
                o = act(u) @ pp["down_proj"]["kernel"].astype(y.dtype)
                if "bias" in pp["down_proj"]:
                    o = o + pp["down_proj"]["bias"].astype(y.dtype)
                return o
        else:  # moe
            mp = jax.tree.map(lambda a: a[j], params["moe"])

            def mixer(y, mp=mp):
                out, aux = moe_block(
                    y, mp, cfg.moe, ACT_FNS["relu2"],
                    experts_backend=backend.experts,
                    fake_gate=backend.fake_balanced_gate,
                    constrain=constrain,
                    platform=backend.platform,
                    fp8=backend.fp8_experts,
                )
                return out, aux

        def layer(h, mixer=mixer, nscale=nscale, is_moe=bt == "moe"):
            y = rms_norm(h, nscale, cfg.rms_eps)
            out = mixer(y)
            if is_moe:
                out, aux = out
            else:
                aux = None
            return constrain(h + out, ("batch", "seq", None)), aux

        h, aux = maybe_remat(layer)(h)
        if aux is not None:
            counts_l.append(aux.expert_counts)
            aux_l.append(aux.aux_loss)

    h = rms_norm(h, params["final_norm"]["scale"], cfg.rms_eps)
    if counts_l:
        return h, MoEModelAux(jnp.stack(counts_l), jnp.stack(aux_l).sum())
    return h, MoEModelAux(
        jnp.zeros((0, 1), jnp.int32), jnp.float32(0.0)
    )


SHARDING_RULES: list[tuple[str, tuple]] = [
    (r"layers/norm/scale$", (None, None)),
    (r"mamba/in_proj/kernel$", (None, "fsdp", "tensor")),
    (r"mamba/out_proj/kernel$", (None, "tensor", "fsdp")),
    (r"mamba/(conv/.*|dt_bias|A_log|D|norm/scale|in_proj/bias|out_proj/bias)$", ()),
    (r"attn/[qkv]_proj/kernel$", (None, "fsdp", "tensor")),
    (r"attn/o_proj/kernel$", (None, "tensor", "fsdp")),
    (r"attn/.*/bias$", ()),
    (r"mlp/up_proj/kernel$", (None, "fsdp", "tensor")),
    (r"mlp/down_proj/kernel$", (None, "tensor", "fsdp")),
    (r"mlp/.*/bias$", ()),
    (r"moe/router/weight$", (None, None, None)),
    (r"moe/router/(bias|linear_bias)$", (None, None)),
    (r"moe/experts/gate_up$", (None, "expert", "expert_fsdp", "tensor")),
    (r"moe/experts/down$", (None, "expert", "tensor", "expert_fsdp")),
    (r"moe/experts/(gate_up_bias|down_bias)$", (None, None, None)),
    (r"moe/shared/(gate|up)_proj/kernel$", (None, "fsdp", "tensor")),
    (r"moe/shared/down_proj/kernel$", (None, "tensor", "fsdp")),
    (r"embed/embedding$", ("tensor", "fsdp")),
    (r"final_norm/scale$", (None,)),
    (r"lm_head/kernel$", ("fsdp", "tensor")),
]


@dataclasses.dataclass
class NemotronV3ForCausalLM:
    config: NemotronV3Config
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
        return params  # correction bias is a constant buffer (train_gate=False)
