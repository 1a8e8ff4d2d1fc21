"""Xing4.0: a four-stream residual path (manifold-constrained hyper-connections)
around rotary latent attention and sparse experts, with a multi-token-prediction
module.

Parity: the published config.json (``model_type: xing4_0``) and the papers it
names; no modeling file or checkpoint was read. Per layer:

- the residual stream is ``hc_mult`` streams wide. Each sublayer (attention
  with ``input_layernorm``, the MLP or expert layer with
  ``post_attention_layernorm``) has its OWN maps ``phi``, ``b``, ``alpha``
  (ops/hyper_connections.py): it reads ``u = sum_i Hpre[i] X[i]``, norms it
  itself (pre-norm) and its output is written back as ``X'[i] = sum_j
  Hres[i, j] X[j] + Hpost[i] y`` with ``Hres`` Sinkhorn-normalised. The stream
  starts as the embedding repeated and ends as the sum of its streams.
- attention: the latent block of models/deepseek_v3 (``mla_branch``) with
  ``q_lora_rank``, the shared rotary key head, interleaved rotary and YaRN
  folded into the softmax scale.
- the MLP: the first ``first_k_dense_replace`` layers a dense SwiGLU, the
  others routed experts (sigmoid scores, selection by score + bias, weights
  renormalised and scaled) plus a shared expert (moe/layer.py).
- ``num_nextn_predict_layers`` = 1: one multi-token-prediction module
  (DeepSeek-V3 report, section 2.2). ``h' = eh_proj [RMS_e(Emb(t_{i+1})),
  RMS_h(h_i)]`` with ``h_i`` the collapsed stream before the final norm, one
  more block of the expert kind with its own hyper-connections, its own final
  norm; the embedding and the head are the main model's. ``hidden`` returns
  its normed hidden state in the aux and the loss adds ``mtp_loss_weight`` x
  its cross-entropy against the labels shifted once more
  (training/train_step.make_causal_lm_loss).

TPU structure: layers of one kind are stacked (``mla``, ``dense_mlp``,
``moe``, the norms and maps over all layers; the MTP module's leaves carry a
leading axis of ``num_nextn_predict_layers``), the layer loop is unrolled with
static routing as models/kimi_linear's is, and the carry between layers is the
flat stream ``[B, S, hc_mult * D]``.

Not served: serving/'s programs carry one hidden vector a row and hold no
latent cache (``ServeConfig.check_layout`` refuses the family).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.deepseek_v3.model import (
    DeepseekV3Config,
    init_mla_layer,
    mla_branch,
)
from automodel_tpu.models.llama.model import ACT_FNS, _dense_init, _noop_constrain
from automodel_tpu.models.qwen3_moe.model import MoEModelAux
from automodel_tpu.moe.gate import update_gate_bias
from automodel_tpu.moe.layer import init_moe_params, moe_block
from automodel_tpu.ops import hyper_connections as hc
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import rope_table


@dataclasses.dataclass(frozen=True)
class Xing4Config(DeepseekV3Config):
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    num_mtp_modules: int = 0
    mtp_loss_weight: float = 0.3

    @classmethod
    def from_hf(cls, hf_cfg: Any) -> "Xing4Config":
        get = lambda k, d=None: (
            hf_cfg.get(k, d) if isinstance(hf_cfg, dict) else getattr(hf_cfg, k, d)
        )
        base = DeepseekV3Config.from_hf(hf_cfg)
        if (get("moe_layer_freq", 1) or 1) != 1:
            raise NotImplementedError("moe_layer_freq != 1")
        n_mtp = get("num_nextn_predict_layers", 0) or 0
        if n_mtp > 1:
            raise NotImplementedError("more than one multi-token-prediction module")
        if (get("hc_mult", 4) or 4) < 2:
            raise ValueError("hc_mult < 2: a single stream is models/deepseek_v3")
        held = get("held_experts")
        fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
        fields.update(
            moe=dataclasses.replace(
                base.moe,
                # the source states no update rate for the selection bias
                bias_update_factor=get("router_bias_update_factor", 0.0) or 0.0,
                held_experts=tuple(held) if held else None,
                held_capacity_factor=get("held_capacity_factor"),
            ),
            hc_mult=get("hc_mult", 4),
            hc_sinkhorn_iters=get("hc_sinkhorn_iters", 20),
            hc_eps=get("hc_eps", 1e-6),
            hc_res_clamp=(float(get("mhc_h_res_clamp_min", -30.0)),
                          float(get("mhc_h_res_clamp_max", 30.0))),
            num_mtp_modules=n_mtp,
            mtp_loss_weight=float(get("mtp_loss_weight", 0.3)),
        )
        return cls(**fields)


def init_hc(cfg: Xing4Config, backend: BackendConfig, key, L: int) -> dict:
    """One sublayer kind's maps for L layers. At these values a block is the
    single-stream pre-norm block on equal streams (``Hpre`` = 1/n, ``Hpost`` =
    1, ``Hres`` near the identity) and the dynamic part is small."""
    n, D = cfg.hc_mult, cfg.hidden_size
    b = jnp.concatenate([
        jnp.full((n,), -math.log(n - 1.0)), jnp.zeros((n,)),
        jnp.where(jnp.eye(n, dtype=bool), 0.0, -8.0).reshape(-1),
    ]).astype(jnp.float32)
    return {
        "phi": _dense_init(key, (L, n * D, hc.n_coefficients(n)), backend.param_jnp_dtype, in_axis=1),
        "b": jnp.tile(b, (L, 1)),
        "alpha": jnp.full((L, 3), 0.01, jnp.float32),
    }


def _init_blocks(cfg: Xing4Config, backend: BackendConfig, key, L: int, nd: int) -> dict:
    """L blocks, the first ``nd`` with a dense MLP: the stacks by kind."""
    pd = backend.param_jnp_dtype
    D, I = cfg.hidden_size, cfg.intermediate_size
    keys = jax.random.split(key, 5)
    out: dict = {
        "layers": {
            "input_norm": {"scale": jnp.ones((L, D), pd)},
            "post_attn_norm": {"scale": jnp.ones((L, D), pd)},
            "attn_hc": init_hc(cfg, backend, keys[0], L),
            "mlp_hc": init_hc(cfg, backend, keys[1], L),
        },
        "mla": init_mla_layer(cfg, backend, keys[2], L)["attn"],
    }
    if nd:
        dk = jax.random.split(keys[3], 3)
        out["dense_mlp"] = {
            "gate_proj": {"kernel": _dense_init(dk[0], (nd, D, I), pd, in_axis=1)},
            "up_proj": {"kernel": _dense_init(dk[1], (nd, D, I), pd, in_axis=1)},
            "down_proj": {"kernel": _dense_init(dk[2], (nd, I, D), pd, in_axis=1)},
        }
    if L > nd:
        out["moe"] = init_moe_params(keys[4], cfg.moe, D, pd, n_layers=L - nd)
    return out


def init_params(cfg: Xing4Config, backend: BackendConfig, key: jax.Array) -> dict:
    pd = backend.param_jnp_dtype
    D, M = cfg.hidden_size, cfg.num_mtp_modules
    keys = jax.random.split(key, 5)
    params: dict = {
        "embed": {"embedding": (jax.random.normal(keys[0], (cfg.vocab_size, D)) * 0.02).astype(pd)},
        "final_norm": {"scale": jnp.ones((D,), pd)},
        **_init_blocks(cfg, backend, keys[1], cfg.num_layers, cfg.moe.num_dense_layers),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(keys[2], (D, cfg.vocab_size), pd)}
    if M:
        params["mtp"] = {
            "enorm": {"scale": jnp.ones((M, D), pd)},
            "hnorm": {"scale": jnp.ones((M, D), pd)},
            "eh_proj": {"kernel": _dense_init(keys[3], (M, 2 * D, D), pd, in_axis=1)},
            "final_norm": {"scale": jnp.ones((M, D), pd)},
            **_init_blocks(cfg, backend, keys[4], M, 0),
        }
    return params


def hc_sublayer(cfg: Xing4Config, backend: BackendConfig, x, hp: dict, branch):
    """One sublayer through the residual path: ``branch(u) -> (y, aux)``.
    Scope ``norm/mhc`` with the segments ``mhc_coeff``, ``mhc_pre``,
    ``mhc_post``; the branch names its own. -> (stream, aux, the largest
    ``|row sum - 1|`` of this sublayer's ``Hres``)."""
    with jax.named_scope("norm"), jax.named_scope("mhc"):
        with jax.named_scope("mhc_coeff"):
            co = hc.coefficients(
                x, hp["phi"], hp["b"], hp["alpha"], n=cfg.hc_mult, norm_eps=cfg.rms_eps,
                sinkhorn_iters=cfg.hc_sinkhorn_iters, sinkhorn_eps=cfg.hc_eps,
                clamp=cfg.hc_res_clamp,
            )
            err = hc.res_row_error(co.res)
        with jax.named_scope("mhc_pre"):
            u = hc.pre_mix(x, co.pre)
    y, aux = branch(u)
    with jax.named_scope("norm"), jax.named_scope("mhc"), jax.named_scope("mhc_post"):
        x = hc.post_mix(x, y, co.post, co.res, platform=backend.platform,
                        mesh_ctx=backend.mesh_ctx)
    return x, aux, err


def block(cfg, backend, x, norms, mla_p, mlp_p, dense, cos, sin, segment_ids, constrain):
    """One decoder block on the flat stream [B, S, n * D]."""
    act = ACT_FNS[cfg.act]

    def attn(u):
        with jax.named_scope("attn"), jax.named_scope("mla"):
            un = rms_norm(u, norms["input_norm"]["scale"], cfg.rms_eps)
            return mla_branch(cfg, backend, un, mla_p, cos, sin, segment_ids), None

    def mlp(u):
        with jax.named_scope("norm"):
            un = rms_norm(u, norms["post_attn_norm"]["scale"], cfg.rms_eps)
        if dense:
            with jax.named_scope("mlp"):
                y = (
                    act(un @ mlp_p["gate_proj"]["kernel"].astype(un.dtype))
                    * (un @ mlp_p["up_proj"]["kernel"].astype(un.dtype))
                ) @ mlp_p["down_proj"]["kernel"].astype(un.dtype)
            return y, None
        return moe_block(
            un, mlp_p, cfg.moe, act,
            experts_backend=backend.experts, fake_gate=backend.fake_balanced_gate,
            constrain=constrain, platform=backend.platform,
            fp8=backend.fp8_experts, act_name=cfg.act,
        )

    x, _, err_a = hc_sublayer(cfg, backend, x, norms["attn_hc"], attn)
    x = constrain(x, ("batch", "seq", None))
    x, aux, err_m = hc_sublayer(cfg, backend, x, norms["mlp_hc"], mlp)
    return constrain(x, ("batch", "seq", None)), aux, jnp.maximum(err_a, err_m)


def _collapse(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """The sum of the streams, in float32, back in the stream's type."""
    return sum(hc.streams(x, n)).astype(x.dtype)


def forward_hidden(
    cfg: Xing4Config,
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
    n, nd = cfg.hc_mult, cfg.moe.num_dense_layers
    embedding = constrain(params["embed"]["embedding"], (None, None)).astype(cd)
    with jax.named_scope("embed"):
        x = jnp.tile(embedding[input_ids], (1, 1, n))  # the embedding in every stream
    x = constrain(x, ("batch", "seq", None))
    if position_ids is None:
        position_ids = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    with jax.named_scope("attn"):
        cos, sin = rope_table(position_ids, cfg.qk_rope_head_dim, cfg.rope)
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)

    counts_l, aux_l, err_l = [], [], []

    def run_blocks(x, stacks: dict, layers, nd: int):
        """Blocks ``layers`` of the stacks, the first ``nd`` with a dense MLP."""
        for i in layers:
            dense = i < nd
            mlp_p = at(stacks["dense_mlp"], i) if dense else at(stacks["moe"], i - nd)
            one = lambda x, norms=at(stacks["layers"], i), mla_p=at(stacks["mla"], i), \
                mlp_p=mlp_p, dense=dense: block(
                    cfg, backend, x, norms, mla_p, mlp_p, dense, cos, sin, segment_ids, constrain)
            x, aux, err = remat_wrap(one, backend.remat)(x)
            err_l.append(err)
            if aux is not None:
                counts_l.append(aux.expert_counts)
                aux_l.append(aux.aux_loss)
        return x

    x = run_blocks(x, params, range(cfg.num_layers), nd)
    with jax.named_scope("final_norm"):
        h_sum = _collapse(x, n)
        h = rms_norm(h_sum, params["final_norm"]["scale"], cfg.rms_eps)

    mtp_h = None
    for k in range(cfg.num_mtp_modules):
        mp = at(params["mtp"], k)
        with jax.named_scope("mtp"):
            # the next token's embedding; a row's last position takes its
            # first token's, and its target is ignored (make_causal_lm_loss)
            with jax.named_scope("embed"):
                e = embedding[jnp.roll(input_ids, -1, axis=1)]
            with jax.named_scope("norm"):
                e = rms_norm(e, mp["enorm"]["scale"], cfg.rms_eps)
                hn = rms_norm(h_sum, mp["hnorm"]["scale"], cfg.rms_eps)
            with jax.named_scope("mlp"):
                hp = jnp.concatenate([e, hn], axis=-1) @ mp["eh_proj"]["kernel"].astype(cd)
                xm = jnp.tile(hp, (1, 1, n))
            xm = constrain(xm, ("batch", "seq", None))
            xm = run_blocks(xm, params["mtp"], (k,), 0)
            with jax.named_scope("final_norm"):
                mtp_h = rms_norm(_collapse(xm, n), mp["final_norm"]["scale"], cfg.rms_eps)

    E = cfg.moe.num_experts
    counts = jnp.stack(counts_l) if counts_l else jnp.zeros((0, E), jnp.int32)
    aux_loss = jnp.stack(aux_l).sum() if aux_l else jnp.float32(0.0)
    held = None
    if cfg.moe.held_experts is not None:
        lo, hi = cfg.moe.held_experts
        held = counts[:, lo:hi].sum()  # picks that landed on held experts, every block
    return h, MoEModelAux(counts, aux_loss, held, mtp_h, jnp.stack(err_l).max())


_L = (None,)  # the stacked-layers axis
_BLOCK_RULES: list[tuple[str, tuple]] = [
    (r"layers/.*norm/scale$", (None, None)),
    # the residual path's maps: replicated, as a norm's scale is
    (r"layers/(attn|mlp)_hc/phi$", (*_L, None, None)),
    (r"layers/(attn|mlp)_hc/(b|alpha)$", (None, None)),
    (r"mla/q_a_proj/kernel$", (*_L, "fsdp", None)),
    (r"mla/q_a_norm/scale$", (None, None)),
    (r"mla/q_b_proj/kernel$", (*_L, "fsdp", "tensor")),
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
]
# the MTP module's block is sharded as the stack's are (the rules above match
# by a path's end); its own leaves:
SHARDING_RULES: list[tuple[str, tuple]] = [
    (r"mtp/(enorm|hnorm|final_norm)/scale$", (None, None)),
    (r"mtp/eh_proj/kernel$", (*_L, "fsdp", "tensor")),
    *_BLOCK_RULES,
    (r"embed/embedding$", ("tensor", "fsdp")),
    (r"final_norm/scale$", (None,)),
    (r"lm_head/kernel$", ("fsdp", "tensor")),
]


@dataclasses.dataclass
class Xing4ForCausalLM:
    config: Xing4Config
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
        u = self.config.moe.bias_update_factor
        if u <= 0 or "expert_counts" not in extras:
            return params
        counts, row = extras["expert_counts"], 0  # the stack's blocks, then the module's
        for stacks in (params, params.get("mtp", {})):
            if "moe" in stacks:
                bias = stacks["moe"]["router"]["bias"]
                stacks["moe"]["router"]["bias"] = jax.vmap(
                    lambda b, c: update_gate_bias(b, c, u)
                )(bias, counts[row:row + bias.shape[0]])
                row += bias.shape[0]
        return params
