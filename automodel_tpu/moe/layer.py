"""The MoE block: gate → experts → (shared experts) → combine.

Parity: reference `MoE` (components/moe/layers.py:516) — routed experts plus
optional always-on shared experts (with optional sigmoid shared-expert gate),
gate aux outputs surfaced for load-balance metrics and aux-free bias updates.
The reference overlaps shared experts on a second CUDA stream (layers.py:41);
here both branches sit in one XLA program and the scheduler overlaps them.
"""

from __future__ import annotations

import logging
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.experts import EXPERT_BACKENDS
from automodel_tpu.moe.gate import GateOutput, fake_balanced_gate, gate


class MoEAux(NamedTuple):
    expert_counts: jnp.ndarray  # [E] int32
    aux_loss: jnp.ndarray  # scalar f32


def make_act2(cfg: MoEConfig, base_act: Callable) -> Callable:
    """Two-argument gated activation from the config."""
    if cfg.activation == "swiglu_oai":
        # gpt-oss: clamp, swish(1.702*g), (up+1) shift
        # (modeling_gpt_oss.py GptOssExperts.forward)
        def act2(g, u):
            g = jnp.minimum(g, 7.0)
            u = jnp.clip(u, -7.0, 7.0)
            import jax

            return (u + 1.0) * (g * jax.nn.sigmoid(1.702 * g))

        return act2
    if cfg.activation_limit is not None:
        if cfg.activation != "swiglu":
            raise NotImplementedError(
                f"activation_limit is only defined for gated swiglu experts "
                f"(step3p5), not activation={cfg.activation!r}"
            )
        lim = float(cfg.activation_limit)

        def act2_lim(g, u):
            g = jnp.minimum(base_act(g), lim)
            return g * jnp.clip(u, -lim, lim)

        return act2_lim
    if cfg.activation == "relu2":
        # nemotron-v3 non-gated experts: square-ReLU on the single up
        # projection (the u operand is the same array, ignored)
        import jax

        return lambda g, u: jnp.square(jax.nn.relu(g))
    return lambda g, u: base_act(g) * u


def moe_block(
    x: jnp.ndarray,  # [B, S, D]
    mp: dict,
    cfg: MoEConfig,
    act: Callable,
    experts_backend: str = "gspmd",
    fake_gate: bool = False,
    constrain: Callable = lambda a, s: a,
    platform: Optional[str] = None,
    fp8: bool = False,
    act_name: str = "silu",
) -> tuple[jnp.ndarray, MoEAux]:
    with jax.named_scope("moe"):  # its parts name themselves inside (experts.py)
        B, S, D = x.shape
        xt = x.reshape(-1, D)

        with jax.named_scope("router"):
            if fake_gate:
                gout = fake_balanced_gate(xt, cfg)
            else:
                gout = gate(
                    xt,
                    mp["router"]["weight"],
                    cfg,
                    bias=mp["router"].get("bias"),
                    seq_len=S,
                    linear_bias=mp["router"].get("linear_bias"),
                )

        act2 = make_act2(cfg, act)
        # mesh-aware backends (a2a) need the real Mesh for their shard_map
        # region; make_constrain attaches it to the constrain callback
        ctx = getattr(constrain, "mesh_ctx", None)
        if experts_backend in ("a2a", "a2a_fused") and ctx is None:
            logger.warning(
                "experts=%r but the constrain callback carries no mesh_ctx "
                "(use parallel.plans.make_constrain, or a custom wrapper must "
                "preserve the attribute); falling back to the single-slice "
                "ragged path — NO expert-parallel token exchange will happen.",
                experts_backend,
            )
        # a callable backend (e.g. the pipeline's ep-manual a2a binding) uses the
        # registry's uniform signature directly
        backend_fn = (
            experts_backend if callable(experts_backend)
            else EXPERT_BACKENDS[experts_backend]
        )
        routed = backend_fn(
            x, gout, mp["experts"], cfg, act2,
            ctx=ctx, constrain=constrain, platform=platform, fp8=fp8,
            act_name=act_name,
        )

        out = routed
        if "shared" in mp:
            # the shared experts are a dense MLP every token takes
            with jax.named_scope("mlp"):
                sp = mp["shared"]
                u = xt @ sp["up_proj"]["kernel"].astype(xt.dtype)
                if "gate_proj" in sp:
                    g = xt @ sp["gate_proj"]["kernel"].astype(xt.dtype)
                    if cfg.activation_limit is not None:
                        lim = float(cfg.activation_limit)
                        mid = jnp.minimum(act(g), lim) * jnp.clip(u, -lim, lim)
                    else:
                        mid = act(g) * u
                else:  # non-gated shared expert (nemotron relu2)
                    mid = act2(u, u)
                shared = mid @ sp["down_proj"]["kernel"].astype(xt.dtype)
                if "shared_gate" in mp:
                    sg = jnp.asarray(xt @ mp["shared_gate"]["kernel"].astype(xt.dtype))
                    shared = shared * jnp.asarray(jnp.reciprocal(1 + jnp.exp(-sg)))
                out = out + shared.reshape(B, S, D)

        return out, MoEAux(gout.expert_counts, gout.aux_loss)


def init_moe_params(
    key,
    cfg: MoEConfig,
    hidden_size: int,
    dtype,
    n_layers: Optional[int] = None,
) -> dict:
    """Init one MoE block's params; with n_layers, leaves get a leading
    stacked layer axis (lax.scan layout shared with the dense family)."""
    def shape(*s):
        return (n_layers, *s) if n_layers else s

    D, E, I = hidden_size, cfg.num_experts, cfg.moe_intermediate_size
    k = jax.random.split(key, 6)

    def init(kk, *s, fan_in):
        return (
            jax.random.normal(kk, shape(*s), jnp.float32) / (fan_in**0.5)
        ).astype(dtype)

    p = {
        "router": {"weight": init(k[0], D, E, fan_in=D)},
        "experts": {
            "gate_up": init(k[1], E, D, (2 * I if cfg.gated else I), fan_in=D),
            "down": init(k[2], E, I, D, fan_in=I),
        },
    }
    if cfg.bias_update_factor > 0 or cfg.expert_bias:
        p["router"]["bias"] = jnp.zeros(shape(E), jnp.float32)
    if cfg.router_linear_bias:
        p["router"]["linear_bias"] = jnp.zeros(shape(E), jnp.float32)
    if cfg.expert_mlp_bias:
        p["experts"]["gate_up_bias"] = jnp.zeros(shape(E, (2 * I if cfg.gated else I)), dtype)
        p["experts"]["down_bias"] = jnp.zeros(shape(E, D), dtype)
    if cfg.num_shared_experts > 0:
        SI = cfg.shared_expert_intermediate_size or cfg.moe_intermediate_size
        SI = SI * cfg.num_shared_experts
        p["shared"] = {
            "up_proj": {"kernel": init(k[4], D, SI, fan_in=D)},
            "down_proj": {"kernel": init(k[5], SI, D, fan_in=SI)},
        }
        if cfg.gated:
            p["shared"]["gate_proj"] = {"kernel": init(k[3], D, SI, fan_in=D)}
        if cfg.shared_expert_gate:
            p["shared_gate"] = {"kernel": jnp.zeros(shape(D, 1), dtype)}
    return p


# Sharding rules for MoE params (logical dims → mesh axes via MeshContext):
# expert dim on `expert` (=ep), expert-FSDP on `expert_fsdp` (=dp_shard,cp),
# expert intermediate on `tensor` — mirrors the reference's dual-mesh design
# (experts on (ep, ep_shard); moe/parallelizer.py:159-277) as pure annotation.
MOE_SHARDING_RULES: list[tuple[str, tuple]] = [
    (r"router/weight$", (None, None)),
    (r"router/(bias|linear_bias)$", (None,)),
    (r"experts/gate_up$", ("expert", "expert_fsdp", "tensor")),
    (r"experts/down$", ("expert", "tensor", "expert_fsdp")),
    (r"experts/gate_up_bias$", ("expert", "tensor")),
    (r"experts/down_bias$", ("expert", None)),
    (r"shared/(gate|up)_proj/kernel$", ("fsdp", "tensor")),
    (r"shared/down_proj/kernel$", ("tensor", "fsdp")),
    (r"shared_gate/kernel$", (None, None)),
]
