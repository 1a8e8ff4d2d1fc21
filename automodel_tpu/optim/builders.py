"""Optimizer construction from config.

Parity: the reference instantiates plain ``_target_: torch.optim.*`` from
YAML (SURVEY.md §2.7). Here optimizers are optax chains; a YAML node like

    optimizer:
      _target_: automodel_tpu.optim.build_optimizer
      name: adamw
      lr: 1.e-4
      weight_decay: 0.01
      betas: [0.9, 0.95]
      grad_clip_norm: 1.0
      lr_schedule: {style: cosine, warmup_steps: 100, decay_steps: 1000}

builds clip → scale_by_adam → weight-decay → schedule. ``_target_:
optax.adamw``-style direct nodes also work through ConfigNode.instantiate.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import optax

from automodel_tpu.optim.scheduler import build_lr_schedule


def scale_by_adam_fp32_moments(
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8
) -> optax.GradientTransformation:
    """optax adam scaling with moments INITIALIZED (hence kept) in fp32.

    optax's scale_by_adam inits mu/nu in the param dtype and its update
    inherits the wider of (moment, grad) dtypes. With bf16 params AND bf16
    grads (the single-microbatch fast path, training/train_step.py) the
    moments would stay bf16, where the (1-b2)·g² increment rounds below
    nu's half-ulp and the second moment freezes. fp32-initialized moments
    promote every update to fp32 (torch AdamW parity) while reusing
    optax's update expression verbatim — XLA fuses that formulation into
    the donated moment buffers without materializing full-size fp32 grad
    intermediates (hand-rolled variants measured +2-3GB of HLO temps on
    the MoE bench's stacked expert grads)."""
    base = optax.scale_by_adam(b1=b1, b2=b2, eps=eps)

    def init(params):
        s = base.init(params)
        f32 = lambda t: jax.tree.map(
            lambda x: x.astype(jnp.float32) if jnp.issubdtype(
                x.dtype, jnp.floating
            ) else x, t
        )
        return s._replace(mu=f32(s.mu), nu=f32(s.nu))

    return optax.GradientTransformation(init, base.update)


def global_norm_fp32(tree: Any) -> jnp.ndarray:
    """Global L2 norm with fp32 accumulation regardless of leaf dtype —
    bf16 partial sums saturate after a few hundred equal-magnitude terms.
    The convert fuses into the reduction (no materialized fp32 copies).
    Shared by the grad-norm metric (training/train_step.py) and the clip."""
    return jnp.sqrt(
        sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(tree)
        )
    )


def clip_by_global_norm_fp32(max_norm: float) -> optax.GradientTransformation:
    """Global-norm clip built on global_norm_fp32 — optax's own
    clip_by_global_norm sums squares in the LEAF dtype."""

    def init(params):
        del params
        return optax.EmptyState()

    def update(updates, state, params=None):
        del params
        norm = global_norm_fp32(updates)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
        return jax.tree.map(lambda g: (g * scale.astype(g.dtype)), updates), state

    return optax.GradientTransformation(init, update)


_SCALERS = {
    "adamw": None,  # dispatched on moments_dtype in build_optimizer
    "adam": None,
    "lion": lambda betas, eps: optax.scale_by_lion(b1=betas[0], b2=betas[1]),
    "sgd": lambda betas, eps: optax.trace(decay=betas[0]),
    "adafactor": None,  # handled specially
}


def build_optimizer(
    name: str = "adamw",
    lr: float = 1e-4,
    weight_decay: float = 0.0,
    betas: Sequence[float] = (0.9, 0.999),
    eps: float = 1e-8,
    grad_clip_norm: float | None = None,
    lr_schedule: Any | None = None,
    moments_dtype: str | None = None,
    **sched_kwargs: Any,
) -> optax.GradientTransformation:
    """``moments_dtype``: None/'float32' (default) keeps Adam moments fp32
    regardless of grad dtype (torch AdamW parity — bf16 moments freeze nu,
    see scale_by_adam_fp32_moments). 'param' stores them in the param/grad
    dtype — HALVES optimizer memory; meant for memory-capacity-bound
    benchmarking (the benchmark's train configuration records it under
    `assumed`), not long training runs."""
    # YAML 1.1 parses dotless scientific notation (`lr: 1e-2`) as a string;
    # coerce here so config-file values behave like `1.0e-2`
    lr, weight_decay, eps = float(lr), float(weight_decay), float(eps)
    betas = tuple(float(b) for b in betas)
    if grad_clip_norm is not None:
        grad_clip_norm = float(grad_clip_norm)
    if lr_schedule is not None:
        sched_kwargs = dict(lr_schedule)
    schedule = (
        build_lr_schedule(lr=lr, **sched_kwargs) if sched_kwargs else optax.constant_schedule(lr)
    )
    parts: list[optax.GradientTransformation] = []
    if grad_clip_norm:
        parts.append(clip_by_global_norm_fp32(grad_clip_norm))
    if name == "adafactor":
        parts.append(optax.adafactor(learning_rate=schedule, weight_decay_rate=weight_decay or None))
        return optax.chain(*parts)
    if name == "muon":
        # Muon for >=2-D weights with adam fallback inside optax.contrib.muon
        # (parity: the reference's Dion/Muon integration, optim/utils.py:151)
        from optax import contrib as _contrib

        parts.append(
            _contrib.muon(
                learning_rate=schedule,
                adam_b1=betas[0],
                adam_b2=betas[1],
                weight_decay=weight_decay,
            )
        )
        return optax.chain(*parts)
    if name not in _SCALERS:
        raise ValueError(f"Unknown optimizer {name!r}; available: {sorted(_SCALERS)}")
    if name in ("adamw", "adam"):
        if moments_dtype in (None, "float32"):
            parts.append(
                scale_by_adam_fp32_moments(b1=betas[0], b2=betas[1], eps=eps)
            )
        elif moments_dtype == "param":
            parts.append(optax.scale_by_adam(b1=betas[0], b2=betas[1], eps=eps))
        else:
            raise ValueError(
                f"moments_dtype must be None, 'float32' or 'param'; got "
                f"{moments_dtype!r}"
            )
    else:
        parts.append(_SCALERS[name](tuple(betas), eps))
    if weight_decay and name in ("adamw", "lion"):
        parts.append(optax.add_decayed_weights(weight_decay))
    parts.append(optax.scale_by_learning_rate(schedule))
    return optax.chain(*parts)


def opt_state_shardings(
    optimizer: optax.GradientTransformation, params: Any, mesh_ctx: Any
) -> Any:
    """Where ``optimizer.init(params)`` belongs on the mesh: every state leaf
    that mirrors a param (Adam's mu/nu: same path suffix, same shape) on
    that param's shards; everything else (step counts, factored statistics)
    replicated. ``params`` may be arrays or sharded ShapeDtypeStructs."""
    from automodel_tpu.parallel.plans import path_str

    by_path = {
        path_str(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }

    def sharding(path, leaf):
        parts = path_str(path).split("/")
        for i in range(len(parts)):
            p = by_path.get("/".join(parts[i:]))
            if p is not None and p.shape == leaf.shape:
                return p.sharding
        return mesh_ctx.replicated()

    return jax.tree_util.tree_map_with_path(
        sharding, jax.eval_shape(optimizer.init, params)
    )


def init_opt_state(
    optimizer: optax.GradientTransformation, params: Any, mesh_ctx: Any = None
) -> Any:
    """``optimizer.init(params)`` created on the shards
    ``opt_state_shardings`` names. A bare ``jax.jit(optimizer.init)(params)``
    does not do this: the moments are ``zeros_like`` — no data dependence on
    the params — so XLA materializes all of them unsharded on device 0 (2x
    the model in fp32 on one chip of the host), the first train step moves
    them, and its outputs come back laid out differently, so the step
    compiles twice."""
    if mesh_ctx is None:
        return jax.jit(optimizer.init)(params)
    return jax.jit(
        optimizer.init,
        out_shardings=opt_state_shardings(optimizer, params, mesh_ctx),
    )(params)
