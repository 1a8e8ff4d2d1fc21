"""Shared layer-stack driver: lax.scan vs unrolled loop, with remat.

Parity: the reference wraps layers in activation-checkpoint modules and
iterates nn.ModuleLists (distributed/parallelizer.py apply-AC flow). The
TPU-native form runs the whole stack through one ``lax.scan`` over stacked
per-layer params (fast compile, one kernel), or an unrolled python loop
(per-layer static specialization — e.g. a distinct attention mask per
layer compiles exactly one kernel each).

The unrolled path passes per-layer flags through the CLOSURE as python
scalars, not traced arguments — ``jax.checkpoint`` would otherwise turn
them into Tracers and force both branches of any flag-conditional kernel
selection to compile (see ops/attention.py windowed_attention).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.moe.experts import SORT_CHECKPOINT_NAMES
from automodel_tpu.ops.attention import SPLASH_RESIDUAL_NAME


# What survives a checkpoint under each recomputing policy, by the name it
# was given where it is computed: splash's ``out`` and ``logsumexp`` under
# every one, the MoE sort permutations under full_save_dispatch besides.
_KEPT_NAMES = {
    "full": (SPLASH_RESIDUAL_NAME,),
    "full_save_dispatch": (*SORT_CHECKPOINT_NAMES, SPLASH_RESIDUAL_NAME),
    "selective": (SPLASH_RESIDUAL_NAME,),
}


def remat_wrap(f: Callable, remat: str) -> Callable:
    """``jax.checkpoint`` over one layer, by ``BackendConfig.remat``:

    - ``none``: ``f`` as it is; autodiff keeps every residual.
    - ``full``: recomputes everything XLA computes in the backward; never
      re-runs the attention kernel. Kept a layer: its input, and splash's
      bfloat16 ``out`` and float32 ``logsumexp``, ``S x N x (2 Dv + 4)``
      bytes an attention layer and sequence (68 MB at 8,192 tokens x 32
      heads x 128: ``N Dv / H`` of the layer's input at every length, while
      the forward kernel it spares grows with the length squared).
    - ``full_save_dispatch``: ``full``, and the MoE sort permutations
      (moe/experts.py _name_ckpt; 2 to 4 int32 ``[T*K]`` arrays a layer),
      so the recompute skips the argsorts over the T*K picks.
    - ``selective``: keeps every product with no batch dimension as well
      (the projections' and MLP's outputs); recomputes the elementwise ops
      between them.

    Off the TPU ``attn: flash`` falls back to sdpa, nothing carries the
    name, and each policy keeps what it kept without it."""
    if remat not in _KEPT_NAMES:
        return f
    policies = jax.checkpoint_policies
    policy = policies.save_only_these_names(*_KEPT_NAMES[remat])
    if remat == "selective":
        policy = policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable, policy
        )
    return jax.checkpoint(f, policy=policy)


# cap for the aperiodic P == num_layers fallback in _flag_period: beyond
# this the "group" is a full unroll of the stack and compile time grows
# linearly in depth, which the traced-flag cond path avoids
_FULL_UNROLL_MAX = 16


def _flag_period(flags: dict, num_layers: int) -> Optional[int]:
    """Smallest P dividing num_layers such that every flag repeats with
    period P (gpt-oss sliding/full alternation → 2, gemma-3 local:global
    → 6, uniform flags → 1). When no short period exists, P == num_layers
    (which always matches) is tried too — 2-layer alternations and
    non-divisible sliding/full patterns then still get the static-flag
    grouped scan instead of the ~6ms/layer traced-flag `lax.cond` path —
    but only up to _FULL_UNROLL_MAX layers: the P=L group is a full unroll
    (one scan step tracing L layer bodies), so deep aperiodic stacks keep
    the cond path to bound compile time/executable size. None when a flag
    is not one scalar per layer or no eligible period exists."""
    import numpy as np

    if not flags:
        return None
    vals = list(flags.values())
    if any(np.ndim(v) != 1 or len(v) != num_layers for v in vals):
        return None
    cands = list(range(1, num_layers // 2 + 1))
    if num_layers <= _FULL_UNROLL_MAX:
        cands.append(num_layers)
    for P in cands:
        if num_layers % P:
            continue
        if all(np.array_equal(np.tile(v[:P], num_layers // P), v) for v in vals):
            return P
    return None


def run_layer_stack(
    layer_fn: Callable,
    h: Any,
    layer_params: Any,
    flags: Optional[dict],
    *,
    scan_layers: bool,
    remat: str,
    num_layers: int,
) -> tuple[Any, Any]:
    """Run ``layer_fn(carry, (layer_slice, flag_slice)) -> (carry, y)`` over
    a stacked layer tree. Returns (final carry, stacked ys or None).

    ``flags`` values must be numpy arrays (leading layer axis): lax.scan
    slices them as traced leaves; the unrolled loop extracts STATIC python
    scalars per layer.

    When the flags repeat with a short period P (alternating sliding/full
    attention and the like), the scan runs over GROUPS of P layers with the
    flags baked in as python scalars: a traced flag otherwise forces a
    lax.cond per layer whose branch-operand copies cost real HBM traffic
    (measured ~6ms/layer on the gpt-oss bench fingerprint), and the cond
    blocks per-branch kernel specialization."""
    flags = flags or {}
    if scan_layers:
        P = _flag_period(flags, num_layers)
        if P is not None:
            Lg = num_layers // P
            grouped = jax.tree.map(
                lambda x: x.reshape(Lg, P, *x.shape[1:]), layer_params
            )
            static_fl = [
                {k: v[j].item() for k, v in flags.items()} for j in range(P)
            ]

            def group_fn(carry, lp_group):
                ys = []
                for j in range(P):
                    lp_j = jax.tree.map(lambda x: x[j], lp_group)
                    # remat per LAYER (not per group): the group is only a
                    # vehicle for static flags; coarser checkpoint blocks
                    # raise the backward working set by a full layer's
                    # activations (OOMs the 16GB bench chip)
                    carry, y = remat_wrap(
                        lambda c, lp_, _j=j: layer_fn(c, (lp_, static_fl[_j])),
                        remat,
                    )(carry, lp_j)
                    ys.append(y)
                if all(y is None for y in ys):
                    return carry, None
                return carry, jax.tree.map(lambda *zs: jnp.stack(zs, 0), *ys)

            h, ys = jax.lax.scan(group_fn, h, grouped)
            if ys is not None:
                # [Lg, P, ...] → [L, ...]
                ys = jax.tree.map(
                    lambda x: x.reshape(num_layers, *x.shape[2:]), ys
                )
            return h, ys
        return jax.lax.scan(remat_wrap(layer_fn, remat), h, (layer_params, flags))
    ys = []
    for i in range(num_layers):
        lp = jax.tree.map(lambda x: x[i], layer_params)
        fl = {k: v[i].item() for k, v in flags.items()}
        h, y = remat_wrap(
            lambda carry, lp_, _fl=fl: layer_fn(carry, (lp_, _fl)), remat
        )(h, lp)
        ys.append(y)
    if all(y is None for y in ys):
        return h, None
    return h, jax.tree.map(lambda *zs: jnp.stack(zs, 0), *ys)
