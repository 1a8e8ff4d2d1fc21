"""The jitted training step.

Parity with the reference hot path (recipes/llm/train_ft.py:1284
_run_train_optim_step): microbatch grad accumulation, GLOBAL label-token
normalization across the dp_cp group and all microbatches
(train_ft.py:1292-1303), grad clip, optimizer step, loss/grad-norm metrics.

TPU-native structure: ONE `jax.jit` covers the whole optimizer step —
the microbatch loop is a `lax.scan` over a leading accumulation axis, so
FSDP all-gathers, loss collectives, and the optimizer update are all
scheduled by XLA inside a single program (the reference needs
MoEFSDPSyncMixin + no_sync contexts to get this right; here it falls out
of functional grads). Collectives are implicit: batches arrive sharded over
(dp, cp); `jnp.sum` of loss/token-count is a global reduction XLA lowers to
psum over the data axes.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax

from automodel_tpu.training.train_state import TrainState


def build_train_step(
    loss_fn: Callable[[Any, dict], tuple],
    optimizer: optax.GradientTransformation,
    lr_schedule: Optional[Callable] = None,
    donate: bool = True,
    post_step_fn: Optional[Callable[[Any, dict], Any]] = None,
    grad_mask: Any = None,
    anomaly_flags: bool = True,
    on_nonfinite: str = "raise",
    nan_grads_at_step: Optional[int] = None,
) -> Callable[[TrainState, dict], tuple[TrainState, dict]]:
    """Build the jitted (state, batch) → (state, metrics) step.

    ``loss_fn(params, microbatch) -> (loss_sum, n_valid_tokens[, extras])``
    where loss_sum is the UN-normalized token-loss sum (normalization happens
    here, globally) and `extras` is an optional pytree of per-microbatch
    auxiliaries (MoE expert counts, aux losses) summed across microbatches.
    ``batch`` leaves carry a leading microbatch axis [A, ...]; A=1 for no
    accumulation.

    ``post_step_fn(new_params, extras_sum) -> new_params`` runs AFTER the
    optimizer update, outside the gradient — the reference's
    update_moe_gate_bias slot (train_ft.py:1341, aux-free load balancing).

    ``anomaly_flags`` (default on): fold `telemetry.anomaly` reductions into
    the metrics dict INSIDE the jit — a boolean ``nonfinite`` (loss or any
    grad), the grad non-finite element count, and per-param-group grad norms
    (``grad_norm/<group>``). A few scalar reductions XLA fuses into the
    existing grad traversal; no extra device round-trips (the metrics dict
    is only fetched at log steps), so a NaN/Inf is caught in the step it
    occurs with the group that produced it.

    ``on_nonfinite`` (resilience/, fault_tolerance.on_nonfinite): with
    ``"skip"``, a step whose loss or gradient goes non-finite DISCARDS the
    update inside the jit — params and opt-state are carried through
    bit-identical (``jnp.where`` on the already-computed new trees, so
    there is no control-flow divergence and no recompile) and the metrics
    gain a ``skipped`` flag the recipe counts. ``"raise"``/``"rollback"``
    are host-side policies (recipes/train_ft.py). The non-default policies
    (skip/rollback) force the bare ``nonfinite`` flag even when
    ``anomaly_flags`` is off; the default ``raise`` policy respects the
    anomaly_flags opt-out — disabling anomaly flags under ``raise``
    disables non-finite detection entirely (the recipe warns loudly at
    setup). The step counter still advances on a skipped step (the LR
    schedule and cadence predicates stay aligned with the data stream).

    ``nan_grads_at_step`` (fault injection): poison every gradient leaf at
    the optimizer step with that 1-based number (``state.step + 1``, the
    number the scheduler and metrics report). Keyed on the TRACED step, so
    arming it costs one fused select per leaf and no recompile.

    ``grad_mask`` (bool pytree, True = trainable): frozen leaves' gradients
    are replaced by zeros immediately after value_and_grad — XLA dead-code-
    eliminates the backward compute that only produced them, and grad_norm
    reflects trainable params only (see training/freeze.py).

    Pipeline-parallel loss_fns (parallel/pp.py wrappers): under
    pp_schedule='zero_bubble' the per-stage VJP is split into B/W passes and
    weight-grad (W) chunks land OUT of microbatch order, summed in fp32
    inside the pipeline's custom_vjp (parallel/zero_bubble.py) — the
    gradient value_and_grad returns here is only materialized once every W
    chunk has landed, so the fp32 global-norm clip below never sees a
    partial gradient. A loss_fn built over a pipelined model carries
    ``pipeline_info`` and the metrics gain the analytic
    ``pp_bubble_fraction`` for the active schedule.
    """

    # a loss_fn may carry frozen params (LoRA base) to pass as a REAL jit
    # argument — closures over device trees become captured constants baked
    # into every lowering (GBs for large bases)
    bound_params = getattr(loss_fn, "bound_params", None)
    # a loss_fn may also want the optimizer step (QAT delayed fake-quant
    # enablement, quantization/qat.py) — passed as a traced kwarg. LoRA
    # dropout additionally folds the microbatch index so accumulation
    # microbatches draw independent masks.
    needs_step = getattr(loss_fn, "needs_step", False)
    needs_mb_index = getattr(loss_fn, "needs_mb_index", False)

    def call_loss(params, mb, bound, step, mb_index=None):
        kw = {"step": step} if needs_step else {}
        if needs_mb_index:
            kw["mb_index"] = mb_index
        out = (
            loss_fn(params, mb, bound, **kw)
            if bound is not None
            else loss_fn(params, mb, **kw)
        )
        if len(out) == 3:
            return out
        loss_sum, n = out
        return loss_sum, n, {}

    def mb_value_and_grad(params, mb, bound, step, mb_index=None):
        def wrapped(p):
            loss_sum, n, extras = call_loss(p, mb, bound, step, mb_index)
            return loss_sum.astype(jnp.float32), (n, extras)
        val, grads = jax.value_and_grad(wrapped, has_aux=True)(params)
        if grad_mask is not None:
            grads = jax.tree.map(
                lambda g, m: g if m else jnp.zeros_like(g), grads, grad_mask
            )
        return val, grads

    def step_fn(state: TrainState, batch: dict, bound=None) -> tuple[TrainState, dict]:
        n_mb = jax.tree.leaves(batch)[0].shape[0]
        if n_mb == 1:
            # no-accumulation fast path: the fp32 zeros+add accumulator would
            # DOUBLE every grad buffer (bf16→fp32) and drag ~3 full-size
            # layout copies through global-norm/scale (measured 2.5GB each on
            # the MoE bench fingerprint's stacked expert grads). Grads stay in
            # param dtype; moment fp32-ness is the OPTIMIZER's contract
            # (optim/builders.scale_by_adam_fp32_moments — optax's own adam
            # would inherit bf16 from these grads and freeze nu).
            mb = jax.tree.map(lambda x: x[0], batch)
            (loss_sum, (n_tokens, extras)), grads = mb_value_and_grad(
                state.params, mb, bound, state.step,
                jnp.int32(0),
            )
            extras_sum = extras
        else:
            # the model's own scopes sit inside this one and win; what is
            # left under `grad_accum` is the loop's bookkeeping
            with jax.named_scope("grad_accum"):
                grads0 = jax.tree.map(
                    lambda p: jnp.zeros_like(p, dtype=jnp.float32), state.params
                )
                carry0 = (grads0, jnp.float32(0.0), jnp.int32(0))

                def body(carry, mb_and_i):
                    mb, mb_i = mb_and_i
                    g_acc, l_acc, n_acc = carry
                    (loss_sum, (n, extras)), grads = mb_value_and_grad(
                        state.params, mb, bound, state.step, mb_i
                    )
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), g_acc, grads
                    )
                    return (g_acc, l_acc + loss_sum, n_acc + n), extras

                (grads, loss_sum, n_tokens), extras_stacked = jax.lax.scan(
                    body, carry0, (batch, jnp.arange(n_mb, dtype=jnp.int32))
                )
                extras_sum = jax.tree.map(lambda x: x.sum(axis=0), extras_stacked)
        denom = jnp.maximum(n_tokens, 1).astype(jnp.float32)
        # divide in fp32 even for bf16 grads (a bf16-rounded token count is
        # off by up to 0.4%); the convert/divide/convert fuses — no
        # materialized fp32 copy
        with jax.named_scope("grad_accum"):
            grads = jax.tree.map(
                lambda g: (g.astype(jnp.float32) / denom).astype(g.dtype), grads
            )
        if nan_grads_at_step is not None:
            poison = jnp.where(
                state.step + 1 == nan_grads_at_step, jnp.float32(jnp.nan), 0.0
            )
            grads = jax.tree.map(lambda g: g + poison.astype(g.dtype), grads)
        from automodel_tpu.optim.builders import global_norm_fp32

        with jax.named_scope("grad_clip"):
            grad_norm = global_norm_fp32(grads)
        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
            # keep params in their original dtype (apply_updates may upcast)
            new_params = jax.tree.map(
                lambda new, old: new.astype(old.dtype), new_params, state.params
            )
            if post_step_fn is not None:
                new_params = post_step_fn(new_params, extras_sum)
        metrics = {
            "loss": loss_sum / denom,
            "grad_norm": grad_norm,
            "num_label_tokens": n_tokens,
            "step": state.step + 1,
        }
        if anomaly_flags:
            from automodel_tpu.telemetry.anomaly import anomaly_metrics

            with jax.named_scope("anomaly"):
                metrics.update(anomaly_metrics(loss_sum, grads))
        elif on_nonfinite != "raise" or nan_grads_at_step is not None:
            # the host-side policies need the flag even with the full
            # anomaly reductions disabled
            from automodel_tpu.telemetry.anomaly import nonfinite_count

            with jax.named_scope("anomaly"):
                metrics["nonfinite"] = ~jnp.isfinite(loss_sum) | (
                    nonfinite_count(grads) > 0
                )
        if on_nonfinite == "skip":
            bad = metrics["nonfinite"]
            # carry params AND opt-state through unchanged (bit-identical:
            # jnp.where with a scalar pred selects whole buffers) — the NaN
            # never reaches the weights or the Adam moments
            with jax.named_scope("optimizer"):
                new_params = jax.tree.map(
                    lambda new, old: jnp.where(bad, old, new),
                    new_params, state.params,
                )
                new_opt_state = jax.tree.map(
                    lambda new, old: jnp.where(bad, old, new),
                    new_opt_state,
                    state.opt_state,
                )
            metrics["skipped"] = bad
        if "moe_aux_loss" in extras_sum:
            metrics["moe_aux_loss"] = extras_sum["moe_aux_loss"] / batch_size(batch)
        pinfo = getattr(loss_fn, "pipeline_info", None)
        if pinfo:
            from automodel_tpu.utils.flops_utils import pipeline_bubble_fraction

            metrics["pp_bubble_fraction"] = pipeline_bubble_fraction(
                pinfo["pp"], pinfo["n_microbatches"],
                pinfo.get("schedule", "gpipe"), pinfo.get("zb_queue"),
                pinfo.get("w_deferred_fraction", 1.0),
            )
        # a loss_fn may derive its own scalar metrics from the summed extras
        # (posttrain/: dpo_loss, accept_margin, kl_to_ref) — the callable
        # runs in-jit over the microbatch-summed tree, so token-weighted
        # means normalize by the SAME global denominator as the loss
        metric_extras = getattr(loss_fn, "metric_extras", None)
        if metric_extras is not None:
            metrics.update(metric_extras(extras_sum, denom))
        if "expert_counts" in extras_sum:
            c = extras_sum["expert_counts"].astype(jnp.float32)  # [L, E]
            per_layer = c.max(axis=-1) / jnp.maximum(c.mean(axis=-1), 1.0)
            metrics["expert_load_imbalance"] = per_layer.mean()
            # per-layer detail for the JSONL (reference:
            # moe/load_balance_metrics.py detailed metrics)
            metrics["expert_load_imbalance_per_layer"] = per_layer
        if "held_expert_rows" in extras_sum:
            metrics["held_expert_rows"] = extras_sum["held_expert_rows"]
        if "mtp_loss_sum" in extras_sum:
            # the multi-token-prediction loss alone: a mean over ITS targets
            metrics["mtp_loss"] = extras_sum["mtp_loss_sum"] / jnp.maximum(
                extras_sum["mtp_tokens"], 1
            ).astype(jnp.float32)
        if "mhc_res_row_err" in extras_sum:
            # each microbatch's largest, averaged over the microbatches
            metrics["mhc_res_row_err"] = extras_sum["mhc_res_row_err"] / batch_size(batch)
        if lr_schedule is not None:
            metrics["lr"] = lr_schedule(state.step)
        new_state = TrainState(
            params=new_params, opt_state=new_opt_state, step=state.step + 1
        )
        return new_state, metrics

    def batch_size(batch) -> jnp.ndarray:
        leaf = jax.tree.leaves(batch)[0]
        return jnp.float32(leaf.shape[0])

    jitted = jax.jit(step_fn, donate_argnums=(0,) if donate else ())
    if bound_params is None:
        return jitted
    return lambda state, batch: jitted(state, batch, bound_params)


def build_eval_step(
    loss_fn: Callable[[Any, dict], tuple[jnp.ndarray, jnp.ndarray]],
) -> Callable[[TrainState, dict], dict]:
    """Validation step: microbatch-scanned loss sum + token count."""
    bound_params = getattr(loss_fn, "bound_params", None)
    needs_step = getattr(loss_fn, "needs_step", False)

    def step_fn(state: TrainState, batch: dict, bound=None) -> dict:
        def body(carry, mb):
            l_acc, n_acc = carry
            kw = {"step": state.step} if needs_step else {}
            out = (
                loss_fn(state.params, mb, bound, **kw)
                if bound is not None
                else loss_fn(state.params, mb, **kw)
            )
            loss_sum, n = out[:2]
            return (l_acc + loss_sum.astype(jnp.float32), n_acc + n), None

        (loss_sum, n), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.int32(0)), batch)
        return {"loss_sum": loss_sum, "num_label_tokens": n}

    jitted = jax.jit(step_fn)
    if bound_params is None:
        return jitted
    return lambda state, batch: jitted(state, batch, bound_params)


def shift_labels(labels: jnp.ndarray, segment_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``labels`` [B, S] shifted left once more (position t takes t + 1's
    target): a multi-token-prediction module's targets. A row's last position
    and the last position of a packed document (``segment_ids`` change after
    it) have none and are ignored."""
    ignore = jnp.full_like(labels[:, :1], -100)
    shifted = jnp.concatenate([labels[:, 1:], ignore], axis=1)
    if segment_ids is not None:
        same = jnp.concatenate(
            [segment_ids[:, 1:] == segment_ids[:, :-1], jnp.zeros_like(ignore, bool)], axis=1
        )
        shifted = jnp.where(same, shifted, -100)
    return shifted


def make_causal_lm_loss(
    model: Any,
    loss: str = "masked_ce",
    constrain: Callable = lambda x, s: x,
    **loss_kwargs: Any,
) -> Callable[[Any, dict], tuple[jnp.ndarray, jnp.ndarray]]:
    """Standard next-token-prediction loss over a causal LM.

    Labels follow the HF convention (already shifted by the collator:
    labels[t] is the target for position t, ignore_index=-100 padding).
    ``loss='fused_linear_ce'`` skips logits materialization (reference:
    FusedLinearCrossEntropy, loss/linear_ce.py:119).
    """
    from automodel_tpu.ops import losses as L

    def loss_fn(params, mb):
        kw = {
            k: mb[k]
            for k in (
                "position_ids", "segment_ids", "pixel_values",
                "mrope_position_ids",
            )
            if k in mb and mb[k] is not None
        }
        if loss in ("fused_linear_ce", "vocab_parallel_ce"):
            out = model.hidden(params, mb["input_ids"], constrain=constrain, **kw)
            hidden, maux = out if isinstance(out, tuple) else (out, None)
            mesh_ctx = getattr(constrain, "mesh_ctx", None)
            with jax.named_scope("lm_head_ce"):  # head and loss, chunk scan included
                kernel = model.lm_head(params).astype(hidden.dtype)

                def head_loss(h, labels):
                    if loss == "vocab_parallel_ce" and mesh_ctx is not None:
                        return L.vocab_parallel_cross_entropy(
                            h, kernel, labels, mesh_ctx,
                            logits_soft_cap=model.config.logits_soft_cap, **loss_kwargs,
                        )
                    return L.fused_linear_cross_entropy(
                        h, kernel, labels,
                        logits_soft_cap=model.config.logits_soft_cap, **loss_kwargs,
                    )

                loss_sum, n = head_loss(hidden, mb["labels"])
        else:
            out = model(params, mb["input_ids"], constrain=constrain, **kw)
            logits, maux = out if isinstance(out, tuple) else (out, None)

            loss_of_logits = L.build_loss(loss, **loss_kwargs)

            def head_loss(h, labels):
                return loss_of_logits(h @ model.lm_head(params).astype(h.dtype), labels)

            with jax.named_scope("lm_head_ce"):
                loss_sum, n = loss_of_logits(logits, mb["labels"])
        mtp_hidden = getattr(maux, "mtp_hidden", None)
        if mtp_hidden is not None:
            # a second pass of the same head over the module's hidden state,
            # against the token after the next; each loss is a mean over its
            # own targets, so the module's sum is weighted to the main count
            # (the step divides the whole by it)
            with jax.named_scope("lm_head_ce"):
                mtp_sum, mtp_n = head_loss(
                    mtp_hidden, shift_labels(mb["labels"], mb.get("segment_ids")))
            loss_sum = loss_sum + model.config.mtp_loss_weight * mtp_sum * (
                n.astype(jnp.float32) / jnp.maximum(mtp_n, 1).astype(jnp.float32)
            )
        if maux is None:
            return loss_sum, n
        # MoE models return (output, aux). The aux loss is a per-batch mean;
        # weighting by this microbatch's token count makes the global
        # normalization (divide by total tokens) produce the correct
        # token-weighted average across microbatches and the dp_cp group.
        loss_sum = loss_sum + maux.aux_loss * n.astype(jnp.float32)
        extras = {
            "moe_aux_loss": maux.aux_loss,
            "expert_counts": maux.expert_counts,
        }
        if getattr(maux, "held_expert_rows", None) is not None:
            extras["held_expert_rows"] = maux.held_expert_rows
        if mtp_hidden is not None:
            extras["mtp_loss_sum"], extras["mtp_tokens"] = mtp_sum, mtp_n
        if getattr(maux, "mhc_res_row_err", None) is not None:
            extras["mhc_res_row_err"] = maux.mhc_res_row_err
        return loss_sum, n, extras

    # pipelined models advertise their schedule so the step metrics (and the
    # benchmark recipe) can report bubble fraction per schedule
    info = getattr(model, "pipeline_info", None)
    if info:
        loss_fn.pipeline_info = info

    return loss_fn
