"""Pipeline parallelism: SPMD microbatch pipeline over the ``pp`` mesh axis.

Parity: the reference's AutoPipeline (distributed/pipelining/autopipeline.py:
46, functional.py:289-560) — FQN-based stage splitting + torch.distributed
pipelining schedules (gpipe/1f1b/interleaved). TPU-native design (SURVEY.md
§7): the decoder stack's stacked layer axis IS the stage structure — slice it
across pp, and run a GPipe wavefront as a `lax.scan` over ticks inside a
`shard_map` that is MANUAL over pp only (`axis_names={'pp'}`): activations
hop stages via `lax.ppermute` while dp/tp/fsdp sharding inside each stage
stays compiler-managed (GSPMD auto axes). `jax.grad` differentiates through
the whole pipeline (transpose of ppermute reverses the ring), so the backward
wavefront needs no hand-written schedule, and XLA overlaps the ppermute with
stage compute.

Outputs leave the pipeline SHARDED on pp (out_specs lead with "pp"); the
caller slices the last stage's entry, which lowers to a broadcast from one
rank instead of the full-activation psum an earlier revision paid per step
(reference keeps loss on the last stage the same way, train_ft.py:1365).

MoE stacks pipeline too: the stage function may return (y, stage_aux) and
per-stage aux (expert counts, aux losses) accumulates across microbatch
ticks under a validity mask, coming back [pp, L/pp, ...] for reassembly —
the composition the reference reaches via PP+EP parallelize_fn per stage
(moe/parallelizer.py:300).

Bubble: (pp-1)/(M+pp-1) with M microbatches — choose M >= 4·pp.

On 1F1B/interleaved schedules (reference functional.py:490-560): in this
SPMD formulation ticks are globally synchronous and the backward wavefront
is generated by AD, so the classic 1F1B payoffs change shape. (a) Bubble:
virtual-stage interleaving NEEDS per-rank asynchronous slots; under
synchronous ticks a v-way circular assignment adds (v-1)·pp warmup ticks
and strictly loses to GPipe-wavefront — so it is deliberately not done.
(b) Memory: 1F1B bounds live activations at pp in-flight microbatches;
here the same bound comes from `jax.checkpoint` over the tick body (remat
per microbatch-tick), which XLA already schedules. A hand-written
fwd/bwd-interleaved schedule (explicit per-chunk vjp in one scan) is the
remaining true-1F1B design if profiling ever shows the AD wavefront
leaving ICI idle between the two wavefronts.

MEASURED (round 4, docs/history/PROFILE_PP_r04.md): a pp=4 sweep of M∈{2..16} fits
t = T_work·(1 + (pp-1)/M) within 5% — the AD backward wavefront achieves
the synchronous-SPMD schedule floor with no extra idle gap; 1F1B's bubble
term is identical, and synchronous-tick interleaving measures strictly
worse.

schedule='zero_bubble' (this round) implements the remaining headroom:
the hand-split vjp in parallel/zero_bubble.py splits each stage backward
into B (activation grads, stays on the ppermute wavefront) and W (weight
grads, deferred into a bounded queue and contracted as flat bubble-free
work after the B wave drains) — bubble 3(pp-1)/(4M+3(pp-1)), strictly
below the GPipe law (pp-1)/(M+pp-1) at every M (docs/history/PROFILE_PP_r06.md).
Select via MeshConfig.pp_schedule; bound stash memory with
MeshConfig.pp_zb_queue.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from automodel_tpu.parallel.mesh import MeshContext


def spmd_pipeline(
    stage_fn: Callable,  # (stage_params, x [mb,...], aux) -> y | (y, stage_aux)
    stage_params: Any,  # pytree, leaves [L, ...] with L divisible by pp
    inputs: jnp.ndarray,  # [M, mb, ...] microbatched activations
    aux: Any,  # pytree of [M, ...] per-microbatch side inputs (cos/sin/seg)
    mesh_ctx: MeshContext,
    has_stage_aux: bool = False,
    ep_manual: bool = False,
) -> Any:
    """Run the stacked-layer decoder as a pp-stage pipeline.

    Returns [M, mb, ...] outputs, or (outputs, global_aux) when
    ``has_stage_aux`` — global_aux leaves lead with the pp axis
    ([pp, L/pp, ...]) for the caller to reassemble into [L, ...].

    ``ep_manual``: open the region manual over {pp, ep} instead of {pp} —
    the microbatch dim of inputs/aux arrives ep-sharded and expert-stacked
    param leaves (path containing 'experts') arrive [L/pp, E/ep, ...], so
    the stage body may issue its own ep collectives (the a2a token
    exchange). Stage aux is psum'd over ep before leaving the region.
    """
    mesh = mesh_ctx.mesh
    pp = mesh.shape["pp"]
    if pp == 1:
        if has_stage_aux:
            ys, auxs = jax.lax.map(
                lambda args: stage_fn(stage_params, args[0], args[1]), (inputs, aux)
            )
            # sum microbatch contributions; prepend the pp=1 stage axis
            auxs = jax.tree.map(lambda a: a.sum(0)[None].astype(jnp.float32), auxs)
            return ys, auxs
        return jax.lax.map(
            lambda args: stage_fn(stage_params, args[0], args[1]), (inputs, aux)
        )
    M = inputs.shape[0]
    compute_dtype = inputs.dtype

    if ep_manual:
        from automodel_tpu.parallel.plans import path_str

        def pspec(path, leaf):
            # expert-stacked leaves [L, E, ...] are ep-sharded on dim 1
            return P("pp", "ep") if "experts" in path_str(path) else P("pp")

        param_specs = jax.tree_util.tree_map_with_path(pspec, stage_params)
        data_spec = P(None, "ep")  # [M, mb, ...] — microbatches ep-sharded
    else:
        param_specs = jax.tree.map(lambda _: P("pp"), stage_params)
        data_spec = P()
    # the input buffer crosses the shard_map boundary replicated over pp; its
    # transpose is a psum of cotangents, which must be f32 (bf16 all-reduce
    # also trips XLA-CPU's AllReducePromotion). Inside the region activations
    # are cast back, so ppermute traffic stays in compute dtype.
    inputs = inputs.astype(jnp.float32)

    def pp_fn(sp, inp, auxb):
        # local views: sp leaves [L/pp, ...]; inp/auxb full [M, ...]
        p = jax.lax.axis_index("pp")
        n_ticks = M + pp - 1
        state0 = jnp.zeros(inp.shape[1:], compute_dtype)

        a0 = jax.tree.map(lambda b: b[0], auxb)
        if has_stage_aux:
            _, aux_shape = jax.eval_shape(stage_fn, sp, state0, a0)
            acc0 = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), aux_shape)
        else:
            acc0 = None

        def tick(carry, t):
            state, acc = carry
            in_idx = jnp.clip(t, 0, M - 1)
            mb_idx = jnp.clip(t - p, 0, M - 1)
            x_in = jnp.where(p == 0, inp[in_idx].astype(compute_dtype), state)
            a = jax.tree.map(lambda b: b[mb_idx], auxb)
            if has_stage_aux:
                y, saux = stage_fn(sp, x_in, a)
                # rank p holds a real microbatch only for ticks [p, p+M)
                valid = jnp.logical_and(t >= p, t < p + M)
                acc = jax.tree.map(
                    lambda A, s: A + jnp.where(valid, s.astype(jnp.float32), 0.0),
                    acc,
                    saux,
                )
            else:
                y = stage_fn(sp, x_in, a)
            state_next = jax.lax.ppermute(
                y, "pp", [(i, (i + 1) % pp) for i in range(pp)]
            )
            return (state_next, acc), y

        (_, acc), ys = jax.lax.scan(tick, (state0, acc0), jnp.arange(n_ticks))
        # each rank returns its own tick outputs, sharded on a leading pp
        # axis; only rank pp-1's row holds final-stage activations and the
        # caller's slice of that row lowers to a broadcast from one rank —
        # no full-activation psum.
        ys = ys[pp - 1 :][None]
        if has_stage_aux:
            if ep_manual:  # per-ep-shard partials → global (ep-replicated)
                acc = jax.tree.map(lambda A: jax.lax.psum(A, "ep"), acc)
            return ys, jax.tree.map(lambda A: A[None], acc)
        return ys

    ys_spec = P("pp", None, "ep") if ep_manual else P("pp")
    out_specs = (ys_spec, P("pp")) if has_stage_aux else ys_spec
    mapped = shard_map(
        pp_fn,
        mesh=mesh,
        in_specs=(param_specs, data_spec, data_spec),
        out_specs=out_specs,
        axis_names={"pp", "ep"} if ep_manual else {"pp"},
        check_vma=False,
    )
    if has_stage_aux:
        ys, acc = mapped(stage_params, inputs, aux)
        return ys[pp - 1], acc
    return mapped(stage_params, inputs, aux)[pp - 1]


_logged_a2a_pp = False


def _log_a2a_pp_fallback():
    global _logged_a2a_pp
    if not _logged_a2a_pp:
        _logged_a2a_pp = True
        import logging

        logging.getLogger(__name__).info(
            "experts='a2a' inside pipeline stages needs ep>1, tp=1, and "
            "microbatch size divisible by ep for the ep-manual token "
            "exchange; this config runs the dropless ragged path with "
            "GSPMD-chosen ep collectives instead (no tokens drop)."
        )


def _maybe_remat(fn, backend):
    from automodel_tpu.models.common.stacking import remat_wrap

    return remat_wrap(fn, backend.remat)


def _log_zb_fallback(why: str) -> None:
    import logging

    logging.getLogger(__name__).info(
        "pp_schedule='zero_bubble' falls back to 'gpipe' for this config "
        "(%s); the AD wavefront is numerically identical, only the bubble "
        "differs.", why,
    )


def _microbatch_plumbing(model, params, input_ids, position_ids, M,
                         constrain=None):
    """Shared embed/rope/split prep for the pipelined forwards."""
    from automodel_tpu.ops.rope import rope_table

    cfg, backend = model.config, model.backend
    cd = backend.compute_jnp_dtype
    B, S = input_ids.shape
    assert B % M == 0, f"batch {B} not divisible by n_microbatches {M}"
    if position_ids is None:
        position_ids = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)
        )
    emb = params["embed"]["embedding"]
    if constrain is not None:
        # explicit planned reshard before the gather (the table's fsdp dim
        # vs batch-sharded output otherwise triggers XLA's "involuntary full
        # rematerialization" warning — VERDICT r2 weak #6)
        emb = constrain(emb, (None, None))
    h = emb.astype(cd)[input_ids]
    rope_dim = (
        getattr(model, "pp_rope_dim", None)
        or getattr(cfg, "rope_dim", None)
        or cfg.head_dim
    )
    cos, sin = rope_table(position_ids, rope_dim, cfg.rope)

    def split(x):
        return None if x is None else x.reshape(M, B // M, *x.shape[1:])

    return h, cos, sin, split


@dataclasses.dataclass
class PipelinedCausalLM:
    """Wrap a dense stacked-layer causal LM (llama family) for PP execution.

    Embedding and lm_head run GSPMD outside the pipeline (they live on the
    reference's first/last stages; here every rank holds them sharded —
    simpler, and XLA fuses their collectives with the pipeline edges).
    Exposes the same model API (call/hidden/lm_head/sharding_rules) so
    make_causal_lm_loss and recipes need no PP-specific code.

    ``schedule``: 'gpipe' (AD-transposed wavefront) or 'zero_bubble'
    (B/W-split backward, parallel/zero_bubble.py); ``zb_queue`` bounds the
    zero-bubble weight-grad deferral queue (None = defer all M).
    """

    model: Any  # LlamaForCausalLM
    mesh_ctx: MeshContext
    n_microbatches: int = 4
    schedule: str = "gpipe"
    zb_queue: Optional[int] = None

    @property
    def config(self):
        return self.model.config

    @property
    def backend(self):
        return self.model.backend

    @property
    def pipeline_info(self) -> dict:
        """Schedule descriptor for bubble-fraction reporting
        (training/train_step.py metrics, recipes/benchmark.py). Dense
        stages defer every projection's weight grad (fraction 1.0).

        NOMINAL value for plain-kernel runs: resolve_sites drops
        LoRA-grafted / NF4-packed projections at trace time (their grads
        stay on the B tick), which this static descriptor cannot see — the
        reported bubble is then optimistic for such runs."""
        return {
            "pp": self.mesh_ctx.pp_size,
            "n_microbatches": self.n_microbatches,
            "schedule": self.schedule,
            "zb_queue": self.zb_queue,
            "w_deferred_fraction": 1.0,
        }

    def init(self, key: jax.Array) -> dict:
        return self.model.init(key)

    def lm_head(self, params: dict) -> jnp.ndarray:
        return self.model.lm_head(params)

    @property
    def sharding_rules(self) -> list[tuple[str, tuple]]:
        """Layer-stacked leaves get their leading dim sharded on `stage`."""
        rules = []
        for pat, spec in self.model.sharding_rules:
            if "layers/" in pat:
                # the family rules already spell the stacked layer dim as a
                # leading None — PP shards that dim on `stage`
                rules.append((pat, ("stage", *tuple(spec)[1:])))
            else:
                rules.append((pat, spec))
        return rules

    # -- forward -------------------------------------------------------------
    def hidden(self, params, input_ids, position_ids=None, segment_ids=None,
               constrain=None):
        from automodel_tpu.models.llama.model import decoder_layer
        from automodel_tpu.ops.norms import rms_norm

        cfg, backend = self.model.config, self.model.backend
        constrain = constrain or (lambda x, s: x)
        M = self.n_microbatches
        B, S = input_ids.shape
        h, cos, sin, split = _microbatch_plumbing(
            self.model, params, input_ids, position_ids, M, constrain
        )
        h = constrain(h, ("batch", "seq", None))
        aux = {"cos": split(cos), "sin": split(sin)}
        if segment_ids is not None:
            aux["seg"] = split(segment_ids)

        def layer_body(carry, lp, a):
            out = decoder_layer(
                cfg, backend, carry, lp, a["cos"], a["sin"], a.get("seg"),
                lambda t, s: t,  # constraints referencing pp are invalid
            )                     # inside the manual region; GSPMD infers
            return out, None

        if self.schedule == "zero_bubble":
            from automodel_tpu.parallel.zero_bubble import (
                DENSE_SITES,
                zb_spmd_pipeline,
            )

            hm = zb_spmd_pipeline(
                layer_body, params["layers"], split(h), aux, self.mesh_ctx,
                sites=DENSE_SITES, zb_queue=self.zb_queue,
                remat=backend.remat,
            )
        else:
            def stage_fn(sp, x, a):
                def layer(carry, lp):
                    return layer_body(carry, lp, a)

                out, _ = jax.lax.scan(_maybe_remat(layer, backend), x, sp)
                return out

            hm = spmd_pipeline(
                stage_fn, params["layers"], split(h), aux, self.mesh_ctx
            )
        h = hm.reshape(B, S, -1)
        h = constrain(h, ("batch", "seq", None))
        return rms_norm(h, params["final_norm"]["scale"], cfg.rms_eps)

    def __call__(self, params, input_ids, **kw):
        h = self.hidden(params, input_ids, **kw)
        logits = h @ self.model.lm_head(params).astype(h.dtype)
        cfg = self.model.config
        if cfg.logits_soft_cap is not None:
            logits = cfg.logits_soft_cap * jnp.tanh(logits / cfg.logits_soft_cap)
        return logits


@dataclasses.dataclass
class PipelinedMoECausalLM:
    """PP for the MoE families (Qwen3-MoE shaped, incl. DeepSeek-V3 MLA).

    The routed-MoE stack pipelines over pp (EP/TP/FSDP stay GSPMD-managed
    inside each stage); the short dense prefix (DeepSeek
    first_k_dense_replace) runs GSPMD outside the pipeline on every rank,
    like embed/lm_head. Per-layer gate aux (expert counts, aux loss) rides
    the tick scan under a validity mask and reassembles to the same
    MoEModelAux the unpipelined forward returns — so aux-free bias updates
    and load-balance metrics work unchanged under PP (reference:
    PP+EP composition via per-stage parallelize_fn, moe/parallelizer.py:300).
    """

    model: Any  # MoEForCausalLM | DeepseekV3ForCausalLM
    mesh_ctx: MeshContext
    n_microbatches: int = 4
    schedule: str = "gpipe"
    zb_queue: Optional[int] = None

    @property
    def config(self):
        return self.model.config

    @property
    def backend(self):
        return self.model.backend

    @property
    def pipeline_info(self) -> dict:
        """MoE stages defer only the attention projections' weight grads
        (zero_bubble.ATTN_SITES — expert/router dW stays on the B tick), so
        the analytic bubble uses the attention share of per-layer
        weight-grad FLOPs, not the dense full-deferral law. Nominal for
        plain kernels (see PipelinedCausalLM.pipeline_info)."""
        cfg = self.model.config
        moe = cfg.moe
        attn_w = cfg.hidden_size * (cfg.q_dim + 2 * cfg.kv_dim) + (
            cfg.q_dim * cfg.hidden_size
        )
        moe_w = 3 * cfg.hidden_size * (
            moe.moe_intermediate_size * moe.num_experts_per_tok
            + getattr(moe, "shared_expert_intermediate_size", 0)
        )
        return {
            "pp": self.mesh_ctx.pp_size,
            "n_microbatches": self.n_microbatches,
            "schedule": self.schedule,
            "zb_queue": self.zb_queue,
            "w_deferred_fraction": attn_w / max(attn_w + moe_w, 1),
        }

    def init(self, key: jax.Array) -> dict:
        return self.model.init(key)

    def lm_head(self, params: dict) -> jnp.ndarray:
        return self.model.lm_head(params)

    def post_step_fn(self, params: dict, extras: dict) -> dict:
        return self.model.post_step_fn(params, extras)

    _NONSTACK = ("embed/", "lm_head/", "final_norm/")

    @property
    def sharding_rules(self) -> list[tuple[str, tuple]]:
        """dense_layers stay replicated over pp (they run outside the
        pipeline); moe_layers leaves get their stack dim sharded on
        `stage`. Family patterns are normalized so both prefixed variants
        match ('layers/attn/...' → 'attn/...')."""
        rules: list[tuple[str, tuple]] = []
        for pat, spec in self.model.sharding_rules:
            if any(s in pat for s in self._NONSTACK):
                rules.append((pat, spec))
                continue
            core = pat[len("layers/"):] if pat.startswith("layers/") else pat
            rules.append((f"^dense_layers/.*{core}", spec))
            rules.append((f"^moe_layers/.*{core}", ("stage", *tuple(spec)[1:])))
        return rules

    def hidden(self, params, input_ids, **kw):
        # same contract as the wrapped MoE models: (hidden, MoEModelAux) —
        # the fused_linear_ce loss path consumes the aux from hidden()
        return self._forward(params, input_ids, **kw)

    def _forward(self, params, input_ids, position_ids=None, segment_ids=None,
                 constrain=None):
        from automodel_tpu.models.llama.model import ACT_FNS
        from automodel_tpu.moe.layer import moe_block
        from automodel_tpu.ops.norms import rms_norm

        cfg, backend = self.model.config, self.model.backend
        moe = cfg.moe
        constrain = constrain or (lambda x, s: x)
        attn_block = self.model.pp_attn_block
        M = self.n_microbatches
        B, S = input_ids.shape
        h, cos, sin, split = _microbatch_plumbing(
            self.model, params, input_ids, position_ids, M, constrain
        )
        h = constrain(h, ("batch", "seq", None))

        # dense prefix outside the pipeline (GSPMD on every rank)
        if "dense_layers" in params:
            def dense_fn(carry, lp):
                hh = attn_block(cfg, backend, carry, lp, cos, sin, segment_ids, constrain)
                x = rms_norm(hh, lp["post_attn_norm"]["scale"], cfg.rms_eps)
                act = ACT_FNS[cfg.act]
                mlp = (
                    act(x @ lp["mlp"]["gate_proj"]["kernel"].astype(x.dtype))
                    * (x @ lp["mlp"]["up_proj"]["kernel"].astype(x.dtype))
                ) @ lp["mlp"]["down_proj"]["kernel"].astype(x.dtype)
                return constrain(hh + mlp, ("batch", "seq", None)), None

            h, _ = jax.lax.scan(_maybe_remat(dense_fn, backend), h, params["dense_layers"])

        aux_in = {"cos": split(cos), "sin": split(sin)}
        if segment_ids is not None:
            aux_in["seg"] = split(segment_ids)

        # a2a inside the pipeline: its shard_map over ep cannot nest inside
        # the pp-manual region, so instead the REGION goes manual over
        # {pp, ep} and the a2a exchange body runs with ep already manual
        # (ep_manual mode below; reference PP×EP composition,
        # moe/parallelizer.py:300). Requirements: ep>1 (else single-slice
        # ragged is already the right thing), tp=1 (tp-sharded expert
        # weights can't ride an auto axis through the Pallas grouped
        # matmul), and the microbatch splitting mb divisible by ep.
        experts_backend = backend.experts
        ep = self.mesh_ctx.size("ep")
        ep_manual = False
        if experts_backend in ("a2a", "a2a_fused"):
            want_fused = experts_backend == "a2a_fused"
            mb = B // M
            if ep > 1 and self.mesh_ctx.tp_size == 1 and mb % ep == 0:
                from automodel_tpu.moe.experts import (
                    _fused_act_of,
                    a2a_experts_manual,
                )

                ep_manual = True

                def _a2a_pp(x, gout, w, mcfg, act2, *, ctx=None,
                            constrain=None, platform=None, fp8=False,
                            act_name="silu"):
                    fa = (
                        _fused_act_of(mcfg, act_name, fp8)
                        if want_fused else None
                    )
                    return a2a_experts_manual(
                        x, gout, w, mcfg, act2, ep=ep, platform=platform,
                        fp8=fp8, fused_act=fa,
                    )

                experts_backend = _a2a_pp
            else:
                _log_a2a_pp_fallback()
                experts_backend = "ragged_fused" if want_fused else "ragged"

        def layer_body(carry, lp, a):
            hh = attn_block(
                cfg, backend, carry, lp, a["cos"], a["sin"], a.get("seg"),
                lambda t, s: t,
            )
            xx = rms_norm(hh, lp["post_attn_norm"]["scale"], cfg.rms_eps)
            out, aux = moe_block(
                xx,
                lp["moe"],
                moe,
                ACT_FNS[cfg.act],
                experts_backend=experts_backend,
                fake_gate=backend.fake_balanced_gate,
                constrain=lambda t, s: t,
                platform=backend.platform,
                fp8=backend.fp8_experts,
                act_name=cfg.act,
            )
            return hh + out, aux

        # schedule policy (zero_bubble eligibility — ep-manual a2a, MLA
        # attention, fp8) is decided ONCE in maybe_pipeline so that
        # pipeline_info always describes the schedule that actually runs;
        # ep_manual implies maybe_pipeline already downgraded to gpipe
        assert not (self.schedule == "zero_bubble" and ep_manual), (
            "zero_bubble + ep-manual a2a must be downgraded in maybe_pipeline"
        )
        if self.schedule == "zero_bubble":
            from automodel_tpu.parallel.zero_bubble import (
                ATTN_SITES,
                zb_spmd_pipeline,
            )

            hm, acc = zb_spmd_pipeline(
                layer_body, params["moe_layers"], split(h), aux_in,
                self.mesh_ctx, sites=ATTN_SITES, has_stage_aux=True,
                zb_queue=self.zb_queue, remat=backend.remat,
            )
        else:
            def stage_fn(sp, x, a):
                def layer(carry, lp):
                    return layer_body(carry, lp, a)

                out, auxs = jax.lax.scan(_maybe_remat(layer, backend), x, sp)
                return out, auxs  # auxs leaves [L/pp, ...]

            hm, acc = spmd_pipeline(
                stage_fn, params["moe_layers"], split(h), aux_in, self.mesh_ctx,
                has_stage_aux=True, ep_manual=ep_manual,
            )
        h = hm.reshape(B, S, -1)
        h = constrain(h, ("batch", "seq", None))
        h = rms_norm(h, params["final_norm"]["scale"], cfg.rms_eps)

        # acc leaves [pp, L/pp, ...] summed over microbatches → [L_moe, ...];
        # aux losses were per-microbatch means, so average over M (and over
        # ep shards in ep_manual mode, where each shard contributed its
        # token-local mean into the psum). NB (documented approximation):
        # the balance objective is nonlinear in routing fractions, so the
        # ep-shard mean of shard-local aux losses is a biased estimate of
        # the global-batch aux loss — the same microbatch-mean approximation
        # the non-PP grad-accumulation path makes, extended over the ep
        # axis; expert_counts ARE psum'd exactly, so logged balance metrics
        # and the aux-free bias update see the true global loads.
        from automodel_tpu.models.qwen3_moe.model import MoEModelAux

        counts = acc.expert_counts.reshape(-1, *acc.expert_counts.shape[2:])
        aux_loss = acc.aux_loss.reshape(-1).sum() / self.n_microbatches
        if ep_manual:
            aux_loss = aux_loss / ep
        return h, MoEModelAux(counts, aux_loss)

    def __call__(self, params, input_ids, **kw):
        h, aux = self._forward(params, input_ids, **kw)
        logits = h @ self.model.lm_head(params).astype(h.dtype)
        cfg = self.model.config
        if cfg.logits_soft_cap is not None:
            logits = cfg.logits_soft_cap * jnp.tanh(logits / cfg.logits_soft_cap)
        return logits, aux


def maybe_pipeline(
    model: Any,
    mesh_ctx: Optional[MeshContext],
    n_microbatches: int = 4,
    schedule: str = "gpipe",
    zb_queue: Optional[int] = None,
):
    """Wrap `model` for PP when the mesh has pp > 1. Dense llama-family and
    MoE (qwen3-moe / deepseek-v3) stacks are supported; mixed-window stacks
    (gemma/gpt-oss) still raise.

    ``schedule``/``zb_queue``: pipeline schedule selection, normally sourced
    from MeshConfig.pp_schedule / MeshConfig.pp_zb_queue."""
    if mesh_ctx is None or mesh_ctx.pp_size == 1:
        return model
    if not hasattr(model, "config"):
        raise NotImplementedError("PP needs a stacked-layer causal LM")
    if schedule not in ("gpipe", "zero_bubble"):
        raise ValueError(
            f"unknown pp schedule {schedule!r}; expected gpipe|zero_bubble"
        )
    if schedule == "zero_bubble" and getattr(model.backend, "fp8", False):
        # split_dot has no fp8 path — the B-pass recompute would silently
        # diverge from the fp8 forward
        _log_zb_fallback("fp8 dense projections")
        schedule = "gpipe"
    cfg = model.config
    if getattr(cfg, "moe", None) is not None:
        if not hasattr(model, "pp_attn_block"):
            raise NotImplementedError(
                f"PP for {type(model).__name__} not supported yet (per-layer "
                "static attention windows don't slice across pp ranks)"
            )
        n_moe = cfg.num_layers - cfg.moe.num_dense_layers
        if n_moe % mesh_ctx.pp_size != 0:
            raise ValueError(
                f"moe layer count {n_moe} must divide pp={mesh_ctx.pp_size}"
            )
        if (
            schedule == "zero_bubble"
            and model.backend.experts in ("a2a", "a2a_fused")
            and mesh_ctx.ep_size > 1
            and mesh_ctx.tp_size == 1
        ):
            # this config takes the ep-manual a2a exchange inside the stages,
            # which the hand-written reverse wavefront does not compose with;
            # decide the fallback HERE so pipeline_info (and the reported
            # bubble fraction) describe the schedule that actually runs.
            # (Slightly conservative: mb % ep != 0 would have dropped to the
            # ragged path where zero_bubble works — acceptable, logged.)
            _log_zb_fallback("ep-manual a2a token exchange")
            schedule = "gpipe"
        if schedule == "zero_bubble":
            from automodel_tpu.models.llama.model import attention_block

            if model.pp_attn_block is not attention_block:
                # the B/W split reaches weights through the zb_tap hook in
                # llama's _proj; an attention block doing raw kernel matmuls
                # (deepseek MLA) would leave the deferred kernels' taps
                # unconsumed and their gradients EXACTLY ZERO — refuse the
                # schedule rather than silently freeze those weights
                _log_zb_fallback(
                    "attention block without split_dot tap support (MLA)"
                )
                schedule = "gpipe"
        return PipelinedMoECausalLM(
            model, mesh_ctx, n_microbatches, schedule, zb_queue
        )
    from automodel_tpu.models.llama.model import LlamaForCausalLM

    if not isinstance(model, LlamaForCausalLM):
        # e.g. gemma: homogeneous llama layers is what the dense stage runs
        raise NotImplementedError(
            f"PP for {type(model).__name__} not supported yet (the dense "
            "pipeline stage runs llama-family decoder layers)"
        )
    if cfg.num_layers % mesh_ctx.pp_size != 0:
        raise ValueError(
            f"num_layers {cfg.num_layers} must divide pp={mesh_ctx.pp_size}"
        )
    return PipelinedCausalLM(
        model, mesh_ctx, n_microbatches, schedule, zb_queue
    )
