"""Zero-bubble pipeline schedule: B/W-split backward with deferred weight-grads.

Reference blueprint: the ZBVZeroBubble schedule family (functional.py:490-560;
"Zero Bubble Pipeline Parallelism", Qi et al.) splits each stage's backward
into B — activation gradients, on the critical inter-stage path — and W —
weight gradients, computable from saved (input, output-cotangent) pairs at any
later tick. In this repo's synchronous-tick SPMD formulation (parallel/pp.py:
one lax.scan over global ticks, stages hop via ppermute) per-rank asynchronous
slots don't exist, so the schedule takes the synchronous-tick form:

  fwd wave   (M+pp-1 ticks, cost F each)   — unchanged GPipe wavefront
  B wave     (M+pp-1 ticks, cost ~2F each) — hand-written reverse wavefront:
             per tick, recompute the stage forward and propagate ONLY the
             activation cotangent dx through ppermute; the per-matmul
             (x, dy) pairs needed for weight grads are exported into a
             deferral buffer instead of being contracted on the tick
  W flush    (M slots of flat work, cost ~F each) — all ranks contract their
             own stage's deferred dW chunks with NO pipeline dependency,
             i.e. zero bubble for the W third of the backward

Per-rank idle drops from 3(pp-1) tick-equivalents (GPipe: fwd + AD backward
at 3F/tick under remat) to 3(pp-1) out of a larger denominator with the W
work bubble-free:   bubble = 3(pp-1) / (4M + 3(pp-1))  <  (pp-1)/(M+pp-1)
for every M — strictly below the GPipe law (analytic model in
utils/flops_utils.pipeline_bubble_fraction; measured in docs/history/PROFILE_PP_r06.md).

Mechanism for the B/W split without hand-writing the transformer backward:
``split_dot`` is a custom_vjp matmul whose backward returns dx immediately,
a symbolically-zero weight cotangent, and EXPORTS (x, dy) as the cotangents
of two zero-valued "tap" primal inputs grafted into the layer param tree
(``zb_tap`` keys, consumed by models/llama/model._proj). jax.vjp over the
tapped stage therefore computes exactly B (the heavy dW contractions are
dead and DCE'd) while the tap cotangents deliver the stash the deferred W
contraction needs — no recompute in the W phase.

Deferral-queue bound: the stash for one microbatch is ~the no-remat
activation footprint of one stage. ``zb_queue`` bounds how many microbatches
may be in flight: a full queue consumes its oldest entry ON the B tick
(degrading that tick toward the combined GPipe cost but capping memory at
queue_size stashes); zb_queue=None defers everything to the flat flush.

Grad-accumulation contract (training/train_step.py): W contributions land
out of microbatch order inside this file's backward — summed here in fp32 —
and the COMPLETE gradient (B-computed small params + W-computed kernels)
is what leaves the custom_vjp, so the train step's fp32 global-norm clip
only ever sees gradients with all W chunks landed.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

# -- B/W split matmul ---------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def split_dot(export_x: bool, x, w, xtap, ytap):
    """``x @ w`` whose backward computes ONLY dx; dw is deferred.

    ``xtap``/``ytap`` are zero-valued primal inputs shaped like x and y (or
    shape [0] for a shared-x site, see SITE specs): their cotangents are
    DEFINED to be (x, dy) — the pair the deferred weight-grad contraction
    dW = x^T dy needs. Taking jax.vjp w.r.t. the taps exports the stash
    from inside an AD-generated backward without any side channel.
    """
    del xtap, ytap
    return x @ w.astype(x.dtype)


def _split_dot_fwd(export_x, x, w, xtap, ytap):
    del xtap, ytap
    return x @ w.astype(x.dtype), (x, w)


def _split_dot_bwd(export_x, res, dy):
    x, w = res
    dy = dy.astype(x.dtype)
    dx = dy @ w.astype(dy.dtype).T
    dw = jnp.zeros_like(w)  # deferred to the W phase; dead → DCE'd
    dxtap = x if export_x else jnp.zeros((0,), x.dtype)
    return dx, dw, dxtap, dy


split_dot.defvjp(_split_dot_fwd, _split_dot_bwd)


# -- site specs ---------------------------------------------------------------
# Site path (inside one layer's param tree) → site to borrow the input-side
# tap from (q/k/v and gate/up consume the same normed activation — one
# export serves all three), or None to export its own.

DENSE_SITES: dict[tuple, Optional[tuple]] = {
    ("attn", "q_proj"): None,
    ("attn", "k_proj"): ("attn", "q_proj"),
    ("attn", "v_proj"): ("attn", "q_proj"),
    ("attn", "o_proj"): None,
    ("mlp", "gate_proj"): None,
    ("mlp", "up_proj"): ("mlp", "gate_proj"),
    ("mlp", "down_proj"): None,
}

# MoE stages defer the attention projections only: expert/router weight
# grads stay on the B tick (the grouped-matmul backends carry their own
# custom_vjp; threading taps through them is future work) — correctness is
# unaffected, the bubble win is proportional to the attention share.
ATTN_SITES: dict[tuple, Optional[tuple]] = {
    k: v for k, v in DENSE_SITES.items() if k[0] == "attn"
}


# -- tree surgery -------------------------------------------------------------


def _copy_tree(d):
    if isinstance(d, dict):
        return {k: _copy_tree(v) for k, v in d.items()}
    return d


def _node(tree: Any, path: tuple) -> Optional[dict]:
    node = tree
    for k in path:
        if not isinstance(node, dict) or k not in node:
            return None
        node = node[k]
    return node if isinstance(node, dict) else None


def resolve_sites(stage_params: Any, sites: dict) -> dict:
    """Filter the site spec to sites actually deferrable in this tree:
    present, a plain stacked [Lp, Din, Dout] kernel (not NF4-packed), and
    no activation-side LoRA riding the projection. A site whose x-source
    got filtered exports its own input instead."""
    elig = {}
    for site, share in sites.items():
        node = _node(stage_params, site)
        if node is None:
            continue
        k = node.get("kernel")
        if not hasattr(k, "ndim") or k.ndim != 3:
            continue
        if "lora_A" in node or "lora_drop_seed" in node:
            continue
        elig[site] = share
    return {
        s: (sh if sh in elig and elig[sh] is None else None)
        for s, sh in elig.items()
    }


def graft_taps(stage_params: Any, resolved: dict, mb: int, S: int, dtype):
    """→ (tapped, heavy): ``tapped`` is the stage tree with each deferred
    site's kernel REMOVED (so the B-pass vjp never accumulates its zero
    cotangent over the layer scan) and a ``zb_tap`` zeros pair inserted;
    ``heavy`` holds the removed stacked kernels, closed over by the stage
    body and re-inserted per layer."""
    tapped = _copy_tree(stage_params)
    heavy = {}
    for site, share in resolved.items():
        node = _node(tapped, site)
        kern = node.pop("kernel")
        heavy[site] = kern
        Lp, Din, Dout = kern.shape
        xtap = (
            jnp.zeros((Lp, mb, S, Din), dtype)
            if share is None
            else jnp.zeros((Lp, 0), dtype)
        )
        node["zb_tap"] = (xtap, jnp.zeros((Lp, mb, S, Dout), dtype))
    return tapped, heavy


def insert_heavy(lp: dict, heavy: dict, i) -> dict:
    """Per-layer: put layer i's slice of each removed kernel back so the
    layer body (which reads p["kernel"]) runs unchanged."""
    lp = _copy_tree(lp)
    for site, kern in heavy.items():
        _node(lp, site)["kernel"] = jax.lax.dynamic_index_in_dim(
            kern, i, 0, keepdims=False
        )
    return lp


def split_taps(d_tapped: Any, resolved: dict):
    """Cotangent tree of the tapped stage → (stash {site: (x, dy)}, rest)."""
    rest = _copy_tree(d_tapped)
    stash = {}
    for site in resolved:
        stash[site] = _node(rest, site).pop("zb_tap")
    return stash, rest


def insert_kernel_grads(d_rest: Any, dW: dict) -> Any:
    out = _copy_tree(d_rest)
    for site, g in dW.items():
        _node(out, site)["kernel"] = g
    return out


class FloatPartition:
    """Static float/int split of a pytree (vjp can only differentiate float
    leaves; int leaves — segment ids, LoRA seed data — are closed over and
    get float0 cotangents)."""

    def __init__(self, tree: Any):
        leaves, self.treedef = jax.tree.flatten(tree)
        self.is_f = [jnp.issubdtype(l.dtype, jnp.floating) for l in leaves]
        self.shapes = [jnp.shape(l) for l in leaves]

    def floats(self, tree: Any) -> list:
        ls = jax.tree.leaves(tree)
        return [l for l, m in zip(ls, self.is_f) if m]

    def ints(self, tree: Any) -> list:
        ls = jax.tree.leaves(tree)
        return [l for l, m in zip(ls, self.is_f) if not m]

    def join(self, floats: list, ints: list) -> Any:
        fi, ii, out = iter(floats), iter(ints), []
        for m in self.is_f:
            out.append(next(fi) if m else next(ii))
        return jax.tree.unflatten(self.treedef, out)

    def cotangent(self, float_cts: list) -> Any:
        """Full cotangent tree: float leaves from ``float_cts``, float0
        zeros for int leaves (the custom_vjp contract for int primals)."""
        from jax import dtypes as jdt

        fi, out = iter(float_cts), []
        for m, shp in zip(self.is_f, self.shapes):
            out.append(next(fi) if m else np.zeros(shp, jdt.float0))
        return jax.tree.unflatten(self.treedef, out)


# -- deferred-W contraction ---------------------------------------------------


def _dw_contract(x, dy):
    """(x [..., Lp, mb, S, Din], dy [..., Lp, mb, S, Dout]) → [Lp, Din, Dout]
    in fp32 — the deferred weight-grad chunk. Leading axes beyond the layer
    axis (queue slots) are contracted too."""
    eq = "lbsi,lbso->lio" if x.ndim == 4 else "qlbsi,qlbso->lio"
    return jnp.einsum(eq, x, dy, preferred_element_type=jnp.float32)


def accumulate_dw(dW_acc: dict, stash: dict, resolved: dict) -> dict:
    out = dict(dW_acc)
    for site, share in resolved.items():
        xv = stash[share or site][0]
        dyv = stash[site][1]
        out[site] = out[site] + _dw_contract(xv, dyv)
    return out


# -- the pipeline -------------------------------------------------------------


def zb_spmd_pipeline(
    layer_fn: Callable,  # (h, lp, aux_slice) -> (h, stage_aux_leaf | None)
    stage_params: Any,   # pytree, leaves [L, ...] with L divisible by pp
    inputs: jnp.ndarray,  # [M, mb, S, D] microbatched activations
    aux: Any,            # pytree of [M, ...] per-microbatch side inputs
    mesh_ctx: Any,
    *,
    sites: dict,
    has_stage_aux: bool = False,
    zb_queue: Optional[int] = None,
    remat: str = "none",
) -> Any:
    """Zero-bubble drop-in for ``pp.spmd_pipeline`` (pp > 1, ep-auto only).

    Same contract: returns the last stage's outputs [M, mb, S, D] (plus the
    microbatch-summed stage aux, leaves [pp, L/pp, ...], when
    ``has_stage_aux``). Forward is the identical GPipe wavefront; the whole
    backward is hand-scheduled inside a custom_vjp (module docstring).
    """
    from automodel_tpu.models.common.stacking import remat_wrap

    mesh = mesh_ctx.mesh
    pp = mesh.shape["pp"]
    M, mb, S = inputs.shape[0], inputs.shape[1], inputs.shape[2]
    cd = inputs.dtype
    n_ticks = M + pp - 1
    Q = M if zb_queue is None else max(1, min(int(zb_queue), M))
    bounded = Q < M

    param_specs = jax.tree.map(lambda _: P("pp"), stage_params)
    data_spec = P()
    aux_part = FloatPartition(aux)
    sp_part = FloatPartition(stage_params)

    def stage_fwd(sp, x, a):
        def body(h, lp):
            return layer_fn(h, lp, a)

        return jax.lax.scan(body, x, sp)

    # ---- forward wavefront (custom_vjp primal; also saves per-tick stage
    # inputs — the 1F1B-equivalent stage-boundary residuals) ----------------
    def fwd_fn(sp, inp, auxb):
        p = jax.lax.axis_index("pp")
        state0 = jnp.zeros(inp.shape[1:], cd)
        if has_stage_aux:
            a0 = jax.tree.map(lambda b: b[0], auxb)
            _, aux_shape = jax.eval_shape(stage_fwd, sp, state0, a0)
            acc0 = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), aux_shape)
        else:
            acc0 = None

        def tick(carry, t):
            state, acc = carry
            in_idx = jnp.clip(t, 0, M - 1)
            mb_idx = jnp.clip(t - p, 0, M - 1)
            x_in = jnp.where(p == 0, inp[in_idx].astype(cd), state)
            a = jax.tree.map(lambda b: b[mb_idx], auxb)
            y, saux = stage_fwd(sp, x_in, a)
            if has_stage_aux:
                valid = jnp.logical_and(t >= p, t < p + M)
                acc = jax.tree.map(
                    lambda A, s_: A + jnp.where(valid, s_.astype(jnp.float32), 0.0),
                    acc,
                    saux,
                )
            state_next = jax.lax.ppermute(
                y, "pp", [(i, (i + 1) % pp) for i in range(pp)]
            )
            return (state_next, acc), (y, x_in)

        (_, acc), (ys, xs) = jax.lax.scan(tick, (state0, acc0), jnp.arange(n_ticks))
        ys = ys[pp - 1 :][None]
        xs = xs[None]
        if has_stage_aux:
            return ys, xs, jax.tree.map(lambda A: A[None], acc)
        return ys, xs

    def run_fwd(sp, inp, auxb):
        out_specs = (P("pp"), P("pp"), P("pp")) if has_stage_aux else (P("pp"), P("pp"))
        return shard_map(
            fwd_fn,
            mesh=mesh,
            in_specs=(param_specs, data_spec, data_spec),
            out_specs=out_specs,
            axis_names={"pp"},
            check_vma=False,
        )(sp, inp, auxb)

    # ---- hand-scheduled backward: B wave + bounded deferral + W flush -----
    def bwd_fn(sp, inp, auxb, xs, d_ys, d_acc):
        p = jax.lax.axis_index("pp")
        off = (pp - 1) - p
        xs = xs[0]  # [n_ticks, mb, S, D] — this rank's saved stage inputs
        d_acc_l = (
            jax.tree.map(lambda a: a[0], d_acc) if has_stage_aux else None
        )
        resolved = resolve_sites(sp, sites)
        tapped, heavy = graft_taps(sp, resolved, mb, S, cd)
        tp_part = FloatPartition(tapped)
        # int leaves (e.g. LoRA seed data) are closed over for the primal
        # and get ZERO fillers on the cotangent side
        tp_ints = tp_part.ints(tapped)
        tp_int_zeros = [jnp.zeros_like(l) for l in tp_ints]
        stripped = split_taps(tapped, resolved)[1]  # structure/dtype reference

        def btick(carry, s):
            dstate, small_acc, dW_acc, buf = carry
            j = s - off
            jc = jnp.clip(j, 0, M - 1)
            valid = jnp.logical_and(j >= 0, j < M)
            # my stage-output cotangent for microbatch jc: the loss feeds
            # the last rank directly; earlier ranks receive the next
            # stage's dx from the reverse ppermute (timing: rank p+1
            # computed mb jc's dx exactly one tick ago)
            dy = jnp.where(p == pp - 1, d_ys[jc].astype(cd), dstate)
            x_in = xs[jnp.clip(jc + p, 0, n_ticks - 1)]
            a_sl = jax.tree.map(lambda b: b[jc], auxb)
            a_ints = aux_part.ints(a_sl)

            def f(tp_floats, x, a_floats):
                tp = tp_part.join(tp_floats, tp_ints)
                a_full = aux_part.join(a_floats, a_ints)

                def body(carry2, lp):
                    h, i = carry2
                    h2, yaux = layer_fn(h, insert_heavy(lp, heavy, i), a_full)
                    return (h2, i + 1), yaux

                (h_out, _), yauxs = jax.lax.scan(
                    remat_wrap(body, remat), (x, jnp.int32(0)), tp
                )
                return (h_out, yauxs) if has_stage_aux else h_out

            _, vjp_fn = jax.vjp(
                f, tp_part.floats(tapped), x_in, aux_part.floats(a_sl)
            )
            if has_stage_aux:
                seed_aux = jax.tree.map(
                    lambda g: jnp.where(valid, g, 0.0), d_acc_l
                )
                d_tpf, dx, d_af = vjp_fn((dy, seed_aux))
            else:
                d_tpf, dx, d_af = vjp_fn(dy)
            d_tapped = tp_part.join(d_tpf, tp_int_zeros)
            stash, d_rest = split_taps(d_tapped, resolved)
            small_acc = jax.tree.map(
                lambda A, g: A + jnp.where(valid, g, 0).astype(jnp.float32),
                small_acc,
                d_rest,
            )
            # deferral buffer: ring slot jc % Q. A full (bounded) queue
            # consumes its oldest entry on this tick — that W contraction
            # rides the B tick, trading bubble for the memory cap. Invalid
            # ticks neither consume nor overwrite (keep the old slot).
            slot = jc % Q
            popped = jax.tree.map(
                lambda b: jax.lax.dynamic_index_in_dim(b, slot, 0, keepdims=False),
                buf,
            )
            if bounded:
                dW_acc = accumulate_dw(
                    dW_acc,
                    jax.tree.map(lambda g: jnp.where(valid, g, 0), popped),
                    resolved,
                )
            new_slot = jax.tree.map(
                lambda new, old: jnp.where(valid, new.astype(old.dtype), old),
                stash,
                popped,
            )
            buf = jax.tree.map(
                lambda b, v: jax.lax.dynamic_update_index_in_dim(b, v, slot, 0),
                buf,
                new_slot,
            )
            dstate_next = jax.lax.ppermute(
                dx, "pp", [(i, (i - 1) % pp) for i in range(pp)]
            )
            return (dstate_next, small_acc, dW_acc, buf), (dx, d_af)

        small0 = jax.tree.map(
            lambda l: jnp.zeros(l.shape, jnp.float32), stripped
        )
        dW0 = {
            site: jnp.zeros(heavy[site].shape, jnp.float32) for site in heavy
        }
        buf0 = jax.tree.map(
            lambda l: jnp.zeros((Q, *l.shape), l.dtype),
            split_taps(tapped, resolved)[0],
        )
        carry0 = (jnp.zeros(inputs.shape[1:], cd), small0, dW0, buf0)
        (_, small_acc, dW_acc, buf), (dxs, d_afs) = jax.lax.scan(
            btick, carry0, jnp.arange(n_ticks)
        )
        # ---- W flush: flat, bubble-free — every rank contracts its own
        # stage's remaining deferred chunks, no inter-stage dependency ----
        dW_acc = accumulate_dw(dW_acc, buf, resolved)
        d_small = jax.tree.map(
            lambda A, ref: A.astype(ref.dtype), small_acc, stripped
        )
        d_sp = insert_kernel_grads(
            d_small,
            {s: dW_acc[s].astype(heavy[s].dtype) for s in dW_acc},
        )
        # only float leaves leave the region; int leaves (if any) get
        # float0 cotangents assembled at the custom_vjp boundary
        d_sp = sp_part.floats(d_sp)
        # per-microbatch rows of this rank's dx / aux cotangents live at
        # ticks j + off; rank 0's dx rows ARE the input cotangent. The
        # replicated-input transpose is a psum — same f32 collective the
        # AD path pays (pp.py:111-115).
        idx = off + jnp.arange(M)
        d_inp = jax.lax.psum(
            jnp.where(p == 0, dxs[idx], 0).astype(jnp.float32), "pp"
        )
        d_aux_f = [
            jax.lax.psum(t[idx].astype(jnp.float32), "pp") for t in d_afs
        ]
        return d_sp, d_inp, d_aux_f

    def run_bwd(sp, inp, auxb, xs, d_ys, d_acc):
        n_aux_f = sum(aux_part.is_f)
        sp_f_specs = [
            s for s, m in zip(jax.tree.leaves(param_specs), sp_part.is_f) if m
        ]
        d_sp_f, d_inp, d_aux_f = shard_map(
            bwd_fn,
            mesh=mesh,
            in_specs=(
                param_specs, data_spec, data_spec, P("pp"), data_spec,
                (P("pp") if has_stage_aux else data_spec),
            ),
            out_specs=(sp_f_specs, P(), [P()] * n_aux_f),
            axis_names={"pp"},
            check_vma=False,
        )(sp, inp, auxb, xs, d_ys, d_acc)
        return d_sp_f, d_inp, d_aux_f

    @jax.custom_vjp
    def pipe(sp, inp, auxb):
        out = run_fwd(sp, inp, auxb)
        if has_stage_aux:
            ys, _, acc = out
            return ys[pp - 1], acc
        ys, _ = out
        return ys[pp - 1]

    def pipe_fwd(sp, inp, auxb):
        out = run_fwd(sp, inp, auxb)
        if has_stage_aux:
            ys, xs, acc = out
            return (ys[pp - 1], acc), (sp, inp, auxb, xs)
        ys, xs = out
        return ys[pp - 1], (sp, inp, auxb, xs)

    def pipe_bwd(res, ct):
        sp, inp, auxb, xs = res
        if has_stage_aux:
            d_ys, d_acc = ct
        else:
            d_ys, d_acc = ct, jnp.zeros((), jnp.float32)
        d_sp_f, d_inp, d_aux_f = run_bwd(sp, inp, auxb, xs, d_ys, d_acc)
        # cotangent dtypes: float leaves cast back to primal dtype; int
        # leaves (segment ids, seed data) get float0 per the vjp contract
        aux_templates = [
            l for l, m in zip(jax.tree.leaves(auxb), aux_part.is_f) if m
        ]
        d_auxb = aux_part.cotangent(
            [g.astype(t.dtype) for g, t in zip(d_aux_f, aux_templates)]
        )
        d_sp = sp_part.cotangent(d_sp_f)
        return d_sp, d_inp, d_auxb

    pipe.defvjp(pipe_fwd, pipe_bwd)

    out = pipe(stage_params, inputs.astype(jnp.float32), aux)
    return out
