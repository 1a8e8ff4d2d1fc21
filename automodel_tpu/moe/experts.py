"""Expert computation backends.

Parity: reference `GroupedExperts*` (components/moe/experts.py:158,478,763,
946) — four CUDA-era backends (loop/grouped_mm, FP8, DeepEP, TE). TPU-native
backends:

- ``dense``  — every expert processes every token, combine by routing weight
  (einsum). O(E/K) extra FLOPs; numerics reference + tiny-model tests.
- ``gspmd``  — capacity-based dispatch/combine einsums (the GSPMD MoE
  formulation proven on TPU pods: Switch/GLaM). Expert dim sharded on the
  ``ep`` mesh axis; XLA inserts the all-to-all that DeepEP hand-codes on
  GPUs (reference fused_a2a.py → here compiler-scheduled ICI collectives).
  Tokens over capacity are dropped (capacity_factor; the aux-free bias and
  aux loss keep loads balanced so drops stay rare).
- ``ragged`` — dropless sort + `jax.lax.ragged_dot` grouped matmul
  (megablocks-style). Best single-slice path; on a mesh of several devices
  it runs per device inside the ``a2a`` backend's shard_map (GSPMD cannot
  partition the Pallas grouped matmul).
- ``a2a``    — the DeepEP-equivalent token-exchange dispatcher (reference
  token_dispatcher.py:339, fused_a2a.py:102,201): explicit shard_map over the
  ``ep`` mesh axis with `lax.all_to_all` dispatch/combine around a local
  `ragged_dot` grouped matmul. Dropless by construction at the default
  capacity (per-peer worst case); `a2a_capacity_factor` bounds buffers for
  perf runs (over-capacity picks contribute zero, like the reference's
  bounded dispatch buffers). TP is handled inside the manual region: with
  tp > 1 gate/up are pre-split so their tp shards align (with one tp shard
  the fused kernel takes the stored tensor whole), down-proj partial sums
  ride the combine all_to_all and a single psum("tp") happens at [T, D].

All backends take fused gate_up weights [E, D, 2I] and down [E, I, D];
SwiGLU-family activation.

Every backend runs inside moe_block's ``moe`` scope and names its three
parts (utils/profiler.SCOPES): ``dispatch`` (sort, permute, exchange, the
weight casts, and the gate/up weight split where one is still needed),
``experts`` (the grouped matmuls or the fused kernel), ``combine``
(unpermute, weighted sum).

The fused-kernel backends hand ``gate_up`` to ops/fused_expert_mlp WHOLE:
its kernels block the gate and the up half out of the stored [E, D, 2I]
tensor by index map, so no per-call copy of the expert weights is made
(`_fused_gate_up`). A copy remains only where no block index can express the
read: gpt-oss's column interleave, tp > 1 (the halves' shards must align),
and widths off the 128 grid, which the kernels pad anyway.
"""

from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp

from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.gate import GateOutput
from automodel_tpu.ops.fp8 import fp8_qdq_blockwise, fp8_qdq_tensor
from automodel_tpu.ops.grouped_matmul import ragged_dot
from automodel_tpu.ops.platform_check import kernel_axes

Act = Callable[[jnp.ndarray], jnp.ndarray]


def _split_gate_up(gu: jnp.ndarray, interleaved: bool) -> tuple[jnp.ndarray, jnp.ndarray]:
    if interleaved:  # gpt-oss checkpoints interleave gate/up on the last dim
        return gu[..., ::2], gu[..., 1::2]
    return jnp.split(gu, 2, axis=-1)


def _fused_gate_up(
    gate_up: jnp.ndarray, cfg: MoEConfig, whole: bool = True
) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """(gate, up) operands of ops.fused_expert_mlp from the stored fused
    weight: ``(gate_up, None)`` — read in place, no copy — unless the
    columns are interleaved or the caller needs two arrays (``whole=False``:
    tp shards). Unaligned widths are the op's own fallback."""
    if whole and not cfg.interleaved_gate_up:
        return gate_up, None
    return _split_gate_up(gate_up, cfg.interleaved_gate_up)


def _ffn(
    h: jnp.ndarray,
    w: dict,
    act2: Act,
    interleaved: bool = False,
    gated: bool = True,
) -> jnp.ndarray:
    """h: [..., D] → [..., D] through one expert's weights dict
    {gate_up [D,2I] (or [D,I] non-gated), down [I,D], (…biases)}.
    `act2(gate, up)` is the two-argument gated activation; non-gated experts
    (nemotron relu2) skip the split and act2 ignores its second operand."""
    gu = h @ w["gate_up"].astype(h.dtype)
    if "gate_up_bias" in w:
        gu = gu + w["gate_up_bias"].astype(h.dtype)
    g, u = _split_gate_up(gu, interleaved) if gated else (gu, gu)
    out = act2(g, u) @ w["down"].astype(h.dtype)
    if "down_bias" in w:
        out = out + w["down_bias"].astype(h.dtype)
    return out


def dense_experts(
    x: jnp.ndarray,  # [T, D]
    gate_out: GateOutput,
    weights: dict,  # leaves with leading expert dim E
    cfg: MoEConfig,
    act2: Act,
) -> jnp.ndarray:
    E = cfg.num_experts
    with jax.named_scope("experts"):
        # combine weights [T, E]
        cw = jnp.zeros((x.shape[0], E), x.dtype)
        cw = cw.at[
            jnp.arange(x.shape[0])[:, None], gate_out.topk_idx
        ].add(gate_out.topk_weights)
        ys = jax.vmap(
            lambda w: _ffn(x, w, act2, cfg.interleaved_gate_up, cfg.gated),
            in_axes=0, out_axes=0,
        )(weights)  # [E, T, D]
        return jnp.einsum("etd,te->td", ys, cw)


def gspmd_experts(
    x: jnp.ndarray,  # [B, S, D] — batch groups kept for sharded dispatch
    gate_out: GateOutput,  # computed over T = B*S flattened tokens
    weights: dict,
    cfg: MoEConfig,
    act2: Act,
    constrain: Callable = lambda a, spec: a,
) -> jnp.ndarray:
    """Capacity-based dispatch/combine (GSPMD MoE). Returns [B, S, D]."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    cap = max(K, int(math.ceil(S * K / E * cfg.capacity_factor)))

    with jax.named_scope("dispatch"):
        idx = gate_out.topk_idx.reshape(B, S, K)
        w = gate_out.topk_weights.reshape(B, S, K).astype(jnp.float32)

        # position of each (token, k) pick inside its expert's buffer, in
        # token-major priority order (reference dispatch order)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [B,S,K,E]
        flat = onehot.reshape(B, S * K, E)
        pos = (jnp.cumsum(flat, axis=1) - flat).reshape(B, S, K, E)  # [B,S,K,E]
        pos = jnp.einsum("bske,bske->bsk", pos, onehot).astype(jnp.int32)
        keep = pos < cap
        pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
        # dispatch/combine tensors [B, S, E, C]
        disp = jnp.einsum("bske,bskc->bsec", onehot, pos_oh)
        comb = jnp.einsum("bsk,bske,bskc->bsec", w, onehot, pos_oh)

        expert_in = jnp.einsum(
            "bsec,bsd->ebcd", disp, x.astype(jnp.float32)
        ).astype(x.dtype)
        expert_in = constrain(expert_in, ("expert", "expert_batch", None, None))
    with jax.named_scope("experts"):
        expert_out = jax.vmap(
            lambda h, w: _ffn(h, w, act2, cfg.interleaved_gate_up, cfg.gated)
        )(expert_in, weights)  # [E, B, C, D]
        expert_out = constrain(expert_out, ("expert", "expert_batch", None, None))
    with jax.named_scope("combine"):
        out = jnp.einsum(
            "bsec,ebcd->bsd", comb, expert_out.astype(jnp.float32)
        )
        return out.astype(x.dtype)


# every tag _name_ckpt gives; models/common/stacking.remat_wrap keeps them
# under remat='full_save_dispatch'
SORT_CHECKPOINT_NAMES = (
    "moe_sort_order", "moe_sort_inv", "moe_sort_order_inv", "moe_sort_inv2",
)


def _name_ckpt(x: jnp.ndarray, name: str) -> jnp.ndarray:
    """checkpoint_name tag: under remat='full_save_dispatch' these values
    are SAVED across the remat boundary (policy save_only_these_names), so
    the recompute pass skips re-argsorting the T·K picks."""
    from jax.ad_checkpoint import checkpoint_name

    assert name in SORT_CHECKPOINT_NAMES, name  # a tag no policy keeps
    return checkpoint_name(x, name)


def _float0_zero(a: jnp.ndarray):
    import numpy as np

    return np.zeros(a.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_take(x, order, inv, K):
    """xs[p] = x[order[p] // K] with a gather-only VJP.

    Autodiff's VJP of this gather is a scatter-add onto [T, D] — the single
    most expensive op in the old MoE step (XLA scatter runs ~4x slower than
    a gather at bench shape in a round-4 profile). Because ``order`` is a
    bijection over the T·K picks, dx[t] = Σ_k dxs[inv[t·K+k]] is a pure
    gather + K-fold dense sum instead. order/inv are explicit args (not a
    closure) so the function stays remat/checkpoint-safe."""
    return jnp.take(x, order // K, axis=0)


def _dispatch_take_fwd(x, order, inv, K):
    return _dispatch_take(x, order, inv, K), (order, inv, x.shape[0])


def _dispatch_take_bwd(K, res, dxs):
    order, inv, T = res
    dx = jnp.take(dxs, inv, axis=0).reshape(T, K, dxs.shape[-1]).sum(axis=1)
    return dx, _float0_zero(order), _float0_zero(inv)


_dispatch_take.defvjp(_dispatch_take_fwd, _dispatch_take_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _sorted_combine(ys, w, order, inv, K):
    """out[t] = Σ_k w[t,k] · ys[inv[t·K+k]] → [T, D] fp32.

    Replaces the fp32 ``.at[token_of].add`` scatter combine (~5ms/layer at
    bench shape) with an unsort GATHER + dense weighted K-fold sum (~1.5ms);
    the hand-written VJP keeps the backward scatter-free too (d_ys is a
    gather of dout rows scaled by the pick weight)."""
    T, D = w.shape[0], ys.shape[-1]
    yu = jnp.take(ys, inv, axis=0).reshape(T, K, D)
    return jnp.einsum(
        "tkd,tk->td", yu, w.astype(yu.dtype),
        preferred_element_type=jnp.float32,
    )


def _sorted_combine_fwd(ys, w, order, inv, K):
    return _sorted_combine(ys, w, order, inv, K), (ys, w, order, inv)


def _sorted_combine_bwd(K, res, dout):
    ys, w, order, inv = res
    T, D = w.shape[0], ys.shape[-1]
    # pick p came from token order[p]//K with weight wflat[order[p]]
    dys = (
        jnp.take(dout, order // K, axis=0)
        * jnp.take(w.reshape(-1), order)[:, None].astype(dout.dtype)
    ).astype(ys.dtype)
    yu = jnp.take(ys, inv, axis=0).reshape(T, K, D)
    dw = jnp.einsum("td,tkd->tk", dout, yu.astype(dout.dtype)).astype(w.dtype)
    return dys, dw, _float0_zero(order), _float0_zero(inv)


_sorted_combine.defvjp(_sorted_combine_fwd, _sorted_combine_bwd)


@jax.custom_vjp
def _perm_take(x, perm, inv):
    """y[i] = x[perm[i]] for a bijection ``perm`` with precomputed inverse
    ``inv`` — the VJP is the INVERSE gather (autodiff's transpose of a
    gather is an XLA scatter, ~4x slower at bench shape)."""
    return jnp.take(x, perm, axis=0)


def _perm_take_fwd(x, perm, inv):
    return jnp.take(x, perm, axis=0), (perm, inv)


def _perm_take_bwd(res, dy):
    perm, inv = res
    return jnp.take(dy, inv, axis=0), _float0_zero(perm), _float0_zero(inv)


_perm_take.defvjp(_perm_take_fwd, _perm_take_bwd)


@jax.custom_vjp
def _slot_pack(xs, src, dst, valid):
    """Peer-chunk send buffer as a GATHER: out[r] = xs[src[r]] where slot r
    is valid, else 0. Because picks arrive sorted by expert (hence
    peer-contiguous), slot r of peer p reads pick peer_off[p] + r%C — no
    ``.at[dst].set`` scatter in the forward. The VJP gathers by ``dst``
    (dropped picks map to the appended zero row): scatter-free both ways."""
    return jnp.where(valid[:, None], jnp.take(xs, src, axis=0), 0)


def _slot_pack_fwd(xs, src, dst, valid):
    return _slot_pack(xs, src, dst, valid), (src, dst, valid)


def _slot_pack_bwd(res, dy):
    src, dst, valid = res
    dxs = jnp.concatenate([dy, jnp.zeros((1, dy.shape[-1]), dy.dtype)])[dst]
    return dxs, _float0_zero(src), _float0_zero(dst), _float0_zero(valid)


_slot_pack.defvjp(_slot_pack_fwd, _slot_pack_bwd)


@jax.custom_vjp
def _slot_unpack(y, dst, src, valid):
    """Slots → picks: out[p] = y[dst[p]], with the sentinel dst (= num rows)
    reading an appended zero row (dropped picks contribute zero). VJP is the
    valid-masked gather by ``src`` — the exact transpose, scatter-free."""
    return jnp.concatenate([y, jnp.zeros((1, y.shape[-1]), y.dtype)])[dst]


def _slot_unpack_fwd(y, dst, src, valid):
    return _slot_unpack(y, dst, src, valid), (dst, src, valid)


def _slot_unpack_bwd(res, dp):
    dst, src, valid = res
    dy = jnp.where(valid[:, None], jnp.take(dp, src, axis=0), 0)
    return dy, _float0_zero(dst), _float0_zero(src), _float0_zero(valid)


_slot_unpack.defvjp(_slot_unpack_fwd, _slot_unpack_bwd)


def _ragged_operands(xs, weights: dict, fp8: bool):
    """(rows, gate_up, down) as the grouped matmuls take them: the weights in
    the rows' type, all three through the e4m3 QDQ when ``fp8``."""
    w_gu = weights["gate_up"].astype(xs.dtype)
    w_dn = weights["down"].astype(xs.dtype)
    if fp8:
        xs = fp8_qdq_tensor(xs)
        w_gu = fp8_qdq_blockwise(w_gu)
        w_dn = fp8_qdq_blockwise(w_dn)
    return xs, w_gu, w_dn


def _ragged_mlp(xs, w_gu, w_dn, weights, group_sizes, row_expert, cfg, act2,
                platform, fp8):
    """Rows sorted by expert -> their experts' MLP, two grouped matmuls."""
    gu = ragged_dot(xs, w_gu, group_sizes, platform=platform)
    if "gate_up_bias" in weights:
        gu = gu + weights["gate_up_bias"].astype(xs.dtype)[row_expert]
    g, u = _split_gate_up(gu, cfg.interleaved_gate_up) if cfg.gated else (gu, gu)
    h_mid = act2(g, u)
    if fp8:
        h_mid = fp8_qdq_tensor(h_mid)
    ys = ragged_dot(h_mid, w_dn, group_sizes, platform=platform)
    if "down_bias" in weights:
        ys = ys + weights["down_bias"].astype(xs.dtype)[row_expert]
    return ys


def _fused_operands(weights: dict, cfg: MoEConfig, dtype):
    """(gate, up, down, gate bias, up bias, down bias) as
    ops.fused_expert_mlp takes them, in the rows' type."""
    gw, uw = _fused_gate_up(weights["gate_up"], cfg)
    gw = gw.astype(dtype)
    uw = None if uw is None else uw.astype(dtype)
    w_dn = weights["down"].astype(dtype)
    gb = ub = db = None
    if "gate_up_bias" in weights:  # gpt-oss expert biases, per I-chunk in-kernel
        gb, ub = _split_gate_up(
            weights["gate_up_bias"], cfg.interleaved_gate_up
        )
        gb, ub = gb.astype(dtype), ub.astype(dtype)
    if "down_bias" in weights:
        db = weights["down_bias"].astype(dtype)
    return gw, uw, w_dn, gb, ub, db


def ragged_experts(
    x: jnp.ndarray,  # [T, D]
    gate_out: GateOutput,
    weights: dict,
    cfg: MoEConfig,
    act2: Act,
    platform: str | None = None,
    fp8: bool = False,
) -> jnp.ndarray:
    """Dropless sort + ragged_dot grouped matmul (single-slice hot path).

    Dispatch and combine are expressed as permutation GATHERS with custom
    VJPs (no XLA scatter anywhere in fwd or bwd — `_dispatch_take` says
    why); group sizes reuse the gate's expert_counts (an exact bincount of
    topk_idx, moe/gate.py).

    ``fp8``: e4m3 QDQ on both grouped-matmul operands — 128×128 blockwise
    scales on the expert weights, per-tensor dynamic on activations, STE
    grads (reference GroupedExpertsFP8, components/moe/experts.py:478)."""
    T, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    with jax.named_scope("dispatch"):
        flat_expert = gate_out.topk_idx.reshape(-1)  # [T*K]
        order = _name_ckpt(jnp.argsort(flat_expert), "moe_sort_order")  # stable
        inv = _name_ckpt(jnp.argsort(order), "moe_sort_inv")
        group_sizes = gate_out.expert_counts.astype(jnp.int32)
        sorted_expert = flat_expert[order]
        xs = _dispatch_take(x, order, inv, K)  # [T*K, D] sorted by expert
        xs, w_gu, w_dn = _ragged_operands(xs, weights, fp8)
    with jax.named_scope("experts"):
        ys = _ragged_mlp(xs, w_gu, w_dn, weights, group_sizes, sorted_expert,
                         cfg, act2, platform, fp8)

    with jax.named_scope("combine"):
        out = _sorted_combine(ys, gate_out.topk_weights, order, inv, K)
        return out.astype(x.dtype)


def _fused_act_of(cfg: MoEConfig, act_name: str, fp8: bool):
    """(act_kind, limit) for the fused expert-MLP kernel, or a loud error
    when the config is outside what the kernel implements (same envelope as
    ragged_fused: silu-gated swiglu / swiglu_oai, no fp8 QDQ in-kernel)."""
    if fp8:
        raise NotImplementedError(
            "fused expert MLP does not implement the fp8 QDQ path — drop "
            "fp8_experts or use the unfused backend"
        )
    if not cfg.gated:
        raise NotImplementedError(
            "fused expert MLP supports gated swiglu experts only"
        )
    if cfg.activation not in ("swiglu", "swiglu_oai") or (
        cfg.activation == "swiglu" and act_name != "silu"
    ):
        raise NotImplementedError(
            f"fused expert MLP implements silu-gated swiglu and swiglu_oai, "
            f"not activation={cfg.activation!r} with base act {act_name!r}"
        )
    return (
        "swiglu_oai" if cfg.activation == "swiglu_oai" else "swiglu",
        cfg.activation_limit,
    )


def a2a_experts(
    x: jnp.ndarray,  # [B, S, D]
    gate_out: GateOutput,
    weights: dict,
    cfg: MoEConfig,
    act2: Act,
    ctx,  # parallel.mesh.MeshContext | None
    platform: str | None = None,
    fp8: bool = False,
    fused_act=None,
) -> jnp.ndarray:
    """Dropless token-exchange EP dispatch (reference DeepEP dispatcher,
    token_dispatcher.py:339 + fused_a2a.py:102 → shard_map + lax.all_to_all).

    Per device block: sort (token, k) picks by expert id, all_to_all the
    per-peer chunks (static capacity C per peer), locally re-sort by expert
    and run `ragged_dot` grouped matmuls, then reverse the exchange and
    scatter-combine. `ragged_all_to_all` would avoid chunk padding but is not
    implemented by XLA:CPU (where the multichip tests run); the padded
    formulation is numerically identical and XLA lowers the all_to_all onto
    ICI either way.

    This is also how EVERY ragged backend meets a mesh of several devices:
    GSPMD cannot partition the grouped-matmul Mosaic calls, so `ragged` /
    `ragged_fused` route here too and the block runs inside the shard_map
    below — with ``ep == 1`` the exchange drops out and what is left is the
    single-slice ragged path on each device's own tokens.
    """
    B, S, D = x.shape
    if ctx is not None:
        platform = ctx.platform
    axes = kernel_axes(ctx)
    if axes is None or len(axes) < len(ctx.mesh.axis_names):
        # one device, or already inside a manual region (pipeline stage):
        # the operands are this device's block and the ragged path is
        # already dropless
        if fused_act is not None:
            return ragged_fused_experts(
                x.reshape(-1, D), gate_out, weights, cfg, act2,
                platform=platform,
            ).reshape(B, S, D)
        return ragged_experts(
            x.reshape(-1, D), gate_out, weights, cfg, act2, platform=platform,
            fp8=fp8,
        ).reshape(B, S, D)

    from automodel_tpu.parallel.mesh import MeshAxisName as A
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    ep = ctx.ep_size
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    if E % ep:
        raise ValueError(f"num_experts={E} must be divisible by ep={ep}")
    E_loc = E // ep
    b_div = mesh.shape[A.DP_REPLICATE] * mesh.shape[A.DP_SHARD] * mesh.shape[A.EP]
    s_div = mesh.shape[A.CP]
    if B % b_div or S % s_div:
        raise ValueError(
            f"batch {B}x{S} not divisible by data axes {b_div}x{s_div} for a2a dispatch"
        )
    Tl = (B // b_div) * (S // s_div)  # tokens per device block
    cap = Tl * min(K, E_loc)  # strict per-peer worst case → dropless
    if cfg.a2a_capacity_factor is not None:
        cap = min(cap, int(math.ceil(cfg.a2a_capacity_factor * Tl * K / ep)))
    C = -(-cap // 8) * 8  # chunk rows per peer, padded for TPU layouts

    with jax.named_scope("dispatch"):
        wd = _a2a_weights(
            weights, cfg,
            whole=fused_act is not None and mesh.shape[A.TP] == 1,
        )

    batch_axes = (A.DP_REPLICATE, A.DP_SHARD, A.EP)
    tok_spec = P(batch_axes, A.CP, None)
    w_specs = {
        "gw": P(A.EP, None, A.TP),
        "uw": P(A.EP, None, A.TP),
        "dw": P(A.EP, A.TP, None),
        "gb": P(A.EP, A.TP),
        "ub": P(A.EP, A.TP),
        "db": P(A.EP, None),
    }

    body = functools.partial(
        _a2a_body,
        ep=ep, ep_axis=A.EP, E=E, E_loc=E_loc, C=C, D=D, K=K,
        act2=act2, gated=cfg.gated, tp_axis=A.TP, platform=platform, fp8=fp8,
        fused_act=fused_act,
    )
    idx = gate_out.topk_idx.reshape(B, S, K)
    cw = gate_out.topk_weights.reshape(B, S, K)
    # check_vma=False (same stance as the ring in parallel/cp.py): the
    # region runs Pallas kernels whose interpret-mode discharge cannot
    # carry mixed vma (jax limitation), and custom-VJP cotangent psums are
    # then placed by the spec-based shard_map transpose. The in-kernel
    # _match_vma/_out_sds plumbing stays for vma-checked callers (pp).
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, {k: w_specs[k] for k in wd}),
        out_specs=tok_spec,
        check_vma=False,
    )(x, idx, cw, wd)


def _a2a_weights(weights: dict, cfg: MoEConfig, whole: bool = False) -> dict:
    """Per-shard weight dict for the a2a body. Gated experts pre-split
    gate/up so their tp shards align; ``whole`` (the fused kernel, one tp
    shard) carries the stored fused tensor as 'gw' with no 'uw' instead
    (`_fused_gate_up`). Non-gated (nemotron relu2) experts carry the single
    up projection as 'gw' and act2 ignores its second operand (same
    convention as _ffn)."""
    if cfg.gated:
        gw, uw = _fused_gate_up(weights["gate_up"], cfg, whole)
        wd = {"gw": gw, "dw": weights["down"]}
        if uw is not None:
            wd["uw"] = uw
        if "gate_up_bias" in weights:
            wd["gb"], wd["ub"] = _split_gate_up(
                weights["gate_up_bias"], cfg.interleaved_gate_up
            )
    else:
        wd = {"gw": weights["gate_up"], "dw": weights["down"]}
        if "gate_up_bias" in weights:
            wd["gb"] = weights["gate_up_bias"]
    if "down_bias" in weights:
        wd["db"] = weights["down_bias"]
    return wd


def _a2a_body(xb, idxb, cwb, wd, *, ep, ep_axis, E, E_loc, C, D, K, act2,
              gated=True, tp_axis=None, platform=None, fp8=False,
              fused_act=None):
    """The per-device token-exchange block. Requires `ep_axis` (and, when
    ``tp_axis`` is set, that axis too) to be MANUAL in the calling context —
    either a2a_experts' own shard_map, or a pipeline region already manual
    over {pp, ep} (parallel.pp ep_manual mode, tp_axis=None)."""
    with jax.named_scope("dispatch"):
        Bl, Sl, _ = xb.shape
        T = Bl * Sl
        xt = xb.reshape(T, D)
        flat = idxb.reshape(T * K)
        order = _name_ckpt(
            jnp.argsort(flat, stable=True), "moe_sort_order"
        )  # sorted-pick → original-pick
        inv_order = _name_ckpt(jnp.argsort(order), "moe_sort_order_inv")
        sorted_e = flat[order]
        # [T*K, D] picks sorted by global expert id; gather-only VJP (the K-fold
        # dense sum) instead of autodiff's scatter-add transpose
        xs = _dispatch_take(xt, order, inv_order, K)

        counts = jnp.bincount(flat, length=E).astype(jnp.int32)
        if ep == 1:
            # one expert shard: the sorted picks are already grouped by local
            # expert and nothing is dropped — no exchange, no second sort
            xs2, sid, gsz = xs, sorted_e, counts
        else:
            peer_counts = counts.reshape(ep, E_loc).sum(-1)
            peer_off = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), jnp.cumsum(peer_counts)[:-1]]
            )
            peer_of = sorted_e // E_loc
            pos_in_peer = jnp.arange(T * K, dtype=jnp.int32) - peer_off[peer_of]
            keep = pos_in_peer < C  # over-capacity picks drop (zero contribution)
            dst = jnp.where(keep, peer_of * C + pos_in_peer, ep * C)
            # slot r of peer p holds pick peer_off[p] + r%C (picks are sorted,
            # hence peer-contiguous) — the send buffer is a gather, not an
            # .at[].set
            slot = jnp.arange(ep * C, dtype=jnp.int32)
            slot_c = slot % C
            slot_valid = slot_c < peer_counts[slot // C]
            src = jnp.minimum(peer_off[slot // C] + slot_c, T * K - 1)

            send_x = _slot_pack(xs, src, dst, slot_valid)
            send_id = jnp.where(slot_valid, sorted_e[src] % E_loc, E_loc)
            a2a = lambda a: jax.lax.all_to_all(
                a, ep_axis, split_axis=0, concat_axis=0, tiled=True
            )
            recv_x, recv_id = a2a(send_x), a2a(send_id)  # [ep*C, ...] by sender

            order2 = _name_ckpt(
                jnp.argsort(recv_id, stable=True), "moe_sort_inv"
            )  # sentinel E_loc sorts last
            inv_order2 = _name_ckpt(jnp.argsort(order2), "moe_sort_inv2")
            xs2 = _perm_take(recv_x, order2, inv_order2)
            sid = jnp.minimum(recv_id[order2], E_loc - 1)
            gsz = jnp.bincount(recv_id, length=E_loc).astype(jnp.int32)  # sentinel drops

        w_g = wd["gw"].astype(xs2.dtype)
        w_d = wd["dw"].astype(xs2.dtype)
    with jax.named_scope("experts"):
        if fused_act is not None:
            # one-kernel local expert MLP (ops/fused_expert_mlp): the [rows, 2I]
            # gate_up output and the [rows, I] activation never touch HBM —
            # the same win the single-chip ragged_fused backend gets, on the
            # post-exchange rows. The down bias stays OUTSIDE the kernel when
            # tp shards the experts (it must land on one tp shard only).
            act_kind, limit = fused_act
            from automodel_tpu.ops.fused_expert_mlp import fused_expert_mlp

            # no 'uw': 'gw' is the fused [E_loc, D, 2I] weight, read in place
            w_u = wd["uw"].astype(xs2.dtype) if "uw" in wd else None
            gb = wd["gb"].astype(xs2.dtype) if "gb" in wd else None
            ub = wd["ub"].astype(xs2.dtype) if "ub" in wd else None
            db = wd.get("db")
            db_in_kernel = db if tp_axis is None else None
            y = fused_expert_mlp(
                xs2, w_g, w_u, w_d, gsz,
                gb, ub,
                None if db_in_kernel is None else db_in_kernel.astype(xs2.dtype),
                act_kind, limit, platform, None,
            )
            if db is not None and tp_axis is not None:
                y = y + jnp.where(
                    jax.lax.axis_index(tp_axis) == 0, db.astype(y.dtype)[sid], 0.0
                )
        else:
            if fp8:
                xs2 = fp8_qdq_tensor(xs2)
                w_g, w_d = fp8_qdq_blockwise(w_g), fp8_qdq_blockwise(w_d)
            g = ragged_dot(xs2, w_g, gsz, platform=platform)
            if "gb" in wd:
                g = g + wd["gb"].astype(g.dtype)[sid]
            if gated:
                w_u = wd["uw"].astype(xs2.dtype)
                if fp8:
                    w_u = fp8_qdq_blockwise(w_u)
                u = ragged_dot(xs2, w_u, gsz, platform=platform)
                if "ub" in wd:
                    u = u + wd["ub"].astype(u.dtype)[sid]
            else:  # non-gated (relu2): one projection, act2 ignores its 2nd operand
                u = g
            h_mid = act2(g, u)
            if fp8:
                h_mid = fp8_qdq_tensor(h_mid)
            y = ragged_dot(h_mid, w_d, gsz, platform=platform)
            if "db" in wd:
                if tp_axis is not None:  # partial over tp: bias on one shard only
                    y = y + jnp.where(
                        jax.lax.axis_index(tp_axis) == 0,
                        wd["db"].astype(y.dtype)[sid], 0.0,
                    )
                else:
                    y = y + wd["db"].astype(y.dtype)[sid]
    with jax.named_scope("combine"):
        # permutations invert as forward GATHERS (out[p[i]] = y[i] is exactly
        # y[argsort(p)]), and every gather here carries a gather-only custom VJP
        # — the EP backward contains no XLA scatter (VERDICT r4 weak #3; jax
        # 0.9's shard_map infers vma through custom_vjp cleanly, which blocked
        # this in r4).
        if ep > 1:
            y = _perm_take(y, inv_order2, order2)  # back to recv order
            y = a2a(y)  # [ep*C, D] back in my send layout
            y = _slot_unpack(y, dst, src, slot_valid)  # picks; dropped → 0
        y = _perm_take(y, inv_order, order)  # original pick order

        # picks of token t are rows [t*K, t*K+K) → combine is a dense reshape
        # + weighted K-fold sum, no scatter in the forward
        out = jnp.einsum(
            "tkd,tk->td",
            y.reshape(T, K, D),
            cwb.reshape(T, K),
            preferred_element_type=jnp.float32,
        )
        if tp_axis is not None:
            out = jax.lax.psum(out, tp_axis)  # down-proj partials, deferred to [T, D]
        return out.astype(xb.dtype).reshape(Bl, Sl, D)


def a2a_experts_manual(
    x: jnp.ndarray,  # [B_loc, S_loc, D] — the LOCAL ep shard
    gate_out: GateOutput,  # over the local tokens
    weights: dict,
    cfg: MoEConfig,
    act2: Act,
    *,
    ep: int,
    ep_axis: str = "ep",
    platform: str | None = None,
    fp8: bool = False,
    fused_act=None,
) -> jnp.ndarray:
    """a2a dispatch for contexts where `ep` is ALREADY a manual axis (the
    pp×ep pipeline region). tp must not shard the expert weights here
    (parallel.pp restricts ep_manual mode to tp=1)."""
    Bl, Sl, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    if E % ep:
        raise ValueError(f"num_experts={E} must be divisible by ep={ep}")
    E_loc = E // ep
    Tl = Bl * Sl
    cap = Tl * min(K, E_loc)  # strict per-peer worst case → dropless
    if cfg.a2a_capacity_factor is not None:
        cap = min(cap, int(math.ceil(cfg.a2a_capacity_factor * Tl * K / ep)))
    C = -(-cap // 8) * 8

    with jax.named_scope("dispatch"):
        wd = _a2a_weights(weights, cfg, whole=fused_act is not None)

    idx = gate_out.topk_idx.reshape(Bl, Sl, K)
    cw = gate_out.topk_weights.reshape(Bl, Sl, K)
    return _a2a_body(
        x, idx, cw, wd,
        ep=ep, ep_axis=ep_axis, E=E, E_loc=E_loc, C=C, D=D, K=K,
        act2=act2, gated=cfg.gated, tp_axis=None, platform=platform, fp8=fp8,
        fused_act=fused_act,
    )


# Registry with a UNIFORM call shape — x is [B, S, D]; every entry accepts
# (and ignores where irrelevant) ctx/constrain/platform, so the dispatch in
# moe.layer stays one registry call as kwargs accrete. The per-backend
# functions above keep their natural signatures for direct/test use.
def _noop_constrain(a, spec):
    return a


_warned_fp8_backend: set = set()


def _warn_fp8_unsupported(name: str) -> None:
    if name not in _warned_fp8_backend:
        _warned_fp8_backend.add(name)
        import logging

        logging.getLogger(__name__).warning(
            "fp8_experts=True but experts=%r does not implement the fp8 "
            "path — running in full precision (use 'ragged' or 'a2a').", name
        )


def _run_dense(x, gate_out, weights, cfg, act2, *, ctx=None,
               constrain=_noop_constrain, platform=None, fp8=False,
               act_name="silu"):
    if fp8:
        _warn_fp8_unsupported("dense")
    B, S, D = x.shape
    return dense_experts(x.reshape(-1, D), gate_out, weights, cfg, act2).reshape(B, S, D)


def _run_gspmd(x, gate_out, weights, cfg, act2, *, ctx=None,
               constrain=_noop_constrain, platform=None, fp8=False,
               act_name="silu"):
    if fp8:
        _warn_fp8_unsupported("gspmd")
    return gspmd_experts(x, gate_out, weights, cfg, act2, constrain=constrain)


def _run_a2a(x, gate_out, weights, cfg, act2, *, ctx=None,
             constrain=_noop_constrain, platform=None, fp8=False,
             act_name="silu"):
    return a2a_experts(x, gate_out, weights, cfg, act2, ctx, platform=platform,
                       fp8=fp8)


def _run_a2a_fused(x, gate_out, weights, cfg, act2, *, ctx=None,
                   constrain=_noop_constrain, platform=None, fp8=False,
                   act_name="silu"):
    """a2a token exchange + the one-kernel local expert MLP: EP training
    gets the same per-layer HBM savings as the single-chip ragged_fused
    backend (reference capability: DeepEP dispatch feeding TE's fused
    epilogues)."""
    fused_act = _fused_act_of(cfg, act_name, fp8)
    return a2a_experts(x, gate_out, weights, cfg, act2, ctx, platform=platform,
                       fp8=fp8, fused_act=fused_act)


def ragged_fused_experts(
    x: jnp.ndarray,  # [T, D]
    gate_out: GateOutput,
    weights: dict,
    cfg: MoEConfig,
    act2: Act,  # unused — the kernel applies the activation from cfg
    platform: str | None = None,
    act_name: str = "silu",
) -> jnp.ndarray:
    """ragged_experts with the WHOLE expert MLP in one Pallas kernel
    (ops/fused_expert_mlp): the [T·K, 2I] gate_up output and the [T·K, I]
    activation never touch HBM, and the kernels read the gate and up halves
    of the stored fused weight in place (`_fused_gate_up`). Same dropless
    sort + permutation-gather dispatch/combine; backward through the
    purpose-tiled kernels of the same module."""
    from automodel_tpu.ops.fused_expert_mlp import fused_expert_mlp

    act_kind, limit = _fused_act_of(cfg, act_name, fp8=False)
    T, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    with jax.named_scope("dispatch"):
        flat_expert = gate_out.topk_idx.reshape(-1)
        order = _name_ckpt(jnp.argsort(flat_expert), "moe_sort_order")
        inv = _name_ckpt(jnp.argsort(order), "moe_sort_inv")
        group_sizes = gate_out.expert_counts.astype(jnp.int32)
        xs = _dispatch_take(x, order, inv, K)
        gw, uw, w_dn, gb, ub, db = _fused_operands(weights, cfg, xs.dtype)
    with jax.named_scope("experts"):
        ys = fused_expert_mlp(
            xs, gw, uw, w_dn, group_sizes,
            gb, ub, db, act_kind, limit, platform, None,
        )
    with jax.named_scope("combine"):
        out = _sorted_combine(ys, gate_out.topk_weights, order, inv, K)
        return out.astype(x.dtype)


def held_experts(
    x: jnp.ndarray,  # [B, S, D]
    gate_out: GateOutput,
    weights: dict,  # leaves with leading dim hi - lo
    cfg: MoEConfig,
    act2: Act,
    experts_backend: str,
    *,
    ctx=None,
    platform: str | None = None,
    fp8: bool = False,
    act_name: str = "silu",
) -> jnp.ndarray:
    """The part of the routed result that the experts ``cfg.held_experts`` =
    [lo, hi) give, where the layer's other experts live on chips this program
    does not span. The router has scored and picked over all ``num_experts``;
    a pick outside the range is dropped here, before any row is gathered, and
    a pick inside it keeps its published combine weight. No exchange, and
    nothing stands in for the absent experts' part.

    Held picks are sorted by expert into a buffer of ``cap`` rows (the strict
    worst case ``T * min(K, hi - lo)``, or ``held_capacity_factor`` x the
    balanced share; picks over a bounded buffer contribute zero, as in the
    a2a dispatcher), the grouped expert MLP runs on the rows that are there,
    and the results are added back to their tokens."""
    if kernel_axes(ctx) is not None:
        raise NotImplementedError(
            "held_experts is one expert-parallel rank run alone, on one device; "
            "across devices the a2a backend's exchange is the same thing"
        )
    if experts_backend not in ("ragged", "a2a", "ragged_fused", "a2a_fused"):
        raise NotImplementedError(f"held_experts with experts={experts_backend!r}")
    fused = experts_backend.endswith("_fused")
    if ctx is not None:
        platform = ctx.platform
    B, S, D = x.shape
    T, K = B * S, cfg.num_experts_per_tok
    lo, hi = cfg.held_experts
    Eh = hi - lo
    cap = T * min(K, Eh)
    if cfg.held_capacity_factor is not None:
        cap = min(cap, int(math.ceil(cfg.held_capacity_factor * T * K * Eh / cfg.num_experts)))
    cap = -(-cap // 8) * 8
    xt = x.reshape(T, D)
    with jax.named_scope("dispatch"):
        flat = gate_out.topk_idx.reshape(-1)  # [T*K]
        held = (flat >= lo) & (flat < hi)
        order = jnp.argsort(jnp.where(held, flat - lo, Eh))[:cap]  # stable; held picks first
        if cap > order.size:  # cap rounded up past T*K: the padding is never valid
            order = jnp.pad(order, (0, cap - order.size))
        ends = jnp.minimum(jnp.cumsum(gate_out.expert_counts[lo:hi].astype(jnp.int32)), cap)
        group_sizes = jnp.diff(ends, prepend=0)
        valid = jnp.arange(cap) < ends[-1]
        token = order // K
        xs = jnp.where(valid[:, None], jnp.take(xt, token, axis=0), 0)
        w = jnp.take(gate_out.topk_weights.reshape(-1), order)
        if fused:
            gw, uw, w_dn, gb, ub, db = _fused_operands(weights, cfg, xs.dtype)
        else:
            xs, w_gu, w_dn = _ragged_operands(xs, weights, fp8)
    with jax.named_scope("experts"):
        if fused:
            from automodel_tpu.ops.fused_expert_mlp import fused_expert_mlp

            act_kind, limit = _fused_act_of(cfg, act_name, fp8)
            ys = fused_expert_mlp(xs, gw, uw, w_dn, group_sizes, gb, ub, db,
                                  act_kind, limit, platform, None)
        else:
            row_expert = jnp.minimum(jnp.searchsorted(ends, jnp.arange(cap), side="right"), Eh - 1)
            ys = _ragged_mlp(xs, w_gu, w_dn, weights, group_sizes, row_expert,
                             cfg, act2, platform, fp8)
    with jax.named_scope("combine"):
        # rows past the held picks are whatever the kernel left there (a unit
        # with no rows writes nothing): select them away BEFORE anything
        # multiplies them, or 0 * NaN reaches the output or the weights' gradient
        ys = jnp.where(valid[:, None], ys.astype(jnp.float32), 0)
        out = jnp.zeros((T, D), jnp.float32).at[token].add(ys * w.astype(jnp.float32)[:, None])
        return out.astype(x.dtype).reshape(B, S, D)


# `ragged` / `ragged_fused` name the same code as `a2a` / `a2a_fused`:
# a2a_experts IS the single-slice ragged path wherever there is no mesh to
# exchange over (no ctx, one device, a pipeline stage), and the shard_map'd
# block — which the Pallas grouped matmul needs on any mesh of several
# devices — wherever there is. The names stay because configs use them.
EXPERT_BACKENDS = {
    "ragged_fused": _run_a2a_fused,
    "dense": _run_dense,
    "gspmd": _run_gspmd,
    "ragged": _run_a2a,
    "a2a": _run_a2a,
    "a2a_fused": _run_a2a_fused,
}
