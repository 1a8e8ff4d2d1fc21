"""The plain reference for Xing4.0 (XingChen-AGI/Xing4.0-29B-A4B, ``model_type``
``xing4_0``): manifold-constrained hyper-connections around rotary latent
attention and sparse experts, with one multi-token-prediction module.

Straight ``jax.numpy`` in float32 with matmuls at ``highest``: no kernel, no
cache, and nothing of the program imported. ``RMS(x; w) = w * x / sqrt(mean(x^2)
+ eps)``, no bias on any projection, untied head. Written from the config.json
and the papers it names (arXiv:2512.24880 on arXiv:2409.19606; the DeepSeek-V3
report, arXiv:2412.19437); the configuration's ``assumed`` lists every choice
the config.json does not settle.

**The stream.** ``n = hc_mult`` streams: ``X_0[i] = Emb(t)`` for every ``i``; after
the last block ``h = sum_i X[i]``, ``logits = Head(RMS_final(h))``.

**One sublayer** ``F`` (attention with ``input_layernorm``, the MLP or expert
layer with ``post_attention_layernorm``) with its own ``phi [nC, n + n + n^2]``,
``b``, ``alpha [3]``, on a token's ``X [n, C]``:

    x     = vec(X);  xhat = x / sqrt(mean(x^2) + rms_norm_eps)
    m     = xhat @ phi
    Hpre  = sigmoid(alpha[0] m[:n] + b[:n]);  Hpost = 2 sigmoid(alpha[1] m[n:2n] + b[n:2n])
    R     = clip(alpha[2] m[2n:] + b[2n:], clamp_min, clamp_max).reshape(n, n)
    M     = exp(R);  hc_sinkhorn_iters times:  M = M / (M.sum(-1) + hc_eps);  M = M / (M.sum(-2) + hc_eps)
    u     = sum_i Hpre[i] X[i];  y = F(u);  X'[i] = sum_j M[i, j] X[j] + Hpost[i] y

**Attention**: DeepSeek-V3's latent attention. ``q = RMS(x W_qa) W_qb`` (heads
of 128 + 64); ``[c, k_pe] = x W_kva`` (512 + 64); ``[k_nope, v] = RMS(c) W_kvb``
(heads of 128 + 128); the 64 rotary channels of every q head and of the ONE
shared key head are rotated pair by pair, ``(x_2i, x_2i+1)`` by ``pos *
inv_freq_i`` with YaRN's frequencies (below); causal softmax at
``(128 + 64)^-0.5 (0.1 mscale_all_dim ln(factor) + 1)^2``; ``W_o``. (The
program de-interleaves the pairs first, which permutes the rotated channels of
q and k alike and leaves every score as it is.)

**The MLP** of the first ``first_k_dense_replace`` layers is a dense SwiGLU;
of the others ``s = sigmoid(x W_r)`` over ALL the published experts, the top-k
of ``s + bias``, weights ``s`` at the picked renormalised to sum 1 and times
``routed_scaling_factor``, the picked SwiGLU experts plus one shared expert.

**The multi-token-prediction module** (one): ``h'_i = eh_proj [RMS_e(Emb(t_{i+1})),
RMS_h(h_i)]`` with ``h_i`` the collapsed stream BEFORE the final norm (a
sequence's last position takes its first token's embedding; its target is
ignored); ``X_0[i] = h'``; one block of the expert kind with its own maps and
experts at the same positions; ``logits = Head(RMS_s(sum_i X[i]))`` with the
main embedding and head; its loss is the mean cross-entropy against the labels
shifted left once more, the last position ignored. ``loss = L_main +
mtp_loss_weight L_mtp``, each a mean over its own targets.

**This chip's share.** The configuration holds the experts ``held_experts`` =
[lo, hi) of the published ``published_experts`` and a slice of the vocabulary.
The router keeps its published width; a pick outside the range adds nothing
here, exactly as in the program.

Departures from a textbook loop, each for memory or time only: sequences are
vmapped; attention takes its heads eight at a time and its queries in blocks;
each held expert multiplies every row with a combine weight of zero where the
row did not pick it; MLPs, head and cross-entropy run in blocks of rows; every
block and each of its halves sits under a ``jax.checkpoint``, and so do the
two spans of the stack and the module (a float32 stream of 8,192 tokens is
470 MB, and the gradient keeps one a checkpoint); the optimizer's moments stay
on the host between steps (``train_step``). Weights arrive
in the type the configuration keeps them in and are upcast where they are used.

``precision`` puts the reference in the program's place at a lower precision
(the control): ``bf16`` / ``fp8`` round both operands of every product
(``xhat @ phi`` included) with float32 accumulation, straight-through. The
router, the norms, the Sinkhorn rounds and the two mixes are float32 at every
precision.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp

import numpy as np

from benchmarks.reference import adam

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
PUBLISHED_EXPERTS = 64  # XingChen-AGI/Xing4.0-29B-A4B config.json: n_routed_experts


@dataclasses.dataclass(frozen=True)
class XingSpec:
    layers: int
    dense_layers: int
    expert_width: int
    published_experts: int
    held: tuple  # [lo, hi)
    top_k: int
    route_scale: float
    renormalize: bool
    heads: int
    nope: int
    pe: int
    v_dim: int
    latent: int
    q_rank: int
    rms_eps: float
    streams: int
    sinkhorn_iters: int
    hc_eps: float
    clamp: tuple  # (min, max)
    mtp_modules: int
    mtp_weight: float
    rope_theta: float
    yarn: Optional[tuple]  # (factor, original positions, beta_fast, beta_slow, mscale, mscale_all_dim)


def _held(hf: dict, mapping: dict) -> tuple[int, tuple]:
    """(published experts, the held range): the file's ``n_routed_experts``
    counts the experts held HERE; its ``reference`` block states the published
    count and the range (absent: every expert is here)."""
    n = int(hf["n_routed_experts"])
    published = int(mapping.get("published_experts", n))
    held = tuple(mapping.get("held_experts", (0, n)))
    if held[1] - held[0] != n or not 0 <= held[0] < held[1] <= published:
        raise ValueError(f"held_experts {held} of {published}: n_routed_experts says {n}")
    return published, held


def _yarn(hf: dict) -> Optional[tuple]:
    rs = hf.get("rope_scaling")
    if not rs:
        return None
    if rs.get("type", rs.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling {rs!r}: this reference has YaRN or none")
    return (float(rs["factor"]), int(rs["original_max_position_embeddings"]),
            float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
            float(rs.get("mscale", 1)), float(rs.get("mscale_all_dim", 0)))


def spec(hf: dict, mapping: dict) -> XingSpec:
    if not hf.get("q_lora_rank"):
        raise ValueError("this reference is the latent block with a low-rank q projection")
    if int(hf.get("n_group", 1)) != 1 or int(hf.get("n_shared_experts", 0)) != 1:
        raise ValueError("one expert group and one shared expert, as published")
    if hf.get("scoring_func") != "sigmoid" or hf.get("topk_method") != "noaux_tc":
        raise ValueError("router: sigmoid scores, selection by score + bias, as published")
    if int(hf.get("num_nextn_predict_layers", 0)) > 1:
        raise ValueError("one multi-token-prediction module at most")
    published, held = _held(hf, mapping)
    return XingSpec(
        layers=int(hf["num_hidden_layers"]), dense_layers=int(hf["first_k_dense_replace"]),
        expert_width=int(hf["moe_intermediate_size"]),
        published_experts=published, held=held, top_k=int(hf["num_experts_per_tok"]),
        route_scale=float(hf["routed_scaling_factor"]), renormalize=bool(hf["norm_topk_prob"]),
        heads=int(hf["num_attention_heads"]),
        nope=int(hf["qk_nope_head_dim"]), pe=int(hf["qk_rope_head_dim"]),
        v_dim=int(hf["v_head_dim"]), latent=int(hf["kv_lora_rank"]), q_rank=int(hf["q_lora_rank"]),
        rms_eps=float(hf["rms_norm_eps"]),
        streams=int(hf["hc_mult"]), sinkhorn_iters=int(hf["hc_sinkhorn_iters"]),
        hc_eps=float(hf["hc_eps"]),
        clamp=(float(hf["mhc_h_res_clamp_min"]), float(hf["mhc_h_res_clamp_max"])),
        mtp_modules=int(hf.get("num_nextn_predict_layers", 0)),
        mtp_weight=float(mapping.get("mtp_loss_weight", 0.3)),
        rope_theta=float(hf.get("rope_theta", 10000.0)), yarn=_yarn(hf),
    )


# -- precision control ---------------------------------------------------------
def _round(x: jnp.ndarray, precision: str) -> jnp.ndarray:
    if precision == "f32":
        return x
    if precision == "bf16":
        q = x.astype(jnp.bfloat16).astype(F32)
    elif precision == "fp8":
        scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    else:
        raise ValueError(f"precision {precision!r}")
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq: str, a, b, precision: str) -> jnp.ndarray:
    return jnp.einsum(eq, _round(a.astype(F32), precision), _round(b.astype(F32), precision),
                      precision=HI, preferred_element_type=F32)


def rms_norm(x, scale, eps: float) -> jnp.ndarray:
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


# -- the residual path (one sequence's stream X [S, n, C]) -------------------------
def residual_maps(X, phi, b, alpha, spec: XingSpec, precision: str):
    """-> Hpre [S, n], Hpost [S, n], Hres [S, n, n], every token its own."""
    S, n, C = X.shape
    x = X.reshape(S, n * C)
    xhat = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + spec.rms_eps)
    m = _mm("sk,kj->sj", xhat, phi, precision)
    a, b = alpha.astype(F32), b.astype(F32)
    pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    R = jnp.clip(a[2] * m[:, 2 * n:] + b[2 * n:], spec.clamp[0], spec.clamp[1]).reshape(S, n, n)
    M = jnp.exp(R)
    for _ in range(spec.sinkhorn_iters):
        M = M / (M.sum(axis=-1, keepdims=True) + spec.hc_eps)
        M = M / (M.sum(axis=-2, keepdims=True) + spec.hc_eps)
    return pre, post, M


def sublayer(X, lp, which: str, F, spec: XingSpec, precision: str):
    pre, post, res = residual_maps(X, lp[f"{which}_phi"], lp[f"{which}_b"], lp[f"{which}_alpha"],
                                   spec, precision)
    u = jnp.einsum("si,sic->sc", pre, X, precision=HI)
    y = F(u)
    return jnp.einsum("sij,sjc->sic", res, X, precision=HI) + post[:, :, None] * y[:, None, :]


# -- rotary positions with YaRN -----------------------------------------------------
def rotary_angles(S: int, spec: XingSpec) -> jnp.ndarray:
    """[S, pe / 2]: position x inverse frequency. YaRN (arXiv:2309.00071 as
    DeepSeek-V3 applies it): a frequency whose wavelength fits the original
    window more than ``beta_fast`` times is kept, one that fits fewer than
    ``beta_slow`` times is divided by ``factor``, a linear ramp over the
    dimensions between. cos and sin carry no factor: ``mscale`` and
    ``mscale_all_dim`` are equal and their ratio is 1; the softmax scale
    carries the correction."""
    d = spec.pe
    inv = spec.rope_theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    if spec.yarn is not None:
        factor, original, beta_fast, beta_slow, _, _ = spec.yarn
        dim_of = lambda turns: d * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(spec.rope_theta))
        low = max(math.floor(dim_of(beta_fast)), 0)
        high = min(math.ceil(dim_of(beta_slow)), d - 1)
        ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
    return jnp.arange(S, dtype=F32)[:, None] * inv[None, :]


def rotate_pairs(x, angles):
    """x [S, H, pe]: the pair (x[2i], x[2i + 1]) turned by angles[:, i]."""
    c, s = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1).reshape(x.shape)


def softmax_scale(spec: XingSpec) -> float:
    scale = (spec.nope + spec.pe) ** -0.5
    if spec.yarn is not None and spec.yarn[0] > 1.0 and spec.yarn[5]:
        m = 0.1 * spec.yarn[5] * math.log(spec.yarn[0]) + 1.0
        scale *= m * m
    return scale


# -- the mixer (one sequence [S, D] in, the branch's output out) ----------------------
HEAD_GROUP = 8  # heads taken together (memory only: heads are independent)


def mla(u, lp, spec: XingSpec, precision: str, q_block: int):
    S, D = u.shape
    N, nope, pe, vd = spec.heads, spec.nope, spec.pe, spec.v_dim
    n = max(N // HEAD_GROUP, 1)
    ng = N // n  # heads a group
    x = rms_norm(u, lp["attn_norm"], spec.rms_eps)
    qa = rms_norm(_mm("sd,dr->sr", x, lp["q_a"], precision), lp["q_a_norm"], spec.rms_eps)
    ckv = _mm("sd,de->se", x, lp["kv_a"], precision)
    c = rms_norm(ckv[:, :spec.latent], lp["kv_a_norm"], spec.rms_eps)
    angles = rotary_angles(S, spec)
    k_pe = rotate_pairs(ckv[:, None, spec.latent:], angles)[:, 0]  # one key head, shared
    q_block = min(q_block, S)
    pad = (-S) % q_block
    row0 = jnp.arange((S + pad) // q_block) * q_block
    kpos = jnp.arange(S)
    cols = lambda w, width: jnp.moveaxis(w.reshape(w.shape[0], n, ng * width), 1, 0)
    groups = {"q_b": cols(lp["q_b"], nope + pe), "kv_b": cols(lp["kv_b"], nope + vd),
              "o": lp["o"].reshape(n, ng * vd, D)}
    scale = softmax_scale(spec)

    @jax.checkpoint
    def group(w):
        q = _mm("sr,re->se", qa, w["q_b"], precision).reshape(S, ng, nope + pe)
        q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], angles)], axis=-1)
        kv = _mm("sr,re->se", c, w["kv_b"], precision).reshape(S, ng, nope + vd)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (S, ng, pe))], axis=-1)
        v = kv[..., nope:]
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, q_block, ng, nope + pe)

        @jax.checkpoint
        def one_block(args):
            qi, r0 = args
            s = _mm("qnh,snh->nqs", qi, k, precision) * scale
            rows = r0 + jnp.arange(q_block)
            s = jnp.where(kpos[None, None, :] <= rows[None, :, None], s, -jnp.inf)
            return _mm("nqs,snh->qnh", jax.nn.softmax(s, axis=-1), v, precision)

        out = jax.lax.map(one_block, (qb, row0)).reshape(-1, ng * vd)[:S]
        return _mm("se,ed->sd", out, w["o"], precision)

    out, _ = jax.lax.scan(lambda acc, w: (acc + group(w), None), jnp.zeros((S, D), F32), groups)
    return out


# -- the MLPs (rows [T, D] in, the branch's output out) ----------------------------------
ROW_BLOCK = 4096  # rows of an MLP taken together (memory only: rows are independent)


def _swiglu(x, gate, up, down, precision: str):
    T, D = x.shape
    pad = (-T) % min(ROW_BLOCK, T)
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, min(ROW_BLOCK, T), D)

    @jax.checkpoint
    def rows(xr):
        mid = jax.nn.silu(_mm("td,di->ti", xr, gate, precision)) * _mm("td,di->ti", xr, up, precision)
        return _mm("ti,id->td", mid, down, precision)

    return jax.lax.map(rows, xb).reshape(-1, D)[:T]


def route(x, lp, spec: XingSpec):
    """-> combine weights [T, published experts], zero where an expert was
    not picked. The router is float32 at every precision."""
    logits = jnp.einsum("td,de->te", x.astype(F32), lp["router"].astype(F32), precision=HI)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + lp["router_bias"].astype(F32), spec.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if spec.renormalize:
        w = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-20)
    w = w * spec.route_scale
    return jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], idx].set(w)


def mlp(u, lp, spec: XingSpec, precision: str):
    x = rms_norm(u, lp["mlp_norm"], spec.rms_eps)
    if "router" not in lp:
        return _swiglu(x, lp["gate"], lp["up"], lp["down"], precision)
    lo, hi = spec.held
    cw = route(x, lp, spec)[:, lo:hi]  # the held experts' columns; the rest is other chips'
    I = spec.expert_width

    @jax.checkpoint
    def expert(args):
        gate_up, down, w = args
        return w[:, None] * _swiglu(x, gate_up[:, :I], gate_up[:, I:], down, precision)

    routed, _ = jax.lax.scan(lambda acc, args: (acc + expert(args), None), jnp.zeros_like(x),
                             (lp["gate_up"], lp["down"], cw.T))
    return routed + _swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"], precision)


# -- the model ---------------------------------------------------------------------------
def _blocks(X, stacks: dict, layers, dense_layers: int, spec, precision, q_block):
    """Blocks ``layers`` of the stacks on the streams X [B, S, n, C]."""
    at = lambda stack, i: {k: v[i] for k, v in stacks[stack].items()}

    @jax.checkpoint
    def attn_half(X, lp):
        one = lambda Xs: sublayer(Xs, lp, "attn", lambda u: mla(u, lp, spec, precision, q_block),
                                  spec, precision)
        return jax.vmap(one)(X)

    @jax.checkpoint
    def mlp_half(X, lp):
        one = lambda Xs: sublayer(Xs, lp, "mlp", lambda u: mlp(u, lp, spec, precision),
                                  spec, precision)
        return jax.vmap(one)(X)

    # a block under a checkpoint of its own, its halves under theirs: the
    # gradient keeps one stream a block and recomputes the rest
    block = jax.checkpoint(lambda X, lp: mlp_half(attn_half(X, lp), lp))
    for i in layers:
        lp = {**at("layers", i), **at("mla", i)}
        lp.update(at("dense_mlp", i) if i < dense_layers else at("moe", i - dense_layers))
        X = block(X, lp)
    return X


def hidden_states(params, ids, spec: XingSpec, precision="f32", q_block=512):
    """``ids`` [B, S] -> (final-norm hidden rows [B * S, D], the same of the
    multi-token-prediction module or None)."""
    B, S = ids.shape
    n = spec.streams
    emb = params["embed"].astype(F32)
    widen = lambda h: jnp.broadcast_to(h[:, :, None, :], (B, S, n, h.shape[-1]))
    stacks = {k: params[k] for k in ("layers", "mla", "dense_mlp", "moe") if k in params}
    run = lambda X, stacks, layers: _blocks(X, stacks, layers, spec.dense_layers, spec, precision, q_block)
    # the stack in two spans, each under a checkpoint above its blocks' own:
    # the gradient keeps one stream a span and, inside the span it is in, one a block
    half = (spec.layers + 1) // 2
    first = jax.checkpoint(lambda e, stacks: run(widen(e), stacks, range(half)))
    second = jax.checkpoint(lambda X, stacks: run(X, stacks, range(half, spec.layers)).sum(axis=2))
    h = second(first(emb[ids], stacks), stacks)  # [B, S, D]: the collapsed stream, before the final norm
    main = rms_norm(h, params["final_norm"], spec.rms_eps).reshape(B * S, -1)
    if not spec.mtp_modules:
        return main, None
    mp = params["mtp"]
    nxt = emb[jnp.roll(ids, -1, axis=1)]  # t_{i+1}; the last position's is ignored by its label
    cat = jnp.concatenate([rms_norm(nxt, mp["enorm"][0], spec.rms_eps),
                           rms_norm(h, mp["hnorm"][0], spec.rms_eps)], axis=-1)
    hp = _mm("bse,ed->bsd", cat, mp["eh_proj"][0], precision)
    module = jax.checkpoint(lambda hp, mp: _blocks(
        widen(hp), mp, range(1), 0, spec, precision, q_block).sum(axis=2))
    block_stacks = {k: mp[k] for k in ("layers", "mla", "moe")}
    return main, rms_norm(module(hp, block_stacks), mp["final_norm"][0], spec.rms_eps).reshape(B * S, -1)


@functools.partial(jax.jit, static_argnames=("spec", "precision", "n_rows"))
def rows_logits(params, ids, row_start, spec, precision="f32", n_rows=1):
    """Logits [n_rows, V] of one sequence's rows from ``row_start`` on (the
    interface's serving entry; the family is not served, tests use it)."""
    h, _ = hidden_states(params, ids[None, :], spec, precision)
    h = jax.lax.dynamic_slice_in_dim(h, row_start, n_rows, axis=0)
    return _mm("sd,dv->sv", h, params["head"], precision)


def _ce_sum(h, head, labels, precision: str, row_block: int = 1024):
    """Sum of the cross-entropy over the labels that count, and their count."""
    lab = labels.reshape(-1)
    pad = (-h.shape[0]) % row_block
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, row_block, h.shape[1])
    lb = jnp.pad(lab, (0, pad), constant_values=-100).reshape(-1, row_block)

    @jax.checkpoint
    def block(args):
        hh, ll = args
        logits = _mm("sd,dv->sv", hh, head, precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, jnp.maximum(ll, 0)[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.where(ll >= 0, lse - picked, 0.0))

    return jnp.sum(jax.lax.map(block, (hb, lb))), jnp.sum(lab >= 0)


def losses(params, ids, labels, spec: XingSpec, precision="f32"):
    """-> (the main loss, the multi-token-prediction loss or 0): each the
    mean cross-entropy over its own targets. ``labels`` [B, S] are the main
    targets (already shifted, -100 = none); the module's are those shifted left
    once more inside each sequence, the last position ignored."""
    main, mtp = hidden_states(params, ids, spec, precision)
    mean = lambda total, n: total / jnp.maximum(n, 1).astype(F32)
    l_main = mean(*_ce_sum(main, params["head"], labels, precision))
    if mtp is None:
        return l_main, jnp.zeros((), F32)
    labels2 = jnp.concatenate([labels[:, 1:], jnp.full_like(labels[:, :1], -100)], axis=1)
    return l_main, mean(*_ce_sum(mtp, params["head"], labels2, precision))


# The step in two programs, the moments on the HOST between them. One program
# with the gradient's checkpoints BESIDE float32 moments (7.3 GB at the
# published widths) does not fit the chip; the gradient needs no moment and the
# update needs no activation, so the moments live in numpy and visit the device
# for the update alone. The arithmetic is ``adam.step``'s, untouched: it asks
# for a loss of the parameters and gets the linear one whose gradient is the
# gradient just computed (exactly: that gradient is already in the
# parameters' type).

def init_moments(params, opt: adam.AdamSpec):
    """``adam.init_moments``' zeros, as numpy arrays on the host."""
    return jax.tree.map(np.asarray, adam.init_moments(params, opt))


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _loss_and_grads(params, ids, labels, spec, precision):
    def mean_loss(p):
        l_main, l_mtp = losses(p, ids, labels, spec, precision)
        return l_main + spec.mtp_weight * l_mtp

    return jax.value_and_grad(mean_loss)(params)


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 2, 3))
def _update(params, grads, mu, nu, step, opt):
    def along_the_gradient(p):
        return sum(jnp.vdot(g.astype(F32), q.astype(F32))
                   for g, q in zip(jax.tree.leaves(grads), jax.tree.leaves(p)))

    new_p, mu, nu, _, norms, small = adam.step(along_the_gradient, params, mu, nu, step, opt)
    return new_p, mu, nu, norms, small


def train_step(params, mu, nu, step, ids, labels, spec, opt, precision="f32"):
    """loss = L_main + mtp_loss_weight x L_mtp, then ``adam.step``; ``mu`` and
    ``nu`` come from the host and go back to it."""
    loss, grads = _loss_and_grads(params, ids, labels, spec, precision)
    params, mu, nu, norms, small = _update(params, grads, *jax.device_put((mu, nu)), step, opt)
    return params, *jax.device_get((mu, nu)), loss, norms, small


# -- the program's parameter tree and this one ----------------------------------------
# The reference keeps the program's STACKS (a kind's layers share a leading
# axis; ``_blocks`` indexes them), so the way there and back is a renaming and
# no leaf carries a layer index. stack -> {reference leaf: the program's leaf}
_HC = {f"{s}_{leaf}": f"{s}_hc/{leaf}" for s in ("attn", "mlp") for leaf in ("phi", "b", "alpha")}
STACKS = {
    "layers": {"attn_norm": "input_norm/scale", "mlp_norm": "post_attn_norm/scale", **_HC},
    "mla": {"q_a": "q_a_proj/kernel", "q_a_norm": "q_a_norm/scale", "q_b": "q_b_proj/kernel",
            "kv_a": "kv_a_proj/kernel", "kv_a_norm": "kv_a_norm/scale", "kv_b": "kv_b_proj/kernel",
            "o": "o_proj/kernel"},
    "dense_mlp": {n: f"{n}_proj/kernel" for n in ("gate", "up", "down")},
    "moe": {
        "router": "router/weight", "router_bias": "router/bias",
        "gate_up": "experts/gate_up", "down": "experts/down",
        **{f"shared_{n}": f"shared/{n}_proj/kernel" for n in ("gate", "up", "down")},
    },
}
TOP = {"embed": "embed/embedding", "head": "lm_head/kernel", "final_norm": "final_norm/scale"}
MTP = {"enorm": "enorm/scale", "hnorm": "hnorm/scale", "eh_proj": "eh_proj/kernel",
       "final_norm": "final_norm/scale"}


def _get(tree: dict, name: str):
    for k in name.split("/"):
        tree = tree[k]
    return tree


def _renamed(tree: dict, top: dict, leaf) -> dict:
    out = {key: leaf(name, key, None) for key, name in top.items()}
    for stack, leaves in STACKS.items():
        if stack in tree:
            out[stack] = {key: leaf(f"{stack}/{name}", key, stack) for key, name in leaves.items()}
    return out


def to_reference(tree: dict) -> dict:
    """The program's tree under this module's names. Only a renaming."""
    out = _renamed(tree, TOP, lambda name, *_: _get(tree, name))
    if "mtp" in tree:
        out["mtp"] = _renamed(tree["mtp"], MTP, lambda name, *_: _get(tree["mtp"], name))
    return out


def program_names(ref_tree: dict) -> dict:
    out = _renamed(ref_tree, TOP, lambda name, *_: (name, None))
    if "mtp" in ref_tree:
        out["mtp"] = _renamed(ref_tree["mtp"], MTP, lambda name, *_: (f"mtp/{name}", None))
    return out


# -- the counts the kernel laws and the roofline readers need ------------------------
def _program_has_the_family() -> bool:
    """A path probe (nothing of the program is imported): a checkout whose
    program lacks the family (the parent commit with this benchmark laid over
    it) must fail in ``loader.load_cell``, at once. Its registry would
    otherwise fall back to the generic llama family and train SOMETHING for a
    whole window before ``to_reference`` found no hyper-connection leaves."""
    return (Path(__file__).resolve().parents[2] / "automodel_tpu" / "models" / "xing4").is_dir()


def shapes(hf: dict) -> dict:
    """Layers by kind and the per-token law. ``hf["n_routed_experts"]`` counts
    the experts HELD here, ``PUBLISHED_EXPERTS`` those the router picks over: a
    token's expected held picks are ``top_k * held / published`` (all ``top_k``
    at the uncut configuration). The multi-token-prediction module counts as
    one more attention layer and one more expert layer."""
    if not _program_has_the_family():
        raise ValueError("the program around this benchmark has no automodel_tpu/models/xing4: "
                         "it cannot run a Xing4_0ForCausalLM configuration")
    d, vocab = int(hf["hidden_size"]), int(hf["vocab_size"])
    L, n_dense = int(hf["num_hidden_layers"]), int(hf["first_k_dense_replace"])
    n_mtp = int(hf.get("num_nextn_predict_layers", 0))
    N = int(hf["num_attention_heads"])
    nope, pe, vd = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"]), int(hf["v_head_dim"])
    latent, q_rank = int(hf["kv_lora_rank"]), int(hf["q_lora_rank"])
    width, dense_width = int(hf["moe_intermediate_size"]), int(hf["intermediate_size"])
    top_k, held = int(hf["num_experts_per_tok"]), int(hf["n_routed_experts"])
    published = max(PUBLISHED_EXPERTS, held)
    n = int(hf["hc_mult"])
    coeffs = n + n + n * n
    n_blocks = L + n_mtp  # every block has attention; all but the dense ones have experts
    n_moe = n_blocks - n_dense

    mla_proj = (d * q_rank + q_rank * N * (nope + pe) + d * (latent + pe)
                + latent * N * (nope + vd) + N * vd * d)
    maps = n * d * coeffs  # one sublayer's phi
    expert = 3 * d * width

    def forward_flops_per_token(seq_len: int) -> float:
        attn = 2 * N * (nope + pe + vd) * (seq_len / 2)  # QK^T and PV over the causal half
        picks = top_k * held / published  # expected held picks a token
        moe = 2 * (expert * (picks + 1) + d * published)  # held picks, the shared expert, the router
        block = 2 * mla_proj + attn + 2 * 2 * maps  # two sublayers' phi products
        return (n_blocks * block + n_dense * 2 * 3 * d * dense_width + n_moe * moe
                + n_mtp * 2 * (2 * d * d)  # eh_proj
                + (1 + n_mtp) * 2 * d * vocab)  # the head, once a loss

    def parameter_count(active: bool = False) -> int:
        """Every parameter held here; ``active``: those one token uses (its
        picked experts, not all held; one embedding row is not counted, as the
        published figure does not)."""
        experts = (top_k if active else held) * expert
        moe = experts + expert + d * published + published
        block = (mla_proj + q_rank + latent + 2 * d  # the projections, their two norms, two layer norms
                 + 2 * (maps + coeffs + 3))  # two sublayers' phi, b, alpha
        blocks = n_blocks * block + n_dense * 3 * d * dense_width + n_moe * moe
        mtp = n_mtp * (2 * d * d + 3 * d)  # eh_proj, enorm, hnorm, its final norm
        return blocks + mtp + (1 if active else 2) * d * vocab + d

    return {
        "attention_layers": n_blocks, "q_heads": N, "qk_head_dim": nope + pe, "v_head_dim": vd,
        "expert_layers": n_moe, "top_k": top_k, "held_experts": held,
        "published_experts": published, "hidden": d, "expert_width": width, "vocab": vocab,
        "hc_streams": n, "hc_sublayers": 2 * n_blocks,
        "forward_flops_per_token": forward_flops_per_token,
        "parameter_count": parameter_count,
    }
