"""The plain reference for the sparse-expert decoder both benchmark models share.

Straight ``jax.numpy`` in float32 with ``jax.default_matmul_precision
("highest")``: no Pallas kernel, no cache, no paging, no program import. It
follows the published block (pre-norm GQA attention with rotary embeddings,
a routed top-k expert MLP, RMSNorm, untied head):

- q/k norm either per head (Qwen3-MoE / SDAR) or over the flattened
  projection (MiniMax-M2, ``qk_norm: flat``);
- rotary on the first ``rotary_dim`` channels of a head (rotate-half), the
  rest pass through (MiniMax-M2 rotates 64 of 128);
- router ``softmax`` (scores = softmax over all experts, top-k, renormalised)
  or ``sigmoid_bias`` (scores = sigmoid, selection by score + correction
  bias, weights from the unbiased scores, renormalised).

Departures from a textbook loop, each for memory or time only: attention
runs in blocks of query rows; each expert multiplies only the rows routed to
it (rows sorted by expert into tiles, one expert a tile, no row dropped);
the head and the cross-entropy run in blocks of rows. Weights arrive in the type the
configuration serves them in (bf16) and are upcast where they are used.

``precision`` puts the reference in the program's place at a lower
precision, which is the control the benchmark's limits are set against:
``bf16`` rounds both operands of every matmul to bfloat16, ``fp8`` to
float8_e4m3 with one scale a tensor. Products accumulate in float32 either
way, and the rounding is straight-through for gradients.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    top_k: int
    expert_width: int
    rms_eps: float
    rope_theta: float
    rotary_dim: int
    qk_norm: Optional[str]  # None | "per_head" | "flat"
    router: str  # "softmax" | "sigmoid_bias"

    @classmethod
    def from_config(cls, hf: dict, mapping: dict) -> "DecoderSpec":
        """``hf``: the configuration file's top-level keys (the source's
        config.json names). ``mapping``: the file's ``reference`` block —
        which published mechanism each of them selects."""
        head_dim = int(hf["head_dim"])
        return cls(
            vocab_size=int(hf["vocab_size"]),
            hidden_size=int(hf["hidden_size"]),
            num_layers=int(hf["num_hidden_layers"]),
            num_heads=int(hf["num_attention_heads"]),
            num_kv_heads=int(hf["num_key_value_heads"]),
            head_dim=head_dim,
            num_experts=int(hf.get("num_experts") or hf["num_local_experts"]),
            top_k=int(hf["num_experts_per_tok"]),
            expert_width=int(hf.get("moe_intermediate_size") or hf["intermediate_size"]),
            rms_eps=float(hf["rms_norm_eps"]),
            rope_theta=float(hf["rope_theta"]),
            rotary_dim=int(hf.get("rotary_dim") or head_dim),
            qk_norm=mapping.get("qk_norm"),
            router=mapping["router"],
        )


# -- precision control ---------------------------------------------------------
def _round(x: jnp.ndarray, precision: str) -> jnp.ndarray:
    if precision == "f32":
        return x
    if precision == "bf16":
        q = x.astype(jnp.bfloat16).astype(F32)
    elif precision == "fp8":
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = 448.0 / amax
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    else:
        raise ValueError(f"precision {precision!r}")
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq: str, a: jnp.ndarray, b: jnp.ndarray, precision: str) -> jnp.ndarray:
    return jnp.einsum(
        eq, _round(a.astype(F32), precision), _round(b.astype(F32), precision),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32,
    )


# -- the block -----------------------------------------------------------------
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def rope(x: jnp.ndarray, positions: jnp.ndarray, spec: DecoderSpec) -> jnp.ndarray:
    """x: [S, heads, head_dim]; rotate-half over the first rotary_dim channels."""
    r = spec.rotary_dim
    inv = 1.0 / (spec.rope_theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = positions.astype(F32)[:, None] * inv[None, :]  # [S, r/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot, rest = x[..., :r], x[..., r:]
    half = r // 2
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    return jnp.concatenate([rot * jnp.cos(ang) + turned * jnp.sin(ang), rest], axis=-1)


def attention(h, lp, spec: DecoderSpec, precision: str, q_start, n_q: int, q_block: int):
    """Causal GQA attention of one sequence. h: [S, D]. Queries are the
    ``n_q`` rows from ``q_start`` on (a traced start is fine); keys and
    values are all rows."""
    S = h.shape[0]
    x = rms_norm(h, lp["attn_norm"], spec.rms_eps)
    xq = jax.lax.dynamic_slice_in_dim(x, q_start, n_q, axis=0)
    q = _mm("sd,de->se", xq, lp["q"], precision)
    k = _mm("sd,de->se", x, lp["k"], precision)
    v = _mm("sd,de->se", x, lp["v"], precision)
    if spec.qk_norm == "flat":
        q = rms_norm(q, lp["q_norm"], spec.rms_eps)
        k = rms_norm(k, lp["k_norm"], spec.rms_eps)
    q = q.reshape(n_q, spec.num_heads, spec.head_dim)
    k = k.reshape(S, spec.num_kv_heads, spec.head_dim)
    v = v.reshape(S, spec.num_kv_heads, spec.head_dim)
    if spec.qk_norm == "per_head":
        q = rms_norm(q, lp["q_norm"], spec.rms_eps)
        k = rms_norm(k, lp["k_norm"], spec.rms_eps)
    q = rope(q, q_start + jnp.arange(n_q), spec)
    k = rope(k, jnp.arange(S), spec)
    group = spec.num_heads // spec.num_kv_heads
    pad = (-n_q) % q_block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, q_block, spec.num_kv_heads, group, spec.head_dim
    )
    row0 = q_start + jnp.arange(qb.shape[0]) * q_block
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one_block(args):
        qi, r0 = args
        s = _mm("qkgh,skh->kgqs", qi, k, precision) / math.sqrt(spec.head_dim)
        rows = r0 + jnp.arange(q_block)
        s = jnp.where(kpos[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("kgqs,skh->qkgh", p, v, precision)

    out = jax.lax.map(one_block, (qb, row0))
    out = out.reshape(-1, spec.num_heads * spec.head_dim)[:n_q]
    hq = jax.lax.dynamic_slice_in_dim(h, q_start, n_q, axis=0)
    return hq + _mm("se,ed->sd", out, lp["o"], precision)


def route(x, lp, spec: DecoderSpec):
    """-> (expert ids [T, K], combine weights [T, K]); the router is float32
    at every precision, as published."""
    logits = jnp.einsum(
        "td,de->te", x.astype(F32), lp["router"].astype(F32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if spec.router == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        choice = scores
    elif spec.router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        choice = scores + lp["router_bias"].astype(F32)
    else:
        raise ValueError(f"router {spec.router!r}")
    _, idx = jax.lax.top_k(choice, spec.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    return idx, w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-20)


def experts(h, lp, spec: DecoderSpec, precision: str):
    """h: [T, D] -> h + the routed expert MLPs. No row is ever dropped: the
    (row, expert) pairs are sorted by expert and laid out in tiles of R rows,
    each expert's group starting on a tile of its own, and every tile is
    multiplied by its one expert's weights."""
    T, D = h.shape
    E, K = spec.num_experts, spec.top_k
    x = rms_norm(h, lp["mlp_norm"], spec.rms_eps)
    idx, w = route(x, lp, spec)
    TK = T * K
    R = max(8, min(256, 1 << (max(TK // E, 1).bit_length() - 1)))
    n_tiles = -(-TK // R) + E
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    tiles_of = (counts + R - 1) // R
    tile_end = jnp.cumsum(tiles_of)
    first_row = (tile_end - tiles_of) * R  # padded row where each expert's group starts
    rank = jnp.arange(TK) - (jnp.cumsum(counts) - counts)[sorted_e]
    dest = first_row[sorted_e] + rank
    src_token = jnp.full((n_tiles * R,), T, jnp.int32).at[dest].set((order // K).astype(jnp.int32))
    src_w = jnp.zeros((n_tiles * R,), F32).at[dest].set(w.reshape(-1)[order])
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"), E - 1
    )
    xpad = jnp.concatenate([x, jnp.zeros((1, D), F32)])
    w_gu, w_down = lp["gate_up"], lp["down"]

    @jax.checkpoint
    def tile(args):
        tok, wt, e = args
        xt = xpad[tok]  # [R, D]
        gu = _mm("rd,df->rf", xt, w_gu[e], precision)
        g, u = gu[:, : spec.expert_width], gu[:, spec.expert_width:]
        y = _mm("ri,id->rd", jax.nn.silu(g) * u, w_down[e], precision)
        return y * wt[:, None]

    y = jax.lax.map(tile, (src_token.reshape(n_tiles, R), src_w.reshape(n_tiles, R), tile_expert))
    out = jnp.zeros((T + 1, D), F32).at[src_token].add(y.reshape(n_tiles * R, D))[:T]
    return h + out


def hidden_states(params, ids, spec, precision="f32", rows=None, q_block=512):
    """Sequences ``ids`` [B, S] -> final-norm hidden rows [B * n, D].
    Attention runs sequence by sequence, the experts over all rows at once.
    ``rows`` = (start, n) keeps only the n rows from ``start`` on (start may
    be traced, n is static) from the last layer on: the earlier layers still
    see the whole sequence."""
    B, S = ids.shape
    h = params["embed"][ids].astype(F32)  # gather, then upcast: no float32 copy of the table
    for i, lp in enumerate(params["layers"]):
        q_start, n_q = 0, S
        if rows is not None and i == spec.num_layers - 1:
            q_start, n_q = rows
        h = jax.vmap(
            lambda hs: attention(hs, lp, spec, precision, q_start, n_q, min(q_block, n_q))
        )(h)
        h = experts(h.reshape(B * n_q, -1), lp, spec, precision).reshape(B, n_q, -1)
    h = rms_norm(h, params["final_norm"], spec.rms_eps)
    return h.reshape(-1, h.shape[-1])


@functools.partial(jax.jit, static_argnames=("spec", "precision", "n_rows"))
def rows_logits(params, ids, row_start, spec, precision="f32", n_rows=1):
    """Logits [n_rows, V] of one sequence's rows from ``row_start`` on.
    ``ids`` may be padded at its end: no row sees a later one."""
    h = hidden_states(params, ids[None, :], spec, precision, rows=(row_start, n_rows))
    return _mm("sd,dv->sv", h, params["head"], precision)


def loss_sum(params, ids, labels, spec, precision="f32", row_block=1024):
    """Sum of next-token cross-entropies of a batch [B, S] over the labels
    that are not -100 (already shifted, as the collator hands them out), and
    the count of those labels."""
    h = hidden_states(params, ids, spec, precision)
    lab = labels.reshape(-1)
    pad = (-h.shape[0]) % row_block
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, row_block, h.shape[1])
    lb = jnp.pad(lab, (0, pad), constant_values=-100).reshape(-1, row_block)

    @jax.checkpoint
    def block(args):
        hh, ll = args
        logits = _mm("sd,dv->sv", hh, params["head"], precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, jnp.maximum(ll, 0)[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.where(ll >= 0, lse - picked, 0.0))

    total = jnp.sum(jax.lax.map(block, (hb, lb)))
    return total, jnp.sum(labels >= 0)


# -- the optimizer the train cells state (AdamW behind a global-norm clip) ------
SMALL_LEAF = 1 << 24  # gradients up to this size are compared element by element


@dataclasses.dataclass(frozen=True)
class AdamSpec:
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    clip_norm: Optional[float]
    moments_dtype: str  # "float32" | "param"


def init_moments(params, opt: AdamSpec):
    def zeros(p):
        return jnp.zeros(p.shape, p.dtype if opt.moments_dtype == "param" else F32)
    return jax.tree.map(zeros, params), jax.tree.map(zeros, params)


@functools.partial(jax.jit, static_argnames=("spec", "opt", "precision"),
                   donate_argnums=(0, 1, 2))
def train_step(params, mu, nu, step, ids, labels, spec, opt, precision="f32"):
    """One optimizer step as the configuration states it: loss = mean CE,
    gradients in the parameters' type, clip by the global norm, Adam with
    bias correction, decoupled weight decay, parameters back in their type.
    -> (params, mu, nu, loss, per-leaf norms of the clipped gradient, the
    clipped gradient itself of every leaf of at most SMALL_LEAF elements)."""
    def mean_loss(p):
        total, n = loss_sum(p, ids, labels, spec, precision)
        return total / jnp.maximum(n, 1).astype(F32)

    loss, grads = jax.value_and_grad(mean_loss)(params)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(F32))) for g in jax.tree.leaves(grads)))
    scale = 1.0
    if opt.clip_norm:
        scale = jnp.minimum(1.0, opt.clip_norm / jnp.maximum(gnorm, 1e-12))
    t = (step + 1).astype(F32)

    def leaf(p, g, m, v):
        g = g.astype(F32) * scale
        m32 = opt.b1 * m.astype(F32) + (1.0 - opt.b1) * g
        v32 = opt.b2 * v.astype(F32) + (1.0 - opt.b2) * g * g
        update = (m32 / (1.0 - opt.b1 ** t)) / (jnp.sqrt(v32 / (1.0 - opt.b2 ** t)) + opt.eps)
        if opt.weight_decay:
            update = update + opt.weight_decay * p.astype(F32)
        new_p = (p.astype(F32) - opt.lr * update).astype(p.dtype)
        small = g if g.size <= SMALL_LEAF else jnp.zeros((), F32)
        return new_p, m32.astype(m.dtype), v32.astype(v.dtype), jnp.sqrt(jnp.sum(g * g)), small

    out = jax.tree.map(leaf, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), loss, pick(3), pick(4)


def leaf_norms(tree: Any) -> Any:
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))), tree)
