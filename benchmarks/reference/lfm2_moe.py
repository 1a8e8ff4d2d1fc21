"""The plain reference for LFM2-MoE (LiquidAI LFM2-8B-A1B, HF ``Lfm2MoeForCausalLM``).

Straight ``jax.numpy`` in float32 with matmuls at ``highest``: no Pallas
kernel, no cache, no paging, no state carried between calls, and nothing of
the program imported. ``RMS(x; w) = w * x / sqrt(mean(x^2) + eps)`` (weight not
zero-centred), no bias anywhere. It follows the published block:

- layer ``i``: ``h = x + Op_i(RMS(x; operator_norm))``, then ``y = h +
  FF_i(RMS(h; ffn_norm))``; after the last layer ``RMS(y; embedding_norm)``
  (LFM2's name for the FINAL norm) and logits through the tied embedding;
- ``Op`` on a ``conv`` layer, the gated short convolution: ``[B, C, z] =
  split3(x W_in)`` in that order; ``u = B * z``; ``v_t = sum_j w[:, K-1-j] *
  u_{t-j}`` over the ``K = conv_L_cache`` taps (depthwise, causal, zeros
  before position 0); ``Op = (C * v) W_out``. The whole sequence goes through
  in one pass, so there is no state here to carry, reset or corrupt;
- ``Op`` on a ``full_attention`` layer: GQA with an RMS over each head's
  ``head_dim`` on q and k (``q_layernorm``, ``k_layernorm``) BEFORE rotary;
  rotary over the whole head, rotate-half; causal softmax at ``1 /
  sqrt(head_dim)``;
- ``FF``: SwiGLU ``w2(silu(w1 x) * w3 x)`` of width ``intermediate_size`` on
  the first ``num_dense_layers`` layers; on the rest ``s = sigmoid(x W_r)``,
  the experts chosen are the top-k of ``s + expert_bias``, their weights ``s``
  at the chosen (bias NOT in the weight) divided by (their sum + 1e-6), times
  ``routed_scaling_factor``; output = sum weight * SwiGLU_e(x). No shared
  expert.

Departures from a textbook loop, each for memory or time only: attention runs
in blocks of query rows; each expert multiplies only the rows routed to it
(rows sorted by expert into tiles, one expert a tile, no row dropped); the
head and the cross-entropy run in blocks of rows. Weights arrive in the type
the configuration serves them in (bf16) and are upcast where they are used.

``precision`` puts the reference in the program's place at a lower precision
(the control the cell's limits are set against): ``bf16`` rounds both operands
of every matmul to bfloat16, ``fp8`` to float8_e4m3 with one scale a tensor;
products accumulate in float32 and the rounding is straight-through. The
router and the conv's taps are float32 at every precision.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import adam
from benchmarks.reference.adam import init_moments  # noqa: F401  (the interface's)

F32 = jnp.float32
KINDS = ("conv", "full_attention")
TOPK_SUM_EPS = 1e-6  # HF: routing_weights / (routing_weights.sum(-1) + 1e-6)


@dataclasses.dataclass(frozen=True)
class Lfm2Spec:
    vocab_size: int
    hidden_size: int
    layer_types: tuple
    num_dense_layers: int
    dense_width: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    top_k: int
    expert_width: int
    conv_taps: int
    eps: float
    rope_theta: float
    route_scale: float


def _head_dim(hf: dict) -> int:
    return int(hf.get("head_dim") or int(hf["hidden_size"]) // int(hf["num_attention_heads"]))


def spec(hf: dict, mapping: dict) -> Lfm2Spec:
    """``hf``: the configuration file's own keys (the source's config.json
    names). ``mapping`` (the file's ``reference`` block) selects nothing
    here: the family has one published form."""
    layer_types = tuple(hf["layer_types"])
    if len(layer_types) != int(hf["num_hidden_layers"]) or set(layer_types) - set(KINDS):
        raise ValueError(f"layer_types {layer_types} for {hf['num_hidden_layers']} layers")
    if hf.get("conv_bias"):
        raise ValueError("conv_bias: the published model has none")
    return Lfm2Spec(
        vocab_size=int(hf["vocab_size"]), hidden_size=int(hf["hidden_size"]),
        layer_types=layer_types, num_dense_layers=int(hf["num_dense_layers"]),
        dense_width=int(hf["intermediate_size"]),
        num_heads=int(hf["num_attention_heads"]), num_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=_head_dim(hf), num_experts=int(hf["num_experts"]),
        top_k=int(hf["num_experts_per_tok"]), expert_width=int(hf["moe_intermediate_size"]),
        conv_taps=int(hf["conv_L_cache"]), eps=float(hf["norm_eps"]),
        rope_theta=float(hf["rope_theta"]), route_scale=float(hf["routed_scaling_factor"]),
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


def _mm(eq: str, a: jnp.ndarray, b: jnp.ndarray, precision: str) -> jnp.ndarray:
    return jnp.einsum(
        eq, _round(a.astype(F32), precision), _round(b.astype(F32), precision),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32,
    )


# -- the operators ---------------------------------------------------------------
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def rope(x: jnp.ndarray, positions: jnp.ndarray, spec: Lfm2Spec) -> jnp.ndarray:
    """x: [S, heads, head_dim]; rotate-half over the whole head."""
    d = spec.head_dim
    inv = 1.0 / (spec.rope_theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def short_conv(h, lp, spec: Lfm2Spec, precision: str):
    """The gated short convolution over one sequence. h: [S, D]."""
    S, D = h.shape
    K = spec.conv_taps
    x = rms_norm(h, lp["operator_norm"], spec.eps)
    bcz = _mm("sd,de->se", x, lp["in_proj"], precision)
    b, c, z = bcz[:, :D], bcz[:, D : 2 * D], bcz[:, 2 * D :]
    u = jnp.pad(b * z, ((K - 1, 0), (0, 0)))  # zeros before position 0
    taps = lp["taps"].astype(F32)  # [D, K]; tap K-1 multiplies the current position
    v = sum(taps[:, K - 1 - j][None, :] * u[K - 1 - j : K - 1 - j + S] for j in range(K))
    return h + _mm("sd,de->se", c * v, lp["out_proj"], precision)


def attention(h, lp, spec: Lfm2Spec, precision: str, q_block: int):
    """Causal GQA attention of one sequence. h: [S, D]."""
    S = h.shape[0]
    x = rms_norm(h, lp["operator_norm"], spec.eps)
    q = _mm("sd,de->se", x, lp["q"], precision).reshape(S, spec.num_heads, spec.head_dim)
    k = _mm("sd,de->se", x, lp["k"], precision).reshape(S, spec.num_kv_heads, spec.head_dim)
    v = _mm("sd,de->se", x, lp["v"], precision).reshape(S, spec.num_kv_heads, spec.head_dim)
    q = rope(rms_norm(q, lp["q_norm"], spec.eps), jnp.arange(S), spec)
    k = rope(rms_norm(k, lp["k_norm"], spec.eps), jnp.arange(S), spec)
    group = spec.num_heads // spec.num_kv_heads
    pad = (-S) % q_block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, q_block, spec.num_kv_heads, group, spec.head_dim
    )
    row0 = jnp.arange(qb.shape[0]) * q_block
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one_block(args):
        qi, r0 = args
        s = _mm("qkgh,skh->kgqs", qi, k, precision) / math.sqrt(spec.head_dim)
        rows = r0 + jnp.arange(q_block)
        s = jnp.where(kpos[None, :] <= rows[:, None], s, -jnp.inf)
        return _mm("kgqs,skh->qkgh", jax.nn.softmax(s, axis=-1), v, precision)

    out = jax.lax.map(one_block, (qb, row0))
    out = out.reshape(-1, spec.num_heads * spec.head_dim)[:S]
    return h + _mm("se,ed->sd", out, lp["o"], precision)


def dense_ff(h, lp, spec: Lfm2Spec, precision: str):
    x = rms_norm(h, lp["ffn_norm"], spec.eps)
    g = _mm("td,di->ti", x, lp["w1"], precision)
    u = _mm("td,di->ti", x, lp["w3"], precision)
    return h + _mm("ti,id->td", jax.nn.silu(g) * u, lp["w2"], precision)


def route(x, lp, spec: Lfm2Spec):
    """-> (expert ids [T, K], combine weights [T, K]); float32 at every precision."""
    logits = jnp.einsum("td,de->te", x.astype(F32), lp["router"].astype(F32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + lp["router_bias"].astype(F32), spec.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    return idx, spec.route_scale * w / (w.sum(axis=-1, keepdims=True) + TOPK_SUM_EPS)


def expert_ff(h, lp, spec: Lfm2Spec, precision: str):
    """h: [T, D] -> h + the routed expert MLPs. No row is ever dropped: the
    (row, expert) pairs are sorted by expert and laid out in tiles of R rows,
    each expert's group starting on a tile of its own, and every tile is
    multiplied by its one expert's weights."""
    T, D = h.shape
    E, K = spec.num_experts, spec.top_k
    x = rms_norm(h, lp["ffn_norm"], spec.eps)
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
    tile_expert = jnp.minimum(jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"), E - 1)
    xpad = jnp.concatenate([x, jnp.zeros((1, D), F32)])
    w_gu, w_down = lp["gate_up"], lp["down"]

    @jax.checkpoint
    def tile(args):
        tok, wt, e = args
        gu = _mm("rd,df->rf", xpad[tok], w_gu[e], precision)
        g, u = gu[:, : spec.expert_width], gu[:, spec.expert_width:]
        y = _mm("ri,id->rd", jax.nn.silu(g) * u, w_down[e], precision)
        return y * wt[:, None]

    y = jax.lax.map(tile, (src_token.reshape(n_tiles, R), src_w.reshape(n_tiles, R), tile_expert))
    return h + jnp.zeros((T + 1, D), F32).at[src_token].add(y.reshape(n_tiles * R, D))[:T]


def hidden_states(params, ids, spec: Lfm2Spec, precision="f32", q_block=512):
    """Sequences ``ids`` [B, S] -> final-norm hidden states [B, S, D]: every
    layer over the whole sequence, the operators sequence by sequence, the
    feed-forward over all rows at once."""
    B, S = ids.shape
    h = params["embed"][ids].astype(F32)  # gather, then upcast: no float32 copy of the table
    for kind, lp in zip(spec.layer_types, params["layers"]):
        if kind == "conv":
            h = jax.vmap(lambda hs: short_conv(hs, lp, spec, precision))(h)
        else:
            h = jax.vmap(lambda hs: attention(hs, lp, spec, precision, min(q_block, S)))(h)
        ff = dense_ff if "w1" in lp else expert_ff
        h = ff(h.reshape(B * S, -1), lp, spec, precision).reshape(B, S, -1)
    return rms_norm(h, params["final_norm"], spec.eps)


@functools.partial(jax.jit, static_argnames=("spec", "precision", "n_rows"))
def rows_logits(params, ids, row_start, spec, precision="f32", n_rows=1):
    """Logits [n_rows, V] of one sequence's rows from ``row_start`` on, through
    the tied embedding. ``ids`` may be padded at its end: no row sees a later
    one. ONE causal forward over the prompt with its served tokens: what the
    program computed by chunks and then token by token through two caches."""
    h = hidden_states(params, ids[None, :], spec, precision)[0]
    rows = jax.lax.dynamic_slice_in_dim(h, row_start, n_rows, axis=0)
    return _mm("sd,vd->sv", rows, params["embed"], precision)


def loss_sum(params, ids, labels, spec, precision="f32", row_block=1024):
    """Sum of next-token cross-entropies of a batch [B, S] over the labels
    that are not -100 (already shifted), and the count of those labels."""
    h = hidden_states(params, ids, spec, precision)
    h = h.reshape(-1, h.shape[-1])
    lab = labels.reshape(-1)
    pad = (-h.shape[0]) % row_block
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, row_block, h.shape[1])
    lb = jnp.pad(lab, (0, pad), constant_values=-100).reshape(-1, row_block)

    @jax.checkpoint
    def block(args):
        hh, ll = args
        logits = _mm("sd,vd->sv", hh, params["embed"], precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, jnp.maximum(ll, 0)[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.where(ll >= 0, lse - picked, 0.0))

    return jnp.sum(jax.lax.map(block, (hb, lb))), jnp.sum(labels >= 0)


@functools.partial(jax.jit, static_argnames=("spec", "opt", "precision"),
                   donate_argnums=(0, 1, 2))
def train_step(params, mu, nu, step, ids, labels, spec, opt, precision="f32"):
    """loss = mean CE over the labels that count, then ``adam.step``."""
    def mean_loss(p):
        total, n = loss_sum(p, ids, labels, spec, precision)
        return total / jnp.maximum(n, 1).astype(F32)

    return adam.step(mean_loss, params, mu, nu, step, opt)


# -- the program's parameter tree and this one -----------------------------------
# reference leaf -> the program's leaf under ``layers/<NN>/``
LAYER_LEAF_NAMES = {
    "operator_norm": "operator_norm/scale", "ffn_norm": "ffn_norm/scale",
    "in_proj": "conv/in_proj/kernel", "taps": "conv/weight", "out_proj": "conv/out_proj/kernel",
    "q": "attn/q_proj/kernel", "k": "attn/k_proj/kernel", "v": "attn/v_proj/kernel",
    "o": "attn/o_proj/kernel", "q_norm": "attn/q_norm/scale", "k_norm": "attn/k_norm/scale",
    "w1": "mlp/gate_proj/kernel", "w3": "mlp/up_proj/kernel", "w2": "mlp/down_proj/kernel",
    "router": "moe/router/weight", "router_bias": "moe/router/bias",
    "gate_up": "moe/experts/gate_up", "down": "moe/experts/down",
}


def _leaf(node: dict, path: str):
    for key in path.split("/"):
        if key not in node:
            return None
        node = node[key]
    return node


def to_reference(tree: dict) -> dict:
    """The program's tree (layers unstacked under ``layers/<NN>``) -> this
    module's: a list of layers in order, short names. Only a restructure:
    every number stays as ``harness/weights.py`` drew it."""
    if "lm_head" in tree:
        raise ValueError("this reference ties the head to the embedding")
    layers = []
    for name in sorted(tree["layers"]):
        lp = {key: _leaf(tree["layers"][name], path) for key, path in LAYER_LEAF_NAMES.items()}
        layers.append({key: leaf for key, leaf in lp.items() if leaf is not None})
    return {"embed": tree["embed"]["embedding"], "final_norm": tree["final_norm"]["scale"],
            "layers": layers}


def program_names(ref_tree: dict) -> dict:
    """``to_reference``'s way back: each leaf -> (program leaf name, None):
    the program's layers are not stacked, so no leaf has a layer index."""
    return {
        "embed": ("embed/embedding", None),
        "final_norm": ("final_norm/scale", None),
        "layers": [{key: (f"layers/{i:02d}/{LAYER_LEAF_NAMES[key]}", None) for key in lp}
                   for i, lp in enumerate(ref_tree["layers"])],
    }


# -- the counts the kernel laws and the roofline readers need --------------------
def shapes(hf: dict) -> dict:
    """Layers counted by KIND: ``conv`` layers keep no K/V and run no
    attention; the first ``num_dense_layers`` have no routed experts. The
    per-token law counts what a token computes here: both operators'
    projections, attention over the causal half, the experts it is routed to
    (and the router), the dense layers' MLP, the tied head."""
    d = int(hf["hidden_size"])
    head_dim = _head_dim(hf)
    q_heads, kv_heads = int(hf["num_attention_heads"]), int(hf["num_key_value_heads"])
    q_dim, kv_dim = q_heads * head_dim, kv_heads * head_dim
    layer_types = list(hf["layer_types"])
    n_attn = layer_types.count("full_attention")
    n_conv = layer_types.count("conv")
    n_dense = int(hf["num_dense_layers"])
    n_expert_layers = len(layer_types) - n_dense
    taps = int(hf["conv_L_cache"])
    width, dense_width = int(hf["moe_intermediate_size"]), int(hf["intermediate_size"])
    top_k, n_exp = int(hf["num_experts_per_tok"]), int(hf["num_experts"])
    vocab = int(hf["vocab_size"])
    conv_params = 3 * d * d + d * d + d * taps
    attn_params = d * (q_dim + 2 * kv_dim) + q_dim * d
    norms = 2 * d  # operator_norm, ffn_norm

    def forward_flops_per_token(seq_len: int) -> float:
        conv = 2 * conv_params + 2 * d  # projections, taps, the two gates
        attn = 2 * attn_params + 2 * 2 * q_dim * (seq_len / 2)  # QK^T and PV, causal half
        experts = 2 * (3 * d * width * top_k + d * n_exp)
        dense = 2 * 3 * d * dense_width
        return (n_conv * conv + n_attn * attn + n_expert_layers * experts + n_dense * dense
                + 2 * d * vocab)

    def parameter_count() -> int:
        expert_layer = n_exp * 3 * d * width + d * n_exp + n_exp  # experts, router, expert_bias
        return (n_conv * conv_params + n_attn * (attn_params + 2 * head_dim)
                + len(layer_types) * norms + n_expert_layers * expert_layer
                + n_dense * 3 * d * dense_width + d * vocab + d)

    return {
        "vocab": vocab, "hidden": d,
        "kv_layers": n_attn, "kv_heads": kv_heads, "head_dim": head_dim,
        "attention_layers": n_attn, "q_heads": q_heads,
        "expert_layers": n_expert_layers, "top_k": top_k, "expert_width": width,
        "conv_layers": n_conv, "conv_taps": taps,
        "forward_flops_per_token": forward_flops_per_token,
        "parameter_count": parameter_count,
    }
