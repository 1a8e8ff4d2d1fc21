"""The plain reference for Sarvam-105B (sarvamai/sarvam-105b, ``model_type``
``sarvam_mla``): latent attention without q compression over sigmoid-routed
experts with a selection bias and one shared expert.

Straight ``jax.numpy`` in float32 with matmuls at ``highest``: no kernel, NO
CACHE and NO ABSORPTION (every key and value is expanded from its latent row,
every position in one causal pass), and nothing of the program imported.
``RMS(x; w) = w * x / sqrt(mean(x^2) + eps)``, no bias on any projection,
untied head. For a token at position ``t`` with residual ``h``, layer ``l``:

    x  = RMS(h; input_layernorm)
    q  = x W_q                      -> heads x (nope + rope): q_nope | q_rope
    ckv = x W_kva                   -> latent + rope
    c  = RMS(ckv[:latent]; kv_a_layernorm);  k_rope = ckv[latent:]  (ONE head, shared)
    rotary on q_rope and k_rope at t: pairs (x_2i, x_2i+1) turned by t * inv_freq_i,
        YaRN's frequencies (below); cos and sin carry no factor
    per head j: [k_nope_j | v_j] = c W_kvb,j
    score = scale * (q_nope_j . k_nope_j(s) + q_rope_j . k_rope(s)),  causal softmax over s <= t
    scale = (nope + rope)^-0.5 * (0.1 mscale_all_dim ln(factor) + 1)^2
    h += concat_j(sum_s p v_j(s)) W_o
    x' = RMS(h; post_attention_layernorm)
    the first ``first_k_dense_replace`` layers:  h += SwiGLU(x')
    the others: s = sigmoid(x' W_r) over ALL the published experts; the top-k of
        s + bias (the bias selects only); w = routed_scaling_factor * s_sel / sum s_sel;
        h += sum_e w_e SwiGLU_e(x') + SwiGLU_shared(x')

then the final RMS and the head. (The program de-interleaves the rotary pairs
first, which permutes the rotated channels of q and k alike and leaves every
score as it is.)

**This chip's share.** The configuration holds the experts ``held_experts`` =
[lo, hi) of the published ``published_experts`` and rows [0, vocab) of the
vocabulary. The router keeps its published width; a pick outside the range
adds nothing here, exactly as in the program. ``layer_output`` with another
range gives another rank's part, and ``shared=False`` leaves the shared expert
to one of them: the tests add eight ranks' parts up to the uncut layer.

Departures from a textbook loop, each for memory or time only: attention
takes its heads eight at a time and its queries in blocks (8,192 rows fit
beside 6.36 GB of bf16 weights); each held expert multiplies every row with a
combine weight of zero where the row did not pick it; the head runs on the
rows that are read alone. Weights arrive in the type the configuration serves
them in (bf16) and are upcast where they are used.

``precision`` puts the reference in the program's place at a lower precision
(the control the cell's limits are set against): ``bf16`` / ``fp8`` round both
operands of every matmul (float32 accumulation, straight-through). The router
and the norms are float32 at every precision.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.reference import adam
from benchmarks.reference.adam import init_moments  # noqa: F401  (the interface's)

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
PUBLISHED_EXPERTS = 128  # sarvamai/sarvam-105b config.json: num_experts


@dataclasses.dataclass(frozen=True)
class SarvamSpec:
    layers: int
    dense_layers: int
    expert_width: int
    published_experts: int
    held: tuple  # [lo, hi)
    top_k: int
    route_scale: float
    heads: int
    nope: int
    pe: int
    v_dim: int
    latent: int
    rms_eps: float
    rope_theta: float
    yarn: Optional[tuple]  # (factor, original positions, beta_fast, beta_slow, mscale, mscale_all_dim)


def _held(hf: dict, mapping: dict) -> tuple[int, tuple]:
    """(published experts, the held range): the file's ``num_experts`` counts
    the experts held HERE; its ``reference`` block states the published count
    and the range (absent: every expert is here)."""
    n = int(hf["num_experts"])
    published = int(mapping.get("published_experts", n))
    held = tuple(mapping.get("held_experts", (0, n)))
    if held[1] - held[0] != n or not 0 <= held[0] < held[1] <= published:
        raise ValueError(f"held_experts {held} of {published}: num_experts says {n}")
    return published, held


def _yarn(hf: dict) -> Optional[tuple]:
    rs = hf.get("rope_scaling")
    if not rs:
        return None
    if rs.get("type", rs.get("rope_type")) not in ("deepseek_yarn", "yarn"):
        raise ValueError(f"rope_scaling {rs!r}: this reference has YaRN or none")
    return (float(rs["factor"]), int(rs["original_max_position_embeddings"]),
            float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
            float(rs.get("mscale", 1)), float(rs.get("mscale_all_dim", 0)))


def spec(hf: dict, mapping: dict) -> SarvamSpec:
    if hf.get("q_lora_rank"):
        raise ValueError("this reference is the latent block WITHOUT q compression")
    if int(hf.get("num_shared_experts", 0)) != 1:
        raise ValueError("one shared expert, as published")
    if hf.get("tie_word_embeddings"):
        raise ValueError("an untied head, as published")
    published, held = _held(hf, mapping)
    return SarvamSpec(
        layers=int(hf["num_hidden_layers"]), dense_layers=int(hf["first_k_dense_replace"]),
        expert_width=int(hf["moe_intermediate_size"]),
        published_experts=published, held=held, top_k=int(hf["num_experts_per_tok"]),
        route_scale=float(hf["routed_scaling_factor"]),
        heads=int(hf["num_attention_heads"]),
        nope=int(hf["qk_nope_head_dim"]), pe=int(hf["qk_rope_head_dim"]),
        v_dim=int(hf["v_head_dim"]), latent=int(hf["kv_lora_rank"]),
        rms_eps=float(hf["rms_norm_eps"]),
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


# -- rotary positions with YaRN -----------------------------------------------------
def rotary_angles(S: int, spec: SarvamSpec) -> jnp.ndarray:
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


def softmax_scale(spec: SarvamSpec) -> float:
    scale = (spec.nope + spec.pe) ** -0.5
    if spec.yarn is not None and spec.yarn[0] > 1.0 and spec.yarn[5]:
        m = 0.1 * spec.yarn[5] * math.log(spec.yarn[0]) + 1.0
        scale *= m * m
    return scale


# -- the mixer (one sequence [S, D] in, the branch's output out) ----------------------
HEAD_GROUP = 8  # heads taken together (memory only: heads are independent)


def mla(h, lp, spec: SarvamSpec, precision: str, q_block: int):
    """The EXPANDED equations: every position's keys and values from its own
    latent row, one causal pass, nothing cached and nothing absorbed."""
    S, D = h.shape
    N, nope, pe, vd = spec.heads, spec.nope, spec.pe, spec.v_dim
    ng = min(HEAD_GROUP, N)  # heads a group
    n = N // ng
    x = rms_norm(h, lp["attn_norm"], spec.rms_eps)
    ckv = _mm("sd,de->se", x, lp["kv_a"], precision)
    c = rms_norm(ckv[:, :spec.latent], lp["kv_a_norm"], spec.rms_eps)
    angles = rotary_angles(S, spec)
    k_pe = rotate_pairs(ckv[:, None, spec.latent:], angles)[:, 0]  # one key head, shared
    q_block = min(q_block, S)
    pad = (-S) % q_block
    row0 = jnp.arange((S + pad) // q_block) * q_block
    kpos = jnp.arange(S)
    cols = lambda w, width: jnp.moveaxis(w.reshape(w.shape[0], n, ng * width), 1, 0)
    groups = {"q": cols(lp["q"], nope + pe), "kv_b": cols(lp["kv_b"], nope + vd),
              "o": lp["o"].reshape(n, ng * vd, D)}
    scale = softmax_scale(spec)

    def group(w):
        q = _mm("sd,de->se", x, w["q"], precision).reshape(S, ng, nope + pe)
        q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], angles)], axis=-1)
        kv = _mm("sr,re->se", c, w["kv_b"], precision).reshape(S, ng, nope + vd)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (S, ng, pe))], axis=-1)
        v = kv[..., nope:]
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, q_block, ng, nope + pe)

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
def _swiglu(x, gate, up, down, precision: str):
    mid = jax.nn.silu(_mm("td,di->ti", x, gate, precision)) * _mm("td,di->ti", x, up, precision)
    return _mm("ti,id->td", mid, down, precision)


def route(x, lp, spec: SarvamSpec):
    """-> combine weights [T, published experts], zero where an expert was
    not picked. The router is float32 at every precision."""
    logits = jnp.einsum("td,de->te", x.astype(F32), lp["router"].astype(F32), precision=HI)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + lp["router_bias"].astype(F32), spec.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    w = spec.route_scale * w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-20)
    return jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], idx].set(w)


def mlp(h, lp, spec: SarvamSpec, precision: str, shared: bool = True):
    """The feed-forward branch on rows ``h`` [T, D]: the dense SwiGLU, or the
    part of the routed sum the experts ``spec.held`` give (``lp["gate_up"]``
    holds exactly those) plus, with ``shared``, the shared expert."""
    x = rms_norm(h, lp["mlp_norm"], spec.rms_eps)
    if "router" not in lp:
        return _swiglu(x, lp["gate"], lp["up"], lp["down"], precision)
    lo, hi = spec.held
    cw = route(x, lp, spec)[:, lo:hi]  # the held experts' columns; the rest is other chips'
    I = spec.expert_width

    def expert(acc, args):
        gate_up, down, w = args
        return acc + w[:, None] * _swiglu(x, gate_up[:, :I], gate_up[:, I:], down, precision), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), (lp["gate_up"], lp["down"], cw.T))
    if shared:
        out = out + _swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"], precision)
    return out


# -- the model ---------------------------------------------------------------------------
def hidden_states(params, ids, spec: SarvamSpec, precision="f32", q_block=512):
    """One sequence ``ids`` [S] -> final-norm hidden rows [S, D]."""
    h = params["embed"][ids].astype(F32)  # gather, then upcast: no float32 copy of the table
    for lp in params["layers"]:
        h = h + mla(h, lp, spec, precision, q_block)
        h = h + mlp(h, lp, spec, precision)
    return rms_norm(h, params["final_norm"], spec.rms_eps)


@functools.partial(jax.jit, static_argnames=("spec", "precision", "n_rows"))
def rows_logits(params, ids, row_start, spec, precision="f32", n_rows=1):
    """Logits [n_rows, V] of one sequence's rows from ``row_start`` on. ``ids``
    may be padded at its end: no row sees a later one. ONE causal forward over
    the prompt with its served tokens: what the program computed by chunks of
    512 against a latent prefix and then token by token, absorbed."""
    h = hidden_states(params, ids, spec, precision)
    rows = jax.lax.dynamic_slice_in_dim(h, row_start, n_rows, axis=0)
    return _mm("sd,dv->sv", rows, params["head"], precision)


@functools.partial(jax.jit, static_argnames=("spec", "opt", "precision"),
                   donate_argnums=(0, 1, 2))
def train_step(params, mu, nu, step, ids, labels, spec, opt, precision="f32"):
    """loss = mean CE over the labels that count, then ``adam.step`` (the
    interface's training entry; the family's cell serves, small tests use it)."""
    def mean_loss(p):
        h = jax.vmap(lambda row: hidden_states(p, row, spec, precision))(ids)
        logits = _mm("bsd,dv->bsv", h, p["head"], precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        keep = labels >= 0
        return jnp.sum(jnp.where(keep, lse - picked, 0.0)) / jnp.maximum(keep.sum(), 1).astype(F32)

    return adam.step(mean_loss, params, mu, nu, step, opt)


# -- the program's parameter tree and this one -----------------------------------
# reference leaf -> the program's leaf under ``layers/<NN>/``
LAYER_LEAF_NAMES = {
    "attn_norm": "input_norm/scale", "mlp_norm": "post_attn_norm/scale",
    "q": "attn/q_proj/kernel", "kv_a": "attn/kv_a_proj/kernel",
    "kv_a_norm": "attn/kv_a_norm/scale", "kv_b": "attn/kv_b_proj/kernel",
    "o": "attn/o_proj/kernel",
    "gate": "mlp/gate_proj/kernel", "up": "mlp/up_proj/kernel", "down_dense": "mlp/down_proj/kernel",
    "router": "moe/router/weight", "router_bias": "moe/router/bias",
    "gate_up": "moe/experts/gate_up", "down": "moe/experts/down",
    "shared_gate": "moe/shared/gate_proj/kernel", "shared_up": "moe/shared/up_proj/kernel",
    "shared_down": "moe/shared/down_proj/kernel",
}


def _leaf(node: dict, path: str):
    for key in path.split("/"):
        if key not in node:
            return None
        node = node[key]
    return node


def to_reference(tree: dict) -> dict:
    """The program's tree (layers unstacked under ``layers/<NN>``) -> this
    module's: a list of layers in order, short names (a dense layer's ``down``
    is its MLP's). Only a restructure: every number stays as
    ``harness/weights.py`` drew it."""
    layers = []
    for name in sorted(tree["layers"]):
        lp = {key: _leaf(tree["layers"][name], path) for key, path in LAYER_LEAF_NAMES.items()}
        lp = {key: leaf for key, leaf in lp.items() if leaf is not None}
        if "down_dense" in lp:
            lp["down"] = lp.pop("down_dense")
        layers.append(lp)
    return {"embed": tree["embed"]["embedding"], "final_norm": tree["final_norm"]["scale"],
            "head": tree["lm_head"]["kernel"], "layers": layers}


def program_names(ref_tree: dict) -> dict:
    """``to_reference``'s way back: each leaf -> (program leaf name, None):
    the program's layers are not stacked, so no leaf has a layer index."""
    def name(lp: dict, key: str) -> str:
        dense_down = key == "down" and "router" not in lp
        return LAYER_LEAF_NAMES["down_dense" if dense_down else key]

    return {
        "embed": ("embed/embedding", None), "final_norm": ("final_norm/scale", None),
        "head": ("lm_head/kernel", None),
        "layers": [{key: (f"layers/{i:02d}/{name(lp, key)}", None) for key in lp}
                   for i, lp in enumerate(ref_tree["layers"])],
    }


# -- the counts the kernel laws and the roofline readers need ------------------------
def _program_has_the_family() -> bool:
    """A path probe (nothing of the program is imported): a checkout whose
    program lacks the family (the parent commit with this benchmark laid over
    it) must fail in ``loader.load_cell``, at once. Its registry would
    otherwise fall back to the generic llama family and serve SOMETHING."""
    return (Path(__file__).resolve().parents[2] / "automodel_tpu" / "models" / "sarvam_mla").is_dir()


def shapes(hf: dict) -> dict:
    """Layers by kind and the per-token law. ``hf["num_experts"]`` counts the
    experts HELD here, ``PUBLISHED_EXPERTS`` those the router picks over: a
    token's expected held picks are ``top_k * held / published`` (all ``top_k``
    at the uncut configuration)."""
    if not _program_has_the_family():
        raise ValueError("the program around this benchmark has no automodel_tpu/models/sarvam_mla: "
                         "it cannot run a sarvam_mla configuration")
    d, vocab = int(hf["hidden_size"]), int(hf["vocab_size"])
    L, n_dense = int(hf["num_hidden_layers"]), int(hf["first_k_dense_replace"])
    N = int(hf["num_attention_heads"])
    nope, pe, vd = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"]), int(hf["v_head_dim"])
    latent = int(hf["kv_lora_rank"])
    width, dense_width = int(hf["moe_intermediate_size"]), int(hf["intermediate_size"])
    top_k, held = int(hf["num_experts_per_tok"]), int(hf["num_experts"])
    published = max(PUBLISHED_EXPERTS, held)
    n_moe = L - n_dense
    mla_proj = d * N * (nope + pe) + d * (latent + pe) + latent * N * (nope + vd) + N * vd * d
    expert = 3 * d * width

    def forward_flops_per_token(seq_len: int) -> float:
        attn = 2 * N * (nope + pe + vd) * (seq_len / 2)  # QK^T and PV over the causal half, expanded
        picks = top_k * held / published  # expected held picks a token
        moe = 2 * (expert * (picks + 1) + d * published)  # held picks, the shared expert, the router
        return (L * (2 * mla_proj + attn) + n_dense * 2 * 3 * d * dense_width + n_moe * moe
                + 2 * d * vocab)

    def parameter_count(active: bool = False) -> int:
        """Every parameter held here; ``active``: those one token uses (its
        picked experts, not all held; one embedding row is not counted, as a
        published active figure does not)."""
        experts = (top_k if active else held) * expert
        moe = experts + expert + d * published + published  # + shared, router, its bias
        block = mla_proj + latent + 2 * d  # the projections, kv_a_norm, two layer norms
        return (L * block + n_dense * 3 * d * dense_width + n_moe * moe
                + (1 if active else 2) * d * vocab + d)

    return {
        "vocab": vocab, "hidden": d,
        "latent_layers": L, "latent_width": latent + pe, "latent_rank": latent,
        "attention_layers": L, "q_heads": N, "qk_dim": nope + pe, "v_dim": vd,
        "expert_layers": n_moe, "top_k": top_k, "expert_width": width,
        "held_experts": held, "published_experts": published,
        "forward_flops_per_token": forward_flops_per_token,
        "parameter_count": parameter_count,
    }
