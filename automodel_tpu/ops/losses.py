"""Loss functions.

Parity with the reference loss zoo (components/loss/): MaskedCrossEntropy
(masked_ce.py:22), ChunkedCrossEntropy (chunked_ce.py:43), and
FusedLinearCrossEntropy (linear_ce.py:119 — cut-cross-entropy that never
materializes full logits). TPU-native formulations:

- masked CE: one fused XLA softmax-CE over fp32 logits.
- chunked CE: lax.scan over vocab— no wait, over sequence chunks, so the
  [tokens, vocab] logits buffer never exceeds chunk_size×vocab.
- linear CE: the chunked formulation but taking hidden states + lm_head and
  doing the final projection inside the chunk loop — the memory win of
  cut-cross-entropy without a custom kernel, letting XLA fuse projection and
  log-softmax per chunk. It carries its own differentiation rule: the same
  loop forms dlogits, dH and dW while a chunk's logits are live (three
  vocabulary-wide products a step, none recomputed), and its chunk width
  comes from the shapes (``_chunk_count``).

All losses return (summed_loss, num_valid_tokens) so callers can normalize
globally across the dp_cp mesh group (reference: reduce_loss,
distributed/utils.py:185) — per-token mean requires the GLOBAL token count.

Labels use the HF convention: ignore_index (-100) marks padding; callers
pre-shift labels for next-token prediction.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

IGNORE_INDEX = -100


def _usable_chunks(t: int, requested: int) -> int:
    """Largest divisor of t that is <= requested; warns (at trace time) when
    the memory bound degrades from what the caller asked for."""
    nc = 1
    for d in range(min(requested, t), 0, -1):
        if t % d == 0:
            nc = d
            break
    if nc != requested:
        logger.warning(
            "token count %d not divisible by num_chunks=%d; using %d chunks "
            "(pad the batch for the full memory bound)", t, requested, nc,
        )
    return nc


def _ce_rows(logits: jnp.ndarray, labels: jnp.ndarray):
    """(valid [T], logsumexp [T], loss [T], zero where not valid) of logits
    [T, V] (any float dtype) against labels [T]."""
    valid = labels != IGNORE_INDEX
    safe_labels = jnp.where(valid, labels, 0)
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    picked = jnp.take_along_axis(logits32, safe_labels[:, None], axis=-1)[:, 0]
    return valid, lse, jnp.where(valid, lse - picked, 0.0)


def _ce_sum(logits: jnp.ndarray, labels: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Summed CE over valid tokens. logits [T, V] (any float dtype), labels [T]."""
    valid, _, loss = _ce_rows(logits, labels)
    return loss.sum(), valid.sum()


def masked_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(sum_loss, n_valid). logits [..., V], labels [...]."""
    v = logits.shape[-1]
    return _ce_sum(logits.reshape(-1, v), labels.reshape(-1))


def chunked_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, num_chunks: int = 8
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """CE over token chunks; bounds the fp32 logits working set.

    Token count must be divisible by num_chunks (pad batches accordingly).
    """
    v = logits.shape[-1]
    flat_logits = logits.reshape(-1, v)
    flat_labels = labels.reshape(-1)
    t = flat_logits.shape[0]
    num_chunks = _usable_chunks(t, num_chunks)
    flat_logits = flat_logits.reshape(num_chunks, t // num_chunks, v)
    flat_labels = flat_labels.reshape(num_chunks, t // num_chunks)

    # checkpoint the body: scan's AD otherwise STACKS each chunk's fp32
    # softmax residuals across iterations — a [chunks, chunk_t, V] buffer
    # that exceeds the unchunked working set it was meant to avoid
    @jax.checkpoint
    def body(carry, chunk):
        lg, lb = chunk
        s, n = _ce_sum(lg, lb)
        return (carry[0] + s, carry[1] + n), None

    (loss, n), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.int32(0)), (flat_logits, flat_labels))
    return loss, n


# Token-chunk width of the fused linear CE, from the shapes (no setting).
# A chunk's weight-gradient product is 2*Tc*D*V FLOPs and drags the carried
# [D, V] accumulator through memory once (read + write: 2*D*V*itemsize bytes),
# so product / traffic = Tc / itemsize FLOPs a byte whatever D and V are. A
# TPU's peaks sit at 166-560 FLOPs a byte (v5e 197e12 / 819e9 = 240): at 512
# bf16 tokens a chunk the two are the same size and the traffic cannot hide.
# Timed on the v5e at D 2048, V 151,936, bf16 (PERF.md, PR 31), the dW
# instruction a step: 38.3 ms at 512 tokens, 29.8 at 1024, 32.6 at 2048, 29.5
# at 4096 against 25.9 for the product alone: twice the v5e's ratio hides it.
_CHUNK_TOKENS_PER_ACC_BYTE = 512
# ... capped by what a chunk keeps live: its f32 logits and the compute-dtype
# dlogits, (4 + itemsize) * Tc * V bytes (0.93 GB at 1024 bf16 tokens and that
# vocabulary). 2 GiB is what a one-layer 30B-A3B step leaves on a 16 GB chip;
# it binds for an f32 head over 131 k entries or a bf16 one over 349 k.
_CHUNK_LIVE_BYTES = 2 << 30


def _chunk_count(t: int, v: int, compute_dtype, acc_dtype) -> int:
    """Token chunks of the fused linear CE for T tokens over a V vocabulary:
    the fewest that divide T with a chunk inside both bounds. A T with no such
    divisor within twice that count (a prime) is left to ``_usable_chunks``:
    fewer, larger chunks and its warning."""
    wanted = _CHUNK_TOKENS_PER_ACC_BYTE * jnp.dtype(acc_dtype).itemsize
    fits = _CHUNK_LIVE_BYTES // ((4 + jnp.dtype(compute_dtype).itemsize) * v)
    need = -(-t // max(1, min(wanted, fits)))
    return next((n for n in range(need, min(t, 2 * need) + 1) if t % n == 0), need)


def _chunk_logits(h, kernel, soft_cap):
    """f32 (capped) logits of one chunk, and tanh of the raw ones under a cap."""
    z = (h @ kernel).astype(jnp.float32)
    if soft_cap is None:
        return z, None
    th = jnp.tanh(z / soft_cap)
    return soft_cap * th, th


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ce(h, kernel, labels, soft_cap):
    """h [chunks, Tc, D], kernel [D, V], labels [chunks, Tc] -> (sum, count).
    Undifferentiated: one product a chunk, nothing saved."""

    def body(carry, chunk):
        s, n = _ce_sum(_chunk_logits(chunk[0], kernel, soft_cap)[0], chunk[1])
        return (carry[0] + s, carry[1] + n), None

    return jax.lax.scan(body, (jnp.float32(0.0), jnp.int32(0)), (h, labels))[0]


def _fused_ce_fwd(h, kernel, labels, soft_cap):
    """The loss is the last op of the forward, so its backward starts when it
    ends: form each chunk's dlogits = softmax - onehot while its logits are
    live, and from them dH (stacked) and dW (carried). Residuals are the two
    gradients of the loss SUM; nothing of size T x V outlives a chunk."""
    want_dh, want_dw = h.perturbed, kernel.perturbed
    h, kernel, labels = h.value, kernel.value, labels.value
    dw0 = jnp.zeros(kernel.shape, kernel.dtype) if want_dw else None

    def body(carry, chunk):
        (s, n), dw = carry
        hc, lb = chunk
        z, th = _chunk_logits(hc, kernel, soft_cap)
        valid, lse, loss = _ce_rows(z, lb)
        out = (s + loss.sum(), n + valid.sum())
        # IGNORE_INDEX matches no column; its rows are zeroed whole
        hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) == lb[:, None]
        dz = jnp.where(valid[:, None], jnp.exp(z - lse[:, None]) - hit, 0.0)
        if th is not None:
            dz = dz * (1.0 - th * th)
        dz = dz.astype(jnp.result_type(hc.dtype, kernel.dtype))  # as autodiff casts it
        dh = None
        if want_dh:
            dh = jax.lax.dot_general(
                dz, kernel, (((1,), (1,)), ((), ())), preferred_element_type=hc.dtype)
        if want_dw:
            dw = dw + jax.lax.dot_general(
                hc, dz, (((0,), (0,)), ((), ())), preferred_element_type=dw.dtype)
        return (out, dw), dh

    (out, dw), dh = jax.lax.scan(
        body, ((jnp.float32(0.0), jnp.int32(0)), dw0), (h, labels))
    return out, (dh, dw)


def _fused_ce_bwd(soft_cap, res, cts):
    """Both residuals times the scalar cotangent of the loss sum (the count
    carries none): one pass over [T, D] and one over [D, V]."""
    dh, dw = res
    g = cts[0]
    dh = None if dh is None else dh * g.astype(dh.dtype)
    dw = None if dw is None else dw * g.astype(dw.dtype)
    return dh, dw, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd, symbolic_zeros=True)


def fused_linear_cross_entropy(
    hidden: jnp.ndarray,
    lm_head_kernel: jnp.ndarray,
    labels: jnp.ndarray,
    num_chunks: int | None = None,
    logits_soft_cap: float | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """CE from hidden states + lm_head without materializing [T, V] logits.

    hidden [..., D], lm_head_kernel [D, V], labels [...]. The projection runs
    inside a scan over token chunks, so peak memory is chunk x V (reference
    capability: FusedLinearCrossEntropy via cut-cross-entropy,
    loss/linear_ce.py:119). The function carries its own differentiation rule
    (``_fused_ce_fwd``): three [T, D] x [D, V]-sized products a step (logits,
    dH, dW) and no recomputed one. The chunk count follows T, V and the dtypes
    (``_chunk_count``) unless ``num_chunks`` is given.
    """
    d = hidden.shape[-1]
    t = labels.size
    if num_chunks is None:
        num_chunks = _chunk_count(
            t, lm_head_kernel.shape[-1],
            jnp.result_type(hidden.dtype, lm_head_kernel.dtype), lm_head_kernel.dtype)
    num_chunks = _usable_chunks(t, num_chunks)
    return _fused_ce(
        hidden.reshape(num_chunks, t // num_chunks, d), lm_head_kernel,
        labels.reshape(num_chunks, t // num_chunks), logits_soft_cap)


def vocab_parallel_cross_entropy(
    hidden: jnp.ndarray,
    lm_head_kernel: jnp.ndarray,
    labels: jnp.ndarray,
    mesh_ctx,
    logits_soft_cap: float | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """TP loss-parallel CE: the lm_head projection AND the softmax run with
    the vocab dim sharded over the ``tensor`` axis — full [T, V] logits never
    exist on any device (reference: TEParallelCrossEntropy,
    loss/te_parallel_ce.py:113 over Triton online-softmax kernels; here a
    shard_map online softmax with psum/pmax collectives over ICI).

    hidden [..., D] (replicated over tensor), lm_head_kernel [D, V] sharded
    on V, labels [...]. Returns (loss_sum fp32, n_valid) replicated.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = mesh_ctx.mesh
    tp = mesh.shape["tp"]
    d = hidden.shape[-1]
    flat_h = hidden.reshape(-1, d)
    flat_labels = labels.reshape(-1)
    if tp == 1:
        return fused_linear_cross_entropy(
            hidden, lm_head_kernel, labels, logits_soft_cap=logits_soft_cap
        )

    def body(h, kern, lb):
        # local shard: kern [D, V/tp]
        vl = kern.shape[-1]
        logits = (h @ kern).astype(jnp.float32)
        if logits_soft_cap is not None:
            logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
        # max shift is gradient-free (lse is invariant to it) and pmax has
        # no differentiation rule — stop the gradient BEFORE pmax so the
        # collective only ever sees constants
        m = jax.lax.pmax(jax.lax.stop_gradient(logits.max(-1)), "tp")  # [T]
        z = jax.lax.psum(jnp.exp(logits - m[:, None]).sum(-1), "tp")
        lse = jnp.log(z) + m
        off = jax.lax.axis_index("tp") * vl
        local = (lb >= off) & (lb < off + vl)
        idx = jnp.clip(lb - off, 0, vl - 1)
        picked = jnp.take_along_axis(logits, idx[:, None], 1)[:, 0]
        correct = jax.lax.psum(jnp.where(local, picked, 0.0), "tp")
        valid = lb != IGNORE_INDEX
        loss = jnp.where(valid, lse - correct, 0.0)
        # post-psum the value is identical on every tp shard; out_specs must
        # name the manual axis, so return [1]-per-shard and slice one copy
        return loss.sum()[None], valid.sum(dtype=jnp.int32)[None]

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(None, "tp"), P()),
        out_specs=(P("tp"), P("tp")),
        axis_names={"tp"},
        check_vma=False,
    )
    # partial-manual shard_map only traces under jit; harmless inside an
    # outer jit (the train step), makes eager calls work too
    loss, n = jax.jit(mapped)(flat_h, lm_head_kernel, flat_labels)
    return loss[0], n[0]


def kd_loss(
    student_logits: jnp.ndarray,
    teacher_logits: jnp.ndarray,
    labels: jnp.ndarray,
    temperature: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Forward-KL knowledge distillation (reference: loss/kd_loss.py:21).

    Returns (sum over valid tokens of KL(teacher || student), n_valid).
    """
    v = student_logits.shape[-1]
    s = student_logits.reshape(-1, v).astype(jnp.float32) / temperature
    t = teacher_logits.reshape(-1, v).astype(jnp.float32) / temperature
    lb = labels.reshape(-1)
    valid = lb != IGNORE_INDEX
    t_logp = jax.nn.log_softmax(t, axis=-1)
    s_logp = jax.nn.log_softmax(s, axis=-1)
    kl = jnp.sum(jnp.exp(t_logp) * (t_logp - s_logp), axis=-1) * (temperature**2)
    return jnp.where(valid, kl, 0.0).sum(), valid.sum()


def nemotron_parse_cross_entropy(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    coordinate_weight: float = 10.0,
    class_token_start_idx: int = 50000,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Coordinate-weighted CE (reference NemotronParseLoss,
    models/nemotron_parse/nemotron_parse_loss.py:21-122): tokens with label
    id >= class_token_start_idx (bbox coordinate/class tokens in the OCR
    vocab) get their per-token loss multiplied by coordinate_weight; the sum
    is normalized by the UNWEIGHTED valid-token count (the reference divides
    by valid_tokens / num_label_tokens, both plain counts). Returns
    (weighted sum, n_valid) in the framework's standard loss contract."""
    v = logits.shape[-1]
    flat = logits.reshape(-1, v).astype(jnp.float32)
    lb = labels.reshape(-1)
    valid = lb != IGNORE_INDEX
    safe = jnp.where(valid, lb, 0)
    lse = jax.nn.logsumexp(flat, axis=-1)
    picked = jnp.take_along_axis(flat, safe[:, None], axis=-1)[:, 0]
    per_tok = jnp.where(valid, lse - picked, 0.0)
    w = jnp.where(lb >= class_token_start_idx, coordinate_weight, 1.0).astype(
        jnp.float32
    )
    return (per_tok * w).sum(), valid.sum()


LOSS_REGISTRY = {
    "masked_ce": masked_cross_entropy,
    "chunked_ce": chunked_cross_entropy,
    "fused_linear_ce": fused_linear_cross_entropy,
    "kd": kd_loss,
    "nemotron_parse": nemotron_parse_cross_entropy,
}


def build_loss(name: str = "masked_ce", **kwargs):
    fn = LOSS_REGISTRY[name]
    return functools.partial(fn, **kwargs) if kwargs else fn
