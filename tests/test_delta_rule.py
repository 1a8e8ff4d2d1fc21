"""The chunked delta-rule operator (ops/delta_rule.py) against the recurrence
it stands for, token by token: forward and every gradient, through the Pallas
kernels in interpret mode and through the same body under ``lax.scan``."""

import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from automodel_tpu.ops.delta_rule import (
    G_MIN,
    _block_merge_inverse,
    _chunk_body,
    _chunk_grads,
    _chunk_rule,
    _unit_lower_inverse,
    chunked_delta_rule,
    l2norm,
)

B, H, DK, DV = 2, 2, 32, 16
F32 = jnp.float32
# float32 both sides: what differs is the order of the sums (one solve and a
# few products a chunk against one update a token). Measured 1e-6 forward and
# 2e-6 on the gradients at |g| ~ 1, 3e-5 at |g| ~ 4 (the factors exp(+-sum of
# g over 8 tokens) then span 1e-14..1e14 and float32 keeps 7 digits of each).
# A bfloat16 operand would read 4e-3.
TOL, TOL_FAST_DECAY = 2e-5, 2e-4


def recurrence(q, k, v, g, beta, segment_ids=None):
    """S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T; o_t = S_t^T q_t;
    S is zeroed where ``segment_ids`` changes. ``[B, S, H, d]`` operands, ``q``
    and ``k`` already normalised."""
    Bq, S, Hq, dk = q.shape
    if g.ndim == 3:
        g = jnp.broadcast_to(g[..., None], q.shape)
    starts = jnp.zeros((Bq, S), bool)
    if segment_ids is not None:
        prev = jnp.pad(segment_ids, ((0, 0), (1, 0)), constant_values=-1)[:, :S]
        starts = segment_ids != prev

    def step(state, x):
        qt, kt, vt, gt, bt, st = x
        state = jnp.where(st[:, None, None, None], 0.0, state) * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt))
        state = state + jnp.einsum("bhk,bhv->bhkv", kt, u)
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta, starts))
    _, o = jax.lax.scan(step, jnp.zeros((Bq, Hq, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def heads(x):
    """The operator's flat ``[B, S, H * d]`` -> ``[B, S, H, d]``."""
    return x.reshape(*x.shape[:2], H, -1)


def reference(q, k, v, g, beta, segment_ids=None, clamp=True):
    """The operator's contract, token by token: raw flat operands in, the
    norms, the scale and the clamp formed here in jnp, flat ``o`` out."""
    g = heads(g) if g.shape[-1] != H else g
    if clamp:
        g = jnp.clip(g, G_MIN, 0.0)
    o = recurrence(l2norm(heads(q)) * DK**-0.5, l2norm(heads(k)), heads(v), g, beta, segment_ids)
    return o.reshape(*q.shape[:2], -1)


def operands(S, per_channel, g_scale, seed=0, norms=(1.0, 1.0)):
    """Flat operands; a head's ``q`` and ``k`` rows have norms drawn
    log-uniformly from ``norms``."""
    ks = jax.random.split(jax.random.key(seed + S), 8)
    lo, hi = jnp.log(norms[0]), jnp.log(norms[1])
    size = lambda key: jnp.exp(jax.random.uniform(key, (B, S, H, 1), minval=lo, maxval=hi))
    q = (l2norm(jax.random.normal(ks[0], (B, S, H, DK))) * size(ks[6])).reshape(B, S, -1)
    k = (l2norm(jax.random.normal(ks[1], (B, S, H, DK))) * size(ks[7])).reshape(B, S, -1)
    v = jax.random.normal(ks[2], (B, S, H * DV))
    g = -g_scale * jax.nn.softplus(jax.random.normal(ks[3], (B, S, H * DK) if per_channel else (B, S, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, S, H * DV))


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


NAMES = "q k v g beta".split()


def run(fn, args, w):
    """(``fn``'s output, the gradients of ``sum(output * w)`` by its five
    operands), as ONE compiled program: called op by op, a case is some
    hundred programs of one primitive each."""
    def weighed(*a):
        o = fn(*a)
        return (o * w).sum(), o

    (_, o), grads = jax.jit(jax.value_and_grad(weighed, argnums=range(5), has_aux=True))(*args)
    return o, dict(zip(NAMES, grads))


# An ``interpret`` / ``scan`` pair has the same operands, and so the same
# answer of the token loop: it is computed once, filed under the operands' own
# bytes (one worker runs the file's cases in turn: ``--dist loadfile``)
_ANSWERS = {}


def run_once(name, fn, args, w, segment_ids=None):
    arrays = (*args, w) if segment_ids is None else (*args, w, segment_ids)
    key = (name, *(np.asarray(a).tobytes() for a in arrays))
    if key not in _ANSWERS:
        _ANSWERS[key] = run(lambda *a: fn(*a, segment_ids), args, w)
    return _ANSWERS[key]


def check(args, w, tol, segment_ids=None, **kw):
    op = lambda *a: chunked_delta_rule(*a, segment_ids=segment_ids, **kw)
    (o, got), (o_ref, want) = run(op, args, w), run_once("recurrence", reference, args, w, segment_ids)
    assert rel(o, o_ref) < tol
    for name in NAMES:
        assert rel(got[name], want[name]) < tol, name
    return got


@pytest.mark.parametrize("mode", ["interpret", "scan"])
@pytest.mark.parametrize("per_channel", [True, False], ids=["per_channel", "scalar"])
def test_forward_and_gradients_match_the_recurrence(mode, per_channel):
    args, w = operands(128, per_channel, 1.0)
    check(args, w, TOL, interpret=mode == "interpret")


@pytest.mark.parametrize("mode", ["interpret", "scan"])
@pytest.mark.parametrize("per_channel", [True, False], ids=["per_channel", "scalar"])
def test_raw_q_and_k_are_normalised_in_the_body(mode, per_channel):
    """Rows of norm 0.1 to 30 a head: the norm and its transpose are the
    operator's, so a unit-norm operand would hide a wrong one."""
    args, w = operands(128, per_channel, 1.0, norms=(0.1, 30.0))
    check(args, w, TOL, interpret=mode == "interpret")


@pytest.mark.parametrize("S", [200, 37], ids=["S200", "S37"])
def test_a_length_that_is_no_multiple_of_the_chunk(S):
    args, w = operands(S, True, 1.0)
    check(args, w, TOL, interpret=True)


def packed(S):
    # starts inside a chunk, on a chunk's first row, on adjacent tokens
    cuts = jnp.array([[0, 37, 38, 100], [0, 64, 129, 130]])
    return cuts, (jnp.arange(S)[None, :, None] >= cuts[:, None, :]).sum(-1)


@pytest.mark.parametrize("mode", ["interpret", "scan"])
def test_packed_documents_reset_the_state(mode):
    S = 200
    args, w = operands(S, True, 1.0)
    _, seg = packed(S)
    check(args, w, TOL, segment_ids=seg, interpret=mode == "interpret")
    # a document's output is what it would be alone
    op = jax.jit(lambda *a, seg=None: chunked_delta_rule(*a, segment_ids=seg, interpret=mode == "interpret"))
    got = op(*args, seg=seg)
    alone = op(*(a[:1, 38:100] for a in args))
    assert rel(got[:1, 38:100], alone) < TOL


@pytest.mark.parametrize("mode", ["interpret", "scan"])
@pytest.mark.parametrize("per_channel", [True, False], ids=["per_channel", "scalar"])
def test_no_gradient_through_a_clamped_decay_or_a_first_token(mode, per_channel):
    """``g`` below ``G_MIN`` on a third of the entries, raw ``q`` and ``k``,
    packed documents: ``dg`` is zero where the clamp holds and on a document's
    first token (its decay meets a state that was just reset), ``dbeta`` and
    the rest match the recurrence on the clamped ``g``."""
    S = 200
    (q, k, v, g, beta), w = operands(S, per_channel, 1.0, norms=(0.1, 30.0))
    low = jax.random.bernoulli(jax.random.key(7), 1 / 3, g.shape)
    g = jnp.where(low, g - 12.0, g)
    cuts, seg = packed(S)
    # a third of the decays are exp(-10) a token: the fast-decay tolerance
    got = check((q, k, v, g, beta), w, TOL_FAST_DECAY, segment_ids=seg, interpret=mode == "interpret")
    dg, low, cuts = np.asarray(got["g"]), np.asarray(low), np.asarray(cuts)
    assert np.abs(np.where(low, dg, 0.0)).max() == 0.0
    assert np.abs(np.where(low, 0.0, dg)).max() > 0.0
    for b in range(B):
        assert np.abs(dg[b, cuts[b]]).max() == 0.0


@pytest.mark.parametrize("g_scale,tol", [(0.01, TOL), (4.0, TOL_FAST_DECAY)],
                         ids=["decay_near_1", "decay_near_0"])
def test_decays_near_one_and_near_zero(g_scale, tol):
    args, w = operands(192, True, g_scale)
    check(args, w, tol, interpret=True)


def test_a_decay_below_the_clamp_is_held_at_it():
    (q, k, v, g, beta), _ = operands(64, True, 1.0)
    g = heads(g).at[:, 10].set(-40.0).reshape(g.shape)  # exp(-40): the state is wiped but for 4e-18 of it
    got = jax.jit(lambda *a: chunked_delta_rule(*a, interpret=True))(q, k, v, g, beta)
    ref = jax.jit(reference, static_argnames="clamp")
    assert rel(got, ref(q, k, v, g, beta)) < TOL
    # and what the clamp changes is exp(-10) of a state of order 1
    assert rel(got, ref(q, k, v, g, beta, clamp=False)) < 2e-4


def test_bfloat16_operands_are_told_apart_by_the_tolerance():
    args, _ = operands(128, True, 1.0)
    q, k, v, g, beta = args
    low = jax.jit(lambda *a: chunked_delta_rule(*a, interpret=True))(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), g, beta)
    assert rel(low, jax.jit(reference)(*args)) > 20 * TOL


def formed_outside(q, k, v, g, beta, segment_ids=None, c=128, rounded=True):
    """The operator as it stood before the kernels formed their own operands:
    ``l2norm``, ``round(k * beta)``, ``round(v * beta)``, the clamp and the
    zero on a first token as jnp passes over ``[B, S, H, d]`` arrays, then the
    same chunk rule (``_chunk_rule``) under a plain scan that autodiff walks.
    ``S`` a whole number of chunks. ``rounded=False``: the rule's float32
    output as it is before its one rounding to the compute type."""
    Bq, S, _ = q.shape
    cd = q.dtype
    qn = (l2norm(heads(q)) * DK**-0.5).astype(cd)
    kn = l2norm(heads(k)).astype(cd)
    b = beta.astype(F32)[..., None]
    kb = (kn.astype(F32) * b).astype(cd)
    vb = (heads(v).astype(F32) * b).astype(cd)
    g = jnp.clip(g.astype(F32), G_MIN, 0.0)
    g = jnp.broadcast_to(g[..., None] if g.shape[-1] == H else heads(g), qn.shape)
    seg = None
    if segment_ids is not None:
        prev = jnp.pad(segment_ids, ((0, 0), (1, 0)), constant_values=-1)[:, :S]
        starts = segment_ids != prev
        g = jnp.where(starts[:, :, None, None], 0.0, g)
        seg = jnp.cumsum(starts.astype(jnp.int32).reshape(Bq, S // c, c), axis=2).swapaxes(0, 1)
        seg = (seg[..., None], seg[:, :, None, :])  # [n, B, C, 1], [n, B, 1, C]
    chunks = lambda x: x.reshape(Bq, S // c, c, H, -1).transpose(1, 0, 3, 2, 4)
    s = None if seg is None else 0
    rule = jax.vmap(jax.vmap(_chunk_rule, in_axes=(0,) * 6 + (None, None)), in_axes=(0,) * 6 + (s, s))

    def step(st, x):
        ops, sg = x
        o, st1 = rule(st, *ops, *(sg if sg is not None else (None, None)))
        return st1, o

    xs = tuple(chunks(a) for a in (qn, kn, kb, vb, g))
    _, o = jax.lax.scan(step, jnp.zeros((Bq, H, DV, DK), F32), (xs, seg))
    o = o.transpose(1, 0, 3, 2, 4).reshape(Bq, S, -1)
    return o.astype(cd) if rounded else o


# bfloat16 operands, both sides the same chunk rule on the same rounded
# operands: the outputs are equal to the last bit. The gradients are rounded to
# bfloat16 in other places: formed outside, ``dk`` is the sum of three bfloat16
# arrays (the rule's own ``dk``, ``dkb * beta`` and the norm's transpose of
# both, each rounded as it left a kernel or a jnp pass); formed inside, one
# float32 sum rounded once. Read (all six cases): ``dv`` and, under the scan,
# ``dg`` equal; ``dq`` 3e-6, ``dg`` 1e-6, ``dbeta`` < 5e-7 (float32 sums over d
# in another order); ``dk`` 0.0039 to 0.0075 of its largest entry, one or two
# last bits of bfloat16 (2^-8). The tolerance is four.
TOL_BF16_GRADS = 2**-6


@pytest.mark.parametrize("mode", ["interpret", "scan"])
@pytest.mark.parametrize("case", ["per_channel", "scalar", "packed"])
def test_bfloat16_rounding_points_are_those_of_the_formation_outside(mode, case):
    S = 256
    (q, k, v, g, beta), w = operands(S, case != "scalar", 1.0, norms=(0.1, 30.0))
    g = jnp.where(jax.random.bernoulli(jax.random.key(7), 0.2, g.shape), g - 12.0, g)
    seg = packed(S)[1] if case == "packed" else None
    args = (*(a.astype(jnp.bfloat16) for a in (q, k, v)), g, beta)
    op = lambda *a: chunked_delta_rule(*a, segment_ids=seg, interpret=mode == "interpret")
    (got, grads), (want, wants) = run(op, args, w), run_once("formed_outside", formed_outside, args, w, seg)
    if mode == "scan":  # the same rounding points in the same XLA ops: equal to the last bit
        assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    else:  # (read equal in interpret mode too; a last bit of bfloat16 is allowed there)
        assert rel(got, want) < 2**-7
    for name in NAMES:
        assert grads[name].dtype == args[NAMES.index(name)].dtype
        assert rel(grads[name], wants[name]) < TOL_BF16_GRADS, name


# -- the epilogue: each head's output row over its root mean square -----------------------

EPS = 1e-5


def row_rms_normed(o, eps=EPS):
    """What the epilogue replaces: the flat ``o`` taken to ``[B, S, H, dv]``
    and each row divided by its root mean square, in float32."""
    o4 = heads(o.astype(F32))
    return (o4 * jax.lax.rsqrt(jnp.mean(o4 * o4, axis=-1, keepdims=True) + eps)).reshape(o.shape)


@pytest.mark.parametrize("mode", ["interpret", "scan"])
@pytest.mark.parametrize("packed_docs", [False, True], ids=["one_document", "packed"])
@pytest.mark.parametrize("per_channel", [True, False], ids=["per_channel", "scalar"])
def test_the_norm_epilogue_is_the_composition_it_replaces(mode, packed_docs, per_channel):
    """``out_norm_eps`` against the operator without it followed by the row
    norm in jnp, float32 operands, 200 tokens (the kernels pad them to 256,
    the scan too): the output and all five gradients. The backward takes the
    cotangent of the NORMED output and differentiates the epilogue with the
    body it recomputes. Rows of ``o`` here have a mean square of 2e-7 to 6e-3
    (median 2e-4): ``eps`` = 1e-5 leads in some rows and hardly counts in
    others. Read: equal to the last bit (the same float32 operations on the
    same numbers, inside the body or after it)."""
    S = 200
    args, w = operands(S, per_channel, 1.0, norms=(0.1, 30.0))
    seg = packed(S)[1] if packed_docs else None
    kw = dict(segment_ids=seg, interpret=mode == "interpret")
    o, got = run(lambda *a: chunked_delta_rule(*a, out_norm_eps=EPS, **kw), args, w)
    o_ref, want = run(lambda *a: row_rms_normed(chunked_delta_rule(*a, **kw)), args, w)
    assert o.shape == o_ref.shape and o.dtype == F32
    assert rel(o, o_ref) < TOL
    for name in NAMES:
        assert rel(got[name], want[name]) < TOL, name


@pytest.mark.parametrize("mode", ["interpret", "scan"])
@pytest.mark.parametrize("case", ["per_channel", "scalar", "packed"])
def test_the_norm_epilogue_rounds_once(mode, case):
    """bfloat16 operands: the epilogue divides the float32 tile and the kernel
    rounds it, so the output is the rule's UNROUNDED float32 output, normed in
    float32 and rounded once, to a last bit of bfloat16 (the composition it
    replaces rounds ``o`` first and the normed rows again)."""
    S = 256
    (q, k, v, g, beta), _ = operands(S, case != "scalar", 1.0, norms=(0.1, 30.0))
    seg = packed(S)[1] if case == "packed" else None
    args = (*(a.astype(jnp.bfloat16) for a in (q, k, v)), g, beta)
    got = jax.jit(lambda *a: chunked_delta_rule(*a, segment_ids=seg, out_norm_eps=EPS,
                                                interpret=mode == "interpret"))(*args)
    want = jax.jit(lambda *a: row_rms_normed(formed_outside(*a, seg, rounded=False))
                   .astype(jnp.bfloat16))(*args)
    assert got.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # one last bit of a bfloat16 number is 2^-8 to 2^-7 of it
    assert (np.abs(got - want) <= 2.0**-7 * np.abs(want)).all()


@pytest.mark.parametrize("chunk", [64, 128])
def test_a_run_of_equal_keys_written_at_full_strength(chunk):
    """(I + N)^-1 with N all ones under the diagonal: the answer is tame (1 on
    the diagonal, -1 under it), the powers of N are not (1e37 at N^64). The
    triangular inverse must never form them."""
    (q, k, v, g, beta), w = operands(256, True, 1.0)
    k = jnp.broadcast_to(heads(k)[:, :1], heads(k).shape).reshape(k.shape)
    args = (q, k, v, jnp.full_like(g, -1e-3), jnp.ones_like(beta))
    check(args, w, TOL_FAST_DECAY, interpret=True, chunk_size=chunk)


# -- the triangular inverse's own cotangent ---------------------------------------------


def inverse_operands(case, c):
    """-> (n [C, C] float32 under ``mask``, a float32 cotangent of T, mask):
    ``random``: entries uniform in +-1/4; ``equal_keys``: all ones (the run of
    equal keys written at full strength); ``packed``: ``beta_t k_t . k_j`` of
    unit keys that share a direction, inside the documents of ``packed``."""
    rng = np.random.default_rng(c)
    mask = np.tril(np.ones((c, c), bool), -1)
    if case == "random":
        n = rng.uniform(-0.25, 0.25, (c, c))
    elif case == "equal_keys":
        n = np.ones((c, c))
    else:
        k = rng.normal(size=(c, DK)) + 2.0 * rng.normal(size=(1, DK))
        k /= np.linalg.norm(k, axis=1, keepdims=True)
        n = (k @ k.T) / (1.0 + np.exp(-rng.normal(size=(c, 1))))
        seg = np.asarray(packed(c)[1][0])
        mask &= seg[:, None] == seg[None, :]
    return np.where(mask, n, 0.0).astype(np.float32), rng.normal(size=(c, c)).astype(np.float32), mask


# Read (all six operands a mode, error on the masked entries over the largest):
# three bfloat16 passes: the identity 4.8e-6 to 1.7e-5, ``jax.vjp`` of the
# construction 3.1e-3 to 3.1e-2 (every transposed pass takes its cotangent
# rounded to bfloat16); float32: both at float32's rounding, the identity 6.6e-8
# to 3.4e-7, the construction 1.4e-7 to 1.4e-6, either ahead by up to 1.2e-7
# (the identity sums C terms an entry, a merge's block-sparse factors fewer),
# so "no further" is held to 2^-20 of the largest entry there.
@pytest.mark.parametrize("case", ["random", "equal_keys", "packed"])
@pytest.mark.parametrize("c", [64, 128])
@pytest.mark.parametrize("exact", [False, True], ids=["split", "exact"])
def test_the_inverse_is_differentiated_by_its_identity(exact, c, case):
    """``dn = -T^T dT T^T`` in the forward's own arithmetic against float64, and
    against what ``jax.vjp`` of the block-merge construction gave before it."""
    n, dt, mask = inverse_operands(case, c)
    t = np.linalg.inv(np.eye(c) + n.astype(np.float64))
    want = np.where(mask, -t.T @ dt.astype(np.float64) @ t.T, 0.0)

    def error(inverse):
        out, vjp = jax.vjp(lambda x: inverse(x, exact), jnp.asarray(n))
        got = np.where(mask, np.asarray(vjp(jnp.asarray(dt))[0], np.float64), 0.0)
        return out, float(np.abs(got - want).max() / np.abs(want).max())

    (out, new), (out_plain, old) = error(_unit_lower_inverse), error(_block_merge_inverse)
    assert bool((out == out_plain).all())  # the forward IS the construction
    assert new < (1e-5 if exact else 1e-4)
    assert new <= (max(old, 2.0**-20) if exact else old)


def dot_generals(jaxpr) -> int:
    from jax._src import core

    return sum((eqn.primitive.name == "dot_general")
               + sum(dot_generals(sub) for sub in core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("eps", [None, EPS], ids=["plain", "norm_epilogue"])
@pytest.mark.parametrize("cd,forward,backward", [(jnp.bfloat16, 52, 87), (F32, 28, 59)],
                         ids=["bfloat16", "float32"])
def test_products_a_chunk_head(cd, forward, backward, eps):
    """The kernels are bound by their count of 128 x 128 x 128 products (module
    docstring): at the cell's shapes a chunk-head is 52 forward (36 the
    inverse's twelve in three passes) and 87 backward: the body recomputed, its
    transpose, and 6 for the inverse's identity. ``jax.vjp`` let back into the
    inverse reads 153 (float32 operands: 81). The norm epilogue is a square, a
    lane sum, an ``rsqrt`` and a multiply: it adds no product either way."""
    c, dk, dv = 128, 128, 128
    st0, rows = jnp.zeros((dv, dk), F32), jnp.zeros((c, dk), cd)
    args = (st0, rows, rows, jnp.zeros((c, dv), cd), jnp.zeros((c, dk), F32), jnp.zeros((c, 1), F32))
    body = lambda *a: _chunk_body(*a, out_norm_eps=eps)
    assert dot_generals(jax.make_jaxpr(body)(*args).jaxpr) == forward
    grads = lambda *a: _chunk_grads(*a[:6], None, None, *a[6:], out_norm_eps=eps)
    assert dot_generals(jax.make_jaxpr(grads)(*args, jnp.zeros((c, dv), F32), st0).jaxpr) <= backward
