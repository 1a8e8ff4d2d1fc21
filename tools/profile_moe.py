"""Apportion MoE step time between dispatch (sort/gather), grouped matmuls,
combine, attention, and the rest — on the real chip at bench shapes.

Round 5: shapes track the CURRENT bench fingerprint (bench.py _moe_hf — the
GPT-OSS-style model: D=I=1536 per expert, E=32 top-4, swiglu_oai with
interleaved gate_up + expert biases, head_dim 64), and the fused expert MLP
(`ragged_fused`) is profiled head-to-head against the two-gmm `ragged` path,
with and without biases. Edit the D/I/E constants below if the bench
fingerprint moves again — the written artifact names the shapes it measured.

Each stage is timed as a jitted `lax.scan` loop whose op inputs DEPEND ON THE
CARRY (else XLA's while-loop LICM hoists the op out and the timing is a lie)
and whose output feeds the next carry (else DCE). The fixed per-call cost
(dispatch + the fetch that syncs) cancels in the slope between a short and a
4x-longer loop; one tiny device_get syncs. Writes PROFILE_MOE_r05.md.

Run: python tools/profile_moe.py  (on a TPU).
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

# bench fingerprint (bench.py _moe_hf, BENCH_MOE_BATCH=4, seq=4096)
D = 1536
I = 1536  # per-expert intermediate (gpt-oss layout, I=D)
E = 32
K = 4
T = 4 * 4096  # tokens per step
TK = T * K
REPS = int(os.environ.get("PROFILE_REPS", 32))


def timed(name, fn, c0, *args, flops=0.0, bytes_moved=0.0, reps=REPS):
    """fn: (carry, *args) -> carry. The carry must flow through the op.

    Per-iteration time comes from the SLOPE between a short and a long loop
    ((t_4r - t_r)/3r): each jitted call pays a fixed cost (dispatch +
    device_get) which a single-loop timing would smear into the per-iter
    number; the slope cancels it."""

    def make(n):
        @jax.jit
        def loop(c, args):
            def body(c, _):
                return fn(c, *args), None

            c, _ = jax.lax.scan(body, c, None, length=n)
            return c

        return loop

    loop_s, loop_l = make(reps), make(4 * reps)

    def run(loop):
        out = loop(c0, args)
        jax.block_until_ready(jax.device_get(jax.tree.leaves(out)[0].ravel()[0]))

    run(loop_s)  # compile
    run(loop_l)
    t0 = time.perf_counter()
    run(loop_s)
    t1 = time.perf_counter()
    run(loop_l)
    t2 = time.perf_counter()
    dt = ((t2 - t1) - (t1 - t0)) / (3 * reps)
    line = f"{name:<40} {dt*1e3:8.2f} ms"
    if flops:
        line += f"  {flops/dt/1e12:7.1f} TFLOP/s"
    if bytes_moved:
        line += f"  {bytes_moved/dt/1e9:7.1f} GB/s"
    print(line, flush=True)
    return dt, line


def _ipert(c):
    """int32 scalar derived from the carry that is always 0 but not provably
    so — defeats LICM without perturbing results."""
    return (jax.lax.stop_gradient(c).ravel()[0] * jnp.asarray(1e-30, c.dtype)).astype(
        jnp.int32
    )


def main():
    from automodel_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})", flush=True)
    rng = np.random.default_rng(0)
    cd = jnp.bfloat16
    eps = jnp.asarray(1e-12, cd)

    x = jnp.asarray(rng.normal(size=(T, D)), cd)
    gu_w = jnp.asarray(rng.normal(size=(E, D, 2 * I)) * 0.02, cd)
    dn_w = jnp.asarray(rng.normal(size=(E, I, D)) * 0.02, cd)
    gu_b = jnp.asarray(rng.normal(size=(E, 2 * I)) * 0.02, cd)
    dn_b = jnp.asarray(rng.normal(size=(E, D)) * 0.02, cd)
    topk_idx = jnp.asarray((rng.permutation(TK).reshape(T, K) % E).astype(np.int32))
    topk_w = jnp.full((T, K), 1.0 / K, cd)

    order_np = jnp.argsort(topk_idx.reshape(-1))
    token_of = order_np // K
    gsizes = jnp.bincount(topk_idx.reshape(-1), length=E).astype(jnp.int32)
    xs0 = x[token_of]
    lines = []

    # ---- components (inputs perturbed by the carry to defeat LICM) --------
    def f_sort(c, idx):
        order = jnp.argsort(idx.reshape(-1) + _ipert(c))
        return c + order[:T].astype(cd)[:, None] * eps

    lines.append(timed("argsort T*K", f_sort, x, topk_idx)[1])

    def f_gather(c, tok):
        xs = c[tok + _ipert(c)]
        return c + xs[:T] * eps

    lines.append(
        timed("gather x[token_of] [TK,D]", f_gather, x, token_of,
              bytes_moved=2 * TK * D * 2)[1]
    )

    from automodel_tpu.ops.grouped_matmul import ragged_dot

    def f_gmm1(c, w, gs):
        out = ragged_dot(c, w, gs, platform="tpu")  # carry IS the lhs
        return c + out.sum(-1, keepdims=True) * eps

    lines.append(
        timed("gmm1 [TK,D]@[E,D,2I]", f_gmm1, xs0, gu_w, gsizes,
              flops=2 * TK * D * 2 * I)[1]
    )

    h0 = jnp.asarray(rng.normal(size=(TK, I)), cd)

    def f_gmm2(c, w, gs):
        out = ragged_dot(c, w, gs, platform="tpu")
        return c + out.sum(-1, keepdims=True) * eps

    lines.append(
        timed("gmm2 [TK,I]@[E,I,D]", f_gmm2, h0, dn_w, gsizes,
              flops=2 * TK * I * D)[1]
    )

    # ---- fused expert MLP kernel vs the two-gmm composition ---------------
    from automodel_tpu.ops.fused_expert_mlp import fused_expert_mlp

    gw0, uw0 = gu_w[:, :, ::2], gu_w[:, :, 1::2]  # any fixed split works here
    gb0, ub0 = gu_b[:, ::2], gu_b[:, 1::2]
    mlp_flops = 2 * TK * D * 2 * I + 2 * TK * I * D

    def f_fused(c, gw, uw, dw, gs):
        out = fused_expert_mlp(c, gw, uw, dw, gs, None, None, None,
                               "swiglu_oai", None, "tpu", None)
        return c + out * eps

    lines.append(
        timed("fused MLP kernel (no bias)", f_fused, xs0, gw0, uw0, dn_w,
              gsizes, flops=mlp_flops)[1]
    )

    def f_fused_b(c, gw, uw, dw, gb, ub, db, gs):
        out = fused_expert_mlp(c, gw, uw, dw, gs, gb, ub, db,
                               "swiglu_oai", None, "tpu", None)
        return c + out * eps

    lines.append(
        timed("fused MLP kernel (biased)", f_fused_b, xs0, gw0, uw0, dn_w,
              gb0, ub0, dn_b, gsizes, flops=mlp_flops)[1]
    )

    # ---- full expert paths (fwd and train), bench config ------------------
    from automodel_tpu.moe.config import MoEConfig
    from automodel_tpu.moe.experts import ragged_experts, ragged_fused_experts
    from automodel_tpu.moe.gate import GateOutput
    from automodel_tpu.moe.layer import make_act2

    # interleaved_gate_up=False matches production: the gpt-oss adapter
    # de-interleaves at the checkpoint boundary, so the hot path splits
    # contiguous halves (strided ::2 splits leak relayout copies)
    cfg = MoEConfig(
        num_experts=E, num_experts_per_tok=K, moe_intermediate_size=I,
        activation="swiglu_oai", interleaved_gate_up=False,
    )
    act2 = make_act2(cfg, jax.nn.silu)

    def gate_of(c, idx):
        return GateOutput(
            topk_idx=idx + _ipert(c), topk_weights=topk_w,
            expert_counts=gsizes, aux_loss=jnp.zeros((), jnp.float32),
        )

    def f_ragged_fwd(c, idx, gu, dn, gub, dnb):
        w = {"gate_up": gu, "down": dn, "gate_up_bias": gub, "down_bias": dnb}
        return ragged_experts(c, gate_of(c, idx), w, cfg, act2,
                              platform="tpu") * eps + c

    lines.append(
        timed("ragged_experts FWD (biased)", f_ragged_fwd, x, topk_idx, gu_w,
              dn_w, gu_b, dn_b, flops=mlp_flops)[1]
    )

    def f_fusedpath_fwd(c, idx, gu, dn, gub, dnb):
        w = {"gate_up": gu, "down": dn, "gate_up_bias": gub, "down_bias": dnb}
        return ragged_fused_experts(c, gate_of(c, idx), w, cfg, act2,
                                    platform="tpu") * eps + c

    lines.append(
        timed("ragged_FUSED_experts FWD (biased)", f_fusedpath_fwd, x,
              topk_idx, gu_w, dn_w, gu_b, dn_b, flops=mlp_flops)[1]
    )

    def train_of(expert_fn):
        def f(c, idx, gu, dn, gub, dnb):
            gout = gate_of(c, idx)

            def loss(args):
                x_, gu_, dn_, gub_, dnb_ = args
                w = {"gate_up": gu_, "down": dn_, "gate_up_bias": gub_,
                     "down_bias": dnb_}
                y = expert_fn(x_, gout, w, cfg, act2, platform="tpu")
                return jnp.sum(y.astype(jnp.float32) ** 2) * 1e-6

            g = jax.grad(loss)((c, gu, dn, gub, dnb))
            return c + g[0] * eps

        return f

    lines.append(
        timed("ragged_experts FWD+BWD (biased)", train_of(ragged_experts), x,
              topk_idx, gu_w, dn_w, gu_b, dn_b, flops=3 * mlp_flops)[1]
    )
    lines.append(
        timed("ragged_FUSED FWD+BWD (biased)", train_of(ragged_fused_experts),
              x, topk_idx, gu_w, dn_w, gu_b, dn_b, flops=3 * mlp_flops)[1]
    )

    # ---- attention at bench shape (flash, gpt-oss heads) ------------------
    from automodel_tpu.ops.attention import flash

    B, S, N, NKV, H = 4, 4096, 16, 4, 64
    k = jnp.asarray(rng.normal(size=(B, S, NKV, H)), cd)
    v = jnp.asarray(rng.normal(size=(B, S, NKV, H)), cd)
    q0 = jnp.asarray(rng.normal(size=(B, S, N, H)), cd)
    att_flops = 2 * 2 * B * N * H * S * S / 2  # causal half

    def f_attn(c, k, v):
        o = flash(c, k, v, causal=True)  # carry is q
        return c + o * eps

    lines.append(timed("flash attention fwd (bench shape)", f_attn, q0, k, v,
                       flops=att_flops)[1])

    def f_attn_train(c, k, v):
        def loss(q):
            o = flash(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) ** 2) * 1e-6

        return c + jax.grad(loss)(c) * eps

    lines.append(timed("flash attention fwd+bwd", f_attn_train, q0, k, v,
                       flops=3 * att_flops)[1])

    with open("PROFILE_MOE_r05.md", "w") as f:
        f.write("# MoE hot-path profile (round 5)\n\n")
        f.write(f"Device: {dev.device_kind}; shapes: T={T}, K={K}, E={E}, "
                f"D={D}, I={I} (bench GPT-OSS fingerprint, BENCH_MOE_BATCH=4 "
                f"seq=4096, swiglu_oai + expert biases)\n\n```\n")
        f.write("\n".join(lines))
        f.write("\n```\n")
    print("wrote PROFILE_MOE_r05.md", flush=True)


if __name__ == "__main__":
    main()
