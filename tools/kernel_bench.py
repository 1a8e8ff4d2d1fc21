"""Kernel tile/block sweep driver → per-chip autotune table + report.

Sweeps VMEM-feasible tile candidates for the hand-scheduled Pallas kernels
(the grouped-matmul family, the fused expert-MLP backward kernels, and the
splash-vs-blockwise flash attention race), measures each with the
PROFILE_MOE methodology (slope between a short and a 4×-longer scan loop so
the fixed per-call cost cancels; carry-fed operands so LICM/DCE can't fake
the numbers), and persists the winners into the autotune registry
(ops/autotune.py) that the kernels consult at trace time.

Outputs under --output-dir:
- ``autotune_<chip>.json`` — the regenerated table. Point
  ``AUTOMODEL_AUTOTUNE_TABLE`` at it, or re-run with ``--write-defaults``
  to merge the winners into the committed
  ``automodel_tpu/ops/autotune_defaults.json``.
- ``KERNEL_BENCH.md`` — human-readable sweep report.
- ``kernel_bench.jsonl`` — one record per measurement with the ``kernel_*``
  keys ``telemetry/report.py --strict`` lints and summarizes
  (docs/observability.md glossary).

On a TPU the sweep times the real kernels. Anywhere else (CI, laptops) it
runs every candidate through the Pallas INTERPRETER on tiny shapes — a
correctness/compile gate for the whole sweep surface, recorded with
``measured: false`` and no timing claims (interpret-mode wall clock says
nothing about MXU behavior). Run: ``python tools/kernel_bench.py --help``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

_VMEM_BUDGET = 12 * 1024 * 1024


def _on_tpu() -> bool:
    try:
        return jax.devices()[0].platform == "tpu"
    except Exception:
        return False


def timed(fn, c0, *args, reps: int = 16):
    """Per-iteration seconds of ``fn: (carry, *args) -> carry`` via the
    slope between a short and a 4×-longer jitted scan loop (see
    tools/profile_moe.py for why a single-loop timing lies)."""

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
        jax.block_until_ready(
            jax.device_get(jax.tree.leaves(out)[0].ravel()[0])
        )

    run(loop_s)  # compile
    run(loop_l)
    t0 = time.perf_counter()
    run(loop_s)
    t1 = time.perf_counter()
    run(loop_l)
    t2 = time.perf_counter()
    return ((t2 - t1) - (t1 - t0)) / (3 * reps)


def _finite_once(fn, c0, *args) -> bool:
    """Interpret-mode gate: run the candidate once, check finiteness."""
    out = jax.jit(lambda c, a: fn(c, *a))(c0, args)
    leaf = jax.tree.leaves(out)[0]
    return bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


@contextlib.contextmanager
def _candidate_table(key: str, cand: dict):
    """Expose one candidate entry to the kernels via the runtime-table env
    hook — the same path a committed entry takes, so the sweep measures
    exactly what the table will later select."""
    from automodel_tpu.ops import autotune

    fd, path = tempfile.mkstemp(suffix=".json", prefix="autotune_cand_")
    os.close(fd)
    prev = os.environ.get(autotune.ENV_TABLE)
    try:
        autotune.save_table(path, {key: dict(cand)})
        os.environ[autotune.ENV_TABLE] = path
        autotune.clear_cache()
        yield
    finally:
        if prev is None:
            os.environ.pop(autotune.ENV_TABLE, None)
        else:
            os.environ[autotune.ENV_TABLE] = prev
        autotune.clear_cache()
        with contextlib.suppress(OSError):
            os.unlink(path)


class Sweep:
    """Accumulates measurements → winners per autotune key + report rows."""

    def __init__(self, logger, on_tpu: bool, peak_tflops: float):
        self.logger = logger
        self.on_tpu = on_tpu
        self.peak = peak_tflops
        self.rows: list[dict] = []
        self.winners: dict[str, dict] = {}

    def add(self, *, key: str, kernel: str, candidate: dict, flops: float,
            dt=None, ok: bool = True, backend=None, error=None,
            persist: bool = True):
        tflops = (flops / dt / 1e12) if (dt and dt > 0) else None
        mfu = (
            round(100.0 * tflops / self.peak, 2)
            if tflops is not None and self.peak == self.peak else None
        )
        row = {
            "event": "kernel_bench",
            "kernel": kernel,
            "autotune_key": key,
            "candidate": candidate,
            "kernel_backend": backend,
            "kernel_ms": round(dt * 1e3, 4) if dt else None,
            "kernel_flops": flops,
            "kernel_tflops": round(tflops, 2) if tflops is not None else None,
            "kernel_mfu_measured_pct": mfu,
            "ok": ok,
            "measured": bool(self.on_tpu and dt is not None),
        }
        if error:
            row["error"] = error
        self.rows.append(row)
        self.logger.log({k: v for k, v in row.items() if v is not None})
        if not (ok and persist):
            return
        score = tflops if tflops is not None else -1.0
        best = self.winners.get(key)
        if best is None or score > best.get("_score", -1.0):
            entry = dict(candidate)
            if backend is not None:
                entry["backend"] = backend
            entry["measured"] = row["measured"]
            if tflops is not None:
                entry["measured_tflops"] = round(tflops, 1)
            entry["source"] = (
                f"kernel_bench {time.strftime('%Y-%m-%d')}"
                + ("" if row["measured"] else " (interpret gate, not timed)")
            )
            entry["_score"] = score
            self.winners[key] = entry

    def table_entries(self) -> dict[str, dict]:
        return {
            k: {kk: vv for kk, vv in v.items() if kk != "_score"}
            for k, v in self.winners.items()
        }


def _run_candidate(sw: Sweep, *, key, kernel, cand, flops, fn, c0, reps,
                   backend=None, persist=True, use_table=True):
    """Measure (TPU) or gate (interpret) one candidate, routed through the
    runtime autotune table so the kernel resolves the candidate tiles."""
    ctx = _candidate_table(key, cand) if use_table else contextlib.nullcontext()
    try:
        with ctx:
            if sw.on_tpu:
                dt = timed(fn, c0, reps=reps)
                if dt <= 0:
                    # noise: the short/long slope went non-positive —
                    # this is not a measurement and must never be persisted
                    # (or stamped measured) as one
                    sw.add(key=key, kernel=kernel, candidate=cand,
                           flops=flops, ok=False, backend=backend,
                           persist=False,
                           error=f"non-positive slope timing ({dt:.3e}s)")
                    return False
                sw.add(key=key, kernel=kernel, candidate=cand, flops=flops,
                       dt=dt, ok=True, backend=backend, persist=persist)
                return True
            ok = _finite_once(fn, c0)
            sw.add(key=key, kernel=kernel, candidate=cand, flops=flops,
                   ok=ok, backend=backend, persist=persist)
            return ok
    except Exception as exc:
        sw.add(key=key, kernel=kernel, candidate=cand, flops=flops, ok=False,
               backend=backend, persist=False,
               error=f"{type(exc).__name__}: {str(exc)[:200]}")
        return False


# -- fused-MoE backward + grouped-matmul sweeps ------------------------------


def _tile_ok(kernel: str, tiles: tuple[int, ...], itemsize: int) -> bool:
    """Candidate feasibility — the EXACT budget predicates the kernels
    validate table entries against (exported from the kernel modules), so a
    candidate that passes here can never be silently replaced by the
    kernel's heuristic fallback at measure time."""
    from automodel_tpu.ops.fused_expert_mlp import (
        _bwd_dwd_budget_ok,
        _bwd_dx_budget_ok,
        _bwd_gu_budget_ok,
    )
    from automodel_tpu.ops.grouped_matmul import _tgmm_budget_ok

    preds = {
        "moe_bwd_gu": _bwd_gu_budget_ok,
        "moe_bwd_dwd": _bwd_dwd_budget_ok,
        "moe_bwd_dx": _bwd_dx_budget_ok,
        "tgmm": _tgmm_budget_ok,
    }
    pred = preds.get(kernel)
    return True if pred is None else pred(*tiles, itemsize)


def _tile_cands(small: bool, names) -> list[dict]:
    if small:
        return [dict(zip(names, (128,) * len(names)))]
    out = []
    for tm in (512, 768, 1024, 2048):
        for t2 in (256, 512):
            for t3 in (256, 512):
                out.append(dict(zip(names, (tm, t2, t3))))
    return out


def sweep_moe_backward(sw: Sweep, small: bool, reps: int):
    from automodel_tpu.ops import autotune
    from automodel_tpu.ops import fused_expert_mlp as fm
    from automodel_tpu.ops import grouped_matmul as gm

    if small:
        M, D, I, G = 256, 128, 128, 4
        cd = jnp.float32
    else:
        # bench GPT-OSS fingerprint (bench.py _moe_hf, BENCH_MOE_BATCH=4)
        M, D, I, G = 4 * 4096 * 4, 1536, 1536, 32
        cd = jnp.bfloat16
    it = jnp.dtype(cd).itemsize
    interpret = not sw.on_tpu
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(M, D)), cd)
    g = jnp.asarray(rng.normal(size=(M, I)), cd)
    u = jnp.asarray(rng.normal(size=(M, I)), cd)
    dmid = jnp.asarray(rng.normal(size=(M, I)), cd)
    dy = jnp.asarray(rng.normal(size=(M, D)), cd)
    gate_w = jnp.asarray(rng.normal(size=(G, D, I)) * 0.05, cd)
    up_w = jnp.asarray(rng.normal(size=(G, D, I)) * 0.05, cd)
    down_w = jnp.asarray(rng.normal(size=(G, I, D)) * 0.05, cd)
    gs = jnp.full((G,), M // G, jnp.int32)
    eps = jnp.asarray(1e-12, cd)

    plans = [
        (
            "moe_bwd_gu", autotune.moe_bwd_gu_key(D, I, cd),
            ("tm", "tk", "tn"), 2 * 2 * M * D * I,
            lambda c, *a: c + fm._bwd_gu(
                c, g, u, dmid, gs, "swiglu", None, interpret, True
            )[0].sum().astype(cd) * eps,
            lhs,
        ),
        (
            "moe_bwd_dwd", autotune.moe_bwd_dwd_key(I, D, cd),
            ("tm", "tk", "tn"), 2 * M * I * D,
            lambda c, *a: c + fm._bwd_dwd(
                g, u, c, gs, "swiglu", None, interpret, True
            )[0].sum().astype(cd) * eps,
            dy,
        ),
        (
            "moe_bwd_dx", autotune.moe_bwd_dx_key(D, I, cd),
            ("tm", "tn", "ic"), 2 * 2 * M * D * I,
            lambda c, *a: c + fm._bwd_dx(
                g, u, c, gate_w, up_w, gs, interpret, "swiglu", None
            )[:, :1].astype(cd) * eps,
            dmid,
        ),
        (
            "tgmm", autotune.tgmm_key(I, D, cd),
            ("tm", "tk", "tn"), 2 * M * I * D,
            lambda c, *a: c + gm._tgmm(
                g, c, gs, interpret=interpret
            ).sum().astype(cd) * eps,
            dy,
        ),
    ]
    for kernel, key, names, flops, fn, c0 in plans:
        for cand in _tile_cands(small, names):
            if not _tile_ok(kernel, tuple(cand[n] for n in names), it):
                continue
            _run_candidate(sw, key=key, kernel=kernel, cand=cand,
                           flops=flops, fn=fn, c0=c0, reps=reps)

    # the A/B the tentpole exists for: purpose-tiled fused backward vs the
    # r5 composed-tgmm backward, full fused_expert_mlp FWD+BWD
    mlp_flops = 3 * (2 * M * D * 2 * I + 2 * M * I * D)

    def train_fn(c, *a):
        def loss(x):
            y = fm.fused_expert_mlp(
                x, gate_w, up_w, down_w, gs, None, None, None,
                "swiglu", None, None, interpret,
            )
            return jnp.sum(y.astype(jnp.float32) ** 2) * 1e-6

        return c + jax.grad(loss)(c) * eps

    prev_bwd = os.environ.get("AUTOMODEL_FUSED_BWD")
    try:
        for label, env in (("fused", "1"), ("composed", "0")):
            os.environ["AUTOMODEL_FUSED_BWD"] = env
            _run_candidate(
                sw, key=f"race:moe_backward:{label}", kernel="expert_mlp_fwd_bwd",
                cand={"path": label}, flops=mlp_flops, fn=train_fn, c0=lhs,
                reps=max(4, reps // 4), backend=label, persist=False,
                use_table=False,
            )
    finally:
        # restore whatever the caller had exported (the documented safety
        # valve must survive an in-process sweep)
        if prev_bwd is None:
            os.environ.pop("AUTOMODEL_FUSED_BWD", None)
        else:
            os.environ["AUTOMODEL_FUSED_BWD"] = prev_bwd


# -- attention race ----------------------------------------------------------


def sweep_attention(sw: Sweep, small: bool, reps: int):
    from automodel_tpu.ops import autotune, ring_flash
    from automodel_tpu.ops import attention as attn_mod

    rng = np.random.default_rng(1)
    cd = jnp.float32 if small else jnp.bfloat16
    eps = jnp.asarray(1e-12, cd)
    interpret = not sw.on_tpu
    cases = (
        [dict(B=1, S=256, N=2, NKV=1, H=64, window=128)] if small
        else [
            dict(B=4, S=4096, N=16, NKV=4, H=64, window=None),
            dict(B=4, S=4096, N=16, NKV=4, H=64, window=128),
            dict(B=2, S=4096, N=16, NKV=8, H=128, window=None),
        ]
    )
    block_cands = (
        [(128, 128)] if small
        else [(256, 128), (256, 256), (256, 512), (512, 512), (512, 1024)]
    )
    for case in cases:
        B, S, N, NKV, H = case["B"], case["S"], case["N"], case["NKV"], case["H"]
        window = case["window"]
        key = autotune.attn_key(H, window, True)
        kernel = f"attention_h{H}_w{window or 0}"
        q0 = jnp.asarray(rng.normal(size=(B, S, N, H)), cd)
        k = jnp.asarray(rng.normal(size=(B, S, NKV, H)), cd)
        v = jnp.asarray(rng.normal(size=(B, S, NKV, H)), cd)
        # fwd+bwd model FLOPs; windowed layers credited at window length
        # (the reference's accounting — utils/flops_utils.py)
        attended = S / 2 if window is None else min(window, S)
        flops = 3 * (2 * 2 * B * N * H * S * attended)

        def block_fn(bq, bkv):
            def loss(qq):
                o = ring_flash.flash_attention(
                    qq, k, v, causal=True, sliding_window=window,
                    block_q=bq, block_kv=bkv, interpret=interpret,
                )
                return jnp.sum(o.astype(jnp.float32) ** 2) * 1e-6

            return lambda c, *a: c + jax.grad(loss)(c) * eps

        def splash_fn(bq, bkv):
            def loss(qq):
                o = attn_mod._splash_flash(
                    qq, k, v, None, None, causal=True,
                    scale=1.0 / (H ** 0.5), logits_soft_cap=None,
                    sliding_window=window, block_q=bq, block_kv=bkv,
                    interpret=interpret,
                )
                return jnp.sum(o.astype(jnp.float32) ** 2) * 1e-6

            return lambda c, *a: c + jax.grad(loss)(c) * eps

        passed: dict[str, dict] = {}  # backend -> first passing candidate
        for bq, bkv in block_cands:
            cand = {"block_q": bq, "block_kv": bkv}
            for backend, make in (("block", block_fn), ("splash", splash_fn)):
                # off-TPU there is no timing, so score-based winner picking
                # would crown whichever backend happens to be iterated
                # first — persist nothing here and decide below
                ok = _run_candidate(
                    sw, key=key, kernel=kernel, cand=cand, flops=flops,
                    fn=make(bq, bkv), c0=q0, reps=max(4, reps // 4),
                    backend=backend, use_table=False, persist=sw.on_tpu,
                )
                if ok:
                    passed.setdefault(backend, cand)
        if not sw.on_tpu and len(passed) == 1:
            # exactly one backend can run the shape at all (e.g. this
            # build's splash refuses head_dim 64) — a capability result,
            # not a race: persist it as the only viable entry
            backend, cand = next(iter(passed.items()))
            sw.winners[key] = {
                **cand, "backend": backend, "measured": False,
                "source": (
                    f"kernel_bench {time.strftime('%Y-%m-%d')} (interpret "
                    "gate: only viable backend on this build, not raced)"
                ),
                "_score": -1.0,
            }


# -- paged-attention race (serving decode: fused Pallas kernel vs gather) ----


def sweep_paged_attention(sw: Sweep, small: bool, reps: int):
    """Race the fused paged-attention decode kernel
    (ops/paged_attention.py) against the XLA gather → sdpa_decode → scatter
    baseline across (block_size, table width, kv dtype) candidates — the
    serving per-token hot path. Winners land as ``backend`` entries under
    ``autotune.paged_key`` that ``serving.decode_kernel: auto`` consults."""
    from automodel_tpu.ops import autotune
    from automodel_tpu.ops import paged_attention as pa
    from automodel_tpu.ops.attention import sdpa_decode

    interpret = not sw.on_tpu
    rng = np.random.default_rng(2)
    cd = jnp.float32 if small else jnp.bfloat16
    eps = jnp.asarray(1e-3, jnp.float32)
    cases = (
        [dict(B=2, BS=8, NBseq=3, Nkv=2, N=4, H=16)] if small
        else [
            # llama3-8B decode fingerprint: 8 kv heads, head_dim 128, a
            # 2k-token view at two block granularities
            dict(B=8, BS=16, NBseq=128, Nkv=8, N=32, H=128),
            dict(B=8, BS=32, NBseq=64, Nkv=8, N=32, H=128),
        ]
    )
    for case in cases:
        B, BS, NBseq = case["B"], case["BS"], case["NBseq"]
        Nkv, N, H = case["Nkv"], case["N"], case["H"]
        NB = B * NBseq + 2
        Cv = NBseq * BS
        pool_k = jnp.asarray(rng.normal(size=(NB, BS, Nkv, H)), cd)
        pool_v = jnp.asarray(rng.normal(size=(NB, BS, Nkv, H)), cd)
        tables = jnp.asarray(
            1 + rng.permutation(NB - 2)[: B * NBseq].reshape(B, NBseq),
            jnp.int32,
        )
        lengths = jnp.asarray(
            rng.integers(Cv // 2, Cv - 1, size=(B,)), jnp.int32
        )
        q0 = jnp.asarray(rng.normal(size=(B, 1, N, H)), jnp.float32)
        mean_len = float(jnp.mean(lengths))
        flops = 2 * 2 * B * N * H * mean_len  # qk + pv per decoded token
        j = jnp.arange(Cv, dtype=jnp.int32)
        kv_mask = j[None, :] <= lengths[:, None]

        for dtype_label in ("bf16", "int8"):
            key = autotune.paged_key(H, BS, dtype_label)
            kernel = f"paged_attention_h{H}_bs{BS}_{dtype_label}"
            if dtype_label == "int8":
                kq, ks = pa.quantize_kv_rows(pool_k)
                vq, vs = pa.quantize_kv_rows(pool_v)
            else:
                kq = vq = ks = vs = None

            def fused_fn(c, *a):
                if dtype_label == "int8":
                    out = pa.paged_attend(
                        c.astype(cd), kq, vq, tables, lengths, ks, vs,
                        interpret=interpret,
                    )
                else:
                    out = pa.paged_attend(
                        c.astype(cd), pool_k, pool_v, tables, lengths,
                        interpret=interpret,
                    )
                return c + out.astype(jnp.float32) * eps

            def gather_fn(c, *a):
                if dtype_label == "int8":
                    view_k = pa.dequantize_kv(kq[tables], ks[tables], cd)
                    view_v = pa.dequantize_kv(vq[tables], vs[tables], cd)
                else:
                    view_k, view_v = pool_k[tables], pool_v[tables]
                out = sdpa_decode(
                    c.astype(cd),
                    view_k.reshape(B, Cv, Nkv, H),
                    view_v.reshape(B, Cv, Nkv, H),
                    kv_mask=kv_mask,
                )
                return c + out.astype(jnp.float32) * eps

            cand = {"table_width": NBseq}
            passed: dict[str, dict] = {}
            it = jnp.dtype(jnp.int8 if dtype_label == "int8" else cd).itemsize
            for backend, fn in (("fused", fused_fn), ("gather", gather_fn)):
                if backend == "fused" and not pa._paged_budget_ok(
                    BS, Nkv, H, 1, N // Nkv, it, dtype_label == "int8"
                ):
                    continue
                ok = _run_candidate(
                    sw, key=key, kernel=kernel, cand=cand, flops=flops,
                    fn=fn, c0=q0, reps=max(4, reps // 4), backend=backend,
                    use_table=False, persist=sw.on_tpu,
                )
                if ok:
                    passed.setdefault(backend, cand)
            if not sw.on_tpu and len(passed) == 1:
                # same rule as the attention race: off-TPU there is no
                # timing, so persist only a capability result
                backend, c = next(iter(passed.items()))
                sw.winners[key] = {
                    **c, "backend": backend, "measured": False,
                    "source": (
                        f"kernel_bench {time.strftime('%Y-%m-%d')} (interpret "
                        "gate: only viable backend on this build, not raced)"
                    ),
                    "_score": -1.0,
                }


# -- report ------------------------------------------------------------------


def render_markdown(sw: Sweep, chip: str, shapes: str) -> str:
    lines = [
        "# Kernel sweep report (tools/kernel_bench.py)",
        "",
        f"Chip: **{chip}** · shapes: {shapes} · "
        + ("measured on hardware" if sw.on_tpu
           else "interpret-mode correctness gate (NOT timed — run on the "
                "chip for real numbers)"),
        "",
        "| kernel | backend | candidate | ms | TFLOP/s | MFU % | ok |",
        "|---|---|---|---|---|---|---|",
    ]

    def _num(v, fmt="{:.1f}"):
        return fmt.format(v) if isinstance(v, (int, float)) else "-"

    for r in sw.rows:
        cand = json.dumps(r.get("candidate", {}), sort_keys=True)
        ok = "yes" if r.get("ok") else f"NO ({r.get('error', '?')[:80]})"
        lines.append(
            f"| {r.get('kernel')} | {r.get('kernel_backend') or '-'} "
            f"| `{cand}` | {_num(r.get('kernel_ms'), '{:.2f}')} "
            f"| {_num(r.get('kernel_tflops'))} "
            f"| {_num(r.get('kernel_mfu_measured_pct'))} | {ok} |"
        )
    lines += [
        "",
        "## Winners (persisted to the autotune table)" if sw.on_tpu else
        "## Gate survivors (persisted with measured=false — NOT raced; "
        "re-sweep on hardware)",
        "",
    ]
    for key, entry in sorted(sw.table_entries().items()):
        lines.append(f"- `{key}` → `{json.dumps(entry, sort_keys=True)}`")
    lines.append("")
    return "\n".join(lines)


def main(argv=None) -> int:
    from automodel_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(
        description="kernel tile/block sweep → autotune table"
    )
    ap.add_argument("--output-dir", default=None)
    ap.add_argument("--shapes", choices=("bench", "small"), default=None,
                    help="bench = the MoE bench fingerprint (default on "
                         "TPU); small = tiny interpret-friendly shapes "
                         "(default elsewhere)")
    ap.add_argument("--reps", type=int, default=16)
    ap.add_argument("--write-defaults", action="store_true",
                    help="merge winners into automodel_tpu/ops/"
                         "autotune_defaults.json for this chip kind")
    ap.add_argument("--skip-attention", action="store_true")
    ap.add_argument("--skip-moe", action="store_true")
    ap.add_argument("--skip-paged", action="store_true")
    args = ap.parse_args(argv)

    from automodel_tpu.loggers.metric_logger import MetricLogger
    from automodel_tpu.ops import autotune
    from automodel_tpu.utils.flops_utils import device_peak_tflops

    on_tpu = _on_tpu()
    small = (args.shapes or ("bench" if on_tpu else "small")) == "small"
    out_dir = args.output_dir or os.path.join(
        "runs", time.strftime("kernel_bench_%Y%m%d_%H%M%S")
    )
    os.makedirs(out_dir, exist_ok=True)
    chip = autotune.chip_key()
    try:
        peak = device_peak_tflops()
    except Exception:
        peak = float("nan")
    logger = MetricLogger(os.path.join(out_dir, "kernel_bench.jsonl"))
    sw = Sweep(logger, on_tpu, peak)
    print(f"[kernel_bench] chip={chip} shapes={'small' if small else 'bench'} "
          f"{'TIMED' if on_tpu else 'interpret gate'}", file=sys.stderr)

    if not args.skip_moe:
        sweep_moe_backward(sw, small, args.reps)
    if not args.skip_attention:
        sweep_attention(sw, small, args.reps)
    if not args.skip_paged:
        sweep_paged_attention(sw, small, args.reps)

    entries = sw.table_entries()
    safe_chip = chip.replace(" ", "_").replace("/", "_")
    table_path = os.path.join(out_dir, f"autotune_{safe_chip}.json")
    autotune.save_table(table_path, entries, chip=chip)
    md_path = os.path.join(out_dir, "KERNEL_BENCH.md")
    with open(md_path, "w") as f:
        f.write(render_markdown(sw, chip, "small" if small else "bench fingerprint"))
    logger.log({
        "event": "kernel_bench_summary",
        "kernel_bench_winners": len(entries),
        "autotune_table": table_path,
        "chip": chip,
    })
    logger.close()
    if args.write_defaults:
        if on_tpu:
            autotune.save_table(autotune.DEFAULTS_PATH, entries, chip=chip)
            print(f"[kernel_bench] merged {len(entries)} winners into "
                  f"{autotune.DEFAULTS_PATH}", file=sys.stderr)
        else:
            print("[kernel_bench] refusing --write-defaults off-TPU: "
                  "interpret-mode winners carry no timing evidence",
                  file=sys.stderr)
    print(f"[kernel_bench] wrote {table_path} + {md_path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
