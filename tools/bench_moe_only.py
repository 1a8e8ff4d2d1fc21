"""Run ONLY the bench MoE leg(s) — for iterating on the expert path without
re-paying the dense + QLoRA legs. Same conditions as bench.py's MoE race.

Usage: python tools/bench_moe_only.py [backend ...]   (default: ragged_fused ragged)
Env: BENCH_MOE_BATCH, BENCH_SEQ as in bench.py.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

import bench


def main() -> None:
    status, detail = bench._probe_tpu()
    if status != "tpu":
        print(f"[bench-moe] no TPU found; aborting\n{detail[-500:]}",
              file=sys.stderr)
        sys.exit(1)
    from automodel_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.utils.flops_utils import calculate_mfu, device_peak_tflops

    ctx = build_mesh(MeshConfig(dp_shard=-1))
    peak = device_peak_tflops()
    seq = int(os.environ.get("BENCH_SEQ", 4096))
    candidates = sys.argv[1:] or ["ragged_fused", "ragged"]
    results = {}
    for experts in candidates:
        try:
            backend = bench._moe_backend(experts)
            tps, fpt = bench._run(
                bench._moe_hf(), backend,
                int(os.environ.get("BENCH_MOE_BATCH", 6)), seq, 8, ctx,
            )
            mfu = calculate_mfu(tps, fpt, peak)
            results[experts] = {
                "mfu_pct": round(mfu * 100, 2),
                "tflops_per_chip": round(tps * fpt / 1e12, 1),
                "tok_per_s_chip": round(tps),
            }
            print(f"[bench-moe] {experts}: {results[experts]}", file=sys.stderr,
                  flush=True)
        except Exception as exc:
            results[experts] = {"error": str(exc)[:500]}
            print(f"[bench-moe] {experts} FAILED: {str(exc)[:2000]}",
                  file=sys.stderr, flush=True)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
